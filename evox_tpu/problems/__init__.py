from . import numerical
from . import neuroevolution
from . import supervised
from . import evoxbench
from . import lm

__all__ = ["numerical", "neuroevolution", "supervised", "evoxbench", "lm"]

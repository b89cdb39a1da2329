from .open_es import OpenES
from .lowrank_open_es import LowRankOpenES
from .pgpe import PGPE, ClipUp
from .cma_es import CMAES, SepCMAES, RestartCMAESDriver, IPOPCMAES, BIPOPCMAES
from .nes import XNES, SeparableNES
from .snes import SNES
from .ars import ARS
from .ma_es import MAES, LMMAES
from .rmes import RMES
from .amalgam import AMaLGaM, IndependentAMaLGaM
from .des import DES
from .esmc import ESMC
from .guided_es import GuidedES
from .persistent_es import PersistentES, NoiseReuseES
from .asebo import ASEBO
from .cr_fm_nes import CR_FM_NES

try:  # flax-dependent (mirrors the reference's optional-dep guard)
    from .les import LES
except ImportError:  # pragma: no cover
    LES = None

__all__ = [
    "OpenES",
    "LowRankOpenES",
    "PGPE",
    "ClipUp",
    "CMAES",
    "SepCMAES",
    "RestartCMAESDriver",
    "IPOPCMAES",
    "BIPOPCMAES",
    "XNES",
    "SeparableNES",
    "SNES",
    "ARS",
    "MAES",
    "LMMAES",
    "RMES",
    "AMaLGaM",
    "IndependentAMaLGaM",
    "DES",
    "ESMC",
    "GuidedES",
    "PersistentES",
    "NoiseReuseES",
    "ASEBO",
    "CR_FM_NES",
    "LES",
]

"""Layer: tell. Device time a generation under the ``evox.tell`` scope: the
sign flip, ``fit_transforms``, the algorithm's ``tell`` or ``init_tell`` and
the migrate ``cond``."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.tell",))

"""Layer: tell. Device time a generation under ``evox.tell/dominance_build``:
the packed dominance matrix and the domination counts."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.tell", "dominance_build"))

"""Layer: tell. Device time a generation under ``evox.tell/peel``: the
front-peeling ``while_loop``."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.tell", "peel"))

"""Layer: ask. Device time a generation under ``evox.ask/noise``: the ES's
normal draw and its mirrored concatenation."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.ask", "noise"))

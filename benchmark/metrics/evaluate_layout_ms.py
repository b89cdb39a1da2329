"""Layer: evaluate. Device time a generation spent re-laying-out the
population for the rollout kernel: ``evox.evaluate/decode`` (the flat genome
cut into the policy's layers) plus ``evox.evaluate/layout`` (the layers
transposed to the kernel's ``(in, out, n)`` planes)."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.evaluate", "decode"), ("evox.evaluate", "layout"))

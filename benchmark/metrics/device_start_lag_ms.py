"""Layer: entry points. Median, over the chunks of the traced stretch, of the
time from an ``evox:run`` span's start to the start of the first device
operation after it: how long the device waits for the host at the head of a
chunk."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.median(scoped.start_lags_ms(ctx))

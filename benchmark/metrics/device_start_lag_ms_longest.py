"""Layer: entry points. The largest, over the chunks of the traced stretch, of
the time from an ``evox:run`` span's start to the start of the first device
operation after it. A stalled chunk shows here if the host was late to start
the device, and does not if the device itself was slow."""

from benchmark.lib import scoped


def read(ctx):
    lags = scoped.start_lags_ms(ctx)
    return max(lags) if lags else None

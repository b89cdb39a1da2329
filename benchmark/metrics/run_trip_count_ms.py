"""Layer: entry points. Median length of the window's ``evox:run/trip_count``
records: ``fused_run`` making ``n_steps`` a device scalar before the loop's
call (a little program and a transfer, ROADMAP D8). With ``run_dispatch_ms``
it is what ``evox:run/loop`` brackets, and nearly all of ``run_host_ms``."""

from benchmark.lib import hostlog


def read(ctx):
    return hostlog.window_median_ms(ctx, hostlog.TRIP_COUNT)

"""Layer: entry points. Median length of the window's ``evox:run/dispatch``
records in the program's host log: the run loop's jitted call alone, from
inside ``evox:run/loop``, apart from the trip count's program and transfer
(``run_trip_count_ms``). The window is traced, and a profiler session moves a
process to the fast level of the two the call has (``run_dispatch_ms_untraced``
reads the level the process was at before)."""

from benchmark.lib import hostlog


def read(ctx):
    return hostlog.window_median_ms(ctx, hostlog.DISPATCH)

"""Layer: entry points. Length of the last ``evox:run/dispatch`` record before
the window: set-up's warm chunk, one ``run`` at the window's own trip count
with every program compiled, made before the harness starts the profiler. So
it reads the level of the run loop's jitted call that an untraced process is
at (two levels, about 5 and about 20 ms in the language cells: PERF.md
section 6, PR 34), which no span of a traced window can show."""

from benchmark.lib import hostlog


def read(ctx):
    warm = hostlog.setup_records(ctx, hostlog.DISPATCH)
    return hostlog.ms(warm[-1]) if warm else None

"""Layer: entry points. The ``evox:init`` record of this run's set-up, less
what the compile records inside it cover: the eager ``init`` from the seed,
its own time. What ``init`` traces, lowers and compiles (or takes from the
cache) reads in ``setup_lower_s`` and ``setup_backend_s``, so the three are
disjoint parts of ``setup_s``."""

from benchmark.lib import hostlog


def read(ctx):
    inits = hostlog.setup_records(ctx, hostlog.INIT)
    if not inits:
        return None
    init = inits[-1]
    compiles = hostlog.setup_records(ctx, hostlog.COMPILE_TRACE, hostlog.COMPILE_LOWER, hostlog.COMPILE_BACKEND)
    inside = [r for r in compiles if r.start_ns >= init.start_ns and r.end_ns <= init.end_ns]
    return hostlog.ms(init) / 1e3 - hostlog.union_s(inside)

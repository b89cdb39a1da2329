"""Layer: entry points. Union of set-up's ``evox:compile/backend`` records:
the backend compiling each module, or taking it from the persistent cache
(``setup_cache_misses`` tells which a run was)."""

from benchmark.lib import hostlog


def read(ctx):
    backend = hostlog.setup_records(ctx, hostlog.COMPILE_BACKEND)
    return None if backend is None else hostlog.union_s(backend)

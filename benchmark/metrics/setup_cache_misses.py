"""Layer: entry points. Set-up's ``evox:compile/backend`` records less its
``evox:compile/cache_hit`` records: the programs the backend really compiled.
0 in a warm run, so a result line says whether its ``setup_s`` belongs to a
cold comparison or a warm one."""

from benchmark.lib import hostlog


def read(ctx):
    backend = hostlog.setup_records(ctx, hostlog.COMPILE_BACKEND)
    if backend is None:
        return None
    return float(len(backend) - len(hostlog.setup_records(ctx, hostlog.COMPILE_CACHE_HIT)))

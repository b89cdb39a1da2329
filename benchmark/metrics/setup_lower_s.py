"""Layer: entry points. Set-up's ``evox:compile/trace`` and
``evox:compile/lower`` records, as the union of their stretches less what
``evox:compile/backend`` records cover of it (a function compiled while another
is traced counts as compiling): the Python-side work of making each program
(jax tracing a function to a jaxpr, the jaxpr lowered to a module), which no
compile cache saves. A Pallas kernel's Mosaic lowering is inside it, and so is
the walker's set-up swing by call path (PERF.md section 6, PR 26). With
``setup_backend_s`` it is all the time set-up spent making programs."""

from benchmark.lib import hostlog


def read(ctx):
    backend = hostlog.setup_records(ctx, hostlog.COMPILE_BACKEND)
    if backend is None:
        return None
    python_side = hostlog.setup_records(ctx, hostlog.COMPILE_TRACE, hostlog.COMPILE_LOWER)
    return hostlog.union_s(python_side + backend) - hostlog.union_s(backend)

"""Layer: entry points. Length of the longest ``evox:run`` record of the
window, from the program's host log: a chunk that stalled because the host sat
inside ``run`` reads here (``run_host_ms`` is the median and hides it), one
that stalled with the host back in ``block_until_ready`` does not
(``idle_in_wait_ms_longest``)."""

from benchmark.lib import hostlog


def read(ctx):
    found = hostlog.window(ctx)
    return max(hostlog.ms(r) for r in found.runs) if found else None

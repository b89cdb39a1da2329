"""Layer: device. Largest over the chunks of the traced stretch of the
device's idle time outside every ``evox:run``: between one chunk's last
operation and the next ``run``'s start (the host waking in
``block_until_ready`` and going round the loop), and after ``run`` returned
where the device had not started yet. A stall the host's own calls did not
cause reads here; one inside ``run`` reads in ``run_host_ms_longest``."""

from benchmark.lib import hostlog


def read(ctx):
    rows = hostlog.idle_by_chunk(ctx)
    return max(row["wait"] / 1e6 for row in rows) if rows else None

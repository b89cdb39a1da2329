"""Layer: device. Median over the chunks of the traced stretch of the device's
idle time (the gaps of ``ctx.events``) that falls inside that chunk's
``evox:run``, the record of the program's host log laid on the trace's clock:
the device waiting while the host is still in the call that starts it. Close
to ``device_start_lag_ms`` where the device starts before ``run`` returns.
Says the alignment's residual and the stretch's split on standard error."""

from benchmark.lib import hostlog, scoped


def read(ctx):
    rows = hostlog.idle_by_chunk(ctx)
    hostlog.say(ctx, rows)
    return scoped.median([row["run"] / 1e6 for row in rows]) if rows else None

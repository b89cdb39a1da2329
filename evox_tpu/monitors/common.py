"""Shared monitor plumbing: host-callback probes and the fixed-capacity
device-ring discipline.

The ring helpers are the one implementation behind every on-device
history buffer in the stack — EvalMonitor's device history,
TelemetryMonitor's trajectory rings, LineageMonitor's lineage rings, the
SurrogateArchive, and the surrogate fallback-event log. All share the
same law: a ``(K, ...)`` buffer plus a monotone ``count``; the write slot
is ``count % K``; host readback is chronological over the last
``min(count, K)`` writes. Keeping them on one helper keeps the discipline
identical (fixed shapes, no retrace as counts grow, zero host callbacks
in the write path).
"""

from __future__ import annotations

import jax
from jax.sharding import SingleDeviceSharding

from ..utils.ring import ring_scatter_indices, ring_slots, ring_write  # noqa: F401


def host0_sharding() -> SingleDeviceSharding:
    """Sharding that pins a host callback to GLOBAL device 0 — on a
    multi-host mesh the callback then fires on process 0 only (the process
    that owns device 0), the same discipline as the reference
    (eval_monitor.py:69 ``SingleDeviceSharding(jax.devices()[0])``)."""
    return SingleDeviceSharding(jax.devices()[0])


# ---------------------------------------------------------- device rings
# The implementation lives in utils/ring.py (the bottom layer, so
# operators — e.g. the SurrogateArchive — can share it without importing
# monitors); monitor code imports the discipline from here.

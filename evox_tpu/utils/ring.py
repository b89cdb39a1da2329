"""The fixed-capacity device-ring discipline, as three primitives.

One implementation behind every on-device history buffer in the stack —
EvalMonitor's device history, TelemetryMonitor's trajectory rings,
LineageMonitor's lineage rings, the SurrogateArchive, and the surrogate
fallback-event log. All share the same law: a ``(K, ...)`` buffer plus a
monotone ``count``; the write slot is ``count % K``; host readback is
chronological over the last ``min(count, K)`` writes. Fixed shapes, no
retrace as counts grow, zero host callbacks in the write path.

Lives in utils (the bottom layer) so both operators and monitors can use
it; monitor code imports the same names via ``monitors/common.py``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["ring_write", "ring_scatter_indices", "ring_slots"]


def ring_write(buf: jax.Array, row, count, cond=None) -> jax.Array:
    """Write ``row`` at slot ``count % buf.shape[0]`` along axis 0.

    One fixed-shape ``dynamic_update_index_in_dim`` — the write cost does
    not grow with history length, and the traced program is identical for
    every generation (no retrace as ``count`` advances). ``row`` may be
    one rank lower than ``buf`` (a single slot's payload) and is cast to
    the buffer dtype. With ``cond`` (a traced bool) the write is
    conditional: the buffer passes through unchanged when false, still
    one fixed-shape program (the surrogate fallback-log pattern)."""
    slot = count % buf.shape[0]
    out = jax.lax.dynamic_update_index_in_dim(
        buf, jnp.asarray(row).astype(buf.dtype), slot, 0
    )
    if cond is not None:
        out = jnp.where(cond, out, buf)
    return out


def ring_scatter_indices(
    count, mask: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Indices for a masked multi-row ring append (the SurrogateArchive
    pattern): the ``mask``-selected rows land consecutively at the ring
    head, masked-out rows get index ``capacity`` so an ``.at[idx].set(...,
    mode="drop")`` scatter drops them — one fixed-shape write regardless
    of how many rows are truly accepted. Returns ``(idx, new_count)``; the
    caller applies ``idx`` to each payload buffer."""
    mask = mask.astype(jnp.int32)
    offsets = jnp.cumsum(mask) - 1  # position among accepted rows
    idx = jnp.where(mask > 0, (count + offsets) % capacity, capacity)
    return idx, count + jnp.sum(mask)


def ring_slots(count, capacity: int) -> list:
    """Host-side chronological slot order: the last ``min(count,
    capacity)`` writes, oldest first. Eager (pulls ``count`` to host)."""
    count = int(count)
    n = min(count, capacity)
    return [(i % capacity) for i in range(count - n, count)]

"""Device time under the scopes a model of two layer kinds adds (``lm/kda``
and, inside it, ``lm/kda_scan``; ``evox_tpu/core/instrument.py``), told apart
as ``lm_scopes.py`` tells the others: an operation counts for the innermost
scope it lies under. ``lm/lowrank`` nests inside ``lm/kda`` as inside the
other parts, and ``lm_lowrank_ms`` reads it there too. Where the program has
no such scope (a parent from before it) there is nothing to read: None."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scoped

INNER = {"kda": ("kda_scan", "lowrank"), "kda_scan": ("lowrank",)}


def part_ns(ctx, part: str) -> Optional[float]:
    """Own device nanoseconds of the traced stretch under ``lm/<part>`` and
    under none of the parts nested in it; None where there is nothing to read."""
    view = scoped.load(ctx)
    if view is None:
        return None
    ns = sum(
        v for k, v in view.own_ns.items()
        if scoped.under(k, "evox.evaluate", "lm", part)
        and not any(scoped.under(k, "lm", inner) for inner in INNER[part])
    )
    return ns or None


def part_ms(ctx, part: str) -> Optional[float]:
    """The same in milliseconds a generation."""
    ns = part_ns(ctx, part)
    if ns is None or not ctx.window["generations"]:
        return None
    return ns / 1e6 / ctx.window["generations"]

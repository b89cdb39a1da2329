"""Device time under the scope the gated short convolution layers add
(``lm/conv``; ``evox_tpu/core/instrument.py``), told apart as ``lm_scopes.py``
tells the others: an operation counts for the innermost scope it lies under.
``lm/lowrank`` nests inside ``lm/conv`` as inside the other parts, and
``lm_lowrank_ms`` reads it there too. And the device time of the grouped-query
attention kernel's own events, by the ``pallas_call``'s name. Where the
program has no such scope or kernel (a parent from before them) there is
nothing to read: None."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scoped
from benchmark.lib import trace as tr

INNER = {"conv": ("lowrank",)}
GQA_KERNEL = r"^gqa_flash_attention"  # the events of the pallas_call of that name


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


def gqa_kernel_ns(ctx) -> Optional[float]:
    """Device nanoseconds of the traced stretch in the events named by the
    ``gqa_flash_attention`` kernel; None where there is none."""
    return tr.matching_ns(ctx.events, GQA_KERNEL) or None

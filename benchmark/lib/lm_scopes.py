"""Device time under the language model's scopes (``lm/...`` of
``evox_tpu/core/instrument.py``), a part at a time. The scopes nest: the
low-rank terms (``lm/lowrank``) lie inside whichever part adds them, and the
held experts' products (``lm/experts``) inside the router's part, whose loop
drives them; all of them lie inside ``lm/forward``, the whole pass. An
operation counts for the innermost of these it lies under, so the parts add up
to the time under ``evox.evaluate`` and none is counted twice.
"""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scoped

INNER = {  # part: the parts nested inside it, which take their operations from it
    # what no other part names: the batch, the loop over chunks of pairs, the compiler's copies in its body
    "forward": ("embed", "attention", "mlp", "router", "experts", "lowrank", "head_loss"),
    "lowrank": (),
    "experts": ("lowrank",),
    "router": ("experts", "lowrank"),
    "embed": ("lowrank",),
    "attention": ("lowrank",),
    "mlp": ("lowrank",),
    "head_loss": ("lowrank",),
}


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

"""What a builder hands the harness, and the helpers every builder shares."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional


@dataclasses.dataclass
class Built:
    """A system under test, ready to be driven.

    ``wf``: the program's workflow; the harness drives ``wf.run(state, n)``
    from ``wf.init(key)``. ``key``: the seed's key. ``pop``: evaluations per
    generation. ``snapshot(state)``: the leaves the comparison reads, as host
    arrays.
    """

    wf: Any
    key: Any
    pop: int
    snapshot: Callable[[Any], dict]


def key_from_seed(seed: int):
    """A raw threefry key from any whole number up to 2**64: the two 32-bit
    words of the seed (``PRNGKey`` refuses what 32 signed bits do not hold).
    Equal to ``jax.random.PRNGKey(seed)`` for a seed below 2**31."""
    import jax.numpy as jnp

    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64 - 1")
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)


def make_mesh(traffic: dict, devices: list) -> Optional[Any]:
    """The ``"pop"`` mesh a traffic mix asks for (``mesh_devices``), or None."""
    n = int(traffic.get("mesh_devices", 0))
    if n <= 1:
        return None
    if len(devices) < n:
        raise RuntimeError(f"traffic asks for a mesh of {n} devices, jax found {len(devices)}")
    from evox_tpu import create_mesh

    return create_mesh(devices=devices[:n])

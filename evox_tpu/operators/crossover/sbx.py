"""Simulated binary crossover (reference: src/evox/operators/crossover/
{sbx,simulated_binary}.py — the reference ships two SBX implementations; this
single one covers both call patterns)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.instrument import CROSSOVER, scope


@scope(CROSSOVER)
def simulated_binary(key: jax.Array, pop: jax.Array, distribution_factor: float = 20.0) -> jax.Array:
    """SBX over consecutive parent pairs; returns offspring of the same shape.

    ``pop`` has an even leading axis; pairs are (0,1), (2,3), ...
    """
    n, d = pop.shape
    half = n // 2
    p1 = pop[0::2][:half]
    p2 = pop[1::2][:half]
    u = jax.random.uniform(key, (half, d))
    beta = jnp.where(
        u <= 0.5,
        (2.0 * u) ** (1.0 / (distribution_factor + 1.0)),
        (1.0 / (2.0 * (1.0 - u))) ** (1.0 / (distribution_factor + 1.0)),
    )
    c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
    c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
    out = jnp.empty_like(pop[: 2 * half])
    out = out.at[0::2].set(c1)
    out = out.at[1::2].set(c2)
    if 2 * half < n:  # odd tail passes through
        out = jnp.concatenate([out, pop[2 * half:]], axis=0)
    return out


class SimulatedBinary:
    def __init__(self, distribution_factor: float = 20.0):
        self.distribution_factor = distribution_factor

    def __call__(self, key, pop):
        return simulated_binary(key, pop, self.distribution_factor)

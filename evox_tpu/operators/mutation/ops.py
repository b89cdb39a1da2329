"""Mutation operators (reference: src/evox/operators/mutation/
{pm_mutation,gaussian,bitflip}.py)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.instrument import MUTATION, scope


@scope(MUTATION)
def polynomial(
    key: jax.Array,
    pop: jax.Array,
    boundary: Tuple[jax.Array, jax.Array],
    pro_m: float = 1.0,
    dis_m: float = 20.0,
) -> jax.Array:
    """Polynomial mutation (Deb & Goyal), fully batched.

    ``boundary`` = (lower, upper), broadcastable to pop rows. Mutation
    probability per gene = ``pro_m / dim``.
    """
    n, d = pop.shape
    lb, ub = boundary
    lb = jnp.broadcast_to(jnp.asarray(lb, pop.dtype), (d,))
    ub = jnp.broadcast_to(jnp.asarray(ub, pop.dtype), (d,))
    k1, k2 = jax.random.split(key)
    site = jax.random.uniform(k1, (n, d)) < (pro_m / d)
    u = jax.random.uniform(k2, (n, d))
    span = ub - lb
    norm = jnp.where(span > 0, (pop - lb) / span, 0.0)
    norm_up = jnp.where(span > 0, (ub - pop) / span, 0.0)
    mut_pow = 1.0 / (dis_m + 1.0)
    lhs = (2.0 * u + (1.0 - 2.0 * u) * (1.0 - norm) ** (dis_m + 1.0)) ** mut_pow - 1.0
    rhs = 1.0 - (2.0 * (1.0 - u) + 2.0 * (u - 0.5) * (1.0 - norm_up) ** (dis_m + 1.0)) ** mut_pow
    delta = jnp.where(u <= 0.5, lhs, rhs)
    mutated = pop + delta * span
    return jnp.clip(jnp.where(site, mutated, pop), lb, ub)


def gaussian(key: jax.Array, pop: jax.Array, stdvar: float = 1.0) -> jax.Array:
    """Additive Gaussian mutation (reference gaussian.py:13)."""
    return pop + stdvar * jax.random.normal(key, pop.shape, dtype=pop.dtype)


def bitflip(key: jax.Array, pop: jax.Array, prob: float = 0.1) -> jax.Array:
    """Flip boolean/binary genes with probability ``prob`` (bitflip.py:34)."""
    flip = jax.random.bernoulli(key, prob, pop.shape)
    return jnp.where(flip, 1 - pop, pop) if pop.dtype != bool else jnp.where(flip, ~pop, pop)


class Polynomial:
    def __init__(self, boundary, pro_m: float = 1.0, dis_m: float = 20.0):
        self.boundary = boundary
        self.pro_m = pro_m
        self.dis_m = dis_m

    def __call__(self, key, pop):
        return polynomial(key, pop, self.boundary, self.pro_m, self.dis_m)


class Gaussian:
    def __init__(self, stdvar: float = 1.0):
        self.stdvar = stdvar

    def __call__(self, key, pop):
        return gaussian(key, pop, self.stdvar)


class Bitflip:
    def __init__(self, prob: float = 0.1):
        self.prob = prob

    def __call__(self, key, pop):
        return bitflip(key, pop, self.prob)

"""Inverted Generational Distance (+ IGD+ variant). Capability parity with
reference src/evox/metrics/igd.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.common import pairwise_euclidean_dist


def igd(objs: jax.Array, pf: jax.Array, p: float = 1.0) -> jax.Array:
    """Mean distance from each true-front point to its nearest solution."""
    d = pairwise_euclidean_dist(pf, objs)
    return jnp.mean(jnp.min(d, axis=1) ** p) ** (1.0 / p)


def masked_igd(
    objs: jax.Array,
    objs_mask: jax.Array,
    pf: jax.Array,
    pf_mask: jax.Array,
) -> jax.Array:
    """IGD between two masked point sets of fixed shape: the mean over
    valid ``pf`` rows of the distance to the nearest valid ``objs`` row.

    Fixed-shape companion to :func:`igd` for jitted monitors
    (monitors/lineage.py's non-dominated-churn ring): fronts change size
    every generation, so both sets arrive zero-padded with boolean row
    masks instead of being sliced (no retrace, no host callback). Returns 0 when
    either set is empty — an undefined churn is reported as "no movement"
    rather than NaN-poisoning the ring."""
    d = pairwise_euclidean_dist(pf, objs)
    d = jnp.where(objs_mask[None, :], d, jnp.inf)
    nearest = jnp.min(d, axis=1)
    n_pf = jnp.sum(pf_mask.astype(jnp.float32))
    mean = jnp.sum(jnp.where(pf_mask, nearest, 0.0)) / jnp.maximum(n_pf, 1.0)
    defined = jnp.any(objs_mask) & jnp.any(pf_mask)
    return jnp.where(defined, mean, 0.0)


def igd_plus(objs: jax.Array, pf: jax.Array) -> jax.Array:
    """IGD+ (Ishibuchi et al. 2015): only dominated directions count."""
    diff = jnp.maximum(objs[None, :, :] - pf[:, None, :], 0.0)
    d = jnp.linalg.norm(diff, axis=-1)
    return jnp.mean(jnp.min(d, axis=1))


class IGD:
    def __init__(self, pf: jax.Array, p: float = 1.0):
        self.pf = pf
        self.p = p

    def __call__(self, objs: jax.Array) -> jax.Array:
        return igd(objs, self.pf, self.p)


class IGDPlus:
    def __init__(self, pf: jax.Array):
        self.pf = pf

    def __call__(self, objs: jax.Array) -> jax.Array:
        return igd_plus(objs, self.pf)

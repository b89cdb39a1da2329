"""OpenES whose members are low-rank perturbations of one shared centre.

For a member too large to be a row of a ``(pop, dim)`` matrix (low-rank ES as
"Evolution Strategies at the Hyperscale", EGGROLL, and "Evolution Strategies
at Scale: LLM Fine-Tuning Beyond Reinforcement Learning"). ``ask`` hands
evaluate a :class:`~evox_tpu.core.lowrank.LowRankPopulation`, never a
population; ``tell`` contracts the shaped fitness with the factors, drawn
again from ``noise_key`` as ``OpenES`` draws its noise again.

The step (minimising, ``f`` the fitness tell is given, ``pairs = pop // 2``):
``grad(leaf) = 1 / (pop * sigma) * sum_p (f_p+ - f_p-) * sigma / sqrt(rank) *
A_p @ B_p.T`` and ``center -= learning_rate * grad``: plain SGD on the
estimated gradient of ``E[f]`` with respect to the perturbation ``sigma *
eps`` itself, as the two papers write it. ``OpenES`` differentiates with
respect to ``eps`` (its ``1 / sigma`` stays), so ``OpenES`` at
``learning_rate * sigma`` takes the same step on the same members.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....core.algorithm import Algorithm
from ....core.distributed import POP_AXIS
from ....core.instrument import CAST, GRADIENT, NOISE, UPDATE, scope
from ....core.lowrank import LowRankPopulation, tree_factors
from ....core.struct import PyTreeNode, field


class LowRankOpenESState(PyTreeNode):
    # ``fitness``: what the last tell was given (the shaped fitness of the
    # last generation), kept so that a comparison can apply its own tell to
    # it. Must stay float32: it is compared, not carried.
    center: Any = field(sharding=P())
    fitness: jax.Array = field(sharding=P(POP_AXIS), storage=False)
    noise_key: jax.Array = field(sharding=P())
    key: jax.Array = field(sharding=P())


class LowRankOpenES(Algorithm):
    """``center_init``: a pytree of float arrays (a bare array is a tree of
    one leaf), or a function of no arguments that makes one: ``init`` then
    calls it, and the algorithm holds no copy of a centre of gigabytes beside
    the state's. ``compute_dtype``: the dtype of the copy of the centre's
    matrices that ``ask`` makes once a generation for the forward pass to
    multiply with (None: the centre's own). ``center_dtype``: the dtype the
    search keeps its centre in, float32 unless a lower-precision path is
    asked for."""

    def __init__(
        self,
        center_init: Any,
        pop_size: int,
        learning_rate: float = 0.0005,
        noise_stdev: float = 0.001,
        rank: int = 1,
        compute_dtype: Optional[Any] = None,
        center_dtype: Any = jnp.float32,
    ):
        assert pop_size > 0 and pop_size % 2 == 0, "mirrored sampling needs an even pop_size"
        assert learning_rate > 0 and noise_stdev > 0 and rank > 0
        self.center_dtype = jnp.dtype(center_dtype)
        self.center_init = center_init
        self.pop_size = pop_size
        self.pairs = pop_size // 2
        self.learning_rate = learning_rate
        self.noise_stdev = noise_stdev
        self.rank = rank
        self.compute_dtype = None if compute_dtype is None else jnp.dtype(compute_dtype)

    def init(self, key: jax.Array) -> LowRankOpenESState:
        key, k = jax.random.split(key)
        center = self.center_init() if callable(self.center_init) else self.center_init
        return LowRankOpenESState(
            center=jax.tree.map(lambda x: jnp.asarray(x, dtype=self.center_dtype), center),
            fitness=jnp.zeros((self.pop_size,), jnp.float32),
            noise_key=k,
            key=key,
        )

    def _cast(self, leaf: jax.Array) -> jax.Array:
        if self.compute_dtype is None or leaf.ndim < 2:
            return leaf.astype(jnp.float32)
        return leaf.astype(self.compute_dtype)

    def ask(self, state: LowRankOpenESState) -> Tuple[LowRankPopulation, LowRankOpenESState]:
        key, k = jax.random.split(state.key)
        with scope(NOISE):
            factors = tree_factors(k, state.center, self.pairs, self.rank)
        with scope(CAST):
            center = jax.tree.map(self._cast, state.center)
        spec = LowRankPopulation(
            center=center,
            factors=factors,
            scale=jnp.float32(self.noise_stdev / math.sqrt(self.rank)),
            signs=jnp.concatenate([jnp.ones(self.pairs), -jnp.ones(self.pairs)]),
            noise_key=k,
            pop_size=self.pop_size,
        )
        return spec, state.replace(noise_key=k, key=key)

    def tell(self, state: LowRankOpenESState, fitness: jax.Array) -> LowRankOpenESState:
        diff = (fitness[: self.pairs] - fitness[self.pairs :]).astype(jnp.float32)
        weight = diff / (self.pop_size * math.sqrt(self.rank))
        leaves, treedef = jax.tree.flatten(state.center)
        with scope(GRADIENT):
            factors = treedef.flatten_up_to(
                tree_factors(state.noise_key, state.center, self.pairs, self.rank)
            )
        new = []
        for leaf, fac in zip(leaves, factors):
            if fac is None:
                new.append(leaf)
                continue
            a, b = fac
            with scope(GRADIENT):
                # one contraction a leaf: sum_p w_p A_p B_p^T, the pair and
                # the rank the contracted axes
                shaped = weight.reshape((self.pairs,) + (1,) * (a.ndim - 1))
                grad = jnp.einsum(
                    "p...ir,p...or->...io", a * shaped, b, precision="highest"
                )
            with scope(UPDATE):
                new.append((leaf - self.learning_rate * grad).astype(leaf.dtype))
        return state.replace(
            center=jax.tree.unflatten(treedef, new), fitness=fitness.astype(jnp.float32)
        )

"""NSGA-II (Deb et al. 2002). Capability parity with reference
src/evox/algorithms/mo/nsga2.py:23-96: merge parents + offspring, then
(rank, crowding) environmental selection; mating by binary tournament on
(rank, -crowding).

TPU-first: the environmental selection's non-dominated sort already produces
the (rank, crowding) keys of the survivors, so they are carried in the state
and reused for next generation's mating tournament — one O(N²) sort per
generation instead of two (the merged-population sort also early-stops once
``pop_size`` individuals are ranked)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ...operators.selection.non_dominate import (
    crowding_distance,
    non_dominated_sort,
    rank_crowding_truncate,
)
from ...operators.selection.basic import tournament_multifit
from jax.sharding import PartitionSpec as P
from ...core.distributed import POP_AXIS
from ...core.instrument import MERGE, SURVIVORS, scope
from ...core.struct import field
from .common import GAMOAlgorithm, MOState


class NSGA2State(MOState):
    rank: jax.Array = field(sharding=P(POP_AXIS), storage=True)  # survivors' Pareto rank from the last selection
    crowd: jax.Array = field(sharding=P(POP_AXIS), storage=True)  # survivors' crowding distance from the last selection


class NSGA2(GAMOAlgorithm):
    def __init__(self, *args, use_kernel=None, topk_interpret=False, **kwargs):
        """``use_kernel``: route the environmental truncation's last-front
        selection through the blockwise Pallas partial-top-k kernel
        (kernels/topk.py) instead of the full ``lexsort`` — survivor set
        identical, survivor order index-major (selection-law-equivalent:
        mating re-keys from the carried (rank, crowd)). ``None`` =
        backend default, currently off everywhere; the f32 lexsort path
        stays bit-identical to pre-kernel behavior. ``topk_interpret``
        runs the kernel in interpreter mode (CPU testing only)."""
        super().__init__(*args, **kwargs)
        self.use_kernel = use_kernel
        self.topk_interpret = topk_interpret

    def init(self, key: jax.Array) -> NSGA2State:
        base = super().init(key)
        return NSGA2State(
            population=base.population,
            fitness=base.fitness,
            offspring=base.offspring,
            key=base.key,
            rank=jnp.zeros((self.pop_size,), dtype=jnp.int32),
            crowd=jnp.zeros((self.pop_size,)),
        )

    def init_tell(self, state: NSGA2State, fitness: jax.Array) -> NSGA2State:
        return state.replace(
            fitness=fitness,
            rank=non_dominated_sort(fitness, mesh=self.mesh),
            crowd=crowding_distance(fitness),
        )

    def mate(self, key: jax.Array, state: NSGA2State) -> jax.Array:
        keys = jnp.stack([state.rank.astype(jnp.float32), -state.crowd], axis=1)
        return tournament_multifit(key, state.population, keys)

    def tell(self, state: NSGA2State, fitness: jax.Array) -> NSGA2State:
        with scope(MERGE):
            merged_pop = jnp.concatenate([state.population, state.offspring], axis=0)
            merged_fit = jnp.concatenate([state.fitness, fitness], axis=0)
        order, ranks = rank_crowding_truncate(
            merged_fit,
            self.pop_size,
            mesh=self.mesh,
            use_kernel=self.use_kernel,
            interpret=self.topk_interpret,
        )
        with scope(SURVIVORS):
            fit_sel = merged_fit[order]
            pop_sel = merged_pop[order]
        return state.replace(
            population=pop_sel,
            fitness=fit_sel,
            rank=ranks,
            # crowding for next generation's mating tournament is recomputed
            # over the survivors (the cut's crowding is masked to the worst
            # front and would leave -inf for the better fronts)
            crowd=crowding_distance(fit_sel),
        )

"""Shared machinery for multi-objective EAs.

Most MOEAs in the reference follow one GA skeleton (reference nsga2.py and
friends): uniform init -> evaluate parents once (init_ask/init_tell) ->
each generation propose offspring by (mating selection, SBX, polynomial
mutation) -> merge parent+offspring -> environmental selection in ``tell``.
:class:`GAMOAlgorithm` captures that skeleton; subclasses implement
``select`` (environmental selection) and may override ``mate`` (mating
selection) or ``variation``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ...core.algorithm import Algorithm
from ...core.distributed import POP_AXIS
from ...core.instrument import MERGE, scope
from ...core.struct import PyTreeNode, field
from ...operators.crossover.sbx import simulated_binary
from ...operators.mutation.ops import polynomial


class MOState(PyTreeNode):
    # per-field mesh layout (core.distributed.state_sharding): population
    # arrays shard over "pop"; the rng key replicates
    population: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    fitness: jax.Array = field(sharding=P(POP_AXIS), storage=True)  # (pop, m)
    offspring: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    key: jax.Array = field(sharding=P())


def uniform_init(key: jax.Array, lb: jax.Array, ub: jax.Array, pop_size: int) -> jax.Array:
    d = lb.shape[0]
    return jax.random.uniform(key, (pop_size, d)) * (ub - lb) + lb


class GAMOAlgorithm(Algorithm):
    """GA-skeleton MO base: subclasses implement ``select(state, merged_pop,
    merged_fit) -> (pop, fit)`` environmental selection.

    ``mesh``: a ``jax.sharding.Mesh`` with a ``"pop"`` axis. When given,
    the O(n²) non-dominated sort inside environmental selection (and
    migration ingest) is row-sharded across the mesh via ``shard_map``
    (operators/selection/non_dominate.py::_non_dominated_sort_sharded) —
    multi-chip MO then scales SELECTION as well as evaluation. Results
    are bit-identical to the replicated sort. Pass the same mesh as the
    workflow's; it can also be assigned later (``algo.mesh = mesh``)
    before the first ``tell`` is traced."""

    def __init__(self, lb, ub, n_objs: int, pop_size: int, mesh=None):
        self.lb = jnp.asarray(lb, dtype=jnp.float32)
        self.ub = jnp.asarray(ub, dtype=jnp.float32)
        self.dim = int(self.lb.shape[0])
        self.n_objs = n_objs
        self.pop_size = pop_size
        self.mesh = mesh

    # -- state ----------------------------------------------------------------
    def init(self, key: jax.Array) -> MOState:
        key, k = jax.random.split(key)
        pop = uniform_init(k, self.lb, self.ub, self.pop_size)
        return MOState(
            population=pop,
            fitness=jnp.full((self.pop_size, self.n_objs), jnp.inf),
            offspring=pop,
            key=key,
        )

    def init_ask(self, state: MOState) -> Tuple[jax.Array, MOState]:
        return state.population, state

    def init_tell(self, state: MOState, fitness: jax.Array) -> MOState:
        return state.replace(fitness=fitness)

    # -- generation -----------------------------------------------------------
    def mate(self, key: jax.Array, state: MOState) -> jax.Array:
        """Mating pool (default: random shuffle of the parent population)."""
        idx = jax.random.permutation(key, self.pop_size)
        return state.population[idx]

    def variation(self, key: jax.Array, mating_pool: jax.Array) -> jax.Array:
        k1, k2 = jax.random.split(key)
        off = simulated_binary(k1, mating_pool)
        return polynomial(k2, off, (self.lb, self.ub))

    def ask(self, state: MOState) -> Tuple[jax.Array, MOState]:
        key, k_mate, k_var = jax.random.split(state.key, 3)
        off = self.variation(k_var, self.mate(k_mate, state))
        return off, state.replace(offspring=off, key=key)

    def tell(self, state: MOState, fitness: jax.Array) -> MOState:
        with scope(MERGE):
            merged_pop = jnp.concatenate([state.population, state.offspring], axis=0)
            merged_fit = jnp.concatenate([state.fitness, fitness], axis=0)
        pop, fit = self.select(state, merged_pop, merged_fit)
        return state.replace(population=pop, fitness=fit)

    # -- migration ------------------------------------------------------------
    def migrate(self, state: MOState, pop: jax.Array, fitness: jax.Array):
        """Multi-objective migration (IslandWorkflow): merge migrants into
        the population and re-run NSGA-II-style (rank, crowding)
        environmental truncation — elitist, so a dominated migrant simply
        doesn't survive. This deliberately uses the rank+crowding criterion
        for every GA-skeleton MOEA (not the subclass's own ``select``):
        migration needs a cheap, universally-valid elitism test, and the
        algorithm's own selection reshapes the population next ``tell``
        anyway. States that cache (rank, crowd) mating keys (e.g. NSGA-II)
        get them refreshed to match the post-migration population."""
        from ...operators.selection.non_dominate import (
            crowding_distance,
            rank_crowding_truncate,
        )

        merged_pop = jnp.concatenate([state.population, pop], axis=0)
        merged_fit = jnp.concatenate([state.fitness, fitness], axis=0)
        order, ranks = rank_crowding_truncate(merged_fit, self.pop_size, mesh=self.mesh)
        fit_sel = merged_fit[order]
        updates = dict(population=merged_pop[order], fitness=fit_sel)
        if hasattr(state, "rank"):
            updates["rank"] = ranks
        if hasattr(state, "crowd"):
            updates["crowd"] = crowding_distance(fit_sel)
        return state.replace(**updates)

    def select(self, state: MOState, pop: jax.Array, fit: jax.Array):
        raise NotImplementedError

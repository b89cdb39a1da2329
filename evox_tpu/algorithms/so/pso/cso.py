"""Competitive Swarm Optimizer (reference:
src/evox/algorithms/so/pso_variants/cso.py:25+).

Each generation, particles are randomly paired; each pair's loser learns
from its winner and from the swarm mean, and only the updated losers are
re-evaluated (half the population per generation) — the ``init_ask`` /
``init_tell`` first-generation pattern of the reference.

TPU-first data movement: the reference formulation indexes
winners/losers through ``students``/``teachers`` index vectors — five random row-gathers in ``ask`` plus three scatters in
``tell`` per generation. A population is a *set*: CSO never needs stable
row identity, so this version permutes the population ONCE into
pair-major layout (`pop[perm]` — the single gather), selects winners and
losers with elementwise ``where`` on the two halves, and writes the next
generation as ``concat(winners, updated_losers)`` — pure streaming, zero
scatters. The swarm ``center`` falls out of the same gathered pass (the
permuted population IS the population), so the separate full-population
mean pass disappears too. Distributionally identical to the reference
update
(same pairing law, same learning rule, same tie-breaking: on equal
fitness the second row of the pair wins). The algorithm streams its
whole state through HBM every generation; no benchmark cell times it yet
(PERF.md section 7, row 2).

State carries NO ask→tell intermediates: ``tell`` replays the pairing
pass from the carried generation key (JAX's PRNG is counter-based, so
the replay is bit-identical — the trick OpenES and PGPE use too).
Inside the fused jitted step XLA CSEs the replay against ``ask``'s pass
(zero extra compute); what it buys is the loop carry — the dead
winners/candidates writes that a ``fori_loop`` of generations otherwise
round-trips through HBM. Under separately-jitted ask/tell (external
problems) the replay costs one extra streaming pass — still cheaper than carrying it in HBM state.
The state structure is branch-invariant, so ``lax.cond`` container
dispatch (containers/clustered.py) needs no special-casing.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ....core.algorithm import Algorithm
from ....core.distributed import POP_AXIS
from ....core.struct import PyTreeNode, field
from ....operators.sanitize import sanitize_bounds, validate_bound_handling


class CSOState(PyTreeNode):
    # per-field mesh layout (consumed by core.distributed.state_sharding /
    # the workflow's constrain_state): population-leading arrays shard over
    # the "pop" axis, everything else replicates
    population: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    fitness: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    velocity: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    key: jax.Array = field(sharding=P())
    # the generation key ``ask`` drew — ``tell`` replays the pairing pass
    # from it instead of carrying five half-pop intermediate arrays in the
    # loop state (see module docstring)
    pair_key: jax.Array = field(sharding=P())


class CSO(Algorithm):
    def __init__(
        self,
        lb,
        ub,
        pop_size: int,
        phi: float = 0.0,
        bound_handling: str = "clip",  # operators/sanitize.py, static
    ):
        self.bound_handling = validate_bound_handling(bound_handling)
        assert pop_size % 2 == 0, "CSO needs an even population size"
        self.lb = jnp.asarray(lb, dtype=jnp.float32)
        self.ub = jnp.asarray(ub, dtype=jnp.float32)
        self.dim = self.lb.shape[0]
        self.pop_size = pop_size
        self.phi = phi

    def init(self, key: jax.Array) -> CSOState:
        k_state, k_pop = jax.random.split(key)
        span = self.ub - self.lb
        pop = jax.random.uniform(k_pop, (self.pop_size, self.dim)) * span + self.lb
        return CSOState(
            population=pop,
            fitness=jnp.full((self.pop_size,), jnp.inf),
            velocity=jnp.zeros((self.pop_size, self.dim)),
            key=k_state,
            pair_key=k_state,  # placeholder; ask overwrites before any tell
        )

    # first generation: evaluate everyone once
    def init_ask(self, state: CSOState) -> Tuple[jax.Array, CSOState]:
        return state.population, state

    def init_tell(self, state: CSOState, fitness: jax.Array) -> CSOState:
        return state.replace(fitness=fitness)

    def _pair_pass(self, state: CSOState, k_gen: jax.Array):
        """The whole pair-major generation pass, derived from ``k_gen``.

        Called once in ``ask`` and replayed bit-identically in ``tell``
        (same key, counter-based PRNG); inside the fused step XLA CSEs the
        two calls into one. Returns (winner x/v/f, candidates, new_v).
        """
        k_pair, k1, k2, k3 = jax.random.split(k_gen, 4)
        half = self.pop_size // 2
        # the ONE gather: population/velocity/fitness into pair-major
        # layout (pair i = permuted rows i and half+i — the block-split
        # pairing, equal in law to any fixed pairing of a uniform perm)
        perm = jax.random.permutation(k_pair, self.pop_size)
        pair_x = state.population[perm].reshape(2, half, self.dim)
        pair_v = state.velocity[perm].reshape(2, half, self.dim)
        pair_f = state.fitness[perm].reshape(2, half)
        # swarm center: the permuted population is the population, so the
        # mean fuses into this same pass instead of a separate full read
        center = (
            jnp.sum(pair_x[0], axis=0) + jnp.sum(pair_x[1], axis=0)
        )[None, :] * (1.0 / self.pop_size)
        a_wins = pair_f[0] < pair_f[1]
        w = a_wins[:, None]
        x_w = jnp.where(w, pair_x[0], pair_x[1])
        x_s = jnp.where(w, pair_x[1], pair_x[0])
        v_s = jnp.where(w, pair_v[1], pair_v[0])
        f_w = jnp.where(a_wins, pair_f[0], pair_f[1])
        v_w = jnp.where(w, pair_v[0], pair_v[1])
        r1 = jax.random.uniform(k1, (half, self.dim))
        r2 = jax.random.uniform(k2, (half, self.dim))
        r3 = jax.random.uniform(k3, (half, self.dim))
        new_v = r1 * v_s + r2 * (x_w - x_s) + self.phi * r3 * (center - x_s)
        candidates = sanitize_bounds(
            x_s + new_v, self.lb, self.ub, self.bound_handling
        )
        return x_w, v_w, f_w, candidates, new_v

    def ask(self, state: CSOState) -> Tuple[jax.Array, CSOState]:
        key, k_gen = jax.random.split(state.key)
        _, _, _, candidates, _ = self._pair_pass(state, k_gen)
        return candidates, state.replace(key=key, pair_key=k_gen)

    def tell(self, state: CSOState, fitness: jax.Array) -> CSOState:
        # replay ask's pass from the carried key (bit-identical; see
        # _pair_pass), then streaming writes only: the next generation's
        # row order is (winners ‖ updated losers) — a set-preserving
        # relabeling, which the next ask's fresh uniform permutation makes
        # distributionally identical to the reference's in-place scatter
        # update
        x_w, v_w, f_w, candidates, new_v = self._pair_pass(state, state.pair_key)
        return state.replace(
            population=jnp.concatenate([x_w, candidates]),
            velocity=jnp.concatenate([v_w, new_v]),
            fitness=jnp.concatenate([f_w, fitness]),
        )

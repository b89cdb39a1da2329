"""Abstract Algorithm: the ask–evaluate–tell contract.

Mirrors the capability of the reference's ``Algorithm`` (reference:
src/evox/core/algorithm.py:10-96) with a purely functional, TPU-idiomatic
signature: the algorithm object holds only *static* hyperparameters; all
mutable data (population, strategy parameters, PRNG key) lives in a typed
pytree state returned by ``init`` and threaded through ``ask``/``tell``.

Optional ``init_ask``/``init_tell`` support algorithms whose first
generation differs from steady state (e.g. GA-style algorithms that evaluate
a full parent population once before producing offspring) — same duck-typed
detection idea as reference algorithm.py:52-96, implemented via method
override detection.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

AlgorithmState = Any


class Algorithm:
    """Base class for every optimization algorithm.

    Contract::

        state = algo.init(key)                 # build initial state
        pop, state = algo.ask(state)           # propose candidates
        state = algo.tell(state, fitness)      # ingest fitness of `pop`

    ``ask`` must return a ``(pop_size, ...)`` candidate array (or pytree with
    leading pop axis). ``tell`` receives fitness with shape ``(pop_size,)``
    for single-objective or ``(pop_size, n_objectives)`` for multi-objective.

    The one exception is a **perturbation spec**: where a member is too large
    to be a row of a population (a model of 1e9 parameters), ``ask`` returns a
    pytree none of whose leaves has a population axis
    (:class:`~evox_tpu.core.lowrank.LowRankPopulation`: the shared centre, the
    noise key, sigma, rank, each member's sign, and the factors as a function
    of key, leaf and pair) and says so with ``has_population_axis = False``.
    The problem applies the perturbation inside its evaluation and still
    returns ``(pop_size,)`` fitness, in the spec's order of members; ``tell``
    contracts the fitness with the factors, which it draws again from the
    state's noise key. ``StdWorkflow`` hands such candidates to ``evaluate``
    as they are, and refuses them under a ``"pop"`` mesh, which has no axis of
    theirs to shard.

    First-generation overrides: implement ``init_ask``/``init_tell`` when the
    initial evaluation differs (different pop size or bookkeeping). Workflows
    dispatch them on generation 0 when present.
    """

    # Opt-in mesh for algorithms whose internal O(n²) machinery (e.g. MO
    # environmental selection) can shard across a device mesh. None =
    # replicated computation; GAMOAlgorithm exposes it as a constructor
    # argument, any other algorithm accepts plain attribute assignment.
    mesh = None

    def init(self, key: jax.Array) -> AlgorithmState:
        raise NotImplementedError

    def ask(self, state: AlgorithmState) -> Tuple[Any, AlgorithmState]:
        raise NotImplementedError

    def tell(self, state: AlgorithmState, fitness: jax.Array) -> AlgorithmState:
        raise NotImplementedError

    # -- optional first-generation hooks ------------------------------------
    def init_ask(self, state: AlgorithmState) -> Tuple[Any, AlgorithmState]:
        """Candidates for the very first evaluation. Default: ``ask``."""
        return self.ask(state)

    def init_tell(self, state: AlgorithmState, fitness: jax.Array) -> AlgorithmState:
        """Ingest the very first fitness batch. Default: ``tell``."""
        return self.tell(state, fitness)

    @property
    def has_init_ask(self) -> bool:
        return type(self).init_ask is not Algorithm.init_ask

    @property
    def has_init_tell(self) -> bool:
        return type(self).init_tell is not Algorithm.init_tell

    # -- optional migration hook --------------------------------------------
    def migrate(
        self, state: AlgorithmState, pop: Any, fitness: jax.Array
    ) -> AlgorithmState:
        """Ingest foreign individuals (island migration / human-in-the-loop;
        the slot behind ``StdWorkflow(migrate_helper=...)`` and
        ``IslandWorkflow`` — reference std_workflow.py:230-244).

        ``fitness`` is in the internal minimization convention. The default
        offers each migrant to the worst rows of ``state.population`` /
        ``state.fitness``, accepting only migrants that beat the row they
        would displace (elitist acceptance — an unconditional overwrite
        would let a bad migrant clobber e.g. a PSO pbest row and break its
        monotonicity invariant). Enough for every population-based
        single-objective state carrying those two fields; algorithms with
        extra per-individual bookkeeping (personal bests, archives) or
        multi-objective selection should override.
        """
        pop_arr = getattr(state, "population", None)
        fit_arr = getattr(state, "fitness", None)
        if pop_arr is None or fit_arr is None or fit_arr.ndim != 1:
            raise NotImplementedError(
                f"{type(self).__name__} has no (population, 1-d fitness) "
                "state fields; override migrate() to support migration"
            )
        k = fitness.shape[0]
        worst = jnp.argsort(-fit_arr)[:k]
        accept = fitness < fit_arr[worst]  # (k,) per-row elitism
        new_rows = jnp.where(accept[:, None], pop, pop_arr[worst])
        new_fit = jnp.where(accept, fitness, fit_arr[worst])
        return state.replace(
            population=pop_arr.at[worst].set(new_rows),
            fitness=fit_arr.at[worst].set(new_fit),
        )

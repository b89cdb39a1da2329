"""Abstract Problem (reference: src/evox/core/problem.py:12-31).

Functional form: ``init(key) -> state`` (``None`` for stateless problems) and
``evaluate(state, pop) -> (fitness, state)``. Fitness is ``(pop,)`` for
single-objective, ``(pop, m)`` for multi-objective. Problems that cannot run
under jit (host simulators, external services) set ``jittable = False`` and
declare ``fit_shape``/``fit_dtype`` so workflows can route them through
``jax.pure_callback`` with a known output signature.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax

ProblemState = Any


class Problem:
    """Base class for every optimization problem."""

    #: False for host-side problems that must run via callback.
    jittable: bool = True

    def init(self, key: Optional[jax.Array] = None) -> ProblemState:
        return None

    def evaluate(self, state: ProblemState, pop: Any) -> Tuple[jax.Array, ProblemState]:
        raise NotImplementedError

    #: Optional ``evaluate_genome(state, pop, genome, adapter)``: the same
    #: result as ``evaluate(state, pop)`` for a problem that can read the
    #: candidates out of the undecoded batch. ``StdWorkflow`` calls it in
    #: ``evaluate``'s place when its ``pop_transforms`` is exactly one
    #: ``TreeAndVector.batched_to_tree``: ``genome`` is the ``(n, dim)``
    #: batch the algorithm proposed, ``adapter`` that ``TreeAndVector``
    #: (its ``offsets`` are the genome's layout), ``pop`` the decoded tree
    #: as ever (what of it nobody reads, XLA drops). ``None``: no such path.
    evaluate_genome = None

    def fit_shape(self, pop_size: int) -> Tuple[int, ...]:
        """Fitness shape for a given pop size (used for callback problems)."""
        return (pop_size,)

    #: dtype of the fitness array (used for callback problems).
    fit_dtype = "float32"

    def pf(self) -> jax.Array:
        """True Pareto front, for problems that know it (MO benchmarks)."""
        raise NotImplementedError(f"{type(self).__name__} has no known Pareto front")

"""Numerical self-defense: on-device degenerate-state detection + restarts.

PR 2 made the EVALUATION side self-healing (farm fault tolerance, crash-safe
checkpoints, NaN fitness quarantine) — but a poisoned ALGORITHM state (a
non-finite ``eigh`` on CMA-ES's covariance, a collapsed ``sigma``, a
stagnated search) persisted forever with no detection and no recovery.
:class:`GuardedAlgorithm` closes that hole: a generic wrapper with the same
:class:`~evox_tpu.core.algorithm.Algorithm` interface that, after every
``tell``, evaluates a set of jit-compatible health predicates over the
wrapped state and — on trigger — performs an ON-DEVICE restart under
``lax.cond``: a fresh ``init()`` from a split key, re-centered on the
best-so-far point, with best-so-far and a restart counter carried in the
wrapper's own state. Everything is pure jittable math (no host
callbacks), so it works identically in ``wf.step`` loops, the fused
``wf.run`` ``fori_loop``, and ``run_host_pipelined``.

The restart-strategy literature this follows: IPOP/BIPOP increasing-
population restarts (Auger & Hansen 2005; Hansen 2009; arXiv 2409.11765)
and evosax's restart wrappers (arXiv 2212.04180). The wrapper implements
the *detect + same-shape restart* half on device; population GROWTH needs
new static shapes and therefore lives at the host boundary —
:class:`IPOPRestarts` (consumed by ``StdWorkflow.run(restarts=...)`` and
``run_host_pipelined(restarts=...)``, workflows/ipop.py) doubles the
population between dispatches, one recompile per doubling.

No-trigger law (asserted in tests/test_numeric_chaos.py): with guards
enabled but never triggered, ``GuardedAlgorithm(alg)`` produces a
BIT-identical trajectory to bare ``alg`` — ``init`` hands the wrapped
algorithm the caller's key unchanged (the wrapper's restart key is derived
via ``fold_in``), ``ask``/``tell`` delegate exactly, and the untriggered
``lax.cond`` branch returns the delegated result untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .algorithm import Algorithm
from .distributed import POP_AXIS
from .struct import PyTreeNode, field, static_field

__all__ = [
    "GuardedAlgorithm",
    "GuardedState",
    "IPOPRestarts",
    "recenter_state",
    "TRIGGER_NONFINITE",
    "TRIGGER_SIGMA",
    "TRIGGER_DIVERSITY",
    "TRIGGER_STAGNATION",
]

# bitmask codes recorded in GuardedState.last_trigger
TRIGGER_NONFINITE = 1  # NaN (optionally Inf) leaves in the wrapped state
TRIGGER_SIGMA = 2  # step size below floor / above ceiling
TRIGGER_DIVERSITY = 4  # candidate diversity collapsed below the floor
TRIGGER_STAGNATION = 8  # generations without best-so-far improvement


class GuardedState(PyTreeNode):
    inner: Any  # wrapped algorithm state (sharding: the inner annotations)
    pop: Any = field(sharding=P(POP_AXIS), storage=True)  # last asked candidate batch
    best_x: Any = field(sharding=P())  # best-so-far candidate
    best_fitness: jax.Array = field(sharding=P())  # internal (minimize) key
    stagnation: jax.Array = field(sharding=P())  # gens since best improved
    restarts: jax.Array = field(sharding=P())  # on-device restarts so far
    # host-boundary baseline: the value of `restarts` when the IPOP driver
    # (workflows/ipop.py) last evaluated its escalation rule. Written ONLY
    # by the host between dispatches; device code never touches it. Living
    # in the state (and therefore in every checkpoint), it makes the
    # escalation decision stateless — a crashed-and-resumed run re-derives
    # the identical doubling schedule.
    checked_restarts: jax.Array = field(sharding=P())
    last_trigger: jax.Array = field(sharding=P())  # bitmask, 0 = healthy
    key: jax.Array = field(sharding=P())  # restart PRNG stream
    # static metadata: the wrapped algorithm's population size, pickled
    # with checkpoints so an IPOP resume (workflows/ipop.py) can rebuild
    # the matching compiled program before restoring the snapshot
    pop_size: int = static_field(default=0)


def _has_field(state: Any, name: str) -> bool:
    return dataclasses.is_dataclass(state) and name in getattr(
        state, "__dataclass_fields__", {}
    )


def recenter_state(astate: Any, best_x: Any) -> Any:
    """Re-center a fresh algorithm state on the best-so-far point.

    Duck-typed, shape-preserving: a distribution-based state (``mean`` or
    ``center`` field matching ``best_x``'s shape) moves its distribution
    center onto ``best_x``; a population-based state (2-D ``population``)
    gets ``best_x`` written into row 0 (elitist seeding — the rest of the
    fresh population keeps exploring). States with neither field are
    returned unchanged (the fresh ``init()`` alone is the restart).
    """
    # accept numpy leaves too: a checkpoint-restored state carries numpy
    # arrays until the next dispatch re-devices them
    if not isinstance(best_x, (jax.Array, np.ndarray)) or best_x.ndim != 1:
        return astate  # pytree candidates (neuroevolution): no re-centering
    best_x = jnp.asarray(best_x)
    for name in ("mean", "center"):
        if _has_field(astate, name):
            cur = getattr(astate, name)
            if isinstance(cur, jax.Array) and cur.shape == best_x.shape:
                return astate.replace(**{name: best_x.astype(cur.dtype)})
    if _has_field(astate, "population"):
        pop = astate.population
        if (
            isinstance(pop, jax.Array)
            and pop.ndim == 2
            and pop.shape[1:] == best_x.shape
        ):
            return astate.replace(
                population=pop.at[0].set(best_x.astype(pop.dtype))
            )
    return astate


class GuardedAlgorithm(Algorithm):
    """Wrap any single-objective :class:`Algorithm` with on-device health
    checks and automatic restart.

    After each ``tell`` the wrapper evaluates the enabled predicates
    against the freshly updated inner state:

    - **non-finite leaves** (``check_nonfinite``): any NaN in a floating
      leaf of the inner state. ``check_inf=True`` also triggers on ±Inf —
      off by default because +Inf fitness sentinels are idiomatic in this
      codebase (DE's unevaluated rows, PSO's initial pbest).
    - **step-size collapse/explosion** (``sigma_floor``/``sigma_ceiling``):
      checked only when the inner state carries a ``sigma`` field (ES
      family); skipped statically otherwise.
    - **diversity collapse** (``diversity_floor``): finite-masked mean
      per-dimension std of the last asked candidate batch (same statistic
      as TelemetryMonitor's diversity ring) below the floor. Off by
      default — the right floor is problem-scale dependent.
    - **stagnation** (``stagnation_limit``): generations since the
      best-so-far fitness improved (the direction-aware counter from
      monitors/telemetry.py, re-derived here on the wrapper's own
      best-so-far key — fitness arrives already sign-flipped by the
      workflow, so "improved" is always "strictly smaller"). Off by
      default.

    On trigger, a ``lax.cond`` swaps in ``inner.init(fresh_key)``
    re-centered on the best-so-far point (:func:`recenter_state`), resets
    the stagnation counter and increments ``restarts``; the best-so-far
    pair survives the restart. With no trigger the trajectory is
    bit-identical to the bare algorithm (see module docstring).

    The wrapper forwards unknown attributes (``pop_size``, ``dim``,
    ``lb``...) to the wrapped algorithm, so it composes with workflows and
    containers that duck-type those.
    """

    def __init__(
        self,
        algorithm: Algorithm,
        check_nonfinite: bool = True,
        check_inf: bool = False,
        sigma_floor: Optional[float] = 1e-20,
        sigma_ceiling: Optional[float] = 1e20,
        diversity_floor: Optional[float] = None,
        stagnation_limit: Optional[int] = None,
    ):
        self.algorithm = algorithm
        self.check_nonfinite = check_nonfinite
        self.check_inf = check_inf
        self.sigma_floor = sigma_floor
        self.sigma_ceiling = sigma_ceiling
        self.diversity_floor = diversity_floor
        self.stagnation_limit = stagnation_limit

    def __getattr__(self, name: str) -> Any:
        # only reached when normal lookup fails: forward hyperparameter
        # reads (pop_size, dim, lb, ub, ...) to the wrapped algorithm
        if name.startswith("__") or name == "algorithm":
            raise AttributeError(name)
        return getattr(object.__getattribute__(self, "algorithm"), name)

    # first-generation dispatch mirrors the wrapped algorithm exactly
    @property
    def has_init_ask(self) -> bool:
        return self.algorithm.has_init_ask

    @property
    def has_init_tell(self) -> bool:
        return self.algorithm.has_init_tell

    # ------------------------------------------------------------------ api
    def init(self, key: jax.Array) -> GuardedState:
        # the INNER algorithm gets the caller's key unchanged — this is
        # what makes the no-trigger trajectory bit-identical to the bare
        # algorithm; the wrapper's restart stream is folded off it
        inner = self.algorithm.init(key)
        restart_key = jax.random.fold_in(key, 0x6A72)  # "gr"
        # the candidate buffer must keep ONE static shape across the whole
        # run or the fused run()'s fori_loop carry changes type: size it to
        # the widest batch the algorithm ever evaluates (init_ask and ask
        # may differ — CSO scores the full population first, halves after),
        # and let tell slice down to the live batch width
        first_sds = jax.eval_shape(self._first_ask, inner)[0]
        steady_sds = jax.eval_shape(self.algorithm.ask, inner)[0]
        pop = jax.tree.map(
            lambda f, s: jnp.zeros(
                (max(f.shape[0], s.shape[0]),) + f.shape[1:], f.dtype
            ),
            first_sds,
            steady_sds,
        )
        best_x = jax.tree.map(
            lambda s: jnp.zeros(s.shape[1:], s.dtype), first_sds
        )
        return GuardedState(
            inner=inner,
            pop=pop,
            best_x=best_x,
            best_fitness=jnp.asarray(jnp.inf, dtype=jnp.float32),
            stagnation=jnp.zeros((), dtype=jnp.int32),
            restarts=jnp.zeros((), dtype=jnp.int32),
            checked_restarts=jnp.zeros((), dtype=jnp.int32),
            last_trigger=jnp.zeros((), dtype=jnp.int32),
            key=restart_key,
            pop_size=int(getattr(self.algorithm, "pop_size", 0)),
        )

    def _first_ask(self, inner: Any):
        # the batch the workflow will evaluate FIRST (init_ask when the
        # algorithm has one) — sizes the `pop`/`best_x` buffers
        if self.algorithm.has_init_ask or self.algorithm.has_init_tell:
            return self.algorithm.init_ask(inner)
        return self.algorithm.ask(inner)

    @staticmethod
    def _store_pop(buf: Any, pop: Any) -> Any:
        """Write ``pop`` into the leading rows of the fixed-width buffer
        (static shapes: the leftover rows keep their previous content and
        are never read — tell slices to the live batch width)."""
        return jax.tree.map(
            lambda b, p: p if p.shape[0] == b.shape[0]
            else jnp.concatenate([p.astype(b.dtype), b[p.shape[0]:]]),
            buf,
            pop,
        )

    def ask(self, state: GuardedState) -> Tuple[Any, GuardedState]:
        pop, inner = self.algorithm.ask(state.inner)
        return pop, state.replace(
            inner=inner, pop=self._store_pop(state.pop, pop)
        )

    def init_ask(self, state: GuardedState) -> Tuple[Any, GuardedState]:
        pop, inner = self.algorithm.init_ask(state.inner)
        return pop, state.replace(
            inner=inner, pop=self._store_pop(state.pop, pop)
        )

    def tell(self, state: GuardedState, fitness: jax.Array) -> GuardedState:
        inner = self.algorithm.tell(state.inner, fitness)
        return self._postcheck(state, inner, fitness)

    def init_tell(self, state: GuardedState, fitness: jax.Array) -> GuardedState:
        inner = self.algorithm.init_tell(state.inner, fitness)
        return self._postcheck(state, inner, fitness)

    def migrate(self, state: GuardedState, pop: Any, fitness: jax.Array) -> GuardedState:
        # migrants count as progress: fold them into best-so-far/stagnation
        # (fitness arrives in the internal minimization convention, like
        # tell's) — otherwise an island's best genome is invisible to the
        # stagnation guard, which would fire a spurious restart and
        # re-center on a stale pre-migration best
        fitness = fitness.astype(jnp.float32)
        masked = jnp.where(jnp.isfinite(fitness), fitness, jnp.inf)
        mig_best = jnp.min(masked)
        mig_best_i = jnp.argmin(masked)
        improved = mig_best < state.best_fitness
        best_x = jax.tree.map(
            lambda b, p: jnp.where(improved, p[mig_best_i].astype(b.dtype), b),
            state.best_x,
            pop,
        )
        return state.replace(
            inner=self.algorithm.migrate(state.inner, pop, fitness),
            best_x=best_x,
            best_fitness=jnp.minimum(state.best_fitness, mig_best),
            stagnation=jnp.where(improved, 0, state.stagnation),
        )

    # ------------------------------------------------------- health checks
    def _postcheck(
        self, state: GuardedState, inner: Any, fitness: jax.Array
    ) -> GuardedState:
        if fitness.ndim != 1:
            raise ValueError(
                "GuardedAlgorithm restarts re-center on a scalar best-so-far "
                f"point and are single-objective; got fitness of shape "
                f"{fitness.shape}"
            )
        fitness = fitness.astype(jnp.float32)
        # the rows of the fixed-width pop buffer this fitness scored
        # (static slice: fitness length is a trace-time constant)
        batch = jax.tree.map(lambda p: p[: fitness.shape[0]], state.pop)

        # -- best-so-far / stagnation (internal minimization convention;
        #    finite-masked so a poison generation cannot claim the best)
        masked = jnp.where(jnp.isfinite(fitness), fitness, jnp.inf)
        gen_best = jnp.min(masked)
        gen_best_i = jnp.argmin(masked)
        improved = gen_best < state.best_fitness
        best_fitness = jnp.minimum(state.best_fitness, gen_best)
        best_x = jax.tree.map(
            lambda b, p: jnp.where(improved, p[gen_best_i].astype(b.dtype), b),
            state.best_x,
            batch,
        )
        stagnation = jnp.where(improved, 0, state.stagnation + 1)

        trigger = jnp.zeros((), dtype=jnp.int32)
        if self.check_nonfinite:
            bad = self._nonfinite_in(inner)
            trigger = trigger | jnp.where(bad, TRIGGER_NONFINITE, 0)
        if _has_field(inner, "sigma") and (
            self.sigma_floor is not None or self.sigma_ceiling is not None
        ):
            sigma = jnp.abs(jnp.asarray(inner.sigma, jnp.float32))
            bad = jnp.zeros((), dtype=bool)
            # inclusive comparisons so the algorithm-local rails compose:
            # clamp_step_size (es/common.py) pins a collapsed sigma at
            # EXACTLY its floor/ceiling, which must still read as collapsed.
            # Per-axis sigma (SNES family): ANY collapsed/exploded axis is
            # degenerate — min against the floor, max against the ceiling
            if self.sigma_floor is not None:
                bad = bad | (jnp.min(sigma) <= self.sigma_floor)
            if self.sigma_ceiling is not None:
                bad = bad | (jnp.max(sigma) >= self.sigma_ceiling)
            trigger = trigger | jnp.where(bad, TRIGGER_SIGMA, 0)
        if self.diversity_floor is not None:
            div = self._diversity(batch)
            trigger = trigger | jnp.where(
                div < self.diversity_floor, TRIGGER_DIVERSITY, 0
            )
        if self.stagnation_limit is not None:
            trigger = trigger | jnp.where(
                stagnation >= self.stagnation_limit, TRIGGER_STAGNATION, 0
            )

        checked = state.replace(
            inner=inner,
            best_x=best_x,
            best_fitness=best_fitness,
            stagnation=stagnation,
            last_trigger=trigger,
        )
        return jax.lax.cond(trigger > 0, self._restart, lambda s: s, checked)

    def _restart(self, state: GuardedState) -> GuardedState:
        key, k_init = jax.random.split(state.key)
        fresh = self.algorithm.init(k_init)
        fresh = recenter_state(fresh, state.best_x)
        return state.replace(
            inner=fresh,
            stagnation=jnp.zeros((), dtype=jnp.int32),
            restarts=state.restarts + 1,
            key=key,
        )

    def _nonfinite_in(self, tree: Any) -> jax.Array:
        bad = jnp.zeros((), dtype=bool)
        for leaf in jax.tree.leaves(tree):
            x = jnp.asarray(leaf)
            if not jnp.issubdtype(x.dtype, jnp.floating):
                continue
            bad = bad | jnp.any(jnp.isnan(x))
            if self.check_inf:
                bad = bad | jnp.any(jnp.isinf(x))
        return bad

    @staticmethod
    def _diversity(pop: Any) -> jax.Array:
        """Finite-masked mean per-dimension std over the batch axis —
        the same statistic TelemetryMonitor rings (telemetry.py)."""
        std_sum = jnp.zeros((), dtype=jnp.float32)
        n_dims = 0
        for x in jax.tree.leaves(pop):
            x = jnp.asarray(x)
            if not jnp.issubdtype(x.dtype, jnp.floating):
                continue
            flat = jnp.asarray(x, jnp.float32).reshape(x.shape[0], -1)
            ok = jnp.isfinite(flat)
            n = jnp.maximum(jnp.sum(ok.astype(jnp.float32), axis=0), 1.0)
            mean = jnp.sum(jnp.where(ok, flat, 0.0), axis=0) / n
            var = jnp.sum(jnp.where(ok, (flat - mean) ** 2, 0.0), axis=0) / n
            std_sum = std_sum + jnp.sum(jnp.sqrt(var))
            n_dims += flat.shape[1]
        return std_sum / max(n_dims, 1)

    # -------------------------------------------------------------- report
    def health_report(self, state: GuardedState) -> dict:
        """Eager JSON-friendly snapshot of the wrapper's health counters."""
        trig = int(state.last_trigger)
        return {
            "restarts": int(state.restarts),
            "stagnation": int(state.stagnation),
            "best_fitness": float(state.best_fitness),
            "pop_size": int(state.pop_size),
            "algorithm": type(self.algorithm).__name__,
            "last_trigger": trig,
            "last_trigger_names": [
                name
                for bit, name in (
                    (TRIGGER_NONFINITE, "nonfinite_state"),
                    (TRIGGER_SIGMA, "sigma_collapse"),
                    (TRIGGER_DIVERSITY, "diversity_collapse"),
                    (TRIGGER_STAGNATION, "stagnation"),
                )
                if trig & bit
            ],
        }


class IPOPRestarts:
    """Host-boundary IPOP policy: double the population on restart.

    Population growth needs new static shapes — on TPU that means a new
    compiled program, so growth lives BETWEEN dispatches (one recompile
    per doubling, amortized over the whole restart segment). Consumed by
    ``StdWorkflow.run(restarts=...)`` and ``run_host_pipelined(...,
    restarts=...)`` (workflows/ipop.py), which chunk the run at
    ``check_every`` generations and consult the GuardedAlgorithm counters
    between chunks.

    Args:
        algorithm_factory: ``pop_size -> Algorithm``; must return a
            :class:`GuardedAlgorithm` (the device-side detector the host
            boundary reads). Must be deterministic in ``pop_size`` so a
            resumed run rebuilds the identical program.
        max_restarts: population doublings allowed (IPOP budget).
        growth: population multiplier per restart (2 = classic IPOP).
        check_every: generations per dispatch segment between host checks.
        stagnation_limit: additionally escalate when the guarded state's
            stagnation counter reaches this limit, even if no on-device
            restart fired (lets the device wrapper keep only cheap NaN /
            sigma guards while the host owns stagnation escalation).
        handoff_pop: population threshold for the low-memory handoff —
            once a doubling reaches/crosses it, the restart builds from
            ``handoff_factory`` instead of ``algorithm_factory``, so IPOP
            growth escapes the dense track's single-device memory/eigh
            wall (``es.common.EighScaleError``) onto the sharded
            low-memory track (e.g. ``GuardedAlgorithm(ShardedES(
            SepCMAES(...), mesh))``). Must be paired with
            ``handoff_factory``. The handoff is deterministic in
            ``pop_size``, so checkpointed resumes rebuild the identical
            (possibly handed-off) program; each handoff is surfaced in
            ``run_report()["guardrail"]["ipop"]``.
        handoff_factory: ``pop_size -> GuardedAlgorithm`` over the
            low-memory track, used at/past ``handoff_pop``.
    """

    def __init__(
        self,
        algorithm_factory,
        max_restarts: int = 4,
        growth: int = 2,
        check_every: int = 50,
        stagnation_limit: Optional[int] = None,
        handoff_pop: Optional[int] = None,
        handoff_factory=None,
    ):
        if max_restarts < 0:
            raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
        if growth < 2:
            raise ValueError(f"growth must be >= 2, got {growth}")
        if check_every < 1:
            raise ValueError(f"check_every must be >= 1, got {check_every}")
        if (handoff_pop is None) != (handoff_factory is None):
            raise ValueError(
                "handoff_pop and handoff_factory must be given together"
            )
        self.algorithm_factory = algorithm_factory
        self.max_restarts = max_restarts
        self.growth = growth
        self.check_every = check_every
        self.stagnation_limit = stagnation_limit
        self.handoff_pop = handoff_pop
        self.handoff_factory = handoff_factory

    def uses_handoff(self, pop_size: int) -> bool:
        """Whether a (re)build at ``pop_size`` lands on the low-memory
        handoff track — pure in ``pop_size`` so resumes re-derive it."""
        return self.handoff_pop is not None and pop_size >= self.handoff_pop

    def make_algorithm(self, pop_size: int) -> "GuardedAlgorithm":
        factory = (
            self.handoff_factory
            if self.uses_handoff(pop_size)
            else self.algorithm_factory
        )
        algo = factory(pop_size)
        if not isinstance(algo, GuardedAlgorithm):
            raise TypeError(
                "IPOPRestarts factories must return a GuardedAlgorithm "
                "(the on-device detector the host boundary reads); got "
                f"{type(algo).__name__}"
            )
        return algo

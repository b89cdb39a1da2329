"""Hierarchical multi-level ES: an outer meta-ES adapting inner-ES
hyperparameters across island groups.

Design sources (PAPERS.md): "Distributed Evolution Strategies with
Multi-Level Learning" (arXiv 2310.05377) — a two-level scheme where an
outer evolutionary loop adapts the hyperparameters (step size, learning
rate, parent count) of a population of inner ES instances from their
observed progress — and Fiber (arXiv 2003.11164) — elastic worker
membership: member loss is a normal scheduling event that degrades the
pool, never a run-killing failure. The reference has no analog of either
(its Ray layer replicates identical workflows; PARITY row 58).

Structure:

- **Groups** are independent inner ES runs (island semantics: separate
  states, separate PRNG streams, no migration — diversity is the point;
  each group is judged on its OWN phase progress).
- Each **outer generation** samples one hyperparameter vector per group
  from an outer Gaussian (``theta_g = mean + sigma * eps_g`` in the
  specs' transformed space), applies it to the group's inner ES, runs
  ``inner_steps`` inner generations (a *phase*), scores each group by
  its phase improvement (best-so-far before minus after — per-phase
  credit, so a group's standing history doesn't mask a bad proposal),
  and updates the outer mean/sigma CEM-style from the elite fraction.
- **Hyperparameters** (:class:`HyperSpec`) bind two ways: ``kind="attr"``
  rebinds a (dotted) template attribute as a TRACED value — the tenant
  fleet's binding law (:func:`~evox_tpu.workflows.tenancy.
  bind_hyperparams`), so ONE compiled program serves every proposal and
  every outer generation — and ``kind="state"`` overwrites an inner
  STATE leaf at phase start (CMA-family ``sigma`` lives in state, not on
  the template). Integer hyperparameters (``mu``) adapt through their
  continuous carriers: bind the padded weight table / derived scalars as
  attrs (see GUIDE §6); a static shape change is a recompile by
  construction and deliberately unsupported inside a run.

Two inner drive modes:

- **fleet** (jittable problems): the groups ARE a
  :class:`~evox_tpu.workflows.tenancy.VectorizedWorkflow` tenant fleet —
  one fused vmapped dispatch per inner phase, (TENANT, POP) 2-D-mesh
  capable, attr hyperparams rebound by state surgery on the fleet's
  traced hyperparam leaves (no recompile). A ``ShardedES(mesh=None,
  n_shards=k)`` template gives every member the per-shard fold_in
  sampling LAW replicated (vmappable); layout comes from the fleet mesh.
- **sequential** (host/external problems, or ``fleet=False``): groups
  run one at a time through two jitted halves (`ask` / `tell`) whose
  hyperparams are jit OPERANDS — two compiles total for all groups and
  outer generations. This is the mode that composes with a true
  POP-sharded ``ShardedES(mesh=...)`` member (each group's dispatch
  spans the whole mesh — multi-host capable for jittable problems) and
  with :class:`~evox_tpu.problems.neuroevolution.process_farm.
  ProcessRolloutFarm` evaluation: a killed/hung worker re-dispatches
  inside the farm (bit-identical fitness law, PR 2), a farm degraded
  below its floor marks only the AFFECTED GROUP inactive
  (``FarmDegradedError`` caught by name — the group parks, its outer
  score is excluded, the run continues on the survivors) and the farm's
  ``admit()`` re-admission hook runs between phases so replacement
  workers rejoin — Fiber's elastic membership on our substrate.

The outer loop is a HOST boundary (like IPOP): ``step()`` is one outer
generation (phase dispatches inside), ``run()`` a Python loop over it.
Callback-free by construction (pinned in test_no_host_callbacks):
everything device-side is plain jit; all orchestration is host-side
between dispatches, so it runs wherever its problem does. Multi-objective outer scoring is out of scope (single-objective
inner ES only).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.algorithm import Algorithm
from ..core.monitor import Monitor
from ..core.problem import Problem
from ..core.struct import PyTreeNode, static_field
from ..utils.common import parse_opt_direction
from .tenancy import VectorizedWorkflow, bind_hyperparams

__all__ = ["HyperSpec", "MultiLevelES", "MultiLevelState"]

# farm/pool exhaustion raised by an evaluation backend whose live
# membership fell below its floor — matched by NAME so workflows never
# import the problems package (dependency direction, CLAUDE.md)
_DEGRADED_ERRORS = ("FarmDegradedError",)


def _is_degraded(e: BaseException) -> bool:
    return any(
        c.__name__ in _DEGRADED_ERRORS for c in type(e).__mro__
    )


@dataclasses.dataclass(frozen=True)
class HyperSpec:
    """One adapted inner-ES hyperparameter.

    Args:
        name: template attribute path (``kind="attr"``; dotted paths
            reach through wrappers, e.g. ``"algorithm.noise_stdev"``
            inside a ``ShardedES``) or inner-STATE leaf name
            (``kind="state"``, e.g. the CMA family's ``sigma``).
        init: initial value (external space).
        sigma: outer mutation stdev (in the TRANSFORMED space — for
            ``transform="log"`` a value of 0.3 means ±35%-ish proposals).
        lb / ub: external-space clip bounds of every proposal.
        transform: ``"log"`` (positive scale parameters — the outer
            Gaussian lives on log-theta) or ``"linear"``.
        kind: ``"attr"`` (traced template attribute) or ``"state"``
            (inner state leaf overwritten at phase start).
    """

    name: str
    init: float
    sigma: float = 0.3
    lb: float = 1e-8
    ub: float = 1e8
    transform: str = "log"
    kind: str = "attr"

    def __post_init__(self):
        if self.transform not in ("log", "linear"):
            raise ValueError(f"unknown transform {self.transform!r}")
        if self.kind not in ("attr", "state"):
            raise ValueError(f"unknown kind {self.kind!r}")
        if not (self.lb < self.ub):
            raise ValueError(f"need lb < ub, got [{self.lb}, {self.ub}]")
        if self.transform == "log" and self.lb <= 0:
            raise ValueError("log-transformed specs need lb > 0")
        if not (self.lb <= self.init <= self.ub):
            raise ValueError(
                f"init {self.init} outside [{self.lb}, {self.ub}]"
            )

    def to_internal(self, v):
        return jnp.log(v) if self.transform == "log" else jnp.asarray(v)

    def to_external(self, z):
        v = jnp.exp(z) if self.transform == "log" else z
        return jnp.clip(v, self.lb, self.ub)


class _PhaseBest(Monitor):
    """Minimal vmappable score tracker (internal minimization
    convention): per group, the best candidate so far (reporting /
    threshold readout) and the MEAN fitness of the newest generation
    (the outer score — the mean is the low-variance progress signal;
    a best-ever readout is dominated by sampling luck and cannot rank
    hyperparameter proposals). Deliberately not a TelemetryMonitor —
    two scalars per group, present even on monitor-less inner states."""

    def hooks(self):
        return ("post_eval",)

    def init(self, key=None):
        return (
            jnp.asarray(jnp.inf, dtype=jnp.float32),  # best-so-far
            jnp.asarray(jnp.inf, dtype=jnp.float32),  # newest-gen mean
        )

    def post_eval(self, mstate, cand, fitness):
        # hooks see USER-convention fitness; fold to minimization
        best, _ = mstate
        f = fitness * self.opt_direction[0]
        return (
            jnp.minimum(best, jnp.min(f).astype(jnp.float32)),
            jnp.mean(f).astype(jnp.float32),
        )


class MultiLevelState(PyTreeNode):
    """Outer state. The small outer-distribution leaves replicate (no
    annotations — the workflow-state convention of StdWorkflowState);
    the inner states carry their own per-field annotations through."""

    generation: jax.Array  # OUTER generation counter
    outer_mean: jax.Array = None  # (H,) transformed space
    outer_sigma: jax.Array = None  # (H,)
    theta: jax.Array = None  # (G, H) live proposals
    key: jax.Array = None
    inner: Any = None  # fleet state | (G,)-stacked inner algo states
    prob: Any = None  # sequential mode: shared problem state
    best: jax.Array = None  # (G,) best-so-far (internal min convention)
    score: jax.Array = None  # (G,) newest phase-end mean fitness
    active: jax.Array = None  # (G,) bool
    first_step: bool = static_field(default=True)


class MultiLevelES:
    """Outer meta-ES over a population of inner ES groups.

    Args:
        algorithm: the inner-ES template (any single-objective
            :class:`Algorithm`; ``ShardedES``-wrapped members supported —
            see the module docstring for which mode carries the
            shard_map island). Algorithms declaring init_ask/init_tell
            are rejected in sequential mode.
        problem: shared :class:`Problem` (host problems force sequential
            mode).
        n_groups: inner group count (the outer population size).
        hyper_specs: the adapted hyperparameters (:class:`HyperSpec`).
        inner_steps: inner generations per outer generation (the phase
            length — the outer credit-assignment window).
        outer_lr: CEM interpolation rate of the outer mean/sigma toward
            the elite proposals (0 disables adaptation — with
            ``explore=False`` that is the frozen-hyperparameter control
            the convergence test baselines against).
        elite_frac: top fraction of ACTIVE groups (by phase improvement)
            recombined into the outer update.
        sigma_decay: multiplicative outer-sigma decay per outer
            generation (1.0 = none), applied after the CEM update.
        explore: sample per-group proposals around the outer mean. With
            ``False`` every group runs the mean exactly (paired with
            ``outer_lr=0`` this freezes hyperparameters entirely).
        exploit: at each phase start, restart every group's inner state
            from the BEST group's phase-end state (each group keeps its
            OWN PRNG-stream leaves, so groups stay decorrelated) — the
            outer SELECTION step of the multi-level scheme, and what
            makes phase-end scores directly comparable (same start
            state, different hyperparameters). ``False`` keeps classic
            independent islands; scores then use per-phase improvement
            credit instead of absolute level.
        opt_direction / pop_transforms: as :class:`StdWorkflow` (single
            objective only).
        mesh: fleet mode — a (TENANT, POP) mesh for the vmapped fleet;
            sequential mode — the inner workflow/ShardedES mesh is the
            algorithm's own affair (pass the mesh to ``ShardedES``).
        fleet: force the drive mode (default: fleet iff the problem is
            jittable).
        admit_every: call the problem's ``admit()`` re-admission hook (if
            it has one) every N phases (sequential mode; 0 disables).
    """

    def __init__(
        self,
        algorithm: Algorithm,
        problem: Problem,
        n_groups: int,
        hyper_specs: Sequence[HyperSpec],
        inner_steps: int = 10,
        outer_lr: float = 0.5,
        elite_frac: float = 0.5,
        sigma_decay: float = 1.0,
        explore: bool = True,
        exploit: bool = True,
        opt_direction: Any = "min",
        pop_transforms: Sequence[Callable] = (),
        mesh: Optional[jax.sharding.Mesh] = None,
        fleet: Optional[bool] = None,
        admit_every: int = 1,
        jit_step: bool = True,
    ):
        if n_groups < 2:
            raise ValueError(f"need >= 2 groups, got {n_groups}")
        if not hyper_specs:
            raise ValueError("need at least one HyperSpec")
        if inner_steps < 1:
            raise ValueError("inner_steps must be >= 1")
        if not (0.0 <= outer_lr <= 1.0):
            raise ValueError("outer_lr must be in [0, 1]")
        if not (0.0 < elite_frac <= 1.0):
            raise ValueError("elite_frac must be in (0, 1]")
        names = [s.name for s in hyper_specs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate hyperparameter names: {names}")
        self.algorithm = algorithm
        self.problem = problem
        self.n_groups = int(n_groups)
        self.specs = tuple(hyper_specs)
        self.inner_steps = int(inner_steps)
        self.outer_lr = float(outer_lr)
        self.elite_frac = float(elite_frac)
        self.sigma_decay = float(sigma_decay)
        self.explore = bool(explore)
        self.exploit = bool(exploit)
        self.opt_direction = parse_opt_direction(opt_direction)
        self.pop_transforms = tuple(pop_transforms)
        self.mesh = mesh
        self.admit_every = int(admit_every)
        self.jit_step = jit_step
        self.fleet_mode = bool(
            problem.jittable if fleet is None else fleet
        )
        if self.fleet_mode and not problem.jittable:
            raise ValueError(
                "fleet mode needs a jittable problem (a host callback "
                "cannot run under vmap); pass fleet=False for the "
                "sequential drive"
            )
        self._attr_specs = tuple(s for s in self.specs if s.kind == "attr")
        self._state_specs = tuple(s for s in self.specs if s.kind == "state")
        # validate attr paths against the template once (the fleet's rule)
        for s in self._attr_specs:
            obj = algorithm
            for part in s.name.split("."):
                if not hasattr(obj, part):
                    raise ValueError(
                        f"HyperSpec[{s.name!r}]: template "
                        f"{type(obj).__name__} has no attribute {part!r}"
                    )
                obj = getattr(obj, part)
        # membership / adaptation event log (host-side observability;
        # surfaced through report())
        self.events: list = []
        if self.fleet_mode:
            self._score_mon = _PhaseBest()
            self._fleet = VectorizedWorkflow(
                algorithm,
                problem,
                n_tenants=self.n_groups,
                hyperparams={
                    s.name: jnp.full(
                        (self.n_groups,), s.init, dtype=jnp.float32
                    )
                    for s in self._attr_specs
                },
                monitors=[self._score_mon],
                opt_direction=opt_direction,
                pop_transforms=pop_transforms,
                mesh=mesh,
                jit_step=jit_step,
            )
        else:
            if getattr(algorithm, "has_init_ask", False) or getattr(
                algorithm, "has_init_tell", False
            ):
                raise ValueError(
                    "sequential multi-level drive supports steady-state "
                    "ask/tell algorithms only (the ES family); "
                    f"{type(algorithm).__name__} declares init hooks"
                )
            self._fleet = None
            # two jitted halves with the hyperparams as TRACED operands:
            # two compiles serve every group and every outer generation
            self._seq_ask = (
                jax.jit(self._seq_ask_impl) if jit_step
                else self._seq_ask_impl
            )
            self._seq_tell = (
                jax.jit(self._seq_tell_impl) if jit_step
                else self._seq_tell_impl
            )

    # ------------------------------------------------------------- internals
    def _seq_ask_impl(self, astate: Any, hp: Dict[str, jax.Array]):
        algo = bind_hyperparams(self.algorithm, hp)
        pop, astate = algo.ask(astate)
        cand = pop
        for t in self.pop_transforms:
            cand = t(cand)
        return cand, astate

    def _seq_tell_impl(
        self, astate: Any, hp: Dict[str, jax.Array], fitness: jax.Array
    ):
        algo = bind_hyperparams(self.algorithm, hp)
        return algo.tell(astate, fitness * self.opt_direction[0])

    def _theta_to_values(self, theta: jax.Array) -> Dict[str, jax.Array]:
        """(G, H) internal proposals -> {name: (G,) external values}."""
        return {
            s.name: s.to_external(theta[:, i])
            for i, s in enumerate(self.specs)
        }

    def hyper_values(self, state: MultiLevelState) -> Dict[str, np.ndarray]:
        """The CURRENT per-group hyperparameter values (external space,
        host numpy) — what each group's inner ES is actually running."""
        return {
            k: np.asarray(jax.device_get(v))
            for k, v in self._theta_to_values(state.theta).items()
        }

    def _apply_values(
        self, state: MultiLevelState, values: Dict[str, jax.Array]
    ) -> MultiLevelState:
        """Install proposals into the inner states: attr specs rebind the
        TRACED hyperparam leaves (fleet) / are handed to the jitted
        halves (sequential); state specs overwrite the (G,)-stacked
        inner-state leaf."""
        inner = state.inner
        if self.fleet_mode and self._attr_specs:
            hp = dict(inner.tenants.hyperparams)
            for s in self._attr_specs:
                hp[s.name] = values[s.name].astype(hp[s.name].dtype)
            inner = inner.replace(tenants=inner.tenants.replace(hyperparams=hp))
        algo_states = inner.tenants.algo if self.fleet_mode else inner
        if self._state_specs:
            updates = {}
            for s in self._state_specs:
                leaf = getattr(algo_states, s.name)
                updates[s.name] = jnp.broadcast_to(
                    values[s.name].astype(leaf.dtype).reshape(
                        (self.n_groups,) + (1,) * (leaf.ndim - 1)
                    ),
                    leaf.shape,
                )
            algo_states = algo_states.replace(**updates)
            if self.fleet_mode:
                inner = inner.replace(
                    tenants=inner.tenants.replace(algo=algo_states)
                )
            else:
                inner = algo_states
        return state.replace(inner=inner)

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> MultiLevelState:
        k_outer, k_inner = jax.random.split(key)
        mean = jnp.stack(
            [s.to_internal(jnp.asarray(s.init, jnp.float32)) for s in self.specs]
        ).astype(jnp.float32)
        sigma = jnp.asarray([s.sigma for s in self.specs], dtype=jnp.float32)
        theta = jnp.tile(mean, (self.n_groups, 1))
        if self.fleet_mode:
            inner = self._fleet.init(k_inner)
            prob = None
        else:
            gkeys = jax.random.split(k_inner, self.n_groups + 1)
            inner = jax.vmap(self.algorithm.init)(gkeys[: self.n_groups])
            prob = self.problem.init(gkeys[-1])
        state = MultiLevelState(
            generation=jnp.zeros((), jnp.int32),
            outer_mean=mean,
            outer_sigma=sigma,
            theta=theta,
            key=k_outer,
            inner=inner,
            prob=prob,
            best=jnp.full((self.n_groups,), jnp.inf, dtype=jnp.float32),
            score=jnp.full((self.n_groups,), jnp.inf, dtype=jnp.float32),
            active=jnp.ones((self.n_groups,), dtype=bool),
            first_step=True,
        )
        # the init proposals ARE the means — install them so group state
        # (CMA sigma etc.) starts where the outer distribution says
        return self._apply_values(
            state, self._theta_to_values(theta)
        )

    # ------------------------------------------------------------------ step
    def step(self, state: MultiLevelState) -> MultiLevelState:
        """ONE outer generation: exploit (restart groups from the best
        group's state) → sample proposals → install → run one inner
        phase → score (phase-end mean fitness) → CEM outer update.
        Host-driven between inner dispatches (the IPOP discipline)."""
        if self.exploit and not state.first_step:
            state = self._exploit_best(state)
        key, k_eps = jax.random.split(state.key)
        if self.explore:
            eps = jax.random.normal(
                k_eps, (self.n_groups, len(self.specs)), dtype=jnp.float32
            )
            theta = state.outer_mean + state.outer_sigma * eps
        else:
            theta = jnp.tile(state.outer_mean, (self.n_groups, 1))
        state = self._apply_values(
            state.replace(theta=theta, key=key),
            self._theta_to_values(theta),
        )
        score_before = state.score
        state = self._run_phase(state)
        if self.exploit:
            # groups started this phase from the SAME state: the
            # phase-end mean fitness ranks the proposals directly
            gain = -state.score
        else:
            # independent islands: per-phase improvement credit, so a
            # group's standing history doesn't mask a bad proposal
            gain = jnp.where(
                jnp.isinf(score_before),
                -state.score,
                score_before - state.score,
            )
        gain = jnp.nan_to_num(gain, nan=0.0, posinf=0.0, neginf=0.0)
        state = self._outer_update(state, gain)
        return state.replace(
            generation=state.generation + 1, first_step=False
        )

    def _exploit_best(self, state: MultiLevelState) -> MultiLevelState:
        """Restart every group's inner ALGORITHM state from the current
        best-scoring active group's, preserving each group's own PRNG
        leaves (any leaf whose field name ends in ``key`` — the OpenES
        ``key``/``noise_key`` convention) so group streams stay
        decorrelated. Hyperparam/monitor/problem leaves are untouched."""
        score = np.asarray(jax.device_get(state.score))
        active = np.asarray(jax.device_get(state.active))
        score = np.where(active, score, np.inf)
        if not np.isfinite(score).any():
            return state
        best_g = int(np.argmin(score))

        def pick(path, x):
            if any(
                str(getattr(k, "name", "")).endswith("key") for k in path
            ):
                return x
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == self.n_groups:
                return jnp.broadcast_to(x[best_g], x.shape)
            return x

        algo_states = (
            state.inner.tenants.algo if self.fleet_mode else state.inner
        )
        algo_states = jax.tree_util.tree_map_with_path(pick, algo_states)
        if self.fleet_mode:
            inner = state.inner.replace(
                tenants=state.inner.tenants.replace(algo=algo_states)
            )
        else:
            inner = algo_states
        return state.replace(inner=inner)

    def run(self, state: MultiLevelState, n_outer: int) -> MultiLevelState:
        for _ in range(int(n_outer)):
            state = self.step(state)
        return state

    # ----------------------------------------------------------- inner phase
    def _run_phase(self, state: MultiLevelState) -> MultiLevelState:
        if self.fleet_mode:
            inner = self._fleet.run(state.inner, self.inner_steps)
            tracker_best, tracker_mean = inner.tenants.monitors[0]
            best = jnp.where(
                state.active,
                jnp.minimum(state.best, tracker_best.astype(jnp.float32)),
                state.best,
            )
            score = jnp.where(
                state.active, tracker_mean.astype(jnp.float32), state.score
            )
            return state.replace(inner=inner, best=best, score=score)
        return self._run_phase_sequential(state)

    def _run_phase_sequential(self, state: MultiLevelState) -> MultiLevelState:
        values = self._theta_to_values(state.theta)
        active = np.asarray(jax.device_get(state.active)).copy()
        best = np.asarray(jax.device_get(state.best)).copy()
        score = np.asarray(jax.device_get(state.score)).copy()
        inner = state.inner
        pstate = state.prob
        phase_idx = int(state.generation)
        if (
            self.admit_every
            and phase_idx % self.admit_every == 0
            and hasattr(self.problem, "admit")
        ):
            admitted = self.problem.admit()
            if admitted:
                self.events.append(
                    {"event": "admit", "phase": phase_idx, "workers": admitted}
                )
        for g in range(self.n_groups):
            if not active[g]:
                continue
            hp_g = {
                s.name: values[s.name][g] for s in self._attr_specs
            }
            astate = jax.tree.map(lambda x: x[g], inner)
            try:
                for _ in range(self.inner_steps):
                    cand, astate = self._seq_ask(astate, hp_g)
                    fitness, pstate = self.problem.evaluate(pstate, cand)
                    f_int = np.asarray(
                        jax.device_get(fitness), dtype=np.float32
                    ) * float(self.opt_direction[0])
                    best[g] = min(best[g], float(f_int.min()))
                    score[g] = float(f_int.mean())
                    astate = self._seq_tell(astate, hp_g, fitness)
            except Exception as e:
                if not _is_degraded(e):
                    raise
                # elastic membership: the evaluation pool fell below its
                # floor mid-phase — THIS group parks (its partial phase
                # is discarded from the outer score), the run continues
                # on the remaining groups; a later admit() can only help
                # future phases, the parked group stays parked (its inner
                # state is no longer comparable to its proposal)
                active[g] = False
                self.events.append(
                    {
                        "event": "group_lost",
                        "phase": phase_idx,
                        "group": g,
                        "error": f"{type(e).__name__}: {e}",
                    }
                )
                continue
            inner = jax.tree.map(
                lambda full, new, _g=g: full.at[_g].set(new)
                if hasattr(full, "at")
                else full,
                inner,
                astate,
            )
        if not active.any():
            raise RuntimeError(
                "multi-level ES: every group lost its evaluation backend "
                f"(events: {self.events[-self.n_groups:]})"
            )
        return state.replace(
            inner=inner,
            prob=pstate,
            best=jnp.asarray(best, dtype=jnp.float32),
            score=jnp.asarray(score, dtype=jnp.float32),
            active=jnp.asarray(active, dtype=bool),
        )

    # ---------------------------------------------------------- outer update
    def _outer_update(
        self, state: MultiLevelState, gain: jax.Array
    ) -> MultiLevelState:
        if self.outer_lr == 0.0:
            return state
        active = np.asarray(jax.device_get(state.active))
        n_active = int(active.sum())
        if n_active < 2:
            return state  # nothing to rank against
        k = max(1, int(round(self.elite_frac * n_active)))
        g = np.asarray(jax.device_get(gain))
        g = np.where(active, g, -np.inf)  # parked groups never elite
        elite = np.argsort(-g)[:k]
        theta = np.asarray(jax.device_get(state.theta))
        elite_theta = theta[elite]
        lr = self.outer_lr
        mean = (1 - lr) * np.asarray(
            jax.device_get(state.outer_mean)
        ) + lr * elite_theta.mean(axis=0)
        # the outer sigma stays FIXED (modulo the explicit decay knob):
        # a CEM-style shrink toward the elite std collapses exploration
        # within a few outer generations whenever the elites cluster
        # (measured: adaptation froze mid-run with best-so-far pinned at
        # an early lucky draw), and a frozen outer sigma is exactly the
        # (1, λ)-ES-with-fixed-step outer loop of the multi-level paper
        sigma = np.maximum(
            np.asarray(jax.device_get(state.outer_sigma))
            * self.sigma_decay,
            1e-4,
        )
        return state.replace(
            outer_mean=jnp.asarray(mean, jnp.float32),
            outer_sigma=jnp.asarray(sigma, jnp.float32),
        )

    # --------------------------------------------------------------- readout
    def best_fitness(self, state: MultiLevelState) -> Tuple[Any, Any]:
        """(per-group best-so-far, overall best) in the USER convention."""
        sign = float(self.opt_direction[0])
        per_group = np.asarray(jax.device_get(state.best)) * sign
        overall = (
            per_group.min() if sign > 0 else per_group.max()
        )
        return per_group, float(overall)

    def report(self, state: Optional[MultiLevelState] = None) -> dict:
        """Host-side observability: outer distribution, per-group scores,
        membership events (run_report picks this up via ``extra=``)."""
        out = {
            "mode": "fleet" if self.fleet_mode else "sequential",
            "n_groups": self.n_groups,
            "inner_steps": self.inner_steps,
            "hyperparams": [s.name for s in self.specs],
            "events": list(self.events),
        }
        if state is not None:
            per_group, overall = self.best_fitness(state)
            out.update(
                {
                    "outer_generation": int(state.generation),
                    "active_groups": int(
                        np.asarray(jax.device_get(state.active)).sum()
                    ),
                    "best_per_group": per_group.tolist(),
                    "best_overall": overall,
                    "outer_mean_external": {
                        s.name: float(
                            s.to_external(state.outer_mean[i])
                        )
                        for i, s in enumerate(self.specs)
                    },
                }
            )
        return out

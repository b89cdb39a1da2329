"""Multi-tenant run serving: vmapped strategy fleets and a RunQueue.

The "millions of users" workload (ROADMAP north star) is thousands of
*small independent searches*, not one big one — and a Python loop of
solo :class:`~evox_tpu.workflows.std.StdWorkflow` runs pays a dispatch,
a compile cache lookup and a host round-trip PER RUN PER CHUNK. evosax
(PAPERS.md, arXiv 2212.04180) proved the fix for JAX ES: ``vmap`` whole strategies so N runs become
ONE fused XLA program; Fiber (PAPERS.md) showed population-of-runs
serving is the shape PBT/RL fleets need. evox_tpu's frozen-``PyTreeNode``
states stack trivially under ``vmap``, so this module makes fleets a
first-class workflow:

- :class:`VectorizedWorkflow` — N instances of the SAME algorithm class
  (stacked hyperparameters, seeds, and per-tenant problem states with a
  shared shape) vmapped into one jitted ``step`` and one fused ``run``
  dispatch. Reuses the existing machinery wholesale: the
  ``make_run_loop``/``fused_run`` fori-loop (one compile covers every
  trip count, carry donation via ``donate_carries=``), ``DtypePolicy``
  bf16 storage, ``quarantine_nonfinite``, monitors (vmapped per-tenant
  rings), checkpointer/supervisor chunking, and ``GuardedAlgorithm``
  (the wrapper's ask/tell vmap like any algorithm's).
- A (TENANT, POP) 2-D mesh layout: the per-field
  ``field(sharding=...)`` annotations are reused unchanged —
  ``constrain_state(axis_prefix=TENANT_AXIS)`` shifts each spec one
  axis right under the tenant axis (``P("pop")`` → ``P("tenant",
  "pop")``, ``P()`` → ``P("tenant")``), and regex ``rules=`` (the
  ``match_partition_rules`` pattern, SNIPPETS.md [2]) override leaves
  the annotations don't describe. No reference analog; this is the
  refactor unlock for ROADMAP items 4 (tenants × big pops) and 5 (PBT).
- :class:`RunQueue` — the service layer on top: submit
  :class:`TenantSpec` jobs beyond the fleet capacity, run in supervised
  dispatch chunks (:class:`~evox_tpu.workflows.supervisor.RunSupervisor`
  deadlines/retry/restore apply to the whole fleet dispatch), retire
  tenants when their generation budget completes, admit pending specs
  into the freed slot WITHOUT recompiling (state surgery at fixed
  shapes), and evict mid-run — an eviction yields a single-tenant
  checkpoint that a solo ``StdWorkflow`` resumes
  (:meth:`VectorizedWorkflow.extract_tenant` /
  :meth:`VectorizedWorkflow.solo_workflow`).

Correctness contract (tests/test_tenancy.py): tenant ``i`` of a fleet
reproduces a solo run of the same (algorithm, seed, hyperparams) —
bitwise where vmap preserves XLA codegen, else within a documented
tolerance (vmap batches matmuls/reductions, which can re-associate at
the last ulp) plus the standard convergence-threshold gates; an evicted
tenant's checkpoint resumed solo reproduces the remaining trajectory;
supervisor chaos laws (retry/restore are replays of immutable states)
hold through the fleet path.

Scope: fleets require a JITTABLE problem (a host-callback ``evaluate``
cannot run under ``vmap``; serve host problems with
``run_host_pipelined`` per run, or wrap them jittable). Hyperparameters
are bound as attributes on a shallow copy of the template algorithm
inside the traced step, so only values the algorithm reads in
``init``/``ask``/``tell`` can vary per tenant — derived quantities baked
at construction (optax optimizer closures, CMA recombination weights)
do not re-derive; shapes (``pop_size``, ``dim``) must be shared.
"""

from __future__ import annotations

import copy
import dataclasses
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _SpecP

from ..core.algorithm import Algorithm
from ..core.attest import IntegrityError
from ..core.distributed import (
    POP_AXIS as _POP,
    TENANT_AXIS as _TENANT,
    constrain_state,
)
from ..core.dtype_policy import DtypePolicy, apply_compute, apply_storage
from ..core.monitor import Monitor
from ..core.problem import Problem
from ..core.struct import PyTreeNode, field, static_field
from ..utils.common import parse_opt_direction
from .checkpoint import (
    CheckpointConfigError,
    WorkflowCheckpointer,
    checkpointed_run,
    enter_run,
)
from .common import (
    build_hook_table,
    fused_run,
    make_run_loop,
    quarantine_nonfinite,
    run_hooks,
)
from .std import StdWorkflow, StdWorkflowState

__all__ = [
    "TenantState",
    "VectorizedWorkflow",
    "VectorizedWorkflowState",
    "TenantSpec",
    "RunQueue",
]


class TenantState(PyTreeNode):
    """One tenant's slice of the fleet (every leaf is tenant-stacked in
    the live :class:`VectorizedWorkflowState`). Mirrors
    ``StdWorkflowState``'s (generation, algo, prob, monitors) plus the
    tenant's traced hyperparameter bindings. ``generation`` is the
    tenant's OWN counter — it differs from the fleet's lockstep counter
    for tenants a RunQueue admitted mid-run, and it is what generation-
    gated monitor hooks and eviction checkpoints see."""

    generation: jax.Array = field(sharding=_SpecP())
    algo: Any = None
    prob: Any = None
    monitors: Tuple[Any, ...] = ()
    hyperparams: Dict[str, Any] = field(default_factory=dict)


class VectorizedWorkflowState(PyTreeNode):
    generation: jax.Array  # scalar: the fleet steps in lockstep
    tenants: TenantState  # leaves carry a leading (n_tenants,) axis
    # optional (n_tenants,) bool mask: a frozen tenant's post-tell state
    # is discarded via an elementwise where-select inside the fused step,
    # so a poisoned slot stops advancing WITHOUT surgery or recompile
    # (FleetHealthPolicy's "freeze" action, workflows/fleet_health.py).
    # None (the default) compiles the step without the select at all —
    # pre-policy fleets keep their exact program; materializing the mask
    # later changes the carry structure (one designed retrace)
    frozen: Any = field(sharding=_SpecP(), default=None)
    first_step: bool = static_field(default=True)


def bind_hyperparams(template: Any, hp: Dict[str, Any]) -> Any:
    """A shallow copy of ``template`` with ``hp``'s (possibly dotted)
    attribute paths bound as TRACED values — the one hyperparameter-
    binding law shared by the vmapped tenant fleet (each tenant's slice
    under vmap) and the multi-level ES's jitted inner halves (each
    group's proposal as a jit operand). Dotted paths copy-on-write each
    intermediate object, so a ``GuardedAlgorithm``'s inner algorithm is
    copied before its attribute is rebound; the template itself is never
    mutated."""
    if not hp:
        return template
    root = copy.copy(template)
    fresh: Dict[str, Any] = {}
    for name, value in hp.items():
        obj = root
        parts = name.split(".")
        for depth, part in enumerate(parts[:-1]):
            prefix = ".".join(parts[: depth + 1])
            child = fresh.get(prefix)
            if child is None:
                child = copy.copy(getattr(obj, part))
                fresh[prefix] = child
                setattr(obj, part, child)
            obj = child
        setattr(obj, parts[-1], value)
    return root


def _tenant_keys(key: jax.Array, n: int) -> jax.Array:
    """Accept one key (split per tenant) or an already-stacked (n, ...)
    key batch — the stacked form is how fleet-vs-solo equivalence tests
    hand tenant ``i`` exactly the key its solo run would get."""
    key = jnp.asarray(key)
    typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    if (typed and key.ndim >= 1) or (not typed and key.ndim >= 2):
        if key.shape[0] != n:
            raise ValueError(
                f"stacked key batch has leading axis {key.shape[0]}, "
                f"expected n_tenants={n}"
            )
        return key
    return jax.random.split(key, n)


class VectorizedWorkflow:
    """Vmap N instances of one algorithm class into ONE fused dispatch.

    Args:
        algorithm: the template :class:`Algorithm`. Static shape
            hyperparameters (``pop_size``, ``dim``) are shared by every
            tenant; per-tenant variation comes from ``hyperparams`` and
            the per-tenant PRNG keys.
        problem: a JITTABLE :class:`Problem`, shared evaluate; each
            tenant gets its own problem STATE (vmapped ``init``), so
            keyed/stochastic problems differ per tenant.
        n_tenants: fleet width. Static — a different width is a new
            compiled program (exactly like a different pop_size).
        hyperparams: ``{name: stacked_value}`` — each value's leading
            axis is ``n_tenants`` and ``name`` is an attribute (or
            dotted path, e.g. ``"algorithm.noise_stdev"`` through a
            :class:`~evox_tpu.core.guardrail.GuardedAlgorithm`) on the
            template. Inside the traced step each tenant's slice is
            bound onto a shallow copy of the template, so the value
            flows through the tenant's ``init``/``ask``/``tell`` math
            as a traced operand. Only attributes the algorithm READS in
            those methods take effect (constructor-derived closures,
            e.g. an optax optimizer's baked learning rate, do not).
        monitors: shared monitor OBJECTS whose states are vmapped —
            each tenant gets its own TelemetryMonitor ring / EvalMonitor
            device archive. Monitors that stream through host callbacks
            (CheckpointMonitor, StepTimerMonitor, PopMonitor,
            EvoXVisMonitor, EvalMonitor full histories) are REJECTED at
            construction — a callback cannot run inside the vmapped
            step on any backend.
        opt_direction / pop_transforms / fit_transforms /
        quarantine_nonfinite: as :class:`StdWorkflow`, applied PER
            TENANT (a rank transform ranks within each tenant's batch).
        mesh: a mesh carrying a ``"tenant"`` axis (and usually a
            ``"pop"`` axis): ``create_mesh((TENANT_AXIS, POP_AXIS),
            shape=(t, p))``. Tenant-stacked state lays out by the
            per-field annotations shifted under the tenant axis
            (``constrain_state(axis_prefix=TENANT_AXIS)``); candidates
            and fitness are sharded ``P(TENANT_AXIS, POP_AXIS)`` /
            ``P(TENANT_AXIS)``.
        rules: optional ``[(regex, PartitionSpec), ...]`` overriding the
            annotation-derived layout per leaf path
            (:func:`~evox_tpu.core.distributed.match_partition_rules`
            semantics; matched against the TENANT-STACKED state's key
            paths, e.g. ``r"\\.algo\\.population$"``).
        dtype_policy / donate_carries / jit_step: as
            :class:`StdWorkflow` — the policy's storage downcast and
            the donated fused-run carry apply to the whole stacked
            state (the bytes win multiplies by N).
    """

    def __init__(
        self,
        algorithm: Algorithm,
        problem: Problem,
        n_tenants: int,
        hyperparams: Optional[Dict[str, Any]] = None,
        monitors: Sequence[Monitor] = (),
        opt_direction: Any = "min",
        pop_transforms: Sequence[Callable] = (),
        fit_transforms: Sequence[Callable] = (),
        mesh: Optional[jax.sharding.Mesh] = None,
        rules: Optional[Sequence[Tuple[str, Any]]] = None,
        num_objectives: int = 1,
        jit_step: bool = True,
        quarantine_nonfinite: bool = False,
        dtype_policy: Optional[DtypePolicy] = None,
        donate_carries: bool = False,
    ):
        if n_tenants < 1:
            raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
        if not problem.jittable:
            raise ValueError(
                "VectorizedWorkflow requires a jittable problem: a host "
                "pure_callback cannot run under vmap. Serve host problems "
                "one run at a time (run_host_pipelined), or wrap the "
                "evaluation jittable."
            )
        self.algorithm = algorithm
        self.problem = problem
        self.n_tenants = n_tenants
        self.monitors = tuple(monitors)
        self._opt_direction_arg = opt_direction
        self.opt_direction = parse_opt_direction(opt_direction)
        self.pop_transforms = tuple(pop_transforms)
        self.fit_transforms = tuple(fit_transforms)
        self.mesh = mesh
        self.rules = tuple(rules) if rules else None
        self.num_objectives = num_objectives
        self.quarantine_nonfinite = quarantine_nonfinite
        self.dtype_policy = dtype_policy
        self.jit_step = jit_step
        self.donate_carries = bool(donate_carries) and jit_step
        self.external = False  # fused_run/instrument duck-typing parity
        for m in self.monitors:
            if getattr(m, "uses_host_callbacks", False):
                raise ValueError(
                    f"{type(m).__name__} streams through host callbacks, "
                    "which cannot run inside the vmapped fleet step on ANY "
                    "backend; use the callback-free monitors for per-tenant "
                    "history (TelemetryMonitor rings, "
                    "EvalMonitor(history_capacity=K))"
                )
        self.hyperparams = self._check_hyperparams(hyperparams or {})
        if mesh is not None:
            if _TENANT not in mesh.axis_names:
                raise ValueError(
                    f"VectorizedWorkflow mesh must carry a '{_TENANT}' "
                    f"axis (got axes {tuple(mesh.axis_names)}); build it "
                    "with create_mesh((TENANT_AXIS, POP_AXIS), shape=(t, p))"
                )
            t_shards = mesh.shape[_TENANT]
            if n_tenants % t_shards != 0:
                raise ValueError(
                    f"n_tenants {n_tenants} is not divisible by the mesh's "
                    f"'{_TENANT}' axis ({t_shards} shards)"
                )
            pop_size = getattr(algorithm, "pop_size", None)
            p_shards = mesh.shape.get(_POP, 1)
            if pop_size is not None and pop_size % p_shards != 0:
                raise ValueError(
                    f"pop_size {pop_size} is not divisible by the mesh's "
                    f"'{_POP}' axis ({p_shards} shards)"
                )
        for m in self.monitors:
            m.set_opt_direction(self.opt_direction)
        self._hook_table = build_hook_table(self.monitors)
        self._step = jax.jit(self._step_impl) if jit_step else self._step_impl
        self._run_loop = make_run_loop(self._step_impl, donate=self.donate_carries)
        # single-tenant first-generation peel for RunQueue admission:
        # hyperparams are TRACED leaves of the TenantState operand, so
        # ONE compile serves every admitted spec regardless of its
        # bindings (a per-admission solo StdWorkflow would recompile)
        self._solo_peel = (
            jax.jit(self._solo_peel_impl) if jit_step else self._solo_peel_impl
        )

    # ------------------------------------------------------------ hyperparams
    def _check_hp_name(self, name: str) -> None:
        """Validate a (possibly dotted) hyperparam attribute path against
        the template — the one resolution rule shared by the constructor
        stack, ``init_tenant``, and RunQueue admission."""
        obj = self.algorithm
        for part in name.split("."):
            if not hasattr(obj, part):
                raise ValueError(
                    f"hyperparams[{name!r}]: template "
                    f"{type(obj).__name__} has no attribute {part!r}"
                )
            obj = getattr(obj, part)

    def _check_hyperparams(self, hp: Dict[str, Any]) -> Dict[str, Any]:
        checked = {}
        for name, value in hp.items():
            self._check_hp_name(name)
            value = jnp.asarray(value)
            if value.ndim < 1 or value.shape[0] != self.n_tenants:
                raise ValueError(
                    f"hyperparams[{name!r}] must be stacked with leading "
                    f"axis n_tenants={self.n_tenants}, got shape "
                    f"{value.shape}"
                )
            checked[name] = value
        return checked

    def _bind(self, hp: Dict[str, Any]) -> Algorithm:
        """A shallow copy of the template with this tenant's hyperparam
        slices bound as attributes (:func:`bind_hyperparams` — shared
        with the multi-level ES's traced inner binding,
        workflows/multilevel.py)."""
        return bind_hyperparams(self.algorithm, hp)

    def tenant_hyperparams(
        self, index: int, state: Optional[VectorizedWorkflowState] = None
    ) -> Dict[str, Any]:
        """Tenant ``index``'s concrete hyperparam bindings (host values).
        Reads the LIVE state's bindings when given (a RunQueue rebinds
        slots on admission), else the constructor stack."""
        source = (
            state.tenants.hyperparams if state is not None else self.hyperparams
        )
        return {
            name: jax.device_get(value)[index]
            for name, value in source.items()
        }

    # ------------------------------------------------------------------ init
    def init(
        self, key: jax.Array, hyperparams: Optional[Dict[str, Any]] = None
    ) -> VectorizedWorkflowState:
        """Build the fleet state. ``key``: one key (split per tenant) or
        a stacked ``(n_tenants, ...)`` key batch. Each tenant's slice is
        initialized EXACTLY like ``StdWorkflow.init`` with that tenant's
        key (same split discipline), so tenant ``i`` starts bit-identical
        to a solo run seeded with key ``i``. ``hyperparams=`` overrides
        the constructor stack (same names/shapes) — the RunQueue's
        admission path."""
        hp = (
            self.hyperparams
            if hyperparams is None
            else self._check_hyperparams(hyperparams)
        )
        keys = _tenant_keys(key, self.n_tenants)
        tenants = jax.vmap(self._build_tenant)(keys, hp)
        state = VectorizedWorkflowState(
            generation=jnp.zeros((), dtype=jnp.int32),
            tenants=tenants,
            first_step=True,
        )
        state = apply_storage(state, self.dtype_policy)
        # pod meshes: assemble the tenant-stacked state into global
        # arrays under the tenant-prefixed annotation layout (no-op on
        # single-process meshes; see core/distributed.ensure_global_state)
        from ..core.distributed import ensure_global_state

        return ensure_global_state(
            state, self.mesh, rules=self.rules, axis_prefix=_TENANT
        )

    def _build_tenant(self, k: jax.Array, h: Dict[str, Any]) -> TenantState:
        """The single-tenant constructor shared by the vmapped fleet
        ``init`` and ``init_tenant`` — ONE key-split discipline (matching
        ``StdWorkflow.init``), so the fleet-vs-solo and admission
        equivalence contracts cannot drift apart."""
        algo = self._bind(h)
        ks = jax.random.split(k, 2 + len(self.monitors))
        return TenantState(
            generation=jnp.zeros((), dtype=jnp.int32),
            algo=algo.init(ks[0]),
            prob=self.problem.init(ks[1]),
            monitors=tuple(
                m.init(kk) for m, kk in zip(self.monitors, ks[2:])
            ),
            hyperparams=h,
        )

    # ------------------------------------------------------------------ step
    def step(self, state: VectorizedWorkflowState) -> VectorizedWorkflowState:
        return self._step(state)

    def run(
        self,
        state: VectorizedWorkflowState,
        n_steps: int,
        checkpointer: Optional[WorkflowCheckpointer] = None,
        resume_from: Any = None,
    ) -> VectorizedWorkflowState:
        """Run ``n_steps`` generations of the WHOLE fleet as one fused
        ``fori_loop`` dispatch (see :meth:`StdWorkflow.run` — same
        checkpointer/resume laws, applied to the fleet state; the
        supervisor drives this entry point for chunked healing)."""
        state, n_steps, checkpointer = enter_run(
            state, n_steps, checkpointer, resume_from, expect_like=state
        )
        if checkpointer is not None:
            return checkpointed_run(self, state, n_steps, checkpointer)
        return fused_run(self, state, n_steps)

    def analysis_targets(self, state: VectorizedWorkflowState) -> dict:
        """AOT cost/memory analysis targets (core/xla_cost.py): the
        steady vmapped step and the fused fleet run (dynamic trip count
        ⇒ statics are per fleet-generation), so
        ``run_report()["roofline"]`` attributes the FUSED FLEET dispatch
        — N tenants' achieved rates in one verdict."""
        if not self.jit_step:
            return {}
        steady = state.replace(first_step=False) if state.first_step else state
        return {
            "step": (self._step, (steady,)),
            "run": (self._run_loop, (steady, jnp.asarray(1, jnp.int32))),
        }

    # ------------------------------------------------------------- internals
    def _filter_fitness(self, t: TenantState, fitness: jax.Array) -> jax.Array:
        """Per-tenant fitness filter applied after quarantine, before the
        fit transforms. Identity here; ``ElasticWorkflow`` overrides it
        with the inert-row padding mask."""
        return fitness

    def _flip(self, fitness: jax.Array) -> jax.Array:
        if fitness.ndim == 1:
            return fitness * self.opt_direction[0]
        return fitness * self.opt_direction

    def _shard_stacked(self, tree: Any, inner_pop: bool) -> Any:
        """Constrain tenant-stacked candidate/fitness batches:
        ``P(tenant, pop)`` for (N, B, ...) candidates, ``P(tenant)``
        when the inner axis doesn't shard (scalar fitness rows)."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        has_pop = _POP in self.mesh.axis_names

        def constrain(x):
            if x.ndim >= 2 and has_pop and inner_pop:
                spec = P(_TENANT, _POP)
            else:
                spec = P(_TENANT)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec)
            )

        return jax.tree.map(constrain, tree)

    def _tenant_ask(self, t: TenantState, use_init: bool):
        mstates = list(t.monitors)
        run_hooks(self.monitors, self._hook_table, "pre_step", mstates)
        run_hooks(self.monitors, self._hook_table, "pre_ask", mstates)
        algo = self._bind(t.hyperparams)
        ask = algo.init_ask if use_init else algo.ask
        pop, astate = ask(t.algo)
        run_hooks(self.monitors, self._hook_table, "post_ask", mstates, pop)
        cand = pop
        for tr in self.pop_transforms:
            cand = tr(cand)
        run_hooks(self.monitors, self._hook_table, "pre_eval", mstates, cand)
        return cand, (astate, tuple(mstates))

    def _tenant_tell(
        self,
        t: TenantState,
        ctx,
        cand: Any,
        fitness: jax.Array,
        pstate: Any,
        use_init: bool,
    ) -> TenantState:
        astate, mstates_t = ctx
        mstates = list(mstates_t)
        run_hooks(
            self.monitors, self._hook_table, "post_eval", mstates, cand, fitness
        )
        fitness = self._flip(fitness)
        if self.quarantine_nonfinite:
            fitness = quarantine_nonfinite(fitness)
        # per-tenant fitness filter hook (identity here): the elastic
        # layer (workflows/elastic.py) overrides it to make padded
        # population rows inert — between the quarantine stage and the
        # user fit transforms, the same pipeline position its solo
        # reference applies the mask at
        fitness = self._filter_fitness(t, fitness)
        for tr in self.fit_transforms:
            fitness = tr(fitness)
        run_hooks(self.monitors, self._hook_table, "pre_tell", mstates, fitness)
        algo = self._bind(t.hyperparams)
        tell = algo.init_tell if use_init else algo.tell
        astate = tell(astate, fitness)
        run_hooks(self.monitors, self._hook_table, "post_tell", mstates)
        # post_step sees the documented workflow-state shape — a solo
        # view with the tenant's OWN .generation (not the fleet's
        # lockstep counter, which runs ahead for queue-admitted tenants)
        # plus .algo/.prob/.monitors — so monitors written against
        # StdWorkflow's contract (generation-gated savers, the guardrail
        # mirror) trace identically per tenant
        generation = t.generation + 1
        hook_state = StdWorkflowState(
            generation=generation,
            algo=astate,
            prob=pstate,
            monitors=tuple(mstates),
            first_step=False,
        )
        ms = list(mstates)
        run_hooks(self.monitors, self._hook_table, "post_step", ms, hook_state)
        return TenantState(
            generation=generation,
            algo=astate,
            prob=pstate,
            monitors=tuple(ms),
            hyperparams=t.hyperparams,
        )

    def _step_impl(
        self, state: VectorizedWorkflowState
    ) -> VectorizedWorkflowState:
        # storage -> compute upcast at the fleet step boundary, exactly
        # like StdWorkflow: all per-tenant math runs in the compute dtype
        state = apply_compute(state, self.dtype_policy)
        use_init = state.first_step and (
            self.algorithm.has_init_ask or self.algorithm.has_init_tell
        )
        tenants = state.tenants
        cand, ctx = jax.vmap(partial(self._tenant_ask, use_init=use_init))(
            tenants
        )
        # the whole fleet's candidates are ONE (N, B, ...) batch laid out
        # over (TENANT, POP) — GSPMD partitions the vmapped evaluation
        # across both axes from this single constraint
        cand = self._shard_stacked(cand, inner_pop=True)
        fitness, pstate = jax.vmap(self.problem.evaluate)(tenants.prob, cand)
        fitness = self._shard_stacked(fitness, inner_pop=True)
        told = jax.vmap(partial(self._tenant_tell, use_init=use_init))(
            tenants, ctx, cand, fitness, pstate
        )
        if state.frozen is not None:
            # fault isolation (fleet_health.py "freeze"): a frozen slot
            # keeps its PRE-step tenant slice — elementwise select, so
            # unfrozen rows pass through the computed values bitwise
            # unchanged (the isolation law's mechanism)
            frozen = state.frozen

            def keep_frozen(old, new):
                mask = frozen.reshape(frozen.shape + (1,) * (new.ndim - 1))
                return jnp.where(mask, old.astype(new.dtype), new)

            told = jax.tree.map(keep_frozen, tenants, told)
        tenants = told
        # end-of-step boundary, fleet-wide: the per-field annotations are
        # applied SHIFTED under the tenant axis (P("pop") -> P("tenant",
        # "pop"), P() -> P("tenant")) with regex rules overriding, and an
        # active dtype policy downcasts storage leaves in the same walk
        tenants = constrain_state(
            tenants,
            self.mesh,
            self.dtype_policy,
            rules=self.rules,
            axis_prefix=_TENANT,
        )
        return state.replace(
            generation=state.generation + 1,
            tenants=tenants,
            first_step=False,
        )

    def init_tenant(
        self, key: jax.Array, hyperparams: Optional[Dict[str, Any]] = None
    ) -> TenantState:
        """A fresh SINGLE tenant (unstacked :class:`TenantState`) with
        concrete ``hyperparams`` bound — the RunQueue admission path.
        Key-split discipline matches :meth:`init`'s per-tenant splits
        (and therefore ``StdWorkflow.init``), so an admitted tenant is
        trajectory-equivalent to a solo run of its (seed, bindings)."""
        hp = {}
        for name, value in (hyperparams or {}).items():
            self._check_hp_name(name)
            hp[name] = jnp.asarray(value)
        return self._build_tenant(jnp.asarray(key), hp)

    def _solo_peel_impl(self, t: TenantState) -> TenantState:
        """One un-vmapped first generation of a single tenant (the
        init_ask/init_tell dispatch the fleet's steady vmapped step must
        never issue for one slot only). Hook order mirrors the vmapped
        step exactly."""
        cand, ctx = self._tenant_ask(t, use_init=True)
        fitness, pstate = self.problem.evaluate(t.prob, cand)
        return self._tenant_tell(t, ctx, cand, fitness, pstate, use_init=True)

    def place_restored(self, state: VectorizedWorkflowState) -> Any:
        """Eagerly re-place a host-restored FLEET snapshot on this
        workflow's mesh using the tenant-prefixed layout (the fleet
        analog of :func:`~evox_tpu.workflows.checkpoint.restore_layouts`
        — the un-prefixed annotations would shard a stacked leaf's
        TENANT axis over the ``pop`` mesh axis). The supervisor's
        restore rung picks this up duck-typed."""
        from ..core.distributed import place_state

        if self.mesh is None:
            return state
        return place_state(
            state, self.mesh, rules=self.rules, axis_prefix=_TENANT
        )

    # ------------------------------------------------- eviction / admission
    def solo_workflow(
        self,
        index: Optional[int] = None,
        hyperparams: Optional[Dict[str, Any]] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        state: Optional[VectorizedWorkflowState] = None,
    ) -> StdWorkflow:
        """A single-tenant :class:`StdWorkflow` equivalent to fleet slot
        ``index`` (or to explicit concrete ``hyperparams``): the template
        algorithm with that tenant's bindings baked in, the same problem,
        monitors, transforms and dtype policy. This is the resume target
        for an evicted tenant's checkpoint — and the reference
        implementation the fleet's per-tenant trajectory is tested
        against. Pass ``state=`` with ``index`` to read the LIVE slot
        bindings (a RunQueue rebinds slots on admission, so the
        constructor stack can be stale for queue-driven fleets)."""
        if hyperparams is None:
            hyperparams = (
                self.tenant_hyperparams(index, state=state)
                if index is not None
                else {}
            )
        algo = self._bind(
            {k: jnp.asarray(v) for k, v in hyperparams.items()}
        )
        return StdWorkflow(
            algo,
            self.problem,
            monitors=self.monitors,
            opt_direction=self._opt_direction_arg,
            pop_transforms=self.pop_transforms,
            fit_transforms=self.fit_transforms,
            mesh=mesh,
            num_objectives=self.num_objectives,
            jit_step=self.jit_step,
            quarantine_nonfinite=self.quarantine_nonfinite,
            dtype_policy=self.dtype_policy,
            donate_carries=self.donate_carries,
        )

    def extract_tenant(
        self,
        state: VectorizedWorkflowState,
        index: int,
        generation: Optional[int] = None,
    ) -> StdWorkflowState:
        """Slice tenant ``index`` out of the fleet as a SOLO
        ``StdWorkflowState`` (host-side ``device_get`` + slice, eager —
        call between dispatches). The result is exactly what
        ``solo_workflow(index)`` would be carrying at this generation:
        checkpoint it with a :class:`WorkflowCheckpointer` and the solo
        workflow's ``resume_from=`` completes the run — the mid-fleet
        eviction contract. ``generation`` overrides the tenant's own
        counter (rarely needed — the state tracks it per tenant)."""
        # slice ON DEVICE first: fetching the whole stacked fleet to
        # discard N-1 tenants would cost N× the bytes per eviction
        t = jax.device_get(
            jax.tree.map(lambda x: x[index], state.tenants)
        )
        gen = int(t.generation) if generation is None else int(generation)
        return StdWorkflowState(
            generation=jnp.asarray(gen, dtype=jnp.int32),
            algo=t.algo,
            prob=t.prob,
            monitors=t.monitors,
            first_step=False,
        )

    def insert_tenant(
        self,
        state: VectorizedWorkflowState,
        index: int,
        solo_state: Any,
        hyperparams: Optional[Dict[str, Any]] = None,
    ) -> VectorizedWorkflowState:
        """Write a solo tenant state into fleet slot ``index`` (state
        surgery at fixed shapes — NO recompile: the fleet program only
        sees different leaf values). ``solo_state``: a
        ``StdWorkflowState`` (from ``solo_workflow(...).init`` or an
        eviction checkpoint) or an unstacked :class:`TenantState` (from
        :meth:`init_tenant`); it must match the fleet's per-tenant
        structure (same algorithm class, pop size, monitor set).
        ``hyperparams``: the slot's new concrete bindings (default: a
        TenantState's own, else the slot's current ones). A solo state's
        ``generation`` is the tenant's — the caller (RunQueue) tracks
        the offset against the fleet's lockstep counter."""
        if hyperparams is not None:
            slot_hp = {
                name: jnp.asarray(value)
                for name, value in hyperparams.items()
            }
        elif isinstance(solo_state, TenantState):
            slot_hp = solo_state.hyperparams
        else:
            slot_hp = jax.tree.map(
                lambda x: x[index], state.tenants.hyperparams
            )
        new_t = TenantState(
            generation=jnp.asarray(solo_state.generation, dtype=jnp.int32),
            algo=solo_state.algo,
            prob=solo_state.prob,
            monitors=solo_state.monitors,
            hyperparams=slot_hp,
        )
        new_t = apply_storage(new_t, self.dtype_policy)
        # shape guard BEFORE the scatter: a solo state carrying another
        # population size would either raise an opaque broadcasting error
        # deep inside `.at[index].set` or — worse, for a pop that happens
        # to broadcast — silently corrupt the slot. Mismatched shapes are
        # a routing bug (e.g. a checkpoint from a different bucket); name
        # it and point at the elastic router.
        slot_leaves = jax.tree_util.tree_flatten_with_path(state.tenants)[0]
        new_leaves = jax.tree_util.tree_flatten_with_path(new_t)[0]
        if len(slot_leaves) == len(new_leaves):
            for (path, stacked), (_, new) in zip(slot_leaves, new_leaves):
                want = tuple(jnp.asarray(stacked).shape[1:])
                got = tuple(jnp.asarray(new).shape)
                if want != got:
                    raise ValueError(
                        f"insert_tenant: solo state leaf "
                        f"{jax.tree_util.keystr(path)} has shape {got} but "
                        f"fleet slot {index} holds {want} — the tenant was "
                        "built for a different shape (population size, dim, "
                        "or monitor capacity). Shapes are compiled into the "
                        "fleet program; route mismatched requests through "
                        "the bucket lattice (workflows/elastic.py "
                        "ElasticServer) instead."
                    )

        def put(stacked, new):
            stacked = jnp.asarray(stacked)
            return stacked.at[index].set(
                jnp.asarray(new, dtype=stacked.dtype)
            )

        return state.replace(
            tenants=jax.tree.map(put, state.tenants, new_t)
        )

    # --------------------------------------------------------------- freezing
    def with_freeze_mask(
        self, state: VectorizedWorkflowState
    ) -> VectorizedWorkflowState:
        """Materialize the per-tenant frozen mask (all False). Changes
        the carry structure, so do it BEFORE the first dispatch — the
        RunQueue does when its health policy can freeze."""
        if state.frozen is not None:
            return state
        return state.replace(
            frozen=jnp.zeros((self.n_tenants,), dtype=bool)
        )

    def set_frozen(
        self, state: VectorizedWorkflowState, index: int, flag: bool
    ) -> VectorizedWorkflowState:
        """Flip one slot's frozen bit (mask must be materialized)."""
        if state.frozen is None:
            raise ValueError(
                "fleet state has no frozen mask; materialize it with "
                "with_freeze_mask(state) before the first dispatch"
            )
        return state.replace(frozen=state.frozen.at[index].set(flag))

    # -------------------------------------------------------------- reporting
    def monitor_reports(self, mstates: Tuple[Any, ...]) -> List[dict]:
        """One monitor's ``report()`` per reporting monitor for a single
        tenant's monitor states — the shared assembly behind the tenancy
        section and the RunQueue's per-tenant results."""
        reports = []
        for j, mon in enumerate(self.monitors):
            if hasattr(mon, "report"):
                r = mon.report(mstates[j])
                r["monitor"] = type(mon).__name__
                reports.append(r)
        return reports

    def tenancy_report(self, state: VectorizedWorkflowState) -> dict:
        """The ``tenancy`` section of ``run_report()``: fleet shape,
        measured leading axes (the validator cross-checks them against
        ``n_tenants``), and each tenant's monitor reports (per-tenant
        telemetry rings). Host-side, strict JSON."""
        from ..core.instrument import sanitize_json

        # leading axes need SHAPES only (zero transfer); only the
        # monitor states — the small rings — are fetched, never the
        # stacked populations/covariances (fetched bytes are the cost)
        leading = {
            int(x.shape[0])
            for x in jax.tree.leaves(state.tenants.algo)
            if getattr(x, "ndim", 0) >= 1
        }
        host_monitors = jax.device_get(state.tenants.monitors)
        per_tenant = []
        for i in range(self.n_tenants):
            entry: dict = {"tenant": i}
            reports = self.monitor_reports(
                tuple(
                    jax.tree.map(lambda x: x[i], ms) for ms in host_monitors
                )
            )
            if reports:
                entry["monitors"] = reports
            per_tenant.append(entry)
        report = {
            "n_tenants": self.n_tenants,
            "generation": int(state.generation),
            "tenant_axis": _TENANT if self.mesh is not None else None,
            "leading_axes": sorted(leading),
            "per_tenant": per_tenant,
        }
        queue = getattr(self, "_run_queue", None)
        if queue is not None and hasattr(queue, "report"):
            report["queue"] = queue.report()
        # fault-isolation actions (fleet_health.py) are a first-class
        # section of the tenancy report: run_report()["tenancy"]
        # ["fleet_health"] is where a poisoned tenant's freeze/evict/
        # restart verdict is surfaced (validated by check_report v6)
        if queue is not None and hasattr(queue, "health_report"):
            health = queue.health_report()
            if health is not None:
                report["fleet_health"] = health
        return sanitize_json(report)


# --------------------------------------------------------------------- queue


@dataclasses.dataclass
class TenantSpec:
    """One queued search: seed (int or PRNG key), concrete hyperparam
    bindings (must use the fleet's hyperparam names), a generation
    budget, and an optional tag for the results table.

    ``pop`` (optional) declares the population size the spec was built
    for: admission validates it against the fleet's compiled pop at
    ``submit()`` — a mismatch is a routing error named there, not a
    shape error deep inside the fused vmapped step (route ragged pops
    through ``workflows/elastic.py`` instead).

    ``deadline`` (optional) is the SLA bound, measured in FLEET
    generations since the queue started (``state.generation`` — a
    deterministic clock, so journal recovery replays every scheduling
    decision identically; wall-clock deadlines would not). A deadlined
    spec is admitted in EDF order ahead of deadline-free work, and the
    queue may PREEMPT the running tenant with the most remaining budget
    (parked via the standard eviction checkpoint, auto-resubmitted as a
    continuation) when waiting one more chunk would miss the deadline."""

    seed: Any
    n_steps: int
    hyperparams: Dict[str, Any] = dataclasses.field(default_factory=dict)
    tag: Optional[str] = None
    pop: Optional[int] = None
    deadline: Optional[int] = None

    def key(self) -> jax.Array:
        import numpy as np

        if isinstance(self.seed, (int, np.integer)):
            return jax.random.PRNGKey(int(self.seed))
        return jnp.asarray(self.seed)


@dataclasses.dataclass
class _Slot:
    spec: TenantSpec
    active: bool = True
    # frozen: the slot's tenant was quarantined in place (fleet_health
    # "freeze" action) — it stays in the fleet at fixed shape but its
    # tell is masked and the slot is never refilled
    frozen: bool = False


def _spec_from_record(rec: dict) -> TenantSpec:
    """Rebuild a :class:`TenantSpec` from its journal ``submit`` record
    (the recovery path). Seeds round-trip as ints or key data; a TYPED
    key seed is re-wrapped with its recorded impl — recovery must hand
    ``init_tenant`` the same key dtype the original driver did, or the
    config fingerprint (and the fleet's key leaves) would diverge."""
    import numpy as np

    if rec.get("seed") is not None:
        seed: Any = int(rec["seed"])
    else:
        seed = np.asarray(
            rec["seed_key"], dtype=rec.get("seed_key_dtype", "uint32")
        )
        impl = rec.get("seed_key_impl")
        if impl is not None:
            seed = jax.random.wrap_key_data(jnp.asarray(seed), impl=impl)
    spec = TenantSpec(
        seed=seed,
        n_steps=int(rec["n_steps"]),
        hyperparams=dict(rec.get("hyperparams") or {}),
        tag=rec.get("tag"),
        pop=int(rec["pop"]) if rec.get("pop") is not None else None,
        deadline=(
            int(rec["deadline"]) if rec.get("deadline") is not None else None
        ),
    )
    spec._journal_seq = int(rec["spec_seq"])
    if rec.get("grows"):
        # restore the elastic grow count (bounds PopAutoscaler.max_grows
        # across recovery — a scheduling input like pop/deadline)
        spec._elastic_grows = int(rec["grows"])
    return spec


class RunQueue:
    """Admit/evict tenants through a fixed-width vmapped fleet.

    The fleet's width is static (a compiled-program shape); the queue
    serves MORE searches than that by running the fleet in dispatch
    chunks and swapping retired tenants for pending specs between
    chunks — state surgery at fixed shapes, no recompile. With a
    :class:`~evox_tpu.workflows.supervisor.RunSupervisor`, every chunk
    dispatch runs under its deadline/retry/restore ladder (the fleet is
    one workflow to the supervisor).

    Args:
        workflow: a :class:`VectorizedWorkflow`. Its constructor
            hyperparam stack is only a default — each admitted spec's
            bindings overwrite its slot. A workflow already driven by an
            UNFINISHED RunQueue is refused (the backref would silently
            rewire ``run_report``'s ``tenancy.queue`` pickup mid-sweep);
            once a queue's sweep completes, a new queue may adopt the
            workflow.
        chunk: generations per dispatch chunk (the admission/eviction
            granularity). A tenant's budget is honored exactly: the
            chunk is shortened when any active tenant would overshoot.
        supervisor: optional :class:`RunSupervisor` driving each chunk.
        checkpoint_dir: when given, every retirement/eviction/freeze
            writes a resumable single-tenant snapshot under
            ``<dir>/<tag-or-tenant_K>/`` (a
            :class:`WorkflowCheckpointer`; ``solo_workflow(...)``
            resumes it). Defaults to ``<journal_dir>/tenants`` when a
            journal is configured.
        keep: snapshots kept per tenant directory.
        journal: a :class:`~evox_tpu.workflows.journal.RunJournal` (or a
            directory path) making the whole sweep DURABLE: every queue
            transition is appended to the hash-chained WAL before (or
            at the barrier of) the mutation it describes, and every
            chunk ends with a fleet-level snapshot written through the
            executor's background checkpoint lane plus a
            ``chunk_complete`` barrier record embedding the queue's full
            bookkeeping. A driver SIGKILL'd at ANY point is resumed by
            :meth:`recover` with per-tenant results identical to the
            uncrashed run.
        health_policy: a :class:`~evox_tpu.workflows.fleet_health.
            FleetHealthPolicy` evaluated at every chunk boundary; maps
            per-tenant health signals to freeze/evict/restart slot
            actions (healthy tenants stay bitwise-untouched).

    Lifecycle: ``submit()`` specs (at least ``n_tenants`` before the
    first ``start()``), then ``run()`` to completion — or ``start()`` +
    repeated ``step_chunk()`` for between-chunk control (the legal
    window for :meth:`evict`). Results accumulate in ``results``;
    :meth:`report` is the ``tenancy.queue`` section of ``run_report``.

    Durability note: a MANUAL :meth:`evict` between chunks is journaled
    for audit, but recovery replays from the last chunk barrier — a
    crash in the narrow window between a manual eviction and the next
    barrier rolls the slot swap back (the eviction checkpoint on disk
    stays valid; the tenant simply continues in the fleet). Policy-driven
    actions are deterministic in the restored state and replay exactly.
    """

    def __init__(
        self,
        workflow: VectorizedWorkflow,
        chunk: int = 10,
        supervisor: Any = None,
        checkpoint_dir: Optional[str] = None,
        keep: int = 2,
        executor: Any = None,
        journal: Any = None,
        health_policy: Any = None,
        metrics: Any = None,
        attest: Any = None,
    ):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        from ..core.executor import GenerationExecutor
        from .journal import RunJournal

        prev = getattr(workflow, "_run_queue", None)
        if prev is not None and prev is not self and not getattr(
            prev, "finished", True
        ):
            raise RuntimeError(
                "this VectorizedWorkflow is already driven by an "
                "unfinished RunQueue — constructing a second one would "
                "silently rewire run_report's tenancy.queue pickup and "
                "interleave two sweeps over one fleet state. Drive the "
                "existing queue to completion (or build a second "
                "workflow) first."
            )
        self.workflow = workflow
        self.chunk = chunk
        self.supervisor = supervisor
        # every serving chunk dispatches through ONE GenerationExecutor
        # (queue scheduling is a thin policy over it): the supervisor
        # ladder becomes an executor hook, and with a journal the fleet
        # snapshot rides the executor's background checkpoint lane.
        # Eviction/retirement snapshots stay SYNCHRONOUS on the caller
        # thread — they happen between chunks and their result is handed
        # out immediately
        self.executor = (
            executor if executor is not None else GenerationExecutor()
        )
        if isinstance(journal, (str, Path)):
            journal = RunJournal(str(journal))
        self.journal = journal
        if checkpoint_dir is None and journal is not None:
            checkpoint_dir = str(journal.directory / "tenants")
        self.checkpoint_dir = (
            Path(checkpoint_dir) if checkpoint_dir is not None else None
        )
        self.keep = keep
        self._fleet_ckpt = (
            WorkflowCheckpointer(
                str(journal.directory / "fleet"), every=1,
                # recovery falls back one barrier when the newest
                # snapshot is torn (a kill mid-background-fsync), so at
                # least two snapshots must survive pruning
                keep=max(2, keep),
            )
            if journal is not None
            else None
        )
        self.health_policy = health_policy
        # serving-plane flight recorder (PR 16): `metrics=None` is an
        # exact no-op — every producer call site below is gated, reads
        # only already-fetched host values, and writes only host memory/
        # files, so states stay bit-identical and no stream file exists.
        # A str/Path builds a stream-backed recorder in that directory.
        if isinstance(metrics, (str, Path)):
            from .flightrec import FlightRecorder

            metrics = FlightRecorder(directory=str(metrics))
        self.metrics = metrics
        if metrics is not None:
            # one recorder serves the whole serving stack: the executor
            # mirrors its dispatch telemetry, the exec cache its
            # hit/miss/compile-ms, the health policy its verdicts; the
            # workflow backref is run_report's `metrics`/`slo` pickup
            workflow._flight_recorder = metrics
            if getattr(self.executor, "metrics", None) is None:
                self.executor.metrics = metrics
            cache = getattr(workflow, "_exec_cache", None)
            if cache is not None and getattr(cache, "metrics", None) is None:
                cache.metrics = metrics
            if (
                health_policy is not None
                and getattr(health_policy, "metrics", None) is None
            ):
                health_policy.metrics = metrics
        # compute-integrity (PR 20): an attestor pins a digest of the
        # fleet state onto every chunk_complete barrier record, so
        # recover() can verify a restored snapshot's BITS against the
        # journal — a corrupt-but-sha256-consistent snapshot is refused
        # and recovery falls back one barrier. `attest=None` is an exact
        # no-op; `attest=True` builds the default StateAttestor.
        if attest is True:
            from ..core.attest import StateAttestor

            attest = StateAttestor()
        self.attest = attest
        self.integrity_events: List[dict] = []
        self.health_events: List[dict] = []
        self._slot_restarts: List[int] = [0] * workflow.n_tenants
        self._config_sha: Optional[str] = None
        self._spec_seq = 0
        self.finished = False
        self.pending: List[TenantSpec] = []
        # parked continuations: specs whose tenant resumes from a
        # checkpoint (preemption, elastic growth) instead of a fresh
        # init — admitted ahead of deadline-free pending work
        self.continuations: List[dict] = []
        self._used_dirs: set = set()
        self.slots: List[Optional[_Slot]] = [None] * workflow.n_tenants
        self.state: Optional[VectorizedWorkflowState] = None
        self.results: List[dict] = []
        self.counters = {
            "submitted": 0,
            "admitted": 0,
            "retired": 0,
            "evicted": 0,
            "frozen": 0,
            "restarted": 0,
            "preempted": 0,
            "readmitted": 0,
            "chunks": 0,
        }
        workflow._run_queue = self  # run_report pickup (tenancy.queue)

    # ------------------------------------------------------------- lifecycle
    def _spec_record(self, spec: TenantSpec, seq: int) -> dict:
        import numpy as np

        rec: dict = {
            "spec_seq": seq,
            "n_steps": int(spec.n_steps),
            "tag": spec.tag,
            "pop": int(spec.pop) if spec.pop is not None else None,
            "deadline": (
                int(spec.deadline) if spec.deadline is not None else None
            ),
            "hyperparams": {
                k: np.asarray(v) for k, v in spec.hyperparams.items()
            },
        }
        # the elastic layer's grow count is a SCHEDULING input (it
        # bounds PopAutoscaler.max_grows): journal it like pop/deadline
        # or a recovered queue would let a grown tenant grow forever
        grows = getattr(spec, "_elastic_grows", 0)
        if grows:
            rec["grows"] = int(grows)
        seed = spec.seed
        if isinstance(seed, (int, np.integer)):
            rec["seed"] = int(seed)
        else:
            arr = jnp.asarray(seed)
            if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
                rec["seed_key_impl"] = str(jax.random.key_impl(arr))
                arr = jax.random.key_data(arr)
            arr = np.asarray(arr)
            rec["seed_key"] = arr
            rec["seed_key_dtype"] = str(arr.dtype)
        return rec

    def _validate_spec(self, spec: TenantSpec) -> None:
        if spec.n_steps < 1:
            raise ValueError(
                f"TenantSpec.n_steps must be >= 1, got {spec.n_steps}"
            )
        fleet_pop = getattr(self.workflow.algorithm, "pop_size", None)
        if (
            spec.pop is not None
            and fleet_pop is not None
            and int(spec.pop) != int(fleet_pop)
        ):
            # the pre-elastic failure mode was a shape error deep inside
            # the fused vmapped step, generations after the bad spec was
            # accepted — reject it AT the submission boundary instead
            raise ValueError(
                f"TenantSpec.pop={spec.pop} does not match this fleet's "
                f"compiled pop_size={fleet_pop}. A fleet program is "
                "compiled at ONE population shape; admitting a mismatched "
                "spec would fail (or silently mis-broadcast) inside the "
                "fused step. Route ragged pops through the bucket lattice "
                "(workflows/elastic.py ElasticServer) or build a fleet at "
                "the requested pop."
            )
        if spec.deadline is not None:
            if spec.deadline < spec.n_steps:
                raise ValueError(
                    f"TenantSpec.deadline={spec.deadline} is infeasible: "
                    f"the spec needs n_steps={spec.n_steps} fleet "
                    "generations even if admitted at generation 0"
                )
            if self.checkpoint_dir is None:
                raise ValueError(
                    "deadlined specs need a checkpoint_dir (or a journal): "
                    "meeting a deadline may preempt a running tenant, and "
                    "preemption parks the victim as a resumable eviction "
                    "checkpoint — without a directory its work would be "
                    "lost"
                )
        if set(spec.hyperparams) != set(self.workflow.hyperparams):
            raise ValueError(
                f"spec hyperparams {sorted(spec.hyperparams)} must use "
                f"exactly the fleet's hyperparam names "
                f"{sorted(self.workflow.hyperparams)}"
            )
        for name in spec.hyperparams:
            self.workflow._check_hp_name(name)

    def _journal_submit(self, spec: TenantSpec, **extra: Any) -> None:
        seq = self._spec_seq
        if self.journal is not None:
            self.journal.append(
                "submit", **self._spec_record(spec, seq), **extra
            )
        spec._journal_seq = seq
        self._spec_seq += 1
        self.counters["submitted"] += 1
        self.finished = False

    def submit(self, spec: TenantSpec) -> None:
        """Queue a spec. Validated HERE — a bad spec must be rejected at
        the submission boundary, not discovered mid-sweep after it was
        popped (which would lose it and leave the queue half-updated).
        With a journal, the spec is durable before it is queued (WAL
        discipline: an acknowledged submit survives a crash)."""
        self._validate_spec(spec)
        self._journal_submit(spec)
        self.pending.append(spec)

    def submit_resume(
        self,
        spec: TenantSpec,
        checkpoint: Optional[str] = None,
        state: Any = None,
        done: Optional[int] = None,
    ) -> None:
        """Queue a CONTINUATION: a spec whose tenant resumes from a
        parked solo state (a preemption/eviction/growth checkpoint, or
        an in-memory state) instead of a fresh init. Continuations are
        admitted ahead of deadline-free pending work — they were
        displaced to make room, so they return before new arrivals.
        ``done`` records the generations already completed at park time
        (the SLA pass uses it to compute the continuation's REAL
        remaining work instead of assuming the whole ``n_steps``).
        With a journal a durable ``checkpoint`` is required: an
        in-memory state would not survive the crash the journal exists
        for. The journal records the submit with its ``resume_from``
        path, so recovery rebuilds the continuation queue."""
        self._validate_spec(spec)
        if checkpoint is None and state is None:
            raise ValueError(
                "submit_resume needs a checkpoint directory or an "
                "in-memory solo state to resume from"
            )
        if self.journal is not None and checkpoint is None:
            raise ValueError(
                "a journaled queue requires continuations to name a "
                "durable checkpoint (resume_from) — an in-memory state "
                "cannot be replayed after a crash"
            )
        self._journal_submit(
            spec,
            resume_from=checkpoint,
            done=int(done) if done is not None else None,
        )
        self.continuations.append(
            {
                "spec": spec,
                "seq": getattr(spec, "_journal_seq", None),
                "checkpoint": checkpoint,
                "state": state,
                "done": int(done) if done is not None else None,
            }
        )

    def release_continuation(self, seq: int) -> dict:
        """Release QUEUED work — a parked continuation, or a still-
        pending spec — because it was stolen: the multi-pod control
        plane (:mod:`~evox_tpu.workflows.control_plane`) re-placed it on
        another pod, where its submit is already durable. Same WAL
        ordering as the elastic-growth handoff: the caller makes the
        work durable in the TARGET journal first, then releases it
        here — a crash between the two leaves a duplicate (healed by
        the control plane's checkpoint/tag dedup at recovery), never a
        loss. The journal records a ``steal`` so recovery of THIS queue
        never requeues the moved seq. Returns a descriptor of the
        released work ({seq, tag, checkpoint, done}). Raises
        ``KeyError`` when no queued work carries ``seq`` — an ACTIVE
        slot cannot be stolen directly (preempt it first; the
        preemption parks a continuation)."""
        seq = int(seq)
        for i, c in enumerate(self.continuations):
            if c.get("seq") is not None and int(c["seq"]) == seq:
                if self.journal is not None and c.get("checkpoint") is None:
                    raise ValueError(
                        "a journaled queue cannot release an in-memory "
                        "continuation — nothing durable exists for the "
                        "target pod to resume from"
                    )
                self.continuations.pop(i)
                desc = {
                    "seq": seq,
                    "tag": c["spec"].tag,
                    "checkpoint": c.get("checkpoint"),
                    "done": c.get("done"),
                }
                break
        else:
            for i, spec in enumerate(self.pending):
                if getattr(spec, "_journal_seq", None) == seq:
                    self.pending.pop(i)
                    desc = {
                        "seq": seq,
                        "tag": spec.tag,
                        "checkpoint": None,
                        "done": None,
                    }
                    break
            else:
                raise KeyError(
                    f"no queued work (continuation or pending spec) "
                    f"carries journal seq {seq}"
                )
        self.counters["stolen"] = self.counters.get("stolen", 0) + 1
        if self.journal is not None:
            self.journal.append(
                "steal",
                spec_seq=seq,
                tag=desc["tag"],
                checkpoint=desc["checkpoint"],
            )
        if self.metrics is not None:
            self.metrics.event(
                "queue.stolen", tag=desc["tag"], seq=seq
            )
        return desc

    def start(self) -> VectorizedWorkflowState:
        """Fill every slot and init the fleet. Slots draw from pending
        specs AND parked continuations under the ``_refill`` priority
        ladder — a recovered queue whose remaining work is (mostly)
        continuations (a cross-journal elastic-growth handoff crashed
        before its target bucket ever started) must be startable, not
        stuck behind a pending-only guard."""
        wf = self.workflow
        if self.state is not None:
            raise RuntimeError("RunQueue already started")
        total = len(self.pending) + len(self.continuations)
        if total < wf.n_tenants:
            raise ValueError(
                f"need at least n_tenants={wf.n_tenants} pending specs or "
                f"parked continuations to fill the fleet, have {total}; "
                "submit more or build a narrower fleet"
            )
        units = [self._take_next_unit() for _ in range(wf.n_tenants)]
        specs = [u if k == "spec" else u["spec"] for k, u in units]
        keys = jnp.stack([s.key() for s in specs])
        hp = self._stack_hp([s.hyperparams for s in specs])
        state = wf.init(keys, hyperparams=hp)
        if self.health_policy is not None and self.health_policy.may_freeze():
            # the mask must exist from the FIRST dispatch: adding it
            # mid-run changes the carry structure (a designed retrace
            # this avoids)
            state = wf.with_freeze_mask(state)
        from .checkpoint import state_config_fingerprint

        self._config_sha = state_config_fingerprint(state)
        if self.journal is not None:
            # journaled BEFORE the queue adopts the fleet: a crash here
            # leaves a start record without barriers, which recovery
            # treats as never-started (every submitted spec re-queued)
            self.journal.append(
                "start",
                config_sha=self._config_sha,
                n_tenants=wf.n_tenants,
                chunk=self.chunk,
                keep=self.keep,
                freeze_mask=state.frozen is not None,
                # the policy CONFIG is part of the sweep: recover() must
                # keep isolating poisoned tenants through the replay, or
                # a crashed run's verdicts would diverge from the
                # uncrashed run's (crash-equivalence law)
                health_policy=(
                    self.health_policy.report()
                    if self.health_policy is not None
                    and hasattr(self.health_policy, "report")
                    else None
                ),
                checkpoint_dir=(
                    str(self.checkpoint_dir)
                    if self.checkpoint_dir is not None
                    else None
                ),
                slots=[getattr(s, "_journal_seq", None) for s in specs],
            )
        self.state = state
        self.slots = [_Slot(spec=s) for s in specs]
        fresh = [i for i, (k, _) in enumerate(units) if k == "spec"]
        self.counters["admitted"] += len(fresh)
        if self.metrics is not None and fresh:
            # start()'s batch seating bypasses _install for fresh specs
            # (one vmapped init instead of N surgeries) — mirror it, or
            # the SLO ledger under-counts exactly the first fleet-full
            # of admissions and the coherence validator flags every run
            self.metrics.count("slo.admissions", len(fresh))
        if self.journal is not None:
            for i in fresh:
                self.journal.append(
                    "admit",
                    slot=i,
                    spec_seq=getattr(specs[i], "_journal_seq", None),
                    fleet_generation=0,
                )
        # continuation slots: the fresh-init state above is a shape
        # donor only — replace it with the parked tenant by the standard
        # surgery (which journals its own resumed admit and counts it)
        for i, (k, u) in enumerate(units):
            if k == "cont":
                self._install(
                    i, u["spec"], self._continuation_state(u), resumed=True
                )
        return self.state

    def _stack_hp(self, hp_dicts: List[Dict[str, Any]]) -> Dict[str, Any]:
        names = set(self.workflow.hyperparams)
        for d in hp_dicts:
            if set(d) != names:
                raise ValueError(
                    f"spec hyperparams {sorted(d)} must use exactly the "
                    f"fleet's hyperparam names {sorted(names)}"
                )
        return {
            name: jnp.stack([jnp.asarray(d[name]) for d in hp_dicts])
            for name in names
        }

    def _dispatch(self, n: int) -> None:
        wf = self.workflow
        running = sum(1 for s in self.slots if s is not None and s.active)
        self.state = self.executor.run_fused(
            wf, self.state, n, supervisor=self.supervisor
        )
        self.counters["chunks"] += 1
        if self.metrics is not None:
            # tenant-generations actually SERVED this chunk: n fused
            # generations × tenants doing real work (parked/frozen rows
            # step in lockstep but serve nobody) — the SLO ledger's
            # numerator, accumulated at the dispatch boundary
            self.metrics.count("slo.tenant_gens", n * running)
            self.metrics.count("queue.chunks")

    def _tenant_generations(self):
        """Per-slot OWN generation counters, read from the state (one
        tiny (N,) int32 fetch — the authoritative ledger the budgets are
        checked against)."""
        import numpy as np

        return np.asarray(jax.device_get(self.state.tenants.generation))

    def _sweep(self):
        """Retire every active tenant at/over budget, refill idle slots
        from the pending queue (covers specs submitted after a previous
        ``run()`` drained the fleet). Loops until stable: a freshly
        admitted tenant whose solo peel already met a 1-generation
        budget retires in the next pass instead of forcing a
        zero-length dispatch. Returns the final per-slot generation
        ledger so the caller doesn't refetch it."""
        changed = True
        gens = self._tenant_generations()
        while changed:
            changed = False
            for i, slot in enumerate(self.slots):
                if (
                    slot is not None
                    and slot.active
                    and gens[i] >= slot.spec.n_steps
                ):
                    self._retire(i, status="completed")
                    changed = True
            for i, slot in enumerate(self.slots):
                if (
                    (slot is None or not slot.active)
                    and not (slot is not None and slot.frozen)
                    and (self.pending or self.continuations)
                ):
                    self._refill(i)
                    changed = True
            if changed:
                # surgery/retirement changed the ledger; refresh once
                # per pass (the fetch is a tiny (N,) int32, but every
                # device-to-host round-trip stalls the dispatch queue)
                gens = self._tenant_generations()
        return gens

    def step_chunk(self) -> bool:
        """Run one dispatch chunk, retire/refill finished tenants, apply
        the health policy, and (with a journal) write the chunk barrier:
        fleet snapshot on the executor's background checkpoint lane plus
        a ``chunk_complete`` journal record. Returns True while work
        remains (active tenants or pending specs). Between calls is the
        legal window for :meth:`evict`."""
        if self.state is None:
            self.start()
        gens = self._sweep()
        # SLA pass BEFORE sizing the chunk: an urgent deadlined spec may
        # preempt its way in, and the chunk length must honor the
        # freshly admitted tenant's budget
        gens = self._apply_sla(gens)
        active = [
            (i, s) for i, s in enumerate(self.slots)
            if s is not None and s.active
        ]
        if not active:
            self._finish()
            return False
        # int(): the budget term is np.int32 (the generation ledger) and
        # the chunk term a python int — left mixed, the dispatched
        # operand's abstract type flips between weak and strong int32
        # depending on which term wins, which reads as a retrace to the
        # strict detector watching the run entry
        n = int(
            min(
                self.chunk,
                min(s.spec.n_steps - gens[i] for i, s in active),
            )
        )
        self._dispatch(n)
        self._sweep()
        self._apply_health_policy()
        self._barrier()
        if self.metrics is not None:
            # the per-chunk sample: queue-depth gauges plus one durable
            # full-registry snapshot whose embedded `queue` counters are
            # the validator's coherence referee (check_report re-checks
            # slo.* against them on every sample record)
            m = self.metrics
            m.set("queue.pending", len(self.pending))
            m.set("queue.continuations", len(self.continuations))
            m.set(
                "queue.running",
                sum(1 for s in self.slots if s is not None and s.active),
            )
            m.sample(
                queue=dict(self.counters),
                generation=int(self.state.generation),
            )
        more = (
            any(s is not None and s.active for s in self.slots)
            or bool(self.pending)
            or bool(self.continuations)
        )
        if not more:
            self._finish()
        return more

    def run(self) -> List[dict]:
        """Drive everything submitted so far to completion."""
        if self.state is None:
            self.start()
        while self.step_chunk():
            pass
        return self.results

    def _finish(self) -> None:
        """Sweep complete: flush the background snapshot lane (a failed
        background fsync must fail the run, not vanish) and mark the
        queue finished — the point at which a NEW RunQueue may adopt
        this workflow (the backref detach contract)."""
        if self.journal is not None:
            self.executor.drain_lane("fleet_snapshot")
        self.finished = True

    # ----------------------------------------------------- durability barrier
    def _barrier(self) -> None:
        """The per-chunk durability barrier: snapshot the whole fleet on
        the executor's background checkpoint lane, then append a
        ``chunk_complete`` record embedding the queue's complete host
        bookkeeping (pending, slots, counters, results length). Recovery
        restores the newest barrier whose snapshot is intact and replays
        the lost stretch deterministically; the journal append is
        synchronous (WAL) while the snapshot pickles in the background —
        a barrier whose snapshot never landed is skipped at recovery."""
        if self.journal is None:
            return
        state, ckpt = self.state, self._fleet_ckpt
        self.executor.submit_background(
            "fleet_snapshot",
            lambda: ckpt.save(state),
            counter="bg_checkpoint",
        )
        gen = int(state.generation)
        # the attestation is computed BEFORE the background pickle runs:
        # the journal pins the digest of the bits the barrier describes,
        # not whatever the snapshot file ends up holding (one jitted
        # dispatch; only the digest words are fetched)
        extra = {}
        if self.attest is not None:
            att_rec = self.attest.attestation(state)
            att_rec["generation"] = gen
            extra["attest"] = att_rec
        self.journal.append(
            "chunk_complete",
            generation=gen,
            snapshot=str(ckpt.directory / f"ckpt_{gen:08d}.pkl"),
            config_sha=self._config_sha,
            pending=[getattr(s, "_journal_seq", None) for s in self.pending],
            continuations=[
                {
                    "seq": c.get("seq"),
                    "checkpoint": c.get("checkpoint"),
                    "done": c.get("done"),
                }
                for c in self.continuations
            ],
            slots=[
                None
                if s is None
                else {
                    "seq": getattr(s.spec, "_journal_seq", None),
                    "active": s.active,
                    "frozen": s.frozen,
                }
                for s in self.slots
            ],
            counters=dict(self.counters),
            results_len=len(self.results),
            health_len=len(self.health_events),
            slot_restarts=list(self._slot_restarts),
            **extra,
        )

    # ------------------------------------------------------- health policy
    def _apply_health_policy(self) -> None:
        """Evaluate the fleet health policy at the chunk boundary and
        apply per-slot actions. Pure function of the (restored) state
        and slot table, so crash recovery replays identical verdicts."""
        if self.health_policy is None:
            return
        from .fleet_health import fleet_health_signals

        signals = fleet_health_signals(self.state)
        for i, slot in enumerate(self.slots):
            if slot is None or not slot.active:
                continue
            row = {k: v[i] for k, v in signals.items()}
            verdict = self.health_policy.decide(row, self._slot_restarts[i])
            if verdict is None:
                continue
            action, reason = verdict
            event = {
                "health_seq": len(self.health_events),
                "chunk": self.counters["chunks"],
                "slot": i,
                "tag": slot.spec.tag,
                "action": action,
                "reason": reason,
                "generation": int(row["generation"]),
            }
            if self.journal is not None:
                self.journal.append("health", **event)
            self.health_events.append(event)
            if self.metrics is not None:
                self.metrics.count(f"health.{action}")
            if action == "freeze":
                self._freeze(i)
            elif action == "evict":
                self.counters["evicted"] += 1
                self._close_out(i, status="evicted")
                # the evicted tenant was by definition unhealthy: if the
                # slot parked (pending empty), mask its rows too
                self._mask_parked(i)
            elif action == "restart":
                self._restart_slot(i)

    def _freeze(self, index: int) -> None:
        """Quarantine a slot in place: close it out (forensic checkpoint
        + result entry, status ``"frozen"``), mask its tell inside the
        fused step, and park the slot — never refilled, so the poisoned
        state stays inspectable at fixed fleet shape."""
        slot = self.slots[index]
        self.counters["frozen"] += 1
        self._close_out(index, status="frozen", refill=False)
        slot.frozen = True
        self.state = self.workflow.set_frozen(self.state, index, True)

    def _restart_slot(self, index: int) -> None:
        """Restart a slot in place (the guardrail ``recenter_state``
        path, budget preserved): deterministic in (spec, fleet
        generation), so recovery replays the identical restart."""
        from .fleet_health import restarted_tenant

        slot = self.slots[index]
        old = jax.device_get(
            jax.tree.map(lambda x: x[index], self.state.tenants)
        )
        fresh = restarted_tenant(
            self.workflow,
            old,
            slot.spec.key(),
            int(self.state.generation),
            slot.spec.hyperparams,
        )
        self.state = self.workflow.insert_tenant(self.state, index, fresh)
        self._slot_restarts[index] += 1
        self.counters["restarted"] += 1

    # ------------------------------------------------------- retire / evict
    def _tenant_dir(self, slot: _Slot, index: int) -> Optional[Path]:
        if self.checkpoint_dir is None:
            return None
        name = slot.spec.tag or f"tenant_{self.counters['retired'] + self.counters['evicted']:04d}_slot{index}"
        # never share a snapshot directory between two close-outs: the
        # config fingerprint cannot tell two same-shape searches apart,
        # so a reused tag would let one tenant's snapshot silently
        # shadow the other's on resume
        if name in self._used_dirs:
            seq = 2
            while f"{name}_{seq}" in self._used_dirs:
                seq += 1
            name = f"{name}_{seq}"
        self._used_dirs.add(name)
        return self.checkpoint_dir / name

    def _extract(self, index: int) -> StdWorkflowState:
        # the tenant's own generation counter rides in the state itself
        return self.workflow.extract_tenant(self.state, index)

    def _close_out(self, index: int, status: str, refill: bool = True) -> dict:
        slot = self.slots[index]
        solo = self._extract(index)
        entry: dict = {
            "tag": slot.spec.tag,
            "slot": index,
            "status": status,
            "generations": int(solo.generation),
            "budget": slot.spec.n_steps,
        }
        tenant_dir = self._tenant_dir(slot, index)
        if tenant_dir is not None:
            ckpt = WorkflowCheckpointer(
                str(tenant_dir), every=max(int(solo.generation), 1),
                keep=self.keep,
            )
            ckpt.save(solo)
            entry["checkpoint"] = str(tenant_dir)
        reports = self.workflow.monitor_reports(solo.monitors)
        if reports:
            entry["monitors"] = reports
        # the crash law's referee: any monitor exposing fingerprint()
        # (TelemetryMonitor's ring digest) stamps the close-out, so
        # recovered and uncrashed sweeps are comparable record-for-record
        prints = [
            mon.fingerprint(solo.monitors[j])
            for j, mon in enumerate(self.workflow.monitors)
            if hasattr(mon, "fingerprint")
        ]
        if prints:
            entry["fingerprints"] = prints
        entry["hyperparams"] = {
            k: jnp.asarray(v).tolist()
            for k, v in self.workflow.tenant_hyperparams(
                index, state=self.state
            ).items()
        }
        if self.metrics is not None:
            fleet_gen = int(self.state.generation)
            deadline = slot.spec.deadline
            if deadline is not None and status in (
                "completed", "evicted", "frozen",
            ):
                # the SLO ledger's verdict column: a deadlined spec is
                # settled ONLY at a terminal close-out (preemption and
                # growth park continuations — the contract still stands)
                if status == "completed" and fleet_gen <= int(deadline):
                    self.metrics.count("slo.deadline_hits")
                else:
                    self.metrics.count("slo.deadline_misses")
            self.metrics.event(
                f"queue.{status}",
                tag=slot.spec.tag,
                slot=index,
                generations=entry["generations"],
            )
            if status in ("evicted", "frozen"):
                # every queue post-mortem carries the black-box tape
                entry["flight_recorder"] = self.metrics.tail(20)
        if self.journal is not None:
            kind = {
                "evicted": "evict",
                "frozen": "freeze",
                "preempted": "preempt",
                "grown": "autoscale",
            }.get(status, "retire")
            self.journal.append(
                kind,
                result_seq=len(self.results),
                spec_seq=getattr(slot.spec, "_journal_seq", None),
                config_sha=self._config_sha,
                entry=entry,
            )
        slot.active = False
        self.results.append(entry)
        if refill:
            self._refill(index)
        return entry

    def _retire(self, index: int, status: str) -> dict:
        self.counters["retired"] += 1
        return self._close_out(index, status)

    def evict(self, index: int) -> dict:
        """Evict slot ``index`` mid-run (between chunks): its state is
        extracted as a solo snapshot (checkpointed when a directory is
        configured — the RESUMABLE artifact), the result is recorded
        with status ``"evicted"``, and the slot is refilled from the
        pending queue (or parked as inactive when pending is empty —
        never an error). Resume the evicted search with
        ``workflow.solo_workflow(hyperparams=...).run(...,
        resume_from=<checkpoint>)``. Legal only between chunks of a
        STARTED queue: evicting before ``start()`` (or a bogus slot
        index) raises instead of corrupting the slot table."""
        if self.state is None:
            raise RuntimeError(
                "RunQueue.evict before start(): there is no fleet state "
                "to extract a tenant from — the legal eviction window is "
                "between step_chunk() calls"
            )
        if not 0 <= index < len(self.slots):
            raise ValueError(
                f"slot index {index} out of range for a "
                f"{len(self.slots)}-wide fleet"
            )
        slot = self.slots[index]
        if slot is None or not slot.active:
            raise ValueError(f"slot {index} has no active tenant to evict")
        self.counters["evicted"] += 1
        entry = self._close_out(index, status="evicted")
        self._mask_parked(index)
        return entry

    def _mask_parked(self, index: int) -> None:
        """After an eviction whose slot could NOT be refilled (pending
        empty), the parked slot may still hold a poisoned tenant that
        would keep churning NaNs through the fused step — with a freeze
        mask available, stop its rows. Unlike a health-policy freeze,
        the SLOT stays refillable: the mask bit (not ``slot.frozen``) is
        set, and the next admission clears it — a late ``submit()``
        still admits into the parked slot."""
        slot = self.slots[index]
        if (
            slot is not None
            and not slot.active
            and not slot.frozen
            and self.state.frozen is not None
        ):
            self.state = self.workflow.set_frozen(self.state, index, True)

    @staticmethod
    def _edf_key(spec: TenantSpec):
        return (spec.deadline, getattr(spec, "_journal_seq", 0))

    def _fresh_tenant(self, spec: TenantSpec) -> TenantState:
        wf = self.workflow
        solo = wf.init_tenant(spec.key(), spec.hyperparams)
        if wf.algorithm.has_init_ask or wf.algorithm.has_init_tell:
            # algorithms with a distinct first generation peel it SOLO:
            # the fleet's steady vmapped step must never dispatch
            # init_ask/init_tell for one slot only (static shape law).
            # The peel is the fleet's own jitted single-tenant step with
            # the bindings as traced operands — one compile serves every
            # admission (and advances the tenant's own generation to 1)
            solo = wf._solo_peel(solo)
        return solo

    def _continuation_state(self, cont: dict) -> Any:
        if cont.get("state") is not None:
            return cont["state"]
        from .checkpoint import _as_checkpointer

        solo = _as_checkpointer(cont["checkpoint"]).latest()
        if solo is None:
            raise RuntimeError(
                f"continuation checkpoint {cont['checkpoint']} holds no "
                "intact snapshot — the parked tenant cannot be resumed"
            )
        return solo

    def _refill(self, index: int) -> None:
        """Admit the next unit of work into a freed slot, or park the
        slot (it keeps stepping in lockstep; its results are ignored).
        Priority: deadlined work in EDF order — pending specs AND parked
        deadlined continuations compete in one EDF ladder (a preempted
        deadlined victim keeps its SLA standing; exempting it would let
        fresh deadlined arrivals starve it) — then parked continuations
        (they were displaced to make room — they return before new FIFO
        arrivals), then FIFO pending."""
        if not self.pending and not self.continuations:
            return
        kind, unit = self._take_next_unit()
        if kind == "spec":
            self._install(index, unit, self._fresh_tenant(unit), resumed=False)
        else:
            self._install(
                index,
                unit["spec"],
                self._continuation_state(unit),
                resumed=True,
            )

    def _take_next_unit(self) -> Tuple[str, Any]:
        """Remove and return the next admissible unit of work under the
        ``_refill`` priority ladder: EDF across ALL deadlined work
        (pending specs and parked continuations), then parked
        continuations FIFO, then pending FIFO. Returns
        ``("spec", TenantSpec)`` or ``("cont", continuation_dict)``."""
        dl_cont = [
            c for c in self.continuations
            if c["spec"].deadline is not None
        ]
        best_c = (
            min(dl_cont, key=lambda c: self._edf_key(c["spec"]))
            if dl_cont
            else None
        )
        dl_pend = [s for s in self.pending if s.deadline is not None]
        best_p = min(dl_pend, key=self._edf_key) if dl_pend else None
        if best_c is not None and (
            best_p is None
            or self._edf_key(best_c["spec"]) < self._edf_key(best_p)
        ):
            self.continuations.remove(best_c)
            return ("cont", best_c)
        if self.pending and (best_p is not None or not self.continuations):
            if best_p is not None:
                self.pending.remove(best_p)
                return ("spec", best_p)
            return ("spec", self.pending.pop(0))
        return ("cont", self.continuations.pop(0))

    def _install(
        self, index: int, spec: TenantSpec, solo: Any, resumed: bool
    ) -> None:
        wf = self.workflow
        hp = (
            {k: jnp.asarray(v) for k, v in spec.hyperparams.items()}
            if resumed
            else None  # fresh TenantState carries its own bindings
        )
        self.state = wf.insert_tenant(self.state, index, solo, hyperparams=hp)
        if self.state.frozen is not None:
            self.state = wf.set_frozen(self.state, index, False)
        self.slots[index] = _Slot(spec=spec)
        self._slot_restarts[index] = 0
        self.counters["admitted"] += 1
        if resumed:
            self.counters["readmitted"] += 1
        if self.metrics is not None:
            # EDF admissions land here too (the SLA pass installs its
            # urgent spec through _install) — one site keeps the SLO
            # ledger coherent with counters["admitted"] by construction
            self.metrics.count("slo.admissions")
            if resumed:
                self.metrics.count("queue.readmissions")
        if self.journal is not None:
            self.journal.append(
                "admit",
                slot=index,
                spec_seq=getattr(spec, "_journal_seq", None),
                fleet_generation=int(self.state.generation),
                resumed=resumed,
            )
        # restore coherence: the supervisor's newest snapshot must
        # contain the ADMITTED tenant — its restore rung would otherwise
        # resurrect a pre-admission fleet (structurally identical, so
        # the config guard cannot object) and silently attribute the old
        # tenant's trajectory to this spec
        ckpt = getattr(self.supervisor, "checkpointer", None)
        if ckpt is not None:
            ckpt.save(self.state)

    # ------------------------------------------------------ SLA scheduling
    def _apply_sla(self, gens):
        """Deadline-weighted admission + preemption, evaluated before
        each chunk dispatch. Every quantity is measured in fleet
        generations or journal order — never wall clock — so recovery
        replays the identical decisions (the PR-11 determinism law).

        Rule: a pending deadlined spec that could NOT meet its deadline
        after waiting one more chunk (``fleet_gen + chunk + n_steps >
        deadline``) must be admitted now. If no slot is free, preempt
        the "most over-budget" running tenant — the one holding its slot
        longest (max remaining generations) among tenants that are not
        deadline-tight themselves. The victim parks as a standard
        eviction checkpoint and is auto-resubmitted as a continuation
        (:meth:`submit_resume`): preemption trades the victim's latency,
        never its work. Returns the refreshed generation ledger."""
        # a deadlined tenant parked as a preemption continuation keeps
        # competing under the same SLA contract as fresh deadlined
        # arrivals: exempting it would let a stream of new deadlined
        # specs starve it past its deadline with no escalation,
        # contradicting "latency traded, never work"
        units = sorted(
            [("pending", s, s) for s in self.pending
             if s.deadline is not None]
            + [("cont", c, c["spec"]) for c in self.continuations
               if c["spec"].deadline is not None],
            key=lambda u: self._edf_key(u[2]),
        )
        if not units:
            return gens
        # ONE fetch for the whole pass: nothing below advances the
        # fleet generation (preemption/admission are state surgery), and
        # every fetch is a blocking device-to-host round trip
        fleet_gen = int(self.state.generation)
        for kind, unit, spec in units:
            # remaining work: exact for a fresh spec, and for a parked
            # continuation whose park-time progress was recorded
            # (``done``); only a done-less continuation (a pre-PR-12
            # journal) falls back to the n_steps upper bound with a
            # 1-generation lower bound for the doomed test — err urgent
            # on the wait side, only skip when provably lost
            if kind == "pending":
                remaining_hi = remaining_lo = spec.n_steps
            elif unit.get("done") is not None:
                remaining_hi = remaining_lo = max(
                    spec.n_steps - int(unit["done"]), 1
                )
            else:
                remaining_hi, remaining_lo = spec.n_steps, 1
            if fleet_gen + remaining_lo > spec.deadline:
                continue  # provably doomed: preemption cannot save it —
                # it stays queued best-effort in EDF order; parking a
                # healthy victim for a guaranteed miss is pure thrash
            if fleet_gen + self.chunk + remaining_hi <= spec.deadline:
                continue  # can still afford to wait one chunk
            # a parked (refillable) slot admits without preemption —
            # _sweep already refilled those in SLA order, so reaching
            # here means every slot is busy (or frozen)
            victim = self._preempt_victim(spec, gens, fleet_gen)
            if victim is None:
                continue  # nothing preemptible: best-effort, no thrash
            self._preempt(victim)
            if kind == "pending":
                self.pending.remove(unit)
                self._install(
                    victim, spec, self._fresh_tenant(spec), resumed=False
                )
            else:
                self.continuations.remove(unit)
                self._install(
                    victim, spec,
                    self._continuation_state(unit), resumed=True,
                )
            # refresh the ledger NOW: a later unit's victim scan must
            # see the just-installed tenant's (zero/resumed) progress,
            # not the preempted tenant's — a stale count would let unit
            # B immediately preempt unit A at zero generations of
            # progress (pure thrash, A tight by construction)
            gens = self._tenant_generations()
        return gens

    def _preempt_victim(
        self, spec: TenantSpec, gens, fleet_gen: int
    ) -> Optional[int]:
        best, best_remaining = None, 0
        for i, slot in enumerate(self.slots):
            if slot is None or not slot.active or slot.frozen:
                continue
            remaining = int(slot.spec.n_steps - gens[i])
            if remaining <= 0:
                continue
            d = slot.spec.deadline
            if d is not None and fleet_gen + self.chunk + remaining > d:
                continue  # itself deadline-tight: preempting it just
                # moves the miss, never removes it
            if remaining > best_remaining:
                best, best_remaining = i, remaining
        return best

    def _preempt(self, index: int) -> None:
        slot = self.slots[index]
        self.counters["preempted"] += 1
        if self.metrics is not None:
            # the discrete event itself rides the _close_out status
            # record (`queue.preempted`); only the ledger counter here
            self.metrics.count("slo.preemptions")
        entry = self._close_out(index, status="preempted", refill=False)
        ckpt_dir = entry.get("checkpoint")
        if ckpt_dir is None:
            # _validate_spec guarantees a checkpoint_dir whenever a
            # deadlined spec (the only preemption trigger) is accepted
            raise RuntimeError(
                "preempted a tenant without a checkpoint directory — "
                "its work would be lost (this is a bug: deadlined specs "
                "require checkpoint_dir at submit())"
            )
        self.submit_resume(
            slot.spec,
            checkpoint=ckpt_dir,
            done=int(entry.get("generations") or 0),
        )

    # ------------------------------------------------------------- recovery
    @classmethod
    def recover(
        cls,
        workflow: VectorizedWorkflow,
        journal_dir: str,
        supervisor: Any = None,
        executor: Any = None,
        health_policy: Any = None,
        allow_config_mismatch: bool = False,
        metrics: Any = None,
        attest: Any = None,
    ) -> "RunQueue":
        """Rebuild a journaled sweep after the driver died — at ANY
        point, including mid-background-fsync.

        Reads the journal (hash chain verified; a torn tail is truncated
        with a warning, a tampered middle raises
        :class:`~evox_tpu.workflows.journal.JournalIntegrityError`),
        checks the journaled config fingerprint against ``workflow``
        (mismatch raises :class:`CheckpointConfigError` — the PR-5
        guard, not a new one), restores the fleet from the newest chunk
        barrier whose snapshot is provably intact (torn snapshots are
        skipped, falling back one barrier), and rebuilds
        pending/slots/counters/results exactly as they stood at that
        barrier. Driving the returned queue (``q.run()``) replays the
        lost stretch deterministically: per-tenant results and telemetry
        fingerprints equal the uncrashed run's, each spec admitted
        exactly once.
        """
        from .checkpoint import state_config_fingerprint
        from .journal import RunJournal

        journal = (
            journal_dir
            if isinstance(journal_dir, RunJournal)
            else RunJournal(str(journal_dir))
        )
        recs = journal.records()
        specs: Dict[int, TenantSpec] = {}
        resume_from: Dict[int, Optional[str]] = {}
        resume_done: Dict[int, Optional[int]] = {}
        for r in recs:
            if r["kind"] == "submit":
                seq = int(r["spec_seq"])
                specs[seq] = _spec_from_record(r)
                if r.get("resume_from") is not None:
                    # a continuation submit (preemption / elastic
                    # growth): its tenant resumes from the named
                    # checkpoint, never a fresh init
                    resume_from[seq] = r["resume_from"]
                    resume_done[seq] = (
                        int(r["done"]) if r.get("done") is not None else None
                    )
        start = next((r for r in recs if r["kind"] == "start"), None)
        ckpt_dir = start.get("checkpoint_dir") if start is not None else None
        if (
            health_policy is None
            and start is not None
            and start.get("health_policy")
        ):
            # the journaled policy config rides the start record so the
            # replay keeps isolating poisoned tenants exactly as the
            # uncrashed run would — an explicit health_policy= overrides
            from .fleet_health import FleetHealthPolicy

            health_policy = FleetHealthPolicy(**start["health_policy"])
        q = cls(
            workflow,
            chunk=int(start["chunk"]) if start is not None else 10,
            supervisor=supervisor,
            checkpoint_dir=ckpt_dir,
            keep=int(start.get("keep", 2)) if start is not None else 2,
            executor=executor,
            journal=journal,
            health_policy=health_policy,
            metrics=metrics,
            attest=attest,
        )
        q._spec_seq = max(specs, default=-1) + 1
        q.counters["submitted"] = len(specs)
        def _requeue_all() -> None:
            # continuations born from a preemption/growth close-out IN
            # THIS journal are replay-derived: their original spec is
            # requeued fresh below and the replay re-creates the
            # continuation — requeueing both would run the tenant twice.
            # Cross-journal continuations (elastic growth admits into
            # the TARGET bucket's journal) have no matching close-out
            # here and are kept.
            derived = {
                (r.get("entry") or {}).get("checkpoint")
                for r in recs
                if r["kind"] in ("preempt", "autoscale")
            }
            # a stolen seq is already durable in ANOTHER pod's journal
            # (the steal record is appended only after the target submit
            # fsynced) — requeueing it here would run the tenant twice,
            # once per pod
            stolen = {
                int(r["spec_seq"])
                for r in recs
                if r["kind"] == "steal" and r.get("spec_seq") is not None
            }
            q.pending = [
                specs[s]
                for s in sorted(specs)
                if s not in resume_from and s not in stolen
            ]
            q.continuations = []
            seen_ckpts: set = set()
            for s in sorted(specs):
                if s in stolen:
                    continue
                if s not in resume_from or resume_from[s] in derived:
                    continue
                if resume_from[s] in seen_ckpts:
                    continue  # replay-duplicated submit for one parked
                    # checkpoint (lowest seq wins — the claimed dedup)
                seen_ckpts.add(resume_from[s])
                q.continuations.append(
                    {
                        "spec": specs[s],
                        "seq": s,
                        "checkpoint": resume_from[s],
                        "state": None,
                        "done": resume_done.get(s),
                    }
                )

        if start is None:
            # crashed before (or during) start(): nothing ran to a
            # durable barrier — the whole sweep re-queues and starts
            # fresh, each spec still executed exactly once overall
            _requeue_all()
            journal.append("recover", generation=None, snapshot=None)
            if q.metrics is not None:
                q.metrics.restore_at(generation=None)
            return q
        # --- config guard (PR 5 fingerprint, reused): the supplied
        # workflow must produce the SAME fleet state structure the
        # journal was written under. eval_shape traces init without
        # running it — shapes/dtypes are all the fingerprint reads.
        first_wave = [specs[s] for s in start["slots"]]
        try:
            hp = q._stack_hp([s.hyperparams for s in first_wave])
            keys = jnp.stack([s.key() for s in first_wave])
            expect = jax.eval_shape(
                partial(workflow.init, hyperparams=hp), keys
            )
            if start.get("freeze_mask"):
                import numpy as np

                expect = expect.replace(
                    frozen=jax.ShapeDtypeStruct(
                        (workflow.n_tenants,), np.bool_
                    )
                )
            expected_sha = state_config_fingerprint(expect)
        except Exception as e:
            raise CheckpointConfigError(
                "the supplied workflow cannot even rebuild the journaled "
                f"fleet structure ({type(e).__name__}: {e}) — algorithm, "
                "hyperparameter names, or fleet width changed since the "
                "journal was written"
            ) from e
        recorded = start.get("config_sha")
        if (
            recorded is not None
            and recorded != expected_sha
            and not allow_config_mismatch
        ):
            raise CheckpointConfigError(
                f"journal {journal.path} was written under a different "
                f"fleet config (journal config_sha {recorded[:12]}… != "
                f"supplied workflow's {expected_sha[:12]}…): algorithm, "
                "population size, fleet width, monitors, or hyperparam "
                "names changed. Rebuild the matching workflow or pass "
                "allow_config_mismatch=True."
            )
        q._config_sha = recorded or expected_sha
        # --- newest barrier with an intact snapshot
        barriers = [r for r in recs if r["kind"] == "chunk_complete"]
        meta: Optional[dict] = None
        state = None
        verifier = q.attest
        for b in reversed(barriers):
            state = q._fleet_ckpt.load(int(b["generation"]))
            if state is None:
                continue
            att_rec = b.get("attest")
            if att_rec is not None:
                # the journal pinned a digest of the fleet bits at this
                # barrier — refuse a snapshot whose BITS drifted even if
                # its pickle bytes are internally sha256-consistent
                # (file swapped/rebuilt after the fact), naming the
                # splitting leaves and falling back one barrier
                if verifier is None:
                    from ..core.attest import StateAttestor

                    verifier = StateAttestor()
                try:
                    verifier.verify(
                        state,
                        att_rec,
                        generation=int(b["generation"]),
                        where=f"fleet snapshot {b.get('snapshot')}",
                    )
                except IntegrityError as e:
                    event = {
                        "event": "corrupt_snapshot",
                        "generation": int(b["generation"]),
                        "snapshot": b.get("snapshot"),
                        "leaves": list(e.leaves),
                        "action": "barrier_fallback",
                    }
                    q.integrity_events.append(event)
                    journal.append("integrity", **event, error=str(e)[:300])
                    if q.metrics is not None:
                        q.metrics.count("integrity.recover_refusals")
                        q.metrics.event(
                            "integrity.corrupt_snapshot", **event
                        )
                    state = None
                    continue
            meta = b
            break
        if meta is None:
            # start()ed but no barrier landed (killed in the first chunk
            # or mid-first-fsync): re-queue everything and start fresh
            _requeue_all()
            journal.append("recover", generation=None, snapshot=None)
            if q.metrics is not None:
                q.metrics.restore_at(generation=None)
            return q
        state = workflow.place_restored(state)
        if (
            health_policy is not None
            and health_policy.may_freeze()
            and state.frozen is None
        ):
            state = workflow.with_freeze_mask(state)
        q.state = state
        # a steal record (pre- OR post-barrier) marks work that is
        # already durable in another pod's journal — the WAL order
        # (target submit fsynced before the steal is appended here)
        # makes honoring EVERY steal safe: the barrier may predate the
        # steal, but the moved work must not be restored into this
        # queue or it runs twice, once per pod
        stolen = {
            int(r["spec_seq"])
            for r in recs
            if r["kind"] == "steal" and r.get("spec_seq") is not None
        }
        q.pending = [
            specs[s] for s in meta["pending"] if int(s) not in stolen
        ]
        q.continuations = [
            {
                "spec": specs[int(c["seq"])],
                "seq": int(c["seq"]),
                "checkpoint": c.get("checkpoint"),
                "state": None,
                "done": (
                    int(c["done"]) if c.get("done") is not None else None
                ),
            }
            for c in meta.get("continuations", []) or []
            if int(c["seq"]) not in stolen
        ]
        q.slots = [
            None
            if s is None
            else _Slot(
                spec=specs[s["seq"]],
                active=bool(s["active"]),
                frozen=bool(s.get("frozen", False)),
            )
            for s in meta["slots"]
        ]
        # merge (not replace): barriers written before a counter existed
        # (older journals) must not strip it from the live dict
        q.counters.update({k: int(v) for k, v in meta["counters"].items()})
        # the WAL records every acknowledged submit — len(specs) is the
        # ground truth, not the barrier-time snapshot (a spec submitted
        # AFTER the barrier is requeued below and must stay counted)
        q.counters["submitted"] = len(specs)
        q._slot_restarts = [
            int(v)
            for v in meta.get(
                "slot_restarts", [0] * workflow.n_tenants
            )
        ]
        # close-outs and health events that were durable AT the barrier;
        # later records describe work the crash rolled back — the replay
        # re-executes (and re-journals) them with identical content
        closeouts = {
            int(r["result_seq"]): r["entry"]
            for r in recs
            if r["kind"] in (
                "retire", "evict", "freeze", "preempt", "autoscale",
            )
        }
        q.results = [closeouts[i] for i in range(int(meta["results_len"]))]
        # --- mid-sweep submits (the WAL law: an ACKNOWLEDGED submit
        # survives a crash). SLA work arrives mid-sweep by nature, so a
        # spec journaled after the restored barrier appears in no
        # barrier list — requeue every seq the barrier does not account
        # for: not pending/parked/slotted at the barrier, and not closed
        # out by a record that was durable BEFORE it (close-outs after
        # the barrier describe progress the crash rolled back; their
        # tenants are still in meta["slots"], so they stay accounted)
        barrier_pos = next(
            i for i, r in enumerate(recs) if r is meta
        )
        accounted = (
            {int(s) for s in meta["pending"] if s is not None}
            | {int(c["seq"]) for c in q.continuations}
            | {
                int(s["seq"]) for s in meta["slots"] if s is not None
            }
            | {
                int(r["spec_seq"])
                for r in recs[:barrier_pos]
                if r["kind"]
                in ("retire", "evict", "freeze", "preempt", "autoscale")
                and r.get("spec_seq") is not None
            }
        )
        # ...EXCEPT continuations born from a post-barrier preemption:
        # their victim is still RUNNING in the restored slots, and the
        # deterministic replay re-derives the preemption (and re-journals
        # an identical continuation) — requeueing the crashed-off one
        # would run the tenant twice
        replay_derived = {
            (r.get("entry") or {}).get("checkpoint")
            for r in recs[barrier_pos:]
            if r["kind"] in ("preempt", "autoscale")
        }
        # ...and dedup by the parked CHECKPOINT itself: after a PRIOR
        # crash the replay re-journals a continuation under a NEW seq
        # for the same parked checkpoint — once any seq resuming from
        # that checkpoint is accounted (or requeued first, lowest seq
        # wins), a second seq must not admit the same work twice
        claimed = {
            resume_from[s] for s in accounted if s in resume_from
        }
        for seq in sorted(specs):
            if seq in accounted or seq in stolen:
                continue
            if seq in resume_from:
                ck = resume_from[seq]
                if ck in replay_derived or ck in claimed:
                    continue
                claimed.add(ck)
                q.continuations.append(
                    {
                        "spec": specs[seq],
                        "seq": seq,
                        "checkpoint": ck,
                        "state": None,
                        "done": resume_done.get(seq),
                    }
                )
            else:
                q.pending.append(specs[seq])
        healths = {
            int(r["health_seq"]): {
                k: v
                for k, v in r.items()
                if k in (
                    "health_seq", "chunk", "slot", "tag", "action",
                    "reason", "generation",
                )
            }
            for r in recs
            if r["kind"] == "health"
        }
        q.health_events = [
            healths[i] for i in range(int(meta.get("health_len", 0)))
        ]
        q._used_dirs = {
            Path(e["checkpoint"]).name
            for e in q.results
            if e.get("checkpoint")
        }
        q.finished = False
        journal.append(
            "recover",
            generation=int(meta["generation"]),
            snapshot=meta.get("snapshot"),
        )
        if q.metrics is not None:
            # restore the metrics plane to the SAME barrier the fleet
            # came back to: the replayed stretch re-counts exactly what
            # the crash rolled back, so the post-crash SLO ledger
            # converges to the uncrashed run's (the validator resets its
            # monotonicity baseline at the queue.recover event)
            q.metrics.restore_at(generation=int(meta["generation"]))
        return q

    # -------------------------------------------------------------- report
    def health_report(self) -> Optional[dict]:
        """The ``tenancy.fleet_health`` section: policy config + the
        chunk-boundary action log. None when no policy ever acted."""
        if self.health_policy is None and not self.health_events:
            return None
        return {
            "policy": (
                self.health_policy.report()
                if self.health_policy is not None
                and hasattr(self.health_policy, "report")
                else None
            ),
            "events": list(self.health_events),
        }

    def report(self) -> dict:
        running = sum(1 for s in self.slots if s is not None and s.active)
        out = {
            "capacity": self.workflow.n_tenants,
            "chunk": self.chunk,
            "counters": dict(self.counters),
            "pending": len(self.pending),
            "continuations": len(self.continuations),
            "running": running,
            "results": [
                {k: v for k, v in r.items() if k != "monitors"}
                for r in self.results
            ],
        }
        if self.journal is not None:
            out["journal"] = self.journal.report()
        if self.integrity_events:
            out["integrity_events"] = [dict(e) for e in self.integrity_events]
        return out

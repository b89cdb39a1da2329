"""StdWorkflow — the single-program, mesh-native orchestration loop.

Capability parity with the reference's ``StdWorkflow`` (reference:
src/evox/workflows/std_workflow.py) **and** its ``RayDistributedWorkflow``
(reference: src/evox/workflows/distributed.py), redesigned for TPU:

- The whole ask → evaluate → tell generation is ONE jitted function over a
  global ``jax.sharding.Mesh``. No pmap, no per-rank slicing, no Ray RPC.
- The candidate population is constrained to a ``NamedSharding`` over the
  ``"pop"`` mesh axis before evaluation; GSPMD partitions the (vmapped)
  evaluation across all devices and inserts the fitness all-gather over ICI
  where the algorithm's ``tell`` consumes it globally — this replaces the
  reference's ``lax.dynamic_slice_in_dim`` + ``lax.all_gather`` pmap dance
  (std_workflow.py:160,189-200) and the entire Ray object-store path.
- Multi-host: initialize ``jax.distributed`` (core/distributed.py), build the
  mesh over all pod devices, run the same program — collectives ride
  ICI within a slice, DCN across slices.
- Host-side (non-jittable) problems run through ``jax.pure_callback`` with a
  declared fitness shape, same contract as the reference's
  ``external_problem=True`` (std_workflow.py:146-158).
- Monitors follow the reference's 8-hook spec but their state is an
  on-device pytree threaded through the step (core/monitor.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.algorithm import Algorithm
from ..core.monitor import Monitor
from ..core.problem import Problem
from ..core.struct import PyTreeNode, static_field, field
from ..core.distributed import (
    POP_AXIS as _POP_AXIS_NAME,
    all_gather,
    shard_pop,
)
from ..core.dtype_policy import DtypePolicy, apply_compute, apply_storage
from ..core.instrument import (
    ASK,
    CONSTRAIN,
    DECODE,
    EVALUATE,
    INIT,
    RUN,
    STEP,
    TELL,
    scope,
    span,
)
from ..utils.common import TreeAndVector, parse_opt_direction
from .checkpoint import (
    WorkflowCheckpointer,
    checkpointed_run,
    enter_run,
)
from .common import (
    build_hook_table,
    callback_evaluate,
    finish_step,
    fused_run,
    ingest_fitness,
    make_run_loop,
    quarantine_nonfinite,
    run_hooks,
)


def _plain_decode_adapter(pop_transforms: tuple) -> Optional[TreeAndVector]:
    """The adapter, where ``pop_transforms`` is exactly one bound
    ``TreeAndVector.batched_to_tree``: then the candidates are that
    adapter's own layout of the algorithm's batch and nothing else, and a
    problem with ``evaluate_genome`` may read them out of the batch itself.
    Any other chain (a wrapped or overridden decode, a second transform):
    ``None``."""
    if len(pop_transforms) != 1:
        return None
    (decode,) = pop_transforms
    adapter = getattr(decode, "__self__", None)
    plain = getattr(decode, "__func__", None) is TreeAndVector.batched_to_tree
    return adapter if plain and isinstance(adapter, TreeAndVector) else None


class StdWorkflowState(PyTreeNode):
    generation: jax.Array
    algo: Any
    prob: Any
    monitors: Tuple[Any, ...]
    first_step: bool = static_field(default=True)


class StdWorkflow:
    """Compose algorithm + problem + monitors into a jitted, sharded step.

    Args:
        algorithm: an :class:`~evox_tpu.core.Algorithm`.
        problem: a :class:`~evox_tpu.core.Problem`.
        monitors: monitors implementing the 8-hook spec.
        opt_direction: ``"min"`` / ``"max"`` or a per-objective list; fitness
            is multiplied by the resulting ±1 vector before ``tell`` so
            algorithms always minimize.
        pop_transforms: applied to candidates before evaluation (e.g.
            ``TreeAndVector.batched_to_tree`` for neuroevolution).
        fit_transforms: applied to the sign-flipped fitness before ``tell``
            (e.g. ``rank_based_fitness``).
        mesh: a ``jax.sharding.Mesh`` with a ``"pop"`` axis. When given, the
            candidate batch and fitness are sharded over it.
        external_problem: force the ``pure_callback`` evaluation path;
            defaults to ``not problem.jittable``.
        num_objectives: fitness arity used to declare callback output shapes.
        jit_step: disable to debug eagerly.
        migrate_helper: optional jittable callable ``() -> (do_migrate,
            foreign_pop, foreign_fitness)`` polled once per generation; when
            ``do_migrate`` is True the algorithm's ``migrate(state, pop,
            fitness) -> state`` ingests the foreign individuals under a
            ``lax.cond`` (the reference's human-in-the-loop migration slot,
            std_workflow.py:230-244). For live injection the helper should
            pull data through ``io_callback``/``pure_callback`` internally —
            a plain closure is traced once and its values baked into the
            compiled step.
        eval_shard_map: evaluate inside an explicit ``jax.shard_map`` island
            — each device scores only its population shard, then the fitness
            is ``all_gather``-ed (tiled) over ICI. Semantically identical to
            the default GSPMD-constraint path (asserted in tests) but the
            collective is explicit; useful when XLA's auto-partitioning of an
            exotic ``evaluate`` is poor. Requires a mesh, a jittable problem
            and a problem state that is replicated-safe (stateless or pure).
        allow_uneven_shards: with a mesh, a population not divisible by the
            ``"pop"`` axis size normally raises at construction (uneven GSPMD
            layouts silently unbalance devices; the reference hard-errors
            too, std_workflow.py:189-193). Set True to accept the uneven
            layout anyway (GSPMD pads internally; shard_map mode still
            requires divisibility).
        quarantine_nonfinite: replace NaN/±Inf fitness entries with the
            worst FINITE value of their generation (per objective) after
            the sign flip and before ``fit_transforms``/``tell`` — a
            poison candidate then loses cleanly instead of corrupting
            argmin/ranking (NaN poisons every comparison-based selection).
            Monitors' ``post_eval`` (including TelemetryMonitor's NaN/Inf
            counters) still observe the RAW fitness, so quarantined
            candidates remain visible in telemetry.
        dtype_policy: an optional :class:`~evox_tpu.core.dtype_policy.
            DtypePolicy` (e.g. ``BF16_STORAGE``). ``field(storage=True)``-
            annotated float leaves of the state are held in the policy's
            storage dtype between generations (halving the memory-bound
            legs' loop-carry HBM traffic) and upcast to the compute dtype
            at step entry, so every reduction/mean/covariance update runs
            full-precision. ``None`` (default) is bit-identical to the
            pre-policy behavior. Checkpoints snapshot the storage-dtype
            leaves; resume with the same policy (the config-fingerprint
            guard records leaf dtypes and refuses cross-policy restores).
        donate_carries: donate the fused ``run`` loop's state carry and
            the pipelined ``tell``'s ask-context (``jax.jit``
            ``donate_argnums``), eliminating the per-dispatch state copy —
            donation shows up as ``alias_bytes`` in the roofline report's
            memory analysis. Caller-visible semantics are preserved:
            ``run()`` advances caller-owned states one non-donating
            ``step`` first and only donates its own intermediates, and
            checkpoint snapshots are always taken from never-donated
            states (snapshot-before-donate). Sharp edges, and why the
            default is False: (a) ``pipeline_ask``'s returned ctx is
            consumed-and-invalidated by ``pipeline_tell`` — don't reuse a
            ctx across tells (``run_host_pipelined`` never does); (b)
            donation changes XLA's fusion clustering inside the run loop,
            which perturbs float results at the last ulp (measured: CSO
            loser rows differ by 1 ulp on the CPU backend) — so the
            default stays off to keep the fused run bit-identical to a
            ``step`` loop (the repo's equivalence laws), and donation is
            an explicit knob no benchmark cell turns on (ROADMAP D4, D8).
    """

    def __init__(
        self,
        algorithm: Algorithm,
        problem: Problem,
        monitors: Sequence[Monitor] = (),
        opt_direction: Any = "min",
        pop_transforms: Sequence[Callable] = (),
        fit_transforms: Sequence[Callable] = (),
        mesh: Optional[jax.sharding.Mesh] = None,
        external_problem: Optional[bool] = None,
        num_objectives: int = 1,
        jit_step: bool = True,
        eval_shard_map: bool = False,
        allow_uneven_shards: bool = False,
        migrate_helper: Optional[Callable] = None,
        quarantine_nonfinite: bool = False,
        dtype_policy: Optional[DtypePolicy] = None,
        donate_carries: bool = False,
    ):
        self.algorithm = algorithm
        self.problem = problem
        self.monitors = tuple(monitors)
        self.opt_direction = parse_opt_direction(opt_direction)
        self.pop_transforms = tuple(pop_transforms)
        self._decode_adapter = _plain_decode_adapter(self.pop_transforms)
        self.fit_transforms = tuple(fit_transforms)
        self.mesh = mesh
        self.num_objectives = num_objectives
        self.external = (not problem.jittable) if external_problem is None else external_problem
        self.eval_shard_map = eval_shard_map
        self.migrate_helper = migrate_helper
        self.quarantine_nonfinite = quarantine_nonfinite
        self.dtype_policy = dtype_policy
        self.donate_carries = bool(donate_carries) and jit_step
        # migration stores raw (sign-flipped) fitness into the algorithm
        # state; population-relative shaped fitness cannot coexist with it
        # (the stored conventions would mix) — see Algorithm.migrate
        if migrate_helper is not None and fit_transforms:
            raise ValueError(
                "migrate_helper cannot be combined with fit_transforms: "
                "migrants carry raw fitness while tell stores shaped values"
            )
        if eval_shard_map and (mesh is None or self.external):
            raise ValueError(
                "eval_shard_map requires a mesh and a jittable problem"
            )
        from ..core.distributed import mesh_spans_processes

        if self.external and mesh_spans_processes(mesh):
            # explicit refusal, not silent corruption: under a mesh that
            # SPANS processes, the pure_callback would run problem.evaluate
            # on EVERY process against its own population shard and an
            # unsynchronized host-side problem object (reference's Ray path
            # existed precisely to own this; SURVEY §7 "host callbacks").
            # A mesh-less workflow — or a process-LOCAL mesh in a
            # multi-process run — stays legal multi-controller JAX: each
            # process owns its whole population locally.
            raise ValueError(
                "external (host) problems are single-process: under "
                "multi-process SPMD each process would invoke the host "
                "evaluate on its own shard against unsynchronized host "
                "state. Scale host rollouts across machines with "
                "ProcessRolloutFarm (problems/neuroevolution/"
                "process_farm.py), or use a jittable problem for mesh "
                "parallelism."
            )
        if mesh is not None:
            n_shards = mesh.shape[_POP_AXIS_NAME]
            pop_size = getattr(algorithm, "pop_size", None)
            if pop_size is not None and pop_size % n_shards != 0:
                if eval_shard_map or not allow_uneven_shards:
                    raise ValueError(
                        f"pop_size {pop_size} is not divisible by the mesh's "
                        f"'pop' axis ({n_shards} shards); pad the population, "
                        "resize the mesh, or pass allow_uneven_shards=True "
                        "to accept an unbalanced GSPMD layout"
                    )
        # everything but the algorithm, for clone_with_algorithm (the IPOP
        # driver rebuilds the workflow around a grown population). Built
        # from the NORMALIZED attributes, not the raw arguments: a caller's
        # one-shot iterable (monitors=iter([...])) is already exhausted by
        # the tuple() above and would silently clone to an empty sequence
        self._ctor_args = dict(
            problem=self.problem,
            monitors=self.monitors,
            opt_direction=opt_direction,
            pop_transforms=self.pop_transforms,
            fit_transforms=self.fit_transforms,
            mesh=self.mesh,
            external_problem=self.external,
            num_objectives=self.num_objectives,
            jit_step=jit_step,
            eval_shard_map=self.eval_shard_map,
            allow_uneven_shards=allow_uneven_shards,
            migrate_helper=self.migrate_helper,
            quarantine_nonfinite=self.quarantine_nonfinite,
            dtype_policy=self.dtype_policy,
            donate_carries=donate_carries,
        )
        for m in self.monitors:
            m.set_opt_direction(self.opt_direction)
        self._hook_table = build_hook_table(self.monitors)
        self.jit_step = jit_step
        self._step = jax.jit(self._step_impl) if jit_step else self._step_impl
        # dynamic trip count: ONE compile covers every n_steps; the carry
        # is donated (fused_run only feeds it internally-produced states)
        self._run_loop = make_run_loop(self._step_impl, donate=self.donate_carries)
        # jitted step halves for the host-overlap driver (pipelined.py);
        # tell consumes-and-invalidates ask's ctx (argnum 1) when donating
        self._p_ask = jax.jit(self._pipeline_ask_impl) if jit_step else self._pipeline_ask_impl
        self._p_tell = (
            jax.jit(
                self._pipeline_tell_impl,
                donate_argnums=(1,) if self.donate_carries else (),
            )
            if jit_step
            else self._pipeline_tell_impl
        )

    def clone_with_algorithm(self, algorithm: Algorithm) -> "StdWorkflow":
        """A new workflow identical to this one but driving ``algorithm``
        (shared problem/monitor OBJECTS, fresh compiled closures) — the
        host-boundary rebuild point for IPOP population growth
        (workflows/ipop.py)."""
        return StdWorkflow(algorithm, **self._ctor_args)

    def analysis_targets(self, state: "StdWorkflowState") -> dict:
        """Entry-point programs for AOT cost/memory analysis
        (core/xla_cost.py): ``{name: (jitted_callable, example_args)}``,
        the exact compiled programs the workflow dispatches.

        The steady state (``first_step=False``) is analyzed — that is
        what every generation after the init peel runs, and what the
        fused ``run`` loop carries. ``run``'s trip count is a traced
        operand and XLA's cost analysis counts a dynamic-trip-count loop
        body once, so its static FLOPs/bytes are PER GENERATION. For
        external (host) problems the jitted step embeds a
        ``pure_callback`` whose host work XLA cannot cost, so the
        pipelined halves (what ``run_host_pipelined`` actually
        dispatches) are analyzed instead; the host ``evaluate`` between
        them is outside XLA and outside this analysis by construction.
        """
        if not self.jit_step:
            return {}
        steady = state.replace(first_step=False) if state.first_step else state
        if self.external:
            cand_sds, ctx_sds = jax.eval_shape(self._p_ask, steady)
            pop = jax.tree.leaves(cand_sds)[0].shape[0]
            if self.num_objectives > 1:
                fit_shape: Tuple[int, ...] = (pop, self.num_objectives)
            else:
                fit_shape = self.problem.fit_shape(pop)
            fit_sds = jax.ShapeDtypeStruct(
                fit_shape, jnp.dtype(self.problem.fit_dtype)
            )
            return {
                "pipeline_ask": (self._p_ask, (steady,)),
                "pipeline_tell": (
                    self._p_tell,
                    (steady, ctx_sds, fit_sds, steady.prob),
                ),
            }
        return {
            "step": (self._step, (steady,)),
            "run": (self._run_loop, (steady, jnp.asarray(1, jnp.int32))),
        }

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> StdWorkflowState:
        with span(INIT):
            return self._init(key)

    def _init(self, key: jax.Array) -> StdWorkflowState:
        keys = jax.random.split(key, 2 + len(self.monitors))
        state = StdWorkflowState(
            generation=jnp.zeros((), dtype=jnp.int32),
            algo=self.algorithm.init(keys[0]),
            prob=self.problem.init(keys[1]),
            monitors=tuple(m.init(k) for m, k in zip(self.monitors, keys[2:])),
            first_step=True,
        )
        # storage-annotated leaves rest in the policy's storage dtype from
        # the very first state, so the step signature never changes
        state = apply_storage(state, self.dtype_policy)
        # pod meshes: the eager init above computed identical host values
        # on every process (same key); assemble them into GLOBAL arrays
        # (per-process make_array_from_single_device_arrays over the
        # field-annotation layout) so the global-mesh jit can consume the
        # state — no-op on single-process meshes (core/distributed.py)
        from ..core.distributed import ensure_global_state

        return ensure_global_state(state, self.mesh)

    # ------------------------------------------------------------------ step
    def step(self, state: StdWorkflowState) -> StdWorkflowState:
        with span(STEP):
            return self._step(state)

    def run(
        self,
        state: StdWorkflowState,
        n_steps: int,
        checkpointer: Optional[WorkflowCheckpointer] = None,
        resume_from: Any = None,
        restarts: Any = None,
    ) -> StdWorkflowState:
        """Run ``n_steps`` generations as ONE compiled program.

        TPU-first: a Python ``for`` loop over ``step`` pays a host dispatch
        per generation; ``run`` fuses generations into a single on-device
        ``lax.fori_loop`` (the reference has no analog — its per-step host
        loop is the cost its Ray pipelining tries to hide). The trip count is
        a traced operand, so one compilation covers every ``n_steps``. The
        first generation is peeled off eagerly (``first_step`` is static so
        the loop carry stays type-stable across the init_ask/init_tell
        dispatch). With ``jit_step=False`` this falls back to an eager
        Python loop for debugging. External (host) problems route through
        the :class:`~evox_tpu.core.executor.GenerationExecutor` host
        pipeline instead (bit-identical to a ``step`` loop, with no host
        callback inside a fused ``fori_loop``); use
        :func:`~evox_tpu.workflows.pipelined.run_host_pipelined` directly
        for ``on_generation``/``eval_chunk``/``max_staleness`` control.

        Crash safety (no host callbacks — see
        workflows/checkpoint.py): ``checkpointer=`` chunks the fused loop
        at the checkpoint cadence and snapshots the state between
        dispatches — final state identical to the unchunked run.
        ``resume_from=`` (a :class:`WorkflowCheckpointer` or directory)
        restores the newest intact snapshot first; ``n_steps`` then counts
        TOTAL generations, so a crashed run re-invoked with identical
        arguments completes the remaining generations and reproduces the
        straight run's final state.

        ``restarts=`` (an :class:`~evox_tpu.core.guardrail.IPOPRestarts`,
        requires the algorithm to be a ``GuardedAlgorithm``) adds
        host-boundary IPOP population doubling: the run is chunked at the
        policy's ``check_every`` cadence, the guarded wrapper's on-device
        health counters are read between dispatches, and a triggered
        restart rebuilds the workflow around a doubled population (one
        recompile per doubling, best-so-far carried across; see
        workflows/ipop.py). Composes with ``checkpointer``/``resume_from``
        — a resumed run rebuilds the snapshot's population size first.

        The call is one ``evox:run`` span (core/instrument.py: on the
        profiler's host plane and in the host log); ``fused_run`` nests in it.
        """
        with span(RUN, n_steps=int(n_steps)):
            if restarts is not None:
                if self.external:
                    # host problems take the executor pipeline for IPOP too —
                    # an ipop segment through fused_run would trace the
                    # pure_callback step the executor routing exists to avoid
                    from .pipelined import run_host_pipelined

                    return run_host_pipelined(
                        self, state, n_steps, checkpointer=checkpointer,
                        resume_from=resume_from, restarts=restarts,
                    )
                from .ipop import ipop_run

                return ipop_run(
                    self,
                    state,
                    n_steps,
                    restarts,
                    segment=lambda w, s, c, ck: (
                        checkpointed_run(w, s, c, ck)
                        if ck is not None
                        else fused_run(w, s, c)
                    ),
                    checkpointer=checkpointer,
                    resume_from=resume_from,
                )
            # shared prologue (workflows/checkpoint.py enter_run): resolve a
            # resume into (restored state, REMAINING steps) with the
            # config-fingerprint guard armed on the caller's live state, and
            # default the checkpointer to the resumed directory
            state, n_steps, checkpointer = enter_run(
                state, n_steps, checkpointer, resume_from, expect_like=state
            )
            if self.external:
                # host-problem path: since PR 8 the fused callback loop is
                # replaced by the executor's double-buffered host pipeline
                # (bit-identical to a step loop — the run==step law — with
                # the host evaluate between dispatches instead of a callback
                # inside the loop); checkpoint snapshots ride its background lane
                from .pipelined import run_host_pipelined

                return run_host_pipelined(
                    self, state, n_steps, checkpointer=checkpointer
                )
            if checkpointer is not None:
                return checkpointed_run(self, state, n_steps, checkpointer)
            return fused_run(self, state, n_steps)

    def resume(
        self,
        checkpointer: WorkflowCheckpointer,
        n_steps: int,
        fallback_state: Optional[StdWorkflowState] = None,
        state_sharding: Any = None,
        allow_config_mismatch: bool = False,
    ) -> StdWorkflowState:
        """Continue an interrupted checkpointed run to ``n_steps`` TOTAL
        generations: restore ``checkpointer``'s newest intact snapshot
        (falling back to ``fallback_state`` — e.g. a fresh ``wf.init`` —
        when no snapshot exists yet) and run the remaining generations
        with checkpointing still on. ``resume()`` of an already-complete
        run returns its final snapshot unchanged.

        Topology portability: snapshots hold mesh-free host arrays, so a
        run checkpointed on one mesh resumes on THIS workflow's mesh —
        however many devices it has (the device-loss recovery path:
        checkpoint on 8 chips, restart on 4 or 1, keep the trajectory).
        The restored leaves are eagerly re-placed by the state's own
        ``field(sharding=...)`` annotations on ``self.mesh``
        (:func:`~evox_tpu.workflows.checkpoint.restore_layouts`); pass
        ``state_sharding=`` (a pytree of shardings, e.g. from
        :func:`~evox_tpu.core.distributed.state_sharding`) to override
        the placement explicitly.

        Config guard: a snapshot written under a different algorithm /
        population size / monitor set raises
        :class:`~evox_tpu.workflows.checkpoint.CheckpointConfigError`
        instead of restoring into a program compiled for other shapes;
        ``allow_config_mismatch=True`` overrides."""
        expect_like = fallback_state
        if expect_like is None:
            try:
                # structure-only init+step: eval_shape never runs the
                # program, and snapshots are written at step boundaries —
                # one traced step materializes any lazily-sized monitor
                # buffers (LineageMonitor's width-discovered rings), so
                # the reference has the SNAPSHOT's structure. For
                # structure-stable states this equals the init structure.
                expect_like = jax.eval_shape(
                    lambda k: self.step(self.init(k)), jax.random.PRNGKey(0)
                )
            except Exception:
                try:
                    expect_like = jax.eval_shape(
                        self.init, jax.random.PRNGKey(0)
                    )
                except Exception:
                    expect_like = None  # exotic init: guard disarms
        state = checkpointer.latest(
            expect_like=expect_like,
            allow_config_mismatch=allow_config_mismatch,
        )
        if state is None:
            if fallback_state is None:
                raise FileNotFoundError(
                    f"no usable checkpoint under {checkpointer.directory}; "
                    "pass fallback_state=wf.init(key) to start fresh"
                )
            state = fallback_state
        else:
            from .checkpoint import restore_layouts

            state = restore_layouts(
                state, mesh=self.mesh, state_sharding=state_sharding
            )
        return self.run(
            state,
            max(n_steps - int(state.generation), 0),
            checkpointer=checkpointer,
        )

    def _dispatch_ask(self, state: StdWorkflowState) -> Tuple[bool, Any, Any]:
        """First-step-aware ask: ``(use_init, pop, astate)``. The single
        dispatch point shared by the step and the sample/validate previews,
        so they can never drift apart."""
        use_init = state.first_step and (
            self.algorithm.has_init_ask or self.algorithm.has_init_tell
        )
        with scope(ASK):
            if use_init:
                pop, astate = self.algorithm.init_ask(state.algo)
            else:
                pop, astate = self.algorithm.ask(state.algo)
        return use_init, pop, astate

    def _candidates(self, pop: Any) -> Any:
        """What the problem is handed: ``pop_transforms`` (the genome
        decoded, part of evaluating it) and the ``"pop"`` constraint. Under a
        mesh every leaf has to lead with the population axis; candidates that
        say they have none (``has_population_axis = False``) are refused."""
        with scope(EVALUATE):
            cand = pop
            with scope(DECODE):
                for t in self.pop_transforms:
                    cand = t(cand)
            if self.mesh is not None and not getattr(cand, "has_population_axis", True):
                # a perturbation spec (core/lowrank.py): shard_pop would lay
                # the mesh over whatever axis comes first in each leaf
                raise ValueError(
                    f"the candidates ({type(cand).__name__}) have no population axis for "
                    "the 'pop' mesh to shard: run the workflow without a mesh, or give "
                    "pop_transforms that materialise the members"
                )
            return shard_pop(cand, self.mesh)

    def _final_fitness(self, fitness: jax.Array) -> jax.Array:
        """Sign-flipped (algorithms minimize) and, when asked, quarantined:
        the fitness ``ingest_fitness`` takes."""
        with scope(TELL):
            fitness = self._flip(fitness)
            if self.quarantine_nonfinite:
                # poison (NaN/Inf) rows get the generation's worst-finite
                # value AFTER monitors saw the raw fitness (telemetry still
                # counts them) and BEFORE fit_transforms/tell (ranking
                # stays sane)
                fitness = quarantine_nonfinite(fitness)
        return fitness

    def _ask_preview(self, state: StdWorkflowState) -> Any:
        # previews see the same compute-dtype view the step itself asks on
        return self._dispatch_ask(apply_compute(state, self.dtype_policy))[1]

    def sample(self, state: StdWorkflowState) -> Any:
        """The population the algorithm would propose next, without
        advancing the workflow (the Ray workflow's ``sample`` path,
        reference distributed.py:156,384-386)."""
        return self._ask_preview(state)

    def validate(
        self,
        state: StdWorkflowState,
        problem: Optional[Problem] = None,
        key: Optional[jax.Array] = None,
        problem_state: Any = None,
    ) -> jax.Array:
        """Score the current population on ``problem`` without ``tell``.

        The mesh-native analog of the Ray workflow's ``valid`` path
        (reference distributed.py:145-156,381-383): ask, transform,
        evaluate — no algorithm-state advance, no fitness sign flip.
        ``problem`` defaults to the training problem; pass a
        validation-mode problem (e.g. ``DatasetProblem.valid()``) to score
        on held-out data. Eager utility: the validation problem's state is
        created ad hoc — seed it with ``key`` (for keyed/stochastic
        validation problems: rollout seeds, noisy benchmarks) or hand in a
        pre-built ``problem_state`` to reuse running statistics
        (e.g. observation-normalizer moments from training).

        Caveat: a training problem that consumes a host stream during
        ``evaluate`` (``DatasetProblem``, host env loops) still consumes
        one draw when validated on — pass a validation problem to keep the
        training stream untouched.
        """
        problem = problem if problem is not None else self.problem
        cand = self._candidates(self._ask_preview(state))
        if problem_state is not None and problem is self.problem:
            raise ValueError(
                "problem_state is only meaningful with an explicit "
                "validation problem"
            )
        if problem is self.problem:
            fitness, _ = self._evaluate(state.prob, cand)
        else:
            pstate = (
                problem_state
                if problem_state is not None
                else (problem.init(key) if key is not None else problem.init())
            )
            fitness, _ = problem.evaluate(pstate, cand)
        return fitness

    def _run_hooks(self, name: str, mstates: list, *args: Any) -> None:
        run_hooks(self.monitors, self._hook_table, name, mstates, *args)

    def _flip(self, fitness: jax.Array) -> jax.Array:
        if fitness.ndim == 1:
            return fitness * self.opt_direction[0]
        return fitness * self.opt_direction

    def _evaluate(
        self, pstate: Any, cand: Any, genome: Any = None
    ) -> Tuple[jax.Array, Any]:
        """``genome``: the batch ``cand`` was decoded from. Where the decode
        is the plain adapter's (``_plain_decode_adapter``) and the problem
        can read the undecoded batch (``Problem.evaluate_genome``), the
        problem is handed both."""
        with scope(EVALUATE):
            if self.external:
                return callback_evaluate(
                    self.problem, pstate, cand, self.num_objectives
                )
            evaluate, batch = self.problem.evaluate, (cand,)
            # looked up on the problem's type: a wrapper that forwards the
            # attributes it lacks to the problem inside it and changes
            # ``evaluate`` (benchmark/lib/faults.py) is asked for its own
            takes_genome = (
                genome is not None
                and self._decode_adapter is not None
                and hasattr(type(self.problem), "evaluate_genome")
                and self.problem.evaluate_genome is not None
            )
            if takes_genome:
                evaluate = functools.partial(
                    self.problem.evaluate_genome, adapter=self._decode_adapter
                )
                batch = (cand, shard_pop(genome, self.mesh))
            if self.eval_shard_map:
                return self._evaluate_shard_map(pstate, evaluate, batch)
            return evaluate(pstate, *batch)

    def _shard_fitness(self, fitness: jax.Array) -> jax.Array:
        with scope(EVALUATE):
            return shard_pop(fitness, self.mesh)

    def _evaluate_shard_map(
        self, pstate: Any, evaluate: Callable, batch: Tuple
    ) -> Tuple[jax.Array, Any]:
        """Explicit-collective evaluation: each device scores its local
        population shard, then all-gathers the fitness over ICI (the
        modernized form of the reference's per-rank dynamic_slice +
        lax.all_gather pmap scheme, std_workflow.py:160,189-200). The
        problem state is replicated in and must come back replicated —
        every shard computes the same update or none. ``batch``: what
        ``evaluate`` takes after the state, every leaf member-leading."""
        from jax.sharding import PartitionSpec as P

        n_cand = jax.tree.leaves(batch)[0].shape[0]
        n_shards = self.mesh.shape[_POP_AXIS_NAME]
        if n_cand % n_shards != 0:
            # catches algorithms whose evaluated batch differs from pop_size
            # (e.g. CSO's half-pop offspring) — the constructor check can't
            raise ValueError(
                f"eval_shard_map: the evaluated candidate batch ({n_cand}) "
                f"is not divisible by the mesh's 'pop' axis ({n_shards} "
                "shards); use the default GSPMD evaluation path for this "
                "algorithm or resize the population/mesh"
            )

        def island(ps, *shard):
            fit, new_ps = evaluate(ps, *shard)
            return all_gather(fit), new_ps

        # check_vma=False: the gathered fitness and pass-through state ARE
        # replicated after the tiled all_gather, but the static analyzer
        # cannot prove it for arbitrary problem code
        return jax.shard_map(
            island,
            mesh=self.mesh,
            in_specs=(P(),) + (P(_POP_AXIS_NAME),) * len(batch),
            out_specs=(P(), P()),
            check_vma=False,
        )(pstate, *batch)

    # ----------------------------------------------- pipelined step halves
    # _step_impl split at the evaluation boundary, for run_host_pipelined
    # (workflows/pipelined.py): the host problem's evaluate runs eagerly in
    # a worker thread between the two jitted halves. Hook order, transforms
    # and state threading are identical to _step_impl, so a pipelined run
    # produces bit-identical states to a wf.step loop.

    def pipeline_ask(self, state: StdWorkflowState):
        """(candidates, ctx): everything before evaluation, jitted."""
        return self._p_ask(state)

    def pipeline_tell(
        self, state: StdWorkflowState, ctx, fitness: jax.Array, pstate: Any
    ) -> StdWorkflowState:
        """Everything after evaluation, jitted; consumes pipeline_ask's ctx
        plus the host-computed (fitness, problem state)."""
        return self._p_tell(state, ctx, fitness, pstate)

    def _pipeline_ask_impl(self, state: StdWorkflowState):
        # storage -> compute at the step boundary: ask's math (and the
        # ctx it hands to tell) runs full-precision
        with scope(CONSTRAIN):
            state = apply_compute(state, self.dtype_policy)
        mstates = list(state.monitors)
        self._run_hooks("pre_step", mstates)
        self._run_hooks("pre_ask", mstates)
        _, pop, astate = self._dispatch_ask(state)
        self._run_hooks("post_ask", mstates, pop)
        cand = self._candidates(pop)
        self._run_hooks("pre_eval", mstates, cand)
        return cand, (astate, tuple(mstates), cand)

    def _pipeline_tell_impl(
        self, state: StdWorkflowState, ctx, fitness: jax.Array, pstate: Any
    ) -> StdWorkflowState:
        astate, mstates_t, cand = ctx
        mstates = list(mstates_t)
        fitness = self._shard_fitness(fitness)
        self._run_hooks("post_eval", mstates, cand, fitness)
        fitness = self._final_fitness(fitness)
        use_init = state.first_step and (
            self.algorithm.has_init_ask or self.algorithm.has_init_tell
        )
        # shared tell half (workflows/common.py): fit_transforms ->
        # pre_tell -> tell dispatch -> migrate cond -> constrain_state
        astate = ingest_fitness(self, astate, mstates, fitness, use_init)
        self._run_hooks("post_tell", mstates)
        new_state = state.replace(
            generation=state.generation + 1,
            algo=astate,
            prob=pstate,
            monitors=tuple(mstates),
            first_step=False,
        )
        return finish_step(self.monitors, self._hook_table, new_state)

    def _step_impl(self, state: StdWorkflowState) -> StdWorkflowState:
        # storage -> compute upcast at step entry: every reduction, mean
        # and covariance update below runs in the compute dtype; only the
        # state carried OUT of the step (constrain_state below) is narrow
        with scope(CONSTRAIN):
            state = apply_compute(state, self.dtype_policy)
        mstates = list(state.monitors)
        self._run_hooks("pre_step", mstates)
        self._run_hooks("pre_ask", mstates)

        use_init, pop, astate = self._dispatch_ask(state)
        self._run_hooks("post_ask", mstates, pop)

        cand = self._candidates(pop)
        self._run_hooks("pre_eval", mstates, cand)
        fitness, pstate = self._evaluate(state.prob, cand, pop)
        fitness = self._shard_fitness(fitness)
        self._run_hooks("post_eval", mstates, cand, fitness)

        fitness = self._final_fitness(fitness)
        # shared tell half (workflows/common.py): fit_transforms ->
        # pre_tell -> tell dispatch -> migrate cond -> constrain_state
        astate = ingest_fitness(self, astate, mstates, fitness, use_init)
        self._run_hooks("post_tell", mstates)

        new_state = state.replace(
            generation=state.generation + 1,
            algo=astate,
            prob=pstate,
            monitors=tuple(mstates),
            first_step=False,
        )
        return finish_step(self.monitors, self._hook_table, new_state)

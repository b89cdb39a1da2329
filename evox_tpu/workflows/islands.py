"""IslandWorkflow — vmapped multi-population evolution with ring migration.

The classic island model: ``n_islands`` independent populations evolve in
parallel and periodically exchange their best individuals. The reference
approximates this only by replicating whole workflows across Ray workers
(reference workflows/distributed.py:224-225 — identical seeds, no actual
migration); here it is a first-class TPU-native workflow:

- Island states are the algorithm's own pytree state ``vmap``-stacked on a
  leading island axis (the same vmap-over-init pattern as the decomposition
  containers). Works with any algorithm supporting ``migrate`` — the base
  default covers states carrying ``(population, 1-d fitness)``; others
  (distribution-based ES) need an override, since ``lax.cond`` traces the
  migration branch on every step.
- One jitted step runs every island: vmapped ask -> ONE flattened
  evaluation batch (islands x pop candidates scored together, sharded over
  the mesh like any population) -> vmapped tell.
- Every ``migrate_every`` generations each island's top ``migrate_k``
  evaluated candidates are rolled one island around the ring
  (``jnp.roll`` on the island axis — under a mesh with islands sharded
  over devices XLA lowers this to a collective permute over ICI) and
  ingested via ``algorithm.migrate``.
- ``mesh``: the island axis is sharded over the ``"pop"`` mesh axis —
  whole islands per device, migration as the only cross-device traffic;
  the EC analog of data parallelism with periodic weight exchange.

``run()`` fuses generations into one compiled ``fori_loop`` exactly like
:class:`StdWorkflow`.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.algorithm import Algorithm
from ..core.distributed import POP_AXIS as _POP_AXIS_NAME, shard_pop
from ..core.dtype_policy import DtypePolicy, apply_compute, apply_storage
from ..core.monitor import Monitor
from ..core.problem import Problem
from ..core.struct import PyTreeNode, static_field
from ..utils.common import parse_opt_direction
from .common import (
    build_hook_table,
    callback_evaluate,
    finish_step,
    fused_run,
    make_run_loop,
    run_hooks,
)


class IslandWorkflowState(PyTreeNode):
    generation: jax.Array
    algo: Any  # island-stacked algorithm state (leading axis = island)
    prob: Any
    monitors: Tuple[Any, ...] = ()
    first_step: bool = static_field(default=True)


class IslandWorkflow:
    """Evolve ``n_islands`` independent populations with ring migration.

    Args:
        algorithm: the per-island :class:`Algorithm` (every island runs the
            same hyperparameters; diversity comes from independent PRNG
            streams). Must support ``migrate`` (the base default covers
            population+fitness states; PSO ships a pbest-aware override).
        problem: shared :class:`Problem`; candidates of all islands are
            scored as one flattened batch.
        n_islands: number of islands.
        migrate_every: generations between migrations.
        migrate_k: individuals sent per island per migration.
        monitors: 8-hook monitors, as :class:`StdWorkflow`; hooks see the
            flattened ``(islands * pop, ...)`` candidate batch.
        opt_direction / pop_transforms: as :class:`StdWorkflow`; transforms
            see the flattened ``(islands * pop, ...)`` batch.
            ``fit_transforms`` is rejected — population-relative shaping
            cannot coexist with migration's raw stored fitness.
        mesh: optional ``jax.sharding.Mesh``; the island axis is sharded
            over its ``"pop"`` axis (``n_islands`` must divide evenly).
        external_problem: route evaluation through ``jax.pure_callback``
            (host problems), same contract as :class:`StdWorkflow`.
        num_objectives: fitness arity. For ``> 1`` the workflow is
            multi-objective: migration elites are chosen per island by
            non-dominated rank + crowding distance and ingested through
            the algorithm's MO ``migrate`` (GA-skeleton MOEAs merge
            migrants into their (rank, crowding) environmental
            selection — :meth:`~evox_tpu.algorithms.mo.common.
            GAMOAlgorithm.migrate`).
        jit_step: disable to debug eagerly.
    """

    def __init__(
        self,
        algorithm: Algorithm,
        problem: Problem,
        n_islands: int,
        migrate_every: int = 10,
        migrate_k: int = 1,
        monitors: Sequence[Monitor] = (),
        opt_direction: Any = "min",
        pop_transforms: Sequence[Callable] = (),
        fit_transforms: Sequence[Callable] = (),
        mesh: Optional[jax.sharding.Mesh] = None,
        external_problem: Optional[bool] = None,
        num_objectives: int = 1,
        jit_step: bool = True,
        dtype_policy: Optional[DtypePolicy] = None,
        donate_carries: bool = False,
        use_topk_kernel: Optional[bool] = None,
        topk_interpret: bool = False,
    ):
        if n_islands < 2:
            raise ValueError(f"need at least 2 islands, got {n_islands}")
        if migrate_every < 1 or migrate_k < 1:
            raise ValueError("migrate_every and migrate_k must be >= 1")
        if num_objectives < 1:
            raise ValueError(f"num_objectives must be >= 1, got {num_objectives}")
        if fit_transforms:
            # migration writes raw (sign-flipped) fitness into algorithm
            # state; shaped fitness is population-relative and the stored
            # conventions would mix — see Algorithm.migrate
            raise ValueError(
                "fit_transforms cannot be combined with island migration: "
                "migrants carry raw fitness while tell stores shaped values"
            )
        self.algorithm = algorithm
        self.problem = problem
        self.n_islands = n_islands
        self.num_objectives = num_objectives
        self.migrate_every = migrate_every
        self.migrate_k = migrate_k
        self.monitors = tuple(monitors)
        self.opt_direction = parse_opt_direction(opt_direction)
        for m in self.monitors:
            m.set_opt_direction(self.opt_direction)
        self._hook_table = build_hook_table(self.monitors)
        self.pop_transforms = tuple(pop_transforms)
        self.mesh = mesh
        self.external = (not problem.jittable) if external_problem is None else external_problem
        if self.external and mesh is not None:
            from ..core.distributed import mesh_spans_processes

            if mesh_spans_processes(mesh):
                # same refusal (and reason) as StdWorkflow: a
                # pure_callback under a PROCESS-SPANNING mesh would run
                # the host evaluate on every process against
                # unsynchronized host problem state; a process-local
                # mesh in a multi-process run stays legal
                raise ValueError(
                    "external (host) problems are single-process: under "
                    "multi-process SPMD each process would invoke the "
                    "host evaluate on its own shard against "
                    "unsynchronized host state. Use a jittable problem "
                    "for pod-mesh islands, or run islands on a "
                    "process-local mesh."
                )
        if mesh is not None:
            n_shards = mesh.shape[_POP_AXIS_NAME]
            if n_islands % n_shards != 0:
                raise ValueError(
                    f"n_islands {n_islands} is not divisible by the mesh's "
                    f"'pop' axis ({n_shards} shards)"
                )
        self.jit_step = jit_step
        self.dtype_policy = dtype_policy
        self.donate_carries = bool(donate_carries) and jit_step
        # per-island elite selection through the Pallas partial-top-k
        # kernel (kernels/topk.py); None = backend default (currently
        # off), topk_interpret is the CPU-testing escape hatch
        self.use_topk_kernel = use_topk_kernel
        self.topk_interpret = topk_interpret
        self._step = jax.jit(self._step_impl) if jit_step else self._step_impl
        self._run_loop = make_run_loop(self._step_impl, donate=self.donate_carries)

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> IslandWorkflowState:
        from ..core.distributed import ensure_global_state, mesh_spans_processes

        keys = jax.random.split(key, 2 + len(self.monitors))
        island_keys = jax.random.split(keys[1], self.n_islands)
        algo = jax.vmap(self.algorithm.init)(island_keys)
        if not mesh_spans_processes(self.mesh):
            # an eager sharding constraint cannot target a cross-process
            # layout; the pod path lays out via ensure_global_state below
            algo = self._constrain(algo)
        state = IslandWorkflowState(
            generation=jnp.zeros((), dtype=jnp.int32),
            algo=algo,
            prob=self.problem.init(keys[0]),
            monitors=tuple(m.init(k) for m, k in zip(self.monitors, keys[2:])),
            first_step=True,
        )
        # island-stacked leaves rest at storage width from the start (the
        # field annotations resolve through the extra island axis)
        state = apply_storage(state, self.dtype_policy)
        # pod meshes: assemble per-process shards of the island-stacked
        # leaves (islands shard whole-island over the pop axis, so the
        # leading-axis rule is the island rule here)
        return ensure_global_state(
            state, self.mesh,
            rules=((r"\.algo\.", jax.sharding.PartitionSpec(_POP_AXIS_NAME)),),
        )

    # ------------------------------------------------------------------ step
    def step(self, state: IslandWorkflowState) -> IslandWorkflowState:
        return self._step(state)

    def run(
        self,
        state: IslandWorkflowState,
        n_steps: int,
        checkpointer: Any = None,
        resume_from: Any = None,
    ) -> IslandWorkflowState:
        """Fused multi-generation run (see :meth:`StdWorkflow.run`).

        ``checkpointer=`` / ``resume_from=`` give island runs the same
        crash-safety law as :meth:`StdWorkflow.run` (chunk at the
        cadence, snapshot between dispatches, resume to the TOTAL
        generation target with the config-fingerprint guard armed) — and
        make :class:`~evox_tpu.workflows.supervisor.RunSupervisor`'s
        restore rung work for island runs too. The cadence chunking and
        background snapshot lane live in
        :class:`~evox_tpu.core.executor.GenerationExecutor` (one
        executor, five policies)."""
        from .checkpoint import checkpointed_run, enter_run

        state, n_steps, checkpointer = enter_run(
            state, n_steps, checkpointer, resume_from, expect_like=state
        )
        if checkpointer is not None:
            return checkpointed_run(self, state, n_steps, checkpointer)
        return fused_run(self, state, n_steps)

    def analysis_targets(self, state: IslandWorkflowState) -> dict:
        """AOT cost/memory analysis targets (see
        :meth:`StdWorkflow.analysis_targets`): the steady jitted step and
        the fused run loop (whose dynamic-trip-count body is counted once
        by XLA, i.e. per generation). External problems are skipped —
        their step embeds a host callback the analysis cannot cost, and the
        island model has no pipelined halves."""
        if not self.jit_step or self.external:
            return {}
        steady = state.replace(first_step=False) if state.first_step else state
        return {
            "step": (self._step, (steady,)),
            "run": (self._run_loop, (steady, jnp.asarray(1, jnp.int32))),
        }

    def best(self, state: IslandWorkflowState) -> Tuple[jax.Array, jax.Array]:
        """(island-stacked best fitness, global best) in the USER
        convention (same as the monitors report: a maximization run's
        best comes back positive), from states carrying pbest/fitness.

        Multi-objective: per-objective minima — the per-island ideal
        points ``(islands, m)`` and the global ideal point ``(m,)``; for
        the actual front use an :class:`~evox_tpu.monitors.EvalMonitor`
        Pareto archive or ``state.algo.fitness`` directly."""
        astate = state.algo
        for name in ("gbest_fitness", "pbest_fitness", "fitness"):
            arr = getattr(astate, name, None)
            if arr is not None:
                if self.num_objectives > 1:
                    per_island = arr.reshape(
                        self.n_islands, -1, self.num_objectives
                    ).min(axis=1)
                    sign = self.opt_direction
                    return per_island * sign, per_island.min(axis=0) * sign
                per_island = arr.reshape(self.n_islands, -1).min(axis=1)
                sign = self.opt_direction[0]
                return per_island * sign, per_island.min() * sign
        raise NotImplementedError(
            f"{type(astate).__name__} exposes no fitness field"
        )

    # ------------------------------------------------------------- internals
    def _constrain(self, algo_state: Any) -> Any:
        """Shard every island-stacked leaf over the mesh's pop axis."""
        if self.mesh is None:
            return algo_state
        from jax.sharding import NamedSharding, PartitionSpec as P

        def constrain(leaf):
            spec = P(_POP_AXIS_NAME, *([None] * (leaf.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(self.mesh, spec)
            )

        return jax.tree.map(constrain, algo_state)

    def _evaluate(self, pstate: Any, cand_flat: Any) -> Tuple[jax.Array, Any]:
        if not self.external:
            return self.problem.evaluate(pstate, cand_flat)
        return callback_evaluate(
            self.problem, pstate, cand_flat, self.num_objectives
        )

    def _migrate(self, astate: Any, cand: Any, fitness: jax.Array) -> Any:
        """Ring migration of each island's current top-k candidates.

        Elites: scalar-fitness ``argsort`` for single-objective; for
        multi-objective, non-dominated rank with crowding-distance
        tie-break per island (the NSGA-II elite criterion)."""
        k = self.migrate_k
        if k > fitness.shape[1]:
            raise ValueError(
                f"migrate_k={k} exceeds the per-island candidate batch "
                f"({fitness.shape[1]})"
            )
        if self.num_objectives > 1:
            from ..operators.selection.non_dominate import (
                crowding_distance,
                non_dominated_sort,
            )

            def island_elites(fit):  # (B, m) -> (k,) indices
                rank = non_dominated_sort(fit)
                crowd = crowding_distance(fit)
                return jnp.lexsort((-crowd, rank))[:k]

            idx = jax.vmap(island_elites)(fitness)  # (islands, k)
            elites = jax.tree.map(
                lambda c: jax.vmap(lambda row, i: row[i])(c, idx), cand
            )
            elite_fit = jax.vmap(lambda f, i: f[i])(fitness, idx)
            recv = jax.tree.map(lambda e: jnp.roll(e, 1, axis=0), elites)
            recv_fit = jnp.roll(elite_fit, 1, axis=0)
            return jax.vmap(self.algorithm.migrate)(astate, recv, recv_fit)
        from ..kernels.topk import default_use_kernel, partial_topk

        use_kernel = (
            default_use_kernel()
            if self.use_topk_kernel is None
            else self.use_topk_kernel
        )
        if use_kernel:
            # best-k per island through the blockwise partial-selection
            # kernel — same indices as the stable argsort (ascending,
            # ties by lowest index), vmapped over the island axis
            idx = jax.vmap(
                lambda f: partial_topk(
                    f, k, use_kernel=True, interpret=self.topk_interpret
                )[1]
            )(fitness)
        else:
            idx = jnp.argsort(fitness, axis=1)[:, :k]  # best-k per island
        elites = jax.tree.map(
            lambda c: jax.vmap(lambda row, i: row[i])(c, idx), cand
        )
        elite_fit = jnp.take_along_axis(fitness, idx, axis=1)
        # island i receives from island i-1; on an island-sharded mesh this
        # roll is a cross-device collective permute over ICI
        recv = jax.tree.map(lambda e: jnp.roll(e, 1, axis=0), elites)
        recv_fit = jnp.roll(elite_fit, 1, axis=0)
        return jax.vmap(self.algorithm.migrate)(astate, recv, recv_fit)

    def _step_impl(self, state: IslandWorkflowState) -> IslandWorkflowState:
        # storage -> compute at step entry (see StdWorkflow._step_impl)
        state = apply_compute(state, self.dtype_policy)
        mstates = list(state.monitors)
        run_hooks(self.monitors, self._hook_table, "pre_step", mstates)
        run_hooks(self.monitors, self._hook_table, "pre_ask", mstates)

        use_init = state.first_step and (
            self.algorithm.has_init_ask or self.algorithm.has_init_tell
        )
        ask = self.algorithm.init_ask if use_init else self.algorithm.ask
        pop, astate = jax.vmap(ask)(state.algo)  # (islands, B, ...)

        batch = jax.tree.leaves(pop)[0].shape[1]
        cand_flat = jax.tree.map(
            lambda x: x.reshape((self.n_islands * batch,) + x.shape[2:]), pop
        )
        run_hooks(self.monitors, self._hook_table, "post_ask", mstates, cand_flat)
        for t in self.pop_transforms:
            cand_flat = t(cand_flat)
        cand_flat = shard_pop(cand_flat, self.mesh)

        run_hooks(self.monitors, self._hook_table, "pre_eval", mstates, cand_flat)
        raw_fitness, pstate = self._evaluate(state.prob, cand_flat)
        # monitors see the flattened (islands * B) batch in the user's
        # fitness convention, exactly like StdWorkflow
        run_hooks(
            self.monitors, self._hook_table, "post_eval", mstates, cand_flat, raw_fitness
        )
        # internal minimization convention, shared by tell and migration
        # (the constructor rejects fit_transforms: shaped fitness is
        # population-relative and would poison the migrants' stored values)
        if self.num_objectives > 1:
            fitness = (raw_fitness * self.opt_direction).reshape(
                self.n_islands, batch, self.num_objectives
            )
        else:
            fitness = (raw_fitness * self.opt_direction[0]).reshape(
                self.n_islands, batch
            )

        run_hooks(
            self.monitors, self._hook_table, "pre_tell", mstates,
            fitness.reshape((self.n_islands * batch,) + fitness.shape[2:]),
        )
        tell = self.algorithm.init_tell if use_init else self.algorithm.tell
        astate = jax.vmap(tell)(astate, fitness)
        run_hooks(self.monitors, self._hook_table, "post_tell", mstates)

        gen = state.generation + 1
        astate = jax.lax.cond(
            gen % self.migrate_every == 0,
            lambda a: self._migrate(a, pop, fitness),
            lambda a: a,
            astate,
        )
        # downcast to storage width BEFORE the shard constraint so the
        # loop carry streams at half width on every device
        astate = self._constrain(apply_storage(astate, self.dtype_policy))
        new_state = state.replace(
            generation=gen,
            algo=astate,
            prob=pstate,
            monitors=tuple(mstates),
            first_step=False,
        )
        return finish_step(self.monitors, self._hook_table, new_state)

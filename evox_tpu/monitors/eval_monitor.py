"""EvalMonitor — elite / Pareto-front tracking (reference:
src/evox/monitors/eval_monitor.py).

TPU-first redesign: instead of shipping every batch to the host through
``io_callback`` and keeping Python-side state (reference eval_monitor.py:
69-96), the elite top-k buffer and the fixed-capacity Pareto archive are
device arrays inside the monitor's pytree state, updated with pure jittable
math — zero host sync in the hot loop. Unbounded full history (opt-in) still
streams host-side via ``io_callback``, pinned to one device like the
reference.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import io_callback

from ..core.monitor import Monitor
from jax.sharding import PartitionSpec as P
from .common import host0_sharding, ring_slots, ring_write
from ..core.struct import PyTreeNode, field
from ..operators.selection.non_dominate import (
    crowding_distance,
    non_dominated_sort,
)


class EvalMonitorState(PyTreeNode):
    # layout annotations are all P(): every buffer here is capacity- or
    # k-leading (elite/archive/ring), never population-leading
    topk_fitness: Optional[jax.Array] = field(sharding=P())  # (k,) or (cap, m) raw user-direction
    topk_solution: Optional[Any] = field(sharding=P())
    pf_count: Optional[jax.Array] = field(sharding=P())
    # device-side generation-history ring buffer (history_capacity > 0):
    hist_fit: Optional[jax.Array] = field(sharding=P(), default=None)  # (K, width[, m]) inf-padded
    hist_sol: Optional[Any] = field(sharding=P(), default=None)  # (K, width, ...) when history_solutions
    hist_len: Optional[jax.Array] = field(sharding=P(), default=None)  # (K,) int32 valid rows per slot
    hist_count: Optional[jax.Array] = field(sharding=P(), default=None)  # () int32 total generations seen


class EvalMonitor(Monitor):
    """Tracks the best-so-far individuals seen at evaluation time.

    Single-objective: a ``topk`` elite buffer. Multi-objective: a running
    Pareto archive of capacity ``pf_capacity`` (set ``multi_obj=True``).

    Generation history comes in two forms:

    - ``full_fit_history`` / ``full_sol_history``: unbounded, streamed to
      HOST memory via ``io_callback`` (the reference's design,
      eval_monitor.py:98-162). Needs a runtime that can call back into
      Python (the TPU v5e runs it: chip_smoke.py's host-callback phase).
    - ``history_capacity=K``: a fixed-capacity on-DEVICE ring buffer of
      the last ``K`` generations' fitness (and solutions with
      ``history_solutions=True``) inside the monitor's pytree state —
      zero host sync, works on every backend including callback-less
      ones. When more than ``K`` generations run, the oldest slots are
      overwritten (ring semantics); per-slot batch widths are tracked so
      variable evaluation sizes (e.g. CSO's full-then-half pattern) read
      back exactly. Rows wider than the first generation's batch raise
      at trace time (the buffer is sized by the first generation).
    """

    def __init__(
        self,
        topk: int = 1,
        multi_obj: bool = False,
        pf_capacity: int = 1024,
        full_fit_history: bool = False,
        full_sol_history: bool = False,
        history_capacity: int = 0,
        history_solutions: bool = False,
    ):
        self.topk = topk
        self.multi_obj = multi_obj
        self.pf_capacity = pf_capacity
        self.full_fit_history = full_fit_history
        self.full_sol_history = full_sol_history
        self.history_capacity = history_capacity
        self.history_solutions = history_solutions
        if history_solutions and not history_capacity:
            raise ValueError("history_solutions requires history_capacity > 0")
        self.fitness_history: list = []
        self.solution_history: list = []
        self.opt_direction = jnp.ones((1,), dtype=jnp.float32)
        # full histories stream through a host callback inside the step
        # (the convention flag VectorizedWorkflow fleets reject — a
        # callback cannot run under vmap); the on-device ring
        # (history_capacity=K) stays fleet-safe
        self.uses_host_callbacks = bool(full_fit_history or full_sol_history)

    def hooks(self):
        return ("post_eval",)

    def init(self, key: Optional[jax.Array] = None) -> EvalMonitorState:
        # lazy: buffers materialize on the first post_eval (shapes unknown here);
        # the workflow's first-generation retrace absorbs the structure change.
        return EvalMonitorState(topk_fitness=None, topk_solution=None, pf_count=None)

    # ------------------------------------------------------------------ hook
    def post_eval(self, mstate: EvalMonitorState, cand: Any, fitness: jax.Array) -> EvalMonitorState:
        if self.full_fit_history or self.full_sol_history:
            self._record_history(cand, fitness)
        hist = {}
        if self.history_capacity:
            hist = self._update_device_history(mstate, cand, fitness)
        if fitness.ndim == 1 and not self.multi_obj:
            return self._update_so(mstate, cand, fitness).replace(**hist)
        return self._update_mo(mstate, cand, fitness).replace(**hist)

    # ------------------------------------------- device-side history ring
    def _update_device_history(self, mstate, cand, fitness) -> dict:
        K = self.history_capacity
        if mstate.hist_fit is None:
            width = fitness.shape[0]
            hist_fit = jnp.full((K, width) + fitness.shape[1:], jnp.inf, fitness.dtype)
            hist_sol = (
                jax.tree.map(
                    lambda x: jnp.zeros((K, width) + x.shape[1:], x.dtype), cand
                )
                if self.history_solutions
                else None
            )
            hist_len = jnp.zeros((K,), dtype=jnp.int32)
            count = jnp.zeros((), dtype=jnp.int32)
        else:
            hist_fit, hist_sol = mstate.hist_fit, mstate.hist_sol
            hist_len, count = mstate.hist_len, mstate.hist_count
            width = hist_fit.shape[1]
        n = fitness.shape[0]
        if n > width:
            raise ValueError(
                f"history ring buffer was sized by the first generation "
                f"(batch {width}); cannot record a larger batch ({n}). "
                "Evaluate the widest batch first or disable history_capacity."
            )
        row = jnp.pad(
            fitness,
            ((0, width - n),) + ((0, 0),) * (fitness.ndim - 1),
            constant_values=jnp.inf,
        )
        # shared ring discipline (monitors/common.py): slot = count % K
        hist_fit = ring_write(hist_fit, row, count)
        if hist_sol is not None:
            hist_sol = jax.tree.map(
                lambda buf, c: ring_write(
                    buf,
                    jnp.pad(c, ((0, width - n),) + ((0, 0),) * (c.ndim - 1)),
                    count,
                ),
                hist_sol,
                cand,
            )
        hist_len = ring_write(hist_len, n, count)
        return dict(
            hist_fit=hist_fit,
            hist_sol=hist_sol,
            hist_len=hist_len,
            hist_count=count + 1,
        )

    def _record_history(self, cand: Any, fitness: jax.Array) -> None:
        def append(fit, sol):
            if self.full_fit_history:
                self.fitness_history.append(fit)
            if self.full_sol_history:
                self.solution_history.append(sol)
            return jnp.zeros((), dtype=jnp.int32)

        # ordered=True threads a token whose replicated sharding the SPMD
        # partitioner rejects on multi-process meshes ("side-effect HLO
        # cannot have a replicated sharding"); drop the ordering token
        # there — the callback still fires exactly once per generation on
        # process 0 (asserted in tests/test_multiprocess_distributed.py),
        # but cross-generation append order follows dispatch order rather
        # than a token chain.
        io_callback(
            append,
            jax.ShapeDtypeStruct((), jnp.int32),
            fitness,
            cand,
            sharding=host0_sharding(),
            ordered=jax.process_count() == 1,
        )

    def _update_so(self, mstate, cand, fitness):
        key_fit = fitness * self.opt_direction[0]  # minimize internally
        if mstate.topk_fitness is None:
            merged_key, merged_fit, merged_sol = key_fit, fitness, cand
        else:
            prev_key = mstate.topk_fitness * self.opt_direction[0]
            merged_key = jnp.concatenate([prev_key, key_fit])
            merged_fit = jnp.concatenate([mstate.topk_fitness, fitness])
            merged_sol = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), mstate.topk_solution, cand
            )
        _, idx = jax.lax.top_k(-merged_key, self.topk)
        return EvalMonitorState(
            topk_fitness=merged_fit[idx],
            topk_solution=jax.tree.map(lambda x: x[idx], merged_sol),
            pf_count=None,
        )

    def _update_mo(self, mstate, cand, fitness):
        key_fit = fitness * self.opt_direction
        if mstate.topk_fitness is None:
            prev_fit = jnp.full((self.pf_capacity,) + fitness.shape[1:], jnp.inf, fitness.dtype)
            prev_sol = jax.tree.map(
                lambda x: jnp.zeros((self.pf_capacity,) + x.shape[1:], x.dtype), cand
            )
        else:
            prev_fit = mstate.topk_fitness * self.opt_direction
            prev_sol = mstate.topk_solution
        merged_fit = jnp.concatenate([prev_fit, key_fit])
        merged_sol = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), prev_sol, cand)
        # fixed-capacity archive refresh: rank once on the merged set, keep
        # the best (rank, -crowding) rows, then inf-pad everything that is
        # not a FINITE rank-0 member — environmental selection tops up with
        # dominated rows whenever the true front is smaller than the
        # capacity, and those must not masquerade as front members. One
        # liveness criterion (finite & rank 0) drives the padding, the
        # count, and get_pf_mask alike.
        rank = non_dominated_sort(merged_fit, until=self.pf_capacity)
        worst = jnp.sort(rank)[self.pf_capacity - 1]
        crowd = crowding_distance(merged_fit, mask=rank == worst)
        order = jnp.lexsort((-crowd, rank))[: self.pf_capacity]
        sel_fit = merged_fit[order]
        live = (rank[order] == 0) & jnp.all(jnp.isfinite(sel_fit), axis=-1)
        # stable re-sort so live rows occupy the leading slots (a finite
        # rank-0 block can be interrupted by an inf-coordinate row)
        reorder = jnp.argsort(~live, stable=True)
        sel_fit = jnp.where(live[reorder][:, None], sel_fit[reorder], jnp.inf)
        new_sol = jax.tree.map(
            lambda x: x[order][reorder], merged_sol
        )
        return EvalMonitorState(
            topk_fitness=sel_fit * self.opt_direction,  # store user direction
            topk_solution=new_sol,
            pf_count=jnp.sum(live.astype(jnp.int32)),
        )

    # --------------------------------------------------------------- getters
    def get_best_fitness(self, mstate: EvalMonitorState) -> jax.Array:
        return mstate.topk_fitness[0]

    def get_topk_fitness(self, mstate: EvalMonitorState) -> jax.Array:
        return mstate.topk_fitness

    def get_best_solution(self, mstate: EvalMonitorState):
        return jax.tree.map(lambda x: x[0], mstate.topk_solution)

    def get_topk_solutions(self, mstate: EvalMonitorState):
        return mstate.topk_solution

    def get_pf_mask(self, mstate: EvalMonitorState) -> jax.Array:
        """(pf_capacity,) bool — which archive rows hold real PF members.
        Jit-safe companion to the padded getters below."""
        return jnp.all(jnp.isfinite(mstate.topk_fitness), axis=-1)

    def get_pf_fitness(self, mstate: EvalMonitorState) -> jax.Array:
        """Pareto-archive fitness. Eagerly: sliced to the live rows. Under
        jit (``mstate`` is traced): the full fixed-capacity buffer, with
        dead rows inf-padded — combine with :meth:`get_pf_mask`."""
        if isinstance(mstate.pf_count, jax.core.Tracer):
            return mstate.topk_fitness
        n = int(mstate.pf_count)
        return mstate.topk_fitness[:n]

    def get_pf_solutions(self, mstate: EvalMonitorState):
        """Pareto-archive solutions; same eager-slice / traced-padded
        contract as :meth:`get_pf_fitness`."""
        if isinstance(mstate.pf_count, jax.core.Tracer):
            return mstate.topk_solution
        n = int(mstate.pf_count)
        return jax.tree.map(lambda x: x[:n], mstate.topk_solution)

    def get_fitness_history(self) -> list:
        jax.effects_barrier()
        return self.fitness_history

    def get_solution_history(self) -> list:
        jax.effects_barrier()
        return self.solution_history

    # ----------------------------------------- device-history ring getters
    def _ring_slots(self, mstate: EvalMonitorState):
        return ring_slots(mstate.hist_count, self.history_capacity)

    def get_device_fitness_history(self, mstate: EvalMonitorState) -> list:
        """The last ``min(count, history_capacity)`` generations' fitness,
        chronological, each sliced to its true batch width. Eager (host)
        utility; for jit-side access read ``mstate.hist_fit`` /
        ``hist_len`` / ``hist_count`` directly (ring layout, inf-padded)."""
        if mstate.hist_fit is None:
            return []
        return [
            mstate.hist_fit[s][: int(mstate.hist_len[s])]
            for s in self._ring_slots(mstate)
        ]

    def get_device_solution_history(self, mstate: EvalMonitorState) -> list:
        if mstate.hist_sol is None:
            return []
        return [
            jax.tree.map(lambda x: x[s][: int(mstate.hist_len[s])], mstate.hist_sol)
            for s in self._ring_slots(mstate)
        ]

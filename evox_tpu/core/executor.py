"""GenerationExecutor — the one async generation loop behind every driver.

Before this module, five drivers each hand-rolled their own generation
loop: ``run_host_pipelined`` (double-buffered host evals),
``StdWorkflow.run`` (host-problem path), ``IslandWorkflow.run``,
``RunSupervisor``'s chunked ladder, and the ``RunQueue``'s serving
chunks. Each serialized some mix of device dispatch, host evaluation,
checkpoint fsync, and telemetry fetch. This module owns that loop once
(the behavioral analog of the reference Ray workflow's async dispatch
queue, reference workflows/distributed.py:361-369 — see PARITY row 54),
and the five drivers become thin policies over it:

- **Double-buffered dispatch** (``run_host``): the device half of
  generation ``k+1`` (``pipeline_tell`` + ``pipeline_ask``, async
  dispatch — PR 4 showed durations don't scale without
  ``block_dispatch``, so the dispatch returns at once) overlaps
  the host evaluation of generation ``k`` on a worker thread, and both
  overlap the user's ``on_generation`` host work — the
  ``run_host_pipelined`` structure, now owned here.
- **Background I/O lanes**: checkpoint pickles+fsyncs, ``on_generation``
  hooks, and telemetry-ring fetches run on dedicated single-thread
  lanes with a bounded in-flight queue (backpressure, never unbounded
  growth); errors are surfaced at the next drain point, and the
  checkpoint lane is always drained before anything reads
  ``checkpointer.latest()`` (the supervisor's restore rung) and before
  the run returns.
- **Bounded-staleness tells** (``max_staleness=K``, opt-in): the loop
  may keep up to ``K+1`` evaluations in flight and admit a tell whose
  candidates were asked up to ``K`` tells ago — stale-gradient ES
  (Fiber; "Distributed ES with Multi-Level Learning", PAPERS.md). Each
  tell keeps its OWN matched (ask-artifacts, fitness) pair: the
  executor detects the ask's artifact leaves (key, noise, candidate
  buffers — the leaves a probe ask changes) once, and grafts them onto
  the newest told state, so updates accumulate while the sampling
  distribution lags by at most ``K`` tells. ``K=0`` (default) is
  BIT-identical to the legacy loops — the repo's run==step laws stay
  the referee; ``K>0`` is a throughput/quality trade documented in
  GUIDE.md §6 and gated by a convergence test, not an equivalence law.
- **Supervision as hooks**: when a supervisor (duck-typed:
  ``call``/``min_eval_chunk``/``checkpointer``) is attached, every
  chunk dispatch runs under its deadline watchdog + retry ladder, the
  restore rung replays from the newest (drained) snapshot, and the
  OOM/413 degrade rung halves the host eval chunk — the
  ``RunSupervisor`` keeps the policy (classification, backoff, ladder),
  the executor owns the loop.

Observability: counters (generations, stale tells, background tasks,
queue high-water) and overlap spans (device dispatch vs host eval vs
background I/O vs wall) land in ``run_report()["executor"]`` (schema
v4, validated by tools/check_report.py) and as an "generation executor"
process in ``write_chrome_trace`` (span slices + queue-depth/stale-lag
counter tracks). Entirely host-side — no host callbacks
(pinned by tests/test_no_host_callbacks.py).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attest import IntegrityError
from .instrument import CHECKPOINT_SAVE, HOST_EVAL, span

# Layering note: this module lives in core/ (it is workflow-shape-agnostic
# infrastructure: any object with pipeline_ask/pipeline_tell or run(state,
# n) drives), but the checkpoint/resume helpers it consults belong to the
# workflows package (ISSUE 9 satellite: enter_run/chunk_to_boundary are
# hoisted into workflows/checkpoint.py and "called from the executor").
# Those imports are deliberately deferred to call time — workflows imports
# core at module level, so eager imports here would be circular; core
# stays importable without workflows, and only executor RUNS need it.

__all__ = ["GenerationExecutor"]

# ask-side monitor hooks: in stale mode an admitted tell's monitor chain
# comes from the newest told state (ctx branches fork), so monitors whose
# state advances in these hooks would silently lose generations
_ASK_SIDE_HOOKS = ("pre_step", "pre_ask", "post_ask", "pre_eval")

_MAX_TRACE_SPANS = 20_000
_MAX_COUNTER_SAMPLES = 20_000


class _IoLane:
    """One ordered background I/O lane: a single worker thread (so saves
    land in submission order) plus a bounded in-flight deque. ``submit``
    applies backpressure by joining the oldest task when the lane is
    full — the queue can never grow without bound behind a slow disk.
    Errors are re-raised at the next ``submit``/``drain`` (a background
    fsync failure must fail the run, not vanish)."""

    def __init__(self, name: str, max_inflight: int):
        self.name = name
        self.max_inflight = max(1, int(max_inflight))
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"executor-{name}"
        )
        self._pending: deque = deque()
        self.submitted = 0
        self.busy_s = 0.0
        self.high_water = 0

    def submit(self, fn: Callable[[], Any]) -> Future:
        while len(self._pending) >= self.max_inflight:
            self._pending.popleft().result()  # backpressure + error surface

        def timed():
            t0 = time.perf_counter()
            try:
                return fn()
            finally:
                self.busy_s += time.perf_counter() - t0

        fut = self._pool.submit(timed)
        self._pending.append(fut)
        self.submitted += 1
        self.high_water = max(self.high_water, len(self._pending))
        return fut

    def depth(self) -> int:
        return sum(1 for f in self._pending if not f.done())

    def drain(self) -> None:
        """Join every pending task, re-raising the first error."""
        while self._pending:
            self._pending.popleft().result()

    def close(self) -> None:
        self._pool.shutdown(wait=False)


class _InflightEval:
    """One generation's in-flight evaluation: its loop index, the ask's
    ctx (astate branch, monitor branch, candidates), the future of the
    host evaluation, and ``base_told`` — how many tells the base state
    had absorbed when this ask sampled from it. A tell admitted after
    further tells landed (``told > base_told``) is STALE: its candidates
    came from a distribution that many updates behind."""

    __slots__ = ("g", "ctx", "fut", "base_told")

    def __init__(self, g: int, ctx: Any, fut: Future, base_told: int):
        self.g = g
        self.ctx = ctx
        self.fut = fut
        self.base_told = base_told


def _is_key_path(path) -> bool:
    name = jax.tree_util.keystr(path)
    return name.endswith(".key") or name.endswith("['key']")


def _ask_artifact_mask(pre_algo: Any, post_algo: Any) -> List[bool]:
    """Which algorithm-state leaves does ``ask`` write? Compared leaf-wise
    between the pre-ask and post-ask state of ONE probe generation:
    unequal leaves (plus every ``key`` leaf, which must always follow the
    ask chain) are ask-artifacts — the leaves a stale tell grafts from
    its own ctx onto the newest told state so the (noise, fitness)
    pairing the algorithm's ``tell`` math assumes stays matched.

    All per-leaf equality scalars are fetched in ONE ``device_get``:
    every blocking device-to-host round trip stalls the dispatch queue,
    and a per-leaf fetch would stall the first steady ask in the very
    module built to hide that latency."""
    pre = jax.tree_util.tree_flatten_with_path(pre_algo)[0]
    post = jax.tree.leaves(post_algo)
    forced: List[Optional[bool]] = []
    comparisons = []
    for (path, a), b in zip(pre, post):
        if _is_key_path(path):
            forced.append(True)
            continue
        try:
            comparisons.append(jnp.array_equal(a, b, equal_nan=True))
            forced.append(None)
        except TypeError:
            forced.append(True)  # exotic leaf (no ==): treat as artifact
    same_flags = iter(jax.device_get(comparisons) if comparisons else [])
    return [
        f if f is not None else not bool(next(same_flags)) for f in forced
    ]


def _merge_artifacts(base_algo: Any, ask_algo: Any, mask: List[bool]) -> Any:
    base_leaves, treedef = jax.tree.flatten(base_algo)
    ask_leaves = jax.tree.leaves(ask_algo)
    return jax.tree.unflatten(
        treedef,
        [a if m else b for b, a, m in zip(base_leaves, ask_leaves, mask)],
    )


def _rekey(algo: Any, entry_key: Any, g: int) -> Any:
    """A deterministic fresh PRNG stream for an ask issued while earlier
    tells are still pending (two asks from the same told state would
    otherwise replay the same key split)."""
    return algo.replace(key=jax.random.fold_in(entry_key, g))


class GenerationExecutor:
    """The unified async generation loop (module docstring for the full
    design). One instance may drive many runs; counters and spans
    accumulate and ``report()`` is the ``run_report()["executor"]``
    section.

    Args:
        max_staleness: default tell-staleness bound ``K`` for
            :meth:`run_host` (overridable per run). ``0`` (default) is
            bit-identical to the legacy drive loops. ``K>0`` keeps up to
            ``K+1`` host evaluations in flight and admits each tell at a
            lag of at most ``K`` tells (stale-gradient semantics;
            requires an algorithm state with a ``key`` field, no
            ``dtype_policy``, no ``donate_carries``, and monitors
            without ask-side hooks — TelemetryMonitor qualifies; the
            host ``evaluate`` must tolerate concurrent calls).
        io_inflight: bound on in-flight background tasks PER LANE
            (checkpoint / hook / fetch); submission past it blocks on
            the oldest task (backpressure).
        supervisor: default supervisor hook (a
            :class:`~evox_tpu.workflows.supervisor.RunSupervisor` or
            anything duck-typing its ``call``/``checkpointer``/
            ``min_eval_chunk``); overridable per run.
        fetch_monitors_every: when set, every N admitted generations the
            executor background-fetches ``state.monitors`` (the small
            telemetry rings — never the population) and keeps the newest
            host copy in ``last_monitor_fetch`` — live telemetry that
            never blocks the loop.
        clock: monotonic seconds source (``time.perf_counter`` — the
            same clock DispatchRecorder and RunSupervisor stamp with, so
            trace tracks align).
    """

    def __init__(
        self,
        max_staleness: int = 0,
        io_inflight: int = 4,
        supervisor: Any = None,
        pod_supervisor: Any = None,
        fetch_monitors_every: Optional[int] = None,
        clock: Callable[[], float] = time.perf_counter,
        metrics: Any = None,
    ):
        if max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, got {max_staleness}")
        if io_inflight < 1:
            raise ValueError(f"io_inflight must be >= 1, got {io_inflight}")
        if fetch_monitors_every is not None and fetch_monitors_every < 1:
            raise ValueError("fetch_monitors_every must be >= 1")
        self.max_staleness = int(max_staleness)
        self.io_inflight = int(io_inflight)
        self.supervisor = supervisor
        # pod fault domain (core/pod_supervisor.py, ISSUE 14): when
        # attached, run_fused puts every SPMD-lockstep collective point
        # (chunk dispatch, pod checkpoint gather) under the pod deadline
        # + classification, rendezvouses at chunk boundaries, and honors
        # the coordinated SIGTERM drain. None (default) changes nothing.
        self.pod_supervisor = pod_supervisor
        self.fetch_monitors_every = fetch_monitors_every
        # serving-plane flight recorder (PR 16, workflows/flightrec.py):
        # when attached (constructor or `executor.metrics = rec` — the
        # RunQueue auto-threads its recorder), _sample mirrors the
        # counter tracks as gauges and _timed_dispatch observes dispatch
        # latency into a histogram. None (default) changes nothing.
        self.metrics = metrics
        self._clock = clock
        self._created = clock()
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "runs": 0,
            "chunks": 0,
            "supervised_chunks": 0,
            "generations": 0,
            "asks": 0,
            "tells": 0,
            "stale_tells": 0,
            "max_lag": 0,
            "bg_checkpoint": 0,
            "bg_hook": 0,
            "bg_fetch": 0,
            # surrogate refits dispatched between tells (ISSUE 15,
            # workflows/surrogate.py refit_due/dispatch_refit hooks)
            "bg_refit": 0,
            # compute-integrity rung (ISSUE 20, core/attest.py): extra
            # dispatches spent re-running chunks for verification, chunks
            # whose digests agreed, digest mismatches detected, and
            # mismatches healed by the 2-of-3 vote. Coherence law:
            # verify_dispatches == verified_chunks + 2 * mismatches
            # (one re-dispatch per rung, one more per mismatch).
            "verify_dispatches": 0,
            "verified_chunks": 0,
            "integrity_mismatches": 0,
            "integrity_healed": 0,
        }
        # rung configuration/outcome state the counters can't carry:
        # newest run's cadence (None = rung off) and no-majority aborts
        self.integrity: Dict[str, Any] = {"verify_every": None, "aborts": 0}
        self.queue_stats: Dict[str, int] = {
            "io_inflight_limit": self.io_inflight,
            "io_inflight_max": 0,
            "stale_window_max": 0,
        }
        # overlap accounting (seconds): device dispatch time (host-side
        # cost of the jitted calls — async dispatch, the PR-1 semantics),
        # host evaluation busy time (inside the eval workers; may exceed
        # wall when K>0 runs evals concurrently), background-I/O busy
        # time, and the wall window covered by executor runs
        self.overlap: Dict[str, float] = {
            "device_dispatch_s": 0.0,
            "host_eval_s": 0.0,
            "io_s": 0.0,
            "wall_s": 0.0,
        }
        self.last_monitor_fetch: Optional[Tuple[int, Any]] = None
        # largest per-run max_staleness override actually driven — the
        # report's bound must cover every run's admitted lag, not just
        # the constructor default
        self._max_k_seen = 0
        self._trace_spans: List[dict] = []
        self._dropped_spans = 0
        self._counter_samples: Dict[str, List[Tuple[float, float]]] = {
            "executor/io_queue_depth": [],
            "executor/stale_lag": [],
        }

    # ------------------------------------------------------------- recording
    def _span(self, track: str, name: str, t0: float, dt: float, **args) -> None:
        with self._lock:
            if len(self._trace_spans) >= _MAX_TRACE_SPANS:
                self._dropped_spans += 1
                return
            span = {"track": track, "name": name, "t_abs": t0, "dur": dt}
            if args:
                span["args"] = args
            self._trace_spans.append(span)

    def _sample(self, track: str, value: float) -> None:
        with self._lock:
            samples = self._counter_samples[track]
            if len(samples) < _MAX_COUNTER_SAMPLES:
                samples.append((self._clock(), float(value)))
        if self.metrics is not None:
            # "executor/io_queue_depth" -> "executor.io_queue_depth":
            # metric names are dotted, trace tracks slash-separated
            self.metrics.set(track.replace("/", "."), float(value))

    def _timed_dispatch(self, name: str, fn: Callable[[], Any]) -> Any:
        t0 = self._clock()
        try:
            return fn()
        finally:
            dt = self._clock() - t0
            self.overlap["device_dispatch_s"] += dt
            self._span("device", name, t0, dt)
            if self.metrics is not None:
                self.metrics.count("executor.dispatches")
                self.metrics.observe("executor.dispatch_ms", dt * 1e3)

    # ---------------------------------------------------------------- report
    def report(self) -> dict:
        """The ``executor`` section of ``run_report()`` — strict JSON."""
        device = self.overlap["device_dispatch_s"]
        host = self.overlap["host_eval_s"]
        wall = self.overlap["wall_s"]
        bound = max(device, host)
        out = {
            # the EFFECTIVE bound: per-run max_staleness= overrides widen it
            "max_staleness": max(self.max_staleness, self._max_k_seen),
            "counters": dict(self.counters),
            "queue": dict(self.queue_stats),
            "overlap": {
                "device_dispatch_s": round(device, 6),
                "host_eval_s": round(host, 6),
                "io_s": round(self.overlap["io_s"], 6),
                "wall_s": round(wall, 6),
                # wall / max(device, host): 1.0 = perfect overlap, 2.0 =
                # fully serialized equal halves (the pre-executor shape)
                "overlap_efficiency": (
                    round(wall / bound, 4) if bound > 1e-9 and wall > 0 else None
                ),
            },
        }
        if self._dropped_spans:
            out["dropped_spans"] = self._dropped_spans
        return out

    def trace_spans(self) -> List[dict]:
        """Recorded spans (absolute ``perf_counter`` timestamps) for
        :func:`~evox_tpu.core.instrument.write_chrome_trace`'s
        "generation executor" process."""
        with self._lock:
            return list(self._trace_spans)

    def counter_samples(self) -> Dict[str, List[Tuple[float, float]]]:
        """(t_abs, value) samples per counter track (queue depth, stale
        lag) for the trace exporter."""
        with self._lock:
            return {k: list(v) for k, v in self._counter_samples.items()}

    # ------------------------------------------------------------ fused runs
    def run_fused(
        self,
        wf: Any,
        state: Any,
        n_steps: int,
        checkpointer: Any = None,
        chunk: Optional[int] = None,
        resume_from: Any = None,
        supervisor: Any = None,
        pod_supervisor: Any = None,
        entry: str = "run",
        attest: Any = None,
        verify_every: Optional[int] = None,
    ) -> Any:
        """Drive ``wf.run(state, n)``-shaped fused dispatches in cadence
        chunks: the loop previously hand-rolled by ``checkpointed_run``,
        ``RunSupervisor.run``, and the ``RunQueue``. Chunking a
        ``fori_loop`` does not change its math, so the final state is
        identical to one straight dispatch; snapshots run on the
        background checkpoint lane (bounded, drained before return and
        before any restore), and with a supervisor every chunk dispatch
        runs under its deadline + classified-retry ladder with the
        restore rung replaying from the newest drained snapshot.
        ``n_steps`` counts REMAINING generations (``resume_from``
        reinterprets it as the TOTAL target, exactly ``wf.run``'s law).

        ``pod_supervisor`` (a :class:`~evox_tpu.core.pod_supervisor.
        PodSupervisor`, ISSUE 14): every SPMD-lockstep collective point
        — the chunk dispatch and, on pod meshes, the synchronous
        checkpoint gather — runs under the pod's disposable-watchdog
        deadline with census-refined failure classification, each chunk
        ends in the classified :meth:`chunk_boundary` rendezvous, and a
        coordinated drain (SIGTERM preemption) finishes the in-flight
        chunk, fsyncs a FINAL barrier checkpoint even off-cadence,
        drains the background lane, and returns early — the caller then
        exits 0. Pod faults surface as :class:`~evox_tpu.core.
        pod_supervisor.PodFailureError` (fatal to the in-process ladder
        by design; re-formation happens in the respawn driver). ``None``
        (default) leaves this loop bit-identical to the pre-pod tree.

        ``verify_every=K`` (with ``attest``, a :class:`~evox_tpu.core.
        attest.StateAttestor`; a default one is built if omitted) is the
        compute-integrity rung (ISSUE 20): every K-th completed chunk is
        re-dispatched from its immutable pre-chunk entry state (the PR-6
        snapshot-before-donate guarantee makes the entry state free) and
        the two results' layout-invariant digests compared. On mismatch a
        third dispatch votes 2-of-3: the majority state proceeds, the
        dissent is journaled against the pod (quarantine via the PR-14
        re-formation path, not a whole-run abort); no majority raises
        :class:`~evox_tpu.core.attest.IntegrityError` (classified
        ``integrity`` — never retried). ``attest=None`` with
        ``verify_every=None`` (default) is the established no-op
        discipline: zero extra dispatches, bit-identical to pre-PR.
        """
        from ..workflows.checkpoint import chunk_to_boundary, enter_run

        supervisor = self.supervisor if supervisor is None else supervisor
        pod = (
            self.pod_supervisor if pod_supervisor is None else pod_supervisor
        )
        wf._run_executor = self
        if supervisor is not None:
            wf._run_supervisor = supervisor
        if pod is not None:
            wf._pod_supervisor = pod
        state, n_steps, ckpt = enter_run(
            state, n_steps, checkpointer, resume_from, expect_like=state
        )
        if ckpt is None and supervisor is not None:
            ckpt = getattr(supervisor, "checkpointer", None)
        self.counters["runs"] += 1
        if verify_every is not None:
            if verify_every < 1:
                raise ValueError(f"verify_every must be >= 1, got {verify_every}")
            if attest is None:
                from .attest import StateAttestor

                attest = StateAttestor()
            self.integrity["verify_every"] = int(verify_every)
        total = n_steps + int(state.generation)
        chunk_i = 0  # completed chunks THIS run — the verify-rung cadence
        budget = {"used": 0}  # restores bounded per RUN, not per chunk
        restore = self._restore_thunk(supervisor, ckpt, wf, state)
        lane = _IoLane("checkpoint", self.io_inflight)
        # registered so the restore rung's _drain_checkpoint_lanes sees
        # THIS run's in-flight snapshots too (not only pipelined segments')
        lanes = getattr(self, "_active_ckpt_lanes", None)
        if lanes is None:
            lanes = self._active_ckpt_lanes = []
        lanes.append(lane)
        t_run0 = self._clock()
        try:
            while int(state.generation) < total:
                remaining = total - int(state.generation)
                step = min(remaining, chunk_to_boundary(state, ckpt, chunk))
                attempted = state
                chunk_fn = lambda: wf.run(attempted, step)  # noqa: E731
                if pod is not None:
                    # innermost: the pod watchdog wraps the raw lockstep
                    # dispatch so a hung collective is classified before
                    # any in-process ladder sees it
                    chunk_fn = lambda: pod.supervised(  # noqa: E731
                        lambda: wf.run(attempted, step), entry=entry
                    )
                dispatch = lambda: self._timed_dispatch(  # noqa: E731
                    entry, chunk_fn
                )
                if supervisor is not None:
                    self.counters["supervised_chunks"] += 1
                    state = supervisor.call(
                        dispatch,
                        entry=entry,
                        restore=restore,
                        restore_budget=budget,
                    )
                else:
                    state = dispatch()
                chunk_i += 1
                if (
                    attest is not None
                    and verify_every is not None
                    and chunk_i % verify_every == 0
                    # only a chunk that truly ran to completion can be
                    # re-dispatched for comparison — a restore-rung result
                    # is an older snapshot, not this chunk's output
                    and int(state.generation)
                    == int(attempted.generation) + step
                ):
                    state = self._verify_chunk(
                        wf,
                        attempted,
                        state,
                        step,
                        attest,
                        entry=entry,
                        supervisor=supervisor,
                        pod=pod,
                    )
                self.counters["chunks"] += 1
                gen = int(state.generation)
                progressed = gen > int(attempted.generation)
                if progressed:
                    self.counters["generations"] += gen - int(
                        attempted.generation
                    )
                # pod rendezvous BEFORE the snapshot decision: the drain
                # law's final checkpoint must be the newest barrier, so
                # a drain decided here forces an (off-cadence) save below
                drain = (
                    progressed
                    and pod is not None
                    and pod.chunk_boundary(gen) == "drain"
                )
                if (
                    ckpt is not None
                    and progressed
                    and (gen % ckpt.every == 0 or gen >= total or drain)
                ):
                    # only snapshot forward progress — the restore rung
                    # hands back an OLDER state that is already durable
                    self._submit_checkpoint(lane, ckpt, state, pod=pod)
                if drain:
                    # preemption-graceful stop: in-flight chunk finished,
                    # final barrier checkpoint submitted — make it (and
                    # every earlier snapshot) durable, record, hand back.
                    # checkpointer-less runs drain too (the process must
                    # still stop cleanly) but the record says no final
                    # snapshot exists — nothing claims resumability
                    lane.drain()
                    pod.note_drained(gen, checkpointed=ckpt is not None)
                    return state
            lane.drain()  # every snapshot durable before the run returns
            return state
        except BaseException:
            try:  # flush what we can without masking the real failure
                lane.drain()
            except Exception:
                pass
            raise
        finally:
            if lane in lanes:
                lanes.remove(lane)
            lane.close()
            self._account_lane(lane)
            self.overlap["wall_s"] += self._clock() - t_run0

    # ------------------------------------------------------- integrity rung
    def _verify_chunk(
        self,
        wf: Any,
        attempted: Any,
        state: Any,
        step: int,
        attest: Any,
        *,
        entry: str,
        supervisor: Any,
        pod: Any,
    ) -> Any:
        """ISSUE 20 voted re-dispatch: re-run the chunk from its immutable
        entry state and compare layout-invariant digests. Agreement
        verifies the chunk; a mismatch escalates to a third dispatch and
        the 2-of-3 majority wins, with the dissent noted against the pod
        (journaled ``pod_failure`` classification ``integrity_dissent`` —
        the PodManager re-formation driver quarantines the pod, the run
        itself proceeds on the majority state). No majority is an
        :class:`IntegrityError`: three mutually disagreeing results leave
        nothing trustworthy to continue from."""

        def _dispatch_again() -> Any:
            fn = lambda: wf.run(attempted, step)  # noqa: E731
            if pod is not None:
                raw = fn
                fn = lambda: pod.supervised(raw, entry=f"{entry}:verify")  # noqa: E731
            dispatch = lambda: self._timed_dispatch(  # noqa: E731
                f"{entry}:verify", fn
            )
            if supervisor is not None:
                # transient dispatch faults during verification retry as
                # usual; no restore rung — the entry state IS the snapshot
                return supervisor.call(dispatch, entry=f"{entry}:verify")
            return dispatch()

        def _digest(s: Any) -> tuple:
            return tuple(
                int(v) for v in np.asarray(jax.device_get(attest.digest(s)))
            )

        gen = int(state.generation)
        self.counters["verify_dispatches"] += 1
        redo = _dispatch_again()
        d0, d1 = _digest(state), _digest(redo)
        if d0 == d1:
            self.counters["verified_chunks"] += 1
            return state
        self.counters["integrity_mismatches"] += 1
        if supervisor is not None:
            supervisor._event(
                "integrity_mismatch", entry=entry, generation=gen
            )
        if self.metrics is not None:
            self.metrics.count("executor.integrity_mismatches")
            self.metrics.event(
                "integrity.mismatch", entry=entry, generation=gen
            )
        self.counters["verify_dispatches"] += 1
        third = _dispatch_again()
        d2 = _digest(third)
        if d2 == d1:
            winner, dissent = redo, "first"
        elif d2 == d0:
            winner, dissent = state, "redo"
        else:
            self.integrity["aborts"] += 1
            raise IntegrityError(
                f"no 2-of-3 majority at generation {gen}: three dispatches "
                f"of the same chunk produced three distinct digests — "
                f"nothing trustworthy to continue from",
                generation=gen,
                where=f"{entry}:verify",
            )
        self.counters["integrity_healed"] += 1
        if supervisor is not None:
            supervisor._event(
                "integrity_heal", entry=entry, generation=gen, dissent=dissent
            )
        if self.metrics is not None:
            self.metrics.count("executor.integrity_healed")
            self.metrics.event(
                "integrity.heal", entry=entry, generation=gen, dissent=dissent
            )
        if pod is not None and hasattr(pod, "note_integrity_dissent"):
            pod.note_integrity_dissent(
                generation=gen, entry=entry, dissent=dissent
            )
        return winner

    def integrity_counters(self) -> Dict[str, Any]:
        """The executor's contribution to run_report v14 ``integrity``
        (``None`` when the verify rung never armed — the no-op form)."""
        if self.integrity["verify_every"] is None:
            return None
        return {
            "verify_every": self.integrity["verify_every"],
            "redispatches": self.counters["verify_dispatches"],
            "verified_chunks": self.counters["verified_chunks"],
            "mismatches": self.counters["integrity_mismatches"],
            "healed": self.counters["integrity_healed"],
            "aborted": self.integrity["aborts"],
        }

    # ---------------------------------------------------------- host-eval runs
    def run_host(
        self,
        wf: Any,
        state: Any,
        n_steps: int,
        on_generation: Optional[Callable[[int, Any, Any], None]] = None,
        checkpointer: Any = None,
        resume_from: Any = None,
        eval_chunk: Optional[int] = None,
        chunk: Optional[int] = None,
        max_staleness: Optional[int] = None,
        supervisor: Any = None,
    ) -> Any:
        """The double-buffered host-evaluation loop (external problems):
        generation ``k``'s host ``evaluate`` runs on a worker thread
        while the device halves of ``k+1`` dispatch and the previous
        generation's ``on_generation`` hook runs on the background hook
        lane — the ``run_host_pipelined`` contract, owned here. With a
        supervisor the loop is chunked and each chunk runs under the
        ladder with the OOM/413 degrade rung halving ``eval_chunk``
        (floored at ``supervisor.min_eval_chunk``). ``max_staleness=K``
        opts into stale tells (see the class docstring); ``K=0`` is
        bit-identical to a ``wf.step`` loop."""
        from ..workflows.checkpoint import chunk_to_boundary, enter_run

        supervisor = self.supervisor if supervisor is None else supervisor
        if not getattr(wf, "external", False):
            raise ValueError(
                "run_host is for external (host) problems; jittable "
                "problems should use run_fused / wf.run's fused device loop"
            )
        K = self.max_staleness if max_staleness is None else int(max_staleness)
        if K < 0:
            raise ValueError(f"max_staleness must be >= 0, got {K}")
        self._max_k_seen = max(self._max_k_seen, K)
        if K > 0:
            self._check_stale_support(wf)
            if getattr(state.algo, "key", None) is None:
                raise ValueError(
                    "max_staleness > 0 needs an algorithm state with a "
                    "'key' field (the rekeyed ask streams fold from it); "
                    f"{type(state.algo).__name__} has none"
                )
        wf._run_executor = self
        if supervisor is not None:
            wf._run_supervisor = supervisor
        state, n_steps, ckpt = enter_run(
            state, n_steps, checkpointer, resume_from, expect_like=state
        )
        if ckpt is None and supervisor is not None:
            ckpt = getattr(supervisor, "checkpointer", None)
        if n_steps <= 0:
            # nothing left (e.g. resuming a complete run) — return BEFORE
            # dispatching ask/eval: a stray background evaluate would
            # waste a generation and race the caller on the problem
            return state
        self.counters["runs"] += 1
        t_run0 = self._clock()
        try:
            if supervisor is None and chunk is None:
                return self._pipeline_segment(
                    wf, state, n_steps, on_generation, ckpt, eval_chunk, K
                )
            # chunked path: the supervisor ladder (or an explicit chunk
            # grid) wraps each pipelined segment; the degrade rung
            # mutates the eval-chunk cell the next attempt closes over
            total = n_steps + int(state.generation)
            cell = {"eval_chunk": eval_chunk}
            degrade = (
                self._degrade_thunk(supervisor, wf, cell)
                if supervisor is not None
                else None
            )
            budget = {"used": 0}
            restore = self._restore_thunk(supervisor, ckpt, wf, state)
            while int(state.generation) < total:
                remaining = total - int(state.generation)
                step = min(remaining, chunk_to_boundary(state, ckpt, chunk))
                attempted = state
                segment = lambda: self._pipeline_segment(  # noqa: E731
                    wf, attempted, step, on_generation, ckpt,
                    cell["eval_chunk"], K,
                )
                if supervisor is not None:
                    self.counters["supervised_chunks"] += 1
                    state = supervisor.call(
                        segment,
                        entry="pipelined",
                        restore=restore,
                        degrade=degrade,
                        restore_budget=budget,
                    )
                else:
                    state = segment()
                self.counters["chunks"] += 1
            return state
        finally:
            self.overlap["wall_s"] += self._clock() - t_run0

    # ------------------------------------------------------------- internals
    def _check_stale_support(self, wf: Any) -> None:
        if getattr(wf, "dtype_policy", None) is not None:
            raise ValueError(
                "max_staleness > 0 cannot compose with a dtype_policy: the "
                "stale-tell graft splices storage- and compute-dtype state "
                "branches; run stale tells at full precision"
            )
        if getattr(wf, "donate_carries", False):
            raise ValueError(
                "max_staleness > 0 cannot compose with donate_carries: a "
                "donated pipeline_tell ctx aliases the base state's buffers, "
                "which stale tells must keep reusing"
            )
        table = getattr(wf, "_hook_table", None)
        if table is not None:
            ask_side = [n for n in _ASK_SIDE_HOOKS if table.get(n)]
            if ask_side:
                raise ValueError(
                    "max_staleness > 0 skips ask-side monitor hooks "
                    f"({ask_side} are implemented by attached monitors): "
                    "stale tells chain monitor state through tells only. "
                    "Use tell-side monitors (TelemetryMonitor) with stale "
                    "runs."
                )

    def _degrade_thunk(self, supervisor: Any, wf: Any, cell: dict):
        """The OOM/HTTP-413 degrade rung: halve the host eval chunk,
        floored at the supervisor's ``min_eval_chunk`` (the policy knob
        stays on the supervisor; the loop it degrades lives here)."""
        floor = max(1, int(getattr(supervisor, "min_eval_chunk", 1)))

        def degrade() -> bool:
            cur = cell["eval_chunk"]
            if cur is None:
                pop = getattr(
                    getattr(wf, "algorithm", None), "pop_size", None
                )
                if pop is None:
                    return False
                nxt = max(int(pop) // 2, floor)
            elif cur <= floor:
                return False
            else:
                nxt = max(cur // 2, floor)
            if nxt == cur:
                return False
            cell["eval_chunk"] = nxt
            return True

        return degrade

    def _restore_thunk(self, supervisor: Any, ckpt: Any, wf: Any, expect_like: Any):
        """The supervisor's replay rung, with one executor addition: any
        in-flight background snapshot is drained before ``latest()`` is
        read, so the restore can never race a half-landed save."""
        if supervisor is None or ckpt is None:
            return None
        restorer = getattr(supervisor, "_restorer", None)
        if restorer is None:
            return None
        inner = restorer(ckpt, wf, expect_like)
        if inner is None:
            return None

        def restore():
            self._drain_checkpoint_lanes()
            return inner()

        return restore

    # the lanes of the CURRENTLY running segments, for the restore rung
    _active_ckpt_lanes: List[_IoLane]

    # ------------------------------------------------- named background lanes
    def background_lane(self, name: str) -> _IoLane:
        """A PERSISTENT ordered background I/O lane owned by this
        executor (created lazily, one worker thread, bounded in-flight
        with backpressure). Unlike the per-run checkpoint lanes, a named
        lane survives across chunks/runs — the serving layer's
        fleet-snapshot and journal traffic lives here, so every chunk's
        snapshot pickle+fsync overlaps the next chunk's dispatch.
        Registered with the restore-drain set: a supervisor restore rung
        waits for these writes too before reading ``latest()``."""
        lanes = getattr(self, "_named_lanes", None)
        if lanes is None:
            lanes = self._named_lanes = {}
        lane = lanes.get(name)
        if lane is None:
            lane = lanes[name] = _IoLane(name, self.io_inflight)
            active = getattr(self, "_active_ckpt_lanes", None)
            if active is None:
                active = self._active_ckpt_lanes = []
            active.append(lane)
        return lane

    def submit_background(
        self, name: str, fn: Callable[[], Any], counter: str = "bg_task"
    ) -> None:
        """Submit ``fn`` to the named persistent lane (ordered within the
        lane; errors re-raise at the next submit/drain), counting it
        under ``counter`` and recording a span for the trace."""
        lane = self.background_lane(name)
        self.counters[counter] = self.counters.get(counter, 0) + 1
        t0 = self._clock()

        def task():
            try:
                return fn()
            finally:
                self._span(f"io:{name}", counter, t0, self._clock() - t0)

        lane.submit(task)
        self._sample("executor/io_queue_depth", lane.depth())

    def drain_lane(self, name: str) -> None:
        """Join every pending task of a named lane (no-op for a name
        that was never used), re-raising the first error — the serving
        layer calls this at sweep completion so a failed background
        fsync fails the sweep instead of vanishing."""
        lane = getattr(self, "_named_lanes", {}).get(name)
        if lane is not None:
            lane.drain()
            # fold the lane's busy time into overlap accounting as it
            # quiesces (idempotent: busy_s is consumed and reset)
            self.overlap["io_s"] += lane.busy_s
            lane.busy_s = 0.0
            self.queue_stats["io_inflight_max"] = max(
                self.queue_stats["io_inflight_max"], lane.high_water
            )

    def close(self) -> None:
        """Quiesce the executor: drain every named background lane (so a
        failed fsync still surfaces), then shut their worker threads
        down and forget them. A lane thread alive at interpreter exit
        races the jax atexit backend teardown the same way a live
        deserialized executable does — pod drains and
        the multi-pod gateway call this before letting the process exit.
        Idempotent; a closed executor lazily re-creates lanes if used
        again."""
        lanes = getattr(self, "_named_lanes", None) or {}
        first_err: Optional[BaseException] = None
        for name in list(lanes):
            try:
                self.drain_lane(name)
            except Exception as e:  # keep closing the rest
                if first_err is None:
                    first_err = e
            lanes[name].close()
        self._named_lanes = {}
        active = getattr(self, "_active_ckpt_lanes", None)
        if active is not None:
            active.clear()
        if first_err is not None:
            raise first_err

    def _drain_checkpoint_lanes(self) -> None:
        for lane in list(getattr(self, "_active_ckpt_lanes", [])):
            try:
                lane.drain()
            except Exception:
                # the restore rung is already on an error path; a failed
                # background save must not mask the restore itself (the
                # snapshot set on disk is still consistent — save is
                # atomic), so the drain error is dropped HERE only
                pass

    def _submit_checkpoint(
        self, lane: _IoLane, ckpt: Any, state: Any, pod: Any = None
    ) -> None:
        self.counters["bg_checkpoint"] += 1
        t0 = self._clock()
        if jax.process_count() > 1:
            # pod meshes: WorkflowCheckpointer.save gathers collectively
            # and barriers across processes — both must run in SPMD
            # lockstep on the admitting thread, never interleaved from a
            # background lane (each process's lanes drain independently,
            # which would reorder the collectives and deadlock the pod).
            # Under a pod supervisor the gather+barrier is itself a
            # supervised collective point: a peer dying mid-save raises
            # a classified PodFailureError instead of wedging the pod.
            # The save gets its OWN (larger) deadline — a full host
            # gather legitimately outlasts a chunk dispatch, and the
            # chunk bound would abort a healthy pod at every cadence
            with span(CHECKPOINT_SAVE):
                if pod is not None:
                    pod.supervised(
                        lambda: ckpt.save(state),
                        entry="checkpoint",
                        deadline_s=getattr(pod, "checkpoint_deadline_s", None),
                    )
                else:
                    ckpt.save(state)
            self._span("io:checkpoint", "save", t0, self._clock() - t0,
                       generation=int(state.generation))
            return

        def save():
            with span(CHECKPOINT_SAVE):
                ckpt.save(state)
            self._span("io:checkpoint", "save", t0, self._clock() - t0,
                       generation=int(state.generation))

        lane.submit(save)
        self._sample("executor/io_queue_depth", lane.depth())

    def _account_lane(self, lane: _IoLane) -> None:
        self.overlap["io_s"] += lane.busy_s
        self.queue_stats["io_inflight_max"] = max(
            self.queue_stats["io_inflight_max"], lane.high_water
        )

    def _pipeline_segment(
        self,
        wf: Any,
        state: Any,
        n_steps: int,
        on_generation: Optional[Callable],
        checkpointer: Any,
        eval_chunk: Optional[int],
        K: int,
    ) -> Any:
        """One uninterrupted pipelined stretch of ``n_steps`` generations.
        ``K=0`` reproduces the legacy ``run_host_pipelined`` loop exactly
        (same dispatch/tell/hook ordering ⇒ bit-identical states); ``K>0``
        widens the in-flight window to ``K+1`` evaluations with
        artifact-grafted stale tells."""
        from ..workflows.pipelined import chunked_evaluate

        if n_steps <= 0:
            return state
        gen0 = int(state.generation)
        eval_pool = ThreadPoolExecutor(
            max_workers=K + 1, thread_name_prefix="executor-eval"
        )
        ckpt_lane = _IoLane("checkpoint", self.io_inflight)
        hook_lane = _IoLane("hook", self.io_inflight)
        fetch_lane = _IoLane("fetch", self.io_inflight)
        lanes = getattr(self, "_active_ckpt_lanes", None)
        if lanes is None:
            lanes = self._active_ckpt_lanes = []
        lanes.append(ckpt_lane)
        # stale bookkeeping: the entry key seeds rekeyed ask streams, the
        # artifact mask is probed at the first STEADY ask (init asks can
        # write a different leaf set than steady asks)
        entry_key = getattr(state.algo, "key", None)
        artifact_mask: Optional[List[bool]] = None
        pending: deque = deque()
        hook_fut: Optional[Future] = None
        asked = 0
        told = 0
        base = state
        # surrogate hooks (ISSUE 15, workflows/surrogate.py — duck-typed
        # so core stays decoupled): host_evaluate slices the screened
        # batch to its truly evaluated rows before the expensive host
        # problem sees it; refit_due/dispatch_refit refit the surrogate
        # between tells as a SEPARATE async-dispatched program — the
        # loop never blocks on it, and the model an ask consumes lags
        # the archive by at most the workflow's refit cadence (the
        # bounded-staleness discipline applied to the model)
        host_eval = getattr(wf, "host_evaluate", None)
        refit_due = getattr(wf, "refit_due", None)
        dispatch_refit = getattr(wf, "dispatch_refit", None)

        def submit_eval(cand, pstate):
            def run_eval():
                t0 = self._clock()
                try:
                    with span(HOST_EVAL):
                        if host_eval is not None:
                            return host_eval(pstate, cand, eval_chunk)
                        return chunked_evaluate(
                            wf.problem, pstate, cand, eval_chunk
                        )
                finally:
                    dt = self._clock() - t0
                    with self._lock:
                        self.overlap["host_eval_s"] += dt
                    self._span("host_eval", "evaluate", t0, dt)

            return eval_pool.submit(run_eval)

        try:
            while told < n_steps:
                # ---------------------------------------------- issue asks
                while asked < n_steps and (asked - told) <= K:
                    ask_state = base
                    if pending:
                        # an ask with tells still pending must not replay
                        # the base state's key split — fold a fresh
                        # deterministic stream per generation
                        ask_state = base.replace(
                            algo=_rekey(base.algo, entry_key, gen0 + asked)
                        )
                    probe_pre = ask_state.algo if (
                        K > 0
                        and artifact_mask is None
                        and not ask_state.first_step
                    ) else None
                    cand, ctx = self._timed_dispatch(
                        "pipeline_ask", lambda: wf.pipeline_ask(ask_state)
                    )
                    if probe_pre is not None:
                        artifact_mask = _ask_artifact_mask(probe_pre, ctx[0])
                    self.counters["asks"] += 1
                    pending.append(
                        _InflightEval(
                            asked, ctx, submit_eval(cand, base.prob), told
                        )
                    )
                    asked += 1
                    self.queue_stats["stale_window_max"] = max(
                        self.queue_stats["stale_window_max"], len(pending)
                    )
                    if artifact_mask is None and K > 0:
                        # mask not probed yet (first_step peel): hold the
                        # window at depth 1 until the steady shape is known
                        break
                # ------------------------------------------------ admit tell
                ev = pending.popleft()
                fitness, _ = ev.fut.result()
                if hook_fut is not None:
                    # surface on_generation errors from the previous
                    # generation BEFORE advancing the state (legacy law)
                    hook_fut.result()
                    hook_fut = None
                # staleness in TELLS: how many updates landed after this
                # generation's candidates were sampled (== K in the steady
                # stale window, including the final drain tells)
                lag = told - ev.base_told
                self._sample("executor/stale_lag", lag)
                if lag > 0:
                    self.counters["stale_tells"] += 1
                    self.counters["max_lag"] = max(
                        self.counters["max_lag"], lag
                    )
                    # graft the admitted generation's ask-artifacts (key,
                    # noise, candidate buffers) onto the newest told state:
                    # tell sees its own matched (noise, fitness) pair while
                    # every earlier tell's update — and the newest monitor
                    # chain — is kept
                    hybrid = _merge_artifacts(
                        base.algo, ev.ctx[0], artifact_mask
                    )
                    ctx = (hybrid, tuple(base.monitors), ev.ctx[2])
                else:
                    ctx = ev.ctx
                tell_state = base
                base = self._timed_dispatch(
                    "pipeline_tell",
                    lambda: wf.pipeline_tell(tell_state, ctx, fitness, tell_state.prob),
                )
                told += 1
                self.counters["tells"] += 1
                self.counters["generations"] += 1
                if (
                    refit_due is not None
                    and dispatch_refit is not None
                    and refit_due(gen0 + told)
                ):
                    # BEFORE the snapshot decision: a checkpoint at this
                    # boundary must embed the refit, so a resumed run
                    # reproduces the schedule (pure in the absolute
                    # generation). Async dispatch — no host block.
                    self.counters["bg_refit"] += 1
                    base = self._timed_dispatch(
                        "surrogate_refit",
                        lambda: dispatch_refit(base, gen0 + told),
                    )
                if checkpointer is not None:
                    if int(base.generation) % checkpointer.every == 0:
                        self._submit_checkpoint(ckpt_lane, checkpointer, base)
                if on_generation is not None:
                    self.counters["bg_hook"] += 1
                    snapshot, fit_snapshot, g_abs = base, fitness, gen0 + ev.g
                    hook_fut = hook_lane.submit(
                        lambda: on_generation(g_abs, snapshot, fit_snapshot)
                    )
                if (
                    self.fetch_monitors_every
                    and told % self.fetch_monitors_every == 0
                    and getattr(base, "monitors", None)
                ):
                    self._submit_monitor_fetch(fetch_lane, base)
            if hook_fut is not None:
                hook_fut.result()
            hook_lane.drain()
            if checkpointer is not None:
                if int(base.generation) % checkpointer.every != 0:
                    # final state is always durable, even off-cadence
                    self._submit_checkpoint(ckpt_lane, checkpointer, base)
            ckpt_lane.drain()
            fetch_lane.drain()
            return base
        except BaseException:
            try:  # flush snapshots without masking the real failure
                ckpt_lane.drain()
            except Exception:
                pass
            raise
        finally:
            if ckpt_lane in lanes:
                lanes.remove(ckpt_lane)
            eval_pool.shutdown(wait=False)
            for lane in (ckpt_lane, hook_lane, fetch_lane):
                lane.close()
                self._account_lane(lane)

    def _submit_monitor_fetch(self, lane: _IoLane, state: Any) -> None:
        self.counters["bg_fetch"] += 1
        gen = int(state.generation)
        monitors = state.monitors
        cross_process = any(
            isinstance(leaf, jax.Array) and not leaf.is_fully_addressable
            for leaf in jax.tree_util.tree_leaves(monitors)
        )
        if cross_process:
            # pod meshes: the ring all-gather is a COLLECTIVE and must be
            # dispatched in SPMD lockstep on every process — run it here
            # on the admitting thread (a background thread interleaving
            # its own collectives with the main loop's dispatches would
            # deadlock the pod); only the host bookkeeping rides the lane
            from .distributed import tree_host_value

            monitors = tree_host_value(monitors)

        def fetch():
            t0 = self._clock()
            host = jax.device_get(monitors)
            self.last_monitor_fetch = (gen, host)
            dt = self._clock() - t0
            self._span("io:fetch", "monitors", t0, dt, generation=gen)
            if self.metrics is not None:
                # the telemetry lane is the callback-free path from the
                # on-device rings into the metrics plane: the fetch just
                # completed on a background thread (registry is
                # thread-safe), so the gauges carry the newest ring
                # values without any callback or extra round-trip
                self.metrics.count("executor.monitor_fetches")
                self.metrics.observe("executor.monitor_fetch_ms", dt * 1e3)
                self.metrics.set("executor.monitor_fetch_gen", gen)

        lane.submit(fetch)
        self._sample("executor/io_queue_depth", lane.depth())

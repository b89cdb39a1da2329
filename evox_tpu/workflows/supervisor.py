"""RunSupervisor — deadlines, transient-error retry, and checkpoint replay
for the dispatch layer.

The last unguarded boundary after the PR-2/PR-3 healing layers is the
dispatch itself: a single ``jit`` dispatch can hang forever (a wedged
runtime or a remote one that stopped answering), die with a transient
RPC error (``UNAVAILABLE``/connection reset), or fail with
``RESOURCE_EXHAUSTED`` / HTTP 413 when a payload outgrows its transport
or HBM. Production
ES-on-accelerator systems (OpenAI ES, EvoJAX — PAPERS.md) treat these as
routine; today any of them kills the whole evox_tpu run even though
PR-2 checkpoints sit on disk. This module closes that gap, entirely
host-side (no host callbacks):

- **Deadline**: every supervised dispatch chunk runs on a disposable
  worker thread while the supervisor waits with a wall-clock timeout — a
  hung dispatch becomes a raised :class:`DispatchDeadlineError` instead
  of an eternal block. (The wedged thread itself cannot be killed from
  Python; it is daemonized and abandoned — the backend either answers
  late into the void or never.)
- **Classifier**: :func:`classify_error` folds the zoo of backend
  failures into ``transient`` / ``oom`` / ``deadline`` / ``fatal`` /
  ``integrity`` (wrong bits — never retried, see core/attest.py).
  Classification is by exception type AND message patterns, so the fake
  faults of tests/_chaos.py::FlakyDispatch classify exactly like the
  real jaxlib ``XlaRuntimeError`` strings they mimic.
- **Escalation ladder**, per dispatch chunk::

      retry (bounded, exponential backoff + deterministic jitter)
        -> restore the latest WorkflowCheckpointer snapshot and replay
        -> degrade (pipelined runs: halve the host eval chunk on OOM/413)
        -> RunAbortedError carrying a structured post-mortem

  (OOM takes the degrade rung first when one exists — retrying the
  identical payload would exhaust the same resource again.) Retrying is
  ALWAYS bit-safe: workflow states are immutable pytrees and the
  dispatch is a pure function of its input state, so a retried (or
  snapshot-replayed) chunk reproduces the exact trajectory of a clean
  run — the chaos acceptance law asserted in tests/test_supervisor.py.

Every supervisor decision (retry, deadline hit, restore, degradation,
abort) is recorded with a host timestamp; :func:`~evox_tpu.core.
instrument.run_report` surfaces them as a ``supervisor`` section and
:func:`~evox_tpu.core.instrument.write_chrome_trace` as instant markers
on a dedicated supervisor track. No reference analog (the reference
assumes every dispatch returns); informed by the fault-domain design of
the PR-2 process farm.

Since the executor port (PR 8) this module is pure POLICY: the chunk
loops live in :class:`~evox_tpu.core.executor.GenerationExecutor`, and
``run``/``run_host_pipelined`` wire the deadline watchdog, the
classified-retry ladder (:meth:`RunSupervisor.call`), the restore
replay, and the eval-chunk degradation in as executor hooks.
"""

from __future__ import annotations

import random
import re
import time
from typing import Any, Callable, Dict, List, Optional

from ..core.attest import IntegrityError
from ..core.distributed import BarrierTimeoutError
from ..core.pod_supervisor import (
    CollectiveDeadlineError,
    PodFailureError,
    _watchdog_call,
)
from .checkpoint import WorkflowCheckpointer

__all__ = [
    "DispatchDeadlineError",
    "RunAbortedError",
    "RunSupervisor",
    "classify_error",
    "TRANSIENT",
    "OOM",
    "DEADLINE",
    "FATAL",
    "INTEGRITY",
]


class DispatchDeadlineError(RuntimeError):
    """A supervised dispatch exceeded its wall-clock deadline — the
    backend hung instead of answering (or erroring)."""


class RunAbortedError(RuntimeError):
    """The supervisor exhausted its escalation ladder. ``post_mortem``
    holds the structured account of what was tried (see
    :meth:`RunSupervisor.report`); ``__cause__`` chains the final
    underlying failure."""

    def __init__(self, message: str, post_mortem: dict):
        super().__init__(message)
        self.post_mortem = post_mortem


# error classes (strings, so reports stay plain JSON)
TRANSIENT = "transient"
OOM = "oom"
DEADLINE = "deadline"
FATAL = "fatal"
# ISSUE 20: a digest violation is its OWN class, distinct from transient —
# retrying corrupt bits "heals" nothing and risks accepting them; the only
# valid responses are an explicit heal (voted re-dispatch, barrier
# fallback) or an abort, never the retry rung
INTEGRITY = "integrity"

# Message fingerprints of retryable backend failures. gRPC/absl status
# names cover jaxlib's XlaRuntimeError surface (one exception type for
# every status code — the status name in the message is the only
# signal); the socket words cover a remote runtime's transport.
# Checked case-insensitively.
_TRANSIENT_PATTERNS = (
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "aborted",
    "cancelled",
    "connection reset",
    "connection refused",
    "connection closed",
    "broken pipe",
    "timed out",
    "timeout",
    "socket",
    "temporarily",
    "eof occurred",
)
# OOM / payload-too-large: degradable, not retry-identical — the same
# dispatch at the same size exhausts the same resource again.
_OOM_PATTERNS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "payload too large",
    "request entity too large",
    "http 413",
)
# bare "413" only counts next to an HTTP-ish word — it is also a shape
_OOM_413 = re.compile(r"(?:^|[^0-9.])413(?:[^0-9.]|$)")


def classify_error(exc: BaseException) -> str:
    """Fold an exception into ``transient`` / ``oom`` / ``deadline`` /
    ``fatal``. Decisive exception TYPES are checked before any message
    pattern (a ``MemoryError`` is OOM and a ``ConnectionError`` transient
    whatever they say; a bubbled-up :class:`RunAbortedError` is always
    fatal — a supervisor never re-litigates another's verdict), and
    patterns are matched against the MESSAGE only, never the type name
    (``RunAbortedError``'s own name must not read as 'aborted')."""
    if isinstance(exc, IntegrityError):
        # wrong BITS, not a failed dispatch (ISSUE 20): the chunk
        # "succeeded" with corrupt state, so no amount of retrying the
        # same path can be trusted to produce different evidence —
        # healing is the caller's explicit job (vote / barrier fallback)
        return INTEGRITY
    if isinstance(exc, (DispatchDeadlineError, CollectiveDeadlineError, BarrierTimeoutError)):
        # the pod-level deadlines (ISSUE 14) fold into the same class as
        # the dispatch watchdog's: a bounded wait expired
        return DEADLINE
    if isinstance(exc, (RunAbortedError, PodFailureError)):
        # a classified pod fault (worker dead / hung collective /
        # coordinator loss) cannot be healed by retrying IN this process
        # — the escalation continues in the re-formation driver, so the
        # in-process ladder must abort, not spin
        return FATAL
    if isinstance(exc, MemoryError):
        return OOM
    msg = str(exc).lower()
    if any(p in msg for p in _OOM_PATTERNS):
        return OOM
    if _OOM_413.search(msg) and ("http" in msg or "remote" in msg):
        return OOM
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return TRANSIENT
    if any(p in msg for p in _TRANSIENT_PATTERNS):
        return TRANSIENT
    if isinstance(exc, OSError):
        return TRANSIENT
    return FATAL


def _call_with_deadline(
    fn: Callable, deadline_s: Optional[float], label: str
):
    """Run ``fn()`` on a disposable daemon thread and wait at most
    ``deadline_s`` (None = no watchdog, call inline). A fresh thread per
    call is deliberate: a hung call occupies its thread forever, so
    pooling would poison the pool. ~50 µs of thread spawn is small next
    to a dispatch chunk of many generations.
    One shared body with the pod fault domain's collective watchdog
    (core/pod_supervisor.py — this call supplies the dispatch-flavored
    timeout exception)."""
    return _watchdog_call(
        fn,
        deadline_s,
        label,
        make_timeout=lambda lbl, dl: DispatchDeadlineError(
            f"dispatch '{lbl}' exceeded its {dl:g} s deadline; "
            "the worker thread is abandoned (a wedged backend never answers)"
        ),
        thread_prefix="supervised",
    )


# event kind -> cumulative counter it increments
_COUNTER_FOR = {
    "retry": "retries",
    "deadline": "deadline_hits",
    "restore": "restores",
    "degrade": "degradations",
    "abort": "aborts",
}


class RunSupervisor:
    """Drive a workflow's dispatch chunks under deadlines, classified
    retry, checkpoint replay, and degradation.

    Args:
        checkpointer: optional :class:`WorkflowCheckpointer`. When given,
            runs are chunked at its cadence and snapshotted between
            dispatches (exactly the PR-2 ``checkpointed_run`` law, so the
            final state is identical to an unsupervised run), and the
            restore rung of the ladder can replay from the newest intact
            snapshot.
        deadline_s: wall-clock bound per supervised dispatch chunk
            (``None`` disables the watchdog). For pipelined chunks the
            bound covers the whole chunk — size it to
            ``chunk * worst-case generation time``.
        max_retries: transient/deadline retries per chunk before
            escalating to the restore rung.
        max_restores: snapshot-restore-and-replay attempts per chunk.
        backoff_s / backoff_factor / jitter: retry sleep is
            ``backoff_s * factor**(attempt-1) * (1 + jitter*u)`` with
            ``u ~ U[0,1)`` from a seeded PRNG — exponential backoff with
            deterministic jitter (reproducible chaos tests).
        min_eval_chunk: floor for the pipelined host-eval chunk; OOM
            below it escalates instead of degrading further.
        seed: jitter PRNG seed.

    One supervisor instance can drive many runs; counters and events
    accumulate (:meth:`report` is the ``run_report()`` ``supervisor``
    section).
    """

    def __init__(
        self,
        checkpointer: Optional[WorkflowCheckpointer] = None,
        deadline_s: Optional[float] = None,
        max_retries: int = 3,
        max_restores: int = 1,
        backoff_s: float = 0.05,
        backoff_factor: float = 2.0,
        jitter: float = 0.25,
        min_eval_chunk: int = 1,
        seed: int = 0,
        clock: Callable[[], float] = time.perf_counter,
        metrics: Any = None,
        attest: Any = None,
        verify_every: Optional[int] = None,
    ):
        if max_retries < 0 or max_restores < 0:
            raise ValueError("max_retries and max_restores must be >= 0")
        if min_eval_chunk < 1:
            raise ValueError(f"min_eval_chunk must be >= 1, got {min_eval_chunk}")
        self.checkpointer = checkpointer
        self.deadline_s = deadline_s
        self.max_retries = max_retries
        self.max_restores = max_restores
        self.backoff_s = backoff_s
        self.backoff_factor = backoff_factor
        self.jitter = jitter
        self.min_eval_chunk = min_eval_chunk
        # compute-integrity rung (ISSUE 20): when both are set, fused runs
        # re-dispatch every verify_every-th chunk from its immutable entry
        # state and compare digests (2-of-3 vote on mismatch). None/None —
        # the default — is the established no-op discipline: zero extra
        # dispatches, bit-identical to pre-PR.
        self.attest = attest
        self.verify_every = verify_every
        self._rng = random.Random(seed)
        # serving-plane flight recorder (PR 16): when attached, every
        # ladder event mirrors into the live metrics plane and aborts
        # carry the recorder's black-box ring in their post-mortem;
        # None (default) changes nothing
        self.metrics = metrics
        self._clock = clock
        self._created = clock()
        self.events: List[dict] = []
        self.counters: Dict[str, int] = {
            "dispatches": 0,
            "retries": 0,
            "deadline_hits": 0,
            "restores": 0,
            "degradations": 0,
            "aborts": 0,
        }
        self._outcome: Optional[str] = None

    # ------------------------------------------------------------- recording
    def _event(self, kind: str, **fields: Any) -> None:
        ev = {"t": round(self._clock() - self._created, 6), "event": kind}
        ev.update(fields)
        self.events.append(ev)
        counter = _COUNTER_FOR.get(kind)
        if counter is not None:
            self.counters[counter] += 1
        if self.metrics is not None:
            self.metrics.count(f"supervisor.{kind}")

    def report(self) -> dict:
        """The ``supervisor`` section of ``run_report()`` — strict-JSON
        account of every decision this supervisor took. ``outcome``:
        ``clean`` (nothing fired), ``recovered`` (faults healed),
        ``aborted`` (ladder exhausted)."""
        healed = any(
            e["event"] in ("retry", "restore", "degrade") for e in self.events
        )
        outcome = self._outcome
        if outcome is None:
            outcome = "recovered" if healed else "clean"
        return {
            "deadline_s": self.deadline_s,
            "max_retries": self.max_retries,
            "max_restores": self.max_restores,
            "counters": dict(self.counters),
            "outcome": outcome,
            "events": list(self.events),
        }

    def markers(self) -> List[dict]:
        """Events as absolute-timestamped instant markers for the
        Chrome-trace exporter (:func:`~evox_tpu.core.instrument.
        write_chrome_trace` re-bases ``t_abs`` — this supervisor's clock
        is the recorder's clock, ``time.perf_counter``)."""
        return [
            {
                "t_abs": self._created + ev["t"],
                "name": f"supervisor:{ev['event']}",
                "args": {k: v for k, v in ev.items() if k not in ("t", "event")},
            }
            for ev in self.events
        ]

    # -------------------------------------------------------------- plumbing
    def _sleep_backoff(self, attempt: int) -> float:
        dt = self.backoff_s * self.backoff_factor ** max(attempt - 1, 0)
        dt *= 1.0 + self.jitter * self._rng.random()
        time.sleep(dt)
        return dt

    def _abort(self, entry: str, error: BaseException, **ladder: Any) -> None:
        self._event("abort", entry=entry, error=str(error)[:300], **ladder)
        self._outcome = "aborted"
        post_mortem = {
            "entry": entry,
            "error": f"{type(error).__name__}: {error}",
            "classification": classify_error(error),
            "ladder": dict(ladder),
            "counters": dict(self.counters),
            "events_tail": self.events[-20:],
        }
        if self.metrics is not None:
            # the black-box tape: the newest flight-recorder records
            # (queue events, samples, barriers) ride in every abort's
            # forensics, so "what was the service doing" survives the
            # process that was doing it
            self.metrics.event(
                "supervisor.abort", entry=entry, error=str(error)[:120]
            )
            post_mortem["flight_recorder"] = self.metrics.tail(20)
        raise RunAbortedError(
            f"supervised '{entry}' exhausted its escalation ladder "
            f"({ladder}); last failure: {type(error).__name__}: {error}",
            post_mortem=post_mortem,
        ) from error

    def call(
        self,
        fn: Callable[[], Any],
        entry: str = "dispatch",
        restore: Optional[Callable[[], Any]] = None,
        degrade: Optional[Callable[[], bool]] = None,
        restore_budget: Optional[Dict[str, int]] = None,
    ) -> Any:
        """One supervised dispatch of the zero-arg ``fn`` under the full
        ladder (``fn`` is re-invoked on retry, so close over any state a
        degradation should be able to change). ``restore()`` (optional)
        returns a snapshot to replay from; when that rung fires, the
        snapshot is returned as the call's result — the CALLER owns the
        replay (it re-derives remaining work from ``state.generation``).
        ``degrade()`` (optional) applies one degradation (e.g. halving an
        eval chunk) and returns True if it could.

        ``restore_budget``: a ``{"used": n}`` cell shared across every
        chunk of one run. The retry budget is per CHUNK (each chunk is an
        independent dispatch), but restores must be bounded per RUN — a
        permanently failing chunk replayed from the same snapshot would
        otherwise ladder-cycle forever. Defaults to a per-call cell."""
        retries = 0
        if restore_budget is None:
            restore_budget = {"used": 0}
        while True:
            self.counters["dispatches"] += 1
            try:
                return _call_with_deadline(fn, self.deadline_s, entry)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — classified below
                kind = classify_error(e)
                if kind == DEADLINE:
                    self._event(
                        "deadline", entry=entry, deadline_s=self.deadline_s
                    )
                if kind == FATAL:
                    self._abort(entry, e, rung="fatal")
                if kind == INTEGRITY:
                    # never retried into acceptance: the voted re-dispatch
                    # rung (executor) and the barrier fallback (tenancy
                    # recover) heal BEFORE raising; an IntegrityError that
                    # reaches the ladder means healing already failed
                    self._abort(entry, e, rung="integrity")
                if kind == OOM and degrade is not None and degrade():
                    self._event("degrade", entry=entry, error=str(e)[:300])
                    continue
                if retries < self.max_retries and kind != OOM:
                    retries += 1
                    waited = self._sleep_backoff(retries)
                    self._event(
                        "retry",
                        entry=entry,
                        attempt=retries,
                        classification=kind,
                        backoff_s=round(waited, 6),
                        error=str(e)[:300],
                    )
                    continue
                if (
                    restore is not None
                    and restore_budget["used"] < self.max_restores
                ):
                    snapshot = restore()
                    if snapshot is not None:
                        restore_budget["used"] += 1
                        self._event(
                            "restore",
                            entry=entry,
                            attempt=restore_budget["used"],
                            classification=kind,
                        )
                        return snapshot
                self._abort(
                    entry, e, rung="exhausted", retries=retries,
                    restores=restore_budget["used"],
                )

    # ------------------------------------------------------------ fused runs
    def run(
        self,
        wf: Any,
        state: Any,
        n_steps: int,
        chunk: Optional[int] = None,
        resume_from: Any = None,
        executor: Any = None,
        pod_supervisor: Any = None,
    ) -> Any:
        """Supervised ``wf.run``: the fused device loop is chunked (at the
        checkpointer cadence, else ``chunk`` generations, else one
        dispatch for the whole run) and every chunk dispatch runs under
        the deadline + ladder. Chunking a ``fori_loop`` does not change
        its math, so the final state is identical to a straight
        ``wf.run(state, n_steps)`` — and on failure the supervisor
        retries the immutable entry state, or replays from the newest
        snapshot, reproducing the clean run's trajectory bit for bit.

        Works for any workflow exposing ``run(state, n)`` whose state
        carries ``generation`` — :class:`~evox_tpu.workflows.std.
        StdWorkflow` and :class:`~evox_tpu.workflows.islands.
        IslandWorkflow` alike. ``resume_from`` (checkpointer or
        directory) restores the newest intact snapshot first and
        reinterprets ``n_steps`` as the TOTAL generation target.

        The chunk loop itself lives in :class:`~evox_tpu.core.executor.
        GenerationExecutor` (this method is the supervision POLICY:
        deadline, classifier, ladder — wired in as executor hooks);
        snapshots land on the executor's background checkpoint lane,
        drained before any restore replays and before the run returns.
        Pass ``executor=`` to accumulate counters/spans on a shared
        instance, and ``pod_supervisor=`` (a
        :class:`~evox_tpu.core.pod_supervisor.PodSupervisor`) to put
        every SPMD-lockstep collective point under the pod fault domain
        — collective deadlines, chunk-boundary rendezvous, coordinated
        SIGTERM drain (ISSUE 14)."""
        from ..core.executor import GenerationExecutor

        ex = executor if executor is not None else GenerationExecutor()
        return ex.run_fused(
            wf,
            state,
            n_steps,
            checkpointer=self.checkpointer,
            chunk=chunk,
            resume_from=resume_from,
            supervisor=self,
            pod_supervisor=pod_supervisor,
            attest=self.attest,
            verify_every=self.verify_every,
        )

    # --------------------------------------------------------- pipelined runs
    def run_host_pipelined(
        self,
        wf: Any,
        state: Any,
        n_steps: int,
        chunk: Optional[int] = None,
        eval_chunk: Optional[int] = None,
        resume_from: Any = None,
        executor: Any = None,
        restarts: Any = None,
        **pipelined_kw: Any,
    ) -> Any:
        """Supervised ``run_host_pipelined`` for external (host)
        problems: the driver loop is chunked like :meth:`run` and each
        chunk runs under the ladder, with the degrade rung live — on
        OOM / HTTP 413 the host evaluation batch is split
        (``eval_chunk`` halves, floored at ``min_eval_chunk``) and the
        chunk retried from its immutable entry state; see
        ``run_host_pipelined(eval_chunk=...)`` for the bit-equivalence
        contract (row-independent host evaluate). The double-buffered
        loop and the degrade cell live in the
        :class:`~evox_tpu.core.executor.GenerationExecutor`; this method
        supplies the ladder. ``restarts=`` (an ``IPOPRestarts``) keeps
        the host-boundary IPOP recipe supervised: the run is chunked at
        the policy cadence and every pipelined segment dispatches under
        this supervisor's ladder."""
        from ..core.executor import GenerationExecutor

        ex = executor if executor is not None else GenerationExecutor()
        if restarts is not None:
            from .ipop import ipop_run

            return ipop_run(
                wf,
                state,
                n_steps,
                restarts,
                segment=lambda w, s, c, ck: ex.run_host(
                    w, s, c, checkpointer=ck, chunk=chunk,
                    eval_chunk=eval_chunk, supervisor=self, **pipelined_kw,
                ),
                checkpointer=self.checkpointer,
                resume_from=resume_from,
            )
        return ex.run_host(
            wf,
            state,
            n_steps,
            checkpointer=self.checkpointer,
            chunk=chunk,
            eval_chunk=eval_chunk,
            resume_from=resume_from,
            supervisor=self,
            **pipelined_kw,
        )

    def _restorer(self, ckpt, wf, expect_like):
        """Restore thunk for the ladder's replay rung. The host-numpy
        snapshot is re-placed on the workflow's CURRENT mesh by the
        state's own sharding annotations (exactly ``StdWorkflow.resume``'s
        law) — without it, a mesh workflow's warm fused executable would
        see replicated host arrays mid-recovery and pay a full
        re-trace/re-shard right when the run is trying to heal."""
        if ckpt is None:
            return None
        from .checkpoint import restore_layouts

        def restore():
            snapshot = ckpt.latest(expect_like=expect_like)
            if snapshot is None:
                return None
            # tenant-stacked fleet states re-place by their own prefixed
            # layout (VectorizedWorkflow.place_restored) — the plain
            # annotation walk would shard a stacked leaf's TENANT axis
            # over the pop mesh axis
            placer = getattr(wf, "place_restored", None)
            if placer is not None:
                return placer(snapshot)
            return restore_layouts(snapshot, mesh=getattr(wf, "mesh", None))

        return restore

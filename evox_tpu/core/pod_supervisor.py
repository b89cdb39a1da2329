"""PodSupervisor — the fault domain of a ``jax.distributed`` pod.

PR 13 made multi-process execution first-class, but SPMD collectives are
LOCKSTEP: a SIGKILLed, wedged, or preempted worker leaves every survivor
blocked inside a collective (or a :func:`~evox_tpu.core.distributed.
process_barrier`) with no deadline, no diagnosis, and no recovery. Every
other layer already heals itself — the evaluation farm (PR 2), the
numerics (PR 3), the dispatch layer (PR 5), the serving queue (PR 11);
Fiber (arXiv 2003.11164) and "Distributed ES with Multi-Level Learning"
(arXiv 2310.05377) treat elastic membership and failure re-formation as
the defining property of a production ES fleet. This module closes the
pod-level gap, entirely host-side (no host callbacks):

- **Heartbeats**: every member runs a daemon thread bumping a sequence
  counter in the coordinator's KV store (the ``process_barrier``
  plumbing — no XLA collective, so it works on backends that cannot run
  one). A :meth:`PodSupervisor.census` is a DOUBLE read separated by a
  probe interval: a member whose counter did not advance is not alive,
  with no cross-host clock comparison involved.
- **Collective deadlines**: :meth:`PodSupervisor.supervised` runs a
  dispatch (an SPMD-lockstep collective point — a pod ``wf.run`` chunk,
  a pod checkpoint gather) on a disposable watchdog thread with a
  wall-clock deadline — the PR-5 ``RunSupervisor`` pattern extended
  cross-process. A hung collective becomes a raised, classified error
  instead of an eternal block (the wedged thread is daemonized and
  abandoned, exactly like the PR-5 dispatch watchdog).
- **Failure classification**: deadline hits and coordination-channel
  errors are refined through the census into ``worker_dead`` (a peer's
  heartbeat stopped), ``hung_collective`` (every peer alive, the
  collective itself is wedged), or ``coordinator_loss`` (the KV channel
  is gone — the coordinator process died). Anything that is NOT a
  pod-domain fault propagates unchanged, and
  :func:`~evox_tpu.workflows.supervisor.classify_error` folds the pod
  errors into the PR-5 error classes (barrier/collective deadlines →
  ``deadline``, a classified :class:`PodFailureError` → ``fatal``: a
  single process cannot heal a pod fault in-process — the escalation
  continues OUTSIDE, in the re-formation driver).
- **Escalation ladder** (the cross-process continuation of PR 5's):
  deadline-abort → survivor census → post-mortem
  (:class:`PodFailureError` carries classification, census, detection
  latency, event tail; every process exits loudly instead of blocking)
  → **pod re-formation** by the respawn driver
  (``tools/_multihost_worker.PodManager``: fresh coordinator
  rendezvous, ``create_pod_mesh`` over the survivor device set) →
  :meth:`PodSupervisor.resume_from_barrier` restores the newest intact
  pod-barrier snapshot, which the PR-5/13 topology-portable manifests
  make process-count-portable — an ``n``-process run killed mid-flight
  replays on the survivor set reproducing the uninjured trajectory.
  ``ShardedES(n_shards=...)`` keeps the sampling law fixed across the
  shrink (bit-identity up to psum order) whenever the survivor DEVICE
  total divides the pinned ``n_shards``; survivor counts that don't
  divide resume on the REPLICATED twin of the same law (``mesh=None``,
  same ``n_shards`` — the documented sharded≡replicated contract), so
  no survivor count is unrecoverable.
- **Preemption-graceful drain**: :meth:`install_sigterm_drain` turns a
  cloud preemption notice (SIGTERM) into a COORDINATED drain — the
  in-flight chunk finishes, every member agrees on the decision at the
  next :meth:`chunk_boundary` (process 0 arbitrates through the KV
  store, so no member drains while another continues into a collective
  nobody will join), a final barrier checkpoint is fsynced, background
  lanes drain, and the process exits 0. The resumed run equals the
  uninterrupted run (the drain law, tests/test_pod_chaos.py).

Membership transitions (join / census / failure / reform / resume /
drain) are journaled through the PR-11 WAL discipline
(:class:`~evox_tpu.workflows.journal.RunJournal` ``pod_*`` kinds,
process-0-writes — the checkpoint commit discipline), surface as the
``pod_supervisor`` section of ``run_report()`` (schema v9, validated by
tools/check_report.py) and as ``supervisor:pod:*`` instant markers in
``write_chrome_trace``. The whole layer is opt-in: with no pod
supervisor configured, single-process and dryrun paths are bit-identical
to the pre-ISSUE-14 tree.

No reference analog: the reference's fault story is Ray actor restart
(PARITY row 59); re-forming a ``jax.distributed`` pod on the survivor
set is the documented deviation this module implements.
"""

from __future__ import annotations

import signal
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

from .distributed import (
    BarrierTimeoutError,
    _dist_client,
    _INTROSPECT_FAILED,
    process_barrier,
)

__all__ = [
    "WORKER_DEAD",
    "HUNG_COLLECTIVE",
    "COORDINATOR_LOSS",
    "INTEGRITY_DISSENT",
    "POD_FAILURE_CLASSES",
    "POD_EVENT_KINDS",
    "JAXLIB_COORD_ABORT_S",
    "CollectiveDeadlineError",
    "PodFailureError",
    "PodSupervisor",
]

#: jaxlib's coordination client aborts the PROCESS (C++ LOG(FATAL) →
#: SIGABRT) roughly this many seconds after it stops reaching the
#: coordinator — the hard ceiling a supervisor deadline
#: must undercut in a real multi-process pod to classify the failure
#: before the runtime kills the classifier
JAXLIB_COORD_ABORT_S = 10.0
#: safety margin under the abort ceiling (the ~10 s figure is empirical,
#: not an API contract)
_COORD_ABORT_MARGIN_S = 0.5

# pod-domain failure classes (strings so post-mortems stay plain JSON) —
# the cross-process refinement of the PR-5 transient/oom/deadline/fatal
# classes (classify_error folds these back into them)
WORKER_DEAD = "worker_dead"
HUNG_COLLECTIVE = "hung_collective"
COORDINATOR_LOSS = "coordinator_loss"
# a pod whose chunk result lost a 2-of-3 integrity vote (ISSUE 20): the
# hardware answered in time with WRONG bits — quarantined through the
# same census/re-formation machinery as a dead worker, but the class is
# distinct because the remedy differs (drop the dissenting result, keep
# the pod out of voted dispatches until re-formed)
INTEGRITY_DISSENT = "integrity_dissent"
POD_FAILURE_CLASSES = (
    WORKER_DEAD,
    HUNG_COLLECTIVE,
    COORDINATOR_LOSS,
    INTEGRITY_DISSENT,
)

#: every event kind a PodSupervisor records (run_report section +
#: ``supervisor:pod:*`` trace markers; tools/check_report.py pins the set)
POD_EVENT_KINDS = (
    "join",
    "census",
    "barrier_timeout",
    "failure",
    "drain_requested",
    "drain",
    "reform",
    "resume",
)

# event kind -> cumulative counter it increments (the RunSupervisor shape)
_COUNTER_FOR = {
    "census": "censuses",
    "barrier_timeout": "barrier_timeouts",
    "failure": "failures",
    "drain": "drains",
    "reform": "reforms",
    "resume": "resumes",
}

# message fingerprints of a dead/dying coordination channel — the errors
# the KV client raises once the coordinator process is gone (gRPC status
# names + the coordination agent's own state strings)
_CHANNEL_PATTERNS = (
    "coordination service",
    "coordination agent",
    "coordinator",
    "unavailable",
    "connection reset",
    "connection refused",
    "connection closed",
    "broken pipe",
    "socket closed",
    "failed to connect",
    "shutting down",
)


class CollectiveDeadlineError(RuntimeError):
    """A supervised pod collective exceeded its wall-clock deadline —
    some peer never entered (or never left) the lockstep dispatch. The
    cross-process twin of :class:`~evox_tpu.workflows.supervisor.
    DispatchDeadlineError`; ``classify_error`` folds it into the
    ``deadline`` class, and the pod supervisor refines it via the
    heartbeat census."""


class PodFailureError(RuntimeError):
    """The pod supervisor diagnosed a pod-domain fault. ``classification``
    is one of :data:`POD_FAILURE_CLASSES`; ``post_mortem`` is the
    structured account (entry point, census, detection latency, event
    tail) every process writes out before aborting — the input to the
    re-formation driver's survivor decision. ``classify_error`` reads it
    as ``fatal``: no single process can heal a pod fault in-process."""

    def __init__(self, message: str, classification: str, post_mortem: dict):
        super().__init__(message)
        self.classification = classification
        self.post_mortem = post_mortem


def _watchdog_call(
    fn: Callable,
    deadline_s: Optional[float],
    label: str,
    make_timeout: Optional[Callable[[str, float], BaseException]] = None,
    thread_prefix: str = "pod",
):
    """Run ``fn()`` on a disposable daemon thread with a wall-clock bound
    (None = call inline). THE disposable-watchdog implementation — the
    PR-5 dispatch watchdog (workflows/supervisor.py) delegates here with
    its own timeout exception via ``make_timeout``, so the two fault
    domains share one body. A hung call occupies its thread forever, so
    the thread is abandoned, never pooled; spawn cost is noise next to
    any cross-host collective or dispatch chunk."""
    if deadline_s is None:
        return fn()
    box: dict = {}
    done = threading.Event()

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(
        target=target, daemon=True, name=f"{thread_prefix}:{label}"
    )
    t.start()
    if not done.wait(deadline_s):
        if make_timeout is not None:
            raise make_timeout(label, deadline_s)
        raise CollectiveDeadlineError(
            f"pod collective '{label}' exceeded its {deadline_s:g} s "
            "deadline; the worker thread is abandoned (a lockstep "
            "collective with a missing peer never completes)"
        )
    if "error" in box:
        raise box["error"]
    return box["value"]


def _is_channel_error(exc: BaseException) -> bool:
    if isinstance(exc, ConnectionError):
        return True
    msg = str(exc).lower()
    return any(p in msg for p in _CHANNEL_PATTERNS)


class PodSupervisor:
    """Per-process liveness, collective deadlines, coordinated drain, and
    shrink-and-resume for one ``jax.distributed`` pod member.

    Args:
        deadline_s: wall-clock bound for supervised collective points
            (chunk dispatches, pod checkpoint gathers) and the default
            barrier timeout. ``None`` disables the watchdog (barriers
            keep the ``process_barrier`` default).
        heartbeat_interval_s: KV heartbeat period. The census probe
            waits ``2 × interval + 0.2 s`` between its two reads, so
            detection latency after a deadline hit is roughly
            ``deadline_s + 2 × interval``.
        journal: a :class:`~evox_tpu.workflows.journal.RunJournal`, a
            directory path for one, or ``None``. Membership transitions
            are appended as ``pod_*`` records by PROCESS 0 only (the
            single-writer WAL discipline; a re-formed pod's new process
            0 ADOPTS the chain and continues it).
        epoch: pod formation counter — 0 for the original pod, bumped by
            the re-formation driver for each survivor pod. Namespaces
            the heartbeat/intent keys so a re-formed pod (new
            coordinator, fresh KV store — or a reused one) never reads
            a dead epoch's records.
        namespace: KV prefix for heartbeat / drain-intent / decision
            keys.
        clock: monotonic seconds source (``time.perf_counter`` — the
            recorder/supervisor clock, so trace tracks align).

    Single-process (or ``jax.distributed`` not initialized) every method
    degrades to its local meaning: census is ``{0: True}``, barriers and
    drain arbitration are local, ``supervised`` keeps only the watchdog.
    That is what the in-process 8→4 shrink-resume analog in
    tests/test_pod_supervisor.py drives on the virtual mesh.
    """

    def __init__(
        self,
        deadline_s: Optional[float] = None,
        checkpoint_deadline_s: Optional[float] = None,
        heartbeat_interval_s: float = 0.5,
        journal: Any = None,
        epoch: int = 0,
        namespace: str = "evox_tpu/pod",
        clock: Callable[[], float] = time.perf_counter,
        metrics: Any = None,
    ):
        if heartbeat_interval_s <= 0:
            raise ValueError(
                f"heartbeat_interval_s must be > 0, got {heartbeat_interval_s}"
            )
        self.deadline_s = deadline_s
        # a pod checkpoint save legitimately outlasts a chunk dispatch
        # (full host gather + pickle + fsync vs a single compiled
        # chunk), so watchdogging it
        # with the chunk deadline would abort a HEALTHY pod at every
        # cadence. Default: 6× the chunk deadline; a dead peer mid-save
        # is usually caught earlier anyway by the save's own commit
        # barrier (WorkflowCheckpointer.barrier_timeout_s, classified)
        self.checkpoint_deadline_s = (
            checkpoint_deadline_s
            if checkpoint_deadline_s is not None
            else (6.0 * deadline_s if deadline_s is not None else None)
        )
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.epoch = int(epoch)
        self.namespace = f"{namespace}/e{self.epoch}"
        # serving-plane flight recorder (PR 16): when attached, every
        # pod transition mirrors into the metrics plane (`pod.*`
        # counters, heartbeat-publish latency histogram), pod barriers
        # stamp stream `barrier` records (the merged-trace alignment
        # anchors), and failures carry the black-box ring in their
        # post-mortem. None (default) changes nothing.
        self.metrics = metrics
        self._clock = clock
        self._created = clock()
        try:
            # runtime-state read, not a backend touch (a supervisor may
            # be built before any device work — see _dist_process_info)
            from .distributed import _dist_process_info

            self.process_id, self.process_count = _dist_process_info()
        except Exception:  # pragma: no cover - backend not initializable
            self.process_id, self.process_count = 0, 1
        # in a REAL multi-process pod, jaxlib's own
        # coordination client LOG(FATAL)s the process ~10 s after it
        # stops reaching the coordinator — a supervisor deadline whose
        # worst-case detection latency (deadline + census probe slack)
        # cannot beat that race yields SIGABRT post-mortems instead of
        # classified failures. Validate at construction: clamp + warn
        # rather than silently losing the race. Single-process
        # supervisors (tests, local watchdogs) have no coordination
        # client, so any deadline is fine there.
        if (
            self.deadline_s is not None
            and self.process_count > 1
        ):
            slack = 2.0 * self.heartbeat_interval_s + 0.2  # census probe
            budget = JAXLIB_COORD_ABORT_S - _COORD_ABORT_MARGIN_S - slack
            if self.deadline_s > budget:
                clamped = max(budget, self.heartbeat_interval_s)
                warnings.warn(
                    f"PodSupervisor deadline_s={self.deadline_s} cannot "
                    f"win the race against jaxlib's ~{JAXLIB_COORD_ABORT_S:g} s "
                    "coordination heartbeat abort: "
                    f"detection needs deadline + {slack:.1f} s census "
                    f"slack; clamping to {clamped:.2f} s so pod faults "
                    "are classified instead of dying by SIGABRT",
                    stacklevel=2,
                )
                self.deadline_s = clamped
                if checkpoint_deadline_s is None:
                    self.checkpoint_deadline_s = 6.0 * clamped
        self._journal = self._resolve_journal(journal)
        self._hb_seq = 0
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._drain_flag = threading.Event()
        self._drain_reason: Optional[str] = None
        self._drain_event_recorded = False
        self._prev_boundary_gen: Optional[int] = None
        self._prev_sigterm: Any = None
        self._lock = threading.Lock()
        self._outcome: Optional[str] = None
        self.events: List[dict] = []
        self.counters: Dict[str, int] = {
            "heartbeats": 0,
            "censuses": 0,
            "barriers": 0,
            "barrier_timeouts": 0,
            "supervised_calls": 0,
            "failures": 0,
            "drains": 0,
            "reforms": 0,
            "resumes": 0,
        }

    # ------------------------------------------------------------- plumbing
    def _resolve_journal(self, journal: Any):
        if journal is None:
            return None
        if isinstance(journal, (str, bytes)) or hasattr(journal, "__fspath__"):
            from ..workflows.journal import RunJournal  # deferred (layering)

            return RunJournal(str(journal))
        return journal

    def _client(self):
        client = _dist_client()
        if client is _INTROSPECT_FAILED or self.process_count <= 1:
            return None
        return client

    def _event(self, kind: str, **fields: Any) -> None:
        assert kind in POD_EVENT_KINDS, kind
        ev = {"t": round(self._clock() - self._created, 6), "event": kind}
        ev.update(fields)
        with self._lock:
            self.events.append(ev)
            counter = _COUNTER_FOR.get(kind)
            if counter is not None:
                self.counters[counter] += 1
        if self.metrics is not None:
            self.metrics.count(f"pod.{kind}")

    def _journal_event(self, kind: str, **payload: Any) -> None:
        """WAL the transition (process-0-writes). A journal append
        failing must never mask the event being journaled — the run's
        own failure path is usually already unwinding."""
        if self._journal is None or self.process_id != 0:
            return
        try:
            self._journal.append(
                kind, epoch=self.epoch, process_id=self.process_id, **payload
            )
        except Exception:  # pragma: no cover - disk-full etc.
            pass

    # ----------------------------------------------------------- heartbeats
    def start(self) -> "PodSupervisor":
        """Join the pod: record membership, start the heartbeat thread.
        Idempotent — a second call neither duplicates the join
        event/WAL record nor spawns a second beater; returns self so
        ``PodSupervisor(...).start()`` chains."""
        if self._hb_thread is None or not self._hb_thread.is_alive():
            self._event(
                "join",
                process_id=self.process_id,
                process_count=self.process_count,
                epoch=self.epoch,
            )
            self._journal_event(
                "pod_join", process_count=self.process_count
            )
            self._hb_stop.clear()
            self.beat()  # first beat lands before any peer can census us
            self._hb_thread = threading.Thread(
                target=self._beat_loop, daemon=True, name="pod:heartbeat"
            )
            self._hb_thread.start()
        return self

    def stop(self) -> None:
        """Stop the heartbeat thread (idempotent; the key simply stops
        advancing, which is exactly what a census reads as death — a
        clean exit should barrier first, not rely on this)."""
        self._hb_stop.set()
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except (ValueError, OSError):  # pragma: no cover - non-main thread
                pass
            self._prev_sigterm = None

    def beat(self) -> int:
        """Advance and publish this process's heartbeat counter once."""
        self._hb_seq += 1
        self.counters["heartbeats"] += 1
        client = self._client()
        if client is not None:
            # overwrite-in-place: one key per member per epoch, no growth
            t0 = self._clock()
            client.key_value_set(
                f"{self.namespace}/hb/{self.process_id}",
                str(self._hb_seq),
                allow_overwrite=True,
            )
            if self.metrics is not None:
                # heartbeat PUBLISH latency: the KV round-trip each beat
                # pays — the earliest coordination-plane distress signal
                # (it climbs before collectives start timing out)
                self.metrics.observe(
                    "pod.heartbeat_ms", (self._clock() - t0) * 1e3
                )
        return self._hb_seq

    #: consecutive failed beats before the heartbeat thread gives up —
    #: ONE transient KV blip must not freeze a healthy member's counter
    #: (a frozen counter reads as worker_dead in every peer's census)
    _HB_MAX_CONSECUTIVE_FAILURES = 5

    def _beat_loop(self) -> None:
        failures = 0
        while not self._hb_stop.wait(self.heartbeat_interval_s):
            try:
                self.beat()
                failures = 0
            except Exception:
                # transient blip: keep beating (the same tolerance
                # classify_failure applies to channel errors). Only a
                # PERSISTENT failure — the coordinator is really gone —
                # ends the loop; even then the MAIN thread classifies
                # at its next collective point with a census — a
                # heartbeat thread must never decide the process's fate
                failures += 1
                if failures >= self._HB_MAX_CONSECUTIVE_FAILURES:
                    return

    def _read_heartbeats(self) -> Dict[int, int]:
        client = self._client()
        if client is None:
            return {self.process_id: self._hb_seq}
        return {
            int(k.rsplit("/", 1)[-1]): int(v)
            for k, v in client.key_value_dir_get(f"{self.namespace}/hb/")
        }

    def census(self, probe_s: Optional[float] = None) -> Dict[int, bool]:
        """Who is alive? Two KV reads separated by ``probe_s`` (default
        ``2 × heartbeat_interval + 0.2 s``): a member whose sequence
        counter advanced between them is alive; one whose counter is
        frozen (SIGKILL, SIGSTOP, machine gone) or absent is not. No
        cross-host clock is compared — the counter IS the liveness
        signal. Raises whatever the KV channel raises when the
        coordinator itself is gone (callers classify that as
        :data:`COORDINATOR_LOSS`)."""
        if self._client() is None:
            alive = {self.process_id: True}
        else:
            probe = (
                2.0 * self.heartbeat_interval_s + 0.2
                if probe_s is None
                else probe_s
            )
            first = self._read_heartbeats()
            if probe > 0:
                time.sleep(probe)
            second = self._read_heartbeats()
            alive = {}
            for p in range(self.process_count):
                if p == self.process_id:
                    alive[p] = True
                    continue
                s0, s1 = first.get(p), second.get(p)
                alive[p] = s0 is not None and s1 is not None and s1 > s0
        self._event(
            "census",
            alive=sorted(p for p, a in alive.items() if a),
            dead=sorted(p for p, a in alive.items() if not a),
        )
        return alive

    # --------------------------------------------------------- classification
    def classify_failure(self, exc: BaseException) -> Optional[str]:
        """Refine ``exc`` into a pod-domain class, or ``None`` when it is
        not a pod fault (a numerics error, an OOM — the caller's own
        ladder owns those). Deadlines (collective or barrier) consult
        the census: a frozen peer ⇒ :data:`WORKER_DEAD`, everyone alive
        ⇒ :data:`HUNG_COLLECTIVE`; a dead KV channel anywhere ⇒
        :data:`COORDINATOR_LOSS`."""
        if isinstance(exc, PodFailureError):
            return exc.classification
        deadline = isinstance(
            exc, (CollectiveDeadlineError, BarrierTimeoutError)
        )
        if not deadline and not _is_channel_error(exc):
            return None
        try:
            alive = self.census()
        except Exception:
            return COORDINATOR_LOSS
        dead = [p for p, a in alive.items() if not a]
        if dead:
            return WORKER_DEAD
        if deadline:
            return HUNG_COLLECTIVE
        # channel error but the census works and everyone is alive: a
        # transient RPC blip, not a pod fault — let the caller retry
        return None

    def _fail(
        self, entry: str, exc: BaseException, t0: float
    ) -> PodFailureError:
        classification = self.classify_failure(exc)
        if classification is None:
            raise exc
        detect_s = round(self._clock() - t0, 6)
        census_ev = next(
            (e for e in reversed(self.events) if e["event"] == "census"), None
        )
        self._event(
            "failure",
            entry=entry,
            classification=classification,
            detect_s=detect_s,
            error=str(exc)[:300],
        )
        self._outcome = "failed"
        post_mortem = {
            "entry": entry,
            "classification": classification,
            "detect_s": detect_s,
            "error": f"{type(exc).__name__}: {exc}",
            "census": (
                {k: v for k, v in census_ev.items() if k in ("alive", "dead")}
                if census_ev
                else None
            ),
            "epoch": self.epoch,
            "process_id": self.process_id,
            "process_count": self.process_count,
            "events_tail": self.events[-20:],
        }
        if self.metrics is not None:
            # every pod post-mortem carries the flight-recorder tail:
            # the last queue/executor/pod records before the fault,
            # recoverable from the surviving stream even if this
            # process dies before the error is printed
            self.metrics.event(
                "pod.failure", entry=entry, classification=classification
            )
            post_mortem["flight_recorder"] = self.metrics.tail(20)
        self._journal_event(
            "pod_failure",
            entry=entry,
            classification=classification,
            detect_s=detect_s,
        )
        return PodFailureError(
            f"pod fault at '{entry}': {classification} "
            f"(detected in {detect_s:g} s): {type(exc).__name__}: {exc}",
            classification=classification,
            post_mortem=post_mortem,
        )

    # ------------------------------------------------------ collective points
    def supervised(
        self,
        fn: Callable[[], Any],
        entry: str = "collective",
        deadline_s: Optional[float] = None,
    ) -> Any:
        """Run one SPMD-lockstep collective point (a pod chunk dispatch,
        a checkpoint gather) under the disposable-watchdog deadline.
        A deadline hit or a dead coordination channel is classified
        through the census and raised as :class:`PodFailureError` with a
        full post-mortem; any other failure propagates untouched (the
        PR-5 ladder, numerics guards, etc. own those)."""
        dl = self.deadline_s if deadline_s is None else deadline_s
        self.counters["supervised_calls"] += 1
        t0 = self._clock()
        try:
            return _watchdog_call(fn, dl, entry)
        except (KeyboardInterrupt, SystemExit):
            raise
        except PodFailureError:
            raise
        except BaseException as e:  # noqa: BLE001 — classified below
            raise self._fail(entry, e, t0) from e

    def barrier(self, name: str, timeout_s: Optional[float] = None) -> None:
        """A classified :func:`~evox_tpu.core.distributed.process_barrier`:
        the timeout (default ``deadline_s``) raises through the census as
        a :class:`PodFailureError` naming the missing processes."""
        tmo = timeout_s if timeout_s is not None else self.deadline_s
        self.counters["barriers"] += 1
        t0 = self._clock()
        try:
            if tmo is None:
                process_barrier(name)
            else:
                process_barrier(name, timeout_s=tmo)
            if self.metrics is not None:
                # a REAL pod rendezvous just released: every member
                # stamps the same barrier name into its own stream at
                # (approximately) the same instant — the clock-alignment
                # anchor merge_pod_streams aligns the per-process trace
                # tracks on
                self.metrics.barrier(
                    f"pod:{name}",
                    wait_ms=round((self._clock() - t0) * 1e3, 3),
                )
        except (KeyboardInterrupt, SystemExit):
            raise
        except BarrierTimeoutError as e:
            self._event(
                "barrier_timeout",
                name=name,
                missing=list(e.missing),
                arrived=list(e.arrived),
            )
            raise self._fail(f"barrier:{name}", e, t0) from e
        except Exception as e:  # channel death inside the barrier RPC
            raise self._fail(f"barrier:{name}", e, t0) from e

    # ------------------------------------------------------------------ drain
    def install_sigterm_drain(self) -> None:
        """Route SIGTERM (the cloud preemption notice) into the
        coordinated drain: the handler only sets a flag — the in-flight
        chunk finishes, the next :meth:`chunk_boundary` arbitrates the
        pod-wide decision, the driver writes a final barrier checkpoint
        and exits 0. Must be called from the main thread (CPython signal
        rule); the previous handler is restored by :meth:`stop`."""
        self._prev_sigterm = signal.signal(
            signal.SIGTERM, lambda signum, frame: self.request_drain("SIGTERM")
        )

    def request_drain(self, reason: str = "api") -> None:
        """Ask the pod to drain at the next chunk boundary (signal-safe:
        only sets a flag; the KV publication happens on the main thread
        inside :meth:`chunk_boundary`)."""
        self._drain_flag.set()
        self._drain_reason = reason

    def drain_requested(self) -> bool:
        return self._drain_flag.is_set()

    def chunk_boundary(
        self, generation: int, timeout_s: Optional[float] = None
    ) -> str:
        """The per-chunk rendezvous: every member publishes its drain
        intent, passes the classified barrier, and PROCESS 0 arbitrates
        one pod-wide decision through the KV store — ``"continue"`` or
        ``"drain"``. Arbitration is what keeps the decision SPMD-
        consistent: a SIGTERM landing between two members' flag reads
        must not let one drain while the other walks into a collective
        nobody will join. Single-process the decision is the local flag."""
        gen = int(generation)
        client = self._client()
        if client is None:
            decision = "drain" if self._drain_flag.is_set() else "continue"
        else:
            ns = self.namespace
            t0 = self._clock()
            try:
                client.key_value_set(
                    f"{ns}/intent/{gen}/{self.process_id}",
                    "drain" if self._drain_flag.is_set() else "ok",
                    allow_overwrite=True,
                )
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                raise self._fail(f"boundary:{gen}", e, t0) from e
            self.barrier(f"{ns}/gen{gen}", timeout_s)
            tmo = timeout_s if timeout_s is not None else self.deadline_s
            tmo_ms = int((tmo if tmo is not None else 120.0) * 1000)
            try:
                if self.process_id == 0:
                    intents = client.key_value_dir_get(f"{ns}/intent/{gen}/")
                    decision = (
                        "drain"
                        if any(v == "drain" for _, v in intents)
                        else "continue"
                    )
                    client.key_value_set(
                        f"{ns}/decision/{gen}", decision, allow_overwrite=True
                    )
                    # KV hygiene (the process_barrier arrival-record
                    # discipline): this gen's intents are consumed, and
                    # by reaching THIS barrier every member has read the
                    # PREVIOUS boundary's decision — long pod runs must
                    # not accrete nprocs+1 keys per chunk forever.
                    # Best-effort: cleanup failure must never fail a
                    # healthy boundary
                    try:
                        for k, _ in intents:
                            client.key_value_delete(k)
                        if self._prev_boundary_gen is not None:
                            client.key_value_delete(
                                f"{ns}/decision/{self._prev_boundary_gen}"
                            )
                    except Exception:
                        pass
                    self._prev_boundary_gen = gen
                else:
                    decision = client.blocking_key_value_get(
                        f"{ns}/decision/{gen}", tmo_ms
                    )
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                raise self._fail(f"decision:{gen}", e, t0) from e
        if decision == "drain" and not self._drain_event_recorded:
            self._drain_event_recorded = True
            self._event(
                "drain_requested",
                generation=gen,
                reason=self._drain_reason or "peer",
            )
        return decision

    def note_integrity_dissent(
        self, generation: int, entry: str = "verify", dissent: str = "first"
    ) -> None:
        """Record that a 2-of-3 integrity vote outvoted THIS pod's chunk
        result (``dissent`` names which dispatch lost: ``"first"`` — the
        original chunk, ``"redo"`` — the re-dispatch). The result was
        already discarded by the voter, so nothing is raised: the pod
        stays schedulable but carries the ``integrity_dissent`` failure
        event for the re-formation driver / fleet health policy to act
        on (the same census-driven quarantine lane as a dead worker)."""
        self._event(
            "failure",
            entry=entry,
            classification=INTEGRITY_DISSENT,
            generation=int(generation),
            dissent=dissent,
        )
        if self.metrics is not None:
            self.metrics.event(
                "pod.failure", entry=entry, classification=INTEGRITY_DISSENT
            )
        self._journal_event(
            "pod_failure",
            entry=entry,
            classification=INTEGRITY_DISSENT,
            generation=int(generation),
            dissent=dissent,
        )

    def note_drained(self, generation: int, checkpointed: bool = True) -> None:
        """Record the completed drain: the driver exits 0 after this —
        preemption became a clean stop. ``checkpointed=False`` records
        honestly that NO final barrier snapshot exists (the run had no
        checkpointer) — the resumed-equals-uninterrupted law then has
        nothing to resume from, and the event/journal record says so
        instead of implying a durable snapshot that was never written."""
        self._event(
            "drain", generation=int(generation), checkpointed=bool(checkpointed)
        )
        self._journal_event(
            "pod_drain",
            generation=int(generation),
            checkpointed=bool(checkpointed),
        )
        self._outcome = "drained"

    # ------------------------------------------------------------ re-formation
    def note_reform(self, survivors: Sequence[int], from_epoch: int) -> None:
        """Record that THIS pod is the re-formation of ``from_epoch`` on
        the ``survivors`` process set (called by the re-formed member,
        normally with the driver-provided survivor list)."""
        self._event(
            "reform",
            survivors=sorted(int(p) for p in survivors),
            from_epoch=int(from_epoch),
            epoch=self.epoch,
        )
        self._journal_event(
            "pod_reform",
            survivors=sorted(int(p) for p in survivors),
            from_epoch=int(from_epoch),
        )

    def resume_from_barrier(
        self,
        wf: Any,
        checkpointer: Any,
        expect_like: Any = None,
        allow_config_mismatch: bool = False,
    ) -> Any:
        """Restore the newest intact pod-barrier snapshot onto the
        CURRENT (re-formed, possibly shrunken) topology and record the
        resume. ``checkpointer`` is a
        :class:`~evox_tpu.workflows.checkpoint.WorkflowCheckpointer` or
        its directory; placement follows the state's own sharding
        annotations on ``wf.mesh`` (``wf.place_restored`` when the
        workflow defines it — tenant fleets), exactly the PR-5
        topology-portable resume law, now driven by the pod ladder.
        Raises ``RuntimeError`` when no intact snapshot exists (the
        re-formation driver treats that as unrecoverable)."""
        from ..workflows.checkpoint import _as_checkpointer, restore_layouts

        ckpt = _as_checkpointer(checkpointer)
        snapshot = ckpt.latest(
            expect_like=expect_like,
            allow_config_mismatch=allow_config_mismatch,
        )
        if snapshot is None:
            raise RuntimeError(
                f"resume_from_barrier: no intact pod-barrier snapshot in "
                f"{ckpt.directory} — nothing to re-form from"
            )
        placer = getattr(wf, "place_restored", None)
        if placer is not None:
            state = placer(snapshot)
        else:
            state = restore_layouts(snapshot, mesh=getattr(wf, "mesh", None))
        gen = int(snapshot.generation)
        self._event("resume", generation=gen)
        self._journal_event("pod_resume", generation=gen)
        self._outcome = "resumed"
        return state

    # ------------------------------------------------------------------ report
    def report(self) -> dict:
        """The ``pod_supervisor`` section of ``run_report()`` (schema v9,
        strict JSON). ``outcome``: ``clean`` (nothing fired),
        ``drained`` (graceful preemption stop), ``failed`` (pod fault
        diagnosed, post-mortem written), ``resumed`` (this pod re-formed
        and restored a barrier snapshot)."""
        return {
            "process_id": self.process_id,
            "process_count": self.process_count,
            "epoch": self.epoch,
            "deadline_s": self.deadline_s,
            "checkpoint_deadline_s": self.checkpoint_deadline_s,
            "heartbeat_interval_s": self.heartbeat_interval_s,
            "outcome": self._outcome or "clean",
            "counters": dict(self.counters),
            "events": list(self.events),
        }

    def markers(self) -> List[dict]:
        """Events as ``supervisor:pod:*`` instant markers for
        :func:`~evox_tpu.core.instrument.write_chrome_trace` (same
        ``perf_counter`` clock as the recorder)."""
        return [
            {
                "t_abs": self._created + ev["t"],
                "name": f"supervisor:pod:{ev['event']}",
                "args": {
                    k: v for k, v in ev.items() if k not in ("t", "event")
                },
            }
            for ev in self.events
        ]

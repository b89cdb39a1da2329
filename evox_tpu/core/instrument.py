"""Host-side dispatch instrumentation for workflow entry points.

The device half of observability (TelemetryMonitor) lives inside the
jitted step; this module is the host half. It wraps a workflow's jitted
entry points (``init`` / ``step`` / ``run`` / ``pipeline_ask`` /
``pipeline_tell``) with plain wall-clock timing *around the dispatch* —
never inside traced code, so it is safe on every backend and adds
nothing to the compiled programs.

Semantics under JAX's async dispatch: a warm call returns once the work
is *dispatched*, so its duration is the host-side dispatch cost. The
first call of an entry point
additionally pays trace + compile, which dominates it — the summary
reports that first call separately (``first_call_s``) plus an estimated
``compile_s`` (first call minus the steady-state median) alongside the
steady-state dispatch statistics. Host fetches go through
:meth:`DispatchRecorder.fetch`, which accounts bytes and seconds per
fetch site (a big-array fetch costs transfer time).

Beyond timing, the recorder is the host half of the roofline analytics
layer (core/xla_cost.py):

- **Work-normalized timing**: each call carries a work count (``run``'s
  ``n_steps``; 1 elsewhere). When an entry was called at two distinct
  trip counts, the per-generation time is the *differenced slope*
  ``(t(n2) - t(n1)) / (n2 - n1)``, in which the per-dispatch latency
  cancels — otherwise the steady median is used and flagged
  ``latency_confounded`` (a single-trip-count timing still contains the
  whole per-dispatch round-trip).
- **Retrace detection**: every call's abstract argument signature is
  recorded. A new *aval* signature (leaf shapes/dtypes changed) after an
  entry's first call is the classic silent TPU perf killer — flagged in
  the summary (``retrace_flags``) and escalated to :class:`RetraceError`
  under ``DispatchRecorder(strict_retrace=True)``. Static-only structure
  changes (e.g. the designed ``first_step`` peel recompile) are counted
  separately and never flagged.
- **Span recording**: every timed call and fetch keeps its
  ``(start, duration)`` so :func:`write_chrome_trace` can export the run
  as a Chrome trace-event JSON timeline (Perfetto / chrome://tracing),
  with TelemetryMonitor rings and farm health counters as counter tracks.

The module also holds the one table of names the program writes, always:
``scope`` (``jax.named_scope``: the layer each device operation belongs to,
in its ``op_name``) and ``span``, the one host primitive. A ``span`` writes
twice: a ``jax.profiler.TraceAnnotation`` (the entry points' host time on
the device trace's clock, kept only while a profiler session is on) and one
record of the process's **host log**, kept whether a session is on or not:
a bounded in-memory ring on ``time.perf_counter_ns``, read with
:func:`host_records` and :func:`host_summary`. The log also takes what jax
says it traced, lowered and compiled (one ``jax.monitoring`` listener), each
under the entry point that was open when it happened. So the program has
two host clocks that are one: the recorder's bookkeeping runs on
``time.perf_counter`` (seconds), the host log on the same clock in
nanoseconds, and the two can be overlaid; the device does not share it.
``write_chrome_trace`` is the recorder's timeline for runs without the
profiler, the profiler's trace the one on the device's clock, and a reader
that holds both an ``evox:run`` record and its trace span (the benchmark's
``lib/hostlog.py``) places the log on the trace's clock by their offset.

``run_report`` merges this host-side summary with the device counters of
any attached monitor exposing ``report(mstate)`` (TelemetryMonitor) into
one JSON-serializable dict — plus, when a :class:`~evox_tpu.core.
xla_cost.CostAnalyzer` is attached (``instrument(wf, analyze=True)``), a
``roofline`` section attributing each entry point compute-bound /
memory-bound / dispatch-bound; ``write_report_jsonl`` appends it to a
JSON-lines file.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import math
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from .xla_cost import CostAnalyzer, abstract_signature, roofline_section

__all__ = [
    "SCOPES",
    "SPANS",
    "LOG_ONLY",
    "HOST_LOG_LEN",
    "HostRecord",
    "scope",
    "span",
    "host_records",
    "host_summary",
    "DispatchRecorder",
    "RetraceError",
    "instrument",
    "run_report",
    "sanitize_json",
    "write_chrome_trace",
    "write_report_jsonl",
]


# ---------------------------------------------------------------- names
# The one table of names. Device side: ``scope`` puts a name into the
# ``op_name`` of every HLO operation traced under it, which the profiler
# writes beside each device event (the ``tf_op`` stat). Host side: ``span``
# writes onto the calling thread's line of the profiler's host plane (the
# main thread's is named after the executable), on the device trace's clock,
# and into the host log below. The benchmark's readers (benchmark/lib/
# scoped.py, benchmark/lib/hostlog.py) match these strings, so they are
# written here once and nowhere else.

ASK = "evox.ask"
EVALUATE = "evox.evaluate"
TELL = "evox.tell"
CONSTRAIN = "evox.constrain"
MONITORS = "evox.monitors"
# parts, as children of the above, where a cell's trace shows device time
DECODE = "decode"  # evaluate: pop_transforms, the flat genome cut into layers
LAYOUT = "layout"  # evaluate: the layers transposed to the kernel's planes
RESET = "reset"  # evaluate: env reset, its keys and the broadcast
ROLLOUT_KERNEL = "rollout_kernel"  # evaluate: the call into the Pallas rollout
FIT_TRANSFORMS = "fit_transforms"  # tell: the workflow's fitness shaping
NOISE = "noise"  # ask (ES): the normal draw and its mirrored concatenation
PERTURB = "perturb"  # ask (ES): centre plus sigma times noise
GRADIENT = "gradient"  # tell (ES): the noise drawn again and the contraction
UPDATE = "update"  # tell (ES): the optimiser's step
CAST = "cast"  # ask (low-rank ES): the centre's matrices cast for the forward pass
# evaluate (the token language model, problems/lm): the parts of its forward pass
LM_FORWARD = "lm/forward"  # the whole pass: what no part below names (the batch, the loop over chunks of pairs)
LM_EMBED = "lm/embed"  # the rows gathered
LM_ATTENTION = "lm/attention"  # the attention layers (MLA, or grouped-query with QK norm): norms, projections, RoPE, scores, softmax, output
LM_CONV = "lm/conv"  # the gated short convolution layers: norm, input projection, gates, taps, output projection
LM_KDA = "lm/kda"  # the KDA layers: norm, projections, convolutions and L2 norms (the kda_conv kernel on the chip), gates, output norm and gate, output
LM_KDA_SCAN = "lm/kda_scan"  # inside lm/kda: the delta rule's recurrence over the row, kernel or XLA body
LM_MLP = "lm/mlp"  # the dense layer's MLP and the shared experts'
LM_ROUTER = "lm/router"  # scores, top-k, the sort, tokens gathered and put back
LM_EXPERTS = "lm/experts"  # the held experts' grouped products
LM_LOWRANK = "lm/lowrank"  # every member's (x A) B^T, wherever it is added
LM_HEAD_LOSS = "lm/head_loss"  # final norm, head, log-likelihood
MATING = "mating"  # ask (GA): tournament selection
CROSSOVER = "crossover"  # ask (GA)
MUTATION = "mutation"  # ask (GA)
MERGE = "merge"  # tell (MO): parents and offspring concatenated
DOMINANCE_BUILD = "dominance_build"  # tell (MO): the packed dominance matrix
PEEL = "peel"  # tell (MO): the front-peeling loop
CROWDING = "crowding"  # tell (MO): crowding distance
SURVIVORS = "survivors"  # tell (MO): the truncation's sort and gathers

SCOPES = (ASK, EVALUATE, TELL, CONSTRAIN, MONITORS)

RUN = "evox:run"
RUN_PEEL = "evox:run/peel"  # the eager first wf.step, when it happens
RUN_LOOP = "evox:run/loop"  # the trip count's transfer and the loop's dispatch
STEP = "evox:step"
INIT = "evox:init"
CHECKPOINT_SAVE = "evox:checkpoint/save"
HOST_EVAL = "evox:executor/host_eval"
FETCH = "evox:fetch"

SPANS = (RUN, RUN_PEEL, RUN_LOOP, STEP, INIT, CHECKPOINT_SAVE, HOST_EVAL, FETCH)

# Names the host log alone carries (``span(name, annotate=False)`` and the
# compile listener): the accepted benchmark compares the set of ``evox:``
# annotations the entry points write with an exact table, so these wait for
# a ``benchmark`` issue before they may be profiler spans too (ROADMAP B8).
RUN_TRIP_COUNT = "evox:run/trip_count"  # in evox:run/loop: n_steps made a device scalar (a little program and a transfer)
RUN_DISPATCH = "evox:run/dispatch"  # in evox:run/loop: the run loop's jitted call; ``cpu_ns`` is the calling thread's CPU time across it
COMPILE_TRACE = "evox:compile/trace"  # jax traced a function to a jaxpr (``fun_name``); traces under a millisecond are left out
COMPILE_LOWER = "evox:compile/lower"  # jaxpr to MLIR module; a Pallas kernel's Mosaic lowering happens here
COMPILE_BACKEND = "evox:compile/backend"  # the backend compiled the module, or took it from the persistent cache
COMPILE_CACHE_HIT = "evox:compile/cache_hit"  # the persistent cache held the module (an instant inside its backend record)

LOG_ONLY = (
    RUN_TRIP_COUNT, RUN_DISPATCH, COMPILE_TRACE, COMPILE_LOWER, COMPILE_BACKEND, COMPILE_CACHE_HIT,
)


class scope(contextlib.ContextDecorator):
    """Name the device operations traced inside the block, or inside the
    function it decorates: a ``jax.named_scope``, which lands in each
    operation's ``op_name`` and changes no instruction. Scopes nest into
    a path (``evox.tell/peel``); under ``vmap``/``shard_map``/``remat``
    jax wraps a component (``vmap(evox.ask)``)."""

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self):
        # as a decorator: a fresh scope each call (jax's keeps the name
        # stack it replaced on the instance, so one instance is not
        # re-entrant)
        return jax.named_scope(self.name)

    def __enter__(self):
        self._cm = jax.named_scope(self.name)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)


# ------------------------------------------------------------- host log
# One ring for the process. A record is appended when its span closes (a
# compile's when jax reports it), so a parent follows its children in the
# ring and ``id`` order is the order in which records were opened.

HOST_LOG_LEN = 8192  # a cell's set-up is some 500 records (three a program), a 30 s window four a chunk


class HostRecord(NamedTuple):
    """One closed stretch of host time. ``parent`` is the ``id`` of the
    innermost record that was open on the same thread when this one opened
    (0: none), so the records of one ``run`` call share its ``evox:run``
    record as root. Times are ``time.perf_counter_ns``."""

    id: int
    parent: int
    name: str
    start_ns: int
    end_ns: int
    thread: int
    args: dict


class _OpenRecords(threading.local):
    """The calling thread's open records, outermost first, as ``(id, name)``."""

    def __init__(self):
        self.stack: list = []


_host_log: collections.deque = collections.deque(maxlen=HOST_LOG_LEN)
_next_id = itertools.count(1)  # ``next`` on it is one bytecode: no lock
_open = _OpenRecords()
# jax reports every function it traces, jnp's own too (``add``, ``multiply``:
# thousands a program, 5 to 400 us each, nested in the trace of the function
# that calls them): logged, they would roll a process's set-up out of the
# ring. A program's own functions take milliseconds to seconds to trace
_TRACE_FLOOR_S = 1e-3
_JAX_EVENTS = {  # the three with a length, and one instant
    "/jax/core/compile/jaxpr_trace_duration": COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE_BACKEND,
    "/jax/compilation_cache/cache_hits": COMPILE_CACHE_HIT,
}


class span:
    """Mark a stretch of host time (``with span(RUN, n_steps=n):``). The one
    host primitive, and it always writes twice: a
    ``jax.profiler.TraceAnnotation`` on the profiler's host plane, on the
    device trace's clock, which records only while a profiler session is on;
    and, on exit, one :class:`HostRecord` of the host log, session or no
    session, with no switch. ``annotate=False`` is the log-only form of the
    same path, for the names of ``LOG_ONLY``. ``with span(...) as s`` hands
    out the span, whose ``args`` the body may add to before it closes
    (``evox:run/dispatch``'s ``cpu_ns``). A ``with`` puts no Python frame
    between the caller and its body. Two clock reads, a push, a pop and an
    append: PERF.md section 6, PR 36 has the cost."""

    __slots__ = ("name", "args", "_annotation", "_id", "_parent", "_start")

    def __init__(self, name: str, annotate: bool = True, **args: Any):
        self.name = name
        self.args = args
        self._annotation = jax.profiler.TraceAnnotation(name, **args) if annotate else None

    def __enter__(self) -> "span":
        stack = _open.stack
        self._parent = stack[-1][0] if stack else 0
        self._id = next(_next_id)
        stack.append((self._id, self.name))
        if self._annotation is not None:
            self._annotation.__enter__()
        # read last, beside the annotation's own start: the two differ by a
        # constant, which is what lets a reader lay the log on the trace
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open.stack.pop()
        # a plain tuple in ``HostRecord``'s order: building the named one
        # here would be a quarter of the span's cost
        _host_log.append(
            (self._id, self._parent, self.name, self._start, end, threading.get_ident(), self.args)
        )


def _on_jax_event(event: str, duration_secs: float = 0.0, **kwargs: Any) -> None:
    """jax's monitoring events into the host log: what it traced, lowered and
    compiled, and each hit of the persistent cache. jax reports on
    ``time.time()`` when the work is over, on the thread that did it: the end
    is stamped here on the log's clock and the start is the end less the
    event's length. The parent is that thread's open record, so the entry
    point a compile fell in is one lookup."""
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    if name == COMPILE_TRACE and duration_secs < _TRACE_FLOOR_S:
        return
    end = time.perf_counter_ns()
    stack = _open.stack
    args = {"fun_name": kwargs["fun_name"]} if "fun_name" in kwargs else {}
    _host_log.append(
        (next(_next_id), stack[-1][0] if stack else 0, name,
         end - int(duration_secs * 1e9), end, threading.get_ident(), args)
    )


# registered once, where the names are: a builder's compiles before the first
# entry point belong to a process's set-up too
jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
jax.monitoring.register_event_listener(_on_jax_event)


def host_records(since_id: int = 0) -> List[HostRecord]:
    """The host log's records opened after record ``since_id`` was (all that
    the ring still holds, for 0), by ``id``. A span appears once it has
    closed. The ring keeps the last ``HOST_LOG_LEN``: a log that long has
    dropped its oldest."""
    return sorted(HostRecord._make(r) for r in tuple(_host_log) if r[0] > since_id)


def host_summary() -> dict:
    """What the host log holds, reduced: ``spans`` maps each name to its
    ``calls``, ``median_ms`` and ``longest_ms``; ``compiles`` lists every
    backend compile (or retrieval from the persistent cache: ``cache_hit``)
    with the function's name, its milliseconds, the record it fell under
    (``under``) and that record's outermost ancestor (``entry_point``:
    ``evox:run``, ``evox:step``, ``evox:init``; None outside every span or
    where the ring has dropped it). A slow chunk or a recompile in a run
    without a profiler session reads here: docs/GUIDE.md."""
    records = host_records()
    by_id = {r.id: r for r in records}
    by_id.update((i, HostRecord(i, 0, n, 0, 0, 0, {})) for i, n in _open.stack)  # still open here
    durations: Dict[str, list] = {}
    for r in records:
        durations.setdefault(r.name, []).append((r.end_ns - r.start_ns) / 1e6)
    hits = [r for r in records if r.name == COMPILE_CACHE_HIT]
    compiles = []
    for r in records:
        if r.name != COMPILE_BACKEND:
            continue
        under = root = by_id.get(r.parent)
        while root is not None and root.parent:
            root = by_id.get(root.parent)
        compiles.append({
            "fun_name": r.args.get("fun_name"),
            "ms": (r.end_ns - r.start_ns) / 1e6,
            "cache_hit": any(
                h.thread == r.thread and r.start_ns <= h.end_ns <= r.end_ns for h in hits
            ),
            "under": under.name if under else None,
            "entry_point": root.name if root else None,
        })
    return {
        "spans": {
            name: {"calls": len(ms), "median_ms": statistics.median(ms), "longest_ms": max(ms)}
            for name, ms in sorted(durations.items())
        },
        "compiles": compiles,
        "records": len(records),
        "full": len(records) >= HOST_LOG_LEN,
    }


def sanitize_json(obj: Any) -> Any:
    """Recursively replace non-finite floats with ``None`` so the result
    is STRICT (RFC 8259) JSON — ``json.dumps`` would otherwise emit bare
    ``Infinity``/``NaN`` tokens that ``jq``/``JSON.parse`` reject. Inf/NaN
    legitimately appear in telemetry (the +inf best before any finite
    generation, inf-padded ring slots of an all-poison generation)."""
    if isinstance(obj, dict):
        return {k: sanitize_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize_json(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj

# bound methods wrapped on the workflow INSTANCE, so instrumentation is
# per-workflow and never leaks into other workflows of the same class
DEFAULT_ENTRY_POINTS = (
    "init",
    "step",
    "run",
    "pipeline_ask",
    "pipeline_tell",
)


class RetraceError(RuntimeError):
    """An instrumented entry point is about to recompile because a call's
    abstract argument signature (leaf shapes/dtypes) changed — raised
    instead of silently paying the compile when
    ``DispatchRecorder(strict_retrace=True)``."""


def _run_work(args: tuple, kwargs: dict) -> int:
    """Work units of a ``run(state, n_steps, ...)`` call. Restart/resume
    drivers may run fewer generations than requested; n_steps is still the
    honest per-call upper bound and exact for plain fused runs."""
    n = kwargs.get("n_steps", args[1] if len(args) > 1 else 1)
    try:
        return max(int(n), 1)
    except (TypeError, ValueError):
        return 1


DEFAULT_WORK_EXTRACTORS: Dict[str, Callable[[tuple, dict], int]] = {
    "run": _run_work,
}


class _EntryStats:
    __slots__ = ("times", "works", "spans", "sigs", "aval_sigs", "retraces")

    def __init__(self) -> None:
        self.times: list = []  # call durations, [0] is the cold call
        self.works: list = []  # work units per call (run: n_steps)
        self.spans: list = []  # (abs_start_s, duration_s, work)
        self.sigs: Dict[str, int] = {}  # full (aval|static) sig -> calls
        self.aval_sigs: Dict[str, int] = {}  # aval sig -> calls
        self.retraces: list = []  # {"call", "kind", "t"} events

    # ------------------------------------------------------------ retrace
    def observe_signature(self, sig: Tuple[str, str], t: float) -> Optional[str]:
        """Record a call's (aval, static) signature; returns the retrace
        kind (``"aval"``/``"static"``) when this call will recompile an
        already-compiled entry, else None. The FIRST signature is the
        initial compile, never a retrace."""
        aval, static = sig
        full = aval + "|" + static
        kind = None
        if self.sigs and full not in self.sigs:
            kind = "aval" if aval not in self.aval_sigs else "static"
            self.retraces.append(
                {"call": len(self.times) + 1, "kind": kind, "t": t}
            )
        self.sigs[full] = self.sigs.get(full, 0) + 1
        self.aval_sigs[aval] = self.aval_sigs.get(aval, 0) + 1
        return kind

    @property
    def aval_retraces(self) -> int:
        return sum(1 for r in self.retraces if r["kind"] == "aval")

    # ------------------------------------------------------------- timing
    def _per_work(self) -> Optional[dict]:
        """Seconds per work unit. Differenced slope over the two extreme
        distinct work counts when available (per-dispatch latency cancels
        exactly); else the steady median divided by
        its median work, flagged latency-confounded. The cold call (index
        0, trace+compile) is excluded whenever warmer data exists."""
        if not self.times:
            return None
        steady = (self.times[1:], self.works[1:]) if len(self.times) > 1 else None
        for source, cold_included in ((steady, False), ((self.times, self.works), True)):
            if source is None:
                continue
            times, works = source
            best: Dict[int, float] = {}
            for w, t in zip(works, times):
                best[w] = min(t, best.get(w, math.inf))
            if len(best) < 2:
                continue
            w1, w2 = min(best), max(best)
            slope = (best[w2] - best[w1]) / (w2 - w1)
            # noise (or a compile inside the smaller-work call) can invert
            # the pair — fall through to the median rather than report it
            if slope > 0:
                out = {
                    "seconds": round(slope, 9),
                    "method": "differenced",
                    "latency_confounded": False,
                    "work_pair": [w1, w2],
                }
                if cold_included:
                    # one end of the slope still contains trace+compile —
                    # warm both trip counts to clear
                    out["cold_call_included"] = True
                return out
        times, works = (self.times, self.works) if steady is None else steady
        med_t = float(np.median(times))
        med_w = max(float(np.median(works)), 1.0)
        return {
            "seconds": round(med_t / med_w, 9),
            "method": "median_per_work",
            # a single trip count cannot cancel the per-dispatch
            # overhead: the rate below under-reports
            "latency_confounded": True,
        }

    def summary(self) -> dict:
        first = self.times[0]
        steady = self.times[1:]
        out = {
            "calls": len(self.times),
            "first_call_s": round(first, 6),
            "total_s": round(sum(self.times), 6),
            "work_total": int(sum(self.works)),
        }
        if steady:
            p50 = float(np.percentile(steady, 50))
            out["dispatch_s"] = {
                "mean": round(float(np.mean(steady)), 6),
                "p50": round(p50, 6),
                "min": round(float(np.min(steady)), 6),
                "max": round(float(np.max(steady)), 6),
            }
            # the cold call = trace + compile + one dispatch; subtracting
            # the steady median leaves a compile estimate (floored: noise
            # can invert it for trivially small programs)
            out["compile_s"] = round(max(first - p50, 0.0), 6)
        else:
            out["dispatch_s"] = None
            out["compile_s"] = round(first, 6)
        out["per_work_s"] = self._per_work()
        out["signatures"] = {
            "aval": len(self.aval_sigs),
            "static": len(self.sigs),
            "retraces": len(self.retraces),
            "aval_retraces": self.aval_retraces,
            # static-only recompiles (e.g. the designed first_step peel)
            # are recorded above but only AVAL changes flag: a new leaf
            # shape/dtype is the silent perf killer
            "flagged": self.aval_retraces > 0,
        }
        return out


class DispatchRecorder:
    """Per-entry-point wall-clock registry; all accounting host-side.

    Args:
        clock: monotonic seconds source (default ``time.perf_counter``: the
            host log's clock, there in nanoseconds, so a recorder's spans
            and ``host_records()`` can be overlaid).
        strict_retrace: raise :class:`RetraceError` *before* dispatching a
            call whose abstract argument signature (leaf shapes/dtypes)
            would recompile an already-compiled entry point. Static-only
            structure changes (the designed ``first_step`` peel) never
            raise.
        max_spans: cap on retained ``(start, duration)`` spans across all
            entries+fetches (timeline export memory bound for very long
            runs); beyond it spans are dropped (counted) while the
            aggregate statistics keep accumulating.
        block_dispatch: block on the returned pytree
            (``jax.block_until_ready``) INSIDE the timed region. Default
            off: a warm call's duration is then the host-side dispatch
            cost (JAX async dispatch — the PR-1 semantics). Turn it ON
            to measure roofline rates: the differenced per-work slope
            needs durations that scale with the work, which async
            dispatch times do not.
    """

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        strict_retrace: bool = False,
        max_spans: int = 100_000,
        block_dispatch: bool = False,
    ):
        self._clock = clock
        self._entries: Dict[str, _EntryStats] = {}
        self._fetches: Dict[str, dict] = {}
        self._fetch_spans: List[dict] = []
        self._created = clock()
        self.strict_retrace = strict_retrace
        self.max_spans = max_spans
        self.block_dispatch = block_dispatch
        self._span_count = 0
        self._dropped_spans = 0
        self.analyzer: Optional[CostAnalyzer] = None

    def _keep_span(self) -> bool:
        if self._span_count >= self.max_spans:
            self._dropped_spans += 1
            return False
        self._span_count += 1
        return True

    # ------------------------------------------------------------- recording
    @contextlib.contextmanager
    def record(self, name: str, work: int = 1):
        """Time a host-side block as one call of entry point ``name``
        covering ``work`` units (generations) of progress."""
        t0 = self._clock()
        try:
            yield
        finally:
            dt = self._clock() - t0
            stats = self._entries.setdefault(name, _EntryStats())
            stats.times.append(dt)
            stats.works.append(work)
            if self._keep_span():
                stats.spans.append((t0, dt, work))

    def wrap(
        self,
        name: str,
        fn: Callable,
        work_fn: Optional[Callable[[tuple, dict], int]] = None,
    ) -> Callable:
        """Wrap ``fn`` so every call is recorded under ``name``, with
        signature tracking for retrace detection."""

        def wrapped(*args: Any, **kwargs: Any):
            stats = self._entries.setdefault(name, _EntryStats())
            sig = abstract_signature(args, kwargs)
            # strict mode raises BEFORE the signature is recorded, so a
            # retried call with the same bad shape raises again instead of
            # silently passing a now-"known" signature to the compiler
            if (
                self.strict_retrace
                and stats.sigs
                and sig[0] not in stats.aval_sigs
            ):
                raise RetraceError(
                    f"entry point '{name}' would retrace: abstract argument "
                    f"signature changed to {sig[0][:200]} after "
                    f"{len(stats.times)} call(s) — a leaf shape or dtype "
                    "changed between calls (the classic silent TPU compile "
                    "cost). Fix the shape instability, or drop "
                    "strict_retrace to record it instead."
                )
            stats.observe_signature(sig, self._clock() - self._created)
            work = work_fn(args, kwargs) if work_fn is not None else 1
            with self.record(name, work=work):
                out = fn(*args, **kwargs)
                if self.block_dispatch:
                    # jax.block_until_ready skips non-array leaves itself;
                    # anything it raises is a REAL device execution error
                    # and must propagate, not be timed as a fast success
                    jax.block_until_ready(out)
                return out

        wrapped._dispatch_recorder = self  # idempotence marker for attach
        wrapped.__wrapped__ = fn
        return wrapped

    def attach(
        self,
        workflow: Any,
        entry_points: Sequence[str] = DEFAULT_ENTRY_POINTS,
    ) -> Any:
        """Wrap the workflow's entry points in place (instance attributes
        shadow the class methods; other instances are untouched). Note
        that ``run`` internally peels its first generation through
        ``step``, so one ``run`` call also records one ``step`` call —
        that peeled dispatch is real and reported where it happens.
        Re-attaching the same recorder is a no-op per entry point."""
        for name in entry_points:
            fn = getattr(workflow, name, None)
            if fn is None or not callable(fn):
                continue
            if getattr(fn, "_dispatch_recorder", None) is self:
                continue
            setattr(
                workflow,
                name,
                self.wrap(name, fn, DEFAULT_WORK_EXTRACTORS.get(name)),
            )
        return workflow

    def fetch(self, tree: Any, name: str = "fetch") -> Any:
        """Bring ``tree`` to host, accounting bytes and seconds under
        ``name``. Returns the numpy-leaved tree. This is the ONLY place
        instrumented code should materialize device data, so every
        device-to-host byte is accounted."""
        t0 = self._clock()
        with span(FETCH, site=name):
            host = jax.device_get(tree)
        dt = self._clock() - t0
        nbytes = int(
            sum(
                x.nbytes
                for x in jax.tree.leaves(host)
                if hasattr(x, "nbytes")
            )
        )
        agg = self._fetches.setdefault(
            name, {"calls": 0, "bytes": 0, "seconds": 0.0}
        )
        agg["calls"] += 1
        agg["bytes"] += nbytes
        agg["seconds"] += dt
        if self._keep_span():
            self._fetch_spans.append(
                {"name": name, "t0": t0, "dt": dt, "bytes": nbytes}
            )
        return host

    # --------------------------------------------------------------- summary
    def summary(self) -> dict:
        out = {
            "entry_points": {
                name: stats.summary()
                for name, stats in sorted(self._entries.items())
            },
            "fetches": {
                name: {
                    "calls": agg["calls"],
                    "bytes": agg["bytes"],
                    "seconds": round(agg["seconds"], 6),
                }
                for name, agg in sorted(self._fetches.items())
            },
            "wall_s": round(self._clock() - self._created, 6),
            "retrace_flags": sorted(
                name
                for name, stats in self._entries.items()
                if stats.aval_retraces > 0
            ),
        }
        if self._dropped_spans:
            out["dropped_spans"] = self._dropped_spans
        return out


def instrument(
    workflow: Any,
    recorder: Optional[DispatchRecorder] = None,
    entry_points: Sequence[str] = DEFAULT_ENTRY_POINTS,
    analyze: bool = False,
    strict_retrace: bool = False,
    block_dispatch: bool = False,
) -> DispatchRecorder:
    """Attach (or create) a :class:`DispatchRecorder` to ``workflow``.

    ``analyze=True`` additionally attaches a :class:`~evox_tpu.core.
    xla_cost.CostAnalyzer`: the first ``run_report`` AOT-lowers and
    compiles the workflow's advertised entry points once (host-side, no
    callbacks) and the report gains a ``roofline`` section.
    ``strict_retrace=True`` makes any aval-signature retrace of an
    instrumented entry raise :class:`RetraceError` instead of silently
    recompiling. ``block_dispatch=True`` makes timed calls wait for
    their result (required for meaningful roofline rates — see
    :class:`DispatchRecorder`).

    Usage::

        rec = instrument(wf, analyze=True, block_dispatch=True)
        state = wf.init(key)
        state = wf.run(state, 100)   # warm
        state = wf.run(state, 300)   # second trip count -> differenced
        report = run_report(wf, state, recorder=rec)
    """
    recorder = recorder if recorder is not None else DispatchRecorder(
        strict_retrace=strict_retrace, block_dispatch=block_dispatch
    )
    if strict_retrace:
        recorder.strict_retrace = True
    if block_dispatch:
        recorder.block_dispatch = True
    recorder.attach(workflow, entry_points)
    if analyze and recorder.analyzer is None:
        recorder.analyzer = CostAnalyzer()
    return recorder


def _sharding_subsection(
    workflow: Any, state: Any, analyses: Dict[str, dict]
) -> Optional[dict]:
    """The roofline ``sharding`` subsection (schema v5): for a workflow
    driving a POP-sharded algorithm (``core.distributed.ShardedES``,
    duck-typed via ``is_pop_sharded``), compare the AOT PER-DEVICE peak
    bytes of the steady entry point against the FULL-POP artifact bytes of
    the algorithm state — a gather-free compiled step must keep the former
    strictly below the latter (``memory_analysis()`` reports per-device
    sizes for SPMD programs; verified in tests/test_large_pop.py)."""
    algo = getattr(workflow, "algorithm", None)
    if not getattr(algo, "is_pop_sharded", False):
        return None
    n_dev = int(getattr(algo, "n_shards", 1) or 1)
    if n_dev < 4:
        # the inequality is meaningful only when the shard is a small
        # fraction of the population: per-device peak carries a constant
        # factor (z in+out, candidates, temps) of roughly 2-4x one shard,
        # so at n_dev < 4 even a perfectly gather-free program can sit at
        # or above full-pop bytes — no claim is attached rather than a
        # false "not gather-free" rejection
        return None
    pop = int(getattr(algo, "pop_size", 0) or 0)
    astate = getattr(state, "algo", None)
    full = 0
    for leaf in jax.tree_util.tree_leaves(astate):
        shape = getattr(leaf, "shape", ())
        if pop and len(shape) >= 1 and shape[0] == pop:
            # count float artifacts at the COMPUTE width (>= 4 bytes):
            # under a bf16 storage policy the leaves REST at half width
            # but the in-step temps the peak actually measures are f32
            # (apply_compute upcasts at step entry) — comparing an f32
            # peak against a bf16-sized reference would falsely fail
            # legitimate gather-free bf16 runs
            itemsize = np.dtype(leaf.dtype).itemsize
            if np.issubdtype(np.dtype(leaf.dtype), np.floating):
                itemsize = max(itemsize, 4)
            full += int(np.prod(shape)) * itemsize
    if full < 4 * 1024 * 1024:
        # the inequality discriminates only when the full-pop artifacts
        # dominate the per-device FIXED footprint (replicated strategy
        # fields, monitor rings, program temps); a small-pop sharded run
        # is legitimate but proves nothing either way — no claim attached
        # rather than a false "not gather-free" rejection
        return None
    for entry in ("step", "run"):
        analysis = analyses.get(entry)
        if not isinstance(analysis, dict) or "error" in analysis:
            continue
        peak = (analysis.get("memory") or {}).get("peak_bytes_estimate")
        if peak:
            return {
                "axis": str(getattr(algo, "axis_name", "pop")),
                "n_devices": int(getattr(algo, "n_shards", 1) or 1),
                "pop_size": pop,
                "entry": entry,
                "per_device_peak_bytes": int(peak),
                "full_pop_bytes": int(full),
                "gather_free": int(peak) < int(full),
            }
    return None


def _multihost_subsection(
    workflow: Any, state: Any, analyses: Dict[str, dict]
) -> Optional[dict]:
    """The roofline ``multihost`` subsection (schema v8, ISSUE 13):
    attached when THIS process is part of a multi-process
    ``jax.distributed`` run. Cites the per-PROCESS peak (``memory_
    analysis`` reports per-device stats for SPMD programs — PR 10 — so a
    process's peak is its local devices' sum), the full-population
    artifact bytes it must stay gather-free against per device, and a
    collective-bytes-per-generation estimate over the ``cost_analysis``
    shapes: the pop-sized fitness/rank traffic every sharded tell
    replicates plus (for the ShardedES protocol) the psum-reduced moment
    tree, sized via ``eval_shape`` of ``pop_moments``."""
    if jax.process_count() <= 1:
        return None
    algo = getattr(workflow, "algorithm", None)
    pop = int(getattr(algo, "pop_size", 0) or 0)
    n_local = jax.local_device_count()
    peak = entry_used = None
    for entry in ("step", "run"):
        analysis = analyses.get(entry)
        if not isinstance(analysis, dict) or "error" in analysis:
            continue
        p = (analysis.get("memory") or {}).get("peak_bytes_estimate")
        if p:
            peak, entry_used = int(p), entry
            break
    if peak is None:
        return None
    full = 0
    astate = getattr(state, "algo", None)
    for leaf in jax.tree_util.tree_leaves(astate):
        shape = getattr(leaf, "shape", ())
        if pop and len(shape) >= 1 and shape[0] == pop:
            itemsize = np.dtype(leaf.dtype).itemsize
            if np.issubdtype(np.dtype(leaf.dtype), np.floating):
                itemsize = max(itemsize, 4)  # compute-width (PR-10 rule)
            full += int(np.prod(shape)) * itemsize
    # collective traffic model per generation: fitness + ranks are
    # replicated pop-sized operands; the ShardedES tell additionally
    # psums its (dim,)-sized moment tree
    collective = 2 * pop * 4
    if getattr(algo, "is_pop_sharded", False):
        try:
            inner = getattr(algo, "algorithm", algo)
            shard = pop // max(int(getattr(algo, "n_shards", 1) or 1), 1)
            rows = {
                name: jax.ShapeDtypeStruct(
                    getattr(astate, name).shape[:0]
                    + (shard,)
                    + getattr(astate, name).shape[1:],
                    jnp.float32,
                )
                for name in getattr(inner, "sharded_pop_fields", ())
            }
            w_sds = jax.ShapeDtypeStruct((shard,), jnp.float32)
            moments = jax.eval_shape(inner.pop_moments, rows, w_sds)
            collective += sum(
                int(np.prod(m.shape)) * 4
                for m in jax.tree_util.tree_leaves(moments)
            )
        except Exception:
            pass  # the base fitness/rank model stands
    return {
        "process_count": int(jax.process_count()),
        "n_local_devices": int(n_local),
        "entry": entry_used,
        "per_device_peak_bytes": peak,
        "per_process_peak_bytes": peak * int(n_local),
        "full_pop_bytes": int(full),
        "collective_bytes_estimate": int(collective),
        "collective_model": (
            "2*pop*4 fitness/rank replication + psum moment tree "
            "(eval_shape over pop_moments); per-process peak = "
            "per-device peak * local device count"
        ),
    }


def run_report(
    workflow: Any = None,
    state: Any = None,
    recorder: Optional[DispatchRecorder] = None,
    extra: Optional[dict] = None,
    analyzer: Optional[CostAnalyzer] = None,
    supervisor: Any = None,
    executor: Any = None,
    pod_supervisor: Any = None,
    metrics: Any = None,
    control_plane: Any = None,
) -> dict:
    """Merge device telemetry and host dispatch timings into ONE
    JSON-serializable dict.

    Device side: every monitor on ``workflow`` exposing ``report(mstate)``
    (duck-typed, so core never imports monitors) is called with its slot
    of ``state.monitors``. Host side: ``recorder.summary()``. Either half
    may be absent — a report can cover a bare recorder or a bare
    workflow+state.

    Roofline: when ``analyzer`` is given (or the recorder carries one —
    ``instrument(wf, analyze=True)``), the workflow's entry points are
    AOT-analyzed (cached; one compile per entry+signature) and merged
    with the measured per-work timings into a ``roofline`` section (see
    :func:`~evox_tpu.core.xla_cost.roofline_section`). With no analyzer
    the report is exactly the pre-roofline shape — a no-op.

    Supervisor: when ``supervisor`` is given — or the workflow was driven
    by a :class:`~evox_tpu.workflows.supervisor.RunSupervisor`, which
    advertises itself as ``workflow._run_supervisor`` — the report gains
    a ``supervisor`` section (deadline/retry/restore/degradation events
    and counters, ``RunSupervisor.report()``). Duck-typed: anything with
    a zero-arg ``report()`` works, and core stays decoupled from the
    workflows package.
    """
    # v2: roofline sections carry dtype_policy + donation provenance
    # (tools/check_report.py enforces them for v2+, exempting the
    # historical v1 captures). v3 adds the optional `tenancy` section
    # (multi-tenant fleets, workflows/tenancy.py). v4 adds the optional
    # `executor` section (core/executor.py GenerationExecutor: queue
    # depth, overlap spans, staleness counters) — validated when present.
    # v5 adds the optional roofline `sharding` subsection (POP-sharded
    # large-pop runs: per-device peak bytes vs the full-pop bytes — the
    # gather-free acceptance signal) and `guardrail.ipop` (host-boundary
    # doubling/handoff events) — both validated when present. v6 adds
    # the serving fault-domain sections (workflows/journal.py +
    # fleet_health.py): `tenancy.queue.journal` (hash-chained WAL event
    # counters, recovered flag) and `tenancy.fleet_health` (per-tenant
    # freeze/evict/restart action log) — validated when present. v7 adds
    # the optional `serving` section (core/exec_cache.py +
    # workflows/elastic.py): the AOT executable cache's hit/miss/compile
    # accounting (`serving.cache`) and the bucket lattice the workflow
    # serves (`serving.buckets`) — validated when present. v8 adds the
    # optional roofline `multihost` subsection (ISSUE 13: multi-process
    # runs cite their per-process AOT peak and a collective-bytes
    # estimate next to the sharding evidence) — validated when present.
    # v9 adds the optional `pod_supervisor` section (ISSUE 14,
    # core/pod_supervisor.py: heartbeat censuses, collective-deadline
    # failures with worker_dead/hung_collective/coordinator_loss
    # classification, coordinated drains, re-formation/resume events) —
    # validated when present, incl. the monotonic-census and
    # reform↔resume coherence rules. v10 adds the optional `surrogate`
    # section (ISSUE 15, workflows/surrogate.py: archive fill, refit
    # count/staleness, the screened-vs-true eval ledger, health
    # readings, chronological fallback events) — validated when present,
    # incl. the counter-sum and event-ordering coherence rules. v11 adds
    # the top-level `schema_version` int (PR 16 satellite: the version
    # is grep-able without parsing the schema string; check_report
    # --schema prints the one version it takes) and the optional `metrics` +
    # `slo` sections (workflows/flightrec.py FlightRecorder: the
    # serving-plane registry snapshot, stream accounting, and the SLO
    # ledger) — validated when present, incl. slo↔tenancy.queue
    # counter coherence. v12 adds the optional `control_plane` section
    # (ISSUE 18, workflows/control_plane.py: the multi-pod gateway's pod
    # census, ledger event counts, tenant accounting with the
    # exactly-once admission audit, and the steal/autoscale event
    # streams) — validated when present, incl. the ledger↔counter
    # coherence and empty-duplicate-admissions rules. v13 adds the
    # optional `search` section (ISSUE 19, monitors/lineage.py
    # LineageMonitor: the operator-attribution credit ledger, best-
    # ancestry traceback, restart-epoch counter, per-generation
    # best/delta trajectory, and the MO front-size/churn rings) —
    # validated when present, incl. the successes≤attempts ledger rule,
    # ancestry-indices-in-range, and churn non-negativity. v14 adds the
    # optional `integrity` section (ISSUE 20, core/attest.py
    # StateAttestor + core/executor.py voted re-dispatch): the on-device
    # attestation ring (generation-stamped state digests at a cadence),
    # the verify rung's dispatch/mismatch/heal counters, any
    # bisect_divergence() forensics report, and a one-word verdict
    # (clean/detected/healed/aborted) — validated when present, incl.
    # the cadence-monotone ring, verdict-set, bisection-in-window, and
    # redispatch-counter coherence rules.
    report: dict = {
        "schema": "evox_tpu.run_report/v14",
        "schema_version": 14,
    }
    if state is not None and hasattr(state, "generation"):
        report["generation"] = int(state.generation)
    if workflow is not None and state is not None:
        telemetry = []
        # a fleet state (VectorizedWorkflowState) has no top-level
        # .monitors — its per-tenant monitor states live tenant-stacked
        # under .tenants and are reported through the tenancy section
        mstates = getattr(state, "monitors", None)
        if mstates is not None:
            for i, mon in enumerate(getattr(workflow, "monitors", ())):
                if hasattr(mon, "report"):
                    entry = mon.report(mstates[i])
                    entry["monitor"] = type(mon).__name__
                    entry["monitor_index"] = i
                    telemetry.append(entry)
        report["telemetry"] = telemetry
        # multi-tenant fleets (duck-typed, core never imports workflows):
        # per-tenant telemetry rings, fleet shape, and — when a RunQueue
        # drives the fleet — the queue's admission/eviction counters
        if hasattr(workflow, "tenancy_report"):
            try:
                report["tenancy"] = workflow.tenancy_report(state)
            except Exception as e:  # report decoration must never sink it
                report["tenancy"] = {"error": f"{type(e).__name__}: {e}"}
        # guarded runs (core/guardrail.py): surface the wrapper's health
        # counters as a first-class section (duck-typed — core stays
        # decoupled from the concrete GuardedAlgorithm class)
        algo = getattr(workflow, "algorithm", None)
        astate = getattr(state, "algo", None)
        if hasattr(algo, "health_report") and hasattr(astate, "restarts"):
            report["guardrail"] = algo.health_report(astate)
        # host-boundary IPOP history (workflows/ipop.py): doubling and
        # low-memory handoff events recorded on the caller's workflow
        # object (clones share the list) — duck-typed like _run_supervisor
        ipop_events = getattr(workflow, "_ipop_events", None)
        if ipop_events:
            report.setdefault("guardrail", {})["ipop"] = list(ipop_events)
        # surrogate pre-screening (schema v10, workflows/surrogate.py):
        # the archive/refit/eval-count ledger proving how many TRUE
        # evaluations the run spent — duck-typed, core never imports the
        # workflows package
        if hasattr(workflow, "surrogate_report"):
            try:
                report["surrogate"] = workflow.surrogate_report(state)
            except Exception as e:  # decoration must never sink the report
                report["surrogate"] = {"error": f"{type(e).__name__}: {e}"}
        # search-dynamics lineage (schema v13, monitors/lineage.py): the
        # first attached monitor exposing `search_report` contributes the
        # top-level `search` section — attribution ledger, best-ancestry
        # traceback, epoch counter, trajectory window (duck-typed: core
        # never imports the monitors package)
        if mstates is not None:
            for i, mon in enumerate(getattr(workflow, "monitors", ())):
                if hasattr(mon, "search_report"):
                    try:
                        report["search"] = mon.search_report(mstates[i])
                    except Exception as e:  # must never sink the report
                        report["search"] = {
                            "error": f"{type(e).__name__}: {e}"
                        }
                    break
        # compute-integrity attestation (schema v14, core/attest.py):
        # the first attached monitor exposing `integrity_report` (a
        # StateAttestor) contributes the generation-stamped digest ring;
        # the executor's verify counters and any forensics report join
        # it below, after the executor pickup
        if mstates is not None:
            for i, mon in enumerate(getattr(workflow, "monitors", ())):
                if hasattr(mon, "integrity_report"):
                    try:
                        report["integrity"] = mon.integrity_report(
                            mstates[i]
                        )
                    except Exception as e:  # must never sink the report
                        report["integrity"] = {
                            "error": f"{type(e).__name__}: {e}"
                        }
                    break
    summary = recorder.summary() if recorder is not None else None
    if summary is not None:
        report["dispatch"] = summary
    if analyzer is None and recorder is not None:
        analyzer = recorder.analyzer
    if analyzer is not None:
        if workflow is not None and state is not None:
            try:
                analyzer.analyze_workflow(workflow, state)
            except Exception as e:
                # analysis must never sink the report it decorates:
                # analyze_callable degrades per entry, but the workflow's
                # analysis_targets itself (eval_shape, fit_shape hooks)
                # can raise — keep telemetry/dispatch, note the loss
                report["roofline"] = {"error": f"{type(e).__name__}: {e}"}
        if "roofline" not in report and analyzer.analyses:
            report["roofline"] = roofline_section(
                analyzer.analyses, summary, analyzer.ceilings
            )
        if (
            isinstance(report.get("roofline"), dict)
            and "entries" in report["roofline"]
        ):
            # precision/donation provenance (PR 6): rates are only
            # interpretable next to the dtype the state was stored at and
            # whether the run carry was donated (alias_bytes per entry
            # live in entries[*].static.memory.alias_bytes). Attached for
            # EVERY v2 roofline — a workflow-less (bare-analyzer) report
            # falls back to the explicit f32/undonated defaults via
            # policy_report(None)/getattr, keeping the v2 schema coherent
            # with tools/check_report.py's required fields
            from .dtype_policy import policy_report

            report["roofline"]["dtype_policy"] = policy_report(workflow)
            report["roofline"]["donation"] = {
                "donate_carries": bool(
                    getattr(workflow, "donate_carries", False)
                ),
                "alias_bytes": {
                    name: (a.get("memory") or {}).get("alias_bytes", 0)
                    for name, a in analyzer.analyses.items()
                    if isinstance(a, dict) and "error" not in a
                },
            }
            # POP-sharded large-pop provenance (schema v5, PR 10): when the
            # workflow drives a ShardedES-backed algorithm, record the AOT
            # per-device peak next to the full-pop artifact bytes — the
            # "per-device memory scales as pop/n_dev, not pop" acceptance
            # signal (tools/check_report.py asserts peak < full-pop bytes)
            sharding = _sharding_subsection(
                workflow, state, analyzer.analyses
            )
            if sharding is not None:
                report["roofline"]["sharding"] = sharding
            # multi-process provenance (schema v8, ISSUE 13): a pod run
            # cites its per-process peak + collective-traffic estimate
            multihost = _multihost_subsection(
                workflow, state, analyzer.analyses
            )
            if multihost is not None:
                report["roofline"]["multihost"] = multihost
    # elastic serving (schema v7, duck-typed — core never imports the
    # workflows package): a bucket workflow warmed through the AOT
    # executable cache advertises it as `_exec_cache`
    # (workflows/elastic.py warm_fleet_cache) and its lattice as
    # `_bucket_table`; the cache's hit/miss/compile-seconds accounting
    # is how a serving process proves its cold path never recompiled
    cache = getattr(workflow, "_exec_cache", None)
    if cache is not None and hasattr(cache, "report"):
        serving: dict = {"cache": cache.report()}
        table = getattr(workflow, "_bucket_table", None)
        if table is not None and hasattr(table, "report"):
            serving["buckets"] = table.report()
        report["serving"] = serving
    if supervisor is None and workflow is not None:
        supervisor = getattr(workflow, "_run_supervisor", None)
    if supervisor is not None and hasattr(supervisor, "report"):
        report["supervisor"] = supervisor.report()
    # pod supervisor (core/pod_supervisor.py, schema v9): a pod-
    # supervised run advertises itself as `_pod_supervisor` — heartbeat
    # censuses, classified failures, drains, and reform/resume events
    # become the `pod_supervisor` section (duck-typed like the others)
    if pod_supervisor is None and workflow is not None:
        pod_supervisor = getattr(workflow, "_pod_supervisor", None)
    if pod_supervisor is not None and hasattr(pod_supervisor, "report"):
        report["pod_supervisor"] = pod_supervisor.report()
    # generation executor (core/executor.py): the workflow's most recent
    # executor-backed run advertises itself as `_run_executor` — queue
    # depth, overlap spans, and staleness counters become the `executor`
    # section (duck-typed: anything with a zero-arg report() works)
    if executor is None and workflow is not None:
        executor = getattr(workflow, "_run_executor", None)
    if executor is not None and hasattr(executor, "report"):
        report["executor"] = executor.report()
    # serving-plane flight recorder (schema v11, workflows/flightrec.py):
    # a metrics-instrumented serving stack advertises its recorder as
    # `_flight_recorder` (the RunQueue backref) — the registry snapshot
    # and stream accounting become the `metrics` section and the SLO
    # ledger a first-class top-level `slo` section (duck-typed like the
    # supervisor pickups; core never imports the workflows package)
    if metrics is None and workflow is not None:
        metrics = getattr(workflow, "_flight_recorder", None)
    if metrics is not None and hasattr(metrics, "report"):
        report["metrics"] = metrics.report()
        if hasattr(metrics, "slo_ledger"):
            report["slo"] = metrics.slo_ledger()
    # multi-pod control plane (schema v12, workflows/control_plane.py):
    # a workflow served through the gateway advertises it as
    # `_control_plane` (duck-typed like every pickup above — core never
    # imports the workflows package); its report() — pod census, ledger
    # event counts, exactly-once admission audit, steal/autoscale
    # streams — becomes the `control_plane` section
    if control_plane is None and workflow is not None:
        control_plane = getattr(workflow, "_control_plane", None)
    if control_plane is not None and hasattr(control_plane, "report"):
        report["control_plane"] = control_plane.report()
    # compute-integrity verify/forensics (schema v14, ISSUE 20): the
    # executor's voted re-dispatch counters (None until the verify rung
    # was armed) and any bisect_divergence() report — advertised as
    # `workflow._integrity_forensics` — join the attestor ring picked up
    # above; the verdict folds the layer's whole story into one word
    verify = (
        executor.integrity_counters()
        if executor is not None and hasattr(executor, "integrity_counters")
        else None
    )
    forensics = (
        getattr(workflow, "_integrity_forensics", None)
        if workflow is not None
        else None
    )
    integ = report.get("integrity")
    if (
        isinstance(integ, dict) and "error" in integ
    ):  # ring pickup failed — leave the error section as-is
        pass
    elif integ is not None or verify is not None or forensics is not None:
        if integ is None:
            integ = {"enabled": True, "attestations": 0, "ring": []}
        if verify is not None:
            integ["verify"] = verify
        if forensics is not None:
            integ["bisection"] = dict(forensics)
        v = integ.get("verify") or {}
        if v.get("aborted"):
            integ["verdict"] = "aborted"
        elif v.get("healed"):
            integ["verdict"] = "healed"
        elif v.get("mismatches") or (
            forensics is not None
            and forensics.get("first_divergent_generation") is not None
        ):
            integ["verdict"] = "detected"
        else:
            integ["verdict"] = "clean"
        report["integrity"] = integ
    if extra:
        report["extra"] = dict(extra)
    return sanitize_json(report)


def write_report_jsonl(report: dict, path: str) -> None:
    """Append ``report`` as one strict-JSON line to a JSON-lines file."""
    with open(path, "a") as f:
        f.write(json.dumps(sanitize_json(report), allow_nan=False) + "\n")


# ------------------------------------------------------------ chrome trace

_US = 1e6  # trace-event timestamps are microseconds


#: trace pids are ``PID_STRIDE * jax_process_index + local track``:
#: track 0 = host dispatch, 1 = device telemetry, 2 = host counters,
#: 3 = run supervisor, 4 = generation executor, 5 = pod supervisor.
#: workflows/flightrec.py shares the stride (its metrics tracks start at
#: the same base), so per-process traces from ``dryrun_multihost`` land
#: on disjoint, deterministic pid ranges and can be concatenated or
#: merged without collision.
PID_STRIDE = 100


def _counter_events(
    track: str, samples: Sequence[Tuple[float, Any]], pid: int
) -> List[dict]:
    """One ``ph: "C"`` event per finite sample; ``samples`` carry
    already-relative timestamps in seconds."""
    short = track.rsplit("/", 1)[-1]
    events = []
    for t, v in samples:
        v = float(v)
        if not math.isfinite(v) or not math.isfinite(t):
            continue
        events.append(
            {
                "ph": "C",
                "name": track,
                "pid": pid,
                "ts": round(max(t, 0.0) * _US, 3),
                "args": {short: v},
            }
        )
    return events


def write_chrome_trace(
    path: str,
    recorder: Optional[DispatchRecorder] = None,
    workflow: Any = None,
    state: Any = None,
    extra_counters: Optional[Dict[str, Sequence[Tuple[float, Any]]]] = None,
    supervisor: Any = None,
    executor: Any = None,
    pod_supervisor: Any = None,
    process_index: Optional[int] = None,
) -> dict:
    """Export a run as Chrome trace-event JSON (open in Perfetto or
    chrome://tracing) and return the trace dict.

    - Recorder spans become complete (``ph: "X"``) slices: one thread per
      entry point under the "host dispatch" process, fetches on their own
      thread with byte counts in ``args``; retrace events appear as
      instant markers on the entry's thread.
    - TelemetryMonitor rings (any monitor on ``workflow`` exposing
      ``counter_tracks(mstate)``) become counter (``ph: "C"``) tracks.
      The rings are generation-indexed — the callback-free design has no
      per-generation host timestamps — so samples are spread uniformly
      across the recorder's observed span window (or 1 ms/generation
      without a recorder): counter shapes are exact, their time axis is
      approximate by construction.
    - ``extra_counters`` maps track names to ``(timestamp, value)``
      samples stamped with the recorder's clock (``time.perf_counter``),
      e.g. :meth:`ProcessRolloutFarm.counter_tracks` worker-health
      samples — these land at their true host times.
    - Supervisor events (``supervisor=`` a :class:`~evox_tpu.workflows.
      supervisor.RunSupervisor`, or picked up duck-typed from
      ``workflow._run_supervisor``) become instant (``ph: "i"``) markers
      — ``supervisor:retry`` / ``supervisor:deadline`` /
      ``supervisor:restore`` / ``supervisor:degrade`` /
      ``supervisor:abort`` — on their own "run supervisor" process at
      their true host timestamps (same ``perf_counter`` clock as the
      recorder).
    - Executor activity (``executor=`` a :class:`~evox_tpu.core.executor.
      GenerationExecutor`, or picked up duck-typed from
      ``workflow._run_executor``) lands on a "generation executor"
      process: overlap spans (device dispatch / host eval / background
      checkpoint+fetch I/O, one thread per track) as complete slices at
      their true host timestamps, plus queue-depth and stale-lag counter
      tracks.

    Every process gets ``process_name``/``thread_name`` metadata events
    and a deterministic pid: ``pid = PID_STRIDE * jax_process_index +
    track`` (track 0-5 per the :data:`PID_STRIDE` table).
    ``process_index`` defaults to the active ``jax.distributed`` process
    id (0 outside a pod), so per-worker traces from ``dryrun_multihost``
    land on disjoint pid ranges with names like ``"p1: host dispatch"``
    instead of colliding anonymously.

    Entirely host-side (no host callbacks): everything exported was
    already recorded outside traced code.
    """
    events: List[dict] = []
    t0 = recorder._created if recorder is not None else 0.0
    t_end = t0

    if process_index is None:
        try:
            from .distributed import _dist_process_info

            process_index, _ = _dist_process_info()
        except Exception:
            process_index = 0
    process_index = int(process_index)
    pid_base = PID_STRIDE * process_index
    # process 0 keeps unprefixed names (the single-process common case
    # reads cleanly); workers carry their index so merged traces name
    # every track's owner
    prefix = f"p{process_index}: " if process_index else ""

    def meta(track: int, name: str, tid: Optional[int] = None) -> dict:
        e = {
            "ph": "M",
            "pid": pid_base + track,
            "name": "process_name" if tid is None else "thread_name",
            "args": {"name": (name if tid is not None else prefix + name)},
        }
        if tid is not None:
            e["tid"] = tid
        return e

    if recorder is not None:
        events.append(meta(0, "host dispatch"))
        names = sorted(recorder._entries)
        for tid, name in enumerate(names, start=1):
            stats = recorder._entries[name]
            events.append(meta(0, name, tid))
            for start, dur, work in stats.spans:
                t_end = max(t_end, start + dur)
                ev = {
                    "ph": "X",
                    "name": name,
                    "cat": "dispatch",
                    "pid": pid_base,
                    "tid": tid,
                    "ts": round((start - t0) * _US, 3),
                    "dur": round(dur * _US, 3),
                }
                if work != 1:
                    ev["args"] = {"work": work}
                events.append(ev)
            for r in stats.retraces:
                events.append(
                    {
                        "ph": "i",
                        "name": f"retrace:{r['kind']}",
                        "cat": "retrace",
                        "pid": pid_base,
                        "tid": tid,
                        "ts": round(max(r["t"], 0.0) * _US, 3),
                        "s": "t",
                    }
                )
        if recorder._fetch_spans:
            tid = len(names) + 1
            events.append(meta(0, "fetch", tid))
            for span in recorder._fetch_spans:
                t_end = max(t_end, span["t0"] + span["dt"])
                events.append(
                    {
                        "ph": "X",
                        "name": span["name"],
                        "cat": "fetch",
                        "pid": pid_base,
                        "tid": tid,
                        "ts": round((span["t0"] - t0) * _US, 3),
                        "dur": round(span["dt"] * _US, 3),
                        "args": {"bytes": span["bytes"]},
                    }
                )

    window_s = max(t_end - t0, 0.0)
    if (
        workflow is not None
        and state is not None
        and getattr(state, "monitors", None) is not None
    ):
        events.append(meta(1, "device telemetry"))
        for i, mon in enumerate(getattr(workflow, "monitors", ())):
            tracks_fn = getattr(mon, "counter_tracks", None)
            if tracks_fn is None:
                continue
            for track, samples in tracks_fn(state.monitors[i]).items():
                if not samples:
                    continue
                gens = [g for g, _ in samples]
                lo, hi = min(gens), max(gens)
                span = max(hi - lo, 1)
                scale = (window_s / span) if window_s > 0 else 1e-3
                rel = [((g - lo) * scale, v) for g, v in samples]
                events.extend(_counter_events(track, rel, pid=pid_base + 1))

    if extra_counters:
        events.append(meta(2, "host counters"))
        for track, samples in extra_counters.items():
            rel = [(t - t0, v) for t, v in samples]
            events.extend(_counter_events(track, rel, pid=pid_base + 2))

    if supervisor is None and workflow is not None:
        supervisor = getattr(workflow, "_run_supervisor", None)
    if supervisor is not None and hasattr(supervisor, "markers"):
        markers = supervisor.markers()
        if markers:
            events.append(meta(3, "run supervisor"))
            for m in markers:
                events.append(
                    {
                        "ph": "i",
                        "name": m["name"],
                        "cat": "supervisor",
                        "pid": pid_base + 3,
                        "tid": 1,
                        "ts": round(max(m["t_abs"] - t0, 0.0) * _US, 3),
                        "s": "p",
                        "args": sanitize_json(m.get("args", {})),
                    }
                )

    # pod supervisor events (ISSUE 14, duck-typed from
    # ``workflow._pod_supervisor``): ``supervisor:pod:*`` instant markers
    # — join / census / barrier_timeout / failure / drain / reform /
    # resume — on their own "pod supervisor" process, same clock
    if pod_supervisor is None and workflow is not None:
        pod_supervisor = getattr(workflow, "_pod_supervisor", None)
    if pod_supervisor is not None and hasattr(pod_supervisor, "markers"):
        markers = pod_supervisor.markers()
        if markers:
            events.append(meta(5, "pod supervisor"))
            for m in markers:
                events.append(
                    {
                        "ph": "i",
                        "name": m["name"],
                        "cat": "supervisor",
                        "pid": pid_base + 5,
                        "tid": 1,
                        "ts": round(max(m["t_abs"] - t0, 0.0) * _US, 3),
                        "s": "p",
                        "args": sanitize_json(m.get("args", {})),
                    }
                )

    if executor is None and workflow is not None:
        executor = getattr(workflow, "_run_executor", None)
    if executor is not None and hasattr(executor, "trace_spans"):
        spans = executor.trace_spans()
        samples = (
            executor.counter_samples()
            if hasattr(executor, "counter_samples")
            else {}
        )
        if spans or any(samples.values()):
            events.append(meta(4, "generation executor"))
            tids: Dict[str, int] = {}
            for span in spans:
                tids.setdefault(span["track"], len(tids) + 1)
            for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
                events.append(meta(4, track, tid))
            for span in spans:
                ev = {
                    "ph": "X",
                    "name": span["name"],
                    "cat": "executor",
                    "pid": pid_base + 4,
                    "tid": tids[span["track"]],
                    "ts": round(max(span["t_abs"] - t0, 0.0) * _US, 3),
                    "dur": round(max(span["dur"], 0.0) * _US, 3),
                }
                if span.get("args"):
                    ev["args"] = sanitize_json(span["args"])
                events.append(ev)
            for track, track_samples in samples.items():
                rel = [(t - t0, v) for t, v in track_samples]
                events.extend(_counter_events(track, rel, pid=pid_base + 4))

    trace = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "exporter": "evox_tpu.core.instrument.write_chrome_trace",
            "time_origin": "DispatchRecorder creation",
        },
    }
    with open(path, "w") as f:
        json.dump(trace, f, allow_nan=False)
    return trace

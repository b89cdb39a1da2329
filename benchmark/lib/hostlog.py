"""The program's host log as a reader needs it: this run's window and set-up
found among the records, and the log laid on the device trace's clock.

``evox_tpu.core.instrument`` keeps one record of every ``span`` the program
closes, and of everything jax says it traced, lowered and compiled, in a ring
on ``time.perf_counter_ns``, whether a profiler session is on or not
(``host_records()``). The harness attaches nothing and hands the readers no
log, so they find their run in it:

* **the window** is the last ``ctx.window["chunks"]`` ``evox:run`` records of
  the thread that calls the reader (the harness's: it drove the window),
  taken only if their starts lie one chunk apart, each difference within
  ``CHUNK_TOLERANCE_MS`` of the harness's own ``chunk_ms`` for that chunk (the
  closed loop calls ``run`` again as soon as a chunk is ready), and each
  record no longer than its chunk: another run's records are never read;
* **set-up** is every record that ended before the window's first ``run``
  began, back to the start of the process, or, in a process that ran a cell
  before (the CPU tests), back to the end of the last ``evox:run`` before
  this run's ``evox:init``. What the builder compiled before ``init`` is
  set-up's. Where the ring is full and no such earlier ``run`` bounds the
  stretch, the start of set-up may have rolled out: nothing is read;
* **one clock**: under the profiler each ``evox:run`` of the window exists
  twice, as a record and as a span of the trace (``scoped.load(ctx).spans``).
  The offset is the median over the chunks of (span's start less record's
  start); where the counts differ or a chunk's difference lies more than
  ``RESIDUAL_LIMIT_NS`` from it, nothing is read. Every record, the
  log-only ones too, then has a place on the trace's clock, and a stretch in
  which the device was idle can be put down to the trip count, the dispatch,
  the rest of ``run``, or the wait outside it.

Every function returns None where there is nothing to read (the parent
commit's program has no host log) and none raises, so a reader is left out
of the result there. ``RECORDS`` points the tests at a hand-made log.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib
import json
import statistics
import sys
import threading
from typing import Optional

from benchmark.lib import scoped

RECORDS: Optional[list] = None  # tests: read these records, whatever the program's log holds
CHUNK_TOLERANCE_MS = 1.0
RESIDUAL_LIMIT_NS = 100_000.0

RUN = "evox:run"
INIT = "evox:init"
TRIP_COUNT = "evox:run/trip_count"
DISPATCH = "evox:run/dispatch"
COMPILE_TRACE = "evox:compile/trace"
COMPILE_LOWER = "evox:compile/lower"
COMPILE_BACKEND = "evox:compile/backend"
COMPILE_CACHE_HIT = "evox:compile/cache_hit"


def _program_log() -> tuple:
    """``(records by id, the ring's length)`` of the program's own log, or
    ``(None, None)`` where the program has none."""
    if RECORDS is not None:
        return sorted(RECORDS), None
    names = importlib.import_module("evox_tpu.core.instrument")  # the package re-exports a function of that name
    read = getattr(names, "host_records", None)
    return (read(), getattr(names, "HOST_LOG_LEN", None)) if read else (None, None)


@dataclasses.dataclass(frozen=True)
class Window:
    """``runs``: the window's ``evox:run`` records, by start. ``records``:
    every record of that thread from the first run's start to the last's
    end. ``setup``: the records of this run's set-up, any thread's, or None
    where its start may have rolled out of the ring."""

    runs: tuple
    records: tuple
    setup: Optional[tuple]

    def named(self, name: str) -> list:
        return [r for r in self.records if r.name == name]


def ms(record) -> float:
    return (record.end_ns - record.start_ns) / 1e6


def window_median_ms(ctx, name: str) -> Optional[float]:
    """Median length of the window's records of that name, or None."""
    found = window(ctx)
    return scoped.median([ms(r) for r in found.named(name)]) if found else None


def setup_records(ctx, *names: str) -> Optional[list]:
    """Set-up's records of those names, by id; None where set-up cannot be
    read (no log, no window found, or its start rolled out of the ring)."""
    found = window(ctx)
    if found is None or found.setup is None:
        return None
    return [r for r in found.setup if r.name in names]


def union_s(records: list) -> float:
    """Length in seconds of the union of the records' intervals (a trace
    nested in a trace counts once)."""
    total, reach = 0, float("-inf")
    for r in sorted(records, key=lambda r: r.start_ns):
        total += max(0, r.end_ns - max(r.start_ns, reach))
        reach = max(reach, r.end_ns)
    return total / 1e9


def window(ctx) -> Optional[Window]:
    """This run's window and set-up in the program's log, or None."""
    records, ring = _program_log()
    chunks, chunk_ms = int(ctx.window["chunks"]), ctx.window["chunk_ms"]
    if not records or chunks < 1 or len(chunk_ms) != chunks:
        return None
    thread = threading.get_ident()
    mine = sorted((r for r in records if r.thread == thread), key=lambda r: r.start_ns)
    runs = [r for r in mine if r.name == RUN][-chunks:]
    if len(runs) != chunks:
        return None
    apart = [(b.start_ns - a.start_ns) / 1e6 for a, b in zip(runs, runs[1:])]
    if any(abs(d - want) > CHUNK_TOLERANCE_MS for d, want in zip(apart, chunk_ms)):
        return None
    if any(ms(r) > want + CHUNK_TOLERANCE_MS for r, want in zip(runs, chunk_ms)):
        return None
    lo, hi = runs[0].start_ns, runs[-1].end_ns
    inside = tuple(r for r in mine if r.start_ns >= lo and r.end_ns <= hi)
    # this run's init is the last before the window; the run before it, if
    # any, is another cell's, and set-up begins where that one ended
    inits = [r for r in mine if r.name == INIT and r.end_ns <= lo]
    setup = None
    if inits:
        earlier = [r.end_ns for r in mine if r.name == RUN and r.end_ns <= inits[-1].start_ns]
        if earlier or ring is None or len(records) < ring:
            since = max(earlier, default=float("-inf"))
            setup = tuple(r for r in records if r.start_ns >= since and r.end_ns <= lo)
    return Window(runs=tuple(runs), records=inside, setup=setup)


# ------------------------------------------------------------ one clock


@dataclasses.dataclass(frozen=True)
class Aligned:
    """``offset_ns``: what to add to a record's time to have it on the
    trace's clock. ``residual_ns``: the largest distance of a chunk's own
    difference from it."""

    window: Window
    offset_ns: float
    residual_ns: float

    def on_trace(self, record) -> tuple:
        return record.start_ns + self.offset_ns, record.end_ns + self.offset_ns


def aligned(ctx) -> Optional[Aligned]:
    """The window with its offset to the trace's clock, or None: no trace
    of this run, no log, counts that differ, or a residual over the limit."""
    spans, found = scoped.run_spans(ctx), window(ctx)
    if not spans or found is None or len(spans) != len(found.runs):
        return None
    deltas = [s.start_ns - r.start_ns for s, r in zip(spans, found.runs)]
    offset = statistics.median(deltas)
    residual = max(abs(d - offset) for d in deltas)
    if residual > RESIDUAL_LIMIT_NS:
        return None
    return Aligned(window=found, offset_ns=offset, residual_ns=residual)


class _Busy:
    """The union of the device's operations as disjoint intervals, for the
    idle time of any stretch."""

    def __init__(self, events: list):
        self.starts, self.ends, self.before = [], [], [0.0]
        for e in sorted(events, key=lambda e: e.start_ns):
            if self.ends and e.start_ns <= self.ends[-1]:
                self.ends[-1] = max(self.ends[-1], e.end_ns)
            else:
                self.starts.append(e.start_ns)
                self.ends.append(e.end_ns)
        for s, e in zip(self.starts, self.ends):
            self.before.append(self.before[-1] + (e - s))

    def _busy_until(self, t: float) -> float:
        at = bisect.bisect_right(self.starts, t)
        if at == 0:
            return 0.0
        return self.before[at - 1] + min(t, self.ends[at - 1]) - self.starts[at - 1]

    def idle_ns(self, lo: float, hi: float) -> float:
        if hi <= lo:
            return 0.0
        return (hi - lo) - (self._busy_until(hi) - self._busy_until(lo))


def idle_by_chunk(ctx) -> Optional[list]:
    """For each chunk of the traced stretch, the device's idle nanoseconds as
    ``{"trip_count", "dispatch", "run", "wait"}``: inside that chunk's
    ``evox:run`` (``run``: all of it, its two parts included) and outside
    every ``evox:run`` (``wait``); and ``busy_at_start``, whether the device
    was busy at the instant ``run`` began. A chunk lasts from its ``run``'s
    start to the next one's; the stretch from the first ``run``'s start for
    ``ctx.stretch_ns`` (the harness's ``bench:chunk`` opens microseconds
    before the ``run`` in it), so the parts add up to the stretch's idle
    time. None without a device plane or an alignment."""
    a = aligned(ctx)
    if a is None or not ctx.events:
        return None
    busy = _Busy(ctx.events)
    runs = [a.on_trace(r) for r in a.window.runs]
    ends = [start for start, _ in runs[1:]] + [runs[0][0] + ctx.stretch_ns]
    parts = {
        key: [a.on_trace(r) for r in a.window.named(name)]
        for key, name in (("trip_count", TRIP_COUNT), ("dispatch", DISPATCH))
    }
    out = []
    for (start, end), chunk_end in zip(runs, ends):
        end = min(end, chunk_end)
        inside = busy.idle_ns(start, end)
        row = {"run": inside, "wait": busy.idle_ns(start, chunk_end) - inside,
               "busy_at_start": busy.idle_ns(start, start + 1.0) == 0.0}
        for key, stretches in parts.items():
            row[key] = sum(
                busy.idle_ns(max(s, start), min(e, end)) for s, e in stretches if s < end and e > start
            )
        out.append(row)
    return out


def say(ctx, rows: Optional[list]) -> None:
    """One line on standard error (the result stays standard output's last
    line): the alignment's offset and largest residual, where the device's
    idle time of the stretch fell (``rows``: ``idle_by_chunk``'s), and in how
    many chunks the device was busy at the instant ``run`` began. A closed
    loop calls ``run`` after it saw the last chunk's result, so that count is
    0 unless the trace's device plane and host plane disagree (a millisecond
    or two in some sessions: PERF.md section 6, PR 36), and then ``run`` and
    ``wait`` are split that far off."""
    a = aligned(ctx)
    if a is None:
        return
    facts = {"hostlog_offset_ns": a.offset_ns, "hostlog_residual_us_largest": a.residual_ns / 1e3,
             "chunks": len(a.window.runs)}
    if rows:
        facts["idle_ms"] = {k: sum(r[k] for r in rows) / 1e6 for k in ("trip_count", "dispatch", "run", "wait")}
        facts["idle_ms"]["stretch"] = (ctx.stretch_ns - ctx.busy_ns) / 1e6
        facts["device_busy_at_run_start"] = sum(r["busy_at_start"] for r in rows)
    print("hostlog: " + json.dumps(facts), file=sys.stderr, flush=True)

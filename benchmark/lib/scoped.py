"""What a reader of the program's own names needs and ``TraceContext`` lacks:
each device operation's ``tf_op`` (the HLO's ``op_name``, where
``jax.named_scope`` lands: ``jit(f)/while/body/evox.tell/peel/and``) beside
its own time, and the host's ``evox:`` spans.

The harness leaves the run's ``.xplane.pb`` under ``<root>/.bench_trace/<cell>/``
while the readers run. :func:`load` takes the file only if its own
``bench:chunk`` stretch is ``ctx.stretch_ns`` to the nanosecond, so a file
another run left is never read; where it finds none (the CPU tests' tiny
checkouts, a parent that writes no trace there) the readers return nothing
and their metrics are left out. ``TRACE_FILE`` points the tests at a fixture.

``tf_op`` is a stat of an event's *metadata*; ``jax.profiler.ProfileData``
(jax 0.9.0) hands out an event's own stats only, so the file is read with a
decoder of the protobuf wire format, field numbers as in
``tsl/profiler/protobuf/xplane.proto``. A later ``benchmark`` issue folds this
into ``TraceContext``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
import statistics
from pathlib import Path
from typing import Optional

from benchmark.lib import manifest as mf
from benchmark.lib import trace as tr

TRACE_FILE: Optional[Path] = None  # tests: read this file, wherever the run's would be
SPAN_PREFIX = "evox:"
CHUNK = tr.HOST_SPAN_PREFIX + "chunk"
TOP_SCOPES = ("evox.ask", "evox.evaluate", "evox.tell", "evox.constrain", "evox.monitors")


# ------------------------------------------------------------ wire format


def _varint(buf: bytes, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, the bytes for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at : at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, at = int.from_bytes(buf[at : at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {kind} is not one an xplane file holds")
        yield key >> 3, value


def _map_entry(buf: bytes) -> tuple:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane(buf: bytes, line_names: tuple) -> tuple:
    """A plane's name and, of its lines named in ``line_names`` (every line
    for an empty tuple), ``(line name, [(metadata id, start ns, end ns)])``,
    with the names and string stats of the event metadata those events use:
    ``{id: (name, {stat name: value})}``."""
    name, lines, metadata, stat_names = "", [], {}, {}
    for number, value in _fields(buf):
        if number == 2:
            name = value.decode()
        elif number == 3:
            lines.append(value)
        elif number == 4:
            key, meta = _map_entry(value)
            metadata[key] = meta
        elif number == 5:
            key, meta = _map_entry(value)
            stat_names[key] = dict(_fields(meta)).get(2, b"").decode()
    out_lines, used = [], set()
    for line in lines:
        line_name, timestamp_ns, events = "", 0, []
        for number, value in _fields(line):
            if number == 2:
                line_name = value.decode()
            elif number == 3:
                timestamp_ns = value
            elif number == 4:
                events.append(value)
        if line_names and line_name not in line_names:
            continue
        rows = []
        for event in events:
            f = dict(_fields(event))  # metadata_id=1, offset_ps=2, duration_ps=3
            # whole nanoseconds, as ProfileData's ``start_ns`` and
            # ``duration_ns`` are: a stretch then compares with the
            # harness's to the nanosecond
            start = float(timestamp_ns + f.get(2, 0) // 1000)
            rows.append((f.get(1, 0), start, start + f.get(3, 0) // 1000))
            used.add(f.get(1, 0))
        out_lines.append((line_name, rows))
    named = {}
    for key in used:
        meta_name, stats = "", {}
        for number, value in _fields(metadata.get(key, b"")):
            if number == 2:
                meta_name = value.decode()
            elif number == 5:  # XStat: metadata_id=1, str_value=5, ref_value=7
                stat = dict(_fields(value))
                if 5 in stat:
                    stats[stat_names.get(stat.get(1), "")] = stat[5].decode()
                elif 7 in stat:
                    stats[stat_names.get(stat.get(1), "")] = stat_names.get(stat[7], "")
        named[key] = (meta_name, stats)
    return name, out_lines, named


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime_ns: int) -> tuple:
    """``(devices, host)`` of an ``.xplane.pb``: ``devices`` maps a device
    plane's name to its operations as ``tr.Event``s named by ``tf_op`` (the
    operation's own short name where it has none), by start; ``host`` holds
    the ``bench:`` and ``evox:`` spans, by start."""
    devices, host = {}, []
    for number, value in _fields(Path(path).read_bytes()):
        if number != 1:
            continue
        # a plane's name comes before its lines: read it alone first, and
        # decode only the planes the readers use
        plane_name = next((v.decode() for n, v in _fields(value) if n == 2), "")
        if tr.DEVICE_PLANE.match(plane_name):
            _, lines, named = _plane(value, (tr.OP_LINE,))
            events = [
                tr.Event(named[m][1].get("tf_op") or tr.short_name(named[m][0]), start, end)
                for _, rows in lines
                for m, start, end in rows
            ]
            devices[plane_name] = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
        elif plane_name.startswith("/host:"):
            _, lines, named = _plane(value, ())
            host += [
                tr.Event(named[m][0], start, end)
                for _, rows in lines
                for m, start, end in rows
                if named[m][0].startswith((SPAN_PREFIX, tr.HOST_SPAN_PREFIX))
            ]
    host.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return devices, host


# ------------------------------------------------------------ what readers get


@dataclasses.dataclass(frozen=True)
class Scoped:
    """``own_ns``: own nanoseconds (duration less what nested operations
    cover, as ``trace.self_ns_by_name`` reckons it) of the fullest device's
    operations inside the traced stretch, by ``tf_op``. ``op_starts``: when
    each of those operations started, ascending. ``spans``: the host's
    ``evox:`` spans that lie inside the stretch, by start."""

    own_ns: dict
    op_starts: tuple
    spans: tuple


def _candidates() -> list:
    if TRACE_FILE is not None:
        return [Path(TRACE_FILE)]
    return sorted((mf.ROOT / ".bench_trace").glob("*/**/*.xplane.pb"))


@functools.lru_cache(maxsize=2)
def _view(path: str, mtime_ns: int, stretch_ns: float) -> Optional[Scoped]:
    """The scoped view of the file, if its own ``bench:chunk`` stretch is
    ``stretch_ns`` to the nanosecond (else it is another run's: None). Kept,
    so that a run's dozen readers reduce the file once."""
    devices, host = _read(path, mtime_ns)
    chunks = [e for e in host if e.name == CHUNK]
    if not chunks or not devices:
        return None
    lo, hi = min(e.start_ns for e in chunks), max(e.end_ns for e in chunks)
    if abs((hi - lo) - stretch_ns) >= 1.0:
        return None
    clipped = {name: tr.clip(events, lo, hi) for name, events in devices.items()}
    fullest = max(clipped, key=lambda name: tr.union_ns(clipped[name]))
    return Scoped(
        own_ns=tr.self_ns_by_name(clipped[fullest]),
        op_starts=tuple(e.start_ns for e in clipped[fullest]),
        spans=tuple(
            e for e in host
            if e.name.startswith(SPAN_PREFIX) and e.start_ns >= lo and e.end_ns <= hi
        ),
    )


def load(ctx) -> Optional[Scoped]:
    """The scoped view of the run ``ctx`` describes, or None."""
    for path in _candidates():
        view = _view(str(path), path.stat().st_mtime_ns, float(ctx.stretch_ns))
        if view is not None:
            return view
    return None


def under(tf_op: str, *path: str) -> bool:
    """Whether ``tf_op`` lies under the scope path ``path`` (``("evox.tell",
    "peel")``): its components appear in that order, each as a whole
    component of ``tf_op``, bare or wrapped by a transform
    (``vmap(evox.ask)``, ``shard_map(remat(peel))``)."""
    parts = [re.sub(r"^(?:\w+\()+|\)+$", "", p) for p in tf_op.rstrip(":").split("/")]
    at = 0
    for want in path:
        try:
            at = parts.index(want, at) + 1
        except ValueError:
            return False
    return True


def scope_ms(ctx, *paths: tuple) -> Optional[float]:
    """Own device time under any of the scope ``paths``, in milliseconds a
    generation of the traced stretch; None where there is nothing to read (no
    file, or no operation under the scopes: a program from before them)."""
    view = load(ctx)
    if view is None or not ctx.window["generations"]:
        return None
    ns = sum(v for k, v in view.own_ns.items() if any(under(k, *p) for p in paths))
    return ns / 1e6 / ctx.window["generations"] if ns else None


def unscoped_ns(view: Scoped) -> float:
    """Own device time of the operations under none of ``TOP_SCOPES``."""
    return sum(
        v for k, v in view.own_ns.items() if not any(under(k, top) for top in TOP_SCOPES)
    )


def run_spans(ctx) -> Optional[list]:
    """The ``evox:run`` spans inside the stretch, or None where there are none."""
    view = load(ctx)
    spans = [e for e in view.spans if e.name == "evox:run"] if view else []
    return spans or None


def start_lags_ms(ctx) -> Optional[list]:
    """For each ``evox:run`` span, the milliseconds from its start to the
    start of the first device operation after it."""
    spans, view = run_spans(ctx), load(ctx)
    if not spans:
        return None
    lags = []
    for span in spans:
        at = bisect.bisect_left(view.op_starts, span.start_ns)
        if at < len(view.op_starts):
            lags.append((view.op_starts[at] - span.start_ns) / 1e6)
    return lags or None


def median(values: Optional[list]) -> Optional[float]:
    return statistics.median(values) if values else None

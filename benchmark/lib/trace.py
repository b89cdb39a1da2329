"""Reduction of a profiler trace (``.xplane.pb``) to device times.

``jax.profiler.ProfileData`` reads the file with nothing but jax. A TPU's
plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event for
each HLO operation that ran, nested where an operation (a ``while``, a
``call``) contains others. Busy time is the union of those intervals, so
nesting counts once; an operation's own time is its duration less what its
children cover. Host spans the benchmark wrote (``TraceAnnotation``s whose
names start with ``bench:``) lie on the host's plane on the same clock, as do
the spans of jax's own Python thread (``PjitFunction(...)``, ``DevicePut``).
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
HLO_TEXT = re.compile(r"^%?(\S+) = .*?[\])}] ([a-z][a-z\-]*)\(")
OP_LINE = "XLA Ops"
HOST_LINE = "python"
HOST_SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter|collective-broadcast)"
)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """``devices``: plane name -> the operation events of that device, by
    start. ``host``: the host's spans (the benchmark's own and those of jax's
    Python thread), by start."""

    devices: dict
    host: list

    def window(self) -> tuple:
        """The stretch the benchmark marked: from the start of its first
        ``bench:chunk`` span to the end of its last; where it wrote none,
        from the first device event to the last."""
        chunks = [e for e in self.host if e.name == HOST_SPAN_PREFIX + "chunk"]
        if chunks:
            return min(e.start_ns for e in chunks), max(e.end_ns for e in chunks)
        events = [e for evs in self.devices.values() for e in evs]
        if not events:
            raise ValueError("the trace holds no device event and no bench:chunk span")
        return min(e.start_ns for e in events), max(e.end_ns for e in events)


def short_name(name: str) -> str:
    """The TPU's trace names an operation by its whole HLO text
    (``%fused_mlp_rollout.12 = f32[1,1,65536]{...} custom-call(...)``): keep
    the instruction's name and its opcode, ``fused_mlp_rollout.12
    custom-call``. Any other name stays as it is."""
    m = HLO_TEXT.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: Path) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = [
                Event(short_name(e.name), float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                for line in plane.lines
                if line.name == OP_LINE
                for e in line.events
            ]
            devices[plane.name] = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    # the benchmark's spans, and what jax's Python thread says it did
                    if e.name.startswith(HOST_SPAN_PREFIX) or line.name == HOST_LINE:
                        host.append(
                            Event(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                        )
    host.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return Trace(devices=devices, host=host)


def clip(events: list, lo: float, hi: float) -> list:
    """The parts of ``events`` inside ``[lo, hi]``."""
    return [
        Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
        for e in events
        if e.end_ns > lo and e.start_ns < hi
    ]


def union_ns(events: list) -> float:
    """Length of the union of the events' intervals."""
    total, reach = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start_ns):
        total += max(0.0, e.end_ns - max(e.start_ns, reach))
        reach = max(reach, e.end_ns)
    return total


def busy_ns(trace: Trace, lo: float, hi: float) -> dict:
    """Busy nanoseconds of each device inside ``[lo, hi]``."""
    return {name: union_ns(clip(events, lo, hi)) for name, events in trace.devices.items()}


def self_ns_by_name(events: list) -> dict:
    """Own time of each operation name: duration less what nested events
    cover. ``events`` are sorted by start (outer before inner on a tie)."""
    out: dict = {}
    stack: list = []  # [event, ns covered by its children]

    def close(entry):
        event, covered = entry
        out[event.name] = out.get(event.name, 0.0) + max(0.0, event.dur_ns - covered)

    for e in events:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            close(stack.pop())
        if stack:
            stack[-1][1] += min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return out


def matching_ns(events: list, pattern: str) -> float:
    """Union of the intervals of the events whose name matches ``pattern``;
    0.0 where none does."""
    rx = re.compile(pattern)
    return union_ns([e for e in events if rx.search(e.name)])


def idle_gaps(trace: Trace, device: str, lo: float, hi: float, top: int = 10) -> list:
    """The longest stretches of ``[lo, hi]`` in which nothing ran on
    ``device``, as ``[what the host was doing, seconds]``: the innermost host
    span that covers the middle of the gap (``bench:chunk`` alone: the host
    waited in ``block_until_ready`` or between dispatches), or ``outside bench
    spans``. Gaps under the same name add up."""
    gaps, reach = [], lo
    for e in sorted(clip(trace.devices[device], lo, hi), key=lambda e: e.start_ns):
        if e.start_ns > reach:
            gaps.append((reach, e.start_ns))
        reach = max(reach, e.end_ns)
    if hi > reach:
        gaps.append((reach, hi))
    by_name: dict = {}
    for start, end in gaps:
        mid = (start + end) / 2
        covering = [h for h in trace.host if h.start_ns <= mid <= h.end_ns]
        name = min(covering, key=lambda h: h.dur_ns).name if covering else "outside bench spans"
        by_name[name] = by_name.get(name, 0.0) + (end - start)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def top_ops(trace: Trace, device: str, lo: float, hi: float, top: int = 10) -> list:
    """The operations of ``device`` that took most of their own time inside
    ``[lo, hi]``, as ``[name, seconds]``."""
    own = self_ns_by_name(clip(trace.devices[device], lo, hi))
    ranked = sorted(own.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]

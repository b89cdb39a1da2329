"""``benchmark/lib/hostlog.py`` and the ten readers that stand on it, on a log
and a trace written by hand.

The process of the story ran another cell first (its ``evox:run`` ends at
1 ms), then this run: the builder compiles a program (the cache holds it),
``init`` compiles one (a miss), a check chunk, the warm chunk, and a traced
window of three chunks, 10, 12 and 11 ms long, whose third ``run`` holds the
host for 6 ms. The trace's clock is the log's plus 5 s. Times below are
milliseconds on the log's clock.
"""

from __future__ import annotations

import importlib
import threading

import jax
import pytest

from benchmark.lib import harness, hostlog, scoped, trace as tr

names = importlib.import_module("evox_tpu.core.instrument")
METRICS = (
    "run_dispatch_ms", "run_dispatch_ms_untraced", "run_trip_count_ms", "run_host_ms_longest",
    "idle_in_run_ms", "idle_in_wait_ms_longest", "setup_init_s", "setup_lower_s",
    "setup_backend_s", "setup_cache_misses",
)
NEED_THE_TRACE = ("idle_in_run_ms", "idle_in_wait_ms_longest")
OFFSET_NS = 5_000_000_000
CHUNK_MS = [10.0, 12.0, 11.0]
RUN, LOOP, TRIP, DISPATCH = names.RUN, names.RUN_LOOP, names.RUN_TRIP_COUNT, names.RUN_DISPATCH
TRACE, LOWER, BACKEND, HIT = (
    names.COMPILE_TRACE, names.COMPILE_LOWER, names.COMPILE_BACKEND, names.COMPILE_CACHE_HIT)


def _read(metric: str, ctx):
    return importlib.import_module(f"benchmark.metrics.{metric}").read(ctx)


def _log(thread: int) -> list:
    """The story's records, ids in the order they opened."""
    rows, ids = [], iter(range(1, 1000))

    def rec(name, start, end, parent=0, thread=thread, **args):
        r = names.HostRecord(next(ids), parent, name, int(start * 1e6), int(end * 1e6), thread, args)
        rows.append(r)
        return r.id

    def run(start, end, trip, dispatch, n_steps=2):
        top = rec(RUN, start, end, n_steps=n_steps)
        loop = rec(LOOP, trip[0], dispatch[1] + 0.05, top, n_steps=n_steps)
        rec(TRIP, *trip, loop)
        return rec(DISPATCH, *dispatch, loop, cpu_ns=1000)

    # another cell's run, earlier in the process, and something it left compiling
    rec(names.INIT, 0.1, 0.2)
    run(0.3, 1.0, (0.35, 0.5), (0.5, 0.9))
    rec(BACKEND, 0.6, 0.8, fun_name="jit(the_other_cell)")
    # this run's set-up: the builder's program, from the cache
    rec(TRACE, 2.0, 2.5, fun_name="build")
    rec(LOWER, 2.5, 3.5, fun_name="jit(build)")
    rec(BACKEND, 3.5, 5.5, fun_name="jit(build)")
    rec(HIT, 5.4, 5.4)
    # init, which compiles: a trace nested in a trace counts once
    init = rec(names.INIT, 6.0, 8.0)
    rec(TRACE, 6.2, 6.4, init, fun_name="init")
    rec(TRACE, 6.25, 6.35, init, fun_name="add")
    rec(BACKEND, 6.3, 6.35, init, fun_name="jit(a_constant)")  # compiled while init was traced: compiling, not tracing
    rec(LOWER, 6.4, 6.9, init, fun_name="jit(init)")
    rec(BACKEND, 6.9, 7.9, init, fun_name="jit(init)")
    # a check chunk that compiles the loop on another thread's watch too
    rec(BACKEND, 16.0, 17.0, thread=thread + 1, fun_name="jit(elsewhere)")
    cold = run(10.0, 30.0, (10.1, 10.6), (10.6, 29.9), n_steps=1)
    rec(LOWER, 11.0, 15.0, cold, fun_name="jit(run_loop)")
    rec(BACKEND, 15.0, 29.0, cold, fun_name="jit(run_loop)")
    # the warm chunk, before the profiler starts: the slow level
    run(40.0, 60.9, (40.1, 40.5), (40.5, 60.8))
    # the traced window
    run(100.0, 101.0, (100.05, 100.55), (100.6, 100.9))
    run(110.3, 111.7, (110.35, 111.05), (111.1, 111.6))
    run(122.0, 128.0, (122.1, 122.7), (122.75, 127.95))
    # after the window: the harness compiles for the memory analysis, the reference follows
    rec(BACKEND, 140.0, 150.0, fun_name="jit(run_loop)")
    return rows


# device operations, ms on the log's clock: (start, end); the second is nested in the first
DEVICE = [(100.4, 109.5), (101.0, 105.0), (111.0, 121.5), (128.5, 132.8)]
TRACE_RUNS = [(100.0, 101.0, 0), (110.3, 111.7, 20_000), (122.0, 128.0, -10_000)]  # start, end, ns off the common offset


def _xplane(path, runs=TRACE_RUNS) -> None:
    """The story's trace: one device, and the host's ``bench:chunk`` and
    ``evox:run`` spans on the trace's clock."""
    def ps(t_ms, extra_ns=0):
        return int(round(t_ms * 1e6 + extra_ns)) * 1000

    ops = "\n".join(
        f"    events {{ metadata_id: 1 offset_ps: {ps(a)} duration_ps: {ps(b) - ps(a)} }}" for a, b in DEVICE)
    # a chunk's span opens 2 us before the run in it; the last closes where the stretch ends
    starts = [r[0] for r in runs]
    ends = starts[1:] + [starts[0] + sum(CHUNK_MS)]
    chunks = "\n".join(
        f"    events {{ metadata_id: 2 offset_ps: {ps(a, -2000)} duration_ps: {ps(b, -2000) - ps(a, -2000)} }}"
        for a, b in zip(starts, ends))
    spans = "\n".join(
        f"    events {{ metadata_id: 1 offset_ps: {ps(a, off)} duration_ps: {ps(b) - ps(a)} }}" for a, b, off in runs)
    text = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: {OFFSET_NS}
{ops}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
    timestamp_ns: {OFFSET_NS}
{chunks}
{spans}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "evox:run" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench:chunk" }} }}
}}
"""
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))


def _ctx(path=None):
    """A ``TraceContext`` as the harness builds it; without a trace file,
    as on the CPU: no device event."""
    events, busy, stretch = [], 0.0, sum(CHUNK_MS) * 1e6
    if path is not None:
        t = tr.load(path)
        lo, hi = t.window()
        events = tr.clip(t.devices["/device:TPU:0"], lo, hi)
        busy, stretch = tr.union_ns(events), hi - lo
    return harness.TraceContext(
        config={}, traffic={}, chips=1, device_kind="TPU v5 lite",
        window={"evals": 48, "seconds": sum(CHUNK_MS) / 1e3, "generations": 6, "chunks": 3, "chunk_ms": list(CHUNK_MS)},
        compiles_in_window=0, events=events, busy_ns=busy, stretch_ns=stretch,
    )


@pytest.fixture
def story(monkeypatch, tmp_path):
    path = tmp_path / "story.xplane.pb"
    _xplane(path)
    monkeypatch.setattr(scoped, "TRACE_FILE", path)
    monkeypatch.setattr(hostlog, "RECORDS", _log(threading.get_ident()))
    return _ctx(path)


EXPECTED = {
    "run_dispatch_ms": 0.5,  # 0.3, 0.5, 5.2
    "run_dispatch_ms_untraced": 20.3,  # the warm chunk's, not the check chunk's 19.3 nor the window's
    "run_trip_count_ms": 0.6,  # 0.5, 0.7, 0.6
    "run_host_ms_longest": 6.0,
    "idle_in_run_ms": 0.7,  # 0.4, 0.7, and the whole of the third run: 6.0
    "idle_in_wait_ms_longest": 0.8,  # 109.5 to 110.3; then 0.5; then 0.5 before the device starts and 0.2 after it ends
    "setup_init_s": 0.0003,  # the record's 2.0 ms less the 1.7 its compile records cover (6.2 to 7.9)
    "setup_lower_s": 0.00615,  # build 0.5 + 1.0, init 0.2 (the nested trace once, less the 0.05 compiled inside it) + 0.5, run_loop 4.0
    "setup_backend_s": 0.01705,  # build 2.0, a constant 0.05, init 1.0, run_loop 14.0 with the other thread's 1.0 inside it
    "setup_cache_misses": 4.0,  # five backend compiles (the other cell's is not set-up's), one hit
}


@pytest.mark.parametrize("metric", METRICS)
def test_reader_on_the_hand_made_log(metric, story):
    assert _read(metric, story) == pytest.approx(EXPECTED[metric], rel=1e-9)


def test_the_window_and_set_up_found_in_the_log(story):
    found = hostlog.window(story)
    assert [hostlog.ms(r) for r in found.runs] == pytest.approx([1.0, 1.4, 6.0])
    assert {r.name for r in found.records} == {RUN, LOOP, TRIP, DISPATCH} and len(found.records) == 12
    first = found.runs[0].start_ns
    assert all(r.end_ns <= first and r.start_ns >= 1_000_000 for r in found.setup)
    assert [r.args["fun_name"] for r in hostlog.setup_records(story, BACKEND)] == [
        "jit(build)", "jit(a_constant)", "jit(init)", "jit(elsewhere)", "jit(run_loop)"]


def test_idle_time_splits_by_run_and_adds_up(story, capsys):
    rows = hostlog.idle_by_chunk(story)
    ms = lambda key: [row[key] / 1e6 for row in rows]
    assert ms("run") == pytest.approx([0.4, 0.7, 6.0])
    assert ms("wait") == pytest.approx([0.8, 0.5, 0.7])
    assert ms("trip_count") == pytest.approx([0.35, 0.65, 0.6])  # idle while the trip count was made
    assert ms("dispatch") == pytest.approx([0.0, 0.0, 5.2])
    # inside and outside run together are the stretch's idle time
    assert sum(ms("run")) + sum(ms("wait")) == pytest.approx((story.stretch_ns - story.busy_ns) / 1e6)
    a = hostlog.aligned(story)
    assert a.offset_ns == OFFSET_NS and a.residual_ns == 20_000
    hostlog.say(story, rows)
    said = capsys.readouterr()
    assert said.out == "" and '"hostlog_residual_us_largest": 20.0' in said.err
    assert '"device_busy_at_run_start": 0' in said.err


def test_another_runs_records_are_refused(story, monkeypatch):
    """The stretch check: the starts of the last three ``run`` records must
    lie a chunk apart as the harness timed them."""
    story.window["chunk_ms"] = [10.0, 10.5, 11.0]  # the second run began 11.7 ms after the first
    assert hostlog.window(story) is None and all(_read(m, story) is None for m in METRICS)
    story.window["chunk_ms"] = list(CHUNK_MS)
    assert hostlog.window(story) is not None
    story.window["chunk_ms"] = [10.0, 12.0, 4.0]  # a run of 6 ms does not fit a chunk of 4
    assert hostlog.window(story) is None
    story.window["chunk_ms"] = list(CHUNK_MS)
    story.window["chunks"] = 4  # the run before the window's three is set-up's warm chunk
    story.window["chunk_ms"] = [60.0] + CHUNK_MS
    assert hostlog.window(story) is not None  # as far apart as said: taken
    story.window["chunk_ms"] = [10.0] + CHUNK_MS
    assert hostlog.window(story) is None
    # another thread's records are not this run's
    monkeypatch.setattr(hostlog, "RECORDS", _log(threading.get_ident() + 7))
    story.window["chunks"], story.window["chunk_ms"] = 3, list(CHUNK_MS)
    assert hostlog.window(story) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_log_reads_nothing(metric, story, monkeypatch):
    """The parent commit: ``core/instrument.py`` has no ``host_records``."""
    monkeypatch.setattr(hostlog, "RECORDS", None)
    monkeypatch.delattr(names, "host_records")
    assert hostlog.window(story) is None and hostlog.aligned(story) is None
    assert _read(metric, story) is None


@pytest.mark.parametrize("metric", METRICS)
def test_without_a_device_plane_the_log_alone_is_read(metric, monkeypatch, tmp_path):
    """The CPU: no trace of the run to align with. What stands on the log
    alone reads as before; the two that need the trace's clock say nothing."""
    monkeypatch.setattr(scoped, "TRACE_FILE", None)
    monkeypatch.setattr(scoped.mf, "ROOT", tmp_path)
    monkeypatch.setattr(hostlog, "RECORDS", _log(threading.get_ident()))
    ctx = _ctx()
    if metric in NEED_THE_TRACE:
        assert _read(metric, ctx) is None
    else:
        assert _read(metric, ctx) == pytest.approx(EXPECTED[metric], rel=1e-9)


def test_alignment_refuses_a_residual_over_the_limit_and_a_count_that_differs(story, monkeypatch, tmp_path):
    late = tmp_path / "late.xplane.pb"
    _xplane(late, runs=[TRACE_RUNS[0], TRACE_RUNS[1], (122.0, 128.0, 150_000)])
    monkeypatch.setattr(scoped, "TRACE_FILE", late)
    ctx = _ctx(late)
    assert scoped.run_spans(ctx) and hostlog.aligned(ctx) is None
    assert _read("idle_in_run_ms", ctx) is None and _read("run_dispatch_ms", ctx) == 0.5
    fewer = tmp_path / "fewer.xplane.pb"
    _xplane(fewer, runs=TRACE_RUNS[:2])
    monkeypatch.setattr(scoped, "TRACE_FILE", fewer)
    ctx = _ctx(fewer)
    assert len(scoped.run_spans(ctx) or ()) == 2 and hostlog.aligned(ctx) is None


def test_set_up_rolled_out_of_a_full_ring_reads_nothing(story, monkeypatch):
    """No earlier ``run`` bounds set-up and the ring is full: its start may
    be gone. The window's readers still read."""
    rows = [r for r in _log(threading.get_ident()) if r.start_ns >= 2_000_000]  # the other cell's records rolled out
    monkeypatch.setattr(hostlog, "RECORDS", None)
    monkeypatch.setattr(names, "host_records", lambda since_id=0: rows)
    monkeypatch.setattr(names, "HOST_LOG_LEN", len(rows))
    assert hostlog.window(story).setup is None
    for metric in ("setup_lower_s", "setup_backend_s", "setup_cache_misses", "setup_init_s", "run_dispatch_ms_untraced"):
        assert _read(metric, story) is None
    assert _read("run_trip_count_ms", story) == pytest.approx(0.6)
    monkeypatch.setattr(names, "HOST_LOG_LEN", len(rows) + 1)  # not full: the process's start is in it
    assert _read("setup_cache_misses", story) == 4.0


def test_the_program_log_is_read_through_its_two_public_functions():
    records, ring = hostlog._program_log()
    assert ring == names.HOST_LOG_LEN and records == names.host_records()
    assert {hostlog.RUN, hostlog.INIT} <= set(names.SPANS)
    assert {hostlog.TRIP_COUNT, hostlog.DISPATCH, hostlog.COMPILE_TRACE, hostlog.COMPILE_LOWER,
            hostlog.COMPILE_BACKEND, hostlog.COMPILE_CACHE_HIT} == set(names.LOG_ONLY)

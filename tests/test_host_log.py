"""The host log behind ``span`` (``evox_tpu/core/instrument.py``): one record
a span, with or without a profiler session; parents by thread; the ring's
bound; jax's compiles under the entry point that caused them; ``run``'s parts;
and the log's ``evox:run`` beside the trace's on one clock."""

from __future__ import annotations

import importlib
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmark/ is no package of the install

names = importlib.import_module("evox_tpu.core.instrument")  # the package re-exports a function of that name


def _workflow(dim: int = 8, pop: int = 16):
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.problems.numerical import Sphere

    return StdWorkflow(OpenES(jnp.ones((dim,)), pop), Sphere())


def _mark() -> int:
    """The id of the newest record: ``host_records(_mark())`` later gives
    what was opened since."""
    with names.span("test:mark", annotate=False):
        pass
    return names.host_records()[-1].id


def _profiled(tmp_path, what) -> list:
    """``what()`` under the profiler as the harness starts it; the trace's
    ``evox:`` spans of the host plane, by start."""
    from benchmark.lib import scoped, trace as tr

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        what()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    _, host = scoped._read(str(path), path.stat().st_mtime_ns)
    return [e for e in host if e.name.startswith("evox:")]


# ------------------------------------------------ a span writes one record


@pytest.mark.parametrize("session", (False, True), ids=("no_session", "profiler_session"))
def test_span_writes_one_record_with_parent_and_args(session, tmp_path):
    def body():
        with names.span(names.RUN, n_steps=3) as outer:
            with names.span(names.RUN_DISPATCH, annotate=False) as inner:
                inner.args["cpu_ns"] = 17
            assert outer.name == names.RUN

    mark = _mark()
    before = time.perf_counter_ns()
    spans = _profiled(tmp_path, body) if session else body()
    after = time.perf_counter_ns()
    run, dispatch = names.host_records(mark)
    assert (run.name, run.parent, run.args) == (names.RUN, 0, {"n_steps": 3})
    assert (dispatch.name, dispatch.parent, dispatch.args) == (names.RUN_DISPATCH, run.id, {"cpu_ns": 17})
    assert run.thread == dispatch.thread == threading.get_ident()
    assert before <= run.start_ns <= dispatch.start_ns <= dispatch.end_ns <= run.end_ns <= after
    if session:  # the annotation is the trace's; the log-only form writes none
        assert [e.name for e in spans] == [names.RUN]


def test_a_span_that_raises_still_closes_its_record():
    mark = _mark()
    with pytest.raises(ValueError):
        with names.span(names.STEP):
            raise ValueError("from the body")
    with names.span(names.STEP):
        pass
    first, second = names.host_records(mark)
    assert first.name == second.name == names.STEP and second.parent == 0  # the stack was popped


def test_nesting_and_two_threads():
    """A record's parent is the innermost open record of ITS thread: a span
    another thread holds open is no parent."""
    mark, ready, done = _mark(), threading.Event(), threading.Event()

    def other():
        with names.span(names.STEP, who="other"):
            with names.span(names.FETCH, annotate=False, who="other"):
                ready.set()
                assert done.wait(timeout=30)

    t = threading.Thread(target=other)
    t.start()
    assert ready.wait(timeout=30)
    with names.span(names.RUN, who="main"):
        with names.span(names.RUN_LOOP, who="main"):
            with names.span(names.RUN_DISPATCH, annotate=False, who="main"):
                pass
    done.set()
    t.join(timeout=30)
    assert not t.is_alive()
    by = {(r.name, r.args["who"]): r for r in names.host_records(mark)}
    assert len(by) == 5
    run, loop, dispatch = by[names.RUN, "main"], by[names.RUN_LOOP, "main"], by[names.RUN_DISPATCH, "main"]
    assert (run.parent, loop.parent, dispatch.parent) == (0, run.id, loop.id)
    step, fetch = by[names.STEP, "other"], by[names.FETCH, "other"]
    assert (step.parent, fetch.parent) == (0, step.id)
    assert step.thread == fetch.thread != run.thread
    assert step.start_ns < run.start_ns and run.end_ns < step.end_ns  # open across main's, and no parent of them


def test_the_ring_is_bounded_and_ids_go_on():
    mark = _mark()
    for i in range(names.HOST_LOG_LEN + 10):
        with names.span("test:fill", annotate=False, i=i):
            pass
    records = names.host_records()
    assert len(records) == names.HOST_LOG_LEN and names.host_summary()["full"]
    assert [r.id for r in records] == sorted(r.id for r in records)
    assert records[-1].args == {"i": names.HOST_LOG_LEN + 9} and records[-1].id >= mark + names.HOST_LOG_LEN + 10
    assert records[0].args["i"] == 10  # the oldest went
    assert names.host_records(records[-3].id) == records[-2:]


def test_the_table_holds_the_log_only_names_apart():
    assert not set(names.LOG_ONLY) & set(names.SPANS)
    assert all(n.startswith("evox:") for n in names.LOG_ONLY)
    assert {names.RUN_TRIP_COUNT, names.RUN_DISPATCH, names.COMPILE_TRACE, names.COMPILE_LOWER,
            names.COMPILE_BACKEND, names.COMPILE_CACHE_HIT} == set(names.LOG_ONLY)


# ------------------------------------------------ compiles, and run's parts


def test_a_compile_lands_under_the_entry_point_that_caused_it():
    """With no profiler session: the first ``run`` compiles the step (in its
    peel) and the loop; the log names each function and the record it fell
    in, ``host_summary`` the entry point; a second, warm ``run`` logs none."""
    wf = _workflow(dim=11, pop=22)  # shapes no other test of this file compiles
    state = wf.init(jax.random.PRNGKey(0))
    mark = _mark()
    state = jax.block_until_ready(wf.run(state, 3))
    cold = names.host_records(mark)
    by_id = {r.id: r for r in cold}
    backend = [r for r in cold if r.name == names.COMPILE_BACKEND]
    under = {(r.args["fun_name"], by_id[r.parent].name) for r in backend}
    assert ("jit(run_loop)", names.RUN_DISPATCH) in under
    assert any(parent == names.STEP for _, parent in under)  # the peeled first step's program
    for kind in (names.COMPILE_TRACE, names.COMPILE_LOWER):
        assert any(r.name == kind and "run_loop" in r.args["fun_name"] and by_id[r.parent].name == names.RUN_DISPATCH
                   for r in cold)
    assert all(r.start_ns <= r.end_ns and by_id[r.parent].start_ns <= r.end_ns <= by_id[r.parent].end_ns
               for r in backend)
    loop = next(c for c in names.host_summary()["compiles"] if c["fun_name"] == "jit(run_loop)")
    assert loop["entry_point"] == names.RUN and loop["under"] == names.RUN_DISPATCH
    assert loop["ms"] > 0 and loop["cache_hit"] is False
    summary = names.host_summary()["spans"]
    assert summary[names.RUN]["calls"] >= 1 and summary[names.RUN]["longest_ms"] >= summary[names.RUN]["median_ms"] > 0

    mark = _mark()
    jax.block_until_ready(wf.run(state, 3))
    warm = names.host_records(mark)
    assert not [r for r in warm if r.name.startswith("evox:compile/")]
    assert [r.name for r in warm] == [names.RUN, names.RUN_LOOP, names.RUN_TRIP_COUNT, names.RUN_DISPATCH]


def test_traces_under_a_millisecond_are_left_out():
    """jax reports every function it traces, jnp's own little ones too,
    nested in the trace of the function that calls them: the log keeps the
    program's (a nested one counts once in a union of stretches)."""
    @jax.jit
    def outer(x):
        for _ in range(40):
            x = jnp.sin(x) * jnp.cos(x) + jnp.tanh(x)  # each a jitted function traced inside this one
        return x

    x = jnp.ones((5, 3))
    mark = _mark()
    outer(x)
    traces = [r for r in names.host_records(mark) if r.name == names.COMPILE_TRACE]
    assert "outer" in [r.args["fun_name"] for r in traces]
    assert all(r.end_ns - r.start_ns >= 1_000_000 for r in traces) and len(traces) < 10


def test_run_logs_trip_count_and_dispatch_inside_loop_inside_run():
    wf = _workflow()
    state = jax.block_until_ready(wf.run(wf.init(jax.random.PRNGKey(0)), 2))
    mark = _mark()
    jax.block_until_ready(wf.run(state, 4))
    run, loop, trip, dispatch = names.host_records(mark)
    assert [r.name for r in (run, loop, trip, dispatch)] == [
        names.RUN, names.RUN_LOOP, names.RUN_TRIP_COUNT, names.RUN_DISPATCH]
    assert (loop.parent, trip.parent, dispatch.parent) == (run.id, loop.id, loop.id)
    assert run.args == {"n_steps": 4} and loop.args == {"n_steps": 4} and trip.args == {}
    assert run.start_ns <= loop.start_ns <= trip.start_ns <= trip.end_ns <= dispatch.start_ns
    assert dispatch.end_ns <= loop.end_ns <= run.end_ns
    length = lambda r: r.end_ns - r.start_ns
    assert length(trip) + length(dispatch) <= length(loop) <= length(run)
    assert 0 <= dispatch.args["cpu_ns"] <= length(dispatch) + 1_000_000  # the calling thread's CPU time, another clock


def test_a_step_loop_logs_one_record_a_generation():
    wf = _workflow()
    state = wf.step(wf.step(wf.init(jax.random.PRNGKey(0))))  # the first step's program, then every later one's
    mark = _mark()
    for _ in range(5):
        state = wf.step(state)
    assert [r.name for r in names.host_records(mark)] == [names.STEP] * 5


# ------------------------------------------------ one clock with the trace


def test_under_the_profiler_log_and_trace_align(tmp_path):
    """Each ``evox:run`` is a record on ``perf_counter_ns`` and a span on the
    profiler's clock: their starts differ by one offset, to under 100 us a
    chunk, and so do their lengths."""
    wf = _workflow()
    state = jax.block_until_ready(wf.run(wf.init(jax.random.PRNGKey(0)), 2))
    mark = _mark()

    def chunks():
        nonlocal state
        for _ in range(8):
            state = jax.block_until_ready(wf.run(state, 50))

    spans = [e for e in _profiled(tmp_path, chunks) if e.name == names.RUN]
    records = [r for r in names.host_records(mark) if r.name == names.RUN]
    assert len(spans) == len(records) == 8
    deltas = sorted(s.start_ns - r.start_ns for s, r in zip(spans, records))
    offset = (deltas[3] + deltas[4]) / 2
    assert max(abs(d - offset) for d in deltas) < 100_000
    assert all(abs(s.dur_ns - (r.end_ns - r.start_ns)) < 100_000 for s, r in zip(spans, records))

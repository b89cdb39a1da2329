"""The program's own names, and the readers that stand on them.

(a) At tiny sizes the compiled run loop of each cell's workflow carries every
scope the cell runs in its ``op_name``s, and the scopes change metadata only.
(b) Under the profiler ``wf.run`` writes its host spans onto the main
thread's line, nested, once whether the workflow is instrumented or not, and
no entry point writes a span the table lacks.
(c) ``benchmark/lib/scoped.py`` and each new reader on a hand-written trace
(``data/scoped.xplane.pb``, written from ``data/scoped.xplane.txt`` with
``ProfileData.text_proto_to_serialized_xspace``) and on the traces the two
cells recorded on the chip after the scopes went in.
"""

from __future__ import annotations

import contextlib
import importlib
import re
from pathlib import Path

import jax
import pytest

import _bench_tiny
from benchmark.lib import harness, manifest as mf, scoped, trace as tr

DATA = Path(__file__).resolve().parent / "data"
MANIFEST = mf.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NEW_METRICS = (
    "ask_ms", "evaluate_ms", "tell_ms", "unscoped_device_share", "evaluate_layout_ms",
    "ask_noise_ms", "tell_dominance_ms", "tell_peel_ms", "tell_survivors_ms",
    "run_host_ms", "device_start_lag_ms", "device_start_lag_ms_longest",
)
names = importlib.import_module("evox_tpu.core.instrument")  # the package re-exports a function of that name

# every part scope a cell runs, as (top scope, part)
PARTS = {
    "walker_openes_pop65k": [
        (names.ASK, names.NOISE), (names.ASK, names.PERTURB),
        (names.EVALUATE, names.DECODE), (names.EVALUATE, names.LAYOUT),
        (names.EVALUATE, names.RESET), (names.EVALUATE, names.ROLLOUT_KERNEL),
        (names.TELL, names.FIT_TRANSFORMS), (names.TELL, names.GRADIENT),
        (names.TELL, names.UPDATE),
    ],
    "nsga2_lsmop1_pop50k": [
        (names.ASK, names.MATING), (names.ASK, names.CROSSOVER), (names.ASK, names.MUTATION),
        (names.TELL, names.MERGE), (names.TELL, names.DOMINANCE_BUILD), (names.TELL, names.PEEL),
        (names.TELL, names.CROWDING), (names.TELL, names.SURVIVORS),
    ],
}


def _read(metric: str, ctx):
    return importlib.import_module(f"benchmark.metrics.{metric}").read(ctx)


# ------------------------------------------------ (a) scopes in the run loop


def _run_loop_text(cell: str, tmp_path) -> str:
    """The optimised HLO of the cell's run loop at the tiny sizes."""
    root = _bench_tiny.tiny_checkout(tmp_path)
    _, _, config, traffic = mf.cell_parts(mf.load(root), cell, root)
    builder = importlib.import_module(f"benchmark.builders.{config['builder']}")
    built = builder.build(config, traffic, 7, jax.devices()[:1])
    state = built.wf.step(built.wf.init(built.key))
    fn, args = built.wf.analysis_targets(state)["run"]
    return fn.lower(*args).compile().as_text()


def _without_metadata(text: str) -> str:
    """HLO text less each instruction's ``metadata={...}`` and the tables of
    file names and stack frames that metadata points into."""
    header, _, rest = text.partition("\n")
    body = rest[re.search(r"^(%|ENTRY)", rest, flags=re.M).start():]
    return header + "\n" + re.sub(r",? ?metadata=\{[^}]*\}", "", body)


@pytest.mark.parametrize("cell", CELLS)
def test_run_loop_carries_every_scope_and_only_as_metadata(cell, tmp_path, monkeypatch):
    (tmp_path / "scoped").mkdir(), (tmp_path / "bare").mkdir()
    text = _run_loop_text(cell, tmp_path / "scoped")
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for top in (names.ASK, names.EVALUATE, names.TELL):
        assert any(scoped.under(n, top) for n in op_names), top
    for path in PARTS[cell]:
        assert any(scoped.under(n, *path) for n in op_names), path
    # a part lies under its own top scope and under no other
    for top, part in PARTS[cell]:
        others = [t for t in names.SCOPES if t != top]
        assert not any(scoped.under(n, t, part) for n in op_names for t in others), (top, part)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _run_loop_text(cell, tmp_path / "bare")
    assert not any("evox." in n for n in re.findall(r'op_name="([^"]*)"', bare))
    assert _without_metadata(bare) == _without_metadata(text)


def test_scope_decorates_and_nests():
    @names.scope("part")
    def f(x):
        return x + 1

    def g(x):
        with names.scope(names.TELL):
            return jax.vmap(f)(x) * f(x)

    text = jax.jit(g).lower(jax.numpy.ones((4,))).as_text(debug_info=True)
    found = set(re.findall(r'loc\("(jit\(g\)[^"]*)"', text))
    assert any(scoped.under(n, names.TELL, "part") and "vmap(part)" in n for n in found)
    assert any(n.endswith("evox.tell/part/add") for n in found)
    assert any(n.endswith("evox.tell/mul") and not scoped.under(n, "part") for n in found)


# ------------------------------------------------ (b) host spans under the profiler


def _host_spans(tmp_path, what) -> list:
    """Run ``what()`` under the profiler as the harness starts it; the
    ``evox:`` spans of the host plane, by start."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        what()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    _, host = scoped._read(str(path), path.stat().st_mtime_ns)  # whatever the main thread's line is called
    return [e for e in host if e.name.startswith(scoped.SPAN_PREFIX)]


def _inside(inner, outer) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


@pytest.mark.parametrize("instrumented", (False, True), ids=("bare", "instrumented"))
def test_run_writes_its_spans_once(instrumented, tmp_path):
    from evox_tpu import StdWorkflow, instrument
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.problems.numerical import Sphere

    wf = StdWorkflow(OpenES(jax.numpy.ones((8,)), 16), Sphere())
    recorder = instrument(wf) if instrumented else None
    state = jax.block_until_ready(wf.run(wf.init(jax.random.PRNGKey(0)), 2))  # compiles

    def two_runs():
        nonlocal state
        fresh = wf.init(jax.random.PRNGKey(1))
        jax.block_until_ready(wf.run(fresh, 3))  # first_step: the peel and the loop
        state = jax.block_until_ready(wf.run(state, 3))  # warm: the loop alone

    spans = _host_spans(tmp_path, two_runs)
    by_name = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e)
    assert {k: len(v) for k, v in by_name.items()} == {
        names.INIT: 1, names.RUN: 2, names.RUN_PEEL: 1, names.STEP: 1, names.RUN_LOOP: 2,
    }
    first, second = by_name[names.RUN]
    assert _inside(by_name[names.RUN_PEEL][0], first) and _inside(by_name[names.STEP][0], by_name[names.RUN_PEEL][0])
    assert _inside(by_name[names.RUN_LOOP][0], first) and _inside(by_name[names.RUN_LOOP][1], second)
    assert by_name[names.RUN_PEEL][0].end_ns <= by_name[names.RUN_LOOP][0].start_ns
    assert not _inside(by_name[names.INIT][0], first)
    if recorder is not None:  # its own bookkeeping is as it was
        entries = recorder.summary()["entry_points"]
        assert entries["run"]["calls"] == 3 and entries["step"]["calls"] == 2  # two peels


@pytest.mark.parametrize("instrumented", (False, True), ids=("bare", "instrumented"))
def test_every_span_written_is_in_the_table(instrumented, tmp_path):
    """The recorder wraps ``pipeline_ask``/``pipeline_tell`` too and times
    them; it opens no span of its own, so neither they nor a ``fetch``
    write a name the table lacks, and a span of one name nests in itself."""
    from evox_tpu import StdWorkflow, instrument
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.problems.numerical import Sphere

    wf = StdWorkflow(OpenES(jax.numpy.ones((8,)), 16), Sphere())
    recorder = instrument(wf) if instrumented else None

    def pipeline():
        state = wf.init(jax.random.PRNGKey(0))
        cand, ctx = wf.pipeline_ask(state)
        fitness, pstate = Sphere().evaluate(state.prob, cand)
        state = jax.block_until_ready(wf.pipeline_tell(state, ctx, fitness, pstate))
        if recorder is not None:
            recorder.fetch(fitness, "fitness")
        with names.span(names.RUN), names.span(names.RUN):
            pass

    spans = [e.name for e in _host_spans(tmp_path, pipeline)]
    assert set(spans) <= set(names.SPANS)
    assert spans == [names.INIT] + [names.FETCH] * instrumented + [names.RUN, names.RUN]
    if recorder is not None:
        entries = recorder.summary()["entry_points"]
        assert entries["pipeline_ask"]["calls"] == 1 and entries["pipeline_tell"]["calls"] == 1


# ------------------------------------------------ (c) the readers


def _ctx(path: Path, generations: int, chunks: int):
    """A ``TraceContext`` as the harness builds it, from a kept trace."""
    t = tr.load(path)
    lo, hi = t.window()
    busy = tr.busy_ns(t, lo, hi)
    fullest = max(busy, key=busy.get)
    events = tr.clip(t.devices[fullest], lo, hi)
    return harness.TraceContext(
        config={}, traffic={}, chips=1, device_kind="TPU v5 lite",
        window={"evals": 8 * generations, "seconds": (hi - lo) / 1e9, "generations": generations,
                "chunks": chunks, "chunk_ms": [1.0] * chunks},
        compiles_in_window=0, events=events, busy_ns=busy[fullest], stretch_ns=hi - lo,
    )


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(scoped, "TRACE_FILE", DATA / "scoped.xplane.pb")
    return _ctx(DATA / "scoped.xplane.pb", generations=2, chunks=2)


def test_scoped_view_of_the_small_trace(small):
    view = scoped.load(small)
    assert small.busy_ns == 6720.0 and small.stretch_ns == 8000.0
    assert sum(view.own_ns.values()) == pytest.approx(small.busy_ns)
    # the fullest device alone, inside the stretch alone (the operation at 9500 ns is outside)
    assert view.own_ns["jit(run)/while/body/evox.ask/noise/jit(_normal)/jit(_normal_real)/mul:"] == 600.0
    assert view.own_ns["jit(run)/while:"] == 800.0  # the loops' own time, less what they hold
    assert view.own_ns["copy.11 copy"] == 100.0  # no tf_op: the operation's short name
    # a ref_value stat names its string through the stat metadata
    assert view.own_ns["jit(run)/while/body/evox.tell/gradient/dot_general:"] == 300.0
    assert [e.name for e in view.spans] == [
        "evox:run", "evox:run/loop", "evox:run", "evox:run/loop", "evox:checkpoint/save",
    ]  # evox:init lies before the stretch
    assert view.op_starts[0] == 1250.0 and len(view.op_starts) == 20


EXPECTED_SMALL = {  # ns over the stretch's two generations, or as said
    "ask_ms": 1600 / 2 / 1e6,  # noise 600 + 600 (one under vmap), perturb 300, mating 100
    "evaluate_ms": 1900 / 2 / 1e6,  # decode 200, layout 100, kernel 1400, a fused mul;add 200
    "tell_ms": 2200 / 2 / 1e6,  # gradient 300, dominance 1000, peel 500, crowding 200, survivors 200
    "unscoped_device_share": 100 * 920 / 6720,  # the loops 800, a copy 100, the trip counts 20
    "evaluate_layout_ms": 300 / 2 / 1e6,
    "ask_noise_ms": 1200 / 2 / 1e6,
    "tell_dominance_ms": 1000 / 2 / 1e6,
    "tell_peel_ms": 500 / 2 / 1e6,  # shard_map(remat(peel)): wrapped twice
    "tell_survivors_ms": 400 / 2 / 1e6,
    "run_host_ms": 500 / 1e6,  # spans of 400 and 600 ns
    "device_start_lag_ms": 225 / 1e6,  # 1100 -> 1250 and 5300 -> 5600
    "device_start_lag_ms_longest": 300 / 1e6,
}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_on_the_small_trace(metric, small):
    assert _read(metric, small) == pytest.approx(EXPECTED_SMALL[metric])


def test_layers_add_up_to_busy_time_on_the_small_trace(small):
    per_gen = sum(_read(m, small) for m in ("ask_ms", "evaluate_ms", "tell_ms"))
    rest = scoped.scope_ms(small, (names.CONSTRAIN,), (names.MONITORS,))
    unscoped = _read("unscoped_device_share", small) / 100 * small.busy_ns / 1e6 / 2
    assert per_gen + rest + unscoped == pytest.approx(small.busy_ns / 1e6 / 2)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_finds_nothing_without_the_runs_file(metric, small, monkeypatch, tmp_path):
    # another run's file: its stretch is not this context's, to the nanosecond
    small.stretch_ns += 1.0
    assert scoped.load(small) is None and _read(metric, small) is None
    # no file at all: the tiny checkouts of the CPU tests, which run from another root
    monkeypatch.setattr(scoped, "TRACE_FILE", None)
    monkeypatch.setattr(mf, "ROOT", tmp_path)
    small.stretch_ns -= 1.0
    assert _read(metric, small) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_finds_nothing_in_a_trace_from_before_the_scopes(metric, monkeypatch):
    """data/walker_pop65k_3s.xplane.pb was recorded by PR 25: no operation
    carries a scope and the host wrote no ``evox:`` span, as on the parent
    commit. Every new reader says nothing there, and none raises."""
    path = DATA / "walker_pop65k_3s.xplane.pb"
    monkeypatch.setattr(scoped, "TRACE_FILE", path)
    ctx = _ctx(path, generations=22, chunks=22)
    view = scoped.load(ctx)
    assert view is not None and not view.spans
    assert sum(view.own_ns.values()) == pytest.approx(ctx.busy_ns)
    assert _read(metric, ctx) is None


def test_under_matches_whole_components():
    op = "jit(f)/while/body/vmap(evox.tell)/shard_map(remat(peel))/and:"
    assert scoped.under(op, "evox.tell") and scoped.under(op, "evox.tell", "peel")
    assert not scoped.under(op, "peel", "evox.tell")  # in that order
    assert not scoped.under("jit(f)/evox.tellx/peeling/and:", "evox.tell")
    assert not scoped.under("jit(f)/evox.tellx/peeling/and:", "peel")
    assert not scoped.under("copy.11 copy", "evox.tell")


def test_the_table_of_names_is_what_the_readers_match():
    assert scoped.TOP_SCOPES == names.SCOPES
    assert scoped.SPAN_PREFIX == "evox:" and all(s.startswith("evox:") for s in names.SPANS)
    assert names.RUN == "evox:run"


# Recorded on the TPU v5e after the scopes went in (my chip runs, PR 26): a
# three-second traced window of each cell through the harness, the file cut
# down to the lines the readers read (the devices' ``XLA Ops`` and the host's
# main thread, which the profiler names ``python3`` after the executable) and
# each event to its metadata, offset and duration. Generations and chunks are
# what the run printed; the numbers are what its result line read.
CHIP_TRACES = {
    "walker_openes_pop65k": ("walker_pop65k_scoped_3s.xplane.pb", 22, 11, {
        "ask_ms": 24.8103, "evaluate_ms": 89.9224, "tell_ms": 25.2573,
        "unscoped_device_share": 0.0717, "evaluate_layout_ms": 18.0146, "ask_noise_ms": 8.1213,
        "run_host_ms": 1.4544, "device_start_lag_ms": 0.2781, "device_start_lag_ms_longest": 0.5280,
    }),
    "nsga2_lsmop1_pop50k": ("nsga2_pop50k_scoped_3s.xplane.pb", 48, 12, {
        "ask_ms": 3.2012, "evaluate_ms": 0.3326, "tell_ms": 57.9990,
        "unscoped_device_share": 4.7465, "tell_dominance_ms": 47.0364, "tell_peel_ms": 3.7108,
        "tell_survivors_ms": 6.4160,
        "run_host_ms": 1.6019, "device_start_lag_ms": 1.3324, "device_start_lag_ms_longest": 1.5737,
    }),
}


@pytest.fixture(params=sorted(CHIP_TRACES))
def chip(request, monkeypatch):
    file, generations, chunks, printed = CHIP_TRACES[request.param]
    monkeypatch.setattr(scoped, "TRACE_FILE", DATA / file)
    return request.param, _ctx(DATA / file, generations, chunks), printed


def test_readers_on_the_traces_recorded_on_the_chip(chip):
    cell, ctx, printed = chip
    listed = {
        m["name"] for m in MANIFEST["per_layer"]
        if m["name"] in NEW_METRICS and cell in m["workloads"]
    }
    assert listed == set(printed)  # every new metric the cell lists was read, none None
    for metric, value in printed.items():
        assert _read(metric, ctx) == pytest.approx(value, abs=5e-5), metric


def test_layers_add_up_to_busy_time_on_the_chip(chip):
    """``ask_ms + evaluate_ms + tell_ms`` with the time under
    ``evox.constrain``, ``evox.monitors`` and no scope is the device's busy
    time a generation, to 1 %."""
    _, ctx, _ = chip
    gens = ctx.window["generations"]
    layers = sum(_read(m, ctx) for m in ("ask_ms", "evaluate_ms", "tell_ms"))
    rest = scoped.scope_ms(ctx, (names.CONSTRAIN,), (names.MONITORS,)) or 0.0
    unscoped = _read("unscoped_device_share", ctx) / 100 * ctx.busy_ns / 1e6 / gens
    assert layers + rest + unscoped == pytest.approx(ctx.busy_ns / 1e6 / gens, rel=0.01)
    assert _read("unscoped_device_share", ctx) < 5.0


def test_spans_nest_on_the_chip(chip):
    """Every chunk holds one ``evox:run`` with one ``evox:run/loop`` inside
    it, and the device starts on the chunk after ``run`` was called."""
    _, ctx, _ = chip
    view = scoped.load(ctx)
    runs = [e for e in view.spans if e.name == names.RUN]
    loops = [e for e in view.spans if e.name == names.RUN_LOOP]
    assert len(runs) == len(loops) == ctx.window["chunks"]
    assert all(r.start_ns <= l.start_ns and l.end_ns <= r.end_ns for r, l in zip(runs, loops))
    assert all(lag > 0 for lag in scoped.start_lags_ms(ctx))

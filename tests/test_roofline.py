"""Roofline analytics layer (core/xla_cost.py + core/instrument.py):
AOT cost/memory analysis contract on the 8-device CPU mesh, retrace
detection semantics, Chrome-trace export validity, and the
analysis-disabled no-op law."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    CostAnalyzer,
    DispatchRecorder,
    RetraceError,
    StdWorkflow,
    create_mesh,
    instrument,
    run_report,
    write_chrome_trace,
)
from evox_tpu.algorithms.so.es import CMAES
from evox_tpu.core.xla_cost import (
    CHIP_CEILINGS,
    abstract_signature,
    analyze_callable,
    chip_ceilings,
    roofline_section,
)
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.problems.numerical import Sphere

DIM, POP = 8, 16


def _cmaes_workflow(mesh=None, monitors=()):
    return StdWorkflow(
        CMAES(center_init=jnp.zeros(DIM), init_stdev=1.0, pop_size=POP),
        Sphere(),
        monitors=monitors,
        mesh=mesh,
    )


# --------------------------------------------------------- cost analysis


def test_cost_analysis_contract_on_mesh(ceilings):
    """Acceptance: a CMAES+Sphere run over the 8-device mesh reports a
    roofline section with positive static FLOPs/bytes, achieved-vs-peak
    ratios, and a bound-ness classification for step and run."""
    wf = _cmaes_workflow(mesh=create_mesh())
    # block_dispatch: async-dispatch timings don't scale with the trip
    # count, so the slope needs calls that wait for their result; the two
    # WIDELY separated trip counts make the work delta dominate noise
    rec = instrument(wf, block_dispatch=True)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 5)
    state = wf.run(state, 5)
    state = wf.run(state, 200)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )

    roofline = report["roofline"]
    # the fractions below are of the ceilings the caller handed in, and
    # the report says so: no table entry stands in for the CPU
    assert roofline["ceilings"] == ceilings
    for name in ("step", "run"):
        entry = roofline["entries"][name]
        assert entry["static"]["flops"] > 0, name
        assert entry["static"]["bytes_accessed"] > 0, name
        assert entry["classification"] in (
            "compute-bound", "memory-bound", "dispatch-bound",
        ), name
        assert entry["achieved_tflops"] >= 0
        assert entry["achieved_gbps"] >= 0
        assert 0 <= entry["frac_peak_compute"]
        assert 0 <= entry["frac_peak_bandwidth"]
        assert 0 <= entry["dispatch_overhead_frac"] <= 1
    # dynamic-trip-count fori_loop bodies are counted once by XLA: run's
    # static cost is per generation, i.e. the same scale as step's
    step_flops = roofline["entries"]["step"]["static"]["flops"]
    run_flops = roofline["entries"]["run"]["static"]["flops"]
    assert run_flops < 10 * step_flops
    # warmed two trip counts -> the latency-cancelling differenced slope
    per_work = report["dispatch"]["entry_points"]["run"]["per_work_s"]
    assert per_work["method"] == "differenced"
    assert not per_work["latency_confounded"]
    # memory analysis present on the CPU backend too
    mem = roofline["entries"]["step"]["static"]["memory"]
    assert mem is None or mem["peak_bytes_estimate"] >= 0
    # the merged report is strict JSON end to end
    json.dumps(report, allow_nan=False)


def test_chip_ceilings_table_is_keyed_by_device_kind():
    """The table holds the published v5e peaks under the device_kind jax
    reports for that chip, each entry naming its source."""
    v5e = chip_ceilings("TPU v5 lite")
    assert v5e["mxu_bf16_tflops"] == 197.0 and v5e["hbm_gbps"] == 819.0
    for kind, entry in CHIP_CEILINGS.items():
        assert entry["mxu_bf16_tflops"] > 0 and entry["hbm_gbps"] > 0, kind
        assert entry["source"], kind
    # a copy: a caller editing its ceilings never edits the table
    v5e["hbm_gbps"] = 0.0
    assert CHIP_CEILINGS["TPU v5 lite"]["hbm_gbps"] == 819.0


def test_unknown_device_has_no_default_peak():
    """A device outside the table is an error wherever a fraction of
    peak is asked for — the CPU these tests run on included."""
    assert jax.devices()[0].device_kind not in CHIP_CEILINGS
    with pytest.raises(LookupError, match="no published peaks"):
        chip_ceilings()
    with pytest.raises(LookupError):
        CostAnalyzer()
    with pytest.raises(LookupError):
        instrument(_cmaes_workflow(), analyze=True)
    analyses = {"step": {"flops": 100.0, "bytes_accessed": 50.0, "memory": None}}
    with pytest.raises(LookupError):
        roofline_section(analyses, {"entry_points": {}})


def test_analyze_callable_reports_error_not_raise():
    bad = analyze_callable(lambda x: jnp.sum(x) + "nope", jnp.ones(4))
    assert "error" in bad


def test_analyzer_caches_per_signature(ceilings):
    calls = []

    def f(x):
        calls.append(1)
        return x * 2.0

    ca = CostAnalyzer(ceilings=ceilings)
    ca.analyze("f", f, jnp.ones(8))
    ca.analyze("f", f, jnp.ones(8))  # same signature: cached, no retrace
    assert len(calls) == 1
    ca.analyze("f", f, jnp.ones(16))  # new signature: analyzed afresh
    assert len(calls) == 2


def test_roofline_merge_noop_when_disabled():
    """Report shape with analysis off is exactly the pre-roofline shape."""
    wf = _cmaes_workflow()
    rec = instrument(wf)  # no analyze
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 5)
    report = run_report(wf, state, recorder=rec)
    assert "roofline" not in report
    assert set(report) == {
        "schema", "schema_version", "generation", "telemetry", "dispatch",
    }


def test_roofline_section_without_timing_keeps_static(ceilings):
    analyses = {"step": {"flops": 100.0, "bytes_accessed": 50.0, "memory": None}}
    sec = roofline_section(analyses, {"entry_points": {}}, ceilings)
    entry = sec["entries"]["step"]
    assert entry["static"]["flops"] == 100.0
    assert entry["classification"] is None
    assert "achieved_tflops" not in entry


def test_roofline_section_no_metrics_classifies_none(ceilings):
    """A backend reporting neither flops nor bytes gives zero static
    evidence — the verdict must stay None, never an invented
    dispatch-bound (the measurement itself is still kept)."""
    analyses = {"step": {"flops": None, "bytes_accessed": None, "memory": None}}
    timing = {"per_work_s": {"seconds": 0.01, "method": "differenced"}}
    sec = roofline_section(analyses, {"entry_points": {"step": timing}}, ceilings)
    entry = sec["entries"]["step"]
    assert entry["classification"] is None
    assert entry["measured_s_per_unit"] == 0.01


def test_run_report_survives_analysis_targets_failure(ceilings):
    """analysis_targets raising must cost only the roofline section —
    telemetry and dispatch stay in the report with the error noted."""
    tm = TelemetryMonitor(capacity=4)
    wf = _cmaes_workflow(monitors=(tm,))
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 3)

    def boom(_state):
        raise ValueError("abstract tracing failed")

    wf.analysis_targets = boom
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    assert report["roofline"] == {"error": "ValueError: abstract tracing failed"}
    assert report["telemetry"] and report["dispatch"]["entry_points"]


def test_external_problem_analyzes_pipeline_halves(ceilings):
    """Host problems embed a pure_callback in the jitted step, whose
    host work XLA cannot cost — so analysis covers the pipelined
    halves instead (what run_host_pipelined actually dispatches)."""
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.core.problem import Problem
    from evox_tpu.workflows.pipelined import run_host_pipelined

    class HostSphere(Problem):
        jittable = False
        fit_dtype = np.float32

        def init(self, key=None):
            return jnp.zeros(())

        def fit_shape(self, pop):
            return (pop,)

        def evaluate(self, state, pop):
            fit = jnp.sum(jnp.asarray(pop) ** 2, axis=1)
            return fit.astype(jnp.float32), state

    wf = StdWorkflow(
        PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8), HostSphere()
    )
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(0))
    state = run_host_pipelined(wf, state, 4)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    entries = report["roofline"]["entries"]
    assert sorted(entries) == ["pipeline_ask", "pipeline_tell"]
    for entry in entries.values():
        assert "error" not in entry["static"]
        assert entry["classification"] in (
            "compute-bound", "memory-bound", "dispatch-bound",
        )


# ------------------------------------------------------ retrace detection


def test_retrace_flag_fires_on_shape_change():
    rec = DispatchRecorder()
    f = rec.wrap("f", jax.jit(lambda x: x * 2.0))
    f(jnp.ones(8))
    f(jnp.ones(8))
    assert rec.summary()["retrace_flags"] == []
    f(jnp.ones(16))  # intentional shape change
    summary = rec.summary()
    assert summary["retrace_flags"] == ["f"]
    sigs = summary["entry_points"]["f"]["signatures"]
    assert sigs["aval"] == 2 and sigs["aval_retraces"] == 1 and sigs["flagged"]


def test_strict_retrace_raises_and_dtype_counts_too():
    rec = DispatchRecorder(strict_retrace=True)
    f = rec.wrap("f", jax.jit(lambda x: x * 2.0))
    f(jnp.ones(8))
    with pytest.raises(RetraceError):
        f(jnp.ones(8, dtype=jnp.bfloat16))  # dtype change is a retrace too
    # the guard is NOT one-shot: the refused signature was never
    # recorded, so the identical retry raises again instead of silently
    # dispatching (and paying) the compile
    with pytest.raises(RetraceError):
        f(jnp.ones(8, dtype=jnp.bfloat16))
    f(jnp.ones(8))  # the original signature still passes


def test_retrace_silent_across_fused_run():
    """A 50-generation fused run (plus a warm re-run and step loop) must
    not flag: the first_step peel is a static-structure recompile by
    design, recorded but never flagged — only aval (shape/dtype) changes
    are the silent killer."""
    wf = _cmaes_workflow()
    rec = instrument(wf, strict_retrace=True)  # would raise if flagged
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 50)
    state = wf.run(state, 25)
    for _ in range(3):
        state = wf.step(state)
    summary = rec.summary()
    assert summary["retrace_flags"] == []
    step_sigs = summary["entry_points"]["step"]["signatures"]
    assert step_sigs["aval_retraces"] == 0
    # the peel IS visible as a static-signature recompile, not hidden
    assert step_sigs["static"] >= step_sigs["aval"]


def test_scalar_values_are_not_signatures():
    """run(state, 100) vs run(state, 200): python ints trace to the same
    weak-typed aval — trip-count changes must never read as retraces."""
    (a1, s1) = abstract_signature((jnp.ones(4), 100))
    (a2, s2) = abstract_signature((jnp.ones(4), 200))
    assert a1 == a2 and s1 == s2
    assert abstract_signature((jnp.ones(4), 1.5))[0] != a1


# --------------------------------------------------------- chrome trace


def _validate_trace(trace):
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    counter_last = {}
    begins = 0
    for ev in events:
        assert ev["ph"] in {"X", "B", "E", "C", "M", "i"}, ev
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] in {"B", "E"}:
            begins += 1 if ev["ph"] == "B" else -1
            assert begins >= 0
        if ev["ph"] == "C":
            key = (ev["pid"], ev["name"])
            assert ev["ts"] >= counter_last.get(key, -1.0), (
                f"counter {ev['name']} ts not monotonic"
            )
            counter_last[key] = ev["ts"]
            for v in ev["args"].values():
                assert np.isfinite(v)
    assert begins == 0  # matched B/E (we only emit X, but law stays)


def test_chrome_trace_schema(tmp_path):
    tm = TelemetryMonitor(capacity=16)
    wf = _cmaes_workflow(monitors=(tm,))
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(1))
    state = wf.run(state, 12)
    for _ in range(2):
        state = wf.step(state)
    rec.fetch(state.algo.mean, name="mean")
    path = tmp_path / "trace.json"
    trace = write_chrome_trace(
        str(path),
        recorder=rec,
        workflow=wf,
        state=state,
        extra_counters={"farm/workers_alive": [(rec._created + 0.5, 2)]},
    )
    on_disk = json.loads(path.read_text())  # strict parse (no NaN tokens)
    assert on_disk == trace
    _validate_trace(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "run" in names and "step" in names  # dispatch spans
    assert "mean" in names  # fetch span
    assert "telemetry/best_fitness" in names  # device counter track
    assert "farm/workers_alive" in names  # extra counter track
    # fetch spans carry byte accounting
    fetch = [e for e in trace["traceEvents"] if e.get("cat") == "fetch"]
    assert fetch and all(e["args"]["bytes"] > 0 for e in fetch)


def test_chrome_trace_marks_retraces(tmp_path):
    rec = DispatchRecorder()
    f = rec.wrap("f", jax.jit(lambda x: x * 2.0))
    f(jnp.ones(8))
    f(jnp.ones(16))
    trace = write_chrome_trace(str(tmp_path / "t.json"), recorder=rec)
    _validate_trace(trace)
    assert any(e.get("cat") == "retrace" for e in trace["traceEvents"])


def test_island_workflow_analysis_targets(ceilings):
    """IslandWorkflow advertises the same step/run analysis surface."""
    from evox_tpu import IslandWorkflow
    from evox_tpu.algorithms.so.pso import PSO

    wf = IslandWorkflow(
        PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8),
        Sphere(),
        n_islands=2,
        migrate_every=2,
    )
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(3))
    state = wf.run(state, 4)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    entries = report["roofline"]["entries"]
    assert set(entries) == {"step", "run"}
    assert entries["step"]["static"]["flops"] > 0
    assert entries["step"]["classification"] in (
        "compute-bound", "memory-bound", "dispatch-bound",
    )


def test_pallas_rollout_entry_cost_analysis():
    """The fused rollout entry AOT-analyzes like any other program
    (interpret mode on CPU; the kernel body lowers to XLA ops whose
    FLOPs/bytes the HLO cost analysis counts)."""
    import functools

    from evox_tpu.kernels import fused_rollout

    obs_dim, hidden, act_dim, T, n = 3, 8, 1, 7, 256
    dim = obs_dim * hidden + hidden + hidden * act_dim + act_dim
    theta = 0.5 * jax.random.normal(jax.random.PRNGKey(0), (n, dim))
    s0 = {
        "th": jnp.linspace(-1.0, 1.0, n),
        "thdot": jnp.linspace(-1.0, 1.0, n),
    }
    fn = functools.partial(
        fused_rollout, T=T, obs_dim=obs_dim, hidden=hidden, act_dim=act_dim,
        interpret=True,
    )
    analysis = analyze_callable(fn, theta, s0)
    assert "error" not in analysis, analysis
    assert analysis["flops"] > 0
    assert analysis["bytes_accessed"] > 0


# -------------------------------------------------------- kernel headroom


def test_fused_rollout_vmem_headroom():
    """The VMEM plan the kernel's CompilerParams use and the analysis
    helper report must agree, and the default walker shape must keep
    positive headroom past double-buffered residency."""
    from evox_tpu.kernels import fused_rollout_analysis
    from evox_tpu.kernels.rollout_mlp import _vmem_plan

    ws = (
        jnp.zeros((244, 64, 128)),
        jnp.zeros((64, 64, 128)),
        jnp.zeros((64, 17, 128)),
    )
    bs = (jnp.zeros((64, 128)), jnp.zeros((64, 128)), jnp.zeros((17, 128)))
    report = fused_rollout_analysis(ws, bs)
    per_cell, limit = _vmem_plan(ws, bs, 128)
    assert report["resident_bytes_per_cell"] == per_cell
    assert report["vmem_limit_bytes"] == limit
    assert report["headroom_bytes"] > 0
    assert report["vmem_limit_bytes"] <= report["vmem_cap_bytes"]
    # bf16 residency halves (the per-tile budget knob)
    bf16 = fused_rollout_analysis(ws, bs, weight_dtype=jnp.bfloat16)
    assert bf16["resident_bytes_per_cell"] * 2 == per_cell

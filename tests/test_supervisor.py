"""RunSupervisor chaos tests: dispatch deadlines, classified retry,
checkpoint replay, degradation, and topology-portable resume.

Every fault is injected deterministically at the call boundary
(tests/_chaos.py::FlakyDispatch — no real backend fault), so the assertions are
exact: a supervised run that healed N transients and one hang produces
BIT-identical final state and telemetry rings to the same supervised run
with no faults; an 8-device checkpoint resumes on 4 and 1 devices and
reproduces the straight run's remaining trajectory.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    CheckpointConfigError,
    DispatchDeadlineError,
    IslandWorkflow,
    RunAbortedError,
    RunSupervisor,
    StdWorkflow,
    WorkflowCheckpointer,
)
from evox_tpu.core.distributed import POP_AXIS, create_mesh
from evox_tpu.core.problem import Problem
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.workflows.checkpoint import (
    restore_layouts,
    state_config_fingerprint,
)
from evox_tpu.workflows.supervisor import classify_error

from tests._chaos import FlakyDispatch, make_fault

pytestmark = pytest.mark.chaos

DIM, POP = 6, 16


def _mk_wf(mesh=None, pop=POP, capacity=32):
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.problems.numerical import Sphere

    algo = PSO(lb=jnp.full((DIM,), -5.0), ub=jnp.full((DIM,), 5.0), pop_size=pop)
    return StdWorkflow(
        algo,
        Sphere(),
        monitors=(TelemetryMonitor(capacity=capacity),),
        mesh=mesh,
    )


def _tree_assert_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tree_assert_allclose(a, b, rtol=1e-6, atol=1e-6):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


# ---------------------------------------------------------------- classifier
def test_classifier_folds_backend_failures():
    assert classify_error(make_fault("transient")) == "transient"
    assert classify_error(make_fault("oom")) == "oom"
    assert classify_error(make_fault("http413")) == "oom"
    assert classify_error(make_fault("fatal")) == "fatal"
    assert classify_error(ConnectionResetError("peer")) == "transient"
    assert classify_error(TimeoutError("no answer")) == "transient"
    assert classify_error(DispatchDeadlineError("late")) == "deadline"
    # a shape that happens to contain 413 must NOT classify as OOM
    assert classify_error(ValueError("shape (413, 2) mismatch")) == "fatal"
    # patterns match the MESSAGE, never the type name — a bubbled-up
    # RunAbortedError must not read as 'aborted'-transient; it is a
    # supervisor's final verdict and always fatal
    assert classify_error(RunAbortedError("ladder spent", {})) == "fatal"
    assert (
        classify_error(type("AbortedCancelledError", (ValueError,), {})("x"))
        == "fatal"
    )


# ------------------------------------------------------------------ deadline
def test_deadline_fires_within_2x_bound():
    """Acceptance: a hung dispatch raises (through the exhausted ladder)
    within 2x the configured deadline instead of blocking forever."""
    wf = _mk_wf()
    state = wf.init(jax.random.PRNGKey(0))
    wf.run = FlakyDispatch(wf.run, faults={0: "hang"}, hang_s=30.0)
    sup = RunSupervisor(deadline_s=0.75, max_retries=0)
    t0 = time.perf_counter()
    with pytest.raises(RunAbortedError) as ei:
        sup.run(wf, state, 4)
    elapsed = time.perf_counter() - t0
    assert elapsed < 2 * 0.75, f"deadline took {elapsed:.2f}s to surface"
    assert isinstance(ei.value.__cause__, DispatchDeadlineError)
    assert ei.value.post_mortem["classification"] == "deadline"
    assert sup.counters["deadline_hits"] == 1


# ------------------------------------------------- transient + hang healing
def test_retry_after_transients_and_hang_is_bit_identical(tmp_path):
    """Chaos acceptance law: <=N transients plus one hang, healed by the
    supervisor, yield BIT-identical final state — telemetry rings
    included — to the identically-chunked run with no faults."""
    key = jax.random.PRNGKey(7)
    wf_clean = _mk_wf()
    state0 = wf_clean.init(key)
    ck_clean = WorkflowCheckpointer(str(tmp_path / "clean"), every=4)
    sup_clean = RunSupervisor(checkpointer=ck_clean)
    final_clean = sup_clean.run(wf_clean, state0, 12)
    assert sup_clean.report()["outcome"] == "clean"

    wf = _mk_wf()
    # warm this instance's compiled closures FIRST: with a deadline armed,
    # a healthy-but-cold dispatch (trace+compile, seconds on one CPU core)
    # must not trip the watchdog meant for the injected hang
    wf.run(state0, 2)
    # chunk dispatches (every=4): two transients before the first chunk
    # lands, then a hang on what would be the second chunk
    wf.run = FlakyDispatch(
        wf.run,
        faults={0: "transient", 1: "transient", 3: "hang"},
        hang_s=10.0,
    )
    ck = WorkflowCheckpointer(str(tmp_path / "chaos"), every=4)
    sup = RunSupervisor(
        checkpointer=ck, deadline_s=2.0, max_retries=3, backoff_s=0.01
    )
    final = sup.run(wf, state0, 12)

    assert int(final.generation) == 12
    _tree_assert_equal(final, final_clean)
    tm = wf.monitors[0]
    assert tm.fingerprint(final.monitors[0]) == tm.fingerprint(
        final_clean.monitors[0]
    )
    rep = sup.report()
    assert rep["outcome"] == "recovered"
    assert rep["counters"]["retries"] == 3  # 2 transients + 1 deadline
    assert rep["counters"]["deadline_hits"] == 1
    assert rep["counters"]["aborts"] == 0


def test_restore_rung_replays_from_snapshot(tmp_path):
    """When retries are exhausted the supervisor restores the newest
    snapshot and replays — same final state as the clean run."""
    key = jax.random.PRNGKey(3)
    wf_clean = _mk_wf()
    state0 = wf_clean.init(key)
    ckc = WorkflowCheckpointer(str(tmp_path / "c"), every=3)
    final_clean = RunSupervisor(checkpointer=ckc).run(wf_clean, state0, 9)

    wf = _mk_wf()
    # chunk 2 (calls: 0 ok, 1 ok, then 2..4 transient) fails past
    # max_retries=2 -> restore rung replays from the gen-6 snapshot
    wf.run = FlakyDispatch(
        wf.run, faults={2: "transient", 3: "transient", 4: "transient"}
    )
    ck = WorkflowCheckpointer(str(tmp_path / "x"), every=3)
    sup = RunSupervisor(
        checkpointer=ck, max_retries=2, max_restores=1, backoff_s=0.01
    )
    final = sup.run(wf, state0, 9)
    assert int(final.generation) == 9
    _tree_assert_equal(final, final_clean)
    rep = sup.report()
    assert rep["counters"]["restores"] == 1
    assert rep["outcome"] == "recovered"


# ------------------------------------------------------------- OOM degrade
class _HostSphere(Problem):
    jittable = False

    def fit_shape(self, pop_size):
        return (pop_size,)

    def evaluate(self, state, pop):
        return np.sum(np.asarray(pop) ** 2, axis=1).astype(np.float32), state


def _mk_pipelined_wf():
    from evox_tpu.algorithms.so.es import OpenES

    algo = OpenES(jnp.zeros(DIM), pop_size=8, learning_rate=0.1, noise_stdev=0.5)
    return StdWorkflow(
        algo, _HostSphere(), monitors=(TelemetryMonitor(capacity=16),)
    )


def test_oom_escalation_halves_pipelined_eval_chunk_and_completes(tmp_path):
    """Acceptance: OOM on full-width host evaluation degrades (the eval
    chunk halves) and the run completes, bit-identical to the clean
    run — _HostSphere scores rows independently, so chunked evaluation
    is invisible."""
    from evox_tpu.workflows.pipelined import run_host_pipelined

    key = jax.random.PRNGKey(5)
    wf_clean = _mk_pipelined_wf()
    state0 = wf_clean.init(key)
    final_clean = run_host_pipelined(wf_clean, state0, 6)

    wf = _mk_pipelined_wf()

    def oom_when_wide(index, args, kwargs):
        batch = jax.tree.leaves(args[1])[0].shape[0]
        return "oom" if batch > 4 else None

    wf.problem.evaluate = FlakyDispatch(
        wf.problem.evaluate, trigger=oom_when_wide
    )
    sup = RunSupervisor(max_retries=2, backoff_s=0.01)
    final = sup.run_host_pipelined(wf, state0, 6)
    assert int(final.generation) == 6
    _tree_assert_equal(final, final_clean)
    rep = sup.report()
    assert rep["counters"]["degradations"] == 1  # 8 -> 4 sufficed
    assert rep["outcome"] == "recovered"
    assert wf.problem.evaluate.served > 0


def test_http413_also_takes_the_degrade_rung():
    wf = _mk_pipelined_wf()
    state0 = wf.init(jax.random.PRNGKey(9))

    def too_large_when_wide(index, args, kwargs):
        batch = jax.tree.leaves(args[1])[0].shape[0]
        return "http413" if batch > 2 else None

    wf.problem.evaluate = FlakyDispatch(
        wf.problem.evaluate, trigger=too_large_when_wide
    )
    sup = RunSupervisor(max_retries=1, backoff_s=0.01)
    final = sup.run_host_pipelined(wf, state0, 2)
    assert int(final.generation) == 2
    assert sup.counters["degradations"] == 2  # 8 -> 4 -> 2


# --------------------------------------------------------- exhausted ladder
def test_exhausted_ladder_raises_run_aborted_with_post_mortem(tmp_path):
    wf = _mk_wf()
    state0 = wf.init(jax.random.PRNGKey(1))
    wf.run = FlakyDispatch(wf.run, trigger=lambda i, a, k: "transient")
    ck = WorkflowCheckpointer(str(tmp_path / "pm"), every=4)
    sup = RunSupervisor(
        checkpointer=ck, max_retries=2, max_restores=1, backoff_s=0.005
    )
    with pytest.raises(RunAbortedError) as ei:
        sup.run(wf, state0, 8)
    pm = ei.value.post_mortem
    assert pm["entry"] == "run"
    assert pm["classification"] == "transient"
    assert pm["ladder"]["rung"] == "exhausted"
    assert pm["ladder"]["retries"] == 2
    assert pm["counters"]["retries"] >= 2
    assert pm["events_tail"], "post-mortem must carry the event trail"
    assert sup.report()["outcome"] == "aborted"
    # no snapshot ever landed (every dispatch died) -> restore rung found
    # nothing and the ladder was exhausted without a restore event
    assert sup.counters["restores"] == 0


def test_restore_budget_is_per_run_not_per_chunk(tmp_path):
    """A permanently failing chunk WITH a snapshot on disk must exhaust
    the run-level restore budget and abort — not ladder-cycle
    restore -> fail -> restore forever."""
    wf = _mk_wf()
    state0 = wf.init(jax.random.PRNGKey(8))
    ck = WorkflowCheckpointer(str(tmp_path / "loop"), every=3)
    # land a real snapshot first, then fail every subsequent dispatch
    good = wf.run(state0, 3, checkpointer=ck)
    assert int(good.generation) == 3
    wf.run = FlakyDispatch(wf.run, trigger=lambda i, a, k: "transient")
    sup = RunSupervisor(
        checkpointer=ck, max_retries=1, max_restores=2, backoff_s=0.005
    )
    with pytest.raises(RunAbortedError) as ei:
        sup.run(wf, good, 9)
    assert sup.counters["restores"] == 2  # budget spent exactly once per run
    assert ei.value.post_mortem["ladder"]["restores"] == 2


def test_fatal_errors_short_circuit_the_ladder():
    wf = _mk_wf()
    state0 = wf.init(jax.random.PRNGKey(2))
    wf.run = FlakyDispatch(wf.run, faults={0: "fatal"})
    sup = RunSupervisor(max_retries=5, backoff_s=0.01)
    with pytest.raises(RunAbortedError) as ei:
        sup.run(wf, state0, 4)
    assert ei.value.post_mortem["classification"] == "fatal"
    assert ei.value.post_mortem["ladder"]["rung"] == "fatal"
    assert sup.counters["retries"] == 0  # fatal never retries


# --------------------------------------------------- report + trace contract
def test_supervisor_section_and_trace_markers_validate(tmp_path):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "check_report",
        pathlib.Path(__file__).resolve().parent.parent
        / "tools"
        / "check_report.py",
    )
    check_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_report)
    validate_chrome_trace = check_report.validate_chrome_trace
    validate_run_report = check_report.validate_run_report

    from evox_tpu import instrument, run_report, write_chrome_trace

    wf = _mk_wf()
    rec = instrument(wf)
    state0 = wf.init(jax.random.PRNGKey(4))
    wf.run = FlakyDispatch(wf.run, faults={0: "transient"})
    sup = RunSupervisor(max_retries=2, backoff_s=0.01)
    final = sup.run(wf, state0, 4)
    # duck-typed pickup: sup advertised itself on the workflow
    report = run_report(wf, final, recorder=rec)
    assert report["supervisor"]["counters"]["retries"] == 1
    assert report["supervisor"]["outcome"] == "recovered"
    assert validate_run_report(report) == []

    trace = write_chrome_trace(
        str(tmp_path / "t.json"), recorder=rec, workflow=wf, state=final
    )
    markers = [
        e for e in trace["traceEvents"] if e.get("cat") == "supervisor"
    ]
    assert markers and all(e["ph"] == "i" for e in markers)
    assert any(e["name"] == "supervisor:retry" for e in markers)
    assert validate_chrome_trace(trace) == []

    # a mangled supervisor section must be CAUGHT by the validator
    bad = dict(report)
    bad["supervisor"] = dict(report["supervisor"], outcome="fine")
    assert any("outcome" in e for e in validate_run_report(bad))


# ---------------------------------------------------- checkpoint durability
def test_manifest_carries_config_and_topology(tmp_path):
    import json

    wf = _mk_wf()
    state = wf.init(jax.random.PRNGKey(0))
    ck = WorkflowCheckpointer(str(tmp_path), every=2)
    path = ck.save(state)
    manifest = json.loads(
        (tmp_path / (path.name + ".manifest.json")).read_text()
    )
    assert manifest["config_sha"] == state_config_fingerprint(state)
    topo = manifest["save_topology"]
    assert topo["device_count"] == jax.device_count()
    # fingerprint is host/device invariant: the snapshot's numpy pytree
    # fingerprints identically to the live state it came from
    assert state_config_fingerprint(jax.device_get(state)) == manifest[
        "config_sha"
    ]
    # ...and static-field invariant: mid-run first_step=False still matches
    assert state_config_fingerprint(state.replace(first_step=False)) == (
        manifest["config_sha"]
    )


def test_config_guard_refuses_foreign_snapshot(tmp_path):
    """resume()/run(resume_from=) refuse a snapshot written under a
    different pop size or algorithm; the override flag restores anyway."""
    wf16 = _mk_wf(pop=16)
    state16 = wf16.init(jax.random.PRNGKey(0))
    ck = WorkflowCheckpointer(str(tmp_path), every=2)
    wf16.run(state16, 4, checkpointer=ck)

    wf8 = _mk_wf(pop=8)
    with pytest.raises(CheckpointConfigError, match="different"):
        wf8.resume(ck, 8)
    state8 = wf8.init(jax.random.PRNGKey(1))
    with pytest.raises(CheckpointConfigError):
        wf8.run(state8, 8, resume_from=ck)
    # override: the snapshot is handed back despite the mismatch
    got = ck.latest(expect_like=state8, allow_config_mismatch=True)
    assert int(got.generation) == 4
    # matching config restores fine
    assert int(wf16.resume(ck, 4).generation) == 4


# ------------------------------------------------- topology-portable resume
@pytest.mark.slow
def test_checkpoint_resumes_across_8_4_1_device_meshes(tmp_path):
    """Acceptance: a run checkpointed on the 8-device mesh resumes on 4
    and on 1 device(s) and reproduces the straight run's remaining
    trajectory (conftest forces an 8-device CPU mesh)."""
    devs = jax.devices()
    assert len(devs) >= 8, "conftest should provide 8 virtual devices"
    mesh8 = create_mesh(devices=devs[:8])
    wf8 = _mk_wf(mesh=mesh8)
    state0 = wf8.init(jax.random.PRNGKey(11))
    straight = wf8.run(state0, 20)

    ck = WorkflowCheckpointer(str(tmp_path / "topo"), every=5)
    wf8b = _mk_wf(mesh=mesh8)
    mid = wf8b.run(state0, 10, checkpointer=ck)
    assert int(mid.generation) == 10

    for n_dev in (4, 1):
        mesh = create_mesh(devices=devs[:n_dev])
        wf = _mk_wf(mesh=mesh)
        resumed = wf.resume(
            WorkflowCheckpointer(str(tmp_path / "topo"), every=5), 20
        )
        assert int(resumed.generation) == 20
        # Min-based trajectory leaves are BIT-identical across meshes (min
        # is exactly associative); sum-based reductions (the telemetry
        # ring's finite-masked MEAN over the population) legitimately
        # reassociate when the pop axis is resharded — observed drift is
        # the last float32 bit (~1e-7 relative). Same-topology replay is
        # held to full bit-identity by the retry/restore tests above.
        np.testing.assert_array_equal(
            np.asarray(resumed.algo.gbest_fitness),
            np.asarray(straight.algo.gbest_fitness),
        )
        np.testing.assert_array_equal(
            np.asarray(resumed.monitors[0].ring_best),
            np.asarray(straight.monitors[0].ring_best),
        )
        # the integer counter surface IS genuinely bitwise across
        # layouts — hold it to the stable attestor fingerprint instead
        # of letting the allclose below paper over it (ISSUE 20)
        tm = TelemetryMonitor(capacity=32)
        assert tm.fingerprint(
            resumed.monitors[0], stable=True
        ) == tm.fingerprint(straight.monitors[0], stable=True)
        _tree_assert_allclose(resumed, straight)


def test_restore_layouts_places_annotated_leaves(tmp_path):
    """restore_layouts puts population-annotated leaves back on the
    'pop' axis of the CURRENT mesh (here: 2 devices) eagerly."""
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    mesh8 = create_mesh(devices=devs[: min(8, len(devs))])
    wf = _mk_wf(mesh=mesh8)
    state = wf.init(jax.random.PRNGKey(0))
    ck = WorkflowCheckpointer(str(tmp_path), every=2)
    ck.save(wf.run(state, 2, checkpointer=ck))
    host = ck.latest(expect_like=state)
    # host numpy leaves, no mesh attached
    assert isinstance(np.asarray(host.algo.population), np.ndarray)

    mesh2 = create_mesh(devices=devs[:2])
    placed = restore_layouts(host, mesh=mesh2)
    pop_sharding = placed.algo.population.sharding
    assert pop_sharding.mesh.shape[POP_AXIS] == 2
    assert pop_sharding.spec == P(POP_AXIS)
    # unannotated/replicated fields land replicated
    assert placed.generation.sharding.spec == P()


# --------------------------------------------------------------- uniformity
def test_supervisor_drives_island_workflow(tmp_path):
    """sup.run works for IslandWorkflow too (same run/state contract),
    and islands gained the checkpointer/resume law."""
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.problems.numerical import Sphere

    def mk():
        return IslandWorkflow(
            PSO(lb=jnp.full((4,), -3.0), ub=jnp.full((4,), 3.0), pop_size=8),
            Sphere(),
            n_islands=2,
            migrate_every=3,
        )

    wf = mk()
    state0 = wf.init(jax.random.PRNGKey(6))
    straight = wf.run(state0, 8)

    wf2 = mk()
    wf2.run = FlakyDispatch(wf2.run, faults={1: "transient"})
    ck = WorkflowCheckpointer(str(tmp_path / "isl"), every=4)
    sup = RunSupervisor(checkpointer=ck, max_retries=2, backoff_s=0.01)
    final = sup.run(wf2, state0, 8)
    assert int(final.generation) == 8
    _tree_assert_equal(final, straight)
    assert sup.counters["retries"] == 1

    # crashed-and-resumed island run reproduces the straight run
    wf3 = mk()
    resumed = wf3.run(state0, 8, resume_from=str(tmp_path / "isl"))
    assert int(resumed.generation) == 8
    _tree_assert_equal(resumed, straight)

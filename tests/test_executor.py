"""GenerationExecutor chaos laws: one executor, five policies.

The executor owns the generation loop every driver used to hand-roll;
these tests pin the laws the port must preserve — K=0 bit-equivalence
to ``wf.step`` loops across Std/host/islands/tenancy, crash-mid-overlap
resume equivalence through the background checkpoint lane, the
supervisor's retry/deadline/degrade ladder re-asserted through the
executor hooks — plus the new opt-in surface: bounded-staleness tells
(OpenES on Sphere convergence gate at K∈{1,2} with the stale-tell
counter asserted through ``run_report``), background-I/O backpressure,
and the v4 ``executor`` report/trace schema.
"""

import importlib.util
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    GenerationExecutor,
    IslandWorkflow,
    RunAbortedError,
    RunSupervisor,
    StdWorkflow,
    VectorizedWorkflow,
    WorkflowCheckpointer,
    instrument,
    run_report,
    write_chrome_trace,
)
from evox_tpu.core.problem import Problem
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.workflows.pipelined import chunked_evaluate, run_host_pipelined

from tests._chaos import FlakyDispatch

pytestmark = pytest.mark.chaos

DIM = 6


def _load_check_report():
    spec = importlib.util.spec_from_file_location(
        "check_report",
        pathlib.Path(__file__).resolve().parent.parent / "tools" / "check_report.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _HostSphere(Problem):
    """Deterministic host (non-jittable) problem, optional sleep and
    call/thread accounting for the overlap assertions."""

    jittable = False

    def __init__(self, sleep: float = 0.0):
        self.sleep = sleep
        self.calls = 0

    def init(self, key=None):
        return jnp.zeros(())

    def fit_shape(self, pop_size):
        return (pop_size,)

    def evaluate(self, state, pop):
        self.calls += 1
        if self.sleep:
            time.sleep(self.sleep)
        return np.sum(np.asarray(pop) ** 2, axis=1).astype(np.float32), state


class _DeviceSphere(Problem):
    jittable = True

    def init(self, key=None):
        return jnp.zeros(())

    def fit_shape(self, pop_size):
        return (pop_size,)

    def evaluate(self, state, pop):
        return jnp.sum(pop**2, axis=1), state


def _pso_wf(problem, pop=16, capacity=32):
    from evox_tpu.algorithms.so.pso import PSO

    algo = PSO(
        lb=jnp.full((DIM,), -5.0), ub=jnp.full((DIM,), 5.0), pop_size=pop
    )
    return StdWorkflow(
        algo, problem, monitors=(TelemetryMonitor(capacity=capacity),)
    )


def _openes_wf(problem, pop=64, lr=0.15, sigma=0.3, monitors=()):
    from evox_tpu.algorithms.so.es import OpenES

    algo = OpenES(
        5.0 * jnp.ones(8), pop_size=pop, learning_rate=lr, noise_stdev=sigma
    )
    return StdWorkflow(algo, problem, monitors=monitors)


def _tree_assert_equal(a, b, rtol=None):
    """Bit for bit; with ``rtol``, float leaves to that tolerance (an
    absolute floor of a tenth of it) and every other leaf still exactly."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if rtol is not None and np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol / 10)
        else:
            np.testing.assert_array_equal(x, y)


# ------------------------------------------------------- K=0 bit-equivalence
def test_host_run_bit_identical_to_step_loop():
    """Executor host pipeline at K=0 ≡ a serial wf.step (callback) loop —
    the legacy run_host_pipelined law, now owned by the executor; also
    covers StdWorkflow.run's re-routed host-problem path."""
    wf_a = _pso_wf(_HostSphere())
    wf_b = _pso_wf(_HostSphere())
    wf_c = _pso_wf(_HostSphere())
    s0 = wf_a.init(jax.random.PRNGKey(3))
    serial = wf_a.init(jax.random.PRNGKey(3))
    for _ in range(7):
        serial = wf_a.step(serial)
    ex = GenerationExecutor()
    via_executor = ex.run_host(wf_b, s0, 7)
    via_run = wf_c.run(wf_c.init(jax.random.PRNGKey(3)), 7)  # host path reroute
    _tree_assert_equal(serial, via_executor)
    _tree_assert_equal(serial, via_run)
    tm = wf_a.monitors[0]
    assert tm.fingerprint(serial.monitors[0]) == tm.fingerprint(
        via_executor.monitors[0]
    )
    rep = ex.report()
    assert rep["counters"]["stale_tells"] == 0
    assert rep["counters"]["generations"] == 7


def test_fused_run_chunked_bit_identical(tmp_path):
    """run_fused's cadence chunking + background snapshot lane ≡ one
    straight fused dispatch — for Std, islands, and a tenancy fleet."""
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.problems.numerical import Sphere

    # Std
    wf = _pso_wf(_DeviceSphere())
    s0 = wf.init(jax.random.PRNGKey(0))
    straight = wf.run(s0, 10)
    ck = WorkflowCheckpointer(str(tmp_path / "std"), every=3)
    chunked = GenerationExecutor().run_fused(wf, s0, 10, checkpointer=ck)
    _tree_assert_equal(straight, chunked)
    assert len(ck.snapshots()) > 0

    # islands
    def isl():
        return IslandWorkflow(
            PSO(lb=jnp.full((4,), -3.0), ub=jnp.full((4,), 3.0), pop_size=8),
            Sphere(),
            n_islands=2,
            migrate_every=3,
        )

    wf_i = isl()
    s0 = wf_i.init(jax.random.PRNGKey(6))
    straight = wf_i.run(s0, 8)
    ck = WorkflowCheckpointer(str(tmp_path / "isl"), every=4)
    chunked = GenerationExecutor().run_fused(isl(), s0, 8, checkpointer=ck)
    _tree_assert_equal(straight, chunked)

    # tenancy fleet
    def fleet():
        return VectorizedWorkflow(
            PSO(lb=jnp.full((4,), -3.0), ub=jnp.full((4,), 3.0), pop_size=8),
            Sphere(),
            n_tenants=3,
        )

    wf_f = fleet()
    s0 = wf_f.init(jax.random.PRNGKey(9))
    straight = wf_f.run(s0, 9)
    ck = WorkflowCheckpointer(str(tmp_path / "fleet"), every=4)
    chunked = GenerationExecutor().run_fused(fleet(), s0, 9, checkpointer=ck)
    _tree_assert_equal(straight, chunked)


# ------------------------------------------------ crash-mid-overlap + resume
def test_crash_mid_overlap_resume_equivalence(tmp_path):
    """A host-pipelined run killed mid-overlap (the eval of the NEXT
    generation already in flight when the hook raises) resumes from the
    background-lane snapshots and reproduces the straight run."""
    wf_clean = _pso_wf(_HostSphere())
    s0 = wf_clean.init(jax.random.PRNGKey(5))
    straight = run_host_pipelined(wf_clean, s0, 12)

    class Crash(RuntimeError):
        pass

    def crashing_hook(g, state, fitness):
        if g == 7:
            raise Crash(f"simulated driver crash at generation {g}")

    wf = _pso_wf(_HostSphere())
    ck = WorkflowCheckpointer(str(tmp_path / "crash"), every=4)
    with pytest.raises(Crash):
        run_host_pipelined(
            wf, s0, 12, checkpointer=ck, on_generation=crashing_hook
        )
    # the crash landed AFTER the gen-4 (and possibly gen-8) snapshots;
    # all in-flight background saves were flushed before the raise
    assert len(ck.snapshots()) >= 1
    wf2 = _pso_wf(_HostSphere())
    resumed = run_host_pipelined(wf2, s0, 12, resume_from=ck)
    assert int(resumed.generation) == 12
    _tree_assert_equal(straight, resumed)
    # resuming the COMPLETED run is a no-op (no stray background eval)
    calls_before = wf2.problem.calls
    again = run_host_pipelined(wf2, resumed, 12, resume_from=ck)
    _tree_assert_equal(straight, again)
    assert wf2.problem.calls == calls_before


def test_fused_crash_resume_through_executor(tmp_path):
    """run_fused + background snapshots: kill between chunks, resume to
    the total target, reproduce the straight run (Std jittable path)."""
    wf = _pso_wf(_DeviceSphere())
    s0 = wf.init(jax.random.PRNGKey(8))
    straight = wf.run(s0, 12)
    ck = WorkflowCheckpointer(str(tmp_path / "fz"), every=3)
    wf2 = _pso_wf(_DeviceSphere())
    wf2.run = FlakyDispatch(wf2.run, faults={2: "fatal"})
    sup = RunSupervisor(max_retries=0)
    with pytest.raises(RunAbortedError):
        GenerationExecutor(supervisor=sup).run_fused(
            wf2, s0, 12, checkpointer=ck
        )
    assert len(ck.snapshots()) >= 1  # chunks 0,1 landed durably
    wf3 = _pso_wf(_DeviceSphere())
    resumed = wf3.run(s0, 12, resume_from=ck)
    _tree_assert_equal(straight, resumed)


# ------------------------------------------- supervisor laws through executor
@pytest.mark.slow
def test_supervisor_retry_heals_bit_identical_through_executor(tmp_path):
    key = jax.random.PRNGKey(7)
    wf_clean = _pso_wf(_DeviceSphere())
    s0 = wf_clean.init(key)
    ck_c = WorkflowCheckpointer(str(tmp_path / "c"), every=4)
    clean = RunSupervisor(checkpointer=ck_c).run(wf_clean, s0, 12)

    wf = _pso_wf(_DeviceSphere())
    wf.run(s0, 2)  # warm before arming the deadline
    wf.run = FlakyDispatch(
        wf.run, faults={0: "transient", 1: "transient", 3: "hang"}, hang_s=10.0
    )
    ck = WorkflowCheckpointer(str(tmp_path / "x"), every=4)
    sup = RunSupervisor(
        checkpointer=ck, deadline_s=2.0, max_retries=3, backoff_s=0.01
    )
    ex = GenerationExecutor(supervisor=sup)
    final = ex.run_fused(wf, s0, 12, checkpointer=ck)
    assert int(final.generation) == 12
    _tree_assert_equal(final, clean)
    rep = sup.report()
    assert rep["outcome"] == "recovered"
    assert rep["counters"]["retries"] == 3
    assert rep["counters"]["deadline_hits"] == 1
    assert ex.counters["supervised_chunks"] >= 3


def test_supervisor_degrade_rung_through_executor():
    """OOM on the full host batch → the executor's degrade hook halves
    eval_chunk (supervisor policy floor honored) and the run completes
    bit-identical to the clean run."""
    from evox_tpu.algorithms.so.es import OpenES

    def mk():
        algo = OpenES(
            jnp.zeros(DIM), pop_size=8, learning_rate=0.1, noise_stdev=0.5
        )
        return StdWorkflow(
            algo, _HostSphere(), monitors=(TelemetryMonitor(capacity=16),)
        )

    key = jax.random.PRNGKey(5)
    wf_clean = mk()
    s0 = wf_clean.init(key)
    clean = run_host_pipelined(wf_clean, s0, 6)

    wf = mk()

    def oom_when_wide(index, args, kwargs):
        batch = jax.tree.leaves(args[1])[0].shape[0]
        return "oom" if batch > 4 else None

    wf.problem.evaluate = FlakyDispatch(
        wf.problem.evaluate, trigger=oom_when_wide
    )
    sup = RunSupervisor(max_retries=2, backoff_s=0.01)
    final = GenerationExecutor(supervisor=sup).run_host(wf, s0, 6)
    assert int(final.generation) == 6
    _tree_assert_equal(final, clean)
    assert sup.counters["degradations"] == 1
    assert sup.report()["outcome"] == "recovered"


def test_supervised_restarts_path_and_effective_staleness_report():
    """Regressions from review: (a) `sup.run_host_pipelined(...,
    restarts=)` must still drive the host-boundary IPOP recipe (each
    segment supervised); (b) a per-run ``max_staleness=`` override must
    be reflected in the report's bound, or check_report rejects a valid
    stale run."""
    from evox_tpu import GuardedAlgorithm, IPOPRestarts
    from evox_tpu.algorithms.so.es import CMAES

    def factory(pop):
        return GuardedAlgorithm(
            CMAES(center_init=jnp.zeros(4), init_stdev=1.0, pop_size=pop)
        )

    policy = IPOPRestarts(factory, max_restarts=1, check_every=5)
    wf = StdWorkflow(factory(8), _HostSphere())
    wf.problem.evaluate = FlakyDispatch(
        wf.problem.evaluate, faults={3: "transient"}
    )
    sup = RunSupervisor(max_retries=2, backoff_s=0.01)
    final = sup.run_host_pipelined(
        wf, wf.init(jax.random.PRNGKey(0)), 12, restarts=policy
    )
    assert int(final.generation) == 12
    assert sup.report()["outcome"] == "recovered"

    # (c) StdWorkflow.run(restarts=) on an EXTERNAL problem must take the
    # executor pipeline too (an ipop segment through fused_run would
    # put the pure_callback step inside a fused loop), and match the
    # direct run_host_pipelined(restarts=) trajectory exactly
    wf_a = StdWorkflow(factory(8), _HostSphere())
    via_run = wf_a.run(wf_a.init(jax.random.PRNGKey(1)), 12, restarts=policy)
    wf_b = StdWorkflow(factory(8), _HostSphere())
    via_pipelined = run_host_pipelined(
        wf_b, wf_b.init(jax.random.PRNGKey(1)), 12, restarts=policy
    )
    assert int(via_run.generation) == 12
    _tree_assert_equal(via_run, via_pipelined)

    check_report = _load_check_report()
    ex = GenerationExecutor()  # constructor K=0 ...
    wf2 = _openes_wf(_HostSphere(sleep=0.002))
    s = ex.run_host(wf2, wf2.init(jax.random.PRNGKey(1)), 20, max_staleness=2)
    rep = run_report(wf2, s, executor=ex)
    assert rep["executor"]["max_staleness"] == 2  # ... widened per run
    assert check_report.validate_run_report(rep) == []


def test_supervisor_restore_rung_drains_background_saves(tmp_path):
    """The restore rung must see every snapshot the background lane has
    accepted — the executor drains the lane before ``latest()`` reads."""
    key = jax.random.PRNGKey(3)
    wf_clean = _pso_wf(_DeviceSphere())
    s0 = wf_clean.init(key)
    ck_c = WorkflowCheckpointer(str(tmp_path / "c"), every=3)
    clean = RunSupervisor(checkpointer=ck_c).run(wf_clean, s0, 9)

    wf = _pso_wf(_DeviceSphere())
    wf.run = FlakyDispatch(
        wf.run, faults={2: "transient", 3: "transient", 4: "transient"}
    )
    ck = WorkflowCheckpointer(str(tmp_path / "x"), every=3)
    sup = RunSupervisor(
        checkpointer=ck, max_retries=2, max_restores=1, backoff_s=0.01
    )
    final = sup.run(wf, s0, 9)
    assert int(final.generation) == 9
    _tree_assert_equal(final, clean)
    assert sup.counters["restores"] == 1


# ------------------------------------------------------- bounded staleness
def test_stale_tells_converge_and_are_counted():
    """Acceptance gate: OpenES on Sphere converges with K∈{1,2} stale
    tells, and the stale-tell counter surfaces through run_report's
    executor telemetry (validated v4 schema)."""
    check_report = _load_check_report()
    for K in (1, 2):
        prob = _HostSphere(sleep=0.002)  # slow host eval forces staleness
        wf = _openes_wf(prob, monitors=(TelemetryMonitor(capacity=16),))
        ex = GenerationExecutor(max_staleness=K)
        s = wf.init(jax.random.PRNGKey(0))
        s = ex.run_host(wf, s, 150)
        assert int(s.generation) == 150
        best = float(jnp.sum(s.algo.center**2))
        assert best < 0.05, f"K={K}: stale OpenES failed to converge ({best})"
        rep = run_report(wf, s, executor=ex)
        exr = rep["executor"]
        assert exr["max_staleness"] == K
        assert exr["counters"]["stale_tells"] > 100
        assert 1 <= exr["counters"]["max_lag"] <= K
        assert exr["counters"]["tells"] == 150
        assert check_report.validate_run_report(rep) == []
        # telemetry rings saw every generation despite the lag
        tm_report = rep["telemetry"][0]
        assert tm_report["generations"] == 150


def test_stale_mode_k0_remains_exact_and_guards_compose():
    """K=0 through the same code path stays exact, and the documented
    stale-mode incompatibilities refuse loudly.

    Like programs are compared bit for bit: the K=0 executor, the
    workflow's own host-problem ``run`` and ``run_host_pipelined`` all
    dispatch the same two jitted halves. The serial ``wf.step`` is ONE
    program holding ask, the callback and tell, which XLA fuses (and so
    rounds OpenES's float32 contraction) differently from the halves: it
    is held to a float32 tolerance stated here, 1e-5 relative over five
    generations (about a hundred units in the last place)."""
    wf_a = _openes_wf(_HostSphere())
    wf_b = _openes_wf(_HostSphere())
    wf_c = _openes_wf(_HostSphere())
    wf_d = _openes_wf(_HostSphere())
    s0 = wf_a.init(jax.random.PRNGKey(1))
    piped = GenerationExecutor(max_staleness=0).run_host(wf_b, s0, 5)
    via_run = wf_c.run(wf_c.init(jax.random.PRNGKey(1)), 5)
    via_pipelined = run_host_pipelined(wf_d, wf_d.init(jax.random.PRNGKey(1)), 5)
    _tree_assert_equal(piped, via_run)
    _tree_assert_equal(piped, via_pipelined)
    serial = wf_a.init(jax.random.PRNGKey(1))
    for _ in range(5):
        serial = wf_a.step(serial)
    _tree_assert_equal(serial, piped, rtol=1e-5)

    from evox_tpu.core.dtype_policy import BF16_STORAGE
    from evox_tpu.algorithms.so.es import OpenES

    algo = OpenES(jnp.zeros(4), pop_size=8, learning_rate=0.1, noise_stdev=0.3)
    wf_policy = StdWorkflow(algo, _HostSphere(), dtype_policy=BF16_STORAGE)
    s = wf_policy.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dtype_policy"):
        GenerationExecutor(max_staleness=1).run_host(wf_policy, s, 2)
    wf_donate = StdWorkflow(
        OpenES(jnp.zeros(4), pop_size=8, learning_rate=0.1, noise_stdev=0.3),
        _HostSphere(),
        donate_carries=True,
    )
    s = wf_donate.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="donate_carries"):
        GenerationExecutor(max_staleness=1).run_host(wf_donate, s, 2)


def test_stale_window_overlaps_slow_evals_wall_clock():
    """K=1 with a 30 ms host eval runs two evaluations concurrently: the
    wall must land clearly under the serialized sum (the overlap that
    motivates staleness)."""
    n, t_eval = 10, 0.03
    prob = _HostSphere(sleep=t_eval)
    wf = _openes_wf(prob, pop=16)
    s = wf.init(jax.random.PRNGKey(2))
    ex = GenerationExecutor(max_staleness=1)
    s = ex.run_host(wf, s, 3)  # warm both halves + probe the artifact mask
    t0 = time.perf_counter()
    s = ex.run_host(wf, s, n)
    jax.block_until_ready(s.algo.center)
    wall = time.perf_counter() - t0
    assert wall < n * t_eval * 0.85, (wall, n * t_eval)
    assert ex.counters["stale_tells"] > 0

    # regression: the documented `executor=` call form of
    # run_host_pipelined must honor the executor's CONFIGURED staleness
    # (it used to override it with its own default 0)
    ex2 = GenerationExecutor(max_staleness=1)
    wf2 = _openes_wf(_HostSphere(sleep=0.002), pop=16)
    run_host_pipelined(wf2, wf2.init(jax.random.PRNGKey(3)), 20, executor=ex2)
    assert ex2.counters["stale_tells"] > 0
    assert ex2.queue_stats["stale_window_max"] == 2


# -------------------------------------------------------- background I/O
def test_background_io_is_bounded_and_error_surfaced(tmp_path):
    """The checkpoint lane applies backpressure at io_inflight and a
    failing background save fails the run instead of vanishing."""
    wf = _pso_wf(_DeviceSphere())
    s0 = wf.init(jax.random.PRNGKey(0))
    ck = WorkflowCheckpointer(str(tmp_path / "b"), every=1)
    ex = GenerationExecutor(io_inflight=2)
    ex.run_fused(wf, s0, 8, checkpointer=ck)
    assert ex.queue_stats["io_inflight_max"] <= 2
    assert ex.counters["bg_checkpoint"] == 8

    class BrokenCkpt(WorkflowCheckpointer):
        def save(self, state):
            raise OSError("disk full (simulated)")

    broken = BrokenCkpt(str(tmp_path / "broken"), every=2)
    with pytest.raises(OSError, match="disk full"):
        GenerationExecutor().run_fused(wf, s0, 8, checkpointer=broken)


def test_background_monitor_fetch():
    """fetch_monitors_every keeps a live host copy of the telemetry rings
    without blocking the loop."""
    wf = _pso_wf(_HostSphere())
    s0 = wf.init(jax.random.PRNGKey(0))
    ex = GenerationExecutor(fetch_monitors_every=3)
    ex.run_host(wf, s0, 9)
    assert ex.counters["bg_fetch"] == 3
    gen, monitors = ex.last_monitor_fetch
    assert gen in (3, 6, 9)
    assert isinstance(np.asarray(jax.tree.leaves(monitors)[0]), np.ndarray)


# --------------------------------------------------- chunked_evaluate contract
def test_chunked_evaluate_device_dtype_consistent():
    """Satellite law: the chunked path mirrors the unchunked path's
    residency and dtype — device in, device out; numpy in, numpy out."""
    cand = jnp.arange(24.0, dtype=jnp.float32).reshape(8, 3)

    dev = _DeviceSphere()
    full, _ = chunked_evaluate(dev, None, cand, None)
    chunked, _ = chunked_evaluate(dev, None, cand, 3)
    assert isinstance(full, jax.Array) and isinstance(chunked, jax.Array)
    assert chunked.dtype == full.dtype
    np.testing.assert_array_equal(np.asarray(full), np.asarray(chunked))

    host = _HostSphere()
    full_h, _ = chunked_evaluate(host, None, cand, None)
    chunked_h, _ = chunked_evaluate(host, None, cand, 3)
    assert isinstance(full_h, np.ndarray) and isinstance(chunked_h, np.ndarray)
    assert chunked_h.dtype == full_h.dtype
    np.testing.assert_array_equal(full_h, chunked_h)


# ------------------------------------------------------- report/trace schema
def test_executor_section_and_trace_validate(tmp_path):
    check_report = _load_check_report()
    wf = _pso_wf(_HostSphere())
    rec = instrument(wf)
    ex = GenerationExecutor(fetch_monitors_every=2)
    s = wf.init(jax.random.PRNGKey(4))
    s = ex.run_host(wf, s, 6)
    rep = run_report(wf, s, recorder=rec)
    assert rep["schema"].endswith("/v14")
    assert rep["schema_version"] == 14
    assert rep["executor"]["counters"]["tells"] == 6
    assert rep["executor"]["overlap"]["wall_s"] > 0
    assert check_report.validate_run_report(rep) == []

    trace = write_chrome_trace(
        str(tmp_path / "t.json"), recorder=rec, workflow=wf, state=s
    )
    ex_events = [e for e in trace["traceEvents"] if e.get("pid") == 4]
    assert any(e.get("ph") == "X" for e in ex_events)
    assert any(e.get("ph") == "C" for e in ex_events)
    assert check_report.validate_chrome_trace(trace) == []

    # a mangled executor section must be CAUGHT
    bad = dict(rep)
    bad["executor"] = dict(
        rep["executor"],
        counters=dict(rep["executor"]["counters"], stale_tells=99),
    )
    assert any("stale_tells" in e for e in check_report.validate_run_report(bad))


def test_run_queue_dispatches_through_executor(tmp_path):
    """RunQueue scheduling is a thin policy over one executor: its chunk
    dispatches accumulate on the queue's executor instance."""
    from evox_tpu import RunQueue, TenantSpec
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.problems.numerical import Sphere

    wf = VectorizedWorkflow(
        PSO(lb=jnp.full((4,), -3.0), ub=jnp.full((4,), 3.0), pop_size=8),
        Sphere(),
        n_tenants=2,
    )
    q = RunQueue(wf, chunk=3, checkpoint_dir=str(tmp_path / "q"))
    for i in range(3):
        q.submit(TenantSpec(seed=i, n_steps=5, tag=f"job{i}"))
    results = q.run()
    assert len(results) == 3
    assert all(r["generations"] >= r["budget"] for r in results)
    assert q.executor.counters["chunks"] >= 2
    assert wf._run_executor is q.executor


# ------------------------------------------------------- executor close law


def test_executor_close_drains_surfaces_and_is_idempotent(tmp_path):
    """PR 18: ``close()`` quiesces the executor — pending background
    lane work is drained (its writes land durably), a lane error still
    surfaces instead of vanishing into a dead thread, the lane threads
    are shut down, and the executor stays usable afterwards (lanes
    re-create lazily)."""
    ex = GenerationExecutor()
    out = tmp_path / "lane.txt"
    ex.submit_background("snap", lambda: out.write_text("durable"))
    ex.close()
    assert out.read_text() == "durable"
    assert ex._named_lanes == {}
    ex.close()  # idempotent

    def boom():
        raise RuntimeError("fsync failed")

    ex.submit_background("snap", boom)  # lanes re-create after close
    time.sleep(0.05)
    with pytest.raises(RuntimeError, match="fsync failed"):
        ex.close()
    # the failed close still tore the lanes down
    assert ex._named_lanes == {}

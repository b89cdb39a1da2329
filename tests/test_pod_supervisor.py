"""Pod fault domain, in-process laws (ISSUE 14, core/pod_supervisor.py).

The REAL-signal matrix (worker SIGKILL / SIGSTOP / hang / coordinator
kill / SIGTERM preemption against spawned ``jax.distributed`` pods) lives
in tests/test_pod_chaos.py behind the ``pod_chaos`` marker. This file
asserts everything the fault domain promises that a single process can
witness:

- classification folding (pod deadlines -> the PR-5 error classes),
- the census / watchdog / drain plumbing,
- the "zero new behavior when disabled" law (a pod-supervised
  single-process run is bit-identical to a plain run),
- the coordinated-drain law through the executor (finish the chunk,
  final barrier checkpoint, resumed == uninterrupted),
- the supervisor-driven 8 -> 4 shrink-resume analog of the crash law on
  the virtual mesh (tier-1; the cross-process twin is the harness's),
- the ``process_barrier`` timeout satellite with a REAL non-arriving
  child process,
- the ``host_value`` replicate-cache invalidation satellite via the
  re-init guard path,
- run_report v9 / chrome-trace schema for the ``pod_supervisor`` section.
"""

import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from evox_tpu import (
    GenerationExecutor,
    PodSupervisor,
    PodFailureError,
    ShardedES,
    StdWorkflow,
    WorkflowCheckpointer,
    run_report,
    write_chrome_trace,
)
from evox_tpu.core import distributed as dist
from evox_tpu.core.pod_supervisor import (
    COORDINATOR_LOSS,
    HUNG_COLLECTIVE,
    WORKER_DEAD,
    CollectiveDeadlineError,
)
from evox_tpu.algorithms.so.es import SepCMAES
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.problems.numerical import Sphere

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _check_report_module():
    spec = importlib.util.spec_from_file_location(
        "check_report", os.path.join(REPO, "tools", "check_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pso_wf(mesh=None):
    return StdWorkflow(
        PSO(lb=-5.0 * jnp.ones(4), ub=5.0 * jnp.ones(4), pop_size=8),
        Sphere(),
        mesh=mesh,
    )


def _sharded_wf(mesh, n_shards, pop=32, dim=16):
    algo = ShardedES(
        SepCMAES(center_init=jnp.zeros(dim), init_stdev=1.0, pop_size=pop),
        mesh=mesh,
        n_shards=n_shards,
    )
    return StdWorkflow(algo, Sphere(), mesh=mesh)


# ------------------------------------------------------------ classification


def test_classify_error_folds_pod_errors():
    """ISSUE 14: the pod failures fold into the PR-5 error classes — barrier
    and collective deadlines are `deadline`, a classified pod fault is
    `fatal` (no in-process rung can heal a pod; re-formation is the
    driver's job)."""
    from evox_tpu.workflows.supervisor import DEADLINE, FATAL, classify_error
    from evox_tpu import BarrierTimeoutError, CollectiveDeadlineError

    assert classify_error(BarrierTimeoutError("b", 5.0, [0], [1])) == DEADLINE
    assert classify_error(CollectiveDeadlineError("hung")) == DEADLINE
    assert (
        classify_error(PodFailureError("x", WORKER_DEAD, {})) == FATAL
    )


def test_barrier_timeout_error_names_processes():
    e = dist.BarrierTimeoutError("gen4", 5.0, arrived=[0, 2], missing=[1])
    assert e.missing == [1] and e.arrived == [0, 2]
    assert "[1]" in str(e) and "gen4" in str(e)


def test_supervised_deadline_classifies_hung_collective():
    """Single-process census is trivially all-alive, so a supervised
    deadline classifies as hung_collective with the detection latency
    and event tail in the post-mortem."""
    sup = PodSupervisor(deadline_s=0.2, heartbeat_interval_s=0.05).start()
    try:
        with pytest.raises(PodFailureError) as ei:
            sup.supervised(lambda: time.sleep(5.0), entry="chunk")
        assert ei.value.classification == HUNG_COLLECTIVE
        pm = ei.value.post_mortem
        assert pm["entry"] == "chunk" and 0.2 <= pm["detect_s"] < 5.0
        assert sup.report()["outcome"] == "failed"
        assert sup.counters["failures"] == 1
    finally:
        sup.stop()


def test_supervised_propagates_non_pod_errors():
    """A numerics error inside a supervised collective is NOT a pod
    fault: it propagates untouched for the caller's own ladder."""
    sup = PodSupervisor(deadline_s=5.0).start()
    try:
        with pytest.raises(ValueError, match="not a pod fault"):
            sup.supervised(
                lambda: (_ for _ in ()).throw(ValueError("not a pod fault"))
            )
        assert sup.report()["outcome"] == "clean"
    finally:
        sup.stop()


def test_classify_failure_coordinator_loss_when_census_unreadable(monkeypatch):
    sup = PodSupervisor(deadline_s=1.0)
    monkeypatch.setattr(
        sup, "census", lambda *a, **k: (_ for _ in ()).throw(
            ConnectionError("coordination service unavailable")
        )
    )
    assert (
        sup.classify_failure(CollectiveDeadlineError("x")) == COORDINATOR_LOSS
    )


def test_classify_failure_worker_dead_from_census(monkeypatch):
    sup = PodSupervisor(deadline_s=1.0)
    monkeypatch.setattr(sup, "census", lambda *a, **k: {0: True, 1: False})
    assert sup.classify_failure(CollectiveDeadlineError("x")) == WORKER_DEAD


# --------------------------------------------------------- disabled == legacy


def test_pod_supervised_run_is_bit_identical_when_untriggered(tmp_path):
    """Zero new behavior: attaching a PodSupervisor that never fires
    leaves the executor run bit-identical to the plain fused run."""
    wf = _pso_wf()
    state0 = wf.init(jax.random.PRNGKey(3))
    plain = wf.run(state0, 6)
    sup = PodSupervisor(deadline_s=60.0, heartbeat_interval_s=0.1).start()
    try:
        ck = WorkflowCheckpointer(str(tmp_path / "ck"), every=2)
        ex = GenerationExecutor(pod_supervisor=sup)
        supervised = ex.run_fused(wf, state0, 6, checkpointer=ck, chunk=2)
    finally:
        sup.stop()
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(supervised)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sup.report()["outcome"] == "clean"


# ------------------------------------------------------------------ drain law


def test_drain_finishes_chunk_final_checkpoint_and_resume_equals(tmp_path):
    """The in-process drain law: a drain requested mid-run finishes the
    in-flight chunk, writes a FINAL barrier checkpoint (off-cadence
    included), and the resumed run equals the uninterrupted run bit for
    bit — the SIGTERM preemption law minus the real signal (which
    tests/test_pod_chaos.py delivers)."""
    wf = _pso_wf()
    state0 = wf.init(jax.random.PRNGKey(5))
    straight = wf.run(state0, 9)

    sup = PodSupervisor(deadline_s=60.0, heartbeat_interval_s=0.1).start()
    ck = WorkflowCheckpointer(str(tmp_path / "ck"), every=3)
    ex = GenerationExecutor(pod_supervisor=sup)
    # request the drain after the first chunk completes: wrap wf.run so
    # the flag is set while a chunk is IN FLIGHT (the preemption shape)
    orig = wf.run
    fired = {"done": False}

    def run(st, n):
        out = orig(st, n)
        if not fired["done"]:
            fired["done"] = True
            sup.request_drain("test-preemption")
        return out

    wf.run = run
    drained = ex.run_fused(wf, state0, 9, checkpointer=ck, chunk=3)
    wf.run = orig
    try:
        assert int(drained.generation) == 3  # finished ITS chunk, no more
        rep = sup.report()
        assert rep["outcome"] == "drained"
        assert [e["event"] for e in rep["events"]][-2:] == [
            "drain_requested",
            "drain",
        ]
        # the final barrier checkpoint is durable and resumable
        snap = ck.latest(expect_like=state0)
        assert int(snap.generation) == 3
        resumed = wf.run(state0, 9, resume_from=ck)
        for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        sup.stop()


def test_real_sigterm_routes_into_drain(tmp_path):
    """install_sigterm_drain: a REAL SIGTERM delivered mid-run drains at
    the next chunk boundary instead of killing the process."""
    wf = _pso_wf()
    state0 = wf.init(jax.random.PRNGKey(7))
    sup = PodSupervisor(deadline_s=60.0, heartbeat_interval_s=0.1).start()
    sup.install_sigterm_drain()
    ck = WorkflowCheckpointer(str(tmp_path / "ck"), every=2)
    ex = GenerationExecutor(pod_supervisor=sup)
    orig = wf.run
    pid = os.getpid()

    def run(st, n):
        out = orig(st, n)
        if int(out.generation) == 2:
            os.kill(pid, signal.SIGTERM)  # the preemption notice
            time.sleep(0.2)  # let the main thread observe the signal
        return out

    wf.run = run
    try:
        drained = ex.run_fused(wf, state0, 10, checkpointer=ck, chunk=2)
        assert int(drained.generation) == 2
        rep = sup.report()
        assert rep["outcome"] == "drained"
        ev = next(
            e for e in rep["events"] if e["event"] == "drain_requested"
        )
        assert ev["reason"] == "SIGTERM"
    finally:
        wf.run = orig
        sup.stop()  # restores the previous SIGTERM handler


# ------------------------------------------- ShardedES topology portability


def test_sharded_es_n_shards_multiple_of_mesh():
    """ISSUE 14 (tentpole substrate): n_shards may be any MULTIPLE of
    the mesh axis — each device draws its consecutive sample blocks, so
    the 8-shard sampling law runs on 8 devices, 4 devices, or
    replicated, and all three agree (psum-order tolerance)."""
    devs = jax.devices()
    assert len(devs) >= 8
    mesh8 = dist.create_mesh(devices=devs[:8])
    mesh4 = dist.create_mesh(devices=devs[:4])

    finals = []
    for mesh in (mesh8, mesh4, None):
        wf = _sharded_wf(mesh, n_shards=8)
        st = wf.init(jax.random.PRNGKey(11))
        for _ in range(5):
            st = wf.step(st)
        finals.append(
            (np.asarray(st.algo.mean), float(st.algo.sigma))
        )
    for got, name in zip(finals[:2], ("8-dev", "4-dev")):
        np.testing.assert_allclose(
            got[0], finals[2][0], rtol=1e-5, atol=1e-5,
            err_msg=f"{name} diverged from the replicated 8-shard law",
        )
        np.testing.assert_allclose(got[1], finals[2][1], rtol=1e-5)


def test_sharded_es_rejects_non_multiple_n_shards():
    devs = jax.devices()
    mesh = dist.create_mesh(devices=devs[:4])
    with pytest.raises(ValueError, match="not a multiple"):
        _sharded_wf(mesh, n_shards=6)


def test_pod_shrink_resume_8_to_4_analog(tmp_path):
    """The tier-1 in-process analog of the crash law: an 8-device
    pod-supervised ShardedES run fails mid-flight (watchdog deadline on
    a wedged chunk), the supervisor writes its post-mortem, and the
    'pod' RE-FORMS on a 4-device mesh — same n_shards=8 sampling law —
    resuming from the newest pod-barrier checkpoint and reproducing the
    uninjured 8-device trajectory (psum-order tolerance). Report/trace
    carry the reform↔resume coherence the v9 validator enforces."""
    devs = jax.devices()
    mesh8 = dist.create_mesh(devices=devs[:8])
    mesh4 = dist.create_mesh(devices=devs[:4])
    total = 8

    # uninjured reference on the full 8-device mesh
    wf_ref = _sharded_wf(mesh8, n_shards=8)
    state0 = wf_ref.init(jax.random.PRNGKey(13))
    straight = wf_ref.run(state0, total)

    # epoch 0: supervised run, wedged chunk after gen 4
    ck_dir = str(tmp_path / "pod_ck")
    sup0 = PodSupervisor(deadline_s=1.0, heartbeat_interval_s=0.1).start()
    wf0 = _sharded_wf(mesh8, n_shards=8)
    ck = WorkflowCheckpointer(ck_dir, every=2)
    # warm the compiled loop OUTSIDE the supervised phase (the harness's
    # warmup-barrier discipline): the first chunk must not spend its
    # 1 s collective deadline on compilation
    wf0.run(wf0.init(jax.random.PRNGKey(99)), 2)
    orig = wf0.run

    def run(st, n):
        if int(st.generation) >= 4:
            time.sleep(30.0)  # the hung-collective shape
        return orig(st, n)

    wf0.run = run
    ex0 = GenerationExecutor(pod_supervisor=sup0)
    with pytest.raises(PodFailureError) as ei:
        ex0.run_fused(wf0, state0, total, checkpointer=ck, chunk=2)
    sup0.stop()
    assert ei.value.classification == HUNG_COLLECTIVE
    assert ei.value.post_mortem["detect_s"] < 30.0

    # re-formation: 4-device survivor mesh, SAME 8-shard sampling law,
    # resume from the newest pod barrier (gen 4) and finish
    sup1 = PodSupervisor(
        deadline_s=60.0, heartbeat_interval_s=0.1, epoch=1
    ).start()
    try:
        wf1 = _sharded_wf(mesh4, n_shards=8)
        expect = wf1.init(jax.random.PRNGKey(0))
        sup1.note_reform(survivors=[0], from_epoch=0)
        state = sup1.resume_from_barrier(wf1, ck_dir, expect_like=expect)
        assert int(state.generation) == 4
        # the restored per-candidate leaves land on the CURRENT mesh
        assert state.algo.z.sharding.mesh.shape[dist.POP_AXIS] == 4
        ex1 = GenerationExecutor(pod_supervisor=sup1)
        final = ex1.run_fused(
            wf1,
            state,
            total - int(state.generation),
            checkpointer=WorkflowCheckpointer(ck_dir, every=2),
            chunk=2,
        )
        assert int(final.generation) == total
        np.testing.assert_allclose(
            np.asarray(final.algo.mean),
            np.asarray(straight.algo.mean),
            rtol=1e-5,
            atol=1e-5,
            err_msg="8→4 shrink-resume diverged from the uninjured run",
        )
        np.testing.assert_allclose(
            float(final.algo.sigma), float(straight.algo.sigma), rtol=1e-5
        )

        # v9 report + trace schema, incl. reform↔resume coherence
        rep = run_report(wf1, final)
        assert rep["schema"] == "evox_tpu.run_report/v14"
        assert rep["schema_version"] == 14
        pod = rep["pod_supervisor"]
        assert pod["outcome"] == "resumed"
        kinds = [e["event"] for e in pod["events"]]
        assert "reform" in kinds and "resume" in kinds
        cr = _check_report_module()
        assert cr.validate_run_report(rep) == []
        trace = write_chrome_trace(
            str(tmp_path / "trace.json"), workflow=wf1, state=final
        )
        assert cr.validate_chrome_trace(trace) == []
        names = {
            e.get("name")
            for e in trace["traceEvents"]
            if e.get("cat") == "supervisor"
        }
        assert "supervisor:pod:resume" in names
    finally:
        sup1.stop()


# ------------------------------------------------- process_barrier satellite

_BARRIER_CHILD = r"""
import os, sys, time, json
os.environ["JAX_PLATFORMS"] = "cpu"
repo, port, pid = sys.argv[1], sys.argv[2], int(sys.argv[3])
sys.path.insert(0, repo)
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util
spec = importlib.util.spec_from_file_location(
    "evox_tpu_distributed_standalone",
    os.path.join(repo, "evox_tpu", "core", "distributed.py"),
)
D = importlib.util.module_from_spec(spec)
spec.loader.exec_module(D)
D.init_distributed(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)
if pid == 1:
    time.sleep(12.0)  # NEVER arrives at the barrier
    os._exit(0)
try:
    D.process_barrier("law", timeout_s=3.0)
    print("RESULT " + json.dumps({"raised": False}), flush=True)
except D.BarrierTimeoutError as e:
    print("RESULT " + json.dumps({
        "raised": True, "missing": e.missing, "arrived": e.arrived,
        "named": "1" in str(e),
    }), flush=True)
# os._exit: skip jax's atexit distributed-shutdown handshake — it
# blocks on a shutdown barrier the non-arriving peer never joins
os._exit(0)
"""


@pytest.mark.pod_chaos
@pytest.mark.slow
def test_process_barrier_timeout_names_missing_process():
    """ISSUE 14 satellite: a barrier with a REAL non-arriving peer
    raises the classified BarrierTimeoutError naming the process that
    never arrived (was: an eternal block / an opaque coordination-
    service string)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _BARRIER_CHILD, REPO, port, str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    out0, _ = procs[0].communicate(timeout=120)
    procs[1].kill()
    procs[1].communicate()
    assert procs[0].returncode == 0, out0
    line = next(
        ln for ln in out0.splitlines() if ln.startswith("RESULT ")
    )
    got = json.loads(line[len("RESULT "):])
    assert got == {
        "raised": True, "missing": [1], "arrived": [0], "named": True,
    }, got


# ------------------------------------------- host_value cache satellite


def test_replicate_cache_invalidated_on_shutdown_and_reinit(monkeypatch):
    """ISSUE 14 satellite: the cached jitted-replicate closures
    (host_value's all-gather programs) are dropped on jax.distributed
    shutdown AND on a real re-init, and KEPT on the warned no-op guard
    path — a re-formed pod never executes a program compiled for the
    dead topology, while the idempotent-init shape loses nothing."""
    mesh = dist.create_pod_mesh()
    dist._replicate_program.cache_clear()
    dist._replicate_program(NamedSharding(mesh, P()))
    assert dist._replicate_program.cache_info().currsize == 1

    # shutdown (no active runtime here: still clears, still safe)
    dist.shutdown_distributed()
    assert dist._replicate_program.cache_info().currsize == 0

    # guard path: an already-initialized matching re-call is a warned
    # no-op and must NOT clear (the live topology did not change)
    dist._replicate_program(NamedSharding(mesh, P()))

    class FakeClient:
        pass

    monkeypatch.setattr(dist, "_dist_client", lambda: FakeClient())
    with pytest.warns(UserWarning, match="no-op"):
        dist.init_distributed()
    assert dist._replicate_program.cache_info().currsize == 1

    # real-init path (uninitialized again): clears before initializing
    monkeypatch.setattr(dist, "_dist_client", lambda: None)
    called = {}
    monkeypatch.setattr(
        dist.jax.distributed,
        "initialize",
        lambda **kw: called.setdefault("kw", kw),
    )
    dist.init_distributed(coordinator_address="127.0.0.1:1")
    assert called["kw"]["coordinator_address"] == "127.0.0.1:1"
    assert dist._replicate_program.cache_info().currsize == 0
    dist._INIT_RECORD = None  # undo the fake init's record


# ------------------------------------------------------------- report schema


def test_pod_report_and_markers_validate(tmp_path):
    """A failed pod report (classification, census, monotonic clock)
    passes the v9 validator, and its markers are well-formed
    supervisor:pod:* instants."""
    sup = PodSupervisor(deadline_s=0.2, heartbeat_interval_s=0.05).start()
    try:
        with pytest.raises(PodFailureError):
            sup.supervised(lambda: time.sleep(2.0))
    finally:
        sup.stop()
    wf = _pso_wf()
    wf._pod_supervisor = sup
    st = wf.init(jax.random.PRNGKey(0))
    rep = run_report(wf, st)
    cr = _check_report_module()
    assert cr.validate_run_report(rep) == []
    assert rep["pod_supervisor"]["outcome"] == "failed"
    assert all(
        m["name"].startswith("supervisor:pod:") for m in sup.markers()
    )


def test_journalled_pod_events_verify(tmp_path):
    """Membership transitions ride the PR-11 WAL: pod_join/pod_failure
    land hash-chained in the journal and the chain verifies."""
    from evox_tpu import RunJournal

    jdir = str(tmp_path / "journal")
    sup = PodSupervisor(
        deadline_s=0.2, heartbeat_interval_s=0.05, journal=jdir
    ).start()
    try:
        with pytest.raises(PodFailureError):
            sup.supervised(lambda: time.sleep(2.0))
    finally:
        sup.stop()
    assert RunJournal.verify(jdir) == 2
    kinds = [r["kind"] for r in RunJournal(jdir).records()]
    assert kinds == ["pod_join", "pod_failure"]


# ------------------------------------------- deadline-vs-coord-abort clamp


def test_deadline_clamped_against_coord_abort(monkeypatch):
    """PR 18: in a REAL multi-process pod a supervisor
    deadline that cannot beat jaxlib's ~10 s coordination-heartbeat
    abort is clamped at construction with a warning — pod faults must be
    classified, not die by SIGABRT."""
    monkeypatch.setattr(dist, "_dist_process_info", lambda: (0, 4))
    with pytest.warns(UserWarning, match="coordination heartbeat abort"):
        sup = PodSupervisor(deadline_s=30.0, heartbeat_interval_s=1.0)
    # budget = 10.0 (abort) - 0.5 (margin) - (2*interval + 0.2) slack
    assert sup.deadline_s == pytest.approx(7.3)
    # the derived checkpoint deadline follows the clamp
    assert sup.checkpoint_deadline_s == pytest.approx(6.0 * 7.3)
    # an explicit checkpoint_deadline_s is the caller's choice — kept
    with pytest.warns(UserWarning, match="clamping"):
        sup2 = PodSupervisor(
            deadline_s=30.0,
            heartbeat_interval_s=1.0,
            checkpoint_deadline_s=120.0,
        )
    assert sup2.checkpoint_deadline_s == 120.0


def test_deadline_within_budget_untouched(monkeypatch, recwarn):
    """Both safe sides: a multi-process deadline inside the abort budget
    and ANY single-process deadline (no coordination client to race)
    pass through unclamped and warning-free."""
    monkeypatch.setattr(dist, "_dist_process_info", lambda: (0, 4))
    sup = PodSupervisor(deadline_s=5.0, heartbeat_interval_s=1.0)
    assert sup.deadline_s == 5.0
    monkeypatch.setattr(dist, "_dist_process_info", lambda: (0, 1))
    solo = PodSupervisor(deadline_s=30.0, heartbeat_interval_s=1.0)
    assert solo.deadline_s == 30.0
    assert not [w for w in recwarn if "clamp" in str(w.message)]

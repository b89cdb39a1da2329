"""Multi-tenant serving (workflows/tenancy.py): vmapped fleets, the
(TENANT, POP) 2-D mesh layout, eviction/resume, and the RunQueue.

Correctness laws under test:

- **Fleet ≡ solo**: tenant ``i`` of a ``VectorizedWorkflow`` reproduces a
  solo ``StdWorkflow`` run of the same (algorithm, seed, hyperparams).
  On the CPU test backend this is observed BITWISE for the covered
  algorithms; the asserted contract is allclose(rtol=1e-5, atol=1e-6) —
  vmap may legally re-associate batched reductions at the last ulp on
  other backends (documented tolerance, ISSUE 8 acceptance).
- **Mesh ≡ no-mesh**: the (TENANT, POP) sharded fleet matches the
  unsharded one, and the committed state carries the annotation-derived
  prefixed layout (``P("pop")`` → ``P("tenant", "pop")``). Asserted on
  an eigh-free algorithm: a sharded batched eigh may return
  differently-signed (equally valid) eigenvectors, so the cross-layout
  bitwise law excludes the CMA family's decomposition (their meshed
  runs are covered by same-layout laws).
- **Eviction/resume**: a mid-fleet eviction yields a single-tenant
  checkpoint that the solo workflow resumes, reproducing the remaining
  trajectory.
- **Chaos**: supervisor retry through the fleet path heals to the clean
  run's exact states (immutable states, pure dispatches — PR-5 law).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from evox_tpu import (
    RunQueue,
    RunSupervisor,
    TenantSpec,
    VectorizedWorkflow,
    run_report,
)
from evox_tpu.core.distributed import (
    POP_AXIS,
    TENANT_AXIS,
    create_mesh,
    match_partition_rules,
)
from evox_tpu.algorithms.so.es import CMAES, OpenES
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.problems.numerical import Sphere
from tests._chaos import FlakyDispatch

N, DIM, POP = 4, 8, 16


def _cmaes(**kw):
    args = dict(center_init=jnp.ones(DIM), init_stdev=1.0, pop_size=POP)
    args.update(kw)
    return CMAES(**args)


def _stacked_keys(n=N, base=0):
    return jnp.stack([jax.random.PRNGKey(base + i) for i in range(n)])


HP = {"init_stdev": jnp.asarray([0.5, 1.0, 1.5, 2.0])}


def _tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(la, dtype=np.float64)
            if jnp.issubdtype(jnp.asarray(la).dtype, jnp.floating)
            else np.asarray(la),
            np.asarray(lb, dtype=np.float64)
            if jnp.issubdtype(jnp.asarray(lb).dtype, jnp.floating)
            else np.asarray(lb),
            rtol=rtol,
            atol=atol,
        )


# --------------------------------------------------------------- equivalence


@pytest.mark.slow
def test_fleet_matches_solo_cmaes():
    """Each tenant's trajectory == a solo run of its (seed, hyperparams),
    with per-tenant init_stdev bound through the traced step."""
    wf = VectorizedWorkflow(
        _cmaes(),
        Sphere(),
        n_tenants=N,
        hyperparams=HP,
        monitors=(TelemetryMonitor(capacity=8),),
    )
    keys = _stacked_keys()
    state = wf.run(wf.init(keys), 12)
    for i in (0, 2, 3):
        solo_wf = wf.solo_workflow(i)
        solo = solo_wf.run(solo_wf.init(keys[i]), 12)
        tenant_algo = jax.tree.map(lambda x: x[i], state.tenants.algo)
        _tree_allclose(tenant_algo, solo.algo)
        # per-tenant telemetry ring == the solo run's ring
        tenant_mon = jax.tree.map(lambda x: x[i], state.tenants.monitors[0])
        _tree_allclose(tenant_mon, solo.monitors[0])


def test_fleet_matches_solo_openes_hyperparams():
    """OpenES noise_stdev varies per tenant and flows through ask/tell
    (an attribute read inside the traced step, not a baked constant)."""
    hp = {"noise_stdev": jnp.asarray([0.01, 0.1])}
    # nonzero center: at Sphere's optimum the mirrored-sampling gradient
    # is exactly zero and the two tenants could never diverge
    algo = OpenES(
        center_init=jnp.ones(DIM), pop_size=POP, learning_rate=0.1,
        noise_stdev=0.05,
    )
    wf = VectorizedWorkflow(
        algo, Sphere(), n_tenants=2, hyperparams=hp
    )
    keys = _stacked_keys(2)
    state = wf.run(wf.init(keys), 8)
    for i in range(2):
        solo_wf = wf.solo_workflow(i)
        solo = solo_wf.run(solo_wf.init(keys[i]), 8)
        _tree_allclose(
            jax.tree.map(lambda x: x[i], state.tenants.algo), solo.algo
        )
    # the two tenants really ran different noise scales
    assert not np.allclose(
        np.asarray(state.tenants.algo.center[0]),
        np.asarray(state.tenants.algo.center[1]),
    )


def test_fleet_sphere_convergence():
    """Convergence-threshold gate (CLAUDE.md convention): every tenant
    of a CMA-ES fleet drives Sphere below threshold."""
    tm = TelemetryMonitor(capacity=4)
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=N, hyperparams=HP, monitors=(tm,)
    )
    state = wf.run(wf.init(_stacked_keys()), 60)
    best = np.asarray(state.tenants.monitors[0].best_key)
    assert best.shape == (N,)
    assert (best < 1e-2).all(), f"fleet best per tenant: {best}"


@pytest.mark.slow
def test_fleet_init_hooks_mo():
    """An init_ask/init_tell algorithm (NSGA-II evaluates its parents
    first) vmaps through the fleet's peeled first step; tenant 0 matches
    the solo run."""
    from evox_tpu.algorithms.mo import NSGA2
    from evox_tpu.problems.numerical import ZDT1

    prob = ZDT1(n_dim=DIM)
    lb, ub = jnp.zeros(DIM), jnp.ones(DIM)
    algo = NSGA2(lb=lb, ub=ub, n_objs=2, pop_size=POP)
    assert algo.has_init_ask or algo.has_init_tell
    wf = VectorizedWorkflow(
        algo, prob, n_tenants=2, num_objectives=2
    )
    keys = _stacked_keys(2)
    state = wf.run(wf.init(keys), 10)
    solo_wf = wf.solo_workflow(0)
    solo = solo_wf.run(solo_wf.init(keys[0]), 10)
    _tree_allclose(
        jax.tree.map(lambda x: x[0], state.tenants.algo), solo.algo
    )


# ----------------------------------------------------------------- 2-D mesh


def _pso(**kw):
    from evox_tpu.algorithms.so.pso import PSO

    args = dict(
        lb=-5.0 * jnp.ones(DIM), ub=5.0 * jnp.ones(DIM), pop_size=POP
    )
    args.update(kw)
    return PSO(**args)


def test_fleet_mesh_matches_single_and_layout():
    """Mesh ≡ no-mesh on an eigh-free algorithm (PSO): CMA's lazy eigh
    is gauge-ambiguous — a sharded batched eigh may return differently-
    signed (equally valid) eigenvectors, so meshed-vs-unmeshed bitwise
    equivalence is only a law for algorithms without an eigendecomp
    (CMA-ES mesh coverage: the same-layout supervisor restore law below
    and the fleet-vs-solo law above)."""
    mesh = create_mesh((TENANT_AXIS, POP_AXIS), shape=(4, 2))
    hp = {"w": jnp.linspace(0.4, 0.8, N)}
    kw = dict(n_tenants=N, hyperparams=hp)
    wf = VectorizedWorkflow(_pso(), Sphere(), **kw)
    wfm = VectorizedWorkflow(_pso(), Sphere(), mesh=mesh, **kw)
    keys = _stacked_keys()
    state = wf.run(wf.init(keys), 10)
    statem = wfm.run(wfm.init(keys), 10)
    _tree_allclose(state.tenants.algo, statem.tenants.algo)
    # committed layout: pop-annotated population is (tenant, pop)-
    # sharded, the replicated-annotated gbest shards over tenant — the
    # P("pop") -> P("tenant", "pop") / P() -> P("tenant") prefix law
    assert statem.tenants.algo.population.sharding.spec == P(
        TENANT_AXIS, POP_AXIS
    )
    assert statem.tenants.algo.gbest_fitness.sharding.spec == P(TENANT_AXIS)


def test_fleet_rules_override_layout():
    """Regex rules (SNIPPETS.md [2] pattern) override the annotation-
    derived spec per leaf path — here pinning the population to
    tenant-only sharding (the rule's P() is prefixed by the tenant axis
    like any spec)."""
    mesh = create_mesh((TENANT_AXIS, POP_AXIS), shape=(4, 2))
    wf = VectorizedWorkflow(
        _pso(),
        Sphere(),
        n_tenants=N,
        mesh=mesh,
        rules=((r"\.algo\.population$", P()),),
    )
    # assert on the jitted STEP's committed output: inside the fused
    # fori_loop XLA unifies the carry layout and may override the tail
    # constraint on the loop's own output — the per-step layout is the
    # contract
    state = wf.step(wf.init(_stacked_keys()))
    assert state.tenants.algo.population.sharding.spec == P(TENANT_AXIS)
    assert state.tenants.algo.velocity.sharding.spec == P(
        TENANT_AXIS, POP_AXIS
    )


def test_match_partition_rules_unit():
    tree = {"algo": {"population": jnp.zeros((4, 2)), "sigma": jnp.zeros(())}}
    specs = match_partition_rules(
        [(r"population", P("pop")), (r".*", P())], tree
    )
    assert specs["algo"]["population"] == P("pop")
    assert specs["algo"]["sigma"] == P()  # scalars never partition
    specs = match_partition_rules([(r"nothing", P())], tree, default=None)
    assert specs["algo"]["population"] is None
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules([(r"nothing", P())], tree, strict=True)


def test_mesh_validation():
    pop_only = create_mesh((POP_AXIS,))
    with pytest.raises(ValueError, match="tenant"):
        VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=N, mesh=pop_only)
    mesh = create_mesh((TENANT_AXIS, POP_AXIS), shape=(8, 1))
    with pytest.raises(ValueError, match="not divisible"):
        VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=6, mesh=mesh)


# ------------------------------------------------------------ construction


def test_hyperparam_validation():
    with pytest.raises(ValueError, match="no attribute"):
        VectorizedWorkflow(
            _cmaes(), Sphere(), n_tenants=2,
            hyperparams={"not_a_knob": jnp.zeros(2)},
        )
    with pytest.raises(ValueError, match="leading"):
        VectorizedWorkflow(
            _cmaes(), Sphere(), n_tenants=2,
            hyperparams={"init_stdev": jnp.zeros(3)},
        )


def test_external_problem_rejected():
    class HostProblem(Sphere):
        jittable = False

    with pytest.raises(ValueError, match="jittable"):
        VectorizedWorkflow(_cmaes(), HostProblem(), n_tenants=2)


# ------------------------------------------------------ eviction and resume


def test_eviction_checkpoint_solo_resume(tmp_path):
    """Mid-fleet eviction → resumable single-tenant checkpoint: the solo
    workflow resumes the snapshot and reproduces the remaining
    trajectory (continuation == direct solo continuation of the same
    snapshot; and it matches the full solo run within the fleet-vs-solo
    tolerance)."""
    from evox_tpu import WorkflowCheckpointer

    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=N, hyperparams=HP,
        monitors=(TelemetryMonitor(capacity=8),),
    )
    keys = _stacked_keys()
    state = wf.run(wf.init(keys), 8)
    i = 1
    solo_state = wf.extract_tenant(state, i)
    assert int(solo_state.generation) == 8
    ckpt = WorkflowCheckpointer(str(tmp_path / "evicted"), every=8)
    ckpt.save(solo_state)
    solo_wf = wf.solo_workflow(i)
    # resume to 20 TOTAL generations from the eviction snapshot
    resumed = solo_wf.run(
        solo_wf.init(keys[i]), 20, resume_from=str(tmp_path / "evicted")
    )
    assert int(resumed.generation) == 20
    # law 1 (exact): resume == continuing the snapshot directly
    direct = solo_wf.run(solo_state, 12)
    for a, b in zip(jax.tree.leaves(resumed.algo), jax.tree.leaves(direct.algo)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # law 2 (toleranced): matches the never-evicted solo run
    straight = solo_wf.run(solo_wf.init(keys[i]), 20)
    _tree_allclose(resumed.algo, straight.algo, rtol=1e-4, atol=1e-5)


def test_insert_tenant_roundtrip():
    """extract → insert is the identity on the slot (state surgery at
    fixed shapes), and insertion replaces exactly one slot."""
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=N, hyperparams=HP)
    state = wf.run(wf.init(_stacked_keys()), 5)
    solo = wf.extract_tenant(state, 2)
    other = jax.tree.map(lambda x: np.asarray(x[3]), state.tenants.algo)
    state2 = wf.insert_tenant(state, 2, solo)
    _tree_allclose(
        jax.tree.map(lambda x: x[2], state2.tenants.algo),
        solo.algo,
        rtol=0,
        atol=0,
    )
    _tree_allclose(
        jax.tree.map(lambda x: x[3], state2.tenants.algo), other, rtol=0, atol=0
    )


# ------------------------------------------------------------------- chaos


@pytest.mark.slow
def test_supervisor_chaos_fleet():
    """PR-5 law through the fleet path: a transient dispatch fault is
    retried from the immutable entry state and the healed run is
    EXACTLY the clean run (telemetry fingerprint equality)."""
    tm = TelemetryMonitor(capacity=8)

    def build():
        return VectorizedWorkflow(
            _cmaes(), Sphere(), n_tenants=N, hyperparams=HP, monitors=(tm,)
        )

    keys = _stacked_keys()
    clean_wf = build()
    clean = RunSupervisor(max_retries=2, backoff_s=0.001).run(
        clean_wf, clean_wf.init(keys), 12, chunk=4
    )
    faulty_wf = build()
    faulty_wf.run = FlakyDispatch(faulty_wf.run, faults={1: "transient"})
    sup = RunSupervisor(max_retries=2, backoff_s=0.001)
    healed = sup.run(faulty_wf, faulty_wf.init(keys), 12, chunk=4)
    assert sup.counters["retries"] == 1
    assert sup.report()["outcome"] == "recovered"
    # fingerprint the stacked telemetry state: byte-identical healing
    fp_clean = tm.fingerprint(clean.tenants.monitors[0])
    fp_healed = tm.fingerprint(healed.tenants.monitors[0])
    assert fp_clean == fp_healed


@pytest.mark.slow
def test_supervisor_restore_meshed_fleet(tmp_path):
    """The restore rung re-places a fleet snapshot by the TENANT-prefixed
    layout (VectorizedWorkflow.place_restored, duck-typed by the
    supervisor) and the replay reproduces the clean meshed run exactly."""
    from evox_tpu import WorkflowCheckpointer

    mesh = create_mesh((TENANT_AXIS, POP_AXIS), shape=(4, 2))
    keys = _stacked_keys()

    def build():
        return VectorizedWorkflow(
            _cmaes(), Sphere(), n_tenants=N, hyperparams=HP, mesh=mesh
        )

    clean_wf = build()
    clean = clean_wf.run(clean_wf.init(keys), 12)
    wf = build()
    ckpt = WorkflowCheckpointer(str(tmp_path / "fleet"), every=4)
    # exhaust retries instantly -> the ladder reaches the restore rung,
    # replays from the newest snapshot, and completes the run
    wf.run = FlakyDispatch(wf.run, faults={2: "transient"})
    sup = RunSupervisor(
        checkpointer=ckpt, max_retries=0, max_restores=1, backoff_s=0.001
    )
    healed = sup.run(wf, wf.init(keys), 12)
    assert sup.counters["restores"] == 1
    assert int(healed.generation) == 12
    for a, b in zip(
        jax.tree.leaves(clean.tenants.algo),
        jax.tree.leaves(healed.tenants.algo),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_checkpointed_run_equivalence(tmp_path):
    """Fleet-level crash-safety: a checkpointer-chunked fleet run equals
    the straight run, and resume completes it."""
    from evox_tpu import WorkflowCheckpointer

    keys = _stacked_keys()
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=N, hyperparams=HP)
    straight = wf.run(wf.init(keys), 12)
    ckpt = WorkflowCheckpointer(str(tmp_path / "fleet"), every=4)
    chunked = wf.run(wf.init(keys), 12, checkpointer=ckpt)
    for a, b in zip(
        jax.tree.leaves(straight.tenants.algo),
        jax.tree.leaves(chunked.tenants.algo),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    resumed = wf.run(wf.init(keys), 12, resume_from=ckpt)
    assert int(resumed.generation) == 12


# ---------------------------------------------------------------- RunQueue


def test_runqueue_lifecycle(tmp_path):
    """5 specs through a 2-wide fleet: budgets honored exactly, retired
    slots admit pending specs without recompiling, per-tenant
    checkpoints + telemetry land in the results."""
    tm = TelemetryMonitor(capacity=8)
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=2,
        hyperparams={"init_stdev": jnp.ones(2)},
        monitors=(tm,),
    )
    q = RunQueue(
        wf, chunk=5, checkpoint_dir=str(tmp_path),
        supervisor=RunSupervisor(max_retries=1, backoff_s=0.001),
    )
    budgets = [12, 13, 14, 15, 16]
    for i, b in enumerate(budgets):
        q.submit(TenantSpec(
            seed=i, n_steps=b,
            hyperparams={"init_stdev": 0.5 + 0.25 * i}, tag=f"job{i}",
        ))
    results = q.run()
    assert [r["tag"] for r in results] == [f"job{i}" for i in range(5)]
    assert [r["generations"] for r in results] == budgets
    assert all(r["status"] == "completed" for r in results)
    assert q.counters["submitted"] == 5
    assert q.counters["admitted"] == 5
    assert q.counters["retired"] == 5
    for r in results:
        assert os.path.isdir(r["checkpoint"])
        tel = r["monitors"][0]
        assert tel["generations"] == r["generations"]
        assert tel["evals"] == r["generations"] * POP


def test_runqueue_evict_resume(tmp_path):
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=2,
        hyperparams={"init_stdev": jnp.ones(2)},
    )
    q = RunQueue(wf, chunk=5, checkpoint_dir=str(tmp_path))
    for i in range(2):
        q.submit(TenantSpec(
            seed=i, n_steps=30, hyperparams={"init_stdev": 1.0}, tag=f"e{i}",
        ))
    q.start()
    q.step_chunk()
    entry = q.evict(0)
    assert entry["status"] == "evicted"
    assert entry["generations"] == 5
    solo_wf = wf.solo_workflow(hyperparams={"init_stdev": 1.0})
    st = solo_wf.run(
        solo_wf.init(jax.random.PRNGKey(0)), 30,
        resume_from=entry["checkpoint"],
    )
    straight = solo_wf.run(solo_wf.init(jax.random.PRNGKey(0)), 30)
    assert int(st.generation) == 30
    _tree_allclose(st.algo, straight.algo, rtol=1e-4, atol=1e-5)


def test_runqueue_admission_resnapshots_for_restore(tmp_path):
    """After slot surgery the supervisor's NEWEST snapshot must contain
    the admitted tenant — otherwise its restore rung would resurrect a
    pre-admission fleet (structurally identical, invisible to the config
    guard) and attribute the old tenant's trajectory to the new spec."""
    from evox_tpu import WorkflowCheckpointer

    ckpt = WorkflowCheckpointer(str(tmp_path / "fleet"), every=5)
    sup = RunSupervisor(checkpointer=ckpt, max_retries=1, backoff_s=0.001)
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(wf, chunk=5, supervisor=sup)
    for i in range(3):
        q.submit(TenantSpec(seed=i, n_steps=10, tag=f"j{i}"))
    q.start()
    q.step_chunk()  # to gen 5, nobody retires
    q.step_chunk()  # to gen 10: both retire, spec 2 admitted into a slot
    assert q.counters["admitted"] == 3
    snap = ckpt.latest()
    assert int(snap.generation) == int(q.state.generation)
    for a, b in zip(
        jax.tree.leaves(snap.tenants.algo),
        jax.tree.leaves(q.state.tenants.algo),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_runqueue_rejects_bad_specs_at_submit():
    """Spec validation happens at the submission boundary, before any
    spec is popped from the queue."""
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=2,
        hyperparams={"init_stdev": jnp.ones(2)},
    )
    q = RunQueue(wf)
    with pytest.raises(ValueError, match="n_steps"):
        q.submit(TenantSpec(seed=0, n_steps=0,
                            hyperparams={"init_stdev": 1.0}))
    with pytest.raises(ValueError, match="hyperparam names"):
        q.submit(TenantSpec(seed=0, n_steps=5, hyperparams={}))
    # numpy integer seeds are real seeds, not scalar arrays
    spec = TenantSpec(seed=np.int64(7), n_steps=5,
                      hyperparams={"init_stdev": 1.0})
    assert spec.key().shape == jax.random.PRNGKey(7).shape


def test_runqueue_duplicate_tags_get_distinct_checkpoints(tmp_path):
    """Two specs sharing a tag must NOT share a snapshot directory —
    the config fingerprint can't tell two same-shape searches apart, so
    a reused directory would let one tenant's snapshot shadow the
    other's on resume."""
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(wf, chunk=5, checkpoint_dir=str(tmp_path))
    for i in range(3):
        q.submit(TenantSpec(seed=i, n_steps=5, tag="sweep"))
    results = q.run()
    dirs = [r["checkpoint"] for r in results]
    assert len(set(dirs)) == 3, dirs


def test_runqueue_requires_full_fleet():
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(wf)
    q.submit(TenantSpec(seed=0, n_steps=5))
    with pytest.raises(ValueError, match="at least n_tenants"):
        q.start()


def test_runqueue_double_start_raises():
    """A second start() would pop fresh specs and re-init the fleet over
    the live one — refused; recovery replays through the journal, never
    through a re-start."""
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(wf, chunk=3)
    for i in range(2):
        q.submit(TenantSpec(seed=i, n_steps=6, tag=f"d{i}"))
    q.start()
    with pytest.raises(RuntimeError, match="already started"):
        q.start()
    results = q.run()
    assert [r["status"] for r in results] == ["completed"] * 2


def test_runqueue_evict_edge_cases(tmp_path):
    """The evict paths recovery must replay exactly: evict outside the
    legal between-chunk window (before start) raises, a bogus slot index
    raises, evict-then-backfill with an EMPTY pending queue parks the
    slot inactive with its rows masked (never crashes, never quarantines
    the SLOT — a late submit must still admit into it), and a parked
    slot is not evictable twice — all without losing the surviving
    tenant's sweep."""
    from evox_tpu import FleetHealthPolicy

    # a freeze-capable policy materializes the mask, so the parked-slot
    # masking path is exercised (healthy tenants: no action ever fires)
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(
        wf, chunk=3, checkpoint_dir=str(tmp_path),
        health_policy=FleetHealthPolicy(on_nonfinite="freeze"),
    )
    for i in range(2):
        q.submit(TenantSpec(seed=i, n_steps=12, tag=f"v{i}"))
    with pytest.raises(RuntimeError, match="before start"):
        q.evict(0)
    q.start()
    q.step_chunk()
    with pytest.raises(ValueError, match="out of range"):
        q.evict(5)
    # pending is empty: the slot must park as inactive, rows masked
    entry = q.evict(0)
    assert entry["status"] == "evicted"
    assert entry["generations"] == 3
    assert os.path.isdir(entry["checkpoint"])
    slot = q.slots[0]
    assert slot is not None and not slot.active
    assert not slot.frozen  # parked, NOT health-quarantined
    assert bool(q.state.frozen[0])  # but its rows stop advancing
    with pytest.raises(ValueError, match="no active tenant"):
        q.evict(0)
    # a late submit refills the parked slot (mask cleared on admission)
    q.submit(TenantSpec(seed=9, n_steps=4, tag="late"))
    results = q.run()
    assert q.counters["evicted"] == 1 and q.counters["retired"] == 2
    assert q.counters["admitted"] == 3
    done = {r["tag"]: r for r in results}
    assert done["v1"]["status"] == "completed"
    assert done["v1"]["generations"] == 12
    assert done["late"]["status"] == "completed"
    assert done["late"]["generations"] == 4


def test_runqueue_backref_clobber_refused():
    """Satellite regression (ISSUE 11): constructing a second RunQueue
    over a workflow an UNFINISHED queue is driving used to silently
    rewire run_report's tenancy.queue pickup mid-sweep — now it raises;
    once the first queue's sweep completes, a new queue may adopt the
    workflow (and the report follows the adopter)."""
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=2)
    q = RunQueue(wf, chunk=3)
    for i in range(2):
        q.submit(TenantSpec(seed=i, n_steps=6, tag=f"b{i}"))
    with pytest.raises(RuntimeError, match="already driven"):
        RunQueue(wf)
    q.run()
    assert q.finished
    q2 = RunQueue(wf, chunk=3)  # completed sweep: adoption is legal
    assert wf._run_queue is q2


def test_runqueue_admission_peels_init_hooks(tmp_path):
    """Admission of an init_ask/init_tell algorithm solo-peels the first
    generation (the fleet's steady step must never dispatch init hooks
    for one slot), and the head start counts toward the budget."""
    from evox_tpu.algorithms.mo import NSGA2
    from evox_tpu.problems.numerical import ZDT1

    algo = NSGA2(
        lb=jnp.zeros(DIM), ub=jnp.ones(DIM), n_objs=2, pop_size=POP
    )
    wf = VectorizedWorkflow(algo, ZDT1(n_dim=DIM), n_tenants=2, num_objectives=2)
    q = RunQueue(wf, chunk=4)
    for i in range(3):
        q.submit(TenantSpec(seed=i, n_steps=8, tag=f"mo{i}"))
    results = q.run()
    assert [r["generations"] for r in results] == [8, 8, 8]


# ------------------------------------------------------------- observability


def test_run_report_tenancy_section_valid():
    """run_report carries the v3 tenancy section and the shipped
    validator accepts it (fleet shape coherent, per-tenant counters
    monotonic) — plus the queue counters when a RunQueue drove it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_report",
        os.path.join(os.path.dirname(__file__), "..", "tools", "check_report.py"),
    )
    check_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_report)

    tm = TelemetryMonitor(capacity=8)
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=2,
        hyperparams={"init_stdev": jnp.ones(2)}, monitors=(tm,),
    )
    q = RunQueue(wf, chunk=5)
    for i in range(2):
        q.submit(TenantSpec(seed=i, n_steps=10,
                            hyperparams={"init_stdev": 1.0}))
    q.run()
    report = run_report(wf, q.state)
    assert report["schema"] == "evox_tpu.run_report/v14"
    assert report["schema_version"] == 14
    ten = report["tenancy"]
    assert ten["n_tenants"] == 2
    assert ten["leading_axes"] == [2]
    assert len(ten["per_tenant"]) == 2
    assert ten["queue"]["counters"]["retired"] == 2
    assert check_report.validate_run_report(report) == []
    # incoherent fleet width must be rejected
    bad = dict(report)
    bad["tenancy"] = dict(ten, n_tenants=3)
    assert check_report.validate_run_report(bad) != []


def test_fleet_roofline_cites_frac_peak(ceilings):
    """The AOT roofline of the FUSED FLEET step/run carries achieved
    frac_peak_* rates (ISSUE 8 acceptance) via the differenced slope."""
    from evox_tpu import CostAnalyzer, instrument

    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=N, hyperparams=HP)
    rec = instrument(wf, block_dispatch=True)
    state = wf.init(_stacked_keys())
    state = wf.run(state, 5)
    state = wf.run(state, 5)
    state = wf.run(state, 50)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    entry = report["roofline"]["entries"]["run"]
    assert entry["timing_method"] == "differenced"
    assert entry["frac_peak_compute"] is not None
    assert entry["frac_peak_bandwidth"] is not None
    assert entry["static"]["flops"] > 0


def test_fleet_rejects_callback_monitors(tmp_path):
    """Host-callback monitors cannot run inside the vmapped fleet step
    on ANY backend — rejected loudly at construction, not with a cryptic
    vmap-of-cond trace error at step time."""
    from evox_tpu.monitors import CheckpointMonitor

    with pytest.raises(ValueError, match="host callbacks"):
        VectorizedWorkflow(
            _cmaes(), Sphere(), n_tenants=2,
            monitors=(CheckpointMonitor(str(tmp_path)),),
        )


def test_queue_admitted_tenant_hooks_see_own_generation():
    """A queue-admitted tenant's post_step hooks see ITS generation
    counter (starting from admission), not the fleet's lockstep counter
    — the law that keeps generation-gated monitors solo-equivalent."""
    from evox_tpu.core.monitor import Monitor

    class GenerationProbe(Monitor):
        def hooks(self):
            return ("post_step",)

        def init(self, key=None):
            return jnp.zeros((), jnp.int32)

        def post_step(self, mstate, wf_state):
            return jnp.asarray(wf_state.generation, jnp.int32)

    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=1, monitors=(GenerationProbe(),)
    )
    q = RunQueue(wf, chunk=4)
    q.submit(TenantSpec(seed=0, n_steps=8))
    q.submit(TenantSpec(seed=1, n_steps=5))
    q.run()
    # fleet lockstep counter reached 13; the second tenant's own counter
    # (what its hooks observed) is 5
    assert int(q.state.generation) == 13
    assert int(q.state.tenants.monitors[0][0]) == 5
    assert int(q.state.tenants.generation[0]) == 5


def test_fleet_post_step_workflow_state_contract():
    """post_step receives the documented workflow-state shape per tenant
    (.generation/.algo/...), not a bare TenantState — monitors written
    against StdWorkflow's contract (generation-gated savers) must trace
    identically inside the fleet."""
    from evox_tpu.core.monitor import Monitor

    class GenerationProbe(Monitor):
        def hooks(self):
            return ("post_step",)

        def init(self, key=None):
            return jnp.zeros((), jnp.int32)

        def post_step(self, mstate, wf_state):
            return jnp.asarray(wf_state.generation, jnp.int32)

    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=2, monitors=(GenerationProbe(),)
    )
    state = wf.run(wf.init(_stacked_keys(2)), 7)
    np.testing.assert_array_equal(
        np.asarray(state.tenants.monitors[0]), np.full(2, 7)
    )


# ------------------------------------------------- machinery reuse coverage


def test_fleet_guarded_algorithm():
    """GuardedAlgorithm vmaps like any algorithm: a fleet of guarded
    CMA-ES runs, tenant 0 matches the guarded solo run, and dotted
    hyperparam paths bind THROUGH the wrapper (copy-on-write)."""
    from evox_tpu import GuardedAlgorithm

    guarded = GuardedAlgorithm(_cmaes())
    wf = VectorizedWorkflow(
        guarded,
        Sphere(),
        n_tenants=2,
        hyperparams={"algorithm.init_stdev": jnp.asarray([0.5, 2.0])},
    )
    keys = _stacked_keys(2)
    state = wf.run(wf.init(keys), 10)
    assert int(state.tenants.algo.restarts.shape[0]) == 2
    solo_wf = wf.solo_workflow(0)
    solo = solo_wf.run(solo_wf.init(keys[0]), 10)
    _tree_allclose(
        jax.tree.map(lambda x: x[0], state.tenants.algo), solo.algo
    )


def test_fleet_bf16_storage_policy():
    """The DtypePolicy storage downcast applies fleet-wide: the stacked
    storage-annotated leaves rest bf16 between generations and the fleet
    still passes the Sphere gate."""
    from evox_tpu import BF16_STORAGE

    tm = TelemetryMonitor(capacity=4)
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=N, hyperparams=HP,
        monitors=(tm,), dtype_policy=BF16_STORAGE,
    )
    state = wf.run(wf.init(_stacked_keys()), 60)
    assert state.tenants.algo.z.dtype == jnp.bfloat16  # at-rest width
    assert state.tenants.algo.C.dtype == jnp.float32  # strategy state f32
    best = np.asarray(state.tenants.monitors[0].best_key)
    assert (best < 0.1).all(), f"bf16 fleet best per tenant: {best}"


def test_fleet_donate_carries_caller_safe():
    """donate_carries through the fleet run loop: the caller's state
    survives (snapshot-before-donate peel), results stay within the
    fleet tolerance of the undonated run."""
    keys = _stacked_keys()
    wf_d = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=N, hyperparams=HP, donate_carries=True
    )
    wf = VectorizedWorkflow(
        _cmaes(), Sphere(), n_tenants=N, hyperparams=HP
    )
    s0 = wf_d.init(keys)
    out = wf_d.run(s0, 10)
    # caller state not invalidated: run() peels through a non-donating
    # step before handing to the donated loop
    np.asarray(s0.tenants.algo.mean)
    ref = wf.run(wf.init(keys), 10)
    _tree_allclose(out.tenants.algo.mean, ref.tenants.algo.mean,
                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------- scale


@pytest.mark.slow
def test_large_fleet_n32_matches_solo():
    """N=32 fleet: spot-check solo equivalence at the bench-adjacent
    width (slow: one big vmapped compile)."""
    n = 32
    hp = {"init_stdev": jnp.linspace(0.5, 2.0, n)}
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=n, hyperparams=hp)
    keys = _stacked_keys(n)
    state = wf.run(wf.init(keys), 15)
    for i in (0, 17, 31):
        solo_wf = wf.solo_workflow(i)
        solo = solo_wf.run(solo_wf.init(keys[i]), 15)
        _tree_allclose(
            jax.tree.map(lambda x: x[i], state.tenants.algo), solo.algo
        )


@pytest.mark.slow
def test_large_fleet_eviction_sweep(tmp_path):
    """Resume-equivalence sweep: every tenant of an N=8 fleet evicted at
    gen 6 resumes solo to the straight solo run's trajectory."""
    from evox_tpu import WorkflowCheckpointer

    n = 8
    hp = {"init_stdev": jnp.linspace(0.5, 2.0, n)}
    wf = VectorizedWorkflow(_cmaes(), Sphere(), n_tenants=n, hyperparams=hp)
    keys = _stacked_keys(n)
    state = wf.run(wf.init(keys), 6)
    for i in range(n):
        d = str(tmp_path / f"t{i}")
        WorkflowCheckpointer(d, every=6).save(wf.extract_tenant(state, i))
        solo_wf = wf.solo_workflow(i)
        resumed = solo_wf.run(solo_wf.init(keys[i]), 14, resume_from=d)
        straight = solo_wf.run(solo_wf.init(keys[i]), 14)
        _tree_allclose(resumed.algo, straight.algo, rtol=1e-4, atol=1e-5)

"""Elastic serving (ISSUE 12): shape buckets, the AOT executable cache,
SLA scheduling, and the population autoscaler.

Laws under test:

- **Bucket admission ≡ solo**: a tenant padded into a bucket (requested
  pop < bucket pop, inert worst-finite fill rows) reproduces its solo
  ``StdWorkflow`` run at the exact bucket shape with the same mask —
  allclose(1e-5), the PR-7 tenancy contract — and the padded neighbour
  never perturbs a healthy tenant's telemetry ring fingerprint
  (bitwise).
- **Executable-cache laws** (core/exec_cache.py): memory hit → disk hit
  → compile ordering with coherent counters; LRU eviction falls back to
  the disk entry (never a recompile); a serialized executable reloaded
  in a FRESH PROCESS reproduces the compiling process's trajectory
  bitwise; torn/corrupt entries self-heal with a warning; intact but
  stale entries (foreign topology, inconsistent manifest key) refuse
  loudly (ExecCacheError, the CheckpointConfigError discipline); a
  frozen cache raises ExecCacheMissError — a RetraceError subclass, so
  the PR-4 strict-retrace alarm family covers cache misses.
- **Zero-retrace warm admission**: admitting tenants into a warmed
  bucket under ``DispatchRecorder(strict_retrace=True)`` AND a frozen
  cache triggers no aval retrace and no unplanned compile (the PR-12
  acceptance assert).
- **SLA scheduling**: EDF admission order, deadline-driven preemption
  (victim parks as a resumable checkpoint and completes later —
  preemption trades latency, never work), infeasible specs rejected at
  submit, and preempt→journal→recover crash equivalence (the in-process
  half; the SIGKILL half lives in tests/test_serving_chaos.py).
- **Autoscaling**: a guarded tenant showing the IPOP escalation signal
  grows into the next pop rung's bucket and completes there
  (workflows/ipop.py grow_guarded, re-targeted as a serving policy).
"""

import json
import multiprocessing as mp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import RunQueue, TenantSpec, instrument, run_report
from evox_tpu.core.exec_cache import (
    ExecCacheError,
    ExecCacheMissError,
    ExecutableCache,
)
from evox_tpu.core.instrument import RetraceError
from evox_tpu.workflows.elastic import (
    ACTIVE_ROWS,
    BucketError,
    BucketShape,
    BucketTable,
    ElasticServer,
    ElasticSpec,
    ElasticWorkflow,
    PopAutoscaler,
    pad_inert_rows,
    warm_fleet_cache,
)
from evox_tpu.algorithms.so.es import CMAES
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.problems.numerical import Sphere

DIM, POP, WIDTH = 4, 8, 2


def _bucket_wf(shape: BucketShape) -> ElasticWorkflow:
    algo = CMAES(
        center_init=jnp.ones(shape.dim), init_stdev=1.0, pop_size=shape.pop
    )
    return ElasticWorkflow(
        algo,
        Sphere(),
        n_tenants=shape.width,
        hyperparams={
            ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
        },
        monitors=(TelemetryMonitor(capacity=8),),
    )


def _pso_bucket_wf(shape: BucketShape) -> ElasticWorkflow:
    """PSO bucket: no LAPACK custom calls, so its executables PERSIST
    off-TPU — the factory for every disk/cold-process law (CMA's eigh
    embeds a host pointer the cache refuses to persist on CPU)."""
    from evox_tpu.algorithms.so.pso import PSO

    algo = PSO(
        lb=-5.0 * jnp.ones(shape.dim),
        ub=5.0 * jnp.ones(shape.dim),
        pop_size=shape.pop,
    )
    return ElasticWorkflow(
        algo,
        Sphere(),
        n_tenants=shape.width,
        hyperparams={
            ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
        },
        monitors=(TelemetryMonitor(capacity=8),),
    )


def _keys(n=WIDTH, base=0):
    return jnp.stack([jax.random.PRNGKey(base + i) for i in range(n)])


def _tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        if np.issubdtype(la.dtype, np.floating):
            np.testing.assert_allclose(
                la.astype(np.float64), lb.astype(np.float64),
                rtol=rtol, atol=atol,
            )
        else:
            np.testing.assert_array_equal(la, lb)


# -------------------------------------------------------------- bucket table


def test_bucket_table_rounds_up_pop_and_width_dim_exact():
    bt = BucketTable()
    b = bt.bucket_for(pop=37, dim=10, width=3)
    assert (b.pop, b.dim, b.width) == (64, 10, 4)
    # exact rungs pass through; dim is never quantized
    assert bt.bucket_for(64, 7, 4) == BucketShape(64, 7, 4)
    assert bt.next_pop_rung(64) == 128
    assert bt.next_pop_rung(1 << 16) is None


def test_bucket_table_custom_rungs_and_errors():
    bt = BucketTable(pop_rungs=[10, 20], width_rungs=[1, 2])
    assert bt.bucket_for(11, 3, 1).pop == 20
    with pytest.raises(BucketError, match="top rung"):
        bt.bucket_for(21, 3, 1)
    with pytest.raises(BucketError, match="dim"):
        bt.bucket_for(10, 0, 1)
    with pytest.raises(BucketError, match="positive"):
        BucketTable(pop_rungs=[0, 8])


def test_pad_inert_rows_unit():
    f = jnp.asarray([3.0, 1.0, 9.0, 2.0])
    out = pad_inert_rows(f, 2)
    # padded rows take the worst FINITE live value; live rows untouched
    np.testing.assert_array_equal(np.asarray(out), [3.0, 1.0, 3.0, 3.0])
    # active == pop is a bitwise identity
    np.testing.assert_array_equal(np.asarray(pad_inert_rows(f, 4)), f)
    # non-finite live rows don't leak into the fill
    f2 = jnp.asarray([jnp.inf, 1.0, 0.0, 5.0])
    np.testing.assert_array_equal(
        np.asarray(pad_inert_rows(f2, 2)), [np.inf, 1.0, 1.0, 1.0]
    )
    # MO: per-objective columns fill independently
    fm = jnp.asarray([[1.0, 8.0], [2.0, 4.0], [0.0, 0.0]])
    np.testing.assert_array_equal(
        np.asarray(pad_inert_rows(fm, 2)), [[1.0, 8.0], [2.0, 4.0], [2.0, 8.0]]
    )
    # all-nonfinite live rows fall back to dtype max, never NaN/Inf fill
    f3 = jnp.asarray([jnp.nan, jnp.inf, 0.0])
    filled = np.asarray(pad_inert_rows(f3, 2))
    assert np.isfinite(filled[2])


# ------------------------------------------------------- padded ≡ solo law


def test_padded_tenant_matches_solo_and_neighbor_unperturbed():
    """Tenant 0 runs padded (5 of 8 rows live), tenant 1 full. Tenant
    0 ≡ its solo reference with the same mask (the bucket-admission
    law); tenant 1's telemetry ring is BITWISE the no-padded-neighbour
    solo run's (inert rows never leak across vmap lanes)."""
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _bucket_wf(shape)
    hp = {ACTIVE_ROWS: jnp.asarray([5, POP], jnp.int32)}
    keys = _keys()
    state = wf.run(wf.init(keys, hyperparams=hp), 10)
    for i, active in enumerate((5, POP)):
        solo_wf = wf.solo_workflow(
            i, hyperparams={ACTIVE_ROWS: jnp.asarray(active, jnp.int32)}
        )
        solo = solo_wf.run(solo_wf.init(keys[i]), 10)
        _tree_allclose(
            jax.tree.map(lambda x: x[i], state.tenants.algo), solo.algo
        )
        # telemetry fingerprint: the whole observed trajectory, bitwise
        mon = wf.monitors[0]
        assert mon.fingerprint(
            jax.tree.map(lambda x: x[i], state.tenants.monitors[0])
        ) == mon.fingerprint(solo.monitors[0])


def test_padded_tenant_converges():
    """Convergence gate (CLAUDE.md convention): the inert fill must not
    poison selection — a padded CMA-ES tenant still drives Sphere below
    threshold at its requested pop."""
    shape = BucketShape(pop=16, dim=DIM, width=WIDTH)
    wf = _bucket_wf(shape)
    hp = {ACTIVE_ROWS: jnp.asarray([11, 16], jnp.int32)}
    state = wf.run(wf.init(_keys(), hyperparams=hp), 60)
    best = np.asarray(state.tenants.monitors[0].best_key)
    assert (best < 1e-2).all(), f"per-tenant best: {best}"


# ------------------------------------------------------------- exec cache


def _double(x):
    return x * 2.0 + 1.0


def test_exec_cache_hit_miss_disk_and_lru(tmp_path):
    cache = ExecutableCache(directory=str(tmp_path))
    x = jnp.arange(4.0)
    c1 = cache.get_or_compile("double", "cfg", _double, (x,))
    assert cache.counters == {
        "hits": 0, "disk_hits": 0, "misses": 1, "saves": 1, "evictions": 0,
    }
    c2 = cache.get_or_compile("double", "cfg", _double, (x,))
    assert c2 is c1 and cache.counters["hits"] == 1
    # a fresh cache over the same store: disk hit, bitwise-equal output
    cache2 = ExecutableCache(directory=str(tmp_path))
    c3 = cache2.get_or_compile("double", "cfg", _double, (x,))
    assert cache2.counters["misses"] == 0
    assert cache2.counters["disk_hits"] == 1
    np.testing.assert_array_equal(np.asarray(c3(x)), np.asarray(c1(x)))
    # LRU eviction drops the executable from MEMORY only: re-requesting
    # the victim is a disk hit, never a recompile
    small = ExecutableCache(directory=str(tmp_path), max_entries=1)
    small.get_or_compile("double", "cfg", _double, (x,))
    small.get_or_compile("double", "cfg", _double, (jnp.arange(8.0),))
    assert small.counters["evictions"] == 1
    small.get_or_compile("double", "cfg", _double, (x,))
    assert small.counters["disk_hits"] == 2 and small.counters["misses"] == 1
    # report: the check_report v7 coherence law (misses == compiled
    # entries, repeats-weighted) holds on the real object
    rep = small.report()
    compiled = sum(
        e.get("repeats", 1)
        for e in rep["entries"]
        if e["source"] == "compiled"
    )
    assert rep["counters"]["misses"] == compiled
    # provenance must not grow with traffic (review finding): the two
    # disk loads of the same key aggregate into ONE record's `repeats`,
    # so a long-lived server cycling over an LRU-bounded working set
    # keeps entries (and report()) bounded by distinct (key, source)
    disk_entries = [e for e in rep["entries"] if e["source"] == "disk"]
    assert len(disk_entries) == 1 and disk_entries[0]["repeats"] == 2
    # cycling the LRU working set forever adds at most ONE (key, disk)
    # record per distinct key — further reloads only bump `repeats`
    small.get_or_compile("double", "cfg", _double, (jnp.arange(8.0),))
    before = len(small.entries)
    small.get_or_compile("double", "cfg", _double, (x,))
    small.get_or_compile("double", "cfg", _double, (jnp.arange(8.0),))
    assert len(small.entries) == before  # reloads aggregated, not appended


def test_exec_cache_corrupt_entry_self_heals(tmp_path):
    cache = ExecutableCache(directory=str(tmp_path))
    x = jnp.arange(4.0)
    cache.get_or_compile("double", "cfg", _double, (x,))
    (payload,) = tmp_path.glob("*.exec")
    payload.write_bytes(payload.read_bytes()[:-7])  # torn write artifact
    fresh = ExecutableCache(directory=str(tmp_path))
    with pytest.warns(UserWarning, match="corrupt"):
        fresh.get_or_compile("double", "cfg", _double, (x,))
    assert fresh.counters["misses"] == 1  # recompiled, self-healed
    healed = ExecutableCache(directory=str(tmp_path))
    healed.get_or_compile("double", "cfg", _double, (x,))
    assert healed.counters["disk_hits"] == 1


def test_exec_cache_stale_topology_refuses_loudly(tmp_path):
    cache = ExecutableCache(directory=str(tmp_path))
    x = jnp.arange(4.0)
    cache.get_or_compile("double", "cfg", _double, (x,))
    (man_path,) = tmp_path.glob("*.manifest.json")
    manifest = json.loads(man_path.read_text())
    manifest["topology"]["device_count"] = 4096  # a foreign machine
    man_path.write_text(json.dumps(manifest))
    fresh = ExecutableCache(directory=str(tmp_path))
    with pytest.raises(ExecCacheError, match="different topology"):
        fresh.get_or_compile("double", "cfg", _double, (x,))
    # an inconsistent manifest key (store rewritten/copied) also refuses
    manifest["topology"]["device_count"] = jax.device_count()
    manifest["key"] = "f" * 64
    man_path.write_text(json.dumps(manifest))
    with pytest.raises(ExecCacheError, match="manifest key"):
        ExecutableCache(directory=str(tmp_path)).get_or_compile(
            "double", "cfg", _double, (x,)
        )


def test_exec_cache_strict_miss_is_retrace_family(tmp_path):
    cache = ExecutableCache(directory=str(tmp_path), strict=True)
    x = jnp.arange(4.0)
    with pytest.raises(ExecCacheMissError, match="frozen cache"):
        cache.get_or_compile("double", "cfg", _double, (x,))
    assert issubclass(ExecCacheMissError, RetraceError)
    # planned warms never trip the alarm; freeze() arms it afterwards
    cache2 = ExecutableCache(directory=str(tmp_path))
    cache2.get_or_compile("double", "cfg", _double, (x,), planned=True)
    cache2.freeze()
    cache2.get_or_compile("double", "cfg", _double, (x,))  # memory hit: fine
    with pytest.raises(ExecCacheMissError):
        cache2.get_or_compile("double", "cfg", _double, (jnp.arange(8.0),))


# ------------------------------------------------- fresh-process reload law


def _cache_child(cache_dir, out_path):
    """Spawned child: warm-start the SAME bucket from the on-disk store
    (asserting zero compiles) and run the reference trajectory."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax as _jax

    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _pso_bucket_wf(shape)
    cache = ExecutableCache(directory=cache_dir)
    warm_fleet_cache(wf, cache, bucket=shape)
    state = wf.run(wf.init(_keys()), 6)
    mon = wf.monitors[0]
    prints = [
        mon.fingerprint(
            _jax.tree.map(lambda x: x[i], state.tenants.monitors[0])
        )
        for i in range(WIDTH)
    ]
    with open(out_path, "w") as f:
        json.dump({"counters": cache.counters, "prints": prints}, f)
        f.flush()
        os.fsync(f.fileno())
    # deserialized executables still alive at interpreter teardown can
    # crash jax's atexit clear_backends on this jax version (the results
    # above are already durable; see core/exec_cache.py's teardown note)
    os._exit(0)


def test_serialized_executable_fresh_process_bitwise(tmp_path):
    """The cold-start law: a cold PROCESS deserializes the fleet's
    executables from disk (zero compiles) and reproduces the compiling
    process's trajectory bitwise (telemetry ring fingerprints)."""
    cache_dir = str(tmp_path / "store")
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _pso_bucket_wf(shape)
    cache = ExecutableCache(directory=cache_dir)
    warm_fleet_cache(wf, cache, bucket=shape)
    assert cache.counters["misses"] == 4  # the four serving executables
    if cache.counters["saves"] == 0:
        pytest.skip("backend cannot serialize executables")
    state = wf.run(wf.init(_keys()), 6)
    mon = wf.monitors[0]
    parent_prints = [
        mon.fingerprint(jax.tree.map(lambda x: x[i], state.tenants.monitors[0]))
        for i in range(WIDTH)
    ]
    out = tmp_path / "child.json"
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_cache_child, args=(cache_dir, str(out)))
    p.start()
    p.join(600)
    assert p.exitcode == 0
    got = json.loads(out.read_text())
    assert got["counters"]["misses"] == 0, got["counters"]
    assert got["counters"]["disk_hits"] == 4
    assert got["prints"] == parent_prints


def _cache_clean_exit_child(cache_dir):
    """Spawned child: deserialize the fleet's executables from disk,
    run, then exit NORMALLY — no ``os._exit`` escape hatch. The cache's
    atexit guard (core/exec_cache.py) must drop the
    deserialized references before jax's ``clear_backends`` runs, or
    this child segfaults instead of returning 0."""
    import sys

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _pso_bucket_wf(shape)
    cache = ExecutableCache(directory=cache_dir)
    warm_fleet_cache(wf, cache, bucket=shape)
    assert cache.counters["disk_hits"] > 0, cache.counters
    wf.run(wf.init(_keys()), 2)
    sys.exit(0)  # normal interpreter teardown IS the law under test


def test_deserialized_executables_clean_interpreter_exit(tmp_path):
    """PR 18 regression: a fresh process whose
    executables all came from the disk store exits 0 through normal
    interpreter teardown — the atexit teardown guard, not ``os._exit``,
    keeps the deserialized refs from outliving the backend."""
    cache_dir = str(tmp_path / "store")
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _pso_bucket_wf(shape)
    cache = ExecutableCache(directory=cache_dir)
    warm_fleet_cache(wf, cache, bucket=shape)
    if cache.counters["saves"] == 0:
        pytest.skip("backend cannot serialize executables")
    # deterministic close() is idempotent and non-destructive: the next
    # lookup pays a disk hit, never a recompile
    cache.close()
    cache.close()
    assert cache._mem == {}
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_cache_clean_exit_child, args=(cache_dir,))
    p.start()
    p.join(600)
    assert p.exitcode == 0


# ---------------------------------------------------- zero-retrace admission


def test_warm_admission_zero_retraces(tmp_path):
    """The acceptance assert: churn tenants through a WARMED bucket under
    DispatchRecorder(strict_retrace=True) and a frozen cache — admission
    is pure state surgery against cached executables; any aval retrace
    or unplanned compile raises."""
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _bucket_wf(shape)
    cache = ExecutableCache(directory=str(tmp_path))
    warm_fleet_cache(wf, cache, bucket=shape)
    cache.freeze()
    rec = instrument(wf, strict_retrace=True)
    q = RunQueue(wf, chunk=3)
    hp0 = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    for i in range(5):  # 5 specs through 2 slots: 3 mid-sweep admissions
        q.submit(
            TenantSpec(
                seed=i,
                n_steps=4,
                hyperparams={
                    **hp0,
                    ACTIVE_ROWS: jnp.asarray(5 + i % 4, jnp.int32),
                },
                tag=f"t{i}",
            )
        )
    results = q.run()  # any retrace/unplanned compile raises here
    assert len(results) == 5
    assert all(r["status"] == "completed" for r in results)
    assert rec.summary()["retrace_flags"] == []
    rep = run_report(wf, q.state, recorder=rec)
    assert rep["serving"]["cache"]["counters"]["misses"] == 4
    assert rep["serving"]["cache"]["strict"] is True


def test_warm_fleet_cache_requires_jit():
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    algo = CMAES(center_init=jnp.ones(DIM), init_stdev=1.0, pop_size=POP)
    wf = ElasticWorkflow(
        algo,
        Sphere(),
        n_tenants=WIDTH,
        hyperparams={ACTIVE_ROWS: jnp.full((WIDTH,), POP, jnp.int32)},
        jit_step=False,
    )
    with pytest.raises(ValueError, match="jit_step"):
        warm_fleet_cache(wf, ExecutableCache(), bucket=shape)


# ------------------------------------------------------------ elastic server


def test_elastic_server_end_to_end(tmp_path):
    """Ragged requests route onto the lattice, run padded, and complete;
    filler tenants are dropped from results; a cold re-serve over the
    same store is all disk hits (zero compiles)."""
    cache_dir = str(tmp_path / "cache")

    def serve_once():
        srv = ElasticServer(
            _pso_bucket_wf, cache_dir=cache_dir, width=WIDTH, chunk=3
        )
        for i, pop in enumerate((5, 8, 13)):
            srv.submit(
                ElasticSpec(
                    seed=i, n_steps=5, pop=pop, dim=DIM, tag=f"req{i}"
                )
            )
        return srv, srv.serve()

    srv1, res1 = serve_once()
    assert sorted(r["tag"] for r in res1) == ["req0", "req1", "req2"]
    assert {r["bucket"] for r in res1} == {
        f"pop{POP}_dim{DIM}_w{WIDTH}", f"pop16_dim{DIM}_w{WIDTH}"
    }
    assert all(r["status"] == "completed" for r in res1)
    assert srv1.cache.counters["misses"] == 8  # 2 buckets x 4 entries
    srv2, res2 = serve_once()
    assert srv2.cache.counters["misses"] == 0
    assert srv2.cache.counters["disk_hits"] == 8
    # identical trajectories across the cold restart
    k = lambda rs: sorted(
        (r["tag"], tuple(r["fingerprints"])) for r in rs
    )
    assert k(res1) == k(res2)
    rep = srv2.report()
    assert set(rep["buckets"]) == {r["bucket"] for r in res2}
    assert rep["cache"]["counters"]["disk_hits"] == 8


def test_elastic_server_factory_validation():
    def bad_width(shape):
        return _bucket_wf(
            BucketShape(pop=shape.pop, dim=shape.dim, width=shape.width + 1)
        )

    srv = ElasticServer(bad_width, width=WIDTH)
    with pytest.raises(ValueError, match="wide fleet"):
        srv.submit(ElasticSpec(seed=0, n_steps=1, pop=POP, dim=DIM))

    def no_active_rows(shape):
        algo = CMAES(
            center_init=jnp.ones(shape.dim), init_stdev=1.0,
            pop_size=shape.pop,
        )
        return ElasticWorkflow(algo, Sphere(), n_tenants=shape.width)

    srv2 = ElasticServer(no_active_rows, width=WIDTH)
    with pytest.raises(ValueError, match="reserved"):
        srv2.submit(ElasticSpec(seed=0, n_steps=1, pop=POP, dim=DIM))


# ------------------------------------------------------------ SLA scheduling


def test_pop_mismatch_rejected_at_submit():
    """Satellite regression: a TenantSpec declaring a pop that doesn't
    match the fleet's compiled shape is rejected AT submit() with a
    routing error, not a shape error deep inside the fused step."""
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    q = RunQueue(wf, chunk=3)
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    with pytest.raises(ValueError, match="compiled pop_size"):
        q.submit(TenantSpec(seed=0, n_steps=2, hyperparams=hp, pop=POP + 5))
    q.submit(TenantSpec(seed=0, n_steps=2, hyperparams=hp, pop=POP))  # ok


def test_insert_tenant_shape_guard():
    """The scatter-side guard: a solo state built for another shape is
    named as a routing bug, not an opaque broadcast error."""
    wf8 = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    wf16 = _bucket_wf(BucketShape(pop=16, dim=DIM, width=WIDTH))
    state = wf8.init(_keys())
    alien = wf16.init_tenant(
        jax.random.PRNGKey(0), {ACTIVE_ROWS: jnp.asarray(16, jnp.int32)}
    )
    with pytest.raises(ValueError, match="bucket lattice"):
        wf8.insert_tenant(state, 0, alien)


def test_sla_spec_validation(tmp_path):
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    q = RunQueue(wf, chunk=3)
    with pytest.raises(ValueError, match="infeasible"):
        q.submit(
            TenantSpec(seed=0, n_steps=9, hyperparams=hp, deadline=5)
        )
    with pytest.raises(ValueError, match="checkpoint_dir"):
        q.submit(
            TenantSpec(seed=0, n_steps=2, hyperparams=hp, deadline=9)
        )
    wf2 = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    q2 = RunQueue(wf2, chunk=3, checkpoint_dir=str(tmp_path))
    q2.submit(TenantSpec(seed=0, n_steps=2, hyperparams=hp, deadline=9))


def test_sla_edf_admission_order(tmp_path):
    """Deadlined specs are admitted ahead of FIFO work, earliest
    deadline first."""
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    q = RunQueue(wf, chunk=3, checkpoint_dir=str(tmp_path))
    q.submit(TenantSpec(seed=0, n_steps=2, hyperparams=hp, tag="fifo"))
    q.submit(
        TenantSpec(seed=1, n_steps=2, hyperparams=hp, tag="d30", deadline=30)
    )
    q.submit(
        TenantSpec(seed=2, n_steps=2, hyperparams=hp, tag="d10", deadline=10)
    )
    q.start()
    assert [s.spec.tag for s in q.slots] == ["d10", "d30"]


def test_sla_preemption_end_to_end(tmp_path):
    """A mid-sweep urgent spec preempts the most over-budget tenant; the
    urgent run meets its deadline; the victim resumes from its parked
    checkpoint and completes its FULL budget (work preserved)."""
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    q = RunQueue(
        wf, chunk=3,
        checkpoint_dir=str(tmp_path / "ckpt"),
        journal=str(tmp_path / "wal"),
    )
    q.submit(TenantSpec(seed=0, n_steps=18, hyperparams=hp, tag="long0"))
    q.submit(TenantSpec(seed=1, n_steps=18, hyperparams=hp, tag="long1"))
    q.start()
    q.step_chunk()
    q.submit(
        TenantSpec(
            seed=2, n_steps=4, hyperparams=hp, tag="urgent", deadline=10
        )
    )
    while not q.finished:
        q.step_chunk()
    by_status = {}
    for r in q.results:
        by_status.setdefault(r["status"], []).append(r)
    assert [r["tag"] for r in by_status["preempted"]] == ["long0"]
    assert q.counters["preempted"] == 1 and q.counters["readmitted"] == 1
    done = {r["tag"]: r for r in by_status["completed"]}
    assert done["urgent"]["generations"] == 4
    # the victim completed its whole budget after resuming
    assert done["long0"]["generations"] == 18
    assert done["long1"]["generations"] == 18
    # the urgent run met its deadline: its admit record's fleet
    # generation + budget fits inside the bound
    recs = q.journal.records()
    urgent_seq = next(
        r["spec_seq"] for r in recs
        if r["kind"] == "submit" and r.get("tag") == "urgent"
    )
    admit = next(
        r for r in recs
        if r["kind"] == "admit" and r.get("spec_seq") == urgent_seq
    )
    assert admit["fleet_generation"] + 4 <= 10
    # preempt close-out is journaled with its resumable artifact
    preempt = next(r for r in recs if r["kind"] == "preempt")
    assert preempt["entry"]["checkpoint"]


def _sla_digest(results):
    return sorted(
        (r["tag"], r["status"], r["generations"], tuple(r["fingerprints"]))
        for r in results
    )


def _sla_drive(tmp, crash_after=None):
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    q = RunQueue(
        wf, chunk=3,
        checkpoint_dir=os.path.join(tmp, "ckpt"),
        journal=os.path.join(tmp, "wal"),
    )
    q.submit(TenantSpec(seed=0, n_steps=15, hyperparams=hp, tag="long0"))
    q.submit(TenantSpec(seed=1, n_steps=15, hyperparams=hp, tag="long1"))
    q.start()
    q.step_chunk()
    q.submit(
        TenantSpec(
            seed=2, n_steps=4, hyperparams=hp, tag="urgent", deadline=10
        )
    )
    n = 1
    while not q.finished:
        if crash_after is not None and n >= crash_after:
            return None  # abandon the queue object = in-process "crash"
        q.step_chunk()
        n += 1
    return _sla_digest(q.results)


@pytest.mark.parametrize("crash_after", [1, 2, 4])
def test_sla_preempt_recover_equivalence(tmp_path, crash_after):
    """Crash equivalence through preemption: recovery replays the EDF +
    preemption decisions deterministically (fleet-generation clock, not
    wall clock) and reproduces the uncrashed digest bitwise. crash_after
    = 1 crashes right after the urgent submit with NO following barrier
    — the acknowledged-submit-survives law for mid-sweep arrivals."""
    ref = _sla_drive(str(tmp_path / "ref"))
    tmp = str(tmp_path / f"crash{crash_after}")
    assert _sla_drive(tmp, crash_after=crash_after) is None
    wf = _bucket_wf(BucketShape(pop=POP, dim=DIM, width=WIDTH))
    q = RunQueue.recover(wf, os.path.join(tmp, "wal"))
    while not q.finished:
        q.step_chunk()
    assert _sla_digest(q.results) == ref


# --------------------------------------------------------------- autoscaler


class _Flatline(Sphere):
    """Constant fitness: nothing ever improves, so the guarded
    stagnation counter climbs deterministically — the escalation signal
    the autoscaler grows on."""

    def evaluate(self, state, pop):
        fit, state = super().evaluate(state, pop)
        return jnp.zeros_like(fit), state


def test_autoscaler_grows_into_next_bucket():
    from evox_tpu import GuardedAlgorithm

    def factory(shape):
        algo = GuardedAlgorithm(
            CMAES(
                center_init=jnp.ones(shape.dim),
                init_stdev=1.0,
                pop_size=shape.pop,
            ),
            stagnation_limit=3,
        )
        return ElasticWorkflow(
            algo,
            _Flatline(),
            n_tenants=shape.width,
            hyperparams={
                ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
            },
            monitors=(TelemetryMonitor(capacity=8),),
        )

    srv = ElasticServer(
        factory, width=1, chunk=4, autoscaler=PopAutoscaler(max_grows=1)
    )
    srv.submit(ElasticSpec(seed=0, n_steps=16, pop=POP, dim=DIM, tag="grow"))
    results = srv.serve()
    assert len(srv.autoscale_events) == 1
    ev = srv.autoscale_events[0]
    assert ev["tag"] == "grow"
    assert ev["from"] == f"pop{POP}_dim{DIM}_w1"
    assert ev["to"] == f"pop16_dim{DIM}_w1"
    by_status = {r["status"]: r for r in results}
    assert by_status["grown"]["bucket"] == ev["from"]
    done = by_status["completed"]
    assert done["bucket"] == ev["to"]
    # the grown continuation finished the ORIGINAL budget at the new rung
    assert done["generations"] == 16
    rep = srv.report()
    assert rep["autoscale"]["events"] == srv.autoscale_events
    assert rep["autoscale"]["policy"] == {
        "stagnation_limit": None, "max_grows": 1,
    }


def test_autoscaler_requires_guarded_algorithm():
    srv = ElasticServer(
        _bucket_wf, width=WIDTH, autoscaler=PopAutoscaler()
    )
    with pytest.raises(ValueError, match="GuardedAlgorithm"):
        srv.submit(ElasticSpec(seed=0, n_steps=1, pop=POP, dim=DIM))


def test_fleet_fingerprint_transform_identity():
    """Cache-key law for transforms (review finding): two DIFFERENT
    lambdas — both named ``<lambda>`` — must not collide (a shared cache
    directory would serve one fleet the other's compiled program), and a
    ``functools.partial`` transform must fingerprint WITHOUT a process-
    local 0x address (an address in the key silently defeats the
    cross-process disk warm start)."""
    from functools import partial

    from evox_tpu.workflows.elastic import (
        _transform_identity,
        fleet_fingerprint,
    )

    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)

    def wf_with(ft):
        algo = CMAES(
            center_init=jnp.ones(shape.dim),
            init_stdev=1.0,
            pop_size=shape.pop,
        )
        return ElasticWorkflow(
            algo,
            Sphere(),
            n_tenants=shape.width,
            hyperparams={
                ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
            },
            fit_transforms=ft,
        )

    fp_double = fleet_fingerprint(wf_with((lambda f: f * 2.0,)))
    fp_sorted = fleet_fingerprint(wf_with((lambda f: jnp.sort(f),)))
    fp_none = fleet_fingerprint(wf_with(()))
    assert len({fp_double, fp_sorted, fp_none}) == 3

    # identical bodies at the same definition site agree (re-built
    # factories across processes must land on the same key)
    def make():
        return wf_with((partial(pad_inert_rows, active=5),))

    ida = fleet_fingerprint(make())
    idb = fleet_fingerprint(make())
    assert ida == idb
    # and a different bound value is a different program
    assert ida != fleet_fingerprint(
        wf_with((partial(pad_inert_rows, active=6),))
    )

    # no process-local address may leak into any identity component
    for t in (
        partial(pad_inert_rows, active=5),
        lambda f: f,
        np.sort,  # builtin-like callable without __code__
    ):
        assert "0x" not in _transform_identity(t), _transform_identity(t)

    # LARGE baked constants must hash by VALUE, not by numpy's
    # truncating repr: two >1000-element arrays differing in ONE
    # element are different programs (confirmed review repro)
    big1 = np.arange(2000, dtype=np.float32)
    big2 = big1.copy()
    big2[1500] += 1.0

    def closing_over(arr):
        return lambda f: f + arr.sum()

    assert _transform_identity(closing_over(big1)) != _transform_identity(
        closing_over(big2)
    )
    assert _transform_identity(
        partial(jnp.add, big1)
    ) != _transform_identity(partial(jnp.add, big2))


@pytest.mark.slow
def test_autoscaler_growth_peels_init_overrides():
    """Review finding: a grown tenant of an init_ask/init_tell algorithm
    (CSO keeps parent fitness from its first generation) must get the
    SOLO init peel at the target rung — exactly like `_fresh_tenant`
    admission and ipop_run's ``first_step=True`` — or its first steady
    tell ingests fitness against an uninitialized parent state."""
    from evox_tpu import GuardedAlgorithm
    from evox_tpu.algorithms.so.pso.cso import CSO

    def factory(shape):
        algo = GuardedAlgorithm(
            CSO(
                lb=-5.0 * jnp.ones(shape.dim),
                ub=5.0 * jnp.ones(shape.dim),
                pop_size=shape.pop,
            ),
            stagnation_limit=3,
        )
        return ElasticWorkflow(
            algo,
            _Flatline(),
            n_tenants=shape.width,
            hyperparams={
                ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
            },
            monitors=(TelemetryMonitor(capacity=8),),
        )

    srv = ElasticServer(
        factory, width=1, chunk=4, autoscaler=PopAutoscaler(max_grows=1)
    )
    # pre-create the target bucket and spy on its solo peel: growth MUST
    # route the grown tenant through it exactly once
    target = srv._get_bucket(BucketShape(pop=2 * POP, dim=DIM, width=1))
    orig_peel = target.workflow._solo_peel
    peels = []

    def spying_peel(t):
        peels.append(int(t.generation))
        return orig_peel(t)

    target.workflow._solo_peel = spying_peel
    srv.submit(ElasticSpec(seed=0, n_steps=16, pop=POP, dim=DIM, tag="g"))
    results = srv.serve()
    assert len(srv.autoscale_events) == 1
    assert peels, "grown init-override tenant skipped the solo init peel"
    done = {r["status"]: r for r in results}["completed"]
    assert done["generations"] == 16


def test_fleet_fingerprint_keys_instance_config():
    """Review finding: closed-over constants (PSO bounds) are BAKED into
    the traced program but appear in neither the class name nor the
    abstract signature — they must key distinct executables, and the
    digest must be stable across reconstruction (the disk warm start)."""
    from evox_tpu.workflows.elastic import fleet_fingerprint

    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)

    def pso_wf(ub):
        from evox_tpu.algorithms.so.pso import PSO

        algo = PSO(
            lb=-5.0 * jnp.ones(shape.dim),
            ub=ub * jnp.ones(shape.dim),
            pop_size=shape.pop,
        )
        return ElasticWorkflow(
            algo, Sphere(), n_tenants=shape.width,
            hyperparams={
                ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
            },
        )

    assert fleet_fingerprint(pso_wf(5.0)) == fleet_fingerprint(pso_wf(5.0))
    assert fleet_fingerprint(pso_wf(5.0)) != fleet_fingerprint(pso_wf(1.0))
    # nested config (a guarded wrapper's INNER algorithm) discriminates
    from evox_tpu import GuardedAlgorithm

    def guarded_wf(stdev):
        algo = GuardedAlgorithm(
            CMAES(
                center_init=jnp.ones(shape.dim),
                init_stdev=stdev,
                pop_size=shape.pop,
            )
        )
        return ElasticWorkflow(
            algo, Sphere(), n_tenants=shape.width,
            hyperparams={
                ACTIVE_ROWS: jnp.full((shape.width,), shape.pop, jnp.int32)
            },
        )

    assert fleet_fingerprint(guarded_wf(1.0)) != fleet_fingerprint(
        guarded_wf(2.0)
    )


def test_start_fills_from_continuations(tmp_path):
    """Review finding: a queue whose remaining work is continuations
    (e.g. a recovered cross-journal growth handoff) must be startable —
    the pending-only guard stranded acknowledged work."""
    shape = BucketShape(pop=POP, dim=DIM, width=WIDTH)
    wf = _bucket_wf(shape)
    hp = {ACTIVE_ROWS: jnp.asarray(POP, jnp.int32)}
    # park a real solo state as the continuation source
    solo_wf = wf.solo_workflow(hyperparams=hp)
    solo = solo_wf.run(solo_wf.init(jax.random.PRNGKey(3)), 4)

    q = RunQueue(wf, chunk=2)
    q.submit(TenantSpec(seed=0, n_steps=8, hyperparams=hp, tag="fresh"))
    q.submit_resume(
        TenantSpec(seed=3, n_steps=8, hyperparams=hp, tag="parked"),
        state=solo,
    )
    results = q.run()
    tags = sorted(r["tag"] for r in results)
    assert tags == ["fresh", "parked"]
    by_tag = {r["tag"]: r for r in results}
    # the parked tenant RESUMED (4 gens done + the remaining budget),
    # it was not restarted from scratch
    assert by_tag["parked"]["generations"] == 8
    assert q.counters["admitted"] == 2 and q.counters["readmitted"] == 1

"""Million-scale populations: gather-free POP-sharded low-memory ES (PR 10).

Laws asserted here:

1. **Stable recombination weights** (es/common.py): the log-rank weights
   computed via the log1p raw form + max-subtracted-logsumexp
   normalization stay positive, strictly decreasing, and Σw=1 at
   pop ∈ {1e4, 1e6} against an f64 numpy reference — where the naive f32
   spelling catastrophically cancels (tail weights to ~0/negative).
2. **Sharded ≡ replicated**: a ShardedES workflow on the 8-device mesh
   reproduces the replicated layout of the SAME per-shard sampling law
   (bitwise-identical samples; summation-order-only differences in the
   state updates — documented tolerance, per-step law in
   tests/test_state_contracts.py).
3. **Gather-free memory law** (the tentpole acceptance): AOT
   `memory_analysis()` of the compiled sharded step shows PER-DEVICE peak
   bytes below the full-pop artifact bytes and scaling with pop/n_dev,
   and the compiled HLO never mentions the full ``(pop, dim)`` shape.
4. **Convergence at scale** (CLAUDE.md threshold rule): sharded SepCMAES
   and LMMAES solve Sphere at pop=1e5 in tier-1; pop=1e6
   Sphere (SepCMAES) + Rosenbrock (mu-capped RMES) are slow-marked.
5. **Dense-track guard + IPOP handoff**: CMAES refuses dim/pop past the
   single-device wall with `EighScaleError` naming the handoff;
   `IPOPRestarts(handoff_pop=, handoff_factory=)` switches doubling onto
   the sharded low-memory track and surfaces the event in
   ``run_report()["guardrail"]["ipop"]``.
6. **Composition**: GuardedAlgorithm + bf16 DtypePolicy + fused run +
   the (TENANT, POP) 2-D mesh all compose with ShardedES.

Large-pop behavioral deviations these tests pin (documented in
GUIDE.md §7 / PARITY row 55): SepCMAES caps ccov at 1.0 (the unclamped
Ros-Hansen rate exceeds 1 past mueff ~ (n+2)^2, flipping the covariance
decay sign), LMMAES norm-rails its path drive at 2*chiN, and both use the
bounded (|Δlog σ| ≤ ln 2) step-size update — all identity at
conventional population sizes.
"""

import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    BF16_STORAGE,
    GuardedAlgorithm,
    IPOPRestarts,
    ShardedES,
    StdWorkflow,
    create_mesh,
    CostAnalyzer,
    instrument,
    run_report,
)
from evox_tpu.algorithms.so.es import CMAES, LMMAES, RMES, SepCMAES
from evox_tpu.algorithms.so.es.common import (
    EighScaleError,
    recombination_weights,
    safe_eigh,
    weights_at_ranks,
)
from evox_tpu.core.distributed import POP_AXIS, TENANT_AXIS
from evox_tpu.problems.numerical import Rosenbrock, Sphere

N_DEV = 8

_REPO = pathlib.Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "check_report", _REPO / "tools" / "check_report.py"
)
check_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_report)


def _mesh():
    return create_mesh()


def _sharded_wf(algo_cls, dim, pop, mesh, n_shards=None, problem=None, **kw):
    algo = ShardedES(
        algo_cls(center_init=jnp.full(dim, 2.0), init_stdev=1.0, pop_size=pop),
        mesh=mesh,
        n_shards=n_shards,
    )
    return StdWorkflow(algo, problem or Sphere(), mesh=mesh, **kw)


# ------------------------------------------------------------------- weights


@pytest.mark.parametrize("pop", [10_000, 1_000_000], ids=["1e4", "1e6"])
def test_stable_weights_at_scale(pop):
    """Satellite 1: f32 log-rank weights at very large mu — positive,
    strictly decreasing, Σw=1, and within 1e-4 relative of an f64 numpy
    reference computed the naive (but f64-safe) way."""
    mu = pop // 2
    w = np.asarray(recombination_weights(mu))
    assert w.shape == (mu,)
    assert w.dtype == np.float32
    assert (w > 0).all(), "weights underflowed to 0 (or went negative)"
    assert (np.diff(w) < 0).all(), "weights not strictly decreasing"
    assert abs(float(w.sum()) - 1.0) < 2e-5, "sum-to-1 invariant lost"
    r = np.arange(1, mu + 1, dtype=np.float64)
    ref = np.log(mu + 0.5) - np.log(r)
    ref /= ref.sum()
    assert np.max(np.abs(w - ref) / ref) < 1e-4


def test_naive_f32_weights_fail_where_stable_ones_hold():
    """The motivation pinned as a fact: at mu=5e5 the naive f32 spelling
    subtracts two ~13.8-magnitude logs whose difference is ~1e-6 — the
    f32 ulp there (~9.5e-7) is the size of the answer, so tail weights
    are quantized to a few percent relative error (and to 0/negative on
    less lucky roundings), while the log1p form stays ulp-accurate. The
    stable tail must be >100x more accurate than the naive tail."""
    mu = 500_000
    r32 = np.arange(1, mu + 1, dtype=np.float32)
    naive_raw = np.float32(np.log(np.float32(mu + 0.5))) - np.log(r32)
    ref_raw = np.log(np.float64(mu + 0.5)) - np.log(
        np.arange(1, mu + 1, dtype=np.float64)
    )
    stable_raw = np.asarray(jnp.log1p((np.float32(mu + 0.5) - r32) / r32))
    tail = slice(-1000, None)
    naive_err = np.max(
        np.abs(naive_raw[tail].astype(np.float64) - ref_raw[tail]) / ref_raw[tail]
    )
    stable_err = np.max(
        np.abs(stable_raw[tail].astype(np.float64) - ref_raw[tail]) / ref_raw[tail]
    )
    assert naive_err > 100 * stable_err, (
        f"naive tail err {naive_err:.2e} vs stable {stable_err:.2e} — if the "
        "naive form stopped degrading, the stable path may be unnecessary"
    )
    assert (np.asarray(recombination_weights(mu)) > 0).all()


def test_weights_at_ranks_matches_table():
    algo = SepCMAES(center_init=jnp.zeros(8), init_stdev=1.0, pop_size=16)
    ranks = jnp.arange(16)
    w = weights_at_ranks(algo.weights, ranks, algo.mu)
    assert jnp.array_equal(w[: algo.mu], algo.weights)
    assert jnp.array_equal(w[algo.mu :], jnp.zeros(16 - algo.mu))
    # shuffled ranks pick the same table entries
    perm = jax.random.permutation(jax.random.PRNGKey(0), 16)
    w_perm = weights_at_ranks(algo.weights, ranks[perm], algo.mu)
    assert jnp.array_equal(w_perm, w[perm])


# -------------------------------------------------- sharded == replicated


def test_sharded_trajectory_matches_replicated():
    """10 generations of sharded SepCMAES through the full StdWorkflow on
    the 8-device mesh track the replicated layout of the same sampling
    law (documented tolerance: summation-order drift only)."""
    mesh = _mesh()
    wf_sh = _sharded_wf(SepCMAES, 16, 512, mesh)
    wf_rp = _sharded_wf(SepCMAES, 16, 512, None, n_shards=N_DEV)
    s_sh = wf_sh.init(jax.random.PRNGKey(2))
    s_rp = wf_rp.init(jax.random.PRNGKey(2))
    for _ in range(10):
        s_sh = wf_sh.step(s_sh)
        s_rp = wf_rp.step(s_rp)
    assert jnp.allclose(s_sh.algo.mean, s_rp.algo.mean, rtol=1e-4, atol=1e-4)
    assert jnp.allclose(s_sh.algo.C, s_rp.algo.C, rtol=1e-4, atol=1e-4)
    assert jnp.allclose(s_sh.algo.sigma, s_rp.algo.sigma, rtol=1e-4)


def test_sharded_fused_run_matches_step_loop():
    """wf.run's fused fori_loop (shard_map inside the loop body) equals
    the eager step loop — the repo's run==step law holds for the sharded
    track."""
    mesh = _mesh()
    wf = _sharded_wf(SepCMAES, 8, 64, mesh)
    s_loop = wf.init(jax.random.PRNGKey(3))
    for _ in range(6):
        s_loop = wf.step(s_loop)
    s_run = wf.run(wf.init(jax.random.PRNGKey(3)), 6)
    for a, b in zip(jax.tree.leaves(s_loop.algo), jax.tree.leaves(s_run.algo)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


def test_sharded_wrapper_identity_without_mesh():
    """ShardedES(mesh=None, n_shards=1) is the bare algorithm bit-for-bit
    (legacy sampling stream, delegated tell)."""
    algo = RMES(center_init=jnp.full(6, 1.0), init_stdev=0.7, pop_size=16)
    wrapped = ShardedES(algo, mesh=None, n_shards=1)
    k = jax.random.PRNGKey(9)
    s1, s2 = algo.init(k), wrapped.init(k)
    p1, s1 = algo.ask(s1)
    p2, s2 = wrapped.ask(s2)
    assert jnp.array_equal(p1, p2)
    f = jnp.sum(p1**2, axis=1)
    s1, s2 = algo.tell(s1, f), wrapped.tell(s2, f)
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        assert jnp.array_equal(a, b)


def test_sharded_rejects_unsupported():
    from evox_tpu.algorithms.so.pso import PSO

    with pytest.raises(TypeError, match="protocol"):
        ShardedES(PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8))
    with pytest.raises(ValueError, match="divisible"):
        ShardedES(
            SepCMAES(center_init=jnp.zeros(4), init_stdev=1.0, pop_size=10),
            mesh=None,
            n_shards=8,
        )


# ------------------------------------------------------- gather-free memory


def _steady_compiled(wf, key=0):
    s = wf.init(jax.random.PRNGKey(key))
    # abstract state: lowering never executes or materializes the big pop
    s = jax.eval_shape(lambda st: st, s)
    return wf._step.lower(s).compile()


def _peak_bytes(compiled):
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )


@pytest.mark.slow
def test_per_device_memory_scales_as_pop_over_ndev():
    """The tentpole acceptance: per-device peak bytes of the compiled
    sharded step sit well below the full-pop z bytes (and below the
    replicated program's peak), and scale ~linearly in pop while staying
    pop/n_dev-sized. memory_analysis reports PER-DEVICE sizes for SPMD
    programs (verified: a sharded (8192,128) argument reports its
    524288-byte shard, not the 4 MB global)."""
    mesh = _mesh()
    pop, dim = 1 << 15, 64
    full_z = pop * dim * 4
    peak_sh = _peak_bytes(_steady_compiled(_sharded_wf(SepCMAES, dim, pop, mesh)))
    peak_rp = _peak_bytes(
        _steady_compiled(_sharded_wf(SepCMAES, dim, pop, None, n_shards=N_DEV))
    )
    assert peak_sh < full_z, (
        f"sharded per-device peak {peak_sh} >= full-pop z bytes {full_z}: "
        "the compiled step materializes the population on one device"
    )
    assert peak_sh * 4 < peak_rp, (
        f"sharded peak {peak_sh} not well below replicated {peak_rp}"
    )
    # doubling pop doubles the per-device shard (still pop/n_dev scaling)
    peak_sh2 = _peak_bytes(
        _steady_compiled(_sharded_wf(SepCMAES, dim, 2 * pop, mesh))
    )
    ratio = peak_sh2 / peak_sh
    assert 1.5 < ratio < 2.6, f"peak scaling with pop looks wrong: {ratio}"


def test_compiled_hlo_is_gather_free():
    """No operand/result in the compiled (post-SPMD-partitioning) HLO has
    the full (pop, dim) shape — every (pop, dim)-logical array lives as a
    (pop/n_dev, dim) shard. Fitness-sized (pop,) arrays are allowed (the
    rank computation is fitness-sized by design)."""
    mesh = _mesh()
    pop, dim = 1 << 14, 32
    txt = _steady_compiled(_sharded_wf(SepCMAES, dim, pop, mesh)).as_text()
    full = re.compile(rf"f32\[{pop},{dim}\]")
    shard = re.compile(rf"f32\[{pop // N_DEV},{dim}\]")
    assert not full.search(txt), "full (pop, dim) tensor found in sharded HLO"
    assert shard.search(txt), "expected the per-device shard shape in the HLO"


# ------------------------------------------------------ convergence at scale


@pytest.mark.slow
def test_sharded_sepcmaes_converges_sphere_pop1e5():
    """CLAUDE.md convergence-threshold rule at pop=1e5 on the 8-device
    mesh (tier-1 shape of the million-scale workload)."""
    mesh = _mesh()
    wf = _sharded_wf(SepCMAES, 16, 100_000, mesh)
    s = wf.run(wf.init(jax.random.PRNGKey(0)), 25)
    f = float(jnp.sum(s.algo.mean**2))
    assert f < 1e-3, f"sharded SepCMAES pop=1e5 did not solve Sphere: {f}"


@pytest.mark.slow
def test_sharded_lmmaes_converges_sphere_pop1e5():
    # slow-marked (ISSUE 14, the PR-2 gate-headroom discipline): tier-1
    # keeps the SepCMAES pop=1e5 convergence gate above as the
    # representative large-pop law; LMMAES's sharded bitwise contract
    # stays tier-1 via test_state_contracts::test_sharded_step_contract
    mesh = _mesh()
    wf = _sharded_wf(LMMAES, 16, 100_000, mesh)
    s = wf.run(wf.init(jax.random.PRNGKey(0)), 30)
    f = float(jnp.sum(s.algo.mean**2))
    assert f < 1e-2, f"sharded LMMAES pop=1e5 did not solve Sphere: {f}"


@pytest.mark.slow
def test_sharded_sepcmaes_converges_sphere_pop1e6():
    """The headline workload: pop=10^6 on the 8-device mesh, each device
    holding a (125000, dim) shard."""
    mesh = _mesh()
    wf = _sharded_wf(SepCMAES, 16, 1_000_000, mesh)
    s = wf.run(wf.init(jax.random.PRNGKey(0)), 25)
    f = float(jnp.sum(s.algo.mean**2))
    assert f < 1e-3, f"sharded SepCMAES pop=1e6 did not solve Sphere: {f}"


@pytest.mark.slow
def test_sharded_rmes_rosenbrock_pop1e6():
    """Rosenbrock at pop=10^6: valley-following is generation-bound, so
    the large-pop win here is STABLE progress, not a 10^6-fold speedup —
    RMES (rank-based PSR step sizes, bounded by construction) with the
    `mu` parent cap (strong truncation keeps mueff = O(10^3), the regime
    the CSA-family constants were derived for).
    Calibrated in-container: THIS config (key 1) measures f=0.436 at
    gen 40 (~11 s/gen on the 1-core 8-device mesh — hence 40 gens, not
    more); the same config at pop=1e5 reaches 0.039 by gen 80 and 2e-10
    by gen 200 from f(0)=7."""
    mesh = _mesh()
    algo = ShardedES(
        RMES(center_init=jnp.zeros(8), init_stdev=0.3, pop_size=1_000_000, mu=2048),
        mesh=mesh,
    )
    wf = StdWorkflow(algo, Rosenbrock(), mesh=mesh)
    s = wf.run(wf.init(jax.random.PRNGKey(1)), 40)
    x = s.algo.mean
    f = float(jnp.sum(100 * (x[1:] - x[:-1] ** 2) ** 2 + (1 - x[:-1]) ** 2))
    assert f < 1.0, f"sharded RMES pop=1e6 stalled on Rosenbrock: {f}"  # f(0)=7


# ----------------------------------------------------- dense guard + handoff


def test_safe_eigh_max_dim_guard():
    with pytest.raises(EighScaleError, match="max_dim"):
        safe_eigh(jnp.eye(64), max_dim=32)
    B, D = safe_eigh(jnp.eye(8), max_dim=32)  # under the limit: unchanged
    assert B.shape == (8, 8) and D.shape == (8,)


def test_cmaes_dense_scale_guards():
    with pytest.raises(EighScaleError, match="eigh_max_dim"):
        CMAES(center_init=jnp.zeros(8192), init_stdev=1.0)
    with pytest.raises(EighScaleError, match="dense_budget_elems"):
        CMAES(center_init=jnp.zeros(64), init_stdev=1.0, pop_size=3_000_000)
    # both guards are configurable escapes, not hard walls
    CMAES(
        center_init=jnp.zeros(64),
        init_stdev=1.0,
        pop_size=8,
        eigh_max_dim=None,
        dense_budget_elems=None,
    )


def test_ipop_hands_off_to_sharded_track():
    """Satellite 2 + tentpole: IPOP doubling past handoff_pop rebuilds
    from handoff_factory (the sharded low-memory track) instead of
    marching the dense CMAES into its wall, and the handoff lands in
    run_report()["guardrail"]["ipop"]."""
    mesh = _mesh()
    dim = 6

    def dense_factory(pop):
        return GuardedAlgorithm(
            CMAES(center_init=jnp.zeros(dim), init_stdev=1.0, pop_size=pop),
            stagnation_limit=None,
        )

    def sharded_factory(pop):
        return GuardedAlgorithm(
            ShardedES(
                SepCMAES(
                    center_init=jnp.zeros(dim), init_stdev=1.0, pop_size=pop
                ),
                mesh=mesh,
            )
        )

    policy = IPOPRestarts(
        dense_factory,
        max_restarts=2,
        check_every=4,
        stagnation_limit=3,  # a plateau problem triggers every boundary
        handoff_pop=32,
        handoff_factory=sharded_factory,
    )
    assert not policy.uses_handoff(16) and policy.uses_handoff(32)

    class Plateau:
        jittable = True

        def init(self, key=None):
            return None

        def evaluate(self, state, pop):
            return jnp.ones(pop.shape[0]), state

    wf = StdWorkflow(dense_factory(16), Plateau(), mesh=mesh)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 16, restarts=policy)
    events = wf._ipop_events
    assert [e["pop_size"] for e in events] == [32, 64]
    assert [e["handoff"] for e in events] == [True, True]
    assert events[0]["algorithm"] == "ShardedES"
    # the doubled state is the sharded track's (SepCMAESState has C as a
    # DIAGONAL, no B)
    assert not hasattr(state.algo.inner, "B")
    assert int(state.algo.pop_size) == 64
    report = run_report(wf, state)
    assert report["guardrail"]["ipop"] == events
    assert report["guardrail"]["algorithm"] == "CMAES"  # caller's wf object
    # the validator accepts the v5 report with the ipop section
    assert check_report.validate_run_report(report) == []


# ------------------------------------------------------------- composition


def test_sharded_with_guardrail_bf16_and_donation():
    """ShardedES composes with GuardedAlgorithm, bf16 storage and the
    donated fused run: the stack converges and the z artifact rests at
    storage width between generations."""
    mesh = _mesh()
    algo = GuardedAlgorithm(
        ShardedES(
            SepCMAES(center_init=jnp.full(16, 1.5), init_stdev=1.0, pop_size=64),
            mesh=mesh,
        )
    )
    wf = StdWorkflow(
        algo, Sphere(), mesh=mesh, dtype_policy=BF16_STORAGE, donate_carries=True
    )
    s = wf.init(jax.random.PRNGKey(4))
    assert s.algo.inner.z.dtype == jnp.bfloat16  # storage annotation active
    s = wf.run(s, 40)
    assert s.algo.inner.z.dtype == jnp.bfloat16
    assert float(s.algo.best_fitness) < 1e-2


def test_sharded_on_tenant_pop_2d_mesh():
    """The (TENANT, POP) 2-D mesh of PR 7 composes: ShardedES shards pop
    over the 'pop' sub-axis (specs name only that axis; tenant rows
    replicate) and matches the 1-D replicated law."""
    mesh2d = create_mesh((TENANT_AXIS, POP_AXIS), shape=(2, 4))
    wf_2d = _sharded_wf(SepCMAES, 8, 64, mesh2d, n_shards=4)
    wf_rp = _sharded_wf(SepCMAES, 8, 64, None, n_shards=4)
    s2, sr = wf_2d.init(jax.random.PRNGKey(6)), wf_rp.init(jax.random.PRNGKey(6))
    for _ in range(4):
        s2, sr = wf_2d.step(s2), wf_rp.step(sr)
    assert jnp.allclose(s2.algo.mean, sr.algo.mean, rtol=1e-4, atol=1e-4)
    assert jnp.allclose(s2.algo.C, sr.algo.C, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_sharded_custom_axis_name():
    """A mesh whose pop axis is named differently: the annotations'
    canonical POP_AXIS is renamed to the wrapper's axis_name in init
    (eager placement AND the traced GuardedAlgorithm-restart path), ask
    and tell alike — regression for two review findings where only ask
    or only tell handled the rename."""
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(8), ("shard",))
    algo = SepCMAES(center_init=jnp.full(8, 1.0), init_stdev=0.5, pop_size=64)
    sh = ShardedES(algo, mesh=mesh, axis_name="shard")
    rp = ShardedES(algo, mesh=None, n_shards=8)
    k = jax.random.PRNGKey(0)
    s1, s2 = sh.init(k), rp.init(k)
    for _ in range(3):
        p1, s1 = sh.ask(s1)
        p2, s2 = rp.ask(s2)
        s1 = sh.tell(s1, jnp.sum(p1**2, axis=1))
        s2 = rp.tell(s2, jnp.sum(p2**2, axis=1))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5)
    # the traced restart path (lax.cond inside a jitted tell) compiles
    g = GuardedAlgorithm(ShardedES(algo, mesh=mesh, axis_name="shard"))
    gs = g.init(jax.random.PRNGKey(1))
    p, gs = g.ask(gs)
    jax.jit(g.tell)(gs, jnp.sum(p**2, axis=1))


@pytest.mark.slow
def test_run_report_sharding_section(ceilings):
    """The v5 roofline.sharding subsection: per-device peak < full-pop
    bytes for an instrumented sharded run, and the schema validator
    accepts the whole report."""
    mesh = _mesh()
    wf = _sharded_wf(SepCMAES, 64, 1 << 14, mesh)
    rec = instrument(wf, block_dispatch=True)
    s = wf.init(jax.random.PRNGKey(7))
    s = wf.run(s, 3)
    s = wf.run(s, 3)
    s = wf.run(s, 12)
    rec.fetch(s.algo.sigma, name="sigma")
    report = run_report(
        wf, s, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    assert report["schema"] == "evox_tpu.run_report/v14"
    assert report["schema_version"] == 14
    shd = report["roofline"]["sharding"]
    assert shd["axis"] == POP_AXIS and shd["n_devices"] == N_DEV
    assert shd["gather_free"] is True
    assert shd["per_device_peak_bytes"] < shd["full_pop_bytes"]
    assert check_report.validate_run_report(report) == []

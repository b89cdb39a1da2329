"""Surrogate-assisted evolution (ISSUE 15): operators/surrogate.py +
workflows/surrogate.py laws.

The laws, in the repo's acceptance order:

- archive ring discipline (masked scatter append, overwrite, fill);
- model sanity: the GP and the ensemble both ORDER unseen Sphere
  candidates correctly after fitting, and their uncertainty grows away
  from the data (the fallback predicates' signal);
- vmap contract: stacked archives/models fit+predict under ``jax.vmap``
  — the mechanical guarantee behind VectorizedWorkflow fleet
  composition (the test_state_contracts.py idiom);
- disabled ≡ bare BITWISE: ``surrogate=None`` and ``screen_frac=1.0``
  reproduce the bare StdWorkflow leaf-for-leaf across a step loop, the
  fused ``run`` on the 8-device mesh, and the pipelined host driver;
- the ROADMAP item 5 bar: ≥5x fewer TRUE evaluations to the Sphere
  threshold than full evaluation (also the CLAUDE.md-mandated
  convergence-threshold test for the SO path);
- lying-surrogate chaos: systematically wrong predictions trip the
  rank-correlation fallback and the run still converges (fallback ==
  full evaluation, never a corrupted search);
- checkpoint/resume mid-refit equivalence, quarantine composition, the
  supervisor retry ladder, and the host-rows == ledger law;
- run_report v10 ``surrogate`` section validated by tools/check_report,
  telemetry mirror counters, executor ``bg_refit`` accounting.
"""

import pathlib
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    GenerationExecutor,
    StdWorkflow,
    SurrogateWorkflow,
    WorkflowCheckpointer,
    create_mesh,
    instrument,
    run_report,
)
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.operators.surrogate import (
    EnsembleSurrogate,
    GPCapacityError,
    GPSurrogate,
    SurrogateArchive,
    spearman_correlation,
)
from evox_tpu.problems.numerical import Sphere
from evox_tpu.workflows.surrogate import (
    FALLBACK_RANK,
    FALLBACK_UNCERTAINTY,
    masked_worst_finite_fill,
)

from tests._chaos import LyingSurrogate

REPO = pathlib.Path(__file__).resolve().parent.parent

DIM = 8
POP = 64


def _pso(pop=POP, dim=DIM):
    return PSO(lb=-5.0 * jnp.ones(dim), ub=5.0 * jnp.ones(dim), pop_size=pop)


class HostSphere:
    """Minimal external (host) Sphere that counts the TRUE rows it was
    asked to score — the independent referee for the eval ledger."""

    jittable = False
    fit_dtype = "float32"

    def __init__(self):
        self.rows = 0
        self.calls = 0

    def init(self, key=None):
        return None

    def fit_shape(self, n):
        return (n,)

    def evaluate(self, state, pop):
        pop = np.asarray(pop)
        self.calls += 1
        self.rows += pop.shape[0]
        return np.sum(pop**2, axis=1).astype(np.float32), state


def _leaves_equal(a, b, where=""):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb), f"{where}: leaf count {len(fa)} != {len(fb)}"
    for (p, x), (_, y) in zip(fa, fb):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True), (
            f"{where}{jax.tree_util.keystr(p)} differs"
        )


def _best(wf, state):
    return float(wf.monitors[0].get_best_fitness(state.monitors[0]))


# ---------------------------------------------------------------- operators


def test_archive_ring_law():
    arc = SurrogateArchive(8)
    st = arc.init(2)
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    y = jnp.arange(6, dtype=jnp.float32)
    mask = jnp.array([True, False, True, True, False, True])
    st = arc.update(st, x, y, mask)
    # only masked rows landed, in order, starting at slot 0
    assert int(arc.fill(st)) == 4
    np.testing.assert_array_equal(np.asarray(st.y[:4]), [0.0, 2.0, 3.0, 5.0])
    np.testing.assert_array_equal(np.asarray(st.x[1]), [4.0, 5.0])
    assert bool(jnp.all(jnp.isinf(st.y[4:])))
    # second write wraps: 6 more accepted rows overwrite the oldest
    st = arc.update(st, x + 100.0, y + 100.0, jnp.ones(6, bool))
    assert int(arc.fill(st)) == 8 and int(st.count) == 10
    # slots 4..7 then 0..1 got the new rows (ring semantics)
    np.testing.assert_array_equal(
        np.asarray(st.y[4:8]), [100.0, 101.0, 102.0, 103.0]
    )
    np.testing.assert_array_equal(np.asarray(st.y[0:2]), [104.0, 105.0])
    # a batch wider than the ring refuses loudly (scatter self-collision)
    with pytest.raises(ValueError, match="capacity"):
        arc.update(st, jnp.zeros((9, 2)), jnp.zeros(9), jnp.ones(9, bool))


def test_spearman_properties():
    a = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    assert float(spearman_correlation(a, a)) == pytest.approx(1.0)
    assert float(spearman_correlation(a, -a)) == pytest.approx(-1.0)
    # monotone transform preserves rank correlation exactly
    assert float(spearman_correlation(a, jnp.exp(a))) == pytest.approx(1.0)
    # mask excludes rows: the outlier in a masked row cannot perturb it
    b = a.at[4].set(-1e9)
    m = jnp.array([True, True, True, True, False])
    assert float(spearman_correlation(a, b, m)) == pytest.approx(1.0)
    # under 3 valid rows: neutral 1.0 (the warmup gate owns that regime)
    assert float(
        spearman_correlation(a, -a, jnp.array([True, True, False, False, False]))
    ) == pytest.approx(1.0)


@pytest.mark.parametrize("kind", ["gp", "ensemble"])
def test_model_orders_unseen_candidates(kind):
    model_op = (
        GPSurrogate()
        if kind == "gp"
        else EnsembleSurrogate(n_members=3, hidden=16, fit_steps=80)
    )
    cap, dim = 64, 4
    X = jax.random.normal(jax.random.PRNGKey(0), (cap, dim))
    Y = jnp.sum(X**2, axis=1)
    model = model_op.init_model(cap, dim)
    model = model_op.fit(model, X, Y, jnp.ones(cap, bool), jax.random.PRNGKey(1))
    Xt = jax.random.normal(jax.random.PRNGKey(2), (32, dim))
    mean, unc = model_op.predict(model, Xt)
    corr = float(spearman_correlation(mean, jnp.sum(Xt**2, axis=1)))
    assert corr > 0.7, f"{kind} failed to order unseen Sphere points: {corr}"
    # uncertainty grows away from the data (the fallback signal)
    far = 25.0 * jax.random.normal(jax.random.PRNGKey(3), (32, dim))
    _, unc_far = model_op.predict(model, far)
    assert float(jnp.mean(unc_far)) > 2.0 * float(jnp.mean(unc))
    # a masked (partially filled) fit must ignore the poisoned tail
    Y_poison = Y.at[cap // 2 :].set(jnp.nan)
    mask = jnp.arange(cap) < cap // 2
    model2 = model_op.init_model(cap, dim)
    model2 = model_op.fit(model2, X, Y_poison, mask, jax.random.PRNGKey(4))
    mean2, _ = model_op.predict(model2, Xt)
    assert bool(jnp.all(jnp.isfinite(mean2)))


def test_degenerate_screen_frac_refused():
    """A screen_frac whose ceil rounds back up to the full batch screens
    NOTHING while paying the surrogate cost forever — refused loudly at
    construction instead of running inert (review finding, ISSUE 15)."""
    with pytest.raises(ValueError, match="screens nothing"):
        SurrogateWorkflow(
            _pso(pop=8, dim=4),
            Sphere(),
            surrogate=GPSurrogate(),
            screen_frac=0.9,  # ceil(0.9 * 8) == 8 == the full batch
        )


def test_gp_capacity_guard():
    with pytest.raises(GPCapacityError, match="EnsembleSurrogate"):
        GPSurrogate(max_capacity=128).check_capacity(256)
    # and through the workflow constructor (the dense-scale discipline)
    with pytest.raises(GPCapacityError):
        SurrogateWorkflow(
            _pso(pop=16, dim=4),
            Sphere(),
            surrogate=GPSurrogate(max_capacity=32),
            screen_frac=0.25,
            archive_capacity=64,
        )


@pytest.mark.parametrize("kind", ["gp", "ensemble"])
def test_models_vmap_contract(kind):
    """Stacked fit+predict under vmap — the mechanical guarantee that a
    VectorizedWorkflow-style fleet can carry per-tenant surrogates (the
    test_state_contracts vmap-contract idiom)."""
    model_op = (
        GPSurrogate()
        if kind == "gp"
        else EnsembleSurrogate(n_members=2, hidden=8, fit_steps=20)
    )
    cap, dim, n_tenants = 16, 3, 2
    arc = SurrogateArchive(cap)

    def run_one(key):
        X = jax.random.normal(key, (cap, dim))
        Y = jnp.sum(X**2, axis=1)
        st = arc.update(arc.init(dim), X, Y, jnp.ones(cap, bool))
        model = model_op.init_model(cap, dim)
        model = model_op.fit(model, st.x, st.y, arc.valid_mask(st), key)
        return model_op.predict(model, X)

    keys = jax.random.split(jax.random.PRNGKey(9), n_tenants)
    stacked_mean, stacked_unc = jax.jit(jax.vmap(run_one))(keys)
    solo_mean, solo_unc = run_one(keys[0])
    assert stacked_mean.shape == (n_tenants,) + solo_mean.shape
    np.testing.assert_allclose(
        np.asarray(stacked_mean[0]), np.asarray(solo_mean), rtol=1e-4, atol=1e-4
    )


def test_masked_worst_finite_fill():
    fit = jnp.asarray([3.0, 1.0, jnp.nan, 7.0, 9.0])
    mask = jnp.array([True, True, True, False, False])
    out = masked_worst_finite_fill(fit, mask)
    # unevaluated rows get the worst FINITE evaluated value (3.0);
    # the evaluated NaN stays visible (telemetry/quarantine semantics)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray([3.0, 1.0, np.nan, 3.0, 3.0])
    )


# ------------------------------------------------------- disabled ≡ bare


def test_disabled_bitwise_step_and_fused_run_on_mesh():
    """surrogate=None AND screen_frac=1.0 are BIT-identical to the bare
    workflow across an eager step loop and the fused run on the 8-device
    mesh — asserted leaf-for-leaf, not assumed."""
    mesh = create_mesh()
    for label, make_dis in (
        ("none", lambda: SurrogateWorkflow(_pso(), Sphere(), surrogate=None, mesh=mesh)),
        (
            "frac1",
            lambda: SurrogateWorkflow(
                _pso(), Sphere(), surrogate=GPSurrogate(), screen_frac=1.0, mesh=mesh
            ),
        ),
    ):
        bare = StdWorkflow(_pso(), Sphere(), mesh=mesh)
        dis = make_dis()
        sb = bare.init(jax.random.PRNGKey(0))
        sd = dis.init(jax.random.PRNGKey(0))
        assert sd.sur is None  # disabled materializes NO surrogate state
        # step loop
        sb_s, sd_s = sb, sd
        for _ in range(3):
            sb_s, sd_s = bare.step(sb_s), dis.step(sd_s)
        _leaves_equal(
            (sb_s.generation, sb_s.algo, sb_s.prob),
            (sd_s.generation, sd_s.algo, sd_s.prob),
            where=f"step[{label}]",
        )
        # fused run
        sb_r, sd_r = bare.run(sb, 5), dis.run(sd, 5)
        _leaves_equal(
            (sb_r.generation, sb_r.algo, sb_r.prob),
            (sd_r.generation, sd_r.algo, sd_r.prob),
            where=f"run[{label}]",
        )


@pytest.mark.slow
def test_disabled_bitwise_pipelined():
    """The third driver of the acceptance criterion: the pipelined host
    path (executor-driven) is bitwise too, monitors included."""
    bare = StdWorkflow(
        _pso(pop=16, dim=4), HostSphere(), monitors=(TelemetryMonitor(capacity=8),)
    )
    dis = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        HostSphere(),
        surrogate=GPSurrogate(),
        screen_frac=1.0,
        monitors=(TelemetryMonitor(capacity=8),),
    )
    sb = bare.init(jax.random.PRNGKey(3))
    sd = dis.init(jax.random.PRNGKey(3))
    sb = bare.run(sb, 5)
    sd = dis.run(sd, 5)
    _leaves_equal(
        (sb.generation, sb.algo, sb.prob, sb.monitors),
        (sd.generation, sd.algo, sd.prob, sd.monitors),
        where="pipelined",
    )
    # and the telemetry fingerprints agree bit for bit
    assert bare.monitors[0].fingerprint(sb.monitors[0]) == dis.monitors[
        0
    ].fingerprint(sd.monitors[0])


@pytest.mark.slow
def test_enabled_run_equals_step_on_mesh():
    """The ENABLED path honors the repo's run==step law too: the fused
    fori_loop trace of the screening step is bitwise the eager step
    loop on the 8-device mesh (screening, archive scatter, cond-refit
    and fallback bookkeeping included)."""
    mesh = create_mesh()
    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        warmup=16,
        refit_every=2,
        mesh=mesh,
    )
    s0 = wf.init(jax.random.PRNGKey(0))
    stepped = s0
    for _ in range(6):
        stepped = wf.step(stepped)
    fused = wf.run(s0, 6)
    _leaves_equal(stepped, fused, where="run==step")


def test_bf16_storage_composition():
    """The archive is bf16-storage-compatible (ISSUE 15): under
    BF16_STORAGE the candidate buffer rests bf16 between generations
    while fitness (and the GP's factorization products) stay f32, and
    the screened run still works end to end."""
    from evox_tpu import BF16_STORAGE

    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        warmup=16,
        refit_every=1,
        dtype_policy=BF16_STORAGE,
    )
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 6)
    assert state.sur.archive.x.dtype == jnp.bfloat16
    assert state.sur.archive.y.dtype == jnp.float32
    assert state.sur.model.chol.dtype == jnp.float32
    assert int(state.sur.true_evals) < 6 * 16


# ------------------------------------------------- the ROADMAP item 5 bar


def _run_to_threshold(wf, key, threshold=1e-2, max_gens=120, chunk=2):
    state = wf.init(key)
    gens = 0
    while gens < max_gens:
        state = wf.run(state, chunk)
        gens += chunk
        if _best(wf, state) < threshold:
            break
    sur = getattr(state, "sur", None)
    true_evals = (
        int(sur.true_evals) if sur is not None else gens * wf.algorithm.pop_size
    )
    return state, gens, true_evals


def test_screening_5x_fewer_true_evals_to_sphere_threshold():
    """The acceptance bar (ROADMAP item 5 / ISSUE 15): >= 5x fewer TRUE
    evaluations to the Sphere convergence threshold than full
    evaluation — and the screened run still CONVERGES, which is the
    CLAUDE.md-mandated convergence-threshold test for the SO path.
    Ledger-audited, not wall-clock: the surrogate's own device counters
    are cross-checked by the problem in test_host_rows_match_ledger."""
    pop = 128
    threshold = 1e-2
    full = StdWorkflow(
        _pso(pop=pop), Sphere(), monitors=(TelemetryMonitor(capacity=4),)
    )
    s_full, _, evals_full = _run_to_threshold(
        full, jax.random.PRNGKey(3), threshold
    )
    assert _best(full, s_full) < threshold
    scr = SurrogateWorkflow(
        _pso(pop=pop),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.125,
        warmup=pop,
        refit_every=1,
        rank_floor=0.3,
        monitors=(TelemetryMonitor(capacity=4),),
    )
    s_scr, _, evals_scr = _run_to_threshold(scr, jax.random.PRNGKey(3), threshold)
    assert _best(scr, s_scr) < threshold, "screened run must still converge"
    ratio = evals_full / max(evals_scr, 1)
    assert ratio >= 5.0, (
        f"true-eval ratio {ratio:.2f} below the 5x bar "
        f"(full {evals_full}, screened {evals_scr})"
    )
    # the ledger is coherent on its own terms
    sur = s_scr.sur
    assert int(sur.true_evals) + int(sur.screened_out) == int(
        sur.candidates_seen
    )
    assert (
        int(sur.screened_gens) + int(sur.fallback_gens) + int(sur.warmup_gens)
        == int(sur.generations)
    )


def test_host_rows_match_ledger():
    """The host problem's own row count equals the device ledger — the
    screened rows truly never reached the expensive evaluate."""
    prob = HostSphere()
    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        prob,
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        warmup=16,
        refit_every=2,
    )
    state = wf.init(jax.random.PRNGKey(2))
    state = wf.run(state, 8)
    assert prob.rows == int(state.sur.true_evals)
    assert prob.rows < 8 * 16  # strictly fewer than full evaluation


# ------------------------------------------------------------ chaos laws


def test_lying_surrogate_trips_fallback_and_still_converges():
    """A systematically wrong surrogate (negated predictions) trips the
    rank-correlation fallback — and because fallback IS full
    evaluation, the guarded run still reaches the Sphere threshold."""
    liar = LyingSurrogate(GPSurrogate())
    wf = SurrogateWorkflow(
        _pso(),
        Sphere(),
        surrogate=liar,
        screen_frac=0.125,
        warmup=POP,
        refit_every=1,
        rank_floor=0.3,
        monitors=(TelemetryMonitor(capacity=4),),
    )
    state, gens, true_evals = _run_to_threshold(
        wf, jax.random.PRNGKey(1), threshold=1e-2, max_gens=160
    )
    assert _best(wf, state) < 1e-2, "lying surrogate must not break the run"
    sur = state.sur
    assert int(sur.fallback_gens) >= 1, "the lie must trip the fallback"
    rep = wf.surrogate_report(state)
    events = rep["fallback_events"]
    assert events, "fallback events must be recorded"
    assert all(ev["reason"] & FALLBACK_RANK for ev in events)
    gens_seq = [ev["generation"] for ev in events]
    assert gens_seq == sorted(gens_seq)  # chunk/chronological order
    # with the surrogate permanently lying, nearly every warm generation
    # fully evaluates: the ledger must show fallback dominating
    assert int(sur.fallback_gens) >= int(sur.screened_gens)


def test_uncertainty_ceiling_trips_immediate_fallback():
    """The second health predicate: a tiny unc_ceiling makes the very
    first post-warmup generation fall back (reason bit 2), without
    waiting for a rank-correlation reading."""
    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        warmup=16,
        refit_every=1,
        unc_ceiling=1e-12,
    )
    state = wf.init(jax.random.PRNGKey(0))
    for _ in range(4):
        state = wf.step(state)
    sur = state.sur
    assert int(sur.fallback_gens) >= 1
    assert int(sur.screened_gens) == 0  # never trusted the surrogate
    rep = wf.surrogate_report(state)
    assert any(
        ev["reason"] & FALLBACK_UNCERTAINTY for ev in rep["fallback_events"]
    )


def test_quarantine_composition():
    """A poison (NaN) true fitness row composes: quarantine keeps the
    tell sane and the archive refuses the poisoned pair."""

    class PoisonSphere:
        jittable = True
        fit_dtype = "float32"

        def init(self, key=None):
            return None

        def fit_shape(self, n):
            return (n,)

        def evaluate(self, state, pop):
            fit = jnp.sum(pop**2, axis=1)
            return fit.at[0].set(jnp.nan), state

    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        PoisonSphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        warmup=16,
        refit_every=1,
        quarantine_nonfinite=True,
        monitors=(TelemetryMonitor(capacity=4),),
    )
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 6)
    # the archive only ever ingests finite pairs
    fill = int(wf._archive.fill(state.sur.archive))
    assert fill > 0
    assert bool(jnp.all(jnp.isfinite(state.sur.archive.y[:fill])))
    # telemetry still SAW the raw poison (quarantine visibility law)
    assert int(state.monitors[0].nan_fitness) > 0
    # and the algorithm state stayed finite
    assert bool(
        jnp.all(jnp.isfinite(state.algo.population))
    )


def test_checkpoint_resume_mid_refit_equivalence():
    """Crash-and-resume between refits reproduces the straight run bit
    for bit: the refit schedule is pure in the absolute generation and
    every snapshot embeds the refit that preceded it (refit_every=3
    deliberately misaligned with the checkpoint cadence of 2)."""

    def mkwf():
        return SurrogateWorkflow(
            _pso(pop=16, dim=4),
            HostSphere(),
            surrogate=GPSurrogate(),
            screen_frac=0.25,
            warmup=16,
            refit_every=3,
            monitors=(TelemetryMonitor(capacity=8),),
        )

    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        wf_a = mkwf()
        s_a = wf_a.init(jax.random.PRNGKey(4))
        s_a = wf_a.run(s_a, 10, checkpointer=WorkflowCheckpointer(d1, every=2))
        # "crash" after 7 generations (mid refit window), resume to 10
        wf_b = mkwf()
        s_b = wf_b.init(jax.random.PRNGKey(4))
        wf_b.run(s_b, 7, checkpointer=WorkflowCheckpointer(d2, every=2))
        wf_c = mkwf()  # a FRESH process resumes from the snapshot
        s_c = wf_c.resume(WorkflowCheckpointer(d2, every=2), 10)
        _leaves_equal(s_a, s_c, where="resume")


def test_supervisor_retry_heals_screened_run():
    """Supervisor chaos-healing composition: one transient dispatch
    fault inside the screened host loop retries to a final state
    fingerprint-identical to the clean run."""
    from evox_tpu.workflows.supervisor import RunSupervisor

    class FlakyHostSphere(HostSphere):
        def __init__(self, fail_at):
            super().__init__()
            self.fail_at = fail_at

        def evaluate(self, state, pop):
            if self.calls == self.fail_at:
                self.calls += 1
                raise RuntimeError("UNAVAILABLE: connection reset by peer")
            return super().evaluate(state, pop)

    def run(prob):
        wf = SurrogateWorkflow(
            _pso(pop=16, dim=4),
            prob,
            surrogate=GPSurrogate(),
            screen_frac=0.25,
            warmup=16,
            refit_every=2,
            monitors=(TelemetryMonitor(capacity=8),),
        )
        state = wf.init(jax.random.PRNGKey(6))
        sup = RunSupervisor(max_retries=2, backoff_s=0.01)
        state = sup.run_host_pipelined(wf, state, 6, chunk=2)
        return wf, state, sup

    wf_clean, s_clean, _ = run(HostSphere())
    wf_flaky, s_flaky, sup = run(FlakyHostSphere(fail_at=4))
    assert sup.counters["retries"] >= 1
    assert wf_clean.monitors[0].fingerprint(
        s_clean.monitors[0]
    ) == wf_flaky.monitors[0].fingerprint(s_flaky.monitors[0])
    _leaves_equal(s_clean.algo, s_flaky.algo, where="supervised")


# ------------------------------------------------------------- reporting


def test_run_report_surrogate_section_and_validator():
    """run_report carries the v10 surrogate section; tools/check_report
    validates it; telemetry mirrors the true-eval counters; the
    executor counts the dispatched refits."""
    prob = HostSphere()
    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        prob,
        surrogate=EnsembleSurrogate(n_members=2, hidden=8, fit_steps=20),
        screen_frac=0.25,
        warmup=16,
        refit_every=2,
        monitors=(TelemetryMonitor(capacity=8),),
    )
    rec = instrument(wf)
    ex = GenerationExecutor()
    state = wf.init(jax.random.PRNGKey(7))
    state = ex.run_host(wf, state, 6)
    report = run_report(wf, state, recorder=rec, executor=ex)
    assert report["schema"] == "evox_tpu.run_report/v14"
    assert report["schema_version"] == 14
    sur = report["surrogate"]
    assert sur["enabled"] is True and sur["model"] == "ensemble"
    c = sur["counters"]
    assert c["true_evals"] + c["screened_out"] == c["candidates_seen"]
    assert (
        c["screened_gens"] + c["fallback_gens"] + c["warmup_gens"]
        == c["generations"]
    )
    assert sur["archive"]["fill"] <= sur["archive"]["capacity"]
    assert report["executor"]["counters"]["bg_refit"] == sur["refit"]["count"]
    # telemetry mirror: the true spend is visible without the sur state
    tel = report["telemetry"][0]
    assert tel["sur_true_evals"] == c["true_evals"]
    assert tel["sur_fallback_gens"] == c["fallback_gens"]
    # the machine referee
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_report

        errors = check_report.validate_run_report(report)
    finally:
        sys.path.pop(0)
    assert errors == [], errors
    # disabled workflows report a minimal, still-valid section
    wf_dis = SurrogateWorkflow(_pso(pop=16, dim=4), Sphere(), surrogate=None)
    s_dis = wf_dis.init(jax.random.PRNGKey(0))
    rep_dis = run_report(wf_dis, s_dis)
    assert rep_dis["surrogate"]["enabled"] is False
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_report as cr

        assert cr.validate_run_report(rep_dis) == []
    finally:
        sys.path.pop(0)


# ------------------------------------------------------- state contracts


def test_surrogate_state_is_checkpoint_stable():
    """State structure (and therefore the checkpoint config fingerprint)
    is identical between a fresh init and a mid-run state — the
    resume-guard precondition the lazy-buffer pattern would break."""
    from evox_tpu.workflows.checkpoint import state_config_fingerprint

    wf = SurrogateWorkflow(
        _pso(pop=16, dim=4),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.25,
        monitors=(TelemetryMonitor(capacity=4),),
    )
    s0 = wf.init(jax.random.PRNGKey(0))
    s5 = wf.run(s0, 5)
    assert state_config_fingerprint(s0) == state_config_fingerprint(s5)

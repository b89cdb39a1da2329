"""Mixed-precision storage policy + buffer donation (PR 6 tentpole).

Three contracts:

1. **Default-path bit identity**: with ``dtype_policy=None`` and
   ``donate_carries=False`` (the defaults), CMAES / CSO / NSGA-II give
   the same bits from the step loop, from the fused run, and from a
   workflow built with the no-op ``DtypePolicy()``. The three are
   compared in one process: a digest of one jax build's PRNG and fusion
   cannot tell a regression from a compiler, a law between paths can.
2. **bf16 storage mode**: storage-annotated leaves rest in bf16, math
   runs f32, and the mode passes the CLAUDE.md convergence-threshold
   gate per algorithm (Sphere thresholds for CMAES/CSO, IGD for
   NSGA-II).
3. **Donation**: the donated fused-run carry shows up as XLA aliasing
   (``memory_analysis().alias_size_in_bytes`` > 0, surfaced in
   ``run_report()["roofline"]["donation"]``), never invalidates
   caller-owned states (snapshot-before-donate), and the supervisor /
   checkpoint healing laws hold through the donated path.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import CostAnalyzer, StdWorkflow, instrument, run_report
from evox_tpu.algorithms.mo import NSGA2
from evox_tpu.algorithms.so.es import CMAES
from evox_tpu.algorithms.so.pso import CSO, PSO
from evox_tpu.core.dtype_policy import (
    BF16_STORAGE,
    DtypePolicy,
    apply_compute,
    apply_storage,
    policy_report,
    storage_eligible_fields,
)
from evox_tpu.metrics import igd
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.numerical import DTLZ2, Sphere, ZDT1
from evox_tpu.workflows.checkpoint import WorkflowCheckpointer
from evox_tpu.workflows.supervisor import RunSupervisor

from tests._chaos import FlakyDispatch


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _wf_cmaes(**kw):
    return StdWorkflow(
        CMAES(center_init=jnp.full(6, 1.5), init_stdev=1.0, pop_size=8),
        Sphere(),
        **kw,
    )


def _wf_cso(**kw):
    return StdWorkflow(
        CSO(lb=-2.0 * jnp.ones(5), ub=2.0 * jnp.ones(5), pop_size=8),
        Sphere(),
        **kw,
    )


def _wf_nsga2(**kw):
    return StdWorkflow(
        NSGA2(lb=jnp.zeros(8), ub=jnp.ones(8), n_objs=3, pop_size=8),
        DTLZ2(d=8, m=3),
        **kw,
    )


@pytest.mark.parametrize(
    "build,seed",
    [(_wf_cmaes, 3), (_wf_cso, 7), (_wf_nsga2, 11)],
    ids=["cmaes", "cso", "nsga2"],
)
def test_default_path_bit_identical_to_pre_pr(build, seed):
    """Acceptance: the default f32 path (no policy, no donation) is one
    program however it is entered: the step loop, the fused run and a
    workflow handed the no-op ``DtypePolicy()`` agree bit for bit."""
    wf = build()
    s = wf.init(jax.random.PRNGKey(seed))
    for _ in range(4):
        s = wf.step(s)
    stepped = _digest(s.algo)
    s2 = wf.run(wf.init(jax.random.PRNGKey(seed)), 4)
    assert _digest(s2.algo) == stepped, "fused run differs from the step loop"
    noop = build(dtype_policy=DtypePolicy())
    s3 = noop.run(noop.init(jax.random.PRNGKey(seed)), 4)
    assert _digest(s3.algo) == stepped, "the no-op policy changed the bits"


# ----------------------------------------------------------- policy basics


def test_policy_validation_and_noop_identity():
    with pytest.raises(ValueError, match="floating"):
        DtypePolicy(storage=jnp.int32)
    noop = DtypePolicy()
    assert noop.is_noop and not BF16_STORAGE.is_noop
    wf = _wf_cso()
    state = wf.init(jax.random.PRNGKey(0))
    # None and no-op policies return the SAME object — zero trace impact
    assert apply_storage(state, None) is state
    assert apply_compute(state, noop) is state


def test_storage_annotations_resolve_and_cast():
    wf = _wf_cso(dtype_policy=BF16_STORAGE)
    state = wf.init(jax.random.PRNGKey(0))
    eligible = storage_eligible_fields(state.algo)
    assert eligible.get("population") and eligible.get("fitness")
    # at rest: annotated float leaves bf16; keys/ints untouched
    assert state.algo.population.dtype == jnp.bfloat16
    assert state.algo.fitness.dtype == jnp.bfloat16
    assert state.algo.key.dtype == jnp.uint32
    # upcast view restores compute dtype without touching keys
    up = apply_compute(state, BF16_STORAGE)
    assert up.algo.population.dtype == jnp.float32
    assert up.algo.key.dtype == jnp.uint32
    # report shape (consumed by run_report / check_report)
    assert policy_report(wf) == {
        "storage": "bfloat16",
        "compute": "float32",
        "active": True,
    }
    assert policy_report(_wf_cso()) == {
        "storage": "float32",
        "compute": "float32",
        "active": False,
    }


def test_bf16_state_stays_bf16_across_step_and_run():
    """The loop carry is type-stable: storage dtype at every boundary,
    for step loops and fused runs alike (no silent retraces)."""
    wf = _wf_cso(dtype_policy=BF16_STORAGE)
    s = wf.init(jax.random.PRNGKey(1))
    for _ in range(3):
        s = wf.step(s)
        assert s.algo.population.dtype == jnp.bfloat16
    s = wf.run(s, 5)
    assert s.algo.population.dtype == jnp.bfloat16
    assert s.algo.velocity.dtype == jnp.bfloat16


def test_cmaes_strategy_params_stay_f32_under_bf16():
    """The must-stay-f32 contract: CMA's mean/covariance/paths (the eigh
    and rank-mu inputs) are replicated, unannotated, and keep f32 even
    under the bf16 policy — only per-individual leaves narrow."""
    wf = _wf_cmaes(dtype_policy=BF16_STORAGE)
    s = wf.run(wf.init(jax.random.PRNGKey(2)), 5)
    a = s.algo
    assert a.mean.dtype == jnp.float32
    assert a.C.dtype == jnp.float32
    assert a.B.dtype == jnp.float32
    assert a.pc.dtype == jnp.float32 and a.ps.dtype == jnp.float32
    assert a.sigma.dtype == jnp.float32
    assert a.z.dtype == jnp.bfloat16  # per-individual: storage width


# ---------------------------------------------- bf16 convergence thresholds
# CLAUDE.md: new modes need convergence-threshold tests, not smoke tests.
# Thresholds match the existing f32 suites (test_so_es / test_mo_algorithms).


def _best_after(wf, steps, seed=17):
    state = wf.init(jax.random.PRNGKey(seed))
    state = wf.run(state, steps)
    mon = wf.monitors[0]
    return float(mon.get_best_fitness(state.monitors[0]))


def test_bf16_cmaes_sphere_convergence():
    wf = StdWorkflow(
        CMAES(center_init=jnp.full(5, -3.0), init_stdev=1.0, pop_size=32),
        Sphere(),
        monitors=(EvalMonitor(),),
        dtype_policy=BF16_STORAGE,
    )
    assert _best_after(wf, 200) < 0.01


def test_bf16_cso_sphere_convergence():
    wf = StdWorkflow(
        CSO(lb=-5.0 * jnp.ones(10), ub=5.0 * jnp.ones(10), pop_size=64),
        Sphere(),
        monitors=(EvalMonitor(),),
        dtype_policy=BF16_STORAGE,
    )
    assert _best_after(wf, 200) < 0.1


def test_bf16_nsga2_zdt1_igd():
    d = 12
    wf = StdWorkflow(
        NSGA2(jnp.zeros(d), jnp.ones(d), n_objs=2, pop_size=100),
        ZDT1(n_dim=d),
        dtype_policy=BF16_STORAGE,
    )
    state = wf.init(jax.random.PRNGKey(3))
    state = wf.run(state, 100)
    fit = jnp.asarray(state.algo.fitness, dtype=jnp.float32)
    finite = jnp.isfinite(fit).all(axis=1)
    fit = jnp.where(finite[:, None], fit, 1e6)
    # bf16 storage quantizes the carried objectives (~2-3 digits): the
    # gate is 2x the f32 suite's 0.1 — still a converged ZDT1 front
    assert float(igd(fit, ZDT1(n_dim=d).pf())) < 0.2


def test_bf16_checkpoint_roundtrip(tmp_path):
    """Snapshots carry the storage dtype; resume reproduces the straight
    bf16 run bit-for-bit (same policy on both sides)."""
    ck = WorkflowCheckpointer(str(tmp_path / "bf16"), every=3)
    wf = _wf_cso(dtype_policy=BF16_STORAGE)
    key = jax.random.PRNGKey(5)
    straight = wf.run(wf.init(key), 9, checkpointer=ck)
    wf2 = _wf_cso(dtype_policy=BF16_STORAGE)
    resumed = wf2.resume(ck, 9)
    assert _digest(straight) == _digest(resumed)
    assert resumed.algo.population.dtype == jnp.bfloat16


# ------------------------------------------------------------------ donation


def test_donated_run_never_invalidates_caller_state():
    """Snapshot-before-donate: run() only donates its own intermediates,
    so a caller state can be re-run, re-stepped and fetched freely."""
    wf = _wf_cso(donate_carries=True)
    st = wf.init(jax.random.PRNGKey(7))
    a = wf.run(st, 5)
    b = wf.run(st, 5)  # same caller state again: must not be deleted
    assert _digest(a) == _digest(b)
    np.asarray(st.algo.population)  # still fetchable
    # and the run's OUTPUT is reusable too (the donated buffer is the
    # internal step intermediate, never the returned state)
    c = wf.step(a)
    np.asarray(a.algo.population)
    np.asarray(c.algo.population)


def test_donation_shows_alias_bytes_in_memory_analysis():
    """The acceptance referee: donation must be visible as reduced
    buffering — XLA's memory_analysis reports alias bytes for the
    donated run loop and zero for the undonated one."""
    wf_d = _wf_cso(donate_carries=True)
    wf_p = _wf_cso()
    state = wf_d.init(jax.random.PRNGKey(0))
    fn_d, args_d = wf_d.analysis_targets(state)["run"]
    fn_p, args_p = wf_p.analysis_targets(state)["run"]
    ma_d = fn_d.lower(*args_d).compile().memory_analysis()
    ma_p = fn_p.lower(*args_p).compile().memory_analysis()
    assert int(ma_d.alias_size_in_bytes) > 0
    assert int(ma_p.alias_size_in_bytes) == 0


def test_run_report_roofline_carries_policy_and_donation(ceilings):
    wf = _wf_cso(dtype_policy=BF16_STORAGE, donate_carries=True)
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 3)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    roof = report["roofline"]
    assert roof["dtype_policy"] == {
        "storage": "bfloat16",
        "compute": "float32",
        "active": True,
    }
    assert roof["donation"]["donate_carries"] is True
    assert roof["donation"]["alias_bytes"]["run"] > 0
    # and the default workflow reports itself honestly too
    wf0 = _wf_cso()
    rec0 = instrument(wf0)
    s0 = wf0.run(wf0.init(jax.random.PRNGKey(0)), 3)
    roof0 = run_report(
        wf0, s0, recorder=rec0, analyzer=CostAnalyzer(ceilings=ceilings)
    )["roofline"]
    assert roof0["dtype_policy"]["active"] is False
    assert roof0["donation"]["donate_carries"] is False


def test_donated_checkpoint_resume_equivalence(tmp_path):
    """Chaos law through the donated path: a checkpointed donated run
    crashed at K and resumed reproduces the identically-chunked straight
    donated run bit-for-bit (chunk boundaries align, and snapshots are
    always taken from never-donated states)."""
    key = jax.random.PRNGKey(9)

    ck_a = WorkflowCheckpointer(str(tmp_path / "straight"), every=3)
    wf_a = _wf_cso(donate_carries=True)
    straight = wf_a.run(wf_a.init(key), 9, checkpointer=ck_a)

    ck_b = WorkflowCheckpointer(str(tmp_path / "crash"), every=3)
    wf_b = _wf_cso(donate_carries=True)
    wf_b.run(wf_b.init(key), 6, checkpointer=ck_b)  # "crash" after gen 6
    wf_c = _wf_cso(donate_carries=True)
    resumed = wf_c.resume(ck_b, 9)
    assert int(resumed.generation) == 9
    assert _digest(straight) == _digest(resumed)


def test_supervisor_heals_bit_identically_through_donated_path(tmp_path):
    """PR-5's healing law re-run with donation on: transient retries
    replay from caller-owned (never-donated) states, so the healed run
    equals the identically-chunked clean run bit-for-bit."""
    def mk():
        return StdWorkflow(
            PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8),
            Sphere(),
            donate_carries=True,
        )

    key = jax.random.PRNGKey(11)
    wf_clean = mk()
    state0 = wf_clean.init(key)
    ck_clean = WorkflowCheckpointer(str(tmp_path / "clean"), every=4)
    final_clean = RunSupervisor(checkpointer=ck_clean).run(wf_clean, state0, 8)

    wf = mk()
    wf.run(state0, 2)  # warm compile before arming any fault
    wf.run = FlakyDispatch(wf.run, faults={0: "transient", 1: "transient"})
    ck = WorkflowCheckpointer(str(tmp_path / "chaos"), every=4)
    sup = RunSupervisor(checkpointer=ck, max_retries=3, backoff_s=0.01)
    final = sup.run(wf, state0, 8)
    assert sup.report()["outcome"] == "recovered"
    assert _digest(final) == _digest(final_clean)


def test_donated_pipelined_converges_and_ctx_is_single_use():
    """run_host_pipelined through a donating workflow: the ask-ctx is
    consumed exactly once per generation, results match the undonated
    driver to float tolerance (donation perturbs fusion at the last ulp
    — the reason donation is opt-in), and a manual ctx reuse fails
    loudly instead of corrupting."""
    from evox_tpu.core.problem import Problem
    from evox_tpu.workflows.pipelined import run_host_pipelined

    class HostSphere(Problem):
        jittable = False
        fit_dtype = np.float32

        def init(self, key=None):
            return None

        def fit_shape(self, pop):
            return (pop,)

        def evaluate(self, state, pop):
            fit = (np.asarray(pop) ** 2).sum(axis=1)
            return np.asarray(fit, dtype=np.float32), state

    def mk(**kw):
        return StdWorkflow(
            PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8),
            HostSphere(),
            **kw,
        )

    wf0 = mk()
    ref = run_host_pipelined(wf0, wf0.init(jax.random.PRNGKey(2)), 6)
    wf1 = mk(donate_carries=True)
    got = run_host_pipelined(wf1, wf1.init(jax.random.PRNGKey(2)), 6)
    np.testing.assert_allclose(
        np.asarray(got.algo.population),
        np.asarray(ref.algo.population),
        rtol=1e-5,
        atol=1e-6,
    )
    # ctx single-use: a second tell on the same ctx hits deleted buffers
    state = wf1.init(jax.random.PRNGKey(3))
    cand, ctx = wf1.pipeline_ask(state)
    fit = np.asarray((np.asarray(cand) ** 2).sum(axis=1), dtype=np.float32)
    state2 = wf1.pipeline_tell(state, ctx, fit, state.prob)
    assert int(state2.generation) == 1
    with pytest.raises((RuntimeError, ValueError)):
        wf1.pipeline_tell(state, ctx, fit, state.prob)

"""Search-dynamics observability (ISSUE 19): LineageMonitor's on-device
rings, the operator-attribution contract, and convergence forensics.

Laws under test:

- **Observer effect is zero**: swapping which observer rides along
  (TelemetryMonitor ↔ LineageMonitor, equal monitor COUNT — StdWorkflow
  splits ``2 + len(monitors)`` keys and threefry split is not
  prefix-stable, so the count is part of the trajectory) leaves every
  algorithm leaf bit-identical.
- **Attribution is invisible**: the DE family run with a
  ``LineageMonitor`` attached (the consumer of the attribution) and run
  with NO monitor give the same digest — population, fitness, AND the
  adaptive internals (SaDE strategy probabilities, JaDE/SHADE memories)
  — so threading Attribution through ask/tell changes nothing an
  optimizer can see. Both runs are made in one process: the law holds
  on whatever jax build the suite lands on.
- **One trajectory, any driver**: the monitor state's fingerprint is
  identical across the step loop, the fused ``run()`` fori_loop, the
  8-device mesh (step and fused), and ``run_host_pipelined``.
- **Ledger is the adaptation**: SaDE's per-strategy success counts in
  the attribution ledger equal its internal ``success_mem`` column sums
  exactly — the credit ledger is the same statistic the adaptation
  consumes, not a parallel approximation.
- **Forensics are valid**: ``best_ancestry()`` on a converged run is an
  in-range, epoch-consistent descent chain; the full run_report (schema
  v13 ``search`` section) passes tools/check_report.py.
- **Restarts fence lineage**: GuardedAlgorithm restarts bump the epoch,
  and ancestry never walks across an epoch boundary (a post-restart
  individual has no meaningful parent in the pre-restart population).
- **Fleets vmap**: VectorizedWorkflow carries per-tenant rings; slicing
  tenant i out yields that tenant's own ancestry.
"""

import hashlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    GuardedAlgorithm,
    StdWorkflow,
    create_mesh,
    run_host_pipelined,
    run_report,
)
from evox_tpu.algorithms import DE, CoDE, JaDE, SaDE, SHADE
from evox_tpu.algorithms.mo.nsga2 import NSGA2
from evox_tpu.algorithms.so.es.cma_es import CMAES, SepCMAES
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.core.attribution import OP_NAMES, SADE_STRATEGY_TAGS
from evox_tpu.core.distributed import ShardedES
from evox_tpu.core.problem import Problem
from evox_tpu.monitors import LineageMonitor, TelemetryMonitor
from evox_tpu.problems.numerical import Sphere, ZDT1
from evox_tpu.workflows.tenancy import VectorizedWorkflow

sys.path.insert(0, "tools")
import check_report  # noqa: E402

DIM = 4
LB, UB = -10.0 * jnp.ones(DIM), 10.0 * jnp.ones(DIM)


def _digest(arrs):
    h = hashlib.sha256()
    for a in arrs:
        x = np.asarray(jax.device_get(a))
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


# ------------------------------------------------------------ no-op laws


def test_observer_swap_is_bit_invisible():
    """Same monitor count, different observer — algo leaves identical."""
    wf_a = StdWorkflow(
        DE(lb=LB, ub=UB, pop_size=20),
        Sphere(),
        monitors=[TelemetryMonitor(8)],
    )
    sa = wf_a.run(wf_a.init(jax.random.PRNGKey(7)), 15)
    wf_b = StdWorkflow(
        DE(lb=LB, ub=UB, pop_size=20),
        Sphere(),
        monitors=[LineageMonitor(8)],
    )
    sb = wf_b.run(wf_b.init(jax.random.PRNGKey(7)), 15)
    for la, lb_ in zip(jax.tree.leaves(sa.algo), jax.tree.leaves(sb.algo)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb_))


def _golden_run(algo, monitors=()):
    """seed 7, 15 fused steps, pop 20, dim 4, Sphere."""
    wf = StdWorkflow(algo, Sphere(), monitors=list(monitors))
    return wf.run(wf.init(jax.random.PRNGKey(7)), 15).algo


@pytest.mark.parametrize(
    "name, build, fields",
    [
        ("de_pop", lambda: DE(LB, UB, pop_size=20), ("population", "fitness")),
        (
            "sade_adapt",
            lambda: SaDE(LB, UB, pop_size=20),
            ("probs", "success_mem", "failure_mem", "CRm"),
        ),
        (
            "sade_pop",
            lambda: SaDE(LB, UB, pop_size=20),
            ("population", "fitness"),
        ),
        (
            "jade_adapt",
            lambda: JaDE(LB, UB, pop_size=20),
            ("mu_F", "mu_CR", "archive_size"),
        ),
        (
            "jade_pop",
            lambda: JaDE(LB, UB, pop_size=20),
            ("population", "fitness"),
        ),
        (
            "code_pop",
            lambda: CoDE(LB, UB, pop_size=20),
            ("population", "fitness"),
        ),
        (
            "shade_adapt",
            lambda: SHADE(LB, UB, pop_size=20),
            ("M_F", "M_CR", "mem_pos", "archive_size"),
        ),
        (
            "shade_pop",
            lambda: SHADE(LB, UB, pop_size=20),
            ("population", "fitness"),
        ),
    ],
)
def test_de_family_matches_pre_attribution_goldens(name, build, fields):
    bare = _golden_run(build())
    watched = _golden_run(build(), monitors=[LineageMonitor(8)])
    got = _digest([getattr(watched, f) for f in fields])
    assert got == _digest([getattr(bare, f) for f in fields]), (
        f"{name}: adaptive-DE behavior differs between the run a "
        f"LineageMonitor watches and the bare run — the operator-"
        f"attribution plumbing must be bit-invisible"
    )


# ------------------------------------------- one trajectory, any driver


def test_step_loop_vs_fused_run_fingerprint():
    m1, m2 = LineageMonitor(8), LineageMonitor(8)
    wf1 = StdWorkflow(DE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[m1])
    wf2 = StdWorkflow(DE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[m2])
    key = jax.random.PRNGKey(7)
    s1 = wf1.init(key)
    for _ in range(15):
        s1 = wf1.step(s1)
    s2 = wf2.run(wf2.init(key), 15)
    assert m1.fingerprint(s1.monitors[0]) == m2.fingerprint(s2.monitors[0])


def test_mesh_fused_vs_step_fingerprint_and_sharded_es():
    assert jax.device_count() >= 8
    mesh = create_mesh()
    m1, m2 = LineageMonitor(8), LineageMonitor(8)
    wf1 = StdWorkflow(
        DE(lb=LB, ub=UB, pop_size=32), Sphere(), monitors=[m1], mesh=mesh
    )
    wf2 = StdWorkflow(
        DE(lb=LB, ub=UB, pop_size=32), Sphere(), monitors=[m2], mesh=mesh
    )
    key = jax.random.PRNGKey(5)
    s1 = wf1.run(wf1.init(key), 12)
    s2 = wf2.init(key)
    for _ in range(12):
        s2 = wf2.step(s2)
    assert m1.fingerprint(s1.monitors[0]) == m2.fingerprint(s2.monitors[0])
    chain = m1.best_ancestry(s1.monitors[0])
    assert len(chain) == 8 and all(0 <= e["slot"] < 32 for e in chain)
    # ShardedES on the same mesh: fallback tagging, global slot indices
    m3 = LineageMonitor(8, default_op="sample")
    algo3 = ShardedES(
        SepCMAES(center_init=jnp.full(DIM, 2.0), init_stdev=1.0, pop_size=32),
        mesh=mesh,
    )
    wf3 = StdWorkflow(algo3, Sphere(), monitors=[m3], mesh=mesh)
    s3 = wf3.run(wf3.init(jax.random.PRNGKey(9)), 10)
    chain3 = m3.best_ancestry(s3.monitors[0])
    assert len(chain3) == 8
    assert all(0 <= e["slot"] < 32 for e in chain3)
    assert all(e["op"] == "sample" for e in chain3)


class _HostSphere(Problem):
    jittable = False

    def evaluate(self, state, pop):
        return np.sum(np.asarray(pop) ** 2, axis=-1).astype(np.float32), state


def test_pipelined_driver_matches_step_loop():
    m4, m5 = LineageMonitor(6), LineageMonitor(6)
    algo = PSO(LB, UB, pop_size=16)
    wf4 = StdWorkflow(algo, _HostSphere(), monitors=[m4])
    wf5 = StdWorkflow(algo, _HostSphere(), monitors=[m5])
    key = jax.random.PRNGKey(7)
    s4 = run_host_pipelined(wf4, wf4.init(key), 6)
    s5 = wf5.init(key)
    for _ in range(6):
        s5 = wf5.step(s5)
    assert m4.fingerprint(s4.monitors[0]) == m5.fingerprint(s5.monitors[0])


# -------------------------------------------------- ledger = adaptation


def test_sade_ledger_equals_internal_success_memory():
    """The per-strategy success counts the ledger reports ARE the
    statistics SaDE adapts on — column sums of its success_mem ring
    (12 steps < LP, so the ring holds every generation)."""
    mon = LineageMonitor(history_capacity=16)
    wf = StdWorkflow(SaDE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[mon])
    s = wf.init(jax.random.PRNGKey(7))
    for _ in range(12):
        s = wf.step(s)
    led = mon.ledger(s.monitors[0])
    colsums = np.asarray(s.algo.success_mem).sum(axis=0)
    for i, tag in enumerate(SADE_STRATEGY_TAGS):
        got = led.get(OP_NAMES[tag], {"successes": 0})["successes"]
        assert got == int(colsums[i]), (
            f"strategy {OP_NAMES[tag]}: ledger says {got} successes, "
            f"SaDE's own success_mem says {int(colsums[i])}"
        )


def test_de_ledger_attempts_accounting():
    """Generation 0 is the initial-population eval: credited to 'init';
    every later generation to the DE operator — attempts sum to
    generations × width (the check_report v13 ledger-sum rule)."""
    mon = LineageMonitor(history_capacity=8)
    wf = StdWorkflow(DE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[mon])
    s = wf.run(wf.init(jax.random.PRNGKey(7)), 15)
    led = mon.ledger(s.monitors[0])
    assert led["init"]["attempts"] == 20
    assert led["de_rand_1"]["attempts"] == 20 * 14
    assert all(v["successes"] <= v["attempts"] for v in led.values())


def test_code_width_folding():
    """CoDE evaluates 3n candidates per later generation; the monitor
    folds them onto the n-wide slot space sized by the gen-0 batch."""
    mon = LineageMonitor(history_capacity=8)
    wf = StdWorkflow(CoDE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[mon])
    s = wf.init(jax.random.PRNGKey(7))
    for _ in range(6):
        s = wf.step(s)
    ms = s.monitors[0]
    assert ms.cur_fit.shape[0] == 20
    chain = mon.best_ancestry(ms)
    assert len(chain) == 6
    assert {e["op"] for e in chain} <= {
        "init",
        "de_rand_1",
        "de_rand_2",
        "de_cur_to_rand_1",
    }


# --------------------------------------------------- forensics validity


def test_best_ancestry_acceptance_and_report_v13():
    """The ISSUE acceptance law: on a converged Sphere run,
    best_ancestry() returns an in-range epoch-consistent chain and the
    full run_report (v13 search section) validates green."""
    for algo, elitist in (
        (DE(lb=LB, ub=UB, pop_size=20), True),
        (
            CMAES(center_init=jnp.zeros(DIM), init_stdev=1.0, pop_size=16),
            False,
        ),
    ):
        mon = LineageMonitor(history_capacity=16)
        wf = StdWorkflow(algo, Sphere(), monitors=[mon])
        state = wf.run(wf.init(jax.random.PRNGKey(7)), 30)
        ms = state.monitors[0]
        width = ms.cur_fit.shape[0]
        chain = mon.best_ancestry(ms)
        assert 1 <= len(chain) <= 16
        epochs = {e["epoch"] for e in chain}
        assert len(epochs) == 1
        gens = [e["generation"] for e in chain]
        assert gens == list(range(gens[0], gens[0] - len(gens), -1))
        for e in chain:
            assert 0 <= e["slot"] < width and 0 <= e["parent"] < width
        traj = mon.get_trajectory(ms)
        bf = traj["best_fitness"]
        if elitist:
            # per-generation best only descends when survivors persist;
            # CMAES resamples, so its window is merely improving overall
            assert all(b <= a + 1e-6 for a, b in zip(bf, bf[1:]))
        assert bf[-1] <= bf[0]
        rep = run_report(workflow=wf, state=state)
        assert rep["schema_version"] == 14
        assert rep["search"]["enabled"] is True
        errors = check_report.validate_run_report(rep)
        assert not errors, errors
        json.dumps(rep["search"], allow_nan=False)


def test_report_without_lineage_has_no_search_section():
    wf = StdWorkflow(
        DE(lb=LB, ub=UB, pop_size=20), Sphere(), monitors=[TelemetryMonitor(8)]
    )
    state = wf.run(wf.init(jax.random.PRNGKey(7)), 5)
    rep = run_report(workflow=wf, state=state)
    assert "search" not in rep
    assert not check_report.validate_run_report(rep)


# ---------------------------------------------------- restarts & epochs


class _Flatline(Sphere):
    def evaluate(self, state, pop):
        fit, state = super().evaluate(state, pop)
        return jnp.zeros_like(fit), state


def test_guarded_restarts_fence_ancestry():
    mon = LineageMonitor(history_capacity=32)
    algo = GuardedAlgorithm(
        CMAES(center_init=jnp.zeros(DIM), init_stdev=1.0, pop_size=16),
        stagnation_limit=2,
    )
    wf = StdWorkflow(algo, _Flatline(), monitors=[mon])
    s = wf.init(jax.random.PRNGKey(3))
    for _ in range(12):
        s = wf.step(s)
    ms = s.monitors[0]
    restarts = int(s.algo.restarts)
    assert restarts > 0
    assert int(ms.restarts_seen) == restarts
    chain = mon.best_ancestry(ms)
    assert len({e["epoch"] for e in chain}) == 1, (
        "ancestry walked across a restart boundary — cross-epoch edges "
        "must never be read as descent"
    )
    assert max(mon.get_trajectory(ms)["epoch"]) == restarts
    # PBT-exploit hook: jit-safe additive epoch bump
    assert int(mon.bump_epoch(ms).epoch_extra) == 1


# --------------------------------------------------------- MO forensics


def test_mo_front_size_and_churn_rings():
    mon = LineageMonitor(
        history_capacity=8, num_objectives=2, default_op="crossover"
    )
    algo = NSGA2(jnp.zeros(6), jnp.ones(6), n_objs=2, pop_size=32)
    wf = StdWorkflow(algo, ZDT1(n_dim=6), monitors=[mon])
    s = wf.init(jax.random.PRNGKey(5))
    for _ in range(10):
        s = wf.step(s)
    ms = s.monitors[0]
    traj = mon.get_trajectory(ms)
    assert all(1 <= f <= 32 for f in traj["front_size"])
    assert all(np.isfinite(c) and c >= 0 for c in traj["churn"])
    assert all(
        e["op"] in ("crossover", "init") for e in mon.best_ancestry(ms)
    )
    rep = mon.search_report(ms)
    json.dumps(rep, allow_nan=False)
    assert rep["num_objectives"] == 2


# --------------------------------------------------------------- fleets


def test_fleet_vmapped_rings_and_per_tenant_ancestry():
    mon = LineageMonitor(8)
    vwf = VectorizedWorkflow(
        DE(lb=LB, ub=UB, pop_size=16), Sphere(), n_tenants=3, monitors=[mon]
    )
    vs = vwf.init(jax.random.PRNGKey(11))
    for _ in range(10):
        vs = vwf.step(vs)
    vms = vs.tenants.monitors[0]
    assert vms.ring_parent.shape == (3, 8, 16)
    chains = []
    for t in range(3):
        per = jax.tree.map(lambda x, _t=t: x[_t], vms)
        chain = mon.best_ancestry(per)
        assert len(chain) == 8
        assert all(0 <= e["slot"] < 16 for e in chain)
        chains.append(tuple((e["slot"], e["parent"]) for e in chain))
        json.dumps(mon.search_report(per), allow_nan=False)
    assert len(set(chains)) > 1, "tenants share one trajectory — vmap broke"


def test_checkpoint_resume_preserves_lineage_rings(tmp_path):
    """Snapshots are written post-step, where the lazily-sized rings are
    materialized; resume's config guard must accept that structure (it
    fingerprints a traced init+step, not the bare init) and the restored
    run must finish fingerprint-identical to the uninterrupted one."""
    from evox_tpu.workflows.checkpoint import WorkflowCheckpointer

    m_ref, m_res = LineageMonitor(8), LineageMonitor(8)
    wf_ref = StdWorkflow(
        PSO(LB, UB, pop_size=32), Sphere(), monitors=[m_ref]
    )
    s0 = wf_ref.init(jax.random.PRNGKey(2))
    ref = wf_ref.run(s0, 15)
    wf_ref.run(s0, 15, checkpointer=WorkflowCheckpointer(tmp_path, every=5))
    wf_res = StdWorkflow(
        PSO(LB, UB, pop_size=32), Sphere(), monitors=[m_res]
    )
    res = wf_res.resume(WorkflowCheckpointer(tmp_path, every=5), 15)
    for a, b in zip(jax.tree.leaves(ref.algo), jax.tree.leaves(res.algo)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert m_ref.fingerprint(ref.monitors[0]) == m_res.fingerprint(
        res.monitors[0]
    )

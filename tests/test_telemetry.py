"""TelemetryMonitor + core.instrument: callback-free observability.

Covers the ISSUE-1 acceptance surface: ring-overwrite semantics, NaN/Inf
counting with injected poison, stagnation reset on improvement, identical
reports from step()-loops vs the fused run() fori_loop across
Std/Island/pipelined workflows on the 8-device CPU mesh, the 100-gen
fused-run compile check, and the run_report / JSON-lines contract."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    DispatchRecorder,
    IslandWorkflow,
    StdWorkflow,
    create_mesh,
    instrument,
    run_host_pipelined,
    run_report,
    write_report_jsonl,
)
from evox_tpu.algorithms.so.pso import CSO, PSO
from evox_tpu.core.problem import Problem
from evox_tpu.monitors import StepTimerMonitor, TelemetryMonitor
from evox_tpu.problems.numerical import Sphere, ZDT1

DIM = 4
LB, UB = -10.0 * jnp.ones(DIM), 10.0 * jnp.ones(DIM)


def _wf(monitors, pop=32, **kw):
    return StdWorkflow(PSO(LB, UB, pop_size=pop), Sphere(), monitors=monitors, **kw)


def _assert_states_match(a, b, atol=1e-5):
    """Integer counters bit-equal; float accumulators allclose (the fused
    fori_loop and the step loop may differ in last-ulp XLA fusion)."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.integer):
            np.testing.assert_array_equal(x, y)
        else:
            fx, fy = np.isfinite(x), np.isfinite(y)
            np.testing.assert_array_equal(fx, fy)
            np.testing.assert_allclose(x[fx], y[fy], atol=atol, rtol=1e-4)


# ------------------------------------------------------------------- rings

def test_ring_overwrite_semantics():
    """capacity=4 after 10 generations holds exactly generations 7-10,
    matching the tail of an uncapped (capacity=16) run bit-for-bit."""
    key = jax.random.PRNGKey(0)
    small, big = TelemetryMonitor(capacity=4), TelemetryMonitor(capacity=16)
    wf1, wf2 = _wf((small,)), _wf((big,))
    s1, s2 = wf1.run(wf1.init(key), 10), wf2.run(wf2.init(key), 10)
    t_small = small.get_trajectory(s1.monitors[0])
    t_big = big.get_trajectory(s2.monitors[0])
    assert t_small["generation"] == [7, 8, 9, 10]
    assert t_big["generation"] == list(range(1, 11))
    np.testing.assert_allclose(t_small["best"], t_big["best"][-4:], rtol=1e-6)
    np.testing.assert_allclose(t_small["mean"], t_big["mean"][-4:], rtol=1e-6)
    np.testing.assert_allclose(
        t_small["diversity"], t_big["diversity"][-4:], rtol=1e-6
    )
    assert int(s1.monitors[0].generations) == 10


def test_eval_counter_variable_batch():
    """CSO evaluates the full pop once, then half per generation — the
    eval counter must track the true batch widths."""
    tm = TelemetryMonitor(capacity=8)
    wf = StdWorkflow(CSO(LB, UB, pop_size=16), Sphere(), monitors=(tm,))
    state = wf.run(wf.init(jax.random.PRNGKey(1)), 5)
    ms = state.monitors[0]
    assert int(ms.generations) == 5
    assert int(ms.evals) == 16 + 4 * 8


# ------------------------------------------------------------ NaN/Inf poison

class PoisonSphere(Problem):
    """Sphere with rows 1,2 NaN and row 3 +inf — deterministic poison."""

    def evaluate(self, state, pop):
        fit = jnp.sum(pop**2, axis=-1)
        fit = fit.at[1].set(jnp.nan).at[2].set(jnp.nan).at[3].set(jnp.inf)
        return fit, state


def test_nan_inf_counting():
    # candidate poison via pop_transform (post_eval sees transformed cand):
    # row 0 dim 0 NaN -> 1 NaN candidate element/gen, and Sphere maps that
    # row to a NaN fitness, joining the problem's rows 1,2
    inject = lambda c: c.at[0, 0].set(jnp.nan)  # noqa: E731
    tm = TelemetryMonitor(capacity=8)
    wf = StdWorkflow(
        PSO(LB, UB, pop_size=16),
        PoisonSphere(),
        monitors=(tm,),
        pop_transforms=(inject,),
    )
    gens = 6
    state = wf.run(wf.init(jax.random.PRNGKey(2)), gens)
    ms = state.monitors[0]
    assert int(ms.nan_candidates) == gens * 1
    assert int(ms.inf_candidates) == 0
    assert int(ms.nan_fitness) == gens * 3
    assert int(ms.inf_fitness) == gens * 1
    # poison must not blank the trajectory: finite-masked stats stay finite
    traj = tm.get_trajectory(ms)
    assert np.isfinite(traj["best"]).all()
    assert np.isfinite(traj["mean"]).all()
    assert np.isfinite(traj["diversity"]).all()
    rep = tm.report(ms)
    assert rep["nan_fitness"] == gens * 3 and rep["inf_fitness"] == gens
    json.dumps(rep, allow_nan=False)  # strict JSON even under poison


# ------------------------------------------------------------- stagnation

class ScheduleProblem(Problem):
    """Fitness follows a fixed per-generation schedule; problem state is
    the generation counter."""

    schedule = jnp.asarray([5.0, 5.0, 5.0, 2.0, 2.0, 2.0])

    def init(self, key=None):
        return jnp.zeros((), dtype=jnp.int32)

    def evaluate(self, state, pop):
        v = self.schedule[jnp.clip(state, 0, self.schedule.shape[0] - 1)]
        return jnp.full((pop.shape[0],), v), state + 1


def test_stagnation_resets_on_improvement():
    tm = TelemetryMonitor(capacity=8)
    wf = StdWorkflow(PSO(LB, UB, pop_size=8), ScheduleProblem(), monitors=(tm,))
    state = wf.init(jax.random.PRNGKey(3))
    expected_stag = [0, 1, 2, 0, 1, 2]  # improves at gens 1 and 4
    for g, want in enumerate(expected_stag, start=1):
        state = wf.step(state)
        ms = state.monitors[0]
        assert int(ms.stagnation) == want, f"gen {g}"
    rep = tm.report(state.monitors[0])
    assert rep["best_fitness"] == 2.0
    assert rep["best_generation"] == 4
    assert rep["stagnation"] == 2


def test_max_direction_user_convention():
    class NegSphere(Problem):
        def evaluate(self, state, pop):
            return -jnp.sum(pop**2, axis=-1), state

    tm = TelemetryMonitor(capacity=8)
    wf = StdWorkflow(
        PSO(LB, UB, pop_size=32), NegSphere(), monitors=(tm,),
        opt_direction="max",
    )
    state = wf.run(wf.init(jax.random.PRNGKey(4)), 30)
    ms = state.monitors[0]
    best = float(tm.get_best_fitness(ms))
    # maximizing -x^2: best approaches 0 from below, reported user-side
    assert -1.0 < best <= 0.0
    # the run keeps improving, so stagnation stays small
    assert int(ms.stagnation) < 30
    traj = tm.get_trajectory(ms)
    # user convention under "max": best-so-far dominates (>=) every
    # windowed per-generation best
    assert best >= max(traj["best"]) - 1e-9


# ---------------------------------------------- step vs fused run equivalence

def test_std_step_vs_run_identical_on_mesh():
    assert jax.device_count() >= 8
    mesh = create_mesh()
    key = jax.random.PRNGKey(5)
    tm1, tm2 = TelemetryMonitor(capacity=8), TelemetryMonitor(capacity=8)
    wf1, wf2 = _wf((tm1,), mesh=mesh), _wf((tm2,), mesh=mesh)
    s1 = wf1.run(wf1.init(key), 12)
    s2 = wf2.init(key)
    for _ in range(12):
        s2 = wf2.step(s2)
    _assert_states_match(s1.monitors[0], s2.monitors[0])
    r1, r2 = tm1.report(s1.monitors[0]), tm2.report(s2.monitors[0])
    for k in ("generations", "evals", "stagnation", "best_generation",
              "nan_fitness", "inf_fitness"):
        assert r1[k] == r2[k]


def test_stable_fingerprint_layout_invariant():
    """fingerprint(stable=True) covers only the integer counter surface
    and is bit-identical across 8-device / 4-device / replicated layouts
    (the default byte fingerprint may legally drift in the rings' last
    ulp when the pop axis is resharded, which is why cross-layout laws
    historically dodged it with allclose)."""
    devs = jax.devices()
    assert len(devs) >= 8
    key = jax.random.PRNGKey(17)
    stable_fps, mons = [], []
    for mesh in (create_mesh(devices=devs[:8]),
                 create_mesh(devices=devs[:4]), None):
        tm = TelemetryMonitor(capacity=8)
        wf = _wf((tm,), mesh=mesh)
        s = wf.run(wf.init(key), 9)
        stable_fps.append(tm.fingerprint(s.monitors[0], stable=True))
        mons.append((tm, s.monitors[0]))
    assert stable_fps[0] == stable_fps[1] == stable_fps[2]
    # 48-char attestor digest vs 64-char sha256 — unmistakable forms
    assert len(stable_fps[0]) == 48
    assert len(mons[0][0].fingerprint(mons[0][1])) == 64
    # the stable surface still changes when the run actually differs
    tm2 = TelemetryMonitor(capacity=8)
    wf2 = _wf((tm2,))
    s2 = wf2.run(wf2.init(key), 10)
    assert tm2.fingerprint(s2.monitors[0], stable=True) != stable_fps[0]


def test_islands_step_vs_run_identical():
    key = jax.random.PRNGKey(6)
    mons = [TelemetryMonitor(capacity=6) for _ in range(2)]
    wfs = [
        IslandWorkflow(
            PSO(LB, UB, pop_size=16), Sphere(), n_islands=4,
            migrate_every=3, monitors=(m,),
        )
        for m in mons
    ]
    s1 = wfs[0].run(wfs[0].init(key), 9)
    s2 = wfs[1].init(key)
    for _ in range(9):
        s2 = wfs[1].step(s2)
    _assert_states_match(s1.monitors[0], s2.monitors[0])
    ms = s1.monitors[0]
    # hooks see the flattened (islands * pop) batch
    assert int(ms.evals) == 9 * 4 * 16


def test_pipelined_matches_step_loop():
    class HostSphere(Problem):
        jittable = False

        def evaluate(self, state, pop):
            return np.sum(np.asarray(pop) ** 2, axis=-1).astype(np.float32), state

    key = jax.random.PRNGKey(7)
    tm1, tm2 = TelemetryMonitor(capacity=6), TelemetryMonitor(capacity=6)
    algo = PSO(LB, UB, pop_size=16)
    wf1 = StdWorkflow(algo, HostSphere(), monitors=(tm1,))
    wf2 = StdWorkflow(algo, HostSphere(), monitors=(tm2,))
    s1 = run_host_pipelined(wf1, wf1.init(key), 6)
    s2 = wf2.init(key)
    for _ in range(6):
        s2 = wf2.step(s2)
    # pipelined runs are bit-identical to step loops (test_pipelined) —
    # telemetry threads through the same hooks, so it must be too
    _assert_states_match(s1.monitors[0], s2.monitors[0], atol=0)


# ------------------------------------------------------------ MO + 100-gen

def test_multi_objective_ideal_point():
    from evox_tpu.algorithms.mo import NSGA2

    tm = TelemetryMonitor(capacity=5, num_objectives=2)
    algo = NSGA2(jnp.zeros(6), jnp.ones(6), n_objs=2, pop_size=32)
    wf = StdWorkflow(algo, ZDT1(n_dim=6), monitors=(tm,), num_objectives=2)
    state = wf.run(wf.init(jax.random.PRNGKey(8)), 7)
    ms = state.monitors[0]
    assert ms.ring_best.shape == (5, 2)
    best = np.asarray(tm.get_best_fitness(ms))
    assert best.shape == (2,) and np.isfinite(best).all()
    traj = tm.get_trajectory(ms)
    assert traj["generation"] == [3, 4, 5, 6, 7]
    assert len(traj["best"][0]) == 2
    json.dumps(tm.report(ms))


def test_report_is_strict_json_before_any_generation():
    """best_key starts at +inf and the rings are inf-padded; the report
    must still be STRICT (RFC 8259) JSON — non-finite values become
    None, never bare Infinity/NaN tokens."""
    tm = TelemetryMonitor(capacity=4)
    rep = tm.report(tm.init())
    assert rep["best_fitness"] is None and rep["generations"] == 0
    json.dumps(rep, allow_nan=False)
    wf = _wf((tm,))
    full = run_report(wf, wf.init(jax.random.PRNGKey(14)))
    json.dumps(full, allow_nan=False)


def test_arity_mismatch_raises():
    tm = TelemetryMonitor(capacity=4)  # declared single-objective
    from evox_tpu.algorithms.mo import NSGA2

    algo = NSGA2(jnp.zeros(6), jnp.ones(6), n_objs=2, pop_size=16)
    wf = StdWorkflow(algo, ZDT1(n_dim=6), monitors=(tm,), num_objectives=2)
    with pytest.raises(ValueError, match="num_objectives"):
        wf.step(wf.init(jax.random.PRNGKey(9)))


def test_fused_run_100_generations():
    """The ISSUE acceptance shape: TelemetryMonitor through
    StdWorkflow.run(state, 100) on the CPU backend, no callbacks."""
    tm = TelemetryMonitor(capacity=16)
    wf = _wf((tm,))
    state = wf.run(wf.init(jax.random.PRNGKey(10)), 100)
    ms = state.monitors[0]
    assert int(ms.generations) == 100
    assert int(ms.evals) == 100 * 32
    traj = tm.get_trajectory(ms)
    assert traj["generation"] == list(range(85, 101))
    # converging swarm: best improves and diversity collapses
    assert traj["best"][-1] < 1e-2
    assert traj["diversity"][-1] < traj["diversity"][0]
    rep = tm.report(ms)
    assert rep["best_fitness"] < 1e-2 and rep["nan_fitness"] == 0
    json.dumps(rep)


# ------------------------------------------------- instrument + run_report

def test_instrument_and_run_report(tmp_path):
    tm = TelemetryMonitor(capacity=8)
    wf = _wf((tm,))
    rec = instrument(wf)
    assert isinstance(rec, DispatchRecorder)
    state = wf.init(jax.random.PRNGKey(11))
    state = wf.run(state, 8)
    state = wf.run(state, 8)  # warm dispatch sample
    state = wf.step(state)
    ep = rec.summary()["entry_points"]
    assert ep["init"]["calls"] == 1
    assert ep["run"]["calls"] == 2
    # run() peels its first generation through step(): 1 peel + 1 direct
    assert ep["step"]["calls"] == 2
    assert ep["run"]["compile_s"] >= 0
    assert ep["run"]["dispatch_s"] is not None
    # host-fetch accounting: generation is one int32 scalar = 4 bytes
    rec.fetch(state.generation, name="gen")
    fetches = rec.summary()["fetches"]
    assert fetches["gen"]["calls"] == 1 and fetches["gen"]["bytes"] == 4

    report = run_report(wf, state, recorder=rec, extra={"tag": "unit"})
    # v3: v2's roofline provenance plus the optional tenancy section
    assert report["schema"] == "evox_tpu.run_report/v14"
    assert report["schema_version"] == 14
    assert report["generation"] == 17
    tel = report["telemetry"][0]
    assert tel["monitor"] == "TelemetryMonitor"
    assert tel["generations"] == 17
    assert "best_fitness" in tel and "stagnation" in tel
    assert report["dispatch"]["entry_points"]["run"]["calls"] == 2
    assert report["extra"] == {"tag": "unit"}
    json.dumps(report)  # the whole report is JSON-serializable

    path = str(tmp_path / "reports.jsonl")
    write_report_jsonl(report, path)
    write_report_jsonl(report, path)
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["generation"] == 17


def test_instrument_is_idempotent_per_recorder():
    wf = _wf(())
    rec = instrument(wf)
    instrument(wf, recorder=rec)  # re-attach: no double counting
    state = wf.init(jax.random.PRNGKey(12))
    wf.step(state)
    assert rec.summary()["entry_points"]["step"]["calls"] == 1


# ------------------------------------------------------ StepTimerMonitor

def test_step_timer_still_works_on_cpu():
    mon = StepTimerMonitor()
    wf = _wf((mon,))
    state = wf.run(wf.init(jax.random.PRNGKey(13)), 4)
    assert mon.get_step_times().shape == (4,)

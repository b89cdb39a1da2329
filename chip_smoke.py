"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

    python chip_smoke.py              # one TPU chip, every phase below
    python chip_smoke.py --chips 4    # four chips, the multi-chip phase only

One process, no child that needs the device, no CPU mode: on a machine where
jax finds no TPU the script says why and exits non-zero before any phase.
It drives ``StdWorkflow.init`` -> ``wf.run(state, n)`` (one fused
``lax.fori_loop`` program) and ``wf.step`` through the entry points a user
calls, at the member models and populations the repo supports, and checks
what comes out by the repo's own laws (run == step, fused kernel == scan
engine, sharded == single device, resume == straight run).

Each phase is a plain function taking its sizes, so ``tests/test_chip_smoke.py``
calls every phase at a tiny size on the CPU mesh (kernels interpreted). A phase
that fails raises; nothing here catches an error to keep going.

Earlier lines of the output give, per phase, the seconds spent compiling, the
seconds spent running and the device's peak memory where the backend reports
it — information, not metrics. The last line is the result the driver reads.
"""

from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from evox_tpu import ShardedES, StdWorkflow, WorkflowCheckpointer, create_mesh
from evox_tpu.algorithms.mo import NSGA2
from evox_tpu.algorithms.so.es import OpenES, SepCMAES
from evox_tpu.algorithms.so.pso import CSO, PSO
from evox_tpu.core.problem import Problem
from evox_tpu.kernels.rollout import pendulum_soa
from evox_tpu.kernels.rollout_mlp import chain_walker_planes
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.neuroevolution import (
    PolicyRolloutProblem,
    flat_mlp_policy,
    mlp_policy,
)
from evox_tpu.problems.numerical import LSMOP1, Ackley, Sphere
from evox_tpu.utils import TreeAndVector, enable_compile_cache, rank_based_fitness

# what the script writes (the resume phase's snapshots) goes under the
# directory the chip tool brings back, never under a temporary name
OUT_DIR = Path(__file__).resolve().parent / "chiprun_out" / "chip_smoke"

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "collective-permute",
    "all-to-all",
    "reduce-scatter",
)


# ------------------------------------------------------------------ helpers


def _close(got, want, rtol: float, atol: float, what: str) -> float:
    """``assert_allclose`` that also returns the largest absolute error."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def _close_fields(got, want, names, rtol: float, atol: float, what: str) -> float:
    """:func:`_close` over the named fields of two algorithm states."""
    return max(
        _close(getattr(got, n), getattr(want, n), rtol, atol, f"{what}: {n}")
        for n in names
    )


def _finite(x, what: str) -> None:
    if not bool(jnp.all(jnp.isfinite(x))):
        raise AssertionError(f"{what}: non-finite values")


def _assert_kernel_compiled(wf: StdWorkflow, state, what: str) -> None:
    """The rollout problems pick interpret mode from ``jax.default_backend()``
    and leave no trace of the choice. The lowered steady step (the workflow's
    own AOT hook hands out the programs it dispatches) tells: on an
    accelerator it must hold the Mosaic custom call; on the CPU (the tests)
    the kernel is interpreted and there is none."""
    fn, args = wf.analysis_targets(state)["step"]
    has_call = "tpu_custom_call" in fn.lower(*args).as_text()
    want_call = jax.default_backend() != "cpu"
    if has_call != want_call:
        raise AssertionError(
            f"{what}: tpu_custom_call in the lowered step is {has_call}, "
            f"expected {want_call} on backend {jax.default_backend()!r}"
        )


def _collective_counts(hlo_text: str) -> dict:
    """Occurrences of each collective in a compiled program's text (the
    async ``-start`` form counts once, its ``-done`` half does not)."""
    return {
        name: len(re.findall(rf"\b{name}(?:-start)?\(", hlo_text))
        for name in _COLLECTIVES
    }


def _assert_spread(x: jax.Array, n_devices: int, what: str) -> None:
    """A population leaf really lives on ``n_devices`` devices, a row block
    of ``pop / n_devices`` on each."""
    if len(x.sharding.device_set) != n_devices:
        raise AssertionError(
            f"{what}: on {len(x.sharding.device_set)} devices, expected {n_devices}"
        )
    shard_shape = x.addressable_shards[0].data.shape
    want = (x.shape[0] // n_devices,) + tuple(x.shape[1:])
    if shard_shape != want:
        raise AssertionError(f"{what}: shard shape {shard_shape}, expected {want}")


# ------------------------------------------------------------------- phases


def phase_quickstart(pop: int = 256, dim: int = 10, gens: int = 200) -> dict:
    """README's quickstart: PSO on Ackley with an EvalMonitor, ``gens``
    generations in one ``wf.run``. Bar: best fitness < 1e-3 (the verify
    skill's quickstart bar)."""
    algo = PSO(lb=-32 * jnp.ones(dim), ub=32 * jnp.ones(dim), pop_size=pop)
    monitor = EvalMonitor()
    wf = StdWorkflow(algo, Ackley(), monitors=(monitor,))
    state = wf.run(wf.init(jax.random.PRNGKey(0)), gens)
    best = float(monitor.get_best_fitness(state.monitors[0]))
    if int(state.generation) != gens:
        raise AssertionError(f"ran {int(state.generation)} generations, not {gens}")
    if not 0.0 <= best < 1e-3:
        raise AssertionError(f"PSO/Ackley best fitness {best} after {gens} gens")
    return {"best_fitness": best}


_CSO_FIELDS = ("population", "velocity", "fitness")


def _cso(pop: int, dim: int, mesh=None) -> StdWorkflow:
    algo = CSO(lb=-32.0 * jnp.ones(dim), ub=32.0 * jnp.ones(dim), pop_size=pop)
    return StdWorkflow(algo, Ackley(), mesh=mesh)


def phase_run_equals_step(pop: int = 4096, dim: int = 1024, gens: int = 10) -> dict:
    """CSO / Ackley: ``wf.run(state, n)`` against ``n`` calls of ``wf.step``
    from the same state — the repo's run == step law."""
    wf = _cso(pop, dim)
    state0 = wf.init(jax.random.PRNGKey(42))
    s_run = wf.run(state0, gens)
    s_step = state0
    for _ in range(gens):
        s_step = wf.step(s_step)
    for name in _CSO_FIELDS:
        _finite(getattr(s_run.algo, name), f"run {name}")
    err = _close_fields(s_run.algo, s_step.algo, _CSO_FIELDS, 1e-5, 1e-5, "run vs step")
    if int(s_run.generation) != gens or int(s_step.generation) != gens:
        raise AssertionError("generation counters disagree with the trip count")
    return {"max_abs_err": err}  # 0.0 where the two are identical


def _run_and_compare_engines(
    wf: StdWorkflow, gens: int, scan_prob, shared_pop, tol: float, what: str
) -> dict:
    """The body both rollout phases share: the lowered step holds the
    compiled kernel; ``gens`` generations by ``wf.run`` move the OpenES
    center and keep it finite; and the fused problem's fitness on
    ``shared_pop`` agrees with the scan engine's within ``tol``.

    The scan engine runs with float32 matmuls kept float32: on a TPU the
    default matmul precision rounds operands to bfloat16, while the fused
    kernels do their MACs in float32 on the VPU."""
    state = wf.init(jax.random.PRNGKey(0))
    _assert_kernel_compiled(wf, state, f"{what} step")
    state = wf.run(state, gens)
    _finite(state.algo.center, f"{what} center")
    if int(state.generation) != gens or not bool(jnp.any(state.algo.center != 0)):
        raise AssertionError(f"{what}: OpenES center did not move")

    pstate = wf.problem.init(jax.random.PRNGKey(9))
    f_fused, _ = jax.jit(wf.problem.evaluate)(pstate, shared_pop)
    _finite(f_fused, f"{what} fused fitness")
    with jax.default_matmul_precision("highest"):
        f_scan, _ = jax.jit(scan_prob.evaluate)(pstate, shared_pop)
    err = _close(f_fused, f_scan, tol, tol, f"{what}: fused kernel vs scan engine")
    # the error means little without the scale of what it is an error of
    return {
        "kernel_vs_scan_max_abs_err": err,
        "fitness_abs_max": float(jnp.max(jnp.abs(f_scan))),
    }


def _walker_problem(hidden: int, episode_len: int, fused: bool):
    penv = chain_walker_planes(max_steps=episode_len)
    env = penv.base
    init_params, apply = mlp_policy((env.obs_dim, hidden, hidden, env.act_dim))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    prob = PolicyRolloutProblem(
        apply,
        env,
        num_episodes=1,
        stochastic_reset=False,
        fused_planes=penv if fused else None,
    )
    return prob, adapter


def _walker_workflow(pop: int, hidden: int, episode_len: int, **wf_kwargs):
    prob, adapter = _walker_problem(hidden, episode_len, fused=True)
    algo = OpenES(jnp.zeros(adapter.dim), pop, learning_rate=0.05, noise_stdev=0.05)
    wf = StdWorkflow(
        algo,
        prob,
        opt_direction="max",
        pop_transforms=(adapter.batched_to_tree,),
        fit_transforms=(rank_based_fitness,),
        **wf_kwargs,
    )
    return wf, adapter


def phase_walker(
    pop: int = 16384,
    hidden: int = 64,
    episode_len: int = 100,
    gens: int = 3,
    compare_pop: int = 2048,
) -> dict:
    """OpenES + the humanoid-scale walker (244-``hidden``-``hidden``-17 MLP;
    dim 20,945 at hidden 64), big-policy ``fused_planes`` kernel,
    ``rank_based_fitness``, ``gens`` generations by ``wf.run``. The kernel
    must be compiled, and must agree with the scan engine on one shared
    population (tolerance of tests/test_kernels_mlp.py)."""
    wf, adapter = _walker_workflow(pop, hidden, episode_len)
    scan_prob, _ = _walker_problem(hidden, episode_len, fused=False)
    shared = adapter.batched_to_tree(
        0.1 * jax.random.normal(jax.random.PRNGKey(4), (compare_pop, adapter.dim))
    )
    facts = _run_and_compare_engines(wf, gens, scan_prob, shared, 2e-3, "walker")
    return {"dim": adapter.dim, **facts}


def _pendulum_problem(hidden: int, episodes: int, episode_len: int, fused: bool):
    soa = pendulum_soa(max_steps=episode_len)
    env = soa.base
    apply, dim = flat_mlp_policy(env.obs_dim, hidden, env.act_dim)
    prob = PolicyRolloutProblem(
        apply,
        env,
        num_episodes=episodes,
        stochastic_reset=False,
        early_exit=False,
        fused_env=soa if fused else None,
    )
    return prob, dim


def phase_pendulum(
    pop: int = 65536,
    episodes: int = 2,
    hidden: int = 16,
    episode_len: int = 200,
    gens: int = 3,
    compare_pop: int = 8192,
) -> dict:
    """OpenES + pendulum, small-policy ``fused_env`` kernel: same two
    assertions as the walker (tolerance of tests/test_kernels.py)."""
    prob, dim = _pendulum_problem(hidden, episodes, episode_len, fused=True)
    algo = OpenES(jnp.zeros(dim), pop, learning_rate=0.05, noise_stdev=0.05)
    wf = StdWorkflow(algo, prob, opt_direction="max")
    scan_prob, _ = _pendulum_problem(hidden, episodes, episode_len, fused=False)
    shared = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (compare_pop, dim))
    facts = _run_and_compare_engines(wf, gens, scan_prob, shared, 2e-4, "pendulum")
    return {"dim": dim, **facts}


def phase_nsga2(pop: int = 10000, d: int = 300, m: int = 3, gens: int = 3) -> dict:
    """NSGA-II / LSMOP1 by ``wf.run``: fitness finite, rank-0 front non-empty."""
    prob = LSMOP1(d=d, m=m)
    lb, ub = prob.bounds()
    wf = StdWorkflow(NSGA2(lb=lb, ub=ub, n_objs=m, pop_size=pop), prob)
    state = wf.run(wf.init(jax.random.PRNGKey(1)), gens)
    fit = state.algo.fitness
    _finite(fit, "NSGA-II fitness")
    front = int(jnp.sum(state.algo.rank == 0))
    if fit.shape != (pop, m) or int(state.generation) != gens or front < 1:
        raise AssertionError(
            f"NSGA-II: fitness {fit.shape}, generation {int(state.generation)}, "
            f"rank-0 front of {front}"
        )
    return {"rank0_front": front}


class _HostSphere(Problem):
    """Sphere evaluated by NumPy on the host: the jitted step reaches it only
    through ``jax.pure_callback``."""

    jittable = False

    def evaluate(self, state, pop):
        return np.sum(np.asarray(pop) ** 2, axis=1).astype(np.float32), state


def phase_host_callbacks(pop: int = 4096, dim: int = 64, gens: int = 8) -> dict:
    """Host callbacks from inside compiled programs: ``io_callback`` (the
    full-history EvalMonitor inside the fused run) and ``pure_callback``
    (an external problem inside the jitted step)."""
    algo = PSO(lb=-10 * jnp.ones(dim), ub=10 * jnp.ones(dim), pop_size=pop)
    monitor = EvalMonitor(full_fit_history=True)
    wf = StdWorkflow(algo, Sphere(), monitors=(monitor,))
    state = wf.run(wf.init(jax.random.PRNGKey(3)), gens)
    jax.block_until_ready(state)
    jax.effects_barrier()
    history = monitor.get_fitness_history()
    if len(history) != gens or any(np.shape(h) != (pop,) for h in history):
        raise AssertionError(
            f"io_callback history: {len(history)} entries of shapes "
            f"{sorted({np.shape(h) for h in history})}, expected {gens} x ({pop},)"
        )
    best = float(monitor.get_best_fitness(state.monitors[0]))
    _close(min(float(np.min(h)) for h in history), best, 1e-6, 1e-6,
           "io_callback history vs on-device best")

    wf_host = StdWorkflow(algo, _HostSphere())  # external: not jittable
    wf_dev = StdWorkflow(algo, Sphere())
    s_host = wf_host.init(jax.random.PRNGKey(7))
    s_dev = wf_dev.init(jax.random.PRNGKey(7))
    for _ in range(gens):
        s_host, s_dev = wf_host.step(s_host), wf_dev.step(s_dev)
    err = _close(s_host.algo.pbest_fitness, s_dev.algo.pbest_fitness, 1e-5, 1e-5,
                 "pure_callback problem vs the on-device problem")
    return {"io_callback_generations": len(history), "pure_callback_max_abs_err": err}


def phase_resume(
    directory: Path,
    pop: int = 4096,
    dim: int = 256,
    total: int = 20,
    every: int = 5,
    crash_at: int = 12,
) -> dict:
    """Crash-safe resume: a checkpointed run stopped at ``crash_at``, then
    ``wf.resume`` from a fresh workflow to ``total``, equals the straight run."""
    if directory.exists():  # snapshots of an earlier smoke run would be adopted
        shutil.rmtree(directory)
    key = jax.random.PRNGKey(11)
    wf = _cso(pop, dim)
    straight = wf.run(wf.init(key), total)
    wf.run(wf.init(key), crash_at, checkpointer=WorkflowCheckpointer(str(directory), every=every))
    fresh = _cso(pop, dim)
    resumed = fresh.resume(WorkflowCheckpointer(str(directory), every=every), total)
    if int(resumed.generation) != total:
        raise AssertionError(f"resumed to generation {int(resumed.generation)}")
    err = _close_fields(
        resumed.algo, straight.algo, _CSO_FIELDS, 1e-5, 1e-5, "resume vs straight run"
    )
    snapshots = sorted(p.name for p in directory.glob("ckpt_*"))
    return {"max_abs_err": err, "snapshots": snapshots}


# ----------------------------------------------------- the multi-chip phase


def _compiled_run_text(wf: StdWorkflow, state) -> str:
    """The compiled text of the steady (``first_step=False``) fused-run
    program: the init-generation peel of a row-wise problem rightly holds no
    collective."""
    fn, args = wf.analysis_targets(state)["run"]
    return fn.lower(*args).compile().as_text()


def _need(counts: dict, name: str, what: str) -> None:
    if counts[name] < 1:
        raise AssertionError(f"{what}: no {name} in the compiled run program: {counts}")


def multichip_cso(mesh, pop: int = 4096, dim: int = 1024, gens: int = 5) -> dict:
    """CSO sharded over ``"pop"`` == the single-device run (README's promise)."""
    n = mesh.devices.size
    wf_sh, wf_one = _cso(pop, dim, mesh=mesh), _cso(pop, dim)
    key = jax.random.PRNGKey(42)
    s_sh, s_one = wf_sh.run(wf_sh.init(key), gens), wf_one.run(wf_one.init(key), gens)
    for name in _CSO_FIELDS:
        _assert_spread(getattr(s_sh.algo, name), n, f"CSO {name}")
    err = _close_fields(
        s_sh.algo, s_one.algo, _CSO_FIELDS, 1e-5, 1e-5, "CSO sharded vs single device"
    )
    counts = _collective_counts(_compiled_run_text(wf_sh, s_sh))
    _need(counts, "all-gather", "CSO")
    return {"max_abs_err": err, "collectives": counts}


def multichip_walker(
    mesh, pop: int = 16384, hidden: int = 64, episode_len: int = 100, gens: int = 3
) -> dict:
    """The walker with ``eval_shard_map=True`` — each device runs the kernel
    on its shard of the candidates, fitness all-gathered — == single device."""
    n = mesh.devices.size
    wf_sh, _ = _walker_workflow(pop, hidden, episode_len, mesh=mesh, eval_shard_map=True)
    wf_one, _ = _walker_workflow(pop, hidden, episode_len)
    key = jax.random.PRNGKey(1)
    s_sh, s_one = wf_sh.run(wf_sh.init(key), gens), wf_one.run(wf_one.init(key), gens)
    _finite(s_sh.algo.center, "sharded walker center")
    err = _close(s_sh.algo.center, s_one.algo.center, 1e-4, 1e-4,
                 "walker eval_shard_map vs single device")
    # OpenES keeps no population leaf (tell replays the noise from its key):
    # the strategy state is replicated on every device, and the spread of the
    # work shows in the program — the all-gather of the fitness, and a kernel
    # whose weight operand holds one shard of the members, not all of them
    if len(s_sh.algo.center.sharding.device_set) != n:
        raise AssertionError("walker: center is not on every device of the mesh")
    text = _compiled_run_text(wf_sh, s_sh)
    counts = _collective_counts(text)
    _need(counts, "all-gather", "walker")
    obs_dim = wf_sh.problem.env.obs_dim
    kernel_lanes = sorted({
        int(m.group(1))
        for line in text.splitlines() if "tpu_custom_call" in line
        for m in re.finditer(rf"f32\[{obs_dim},{hidden},(\d+)\]", line)
    })
    if jax.default_backend() != "cpu" and kernel_lanes != [pop // n]:
        raise AssertionError(
            f"walker: kernel weight operands hold {kernel_lanes} members per "
            f"device, expected [{pop // n}]"
        )
    return {"max_abs_err": err, "collectives": counts, "kernel_members_per_device": kernel_lanes}


def multichip_sharded_es(mesh, pop: int = 32768, dim: int = 64, gens: int = 5) -> dict:
    """``ShardedES(SepCMAES)`` sharded == the same sampling law replicated."""
    n = mesh.devices.size

    def build(mesh_arg):
        algo = ShardedES(
            SepCMAES(center_init=jnp.full(dim, 2.0), init_stdev=1.0, pop_size=pop),
            mesh=mesh_arg,
            n_shards=n,
        )
        return StdWorkflow(algo, Sphere(), mesh=mesh_arg)

    wf_sh, wf_rep = build(mesh), build(None)
    key = jax.random.PRNGKey(2)
    s_sh, s_rep = wf_sh.run(wf_sh.init(key), gens), wf_rep.run(wf_rep.init(key), gens)
    _assert_spread(s_sh.algo.z, n, "SepCMAES z")
    err = _close_fields(
        s_sh.algo, s_rep.algo, ("mean", "C", "sigma"), 1e-4, 1e-4,
        "ShardedES sharded vs replicated",
    )
    counts = _collective_counts(_compiled_run_text(wf_sh, s_sh))
    _need(counts, "all-reduce", "ShardedES")
    return {"max_abs_err": err, "collectives": counts}


# --------------------------------------------------------------------- main


class _CompileClock:
    """Splits a phase's wall time into compiling and running without running
    anything twice: jax reports the time span of every trace, lowering and
    backend compile (or cache retrieval), and a persistent-cache hit, as
    monitoring events. Nested traces report nested spans, so a phase's
    compile time is the length of the union of its spans."""

    _SPANS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.spans: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        if event in self._SPANS:
            self.spans.append((start, end))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @staticmethod
    def _union_seconds(spans: list) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(spans):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total

    def run(self, name: str, fn, *args, **sizes) -> None:
        n0, h0, t0 = len(self.spans), self.cache_hits, time.perf_counter()
        facts = fn(*args, **sizes)
        wall = time.perf_counter() - t0
        compile_s = self._union_seconds(self.spans[n0:])
        line = {
            "phase": name,
            "compile_s": round(compile_s, 2),
            "run_s": round(wall - compile_s, 2),
            "cache_hits": self.cache_hits - h0,
        }
        stats = jax.devices()[0].memory_stats() or {}  # None on the CPU
        for key in ("peak_bytes_in_use", "largest_alloc_size", "bytes_limit"):
            if key in stats:
                line[key] = stats[key]
        print(json.dumps({**line, **facts}), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the multi-chip phase, one process driving four chips",
    )
    args = parser.parse_args(argv)
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: jax found platform {devices[0].platform!r} "
            f"({devices[0].device_kind}), not a TPU; this script has no CPU mode",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but jax found {len(devices)} device(s)",
            file=sys.stderr,
        )
        return 1
    print(json.dumps({"compile_cache_dir": cache_dir, "jax": jax.__version__}), flush=True)

    clock = _CompileClock()
    if args.chips == 4:
        mesh = create_mesh(devices=devices[:4])
        clock.run("multichip_cso", multichip_cso, mesh)
        clock.run("multichip_walker", multichip_walker, mesh)
        clock.run("multichip_sharded_es", multichip_sharded_es, mesh)
    else:
        clock.run("quickstart", phase_quickstart)
        clock.run("run_equals_step", phase_run_equals_step)
        clock.run("walker", phase_walker)
        clock.run("pendulum", phase_pendulum)
        clock.run("nsga2", phase_nsga2)
        clock.run("host_callbacks", phase_host_callbacks)
        clock.run("resume", phase_resume, OUT_DIR / "resume_ckpt")

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Humanoid-scale neuroevolution: OpenES on the chain_walker env with the
big-policy fused rollout kernel.

The workload shape of the north-star benchmark (BASELINE.md; reference
brax.py:45-97 is the engine it replaces): obs=244, act=17, a 2-hidden
MLP of ~21k parameters per individual, contact physics, termination on
falling. The fused kernel (kernels/rollout_mlp.py) keeps each tile of
individuals' full weight matrices resident in VMEM for the whole episode.
This is the benchmark's `walker_openes_pop65k` cell at another population;
what the chip says of it is in PERF.md sections 4 and 5.

Run (on the TPU, from the root of the checkout):
    PYTHONPATH=. python examples/humanoid_walker.py
or CPU (slow, interpret-mode kernel):
    PYTHONPATH=/root/repo JAX_PLATFORMS=cpu \
        python examples/humanoid_walker.py --pop 256 --gens 5
"""

import argparse

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import OpenES
from evox_tpu.kernels.rollout_mlp import chain_walker_planes
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, mlp_policy
from evox_tpu.utils import TreeAndVector, enable_compile_cache, rank_based_fitness


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pop", type=int, default=8192)
    ap.add_argument("--gens", type=int, default=50)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--episode-len", type=int, default=200)
    ap.add_argument(
        "--rank", type=int, default=0,
        help="low-rank factorize the input layer (0 = dense): fewer "
        "MACs a step and a smaller genome",
    )
    args = ap.parse_args()

    penv = chain_walker_planes(max_steps=args.episode_len)
    env = penv.base
    if args.rank:
        sizes = (env.obs_dim, args.rank, args.hidden, args.hidden, env.act_dim)
        linear = (0,)
    else:
        sizes = (env.obs_dim, args.hidden, args.hidden, env.act_dim)
        linear = ()
    init_params, apply = mlp_policy(sizes, linear_layers=linear)
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    print(f"policy dim: {adapter.dim}, pop: {args.pop}")

    prob = PolicyRolloutProblem(
        apply,
        env,
        num_episodes=1,
        stochastic_reset=False,
        fused_planes=penv,
        fused_planes_linear=linear,
    )
    algo = OpenES(
        0.05 * jax.random.normal(jax.random.PRNGKey(1), (adapter.dim,)),
        args.pop,
        learning_rate=0.05,
        noise_stdev=0.05,
    )
    monitor = EvalMonitor()
    wf = StdWorkflow(
        algo,
        prob,
        monitors=(monitor,),
        opt_direction="max",
        pop_transforms=(adapter.batched_to_tree,),
        fit_transforms=(rank_based_fitness,),
    )
    state = wf.init(jax.random.PRNGKey(2))
    blocks = [10] * (args.gens // 10) + ([args.gens % 10] if args.gens % 10 else [])
    for n in blocks:
        state = wf.run(state, n)
        best = float(monitor.get_best_fitness(state.monitors[0]))
        print(f"gen {int(state.generation)}: best episode reward {best:.1f}")

    # render the trained center policy's trajectory via the scan engine
    scan_prob = PolicyRolloutProblem(apply, env)
    traj = scan_prob.visualize(adapter.to_tree(state.algo.center))
    alive = int(traj.length)
    print(f"center policy: survived {alive}/{args.episode_len} steps, "
          f"return {float(traj.rewards.sum()):.1f}")
    stats = jax.devices()[0].memory_stats() or {}  # None on the CPU
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory: {stats['peak_bytes_in_use']} bytes")


if __name__ == "__main__":
    enable_compile_cache()
    main()

"""Neuroevolution: OpenES trains an MLP policy on cartpole, fully on-device
(double-vmapped rollouts inside one jit), then traces the trained policy.

Run: python examples/neuroevolution_cartpole.py
"""

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import OpenES
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, mlp_policy
from evox_tpu.problems.neuroevolution.control import envs
from evox_tpu.utils import TreeAndVector, enable_compile_cache, rank_based_fitness


def main():
    env = envs.cartpole()
    init_params, apply = mlp_policy((env.obs_dim, 16, env.act_dim))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))

    problem = PolicyRolloutProblem(apply, env, num_episodes=4)
    algo = OpenES(
        center_init=jnp.zeros(adapter.dim),
        pop_size=256,
        learning_rate=0.05,
        noise_stdev=0.1,
    )
    monitor = EvalMonitor()
    wf = StdWorkflow(
        algo,
        problem,
        monitors=(monitor,),
        opt_direction="max",  # reward is maximized
        pop_transforms=(adapter.batched_to_tree,),
        fit_transforms=(rank_based_fitness,),  # centered-rank shaping
    )
    state = wf.init(jax.random.PRNGKey(42))
    state = wf.run(state, 40)
    print("best reward:", float(monitor.get_best_fitness(state.monitors[0])))

    # inspect the trained policy: full trajectory of one rollout (the monitor
    # stores candidates post-transform, i.e. already as param pytrees)
    best = monitor.get_best_solution(state.monitors[0])
    traj = problem.visualize(best, key=jax.random.PRNGKey(1))
    print("episode length:", int(traj.length), "return:", float(traj.rewards.sum()))


if __name__ == "__main__":
    enable_compile_cache()
    main()

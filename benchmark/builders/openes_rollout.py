"""OpenES on a policy-rollout problem, through ``StdWorkflow`` (the path
``examples/humanoid_walker.py`` builds): the fused big-policy kernel,
rank-based fitness, maximise.

Reads from the configuration: ``policy_sizes``, ``episode_len``,
``episodes``, ``learning_rate``, ``noise_stdev``, ``center_init_std``,
``env``. From the traffic mix: ``pop`` and ``mesh_devices``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.build import Built, key_from_seed, make_mesh


def build(config: dict, traffic: dict, seed: int, devices: list) -> Built:
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.kernels.rollout_mlp import chain_walker_planes
    from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, mlp_policy
    from evox_tpu.utils import TreeAndVector, rank_based_fitness

    if config["env"] != "chain_walker":
        raise ValueError(f"openes_rollout knows the chain_walker env, not {config['env']!r}")
    sizes = tuple(config["policy_sizes"])
    penv = chain_walker_planes(max_steps=int(config["episode_len"]))
    env = penv.base
    if (env.obs_dim, env.act_dim) != (sizes[0], sizes[-1]):
        raise ValueError(f"policy {sizes} does not fit env {env.obs_dim}->{env.act_dim}")
    init_params, apply = mlp_policy(sizes)
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    weight_dtype = config.get("kernel_weight_dtype")  # None: f32, as the configuration states
    problem = PolicyRolloutProblem(
        apply,
        env,
        num_episodes=int(config["episodes"]),
        stochastic_reset=False,
        fused_planes=penv,
        fused_planes_dtype=jnp.dtype(weight_dtype) if weight_dtype else None,
    )
    key = key_from_seed(seed)
    center = float(config["center_init_std"]) * jax.random.normal(
        jax.random.fold_in(key, 1), (adapter.dim,)
    )
    pop = int(traffic["pop"])
    algo = OpenES(
        center,
        pop,
        learning_rate=float(config["learning_rate"]),
        noise_stdev=float(config["noise_stdev"]),
    )
    mesh = make_mesh(traffic, devices)
    wf = StdWorkflow(
        algo,
        problem,
        opt_direction="max",
        pop_transforms=(adapter.batched_to_tree,),
        fit_transforms=(rank_based_fitness,),
        mesh=mesh,
        eval_shard_map=bool(traffic.get("eval_shard_map", False)) and mesh is not None,
    )
    return Built(wf=wf, key=key, pop=pop, snapshot=snapshot)


def snapshot(state) -> dict:
    """What the comparison reads of a state, on the host."""
    return {
        "generation": int(state.generation),
        "center": np.asarray(state.algo.center),
    }

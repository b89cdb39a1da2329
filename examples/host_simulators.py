"""Host-side simulators and validation mode.

Three things the on-device quickstart doesn't show:

1. The native C++ vectorized env engine (``NativeVectorEnv`` — the
   built-in EnvPool analog, compiled with g++ on first use) stepped from
   inside jit through ``HostEnvProblem``'s ``io_callback`` episode loop.
2. Supervised neuroevolution on a host data stream (``DatasetProblem``).
3. Validation mode: scoring the current population on held-out data with
   ``StdWorkflow.validate`` without advancing training.

Host callbacks need a local backend (CPU here); see docs/GUIDE.md §7.

Run: python examples/host_simulators.py
"""

import numpy as np

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import OpenES
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.neuroevolution import (
    HostEnvProblem,
    NativeVectorEnv,
    NumpyCartPoleVec,
    mlp_policy,
    native_available,
)
from evox_tpu.problems.supervised import DatasetProblem, InMemoryDataLoader
from evox_tpu.utils import TreeAndVector, enable_compile_cache


def host_env_cartpole():
    pop = 32
    init_params, apply = mlp_policy((4, 8, 2))
    adapter = TreeAndVector(init_params(jax.random.PRNGKey(0)))
    if native_available():
        env = NativeVectorEnv("cartpole", pop, max_steps=200, num_threads=2)
        print("using the native C++ engine")
    else:
        env = NumpyCartPoleVec(num_envs=pop, max_steps=200)
        print("no C++ toolchain; using the numpy engine")
    monitor = EvalMonitor()
    wf = StdWorkflow(
        PSO(lb=-2.0 * jnp.ones(adapter.dim), ub=2.0 * jnp.ones(adapter.dim), pop_size=pop),
        HostEnvProblem(apply, env, cap_episode_length=200),
        monitors=(monitor,),
        opt_direction="max",
        pop_transforms=(adapter.batched_to_tree,),
    )
    state = wf.init(jax.random.PRNGKey(1))
    for _ in range(15):
        state = wf.step(state)
    print("cartpole best reward:", float(monitor.get_best_fitness(state.monitors[0])))


def supervised_with_validation():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(8,))

    def make_split(seed, n):
        r = np.random.default_rng(seed)
        X = r.normal(size=(n, 8)).astype(np.float32)
        return {"x": X, "y": (X @ w_true).astype(np.float32)}

    prob = DatasetProblem(
        InMemoryDataLoader(make_split(1, 512), batch_size=64, seed=3),
        lambda w, b: jnp.mean((b["x"] @ w - b["y"]) ** 2),
        valid_iterator=InMemoryDataLoader(make_split(2, 256), batch_size=128, seed=4),
    )
    wf = StdWorkflow(
        OpenES(jnp.zeros(8), 128, learning_rate=0.1, noise_stdev=0.2), prob
    )
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 150)
    print("train-batch MSE :", float(wf.validate(state).mean()))
    print("held-out MSE    :", float(wf.validate(state, problem=prob.valid()).mean()))
    mae = prob.valid(metric=lambda w, b: jnp.mean(jnp.abs(b["x"] @ w - b["y"])))
    print("held-out MAE    :", float(wf.validate(state, problem=mae).mean()))


if __name__ == "__main__":
    enable_compile_cache()
    host_env_cartpole()
    supervised_with_validation()

"""Quickstart: PSO on Ackley — the canonical ask-evaluate-tell loop.

Run: python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.numerical import Ackley
from evox_tpu.utils import enable_compile_cache


def main():
    dim = 10
    algo = PSO(lb=-32.0 * jnp.ones(dim), ub=32.0 * jnp.ones(dim), pop_size=256)
    monitor = EvalMonitor(topk=3)
    wf = StdWorkflow(algo, Ackley(), monitors=(monitor,))

    state = wf.init(jax.random.PRNGKey(0))

    # step-at-a-time (each step is one jitted generation)...
    for _ in range(10):
        state = wf.step(state)
    print("after 10 gens:", float(monitor.get_best_fitness(state.monitors[0])))

    # ...or fuse many generations into ONE compiled program
    state = wf.run(state, 190)
    print("after 200 gens:", float(monitor.get_best_fitness(state.monitors[0])))
    print("top-3 fitness:", monitor.get_topk_fitness(state.monitors[0]))


if __name__ == "__main__":
    enable_compile_cache()
    main()

"""Multi-objective: NSGA-II on ZDT1 with a running Pareto archive, IGD
against the true front, and an objective-space plot.

Run: python examples/multi_objective.py
"""

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.mo import NSGA2
from evox_tpu.metrics import igd
from evox_tpu.monitors import EvalMonitor, PopMonitor
from evox_tpu.problems.numerical import ZDT1
from evox_tpu.utils import enable_compile_cache


def main():
    dim = 12
    prob = ZDT1(n_dim=dim)
    algo = NSGA2(jnp.zeros(dim), jnp.ones(dim), n_objs=2, pop_size=100)
    archive = EvalMonitor(multi_obj=True, pf_capacity=256)
    history = PopMonitor(fitness_only=True)
    wf = StdWorkflow(algo, prob, monitors=(archive, history))

    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 200)

    pf = archive.get_pf_fitness(state.monitors[0])
    print("archive size:", pf.shape[0])
    print("IGD vs true front:", float(igd(prob.pf(), pf)))

    fig = history.plot(problem_pf=prob.pf())
    fig.savefig("zdt1_front.png", dpi=120)
    print("wrote zdt1_front.png")


if __name__ == "__main__":
    enable_compile_cache()
    main()

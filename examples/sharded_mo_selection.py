"""Multi-chip multi-objective: NSGA-II with BOTH evaluation and the
O(n²) environmental selection sharded over the device mesh.

Passing the mesh to the ALGORITHM (not just the workflow) row-shards the
bit-packed dominance build and every front-peel pass across devices
(operators/selection/non_dominate.py). The sharded SORT's ranks are
bit-identical to the replicated sort (integer computation); the full
workflow is asserted below to match single-device within 1e-5 (float
evaluation reductions may reassociate under GSPMD). On a TPU
slice the per-peel psum rides ICI; here it runs on a virtual 8-device
CPU mesh so the example works anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sharded_mo_selection.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.mo import NSGA2
from evox_tpu.core.distributed import create_mesh
from evox_tpu.metrics import igd
from evox_tpu.problems.numerical import LSMOP1
from evox_tpu.utils import enable_compile_cache


def run(mesh, d, m, pop, gens):
    prob = LSMOP1(d=d, m=m)
    lb, ub = prob.bounds()
    # mesh on the algorithm => sharded selection; mesh on the workflow
    # => sharded evaluation. Use the same mesh for both.
    algo = NSGA2(lb=lb, ub=ub, n_objs=m, pop_size=pop, mesh=mesh)
    wf = StdWorkflow(algo, prob, mesh=mesh, num_objectives=m)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, gens)
    return np.asarray(state.algo.fitness), prob


def main():
    print("devices:", jax.devices())
    mesh = create_mesh()
    d, m, pop, gens = 30, 3, 256, 60

    fit_sharded, prob = run(mesh, d, m, pop, gens)
    fit_single, _ = run(None, d, m, pop, gens)

    np.testing.assert_allclose(fit_sharded, fit_single, rtol=1e-5, atol=1e-5)
    print(f"sharded == single-device: True "
          f"(max |diff| = {np.max(np.abs(fit_sharded - fit_single)):.2e})")
    print(f"IGD after {gens} gens: "
          f"{float(igd(jnp.asarray(fit_sharded), prob.pf())):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()

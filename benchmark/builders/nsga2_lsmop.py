"""NSGA-II (the program's defaults) on an LSMOP problem, through
``StdWorkflow``.

Reads from the configuration: ``problem`` (``LSMOP1`` ...), ``d``, ``m``.
From the traffic mix: ``pop`` and ``mesh_devices``.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.build import Built, key_from_seed, make_mesh


def build(config: dict, traffic: dict, seed: int, devices: list) -> Built:
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.mo import NSGA2
    from evox_tpu.problems import numerical

    problem = getattr(numerical, config["problem"])(d=int(config["d"]), m=int(config["m"]))
    lb, ub = problem.bounds()
    pop = int(traffic["pop"])
    mesh = make_mesh(traffic, devices)
    algo = NSGA2(lb=lb, ub=ub, n_objs=int(config["m"]), pop_size=pop, mesh=mesh)
    wf = StdWorkflow(algo, problem, mesh=mesh)
    return Built(wf=wf, key=key_from_seed(seed), pop=pop, snapshot=snapshot)


def snapshot(state) -> dict:
    """What the comparison reads of a state, on the host."""
    return {
        "generation": int(state.generation),
        "population": np.asarray(state.algo.population),
        "fitness": np.asarray(state.algo.fitness),
        "rank": np.asarray(state.algo.rank),
    }

"""Distributed: the same workflow sharded over a device mesh, with
checkpointing mid-run. On a TPU slice this shards the population across
chips and rides ICI; here it runs on a virtual 8-device CPU mesh so the
example works anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/distributed_mesh.py

For multi-host TPU pods: call evox_tpu.core.distributed.init_distributed()
on every host first, then create_mesh() over jax.devices() — same program.
"""

import os
import tempfile

import jax
import jax.numpy as jnp

from evox_tpu import RunSupervisor, StdWorkflow, WorkflowCheckpointer
from evox_tpu.algorithms.so.pso import PSO
from evox_tpu.core import state_io
from evox_tpu.core.distributed import create_mesh, place_state
from evox_tpu.monitors import EvalMonitor
from evox_tpu.problems.numerical import Ackley
from evox_tpu.utils import enable_compile_cache


def main():
    print("devices:", jax.devices())
    mesh = create_mesh()  # 1-D mesh named "pop" over all devices

    dim = 32
    algo = PSO(lb=-32.0 * jnp.ones(dim), ub=32.0 * jnp.ones(dim), pop_size=512)
    monitor = EvalMonitor()
    # eval_shard_map=True uses an explicit shard_map + all_gather island;
    # the default GSPMD-constraint path gives identical numbers
    wf = StdWorkflow(algo, Ackley(), monitors=(monitor,), mesh=mesh,
                     eval_shard_map=True)

    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 100)
    print("best after 100 gens:", float(monitor.get_best_fitness(state.monitors[0])))

    # checkpoint, restore (optionally into a different mesh), continue
    path = os.path.join(tempfile.mkdtemp(), "ckpt")
    state_io.save(state, path, backend="orbax")
    restored = state_io.load(path, target=state, backend="orbax")
    restored = restored.replace(algo=place_state(restored.algo, mesh))
    restored = wf.run(restored, 100)
    print("best after resume:", float(monitor.get_best_fitness(restored.monitors[0])))

    # production shape (GUIDE.md §6): the same run SUPERVISED — per-chunk
    # wall-clock deadlines, transient-RPC retry, and checkpoint replay; a
    # hung or dropped dispatch heals instead of killing the run. Snapshots are topology-portable: if this 8-device run dies,
    # a 4- or 1-device process resumes it with
    # wf.resume(WorkflowCheckpointer(ckpt_dir), n) on ITS mesh.
    ckpt_dir = os.path.join(tempfile.mkdtemp(), "supervised")
    sup = RunSupervisor(
        checkpointer=WorkflowCheckpointer(ckpt_dir, every=25),
        deadline_s=300.0,  # generous: the first chunk pays the compile
        max_retries=3,
    )
    state = sup.run(wf, wf.init(jax.random.PRNGKey(1)), 100)
    print("supervised best:", float(monitor.get_best_fitness(state.monitors[0])))
    print("supervisor outcome:", sup.report()["outcome"])


if __name__ == "__main__":
    enable_compile_cache()
    main()

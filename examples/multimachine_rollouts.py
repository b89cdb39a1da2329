"""Multi-machine host rollouts: OpenES over a farm of worker PROCESSES.

`ProcessRolloutFarm` is the replacement for the reference's Ray
Supervisor/Worker stack (reference workflows/distributed.py:224-380):
a TCP coordinator shards non-jittable CPU rollouts across worker
processes — started locally below, or on any reachable machine with

    python -m evox_tpu.problems.neuroevolution.process_farm HOST:PORT

The env/policy must be picklable by qualified name (same constraint Ray
puts on remote functions), hence the module-level definitions. Run:

    JAX_PLATFORMS=cpu python examples/multimachine_rollouts.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import OpenES
from evox_tpu.problems.neuroevolution import (
    ProcessRolloutFarm,
    spawn_local_workers,
)
from evox_tpu.problems.neuroevolution.hostenv import NumpyCartPoleVec
from evox_tpu.utils import enable_compile_cache
from evox_tpu.workflows.pipelined import run_host_pipelined

D_IN, D_H, D_OUT = 4, 8, 2
DIM = D_IN * D_H + D_H + D_H * D_OUT + D_OUT


class CartPole:
    """Single-episode gymnasium-API env (picklable by name)."""

    def __init__(self):
        self.vec = NumpyCartPoleVec(num_envs=1, max_steps=200)

    def reset(self, seed=0):
        return self.vec.reset(seed)[0], {}

    def step(self, action):
        obs, r, term, trunc = self.vec.step(np.asarray(action)[None])
        return obs[0], float(r[0]), bool(term[0]), bool(trunc[0]), {}


def policy(params, obs):
    """Flat-genome MLP 4 -> 8 -> 2 (picklable by name)."""
    i = 0
    w1 = params[i : i + D_IN * D_H].reshape(D_IN, D_H); i += D_IN * D_H
    b1 = params[i : i + D_H]; i += D_H
    w2 = params[i : i + D_H * D_OUT].reshape(D_H, D_OUT); i += D_H * D_OUT
    b2 = params[i : i + D_OUT]
    return jnp.tanh(obs @ w1 + b1) @ w2 + b2


def main():
    # the farm is self-healing (GUIDE.md §6 fault tolerance): a worker
    # dying or hanging mid-generation has its slice re-rolled on a
    # survivor (bit-identical fitness), request_timeout bounds every
    # rollout, and replacement workers are re-admitted automatically
    farm = ProcessRolloutFarm(policy, CartPole, num_workers=2,
                              cap_episode=200, host="127.0.0.1",
                              min_workers=1, request_timeout=120.0)
    procs = spawn_local_workers(farm.address, 2)
    farm.bind()
    print(f"2 worker processes bound on {farm.address}")

    algo = OpenES(jnp.zeros(DIM), pop_size=32, learning_rate=0.1,
                  noise_stdev=0.5)
    wf = StdWorkflow(algo, farm, opt_direction="max")
    state = wf.init(jax.random.PRNGKey(0))

    # run_host_pipelined overlaps device ask/tell with the farm round-trip
    # and the on_generation host work; checkpointer= makes the run
    # crash-safe — after a crash, resume with
    #   run_host_pipelined(wf, state, 10, resume_from=<printed dir>)
    import tempfile

    from evox_tpu import WorkflowCheckpointer

    ckpt_dir = tempfile.mkdtemp(prefix="evox_tpu_ckpt_")
    print(f"checkpointing to {ckpt_dir} (resume_from= this path)")
    ckpt = WorkflowCheckpointer(ckpt_dir, every=5, keep=2)
    state = run_host_pipelined(
        wf, state, 10, checkpointer=ckpt,
        on_generation=lambda g, s, f:
            print(f"gen {g}: best episode return {float(jnp.max(f)):.0f}"),
    )
    farm.shutdown()
    for p in procs:
        p.join(timeout=20)


if __name__ == "__main__":
    enable_compile_cache()
    main()

"""Island model: 8 vmapped DE populations with ring migration, sharded
over a device mesh.

Each island evolves independently; every 5 generations its 4 best
candidates of the generation migrate one island around the ring (on a
multi-device mesh the roll on the island axis is a collective permute over
ICI). Compare the spread of per-island bests with and without migration.

Run (virtual 8-device mesh anywhere):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/island_model.py
"""

import jax
import jax.numpy as jnp

from evox_tpu import IslandWorkflow, create_mesh
from evox_tpu.algorithms.so.de import DE
from evox_tpu.problems.numerical import Ackley
from evox_tpu.utils import enable_compile_cache


def run(migrate_every, mesh=None):
    algo = DE(lb=jnp.full((8,), -32.0), ub=jnp.full((8,), 32.0), pop_size=32)
    wf = IslandWorkflow(
        algo,
        Ackley(),
        n_islands=8,
        migrate_every=migrate_every,
        migrate_k=4,
        mesh=mesh,
    )
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 80)
    per_island, best = wf.best(state)
    return per_island, best


def main():
    mesh = create_mesh() if len(jax.devices()) > 1 else None
    if mesh is not None:
        print(f"islands sharded over {len(jax.devices())} devices")
    with_mig, best = run(migrate_every=5, mesh=mesh)
    without, _ = run(migrate_every=10**6, mesh=mesh)
    print("per-island best WITH migration   :", [f"{float(x):.4f}" for x in with_mig])
    print("per-island best WITHOUT migration:", [f"{float(x):.4f}" for x in without])
    print(f"global best: {float(best):.6f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()

"""Compile each cell's ``run`` program at its real shapes for a described
``v5e:2x2``, without a chip, and print what the compiler says of it.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [cell ...]

For each cell: ``memory_analysis()`` of the steady fused-run program
(arguments, outputs, temporaries, for each device), the compile seconds, and
the collectives and custom calls in the compiled text. Nothing runs, so it
says nothing about results or times; what the chip's compiler refuses (a
program that does not fit, a kernel it cannot lower) shows here at no chip
time. Only one process at a time may load the TPU's library: run nothing
else that describes the chip beside it.

Under ``JAX_PLATFORMS=cpu`` a rollout problem would take its interpret branch
(it asks ``jax.default_backend()``) and the step would compile with no kernel
and other bytes. The script sets ``fused_interpret = False`` on the problem it
built and fails unless the compiled text of such a cell holds
``tpu_custom_call``.
"""

import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all", "reduce-scatter")


def rehearse(manifest: dict, name: str, topo) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import manifest as mf

    cell, _, config, traffic = mf.cell_parts(manifest, name)
    builder = importlib.import_module(f"benchmark.builders.{config['builder']}")
    devices = list(topo.devices)[: int(cell["chips"])]
    built = builder.build(config, traffic, 0, devices)
    wf = built.wf
    kernel = hasattr(wf.problem, "fused_interpret")
    if kernel:
        wf.problem.fused_interpret = False
    state = jax.eval_shape(wf.init, built.key).replace(first_step=False)
    if wf.mesh is not None:
        from evox_tpu.core.distributed import state_sharding

        shardings = state_sharding(state, wf.mesh)
    else:
        shardings = jax.tree.map(lambda _: SingleDeviceSharding(devices[0]), state)
    shapes = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), state, shardings
    )
    trips = jax.ShapeDtypeStruct((), jnp.int32, sharding=jax.tree.leaves(shardings)[0]
                                 if wf.mesh is None else
                                 jax.sharding.NamedSharding(wf.mesh, jax.sharding.PartitionSpec()))
    t0 = time.perf_counter()
    compiled = wf._run_loop.lower(shapes, trips).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    if kernel and "tpu_custom_call" not in text:
        raise SystemExit(f"{cell['name']}: the compiled run program holds no tpu_custom_call")
    stats = compiled.memory_analysis()
    total = stats.argument_size_in_bytes + stats.output_size_in_bytes + stats.temp_size_in_bytes
    return {
        "cell": cell["name"],
        "devices": len(devices),
        "compile_s": round(seconds, 1),
        "argument_bytes_per_device": int(stats.argument_size_in_bytes),
        "output_bytes_per_device": int(stats.output_size_in_bytes),
        "temp_bytes_per_device": int(stats.temp_size_in_bytes),
        "alias_bytes_per_device": int(stats.alias_size_in_bytes),
        "total_bytes_per_device": int(total - stats.alias_size_in_bytes),
        "share_of_16e9": round((total - stats.alias_size_in_bytes) / 16e9, 3),
        "custom_calls": text.count("tpu_custom_call"),
        "collectives": {
            name: len(re.findall(rf"\b{name}(?:-start)?\(", text)) for name in COLLECTIVES
        },
    }


def main(argv: list) -> int:
    import jax
    from jax.experimental import topologies

    from benchmark.lib import manifest as mf

    jax.config.update("jax_enable_compilation_cache", False)  # cannot be read back without a chip
    manifest = mf.load()
    names = argv or [w["name"] for w in manifest["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(rehearse(manifest, name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

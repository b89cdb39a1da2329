"""Readings that the limits of ``correct`` are set from, taken on the chip at
a cell's own size. Not part of a benchmark run; the driver never calls it.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --what program,control,faults

- ``program``: for each seed the program's first chunks (the window's own
  call) against the plain reference: the lower readings;
- ``control``: the reference computed in bfloat16, put in the program's
  place, against the reference as the configuration states it: the upper
  readings;
- ``faults``: the program with each fault of benchmark/lib/faults.py planted
  that the cell can have, against the reference;
- ``program_low``: the program with its own lower-precision path switched on
  (the configuration's ``low_precision_path``), against the reference: where
  the program has such a path, it is the control that a later PR is most
  tempted by.

One process for all seeds: the programs compile once. Prints one JSON line a
reading: ``{"what", "seed", "numbers"}``.
"""

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def program_snaps(builder, config, traffic, seed, devices, fault=None):
    from benchmark.lib import faults, harness

    built = builder.build(config, traffic, seed, devices)
    if fault:
        built = faults.FAULTS[fault](built)
    return harness.check_chunks(built, int(traffic["check_steps"]))[1]


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--what", default="program,control,faults")
    parser.add_argument("--faults", default="", help="comma-separated; default: the configuration's")
    args = parser.parse_args(argv)

    import jax

    from benchmark.lib import harness
    from benchmark.lib import manifest as mf

    manifest = mf.load()
    cell, entry, config, traffic = mf.cell_parts(manifest, args.workload)
    harness.place_compile_cache()
    devices = harness.device_gate(int(cell["chips"]))
    builder = importlib.import_module(f"benchmark.builders.{config['builder']}")
    reference = importlib.import_module(f"benchmark.reference.{entry['name']}")
    generations = [harness.CHECK_GENS * (i + 1) for i in range(int(traffic["check_steps"]))]
    what = args.what.split(",")
    plant = [f for f in args.faults.split(",") if f] or list(config["faults"])

    def say(kind, seed, numbers, t0):
        print(json.dumps({"what": kind, "seed": seed, "numbers": numbers,
                          "seconds": round(time.perf_counter() - t0, 1)}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        def against_reference(snaps):
            want = reference.follow(config, traffic, seed, generations, program=snaps)
            return reference.numbers(config, snaps, want)

        if "program" in what:
            say("program", seed, against_reference(program_snaps(builder, config, traffic, seed, devices)), t0)
        if "program_low" in what and config.get("low_precision_path"):
            t0 = time.perf_counter()
            low_config = {**config, **config["low_precision_path"]}
            say("program_low", seed,
                against_reference(program_snaps(builder, low_config, traffic, seed, devices)), t0)
        if "control" in what:
            t0 = time.perf_counter()
            low = reference.follow(config, traffic, seed, generations, precision="bfloat16")
            say("control", seed, against_reference(low), t0)
        if "faults" in what:
            for fault in plant:
                t0 = time.perf_counter()
                snaps = program_snaps(builder, config, traffic, seed, devices, fault)
                say(f"fault:{fault}", seed, against_reference(snaps), t0)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

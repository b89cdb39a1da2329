"""A checkout of the benchmark at tiny sizes, for the CPU tests: the real
``BENCHMARK.json`` and the real files under ``benchmark/``, with the sizes in
the configuration and traffic files cut so that a run takes seconds. Code is
imported from the real package; only data is read from the copy."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "openes_walker": {"policy_sizes": [244, 8, 8, 17], "genome_dim": 2121, "episode_len": 12},
    "nsga2_lsmop1": {"d": 32},
}
TINY_TRAFFIC = {
    "closed_pop65k_g2": {"pop": 256},
    "closed_pop50k_g4": {"pop": 200},
    "closed_pop131k_g2_mesh4": {"pop": 1024},
}


# The four-chip cell that BENCHMARK.json leaves out (PERF.md, Open questions,
# row 1). Its traffic file and its reader are kept, and the tiny checkout
# enters it, so that the harness's mesh path stays driven on the CPU's
# virtual devices and a later PR can bring the cell back by entries alone.
MESH_CELL = {
    "name": "walker_openes_pop131k_4chip",
    "config": "openes_walker",
    "traffic": "closed_pop131k_g2_mesh4",
    "chips": 4,
    "why": "closed loop, pop 131072 sharded over a 4-device pop mesh with eval_shard_map, two generations a chunk",
}
MESH_METRIC = {
    "name": "collective_time_share", "unit": "%", "better": "lower", "source": "device_trace",
    "layer": "mesh / collectives", "moves": "evals_per_s", "workloads": [MESH_CELL["name"]],
}


def with_mesh_cell(manifest: dict) -> dict:
    """``manifest`` with the mesh cell entered beside the walker's cell."""
    beside = next(w["name"] for w in manifest["workloads"] if w["config"] == MESH_CELL["config"])
    manifest["workloads"].append(dict(MESH_CELL))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if beside in metric.get("workloads", ()):
            metric["workloads"].append(MESH_CELL["name"])
    manifest["per_layer"].append(dict(MESH_METRIC))
    return manifest


def tiny_checkout(tmp: Path) -> Path:
    """Copy the benchmark into ``tmp``, enter the mesh cell and cut the
    sizes; returns the root."""
    manifest = with_mesh_cell(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    shutil.copytree(
        ROOT / "benchmark", tmp / "benchmark", ignore=shutil.ignore_patterns("__pycache__")
    )
    (tmp / "tests" / "benchmark_checks").mkdir(parents=True)
    for folder, cuts in (("configs", TINY_CONFIG), ("traffic", TINY_TRAFFIC)):
        for name, cut in cuts.items():
            path = tmp / "benchmark" / folder / f"{name}.json"
            data = json.loads(path.read_text())
            data.update(cut)
            path.write_text(json.dumps(data))
    return tmp

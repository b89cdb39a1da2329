"""``correct`` is shown to fail: the control (the plain reference computed in
bfloat16, put in the program's place) and each fault a cell can have, planted
under the timed path, at sizes a test run can hold. The same readings are
taken on the chip at the cells' own sizes by ``benchmark/control.py``."""

from __future__ import annotations

import importlib
import json
import time

import jax
import pytest

import _bench_tiny
from benchmark.lib import faults, harness, manifest as mf
from test_harness import on_cpu  # noqa: F401  (the fixture)

MANIFEST = _bench_tiny.with_mesh_cell(mf.load())  # as the tiny checkout holds it
CASES = [
    (w["name"], fault)
    for w in MANIFEST["workloads"]
    for fault in mf.load_json(mf.ROOT, mf.config_entry(MANIFEST, w["config"])["file"])["faults"]
]


def _tiny(tmp_path, cell: str) -> tuple:
    root = _bench_tiny.tiny_checkout(tmp_path)
    _, entry, config, traffic = mf.cell_parts(MANIFEST, cell, root)
    return root, entry, config, traffic


@pytest.mark.parametrize("config_name", [c["name"] for c in MANIFEST["configs"]])
def test_control_is_not_correct(config_name, tmp_path):
    cell = next(w["name"] for w in MANIFEST["workloads"] if w["config"] == config_name)
    _, entry, config, traffic = _tiny(tmp_path, cell)
    reference = importlib.import_module(f"benchmark.reference.{entry['name']}")
    generations = [1, 2, 3]
    for seed in (3, 2**31 + 5, 2**33 + 1):
        def against_reference(snaps):
            want = reference.follow(config, traffic, seed, generations, program=snaps)
            return harness.compare(reference.numbers(config, snaps, want), config["limits"])

        same = reference.follow(config, traffic, seed, generations)
        low = reference.follow(config, traffic, seed, generations, precision="bfloat16")
        assert against_reference(same)[1] is True
        compared, correct = against_reference(low)
        assert correct is False, compared


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_under_the_timed_path_is_not_correct(cell, fault, tmp_path, capsys, monkeypatch, on_cpu):  # noqa: F811
    root, _, config, _ = _tiny(tmp_path, cell)
    builder = importlib.import_module(f"benchmark.builders.{config['builder']}")
    build = builder.build
    monkeypatch.setattr(builder, "build", lambda *a, **k: faults.FAULTS[fault](build(*a, **k)))
    rc = harness.run_cell(
        ["--workload", cell, "--seed", "12345", "--seconds", "0.3", "--trace", "0"],
        time.perf_counter(), root=root,
    )
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is False, result["compared"]

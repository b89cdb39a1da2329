"""``BENCHMARK.json`` and every data file it names, held to the benchmark's
rules on the CPU, before any chip call: each rule is a case, and each cell,
configuration and per-layer metric is a case of the rules that name files."""

from __future__ import annotations

import copy
import json

import pytest

from benchmark.lib import manifest as mf

MANIFEST = mf.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
LAYER_METRICS = [m["name"] for m in MANIFEST["per_layer"]]


@pytest.mark.parametrize("rule", sorted(mf.RULES))
def test_rule_holds(rule):
    assert mf.RULES[rule](MANIFEST, mf.ROOT) == []


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_strings(cell):
    w = mf.cell(MANIFEST, cell)
    entry = mf.config_entry(MANIFEST, w["config"])
    assert (mf.ROOT / entry["file"]).is_file()
    traffic = json.loads(mf.traffic_file(MANIFEST, w["traffic"]).read_text())
    assert int(traffic["pop"]) > 0 and int(traffic["gens_per_chunk"]) > 0 and int(traffic["check_steps"]) > 0
    assert (mf.bench_dir(MANIFEST) / "reference" / f"{w['config']}.py").is_file()
    assert 1 <= len(w["why"]) <= 200 and w["why"].isascii() and w["why"].isprintable()
    assert (w["chips"] == 4) == (int(traffic.get("mesh_devices", 0)) == 4)
    names = [m["name"] for m in mf.metrics_of(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in names and len(names) >= 2
    assert mf.metrics_of(MANIFEST, "per_layer", cell)


@pytest.mark.parametrize("config", CONFIGS)
def test_config_source_and_limits(config):
    entry = mf.config_entry(MANIFEST, config)
    assert 1 <= len(entry["source"]) <= 200
    assert entry["source"].isascii() and entry["source"].isprintable()
    data = mf.load_json(mf.ROOT, entry["file"])
    assert data["source"] == entry["source"] and data["reduced"] == entry["reduced"]
    assert data["limits"] and all(isinstance(v, (int, float)) for v in data["limits"].values())
    assert (mf.bench_dir(MANIFEST) / "builders" / f"{data['builder']}.py").is_file()


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(metric):
    m = next(e for e in MANIFEST["per_layer"] if e["name"] == metric)
    assert (mf.bench_dir(MANIFEST) / "metrics" / f"{metric}.py").is_file()
    moved = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    reporting = set(moved.get("workloads", CELLS))
    assert set(m.get("workloads", CELLS)) <= reporting


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


BREACHES = {
    "ascii": lambda m: m["configs"][0].__setitem__("source", "d 100×m"),
    "configs": lambda m: m["configs"][0].__setitem__("source", "x" * 201),
    "names": lambda m: m["workloads"][0].__setitem__("name", "a cell"),
    "units_and_better": lambda m: m["end_to_end"][0].__setitem__("unit", "evals per s"),
    "entry_keys": lambda m: m["per_layer"][0].__setitem__("why", "because"),
    "four_chip_share": lambda m: [w.__setitem__("chips", 4) for w in m["workloads"]],
    "end_to_end": lambda m: m["end_to_end"][0].__setitem__("bound", 0.5),
    "per_layer": lambda m: m["per_layer"][0].__setitem__("moves", "nothing"),
    "run_seconds": lambda m: m.__setitem__("run_seconds", 52),
    "files_exist": lambda m: m["per_layer"][0].__setitem__("name", "no_such_reader"),
    "top_level": lambda m: m.__setitem__("extra", 1),
    "workloads": lambda m: m["workloads"][0].__setitem__("config", "no_such_config"),
}


@pytest.mark.parametrize("rule", sorted(BREACHES))
def test_rule_catches_its_breach(rule):
    """PR 22 was refused for one string: each rule is shown to fail."""
    broken = copy.deepcopy(MANIFEST)
    BREACHES[rule](broken)
    assert mf.RULES[rule](broken, mf.ROOT) != []

"""``BENCHMARK.json`` and the data files it names: loading, and the rules they
are held to before any chip call.

``RULES`` maps a rule's name to a function ``(manifest, root) -> [messages]``;
an empty list means the rule holds. ``tests/benchmark_checks/test_manifest.py``
runs each rule as a case, and ``run.py`` refuses a manifest that breaks one.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_size", "expansion")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def load(root: Path = ROOT) -> dict:
    """The manifest at ``root``."""
    with open(Path(root) / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def load_json(root: Path, relative: str) -> dict:
    with open(Path(root) / relative, encoding="utf-8") as f:
        return json.load(f)


def bench_dir(manifest: dict, root: Path = ROOT) -> Path:
    """The directory of ``paths`` that holds the harness (the command's)."""
    return (Path(root) / manifest["command"][1]).parent


def traffic_file(manifest: dict, traffic: str, root: Path = ROOT) -> Path:
    """The data file of a traffic mix, found by its name."""
    folder = bench_dir(manifest, root) / "traffic"
    for suffix in DATA_SUFFIXES:
        if (folder / (traffic + suffix)).is_file():
            return folder / (traffic + suffix)
    raise FileNotFoundError(f"no traffic file {folder}/{traffic}.<json|jsonl|toml|txt|csv>")


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def cell_parts(manifest: dict, name: str, root: Path = ROOT) -> tuple:
    """A cell with what it names: ``(cell, its configs entry, the
    configuration file's contents, the traffic file's contents)``."""
    w = cell(manifest, name)
    entry = config_entry(manifest, w["config"])
    traffic = json.loads(traffic_file(manifest, w["traffic"], root).read_text(encoding="utf-8"))
    return w, entry, load_json(root, entry["file"]), traffic


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that the cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [
        m for m in manifest[group] if "workloads" not in m or cell_name in m["workloads"]
    ]


def _line(s, limit: int = 200) -> bool:
    """1 to ``limit`` printable ASCII characters, on one line, no tab."""
    return isinstance(s, str) and 1 <= len(s) <= limit and all(32 <= ord(c) < 127 for c in s)


def _strings(node, where: str):
    """Every string in a JSON value, keys included, with the path to it."""
    if isinstance(node, str):
        yield where, node
    elif isinstance(node, dict):
        for k, v in node.items():
            yield f"{where}.{k} (key)", k
            yield from _strings(v, f"{where}.{k}")
    elif isinstance(node, list):
        for i, v in enumerate(node):
            yield from _strings(v, f"{where}[{i}]")


def _data_files(manifest: dict, root: Path) -> list:
    files = [Path(root) / c["file"] for c in manifest["configs"]]
    for w in manifest["workloads"]:
        try:
            files.append(traffic_file(manifest, w["traffic"], root))
        except FileNotFoundError:
            pass  # files_exist reports it
    return sorted(set(files))


# ------------------------------------------------------------------- rules


def rule_top_level(m: dict, root: Path) -> list:
    out = []
    if set(m) != TOP_KEYS:
        out.append(f"top-level keys are {sorted(m)}, expected exactly {sorted(TOP_KEYS)}")
    size = (Path(root) / "BENCHMARK.json").stat().st_size
    if size > 64 * 1024:
        out.append(f"BENCHMARK.json is {size} bytes, over 64 KiB")
    return out


def rule_ascii(m: dict, root: Path) -> list:
    """Every string, in the manifest and in every data file, is printable ASCII."""
    out = [
        f"BENCHMARK.json {where}: {s!r}"
        for where, s in _strings(m, "$")
        if not all(32 <= ord(c) < 127 for c in s)
    ]
    for path in _data_files(m, root):
        text = path.read_text(encoding="utf-8")
        bad = sorted({c for c in text if not (32 <= ord(c) < 127 or c == "\n")})
        if bad:
            out.append(f"{path.relative_to(root)}: characters {bad!r}")
    return out


def rule_command_and_paths(m: dict, root: Path) -> list:
    out = []
    paths, command = m.get("paths", []), m.get("command", [])
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        out.append("paths: 1 to 16 directories")
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") or ".." in p.split("/"):
            out.append(f"paths: {p!r} is not a plain relative path")
        elif not (Path(root) / p).is_dir():
            out.append(f"paths: {p!r} is not a directory")
    if not (isinstance(command, list) and 1 <= len(command) <= 32 and all(_line(w) for w in command)):
        out.append("command: a list of 1 to 32 one-line strings")
    for word in command:
        if word.startswith("/") or ".." in word.split("/"):
            out.append(f"command: {word!r} leads out of the repo")
        elif (Path(root) / word).exists() and not any(
            word == p or word.startswith(p + "/") for p in paths
        ):
            out.append(f"command: {word!r} is a file of the repo outside paths")
    for p in paths:
        for f in (Path(root) / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            if not PATH.match(str(f.relative_to(root))):
                out.append(f"file name {f.relative_to(root)} has characters outside a name's")
    return out


def rule_run_seconds(m: dict, root: Path) -> list:
    s = m.get("run_seconds")
    if not (isinstance(s, int) and not isinstance(s, bool) and 1 <= s <= 51):
        return [f"run_seconds is {s!r}, expected a whole number from 1 to 51"]
    # a full check at 24 cells: 2 + 14 * cells runs of s + 60, 180 more a cell, 1200 spare
    need = (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200
    return [] if need <= 43200 else [f"run_seconds {s}: a full check needs {need} s > 43200"]


def rule_names(m: dict, root: Path) -> list:
    out = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in m.get(group, []):
            n = e.get("name")
            if not (isinstance(n, str) and NAME.match(n)):
                out.append(f"{group}: name {n!r}")
            if n in seen:
                out.append(f"{group}: name {n!r} appears twice")
            seen.add(n)
    metric_names = [e.get("name") for g in ("end_to_end", "per_layer") for e in m.get(g, [])]
    if len(metric_names) != len(set(metric_names)):
        out.append("a metric name appears in both end_to_end and per_layer")
    for w in m.get("workloads", []):
        for key in ("config", "traffic"):
            if not (isinstance(w.get(key), str) and NAME.match(w[key])):
                out.append(f"workload {w.get('name')}: {key} {w.get(key)!r}")
    for c in m.get("configs", []):
        for key in c.get("reduced", []):
            if not (isinstance(key, str) and NAME.match(key)):
                out.append(f"config {c.get('name')}: reduced key {key!r}")
    return out


def rule_units_and_better(m: dict, root: Path) -> list:
    out = []
    for group in ("end_to_end", "per_layer"):
        for e in m.get(group, []):
            if not (isinstance(e.get("unit"), str) and UNIT.match(e["unit"])):
                out.append(f"{group} {e.get('name')}: unit {e.get('unit')!r}")
            if e.get("better") not in ("lower", "higher"):
                out.append(f"{group} {e.get('name')}: better {e.get('better')!r}")
            if e.get("source") not in SOURCES:
                out.append(f"{group} {e.get('name')}: source {e.get('source')!r}")
    return out


def rule_entry_keys(m: dict, root: Path) -> list:
    out = []
    wanted = (
        ("configs", CONFIG_KEYS, set()),
        ("workloads", WORKLOAD_KEYS, set()),
        ("end_to_end", E2E_KEYS, {"workloads"}),
        ("per_layer", LAYER_KEYS, {"workloads"}),
    )
    for group, keys, optional in wanted:
        entries = m.get(group, [])
        limit = {"configs": 24, "workloads": 24, "end_to_end": 16, "per_layer": 128}[group]
        if not (isinstance(entries, list) and 1 <= len(entries) <= limit):
            out.append(f"{group}: 1 to {limit} entries")
        for e in entries:
            if not keys <= set(e) <= keys | optional:
                out.append(f"{group} {e.get('name')}: keys {sorted(e)}, expected {sorted(keys)}")
    return out


def rule_configs(m: dict, root: Path) -> list:
    out, files = [], set()
    used = {w.get("config") for w in m.get("workloads", [])}
    for c in m.get("configs", []):
        name = c.get("name")
        if not _line(c.get("source")):
            out.append(f"config {name}: source must be 1 to 200 printable ASCII characters")
        if not _line(c.get("why")):
            out.append(f"config {name}: why must be 1 to 200 printable ASCII characters")
        f = c.get("file", "")
        if not any(f.startswith(p + "/") for p in m.get("paths", [])):
            out.append(f"config {name}: file {f!r} is not under paths")
        if f in files:
            out.append(f"config {name}: file {f!r} is another configuration's")
        files.add(f)
        reduced = c.get("reduced")
        if not (isinstance(reduced, list) and len(reduced) <= 16):
            out.append(f"config {name}: reduced is a list of at most 16 keys")
        for key in reduced or []:
            low = str(key).lower()
            if low.endswith(("_dim", "_rank")) or any(w in low for w in WIDTH_WORDS):
                out.append(f"config {name}: reduced names a width, {key!r}")
        if name not in used:
            out.append(f"config {name}: no workload uses it")
    return out


def rule_workloads(m: dict, root: Path) -> list:
    out, pairs = [], set()
    config_names = {c.get("name") for c in m.get("configs", [])}
    for w in m.get("workloads", []):
        name = w.get("name")
        if w.get("config") not in config_names:
            out.append(f"workload {name}: config {w.get('config')!r} is not in configs")
        if w.get("chips") not in (1, 4):
            out.append(f"workload {name}: chips {w.get('chips')!r}")
        if not _line(w.get("why")):
            out.append(f"workload {name}: why must be 1 to 200 printable ASCII characters")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            out.append(f"workload {name}: the pair {pair} appears twice")
        pairs.add(pair)
    return out


def rule_four_chip_share(m: dict, root: Path) -> list:
    cells = m.get("workloads", [])
    four = sum(1 for w in cells if w.get("chips") == 4)
    allowed = max(1, len(cells) // 4)
    return [] if four <= allowed else [f"{four} cells ask for 4 chips, at most {allowed} may"]


def rule_end_to_end(m: dict, root: Path) -> list:
    out = []
    e2e = m.get("end_to_end", [])
    if not any(e.get("name") == "setup_s" for e in e2e):
        out.append("end_to_end: setup_s is missing")
    for e in e2e:
        b = e.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool) and 0.01 <= b <= 0.1):
            out.append(f"end_to_end {e.get('name')}: bound {b!r} is outside 0.01 to 0.1")
        if e.get("source") not in ("host_clock", "device_trace"):
            out.append(f"end_to_end {e.get('name')}: source {e.get('source')!r}")
    return out


def rule_per_layer(m: dict, root: Path) -> list:
    out = []
    e2e = {e.get("name"): e for e in m.get("end_to_end", [])}
    cells = [w.get("name") for w in m.get("workloads", [])]
    for e in m.get("per_layer", []):
        name = e.get("name")
        if not _line(e.get("layer")):
            out.append(f"per_layer {name}: layer {e.get('layer')!r}")
        if e.get("moves") not in e2e:
            out.append(f"per_layer {name}: moves {e.get('moves')!r} is no end-to-end metric")
            continue
        moved = e2e[e["moves"]]
        reporting = set(moved.get("workloads", cells))
        for w in e.get("workloads", cells):
            if w not in cells:
                out.append(f"per_layer {name}: workload {w!r} is no cell")
            elif w not in reporting:
                out.append(f"per_layer {name}: cell {w!r} does not report {e['moves']}")
    for group in ("end_to_end", "per_layer"):
        for e in m.get(group, []):
            ws = e.get("workloads")
            if ws is not None and not (isinstance(ws, list) and ws and set(ws) <= set(cells)):
                out.append(f"{group} {e.get('name')}: workloads {ws!r}")
    return out


def rule_cells_report(m: dict, root: Path) -> list:
    """Every cell reports setup_s, another end-to-end metric and a per-layer one."""
    out = []
    for w in m.get("workloads", []):
        names = [e["name"] for e in metrics_of(m, "end_to_end", w["name"])]
        if "setup_s" not in names or len(names) < 2:
            out.append(f"cell {w['name']}: end-to-end metrics {names}")
        if not metrics_of(m, "per_layer", w["name"]):
            out.append(f"cell {w['name']}: no per-layer metric")
    return out


def rule_files_exist(m: dict, root: Path) -> list:
    """Every configuration, traffic mix, reference and metric reader is a file
    that the harness finds by the name in the manifest."""
    out = []
    bench = bench_dir(m, root)
    for c in m.get("configs", []):
        if not (Path(root) / c.get("file", "")).is_file():
            out.append(f"config {c.get('name')}: no file {c.get('file')!r}")
        if not (bench / "reference" / f"{c.get('name')}.py").is_file():
            out.append(f"config {c.get('name')}: no reference/{c.get('name')}.py")
    for w in m.get("workloads", []):
        try:
            traffic_file(m, w.get("traffic", ""), root)
        except FileNotFoundError as e:
            out.append(f"workload {w.get('name')}: {e}")
    for e in m.get("per_layer", []):
        if not (bench / "metrics" / f"{e.get('name')}.py").is_file():
            out.append(f"per_layer {e.get('name')}: no metrics/{e.get('name')}.py")
    return out


def rule_config_files(m: dict, root: Path) -> list:
    """A configuration's file states what it runs: builder, precision, source,
    assumed and reduced, the same source and reduced as the manifest."""
    out = []
    for c in m.get("configs", []):
        path = Path(root) / c.get("file", "")
        if not path.is_file():
            continue
        data = json.loads(path.read_text(encoding="utf-8"))
        for key in ("builder", "precision", "source", "assumed", "reduced"):
            if key not in data:
                out.append(f"{c['file']}: no {key!r}")
        if data.get("source") != c.get("source"):
            out.append(f"{c['file']}: source differs from the manifest's")
        if data.get("reduced") != c.get("reduced"):
            out.append(f"{c['file']}: reduced differs from the manifest's")
        builder = bench_dir(m, root) / "builders" / f"{data.get('builder')}.py"
        if not builder.is_file():
            out.append(f"{c['file']}: no builders/{data.get('builder')}.py")
    return out


RULES = {
    "top_level": rule_top_level,
    "ascii": rule_ascii,
    "command_and_paths": rule_command_and_paths,
    "run_seconds": rule_run_seconds,
    "names": rule_names,
    "units_and_better": rule_units_and_better,
    "entry_keys": rule_entry_keys,
    "configs": rule_configs,
    "workloads": rule_workloads,
    "four_chip_share": rule_four_chip_share,
    "end_to_end": rule_end_to_end,
    "per_layer": rule_per_layer,
    "cells_report": rule_cells_report,
    "files_exist": rule_files_exist,
    "config_files": rule_config_files,
}


def problems(manifest: dict, root: Path = ROOT) -> list:
    """Every breach of every rule, as ``"rule: message"`` lines."""
    return [f"{name}: {msg}" for name, rule in RULES.items() for msg in rule(manifest, root)]


if __name__ == "__main__":
    found = problems(load())
    print("\n".join(found) if found else "BENCHMARK.json: every rule holds")
    raise SystemExit(1 if found else 0)

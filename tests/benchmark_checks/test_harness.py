"""The benchmark's command end to end on the CPU at tiny sizes (the device
gate stepped over here, in the test, never by an option of ``run.py``), the
trace reduction on a small recorded ``.xplane.pb``, and the work counts."""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import _bench_tiny
from benchmark.lib import harness, manifest as mf, peaks, trace as tr, work

DATA = Path(__file__).resolve().parent / "data"
MANIFEST = _bench_tiny.with_mesh_cell(mf.load())  # as the tiny checkout holds it
CELLS = [w["name"] for w in MANIFEST["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.fixture
def on_cpu(monkeypatch):
    """Step over the look for a chip, keep the compile cache off (what the
    CPU backend caches it warns about on reading back), and give the CPU
    stand-in peaks: it has none, and a device outside the table raises."""
    monkeypatch.setattr(harness, "device_gate", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")
    monkeypatch.setitem(
        peaks.PEAKS, "cpu",
        {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
         "source": "tests/benchmark_checks stand-in, not a device's peaks"},
    )


def run_tiny(tmp_path, capsys, cell: str, trace: int, seed: int = 2**31 + 11) -> tuple:
    """One run of ``cell`` at tiny sizes; the exit code, the result line and
    the earlier lines."""
    root = _bench_tiny.tiny_checkout(tmp_path)
    rc = harness.run_cell(
        ["--workload", cell, "--seed", str(seed), "--seconds", "0.5", "--trace", str(trace)],
        time.perf_counter(), root=root,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    return rc, lines[-1], lines[:-1], root


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_run_end_to_end(cell, trace, tmp_path, capsys, on_cpu):
    rc, result, earlier, root = run_tiny(tmp_path, capsys, cell, trace)
    assert rc == 0 and result["correct"] is True
    assert set(result) == RESULT_KEYS | ({"breakdown"} & set(result))
    assert list(result)[-1] == "compared"
    assert result["failed"] == 0 and result["attempted"] >= 1
    chips = mf.cell(MANIFEST, cell)["chips"]
    assert result["device"]["count"] == chips and result["device"]["memory_peak_bytes"] > 0
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in mf.metrics_of(MANIFEST, group, cell)}
    assert set(result["metrics"]) <= set(declared)
    assert all(result["metrics"][n]["unit"] == declared[n] for n in result["metrics"])
    if trace:
        # the CPU has no device plane: readers of the device trace find
        # nothing and are left out; the counters and host clocks report
        assert {"compiles_in_window", "chunk_ms_median"} <= set(result["metrics"])
        assert result["metrics"]["compiles_in_window"]["value"] == 0.0
        assert {"busy_s", "window_s"} <= set(result["device"])
    else:
        assert set(result["metrics"]) == set(declared)
        assert all(v["value"] > 0 for v in result["metrics"].values())
    # the window's evaluation count is population times the generations the
    # state's own counter says it ran
    window = next(l for l in earlier if "evals" in l)
    traffic = mf.cell_parts(MANIFEST, cell, root)[3]
    assert window["evals"] == traffic["pop"] * window["generations"]
    assert window["generations"] == window["chunks"] * traffic["gens_per_chunk"]
    assert result["compared"]["window_generations_off"] == {"value": 0.0, "limit": 0}
    assert not (root / ".bench_trace" / cell).exists()


def test_seed_decides_the_inputs(tmp_path, capsys, on_cpu):
    (tmp_path / "a").mkdir(), (tmp_path / "b").mkdir(), (tmp_path / "c").mkdir()
    _, one, _, _ = run_tiny(tmp_path / "a", capsys, "nsga2_lsmop1_pop50k", 0, seed=7)
    _, two, _, _ = run_tiny(tmp_path / "b", capsys, "nsga2_lsmop1_pop50k", 0, seed=7)
    _, other, _, _ = run_tiny(tmp_path / "c", capsys, "nsga2_lsmop1_pop50k", 0, seed=2**32 + 7)
    assert one["compared"] == two["compared"]
    assert one["compared"] != other["compared"]


def test_no_tpu_no_result(tmp_path):
    """The command itself, on this machine without a chip: non-zero, and not
    one line on standard output."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=mf.ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
    )
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(l.startswith("{") and "metrics" in l for l in p.stdout.splitlines())


def test_without_the_program_no_result(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files under
    ``paths``: non-zero, no result."""
    root = _bench_tiny.tiny_checkout(tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


# ----------------------------------------------------------- trace reduction


@pytest.fixture(scope="module")
def small_trace():
    """data/small.xplane.pb, written from data/small.xplane.txt
    (``ProfileData.text_proto_to_serialized_xspace``): two devices and the
    host, nested operations, two names as the TPU writes them (whole HLO
    text), a module line that is not operations."""
    return tr.load(DATA / "small.xplane.pb")


def test_trace_window_and_busy(small_trace):
    lo, hi = small_trace.window()
    assert (lo, hi) == (500.0, 4000.0)
    assert tr.busy_ns(small_trace, lo, hi) == {"/device:TPU:0": 1700.0, "/device:TPU:1": 100.0}
    assert tr.busy_ns(small_trace, 1200.0, 2500.0)["/device:TPU:0"] == 700.0  # clipped


def test_trace_own_time_of_nested_operations(small_trace):
    lo, hi = small_trace.window()
    ops = dict(tr.top_ops(small_trace, "/device:TPU:0", lo, hi))
    assert ops == pytest.approx(
        {"fused_mlp_rollout.3 custom-call": 600e-9, "fusion.1": 400e-9,
         "all-gather.5 all-gather": 300e-9, "fusion.4": 200e-9, "while.2": 200e-9}
    )
    assert "jit_run(1)" not in ops  # the module line is not an operation


def test_trace_idle_gaps_by_what_the_host_did(small_trace):
    lo, hi = small_trace.window()
    gaps = dict(tr.idle_gaps(small_trace, "/device:TPU:0", lo, hi))
    assert gaps == pytest.approx({"bench:chunk": 1300e-9, "outside bench spans": 500e-9})
    assert sum(gaps.values()) == pytest.approx((hi - lo - 1700.0) / 1e9)


def test_trace_readers_on_the_small_trace(small_trace):
    from benchmark.metrics import (
        collective_time_share, device_idle_share, rollout_kernel_busy_share,
        rollout_kernel_roofline, step_hbm_roofline, step_mfu,
    )

    lo, hi = small_trace.window()
    config = mf.load_json(mf.ROOT, "benchmark/configs/openes_walker.json")
    events = tr.clip(small_trace.devices["/device:TPU:0"], lo, hi)
    ctx = harness.TraceContext(
        config=config, traffic={"pop": 2}, chips=2, device_kind="TPU v5 lite",
        window={"evals": 4, "seconds": 3.5e-6, "generations": 2, "chunks": 2, "chunk_ms": [1, 2]},
        compiles_in_window=0, events=events, busy_ns=tr.union_ns(events), stretch_ns=hi - lo,
    )
    assert device_idle_share.read(ctx) == pytest.approx(100 * 1800 / 3500)
    assert collective_time_share.read(ctx) == pytest.approx(100 * 300 / 3500)
    assert rollout_kernel_busy_share.read(ctx) == pytest.approx(100 * 600 / 1700)
    # two evaluations on this device, bytes-bound: 2 * 83,780 B / 819 GB/s over 600 ns
    assert rollout_kernel_roofline.read(ctx) == pytest.approx(100 * (2 * 83780 / 819e9) / 600e-9)
    # the whole step's shares stand on the traced stretch (3500 ns), not on
    # the host's clock (3.5e-6 s here only by the fixture's choice: halve it)
    ctx.window["seconds"] = 1.75e-6
    assert step_mfu.read(ctx) == pytest.approx(100 * (4 * 9_820_000 / 3500e-9) / (2 * 197e12))
    assert step_hbm_roofline.read(ctx) is None  # no ``d``: not this configuration's
    ctx.config = mf.load_json(mf.ROOT, "benchmark/configs/nsga2_lsmop1.json")
    assert step_hbm_roofline.read(ctx) == pytest.approx(
        100 * (4 * 2 * 300 * 4 / (2 * 819e9)) / (3500e-9 / 2)
    )
    assert step_mfu.read(ctx) is None
    ctx.config = dict(config, kernel_event_pattern="no_such_kernel")
    assert rollout_kernel_roofline.read(ctx) is None  # nothing to read: nothing, never 0
    ctx.events = []  # no device plane (the CPU): the device's shares say nothing
    assert step_mfu.read(ctx) is None


def test_trace_recorded_on_the_chip():
    """data/walker_pop65k_3s.xplane.pb: a three-second window of
    walker_openes_pop65k at one generation a chunk, recorded on the TPU v5e
    (my chip run, PR 25). The reduction gives what that run printed."""
    t = tr.load(DATA / "walker_pop65k_3s.xplane.pb")
    lo, hi = t.window()
    assert (hi - lo) / 1e9 == pytest.approx(3.133253025, abs=1e-9)
    assert tr.busy_ns(t, lo, hi) == {"/device:TPU:0": pytest.approx(3081877136.0)}
    events = tr.clip(t.devices["/device:TPU:0"], lo, hi)
    assert tr.matching_ns(events, "fused_mlp_rollout") / 1e9 == pytest.approx(1.580555221, abs=1e-9)
    ops = tr.top_ops(t, "/device:TPU:0", lo, hi)
    assert ops[0][0] == "fused_mlp_rollout.12 custom-call"
    assert all(len(name) < 64 for name, _ in ops)
    assert sum(s for _, s in tr.idle_gaps(t, "/device:TPU:0", lo, hi)) == pytest.approx(
        (hi - lo - 3081877136.0) / 1e9
    )


# --------------------------------------------------------------- work counts


def test_walker_work_counts():
    config = mf.load_json(mf.ROOT, "benchmark/configs/openes_walker.json")
    assert work.mlp_dim(config["policy_sizes"]) == config["genome_dim"] == 20945
    assert work.rollout_flops_per_eval(config) == 9_820_000
    assert work.rollout_bytes_per_eval(config) == 83_780


def test_nsga2_generation_bytes():
    config = mf.load_json(mf.ROOT, "benchmark/configs/nsga2_lsmop1.json")
    assert work.generation_hbm_bytes(config, 50000) == 240_000_000


def test_peaks_raise_for_a_device_outside_the_table():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("some other chip")

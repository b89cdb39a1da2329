"""One run of one cell: gate, build, set-up, window, comparison, result line.

The harness knows no cell, configuration or metric by name. ``BENCHMARK.json``
names them, and it finds ``configs/<file>``, ``traffic/<name>.json``,
``builders/<builder>.py``, ``reference/<config>.py`` and ``metrics/<name>.py``
by those names.

The order of a run:

1. set-up (``setup_s``, from the start of the process): imports, the compile
   cache, ``init`` from the seed, the first ``check_steps`` chunks through
   the window's own call, which compile and warm every program the window
   runs, a snapshot of the state kept after each, and one chunk at the
   window's own trip count;
2. the window: ``state = wf.run(state, gens_per_chunk)`` and
   ``block_until_ready``, again, until ``--seconds`` are used up, from the
   state that set-up left; with ``--trace 1`` under the profiler, for at most
   ``TRACE_SECONDS``;
3. after the window: the device's memory is read, the program's state is
   freed, and the plain reference follows the same generations: the first
   chunk from the seed, each later chunk from the snapshot before it;
   ``correct`` says whether every number compared is within its limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import re
import shutil
import statistics
import sys
import time
from pathlib import Path

from benchmark.lib import manifest as mf
from benchmark.lib import timing


CHECK_GENS = 1  # generations in each check chunk: the comparison follows them one by one
TRACE_SECONDS = 5.0  # the most a traced window lasts: the trace of a longer one is too long to read in a run


class GateError(RuntimeError):
    """The machine does not hold what the cell asks for."""


def device_gate(chips: int) -> list:
    """The TPU devices the cell runs on; raises where jax finds no TPU or
    fewer chips than the cell asks for. There is no CPU mode."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise GateError(
            f"jax found platform {devices[0].platform!r} ({devices[0].device_kind}), not a TPU"
        )
    if len(devices) < chips:
        raise GateError(f"the cell asks for {chips} chip(s), jax found {len(devices)}")
    return devices[:chips]


def place_compile_cache() -> str:
    """jax's persistent compilation cache, placed by the program's own
    ``enable_compile_cache`` (where ``JAX_COMPILATION_CACHE_DIR`` says, else
    ``<checkout>/.jax_cache``). Every program is kept, however quick it
    compiled, so that a second run finds all of them."""
    import jax
    from evox_tpu.utils.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return enable_compile_cache()


def check_chunks(built, steps: int) -> tuple:
    """``init`` from the seed, then ``steps`` chunks of ``CHECK_GENS``
    generations through the window's own call, a snapshot kept after each.
    Returns the state they leave and the snapshots."""
    import jax

    state, snaps = built.wf.init(built.key), []
    for _ in range(steps):
        state = jax.block_until_ready(built.wf.run(state, CHECK_GENS))
        snaps.append(built.snapshot(state))
    return state, snaps


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric's reader may read."""

    config: dict
    traffic: dict
    chips: int
    device_kind: str
    window: dict  # chunks, generations, evals, seconds, chunk_ms (list)
    compiles_in_window: int
    events: list  # the fullest device's operations, clipped to the traced stretch; [] without a device plane
    busy_ns: float  # the union of their intervals
    stretch_ns: float  # the traced stretch: first chunk's start to last chunk's end


def _say(**facts) -> None:
    print(json.dumps(facts), flush=True)


def _program_bytes(wf, state) -> dict:
    """The compiler's own account of the window's program, for each device:
    arguments, outputs and temporaries of the compiled ``run`` loop. The
    executable is the one the window ran (jax keeps it; nothing compiles)."""
    fn, args = wf.analysis_targets(state)["run"]
    stats = fn.lower(*args).compile().memory_analysis()
    out = {
        "argument": int(stats.argument_size_in_bytes),
        "output": int(stats.output_size_in_bytes),
        "temp": int(stats.temp_size_in_bytes),
        "alias": int(stats.alias_size_in_bytes),
    }
    out["total"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    return out


def _memory_peak(devices: list, program: dict) -> tuple:
    """``memory_peak_bytes`` on the fullest chip. The allocator's
    ``peak_bytes_in_use`` counts the buffers the client holds and not a
    running executable's temporaries (PERF.md, Findings PR 21 and PR 25), so
    the peak is the larger of that stat and the compiled program's own total
    for a device."""
    stat = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    return max(stat, program["total"]), stat


def _percentile(values: list, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between closest ranks."""
    s = sorted(values)
    at = (len(s) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (at - lo)


def run_cell(argv: list, t0: float, root: Path = mf.ROOT) -> int:
    """Run one cell as the command line says; returns the exit code."""
    parser = argparse.ArgumentParser(description="one run of one cell of the benchmark")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = mf.load(root)
    broken = mf.problems(manifest, root)
    if broken:
        print("BENCHMARK.json breaks its rules:\n" + "\n".join(broken), file=sys.stderr)
        return 2
    cell, entry, config, traffic = mf.cell_parts(manifest, args.workload, root)

    t_import = time.perf_counter()
    import jax

    cache_dir = place_compile_cache()
    try:
        devices = device_gate(int(cell["chips"]))
    except GateError as e:
        print(f"benchmark: {e}; there is no CPU mode", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    t_devices = time.perf_counter()
    _say(platform=devices[0].platform, device_kind=kind, devices=len(devices),
         compile_cache_dir=cache_dir, jax=jax.__version__)

    clock = timing.CompileClock()
    builder = importlib.import_module(f"benchmark.builders.{config['builder']}")
    reference = importlib.import_module(f"benchmark.reference.{entry['name']}")
    built = builder.build(config, traffic, args.seed, devices)
    wf, gens = built.wf, int(traffic["gens_per_chunk"])

    def chunk(state):
        return jax.block_until_ready(wf.run(state, gens))

    # ---- set-up: the first chunks go through the window's own call, with the
    # trip count the comparison follows (one compiled loop serves every count),
    # then one chunk as the window runs them
    t_built = time.perf_counter()
    state, snaps = check_chunks(built, int(traffic["check_steps"]))
    state = chunk(state)
    warm_generations = int(state.generation)
    setup_s = time.perf_counter() - t0
    _say(setup_s=setup_s, start_s=t_import - t0, jax_and_devices_s=t_devices - t_import,
         build_s=t_built - t_devices, warm_chunks_s=time.perf_counter() - t_built,
         compile_s=clock.compile_seconds(), cache_hits=clock.cache_hits,
         backend_compiles=clock.backend_compiles)

    # ---- the window
    traced = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    trace_dir = Path(root) / ".bench_trace" / cell["name"]
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    compiles_before = clock.backend_compiles
    try:
        state, chunk_s, window_s = timing.run_window(chunk, state, seconds, annotate=traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
    compiles_in_window = clock.backend_compiles - compiles_before
    generations = int(state.generation) - warm_generations
    window = {
        "chunks": len(chunk_s),
        "generations": generations,
        "evals": generations * built.pop,
        "seconds": window_s,
        "chunk_ms": [1e3 * s for s in chunk_s],
    }
    _say(chunks=window["chunks"], generations=generations, evals=window["evals"],
         window_s=window_s, compiles_in_window=compiles_in_window,
         chunk_ms_median=statistics.median(window["chunk_ms"]),
         chunk_ms_max=max(window["chunk_ms"]),
         chunk_ms=[round(ms, 2) for ms in window["chunk_ms"]])

    # ---- after the window: memory, then the comparison
    program_bytes = _program_bytes(wf, state)
    peak, allocator_peak = _memory_peak(devices, program_bytes)
    _say(memory_peak_bytes=peak, allocator_peak_bytes_in_use=allocator_peak,
         compiled_run_program_bytes_per_device=program_bytes)
    counted = {
        "window_generations_off": float(abs(generations - gens * len(chunk_s))),
    }
    del state, built, wf, chunk
    jax.clear_caches()
    t_ref = time.perf_counter()
    want = reference.follow(
        config, traffic, args.seed, [s["generation"] for s in snaps], program=snaps
    )
    numbers = {**reference.numbers(config, snaps, want), **counted}
    compared, correct = compare(numbers, config["limits"])
    _say(reference_s=time.perf_counter() - t_ref)

    metrics = {"setup_s": setup_s}
    result = {
        "correct": correct,
        "attempted": window["chunks"],
        "failed": 0,
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if traced:
        from benchmark.lib import trace as tr

        t = tr.load(tr.find_xplane(trace_dir))
        lo, hi = t.window()
        busy = tr.busy_ns(t, lo, hi)
        used = sorted(busy, key=busy.get, reverse=True)[: len(devices)]
        result["device"]["busy_s"] = sum(busy[d] for d in used) / max(len(used), 1) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        fullest = used[0] if used else None
        events = tr.clip(t.devices[fullest], lo, hi) if used else []
        ctx = TraceContext(config=config, traffic=traffic, chips=len(devices),
                           device_kind=kind, window=window,
                           compiles_in_window=compiles_in_window, events=events,
                           busy_ns=busy[fullest] if used else 0.0, stretch_ns=hi - lo)
        for m in mf.metrics_of(manifest, "per_layer", cell["name"]):
            reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:  # a reader that finds nothing to read returns nothing
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        if used:
            result["breakdown"] = {
                "device_ops": tr.top_ops(t, fullest, lo, hi),
                "idle_gaps": tr.idle_gaps(t, fullest, lo, hi),
            }
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics["evals_per_s"] = window["evals"] / window_s
        for m in mf.metrics_of(manifest, "end_to_end", cell["name"]):
            tail = re.fullmatch(r"chunk_ms_p(\d+)", m["name"])  # the tail a cell asks for
            if tail:
                metrics[m["name"]] = _percentile(window["chunk_ms"], float(tail.group(1)))
            result["metrics"][m["name"]] = {"value": float(metrics[m["name"]]), "unit": m["unit"]}
    result["compared"] = compared
    for name, pair in compared.items():
        print(f"compared {name}: {pair['value']!r} limit {pair['limit']!r}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def compare(numbers: dict, limits: dict) -> tuple:
    """Each number beside its limit, and whether all are within. A step's
    number (``step2_center_err``) takes the limit of its kind
    (``center_err``). A number with no limit is not correct, nor is a run
    that compared nothing."""
    compared, correct = {}, bool(numbers)
    for name in sorted(numbers):
        value = numbers[name]
        limit = limits.get(name, limits.get(re.sub(r"^step\d+_", "", name)))
        compared[name] = {"value": value, "limit": limit}
        if limit is None or not value <= limit:  # NaN fails too
            correct = False
    return compared, correct

"""chip_smoke.py rehearsed on the CPU: every phase called at a tiny size on
the virtual mesh (Pallas kernels interpreted), the device gate, and the
placement of the compile cache. The sizes the script itself runs need the
chip; what is checked here is paths, arguments and control flow."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


# ------------------------------------------------------- one-chip phases

ONE_CHIP_PHASES = {
    "quickstart": (chip_smoke.phase_quickstart, {}),
    "run_equals_step": (
        chip_smoke.phase_run_equals_step, dict(pop=64, dim=16, gens=4),
    ),
    "walker": (
        chip_smoke.phase_walker,
        dict(pop=8, hidden=8, episode_len=5, gens=2, compare_pop=4),
    ),
    "pendulum": (
        chip_smoke.phase_pendulum,
        dict(pop=16, episodes=2, hidden=4, episode_len=10, gens=2, compare_pop=8),
    ),
    "nsga2": (chip_smoke.phase_nsga2, dict(pop=32, d=12, m=3, gens=2)),
    "host_callbacks": (
        chip_smoke.phase_host_callbacks, dict(pop=16, dim=4, gens=3),
    ),
}


@pytest.mark.parametrize("name", sorted(ONE_CHIP_PHASES))
def test_one_chip_phase_at_tiny_size(name):
    fn, sizes = ONE_CHIP_PHASES[name]
    facts = fn(**sizes)
    json.dumps(facts, allow_nan=False)  # what main() prints per phase


def test_run_equals_step_is_identical_on_cpu():
    """The law as the repo holds it on the CPU backend: not merely close."""
    assert chip_smoke.phase_run_equals_step(pop=64, dim=16, gens=4)["max_abs_err"] == 0.0


def test_resume_phase_at_tiny_size(tmp_path):
    directory = tmp_path / "resume_ckpt"
    kw = dict(pop=32, dim=8, total=8, every=2, crash_at=5)
    facts = chip_smoke.phase_resume(directory, **kw)
    assert facts["snapshots"], "the checkpointed leg left no snapshot to resume"
    # a second smoke run in the same checkout starts from a clean directory
    # (stale snapshots would be adopted and the resume would resume nothing)
    assert chip_smoke.phase_resume(directory, **kw)["snapshots"] == facts["snapshots"]


# ------------------------------------------------------ the multi-chip phase

MULTICHIP = {
    "cso": (chip_smoke.multichip_cso, dict(pop=64, dim=16, gens=3)),
    "walker": (
        chip_smoke.multichip_walker,
        dict(pop=8, hidden=8, episode_len=5, gens=2),
    ),
    "sharded_es": (chip_smoke.multichip_sharded_es, dict(pop=64, dim=8, gens=3)),
}


@pytest.mark.parametrize("name", sorted(MULTICHIP))
def test_multichip_phase_on_four_virtual_devices(name):
    mesh = chip_smoke.create_mesh(devices=jax.devices()[:4])
    fn, sizes = MULTICHIP[name]
    facts = fn(mesh, **sizes)
    assert sum(facts["collectives"].values()) >= 1
    json.dumps(facts, allow_nan=False)


def test_collective_counts_reads_compiled_text():
    text = (
        "  %ag = f32[8] all-gather(f32[2] %x), dimensions={0}\n"
        "  %ars = f32[4] all-reduce-start(f32[4] %y)\n"
        "  %ard = f32[4] all-reduce-done(f32[4] %ars)\n"
        "  %cp = f32[4] collective-permute(f32[4] %z)\n"
    )
    assert chip_smoke._collective_counts(text) == {
        "all-gather": 1,
        "all-reduce": 1,
        "collective-permute": 1,
        "all-to-all": 0,
        "reduce-scatter": 0,
    }


# ------------------------------------------------------------------- gate


def test_script_exits_nonzero_at_the_gate_without_a_tpu():
    """No CPU mode: under JAX_PLATFORMS=cpu the script stops at the device
    gate — non-zero, the reason on stderr, no phase line and no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert '"phase"' not in proc.stdout and '"ok"' not in proc.stdout


def test_compile_clock_unions_nested_spans():
    clock = chip_smoke._CompileClock._union_seconds
    assert clock([]) == 0.0
    # a nested trace (2..3 inside 1..4) and a disjoint compile (6..8)
    assert clock([(2.0, 3.0), (1.0, 4.0), (6.0, 8.0)]) == 5.0


# ---------------------------------------------------------- compile cache


_CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_compilation_cache_include_metadata_in_key",
    "jax_hlo_source_file_canonicalization_regex",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()  # the rest of the session caches nowhere, as before


def test_compile_cache_placed_from_outside_is_left_alone(
    monkeypatch, restore_cache_dir
):
    from evox_tpu.utils import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compile_cache() == "/some/dir"
    # jax reads the variable by itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_directory_in_the_checkout(
    monkeypatch, restore_cache_dir
):
    from evox_tpu.utils import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache") == enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_compile_cache_keys_a_program_with_its_names(
    tmp_path, monkeypatch, restore_cache_dir
):
    """A cached executable carries the ``op_name``s of the compile that
    wrote it, and the profiler shows those. So two programs that differ in
    a ``scope`` alone get an entry each, which jax's default key (metadata
    stripped) does not give them; and the source paths in the key are
    relative to the checkout, so a copy elsewhere finds the same entries."""
    import re

    from jax.experimental.compilation_cache import compilation_cache

    from evox_tpu.core.instrument import scope
    from evox_tpu.utils import compile_cache

    def program(name):
        def f(x):
            with scope(name):
                return jax.numpy.sin(x) * 2

        return jax.jit(f)

    def new_entries(names):
        held = set(os.listdir(tmp_path))
        for name in names:
            program(name)(x)  # one call site, so the scope is all that differs
        return len([p for p in set(os.listdir(tmp_path)) - held if p.endswith("-cache")])

    x = jax.numpy.ones((8,))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_REPO_CACHE", tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert new_entries("ab") == 2
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    assert new_entries("cd") == 1  # jax's default: "d" would load "c"'s names

    assert jax.config.jax_hlo_source_file_canonicalization_regex == re.escape(str(REPO) + os.sep)
    text = program("a").lower(x).as_text(debug_info=True)
    assert '"tests/test_chip_smoke.py"' in text and str(REPO) not in text

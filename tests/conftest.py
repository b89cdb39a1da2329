"""Test configuration: force an 8-device virtual CPU mesh so sharded
workflows and shard_map collectives are exercised without TPU hardware
(the multi-chip test story the reference lacks — SURVEY.md §4).

Note: jax may already be imported by pytest plugins, so the platform is
forced via ``jax.config`` (still before any backend is initialized), not
just env vars.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# --xla_backend_optimization_level=0 drops the LLVM codegen opt level in
# the CPU backend only (XLA's HLO passes still run): the suite is
# compile-bound on one core, and this halves compile-heavy files
# (test_islands 90s -> 46s) while execution-heavy ones stay within ~5%
# (the n=20032 chunked-build test 68 -> 72s). With the shape trims the
# suite runs ~21 min single-process (18:57-22:08 observed; was 28) with
# identical assertions. TPU runs are unaffected (flag is CPU-test only,
# set here).
_flags = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in _flags:  # allow override
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def ceilings():
    """Stand-in peaks for roofline tests: the CPU has no entry in
    ``core.xla_cost.CHIP_CEILINGS`` (no default peak exists), so a test
    that wants fractions of peak hands the analyzer its own."""
    return {
        "mxu_bf16_tflops": 1.0,
        "hbm_gbps": 10.0,
        "source": "tests/conftest.py stand-in, not a device's peaks",
    }

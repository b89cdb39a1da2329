"""Multi-process rollout farm: a 2-worker-PROCESS
farm must reproduce the single-process farm's fitness exactly, and drive
through the workflow + run_host_pipelined like any host problem."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.problems.neuroevolution.process_farm import (
    ProcessRolloutFarm,
    spawn_local_workers,
)
from evox_tpu.problems.neuroevolution.rollout_farm import HostRolloutFarm

from tests._farm_helpers import DIM, ScalarCartPole, flat_policy

pytestmark = pytest.mark.farm


@pytest.fixture
def farm():
    farm = ProcessRolloutFarm(
        flat_policy, ScalarCartPole, num_workers=2, cap_episode=60,
        host="127.0.0.1",
    )
    procs = spawn_local_workers(farm.address, 2)
    try:
        farm.bind(timeout=120.0)
        yield farm
    finally:
        farm.shutdown()
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()


def test_process_farm_matches_single_process(farm):
    """Same slices, same per-slice seed law -> identical fitness to the
    in-process HostRolloutFarm(batch_policy=False)."""
    pop = 0.5 * jax.random.normal(jax.random.PRNGKey(0), (10, DIM))

    local = HostRolloutFarm(
        flat_policy, ScalarCartPole, num_workers=2, batch_policy=False,
        cap_episode=60,
    )
    # pin both farms' per-generation seed draws to the same stream
    farm._seed_rng = np.random.default_rng(123)
    local._seed_rng = np.random.default_rng(123)

    f_proc, _ = farm.evaluate(farm.init(), pop)
    f_local, _ = local.evaluate(local.init(), pop)
    assert f_proc.shape == (10,)
    np.testing.assert_allclose(
        np.asarray(f_proc), np.asarray(f_local), rtol=1e-6, atol=1e-6
    )
    assert float(np.max(np.asarray(f_proc))) >= 1.0  # episodes ran

    # a second generation reuses the persistent workers
    f2, _ = farm.evaluate(farm.init(), pop)
    assert f2.shape == (10,)


def test_process_farm_through_pipelined_workflow(farm):
    """The farm is a normal host problem: StdWorkflow + the overlapped
    run_host_pipelined driver work unchanged on top of worker processes."""
    from evox_tpu import StdWorkflow
    from evox_tpu.algorithms.so.es import OpenES
    from evox_tpu.workflows.pipelined import run_host_pipelined

    algo = OpenES(jnp.zeros(DIM), pop_size=10, learning_rate=0.1, noise_stdev=0.5)
    wf = StdWorkflow(algo, farm, opt_direction="max")
    state = wf.init(jax.random.PRNGKey(1))
    seen = []
    state = run_host_pipelined(
        wf, state, 3, on_generation=lambda g, s, f: seen.append(float(jnp.max(f)))
    )
    assert len(seen) == 3
    assert all(v >= 1.0 for v in seen)


def test_process_farm_unbound_raises():
    farm = ProcessRolloutFarm(
        flat_policy, ScalarCartPole, num_workers=1, host="127.0.0.1"
    )
    try:
        with pytest.raises(RuntimeError, match="no workers bound"):
            farm.evaluate(farm.init(), jnp.zeros((2, DIM)))
    finally:
        farm.shutdown()


def test_process_farm_rejects_wrong_authkey():
    """A peer that fails the HMAC handshake is dropped before any pickle
    is read from it; a correct-key worker connecting next still binds."""
    farm = ProcessRolloutFarm(
        flat_policy, ScalarCartPole, num_workers=1, cap_episode=30,
        host="127.0.0.1", authkey=b"right-key",
    )
    bad = spawn_local_workers(farm.address, 1, authkey=b"wrong-key")
    good = spawn_local_workers(farm.address, 1, authkey=b"right-key")
    try:
        farm.bind(timeout=120.0)
        assert len(farm._conns) == 1
        fit, _ = farm.evaluate(farm.init(), jnp.zeros((4, DIM)))
        assert fit.shape == (4,)
    finally:
        farm.shutdown()
        for p in bad + good:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()


@pytest.mark.parametrize("parent_value", [None, "tpu"])
def test_local_workers_start_pinned_to_the_cpu(monkeypatch, parent_value):
    """One process per chip: a local worker shares its machine with the
    coordinator, which may hold the accelerator. The child reads the
    environment as it stands at start(), so JAX_PLATFORMS=cpu is there at
    that moment — and the parent's own value is back afterwards."""
    import multiprocessing as mp
    import os

    if parent_value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent_value)
    seen = []

    class FakeProcess:
        def __init__(self, **kwargs):
            pass

        def start(self):
            seen.append(os.environ.get("JAX_PLATFORMS"))

    class FakeContext:
        Process = FakeProcess

    monkeypatch.setattr(mp, "get_context", lambda method: FakeContext)
    procs = spawn_local_workers(("127.0.0.1", 1), 3)
    assert len(procs) == 3 and seen == ["cpu"] * 3
    assert os.environ.get("JAX_PLATFORMS") == parent_value

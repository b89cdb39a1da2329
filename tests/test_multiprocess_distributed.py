"""Multi-process SPMD tests (the reference tests its Ray path
with 2 fractional-CPU workers; the TPU-native analog is 2 JAX processes
over a DCN-emulating local coordinator, collectives on the CPU backend).

Since ISSUE 13 the 2-process psum/all_gather law (the old
``test_two_process_spmd``) is SUPERSEDED by the ``dryrun_multihost(n)``
harness (tests/test_multihost.py + tools/_multihost_worker.py), which
runs the same collective laws — and much stronger ones: ShardedES
sharded ≡ replicated across process boundaries, 1→n-process checkpoint
resume, the pod save — behind the SAME jaxlib >= 0.5 gate, while its
membership tier (init guard, pod mesh, assembly) runs on every jaxlib.
This file keeps only the monitor-callback pinning law in its original
standalone form (the harness runs it too, as Tier B's
``monitor_process0_pinning``)."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

MONITOR_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    pid = int(sys.argv[1]); nprocs = int(sys.argv[2]); port = sys.argv[3]
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=pid,
        local_device_ids=[0],
    )
    import jax.numpy as jnp
    from evox_tpu import StdWorkflow, create_mesh
    from evox_tpu.algorithms import PSO
    from evox_tpu.monitors import EvalMonitor
    from evox_tpu.problems.numerical import Sphere
    from evox_tpu.core.problem import Problem
    import numpy as np

    mesh = create_mesh(devices=jax.devices())
    algo = PSO(lb=jnp.full((4,), -5.0), ub=jnp.full((4,), 5.0), pop_size=8)
    mon = EvalMonitor(full_fit_history=True)
    wf = StdWorkflow(algo, Sphere(), monitors=[mon], mesh=mesh)
    state = wf.init(jax.random.PRNGKey(0))
    for _ in range(3):
        state = wf.step(state)
    jax.effects_barrier()
    n_hist = len(mon.get_fitness_history())
    # host0_sharding pins the history io_callback to global device 0:
    # it must fire exactly once per generation, on process 0 ONLY
    expected = 3 if pid == 0 else 0
    assert n_hist == expected, (pid, n_hist, expected)

    # external (host) problems must be REFUSED under multi-process SPMD
    class HostSphere(Problem):
        jittable = False
        def evaluate(self, state, pop):
            return np.sum(np.asarray(pop) ** 2, axis=1), state

    try:
        StdWorkflow(algo, HostSphere(), mesh=mesh)
        raise SystemExit("external problem was not refused")
    except ValueError as e:
        assert "single-process" in str(e), e
    print(f"proc {pid} MONITOR-OK hist={n_hist}", flush=True)
    """
)


def test_two_process_monitor_callback_fires_on_process0_only(tmp_path):
    """The history io_callback fires exactly once per
    generation (process 0), and external problems are refused loudly on
    multi-process runs."""
    import socket

    nprocs = 2
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "monitor_worker.py"
    script.write_text(MONITOR_WORKER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nprocs), port],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
        )
        for i in range(nprocs)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("monitor workers timed out")
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} MONITOR-OK" in out

"""Checkpoint round-trip tests (reference state.py:264-301 save/load) and
the observability tail: PopMonitor, Arrow-streaming EvoXVisMonitor,
StepTimerMonitor, vis_tools plots."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.pso import CSO, PSO
from evox_tpu.core import state_io
from evox_tpu.core.distributed import create_mesh, place_pop
from evox_tpu.monitors import (
    EvalMonitor,
    EvoXVisMonitor,
    PopMonitor,
    StepTimerMonitor,
)
from evox_tpu.problems.numerical import Ackley, Sphere, ZDT1
from evox_tpu.algorithms.mo import NSGA2

DIM = 5
LB, UB = -10.0 * jnp.ones(DIM), 10.0 * jnp.ones(DIM)


def _workflow(monitors=(), mesh=None):
    algo = PSO(LB, UB, pop_size=32)
    return StdWorkflow(algo, Sphere(), monitors=monitors, mesh=mesh)


# ------------------------------------------------------------- checkpoints

@pytest.mark.parametrize("backend", ["pickle", "orbax"])
def test_checkpoint_roundtrip(tmp_path, backend):
    wf = _workflow()
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 5)
    path = str(tmp_path / f"ckpt_{backend}")
    state_io.save(state, path, backend=backend)
    restored = state_io.load(
        path, target=state if backend == "orbax" else None, backend=backend
    )
    # restored state equals saved state leaf-by-leaf
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # and stepping the restored state continues identically
    s1 = wf.run(state, 3)
    s2 = wf.run(restored, 3)
    np.testing.assert_allclose(
        np.asarray(s1.algo.pbest_fitness), np.asarray(s2.algo.pbest_fitness), rtol=1e-6
    )


def test_checkpoint_restore_into_mesh(tmp_path):
    """Save unsharded, restore into an 8-device mesh layout, keep stepping —
    the sharding-aware restore claim in core/state_io.py."""
    wf = _workflow()
    state = wf.init(jax.random.PRNGKey(1))
    state = wf.run(state, 4)
    path = str(tmp_path / "ckpt_mesh")
    state_io.save(state, path, backend="orbax")

    mesh = create_mesh()
    wf_sharded = _workflow(mesh=mesh)
    from evox_tpu.core.distributed import replicated_sharding

    restored = state_io.load(path, target=state, backend="orbax")
    rep = replicated_sharding(mesh)
    restored = jax.tree.map(
        lambda x: place_pop(x, mesh)
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == 32
        else jax.device_put(x, rep),
        restored,
    )
    cont = wf_sharded.run(restored, 3)
    ref = wf.run(state, 3)
    np.testing.assert_allclose(
        np.asarray(cont.algo.pbest_fitness),
        np.asarray(ref.algo.pbest_fitness),
        rtol=1e-5,
        atol=1e-5,
    )


# ---------------------------------------------------------------- monitors

def test_pop_monitor_histories():
    mon = PopMonitor(fitness_name="pbest_fitness")
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(2))
    state = wf.run(state, 10)
    fits = mon.get_fitness_history()
    pops = mon.get_population_history()
    assert len(fits) == 10 and len(pops) == 10
    assert fits[0].shape == (32,)
    assert pops[0].shape == (32, DIM)
    # populations actually move
    assert not np.allclose(pops[0], pops[-1])
    np.testing.assert_array_equal(mon.get_latest_fitness(), fits[-1])


def test_pop_monitor_fitness_only():
    mon = PopMonitor(fitness_name="pbest_fitness", fitness_only=True)
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(3))
    state = wf.run(state, 5)
    assert len(mon.get_fitness_history()) == 5
    assert mon.get_population_history() == []


def test_evoxvis_monitor_arrow_file(tmp_path):
    import pyarrow as pa

    mon = EvoXVisMonitor(
        out_dir=str(tmp_path), batch_size=4, record_population=True
    )
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(4))
    state = wf.run(state, 10)
    mon.close()
    with pa.OSFile(str(mon.path), "rb") as f:
        table = pa.ipc.open_file(f).read_all()
    assert table.num_rows == 10
    assert table.column("generation").to_pylist() == list(range(10))
    meta = table.schema.metadata
    assert meta[b"population_size"] == b"32"
    fit0 = np.frombuffer(
        table.column("fitness")[0].as_py(), dtype=meta[b"fitness_dtype"].decode()
    )
    assert fit0.shape == (32,)
    assert np.isfinite(fit0).all()
    # durations are monotonically non-decreasing
    dur = table.column("duration").to_pylist()
    assert all(b >= a for a, b in zip(dur, dur[1:]))


def test_step_timer_monitor():
    mon = StepTimerMonitor()
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(5))
    state = wf.run(state, 8)
    times = mon.get_step_times()
    assert times.shape == (8,)
    assert (times >= 0).all()
    s = mon.summary()
    assert s["steps"] == 8 and s["total_s"] >= 0


# --------------------------------------------------------------- vis_tools

def test_vis_tools_plots():
    from evox_tpu.vis_tools import (
        plot_dec_space,
        plot_obj_space_1d,
        plot_obj_space_2d,
        plot_obj_space_3d,
    )

    rng = np.random.default_rng(0)
    so_hist = [rng.random(16) for _ in range(5)]
    fig = plot_obj_space_1d(so_hist)
    assert fig is not None

    mo2 = [rng.random((16, 2)) for _ in range(5)]
    fig = plot_obj_space_2d(mo2, problem_pf=rng.random((50, 2)))
    assert fig is not None
    anim = plot_obj_space_2d(mo2, animated=True)
    assert anim is not None

    mo3 = [rng.random((16, 3)) for _ in range(5)]
    assert plot_obj_space_3d(mo3) is not None

    dec = [rng.random((16, 2)) for _ in range(5)]
    assert plot_dec_space(dec, lb=np.zeros(2), ub=np.ones(2)) is not None


def test_pop_monitor_plot_mo():
    mon = PopMonitor(fitness_only=True)
    algo = NSGA2(jnp.zeros(6), jnp.ones(6), n_objs=2, pop_size=32)
    wf = StdWorkflow(algo, ZDT1(n_dim=6), monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(6))
    state = wf.run(state, 5)
    fig = mon.plot(problem_pf=ZDT1(n_dim=6).pf())
    assert fig is not None


def test_evoxvis_monitor_variable_batch(tmp_path):
    """CSO evaluates full pop on gen 1 and half afterwards — the Arrow
    schema must absorb varying row byte-lengths."""
    import pyarrow as pa

    mon = EvoXVisMonitor(out_dir=str(tmp_path), batch_size=4)
    algo = CSO(LB, UB, pop_size=16)
    wf = StdWorkflow(algo, Sphere(), monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(7))
    state = wf.run(state, 6)
    mon.close()
    with pa.OSFile(str(mon.path), "rb") as f:
        table = pa.ipc.open_file(f).read_all()
    assert table.num_rows == 6
    lens = [len(b.as_py()) for b in table.column("fitness")]
    assert lens[0] == 16 * 4 and lens[1] == 8 * 4  # full pop, then half


def test_evoxvis_close_then_keep_running(tmp_path):
    mon = EvoXVisMonitor(out_dir=str(tmp_path), batch_size=4)
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(8))
    state = wf.run(state, 4)
    mon.close()
    state = wf.run(state, 3)  # must not raise from inside the callback
    jax.effects_barrier()


def test_vis_1d_animated():
    from evox_tpu.vis_tools import plot_obj_space_1d

    rng = np.random.default_rng(1)
    anim = plot_obj_space_1d([rng.random(8) for _ in range(4)], animated=True)
    assert hasattr(anim, "save")


def test_plotly_json_figures(tmp_path):
    """plotly_json emits plotly-schema figure dicts (the reference's
    plotly animation capability, reference vis_tools/plot.py, without the
    plotly dependency): frames + generation slider + play/pause controls,
    JSON-serializable, and a standalone HTML export."""
    import json

    import numpy as np

    from evox_tpu.vis_tools import plotly_json as pj

    rng = np.random.default_rng(0)
    pops = [rng.normal(size=(16, 2)) for _ in range(5)]
    fits1 = [rng.normal(size=(16,)) + 10 - g for g in range(5)]
    fits2 = [rng.uniform(size=(16, 2)) for _ in range(5)]
    fits3 = [rng.uniform(size=(16, 3)) for _ in range(5)]

    fig = pj.plot_dec_space(pops)
    assert set(fig) == {"data", "layout", "frames"}
    assert len(fig["frames"]) == 5
    assert len(fig["layout"]["sliders"][0]["steps"]) == 5
    assert fig["layout"]["updatemenus"][0]["buttons"][0]["label"] == "Play"
    assert fig["frames"][2]["data"][0]["type"] == "scatter"
    json.dumps(fig)  # strictly JSON-serializable

    f1 = pj.plot_obj_space_1d(fits1)
    # frame i reveals i+1 generations of the Min curve
    assert len(f1["frames"][2]["data"][0]["x"]) == 3
    assert f1["frames"][4]["data"][0]["name"] == "Min"
    static = pj.plot_obj_space_1d(fits1, animation=False)
    assert "frames" not in static and len(static["data"]) == 4
    # min curve is what it says
    assert static["data"][0]["y"][0] == float(np.min(fits1[0]))

    pf = np.stack([np.linspace(0, 1, 8), 1 - np.linspace(0, 1, 8)], axis=1)
    f2 = pj.plot_obj_space_2d(fits2, problem_pf=pf, sort_points=True)
    assert f2["frames"][0]["data"][0]["name"] == "Pareto Front"
    f3 = pj.plot_obj_space_3d(fits3)
    assert f3["frames"][0]["data"][0]["type"] == "scatter3d"
    assert "scene" in f3["layout"]

    out = tmp_path / "fig.html"
    pj.save_html(fig, str(out))
    text = out.read_text()
    assert "Plotly.newPlot" in text and "addFrames" in text
    assert json.loads(pj.to_json(fig)) == fig

    # script-injection guard: '</script>' in user strings must not
    # terminate the embedding <script> element or escape the title
    evil = pj.plot_dec_space(pops, title={"text": "a</script><b>"})
    out2 = tmp_path / "evil.html"
    pj.save_html(evil, str(out2), title="<t>")
    body = out2.read_text()
    assert "a</script>" not in body and "<title>&lt;t&gt;</title>" in body


def test_checkpoint_monitor_autosaves(tmp_path):
    from evox_tpu.monitors import CheckpointMonitor

    mon = CheckpointMonitor(str(tmp_path), every=3, keep=2)
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(9))
    state = wf.run(state, 10)
    jax.effects_barrier()
    # gens 3, 6, 9 saved; keep=2 -> 6 and 9 remain
    names = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("ckpt"))
    assert names == ["ckpt_00000006", "ckpt_00000009"]
    restored = mon.latest()
    assert int(restored.generation) == 9
    # restored state continues through the workflow
    cont = wf.run(restored, 2)
    assert int(cont.generation) == 11


def test_checkpoint_monitor_adopts_existing_and_validates(tmp_path):
    from evox_tpu.monitors import CheckpointMonitor

    with pytest.raises(ValueError, match="every"):
        CheckpointMonitor(str(tmp_path), every=0)
    with pytest.raises(ValueError, match="keep"):
        CheckpointMonitor(str(tmp_path), keep=0)

    mon = CheckpointMonitor(str(tmp_path), every=2, keep=2)
    wf = _workflow(monitors=(mon,))
    state = wf.init(jax.random.PRNGKey(10))
    state = wf.run(state, 5)
    jax.effects_barrier()
    # a NEW monitor over the same directory adopts the files on disk
    mon2 = CheckpointMonitor(str(tmp_path), every=2, keep=2)
    restored = mon2.latest()
    assert restored is not None and int(restored.generation) == 4
    # restore + rerun re-saves the same generations without duplicating
    wf2 = _workflow(monitors=(mon2,))
    state = wf2.run(restored.replace(first_step=False), 4)
    jax.effects_barrier()
    assert len(mon2.saved) == len(set(mon2.saved)) <= 2
    assert all(p.exists() for p in mon2.saved)


def test_checkpoint_monitor_latest_skips_corrupt(tmp_path):
    """latest() must warn and fall back past torn snapshots instead of
    raising mid-restore."""
    from evox_tpu.monitors import CheckpointMonitor

    mon = CheckpointMonitor(str(tmp_path), every=1, keep=5)
    mon._save(1, {"gen": 1})
    mon._save(2, {"gen": 2})
    mon.saved[-1].write_bytes(b"\x80torn")  # newest is torn
    with pytest.warns(UserWarning, match="skipping corrupt checkpoint"):
        obj = mon.latest()
    assert obj == {"gen": 1}
    mon.saved[0].write_bytes(b"")  # now everything is bad
    with pytest.warns(UserWarning):
        assert mon.latest() is None


def test_async_orbax_save_roundtrip(tmp_path):
    """save(wait=False) stages and returns; wait_for_saves commits; load
    restores identically (and itself waits for pending saves)."""
    from evox_tpu.core import state_io

    state = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": (jnp.ones((5,)), jnp.zeros((2, 2), dtype=jnp.int32)),
    }
    p = tmp_path / "async_ckpt"
    state_io.save(state, str(p), backend="orbax", wait=False)
    restored = state_io.load(str(p), target=state, backend="orbax")
    jax.tree.map(np.testing.assert_allclose, restored, state)
    state_io.wait_for_saves()  # idempotent after load's implicit wait

"""One process of the ``dryrun_multihost(n)`` harness (__graft_entry__.py).

Launched ``n`` times (plus once solo at ``nprocs=1`` as the 1-process
reference leg) with a JSON spec on argv[1]. Each worker:

1. BEFORE any jax backend touch: loads ``evox_tpu/core/distributed.py``
   standalone (importing the package would build jnp constants and
   initialize the backend ahead of ``jax.distributed`` — the same loader
   discipline the pre-PR-13 multiprocess test used) and runs the
   ``init_distributed`` guard laws: the ``is_dist_initialized`` fix (a
   1-process ``jax.distributed`` run MUST read as initialized — the old
   ``process_count() > 1`` predicate misread it), the warned no-op on a
   matching re-init, and the loud ``RuntimeError`` on a conflicting one.
2. Imports evox_tpu, builds the pod mesh over the global device list,
   and asserts the Tier-A membership laws (works on ANY jaxlib): global
   device discovery, process-contiguous mesh order, per-process
   ``make_array_from_single_device_arrays`` assembly, and the
   external-problem refusal under a process-spanning mesh.
3. Where the backend can run cross-process computations (jaxlib >= 0.5;
   the CPU backend below that refuses at COMPILE time with
   "Multiprocess computations aren't implemented"), runs the Tier-B
   collective laws: ShardedES sharded ≡ replicated across process
   boundaries, the 1-process → n-process checkpoint-resume trajectory
   law, process-0-only monitor-callback pinning, the pod save
   (process-0-writes + barrier, one manifest), and the AOT per-process
   memory table.

Results land as ``result_<tag>.json`` in the shared workdir; the parent
(`dryrun_multihost`) aggregates and asserts.

ISSUE 14 additions:

4. ``spec["pod_run"]`` switches the worker into POD-RUN mode: a
   supervised chunked workload under a
   :class:`~evox_tpu.core.pod_supervisor.PodSupervisor` (KV heartbeats,
   collective deadlines, barrier-checkpointed chunk boundaries,
   coordinated SIGTERM drain) with optional SCRIPTED chaos
   self-injection (SIGKILL pre-barrier / mid-chunk / mid-checkpoint,
   a hung chunk). A diagnosed pod fault dumps its post-mortem result
   and exits with code 23 — the detected-and-aborted signal the
   :class:`PodManager` re-formation driver keys on.
5. :class:`PodManager` (importable — the module's imports stay stdlib;
   jax only loads inside ``main``): the respawn/re-form driver of the
   pod escalation ladder. It spawns reference/chaos/re-formed pods,
   delivers parent-side signals (SIGSTOP, SIGTERM preemption notices),
   collects post-mortems, and re-forms the pod on the survivor process
   set against a FRESH coordinator rendezvous, resuming from the newest
   intact pod-barrier checkpoint. Driven by
   ``__graft_entry__.dryrun_multihost(chaos=...)``.

Every worker installs ``faulthandler`` with a pre-deadline traceback
dump at ~80% of the harness timeout, so a hung worker leaves its stacks
in the harness log instead of dying silently at the parent's kill.
Running ``main`` requires being a spawned subprocess (it initializes
``jax.distributed``); importing the module is safe.
"""

import json
import os
import signal
import subprocess
import sys
import time
import warnings

_WORKER_FILE = os.path.abspath(__file__)

#: exit code of a worker that DIAGNOSED a pod fault and aborted with a
#: post-mortem (vs. a raw crash) — what the PodManager's survivor
#: census keys on
POD_FAULT_EXIT = 23


def main() -> None:
    spec = json.loads(sys.argv[1])
    if spec.get("control_pod"):
        # ISSUE 18: one POD DRIVER of the multi-pod control plane — a
        # plain single-process server over its own pod directory, no
        # jax.distributed (the gateway composes pods; each pod is just
        # an ElasticServer whose durable surfaces the gateway can read)
        _control_pod_run(spec)
        return
    pid = int(spec["pid"])
    nprocs = int(spec["nprocs"])
    n_local = int(spec["n_local"])
    workdir = spec["workdir"]
    repo = spec["repo"]
    tag = spec.get("tag", f"{nprocs}x{n_local}_p{pid}")
    result = {
        "pid": pid,
        "nprocs": nprocs,
        "n_local": n_local,
        "tag": tag,
        "laws": {},
        "collectives": {},
    }

    # worker debuggability (ISSUE 14 satellite): a worker wedged in a
    # collective must leave its tracebacks in the harness log, not die
    # silently when the parent's fleet deadline kills it — dump every
    # thread's stack shortly BEFORE the harness timeout would fire
    import faulthandler

    faulthandler.enable()
    hard = float(spec.get("harness_timeout", 600.0))
    faulthandler.dump_traceback_later(max(hard * 0.8, 5.0), exit=False)

    # --- phase 0: environment, BEFORE importing jax -----------------------
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_local}"
    )
    sys.path.insert(0, repo)

    import jax

    jax.config.update("jax_platforms", "cpu")

    # --- phase 1: pre-backend init + guard laws (file-loaded module) ------
    import importlib.util

    dist_py = os.path.join(repo, "evox_tpu", "core", "distributed.py")
    loader_spec = importlib.util.spec_from_file_location(
        "evox_tpu_distributed_standalone", dist_py
    )
    D = importlib.util.module_from_spec(loader_spec)
    loader_spec.loader.exec_module(D)

    coord = f"127.0.0.1:{spec['port']}"
    pod_cfg = spec.get("pod_run")
    if pod_cfg is not None:
        # pod-run mode: init only (the guard laws have their own tier)
        D.init_distributed(
            coordinator_address=coord, num_processes=nprocs, process_id=pid
        )
        assert D.process_count() == nprocs and D.process_id() == pid
        _pod_run(spec, result, pod_cfg)
        _dump(result, workdir, tag)
        print(f"WORKER {tag} OK", flush=True)
        return

    assert not D.is_dist_initialized(), "fresh process reads initialized"
    D.init_distributed(
        coordinator_address=coord, num_processes=nprocs, process_id=pid
    )
    # THE satellite regression: a 1-process jax.distributed run is
    # initialized — the old `process_count() > 1` predicate said False
    assert D.is_dist_initialized(), (
        f"is_dist_initialized() False after init (nprocs={nprocs})"
    )
    result["laws"]["is_dist_initialized"] = "ok"

    # idempotent re-call: warned no-op
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        D.init_distributed(
            coordinator_address=coord, num_processes=nprocs, process_id=pid
        )
    assert any("already initialized" in str(w.message) for w in caught), (
        "matching re-init did not warn"
    )
    # constraint-free re-call (the auto-detect shape): also a warned no-op
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        D.init_distributed()
    assert any("no-op" in str(w.message) for w in caught)
    # conflicting re-call: loud RuntimeError naming the conflict
    try:
        D.init_distributed(
            coordinator_address="127.0.0.1:1", num_processes=nprocs,
            process_id=pid,
        )
        raise SystemExit("conflicting re-init did not raise")
    except RuntimeError as e:
        assert "coordinator_address" in str(e), e
    result["laws"]["init_guard"] = "ok"

    assert D.process_count() == nprocs, D.process_count()
    assert D.process_id() == pid

    # --- phase 2: package import + Tier-A membership laws -----------------
    import numpy as np

    import evox_tpu  # noqa: F401  (backend initializes under jax.distributed)
    from evox_tpu.core import distributed as dist

    n_total = nprocs * n_local
    assert jax.device_count() == n_total, (jax.device_count(), n_total)
    assert jax.local_device_count() == n_local

    mesh = dist.create_pod_mesh()
    assert int(mesh.shape[dist.POP_AXIS]) == n_total
    # process contiguity: block k of the leading axis belongs to process k
    flat = list(mesh.devices.flat)
    for k in range(nprocs):
        block = flat[k * n_local : (k + 1) * n_local]
        assert all(d.process_index == k for d in block), (
            "pod mesh is not process-contiguous"
        )
    result["laws"]["pod_mesh"] = "ok"

    # per-process assembly: every process holds the full host value, puts
    # only its own slices, and the global array's local shards are exactly
    # the process's block of the leading axis
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.arange(4 * n_total, dtype=np.float32).reshape(n_total, 4)
    g = dist.assemble_global_array(x, NamedSharding(mesh, P(dist.POP_AXIS)))
    for shard in g.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), x[shard.index])
        assert shard.device.process_index == pid
    result["laws"]["assembly"] = "ok"

    # external (host) problems refuse a process-spanning mesh AT
    # CONSTRUCTION (no compile involved — Tier A even on jaxlib < 0.5)
    if nprocs > 1:
        import jax.numpy as jnp

        from evox_tpu import StdWorkflow
        from evox_tpu.core.problem import Problem

        class HostSphere(Problem):
            jittable = False

            def evaluate(self, state, pop):
                return np.sum(np.asarray(pop) ** 2, axis=1), state

        algo = _pso(jnp)
        try:
            StdWorkflow(algo, HostSphere(), mesh=mesh)
            raise SystemExit("external problem was not refused on pod mesh")
        except ValueError as e:
            assert "single-process" in str(e), e
        result["laws"]["external_refusal"] = "ok"

    # --- phase 2b: pod metrics tier (Tier A — KV-store barriers + a
    # replicated workload, so it runs on ANY jaxlib, every leg) ------------
    if spec.get("metrics", False):
        _metrics_tier(spec, result, dist, nprocs, n_local, workdir)

    # --- phase 3: Tier B (cross-process computations) ---------------------
    if spec.get("collectives", False) or nprocs == 1:
        _collective_laws(spec, result, dist, mesh, nprocs, n_local, workdir)
    else:
        result["collectives"]["skipped"] = spec.get(
            "skip_reason", "collectives disabled"
        )

    _dump(result, workdir, tag)
    print(f"WORKER {tag} OK", flush=True)


def _pso(jnp):
    from evox_tpu.algorithms.so.pso import PSO

    return PSO(lb=-5.0 * jnp.ones(4), ub=5.0 * jnp.ones(4), pop_size=8)


def _init_shapes(wf, key):
    """What ``jax.eval_shape(wf.init, key)`` gives, for a mesh that may
    span processes: there ``init`` assembles global arrays eagerly
    (``ensure_global_state``), which a trace cannot do, so ``init`` runs
    and its result is described."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wf.init(key)
    )


def _law_workflow(mesh, n_shards, pop=32, dim=16):
    """The law workload: POP-sharded ShardedES(SepCMAES) on Sphere —
    per-shard fold_in sampling + psum-of-moments recombination, the PR-10
    substrate now spanning processes."""
    import jax.numpy as jnp

    from evox_tpu import ShardedES, StdWorkflow
    from evox_tpu.algorithms.so.es import SepCMAES
    from evox_tpu.problems.numerical import Sphere

    algo = ShardedES(
        SepCMAES(center_init=jnp.zeros(dim), init_stdev=1.0, pop_size=pop),
        mesh=mesh,
        n_shards=n_shards,
    )
    return StdWorkflow(algo, Sphere(), mesh=mesh)


def _collective_laws(spec, result, dist, mesh, nprocs, n_local, workdir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_total = nprocs * n_local
    gens_snapshot, gens_total = 3, 6

    # (a) sharded ≡ replicated across process boundaries: the pod-mesh
    # ShardedES run must match the SAME per-shard sampling law executed
    # replicated in-process (mesh=None, n_shards=n_total — no collectives,
    # identical on every process since the key is identical)
    wf = _law_workflow(mesh, n_total)
    state = wf.init(jax.random.PRNGKey(7))
    for _ in range(gens_total):
        state = wf.step(state)
    mean_sh = dist.host_value(state.algo.mean)
    sigma_sh = float(dist.host_value(state.algo.sigma))
    wf_rep = _law_workflow(None, n_total)
    state_rep = wf_rep.init(jax.random.PRNGKey(7))
    for _ in range(gens_total):
        state_rep = wf_rep.step(state_rep)
    np.testing.assert_allclose(
        mean_sh, np.asarray(state_rep.algo.mean), rtol=1e-5, atol=1e-5,
        err_msg="pod-sharded ShardedES diverged from the replicated law",
    )
    np.testing.assert_allclose(
        sigma_sh, float(state_rep.algo.sigma), rtol=1e-5, atol=1e-5
    )
    result["collectives"]["sharded_vs_replicated"] = "ok"

    # (b) checkpoint topology portability across PROCESS counts
    from evox_tpu.workflows.checkpoint import (
        WorkflowCheckpointer, restore_layouts,
    )

    ckpt_dir = os.path.join(workdir, "solo_ckpt")
    if nprocs == 1:
        # the reference leg: 1-process run over ALL devices, snapshot at
        # gens_snapshot, straight finish recorded for the pod legs
        ckpt = WorkflowCheckpointer(ckpt_dir, every=gens_snapshot, keep=10)
        st = wf.init(jax.random.PRNGKey(11))
        for g in range(gens_total):
            st = wf.step(st)
            ckpt.maybe_save(st)
        result["final"] = {
            "mean": np.asarray(dist.host_value(st.algo.mean)).tolist(),
            "sigma": float(dist.host_value(st.algo.sigma)),
            "generation": int(st.generation),
        }
    else:
        # pod leg: resume the 1-process gen-K snapshot on THIS process
        # layout and reproduce the solo trajectory's remaining stretch
        ckpt = WorkflowCheckpointer(ckpt_dir, every=gens_snapshot, keep=10)
        expect = _init_shapes(wf, jax.random.PRNGKey(0))
        snap = ckpt.load(gens_snapshot, expect_like=expect)
        assert snap is not None, "1-process snapshot missing"
        st = restore_layouts(snap, mesh=mesh)
        for _ in range(gens_total - gens_snapshot):
            st = wf.step(st)
        solo = json.load(
            open(os.path.join(workdir, "result_solo.json"))
        )["final"]
        np.testing.assert_allclose(
            np.asarray(dist.host_value(st.algo.mean)),
            np.asarray(solo["mean"], dtype=np.float32),
            rtol=1e-5, atol=1e-5,
            err_msg="1-process snapshot resumed on the pod diverged",
        )
        result["collectives"]["resume_1_to_n"] = "ok"

        # (c) pod save: process-0-writes + barrier — ONE manifest
        pod_dir = os.path.join(workdir, f"pod_ckpt_{nprocs}x{n_local}")
        pod_ckpt = WorkflowCheckpointer(pod_dir, every=1, keep=3)
        pod_ckpt.save(st)
        manifests = [
            f for f in os.listdir(pod_dir) if f.endswith(".manifest.json")
        ]
        assert len(manifests) == 1, manifests
        if jax.process_index() == 0:
            back = pod_ckpt.latest(expect_like=st)
            assert back is not None
            np.testing.assert_allclose(
                np.asarray(back.algo.mean),
                np.asarray(dist.host_value(st.algo.mean)),
                rtol=0, atol=0,
            )
        result["collectives"]["pod_save"] = "ok"

        # (d) monitor io_callback pinning: history fires on process 0 only
        from evox_tpu import StdWorkflow
        from evox_tpu.monitors import EvalMonitor
        from evox_tpu.problems.numerical import Sphere

        mon = EvalMonitor(full_fit_history=True)
        mwf = StdWorkflow(_pso(jnp), Sphere(), monitors=[mon], mesh=mesh)
        mstate = mwf.init(jax.random.PRNGKey(0))
        for _ in range(3):
            mstate = mwf.step(mstate)
        jax.effects_barrier()
        n_hist = len(mon.get_fitness_history())
        expected = 3 if jax.process_index() == 0 else 0
        assert n_hist == expected, (jax.process_index(), n_hist, expected)
        result["collectives"]["monitor_process0_pinning"] = "ok"

    # (e) AOT per-process memory table at the acceptance shape
    mem_pop, mem_dim = spec.get("mem_shape", (32768, 64))
    try:
        from evox_tpu.core.xla_cost import analyze_callable

        mwf = _law_workflow(mesh, n_total, pop=mem_pop, dim=mem_dim)
        sds = _init_shapes(mwf, jax.random.PRNGKey(0))
        sds = sds.replace(first_step=False)
        mem = analyze_callable(mwf._step, sds).get("memory") or {}
        peak = mem.get("peak_bytes_estimate")
        if peak:
            result["memory"] = {
                "pop": mem_pop,
                "dim": mem_dim,
                "per_device_peak_bytes": int(peak),
                # memory_analysis reports per-device stats for SPMD
                # programs (PR-10 precedent); a process's peak is its
                # local devices' sum
                "per_process_peak_bytes": int(peak) * n_local,
                "n_local": n_local,
                "full_pop_bytes": mem_pop * mem_dim * 4,
            }
    except Exception as e:  # the table must never sink the laws
        result["memory"] = {"error": f"{type(e).__name__}: {e}"}


def _metrics_tier(spec, result, dist, nprocs, n_local, workdir):
    """PR-16 pod-metrics law: every process drives a real workload with
    its own :class:`FlightRecorder` stream, stamping ``barrier`` records
    only AFTER the KV-store rendezvous (``dist.process_barrier`` — no
    XLA collective, so this tier is Tier A on any jaxlib) releases; the
    stamps then bracket a true cross-process alignment instant. Process
    0 merges the per-process streams into ONE named-track Perfetto
    trace plus an aggregated stream and runs both artifacts through the
    public validator (tools/check_report.py)."""
    import jax

    from evox_tpu.workflows.flightrec import FlightRecorder, merge_pod_streams

    pid = int(spec["pid"])
    # per-LEG namespace: the solo leg and the pod leg share workdir, and
    # a recorder pointed at an existing stream would adopt and APPEND a
    # second run whose counters restart — a legal-looking file the
    # monotonicity law correctly rejects
    mdir = os.path.join(workdir, f"metrics_{nprocs}x{n_local}")
    fr = FlightRecorder(directory=os.path.join(mdir, f"p{pid}"))
    assert fr.process_id == pid and fr.process_count == nprocs, (
        "FlightRecorder mis-detected pod identity",
        fr.process_id,
        fr.process_count,
    )
    # replicated twin of the law workload: identical trajectory on every
    # process, no collective — the metrics plane is what's under test
    wf = _law_workflow(None, nprocs * n_local)
    state = wf.init(jax.random.PRNGKey(3))
    chunk, total = 2, 6
    for _ in range(0, total, chunk):
        t0 = time.perf_counter()
        state = wf.run(state, chunk)
        sigma = float(dist.host_value(state.algo.sigma))  # real fetch
        fr.count("slo.tenant_gens", chunk)
        fr.observe("worker.chunk_ms", (time.perf_counter() - t0) * 1e3)
        fr.set("worker.sigma", sigma)
        g = int(state.generation)
        dist.process_barrier(f"metrics_g{g}", timeout_s=120.0)
        fr.barrier(f"pod:metrics_g{g}")
        fr.sample(generation=g)
    fr.event("worker.done", generation=int(state.generation))
    info = {"stream": fr.stream.report()}
    # every stream must be durably complete before process 0 reads them
    dist.process_barrier("metrics_merge", timeout_s=120.0)
    if pid == 0:
        dirs = [os.path.join(mdir, f"p{p}") for p in range(nprocs)]
        trace_path = os.path.join(mdir, "pod_trace.json")
        merged_path = os.path.join(mdir, "pod_metrics.jsonl")
        merged = merge_pod_streams(
            dirs, trace_path=trace_path, merged_stream_path=merged_path
        )
        names = {
            e["args"]["name"]
            for e in merged["trace"]["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        want = {f"process {p}: metrics" for p in range(nprocs)}
        assert want <= names, (names, want)
        assert merged["processes"] == nprocs
        assert len(merged["offsets_s"]) == nprocs, merged["offsets_s"]
        errs = _validate_files(spec["repo"], [merged_path, trace_path])
        assert not errs, errs
        info["merged"] = {
            "processes": merged["processes"],
            "offsets_s": merged["offsets_s"],
            "records": len(merged["records"]),
            "trace_events": len(merged["trace"]["traceEvents"]),
            "named_tracks": sorted(names),
            "validated": ["pod_metrics.jsonl", "pod_trace.json"],
        }
    result["metrics"] = info


def _dump(result, workdir, tag):
    path = os.path.join(workdir, f"result_{tag}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(path + ".tmp", path)


# ------------------------------------------------------------- control pod


def _control_pod_run(spec: dict) -> None:
    """CONTROL-POD mode (ISSUE 18): one pod driver of the multi-pod
    control plane, as its OWN process. The parent gateway owns the
    ledger and the pod's journal/checkpoint directories; this child
    either ADOPTS the pod (``adopt: true`` — recover every journaled
    bucket, the single-writer handoff: the parent must not append to
    the pod's journals while this process lives) or submits fresh specs
    from ``specs_file`` (a JSON list of elastic submit records), then
    serves round by round. ``kill_after_round: N`` SIGKILLs the process
    at that round boundary — the real-process pod-death flavor of the
    kill-anywhere law; the parent then steals from the journals this
    process fsynced. Spec keys: repo, workdir, tag, pod_dir, factory
    ("module:callable"), width, chunk, cache_dir?, specs_file?, adopt?,
    kill_after_round?, n_local?."""
    repo = spec["repo"]
    workdir = spec["workdir"]
    tag = spec.get("tag", "control_pod")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{int(spec.get('n_local', 8))} --xla_backend_optimization_level=0"
    )
    sys.path.insert(0, repo)
    for extra in spec.get("sys_path", []):
        sys.path.insert(0, extra)

    import faulthandler

    faulthandler.enable()
    hard = float(spec.get("harness_timeout", 600.0))
    faulthandler.dump_traceback_later(max(hard * 0.8, 5.0), exit=False)

    import importlib

    import jax

    jax.config.update("jax_platforms", "cpu")

    from evox_tpu.workflows.control_plane import (
        _elastic_spec_from_record,
        _parse_bucket_key,
    )
    from evox_tpu.workflows.elastic import ElasticServer
    from evox_tpu.workflows.journal import jsonable

    mod_name, fn_name = spec["factory"].split(":")
    factory = getattr(importlib.import_module(mod_name), fn_name)
    pod_dir = spec["pod_dir"]
    server = ElasticServer(
        factory=factory,
        width=int(spec.get("width", 2)),
        chunk=int(spec.get("chunk", 3)),
        cache_dir=spec.get("cache_dir"),
        journal_dir=os.path.join(pod_dir, "journal"),
        checkpoint_dir=os.path.join(pod_dir, "ckpt"),
    )
    if spec.get("adopt"):
        jroot = os.path.join(pod_dir, "journal")
        for name in sorted(os.listdir(jroot)) if os.path.isdir(jroot) else []:
            shape = _parse_bucket_key(name)
            if shape is not None and os.path.isdir(os.path.join(jroot, name)):
                server.recover_bucket(shape)
    if spec.get("specs_file"):
        with open(spec["specs_file"]) as f:
            recs = json.load(f)
        for rec in recs:
            server.submit(_elastic_spec_from_record(rec))
    kill_after = spec.get("kill_after_round")
    rounds = 0
    while server.has_work():
        server.serve_round()
        rounds += 1
        if kill_after is not None and rounds >= int(kill_after):
            os.kill(os.getpid(), signal.SIGKILL)
    result = jsonable(
        {
            "tag": tag,
            "pod_dir": pod_dir,
            "rounds": rounds,
            "results": server.results(),
        }
    )
    _dump(result, workdir, tag)
    print(f"CONTROL_POD {tag} OK", flush=True)


# ---------------------------------------------------------------- pod chaos


def _arm_chaos(chaos: dict, wf) -> None:
    """Arm the scripted self-injection on THIS (victim) worker: a real
    ``os.kill(os.getpid(), SIGKILL)`` at the named point, or a hung
    chunk (the workload thread sleeps forever while the heartbeat
    thread keeps beating — the hung-collective shape). Points:

    - ``pre_barrier``: after the chunk dispatch whose result reaches
      ``at_gen`` returns, BEFORE the chunk-boundary rendezvous.
    - ``mid_chunk``: inside the supervised dispatch of the chunk that
      contains ``at_gen`` (survivors are mid-collective / pre-barrier).
    - ``mid_checkpoint``: inside the durable-write path, between the
      committed data file and its manifest (the torn-snapshot shape,
      via the checkpoint layer's crash hook — victim must be the
      writing process 0); recovery must fall back one barrier.
    - ``hang``: the chunk containing ``at_gen`` never returns.
    """
    kind = chaos["kind"]
    at_gen = int(chaos.get("at_gen", 0))
    if kind == "mid_checkpoint":
        from evox_tpu.workflows import checkpoint as _ckpt

        nth = int(chaos.get("nth", 2))
        seen = {"n": 0}

        def hook(point: str) -> None:
            if point.startswith("manifest_pending"):
                seen["n"] += 1
                if seen["n"] >= nth:
                    os.kill(os.getpid(), signal.SIGKILL)

        _ckpt._CRASH_HOOK = hook
        return

    orig = wf.run
    armed = {"on": True}

    def run(st, n):
        entering = armed["on"] and int(st.generation) + int(n) >= at_gen
        if kind == "mid_chunk" and entering:
            armed["on"] = False
            os.kill(os.getpid(), signal.SIGKILL)
        if kind == "hang" and entering:
            armed["on"] = False
            time.sleep(3600.0)
        out = orig(st, n)
        if kind == "pre_barrier" and armed["on"] and int(out.generation) >= at_gen:
            armed["on"] = False
            os.kill(os.getpid(), signal.SIGKILL)
        return out

    wf.run = run


def _pod_run(spec: dict, result: dict, pr: dict) -> None:
    """POD-RUN mode: drive the supervised chunked workload under the
    PodSupervisor fault domain. The workload is the PR-10/13 law
    substrate — ``ShardedES(SepCMAES)`` on Sphere — POP-sharded over the
    pod mesh where the backend can run cross-process collectives
    (``pr["sharded"]``), else the REPLICATED twin of the same sampling
    law (``mesh=None`` with the same ``n_shards``: every process
    computes the identical trajectory from the identical seed, so pod
    semantics — heartbeats, barriers, pod-barrier checkpoints, drain —
    stay real while the math needs no collective). ``n_shards`` is
    pinned in the spec, NOT derived from the live device count, so a
    re-formed (shrunken) pod reproduces the original sampling law."""
    import jax
    import numpy as np

    import evox_tpu  # noqa: F401
    from evox_tpu import (
        GenerationExecutor,
        PodFailureError,
        PodSupervisor,
        WorkflowCheckpointer,
        run_report,
    )
    from evox_tpu.core import distributed as dist

    pid, nprocs = int(spec["pid"]), int(spec["nprocs"])
    workdir, tag = spec["workdir"], spec["tag"]
    chunk, total = int(pr["chunk"]), int(pr["total"])
    epoch = int(pr.get("epoch", 0))
    subdir = os.path.join(workdir, pr.get("subdir", "pod"))
    os.makedirs(subdir, exist_ok=True)
    deadline_s = float(pr.get("deadline_s", 8.0))

    mesh = dist.create_pod_mesh() if pr.get("sharded") else None
    wf = _law_workflow(mesh, int(pr["n_shards"]), pop=int(pr.get("pop", 32)))
    # the pod flight recorder: pod.* transitions, supervised-barrier
    # stamps, and the black-box tail every classified post-mortem must
    # carry (PR-16 pod law) — stream under the pod's own subdir so a
    # re-formed epoch appends to a fresh directory
    from evox_tpu.workflows.flightrec import FlightRecorder

    fr = FlightRecorder(
        directory=os.path.join(subdir, f"metrics_e{epoch}_p{pid}")
    )
    sup = PodSupervisor(
        deadline_s=deadline_s,
        heartbeat_interval_s=float(pr.get("hb_interval_s", 0.2)),
        journal=os.path.join(subdir, "pod_journal"),
        epoch=epoch,
        metrics=fr,
    ).start()
    sup.install_sigterm_drain()
    if pr.get("resume"):
        sup.note_reform(pr.get("survivors", [pid]), int(pr.get("reform_from", 0)))
    ck = WorkflowCheckpointer(
        os.path.join(subdir, "pod_ckpt"),
        every=chunk,
        keep=10,
        barrier_timeout_s=deadline_s,
    )

    # warm the compiled first-step peel + steady loop on a scratch state
    # BEFORE the supervised phase, then align: the first supervised
    # chunk must not spend its deadline on compilation skew
    warm = wf.init(jax.random.PRNGKey(999))
    jax.block_until_ready(wf.run(warm, chunk))
    sup.barrier(f"warmup_e{epoch}", timeout_s=120.0)

    pace = float(pr.get("pace_s", 0.0))
    if pace > 0:
        # pace the chunks so a parent-delivered signal (SIGSTOP /
        # SIGTERM preemption notice) demonstrably lands MID-RUN; every
        # member paces identically, so lockstep is preserved
        orig_run = wf.run

        def paced(st, n):
            time.sleep(pace)
            return orig_run(st, n)

        wf.run = paced
    if pr.get("chaos"):
        _arm_chaos(pr["chaos"], wf)

    state = wf.init(jax.random.PRNGKey(int(pr.get("seed", 17))))
    resume_generation = None
    if pr.get("resume"):
        state = sup.resume_from_barrier(wf, ck, expect_like=state)
        resume_generation = int(state.generation)
    ex = GenerationExecutor(pod_supervisor=sup, metrics=fr)
    try:
        state = ex.run_fused(
            wf,
            state,
            total - int(state.generation),
            checkpointer=ck,
            chunk=chunk,
        )
    except PodFailureError as e:
        result["pod"] = {
            "status": "failed",
            "classification": e.classification,
            "post_mortem": e.post_mortem,
            "report": sup.report(),
        }
        _dump(result, workdir, tag)
        sup.stop()
        print(f"WORKER {tag} PODFAIL", flush=True)
        # the detected-and-aborted signal: distinguishable from both a
        # clean exit and a raw crash; os._exit dodges jax's atexit
        # teardown racing the abandoned watchdog/collective threads
        sys.stdout.flush()
        os._exit(POD_FAULT_EXIT)

    report = run_report(wf, state, metrics=fr)
    result["pod"] = {
        "status": sup.report()["outcome"],
        "generation": int(state.generation),
        "resume_generation": resume_generation,
        "final": {
            "mean": np.asarray(
                dist.host_value(state.algo.mean), dtype=np.float64
            ).tolist(),
            "sigma": float(dist.host_value(state.algo.sigma)),
        },
        "report": report.get("pod_supervisor"),
        "report_valid": _validate_report(spec["repo"], report),
    }
    sup.stop()


def _load_validator(repo: str):
    import importlib.util

    cr_spec = importlib.util.spec_from_file_location(
        "evox_tpu_check_report", os.path.join(repo, "tools", "check_report.py")
    )
    cr = importlib.util.module_from_spec(cr_spec)
    cr_spec.loader.exec_module(cr)
    return cr


def _validate_report(repo: str, report: dict):
    """Worker-side schema check of the run_report (the chaos tier's
    reports never reach the in-process validator tests otherwise)."""
    try:
        return _load_validator(repo).validate_run_report(report)
    except Exception as e:  # pragma: no cover - validator load failure
        return [f"validator unavailable: {type(e).__name__}: {e}"]


def _validate_files(repo: str, paths):
    """Worker-side ``check_report.validate_file`` over merged metrics
    artifacts (stream .jsonl + Perfetto trace .json)."""
    try:
        cr = _load_validator(repo)
        errs = []
        for p in paths:
            errs += [f"{os.path.basename(p)}: {e}" for e in cr.validate_file(p)]
        return errs
    except Exception as e:  # pragma: no cover - validator load failure
        return [f"validator unavailable: {type(e).__name__}: {e}"]


class PodManager:
    """Spawn, watch, signal, and RE-FORM pods of real worker processes —
    the driver-side rung of the ISSUE-14 escalation ladder. A pod whose
    member died (or hung, or was preempted) aborts itself with
    classified post-mortems (exit code :data:`POD_FAULT_EXIT`); this
    driver collects them, computes the survivor set, and respawns a
    SHRUNKEN pod against a fresh coordinator rendezvous (new port, new
    ``process_id`` assignments, ``epoch+1`` KV namespace) whose workers
    build ``create_pod_mesh`` over the surviving device set and resume
    from the newest intact pod-barrier checkpoint.

    ``run_scenario`` drives the full chaos matrix end to end:
    reference pod → injured pod (scripted self-kill or parent-delivered
    SIGSTOP/SIGTERM) → detection/post-mortem collection → re-formation
    → resumed completion. Scenario names: :data:`SCENARIOS`."""

    SCENARIOS = (
        "sigkill_pre_barrier",
        "sigkill_mid_chunk",
        "sigkill_mid_checkpoint",
        "sigstop",
        "hang",
        "coordinator_kill",
        "sigterm_drain",
    )

    #: scenario -> the classification every survivor's post-mortem must
    #: carry (sigterm_drain has no failure: it drains cleanly)
    EXPECTED_CLASS = {
        "sigkill_pre_barrier": "worker_dead",
        "sigkill_mid_chunk": "worker_dead",
        "sigkill_mid_checkpoint": "coordinator_loss",
        "sigstop": "worker_dead",
        "hang": "hung_collective",
        "coordinator_kill": "coordinator_loss",
    }

    def __init__(self, repo: str, workdir: str, n_local: int = 2,
                 timeout: float = 600.0):
        self.repo = repo
        self.workdir = workdir
        self.n_local = int(n_local)
        self.timeout = float(timeout)
        self.env = dict(os.environ)
        self.env.pop("XLA_FLAGS", None)
        # CPU pods only: never let a child reach an accelerator the
        # parent may hold (the workers also pin themselves in main())
        self.env["JAX_PLATFORMS"] = "cpu"

    @staticmethod
    def free_port() -> str:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return str(s.getsockname()[1])

    # ------------------------------------------------------------- plumbing
    def spawn_pod(self, nprocs: int, pod_cfg: dict, leg: str, epoch: int = 0,
                  per_pid: dict = None):
        """Spawn ``nprocs`` pod-run workers against a fresh coordinator.
        ``per_pid`` maps a process id to extra pod_cfg entries (the
        victim's chaos script). Returns ``(procs, tags)``."""
        port = self.free_port()
        procs, tags = [], []
        for pid in range(nprocs):
            tag = f"{leg}_e{epoch}_p{pid}"
            cfg = dict(pod_cfg, epoch=epoch)
            if per_pid and pid in per_pid:
                cfg.update(per_pid[pid])
            worker_spec = {
                "pid": pid,
                "nprocs": nprocs,
                "n_local": self.n_local,
                "workdir": self.workdir,
                "repo": self.repo,
                "port": port,
                "tag": tag,
                "harness_timeout": self.timeout,
                "pod_run": cfg,
            }
            procs.append(
                subprocess.Popen(
                    [sys.executable, _WORKER_FILE, json.dumps(worker_spec)],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                    env=self.env,
                )
            )
            tags.append(tag)
        return procs, tags

    def wait(self, procs, tags):
        """Join every worker under ONE fleet deadline; returns
        ``[{tag, rc, out}]`` WITHOUT asserting exit codes — chaos legs
        exit nonzero by design."""
        deadline = time.monotonic() + self.timeout
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(
                    timeout=max(deadline - time.monotonic(), 1.0)
                )
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise RuntimeError(
                    f"PodManager: pod {tags} timed out after {self.timeout}s"
                )
            outs.append(out)
        return [
            {"tag": t, "rc": p.returncode, "out": o}
            for t, p, o in zip(tags, procs, outs)
        ]

    def load_result(self, tag: str) -> dict:
        with open(os.path.join(self.workdir, f"result_{tag}.json")) as f:
            return json.load(f)

    def wait_for_file(self, path: str, timeout_s: float = None) -> None:
        deadline = time.monotonic() + (
            self.timeout if timeout_s is None else timeout_s
        )
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise RuntimeError(f"PodManager: {path} never appeared")
            time.sleep(0.05)

    @staticmethod
    def _require(cond, msg, entries=None):
        if not cond:
            detail = ""
            if entries:
                detail = "\n" + "\n".join(
                    f"--- {e['tag']} (rc={e['rc']}) ---\n{e['out'][-2000:]}"
                    for e in entries
                )
            raise RuntimeError(f"PodManager: {msg}{detail}")

    # ------------------------------------------------------------ scenarios
    def run_scenario(
        self,
        scenario: str,
        nprocs: int = 2,
        chunk: int = 2,
        total: int = 8,
        kill_gen: int = 4,
        deadline_s: float = 5.0,
        hb_interval_s: float = 0.2,
        sharded: bool = False,
        seed: int = 17,
    ) -> dict:
        """One full chaos law: reference run → injured run → detection →
        re-formation on the survivor set → resumed completion. Returns
        the structured summary the tests assert on (detections,
        post-mortems, reference vs resumed finals, pod reports).

        ``deadline_s`` must comfortably undercut the coordination
        CLIENT's own missed-heartbeat abort (~10 s after coordinator
        death it SIGABRTs the process from inside jaxlib): the
        classified deadline → census → post-mortem path has to win that
        race, or a coordinator-loss scenario dies silently with rc -6
        instead of exiting 23 with a diagnosis (observed at 8 s;
        ``PodSupervisor`` clamps a deadline that cannot win it)."""
        if scenario not in self.SCENARIOS:
            raise ValueError(
                f"unknown scenario {scenario!r}; expected one of "
                f"{self.SCENARIOS}"
            )
        n_shards = nprocs * self.n_local
        base = {
            "chunk": chunk,
            "total": total,
            "deadline_s": deadline_s,
            "hb_interval_s": hb_interval_s,
            "sharded": bool(sharded),
            "n_shards": n_shards,
            # pop must divide by n_shards (ShardedES law); the default
            # 32 only does for pow2 pods — scale up for e.g. nprocs=3
            "pop": 32 if 32 % n_shards == 0 else 4 * n_shards,
            "seed": seed,
        }
        summary = {
            "scenario": scenario,
            "n_processes": nprocs,
            "sharded": bool(sharded),
        }

        # --- reference leg: the uninjured trajectory. Replicated mode is
        # process-count-invariant by construction (every member computes
        # the identical local trajectory), so ONE process suffices;
        # sharded mode needs the full pod for the collective math.
        ref_n = nprocs if sharded else 1
        ref = self.wait(*self.spawn_pod(ref_n, dict(base, subdir="ref"), "ref"))
        self._require(all(e["rc"] == 0 for e in ref), "reference pod failed", ref)
        ref_pod = self.load_result(ref[0]["tag"])["pod"]
        self._require(
            ref_pod["status"] == "clean"
            and ref_pod["generation"] == total
            and not ref_pod["report_valid"],
            f"reference leg incoherent: {ref_pod.get('status')}, "
            f"gen {ref_pod.get('generation')}, "
            f"report errors {ref_pod.get('report_valid')}",
            ref,
        )
        summary["reference"] = {
            "generation": ref_pod["generation"],
            "final": ref_pod["final"],
        }

        # --- injured leg ---------------------------------------------------
        chaos_dir = os.path.join(self.workdir, "chaos")
        parent_side = scenario in ("sigstop", "sigterm_drain")
        victim = (
            0
            if scenario in ("sigkill_mid_checkpoint", "coordinator_kill")
            else nprocs - 1
        )
        per_pid = None
        if not parent_side:
            kind = {
                "sigkill_pre_barrier": "pre_barrier",
                "sigkill_mid_chunk": "mid_chunk",
                "sigkill_mid_checkpoint": "mid_checkpoint",
                "hang": "hang",
                "coordinator_kill": "pre_barrier",
            }[scenario]
            chaos = {"kind": kind, "at_gen": kill_gen}
            if kind == "mid_checkpoint":
                chaos["nth"] = max(kill_gen // chunk, 1)
            per_pid = {victim: {"chaos": chaos}}
        cfg = dict(base, subdir="chaos")
        if parent_side:
            # pace the chunks so the parent's signal demonstrably lands
            # MID-RUN (first barrier snapshot is the synchronization point)
            cfg["pace_s"] = 0.4
        procs, tags = self.spawn_pod(nprocs, cfg, "chaos", per_pid=per_pid)
        first_snap = os.path.join(
            chaos_dir, "pod_ckpt", f"ckpt_{chunk:08d}.pkl.manifest.json"
        )
        if scenario == "sigstop":
            self.wait_for_file(first_snap)
            os.kill(procs[victim].pid, signal.SIGSTOP)
            # reap survivors first — the stopped victim never exits on
            # its own; SIGCONT+SIGKILL it once the survivors diagnosed.
            # finally: even a survivor-wait timeout must not leak the
            # victim in the stopped state (holding its port + workdir)
            try:
                survivors_entries = self.wait(
                    [p for i, p in enumerate(procs) if i != victim],
                    [t for i, t in enumerate(tags) if i != victim],
                )
            finally:
                try:
                    os.kill(procs[victim].pid, signal.SIGCONT)
                except OSError:
                    pass
                procs[victim].kill()
                procs[victim].communicate()
            entries = survivors_entries
            victim_rc = procs[victim].returncode
        elif scenario == "sigterm_drain":
            self.wait_for_file(first_snap)
            for p in procs:
                p.send_signal(signal.SIGTERM)
            entries = self.wait(procs, tags)
            victim_rc = None
        else:
            entries = self.wait(procs, tags)
            victim_rc = entries[victim]["rc"]
            entries = [e for i, e in enumerate(entries) if i != victim]
        summary["victim"] = None if scenario == "sigterm_drain" else victim
        summary["victim_rc"] = victim_rc

        if scenario == "sigterm_drain":
            # the drain law: every member finished its in-flight chunk,
            # agreed on ONE drain boundary, fsynced the final barrier
            # checkpoint, and exited 0
            self._require(
                all(e["rc"] == 0 for e in entries), "drain leg exit != 0",
                entries,
            )
            pods = [self.load_result(e["tag"])["pod"] for e in entries]
            gens = {p["generation"] for p in pods}
            self._require(
                all(p["status"] == "drained" for p in pods)
                and len(gens) == 1
                and chunk <= min(gens) <= total,
                f"drain incoherent: statuses "
                f"{[p['status'] for p in pods]}, generations {gens}",
                entries,
            )
            drained_gen = gens.pop()
            summary["drain"] = {
                "generation": drained_gen,
                "reports": [p["report"] for p in pods],
            }
            survivors = list(range(nprocs))
        else:
            # detection: every survivor terminated PROMPTLY (we joined
            # them all above — no eternal block), each in one of two
            # shapes. (a) exit 23: OUR classified post-mortem. (b) for
            # coordinator-death scenarios only, jaxlib's own
            # coordination-fatal (SIGABRT from the C++ client the
            # moment its coordinator connection dies) can win the race
            # with the classified path — a prompt, logged termination,
            # observed nondeterministically on the same box; the pod
            # layer's job is the re-formation either way.
            coordinator_dead = victim == 0
            expected = self.EXPECTED_CLASS[scenario]
            detections, jaxlib_fatals = [], []
            for e in entries:
                if e["rc"] == POD_FAULT_EXIT:
                    pod = self.load_result(e["tag"])["pod"]
                    pm = pod["post_mortem"]
                    detections.append(
                        {
                            "tag": e["tag"],
                            "classification": pod["classification"],
                            "detect_s": pm["detect_s"],
                            "census": pm.get("census"),
                            "entry": pm.get("entry"),
                            "flight_recorder_tail": len(
                                pm.get("flight_recorder") or []
                            ),
                        }
                    )
                elif coordinator_dead and e["rc"] not in (0, None):
                    jaxlib_fatals.append({"tag": e["tag"], "rc": e["rc"]})
                else:
                    self._require(
                        False,
                        f"survivor {e['tag']} terminated unclassified "
                        f"(rc {e['rc']})",
                        entries,
                    )
            self._require(
                all(d["classification"] == expected for d in detections),
                f"classification mismatch: wanted {expected}, got "
                f"{[d['classification'] for d in detections]}",
                entries,
            )
            # PR-16 pod law: every classified post-mortem carries the
            # flight-recorder black-box tail
            self._require(
                all(d["flight_recorder_tail"] > 0 for d in detections),
                f"post-mortem missing flight-recorder tail: "
                f"{[d['flight_recorder_tail'] for d in detections]}",
                entries,
            )
            budget = deadline_s + 2.0 * (2.0 * hb_interval_s + 0.2) + 10.0
            self._require(
                all(d["detect_s"] <= budget for d in detections),
                f"detection exceeded budget {budget}s: "
                f"{[d['detect_s'] for d in detections]}",
            )
            if scenario == "hang":
                # the hung member's own watchdog diagnosed it too
                self._require(
                    victim_rc == POD_FAULT_EXIT,
                    f"hung victim rc {victim_rc} != {POD_FAULT_EXIT}",
                )
            summary["detections"] = detections
            summary["jaxlib_fatals"] = jaxlib_fatals
            survivors = [p for p in range(nprocs) if p != victim]

        # --- re-formation: shrink to the survivor set and resume ----------
        # sharded resumes need the survivor DEVICE total to divide the
        # pinned n_shards (whole sample blocks per device); otherwise
        # the survivors resume on the REPLICATED twin of the same law —
        # documented sharded≡replicated contract, still the same math
        reform_sharded = bool(sharded) and (
            n_shards % (len(survivors) * self.n_local) == 0
        )
        re_cfg = dict(
            base,
            subdir="chaos",
            resume=True,
            reform_from=0,
            survivors=survivors,
            sharded=reform_sharded,
        )
        rentries = self.wait(
            *self.spawn_pod(len(survivors), re_cfg, "reform", epoch=1)
        )
        self._require(
            all(e["rc"] == 0 for e in rentries), "re-formed pod failed",
            rentries,
        )
        rpods = [self.load_result(e["tag"])["pod"] for e in rentries]
        self._require(
            all(
                p["generation"] == total and not p["report_valid"]
                for p in rpods
            ),
            f"re-formed pod incoherent: generations "
            f"{[p['generation'] for p in rpods]}, report errors "
            f"{[p['report_valid'] for p in rpods]}",
            rentries,
        )
        summary["survivors"] = survivors
        summary["reformed"] = {
            "n_processes": len(survivors),
            "mode": "sharded" if reform_sharded else "replicated",
            "generation": rpods[0]["generation"],
            "resume_generation": rpods[0]["resume_generation"],
            "final": rpods[0]["final"],
            "report": rpods[0]["report"],
        }
        return summary


if __name__ == "__main__":
    main()


"""Static analysis: host callbacks must not creep into hot-path modules.

io_callback / pure_callback — and jax.debug.*, which lowers to the same
host-callback machinery — put a device-to-host round trip into every
generation of the compiled program, cannot run under ``vmap`` (tenant
fleets) and tie the program to a runtime that can call back into Python.
Hot paths therefore use none. This test AST-scans every module under
evox_tpu/ and fails if a callback primitive appears outside the explicit
allowlist of host-only modules, so new code cannot silently put host
traffic back into a hot path. Docstrings and comments never trigger it
(AST, not grep).

The allowlist is also checked for staleness: an entry whose module no
longer uses callbacks must be removed, keeping the host-only surface
exactly as small as it really is.
"""

import ast
import pathlib

PKG = pathlib.Path(__file__).resolve().parent.parent / "evox_tpu"

# Host-only modules whose PURPOSE is host traffic: monitors that stream
# history/files to the host, and the declared host-problem paths. Each is
# documented (GUIDE.md §6) as requiring a callback-capable backend.
ALLOWED = {
    "monitors/eval_monitor.py",  # full_*_history streaming (opt-in)
    "monitors/pop_monitor.py",  # host-side population history
    "monitors/evoxvis_monitor.py",  # Arrow IPC file streaming
    "monitors/checkpoint_monitor.py",  # host checkpoint saves
    "monitors/profiler.py",  # StepTimerMonitor (ordered host timestamps)
    "workflows/common.py",  # callback_evaluate: external-problem contract
    "problems/neuroevolution/hostenv.py",  # in-jit host env stepping
    "problems/supervised/dataset.py",  # in-jit host batch source
    "problems/evoxbench.py",  # host benchmark backend
}

CALLBACK_NAMES = {"io_callback", "pure_callback"}


def _uses_host_callbacks(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        # from jax.experimental import io_callback / jax.pure_callback import
        if isinstance(node, ast.ImportFrom):
            if any(alias.name in CALLBACK_NAMES for alias in node.names):
                return True
        # bare or attribute references: io_callback(...), jax.pure_callback
        elif isinstance(node, ast.Name) and node.id in CALLBACK_NAMES:
            return True
        elif isinstance(node, ast.Attribute):
            if node.attr in CALLBACK_NAMES:
                return True
            # jax.debug.print / jax.debug.callback / jax.debug.breakpoint
            v = node.value
            if (
                isinstance(v, ast.Attribute)
                and v.attr == "debug"
                and isinstance(v.value, ast.Name)
                and v.value.id == "jax"
            ):
                return True
    return False


_SCAN_CACHE = None


def _scan():
    # memoized: every pin test re-ran the full-package AST parse
    # (~1 s × 14 tests on one core); the sources cannot change mid
    # pytest session, so one scan serves them all (a fresh copy is
    # returned so no test can mutate another's view)
    global _SCAN_CACHE
    if _SCAN_CACHE is None:
        users = set()
        for path in sorted(PKG.rglob("*.py")):
            rel = path.relative_to(PKG).as_posix()
            tree = ast.parse(path.read_text(), filename=str(path))
            if _uses_host_callbacks(tree):
                users.add(rel)
        _SCAN_CACHE = frozenset(users)
    return set(_SCAN_CACHE)


def test_no_host_callbacks_outside_allowlist():
    users = _scan()
    violations = users - ALLOWED
    assert not violations, (
        "host-callback primitives (io_callback/pure_callback/jax.debug) "
        f"found outside the host-only allowlist: {sorted(violations)}. "
        "A host callback stalls every generation on the host and cannot "
        "run under vmap — keep hot paths callback-free (TelemetryMonitor/core.instrument patterns) or, "
        "for a genuinely host-only module, extend the allowlist with a "
        "justification comment."
    )


def test_allowlist_has_no_stale_entries():
    users = _scan()
    stale = ALLOWED - users
    assert not stale, (
        f"allowlisted modules no longer use host callbacks: {sorted(stale)} "
        "— remove them so the host-only surface stays minimal"
    )


def test_telemetry_modules_exist_and_are_callback_free():
    """The observability tentpole must stay callback-free by construction."""
    users = _scan()
    for rel in ("monitors/telemetry.py", "core/instrument.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_lineage_and_attribution_are_callback_free():
    """The search-dynamics tentpole (ISSUE 19) records lineage/ledger
    rings entirely on device — its forensics (best_ancestry, ledger,
    search_report) read fetched arrays AFTER the run. A callback in
    either module would break the one place convergence forensics
    matter most: long fused runs that never leave the device."""
    users = _scan()
    for rel in ("monitors/lineage.py", "core/attribution.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_control_plane_is_callback_free():
    """The multi-pod gateway (ISSUE 18) is host-side scheduling by
    construction — ledger appends, journal parses, checkpoint-manifest
    probes. A callback anywhere in it (or in the serving modules it
    composes) would break the one deployment it exists for: a gateway
    over TPU pods it must never stall."""
    users = _scan()
    for rel in (
        "workflows/control_plane.py",
        "workflows/journal.py",
        "workflows/flightrec.py",
    ):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_roofline_modules_are_callback_free():
    """The roofline analytics layer must stay callback-free by
    construction: AOT lowering/compiling (core/xla_cost.py) and the
    Chrome-trace export path (core/instrument.py write_chrome_trace) are
    pure host-side work on data recorded outside traced code — a host
    callback anywhere in them would put host traffic into the programs
    `run_report(analyze)` exists to cost. tools/check_report.py is
    scanned too (it imports nothing from jax today; the pin keeps it
    that way on the callback axis)."""
    users = _scan()
    for rel in ("core/xla_cost.py", "core/instrument.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"
    tools_validator = (
        pathlib.Path(__file__).resolve().parent.parent
        / "tools"
        / "check_report.py"
    )
    tree = ast.parse(tools_validator.read_text(), filename=str(tools_validator))
    assert not _uses_host_callbacks(tree), (
        "tools/check_report.py must stay callback-free"
    )


def test_run_report_with_roofline_is_callback_free(ceilings):
    """Functional half of the pin: run_report with analysis enabled plus
    the trace export complete WITHOUT any callback primitive executing —
    i.e. it succeeds end-to-end while the AST scan above proves no
    callback primitive exists to lower."""
    import jax
    import jax.numpy as jnp

    from evox_tpu import (
        CostAnalyzer,
        StdWorkflow,
        instrument,
        run_report,
        write_chrome_trace,
    )
    from evox_tpu.algorithms.so.pso import PSO
    from evox_tpu.monitors import TelemetryMonitor
    from evox_tpu.problems.numerical import Sphere

    wf = StdWorkflow(
        PSO(lb=-jnp.ones(4), ub=jnp.ones(4), pop_size=8),
        Sphere(),
        monitors=(TelemetryMonitor(capacity=4),),
    )
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 3)
    report = run_report(
        wf, state, recorder=rec, analyzer=CostAnalyzer(ceilings=ceilings)
    )
    assert "roofline" in report and report["roofline"]["entries"]
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        trace = write_chrome_trace(
            f"{d}/t.json", recorder=rec, workflow=wf, state=state
        )
    assert trace["traceEvents"]


def test_guardrail_modules_are_callback_free():
    """The numerical self-defense layer must run without host callbacks
    by construction: GuardedAlgorithm's predicates/restart
    are pure lax math, the sanitizer is elementwise, the IPOP driver is
    host-side BETWEEN dispatches, and the chaos harness's poison helpers
    must stay injectable into traced state without host traffic."""
    users = _scan()
    for rel in (
        "core/guardrail.py",
        "operators/sanitize.py",
        "workflows/ipop.py",
    ):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"
    # tests/_chaos.py lives outside the package tree the scanner walks:
    # scan it directly (its fault injectors run inside jitted steps)
    chaos = pathlib.Path(__file__).resolve().parent / "_chaos.py"
    tree = ast.parse(chaos.read_text(), filename=str(chaos))
    assert not _uses_host_callbacks(tree), (
        "tests/_chaos.py must stay callback-free: its poison helpers and "
        "plateau problems are used inside jitted steps and fused run loops"
    )


def test_fault_tolerance_modules_are_callback_free():
    """The self-healing stack must work without host callbacks by
    construction: WorkflowCheckpointer snapshots host-side between
    dispatches and the process farm is pure host networking — neither
    may grow a host callback."""
    users = _scan()
    for rel in (
        "workflows/checkpoint.py",
        "problems/neuroevolution/process_farm.py",
        "problems/neuroevolution/rollout_farm.py",
    ):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_precision_and_topk_modules_are_callback_free():
    """The PR-6 precision/memory layer must stay callback-free by
    construction: the dtype policy is pure ``convert_element_type`` math
    applied inside traced code, and the partial-top-k kernel is a Pallas
    body + XLA merge — a host callback in either would put host traffic
    into every step that stores in bf16 or selects with the kernel."""
    users = _scan()
    for rel in ("core/dtype_policy.py", "kernels/topk.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_executor_module_is_callback_free():
    """The generation executor (core/executor.py) is the loop every
    driver now runs through: double-buffered
    dispatch, background I/O lanes, and stale-tell grafts are all plain
    host threads + eager jax around dispatches — a host callback
    anywhere in it would take down every workflow at once."""
    users = _scan()
    rel = "core/executor.py"
    assert (PKG / rel).exists(), f"{rel} missing"
    assert rel not in users, f"{rel} must not use host callbacks"


def test_supervisor_module_is_callback_free():
    """The PR-5 run supervisor is pure host-side control flow — watchdog
    threads, error classification, backoff sleeps, checkpoint replay —
    wrapped AROUND dispatches. A host callback anywhere in it (or in the
    checkpoint layer it replays through) would make supervised runs
    unusable on the very backend whose failure modes it exists to heal."""
    users = _scan()
    for rel in ("workflows/supervisor.py", "workflows/checkpoint.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_serving_fault_domain_modules_are_callback_free():
    """The ISSUE-11 serving fault domains must stay callback-free
    by construction: the journal is pure host file I/O between
    dispatches (fsynced JSON-lines appends), and the fleet health layer
    is one jitted signal computation plus host-side policy decisions at
    chunk boundaries — a host callback in either cannot run under the
    fleet's ``vmap`` and would stall the serving loop it keeps alive."""
    users = _scan()
    for rel in ("workflows/journal.py", "workflows/fleet_health.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_elastic_serving_modules_are_callback_free():
    """The ISSUE-12 elastic serving layer must stay callback-free
    by construction: the executable cache is host-side file I/O + AOT
    compilation (lower/compile/serialize happen OUTSIDE traced code),
    and the bucket/admission/autoscale layer is host orchestration
    between dispatches whose only traced addition (the inert-row mask)
    is pure lax math — a host callback in either cannot be serialized
    with the AOT executables whose compile costs the layer exists to
    hide."""
    users = _scan()
    for rel in ("core/exec_cache.py", "workflows/elastic.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_multihost_modules_are_callback_free():
    """The ISSUE-13 multi-host layer must stay callback-free by
    construction: pod-mesh construction / global-array assembly /
    host_value all-gathers (core/distributed.py) are eager host-side
    orchestration or plain jitted identities, and the multi-level ES
    (workflows/multilevel.py) drives its inner phases entirely between
    dispatches — a host callback in either would run on every process
    of a pod against unsynchronized host state."""
    users = _scan()
    for rel in ("core/distributed.py", "workflows/multilevel.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"

def test_surrogate_modules_are_callback_free():
    """The ISSUE-15 surrogate layer must stay callback-free by
    construction: the archive scatter, GP Cholesky, ensemble training
    loop, screening cond, and fallback predicates are all pure jittable
    math inside the step, and the workflow's host hooks (host_evaluate,
    dispatch_refit) are eager host orchestration between dispatches — a
    host callback in either module would add host traffic to the very
    step whose evaluation cost screening exists to cut."""
    users = _scan()
    for rel in ("operators/surrogate.py", "workflows/surrogate.py"):
        assert (PKG / rel).exists(), f"{rel} missing"
        assert rel not in users, f"{rel} must not use host callbacks"


def test_attest_module_is_callback_free():
    """The ISSUE-20 compute-integrity layer must stay callback-free
    by construction: the attestation digest runs INSIDE the fused
    fori_loop (a lax.cond around pure uint32 mixing), the voted
    re-dispatch rung compares tiny fetched digest words between
    dispatches, and bisection replays chunks eagerly from the host — a
    host callback anywhere in core/attest.py would take the digest out
    of the fused loop whose silent data corruption it exists to
    catch."""
    users = _scan()
    rel = "core/attest.py"
    assert (PKG / rel).exists(), f"{rel} missing"
    assert rel not in users, f"{rel} must not use host callbacks"


def test_pod_supervisor_module_is_callback_free():
    """The ISSUE-14 pod fault domain must stay callback-free by
    construction: heartbeats, censuses, watchdog deadlines, drain
    arbitration, and barrier-snapshot resumes are all coordination-
    service/host work between dispatches — a host callback here would
    take the healing layer down with the backend it exists to heal."""
    users = _scan()
    rel = "core/pod_supervisor.py"
    assert (PKG / rel).exists(), f"{rel} missing"
    assert rel not in users, f"{rel} must not use host callbacks"

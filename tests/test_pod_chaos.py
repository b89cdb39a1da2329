"""Pod chaos tier (ISSUE 14): REAL signals against REAL jax.distributed
pods, end to end through the ``PodManager`` respawn/re-form driver.

Each scenario spawns a reference pod (the uninjured trajectory), an
injured pod with a scripted or parent-delivered signal, asserts
DETECTION (every survivor aborts within the deadline budget with the
expected ``worker_dead`` / ``hung_collective`` / ``coordinator_loss``
classification and a census-bearing post-mortem — never an eternal
collective block), then RE-FORMS the pod on the survivor process set
(fresh coordinator rendezvous, ``create_pod_mesh`` over the shrunken
device set, epoch+1) and asserts the resumed run completes from the
newest intact pod-barrier checkpoint REPRODUCING the uninjured
trajectory.

Backend capability discipline (the PR-13 precedent): the workload runs
cross-process POP-sharded where jaxlib >= 0.5 can compile multiprocess
CPU programs; below that it runs the REPLICATED twin of the same
8-shard sampling law — the detection / re-formation / post-mortem /
drain laws are fully real on ANY jaxlib (they ride the coordination
service, not XLA collectives), trajectory equality is exact (bitwise)
in replicated mode, and the sharded-collective flavor of the
bit-identity law records ``MULTIHOST_SKIP_NOTE`` verbatim (asserted).

Tier-1 keeps the 1-kill smoke; the SIGTERM drain law and the full
matrix are additionally slow-marked (each scenario spawns 5-6 real jax
processes).
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from __graft_entry__ import (  # noqa: E402
    MULTIHOST_SKIP_NOTE,
    _jaxlib_supports_multiprocess_cpu,
    dryrun_multihost,
)

pytestmark = pytest.mark.pod_chaos

# deadline 5 s: must undercut the coordination client's own ~10 s
# missed-heartbeat SIGABRT so the classified path wins the race
# (PodManager.run_scenario docstring)
_OPTS = {"deadline_s": 5.0, "chunk": 2, "total": 8, "kill_gen": 4}


def _assert_crash_law(s, expected_class, n_survivors=1):
    """The ISSUE-14 crash law on one scenario summary: detection within
    the budget with the expected classification and a census naming the
    dead peer, re-formation on the survivor set, resume from the newest
    intact barrier, and the resumed trajectory equal to the uninjured
    reference (bitwise in replicated mode; the sharded flavor carries
    the provenance skip note on jaxlib < 0.5).

    Coordinator-death scenarios: jaxlib's OWN coordination-fatal (the
    C++ client SIGABRTs the moment its coordinator connection dies) can
    beat our classified path to the kill — a prompt, logged termination
    the PodManager accepts alongside exit-23 post-mortems; the eternal
    block stays outlawed either way, and re-formation is asserted
    unconditionally."""
    dets = s["detections"]
    fatals = s.get("jaxlib_fatals", [])
    assert len(dets) + len(fatals) == n_survivors, (dets, fatals)
    assert all(d["classification"] == expected_class for d in dets), dets
    if expected_class != "coordinator_loss":
        # only coordinator death races jaxlib's internal fatal
        assert not fatals and len(dets) == n_survivors, (dets, fatals)
    # detection bounded: deadline + census probe + generous slack, and
    # emphatically not the eternal block the issue outlaws
    assert all(d["detect_s"] < 30.0 for d in dets), dets
    r = s["reformed"]
    assert r["n_processes"] == len(s["survivors"]) == n_survivors
    assert r["generation"] == _OPTS["total"]
    # resumed from a REAL mid-flight barrier, not from scratch
    assert 0 < r["resume_generation"] < _OPTS["total"], r
    # re-formation ↔ resume coherence in the v9 report
    kinds = [e["event"] for e in r["report"]["events"]]
    assert "reform" in kinds and "resume" in kinds
    assert r["report"]["outcome"] == "resumed"
    if s["sharded"]:
        assert s["skip_reason"] is None
        # cross-process psum order may differ across the shrink
        import numpy as np

        np.testing.assert_allclose(
            np.asarray(r["final"]["mean"]),
            np.asarray(s["reference"]["final"]["mean"]),
            rtol=1e-5,
            atol=1e-5,
        )
    else:
        import jaxlib

        assert s["skip_reason"] == MULTIHOST_SKIP_NOTE.format(
            ver=jaxlib.__version__
        )
        # replicated mode: the trajectory is process-local and the
        # resumed run must be BIT-identical to the reference
        assert r["final"] == s["reference"]["final"], (
            r["final"],
            s["reference"]["final"],
        )


# ------------------------------------------------------------- tier-1 smoke


def test_pod_sigkill_mid_chunk_detect_reform_resume():
    """The 1-kill smoke (tier-1): a worker SIGKILLed mid-chunk is
    detected within the deadline, classified worker_dead with the dead
    peer named in the census, and the pod re-forms at n-1 resuming the
    uninjured trajectory from the newest barrier."""
    s = dryrun_multihost(2, chaos="sigkill_mid_chunk", chaos_opts=_OPTS)
    assert s["victim_rc"] == -9  # a real SIGKILL, not a polite exit
    assert s["detections"][0]["census"]["dead"] == [s["victim"]]
    _assert_crash_law(s, "worker_dead")


# ------------------------------------------------------ slow: the full matrix


@pytest.mark.slow
def test_pod_sigterm_drain_law():
    """SIGTERM drain law: a preemption notice delivered to every member
    finishes the in-flight chunk, agrees on ONE drain boundary, fsyncs
    a final barrier checkpoint, exits 0 — and the resumed run equals
    the uninterrupted run."""
    s = dryrun_multihost(
        2, chaos="sigterm_drain", chaos_opts=dict(_OPTS, total=10)
    )
    drain = s["drain"]
    assert all(r["outcome"] == "drained" for r in drain["reports"])
    assert 2 <= drain["generation"] <= 10
    r = s["reformed"]
    assert r["generation"] == 10
    assert r["resume_generation"] == drain["generation"]
    if not s["sharded"]:
        assert r["final"] == s["reference"]["final"]


@pytest.mark.slow
def test_pod_sigkill_pre_barrier():
    s = dryrun_multihost(2, chaos="sigkill_pre_barrier", chaos_opts=_OPTS)
    assert s["victim_rc"] == -9
    _assert_crash_law(s, "worker_dead")


@pytest.mark.slow
def test_pod_sigkill_mid_checkpoint_falls_back_one_barrier():
    """Kill the WRITING process between a snapshot's committed data
    file and its manifest: survivors classify coordinator loss (the
    writer hosts the coordinator), and recovery restores the PREVIOUS
    intact barrier — the manifest-commit rule under pod failure."""
    s = dryrun_multihost(
        2, chaos="sigkill_mid_checkpoint", chaos_opts=_OPTS
    )
    assert s["victim_rc"] == -9 and s["victim"] == 0
    _assert_crash_law(s, "coordinator_loss")
    # the gen-4 snapshot was torn (manifest never landed): the resumed
    # run provably restarted from the gen-2 barrier
    assert s["reformed"]["resume_generation"] == 2


@pytest.mark.slow
def test_pod_hang_classifies_hung_collective():
    """A wedged (not dead) worker: every heartbeat stays fresh, so the
    deadline refines to hung_collective — on the survivors AND on the
    hung member's own watchdog."""
    s = dryrun_multihost(2, chaos="hang", chaos_opts=_OPTS)
    assert s["victim_rc"] == 23  # its own watchdog diagnosed it too
    _assert_crash_law(s, "hung_collective")


@pytest.mark.slow
def test_pod_coordinator_kill():
    """SIGKILL the coordinator-hosting process: survivors lose the KV
    channel and classify coordinator_loss; re-formation rendezvouses on
    a FRESH coordinator."""
    s = dryrun_multihost(2, chaos="coordinator_kill", chaos_opts=_OPTS)
    assert s["victim_rc"] == -9 and s["victim"] == 0
    _assert_crash_law(s, "coordinator_loss")


@pytest.mark.slow
def test_pod_sigstop_reads_as_worker_dead():
    """SIGSTOP freezes every thread incl. the heartbeat — by the census
    a stopped worker IS dead (its counter no longer advances), which is
    exactly the preempted-VM shape."""
    s = dryrun_multihost(2, chaos="sigstop", chaos_opts=_OPTS)
    _assert_crash_law(s, "worker_dead")


@pytest.mark.slow
def test_pod_chaos_collective_tier_gate():
    """Provenance discipline: the chaos tier runs the sharded workload
    exactly when the backend can compile multiprocess programs; the
    summary must say which flavor ran (the PR-13 note verbatim below
    jaxlib 0.5)."""
    s = dryrun_multihost(2, chaos="sigkill_mid_chunk", chaos_opts=_OPTS)
    assert s["sharded"] == _jaxlib_supports_multiprocess_cpu()
    if not s["sharded"]:
        import jaxlib

        assert s["skip_reason"] == MULTIHOST_SKIP_NOTE.format(
            ver=jaxlib.__version__
        )

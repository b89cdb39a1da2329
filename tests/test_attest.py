"""Compute-integrity laws (ISSUE 20, core/attest.py).

The full detect → localize → heal story against real silent-data-
corruption injection (tests/_chaos.py ``flip_bit`` / ``LyingPod``):

- **Digest laws**: the 6-word attestation digest is a pure function of
  the state's VALUES — device and host paths produce the same bits, and
  resharding a state across 8/4/1-device layouts (including a ShardedES
  population layout) never moves the digest. A single mantissa-bit flip
  moves it, and the per-leaf form names exactly the flipped leaf.
- **Ring cadence**: ``StateAttestor(every=K)`` attests inside the fused
  ``fori_loop`` at generations K, 2K, … with ring-overwrite semantics —
  no host callbacks anywhere.
- **Detect**: one mantissa bit flipped in a CMA covariance leaf at
  generation k splits the attestation ring at the first cadence point
  at/after k — detection within one cadence.
- **Localize**: ``bisect_divergence`` replays the journaled ring and
  names EXACTLY generation k and the flipped leaf.
- **Heal**: the executor's ``verify_every`` voted re-dispatch outvotes a
  lying dispatch 2-of-3 and the healed run's final state is bit-identical
  to the uninjured run; no 2-of-3 majority aborts with ``IntegrityError``
  (classified ``integrity`` — the ladder never retries it).
- **Recover**: a journaled barrier whose snapshot bits disagree with the
  barrier attestation is refused and recovery falls back one barrier
  (the PR-11 manifest-commit shape), naming leaf and generation.

Heavy vote/bisect matrices are additionally slow-marked (PR-2
discipline); tier-1 keeps the single-flip detect/heal laws.
"""

import hashlib
import json
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu import (
    GenerationExecutor,
    RunQueue,
    RunSupervisor,
    StdWorkflow,
    TenantSpec,
    VectorizedWorkflow,
    create_mesh,
    run_report,
)
from evox_tpu.algorithms.so.es import CMAES
from evox_tpu.core.attest import (
    IntegrityError,
    StateAttestor,
    bisect_divergence,
    digest_hex,
    host_state_digest,
    state_digest,
    verify_state_digest,
)
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.problems.numerical import Sphere
from evox_tpu.workflows.journal import RunJournal
from evox_tpu.workflows.supervisor import classify_error

from tests._chaos import BitFlipStep, LyingPod, flip_bit

pytestmark = pytest.mark.integrity

DIM, POP = 4, 8


def _cma_wf(monitors=(), **kw):
    algo = CMAES(center_init=jnp.ones(DIM), init_stdev=1.0, pop_size=POP)
    return StdWorkflow(algo, Sphere(), monitors=monitors, **kw)


# ------------------------------------------------------------- digest laws

def test_digest_device_host_mirror():
    """state_digest (jittable, on-device) and host_state_digest (NumPy)
    are exact mirrors, leaf digests included."""
    wf = _cma_wf()
    s = wf.run(wf.init(jax.random.PRNGKey(0)), 3)
    att = StateAttestor()
    assert att.digest_hex(s) == att.host_digest_hex(s)
    dev = digest_hex(state_digest(s))
    host = digest_hex(host_state_digest(s))
    assert dev == host and len(dev) == 48


def test_digest_layout_invariant():
    """The digest is a function of the VALUES: replicating or resharding
    one state across 8/4/1-device layouts never moves it."""
    devs = jax.devices()
    assert len(devs) >= 8
    wf = _cma_wf(mesh=create_mesh(devices=devs[:8]))
    s = wf.run(wf.init(jax.random.PRNGKey(1)), 3)
    att = StateAttestor()
    want = att.digest_hex(s)
    assert want == att.host_digest_hex(s)
    # gather to host, then digest the plain numpy pytree
    host_state = jax.device_get(s)
    assert att.host_digest_hex(host_state) == want
    # re-place on 4-device and 1-device meshes through the checkpoint
    # layer's own layout pass — the digest never moves
    from evox_tpu.workflows.checkpoint import restore_layouts

    for n_dev in (4, 1):
        placed = restore_layouts(
            host_state, mesh=create_mesh(devices=devs[:n_dev])
        )
        assert att.digest_hex(placed) == want


def test_sharded_es_digest_layout_invariant():
    """ShardedES population layouts (ISSUE 14) digest identically on the
    8-device mesh and after a host gather — the layout-invariance law on
    the one state family whose leaves actually live sharded."""
    from evox_tpu.algorithms.so.es import SepCMAES
    from evox_tpu.core.distributed import ShardedES

    devs = jax.devices()
    mesh = create_mesh(devices=devs[:8])
    algo = ShardedES(
        SepCMAES(center_init=jnp.zeros(8), init_stdev=1.0, pop_size=16),
        mesh=mesh,
        n_shards=8,
    )
    wf = StdWorkflow(algo, Sphere(), mesh=mesh)
    s = wf.run(wf.init(jax.random.PRNGKey(2)), 3)
    att = StateAttestor()
    assert att.digest_hex(s) == att.host_digest_hex(s)


def test_digest_names_the_flipped_leaf():
    """One mantissa bit in the CMA covariance moves the combined digest,
    and the per-leaf comparison names exactly ``.algo.C``."""
    wf = _cma_wf()
    s = wf.run(wf.init(jax.random.PRNGKey(3)), 4)
    att = StateAttestor()
    clean_hex = att.digest_hex(s)
    attn = att.attestation(s)
    assert attn["digest"] == clean_hex
    bad = flip_bit(s, "algo.C", index=1, bit=0)
    assert att.digest_hex(bad) != clean_hex
    with pytest.raises(IntegrityError) as ei:
        att.verify(bad, attn, generation=4, where="test")
    assert ei.value.leaves == (".algo.C",)
    assert ei.value.generation == 4
    # exponent flavor is just as visible
    bad2 = flip_bit(s, "algo.mean", index=0, bit=2, kind="exponent")
    with pytest.raises(IntegrityError) as ei2:
        att.verify(bad2, attn, generation=4, where="test")
    assert ei2.value.leaves == (".algo.mean",)
    # the clean state verifies against its own attestation
    assert att.verify(s, attn) == clean_hex


def test_typed_prng_key_leaves_digest():
    """Typed PRNG key leaves (``key<fry>`` dtype) digest as their uint32
    key words on BOTH paths — the recover gate must never crash on a
    state whose seeds were stored as typed keys (regression: np.asarray
    refuses typed keys)."""
    typed = {"seed": jax.random.key(42)}
    raw = {"seed": jax.random.key_data(jax.random.key(42))}
    d_host = digest_hex(host_state_digest(typed))
    assert d_host == digest_hex(state_digest(typed))
    assert d_host == digest_hex(host_state_digest(raw))
    att = StateAttestor()
    assert att.verify(typed, att.attestation(typed)) == d_host


def test_empty_and_scalar_canonicalization():
    """Scalars of different byte widths digest deterministically and an
    empty selection digests to the canonical empty-tree words (regression
    guard for the x32 canonicalization path)."""
    d1 = digest_hex(host_state_digest({"a": np.float64(1.5)}))
    d2 = digest_hex(host_state_digest({"a": np.float64(1.5)}))
    assert d1 == d2 and len(d1) == 48
    assert digest_hex(host_state_digest({})) == digest_hex(
        host_state_digest({})
    )
    # different leaf NAME, same value -> different digest (salted paths)
    assert digest_hex(host_state_digest({"b": np.float64(1.5)})) != d1


# ------------------------------------------------------------- ring cadence

def test_ring_cadence_and_overwrite():
    """every=3 over 12 fused generations attests at 3,6,9,12; capacity=3
    keeps the newest three (ring semantics); digests match the honest
    recompute of the SAME driver's states."""
    att = StateAttestor(every=3, capacity=3)
    wf = _cma_wf(monitors=(att,))
    s = wf.run(wf.init(jax.random.PRNGKey(4)), 12)
    ledger = att.ledger(s.monitors[0])
    assert [e["generation"] for e in ledger] == [6, 9, 12]
    assert all(len(e["digest"]) == 48 for e in ledger)
    rep = att.integrity_report(s.monitors[0])
    assert rep["enabled"] is True and rep["every"] == 3
    assert rep["attestations"] == 4  # 3,6,9,12 attested; ring kept 3
    # the newest ring digest matches a host recompute of the final state
    assert ledger[-1]["digest"] == att.host_digest_hex(s)


def test_chunked_run_ring_agrees():
    """Chunking a fused run never moves the ring: run(8) and
    run(4)+run(4) attest the same generations with the same digests (the
    fori_loop chunking law extends to the attestation ring — this is
    what makes journaled attestations replayable by bisect_divergence)."""
    att1, att2 = StateAttestor(every=4, capacity=4), StateAttestor(
        every=4, capacity=4
    )
    wf1, wf2 = _cma_wf(monitors=(att1,)), _cma_wf(monitors=(att2,))
    key = jax.random.PRNGKey(5)
    s1 = wf1.run(wf1.init(key), 8)
    s2 = wf2.run(wf2.init(key), 4)
    s2 = wf2.run(s2, 4)
    l1 = att1.ledger(s1.monitors[0])
    l2 = att2.ledger(s2.monitors[0])
    assert l1 == l2


# ---------------------------------------------------------- detect / localize

def test_bit_flip_detected_within_one_cadence():
    """A single mantissa-bit flip in the CMA covariance at generation 7
    splits the attestation ring at generation 10 — the first cadence
    point at/after the fault (every=5)."""
    key = jax.random.PRNGKey(6)
    att = StateAttestor(every=5, capacity=8)
    clean_wf = _cma_wf(monitors=(att,))
    clean = clean_wf.run(clean_wf.init(key), 20)

    att_f = StateAttestor(every=5, capacity=8)
    faulty_wf = _cma_wf(monitors=(att_f,))
    faulty = BitFlipStep(faulty_wf, "algo.C", at_gen=7, index=1, bit=0).run(
        faulty_wf.init(key), 20
    )
    lc = att.ledger(clean.monitors[0])
    lf = att_f.ledger(faulty.monitors[0])
    assert [e["generation"] for e in lc] == [5, 10, 15, 20]
    assert [e["generation"] for e in lf] == [5, 10, 15, 20]
    assert lc[0] == lf[0]  # generation 5 pre-dates the fault
    split = [c["generation"] for c, f in zip(lc, lf) if c != f]
    assert split and split[0] == 10  # within one cadence of gen 7


@pytest.mark.slow
def test_bisect_names_exactly_gen_k(tmp_path):
    """Journal-guided bisection (the localize rung) names EXACTLY the
    injection generation and the flipped leaf, and the forensics ride
    run_report v14 with verdict ``detected``."""
    key = jax.random.PRNGKey(7)
    flip_gen = 13
    att = StateAttestor(every=5, capacity=16)
    wf = _cma_wf(monitors=(att,))
    state0 = wf.init(key)
    bad_final = BitFlipStep(wf, "algo.C", at_gen=flip_gen, index=2, bit=0).run(
        state0, 30
    )
    # journal the faulty run's ring, then bisect with an honest replay
    jd = str(tmp_path / "journal")
    n = att.journal_ring(bad_final.monitors[0], RunJournal(jd))
    assert n == 6
    report = bisect_divergence(
        jd,
        wf=wf,
        start_state=state0,
        suspect=BitFlipStep(
            wf, "algo.C", at_gen=flip_gen, index=2, bit=0
        ).run,
        attestor=att,
        report_to=wf,
    )
    assert report["first_divergent_generation"] == flip_gen
    assert report["window"] == [11, 15]
    assert report["leaves"] == [".algo.C"]
    assert report["reproducible"] is True
    assert report["verdict"] == "detected"
    # no suspect leg -> window-only forensics, still "detected"
    window_only = bisect_divergence(jd, wf=wf, start_state=state0, attestor=att)
    assert window_only["first_divergent_generation"] is None
    assert window_only["window"] == [11, 15]
    # forensics ride the v14 report and the validator accepts them
    rep = run_report(workflow=wf, state=bad_final)
    assert rep["schema_version"] == 14
    assert rep["integrity"]["bisection"]["first_divergent_generation"] == flip_gen
    assert rep["integrity"]["verdict"] == "detected"
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "check_report",
        pathlib.Path(__file__).resolve().parent.parent
        / "tools"
        / "check_report.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.validate_run_report(rep) == []


# ------------------------------------------------------------------- heal

def test_voted_redispatch_heals_bit_identical():
    """A lying dispatch (one mantissa bit flipped in one chunk result) is
    outvoted 2-of-3 and the healed run's final state is BIT-IDENTICAL to
    the uninjured run; counter coherence holds."""
    key = jax.random.PRNGKey(8)
    wf_ref = _cma_wf()
    straight = wf_ref.run(wf_ref.init(key), 20)

    wf = _cma_wf()
    state0 = wf.init(key)
    # verify_every=1: dispatches go chunk1, verify1, chunk2, verify2, ...
    # call index 2 is chunk2's primary dispatch — the lie
    lying = LyingPod(wf.run, lies={2: "perturb"}, leaf="algo.mean", bit=0)
    wf.run = lying
    ex = GenerationExecutor()
    att = StateAttestor()
    healed = ex.run_fused(wf, state0, 20, chunk=5, attest=att, verify_every=1)
    assert att.digest_hex(healed) == att.digest_hex(straight)
    for a, b in zip(jax.tree.leaves(healed), jax.tree.leaves(straight)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = ex.integrity_counters()
    assert c["mismatches"] == 1 and c["healed"] == 1 and c["aborted"] == 0
    assert c["verified_chunks"] == 3  # chunks 1, 3, 4 verified clean
    assert c["redispatches"] == c["verified_chunks"] + 2 * c["mismatches"]
    rep = run_report(workflow=wf, state=healed)
    assert rep["integrity"]["verdict"] == "healed"


def test_no_majority_aborts_with_integrity_error():
    """Three mutually disagreeing dispatches of one chunk leave nothing
    trustworthy: IntegrityError, classified ``integrity``, aborted=1."""
    key = jax.random.PRNGKey(9)
    wf = _cma_wf()
    state0 = wf.init(key)
    # chunk2 primary lies (perturb), its verify redo lies differently
    # (stale = chunk1's result), the third dispatch is honest -> 3 digests
    lying = LyingPod(
        wf.run, lies={2: "perturb", 3: "stale"}, leaf="algo.mean"
    )
    wf.run = lying
    ex = GenerationExecutor()
    with pytest.raises(IntegrityError) as ei:
        ex.run_fused(wf, state0, 20, chunk=5, verify_every=1)
    assert classify_error(ei.value) == "integrity"
    c = ex.integrity_counters()
    assert c["aborted"] == 1 and c["mismatches"] == 1 and c["healed"] == 0


def test_integrity_abort_is_never_retried():
    """The supervisor ladder aborts on the ``integrity`` rung without
    burning a single retry — wrong bits are not transient."""
    from evox_tpu import RunAbortedError

    sup = RunSupervisor(max_retries=3, backoff_s=0.0)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise IntegrityError("bits are wrong", generation=5, where="test")

    with pytest.raises(RunAbortedError):
        sup.call(fn, entry="test")
    assert calls["n"] == 1  # no retry ever fired
    events = [e["event"] for e in sup.events]
    assert "abort" in events and "retry" not in events
    abort = [e for e in sup.events if e["event"] == "abort"][-1]
    assert abort["rung"] == "integrity"


@pytest.mark.slow
def test_vote_matrix():
    """The full 2-of-3 decision table: lie in the primary -> redo wins
    (dissent=first); lie in the redo -> primary wins (dissent=redo);
    both outcomes end bit-identical to the uninjured run."""
    key = jax.random.PRNGKey(10)
    wf_ref = _cma_wf()
    straight = wf_ref.run(wf_ref.init(key), 10)
    att = StateAttestor()
    want = att.digest_hex(straight)

    for lies, dissent in (({0: "perturb"}, "first"), ({1: "perturb"}, "redo")):
        wf = _cma_wf()
        state0 = wf.init(key)
        lying = LyingPod(wf.run, lies=dict(lies), leaf="algo.mean")
        wf.run = lying
        sup = RunSupervisor(attest=att, verify_every=1)
        healed = sup.run(wf, state0, 10, chunk=10)
        assert att.digest_hex(healed) == want, (lies, dissent)
        heal_events = [
            e for e in sup.events if e["event"] == "integrity_heal"
        ]
        assert len(heal_events) == 1
        assert heal_events[0]["dissent"] == dissent


# ---------------------------------------------------- recover digest gate

def _build_queue_wf():
    algo = CMAES(center_init=jnp.ones(DIM), init_stdev=1.0, pop_size=POP)
    return VectorizedWorkflow(
        algo, Sphere(), n_tenants=2, monitors=(TelemetryMonitor(capacity=8),)
    )


def test_recover_refuses_corrupt_snapshot(tmp_path):
    """A tampered barrier snapshot that fools the checkpoint layer
    (payload + sha256 + manifest attest rewritten consistently) is still
    refused by the journaled barrier attestation: recovery names leaf and
    generation and falls back exactly one barrier."""
    from evox_tpu.workflows.checkpoint import attest_digest_hex

    jd = str(tmp_path / "journal")
    q = RunQueue(_build_queue_wf(), chunk=3, journal=jd, attest=True)
    for i in range(4):
        q.submit(TenantSpec(seed=i, n_steps=5, tag=f"job{i}"))
    q.start()
    while q.step_chunk():
        pass
    assert q.finished
    barriers = [
        r for r in q.journal.records() if r["kind"] == "chunk_complete"
    ]
    assert len(barriers) >= 2
    for b in barriers:  # every barrier carries a well-formed attestation
        a = b["attest"]
        assert a["generation"] == b["generation"]
        assert len(a["digest"]) == 48
        assert a["leaves"] and all(len(v) == 48 for v in a["leaves"].values())

    # clean recover verifies every barrier silently
    q2 = RunQueue.recover(_build_queue_wf(), jd, attest=StateAttestor())
    assert q2.integrity_events == [] and q2.state is not None

    # tamper the NEWEST snapshot consistently with the checkpoint layer
    newest = barriers[-1]
    snap = newest["snapshot"]
    with open(snap, "rb") as f:
        state = pickle.loads(f.read())
    mean = np.array(state.tenants.algo.mean)
    mean[0, 0] += 1e-3
    tampered = state.replace(
        tenants=state.tenants.replace(
            algo=state.tenants.algo.replace(mean=mean)
        )
    )
    payload = pickle.dumps(tampered)
    with open(snap, "wb") as f:
        f.write(payload)
    mpath = snap + ".manifest.json"
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["bytes"] = len(payload)
    manifest["sha256"] = hashlib.sha256(payload).hexdigest()
    manifest["attest"]["digest"] = attest_digest_hex(tampered)
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    q3 = RunQueue.recover(_build_queue_wf(), jd, attest=StateAttestor())
    assert len(q3.integrity_events) == 1
    ev = q3.integrity_events[0]
    assert ev["event"] == "corrupt_snapshot"
    assert ev["generation"] == newest["generation"]
    assert ev["action"] == "barrier_fallback"
    assert any("mean" in leaf for leaf in ev["leaves"])
    # fell back exactly one barrier
    assert int(q3.state.generation) == barriers[-2]["generation"]
    ints = [r for r in q3.journal.records() if r["kind"] == "integrity"]
    assert len(ints) == 1 and ints[0]["snapshot"] == snap
    assert "integrity_events" in q3.report()


def test_attest_none_is_a_no_op(tmp_path):
    """attest=None everywhere is the established discipline: no extra
    dispatches, no journal keys, bit-identical final states per driver."""
    key = jax.random.PRNGKey(11)
    att = StateAttestor()
    # fused executor: verify rung off -> state equals the plain run
    wf_plain = _cma_wf()
    plain = wf_plain.run(wf_plain.init(key), 12)
    wf_ex = _cma_wf()
    ex = GenerationExecutor()
    fused = ex.run_fused(wf_ex, wf_ex.init(key), 12, chunk=4)
    assert att.digest_hex(fused) == att.digest_hex(plain)
    assert ex.integrity_counters() is None
    rep = run_report(workflow=wf_ex, state=fused)
    assert "verify" not in rep.get("integrity", {})
    # ...and arming the rung on a clean run does NOT move the bits
    wf_v = _cma_wf()
    exv = GenerationExecutor()
    verified = exv.run_fused(
        wf_v, wf_v.init(key), 12, chunk=4, attest=att, verify_every=2
    )
    assert att.digest_hex(verified) == att.digest_hex(plain)
    assert exv.integrity_counters()["mismatches"] == 0
    # queue barriers never write the attest key when disabled
    jd = str(tmp_path / "j")
    q = RunQueue(_build_queue_wf(), chunk=3, journal=jd)
    q.submit(TenantSpec(seed=0, n_steps=4, tag="t0"))
    q.submit(TenantSpec(seed=1, n_steps=4, tag="t1"))
    q.run()
    assert all(
        "attest" not in r
        for r in q.journal.records()
        if r["kind"] == "chunk_complete"
    )

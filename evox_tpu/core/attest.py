"""Compute-integrity layer: on-device state attestation and forensics.

No reference analog (PARITY row 64): the reference stack (SURVEY §2.9)
trusts every bit the accelerator returns. This module gives the stack an
answer to "is the state still the state we computed?":

- ``state_digest`` — a jitted, callback-free, fixed-shape digest of a
  state pytree. Built from **bitwise-stable reductions only** (wrapping
  u32 sum / XOR / min / max over position-mixed bit-cast uint32 views,
  plus exact nan/inf counts). Float sums are reassociation-dependent
  across GSPMD layouts, so a digest built on them would
  false-alarm on every mesh change; modular-integer reductions are
  associative *and* commutative exactly, so the digest is a function of
  the logical value alone — layout-invariant by construction (law tested
  across 1/4/8-device meshes and ShardedES).
- ``host_state_digest`` — an exact NumPy mirror: digesting a fetched host
  copy gives bitwise the same 6 words as the device digest. This is what
  lets checkpoint manifests and journal barriers attest state cheaply.
- ``StateAttestor`` — a Monitor that records the digest ring at a cadence
  inside the fused loop (traced ``lax.cond``, the TelemetryMonitor ring
  discipline via ``utils/ring.py``; zero host callbacks), and
  the digest engine handed to ``GenerationExecutor.run_fused``'s
  ``verify_every=K`` voted re-dispatch rung.
- ``IntegrityError`` — corruption is its own ``classify_error`` class
  (``"integrity"``): never retried into acceptance, always an abort or an
  explicit heal (vote / barrier fallback).
- ``bisect_divergence`` — host-side forensic: replay from the last
  attested barrier at halving chunk sizes to name the first divergent
  generation and the leaf paths whose digests split.

Digest layout (``DIGEST_WORDS = 6`` uint32 words)::

    [ wrapping-sum(mix(w ^ i·φ ^ salt)),        # order-sensitive, exact
      wrapping-sum(mix(w ^ i·φ ^ salt ^ c2)),   # independent mixed channel
      min(w), max(w),                           # raw word envelope
      nan_count, inf_count ]                    # exact counts, float leaves

(the second per-leaf channel is a second independently-mixed modular sum
rather than an elementwise XOR-reduce: GSPMD's partitioned reduce only
supports the standard monoids, and modular add is exactly as
layout-invariant; across *leaves* word 1 combines by true bit-sliced XOR)

where ``w`` is the leaf's canonical uint32 word stream (4-byte dtypes are
bit-cast; 2-byte bit-cast to u16 then zero-extended; 1-byte via u8;
8-byte split into u32 pairs), ``i`` the global flat logical index, ``φ``
the golden-ratio constant, and ``salt`` a static hash of the leaf's
keystr path (so swapping two identically-shaped leaves changes the
digest). Per-leaf digests combine across leaves by the same exact
reductions. Hex form is the 48-char concatenation of the 6 words.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .monitor import Monitor
from .struct import PyTreeNode, field
from ..utils.ring import ring_slots, ring_write

__all__ = [
    "DIGEST_WORDS",
    "AttestState",
    "IntegrityError",
    "StateAttestor",
    "bisect_divergence",
    "digest_hex",
    "host_leaf_digests",
    "host_state_digest",
    "leaf_digests",
    "state_digest",
    "verify_state_digest",
]

DIGEST_WORDS = 6

_PHI = 0x9E3779B1  # 2**32 / golden ratio — index decorrelation
_MIX1 = 0x85EBCA6B  # murmur3 finalizer constants
_MIX2 = 0xC2B2AE35
_CH2 = 0x5BD1E995  # second-channel tweak (murmur2 constant)
_MIN_IDENTITY = 0xFFFFFFFF  # empty-leaf min/max identities


class IntegrityError(RuntimeError):
    """State bits do not match their attestation.

    Distinct from transient dispatch failures: corruption must never be
    "retried" into acceptance, so ``classify_error`` maps this to the
    ``"integrity"`` class which the supervisor ladder aborts (or the
    caller heals explicitly — voted re-dispatch, barrier fallback)."""

    def __init__(
        self,
        message: str,
        *,
        generation: Optional[int] = None,
        leaves: Sequence[str] = (),
        where: Optional[str] = None,
    ):
        super().__init__(message)
        self.generation = generation
        self.leaves = tuple(leaves)
        self.where = where


# -- word canonicalization ---------------------------------------------------


def _mix32(h):
    """Murmur3 finalizer over uint32 — bijective, elementwise, exact."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_MIX1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_MIX2)
    h = h ^ (h >> 16)
    return h


def _mix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32, copy=True)
    h ^= h >> np.uint32(16)
    h *= np.uint32(_MIX1)
    h ^= h >> np.uint32(13)
    h *= np.uint32(_MIX2)
    h ^= h >> np.uint32(16)
    return h


def _xor_reduce(h):
    """Exact XOR-reduce expressed through add-monoid reductions (bit-sliced
    parity), because GSPMD's partitioned ``lax.reduce`` rejects custom
    reduction computations. Only used over small stacks (one row per leaf),
    never over full leaf word streams."""
    h = h.reshape(-1)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (h[:, None] >> shifts[None, :]) & jnp.uint32(1)
    parity = jnp.sum(bits, axis=0, dtype=jnp.uint32) & jnp.uint32(1)
    return jnp.sum(parity << shifts, dtype=jnp.uint32)


def _salt(name: str) -> int:
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")


def _leaf_words(x) -> jax.Array:
    """Canonical uint32 word stream for one leaf (traced).

    Canonicalization mirrors ``_leaf_words_np`` bit-for-bit: weak Python
    scalars take jnp's x32 defaults; 1-byte dtypes route through uint8 on
    BOTH sides (a direct int8→uint32 astype would sign-extend on device
    but zero-extend through a host u8 view)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)  # typed keys digest as their uint32 words
    dt = x.dtype
    if dt == jnp.bool_:
        w = x.astype(jnp.uint32)
    elif dt.itemsize == 1:
        w = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    elif dt.itemsize == 2:
        w = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    elif dt.itemsize == 4:
        w = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif dt.itemsize == 8:
        w = jax.lax.bitcast_convert_type(x, jnp.uint32)  # trailing dim 2
    else:
        raise TypeError(f"state_digest: unsupported leaf dtype {dt}")
    return w.reshape(-1)


def _canon_np(x) -> np.ndarray:
    # Mirror jnp.asarray's x32 weak-type defaults for bare Python scalars
    # so host and device word streams agree.
    if isinstance(x, (bool, np.bool_)):
        return np.asarray(x, np.bool_)
    if isinstance(x, int) and not isinstance(x, np.generic):
        return np.asarray(x, np.int32)
    if isinstance(x, float) and not isinstance(x, np.generic):
        return np.asarray(x, np.float32)
    if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        # Typed PRNG keys refuse np.asarray; digest their uint32 key words
        # (bit-identical to the device path's jax.random.key_data).
        return np.asarray(jax.device_get(jax.random.key_data(x)))
    return np.asarray(x)


def _leaf_words_np(x) -> np.ndarray:
    x = np.ascontiguousarray(_canon_np(x))
    dt = x.dtype
    if dt == np.bool_:
        w = x.astype(np.uint32)
    elif dt.itemsize == 1:
        w = x.view(np.uint8).astype(np.uint32)
    elif dt.itemsize == 2:
        w = x.view(np.uint16).astype(np.uint32)
    elif dt.itemsize in (4, 8):
        w = x.view(np.uint32)
    else:
        raise TypeError(f"host_state_digest: unsupported leaf dtype {dt}")
    return w.reshape(-1)


def _float_counts(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating) and x.size:
        return (
            jnp.sum(jnp.isnan(x), dtype=jnp.uint32),
            jnp.sum(jnp.isinf(x), dtype=jnp.uint32),
        )
    return jnp.uint32(0), jnp.uint32(0)


def _float_counts_np(x: np.ndarray):
    if np.issubdtype(x.dtype, np.floating) and x.size:
        return (
            np.sum(np.isnan(x), dtype=np.uint32),
            np.sum(np.isinf(x), dtype=np.uint32),
        )
    return np.uint32(0), np.uint32(0)


def _empty_leaf_digest_np(salt: int) -> np.ndarray:
    h = _mix32_np(np.asarray([salt ^ _PHI, salt ^ _PHI ^ _CH2], np.uint32))
    return np.asarray([h[0], h[1], _MIN_IDENTITY, 0, 0, 0], np.uint32)


def _leaf_digest(x, salt: int) -> jax.Array:
    w = _leaf_words(x)
    if w.shape[0] == 0:  # static — no retrace risk
        return jnp.asarray(_empty_leaf_digest_np(salt))
    nan, inf = _float_counts(x)
    idx = jnp.arange(w.shape[0], dtype=jnp.uint32)
    base = w ^ (idx * jnp.uint32(_PHI)) ^ jnp.uint32(salt)
    return jnp.stack(
        [
            jnp.sum(_mix32(base), dtype=jnp.uint32),
            jnp.sum(_mix32(base ^ jnp.uint32(_CH2)), dtype=jnp.uint32),
            jnp.min(w),
            jnp.max(w),
            nan,
            inf,
        ]
    )


def _leaf_digest_np(x, salt: int) -> np.ndarray:
    x = _canon_np(x)
    w = _leaf_words_np(x)
    if w.shape[0] == 0:
        return _empty_leaf_digest_np(salt)
    nan, inf = _float_counts_np(np.asarray(x))
    idx = np.arange(w.shape[0], dtype=np.uint32)
    base = w ^ (idx * np.uint32(_PHI)) ^ np.uint32(salt)
    return np.asarray(
        [
            np.sum(_mix32_np(base), dtype=np.uint32),
            np.sum(_mix32_np(base ^ np.uint32(_CH2)), dtype=np.uint32),
            np.min(w),
            np.max(w),
            nan,
            inf,
        ],
        np.uint32,
    )


_EMPTY_TREE = np.asarray([0, 0, _MIN_IDENTITY, 0, 0, 0], np.uint32)


def _named_leaves(tree):
    return [
        (jax.tree_util.keystr(path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        if leaf is not None
    ]


def _combine(digests: List) -> Any:
    d = jnp.stack(digests)  # (L, 6) uint32
    return jnp.stack(
        [
            jnp.sum(d[:, 0], dtype=jnp.uint32),
            _xor_reduce(d[:, 1]),
            jnp.min(d[:, 2]),
            jnp.max(d[:, 3]),
            jnp.sum(d[:, 4], dtype=jnp.uint32),
            jnp.sum(d[:, 5], dtype=jnp.uint32),
        ]
    )


def _combine_np(digests: List[np.ndarray]) -> np.ndarray:
    d = np.stack(digests).astype(np.uint32)
    return np.asarray(
        [
            np.sum(d[:, 0], dtype=np.uint32),
            np.bitwise_xor.reduce(d[:, 1]),
            np.min(d[:, 2]),
            np.max(d[:, 3]),
            np.sum(d[:, 4], dtype=np.uint32),
            np.sum(d[:, 5], dtype=np.uint32),
        ],
        np.uint32,
    )


# -- public digest API ---------------------------------------------------------


def state_digest(tree) -> jax.Array:
    """Layout-invariant ``uint32[6]`` digest of a pytree (traced/jittable)."""
    named = _named_leaves(tree)
    if not named:
        return jnp.asarray(_EMPTY_TREE)
    return _combine([_leaf_digest(leaf, _salt(name)) for name, leaf in named])


def host_state_digest(tree) -> np.ndarray:
    """Exact NumPy mirror of ``state_digest`` (fetches device leaves)."""
    named = _named_leaves(tree)
    if not named:
        return _EMPTY_TREE.copy()
    return _combine_np(
        [_leaf_digest_np(jax.device_get(leaf), _salt(name)) for name, leaf in named]
    )


def leaf_digests(tree) -> Dict[str, jax.Array]:
    """Per-leaf ``uint32[6]`` digests keyed by keystr path (traced/jittable)."""
    return {
        name: _leaf_digest(leaf, _salt(name)) for name, leaf in _named_leaves(tree)
    }


def host_leaf_digests(tree) -> Dict[str, str]:
    """Per-leaf hex digests computed on host (exact device mirror)."""
    return {
        name: digest_hex(_leaf_digest_np(jax.device_get(leaf), _salt(name)))
        for name, leaf in _named_leaves(tree)
    }


def digest_hex(words) -> str:
    """48-char hex form of a 6-word digest."""
    w = np.asarray(jax.device_get(words)).astype(np.uint32).reshape(-1)
    if w.shape[0] != DIGEST_WORDS:
        raise ValueError(f"digest must have {DIGEST_WORDS} words, got {w.shape}")
    return "".join(f"{int(v):08x}" for v in w)


def verify_state_digest(
    state,
    expected: Union[str, Any],
    *,
    generation: Optional[int] = None,
    where: str = "state",
    expected_leaves: Optional[Dict[str, str]] = None,
) -> str:
    """Verify ``state``'s bits against an attestation; raise on mismatch.

    ``expected`` is a hex digest (or 6-word array). When a per-leaf
    attestation map is supplied the error names the exact leaf paths whose
    digests split. Returns the verified hex digest on success."""
    got = digest_hex(host_state_digest(state))
    want = expected if isinstance(expected, str) else digest_hex(expected)
    if got == want:
        return got
    split: List[str] = []
    if expected_leaves:
        actual = host_leaf_digests(state)
        split = [
            name
            for name in sorted(set(actual) | set(expected_leaves))
            if actual.get(name) != expected_leaves.get(name)
        ]
    at = f" at generation {generation}" if generation is not None else ""
    leaf_note = f" (splitting leaves: {', '.join(split)})" if split else ""
    raise IntegrityError(
        f"integrity violation in {where}{at}: digest {got} != attested "
        f"{want}{leaf_note}",
        generation=generation,
        leaves=split,
        where=where,
    )


# -- the attestor monitor ------------------------------------------------------


class AttestState(PyTreeNode):
    """On-device attestation ring (all replicated — tiny)."""

    count: jax.Array = field(sharding=P())
    ring_digest: jax.Array = field(sharding=P())
    ring_generation: jax.Array = field(sharding=P())


class StateAttestor(Monitor):
    """Digest the workflow state at a cadence, on device, inside the loop.

    Attach as a monitor: every ``every`` generations the post_step hook
    records ``(generation, digest)`` in a fixed-capacity ring (one traced
    ``lax.cond`` around a ``ring_write`` — no retrace, no host
    callbacks). The same object is the digest engine for the executor's
    ``verify_every`` voted re-dispatch rung and for journal/checkpoint
    attestation.

    ``select`` narrows the digested subtree (e.g. ``lambda s: s.algo``).
    The default digests the workflow state *minus its ``monitors`` field*:
    monitor states are observability artifacts (and include this ring
    itself, which updates after the digest is taken — including it would
    make a recorded digest unreproducible from the state it describes).
    """

    def __init__(
        self,
        every: int = 10,
        capacity: int = 64,
        select: Optional[Callable[[Any], Any]] = None,
    ):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.every = int(every)
        self.capacity = int(capacity)
        self.select = select
        self._jit_digest = jax.jit(self._digest_traced)
        self._jit_attest = jax.jit(
            lambda s: (
                state_digest(self._selected(s)),
                leaf_digests(self._selected(s)),
            )
        )

    # -- digest engine --------------------------------------------------------

    def _selected(self, state):
        if self.select is not None:
            return self.select(state)
        try:
            return state.replace(monitors=())
        except (AttributeError, TypeError):
            return state

    def _digest_traced(self, state):
        return state_digest(self._selected(state))

    def digest(self, state) -> jax.Array:
        """Device digest of (the selected subtree of) ``state`` — jitted."""
        return self._jit_digest(state)

    def digest_hex(self, state) -> str:
        return digest_hex(self.digest(state))

    def host_digest_hex(self, state) -> str:
        """NumPy-mirror digest (no device dispatch; fetches leaves)."""
        return digest_hex(host_state_digest(self._selected(state)))

    def leaf_digest_hex(self, state) -> Dict[str, str]:
        return host_leaf_digests(self._selected(state))

    def attestation(self, state) -> Dict[str, Any]:
        """One jitted call -> ``{"digest": hex, "leaves": {path: hex}}``.

        Both digests come back from a single dispatch, and only the
        L x 6 uint32 digest words are fetched — never the state itself
        (a full-state fetch on a synchronous barrier path would stall
        every process at the barrier for the length of the transfer).
        """
        combined, leaves = jax.device_get(self._jit_attest(state))
        return {
            "digest": digest_hex(np.asarray(combined)),
            "leaves": {k: digest_hex(np.asarray(v)) for k, v in leaves.items()},
        }

    def verify(self, state, attestation, *, generation=None, where="state") -> str:
        """Check ``state`` against a journaled attestation record.

        ``attestation`` is a hex digest or an :meth:`attestation` dict.
        Returns the (matching) hex digest, or raises :class:`IntegrityError`
        naming the first splitting leaves. Host-side — safe on restored
        (unplaced) pytrees.
        """
        want = attestation["digest"] if isinstance(attestation, dict) else attestation
        expected_leaves = (
            attestation.get("leaves") if isinstance(attestation, dict) else None
        )
        return verify_state_digest(
            self._selected(state),
            want,
            generation=generation,
            where=where,
            expected_leaves=expected_leaves,
        )

    # -- monitor surface -------------------------------------------------------

    def hooks(self) -> Sequence[str]:
        return ("post_step",)

    def init(self, key=None) -> AttestState:
        return AttestState(
            count=jnp.zeros((), jnp.int32),
            ring_digest=jnp.zeros((self.capacity, DIGEST_WORDS), jnp.uint32),
            ring_generation=jnp.full((self.capacity,), -1, jnp.int32),
        )

    def post_step(self, mstate: AttestState, wf_state) -> AttestState:
        gen = jnp.asarray(wf_state.generation, jnp.int32)
        due = (gen % self.every) == 0

        def _attest(ms):
            words = state_digest(self._selected(wf_state))
            return ms.replace(
                count=ms.count + 1,
                ring_digest=ring_write(ms.ring_digest, words, ms.count),
                ring_generation=ring_write(ms.ring_generation, gen, ms.count),
            )

        return jax.lax.cond(due, _attest, lambda ms: ms, mstate)

    # -- host readback ---------------------------------------------------------

    def ledger(self, mstate: AttestState) -> List[Dict[str, Any]]:
        """Chronological ``[{generation, digest}]`` over the ring."""
        count = int(jax.device_get(mstate.count))
        gens = np.asarray(jax.device_get(mstate.ring_generation))
        digs = np.asarray(jax.device_get(mstate.ring_digest))
        return [
            {"generation": int(gens[s]), "digest": digest_hex(digs[s])}
            for s in ring_slots(count, self.capacity)
        ]

    def integrity_report(self, mstate: AttestState) -> Dict[str, Any]:
        """run_report ``integrity`` section contribution (host-side)."""
        ring = self.ledger(mstate)
        return {
            "enabled": True,
            "every": self.every,
            "capacity": self.capacity,
            "attestations": int(jax.device_get(mstate.count)),
            "ring": ring,
        }

    def journal_ring(self, mstate: AttestState, journal) -> int:
        """Append one ``attest`` record per ring entry to a RunJournal."""
        ring = self.ledger(mstate)
        for rec in ring:
            journal.append(
                "attest", generation=rec["generation"], digest=rec["digest"]
            )
        return len(ring)


# -- divergence forensics ------------------------------------------------------


def _journal_records(journal_dir) -> List[Dict[str, Any]]:
    if isinstance(journal_dir, (list, tuple)):
        return list(journal_dir)
    journal = journal_dir
    if not hasattr(journal, "records"):
        from ..workflows.journal import RunJournal  # deferred: layering

        journal = RunJournal(os.fspath(journal_dir))
    return journal.records()


def _pod_context(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Epoch and pod census from the journal's pod lifecycle records."""
    epoch, census = 0, None
    for rec in records:
        if not isinstance(rec, dict):
            continue
        if "epoch" in rec:
            epoch = max(epoch, int(rec["epoch"]))
        if rec.get("kind") == "census":
            census = rec.get("alive", rec.get("census"))
        elif rec.get("kind") == "pod_join":
            census = rec.get("world", census)
    return {"epoch": epoch, "pod_census": census}


def _load_attestations(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Distill ``[{generation, digest}]`` sorted by generation from journal
    records or an explicit ledger list (deduped, last record wins —
    re-attestation after a heal supersedes)."""
    by_gen: Dict[int, str] = {}
    for rec in records:
        kind = rec.get("kind") if isinstance(rec, dict) else None
        if kind == "attest" or (kind is None and "digest" in rec):
            by_gen[int(rec["generation"])] = str(rec["digest"])
        elif kind == "chunk_complete" and isinstance(rec.get("attest"), dict):
            att = rec["attest"]
            if "digest" in att:
                by_gen[int(rec["generation"])] = str(att["digest"])
    return [
        {"generation": g, "digest": by_gen[g]} for g in sorted(by_gen)
    ]


def bisect_divergence(
    journal_dir,
    *,
    wf,
    start_state,
    suspect: Optional[Callable[[Any, int], Any]] = None,
    attestor: Optional[StateAttestor] = None,
    report_to=None,
) -> Dict[str, Any]:
    """Name the first generation where a run's bits went wrong.

    ``journal_dir`` holds the suspect run's attestations (``attest``
    records or ``chunk_complete`` barriers with an ``attest`` field; an
    explicit ``[{generation, digest}]`` ledger is also accepted).
    ``start_state`` is the trusted state at the last attested barrier
    (digest-verified against the journal when attested there); ``wf.run``
    replays the honest trajectory from it.

    Phase 1 (windowing) replays through the journaled attestations to find
    the first cadence window whose digest splits. Phase 2 (bisection)
    needs a reproducible suspect leg — ``suspect(state, n_steps)`` re-runs
    the faulty path (a sticky-fault pod, or a fault-injected drive in
    tests) — and advances both legs at halving chunk sizes until the first
    divergent generation is pinned exactly. Without ``suspect`` (transient
    SDC) the report carries the window only.

    Returns the structured report consumed by run_report schema v14
    ``integrity.bisection`` and the ``integrity.*`` FlightRecorder gauges;
    ``report_to`` (a workflow) additionally stashes it on
    ``._integrity_forensics`` for run_report pickup.
    """
    att = attestor if attestor is not None else StateAttestor()
    records = _journal_records(journal_dir)
    ledger = _load_attestations(records)

    cur = int(jax.device_get(start_state.generation))
    start_gen = cur
    report: Dict[str, Any] = {
        "enabled": True,
        "barrier_generation": start_gen,
        **_pod_context(records),
        "attestations_checked": 0,
        "chunks_replayed": 0,
        "generations_replayed": 0,
        "first_divergent_generation": None,
        "window": None,
        "leaves": [],
        "reproducible": None,
        "verdict": "clean",
    }
    if report_to is not None:
        report_to._integrity_forensics = report

    # Trust check: the start state must match its own journaled attestation.
    at_start = [r for r in ledger if r["generation"] == start_gen]
    if at_start and att.digest_hex(start_state) != at_start[-1]["digest"]:
        raise IntegrityError(
            f"bisect_divergence: start state at generation {start_gen} does "
            f"not match its journaled attestation — no trusted barrier to "
            f"replay from",
            generation=start_gen,
            where="bisect_divergence",
        )

    # Phase 1: replay the honest leg through the journaled attestations.
    ref_state = start_state
    g_lo, g_hi = start_gen, None
    for rec in ledger:
        gen = rec["generation"]
        if gen <= cur:
            continue
        ref_state = wf.run(ref_state, gen - cur)
        report["chunks_replayed"] += 1
        report["generations_replayed"] += gen - cur
        cur = gen
        report["attestations_checked"] += 1
        if att.digest_hex(ref_state) == rec["digest"]:
            g_lo = gen
        else:
            g_hi = gen
            break
    if g_hi is None:
        return report  # every attestation matches the honest replay

    report["window"] = [g_lo + 1, g_hi]
    report["verdict"] = "detected"
    if suspect is None:
        return report

    # Phase 2: synchronized two-leg halving replay inside (g_lo, g_hi].
    ref_state = start_state
    if g_lo > start_gen:
        ref_state = wf.run(ref_state, g_lo - start_gen)
        report["generations_replayed"] += g_lo - start_gen
        report["chunks_replayed"] += 1
    sus_state = ref_state
    g, hi = g_lo, g_hi
    first_divergent = None
    while g < hi:
        step = max(1, (hi - g) // 2)
        ref_next = wf.run(ref_state, step)
        sus_next = suspect(sus_state, step)
        report["chunks_replayed"] += 2
        report["generations_replayed"] += 2 * step
        if att.digest_hex(ref_next) == att.digest_hex(sus_next):
            g += step
            ref_state, sus_state = ref_next, sus_next
            if g == hi:
                # The suspect leg did not reproduce the journaled fault.
                report["reproducible"] = False
                return report
        else:
            hi = g + step
            if step == 1:
                first_divergent = hi
                ref_leaves = host_leaf_digests(att._selected(ref_next))
                sus_leaves = host_leaf_digests(att._selected(sus_next))
                report["leaves"] = [
                    name
                    for name in sorted(set(ref_leaves) | set(sus_leaves))
                    if ref_leaves.get(name) != sus_leaves.get(name)
                ]
                break
    report["reproducible"] = True
    report["first_divergent_generation"] = first_divergent
    return report

"""Crash-safe, callback-free, topology-portable run checkpointing.

:class:`~evox_tpu.monitors.CheckpointMonitor` auto-saves from INSIDE the
jitted step via ``io_callback`` — a device-to-host transfer in the hot
path, unusable under ``vmap`` (tenant fleets) and on any runtime that
cannot call back into Python. :class:`WorkflowCheckpointer` is the
backend-universal alternative: it runs entirely on the host BETWEEN
dispatches (never inside traced code), so it works identically on CPU
and TPU.

Durability contract:

- Snapshots are written atomically (tmp + fsync + ``os.replace`` +
  parent-directory fsync), with a digest-validated JSON manifest
  committed AFTER the data file the same way — a crash (or power loss:
  the directory fsync is what makes the rename itself durable, a rename
  without it can tear) at any byte leaves either a complete (manifest +
  digest-verified data) snapshot or an ignorable partial, never a torn
  restore.
- :meth:`WorkflowCheckpointer.latest` walks snapshots newest → oldest and
  skips (with a warning) anything whose manifest is missing/garbled or
  whose payload fails the SHA-256 check, restoring the newest snapshot
  that is provably intact.
- Each manifest carries a **config fingerprint** of the snapshotted
  state (leaf paths + shapes + dtypes + the algorithm state's type) —
  ``latest(expect_like=...)`` / ``resume()`` refuse a snapshot written
  under a different algorithm or population size
  (:class:`CheckpointConfigError`) instead of feeding it to a compiled
  program built for other shapes; ``allow_config_mismatch=True``
  overrides.

Topology portability: snapshot leaves are plain host numpy arrays
(``jax.device_get`` gathers every shard; cross-process-sharded leaves
all-gather through ``core.distributed.host_value`` first), so a snapshot
carries NO mesh — the manifest records the save-time topology (device
AND process counts) and per-leaf sharding specs for provenance only.
Restoring onto a *different* device count OR PROCESS count (checkpoint
on 8 devices in 1 process, restart as 2×4 or 4×2 processes — the pod
recovery path; ``place_state`` reassembles each process's addressable
shards from the host leaves) is therefore data-complete by construction.
Pod saves follow process-0-writes + barrier discipline: the gather is
collective, process 0 writes the one manifest, a coordinator-KV barrier
holds the others until it is durable — one pod save is one manifest,
not N (see :meth:`WorkflowCheckpointer.save`). Restoring on a pod reads
the snapshot on every process (shared or replicated filesystem) and
reassembles; the dryrun_multihost harness asserts the 1-process→
n-process trajectory-reproduction law where the backend can run
cross-process collectives.
:func:`restore_layouts` (or ``StdWorkflow.resume(state_sharding=...)``)
eagerly re-places the host leaves onto the CURRENT mesh according to the
state's own ``field(sharding=...)`` annotations — the same layout law
``constrain_state`` applies inside the step, so the resumed run
reproduces the straight run's remaining trajectory
(tests/test_supervisor.py asserts 8→4→1 equivalence).

Resume contract (asserted in tests/test_chaos.py): a run of ``n`` total
generations that crashes after generation ``K`` and is resumed from the
gen-``K`` snapshot produces the same final state pytree as the
uninterrupted run — every random draw lives in the state, so the chunked
run re-traverses the identical program. (Host problems that keep
generation-to-generation state on the problem OBJECT — e.g. the rollout
farms' per-generation seed draw — are outside the snapshot; resume
equivalence there requires the problem's evaluate to be deterministic or
externally seeded, see GUIDE.md §6.)
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import warnings
from pathlib import Path
from typing import Any, List, Optional

import jax

from ..core.attest import IntegrityError, digest_hex, host_state_digest

_SCHEMA = "evox_tpu.workflow_checkpoint/v1"


def attest_digest_hex(state: Any) -> str:
    """Hex attestation of a (host) state — the NumPy digest mirror, so
    manifest writing costs one host pass, no device dispatch. Bitwise
    equal to the on-device ``state_digest`` of the same bits (the
    core/attest.py host-mirror law)."""
    return digest_hex(host_state_digest(state))

# Crash-injection hook for the process-chaos harness (tests/_proc_chaos.py):
# when set, it is called with a named point inside the durable-write path
# ("pre_rename:<suffix>" before the atomic os.replace, "manifest_pending"
# between a snapshot's committed data file and its manifest) — the chaos
# child SIGKILLs itself there to reproduce a power-loss-shaped tear at an
# exact byte boundary, including on the executor's BACKGROUND checkpoint
# lane (the hook runs on whatever thread performs the write). Always None
# in production; never set it outside tests.
_CRASH_HOOK = None


def _crash_point(point: str) -> None:
    if _CRASH_HOOK is not None:
        _CRASH_HOOK(point)


class CheckpointConfigError(RuntimeError):
    """A snapshot's config fingerprint does not match the run asking to
    restore it — different algorithm, population size, monitors, or
    state structure. Restoring it anyway would hand a compiled program
    arrays of the wrong shape (or silently resurrect a different
    experiment); pass ``allow_config_mismatch=True`` to override."""


def state_config_fingerprint(state: Any) -> str:
    """SHA-256 over the state's structural identity: every leaf's key
    path, shape, and dtype, plus the algorithm state's type name.
    Invariant across devices/meshes/backends AND across the host/device
    boundary (a pickled-numpy snapshot fingerprints identically to the
    live jax state it came from); sensitive to algorithm class,
    population size, dimensionality, and monitor set. Static fields
    (e.g. the ``first_step`` peel flag) are deliberately excluded — they
    legitimately differ between a fresh state and a mid-run snapshot."""
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    parts = [type(getattr(state, "algo", state)).__name__]
    for path, leaf in leaves:
        arr = leaf if hasattr(leaf, "shape") else None
        shape = tuple(arr.shape) if arr is not None else ()
        dtype = str(arr.dtype) if arr is not None else type(leaf).__name__
        parts.append(f"{jax.tree_util.keystr(path)}:{shape}:{dtype}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def _fsync_path(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_durable(path: Path, payload: bytes, tmp_suffix: str) -> None:
    """tmp + flush + fsync(file) + atomic rename + fsync(directory): the
    full crash/power-loss discipline — an os.replace alone is atomic
    against CRASHES but not durable against power loss until the parent
    directory entry itself is synced."""
    tmp = path.with_suffix(tmp_suffix)
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    _crash_point(f"pre_rename:{path.name}")
    os.replace(tmp, path)
    _fsync_path(path.parent)


def _leaf_shardings(state: Any) -> dict:
    """Per-leaf ``PartitionSpec`` strings of a LIVE (device) state, for
    the manifest's provenance record. Host/numpy leaves record nothing."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None and any(s is not None for s in spec):
            out[jax.tree_util.keystr(path)] = str(spec)
    return out


def restore_layouts(state: Any, mesh: Any = None, state_sharding: Any = None) -> Any:
    """Eagerly place a host-restored snapshot onto the CURRENT mesh.

    ``state_sharding``: an explicit pytree of shardings (e.g. from
    :func:`~evox_tpu.core.distributed.state_sharding`) applied leaf-wise
    with ``jax.device_put``. Without it, the state's own
    ``field(sharding=...)`` annotations drive the placement on ``mesh``
    (:func:`~evox_tpu.core.distributed.place_state`) — the same law
    ``constrain_state`` applies inside every step, now on whatever mesh
    the restoring process built. No-op when both are ``None`` (the first
    dispatch then places leaves with its default device_put, and the
    in-step constraints still land the declared layouts)."""
    if state_sharding is not None:
        return jax.tree.map(jax.device_put, state, state_sharding)
    if mesh is None:
        return state
    from ..core.distributed import place_state

    return place_state(state, mesh)


def chunk_to_boundary(state: Any, checkpointer: Optional["WorkflowCheckpointer"],
                      chunk: Optional[int] = None) -> int:
    """Generations from ``state.generation`` to the next chunk boundary:
    the checkpoint cadence grid when a checkpointer is given, else the
    ``chunk`` grid, else effectively-unbounded (one dispatch for the
    rest). Aligning chunks to a GLOBAL grid (not the entry generation)
    keeps boundary generations identical across crash/resume/replay —
    the same determinism law as ``checkpointed_run`` and ``ipop_run``."""
    every = checkpointer.every if checkpointer is not None else chunk
    if every is None:
        return 1 << 30
    return every - int(state.generation) % every


class WorkflowCheckpointer:
    """Host-side periodic snapshots of a workflow state (no host callback).

    Args:
        directory: snapshot directory (created if missing). Snapshots from
            a previous process in the same directory are adopted — that is
            the crash-recovery path.
        every: checkpoint cadence in generations. ``wf.run(...,
            checkpointer=...)`` chunks its fused device loop at this
            cadence and snapshots between dispatches;
            ``run_host_pipelined`` snapshots whenever
            ``state.generation`` crosses a multiple of ``every``.
        keep: newest snapshots retained (older ones pruned after each
            successful save).
        barrier_timeout_s: deadline for the pod save's commit barriers
            (multi-process only). A peer SIGKILLed mid-save then raises
            the classified
            :class:`~evox_tpu.core.distributed.BarrierTimeoutError`
            naming the missing processes after this bound instead of
            holding the survivors for the 120 s default (ISSUE 14; the
            pod supervisor further refines it through the census).
    """

    _CONFIG = "checkpointer.json"

    def __init__(
        self,
        directory: str,
        every: int = 10,
        keep: int = 3,
        barrier_timeout_s: Optional[float] = None,
    ):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.every = every
        self.keep = keep
        self.barrier_timeout_s = barrier_timeout_s

    def _commit_barrier(self) -> None:
        from ..core.distributed import process_barrier

        if self.barrier_timeout_s is None:
            process_barrier()
        else:
            process_barrier(timeout_s=self.barrier_timeout_s)

    def _write_config(self) -> None:
        """Persist (every, keep) next to the snapshots, so a resume that
        only names the DIRECTORY (``resume_from="ckpts/run"``) recreates
        the run's configured cadence instead of silently falling back to
        the defaults (and a weaker durability promise)."""
        payload = json.dumps({"every": self.every, "keep": self.keep}).encode()
        _write_durable(self.directory / self._CONFIG, payload, ".json.tmp")

    # ------------------------------------------------------------------ save
    def save(self, state: Any) -> Path:
        """Atomically snapshot ``state`` (blocking host-side pickle).

        Writes ``ckpt_GGGGGGGG.pkl`` via tmp + fsync + rename + directory
        fsync, then its ``.manifest.json`` (schema, generation, byte
        count, SHA-256, config fingerprint, save-time topology) the same
        way — the manifest is the commit record, so a torn data file can
        never masquerade as a valid snapshot.

        Multi-process (pod) discipline: every process participates in the
        device→host gather (cross-process-sharded leaves all-gather
        through :func:`~evox_tpu.core.distributed.host_value` — a
        collective, so ``save`` must be called on EVERY process, the SPMD
        law every dispatch already obeys), but only PROCESS 0 writes —
        one pod save is ONE manifest, not N racing copies — and a KV-
        store barrier holds the others until the manifest is durable, so
        no process can run ahead of a commit it may later restore. The
        snapshot itself stays topology-free host data: a 1-process save
        resumes on any process count and vice versa (``place_state``
        reassembles per-process shards on the restoring pod's mesh)."""
        multiproc = jax.process_count() > 1
        shardings = _leaf_shardings(state)
        if multiproc:
            from ..core.distributed import tree_host_value

            # collective all-gather: every process ends with the FULL
            # host value of every leaf (identical bytes on each process)
            host_state = tree_host_value(state)
        else:
            host_state = jax.device_get(state)
        gen = int(host_state.generation)
        path = self.directory / f"ckpt_{gen:08d}.pkl"
        if multiproc and jax.process_index() != 0:
            # process-0-writes: wait for the writer's manifest commit
            # (save() below hits the same barrier after its writes)
            self._commit_barrier()
            return path
        payload = pickle.dumps(host_state, protocol=pickle.HIGHEST_PROTOCOL)
        _write_durable(path, payload, ".pkl.tmp")
        # a kill here (data durable, manifest not) must leave latest()
        # on the PREVIOUS intact snapshot — the manifest is the commit
        # record; asserted through the background lane by the process-
        # chaos harness
        _crash_point(f"manifest_pending:{path.name}")
        manifest = {
            "schema": _SCHEMA,
            "generation": gen,
            "bytes": len(payload),
            "sha256": hashlib.sha256(payload).hexdigest(),
            "file": path.name,
            # compute-integrity attestation (ISSUE 20, core/attest.py):
            # the layout-invariant digest of the STATE the payload
            # unpickles to, not of the payload bytes — sha256 above
            # guards the file, this guards the bits the run will resume
            # from (_load_validated recomputes and refuses a mismatch)
            "attest": {
                "digest": attest_digest_hex(host_state),
                "generation": gen,
            },
            # structural identity of the run (see state_config_fingerprint)
            "config_sha": state_config_fingerprint(host_state),
            # provenance only: the snapshot itself is topology-free host
            # data; restore_layouts re-places it on whatever mesh the
            # restoring process has
            "save_topology": {
                "device_count": jax.device_count(),
                "process_count": jax.process_count(),
                "leaf_shardings": shardings,
            },
        }
        _write_durable(
            self._manifest_path(path),
            json.dumps(manifest).encode(),
            ".json.tmp",
        )
        self._write_config()
        self._prune()
        if multiproc:
            # release the non-writer processes only after the manifest
            # (the commit record) is durable on disk
            self._commit_barrier()
        return path

    def maybe_save(self, state: Any) -> Optional[Path]:
        """Snapshot iff ``state.generation`` is a multiple of ``every``.
        Call between dispatches (it blocks on a device->host copy of the
        whole state). Always (re)writes the snapshot — an existing file
        for the same generation might be a torn leftover or belong to a
        previous run of a reused directory, and skipping on its mere
        existence would let it permanently shadow the live state."""
        if int(state.generation) % self.every != 0:
            return None
        return self.save(state)

    # ------------------------------------------------------------------ load
    def snapshots(self) -> List[Path]:
        """Committed snapshot data files, oldest -> newest (manifest
        presence = committed; digest validation happens at restore)."""
        tail = len(".manifest.json")
        return sorted(
            p.parent / p.name[:-tail]
            for p in self.directory.glob("ckpt_????????.pkl.manifest.json")
        )

    def latest(
        self,
        expect_like: Any = None,
        allow_config_mismatch: bool = False,
    ) -> Optional[Any]:
        """Restore the newest intact snapshot (None when nothing usable).

        Corrupt or torn snapshots — missing/garbled manifest, size or
        SHA-256 mismatch, unpicklable payload — are skipped with a warning
        and the next-older snapshot is tried, so one bad file never takes
        down a resume.

        ``expect_like``: a state pytree of the RESTORING run (a fresh
        ``wf.init`` result, or the live state being resumed). A snapshot
        whose recorded config fingerprint differs — different algorithm,
        pop size, monitors — raises :class:`CheckpointConfigError`
        instead of being silently restored into a program compiled for
        other shapes (``allow_config_mismatch=True`` overrides; manifests
        predating the fingerprint are never checked)."""
        expected = (
            None if expect_like is None
            else state_config_fingerprint(expect_like)
        )
        for path in reversed(self.snapshots()):
            got = self._load_validated(path)
            if got is None:
                continue
            manifest, state = got
            self._check_config(
                manifest, expected, path, allow_config_mismatch
            )
            return state
        return None

    @staticmethod
    def _check_config(
        manifest: dict,
        expected: Optional[str],
        path: Path,
        allow_config_mismatch: bool,
    ) -> None:
        recorded = manifest.get("config_sha")
        if (
            expected is not None
            and recorded is not None
            and recorded != expected
            and not allow_config_mismatch
        ):
            raise CheckpointConfigError(
                f"checkpoint {path.name} was written under a different "
                f"run config (snapshot config_sha {recorded[:12]}… != "
                f"expected {expected[:12]}…): algorithm, population "
                "size, or monitor set changed. Rebuild the matching "
                "workflow, point at the right directory, or pass "
                "allow_config_mismatch=True to restore anyway."
            )

    def load(
        self,
        generation: int,
        expect_like: Any = None,
        allow_config_mismatch: bool = False,
    ) -> Optional[Any]:
        """Restore the snapshot of ONE specific generation, or None when
        it is absent/uncommitted/torn (same validation + config guard as
        :meth:`latest`). The serving journal's recovery path uses this:
        a ``chunk_complete`` barrier names its snapshot generation, and a
        barrier whose snapshot never landed (driver killed
        mid-background-fsync) must fall back to the previous barrier
        rather than silently restoring a newer-but-unrelated snapshot."""
        path = self.directory / f"ckpt_{int(generation):08d}.pkl"
        if not self._manifest_path(path).exists():
            return None
        got = self._load_validated(path)
        if got is None:
            return None
        manifest, state = got
        expected = (
            None if expect_like is None
            else state_config_fingerprint(expect_like)
        )
        self._check_config(manifest, expected, path, allow_config_mismatch)
        return state

    def _manifest_path(self, path: Path) -> Path:
        return path.with_suffix(".pkl.manifest.json")

    def _load_validated(self, path: Path) -> Optional[tuple]:
        try:
            with open(self._manifest_path(path)) as f:
                manifest = json.load(f)
            payload = path.read_bytes()
            if len(payload) != manifest["bytes"]:
                raise ValueError(
                    f"size mismatch: {len(payload)} != {manifest['bytes']}"
                )
            digest = hashlib.sha256(payload).hexdigest()
            if digest != manifest["sha256"]:
                raise ValueError("sha256 mismatch")
            state = pickle.loads(payload)
            att = manifest.get("attest")  # absent in pre-v20 manifests
            if isinstance(att, dict) and "digest" in att:
                got = attest_digest_hex(state)
                if got != att["digest"]:
                    # file bytes intact but the STATE is not the one
                    # attested at save time — same corrupt-skip law as a
                    # torn payload: warn, fall back one snapshot
                    raise IntegrityError(
                        f"state digest {got} != manifest attestation "
                        f"{att['digest']}",
                        generation=manifest.get("generation"),
                        where=path.name,
                    )
            return manifest, state
        except Exception as e:
            warnings.warn(
                f"skipping corrupt checkpoint {path.name}: {e}", stacklevel=2
            )
            return None

    def _prune(self) -> None:
        snaps = self.snapshots()
        for old in snaps[: max(len(snaps) - self.keep, 0)]:
            for p in (old, self._manifest_path(old)):
                try:
                    p.unlink()
                except FileNotFoundError:
                    pass


def snapshot_dir_intact(directory: Any) -> bool:
    """Host-only intactness probe: does ``directory`` hold at least one
    COMMITTED, UNTORN snapshot — manifest present, payload bytes and
    SHA-256 matching — without unpickling anything? The multi-pod
    control plane uses this before stealing a parked continuation off a
    dead pod: a continuation whose checkpoint is torn cannot be re-
    placed (the target would crash at admission), so it is re-run fresh
    instead. Pure file I/O — safe from the gateway process with no jax
    state."""
    directory = Path(directory)
    tail = len(".manifest.json")
    manifests = sorted(
        directory.glob("ckpt_????????.pkl.manifest.json"), reverse=True
    )
    for mpath in manifests:
        try:
            with open(mpath) as f:
                manifest = json.load(f)
            payload = (mpath.parent / mpath.name[:-tail]).read_bytes()
            if len(payload) != manifest["bytes"]:
                continue
            if hashlib.sha256(payload).hexdigest() != manifest["sha256"]:
                continue
            return True
        except Exception:
            continue
    return False


def _as_checkpointer(resume_from: Any) -> WorkflowCheckpointer:
    if isinstance(resume_from, WorkflowCheckpointer):
        return resume_from
    # directory string: adopt the crashed run's persisted cadence (see
    # _write_config) rather than silently resuming with the defaults
    kw = {}
    try:
        with open(Path(resume_from) / WorkflowCheckpointer._CONFIG) as f:
            cfg = json.load(f)
        kw = {"every": int(cfg["every"]), "keep": int(cfg["keep"])}
    except Exception:
        pass  # no/garbled config (pre-existing dir): defaults apply
    return WorkflowCheckpointer(str(resume_from), **kw)


def resolve_resume(
    resume_from: Any,
    state: Any,
    n_steps: int,
    expect_like: Any = None,
    allow_config_mismatch: bool = False,
):
    """Shared ``resume_from=`` handling for Std and pipelined runs.

    ``resume_from`` (a :class:`WorkflowCheckpointer` or a directory path)
    overrides ``state`` with its newest intact snapshot when one exists;
    ``n_steps`` then counts TOTAL generations from 0, so the remaining
    trip count is ``n_steps - state.generation``. ``expect_like``
    (normally the caller's live ``state``) arms the config-fingerprint
    guard. Returns ``(state, remaining_steps)``."""
    loaded = _as_checkpointer(resume_from).latest(
        expect_like=expect_like, allow_config_mismatch=allow_config_mismatch
    )
    if loaded is not None:
        state = loaded
    return state, max(n_steps - int(state.generation), 0)


def enter_run(
    state: Any,
    n_steps: int,
    checkpointer: Optional[WorkflowCheckpointer] = None,
    resume_from: Any = None,
    expect_like: Any = None,
    allow_config_mismatch: bool = False,
):
    """The shared run prologue every driver used to hand-roll (std.py,
    islands.py, pipelined.py, tenancy.py, supervisor.py each repeated
    the same three steps): resolve ``resume_from`` into (restored state,
    REMAINING generations), and default the checkpointer to the resumed
    directory — a resumed run must stay crash-safe and record its own
    completion, or a second resume would re-run generations. Returns
    ``(state, remaining_steps, checkpointer)``; a no-op (checkpointer
    passed through) when ``resume_from`` is None."""
    if resume_from is not None:
        state, n_steps = resolve_resume(
            resume_from,
            state,
            n_steps,
            expect_like=expect_like,
            allow_config_mismatch=allow_config_mismatch,
        )
        if checkpointer is None:
            checkpointer = _as_checkpointer(resume_from)
    return state, n_steps, checkpointer


def checkpointed_run(wf, state, n_steps: int, checkpointer: WorkflowCheckpointer):
    """``wf.run`` with host-side snapshots between dispatches.

    The fused device loop is chunked at the checkpoint cadence: each chunk
    ends exactly on a multiple of ``checkpointer.every`` (or at
    ``n_steps``), the state is snapshotted, and the next chunk is
    dispatched. Chunking a ``fori_loop`` does not change its math, so the
    final state is identical to a straight ``wf.run(state, n_steps)`` —
    and a crash between chunks resumes from the last snapshot with
    nothing lost but the current chunk. The final state is always
    snapshotted (even off-cadence) so a completed run restores to its
    true end.

    Since the executor port this is a thin policy over
    :class:`~evox_tpu.core.executor.GenerationExecutor` — the cadence
    chunking lives there once, and the snapshot pickle+fsync runs on the
    executor's background checkpoint lane (bounded in-flight, drained
    before return) instead of stalling the next chunk's dispatch."""
    from ..core.executor import GenerationExecutor

    return GenerationExecutor().run_fused(
        wf, state, n_steps, checkpointer=checkpointer
    )

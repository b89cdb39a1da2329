"""AOT executable cache: compile once, serve from memory, warm-start from disk.

XLA's world is static-shape: every distinct (program, abstract signature)
pays a full trace+compile — seconds to minutes on real fleets — and the
PR-4 retrace detector can only *report* that cost after it landed on the
critical path. This module makes the compile an *artifact* instead of an
event: entry points are AOT-compiled once per cache key
(``jax.jit(fn).lower(*args).compile()``), held in memory, and persisted
through :func:`jax.experimental.serialize_executable.serialize` to a
content-addressed on-disk store, so a COLD PROCESS warm-starts its fleet
by deserializing executables instead of recompiling (no benchmark cell
drives it yet: ROADMAP D6).

Cache key anatomy (what must match for an entry to be reusable):

- the **entry label** (``step`` / ``run_loop`` / ``solo_peel`` …),
- the caller's **config fingerprint** — algorithm class + any static
  workflow config the traced program depends on (the elastic layer
  passes ``workflows/elastic.py``'s bucket workflow fingerprint),
- the **abstract argument signature** (leaf shapes/dtypes + static
  pytree metadata — :func:`~evox_tpu.core.xla_cost.abstract_signature`,
  the same signature the retrace detector watches),
- the **bucket** (the elastic serving shape, when one applies) and the
  **mesh axes/shape**.

Deliberately NOT in the key: platform, device count, process count, and
jax version. Those are recorded in the entry's manifest as **topology
provenance** instead, and a lookup that finds an entry written under a
different topology *refuses loudly* (:class:`ExecCacheError`, the
``CheckpointConfigError`` discipline from PR 5) rather than silently
recompiling — a silently-cold store on the serving path is exactly the
failure this cache exists to make visible. A torn/corrupt entry (size or
SHA-256 mismatch, unpicklable payload — the crash artifact) is skipped
with a warning and recompiled, the ``WorkflowCheckpointer.latest()``
corrupt-skip discipline.

Durability: payload and manifest are written tmp + fsync + atomic rename
+ parent-directory fsync (the PR-5 power-loss discipline), manifest
last — the manifest is the commit record, so a torn payload can never
masquerade as a valid entry.

Strictness: ``strict=True`` (or :meth:`ExecutableCache.freeze` after
warming) promotes any UNPLANNED miss to :class:`ExecCacheMissError` — a
subclass of :class:`~evox_tpu.core.instrument.RetraceError`, so the PR-4
``strict_retrace`` machinery and the cache-miss alarm are one alarm
family: shape instability raises at dispatch, cold programs raise at
lookup. Planned warms (``planned=True``) never raise.

Everything here is host-side file I/O + AOT compilation outside traced
code — no host callbacks (pinned by tests/test_no_host_callbacks.py).

Portability caveats (jax 0.4.x, non-TPU backends):

- Programs embedding HOST custom calls (LAPACK eigh — the CMA family's
  decomposition) serialize raw function pointers that do not survive a
  process boundary under ASLR: a cold process would SEGFAULT, not
  recompile. ``_save_disk`` therefore refuses to persist such entries
  off-TPU (warned; the entry amortizes in memory only). Custom-call-free
  algorithms (PSO, OpenES, SepCMAES) persist and cold-start fine.
- A DESERIALIZED executable still referenced at interpreter exit can
  segfault jax's atexit ``clear_backends`` — every cache therefore
  registers itself with a module-level atexit guard that calls
  :meth:`ExecutableCache.close` (drop the in-memory executable refs)
  before jax's teardown runs, so a process exiting with cache hits no
  longer needs ``os._exit`` or manual reference surgery (PR 18; the
  guard is armed at first construction, AFTER jax registered its own
  handler, so atexit's LIFO order runs ours first). ``close()`` is
  also callable directly for deterministic teardown. Executables
  compiled in-process are unaffected either way.
"""

from __future__ import annotations

import atexit
import hashlib
import json
import os
import pickle
import time
import warnings
import weakref
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .instrument import RetraceError
from .xla_cost import abstract_signature

__all__ = [
    "ExecCacheError",
    "ExecCacheMissError",
    "ExecutableCache",
    "topology_fingerprint",
]

_SCHEMA = "evox_tpu.exec_cache/v1"

# every live cache, so the atexit guard can drop deserialized-executable
# references before jax's clear_backends runs (such a reference
# surviving to interpreter teardown can segfault). WeakSet: the
# guard must never be what keeps a cache alive.
_LIVE_CACHES: "weakref.WeakSet" = weakref.WeakSet()
_GUARD_ARMED = False


def _close_live_caches() -> None:
    for cache in list(_LIVE_CACHES):
        try:
            cache.close()
        except Exception:
            pass  # teardown must never raise over other atexit handlers


def _arm_teardown_guard() -> None:
    global _GUARD_ARMED
    if not _GUARD_ARMED:
        # registered lazily at FIRST cache construction — necessarily
        # after `import jax` registered clear_backends, so LIFO atexit
        # ordering runs this guard before jax tears the backend down
        atexit.register(_close_live_caches)
        _GUARD_ARMED = True


class ExecCacheError(RuntimeError):
    """An on-disk executable entry exists for the requested key but was
    written under a different topology (platform, device count, process
    count) or fails its own manifest (key mismatch) — loading it would
    hand the runtime an executable compiled for other hardware. Like
    :class:`~evox_tpu.workflows.checkpoint.CheckpointConfigError`, the
    refusal is loud: rebuild the store on this topology (delete the
    entry) instead of silently eating a recompile."""


class ExecCacheMissError(RetraceError):
    """A frozen/strict cache was asked for an executable it does not
    hold — the serving-path analog of a retrace (and a subclass of
    :class:`~evox_tpu.core.instrument.RetraceError`, so the PR-4
    strict-retrace alarm family catches both): compile cost is about to
    land on the critical path. Raised instead of compiling; warm the
    entry explicitly (``planned=True``) or drop ``strict``."""


def topology_fingerprint(mesh: Any = None) -> Dict[str, Any]:
    """The hardware/runtime identity an executable is only valid on:
    platform, device/process counts, jax version, and (when the program
    was lowered under one) the mesh's axis names and shape. Recorded in
    every entry manifest; checked — not keyed — so a foreign store
    refuses loudly instead of silently missing."""
    fp: Dict[str, Any] = {
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "jax": jax.__version__,
    }
    if mesh is not None:
        fp["mesh_axes"] = list(mesh.axis_names)
        fp["mesh_shape"] = [int(mesh.shape[a]) for a in mesh.axis_names]
    return fp


def _fsync_path(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_durable(path: Path, payload: bytes) -> None:
    """tmp + fsync + atomic rename + directory fsync — the PR-5
    checkpoint durability discipline (workflows/checkpoint.py), spelled
    locally because core must not import workflows."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_path(path.parent)


class _CachedDispatch:
    """Callable shim over a cached ``jax.stages.Compiled``: dispatches
    the executable directly, while keeping the ORIGINAL jittable
    reachable through ``.lower`` so the roofline analyzer
    (core/xla_cost.py ``analyze_callable`` — ``fn if hasattr(fn,
    "lower")``) still AOT-analyzes the same program instead of failing
    to trace through a Compiled."""

    def __init__(self, compiled: Any, original: Any):
        self._compiled = compiled
        self._original = original
        if hasattr(original, "lower"):
            self.lower = original.lower

    def __call__(self, *args: Any, **kwargs: Any):
        return self._compiled(*args, **kwargs)


class ExecutableCache:
    """Keyed store of AOT-compiled XLA executables, memory + disk.

    Args:
        directory: on-disk store (created if missing). ``None`` keeps
            the cache memory-only — hits still amortize compiles within
            the process, but a cold process starts cold.
        strict: promote any unplanned miss to
            :class:`ExecCacheMissError` (see module docstring). Usually
            set via :meth:`freeze` after the serving layer warmed its
            buckets.
        max_entries: in-memory executables retained (LRU eviction
            preferring DISK-BACKED victims, whose re-request is a disk
            hit; a memory-only entry — one the backend refused to
            persist, see the module's portability caveats — is evicted
            only when every resident entry is memory-only, and its
            re-request pays a full recompile). ``None`` = unbounded.

    Counters (``report()["counters"]``): ``hits`` (memory),
    ``disk_hits`` (deserialized from the store), ``misses`` (compiled —
    every miss is a compile event, the coherence rule
    tools/check_report.py v7 enforces), ``saves``, ``evictions``.
    """

    def __init__(
        self,
        directory: Optional[str] = None,
        strict: bool = False,
        max_entries: Optional[int] = None,
    ):
        self.directory = Path(directory) if directory is not None else None
        if self.directory is not None:
            self.directory.mkdir(parents=True, exist_ok=True)
        self.strict = strict
        self.max_entries = max_entries
        self._mem: Dict[str, Any] = {}  # key -> Compiled (insertion = LRU)
        self._on_disk: set = set()  # keys with a committed disk entry
        self.counters = {
            "hits": 0,
            "disk_hits": 0,
            "misses": 0,
            "saves": 0,
            "evictions": 0,
        }
        self.compile_s_paid = 0.0  # misses: measured lower+compile time
        self.compile_s_saved = 0.0  # disk hits: manifest-recorded compile_s
        self.load_s = 0.0  # disk hits: measured deserialize time
        self.bytes_written = 0
        self.bytes_read = 0
        self.entries: List[dict] = []  # per-key provenance, report() order
        # serving-plane flight recorder (PR 16): attach with
        # `cache.metrics = rec` (RunQueue auto-threads its recorder) to
        # mirror hit/miss/compile-ms into the live metrics plane; None
        # (default) changes nothing
        self.metrics: Any = None
        _LIVE_CACHES.add(self)
        _arm_teardown_guard()

    def close(self) -> None:
        """Drop every in-memory executable reference (a DESERIALIZED
        executable alive at interpreter exit can segfault jax's atexit
        teardown). Durable state — the on-disk
        store, counters, provenance — is untouched, and the cache stays
        usable: a later request simply pays a disk hit (or a recompile)
        again. Idempotent; also run by the module's atexit guard."""
        self._mem.clear()

    # -------------------------------------------------------------- keying
    @staticmethod
    def cache_key(
        label: str,
        config_fingerprint: str,
        args: tuple,
        kwargs: Optional[dict] = None,
        bucket: Optional[Tuple[int, ...]] = None,
        mesh: Any = None,
    ) -> str:
        """Content address of one executable: sha256 over (label, the
        caller's static-config fingerprint, the abstract argument
        signature, the serving bucket, the mesh axes/shape). Topology is
        deliberately excluded — see the module docstring."""
        aval, static = abstract_signature(args, kwargs or {})
        parts = [label, config_fingerprint, aval, static]
        if bucket is not None:
            parts.append("bucket:" + ",".join(str(int(b)) for b in bucket))
        if mesh is not None:
            parts.append(
                "mesh:"
                + ",".join(
                    f"{a}={int(mesh.shape[a])}" for a in mesh.axis_names
                )
            )
        return hashlib.sha256("|".join(parts).encode()).hexdigest()

    # ------------------------------------------------------------- lookup
    def _paths(self, key: str) -> Tuple[Path, Path]:
        return (
            self.directory / f"{key}.exec",
            self.directory / f"{key}.manifest.json",
        )

    def _mem_put(self, key: str, compiled: Any) -> None:
        self._mem[key] = compiled
        if self.max_entries is not None:
            while len(self._mem) > self.max_entries:
                # prefer the oldest DISK-BACKED victim: its re-request
                # deserializes; evicting a memory-only entry (one the
                # backend refused to persist) forfeits its compile
                victim = next(
                    (k for k in self._mem if k in self._on_disk),
                    next(iter(self._mem)),
                )
                del self._mem[victim]
                self.counters["evictions"] += 1

    def _load_disk(self, key: str, mesh: Any) -> Optional[Tuple[Any, dict]]:
        """Deserialize the on-disk entry for ``key``. Returns
        ``(compiled, manifest)``; ``None`` when no committed entry
        exists OR the entry is torn/corrupt (warned, recompile path);
        raises :class:`ExecCacheError` when the entry is intact but
        written under a different topology or an inconsistent key —
        stale entries refuse loudly, broken ones self-heal."""
        exec_path, man_path = self._paths(key)
        if not man_path.exists():
            return None
        try:
            with open(man_path) as f:
                manifest = json.load(f)
            payload = exec_path.read_bytes()
            if len(payload) != manifest["bytes"]:
                raise ValueError(
                    f"size mismatch: {len(payload)} != {manifest['bytes']}"
                )
            if hashlib.sha256(payload).hexdigest() != manifest["sha256"]:
                raise ValueError("sha256 mismatch")
        except ExecCacheError:
            raise
        except Exception as e:
            warnings.warn(
                f"skipping corrupt executable-cache entry {key[:12]}…: {e}",
                stacklevel=3,
            )
            return None
        # the entry is INTACT: now the provenance guards, loud by design
        if manifest.get("key") != key:
            raise ExecCacheError(
                f"executable-cache entry {key[:12]}… carries manifest key "
                f"{str(manifest.get('key'))[:12]}… — the store was "
                "rewritten or copied inconsistently; delete the entry and "
                "re-warm"
            )
        recorded = manifest.get("topology") or {}
        current = topology_fingerprint(mesh)
        mismatched = {
            k: (recorded.get(k), current[k])
            for k in current
            if recorded.get(k) != current[k]
        }
        if mismatched:
            raise ExecCacheError(
                f"executable-cache entry {key[:12]}… was compiled under a "
                f"different topology ({mismatched}) — an executable is only "
                "valid on the hardware it was compiled for. Re-warm the "
                "store on this topology (delete the stale entry) instead "
                "of serving a foreign binary."
            )
        from jax.experimental import serialize_executable as _se

        t0 = time.perf_counter()
        serialized, in_tree, out_tree = pickle.loads(payload)
        compiled = _se.deserialize_and_load(serialized, in_tree, out_tree)
        self.load_s += time.perf_counter() - t0
        self.bytes_read += len(payload)
        self._on_disk.add(key)
        return compiled, manifest

    @staticmethod
    def _host_custom_calls(compiled: Any) -> List[str]:
        """Custom-call targets embedded in the compiled program. On
        non-TPU backends these lower to RAW HOST FUNCTION POINTERS
        (LAPACK eigh is the canonical case — CMA-ES fleets), which do
        not survive a process boundary: a cold process executing the
        deserialized binary segfaults under ASLR instead of erroring.
        Verified empirically on jax 0.4.x CPU; TPU executables are
        device binaries and unaffected."""
        try:
            txt = compiled.as_text()
        except Exception:
            return []
        return sorted(
            {
                line.split('custom_call_target="', 1)[1].split('"', 1)[0]
                for line in txt.splitlines()
                if 'custom_call_target="' in line
            }
        )

    def _save_disk(
        self,
        key: str,
        compiled: Any,
        label: str,
        bucket: Optional[Tuple[int, ...]],
        mesh: Any,
        compile_s: float,
    ) -> Optional[int]:
        from jax.experimental import serialize_executable as _se

        if jax.devices()[0].platform != "tpu":
            calls = self._host_custom_calls(compiled)
            if calls:
                # refuse to write an artifact that would SEGFAULT (not
                # recompile) a cold process — memory-only is the honest
                # degradation, and the warning names the culprit ops
                warnings.warn(
                    f"executable for {label!r} embeds host custom calls "
                    f"{calls} — raw function pointers that do not survive "
                    "a process boundary on the "
                    f"{jax.devices()[0].platform} backend; entry stays "
                    "memory-only (a cold process will recompile, not "
                    "crash). Algorithms without LAPACK decompositions "
                    "(PSO/OpenES/SepCMAES) persist fine.",
                    stacklevel=4,
                )
                return None
        try:
            serialized, in_tree, out_tree = _se.serialize(compiled)
            payload = pickle.dumps(
                (serialized, in_tree, out_tree),
                protocol=pickle.HIGHEST_PROTOCOL,
            )
        except Exception as e:
            # some backends cannot serialize (ValueError "Compilation
            # does not support serialization") — the cache degrades to
            # memory-only for that entry, recorded so report() explains
            # the missing bytes instead of faking persistence
            warnings.warn(
                f"executable for {label!r} is not serializable on this "
                f"backend ({type(e).__name__}: {e}); entry stays "
                "memory-only",
                stacklevel=3,
            )
            return None
        exec_path, man_path = self._paths(key)
        _write_durable(exec_path, payload)
        manifest = {
            "schema": _SCHEMA,
            "key": key,
            "label": label,
            "bucket": list(bucket) if bucket is not None else None,
            "bytes": len(payload),
            "sha256": hashlib.sha256(payload).hexdigest(),
            "topology": topology_fingerprint(mesh),
            "compile_s": round(compile_s, 6),
            "created": round(time.time(), 3),
        }
        # manifest LAST: it is the commit record (a torn payload without
        # a manifest is invisible; a manifest always points at a payload
        # that was durable first)
        _write_durable(man_path, json.dumps(manifest).encode())
        self.counters["saves"] += 1
        self._on_disk.add(key)
        self.bytes_written += len(payload)
        return len(payload)

    # ---------------------------------------------------------------- get
    def get_or_compile(
        self,
        label: str,
        config_fingerprint: str,
        fn: Callable,
        args: tuple,
        kwargs: Optional[dict] = None,
        bucket: Optional[Tuple[int, ...]] = None,
        mesh: Any = None,
        planned: bool = False,
    ) -> Any:
        """The one lookup: memory hit → disk hit → compile (the miss).

        ``fn`` may be a ``jax.jit`` wrapper (lowered directly — the same
        program the workflow dispatches) or any traceable callable.
        ``args``/``kwargs`` may be concrete arrays or
        ``jax.ShapeDtypeStruct`` pytrees — lowering never executes.
        ``planned=True`` marks a warm-path compile that must not trip
        the strict-miss alarm. Returns a ``jax.stages.Compiled``."""
        kwargs = kwargs or {}
        key = self.cache_key(
            label, config_fingerprint, args, kwargs, bucket, mesh
        )
        compiled = self._mem.get(key)
        if compiled is not None:
            # refresh LRU position
            del self._mem[key]
            self._mem[key] = compiled
            self.counters["hits"] += 1
            if self.metrics is not None:
                self.metrics.count("exec_cache.hits")
            return compiled
        if self.directory is not None:
            got = self._load_disk(key, mesh)
            if got is not None:
                compiled, manifest = got
                self._mem_put(key, compiled)
                self.counters["disk_hits"] += 1
                self.compile_s_saved += float(manifest.get("compile_s") or 0.0)
                if self.metrics is not None:
                    self.metrics.count("exec_cache.disk_hits")
                self._note_entry(
                    {
                        "key": key[:16],
                        "label": label,
                        "bucket": list(bucket) if bucket is not None else None,
                        "source": "disk",
                        "bytes": int(manifest["bytes"]),
                        "compile_s_saved": float(
                            manifest.get("compile_s") or 0.0
                        ),
                    }
                )
                return compiled
        if self.strict and not planned:
            raise ExecCacheMissError(
                f"executable cache miss for entry {label!r} (key "
                f"{key[:12]}…) on a frozen cache — an unplanned compile "
                "was about to land on the serving path. Warm the bucket "
                "explicitly (planned=True) or drop strict."
            )
        self.counters["misses"] += 1
        t0 = time.perf_counter()
        lowerable = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = lowerable.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
        self.compile_s_paid += compile_s
        if self.metrics is not None:
            self.metrics.count("exec_cache.misses")
            self.metrics.observe("exec_cache.compile_ms", compile_s * 1e3)
        nbytes = None
        if self.directory is not None:
            nbytes = self._save_disk(
                key, compiled, label, bucket, mesh, compile_s
            )
        self._mem_put(key, compiled)
        self._note_entry(
            {
                "key": key[:16],
                "label": label,
                "bucket": list(bucket) if bucket is not None else None,
                "source": "compiled",
                "bytes": int(nbytes) if nbytes is not None else None,
                "compile_s": round(compile_s, 6),
            }
        )
        return compiled

    def _note_entry(self, entry: dict) -> None:
        """Record per-key provenance WITHOUT growing without bound: a
        long-lived server whose ``max_entries`` is smaller than its
        working set reloads evicted keys from disk continuously, and a
        fresh dict per reload would leak memory (and bloat ``report()``)
        linearly with traffic. Repeat events for the same (key, source)
        aggregate into the existing record's ``repeats`` count."""
        for e in self.entries:
            if e["key"] == entry["key"] and e["source"] == entry["source"]:
                e["repeats"] = int(e.get("repeats", 1)) + 1
                return
        self.entries.append(entry)

    def freeze(self) -> "ExecutableCache":
        """Arm the miss alarm: after the serving layer has warmed every
        planned bucket, any further miss is an unplanned compile and
        raises :class:`ExecCacheMissError`."""
        self.strict = True
        return self

    # ------------------------------------------------------------- report
    def report(self) -> dict:
        """The ``serving.cache`` section of ``run_report()`` (schema v7,
        validated by tools/check_report.py): counters whose coherence
        rule is *misses == compile events* (every miss pays exactly one
        compile; every disk hit saves the manifest-recorded one), byte
        traffic, and per-entry provenance."""
        return {
            "directory": str(self.directory) if self.directory else None,
            "strict": bool(self.strict),
            "counters": dict(self.counters),
            "compile_s_paid": round(self.compile_s_paid, 6),
            "compile_s_saved": round(self.compile_s_saved, 6),
            "load_s": round(self.load_s, 6),
            "bytes_written": int(self.bytes_written),
            "bytes_read": int(self.bytes_read),
            "entries": list(self.entries),
        }

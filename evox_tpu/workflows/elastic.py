"""Elastic serving: bucketed fleet shapes, warm admission, pop autoscaling.

``VectorizedWorkflow``/``RunQueue`` (PRs 7/8/11) serve FIXED fleet
shapes: a tenant whose (pop, dim, fleet-width) doesn't match the
compiled shape triggers a full XLA retrace on the critical path — the
one cost the PR-4 detector can only report. This module hides XLA's
static-shape world behind a small lattice of canonical shapes (Fiber's
elastic-membership serving model, PAPERS.md arXiv 2003.11164):

- :class:`BucketTable` quantizes a request's ``pop`` and fleet ``width``
  UP to powers-of-two rungs (user-overridable); ``dim`` is an exact key
  component, never padded — padding the population adds candidates whose
  fitness can be made inert, but padding the search space changes the
  objective itself (a separable problem's padded coordinates shift every
  fitness value), so each distinct dim is its own bucket.
- :class:`ElasticWorkflow` pads admission: a tenant requesting
  ``pop=p`` into a ``pop=B`` bucket runs the bucket shape with its last
  ``B − p`` fitness rows replaced by the worst FINITE fitness of its
  live rows (:func:`pad_inert_rows` — the quarantine fill law from
  PR 2), so the inert rows lose every comparison, never become
  best-so-far, and never perturb telemetry. The per-tenant live-row
  count rides as the reserved traced hyperparam ``ACTIVE_ROWS``, so ONE
  compiled bucket program serves every requested pop ≤ B. Width padding
  is idle filler slots (vmap rows are independent — asserted).
- :class:`ElasticServer` owns the bucket map: get-or-create a bucket's
  :class:`ElasticWorkflow` + :class:`~evox_tpu.workflows.tenancy.
  RunQueue` per canonical shape, AOT-warm its executables through
  :class:`~evox_tpu.core.exec_cache.ExecutableCache`
  (:func:`warm_fleet_cache` — memory/disk/compile), and route every
  submitted :class:`ElasticSpec` to its bucket. Admitting a tenant into
  a WARM bucket is pure state surgery (``insert_tenant``) against a
  cached executable — never a retrace (asserted with
  ``DispatchRecorder(strict_retrace=True)``); a COLD PROCESS warm-starts
  its buckets by deserializing executables from the cache directory in
  milliseconds instead of recompiling.
- :class:`PopAutoscaler` re-targets IPOP's increasing-population
  machinery (PAPERS.md arXiv 2409.11765; ``workflows/ipop.py``) as a
  SERVING policy: a guarded tenant showing the restart/stagnation
  escalation signal grows into the next pop rung's bucket when that
  bucket has capacity — the same :func:`~evox_tpu.workflows.ipop.
  grow_guarded` surgery the host-boundary doubling uses (pure in
  pop_size, so recovery re-derives it; the PR-10 handoff precedent),
  journaled as an ``autoscale`` close-out plus a continuation admit in
  the target bucket's journal.

Correctness contract (tests/test_elastic.py): a padded tenant ≡ its
:meth:`ElasticWorkflow.solo_workflow` run at the exact bucket shape with
the same inert-row mask (allclose(1e-5), the PR-7 tenancy contract);
inert rows and filler neighbours never change a healthy tenant's
telemetry ring fingerprint (bitwise); a serialized executable reloaded
in a fresh process reproduces the compiling process's trajectory
bitwise; stale-topology cache entries refuse loudly
(:class:`~evox_tpu.core.exec_cache.ExecCacheError`).

No reference analog (the reference has no serving layer; SURVEY §5):
design sources are Fiber and the IPOP-CMA-ES paper, see PARITY row 57.
Everything here is host-side orchestration + AOT compilation between
dispatches — no host callbacks (pinned by
tests/test_no_host_callbacks.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
import warnings
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.exec_cache import ExecutableCache
from .tenancy import RunQueue, TenantSpec, VectorizedWorkflow

__all__ = [
    "ACTIVE_ROWS",
    "BucketError",
    "BucketShape",
    "BucketTable",
    "ElasticServer",
    "ElasticSpec",
    "ElasticWorkflow",
    "PopAutoscaler",
    "pad_inert_rows",
    "warm_fleet_cache",
]

# reserved per-tenant hyperparam: the tenant's LIVE population rows
# (requested pop ≤ bucket pop). Traced like any hyperparam — one
# compiled bucket program serves every value — but never bound onto the
# algorithm template (ElasticWorkflow strips it before _bind)
ACTIVE_ROWS = "_elastic_active_rows"


def pad_inert_rows(fitness: jax.Array, active: Any) -> jax.Array:
    """Replace fitness rows at index ``>= active`` with the worst FINITE
    fitness among the live rows (per objective column — the
    ``quarantine_nonfinite`` fill law), so padded candidates lose every
    comparison-based selection cleanly: never top-k, never best-so-far,
    never a telemetry best. A live-row set with no finite entry falls
    back to the dtype's max finite value. ``active`` may be a traced
    scalar (the fleet path) or a python int (the solo fit_transform).
    Jittable, shape-preserving; ``active == pop`` is a bitwise
    identity."""
    n = fitness.shape[0]
    live = jnp.arange(n) < active
    live_b = live if fitness.ndim == 1 else live[:, None]
    finite_live = jnp.isfinite(fitness) & live_b
    worst = jnp.max(jnp.where(finite_live, fitness, -jnp.inf), axis=0)
    worst = jnp.where(
        jnp.isfinite(worst), worst, jnp.finfo(fitness.dtype).max
    )
    return jnp.where(live_b, fitness, worst)


# ------------------------------------------------------------------ buckets


class BucketError(ValueError):
    """A request cannot be mapped onto the bucket lattice (beyond the
    top rung, or a non-positive shape)."""


@dataclasses.dataclass(frozen=True)
class BucketShape:
    """One canonical compiled fleet shape: every tenant in the bucket
    runs ``pop`` candidates over ``dim`` dimensions in a ``width``-wide
    vmapped fleet."""

    pop: int
    dim: int
    width: int

    @property
    def key(self) -> str:
        return f"pop{self.pop}_dim{self.dim}_w{self.width}"

    def as_tuple(self) -> Tuple[int, int, int]:
        return (self.pop, self.dim, self.width)


def _pow2_rungs(lo: int, hi: int) -> Tuple[int, ...]:
    rungs, v = [], max(int(lo), 1)
    while v < hi:
        rungs.append(v)
        v *= 2
    rungs.append(int(hi))
    return tuple(rungs)


class BucketTable:
    """The lattice of canonical shapes requests are rounded UP onto.

    Args:
        pop_rungs: explicit sorted pop rungs; default powers of two from
            ``min_pop`` to ``max_pop``.
        width_rungs: explicit sorted fleet-width rungs; default powers
            of two from 1 to ``max_width``.
        min_pop / max_pop / max_width: lattice bounds for the defaults.

    ``dim`` has no rungs: it keys buckets exactly (see module
    docstring). A request beyond the top rung raises
    :class:`BucketError` — elastic serving rounds up, it never silently
    truncates a search."""

    def __init__(
        self,
        pop_rungs: Optional[Sequence[int]] = None,
        width_rungs: Optional[Sequence[int]] = None,
        min_pop: int = 8,
        max_pop: int = 1 << 16,
        max_width: int = 256,
    ):
        self.pop_rungs = (
            tuple(sorted(int(r) for r in pop_rungs))
            if pop_rungs
            else _pow2_rungs(min_pop, max_pop)
        )
        self.width_rungs = (
            tuple(sorted(int(r) for r in width_rungs))
            if width_rungs
            else _pow2_rungs(1, max_width)
        )
        if any(r < 1 for r in self.pop_rungs + self.width_rungs):
            raise BucketError("bucket rungs must be positive")

    @staticmethod
    def _round_up(value: int, rungs: Tuple[int, ...], what: str) -> int:
        if value < 1:
            raise BucketError(f"requested {what} must be >= 1, got {value}")
        for r in rungs:
            if r >= value:
                return r
        raise BucketError(
            f"requested {what}={value} exceeds the lattice's top rung "
            f"{rungs[-1]}; extend the {what} rungs (BucketTable("
            f"{what}_rungs=...)) or shrink the request"
        )

    def bucket_for(self, pop: int, dim: int, width: int = 1) -> BucketShape:
        """Quantize a (pop, dim, width) request onto the lattice: pop and
        width round UP to their rungs, dim passes through exactly."""
        if dim < 1:
            raise BucketError(f"requested dim must be >= 1, got {dim}")
        return BucketShape(
            pop=self._round_up(int(pop), self.pop_rungs, "pop"),
            dim=int(dim),
            width=self._round_up(int(width), self.width_rungs, "width"),
        )

    def next_pop_rung(self, pop: int) -> Optional[int]:
        """The smallest rung strictly above ``pop`` (the autoscaler's
        growth target), or None at the top of the lattice."""
        for r in self.pop_rungs:
            if r > pop:
                return r
        return None

    def report(self) -> dict:
        return {
            "pop_rungs": list(self.pop_rungs),
            "width_rungs": list(self.width_rungs),
            "dim": "exact",
        }


# ----------------------------------------------------------- padded fleets


class ElasticWorkflow(VectorizedWorkflow):
    """A :class:`VectorizedWorkflow` that understands the reserved
    ``ACTIVE_ROWS`` hyperparam: each tenant's fitness rows beyond its
    requested pop are replaced by the inert worst-finite fill
    (:func:`pad_inert_rows`) between the quarantine stage and the
    fit transforms — the bucket's padded-admission mechanism. Tenants
    without the binding behave exactly like the parent class."""

    def _check_hp_name(self, name: str) -> None:
        if name == ACTIVE_ROWS:
            return  # reserved: consumed by the workflow, never bound
        super()._check_hp_name(name)

    def _bind(self, hp: Dict[str, Any]):
        if ACTIVE_ROWS in hp:
            hp = {k: v for k, v in hp.items() if k != ACTIVE_ROWS}
        return super()._bind(hp)

    def _filter_fitness(self, t, fitness: jax.Array) -> jax.Array:
        active = t.hyperparams.get(ACTIVE_ROWS)
        if active is None:
            return fitness
        return pad_inert_rows(fitness, active)

    def solo_workflow(
        self,
        index: Optional[int] = None,
        hyperparams: Optional[Dict[str, Any]] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        state: Any = None,
    ):
        """The solo reference/resume workflow for a PADDED tenant: the
        parent's :class:`~evox_tpu.workflows.std.StdWorkflow` at the
        exact bucket shape, with the tenant's inert-row mask prepended
        to ``fit_transforms`` — the same pipeline position the fleet
        applies it at (after quarantine, before the user transforms), so
        the padded-tenant ≡ solo law holds with the mask on both
        sides."""
        if hyperparams is None:
            hyperparams = (
                self.tenant_hyperparams(index, state=state)
                if index is not None
                else {}
            )
        hp = dict(hyperparams)
        active = hp.pop(ACTIVE_ROWS, None)
        wf = super().solo_workflow(hyperparams=hp, mesh=mesh)
        if active is not None:
            wf.fit_transforms = (
                partial(pad_inert_rows, active=int(np.asarray(active))),
            ) + wf.fit_transforms
        return wf


# --------------------------------------------------------------- AOT warm


def _value_digest(v: Any) -> str:
    """Value identity for a BAKED constant (a closure cell, a partial's
    bound argument, an instance attribute). Arrays hash by
    dtype/shape/BYTES — ``repr`` truncates past 1000 elements, so two
    big constants differing in one element would collide — containers
    recurse element-wise, callables defer to
    :func:`_transform_identity`, the rest use an address-stripped
    repr."""
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_value_digest(x) for x in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k!r}:{_value_digest(x)}"
            for k, x in sorted(v.items(), key=lambda kv: repr(kv[0]))
        ) + "}"
    if callable(v) and not isinstance(v, type):
        return _transform_identity(v)
    try:
        arr = np.asarray(v)
        if arr.dtype == object:
            raise TypeError
        return (
            f"ndarray({arr.dtype},{arr.shape})#"
            + hashlib.sha256(arr.tobytes()).hexdigest()[:16]
        )
    except Exception:
        return re.sub(r" at 0x[0-9a-f]+", "", repr(v))


def _transform_identity(t: Any) -> str:
    """A content-addressed identity for a pop/fit transform. Bare
    ``__name__`` is not enough: two different lambdas both print
    ``<lambda>`` (two fleets sharing a cache directory would silently
    serve each other's compiled program), while ``repr`` of a partial
    embeds a ``0x`` address that changes every process (silently
    defeating the on-disk warm start). Functions key by module+qualname
    plus a digest of their BYTECODE and closure values; partials recurse
    into their func and key their bound arguments by value
    (:func:`_value_digest` — array bytes, never truncated repr)."""
    if isinstance(t, partial):
        args = ",".join(_value_digest(a) for a in t.args)
        kw = ",".join(
            f"{k}={_value_digest(v)}"
            for k, v in sorted(t.keywords.items())
        )
        return (
            f"partial({_transform_identity(t.func)},"
            f"args=({args}),kw=({kw}))"
        )
    code = getattr(t, "__code__", None)
    if code is not None:
        body = hashlib.sha256(
            code.co_code + repr(code.co_consts).encode()
        ).hexdigest()[:16]
        cells = []
        for c in getattr(t, "__closure__", None) or ():
            try:
                cells.append(_value_digest(c.cell_contents))
            except ValueError:  # empty cell
                cells.append("<empty>")
        name = getattr(t, "__qualname__", getattr(t, "__name__", "?"))
        return (
            f"{getattr(t, '__module__', '?')}.{name}"
            f"#{body}({','.join(cells)})"
        )
    # callable object: type identity + an address-stripped repr (the
    # config a __call__ object carries is in its repr by convention)
    return (
        f"{type(t).__module__}.{type(t).__qualname__}:"
        + re.sub(r" at 0x[0-9a-f]+", "", repr(t))
    )


def _instance_identity(obj: Any, depth: int = 0) -> str:
    """A content digest of an algorithm/problem instance's constructor
    config. The traced program BAKES closed-over constants (PSO's
    lb/ub, coefficients, a problem's parameters) that appear in neither
    the class name nor the abstract argument signature — two fleets
    differing only in those values must key distinct executables, or a
    shared cache directory silently serves one fleet the other's
    compiled program (the same hazard :func:`_transform_identity`
    guards for transforms). Public attributes hash by VALUE: arrays by
    bytes, nested objects (GuardedAlgorithm's inner algorithm) by
    recursion, callables by :func:`_transform_identity`, the rest by
    address-stripped repr."""
    name = f"{type(obj).__module__}.{type(obj).__qualname__}"
    if depth > 4 or not hasattr(obj, "__dict__"):
        return name
    h = hashlib.sha256(name.encode())
    for k, v in sorted(vars(obj).items()):
        if k.startswith("_"):
            continue
        h.update(k.encode())
        if callable(v) and not hasattr(v, "__dict__"):
            h.update(_transform_identity(v).encode())
            continue
        try:
            arr = np.asarray(v)
            if arr.dtype == object:
                raise TypeError
            h.update(
                str(arr.dtype).encode()
                + str(arr.shape).encode()
                + arr.tobytes()
            )
        except Exception:
            if hasattr(v, "__dict__") and not callable(v):
                h.update(_instance_identity(v, depth + 1).encode())
            else:
                # containers/callables/scalars: by VALUE, never by a
                # (truncating) repr — see _value_digest
                h.update(_value_digest(v).encode())
    return f"{name}#{h.hexdigest()[:16]}"


def fleet_fingerprint(wf: VectorizedWorkflow) -> str:
    """The static-config half of the executable cache key: everything
    that changes the TRACED fleet program without changing the abstract
    argument signature — algorithm/problem/monitor instance CONFIG
    (baked constants included, by value), fleet width, opt direction,
    quarantine/policy/donation flags, transform identities, hyperparam
    names. Leaf shapes/dtypes are keyed separately by the abstract
    signature."""
    parts = [
        type(wf).__qualname__,
        _instance_identity(wf.algorithm),
        _instance_identity(wf.problem),
        f"n={wf.n_tenants}",
        f"dir={np.asarray(wf.opt_direction).tolist()}",
        f"q={wf.quarantine_nonfinite}",
        f"donate={wf.donate_carries}",
        f"policy={wf.dtype_policy}",
        "pt:" + ",".join(_transform_identity(t) for t in wf.pop_transforms),
        "ft:" + ",".join(_transform_identity(t) for t in wf.fit_transforms),
        "mon:" + ",".join(_instance_identity(m) for m in wf.monitors),
        "hp:" + ",".join(sorted(wf.hyperparams)),
    ]
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def warm_fleet_cache(
    wf: VectorizedWorkflow,
    cache: ExecutableCache,
    bucket: Optional[BucketShape] = None,
    seed_key: Any = None,
    planned: bool = True,
) -> Dict[str, Any]:
    """AOT-compile (or reload from ``cache``) the fleet's four serving
    executables and swap them onto the workflow, so every subsequent
    dispatch runs a cached program:

    - ``fleet_step_first`` — the ``first_step=True`` init_ask peel,
    - ``fleet_step`` — the steady vmapped step,
    - ``fleet_run_loop`` — the fused fori_loop (trip count is a traced
      operand: ONE executable covers every chunk length),
    - ``fleet_solo_peel`` — the single-tenant admission peel (bindings
      are traced operands: one executable serves every admitted spec).

    Lowering uses ``jax.eval_shape`` abstract states — zero FLOPs, no
    state materialized. Idempotent: re-warming reuses the originals
    captured on first warm (a cache hit, not a recompile). The cache is
    advertised as ``wf._exec_cache`` so ``run_report`` surfaces the
    ``serving.cache`` section.

    Mesh caveat: executables are exact about input placement; a meshed
    fleet must be warmed AND driven with states placed the same way the
    lowering example was (``wf.init`` → dispatch, the normal serving
    path). Returns ``{"fingerprint", "entries"}``."""
    if not wf.jit_step:
        raise ValueError(
            "warm_fleet_cache requires jit_step=True: an eager fleet has "
            "no executable to cache"
        )
    fp = fleet_fingerprint(wf)
    originals = getattr(wf, "_exec_cache_originals", None)
    if originals is None:
        originals = {
            "step": wf._step,
            "run_loop": wf._run_loop,
            "solo_peel": wf._solo_peel,
        }
        wf._exec_cache_originals = originals
    key = (
        seed_key if seed_key is not None else jax.random.PRNGKey(0)
    )
    bt = bucket.as_tuple() if bucket is not None else None
    state0 = jax.eval_shape(wf.init, key)
    steady = state0.replace(first_step=False)
    hp0 = {k: v[0] for k, v in wf.hyperparams.items()}
    tenant0 = jax.eval_shape(lambda k: wf.init_tenant(k, hp0), key)
    n_sds = jax.ShapeDtypeStruct((), jnp.int32)

    get = partial(
        cache.get_or_compile,
        bucket=bt,
        mesh=wf.mesh,
        planned=planned,
    )
    step_first = get("fleet_step_first", fp, originals["step"], (state0,))
    step = get("fleet_step", fp, originals["step"], (steady,))
    run_loop = get("fleet_run_loop", fp, originals["run_loop"], (steady, n_sds))
    solo_peel = get("fleet_solo_peel", fp, originals["solo_peel"], (tenant0,))

    from ..core.exec_cache import _CachedDispatch

    def _step_dispatch(state):
        # first_step is STATIC pytree metadata: the designed init peel is
        # its own executable, the steady step another — exactly the two
        # programs jit would hold, now pinned to cached binaries
        return (step_first if state.first_step else step)(state)

    _step_dispatch.lower = originals["step"].lower  # roofline analyzer path
    wf._step = _step_dispatch
    wf._run_loop = _CachedDispatch(run_loop, originals["run_loop"])
    wf._solo_peel = _CachedDispatch(solo_peel, originals["solo_peel"])
    wf._exec_cache = cache
    return {
        "fingerprint": fp,
        "entries": ["fleet_step_first", "fleet_step", "fleet_run_loop",
                    "fleet_solo_peel"],
    }


# ------------------------------------------------------------- autoscaler


@dataclasses.dataclass
class PopAutoscaler:
    """IPOP-as-serving-policy (PAPERS.md arXiv 2409.11765): grow a
    struggling run into the next pop rung's bucket when capacity frees
    up. Requires the bucket factory to produce
    :class:`~evox_tpu.core.guardrail.GuardedAlgorithm` templates — the
    growth TRIGGER is the wrapper's on-device escalation signal
    (``restarts`` advanced past ``checked_restarts``, optionally a
    stagnation floor), the same rule ``workflows/ipop.py`` doubles on.

    Args:
        stagnation_limit: additionally trigger when a tenant's guarded
            ``stagnation`` counter reaches this (None: restart signal
            only — the IPOP default).
        max_grows: rungs a single run may climb (bounds the compile
            surface the autoscaler can create).
    """

    stagnation_limit: Optional[int] = None
    max_grows: int = 1

    def triggered(self, restarts: int, checked: int, stagnation: int) -> bool:
        trig = restarts > checked
        if self.stagnation_limit is not None:
            trig = trig or stagnation >= self.stagnation_limit
        return trig

    def report(self) -> dict:
        return {
            "stagnation_limit": self.stagnation_limit,
            "max_grows": self.max_grows,
        }


# ----------------------------------------------------------------- server


@dataclasses.dataclass
class ElasticSpec:
    """One elastic search request: any (pop, dim) — the server rounds it
    onto the bucket lattice. ``deadline`` is the SLA bound in the
    bucket's fleet generations (see :class:`~evox_tpu.workflows.tenancy.
    TenantSpec`)."""

    seed: Any
    n_steps: int
    pop: int
    dim: int
    hyperparams: Dict[str, Any] = dataclasses.field(default_factory=dict)
    tag: Optional[str] = None
    deadline: Optional[int] = None


@dataclasses.dataclass
class _Bucket:
    shape: BucketShape
    workflow: ElasticWorkflow
    queue: RunQueue
    fillers: int = 0


class ElasticServer:
    """The elastic serving front end: submit any (pop, dim) search; the
    server buckets it, warms the bucket's executables through the AOT
    cache, pads admission, and drives every bucket's
    :class:`~evox_tpu.workflows.tenancy.RunQueue` (SLA ordering,
    preemption, journal durability included — they are queue features).

    Args:
        factory: ``factory(bucket: BucketShape) -> ElasticWorkflow`` —
            builds the bucket's fleet at the canonical shape. The
            returned workflow must be an :class:`ElasticWorkflow` with
            ``n_tenants == bucket.width`` and the reserved
            ``ACTIVE_ROWS`` hyperparam in its constructor stack (see
            GUIDE.md §6 for the three-line recipe).
        table: the :class:`BucketTable` lattice (default powers of two).
        cache: an :class:`~evox_tpu.core.exec_cache.ExecutableCache`
            (or ``cache_dir`` to build one). A shared on-disk cache is
            what makes a cold process start in milliseconds.
        width: fleet-width request quantized per bucket (how many
            co-resident tenants a bucket serves).
        chunk: generations per dispatch chunk (RunQueue granularity).
        journal_dir / checkpoint_dir: per-bucket subdirectories are
            created under these (``<dir>/<bucket.key>``) — the PR-11
            durability story applies per bucket.
        autoscaler: a :class:`PopAutoscaler`, evaluated after every
            serve round.
        supervisor: optional RunSupervisor shared by every bucket queue.
        strict_after_warm: freeze the cache once a bucket is warmed —
            any later unplanned compile raises
            :class:`~evox_tpu.core.exec_cache.ExecCacheMissError`.
    """

    def __init__(
        self,
        factory: Callable[[BucketShape], ElasticWorkflow],
        table: Optional[BucketTable] = None,
        cache: Optional[ExecutableCache] = None,
        cache_dir: Optional[str] = None,
        width: int = 4,
        chunk: int = 5,
        journal_dir: Optional[str] = None,
        checkpoint_dir: Optional[str] = None,
        autoscaler: Optional[PopAutoscaler] = None,
        supervisor: Any = None,
        strict_after_warm: bool = False,
        metrics: Any = None,
        executor: Any = None,
    ):
        self.factory = factory
        self.table = table if table is not None else BucketTable()
        self.cache = (
            cache
            if cache is not None
            else ExecutableCache(directory=cache_dir)
        )
        self.width = width
        self.chunk = chunk
        self.journal_dir = Path(journal_dir) if journal_dir else None
        self.checkpoint_dir = Path(checkpoint_dir) if checkpoint_dir else None
        self.autoscaler = autoscaler
        self.supervisor = supervisor
        self.strict_after_warm = strict_after_warm
        # one GenerationExecutor can serve every bucket queue (the
        # background lanes are per-instance, so sharing keeps ONE
        # checkpoint lane for the whole server); None lets each RunQueue
        # build its own, as before
        self.executor = executor
        # serving-plane flight recorder (PR 16): ONE recorder spans the
        # whole lattice — threaded into every bucket RunQueue (whose
        # samples then share one SLO ledger across buckets) and the
        # shared executable cache. A str/Path builds a stream-backed
        # recorder; None (default) changes nothing.
        if isinstance(metrics, (str, Path)):
            from .flightrec import FlightRecorder

            metrics = FlightRecorder(directory=str(metrics))
        self.metrics = metrics
        if metrics is not None and getattr(self.cache, "metrics", None) is None:
            self.cache.metrics = metrics
        self._buckets: Dict[str, _Bucket] = {}
        self._filler_seq = 0
        self.autoscale_events: List[dict] = []

    # ------------------------------------------------------------- buckets
    def bucket_for(self, spec: ElasticSpec) -> BucketShape:
        return self.table.bucket_for(spec.pop, spec.dim, self.width)

    def _get_bucket(
        self, shape: BucketShape, recover: bool = False
    ) -> _Bucket:
        b = self._buckets.get(shape.key)
        if b is not None:
            return b
        wf = self.factory(shape)
        if not isinstance(wf, ElasticWorkflow):
            raise TypeError(
                "ElasticServer factory must return an ElasticWorkflow "
                f"(got {type(wf).__name__}) — the padded-admission mask "
                "lives there"
            )
        if wf.n_tenants != shape.width:
            raise ValueError(
                f"factory built a {wf.n_tenants}-wide fleet for bucket "
                f"{shape.key} (width {shape.width})"
            )
        if ACTIVE_ROWS not in wf.hyperparams:
            raise ValueError(
                f"bucket workflow must declare the reserved {ACTIVE_ROWS!r} "
                "hyperparam in its constructor stack (e.g. hyperparams={"
                f"{ACTIVE_ROWS!r}: jnp.full((width,), pop, jnp.int32)}}) — "
                "it carries each tenant's live-row count"
            )
        if self.autoscaler is not None and not hasattr(
            wf.algorithm, "health_report"
        ):
            raise ValueError(
                "PopAutoscaler needs the guarded escalation signal: the "
                "bucket factory must wrap its algorithm in "
                "GuardedAlgorithm (core/guardrail.py)"
            )
        warm_fleet_cache(wf, self.cache, bucket=shape, planned=True)
        wf._bucket_table = self.table  # run_report serving pickup
        if recover:
            # graft a journal-recovered queue in place of a fresh one:
            # same factory/warm/validation path, but the queue's
            # pending/slots/results come back from the bucket's journal
            # (RunQueue.recover — the PR-11 replay law). The multi-pod
            # control plane rebuilds dead or killed pods through this.
            if self.journal_dir is None:
                raise ValueError(
                    "recovering a bucket needs journal_dir — there is "
                    "no journal to replay without one"
                )
            q = RunQueue.recover(
                wf,
                str(self.journal_dir / shape.key),
                supervisor=self.supervisor,
                metrics=self.metrics,
                executor=self.executor,
            )
        else:
            q = RunQueue(
                wf,
                chunk=self.chunk,
                supervisor=self.supervisor,
                journal=(
                    str(self.journal_dir / shape.key)
                    if self.journal_dir is not None
                    else None
                ),
                checkpoint_dir=(
                    str(self.checkpoint_dir / shape.key)
                    if self.checkpoint_dir is not None
                    else None
                ),
                metrics=self.metrics,
                executor=self.executor,
            )
        b = _Bucket(shape=shape, workflow=wf, queue=q)
        self._buckets[shape.key] = b
        if self.strict_after_warm:
            self.cache.freeze()
        return b

    # -------------------------------------------------------------- submit
    def submit(self, spec: ElasticSpec) -> BucketShape:
        """Route a request onto the lattice and queue it in its bucket.
        Admission into an already-warm bucket is state surgery against a
        cached executable — no retrace."""
        shape = self.bucket_for(spec)
        b = self._get_bucket(shape)
        tspec = TenantSpec(
            seed=spec.seed,
            n_steps=spec.n_steps,
            hyperparams={
                **spec.hyperparams,
                ACTIVE_ROWS: jnp.asarray(int(spec.pop), jnp.int32),
            },
            tag=spec.tag,
            pop=shape.pop,
            deadline=spec.deadline,
        )
        b.queue.submit(tspec)
        return shape

    def _filler_spec(self, b: _Bucket) -> TenantSpec:
        """An inert width-padding tenant: full live rows (the mask is an
        identity), one-generation budget, result discarded. Fills the
        fleet to its static width when fewer real tenants are pending —
        the width half of padded admission."""
        self._filler_seq += 1
        b.fillers += 1
        hp0 = {
            name: jnp.asarray(stack[0])
            for name, stack in b.workflow.hyperparams.items()
        }
        hp0[ACTIVE_ROWS] = jnp.asarray(b.shape.pop, jnp.int32)
        return TenantSpec(
            seed=1_000_003 + self._filler_seq,
            n_steps=1,
            hyperparams=hp0,
            tag=f"_pad_{self._filler_seq:04d}",
            pop=b.shape.pop,
        )

    def _ensure_started(self, b: _Bucket) -> None:
        q = b.queue
        if q.state is not None:
            return
        if not q.pending and not q.continuations:
            return
        # continuations fill slots too (start() draws from both): only
        # top up the REAL shortfall, or continuation-fed buckets carry
        # surplus fillers that each cost an admission + serve rounds
        while (
            len(q.pending) + len(q.continuations) < b.workflow.n_tenants
        ):
            q.submit(self._filler_spec(b))
        q.start()

    # --------------------------------------------------------------- serve
    def _has_work(self) -> bool:
        for b in self._buckets.values():
            q = b.queue
            if q.pending or q.continuations:
                return True
            if q.state is not None and not q.finished:
                return True
        return False

    def has_work(self) -> bool:
        """Public face of the scheduling loop's continue condition —
        the multi-pod control plane polls it per pod."""
        return self._has_work()

    def serve_round(self) -> None:
        """ONE scheduling quantum: every bucket with work advances one
        chunk, then the autoscale pass runs. ``serve()`` is this in a
        loop; the multi-pod control plane calls it directly so the
        gateway can interleave rounds across pods (and kill/steal/
        recover between them at chunk granularity)."""
        for b in list(self._buckets.values()):
            self._ensure_started(b)
            q = b.queue
            if q.state is None:
                continue
            if q.finished and not (q.pending or q.continuations):
                continue
            q.step_chunk()
        self._autoscale_pass()

    def serve(self, max_rounds: Optional[int] = None) -> List[dict]:
        """Drive every bucket to completion (round-robin, one chunk per
        bucket per round; autoscale decisions between rounds). Returns
        the merged real-tenant results."""
        rounds = 0
        while self._has_work():
            self.serve_round()
            rounds += 1
            if max_rounds is not None and rounds >= max_rounds:
                break
        return self.results()

    # ------------------------------------------------------------- recover
    def recover_bucket(self, shape: BucketShape) -> "_Bucket":
        """Rebuild one bucket from its journal: the factory re-creates
        the workflow (same validation + cache warm as a fresh bucket),
        then :meth:`RunQueue.recover` replays the bucket's journal to
        the newest intact barrier. Driving the server afterwards
        completes the sweep with per-tenant results identical to the
        uncrashed run — the PR-11 law, lifted to the lattice. Raises if
        the bucket is already live (recovery is for dead processes, not
        running ones)."""
        if shape.key in self._buckets:
            raise RuntimeError(
                f"bucket {shape.key} is already live in this server — "
                "recover_bucket rebuilds dead buckets, it cannot replace "
                "a running queue"
            )
        return self._get_bucket(shape, recover=True)

    # ----------------------------------------------------------- autoscale
    def _autoscale_pass(self) -> None:
        """Grow triggered tenants into the next pop rung's bucket. The
        decision reads the guarded wrapper's on-device counters (one
        tiny per-fleet fetch); the move is the shared IPOP surgery
        (:func:`~evox_tpu.workflows.ipop.grow_guarded`) + a continuation
        submit to the target queue — pure state surgery on both sides,
        journaled on both sides."""
        if self.autoscaler is None:
            return
        for b in list(self._buckets.values()):
            q = b.queue
            if q.state is None:
                continue
            astate = q.state.tenants.algo
            if not hasattr(astate, "restarts"):
                continue
            sig = jax.device_get(
                {
                    "restarts": astate.restarts,
                    "checked": astate.checked_restarts,
                    "stagnation": astate.stagnation,
                }
            )
            for i, slot in enumerate(q.slots):
                if slot is None or not slot.active or slot.frozen:
                    continue
                spec = slot.spec
                if (spec.tag or "").startswith("_pad_"):
                    continue
                grows = getattr(spec, "_elastic_grows", 0)
                if grows >= self.autoscaler.max_grows:
                    continue
                if not self.autoscaler.triggered(
                    int(sig["restarts"][i]),
                    int(sig["checked"][i]),
                    int(sig["stagnation"][i]),
                ):
                    continue
                new_pop = self.table.next_pop_rung(b.shape.pop)
                if new_pop is None:
                    continue
                target_shape = BucketShape(
                    pop=new_pop, dim=b.shape.dim, width=b.shape.width
                )
                tb = self._get_bucket(target_shape)
                if not self._has_capacity(tb):
                    continue
                self._grow(b, i, tb, grows)

    @staticmethod
    def _has_capacity(tb: _Bucket) -> bool:
        """'When slots free up': an unstarted bucket always has room; a
        started one needs a parked (inactive, unfrozen) slot and an
        empty pending queue that would otherwise claim it."""
        q = tb.queue
        if q.state is None:
            return True
        if q.pending or q.continuations:
            return False
        return any(
            s is None or (not s.active and not s.frozen) for s in q.slots
        )

    def _grow(
        self, b: _Bucket, index: int, tb: _Bucket, grows: int
    ) -> None:
        from .checkpoint import WorkflowCheckpointer
        from .ipop import grow_guarded

        q, twf = b.queue, tb.workflow
        slot = q.slots[index]
        spec = slot.spec
        # 1) build the grown tenant at the target rung: fresh init from
        #    the tenant's deterministic growth stream, re-centered on
        #    the old best, counters carried (the IPOP surgery — pure in
        #    pop_size, so recovery re-derives the same state from the
        #    same spec + old snapshot). The source slot is closed out
        #    LAST: the WAL discipline demands the continuation be
        #    durable in the target journal BEFORE the source journal
        #    retires the tenant, or a crash between the two appends
        #    loses acknowledged work (duplicates heal — recovery dedups
        #    continuations by parked checkpoint — lost work cannot)
        old = jax.device_get(
            jax.tree.map(lambda x: x[index], q.state.tenants)
        )
        hp2 = {
            **spec.hyperparams,
            ACTIVE_ROWS: jnp.asarray(tb.shape.pop, jnp.int32),
        }
        fresh = twf.init_tenant(
            jax.random.fold_in(spec.key(), grows + 1), hp2
        )
        fresh = fresh.replace(algo=grow_guarded(fresh.algo, old.algo))
        if twf.algorithm.has_init_ask or twf.algorithm.has_init_tell:
            # algorithms with a distinct first generation peel it SOLO
            # at the target rung AFTER the re-center (the _fresh_tenant
            # admission law; ipop_run's first_step=True analog) — the
            # steady vmapped step must never ingest fitness against an
            # un-initialized archive/parent state
            fresh = twf._solo_peel(fresh)
        # monitor state may be POP-SHAPED (EvalMonitor's (K, pop)
        # history ring): it cannot cross a rung — carry the ring only
        # when its shapes are pop-independent, else keep the target
        # rung's fresh monitors and say so (losing ring continuity must
        # not kill the serve sweep)
        def _sig(t):
            return [
                (getattr(x, "shape", ()), getattr(x, "dtype", None))
                for x in jax.tree.leaves(t)
            ]

        if _sig(old.monitors) == _sig(fresh.monitors):
            mon2 = old.monitors  # ring continuity across the rung
        else:
            warnings.warn(
                f"autoscale growth {b.shape.key} -> {tb.shape.key}: "
                "monitor state is population-shaped and cannot cross "
                "the rung; the grown tenant starts a fresh ring "
                "(telemetry continuity lost for this tenant)"
            )
            mon2 = fresh.monitors
        grown = fresh.replace(
            generation=jnp.asarray(old.generation, jnp.int32),
            monitors=mon2,
        )
        # 2) durable continuation + admit in the TARGET queue. Deadlines
        #    are measured on the OWNING queue's fleet clock: carry the
        #    REMAINING slack onto the target clock, never the raw number
        #    (a fresh bucket would grant ~source_gen extra slack, an old
        #    one would mark an on-schedule run doomed on arrival);
        #    clamped to the submit-time feasibility floor (n_steps)
        deadline2 = spec.deadline
        if deadline2 is not None:
            sgen = int(q.state.generation)
            tgen = (
                int(tb.queue.state.generation)
                if tb.queue.state is not None
                else 0
            )
            deadline2 = max(tgen + (spec.deadline - sgen), spec.n_steps)
        spec2 = dataclasses.replace(
            spec,
            pop=tb.shape.pop,
            hyperparams=hp2,
            deadline=deadline2,
        )
        spec2._elastic_grows = grows + 1
        cont_dir = None
        if tb.queue.checkpoint_dir is not None:
            cont_dir = Path(tb.queue.checkpoint_dir) / (
                f"{spec.tag or 'tenant'}_grown{grows + 1}"
            )
            ckpt = WorkflowCheckpointer(
                str(cont_dir),
                every=max(int(old.generation), 1),
                keep=tb.queue.keep,
            )
            from .std import StdWorkflowState

            ckpt.save(
                StdWorkflowState(
                    generation=grown.generation,
                    algo=grown.algo,
                    prob=grown.prob,
                    monitors=grown.monitors,
                    first_step=False,
                )
            )
        tb.queue.submit_resume(
            spec2,
            checkpoint=str(cont_dir) if cont_dir is not None else None,
            state=grown,
            done=int(old.generation),
        )
        # 3) only NOW close the source slot out (forensic checkpoint +
        #    source-journal `autoscale` record + refill): the handoff is
        #    already durable on the target side
        q.counters["grown"] = q.counters.get("grown", 0) + 1
        entry = q._close_out(index, status="grown")
        if self.metrics is not None:
            self.metrics.count("elastic.grows")
            self.metrics.event(
                "elastic.grow",
                tag=spec.tag,
                from_bucket=b.shape.key,
                to_bucket=tb.shape.key,
            )
        self.autoscale_events.append(
            {
                "tag": spec.tag,
                "from": b.shape.key,
                "to": tb.shape.key,
                "generation": int(old.generation),
                "grows": grows + 1,
                "source_entry": {
                    k: entry.get(k) for k in ("status", "generations")
                },
            }
        )

    # -------------------------------------------------------------- results
    def results(self) -> List[dict]:
        """Merged per-tenant results across buckets, filler tenants
        dropped, each entry annotated with its bucket key."""
        out = []
        for key, b in self._buckets.items():
            for r in b.queue.results:
                if (r.get("tag") or "").startswith("_pad_"):
                    continue
                out.append({**r, "bucket": key})
        return out

    def report(self) -> dict:
        """The server-level serving summary: the lattice, per-bucket
        queue reports, autoscale events, and the shared cache."""
        return {
            "table": self.table.report(),
            "buckets": {
                key: b.queue.report() for key, b in self._buckets.items()
            },
            "autoscale": {
                "policy": (
                    self.autoscaler.report()
                    if self.autoscaler is not None
                    else None
                ),
                "events": list(self.autoscale_events),
            },
            "cache": self.cache.report(),
        }

"""Mesh-native distributed primitives.

Replaces the reference's pmap + Ray stack (reference: src/evox/core/
distributed.py, src/evox/workflows/distributed.py) with the modern JAX
sharding model: one global ``jax.sharding.Mesh`` whose default axis is
``"pop"``; population arrays are sharded along ``"pop"``; algorithm state is
replicated; collectives (all_gather / psum over fitness) ride ICI within a
TPU slice and DCN across slices, inserted either automatically by GSPMD from
sharding constraints or explicitly inside ``shard_map`` islands.

Multi-host: call :func:`init_distributed` (an idempotency-guarded wrapper
over ``jax.distributed.initialize``) on every process FIRST, build the
global mesh with :func:`create_pod_mesh` (pod-ordered devices: each
process's local devices contiguous along the sharded axis), assemble
eager states into global arrays with :func:`ensure_global_state` — the
same single-program step then runs SPMD across the whole pod, which is
the TPU-native equivalent of the reference's ``jax.distributed`` + NCCL
path and entirely replaces its Ray RPC path for jittable problems.
Host-side rendezvous (checkpoint commits) rides :func:`process_barrier`;
cross-process host readbacks ride :func:`host_value`. The whole layer is
exercised end to end by ``__graft_entry__.dryrun_multihost(n)``
(real coordinator + n worker processes; GUIDE.md §6 "going multi-host").
"""

from __future__ import annotations

import functools
import re
import warnings
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POP_AXIS = "pop"
# Second mesh axis for multi-tenant fleets (workflows/tenancy.py): N
# independent runs vmap-stacked on a leading tenant axis lay out on a
# (TENANT, POP) 2-D mesh — tenant-leading leaves sharded over "tenant",
# per-individual leaves over ("tenant", "pop").
TENANT_AXIS = "tenant"

__all__ = [
    "POP_AXIS",
    "TENANT_AXIS",
    "create_mesh",
    "pop_sharding",
    "replicated_sharding",
    "shard_pop",
    "place_pop",
    "replicate",
    "match_partition_rules",
    "state_sharding",
    "annotation_specs",
    "constrain_state",
    "place_state",
    "all_gather",
    "tree_all_gather",
    "ShardedES",
    "sharded_es_tell",
    "init_distributed",
    "shutdown_distributed",
    "process_id",
    "process_count",
    "is_dist_initialized",
    "BarrierTimeoutError",
    "pod_devices",
    "create_pod_mesh",
    "mesh_spans_processes",
    "process_barrier",
    "assemble_global_array",
    "host_value",
    "tree_host_value",
    "ensure_global_state",
]


def create_mesh(
    axis_names: Sequence[str] = (POP_AXIS,),
    devices: Optional[Sequence[jax.Device]] = None,
    shape: Optional[Sequence[int]] = None,
) -> Mesh:
    """Build a device mesh. Default: 1-D mesh named ``"pop"`` over all devices."""
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    return Mesh(devices.reshape(shape), axis_names)


def pop_sharding(mesh: Mesh, axis_name: str = POP_AXIS) -> NamedSharding:
    """Sharding that splits the leading (population) axis across the mesh."""
    return NamedSharding(mesh, P(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated sharding over the mesh."""
    return NamedSharding(mesh, P())


def _constrain(tree: Any, sharding: NamedSharding) -> Any:
    return jax.tree.map(lambda x: jax.lax.with_sharding_constraint(x, sharding), tree)


def shard_pop(tree: Any, mesh: Optional[Mesh], axis_name: str = POP_AXIS) -> Any:
    """Constrain every leaf's leading axis to be sharded over ``axis_name``.

    No-op when ``mesh`` is None (single-device path compiles identically).
    Every leaf's leading axis is taken for the population's: a leaf without
    one is the caller's to keep out (a scalar is refused here).
    """
    if mesh is None:
        return tree
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jnp.ndim(leaf) == 0:
            raise ValueError(
                f"shard_pop: leaf {jax.tree_util.keystr(path) or '(root)'} is a scalar and "
                f"has no population axis to shard over {axis_name!r}"
            )
    return _constrain(tree, pop_sharding(mesh, axis_name))


def replicate(tree: Any, mesh: Optional[Mesh]) -> Any:
    """Constrain every leaf to be replicated over the mesh (no-op sans mesh)."""
    if mesh is None:
        return tree
    return _constrain(tree, replicated_sharding(mesh))


def _spec_for_path(state: Any, path: tuple, default: "P") -> "P":
    """Resolve the deepest ``field(sharding=...)`` annotation along a pytree
    key path (inner annotations override outer ones)."""
    import dataclasses

    obj, spec = state, default
    for key in path:
        if isinstance(key, jax.tree_util.GetAttrKey) and dataclasses.is_dataclass(obj):
            f = obj.__dataclass_fields__.get(key.name)
            if f is not None and f.metadata.get("sharding") is not None:
                spec = f.metadata["sharding"]
            obj = getattr(obj, key.name)
        elif isinstance(key, jax.tree_util.SequenceKey):
            obj = obj[key.idx]
        elif isinstance(key, jax.tree_util.DictKey):
            obj = obj[key.key]
        else:
            break
    return spec


def match_partition_rules(
    rules: Sequence[Tuple[str, "P"]],
    tree: Any,
    default: Optional["P"] = None,
    strict: bool = False,
) -> Any:
    """A pytree of ``PartitionSpec`` assigned by REGEX RULES over leaf key
    paths — the rule-driven alternative to per-field annotations (the
    ``match_partition_rules`` pattern of LLM sharding stacks, SNIPPETS.md
    [2]), for states whose layout the annotations don't (or shouldn't)
    describe: tenant-stacked fleets, externally defined pytrees, one-off
    layout experiments.

    ``rules``: ``[(pattern, spec), ...]`` tried in order against each
    leaf's ``jax.tree_util.keystr`` path (``re.search`` semantics, so
    ``r"\\.population$"`` anchors a suffix and ``r"algo"`` matches
    anywhere); the FIRST match wins. Scalar (0-d) leaves always resolve
    to ``P()`` — there is nothing to partition. Unmatched leaves get
    ``default`` (``None`` keeps them unconstrained / GSPMD-propagated);
    ``strict=True`` raises on an unmatched leaf instead, the
    exhaustiveness check of the exemplar.

    Returns a pytree of ``PartitionSpec``/``None`` matching ``tree`` —
    feed it to :func:`constrain_state` (``rules=`` takes the raw rule
    list directly), ``jax.device_put`` via ``NamedSharding``, or jit's
    ``in_shardings``."""
    resolve = _rule_resolver(rules)

    def assign(path, leaf):
        if getattr(leaf, "ndim", None) == 0:
            return P()
        spec = resolve(path, leaf)
        if spec is not None:
            return spec
        if strict:
            raise ValueError(
                "no partition rule matched leaf "
                f"{jax.tree_util.keystr(path)!r}"
            )
        return default

    return jax.tree_util.tree_map_with_path(assign, tree)


def _rule_resolver(rules: Optional[Sequence[Tuple[str, "P"]]]):
    """Compile ``rules`` into ``path -> spec | None`` (None = no match)."""
    if not rules:
        return lambda path, leaf: None
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def resolve(path, leaf):
        if getattr(leaf, "ndim", None) == 0:
            return P()
        name = jax.tree_util.keystr(path)
        for pat, spec in compiled:
            if pat.search(name) is not None:
                return spec
        return None

    return resolve


def _prefix_spec(spec: "P", leaf: Any, axis_prefix: Optional[str]) -> "P":
    """Shift ``spec`` one axis right under ``axis_prefix`` (the stacked-
    state law: ``P("pop")`` -> ``P(prefix, "pop")``, ``P()`` ->
    ``P(prefix)``); leaves too narrow for the inner spec fall back to
    prefix-only (or fully replicated for scalars)."""
    if axis_prefix is None or axis_prefix in spec:
        return spec
    if getattr(leaf, "ndim", 0) < 1 + len(spec):
        return P(axis_prefix) if getattr(leaf, "ndim", 0) >= 1 else P()
    return P(axis_prefix, *spec)


def state_sharding(
    state: Any,
    mesh: Mesh,
    default: Optional["P"] = None,
    rules: Optional[Sequence[Tuple[str, "P"]]] = None,
    axis_prefix: Optional[str] = None,
) -> Any:
    """A pytree of ``NamedSharding`` matching ``state``, driven by the
    ``field(sharding=...)`` annotations on its dataclasses (unannotated
    fields get ``default``, replicated unless overridden).

    This is the consumer the reference's sharding metadata never had
    (reference state.py:304-334 ``get_state_sharding`` exists but
    StdWorkflow ignores it): feed the result to ``jax.device_put``,
    ``with_sharding_constraint`` or jit's ``in_shardings``.

    ``rules`` / ``axis_prefix``: same semantics as
    :func:`constrain_state` — regex rules override annotations per leaf
    path, and every resolved spec is shifted under ``axis_prefix``
    (tenant-stacked fleet states, :mod:`evox_tpu.workflows.tenancy`).
    """
    default = P() if default is None else default
    rule_spec = _rule_resolver(rules)

    def resolve(path, leaf):
        spec = rule_spec(path, leaf)
        if spec is None:
            spec = _spec_for_path(state, path, default)
        return NamedSharding(mesh, _prefix_spec(spec, leaf, axis_prefix))

    return jax.tree_util.tree_map_with_path(resolve, state)


def constrain_state(
    state: Any,
    mesh: Optional[Mesh],
    policy: Any = None,
    rules: Optional[Sequence[Tuple[str, "P"]]] = None,
    axis_prefix: Optional[str] = None,
) -> Any:
    """Tracing-time: constrain ANNOTATED leaves to their declared sharding.

    Unannotated leaves are left to GSPMD's propagation (constraining them
    to replicated would pessimize algorithms whose working arrays are
    naturally population-sharded).

    ``policy``: an optional :class:`~evox_tpu.core.dtype_policy.
    DtypePolicy`. When active, ``field(storage=True)``-annotated float
    leaves are additionally cast to the policy's *storage* dtype in the
    same tree walk — this is the workflow's end-of-step boundary, so the
    loop-carried state leaves HBM at half width while every in-step
    reduction already ran in the compute dtype (see core/dtype_policy.py).
    ``policy=None`` (or a no-op policy) changes nothing, and a policy
    applies even without a mesh (single-device bf16 storage is the same
    bytes win).

    ``rules``: optional ``[(regex, PartitionSpec), ...]`` matched against
    leaf key paths BEFORE the field annotations (first match wins; see
    :func:`match_partition_rules`) — the escape hatch for layouts the
    annotations don't describe.

    ``axis_prefix``: prepend a mesh axis to every resolved spec —
    ``P(POP_AXIS)`` becomes ``P(axis_prefix, POP_AXIS)`` and ``P()``
    becomes ``P(axis_prefix)``. This is how a TENANT-stacked state (every
    leaf grew a leading tenant axis, :mod:`evox_tpu.workflows.tenancy`)
    reuses the per-field annotations unchanged on a (TENANT, POP) 2-D
    mesh: the stacking axis shards over ``axis_prefix`` while each
    field's own layout shifts one axis right — no per-state annotation
    churn. Ignored for specs already naming the prefix axis."""
    from .dtype_policy import _castable, _storage_flag_for_path

    active = policy is not None and not policy.is_noop
    if mesh is None and not active:
        return state
    rule_spec = _rule_resolver(rules)

    def constrain(path, x):
        if active and _castable(x) and _storage_flag_for_path(state, path):
            x = jax.lax.convert_element_type(x, policy.storage)
        if mesh is None:
            return x
        spec = rule_spec(path, x)
        if spec is None:
            spec = _spec_for_path(state, path, None)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, _prefix_spec(spec, x, axis_prefix))
        )

    return jax.tree_util.tree_map_with_path(constrain, state)


def place_state(
    state: Any,
    mesh: Optional[Mesh],
    rules: Optional[Sequence[Tuple[str, "P"]]] = None,
    axis_prefix: Optional[str] = None,
) -> Any:
    """Eager: ``device_put`` every leaf onto its annotated sharding
    (``rules``/``axis_prefix`` as :func:`state_sharding` — the restore
    path for tenant-stacked fleet snapshots). On a mesh spanning
    processes this routes through :func:`ensure_global_state` — each
    process assembles only its addressable shards from the full host
    value (the process-count-portable checkpoint-restore path)."""
    if mesh is None:
        return state
    if mesh_spans_processes(mesh):
        return ensure_global_state(
            state, mesh, rules=rules, axis_prefix=axis_prefix
        )
    shardings = state_sharding(
        state, mesh, rules=rules, axis_prefix=axis_prefix
    )
    return jax.tree.map(jax.device_put, state, shardings)


def annotation_specs(state: Any, default: "P" = P()) -> Any:
    """A pytree of ``PartitionSpec`` matching ``state``, resolved purely
    from the per-field ``field(sharding=...)`` annotations (the mesh-free
    sibling of :func:`state_sharding`) — e.g. the ``in_specs`` of a
    ``shard_map`` island over an annotated state (:class:`ShardedES`)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for_path(state, path, default), state
    )


def place_pop(tree: Any, mesh: Optional[Mesh], axis_name: str = POP_AXIS) -> Any:
    """EAGER placement: ``device_put`` every leaf with its leading axis
    sharded over ``axis_name``. Use when loading host data or a restored
    checkpoint into a mesh layout (``shard_pop`` is the tracing-time
    constraint form and only works inside jit). Pod meshes assemble the
    per-process shards (:func:`assemble_global_array`)."""
    if mesh is None:
        return tree
    s = pop_sharding(mesh, axis_name)
    if mesh_spans_processes(mesh):
        return jax.tree.map(lambda x: assemble_global_array(x, s), tree)
    return jax.tree.map(lambda x: jax.device_put(x, s), tree)


def all_gather(x: jax.Array, axis_name: str = POP_AXIS, tiled: bool = True) -> jax.Array:
    """``lax.all_gather`` for use *inside* shard_map islands."""
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=tiled)


def tree_all_gather(tree: Any, axis_name: str = POP_AXIS, tiled: bool = True) -> Any:
    return jax.tree.map(lambda x: all_gather(x, axis_name, tiled), tree)


# --------------------------------------------------------------------------
# Gather-free POP-sharded large-population ES (PR 10, ROADMAP item 4).
#
# "Massively parallel CMA-ES with increasing population" (PAPERS.md) shows
# the CMA family keeps improving at pop ~ 1e4..1e6 on parallel hardware —
# but a naive mesh run still materializes the full (pop, dim) sample matrix
# on every device: jax.random's default threefry is non-partitionable (each
# device generates the FULL matrix and slices its shard), and the
# sort-select-recombine tell gathers the population to apply `z[order][:mu]`.
# The two pieces below close both holes for the low-memory CMA track
# (SepCMAES / LMMAES / RMES — diagonal / low-rank covariance):
#
# - sampling: each device draws only its own (pop/n_dev, dim) block from a
#   fold_in-derived per-shard stream inside a shard_map island
#   (`ShardedES.ask`);
# - recombination: "sort, select mu, dot with weights" is reformulated as
#   "weight every candidate by its global fitness RANK and sum" — ranks are
#   fitness-sized (pop floats, cheap to replicate), the weighted sums are
#   (dim,)-sized moments accumulated per shard and `psum`-reduced
#   (`sharded_es_tell`), and the weight table lookup is bitwise-identical
#   to the sorted-selection weights, so sharded == replicated up to
#   summation order (documented tolerance, tests/test_state_contracts.py).
#
# Per-device peak memory therefore scales as pop/n_dev, verified by AOT
# `memory_analysis()` + compiled-HLO inspection (tests/test_large_pop.py).


def _require_shard_protocol(algorithm: Any) -> None:
    missing = [
        name
        for name in ("ask_rows", "rank_weights", "pop_moments", "tell_with_moments")
        if not callable(getattr(algorithm, name, None))
    ]
    if missing or not getattr(algorithm, "pop_shard_capable", False):
        raise TypeError(
            f"{type(algorithm).__name__} does not implement the POP-sharded "
            "low-memory ES protocol (pop_shard_capable + ask_rows/"
            "rank_weights/pop_moments/tell_with_moments); capable "
            "algorithms: the low-memory CMA track (SepCMAES, LMMAES, RMES)"
            + (f"; missing: {missing}" if missing else "")
        )


def sharded_es_tell(
    algorithm: Any,
    state: Any,
    fitness: jax.Array,
    mesh: Mesh,
    axis_name: str = POP_AXIS,
) -> Any:
    """One gather-free ``tell`` over a POP-sharded sample matrix.

    Global fitness ranks are computed in the surrounding (GSPMD) program —
    fitness is ``(pop,)``-sized, cheap to gather/replicate — then a
    ``shard_map`` island turns each device's ``(pop/n_dev, dim)`` artifact
    shard into weighted partial moments and ``psum``s them; the small
    replicated strategy-state update (``tell_with_moments``) runs on the
    reduced ``(dim,)``/``(k, dim)`` moments. No collective ever moves a
    ``(pop, dim)`` operand. Works unchanged on a (TENANT, POP) 2-D mesh
    (PR 7): specs name only the ``pop`` axis, so tenant rows replicate."""
    if fitness.ndim != 1:
        raise ValueError(
            f"sharded_es_tell is single-objective; got fitness {fitness.shape}"
        )
    fields = tuple(algorithm.sharded_pop_fields)
    rows = {name: getattr(state, name) for name in fields}
    # global 0-based ranks as the scatter-inverse of ONE stable argsort
    # (identical to the classic double argsort — ties break by index,
    # exactly like the replicated z[argsort(fitness)][:mu] selection —
    # but one pop-sized sort cheaper)
    order = jnp.argsort(fitness)
    ranks = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype)
    )

    def island(rows_local, ranks_local):
        w_local = algorithm.rank_weights(ranks_local)
        return jax.lax.psum(
            algorithm.pop_moments(rows_local, w_local), axis_name
        )

    moments = jax.shard_map(
        island,
        mesh=mesh,
        in_specs=(
            {name: P(axis_name) for name in fields},
            P(axis_name),
        ),
        out_specs=P(),
        check_vma=False,
    )(rows, ranks)
    # reuse the rank sort for the top-mu SORTED fitness (fitness-sized
    # gather, no second pop-sized sort): RMES's PSR consumes it via the
    # same `f_sel` key the replicated tell threads; algorithms that don't
    # read it cost nothing (XLA dead-code-eliminates the gather)
    moments = dict(moments, f_sel=fitness[order][: algorithm.mu])
    return algorithm.tell_with_moments(state, moments, fitness)


class ShardedES:
    """Wrap a low-memory ES (SepCMAES / LMMAES / RMES) so every
    per-candidate array stays POP-sharded: per-shard sampling in ``ask``,
    psum-of-moments recombination in ``tell`` (:func:`sharded_es_tell`).

    Drop-in :class:`~evox_tpu.core.algorithm.Algorithm`: state type, field
    annotations and hyperparameter attributes are the wrapped algorithm's
    (attribute reads forward), so it composes with ``StdWorkflow`` (pass
    the same ``mesh``), :class:`~evox_tpu.core.guardrail.GuardedAlgorithm`
    (wrap OUTSIDE: ``GuardedAlgorithm(ShardedES(algo, mesh))``),
    ``DtypePolicy`` bf16 storage, donated fused runs, the
    ``GenerationExecutor``, and IPOP handoff
    (``IPOPRestarts(handoff_factory=...)``).

    Sampling law: ``ask`` splits the state key once, then shard ``s`` draws
    its block from ``fold_in(k, s)`` — on the mesh each device computes
    only its own block inside a ``shard_map`` island (jax's default
    threefry is NOT partitionable, so constraining a plain
    ``jax.random.normal`` would still materialize the full matrix per
    device). ``mesh=None`` with ``n_shards=N`` runs the SAME law
    replicated (concatenated blocks) — the reference the sharded path is
    tested against (bitwise-equal samples, psum-order-only differences).
    ``mesh=None, n_shards=1`` is the wrapped algorithm's legacy stream,
    bit-identical to the bare algorithm.

    Args:
        algorithm: a ``pop_shard_capable`` algorithm (the low-memory CMA
            track). Population size must divide ``n_shards``.
        mesh: mesh with a ``axis_name`` axis — 1-D ``(POP,)`` or the
            (TENANT, POP) 2-D mesh of workflows/tenancy.py (tenant rows
            replicate the strategy state; specs name only the pop axis).
        axis_name: mesh axis to shard the population over.
        n_shards: sampling-law shard count; defaults to the mesh's
            ``axis_name`` size (or 1 without a mesh). Pass explicitly on
            ``mesh=None`` to build the replicated reference of an n-device
            sharded run. May be any positive MULTIPLE of the mesh's
            ``axis_name`` size: each device then draws
            ``n_shards / n_dev`` consecutive sample blocks from its
            global block indices — the SAME sampling law on fewer
            devices, which is what makes a pod run topology-portable
            (an 8-shard trajectory killed mid-flight resumes on a
            4-device survivor mesh with ``n_shards=8`` and reproduces
            the uninjured law up to psum order; the pod-supervisor
            shrink-and-resume path, ISSUE 14).
    """

    is_pop_sharded = False  # overridden per instance when a mesh is given

    def __init__(
        self,
        algorithm: Any,
        mesh: Optional[Mesh] = None,
        axis_name: str = POP_AXIS,
        n_shards: Optional[int] = None,
    ):
        _require_shard_protocol(algorithm)
        if getattr(algorithm, "has_init_ask", False) or getattr(
            algorithm, "has_init_tell", False
        ):
            raise TypeError(
                "ShardedES supports steady-state ask/tell algorithms only "
                f"({type(algorithm).__name__} declares init_ask/init_tell)"
            )
        self.algorithm = algorithm
        self.mesh = mesh
        self.axis_name = axis_name
        if n_shards is None:
            n_shards = int(mesh.shape[axis_name]) if mesh is not None else 1
        self.n_shards = int(n_shards)
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if mesh is not None and self.n_shards % int(mesh.shape[axis_name]):
            raise ValueError(
                f"n_shards={self.n_shards} is not a multiple of the mesh's "
                f"'{axis_name}' axis ({int(mesh.shape[axis_name])}); the "
                "per-shard sampling law needs whole blocks per device"
            )
        pop = int(algorithm.pop_size)
        if pop % self.n_shards != 0:
            raise ValueError(
                f"pop_size {pop} is not divisible by n_shards={self.n_shards}"
            )
        self.is_pop_sharded = mesh is not None

    def __getattr__(self, name: str) -> Any:
        # only reached when normal lookup fails: forward hyperparameter
        # reads (pop_size, dim, mu, weights, ...) to the wrapped algorithm
        if name.startswith("__") or name == "algorithm":
            raise AttributeError(name)
        return getattr(object.__getattribute__(self, "algorithm"), name)

    # the steady-state-only contract asserted in __init__
    @property
    def has_init_ask(self) -> bool:
        return False

    @property
    def has_init_tell(self) -> bool:
        return False

    def _rename_axis(self, spec: "P") -> "P":
        """Field annotations name the canonical ``POP_AXIS``; substitute
        this wrapper's ``axis_name`` when the mesh calls it differently."""
        if self.axis_name == POP_AXIS:
            return spec
        return P(*(self.axis_name if ax == POP_AXIS else ax for ax in spec))

    def _state_shardings(self, state: Any) -> Any:
        """Per-leaf ``NamedSharding`` from the field annotations, with the
        pop axis renamed to ``axis_name`` (the placement twin of
        :meth:`_state_specs`)."""
        return jax.tree_util.tree_map(
            lambda sp: NamedSharding(self.mesh, sp),
            self._state_specs(state),
            is_leaf=lambda x: isinstance(x, P),
        )

    # ------------------------------------------------------------------ api
    def init(self, key: jax.Array) -> Any:
        if self.mesh is None:
            return self.algorithm.init(key)
        if isinstance(key, jax.core.Tracer):
            # inside a trace (e.g. GuardedAlgorithm's on-device restart):
            # constrain instead of placing — GSPMD lays the fresh state out
            state = self.algorithm.init(key)
            return jax.tree.map(
                jax.lax.with_sharding_constraint,
                state,
                self._state_shardings(state),
            )
        # eager: compile init with its OUTPUT shardings pinned to the field
        # annotations, so the (pop, dim) buffers are born sharded — never
        # materialized on one device and re-placed. On a pod mesh the key
        # operand must itself be a GLOBAL (replicated) array first — a
        # process-local committed array is not a legal global-jit operand
        if mesh_spans_processes(self.mesh):
            rep = NamedSharding(self.mesh, P())
            if _is_typed_key(key):
                key = jax.random.wrap_key_data(
                    assemble_global_array(
                        np.asarray(jax.device_get(jax.random.key_data(key))),
                        rep,
                    ),
                    impl=jax.random.key_impl(key),
                )
            else:
                key = assemble_global_array(
                    np.asarray(jax.device_get(key)), rep
                )
        sds = jax.eval_shape(self.algorithm.init, key)
        shardings = self._state_shardings(sds)
        return jax.jit(self.algorithm.init, out_shardings=shardings)(key)

    def ask(self, state: Any) -> Tuple[Any, Any]:
        if self.mesh is None and self.n_shards == 1:
            return self.algorithm.ask(state)  # legacy stream, bare-identical
        key, k = jax.random.split(state.key)
        shard = int(self.algorithm.pop_size) // self.n_shards
        fields = tuple(self.algorithm.sharded_pop_fields)
        if self.mesh is None:
            # replicated reference of the per-shard sampling law
            pops, arts = [], []
            for s in range(self.n_shards):
                p, a = self.algorithm.ask_rows(
                    state, jax.random.fold_in(k, s), shard
                )
                pops.append(p)
                arts.append(a)
            pop = jnp.concatenate(pops)
            art = {
                name: jnp.concatenate([a[name] for a in arts])
                for name in fields
            }
        else:
            axis = self.axis_name
            # n_shards may exceed the device count (shrunken survivor
            # mesh resuming a wider run's sampling law): device d owns
            # the consecutive global blocks [d*bpd, (d+1)*bpd) and
            # concatenates them — identical draws to the wider mesh,
            # just fewer devices holding more blocks each
            bpd = self.n_shards // int(self.mesh.shape[axis])

            def island(st, k_op):
                d = jax.lax.axis_index(axis)
                if bpd == 1:
                    return self.algorithm.ask_rows(
                        st, jax.random.fold_in(k_op, d), shard
                    )
                pops_b, arts_b = [], []
                for b in range(bpd):
                    p_b, a_b = self.algorithm.ask_rows(
                        st, jax.random.fold_in(k_op, d * bpd + b), shard
                    )
                    pops_b.append(p_b)
                    arts_b.append(a_b)
                return (
                    jnp.concatenate(pops_b),
                    {
                        name: jnp.concatenate([a[name] for a in arts_b])
                        for name in fields
                    },
                )

            pop, art = jax.shard_map(
                island,
                mesh=self.mesh,
                # the state rides in under its own field annotations (the
                # (pop, dim) artifact enters as a local shard, unused by
                # ask_rows; the small strategy fields replicate), with the
                # annotations' POP_AXIS renamed to this wrapper's axis
                in_specs=(self._state_specs(state), P()),
                out_specs=(P(axis), {name: P(axis) for name in fields}),
                check_vma=False,
            )(state, k)
        return pop, state.replace(key=key, **art)

    def _state_specs(self, state: Any) -> Any:
        """Per-leaf shard_map specs from the field annotations
        (:func:`annotation_specs`), with ``POP_AXIS`` substituted by this
        wrapper's ``axis_name`` (the annotations name the canonical axis;
        the mesh may not)."""
        return jax.tree_util.tree_map(
            self._rename_axis,
            annotation_specs(state),
            is_leaf=lambda x: isinstance(x, P),
        )

    def tell(self, state: Any, fitness: jax.Array) -> Any:
        if self.mesh is None:
            return self.algorithm.tell(state, fitness)
        return sharded_es_tell(
            self.algorithm, state, fitness, self.mesh, self.axis_name
        )


# --------------------------------------------------------------------------
# Multi-process (pod-style) execution (PR 13, ROADMAP item 3).
#
# jax's multi-controller model: every process runs the SAME program over a
# mesh built from the GLOBAL device list (`jax.devices()` spans processes
# once `jax.distributed` is initialized); each process physically owns only
# its local devices, GSPMD inserts the cross-host collectives. Three host-
# side obligations fall out, owned by the helpers below:
#
# - mesh construction must put each process's local devices in a CONTIGUOUS
#   block of the sharded axis (`create_pod_mesh` sorts by (process_index,
#   id)), so a per-process data shard is a contiguous slice;
# - eager values (fresh inits, restored checkpoints) must become GLOBAL
#   arrays before a global-mesh jit may consume them — each process builds
#   its addressable shards from the full host value with
#   ``jax.make_array_from_single_device_arrays`` (`assemble_global_array` /
#   `ensure_global_state`); a plain ``device_put`` onto a cross-process
#   sharding is not legal;
# - host readbacks of a cross-process-sharded array must all-gather first
#   (`host_value`: a jitted identity with replicated out_shardings), and
#   host-side rendezvous (checkpoint commit) goes through the coordinator's
#   KV store (`process_barrier`) — no XLA collective, so it works even
#   where the backend cannot run one.
#
# `constrain_state` itself is already collective-aware: it is a TRACE-time
# constraint, and on a pod mesh GSPMD lowers the declared layouts to
# ICI/DCN collectives exactly as on a single host. The eager twin
# `place_state` routes through the assembly path on pod meshes.

# what THIS process passed to init_distributed (guards a second call even
# on jax builds whose global_state exposes nothing)
_INIT_RECORD: Optional[dict] = None


#: sentinel: the jax build exposes no distributed introspection at all
#: (distinct from "introspection works and there is no client")
_INTROSPECT_FAILED = object()


def _dist_client():
    """The live distributed-runtime client, None when introspection works
    and none is active, or :data:`_INTROSPECT_FAILED` on jax builds
    without `jax._src.distributed.global_state` (the only introspection
    point jax exposes)."""
    try:
        from jax._src import distributed as _jd

        return _jd.global_state.client
    except Exception:  # pragma: no cover - exotic jax builds
        return _INTROSPECT_FAILED


def _dist_process_info() -> Tuple[int, int]:
    """(process_id, num_processes) of the ACTIVE jax.distributed runtime
    WITHOUT touching the backend: ``jax.process_count()`` initializes
    the backend, and a multiprocess CPU backend init BLOCKS until every
    peer initializes too — so a barrier called before the backend is up
    (the pod supervisor's join/warmup rendezvous, a coordination-only
    worker) would wedge exactly where it must not. Falls back to the
    backend-derived counts only when the runtime exposes nothing."""
    try:
        from jax._src import distributed as _jd

        gs = _jd.global_state
        pid, n = gs.process_id, gs.num_processes
        if pid is not None and n is not None:
            return int(pid), int(n)
    except Exception:  # pragma: no cover - exotic jax builds
        pass
    return int(jax.process_index()), int(jax.process_count())


def _current_dist_config() -> dict:
    """Best-effort record of the ACTIVE jax.distributed configuration."""
    cfg: dict = dict(_INIT_RECORD or {})
    try:
        from jax._src import distributed as _jd

        gs = _jd.global_state
        for ours, theirs in (
            ("coordinator_address", "coordinator_address"),
            ("num_processes", "num_processes"),
            ("process_id", "process_id"),
        ):
            val = getattr(gs, theirs, None)
            if val is not None:
                cfg[ours] = val
    except Exception:  # pragma: no cover
        pass
    return cfg


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    **kwargs: Any,
) -> None:
    """Initialize multi-host JAX (call once per process, BEFORE any jax
    backend use, then build meshes over ``jax.devices()``).

    On TPU pods the arguments are auto-detected from the environment, so a
    bare ``init_distributed()`` suffices.

    Idempotency: ``jax.distributed.initialize`` raises an opaque jaxlib
    error on a second call ("must be called before any JAX computations"
    — true but useless when the real cause is double-init). This wrapper
    makes the second call explicit: a re-call whose arguments agree with
    the active configuration (or constrain nothing) is a WARNED NO-OP —
    the idempotent shape library/driver layers need — while a re-call
    naming a DIFFERENT coordinator/process layout raises a
    ``RuntimeError`` that says exactly which argument conflicts
    (tests/test_multihost.py regression-tests both through the
    ``dryrun_multihost`` harness)."""
    global _INIT_RECORD
    requested = {
        "coordinator_address": coordinator_address,
        "num_processes": num_processes,
        "process_id": process_id,
        **kwargs,
    }
    if is_dist_initialized():
        current = _current_dist_config()
        conflicts = {
            name: (req, current[name])
            for name, req in requested.items()
            if req is not None
            and current.get(name) is not None
            and req != current[name]
        }
        if conflicts:
            detail = ", ".join(
                f"{k}: requested {req!r} != active {cur!r}"
                for k, (req, cur) in sorted(conflicts.items())
            )
            raise RuntimeError(
                "init_distributed: jax.distributed is already initialized "
                f"with a CONFLICTING configuration ({detail}). One process "
                "belongs to one coordinator for its lifetime — restart the "
                "process to join a different one."
            )
        # arguments whose active value is unknowable (the first init ran
        # outside this wrapper, or jax's global_state doesn't expose the
        # field) cannot be verified as matching — say so instead of
        # claiming a match that was never checked
        unverified = sorted(
            name for name, req in requested.items()
            if req is not None and current.get(name) is None
        )
        note = (
            f" (arguments not verifiable against the active config and "
            f"IGNORED: {unverified})" if unverified else ""
        )
        warnings.warn(
            "init_distributed: jax.distributed is already initialized "
            f"(coordinator {current.get('coordinator_address')!r}, "
            f"{current.get('num_processes')} process(es)); this matching "
            f"call is a no-op{note}",
            stacklevel=2,
        )
        return
    # cache hardening (ISSUE 14 satellite): any jitted-replicate closure
    # cached for a PREVIOUS topology (a pod this process left via
    # shutdown_distributed, or a pre-distributed backend) must never run
    # on the re-formed pod — it was compiled for the dead device set
    _replicate_program.cache_clear()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _INIT_RECORD = {k: v for k, v in requested.items() if v is not None}


def shutdown_distributed() -> None:
    """Tear down this process's ``jax.distributed`` membership (no-op
    when none is active) and invalidate every cross-process-compiled
    host-readback program.

    The ``host_value``/``tree_host_value`` replicate closures are cached
    per ``NamedSharding`` (:func:`_replicate_program`); a pod that
    re-forms after a failure builds a NEW mesh, but a sharding that
    hashes equal to a dead pod's (same spec, revived device objects on
    exotic backends) would silently reuse a program compiled for the
    dead topology and wedge the first readback of the healed run. The
    cache is therefore dropped on BOTH edges — here at shutdown and in
    :func:`init_distributed`'s real-init path — so a re-formed pod
    always compiles its gathers against the live topology
    (regression-tested via the re-init guard path, tests/
    test_pod_supervisor.py::
    test_replicate_cache_invalidated_on_shutdown_and_reinit)."""
    global _INIT_RECORD
    _replicate_program.cache_clear()
    _INIT_RECORD = None
    client = _dist_client()
    if client is not _INTROSPECT_FAILED and client is None:
        return
    try:
        jax.distributed.shutdown()
    except Exception as e:  # pragma: no cover - backend-dependent teardown
        warnings.warn(
            f"shutdown_distributed: jax.distributed.shutdown raised "
            f"{type(e).__name__}: {e} (caches were still invalidated)",
            stacklevel=2,
        )


def process_id() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_dist_initialized() -> bool:
    """True iff ``jax.distributed`` has been initialized in THIS process.

    Reads the distributed-runtime CLIENT, not ``jax.process_count() > 1``:
    a 1-process ``jax.distributed`` run (a pod job launched at n=1, a
    coordinator smoke test) is initialized but has one process, and the
    old count-based predicate misread it as uninitialized
    (ISSUE 13 satellite; regression-tested via the 1-process leg of the
    ``dryrun_multihost`` harness). The count check survives only as a
    last-ditch fallback for jax builds whose ``global_state`` is
    unreadable — a multi-process device list cannot exist without an
    initialized runtime. Never touches an UNinitialized backend: probing
    ``jax.process_count()`` would initialize it, which is precisely what
    callers checking "may I still init_distributed?" must not do.

    The live client is authoritative whenever introspection works: after
    ``jax.distributed.shutdown()`` the client is gone and this reads
    False again (so a re-``init_distributed`` actually re-initializes —
    the wrapper's own ``_INIT_RECORD`` must never shadow a shutdown)."""
    client = _dist_client()
    if client is not _INTROSPECT_FAILED:
        return client is not None
    # introspection unavailable: fall back to what THIS wrapper did,
    # then to the (backend-safe) process count
    if _INIT_RECORD is not None:  # pragma: no cover - exotic jax builds
        return True
    try:  # pragma: no cover - exotic jax builds
        from jax._src import xla_bridge as _xb

        backend_up = bool(getattr(_xb, "_backends", None))
    except Exception:
        backend_up = True
    return backend_up and jax.process_count() > 1  # pragma: no cover


def pod_devices() -> list:
    """The global device list in POD ORDER: sorted by ``(process_index,
    id)`` so each process's local devices form one contiguous block —
    the device order `create_pod_mesh` lays axes over."""
    return sorted(jax.devices(), key=lambda d: (d.process_index, d.id))


def create_pod_mesh(
    axis_names: Sequence[str] = (POP_AXIS,),
    shape: Optional[Sequence[int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a global mesh spanning every process's devices.

    The multi-host twin of :func:`create_mesh`: devices come from
    ``jax.devices()`` (the GLOBAL list once ``init_distributed`` ran on
    every process) sorted into pod order, so with the default C-order
    reshape each process's local devices occupy a contiguous block of the
    LEADING axis — a ``P("pop")``-sharded array then stores each
    process's population slice on that process, and the (TENANT, POP)
    2-D fleet mesh (``axis_names=(TENANT_AXIS, POP_AXIS), shape=(t,
    p)``) keeps whole tenant rows process-local whenever ``t`` is a
    multiple of the process count. Single-process it degenerates to
    exactly :func:`create_mesh`. Validates that every process
    contributes the same device count (jax requires symmetric
    processes) and that the mesh consumes the whole pod."""
    if devices is None:
        devices = pod_devices()
    devices = list(devices)
    n = len(devices)
    counts = {}
    for d in devices:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    if len(set(counts.values())) > 1:
        raise ValueError(
            "create_pod_mesh: processes contribute unequal device counts "
            f"({counts}); a pod mesh needs symmetric processes"
        )
    if shape is None:
        shape = (n,) + (1,) * (len(axis_names) - 1)
    total = 1
    for s in shape:
        total *= int(s)
    if total != n:
        raise ValueError(
            f"create_pod_mesh: shape {tuple(shape)} does not consume the "
            f"{n} pod devices"
        )
    return Mesh(np.asarray(devices, dtype=object).reshape(shape), axis_names)


def mesh_spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when ``mesh`` places devices of more than one process — the
    gate for the eager global-assembly paths below (a single-process mesh
    keeps the plain ``device_put`` fast path)."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


_BARRIER_SEQ = [0]

#: KV prefix under which every process records its barrier arrival — the
#: census the timeout path reads to NAME the processes that never came
_BARRIER_KV_PREFIX = "evox_tpu/barrier_arrival"


class BarrierTimeoutError(RuntimeError):
    """A :func:`process_barrier` deadline expired with peers missing —
    the cross-process twin of the dispatch-deadline error (ISSUE 14
    satellite: a barrier with a dead peer must raise a CLASSIFIED
    deadline naming the processes that never arrived, not block forever
    or die with an opaque coordination-service string).
    ``classify_error`` folds it into the ``deadline`` class; the pod
    supervisor refines it into worker-dead / hung-collective via the
    heartbeat census. ``arrived``/``missing`` are sorted process-id
    lists reconstructed from the barrier's KV arrival records."""

    def __init__(
        self,
        name: str,
        timeout_s: float,
        arrived: Sequence[int],
        missing: Sequence[int],
        cause: str = "",
    ):
        self.barrier_name = name
        self.timeout_s = timeout_s
        self.arrived = sorted(int(p) for p in arrived)
        self.missing = sorted(int(p) for p in missing)
        detail = f" [{cause}]" if cause else ""
        super().__init__(
            f"process_barrier '{name}' timed out after {timeout_s:g} s: "
            f"processes {self.missing or '<unknown>'} never arrived "
            f"(arrived: {self.arrived}){detail}"
        )


def process_barrier(name: Optional[str] = None, timeout_s: float = 120.0) -> None:
    """Block until every process reached this barrier.

    Rides the coordinator's KV store (``wait_at_barrier``), NOT an XLA
    collective — so it works during startup, between dispatches, and on
    backends that cannot run a cross-process computation at all. No-op
    single-process. SPMD discipline applies: every process must call the
    same barriers in the same order (auto-generated names are a per-
    process counter). The checkpoint commit protocol is the canonical
    user: non-zero processes must not proceed past a save point before
    process 0's manifest is durable.

    Deadline discipline (ISSUE 14): each process records its arrival in
    the coordinator KV store before waiting, so when the wait times out
    — a peer was SIGKILLed, wedged, or preempted — the survivor raises
    :class:`BarrierTimeoutError` NAMING the processes that never
    arrived instead of surfacing the coordination service's opaque
    deadline string (regression-tested with a real non-arriving child,
    tests/test_pod_supervisor.py::
    test_process_barrier_timeout_names_missing_process). Process 0
    deletes the arrival records after a successful pass so long runs
    don't accrete KV garbage."""
    client = _dist_client()
    if client is None:
        return
    # process identity from the distributed runtime, NOT the backend:
    # jax.process_count() would initialize the backend, and multiprocess
    # CPU backend init blocks on every peer — a barrier must stay a
    # pure coordination-service operation (it is what startup code and
    # the pod supervisor rendezvous on)
    pid, nprocs = _dist_process_info()
    if nprocs <= 1:
        return
    if client is _INTROSPECT_FAILED:  # pragma: no cover - exotic builds
        # multi-process with no readable client: a silent no-op here
        # would turn the checkpoint COMMIT barrier into a data race
        # (a non-writer could resume a manifest that is not yet
        # durable) — fail loudly instead
        raise RuntimeError(
            "process_barrier: this jax build exposes no distributed-"
            "runtime client introspection, so a multi-process rendezvous "
            "cannot be performed safely"
        )
    if name is None:
        _BARRIER_SEQ[0] += 1
        name = f"evox_tpu_barrier_{_BARRIER_SEQ[0]}"
    kv_dir = f"{_BARRIER_KV_PREFIX}/{name}"
    try:
        client.key_value_set(f"{kv_dir}/{pid}", "1")
    except Exception:  # arrival bookkeeping must never fail the barrier
        pass
    try:
        client.wait_at_barrier(name, int(timeout_s * 1000))
    except Exception as e:
        msg = str(e)
        low = msg.lower()
        if "barrier timed out" in low or "deadline_exceeded" in low:
            arrived: list = []
            try:
                arrived = [
                    int(k.rsplit("/", 1)[-1])
                    for k, _ in client.key_value_dir_get(kv_dir + "/")
                ]
            except Exception:
                pass  # census unavailable (coordinator dying): keep []
            missing = sorted(set(range(nprocs)) - set(arrived))
            raise BarrierTimeoutError(
                name, timeout_s, arrived, missing, cause=msg.splitlines()[0]
            ) from e
        raise
    if pid == 0:
        try:
            for k, _ in client.key_value_dir_get(kv_dir + "/"):
                client.key_value_delete(k)
        except Exception:
            pass


def _is_typed_key(x: Any) -> bool:
    dt = getattr(x, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jax.dtypes.prng_key)


def assemble_global_array(host_arr: Any, sharding: NamedSharding) -> jax.Array:
    """Build a GLOBAL ``jax.Array`` on ``sharding`` from a full host
    value every process holds (deterministic init, restored snapshot):
    each process ``device_put``s only the index slices its own devices
    own and stitches them with
    ``jax.make_array_from_single_device_arrays`` — the per-process
    assembly step a cross-process sharding requires (an eager
    ``device_put`` onto it is not addressable-complete and raises).
    Single-process shardings take the plain ``device_put`` fast path."""
    if not mesh_spans_processes(getattr(sharding, "mesh", None)):
        return jax.device_put(host_arr, sharding)
    arr = np.asarray(host_arr)
    shards = [
        jax.device_put(arr[idx], d)
        for d, idx in sharding.addressable_devices_indices_map(
            arr.shape
        ).items()
    ]
    return jax.make_array_from_single_device_arrays(
        arr.shape, sharding, shards
    )


@functools.lru_cache(maxsize=64)
def _replicate_program(sharding: NamedSharding):
    """One cached jitted identity-with-allgather per target sharding: a
    fresh ``jax.jit(lambda ...)`` per call would defeat the dispatch
    cache and recompile the gather for every leaf of every pod
    checkpoint/fetch (NamedSharding hashes by (mesh, spec), so the
    steady-state hot path hits this cache)."""
    return jax.jit(lambda a: a, out_shardings=sharding)


def host_value(x: Any) -> Any:
    """The FULL host (numpy) value of ``x``, even when it is sharded
    across processes: fully-addressable arrays are a plain
    ``device_get``; a cross-process-sharded array is first replicated
    through a jitted identity (``out_shardings=P()`` — GSPMD inserts the
    all-gather) and read from the local replica. Every process receives
    the same value and every process must call this collectively for
    cross-process operands (it dispatches a computation there)."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    if x.is_fully_addressable:
        return np.asarray(jax.device_get(x))
    if getattr(x, "is_fully_replicated", False):
        # replicated global array: the local replica IS the value — no
        # collective needed (the common case for every strategy-state
        # scalar in a pod checkpoint gather)
        return np.asarray(jax.device_get(x.addressable_data(0)))
    sharding = x.sharding
    mesh = getattr(sharding, "mesh", None)
    if mesh is None:  # pragma: no cover - non-named cross-process layout
        raise ValueError(
            "host_value: cannot all-gather a cross-process array without "
            "a named-sharding mesh"
        )
    rep = _replicate_program(NamedSharding(mesh, P()))(x)
    return np.asarray(jax.device_get(rep.addressable_data(0)))


def tree_host_value(tree: Any) -> Any:
    """:func:`host_value` over a pytree (typed PRNG-key leaves pass
    through ``key_data`` and come back typed)."""

    def fetch(leaf):
        if _is_typed_key(leaf):
            return jax.random.wrap_key_data(
                jnp.asarray(host_value(jax.random.key_data(leaf))),
                impl=jax.random.key_impl(leaf),
            )
        return host_value(leaf)

    return jax.tree.map(fetch, tree)


def ensure_global_state(
    state: Any,
    mesh: Optional[Mesh],
    default: Optional["P"] = None,
    rules: Optional[Sequence[Tuple[str, "P"]]] = None,
    axis_prefix: Optional[str] = None,
) -> Any:
    """Per-process GLOBAL-state assembly: place every leaf of an
    eagerly-built (process-local) state onto its annotation-resolved
    sharding over a pod mesh via :func:`assemble_global_array`, so the
    state a global-mesh jit consumes is made of global arrays on every
    process. This is the init/restore boundary of multi-process runs —
    ``StdWorkflow.init`` et al. call it after their eager ``init`` (which
    computes the same host value on every process from the same key), and
    ``place_state`` routes restored snapshots through it.

    No-op when ``mesh`` does not span processes. Leaves that are already
    global (non-fully-addressable) pass through untouched. Typed PRNG-key
    leaves are assembled REPLICATED via ``key_data`` (strategy-level
    keys; a pod layout for key leaves comes from ``constrain_state``
    inside the step)."""
    if not mesh_spans_processes(mesh):
        return state
    shardings = state_sharding(
        state, mesh, default=default, rules=rules, axis_prefix=axis_prefix
    )

    def place(leaf, sh):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            return leaf  # already a global array
        if _is_typed_key(leaf):
            data = assemble_global_array(
                np.asarray(jax.device_get(jax.random.key_data(leaf))),
                NamedSharding(mesh, P()),
            )
            return jax.random.wrap_key_data(
                data, impl=jax.random.key_impl(leaf)
            )
        return assemble_global_array(
            np.asarray(jax.device_get(leaf)), sh
        )

    return jax.tree.map(place, state, shardings)

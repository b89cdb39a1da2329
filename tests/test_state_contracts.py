"""Static/structural enforcement of the state-layout convention.

CLAUDE.md: "Every state is a frozen PyTreeNode; annotate population-leading
fields ``field(sharding=P(POP_AXIS))``, the rest ``field(sharding=P())`` —
the workflow applies layouts each step via ``constrain_state``." Until this
test, the convention was enforced by review only; a forgotten annotation
silently pessimizes mesh runs (the leaf is left to GSPMD propagation
instead of its declared layout) or — worse — a wrong ``P(POP_AXIS)`` on a
replicated leaf breaks divisibility on the 8-device mesh.

Mechanics: every registered algorithm (``evox_tpu.algorithms.__all__``)
whose constructor we can satisfy from a standard argument pool is
instantiated with ``pop_size=8`` in ``dim=5`` (different values, so a
leading axis equal to 8 really is the population axis), its state is
built with ``init(key)``, and each dataclass field is checked against the
actual leaf shapes:

- a field with any leaf whose leading axis == pop_size must be annotated
  ``P(POP_AXIS)``;
- every other (non-static) field must be annotated ``P()``;
- the state class must be a frozen dataclass registered as a JAX pytree.

PR 6 adds the dtype-policy half of the convention (core/dtype_policy.py):

- every population-leading field with FLOAT leaves must carry an explicit
  ``storage`` annotation (``True`` = held at storage width under a
  ``DtypePolicy``; ``False`` = documented must-stay-f32 opt-out) — a
  forgotten annotation silently exempts the field from the bf16 storage
  mode and the memory-bound legs stop shrinking;
- non-population fields must NOT be ``storage=True``: replicated strategy
  state (CMA mean/covariance/paths, step sizes) is exactly the
  must-stay-f32 set, kept full-precision by being unannotated.

Monitor states get the same structural checks (their buffers are
capacity-leading, never population-leading, so everything is ``P()``
and never storage-annotated).
Classes the pool cannot construct are skipped EXPLICITLY — a baseline
assertion pins the set of covered classes so coverage can only grow.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import evox_tpu
from evox_tpu.core.distributed import POP_AXIS
from evox_tpu.core.guardrail import GuardedAlgorithm
from evox_tpu.core.struct import PyTreeNode

POP = 8
DIM = 5
N_OBJS = 3

# constructor argument pool, matched by parameter name
ARG_POOL = {
    "lb": jnp.full((DIM,), -5.0),
    "ub": jnp.full((DIM,), 5.0),
    "center_init": jnp.full((DIM,), 1.0),
    "init_stdev": 1.0,
    "pop_size": POP,
    "dim": DIM,
    "n_objs": N_OBJS,
    "learning_rate": 0.1,
    "noise_stdev": 0.2,
}


# per-class constructor overrides where the pool's defaults violate a
# constructor constraint (shapes stay distinguishable: pop != DIM)
CTOR_OVERRIDES = {
    "ESMC": {"center_init": ARG_POOL["center_init"], "pop_size": 9},
    # default memory_size is 8 at DIM=5 — collides with POP, which would
    # misclassify the (memory, dim) transform archive as population-leading
    "LMMAES": {
        "center_init": ARG_POOL["center_init"],
        "init_stdev": 1.0,
        "pop_size": POP,
        "memory_size": 3,
    },
    # a centre with a matrix in it: vectors alone are never perturbed
    "LowRankOpenES": {
        "center_init": {"w": jnp.ones((DIM, 3)), "b": jnp.ones((DIM,))},
        "pop_size": POP,
    },
}

# fallback positional idioms for subclasses with (*args, **kwargs) ctors
FALLBACK_KWARGS = (
    {"lb": ARG_POOL["lb"], "ub": ARG_POOL["ub"], "pop_size": POP},
    {
        "lb": ARG_POOL["lb"],
        "ub": ARG_POOL["ub"],
        "n_objs": N_OBJS,
        "pop_size": POP,
    },
    {
        "center_init": ARG_POOL["center_init"],
        "init_stdev": 1.0,
        "pop_size": POP,
    },
)


def _construct(cls, name=None):
    """Instantiate ``cls`` from the argument pool, or None if a required
    parameter is not in the pool."""
    import inspect

    if name in CTOR_OVERRIDES:
        try:
            return cls(**CTOR_OVERRIDES[name])
        except Exception:
            return None
    try:
        sig = inspect.signature(cls.__init__)
    except (TypeError, ValueError):  # pragma: no cover
        return None
    kwargs = {}
    var_args = False
    for pname, p in list(sig.parameters.items())[1:]:  # skip self
        if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
            var_args = True
            continue
        if pname in ARG_POOL:
            kwargs[pname] = ARG_POOL[pname]
        elif p.default is p.empty:
            return None
    try:
        return cls(**kwargs)
    except Exception:
        if not var_args:
            return None
    for fb in FALLBACK_KWARGS:  # (*args, **kwargs) subclasses
        try:
            return cls(**fb)
        except Exception:
            continue
    return None


def _algorithm_classes():
    from evox_tpu.core.algorithm import Algorithm

    seen = {}
    for name in evox_tpu.algorithms.__all__:
        obj = getattr(evox_tpu.algorithms, name, None)
        if isinstance(obj, type) and issubclass(obj, Algorithm):
            seen[name] = obj
    return seen


def _iter_state_fields(state, prefix=""):
    """Yield (path, field, value) for every dataclass field, recursing
    into PyTreeNode-valued fields (wrappers/containers)."""
    for f in dataclasses.fields(state):
        value = getattr(state, f.name)
        path = f"{prefix}{f.name}"
        yield path, f, value
        if dataclasses.is_dataclass(value):
            yield from _iter_state_fields(value, prefix=f"{path}.")


def _check_state(state, where, pop=POP):
    errors = []
    assert dataclasses.is_dataclass(state), f"{where}: state is not a dataclass"
    assert type(state).__dataclass_params__.frozen, f"{where}: not frozen"
    # registered as a pytree: flatten must not treat it as a leaf
    leaves = jax.tree.leaves(state)
    assert not any(l is state for l in leaves), f"{where}: not a pytree"
    for path, f, value in _iter_state_fields(state):
        if f.metadata.get("static", False):
            continue
        spec = f.metadata.get("sharding")
        field_leaves = [
            jnp.asarray(x)
            for x in jax.tree.leaves(value)
            if hasattr(x, "shape") or not isinstance(x, (type(None), str))
        ]
        # pop-leading: leading axis is the population size or a multiple
        # of it (CoDE's 3-trials-per-parent batch is (3*pop, dim) and
        # legitimately shards over "pop")
        pop_leading = any(
            l.ndim >= 1 and l.shape[0] >= pop and l.shape[0] % pop == 0
            for l in field_leaves
        )
        if dataclasses.is_dataclass(value):
            # nested state: its own fields are checked by the recursion;
            # the outer field needs no (single) annotation
            continue
        storage = f.metadata.get("storage")
        has_float = any(
            jnp.issubdtype(l.dtype, jnp.floating) for l in field_leaves
        )
        if pop_leading:
            if spec != P(POP_AXIS):
                errors.append(
                    f"{where}.{path}: population-leading "
                    f"(shape {field_leaves[0].shape}) but annotated {spec!r}; "
                    f"expected field(sharding=P(POP_AXIS))"
                )
            if has_float and storage is None:
                errors.append(
                    f"{where}.{path}: population-leading float field has no "
                    "dtype-policy annotation; add field(..., storage=True) "
                    "(or an explicit storage=False must-stay-f32 opt-out, "
                    "documented in the state class)"
                )
        else:
            if spec != P():
                errors.append(
                    f"{where}.{path}: annotated {spec!r}; expected "
                    "field(sharding=P()) for non-population fields"
                )
            if storage:
                errors.append(
                    f"{where}.{path}: non-population field annotated "
                    "storage=True — replicated strategy state is the "
                    "must-stay-f32 set (CMA mean/covariance/paths); leave "
                    "it unannotated"
                )
    assert not errors, "\n".join(errors)


# algorithms the pool genuinely cannot build (need sub-algorithms, meta
# params, or divisibility constraints the pool's POP breaks); every OTHER
# registered algorithm must be covered — see test_coverage_baseline
KNOWN_UNCONSTRUCTIBLE = {
    "Coevolution",  # container: needs a base algorithm
    "ClusteredAlgorithm",  # container: needs a base algorithm
    "TreeAlgorithm",  # container: needs per-node algorithms
    "RandomMaskAlgorithm",  # container: needs a base algorithm
    "VectorizedCoevolution",  # container: needs a base algorithm
    "DMSPSOEL",  # pop_size must be divisible by sub_swarm_size=10
    "RestartCMAESDriver",  # host driver, not an Algorithm
}


def _constructible():
    out = {}
    for name, cls in _algorithm_classes().items():
        algo = _construct(cls, name)
        if algo is not None:
            out[name] = algo
    return out


def test_coverage_baseline():
    """The pool must keep covering at least the current surface: a new
    registered algorithm either constructs from the pool or is explicitly
    listed as unconstructible (forcing a conscious decision)."""
    classes = _algorithm_classes()
    built = set(_constructible())
    missed = set(classes) - built - KNOWN_UNCONSTRUCTIBLE
    assert not missed, (
        f"registered algorithms neither constructible from the ARG_POOL "
        f"nor listed in KNOWN_UNCONSTRUCTIBLE: {sorted(missed)}"
    )
    stale = {
        n for n in KNOWN_UNCONSTRUCTIBLE if n in built
    }
    assert not stale, f"KNOWN_UNCONSTRUCTIBLE entries now constructible: {sorted(stale)}"


@pytest.mark.parametrize("name", sorted(_constructible()))
def test_algorithm_state_contract(name):
    algo = _constructible()[name]
    state = algo.init(jax.random.PRNGKey(0))
    # some algorithms normalize pop_size in __init__ (MOEA/D's K*S grid,
    # ESMC's odd-size rule): detect against the size they actually use
    _check_state(state, name, pop=int(getattr(algo, "pop_size", POP)))


def test_guarded_wrapper_state_contract():
    """GuardedState itself (and its nested inner state) follows the
    convention — the wrapper must not break mesh layouts."""
    from evox_tpu.algorithms import CMAES

    algo = GuardedAlgorithm(
        CMAES(center_init=jnp.full((DIM,), 1.0), init_stdev=1.0, pop_size=POP)
    )
    state = algo.init(jax.random.PRNGKey(0))
    _check_state(state, "GuardedAlgorithm[CMAES]")


def _fake_fitness(pop, n_objs):
    """Deterministic jittable fitness for an arbitrary candidate pytree:
    per-row sum of squares across every float leaf (shape (B,) or
    (B, n_objs))."""
    if not getattr(pop, "has_population_axis", True):
        pop = pop.materialise()  # a perturbation spec: its members, dense
    leaves = [
        jnp.asarray(x, jnp.float32)
        for x in jax.tree.leaves(pop)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    ]
    base = sum(
        jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1) for x in leaves
    )
    if n_objs == 1:
        return base
    return jnp.stack([base * (j + 1.0) for j in range(n_objs)], axis=1)


# algorithms whose ask/tell cannot run under a leading tenant axis; every
# other registered algorithm must vmap — additions here require a
# conscious decision (and a note on why), exactly like
# KNOWN_UNCONSTRUCTIBLE
KNOWN_UNVMAPPABLE = set()


# the heaviest vmap-contract params (compile-bound MOEAs / ensemble DE)
# run slow-marked: the mechanical contract keeps full tier-1 breadth via
# every other registered algorithm, and the full suite still sweeps all
# (ISSUE 14 gate-headroom, the PR-2 slow-marking discipline)
_VMAP_CONTRACT_SLOW = {
    "BCEIBEA",
    "BiGE",
    "CoDE",
    "EAGMOEAD",
    "IBEA",
    "IMMOEA",
    "KnEA",
    "LMOCSO",
    "MOEADM2M",
    "RVEAa",
}


@pytest.mark.parametrize(
    "name",
    [
        pytest.param(n, marks=pytest.mark.slow)
        if n in _VMAP_CONTRACT_SLOW
        else n
        for n in sorted(_constructible())
    ],
)
def test_algorithm_vmap_contract(name):
    """vmap-ability as a state contract (PR 8, workflows/tenancy.py):
    every registered algorithm must run init -> (init_ask/init_tell ->)
    ask -> tell with a leading TENANT axis added by ``jax.vmap`` — the
    mechanical guarantee behind ``VectorizedWorkflow`` fleets. A state
    or ask/tell that breaks under vmap (host-side control flow on traced
    values, shape-dependent python branching on per-instance data) is
    caught here, not when a user stacks the algorithm into a fleet.
    Structural contract only (each leaf gains exactly the tenant axis
    and stays finite-typed); trajectory equivalence vs solo runs is
    asserted per-algorithm in tests/test_tenancy.py, where codegen
    tolerance is documented."""
    if name in KNOWN_UNVMAPPABLE:
        pytest.skip(f"{name} is explicitly excluded from the vmap contract")
    algo = _constructible()[name]
    n_objs = int(getattr(algo, "n_objs", 1))

    def run_one(key):
        s = algo.init(key)
        if algo.has_init_ask or algo.has_init_tell:
            pop, s = algo.init_ask(s)
            s = algo.init_tell(s, _fake_fitness(pop, n_objs))
        pop, s = algo.ask(s)
        return algo.tell(s, _fake_fitness(pop, n_objs))

    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    stacked = jax.jit(jax.vmap(run_one))(keys)
    solo = run_one(keys[0])
    stacked_leaves = jax.tree_util.tree_flatten_with_path(stacked)[0]
    solo_leaves = jax.tree_util.tree_flatten_with_path(solo)[0]
    assert len(stacked_leaves) == len(solo_leaves)
    for (path, a), (_, b) in zip(stacked_leaves, solo_leaves):
        where = f"{name}{jax.tree_util.keystr(path)}"
        assert a.shape == (2,) + jnp.shape(b), (
            f"{where}: vmapped leaf shape {a.shape} is not the solo "
            f"shape {jnp.shape(b)} plus a leading tenant axis"
        )
        assert a.dtype == jnp.asarray(b).dtype, f"{where}: dtype changed"


# ---------------------------------------------------------------- sharded ES
# PR 10: every algorithm advertising the POP-sharded low-memory protocol
# (pop_shard_capable) must run one full ask/tell under ShardedES on the
# 8-device mesh and match the replicated path of the SAME per-shard
# sampling law. Documented tolerance: samples are bitwise-identical
# (identical per-shard streams), state updates differ only by summation
# order (psum-of-partial-moments vs one ordered reduction) — rtol/atol
# 1e-5 at these shapes; multi-step trajectories drift gradually toward
# ~1e-4 (see tests/test_large_pop.py for trajectory + convergence laws).

SHARDED_TRACK_BASELINE = {"SepCMAES", "LMMAES", "RMES"}


def _sharded_capable():
    return {
        name: algo
        for name, algo in _constructible().items()
        if getattr(algo, "pop_shard_capable", False)
    }


def test_sharded_track_baseline():
    """The sharded low-memory track covers at least the PR-10 set; a new
    pop_shard_capable algorithm joins the mechanical contract for free."""
    got = set(_sharded_capable())
    missing = SHARDED_TRACK_BASELINE - got
    assert not missing, f"sharded track lost algorithms: {sorted(missing)}"


@pytest.mark.parametrize("name", sorted(_sharded_capable()))
def test_sharded_step_contract(name):
    from evox_tpu.core.distributed import ShardedES, create_mesh

    algo = _sharded_capable()[name]
    mesh = create_mesh()
    n_dev = jax.device_count()
    sharded = ShardedES(algo, mesh=mesh)
    repl = ShardedES(algo, mesh=None, n_shards=n_dev)
    key = jax.random.PRNGKey(5)
    s_sh, s_rp = sharded.init(key), repl.init(key)
    pop_sh, s_sh = sharded.ask(s_sh)
    pop_rp, s_rp = repl.ask(s_rp)
    # identical per-shard streams: the samples agree to fp noise
    assert jnp.allclose(pop_sh, pop_rp, rtol=1e-6, atol=1e-6), name
    fit = jnp.sum(jnp.asarray(pop_sh, jnp.float32) ** 2, axis=1)
    s_sh = sharded.tell(s_sh, fit)
    s_rp = repl.tell(s_rp, jnp.sum(jnp.asarray(pop_rp, jnp.float32) ** 2, axis=1))
    sh_leaves = jax.tree_util.tree_flatten_with_path(s_sh)[0]
    rp_leaves = jax.tree_util.tree_flatten_with_path(s_rp)[0]
    assert len(sh_leaves) == len(rp_leaves)
    for (path, a), (_, b) in zip(sh_leaves, rp_leaves):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5), (
            f"{name}{jax.tree_util.keystr(path)}: sharded tell diverged "
            "from the replicated path beyond the documented tolerance"
        )


def test_surrogate_state_contracts():
    """ISSUE 15 (operators/surrogate.py + workflows/surrogate.py): the
    paired archive's capacity-leading buffers are the shardable axis —
    ``P(POP_AXIS)`` with candidates ``storage=True`` (bf16-storage-
    compatible) and fitness/factorization products explicitly
    ``storage=False`` (must-stay-f32); every scalar/replicated field is
    ``P()``. Checked with the same mechanical walker as the algorithm
    states, with ``pop`` = the archive capacity (the leading axis the
    convention keys on); the full SurrogateState (archive + model
    nested) passes the same walk."""
    from evox_tpu.operators.surrogate import (
        EnsembleSurrogate,
        GPSurrogate,
        SurrogateArchive,
    )
    from evox_tpu.problems.numerical import Sphere
    from evox_tpu.workflows.surrogate import SurrogateWorkflow
    from evox_tpu.algorithms.so.pso import PSO

    cap, dim = 16, 3
    arc = SurrogateArchive(cap)
    _check_state(arc.init(dim), "ArchiveState", pop=cap)
    _check_state(
        GPSurrogate().init_model(cap, dim), "GPModelState", pop=cap
    )
    # the ensemble's member axis must NOT read as the population axis:
    # pick a member count that differs from every leaf dimension
    ens = EnsembleSurrogate(n_members=2, hidden=7, fit_steps=1)
    _check_state(ens.init_model(cap, dim), "EnsembleModelState", pop=cap)
    # the assembled workflow-state slice, after real steps (fitted model)
    wf = SurrogateWorkflow(
        PSO(lb=-jnp.ones(dim), ub=jnp.ones(dim), pop_size=8),
        Sphere(),
        surrogate=GPSurrogate(),
        screen_frac=0.5,
        archive_capacity=cap,
        warmup=8,
        refit_every=1,
        # a log size that is NOT a multiple of cap, so the event ring
        # cannot be misread as capacity-leading by the walker
        fallback_log=5,
    )
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.step(wf.step(state))
    _check_state(state.sur, "SurrogateState", pop=cap)


def test_monitor_state_contracts():
    """Monitor states: frozen pytree dataclasses, all fields P() (their
    buffers are capacity-leading, not population-leading)."""
    from evox_tpu.monitors import EvalMonitor, LineageMonitor, TelemetryMonitor

    for mon in (
        TelemetryMonitor(capacity=4),
        EvalMonitor(),
        LineageMonitor(history_capacity=4),
    ):
        mstate = mon.init(jax.random.PRNGKey(0))
        if mstate is None:  # pragma: no cover
            continue
        assert dataclasses.is_dataclass(mstate), type(mon).__name__
        assert type(mstate).__dataclass_params__.frozen
        for path, f, value in _iter_state_fields(mstate):
            if f.metadata.get("static", False):
                continue
            spec = f.metadata.get("sharding")
            assert spec == P(), (
                f"{type(mon).__name__}.{path}: annotated {spec!r}; monitor "
                "state fields must be field(sharding=P())"
            )
            assert not f.metadata.get("storage"), (
                f"{type(mon).__name__}.{path}: monitor state must not be "
                "storage-annotated (telemetry/history buffers stay f32)"
            )

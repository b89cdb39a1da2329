"""Mixed-precision storage policy — bf16 at rest, f32 in flight.

The streaming algorithms (CSO, PSO) are memory-bound by their
arithmetic: every generation round-trips the whole
population/velocity/fitness state through HBM (no benchmark cell times
them yet: PERF.md section 7, row 2). evosax (PAPERS.md) made the same
observation for batched JAX strategies — memory traffic per generation
is the budget. The cheapest lever is to halve the bytes: store the per-individual state in
``bfloat16`` and compute in ``float32``.

Design (mirrors the ``field(sharding=...)`` layout convention):

- Fields declare eligibility with ``field(storage=True)`` —
  population-leading float arrays (population, fitness, velocity,
  offspring, per-individual noise) — or explicitly opt out with
  ``storage=False`` (must-stay-f32). Replicated strategy parameters
  (CMA mean/covariance/paths, step sizes) are simply never annotated,
  so CMA's eigh and rank-µ update paths stay f32 by construction.
- The workflow applies the policy at the *state boundary*: annotated
  leaves are cast to ``policy.storage`` when the step's new state is
  formed (fused into the same tree walk as ``constrain_state``) and
  cast back to ``policy.compute`` at step entry. All algorithm math —
  sorting, reductions, means, covariance — therefore runs in the
  compute dtype; only the loop-carried bytes shrink.
- The default workflow policy is ``None``: every ``apply_*`` helper
  returns the state object *unchanged* (same python object, no tree
  traversal), so the f32 path is bit-identical to the pre-policy
  behavior (golden-pinned in tests/test_dtype_policy.py).

Accuracy contract: bf16 storage quantizes the carried per-individual
state once per generation (~3 decimal digits). Convergence-threshold
tests (CLAUDE.md convention) gate the mode per algorithm — see
tests/test_dtype_policy.py for CMAES / CSO / NSGA-II. Integer, bool and
PRNG-key leaves are never cast.

Callback-free by construction: pure ``convert_element_type`` inside traced
code, no host callbacks (pinned by tests/test_no_host_callbacks.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "DtypePolicy",
    "BF16_STORAGE",
    "apply_storage",
    "apply_compute",
    "storage_eligible_fields",
    "policy_report",
]


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """``(storage, compute)`` dtype pair threaded through a workflow.

    ``storage``: dtype of storage-annotated leaves *at rest* (between
    generations, in the fused-run carry, in checkpoints).
    ``compute``: dtype those leaves are upcast to at the step boundary,
    and the dtype every reduction/accumulation therefore runs in.

    Hashable and static: policies ride workflow objects (not states), so
    switching policy means a new compiled program — by design, exactly
    like resizing a population. The no-op policy (storage == compute)
    and ``None`` compile identical programs.
    """

    storage: Any = jnp.float32
    compute: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "storage", jnp.dtype(self.storage))
        object.__setattr__(self, "compute", jnp.dtype(self.compute))
        for name in ("storage", "compute"):
            dt = getattr(self, name)
            if not jnp.issubdtype(dt, jnp.floating):
                raise ValueError(
                    f"DtypePolicy.{name} must be a floating dtype, got {dt}"
                )

    @property
    def is_noop(self) -> bool:
        return self.storage == self.compute

    def report(self) -> dict:
        """JSON-serializable description (lands in run_report)."""
        return {
            "storage": str(self.storage.name),
            "compute": str(self.compute.name),
            "active": not self.is_noop,
        }


# the one policy the docs talk about: bf16 at rest, f32 in flight
BF16_STORAGE = DtypePolicy(storage=jnp.bfloat16, compute=jnp.float32)


def _storage_flag_for_path(state: Any, path: tuple) -> bool:
    """Resolve the deepest ``field(storage=...)`` annotation along a
    pytree key path (same walk as distributed._spec_for_path — inner
    annotations override outer ones; absent means ineligible)."""
    obj, flag = state, False
    for key in path:
        if isinstance(key, jax.tree_util.GetAttrKey) and dataclasses.is_dataclass(obj):
            f = obj.__dataclass_fields__.get(key.name)
            if f is not None and "storage" in f.metadata:
                flag = bool(f.metadata["storage"])
            obj = getattr(obj, key.name)
        elif isinstance(key, jax.tree_util.SequenceKey):
            obj = obj[key.idx]
        elif isinstance(key, jax.tree_util.DictKey):
            obj = obj[key.key]
        else:
            break
    return flag


def _castable(leaf: Any) -> bool:
    dt = getattr(leaf, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


def _apply(state: Any, policy: Optional[DtypePolicy], target_attr: str) -> Any:
    if policy is None or policy.is_noop:
        return state  # same object: the default path traces identically
    target = getattr(policy, target_attr)

    def cast(path, leaf):
        if _castable(leaf) and _storage_flag_for_path(state, path):
            return jax.lax.convert_element_type(leaf, target)
        return leaf

    return jax.tree_util.tree_map_with_path(cast, state)


def apply_storage(state: Any, policy: Optional[DtypePolicy]) -> Any:
    """Cast storage-annotated float leaves to the storage dtype — the
    at-rest form carried between generations / in checkpoints. Exact
    no-op (same object) when ``policy`` is ``None`` or storage == compute.
    """
    return _apply(state, policy, "storage")


def apply_compute(state: Any, policy: Optional[DtypePolicy]) -> Any:
    """Cast storage-annotated float leaves to the compute dtype — the
    step-entry upcast, so all algorithm math runs full-precision."""
    return _apply(state, policy, "compute")


def storage_eligible_fields(state: Any) -> dict:
    """``{field_path: bool}`` of every *annotated* dataclass field in
    ``state`` (recursing into nested dataclasses) — the introspection
    surface tests/test_state_contracts.py enforces the convention with.
    Unannotated fields are absent (ineligible by default)."""
    out: dict = {}

    def walk(obj: Any, prefix: str) -> None:
        if not dataclasses.is_dataclass(obj):
            return
        for f in dataclasses.fields(obj):
            path = f"{prefix}{f.name}"
            if "storage" in f.metadata:
                out[path] = bool(f.metadata["storage"])
            walk(getattr(obj, f.name), f"{path}.")

    walk(state, "")
    return out


def policy_report(workflow: Any) -> dict:
    """The ``dtype_policy`` section for run_report, duck-
    typed off ``workflow.dtype_policy`` (absent → explicit f32 default,
    so reports always state the precision they ran at)."""
    policy = getattr(workflow, "dtype_policy", None)
    if policy is None:
        return {"storage": "float32", "compute": "float32", "active": False}
    return policy.report()

"""Per-tenant fault isolation for vmapped fleets: signals + policy.

A ``VectorizedWorkflow`` fuses N tenants into ONE dispatch — which means
one tenant whose state goes non-finite (a NaN covariance, a collapsed
sigma) used to keep riding in every subsequent fused step, wasting its
slot's compute forever and polluting the fleet's reports, with no
per-tenant recovery story. This module closes that hole at the serving
layer's natural boundary (between dispatch chunks, where the
``RunQueue`` already retires/admits):

- :func:`fleet_health_signals` reads the per-tenant health signals that
  are ALREADY on device — a NaN scan over each tenant's algorithm
  leaves, the stacked :class:`~evox_tpu.core.guardrail.GuardedState`
  trigger bitmask / restart / stagnation counters when the fleet wraps a
  ``GuardedAlgorithm``, and the stacked TelemetryMonitor stagnation and
  non-finite-fitness counters when one is attached — as one jitted
  computation and ONE small host fetch (a handful of ``(N,)`` arrays;
  bytes and round-trips to the host are the cost).
- :class:`FleetHealthPolicy` maps those signals to per-slot actions,
  evaluated by ``RunQueue.step_chunk`` at every chunk boundary:

  * ``"freeze"`` — mask the tenant's tell (``jnp.where`` on the frozen
    mask inside the fused step) so its state stops advancing; the slot
    parks with a forensic checkpoint and the fleet keeps its shape.
  * ``"evict"`` — checkpoint the tenant via the existing
    ``extract_tenant`` surgery and backfill the slot from the pending
    queue (or park it when pending is empty).
  * ``"restart"`` — restart in place: a fresh ``init_tenant`` re-centered
    on the tenant's best-so-far via the guardrail's
    :func:`~evox_tpu.core.guardrail.recenter_state` path, budget counter
    preserved so a permanently-poisoned tenant still terminates; after
    ``max_restarts_per_slot`` the action escalates to ``"freeze"``.

Isolation law (tests/test_serving_chaos.py): healthy tenants'
trajectories are BITWISE-unchanged under any mix of actions on other
slots — vmapped per-tenant math is row-independent, ``insert_tenant``
writes exactly one row, and the freeze select is an elementwise
``where`` that returns the computed row unchanged for unfrozen tenants.
Entirely callback-free (host work happens between dispatches), pinned by
tests/test_no_host_callbacks.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.guardrail import GuardedState, recenter_state

__all__ = ["FleetHealthPolicy", "fleet_health_signals"]

ACTIONS = ("freeze", "evict", "restart")


def _per_tenant_nan(tree: Any) -> jax.Array:
    """(N,) bool: any NaN in a floating leaf of each tenant's slice.
    Inf is deliberately NOT counted — +Inf sentinels are idiomatic here
    (DE's unevaluated rows, the guardrail's initial best_fitness)."""
    flags = None
    for leaf in jax.tree.leaves(tree):
        x = jnp.asarray(leaf)
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.ndim < 1:
            continue
        bad = jnp.any(
            jnp.isnan(x), axis=tuple(range(1, x.ndim))
        ) if x.ndim > 1 else jnp.isnan(x)
        flags = bad if flags is None else flags | bad
    if flags is None:
        raise ValueError(
            "fleet state has no floating tenant-stacked leaves to scan"
        )
    return flags


def _has_fields(state: Any, *names: str) -> bool:
    fields = getattr(state, "__dataclass_fields__", {})
    return all(n in fields for n in names)


def _signals_impl(tenants: Any) -> Dict[str, jax.Array]:
    """Jittable per-tenant signal vector over the tenant-stacked state.
    Structure-dependent branches (guarded? telemetry attached?) resolve
    at trace time, so the compiled program carries only the signals this
    fleet actually has."""
    out: Dict[str, jax.Array] = {
        "generation": jnp.asarray(tenants.generation, jnp.int32),
        "nonfinite": _per_tenant_nan(tenants.algo),
    }
    algo = tenants.algo
    if isinstance(algo, GuardedState):
        # per-tenant trigger export (core/guardrail.py): the stacked
        # wrapper counters ARE the device-side detector's verdicts
        out["guard_trigger"] = jnp.asarray(algo.last_trigger, jnp.int32)
        out["guard_restarts"] = jnp.asarray(algo.restarts, jnp.int32)
        out["guard_stagnation"] = jnp.asarray(algo.stagnation, jnp.int32)
    for ms in tenants.monitors:
        if _has_fields(ms, "stagnation", "nan_fitness", "nan_candidates"):
            out["stagnation"] = jnp.asarray(ms.stagnation, jnp.int32)
            out["nan_fitness"] = jnp.asarray(ms.nan_fitness, jnp.int32)
            out["nan_candidates"] = jnp.asarray(ms.nan_candidates, jnp.int32)
            break
    return out


_signals_jit = jax.jit(_signals_impl)


def fleet_health_signals(state: Any) -> Dict[str, np.ndarray]:
    """Per-tenant health signals of a ``VectorizedWorkflowState``, as
    host numpy arrays (one jitted computation + one small fetch). Keys
    always present: ``generation``, ``nonfinite``; plus
    ``guard_trigger``/``guard_restarts``/``guard_stagnation`` for
    guarded fleets and ``stagnation``/``nan_fitness``/``nan_candidates``
    when a TelemetryMonitor rides along."""
    device = _signals_jit(state.tenants)
    return {k: np.asarray(v) for k, v in jax.device_get(device).items()}


@dataclasses.dataclass
class FleetHealthPolicy:
    """Chunk-boundary policy mapping per-tenant signals to slot actions.

    Args:
        on_nonfinite: action when a tenant's algorithm state carries NaN
            (``"freeze"`` / ``"evict"`` / ``"restart"`` / None to
            ignore). This is the poisoned-tenant isolation knob.
        on_trigger: action when a guarded fleet's trigger bitmask is
            nonzero (the on-device detector already restarted the inner
            state same-shape; the policy can additionally evict or
            freeze the slot at the serving layer). Default None — the
            guardrail's own restart is usually the right response.
        stagnation_limit: generations without best-so-far improvement
            (TelemetryMonitor's counter, else the guardrail's) before
            ``on_stagnation`` fires. None disables.
        on_stagnation: action for stagnated tenants (default
            ``"restart"`` — re-center on best-so-far and keep spending
            the budget exploring).
        max_restarts_per_slot: in-place restarts per slot before a
            ``"restart"`` decision escalates to ``"freeze"`` (a tenant
            that re-poisons after every restart must not restart
            forever; freezing parks it with its budget unspent).

    ``decide`` returns ``(action, reason)`` or None per tenant; severity
    order is nonfinite > trigger > stagnation (a NaN state is beyond
    what a stagnation restart could help).
    """

    on_nonfinite: Optional[str] = "evict"
    on_trigger: Optional[str] = None
    stagnation_limit: Optional[int] = None
    on_stagnation: Optional[str] = "restart"
    max_restarts_per_slot: int = 2
    # serving-plane flight recorder (PR 16): when attached (RunQueue
    # auto-threads its recorder), every verdict counts into the metrics
    # plane by reason (`fleet_health.<reason-class>`); excluded from
    # comparison/repr — the policy's identity is its thresholds
    metrics: Any = dataclasses.field(
        default=None, compare=False, repr=False
    )

    def __post_init__(self) -> None:
        for name in ("on_nonfinite", "on_trigger", "on_stagnation"):
            action = getattr(self, name)
            if action is not None and action not in ACTIONS:
                raise ValueError(
                    f"{name} must be one of {ACTIONS} or None, got "
                    f"{action!r}"
                )
        if self.max_restarts_per_slot < 0:
            raise ValueError(
                "max_restarts_per_slot must be >= 0, got "
                f"{self.max_restarts_per_slot}"
            )

    def may_freeze(self) -> bool:
        """Whether any decision path can freeze a slot — the RunQueue
        materializes the fleet's frozen mask up front iff so (adding the
        mask later would change the compiled program mid-run)."""
        actions = {self.on_nonfinite, self.on_trigger, self.on_stagnation}
        return "freeze" in actions or "restart" in actions  # escalation

    def _resolve(self, action: str, slot_restarts: int) -> str:
        if action == "restart" and slot_restarts >= self.max_restarts_per_slot:
            return "freeze"
        return action

    def decide(
        self, row: Dict[str, Any], slot_restarts: int = 0
    ) -> Optional[Tuple[str, str]]:
        """One tenant's verdict. ``row``: that tenant's slice of
        :func:`fleet_health_signals` (python scalars); ``slot_restarts``:
        in-place restarts this slot has already had (queue-tracked)."""
        if self.on_nonfinite is not None and bool(row.get("nonfinite")):
            return self._verdict(
                self._resolve(self.on_nonfinite, slot_restarts),
                "nonfinite_state",
            )
        if self.on_trigger is not None and int(row.get("guard_trigger", 0)):
            return self._verdict(
                self._resolve(self.on_trigger, slot_restarts),
                f"guard_trigger:{int(row['guard_trigger'])}",
            )
        if self.stagnation_limit is not None and self.on_stagnation is not None:
            stag = row.get("stagnation", row.get("guard_stagnation"))
            if stag is not None and int(stag) >= self.stagnation_limit:
                return self._verdict(
                    self._resolve(self.on_stagnation, slot_restarts),
                    f"stagnation:{int(stag)}",
                )
        return None

    def _verdict(self, action: str, reason: str) -> Tuple[str, str]:
        if self.metrics is not None:
            # reason class only (strip the per-tenant numeric suffix):
            # metric names must be low-cardinality for the stream's
            # monotonic-counter law to stay meaningful
            self.metrics.count(
                f"fleet_health.{action}.{reason.split(':', 1)[0]}"
            )
        return (action, reason)

    def report(self) -> dict:
        """Static policy config for ``run_report``'s ``fleet_health``."""
        return {
            "on_nonfinite": self.on_nonfinite,
            "on_trigger": self.on_trigger,
            "stagnation_limit": self.stagnation_limit,
            "on_stagnation": self.on_stagnation,
            "max_restarts_per_slot": self.max_restarts_per_slot,
        }


def restarted_tenant(wf: Any, old_tenant: Any, spec_key: jax.Array,
                     fleet_generation: int, hyperparams: Dict[str, Any]):
    """Build the in-place-restart replacement for a slot: a fresh tenant
    from a deterministic new stream (``fold_in`` of the spec's key with
    the fleet generation — replayable by recovery), re-centered on the
    old tenant's best-so-far via the guardrail's
    :func:`~evox_tpu.core.guardrail.recenter_state` path when the fleet
    is guarded (best/restart bookkeeping carried across, restart counter
    incremented — the host-boundary analog of the wrapper's own
    ``lax.cond`` restart). The tenant's OWN generation counter is
    preserved so its budget keeps counting down."""
    key = jax.random.fold_in(jnp.asarray(spec_key), int(fleet_generation))
    fresh = wf.init_tenant(key, hyperparams)
    if wf.algorithm.has_init_ask or wf.algorithm.has_init_tell:
        fresh = wf._solo_peel(fresh)  # static-shape law, as admission does
    old_algo = old_tenant.algo
    if isinstance(old_algo, GuardedState) and isinstance(
        fresh.algo, GuardedState
    ):
        inner = recenter_state(fresh.algo.inner, jnp.asarray(old_algo.best_x))
        fresh = fresh.replace(
            algo=fresh.algo.replace(
                inner=inner,
                best_x=jnp.asarray(old_algo.best_x),
                best_fitness=jnp.asarray(old_algo.best_fitness),
                restarts=jnp.asarray(old_algo.restarts) + 1,
            )
        )
    return fresh.replace(
        generation=jnp.asarray(old_tenant.generation, jnp.int32)
    )

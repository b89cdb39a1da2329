"""TelemetryMonitor — callback-free, on-device run telemetry.

SURVEY.md §5.1: the reference has no built-in observability; evox_tpu's
StepTimerMonitor covers wall-clock but rides ``io_callback`` — host
traffic every generation, and unusable under ``vmap``. This monitor is the
callback-free alternative: every accumulator is a device array inside
the monitor's frozen pytree state, updated with pure jittable math in the
``post_eval`` hook — zero host traffic on the hot path, so it works
identically in a ``wf.step`` loop, inside ``wf.run``'s fused
``lax.fori_loop`` (where host callbacks are impossible on every backend),
and under ``run_host_pipelined``. Host-side wall-clock/compile timing is
the job of :mod:`evox_tpu.core.instrument`, which wraps the workflow's
entry points *outside* traced code; :func:`evox_tpu.core.instrument.
run_report` merges both sides into one structured report.

Tracked per generation (fixed-capacity ring, same pattern as
``EvalMonitor(history_capacity=K)``): best and mean fitness (finite-masked
mean, so a few poison rows don't blank the trajectory) and population
diversity (mean per-dimension std of the candidate batch). Tracked
cumulatively: NaN/Inf element counts for candidates and fitness,
generations-since-improvement (stagnation), the generation of the last
improvement, and generation/evaluation counters.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.instrument import sanitize_json
from ..core.monitor import Monitor
from jax.sharding import PartitionSpec as P
from ..core.struct import PyTreeNode, field
from .common import ring_slots, ring_write


class TelemetryState(PyTreeNode):
    # cumulative counters (int32: documented bound, ~2.1e9 events)
    generations: jax.Array = field(sharding=P())  # () generations observed
    evals: jax.Array = field(sharding=P())  # () candidate evaluations observed
    nan_candidates: jax.Array = field(sharding=P())  # () NaN elements across candidate leaves
    inf_candidates: jax.Array = field(sharding=P())  # () Inf elements across candidate leaves
    nan_fitness: jax.Array = field(sharding=P())  # () NaN fitness elements
    inf_fitness: jax.Array = field(sharding=P())  # () Inf fitness elements
    # best-so-far tracking, internal minimization convention
    best_key: jax.Array = field(sharding=P())  # () or (m,): per-objective ideal point for MO
    best_generation: jax.Array = field(sharding=P())  # () 1-based generation of last improvement
    stagnation: jax.Array = field(sharding=P())  # () generations since best improved
    # per-generation rings, slot = (generation - 1) % capacity
    ring_best: jax.Array = field(sharding=P())  # (K,) or (K, m), USER fitness convention
    ring_mean: jax.Array = field(sharding=P())  # (K,) or (K, m), finite-masked mean
    ring_diversity: jax.Array = field(sharding=P())  # (K,) mean per-dim std of the candidates
    # guardrail mirror (core/guardrail.py): cumulative on-device restarts
    # and the latest trigger bitmask of a GuardedAlgorithm driving this
    # run; stays 0 for unguarded algorithms (picked up in post_step)
    restarts: jax.Array = field(sharding=P())
    last_trigger: jax.Array = field(sharding=P())
    # surrogate mirror (workflows/surrogate.py, ISSUE 15): the TRUE
    # evaluation count and triggered-fallback count of a screening
    # SurrogateWorkflow driving this run — the headline `evals` counter
    # above counts batch ROWS, which under screening includes the inert
    # filled rows; these two make the real spend visible in report().
    # Always materialized (zeros for every other workflow) so the state
    # structure — and with it the checkpoint config fingerprint — never
    # changes mid-run.
    sur_true_evals: jax.Array = field(sharding=P(), default=None)
    sur_fallback_gens: jax.Array = field(sharding=P(), default=None)


class TelemetryMonitor(Monitor):
    """On-device run telemetry with no host callbacks anywhere.

    Args:
        capacity: ring size — the last ``capacity`` generations' best/mean
            fitness and diversity are kept on device; older slots are
            overwritten (ring semantics, exactly like
            ``EvalMonitor(history_capacity=K)``).
        num_objectives: fitness arity. ``1`` tracks scalar best/mean;
            ``m > 1`` tracks the per-objective ideal point and
            per-objective means (rings become ``(capacity, m)``).
            Declared up front so the state structure is static from
            ``init`` — no lazy buffers, no retrace beyond the workflow's
            own first-step peel.

    All fitness values are reported in the USER's direction convention
    (the workflow un-flips ``opt_direction`` before ``post_eval``, so a
    maximization run's best comes back positive); improvement/stagnation
    honor the direction internally. Counters are int32.
    """

    def __init__(self, capacity: int = 128, num_objectives: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if num_objectives < 1:
            raise ValueError(
                f"num_objectives must be >= 1, got {num_objectives}"
            )
        self.capacity = capacity
        self.num_objectives = num_objectives
        self.opt_direction = jnp.ones((1,), dtype=jnp.float32)

    def hooks(self):
        return ("post_eval", "post_step")

    def init(self, key: Optional[jax.Array] = None) -> TelemetryState:
        K, m = self.capacity, self.num_objectives
        stat_shape = () if m == 1 else (m,)
        ring_shape = (K,) if m == 1 else (K, m)
        i32 = lambda: jnp.zeros((), dtype=jnp.int32)  # noqa: E731
        return TelemetryState(
            generations=i32(),
            evals=i32(),
            nan_candidates=i32(),
            inf_candidates=i32(),
            nan_fitness=i32(),
            inf_fitness=i32(),
            best_key=jnp.full(stat_shape, jnp.inf, dtype=jnp.float32),
            best_generation=i32(),
            stagnation=i32(),
            ring_best=jnp.full(ring_shape, jnp.inf, dtype=jnp.float32),
            ring_mean=jnp.full(ring_shape, jnp.inf, dtype=jnp.float32),
            ring_diversity=jnp.full((K,), jnp.inf, dtype=jnp.float32),
            restarts=i32(),
            last_trigger=i32(),
            sur_true_evals=i32(),
            sur_fallback_gens=i32(),
        )

    # ------------------------------------------------------------------ hook
    def post_eval(
        self, mstate: TelemetryState, cand: Any, fitness: jax.Array
    ) -> TelemetryState:
        m = self.num_objectives
        if m == 1 and fitness.ndim != 1:
            raise ValueError(
                f"TelemetryMonitor(num_objectives=1) got fitness of shape "
                f"{fitness.shape}; pass num_objectives={fitness.shape[-1]} "
                "for multi-objective runs"
            )
        if m > 1 and (fitness.ndim != 2 or fitness.shape[-1] != m):
            raise ValueError(
                f"TelemetryMonitor(num_objectives={m}) got fitness of "
                f"shape {fitness.shape}"
            )
        fitness = fitness.astype(jnp.float32)

        # -- NaN/Inf element counts -----------------------------------------
        def _count(pred, tree):
            leaves = [
                x for x in jax.tree.leaves(tree)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            ]
            total = jnp.zeros((), dtype=jnp.int32)
            for x in leaves:
                total = total + jnp.sum(pred(x)).astype(jnp.int32)
            return total

        nan_cand = mstate.nan_candidates + _count(jnp.isnan, cand)
        inf_cand = mstate.inf_candidates + _count(jnp.isinf, cand)
        nan_fit = mstate.nan_fitness + _count(jnp.isnan, fitness)
        inf_fit = mstate.inf_fitness + _count(jnp.isinf, fitness)

        # -- population diversity: mean per-dim std over the batch axis.
        # Finite-masked like the fitness stats (matches jnp.std when every
        # element is finite): one poison candidate must not NaN the whole
        # diversity trajectory — the counters record the poison instead.
        float_leaves = [
            jnp.asarray(x, jnp.float32)
            for x in jax.tree.leaves(cand)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
        ]
        std_sum = jnp.zeros((), dtype=jnp.float32)
        n_dims = 0
        for x in float_leaves:
            flat = x.reshape(x.shape[0], -1)
            ok = jnp.isfinite(flat)
            n = jnp.maximum(jnp.sum(ok.astype(jnp.float32), axis=0), 1.0)
            mean = jnp.sum(jnp.where(ok, flat, 0.0), axis=0) / n
            var = jnp.sum(jnp.where(ok, (flat - mean) ** 2, 0.0), axis=0) / n
            std_sum = std_sum + jnp.sum(jnp.sqrt(var))
            n_dims += flat.shape[1]
        diversity = std_sum / max(n_dims, 1)

        # -- per-generation fitness stats, internal minimization key --------
        direction = self.opt_direction[0] if m == 1 else self.opt_direction
        key_fit = fitness * direction
        finite = jnp.isfinite(key_fit)
        masked_key = jnp.where(finite, key_fit, jnp.inf)
        gen_best_key = jnp.min(masked_key, axis=0)  # () or (m,)
        n_finite = jnp.sum(finite.astype(jnp.float32), axis=0)
        gen_mean = jnp.sum(
            jnp.where(finite, fitness, 0.0), axis=0
        ) / jnp.maximum(n_finite, 1.0)

        # -- stagnation / best-so-far ---------------------------------------
        improved = jnp.any(gen_best_key < mstate.best_key)
        best_key = jnp.minimum(mstate.best_key, gen_best_key)
        generations = mstate.generations + 1
        best_generation = jnp.where(
            improved, generations, mstate.best_generation
        )
        stagnation = jnp.where(improved, 0, mstate.stagnation + 1)

        # -- ring update (shared discipline: monitors/common.py) ------------
        upd = lambda buf, row: ring_write(buf, row, mstate.generations)  # noqa: E731
        return TelemetryState(
            generations=generations,
            evals=mstate.evals + jnp.int32(fitness.shape[0]),
            nan_candidates=nan_cand,
            inf_candidates=inf_cand,
            nan_fitness=nan_fit,
            inf_fitness=inf_fit,
            best_key=best_key,
            best_generation=best_generation,
            stagnation=stagnation,
            ring_best=upd(mstate.ring_best, gen_best_key * direction),
            ring_mean=upd(mstate.ring_mean, gen_mean),
            ring_diversity=upd(mstate.ring_diversity, diversity),
            restarts=mstate.restarts,  # owned by post_step (guardrail mirror)
            last_trigger=mstate.last_trigger,
            sur_true_evals=mstate.sur_true_evals,  # owned by post_step
            sur_fallback_gens=mstate.sur_fallback_gens,
        )

    def post_step(self, mstate: TelemetryState, wf_state: Any) -> TelemetryState:
        """Mirror a GuardedAlgorithm's health counters (restart count and
        latest trigger bitmask) into the telemetry state, so they reach
        ``report()``/``run_report()`` without the caller touching the
        algorithm state. Structural (trace-time) detection: unguarded
        workflows compile this hook to a no-op."""
        astate = getattr(wf_state, "algo", None)
        if hasattr(astate, "restarts") and hasattr(astate, "last_trigger"):
            mstate = mstate.replace(
                restarts=jnp.asarray(astate.restarts, jnp.int32),
                last_trigger=jnp.asarray(astate.last_trigger, jnp.int32),
            )
        # surrogate mirror (workflows/surrogate.py): structural
        # detection, compiles to a no-op for every other workflow
        sur = getattr(wf_state, "sur", None)
        if hasattr(sur, "true_evals") and hasattr(sur, "fallback_gens"):
            mstate = mstate.replace(
                sur_true_evals=jnp.asarray(sur.true_evals, jnp.int32),
                sur_fallback_gens=jnp.asarray(sur.fallback_gens, jnp.int32),
            )
        return mstate

    # --------------------------------------------------------------- getters
    def get_best_fitness(self, mstate: TelemetryState) -> jax.Array:
        """Best-so-far (SO) / per-objective ideal point (MO), in the
        user's direction convention. Jit-safe."""
        direction = (
            self.opt_direction[0]
            if self.num_objectives == 1
            else self.opt_direction
        )
        return mstate.best_key * direction

    def _ring_slots(self, mstate: TelemetryState):
        return ring_slots(mstate.generations, self.capacity)

    def get_trajectory(self, mstate: TelemetryState) -> dict:
        """Chronological per-generation history of the last
        ``min(generations, capacity)`` generations. Eager (host) utility;
        under jit read the ring fields directly (ring layout,
        slot = (generation - 1) % capacity, inf-padded)."""
        slots = self._ring_slots(mstate)
        count = int(mstate.generations)
        best = np.asarray(mstate.ring_best)
        mean = np.asarray(mstate.ring_mean)
        div = np.asarray(mstate.ring_diversity)
        return {
            "generation": list(range(count - len(slots) + 1, count + 1)),
            "best": [best[s].tolist() for s in slots],
            "mean": [mean[s].tolist() for s in slots],
            "diversity": [float(div[s]) for s in slots],
        }

    def counter_tracks(self, mstate: TelemetryState) -> dict:
        """Generation-indexed counter samples for the Chrome-trace
        exporter (:func:`evox_tpu.core.instrument.write_chrome_trace`):
        ``{track_name: [(generation, value), ...]}``. Per-generation
        tracks come from the on-device rings (best/mean fitness,
        diversity — the last ``min(generations, capacity)`` generations);
        cumulative counters without a ring (stagnation, restarts, NaN
        fitness elements) contribute their final value as a single sample
        at the last generation. Non-finite samples are the exporter's
        problem (it skips them) — this stays a faithful read-back."""
        traj = self.get_trajectory(mstate)
        gens = traj["generation"]
        tracks: dict = {}
        if self.num_objectives == 1:
            tracks["telemetry/best_fitness"] = list(zip(gens, traj["best"]))
            tracks["telemetry/mean_fitness"] = list(zip(gens, traj["mean"]))
        else:
            for j in range(self.num_objectives):
                tracks[f"telemetry/best_obj{j}"] = [
                    (g, row[j]) for g, row in zip(gens, traj["best"])
                ]
        tracks["telemetry/diversity"] = list(zip(gens, traj["diversity"]))
        last = int(mstate.generations)
        for name, v in (
            ("stagnation", mstate.stagnation),
            ("restarts", mstate.restarts),
            ("nan_fitness", mstate.nan_fitness),
        ):
            tracks[f"telemetry/{name}"] = [(last, int(v))]
        return tracks

    # The bitwise-stable counter surface: integer accumulators whose bits
    # are identical across device-mesh layouts (each is a count of exact
    # events — no float reduction whose summation order a resharding could
    # permute). The float rings and best_key are deliberately OUT: a mean
    # over a differently-laid-out population batch may legally differ in
    # the last ulp, and a fingerprint that flickers across layouts is
    # worse than none.
    STABLE_SURFACE = (
        "generations",
        "evals",
        "nan_candidates",
        "inf_candidates",
        "nan_fitness",
        "inf_fitness",
        "best_generation",
        "stagnation",
        "restarts",
        "last_trigger",
        "sur_true_evals",
        "sur_fallback_gens",
    )

    def fingerprint(self, mstate: TelemetryState, stable: bool = False) -> str:
        """Host-side bit-identity witness over the telemetry state.

        Default (``stable=False``): SHA-256 over the EXACT bytes of every
        telemetry field (rings included). Two runs whose fingerprints
        match produced byte-identical trajectories and counters; the
        supervisor chaos law (tests/test_supervisor.py) asserts a
        faulted-and-healed run fingerprints identically to the clean run,
        and a post-mortem can cite the fingerprint as evidence of how far
        a run got before aborting. This form is layout-DEPENDENT: the
        float rings hold reduction results (mean fitness, diversity)
        whose bits can shift across device-mesh layouts.

        ``stable=True``: the attestor reduction (:func:`evox_tpu.core.
        attest.host_state_digest`) over only the integer counter surface
        (``STABLE_SURFACE``) — bitwise-identical across 1/4/8-device
        layouts because every field is an exact event count. Use this
        form for cross-layout equality laws; use the default when both
        runs share one layout and you want the rings covered too. The two
        forms are different widths (48 vs 64 hex chars) so they can never
        be confused for one another.
        """
        if stable:
            from ..core.attest import digest_hex, host_state_digest

            surface = {
                name: getattr(mstate, name) for name in self.STABLE_SURFACE
            }
            return digest_hex(host_state_digest(surface))
        import hashlib

        h = hashlib.sha256()
        for path, leaf in jax.tree_util.tree_flatten_with_path(mstate)[0]:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        return h.hexdigest()

    def report(self, mstate: TelemetryState) -> dict:
        """One strictly JSON-serializable dict of every device counter
        plus the ring trajectory (non-finite values → ``None``) — the
        device half of :func:`evox_tpu.core.instrument.run_report`."""
        best = np.asarray(self.get_best_fitness(mstate))
        return sanitize_json({
            "generations": int(mstate.generations),
            "evals": int(mstate.evals),
            "best_fitness": best.tolist(),
            "best_generation": int(mstate.best_generation),
            "stagnation": int(mstate.stagnation),
            "nan_candidates": int(mstate.nan_candidates),
            "inf_candidates": int(mstate.inf_candidates),
            "nan_fitness": int(mstate.nan_fitness),
            "inf_fitness": int(mstate.inf_fitness),
            "restarts": int(mstate.restarts),
            "last_trigger": int(mstate.last_trigger),
            "sur_true_evals": int(mstate.sur_true_evals),
            "sur_fallback_gens": int(mstate.sur_fallback_gens),
            "capacity": self.capacity,
            "num_objectives": self.num_objectives,
            "trajectory": self.get_trajectory(mstate),
        })

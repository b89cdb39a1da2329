"""Operator attribution: the uniform tag vocabulary + success/credit
contract behind search-dynamics observability (ISSUE 19).

An :class:`Attribution` is what an algorithm's ``tell`` already knows the
moment it selects survivors: which slot each candidate targets
(``parent_idx``), which variation operator produced it (``op_tag``),
whether it replaced its parent (``success``), and how much fitness it
gained (``improvement``, internal minimize direction, clipped to 0 for
non-improving candidates). Adaptive DE variants (SaDE/JaDE/CoDE/SHADE)
compute exactly this bookkeeping internally for self-adaptation; the
helpers here are those expressions factored out *verbatim* so attribution
reads what the algorithm already knows — the adaptive-DE regression tests
(tests/test_lineage.py) pin the refactor bit-identical to the pre-PR
adaptation goldens.

Algorithms that carry an ``attrib`` field in their state publish it for
``monitors/lineage.py``'s :class:`LineageMonitor`, which folds it into
on-device rings and a per-operator credit ledger (attempts, successes,
improvement mass). Algorithms without the field (ES/PSO/MO families) are
tagged by the monitor at the selection boundary instead — same ledger,
coarser tags. Everything here is pure jittable math: zero host callbacks
(pinned by tests/test_no_host_callbacks.py), so the contract holds inside
the fused run loop and under ``vmap``.

No reference analog (PARITY row 63); design sources are the PBT/Fiber
per-member provenance arcs (PAPERS.md).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .distributed import POP_AXIS
from .struct import PyTreeNode, field

__all__ = [
    "OP_NONE",
    "OP_INIT",
    "OP_SAMPLE",
    "OP_VELOCITY",
    "OP_DE_RAND_1",
    "OP_DE_RAND_2",
    "OP_DE_RAND_TO_BEST_2",
    "OP_DE_CUR_TO_RAND_1",
    "OP_DE_CUR_TO_PBEST_1",
    "OP_DE_BEST",
    "OP_CROSSOVER",
    "OP_MUTATION",
    "N_OPS",
    "OP_NAMES",
    "SADE_STRATEGY_TAGS",
    "CODE_STRATEGY_TAGS",
    "Attribution",
    "de_variant_tag",
    "success_mask",
    "improvement_mass",
    "slot_attribution",
    "strategy_success_counts",
    "lehmer_mean_of_successful",
    "arithmetic_mean_of_successful",
    "op_credit",
    "argsort_inverse",
    "find_attribution",
]

# ---------------------------------------------------------------- vocabulary
# A single flat namespace so ledgers from different algorithms are
# comparable. Growing it is append-only: tags are persisted in lineage
# rings and run_report ledgers, so renumbering would corrupt forensics
# across checkpoint resumes.
OP_NONE = 0  # no attribution recorded (padding / pre-first-tell)
OP_INIT = 1  # initial population sampling (generation 0)
OP_SAMPLE = 2  # distribution sampling (ES/CMA-family ask)
OP_VELOCITY = 3  # PSO velocity update
OP_DE_RAND_1 = 4  # DE/rand/1/bin
OP_DE_RAND_2 = 5  # DE/rand/2/bin
OP_DE_RAND_TO_BEST_2 = 6  # DE/rand-to-best/2/bin
OP_DE_CUR_TO_RAND_1 = 7  # DE/current-to-rand/1
OP_DE_CUR_TO_PBEST_1 = 8  # DE/current-to-pbest/1 (JaDE/SHADE)
OP_DE_BEST = 9  # DE/best/n/bin
OP_CROSSOVER = 10  # GA crossover (MO selection boundary)
OP_MUTATION = 11  # GA mutation / unclassified variation
N_OPS = 12

OP_NAMES = (
    "none",
    "init",
    "sample",
    "velocity",
    "de_rand_1",
    "de_rand_2",
    "de_rand_to_best_2",
    "de_cur_to_rand_1",
    "de_cur_to_pbest_1",
    "de_best",
    "crossover",
    "mutation",
)
assert len(OP_NAMES) == N_OPS

# SaDE's strategy axis (sade.py ask: v0..v3) in vocabulary terms
SADE_STRATEGY_TAGS = (
    OP_DE_RAND_1,
    OP_DE_RAND_TO_BEST_2,
    OP_DE_RAND_2,
    OP_DE_CUR_TO_RAND_1,
)
# CoDE's trial axis (code.py ask: t1..t3)
CODE_STRATEGY_TAGS = (OP_DE_RAND_1, OP_DE_RAND_2, OP_DE_CUR_TO_RAND_1)


def de_variant_tag(base_vector: str, n_diff: int) -> int:
    """The vocabulary tag for a plain-DE configuration (static)."""
    if base_vector == "best":
        return OP_DE_BEST
    if n_diff == 1:
        return OP_DE_RAND_1
    if n_diff == 2:
        return OP_DE_RAND_2
    return OP_MUTATION


# ----------------------------------------------------------------- contract


class Attribution(PyTreeNode):
    """Per-slot attribution for one generation's selection.

    All fields are population-leading, one row per *surviving slot* (the
    algorithm's ``pop_size``, even when the evaluated batch was wider —
    CoDE folds its 3-trials-per-parent axis before attributing). Fitness
    quantities are in the algorithm-internal minimize direction.
    """

    parent_idx: jax.Array = field(sharding=P(POP_AXIS))  # (pop,) i32
    op_tag: jax.Array = field(sharding=P(POP_AXIS))  # (pop,) i32
    success: jax.Array = field(sharding=P(POP_AXIS))  # (pop,) bool
    # credit mass must stay f32 between steps — bf16 storage would shear
    # the ledger sums the v13 validator cross-checks (explicit opt-out)
    improvement: jax.Array = field(sharding=P(POP_AXIS), storage=False)  # (pop,) f32

    @staticmethod
    def empty(pop_size: int) -> "Attribution":
        return Attribution(
            parent_idx=jnp.arange(pop_size, dtype=jnp.int32),
            op_tag=jnp.full((pop_size,), OP_INIT, jnp.int32),
            success=jnp.zeros((pop_size,), bool),
            improvement=jnp.zeros((pop_size,), jnp.float32),
        )


def success_mask(new_fitness: jax.Array, prev_fitness: jax.Array) -> jax.Array:
    """The greedy-selection success mask, exactly as the DE family writes
    it: strict improvement over the incumbent (de.py:112, sade.py:123,
    jade.py:116, shade.py:106)."""
    return new_fitness < prev_fitness

def improvement_mass(
    new_fitness: jax.Array, prev_fitness: jax.Array, success: jax.Array
) -> jax.Array:
    """Clipped per-slot fitness gain. The first greedy tell improves on an
    ``inf`` incumbent — that is initialization credit, not operator
    credit, so non-finite incumbents contribute zero mass."""
    gain = prev_fitness - new_fitness
    return jnp.where(
        success & jnp.isfinite(prev_fitness), gain, jnp.float32(0.0)
    ).astype(jnp.float32)


def slot_attribution(
    new_fitness: jax.Array,
    prev_fitness: jax.Array,
    op_tag,
    parent_idx: jax.Array | None = None,
) -> Attribution:
    """Attribution for 1:1 slot-descent selection (every DE variant: slot
    ``i``'s trial competes only with parent ``i``). ``op_tag`` may be a
    scalar (one operator for the whole generation) or a (pop,) array."""
    n = new_fitness.shape[0]
    succ = success_mask(new_fitness, prev_fitness)
    tags = jnp.broadcast_to(jnp.asarray(op_tag, jnp.int32), (n,))
    if parent_idx is None:
        parent_idx = jnp.arange(n, dtype=jnp.int32)
    return Attribution(
        parent_idx=parent_idx.astype(jnp.int32),
        op_tag=tags,
        success=succ,
        improvement=improvement_mass(new_fitness, prev_fitness, succ),
    )


def strategy_success_counts(
    success: jax.Array, strategy: jax.Array, n_strategy: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """SaDE's per-strategy success/failure bookkeeping, verbatim
    (sade.py:124-126 pre-refactor): one-hot the chosen strategies and sum
    the (pop,) success mask through them. Returns ``(succ, fail, onehot)``
    with the one-hot reused for the CR-memory update."""
    onehot = jax.nn.one_hot(strategy, n_strategy)
    succ = (success[:, None] * onehot).sum(axis=0)
    fail = ((~success)[:, None] * onehot).sum(axis=0)
    return succ, fail, onehot


def lehmer_mean_of_successful(values: jax.Array, success: jax.Array) -> jax.Array:
    """JaDE/SHADE F adaptation: Lehmer mean over successful parameters,
    verbatim (jade.py:120-122 pre-refactor)."""
    s = jnp.where(success, values, 0.0)
    return jnp.sum(s**2) / jnp.maximum(jnp.sum(s), 1e-12)


def arithmetic_mean_of_successful(
    values: jax.Array, success: jax.Array, n_success: jax.Array
) -> jax.Array:
    """JaDE CR adaptation: arithmetic mean over successful parameters,
    verbatim (jade.py:121-123 pre-refactor). ``n_success`` is passed in so
    the caller's existing count is reused (bit-identity)."""
    s = jnp.where(success, values, 0.0)
    return jnp.sum(s) / jnp.maximum(n_success, 1)


def op_credit(
    attrib: Attribution, n_ops: int = N_OPS
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one generation's attribution into ledger increments:
    ``(attempts, successes, improvement)`` per operator tag — attempts
    count every candidate that carried the tag, successes those that
    replaced their parent, improvement the clipped fitness mass. The
    ledger identity ``successes[tag] == strategy_success_counts(...)[0]``
    for DE strategies is asserted by tests/test_lineage.py."""
    onehot = jax.nn.one_hot(attrib.op_tag, n_ops, dtype=jnp.int32)
    attempts = onehot.sum(axis=0)
    successes = (attrib.success[:, None].astype(jnp.int32) * onehot).sum(axis=0)
    improvement = (
        attrib.improvement[:, None] * onehot.astype(jnp.float32)
    ).sum(axis=0)
    return attempts, successes, improvement


def argsort_inverse(order: jax.Array) -> jax.Array:
    """Parent map for sort-based survivor selection: when a tell places
    the candidate at pre-selection position ``order[i]`` into slot ``i``
    (the usual truncation/sort pattern), the slot->origin map IS
    ``order`` itself — and when a tell instead says "candidate ``i`` went
    to slot ``order[i]``", this inverse turns that scatter into the
    gather the lineage ring wants. One pop-sized scatter, O(n)."""
    n = order.shape[0]
    return (
        jnp.zeros((n,), jnp.int32)
        .at[order]
        .set(jnp.arange(n, dtype=jnp.int32))
    )


def find_attribution(algo_state):
    """Structurally locate an ``attrib`` field on an algorithm state,
    unwrapping guardrail/recenter wrappers (``.inner``). Trace-time
    (hasattr on the state object), so it is free inside jit — the same
    discipline as TelemetryMonitor's restart mirror. Returns ``None`` if
    the algorithm does not publish attribution."""
    seen = 0
    while algo_state is not None and seen < 8:
        attrib = getattr(algo_state, "attrib", None)
        if attrib is not None:
            return attrib
        algo_state = getattr(algo_state, "inner", None)
        seen += 1
    return None

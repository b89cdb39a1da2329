"""Non-dominated sorting + crowding distance (reference:
src/evox/operators/selection/non_dominate.py:13-232).

TPU-first formulation: the dominance matrix is built lane-oriented (a
static loop over the small objective axis keeps the population in the TPU
lane dimension — see kernels/dominance.py) and bit-packed 32 dominators
per uint32 word; front peeling runs as a ``lax.while_loop`` whose body is
one fused ``popcount(and)`` reduction over the packed matrix — each peel
iteration streams n^2/8 bytes instead of doing data-dependent
gather/scatter. No host fallback is needed (the reference's "host" numpy
mode exists because data-dependent loops were slow on its backends;
XLA:TPU handles the while_loop natively).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.distributed import POP_AXIS
from ...core.instrument import CROWDING, DOMINANCE_BUILD, PEEL, SURVIVORS, scope
from ...kernels.dominance import pack_dominator_rows, packed_dominance
from ...kernels.topk import default_use_kernel, partial_topk
from ...utils.common import dominate_relation

INF = jnp.inf


def _mesh_axis_size(mesh, axis_name: str) -> int:
    if mesh is None:
        return 1
    return dict(mesh.shape).get(axis_name, 1)


def _pack_front(front: jax.Array, n_words: int) -> jax.Array:
    """Bit-pack a boolean front vector ``(n,)`` into ``(n_words,)`` uint32
    (bit ``k`` of word ``w`` <- row ``32w + k``)."""
    n = front.shape[0]
    bit_weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        jnp.pad(front, (0, n_words * 32 - n))
        .reshape(n_words, 32)
        .astype(jnp.uint32)
        * bit_weights[None, :],
        axis=1,
        dtype=jnp.uint32,
    )


@scope(PEEL)
def _peel_fronts(count: jax.Array, stop, n_words: int, delta_fn):
    """The front-peel ``while_loop`` shared by the replicated and
    mesh-sharded sorts — ONE source of truth for the rank/done/cut
    bookkeeping so the sharded path's bit-identical guarantee cannot
    drift.

    ``count``: (n,) int32 domination counts. ``delta_fn(front_words)``
    maps the packed current front ``(n_words,)`` to the (n,) int32 count
    of current-front dominators per column — a local popcount pass for
    the replicated sort, slab popcount + ``psum`` for the sharded one.
    Each iteration peels one front: ranked rows get rank ``r``, their
    domination contributions are subtracted, and processed rows drop to
    -1 so they never re-enter. Returns ``(rank, cut)`` where unranked
    rows hold the sentinel ``n`` and ``cut`` is the first rank whose
    cumulative front sizes reach ``stop`` (the "worst admitted rank" of
    a ``stop``-sized environmental selection — known for free here,
    saving the O(n log n) ``jnp.sort(rank)`` pass).
    """
    n = count.shape[0]
    rank = jnp.full((n,), n, dtype=jnp.int32)  # sentinel: unranked
    front = count == 0

    def cond(carry):
        _, _, front, _, done, _ = carry
        return jnp.any(front) & (done < stop)

    def body(carry):
        rank, count, front, r, done, cut = carry
        rank = jnp.where(front, r, rank)
        done = done + jnp.sum(front, dtype=jnp.int32)
        cut = jnp.where((done >= stop) & (cut == n), r, cut)
        delta = delta_fn(_pack_front(front, n_words))
        count = count - delta - front.astype(jnp.int32)
        return rank, count, count == 0, r + 1, done, cut

    rank, _, _, _, _, cut = jax.lax.while_loop(
        cond,
        body,
        (rank, count, front, jnp.int32(0), jnp.int32(0), jnp.int32(n)),
    )
    return rank, cut


def non_dominated_sort(
    fitness: jax.Array,
    until: Optional[int] = None,
    return_cut_rank: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
    axis_name: str = POP_AXIS,
):
    """Pareto-rank each row of ``fitness`` (n, m); rank 0 = non-dominated.

    Minimization convention. With ``until=k`` the peeling stops once at
    least ``k`` individuals have been ranked — environmental selection only
    needs fronts up to the cut, so this roughly halves the peel iterations
    on a merged parent+offspring population. Unranked rows get the sentinel
    rank ``n`` (worse than every real rank).

    ``return_cut_rank=True`` additionally returns the rank at which the
    cumulative front sizes first reach ``until`` — the "worst admitted
    rank" of environmental selection. The peel loop knows it for free,
    which saves the O(n log n) ``jnp.sort(rank)`` pass selection would
    otherwise spend deriving it.

    The dominance matrix is BIT-PACKED along the dominator axis: 32 rows
    per uint32 word, so each peel iteration is a fused
    ``popcount(front_word & dom_word)`` reduction reading n^2/8 bytes —
    8x less HBM traffic than an int8 matvec. The build itself is
    VPU-bound and lane-layout-sensitive — see kernels/dominance.py. What
    each costs on the chip at merged n=100000: PERF.md section 5
    (``tell_dominance_ms``, ``tell_peel_ms``).

    With ``mesh`` (holding a >1-sized ``axis_name`` axis) the O(n²)
    dominance build AND every peel pass are row-sharded across the mesh
    via ``shard_map`` — see :func:`_non_dominated_sort_sharded`. Ranks are
    bit-identical to the replicated path (integer computation), so sharded
    environmental selection matches single-device selection exactly.
    """
    if _mesh_axis_size(mesh, axis_name) > 1:
        return _non_dominated_sort_sharded(
            fitness, mesh, until, return_cut_rank, axis_name
        )
    n = fitness.shape[0]
    stop = n if until is None else min(until, n)
    n_words = (n + 31) // 32
    # compare + pack + count: the ``dominance_pack`` kernel on the TPU writes
    # the packed matrix once (the bool (n, n) matrix never exists in HBM),
    # the identical-output XLA build elsewhere
    with scope(DOMINANCE_BUILD):
        dom_packed, count = packed_dominance(fitness)

    def delta_fn(front_words):
        # remove the current front's domination counts in one fused
        # and+popcount pass over the packed matrix
        return jnp.sum(
            jax.lax.population_count(
                jnp.bitwise_and(front_words[:, None], dom_packed)
            ),
            axis=0,
            dtype=jnp.int32,
        )

    rank, cut = _peel_fronts(count, stop, n_words, delta_fn)
    if return_cut_rank:
        return rank, cut
    return rank


def _non_dominated_sort_sharded(
    fitness: jax.Array,
    mesh: jax.sharding.Mesh,
    until: Optional[int],
    return_cut_rank: bool,
    axis_name: str,
):
    """Mesh-sharded non-dominated sort: identical outputs to the replicated
    path, with the O(n²) work row-sharded over ``axis_name``.

    The packed dominance matrix ``(n_words, n)`` is sharded along its WORD
    (dominator) axis: each device builds and keeps only its slab of
    ``n_words/D`` words — it compares its ~``n/D`` dominator rows against
    the full (replicated, small) fitness matrix and bit-packs locally, so
    the build's compare work, the slab's HBM residency, and every peel
    pass's ``popcount(front & packed)`` read are all 1/D per device. Per
    peel iteration the only communication is one ``psum`` of the (n,)
    int32 partial domination-count delta — 4n bytes over ICI vs the n²/8
    bytes of matrix each device no longer reads. Rank/count/front stay
    replicated (O(n) work), so the returned ranks are bit-identical to the
    single-device path and everything downstream (crowding, lexsort) is
    unchanged.

    This is what the reference's pmap/Ray stack never did: its
    non-dominated sort ran fully replicated on every worker (reference
    src/evox/operators/selection/non_dominate.py:32-115 has no sharded
    form), so multi-device NSGA-II scaled evaluation but not selection —
    the hot path at large populations.

    Dominator rows are padded to ``32 * D`` granularity with ``+inf``
    rows, which dominate nothing (``<=`` fails against every real row),
    so padding only appends all-zero words.
    """
    n, m = fitness.shape
    D = _mesh_axis_size(mesh, axis_name)
    stop = n if until is None else min(until, n)
    n_words = (n + 31) // 32
    words_per = -(-n_words // D)
    rows_pad = words_per * D * 32
    fit_rows = jnp.pad(
        fitness, ((0, rows_pad - n), (0, 0)), constant_values=jnp.inf
    )

    def island(local_rows: jax.Array, fit: jax.Array):
        # local_rows: this device's (rows_pad / D, m) dominator slab;
        # fit: the full (n, m) fitness, replicated (n·m floats — tiny)
        with scope(DOMINANCE_BUILD):
            dom_local = dominate_relation(local_rows, fit)
            # (words_per, n): this device's slab of the packed matrix
            packed_local = pack_dominator_rows(dom_local, words_per)
            count = jax.lax.psum(
                jnp.sum(
                    jax.lax.population_count(packed_local),
                    axis=0,
                    dtype=jnp.int32,
                ),
                axis_name,
            )
        word0 = jax.lax.axis_index(axis_name) * words_per

        def delta_fn(front_words):
            front_local = jax.lax.dynamic_slice(
                front_words, (word0,), (words_per,)
            )
            return jax.lax.psum(
                jnp.sum(
                    jax.lax.population_count(
                        jnp.bitwise_and(front_local[:, None], packed_local)
                    ),
                    axis=0,
                    dtype=jnp.int32,
                ),
                axis_name,
            )

        return _peel_fronts(count, stop, words_per * D, delta_fn)

    # check_vma=False: every output is derived from psum results (hence
    # genuinely replicated), but the device-varying dynamic_slice start
    # defeats the static replication analysis
    rank, cut = jax.shard_map(
        island,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(fit_rows, fitness)
    if return_cut_rank:
        return rank, cut
    return rank


@scope(CROWDING)
def crowding_distance(fitness: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """NSGA-II crowding distance per individual (n,), larger = less crowded.

    ``mask``: boolean (n,) — individuals outside the mask get ``-inf`` so they
    sort last; boundary individuals of each objective get ``+inf``.
    (reference: non_dominate.py:118-158)
    """
    n, m = fitness.shape
    if mask is None:
        mask = jnp.ones((n,), dtype=bool)
    num_valid = jnp.sum(mask.astype(jnp.int32))
    pos = jnp.arange(n)

    def per_objective(fv):
        fv_masked = jnp.where(mask, fv, INF)
        order = jnp.argsort(fv_masked)
        s = fv_masked[order]
        last = jnp.maximum(num_valid - 1, 0)
        f_range = jnp.maximum(s[last] - s[0], 1e-12)
        inner = (s[2:] - s[:-2]) / f_range
        d_sorted = jnp.concatenate([jnp.full((1,), INF), inner, jnp.full((1,), INF)])
        d_sorted = jnp.where(pos == last, INF, d_sorted)
        d_sorted = jnp.where(pos >= num_valid, -INF, d_sorted)
        d_sorted = jnp.nan_to_num(d_sorted, nan=0.0, posinf=INF, neginf=-INF)
        return jnp.zeros((n,)).at[order].set(d_sorted)

    return jnp.sum(jax.vmap(per_objective)(fitness.T), axis=0)


def crowding_distance_sort(fitness: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Indices sorted by descending crowding distance (reference :161-180)."""
    return jnp.argsort(-crowding_distance(fitness, mask))


def non_dominate_indices(
    fitness: jax.Array,
    topk: int,
    pop: Optional[jax.Array] = None,
    deduplicate: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Indices of the ``topk`` best by (rank, -crowding) environmental
    selection. With ``deduplicate`` (requires ``pop``), duplicate decision
    vectors are pushed to the back before ranking. ``mesh``: shard the
    O(n²) sort across its ``"pop"`` axis (same result)."""
    if deduplicate:
        n = pop.shape[0]
        _, idx = jnp.unique(pop, axis=0, size=n, return_index=True, fill_value=jnp.nan)
        is_first = jnp.zeros((n,), dtype=bool).at[idx].set(True)
        fitness = jnp.where(is_first[:, None], fitness, INF)
    # the peel loop reports the worst admitted rank for free (vs an
    # O(n log n) jnp.sort(rank) pass); crowding tie-break only matters
    # within that rank
    rank, worst_rank = non_dominated_sort(
        fitness, until=topk, return_cut_rank=True, mesh=mesh
    )
    crowd = crowding_distance(fitness, mask=rank == worst_rank)
    with scope(SURVIVORS):
        return jnp.lexsort((-crowd, rank))[:topk]


def non_dominate(
    pop: jax.Array,
    fitness: jax.Array,
    topk: int,
    deduplicate: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Environmental selection: keep the ``topk`` best by (rank, -crowding).

    (reference: non_dominate.py:183-222). ``pop`` may be a pytree with a
    leading population axis.
    """
    pop_leaf = pop if isinstance(pop, jax.Array) else jax.tree.leaves(pop)[0]
    order = non_dominate_indices(fitness, topk, pop_leaf, deduplicate, mesh)
    with scope(SURVIVORS):
        return jax.tree.map(lambda x: x[order], pop), fitness[order]


class NonDominate:
    """Class-form environmental selector (reference: non_dominate.py:225-232)."""

    def __init__(self, topk: int, deduplicate: bool = False, mesh=None):
        self.topk = topk
        self.deduplicate = deduplicate
        self.mesh = mesh

    def __call__(self, pop, fitness):
        return non_dominate(pop, fitness, self.topk, self.deduplicate, self.mesh)


def rank_crowding_truncate(
    fitness: jax.Array,
    k: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """NSGA-II environmental truncation: the ``k`` survivors of ``fitness``
    ``(n, m)`` by (Pareto rank asc, crowding distance desc on the cut
    front). Returns ``(order, ranks)`` — survivor indices into ``fitness``
    and their ranks. Shared by NSGA-II's ``tell`` and the GA-skeleton
    MOEAs' migration ingest (one source of truth for the truncation).
    ``mesh``: shard the O(n²) sort across its ``"pop"`` axis.

    The worst admitted rank comes from the peel loop's free cut-rank
    by-product — a ``jnp.sort(rank)[k-1]`` here would re-pay the
    O(n log n) pass that by-product removed.

    ``use_kernel`` (``None`` = backend default, currently off —
    kernels/topk.py): replace the O(n log n) full ``lexsort`` with the
    last-front decomposition the peel loop already paid for — ranks
    better than the cut are admitted wholesale by an O(n) stable
    cumsum-scatter compaction (no sort), and only the CUT front is
    actually selected on, by crowding distance through the blockwise
    partial-top-k kernel. The survivor SET is identical to the lexsort
    path (same rank admission, same crowding ties broken by lowest
    index); the survivor ORDER differs — auto-admitted fronts come back
    in index order rather than rank-major order — which is selection-
    law-equivalent for every caller (NSGA-II re-keys its mating
    tournament from the returned ranks/crowding, and the population is
    a set). Asserted in tests/test_topk.py."""
    rank, worst_rank = non_dominated_sort(
        fitness, until=k, return_cut_rank=True, mesh=mesh
    )
    crowd = crowding_distance(fitness, mask=rank == worst_rank)
    return _truncate(rank, worst_rank, crowd, k, use_kernel, interpret)


@scope(SURVIVORS)
def _truncate(rank, worst_rank, crowd, k: int, use_kernel, interpret: bool):
    """The survivors of :func:`rank_crowding_truncate`, once rank, cut and
    crowding are known."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        order = jnp.lexsort((-crowd, rank))[:k]
        return order, rank[order]
    n = rank.shape[0]
    better = rank < worst_rank  # whole fronts above the cut: all admitted
    n_better = jnp.sum(better, dtype=jnp.int32)  # < k by cut construction
    # stable O(n) compaction of the auto-admitted rows (index order)
    pos = jnp.cumsum(better.astype(jnp.int32)) - 1
    order = jnp.zeros((k,), dtype=jnp.int32).at[
        jnp.where(better, pos, k)
    ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    # cut front: fill the remaining k - n_better slots by crowding desc.
    # Non-front rows carry +inf keys; boundary members carry -inf (from
    # crowd=+inf) — the kernel's masked-min handles both exactly
    cut_key = jnp.where(rank == worst_rank, -crowd, jnp.inf)
    _, cut_idx = partial_topk(
        cut_key, k, use_kernel=True, interpret=interpret
    )
    j = jnp.arange(k, dtype=jnp.int32)
    slots = jnp.where(j < (k - n_better), n_better + j, k)
    order = order.at[slots].set(cut_idx, mode="drop")
    return order, rank[order]

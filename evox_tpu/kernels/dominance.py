"""Fused bit-packed Pareto-dominance matrix (Pallas TPU kernel).

``non_dominated_sort`` peels fronts off a bit-packed dominance matrix
(operators/selection/non_dominate.py). Building that matrix is the hot
part at large populations: the naive formulation
``all(x[:,None,:] <= y[None,:,:], -1)`` (reference
src/evox/utils/common.py:94-97) puts the tiny objective axis in the TPU
lane dimension (m of 128 lanes used) and materializes an (n, n) boolean
intermediate (~400 MB at n=20000) that is then re-read by the packing
reshape and the domination-count reduction.

This kernel fuses compare + bit-pack + count into one pass per (row-tile,
column-tile): each grid cell loads two thin fitness tiles, compares per
objective with n in the lane dimension, ORs/ANDs across the (static,
small) objective loop in vector registers, packs 32 dominator rows per
uint32 word in VMEM, and writes only the packed words — n^2/8 bytes of
HBM traffic instead of ~9 n^2. The domination count comes from one
popcount pass over the packed words.

The op is VPU-compute-bound (~2 n^2 m compares + pack logic), NOT
HBM-bound, so once the lane layout is fixed XLA's own fusion does the
same vector work as the kernel. The lane-oriented XLA fallback is the
default everywhere and is what the NSGA-II cell runs (PERF.md section 5:
the build is the cell's largest share of device time); the kernel stays
behind `use_pallas=True`, which no package code passes, and has not been
timed at the cell's n=100000 (ROADMAP S3(ii), D4).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..utils.common import dominate_relation

# Default tiles: 512 rows (16 words) x 2048 lanes (the op is
# compute-bound, so the tile matters little). VMEM per cell ~6 MB (dom +
# masks + words); 1024x4096 exceeds the 16 MB scoped-vmem limit.
_TILE_I = 512
_TILE_J = 2048


def _dominance_pack_kernel(x_ref, yt_ref, out_ref, *, m: int, tile_i: int, tile_j: int):
    """One (row-tile, column-tile) cell: compare, AND/OR across objectives,
    pack 32 rows per uint32 word.

    ``x_ref``: (TILE_I, m) row fitness tile; ``yt_ref``: (m, TILE_J)
    transposed column tile, so each objective is one sublane row and the
    compare broadcasts (TILE_I, 1) x (1, TILE_J) with n in the lane dim.
    """
    le = jnp.ones((tile_i, tile_j), dtype=jnp.bool_)
    lt = jnp.zeros((tile_i, tile_j), dtype=jnp.bool_)
    for k in range(m):  # m is static and small: unrolled, stays in vregs
        xk = x_ref[:, k : k + 1]
        yk = yt_ref[k : k + 1, :]
        le &= xk <= yk
        lt |= xk < yk
    # int32 throughout: Mosaic has no unsigned reductions, and the packing
    # sum is bit-exact in int32 (each row owns one distinct bit, so no
    # carries — bit 31 merely lands in the sign)
    dom = (le & lt).astype(jnp.int32)
    # bit k of word w <- row 32 w + k
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)
    for w in range(tile_i // 32):
        rows = dom[w * 32 : (w + 1) * 32, :] << shifts
        out_ref[w : w + 1, :] = jnp.sum(
            rows, axis=0, keepdims=True, dtype=jnp.int32
        )


def pack_dominator_rows(dom: jax.Array, n_words: int) -> jax.Array:
    """Bit-pack a boolean ``(rows, n)`` dominator matrix into ``(n_words,
    n)`` uint32 words (bit ``k`` of word ``w`` <- row ``32w + k``) via the
    reshape-multiply-reduce path. Shared by the XLA fallback below and the
    mesh-sharded sort's per-device slab build."""
    pad = n_words * 32 - dom.shape[0]
    bit_weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        jnp.pad(dom, ((0, pad), (0, 0)))
        .reshape(n_words, 32, dom.shape[1])
        .astype(jnp.uint32)
        * bit_weights[None, :, None],
        axis=1,
        dtype=jnp.uint32,
    )


# Above this population size the dense (n, n) bool intermediate of the
# one-shot build becomes the memory wall (n=100k -> 10 GB); the chunked
# build below caps it at (chunk_rows, n).
_DENSE_BUILD_MAX_N = 20_000
_BUILD_CHUNK_ROWS = 4096


def packed_dominance_reference(
    fitness: jax.Array,
    n_words: Optional[int] = None,
    chunk_rows: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pure-XLA fallback with identical outputs.

    Builds the matrix with ``dominate_relation`` (whose lane-oriented
    objective loop is the same layout the kernel uses), then packs via the
    reshape-multiply-reduce path. Beyond ``_DENSE_BUILD_MAX_N`` rows (or
    with an explicit ``chunk_rows``) the build runs as a ``lax.map`` over
    dominator-row slabs so the boolean intermediate never exceeds
    ``(chunk_rows, n)`` — the packed (n²/8-byte) matrix itself is the only
    O(n²) resident, which is what makes NSGA-II at pop=50k (merged
    n=100k: packed ~1.25 GB vs a ~10 GB dense bool) fit on one chip.
    ``+inf`` padding rows dominate nothing, so slab padding only appends
    zero words (same argument as the mesh-sharded build).
    """
    n, m = fitness.shape
    if n_words is None:
        n_words = (n + 31) // 32
    if chunk_rows is None:
        chunk_rows = n if n <= _DENSE_BUILD_MAX_N else _BUILD_CHUNK_ROWS
    if chunk_rows % 32 != 0:
        chunk_rows = ((chunk_rows + 31) // 32) * 32
    if chunk_rows >= n:
        dom = dominate_relation(fitness, fitness)
        packed = pack_dominator_rows(dom, n_words)
        count = jnp.sum(dom, axis=0, dtype=jnp.int32)
        return packed, count

    n_chunks = -(-n // chunk_rows)
    rows_pad = n_chunks * chunk_rows
    fit_rows = jnp.pad(
        fitness, ((0, rows_pad - n), (0, 0)), constant_values=jnp.inf
    )
    slabs = fit_rows.reshape(n_chunks, chunk_rows, m)

    def one(slab):
        return pack_dominator_rows(
            dominate_relation(slab, fitness), chunk_rows // 32
        )

    packed = jax.lax.map(one, slabs).reshape(n_chunks * (chunk_rows // 32), n)
    built = packed.shape[0]
    if built >= n_words:
        packed = packed[:n_words]
    else:  # caller requested extra word budget: zero-pad like the dense path
        packed = jnp.pad(packed, ((0, n_words - built), (0, 0)))
    count = jnp.sum(jax.lax.population_count(packed), axis=0, dtype=jnp.int32)
    return packed, count


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret", "tile_i", "tile_j")
)
def packed_dominance(
    fitness: jax.Array,
    use_pallas: bool = False,
    interpret: bool = False,
    tile_i: int = _TILE_I,
    tile_j: int = _TILE_J,
) -> Tuple[jax.Array, jax.Array]:
    """Bit-packed dominance matrix + domination counts.

    Returns ``(packed, count)`` where ``packed`` is ``(ceil(n/32), n)``
    uint32 with bit ``k`` of ``packed[w, j]`` set iff row ``32w + k``
    Pareto-dominates row ``j`` (minimization), and ``count[j]`` is the
    number of rows dominating ``j``.

    Args:
        fitness: ``(n, m)`` objective matrix.
        use_pallas: run the Pallas kernel instead of the XLA fallback.
            Default False: the op is VPU-bound either way and the
            fallback runs on every backend.
        interpret: run the kernel in interpreter mode (CPU testing).
    """
    if use_pallas:  # the fallback ignores tiling entirely
        if tile_i <= 0 or tile_i % 32 != 0:
            raise ValueError(
                f"tile_i must be a positive multiple of 32, got {tile_i}"
            )
        if tile_j <= 0 or tile_j % 128 != 0:
            raise ValueError(
                f"tile_j must be a positive multiple of 128, got {tile_j}"
            )
    n, m = fitness.shape
    n_words = (n + 31) // 32
    if not use_pallas:
        return packed_dominance_reference(fitness, n_words)

    pad_i = (-n) % tile_i
    pad_j = (-n) % tile_j
    # +inf padding rows/cols never dominate and are never dominated by a
    # padding peer (le holds but lt fails on all-equal +inf), and padded
    # COLUMNS are sliced off below, so only the harmless extra zero words
    # of padded ROWS remain
    fit_pad = jnp.pad(fitness, ((0, max(pad_i, pad_j)), (0, 0)), constant_values=jnp.inf)
    x = fit_pad[: n + pad_i]
    y_t = fit_pad[: n + pad_j].T  # (m, n_pad): objectives become sublanes
    grid = ((n + pad_i) // tile_i, (n + pad_j) // tile_j)
    kernel = functools.partial(
        _dominance_pack_kernel, m=m, tile_i=tile_i, tile_j=tile_j
    )
    packed = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_i, m), lambda i, j: (i, 0)),
            pl.BlockSpec((m, tile_j), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_i // 32, tile_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            ((n + pad_i) // 32, n + pad_j), jnp.int32
        ),
        interpret=interpret,
        name="dominance_pack",
    )(x, y_t)
    packed = jax.lax.bitcast_convert_type(packed[:n_words, :n], jnp.uint32)
    count = jnp.sum(
        jax.lax.population_count(packed), axis=0, dtype=jnp.int32
    )
    return packed, count

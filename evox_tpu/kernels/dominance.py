"""Bit-packed Pareto-dominance matrix: the ``dominance_pack`` kernel on the
TPU, a lane-oriented XLA build everywhere else.

``non_dominated_sort`` peels fronts off a bit-packed dominance matrix
(operators/selection/non_dominate.py). Building that matrix is the hot
part at large populations: the naive formulation
``all(x[:,None,:] <= y[None,:,:], -1)`` (reference
src/evox/utils/common.py:94-97) puts the tiny objective axis in the TPU
lane dimension (m of 128 lanes used) and materializes an (n, n) boolean
intermediate (~400 MB at n=20000) that is then re-read by the packing
reshape and the domination-count reduction.

On the TPU, ``packed_dominance`` runs the kernel: each grid cell compares
a tile of dominator rows against a tile of columns with n in the lane
dimension, ANDs/ORs across the (static, small) objective loop in vector
registers, packs 32 rows per uint32 word and writes the words once,
straight into the final ``(ceil(n/32), n)`` array in the row-major layout
the peel's ``popcount(front & packed)`` reads; it counts each column's
dominators as it goes (a popcount of each finished word). The XLA build (``packed_dominance_reference``) is
the path on other backends and the tests' reference; at the NSGA-II cell's
merged n = 100,000 it stacked its row slabs, transposed them in a copy and
sliced off the padded words: 9.5 ms a generation of the chip's time moving
the 1.25 GB matrix with no arithmetic (PERF.md section 6, PR 37, has both
builds' times and the kernel's tile sweep; section 5 the cell's breakdown,
read under ``tell_dominance_ms``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.common import dominate_relation

# Tiles of one grid cell: 512 dominator rows (16 words) x 2048 columns,
# walked ``_LANES`` columns at a time with ``_UNROLL`` bits a loop step, in
# values of ``_SUB`` vector registers. Chosen by sweeps on the chip (PERF.md
# section 6, PR 37).
_TILE_I = 512
_TILE_J = 2048
_LANES = 1024
_UNROLL = 4
_SUB = 2
_GROUP = 256  # rows that fill the 8 sublanes of one word row: 8 words


def _interleave_rows(rows: jax.Array) -> jax.Array:
    """Order a ``(R, m)`` block of dominator rows, ``R`` a multiple of 256,
    so that within each group of 256 rows, row ``32 s + r`` sits at ``8 r +
    s``: the 8 rows the kernel compares together then belong to 8 different
    words at the same bit ``r``, and packing is a select and an OR of whole
    vector registers, with no reduction across sublanes."""
    R, m = rows.shape
    return rows.reshape(R // _GROUP, 8, 32, m).transpose(0, 2, 1, 3).reshape(R, m)


def _dominance_pack_kernel(x_ref, y_ref, out_ref, cnt_ref, xs_ref, *, m: int, vregs: int):
    """One (row-tile, column-tile) cell: compare, AND/OR across objectives,
    pack 32 rows per word, and count each column's dominators.

    ``x_ref``: ``(tile_i, m)`` dominator rows in ``_interleave_rows`` order;
    ``y_ref``: ``(m, tile_j // 128, 8, 128)``, the column tile transposed,
    each objective's 128 columns repeated on 8 sublanes: one vector register
    as it is loaded; ``xs_ref``: ``(tile_i, 128 m)`` scratch holding each
    row's objectives broadcast across the lanes, filled at the row tile's
    first column tile; ``out_ref``: ``(tile_i // 32, tile_j)`` words;
    ``cnt_ref``: ``(1, 1, tile_j)``, the cell's dominators of each column.
    The loop works a chunk's ``vregs`` registers of columns in values of
    ``(_SUB, 8, 128)`` (Mosaic unrolls the leading axis, so the body traced
    and lowered on the host stays small): per register of 8 rows x 128
    columns, 2 m compares, the AND/OR across objectives, one select of bit
    ``r`` and one OR into an int32 accumulator (Mosaic has no unsigned
    vectors; the bits are the same), and once per 32 bits a popcount of the
    finished words.
    """
    tile_i, tile_j = x_ref.shape[0], y_ref.shape[1] * 128
    lanes = 128 * vregs

    @pl.when(pl.program_id(1) == 0)
    def _():
        def fill(t, carry):
            rows = pl.ds(pl.multiple_of(lax.mul(t, 8), 8), 8)
            for k in range(m):
                xs_ref[rows, pl.ds(k * 128, 128)] = lax.broadcast_in_dim(
                    x_ref[rows, pl.ds(k, 1)], (8, 128), (0, 1)
                )
            return carry

        lax.fori_loop(0, tile_i // 8, fill, 0)

    # lax, not jnp, in the loop: each jnp call is a jit of its own to trace,
    # and this body is traced and lowered again by every program that holds it
    sub = math.gcd(_SUB, vregs)
    shape = (sub, 8, 128)
    subs = range(vregs // sub)
    zeros = lax.full(shape, 0, jnp.int32)

    def chunk(c, carry):
        reg0 = lax.mul(c, vregs)
        ys = [[y_ref[k, pl.ds(lax.add(reg0, b * sub), sub)] for b in subs] for k in range(m)]
        col0 = lax.mul(c, lanes)

        def group(g, cnt):
            def bits(q, acc):
                acc = list(acc)
                r0 = lax.mul(q, _UNROLL)
                row0 = lax.add(lax.mul(g, _GROUP), lax.mul(r0, 8))
                for u in range(_UNROLL):  # Mosaic unrolls a loop wholly or not at all
                    rows = pl.ds(pl.multiple_of(lax.add(row0, 8 * u), 8), 8)
                    xs = [
                        lax.broadcast_in_dim(xs_ref[rows, pl.ds(k * 128, 128)], shape, (1, 2))
                        for k in range(m)
                    ]
                    bit = lax.broadcast(lax.shift_left(1, lax.add(r0, u)), shape)
                    for b in subs:
                        le = lt = None
                        for k in range(m):  # m is static and small
                            y = ys[k][b]
                            le_k, lt_k = lax.le(xs[k], y), lax.lt(xs[k], y)
                            le = le_k if le is None else lax.bitwise_and(le, le_k)
                            lt = lt_k if lt is None else lax.bitwise_or(lt, lt_k)
                        dom = lax.bitwise_and(le, lt)
                        acc[b] = lax.bitwise_or(acc[b], lax.select(dom, bit, zeros))
                return tuple(acc)

            acc = lax.fori_loop(0, 32 // _UNROLL, bits, tuple(zeros for _ in subs))
            words = pl.ds(pl.multiple_of(lax.mul(g, 8), 8), 8)
            for v in range(vregs):
                cols = pl.ds(pl.multiple_of(lax.add(col0, v * 128), 128), 128)
                out_ref[words, cols] = lax.bitcast_convert_type(acc[v // sub][v % sub], jnp.uint32)
            return tuple(lax.add(n, lax.population_count(a)) for n, a in zip(cnt, acc))

        cnt = lax.fori_loop(0, tile_i // _GROUP, group, tuple(zeros for _ in subs))
        for v in range(vregs):
            cols = pl.ds(pl.multiple_of(lax.add(col0, v * 128), 128), 128)
            cnt_ref[0, :, cols] = jnp.sum(cnt[v // sub][v % sub], axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, tile_j // lanes, chunk, 0)


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


@functools.partial(jax.jit, static_argnames=("chunk_rows",))
def packed_dominance_reference(
    fitness: jax.Array, chunk_rows: Optional[int] = None
) -> Tuple[jax.Array, jax.Array]:
    """Pure-XLA build with the kernel's outputs: ``packed_dominance`` on
    every backend but the TPU's, and the kernel's reference in the tests.

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
    packed = packed[:n_words]
    count = jnp.sum(jax.lax.population_count(packed), axis=0, dtype=jnp.int32)
    return packed, count


def packed_dominance(
    fitness: jax.Array,
    interpret: bool = False,
    tile_i: int = _TILE_I,
    tile_j: int = _TILE_J,
) -> Tuple[jax.Array, jax.Array]:
    """Bit-packed dominance matrix + domination counts.

    Returns ``(packed, count)`` where ``packed`` is ``(ceil(n/32), n)``
    uint32 with bit ``k`` of ``packed[w, j]`` set iff row ``32w + k``
    Pareto-dominates row ``j`` (minimization), and ``count[j]`` is the
    number of rows dominating ``j``.

    The backend decides, where this is traced: on the TPU the
    ``dominance_pack`` kernel writes the matrix once, in its final shape
    and layout; elsewhere ``packed_dominance_reference`` builds it in XLA.

    Args:
        fitness: ``(n, m)`` objective matrix.
        interpret: run the kernel in interpreter mode on any backend (tests).
        tile_i, tile_j: the kernel's grid cell (tests use small ones).
    """
    if interpret or jax.default_backend() == "tpu":
        return _packed_dominance_kernel(
            fitness, tile_i=tile_i, tile_j=tile_j, interpret=interpret
        )
    return packed_dominance_reference(fitness)


@functools.partial(jax.jit, static_argnames=("tile_i", "tile_j", "interpret"))
def _packed_dominance_kernel(
    fitness: jax.Array, tile_i: int, tile_j: int, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    """``packed_dominance`` through the kernel: a grid over an output of
    exactly ``(n_words, n)``, so the edge cells' words and columns past the
    matrix are dropped as they are written and nothing is cut afterwards.
    Only the small fitness is padded: ``+inf`` rows up to whole row tiles,
    which dominate nothing, so the last, partial word holds zero bits for
    them and the counts the cells add up are exact; ``+inf`` columns up to
    whole column tiles, whose words and counts are never written. The
    count is the sum of the row tiles' ``(n,)`` partial counts (78 MB at n
    = 100,000), not a pass over the 1.25 GB matrix."""
    if tile_i <= 0 or tile_i % _GROUP != 0:
        raise ValueError(f"tile_i must be a positive multiple of {_GROUP}, got {tile_i}")
    if tile_j <= 0 or tile_j % 128 != 0 or tile_j % min(_LANES, tile_j) != 0:
        raise ValueError(
            f"tile_j must be a positive multiple of 128 and of {_LANES} above it, got {tile_j}"
        )
    n, m = fitness.shape
    n_words = (n + 31) // 32
    tile_i = min(tile_i, -(-n // _GROUP) * _GROUP)
    rows = -(-n // tile_i) * tile_i
    x = _interleave_rows(
        jnp.pad(fitness, ((0, rows - n), (0, 0)), constant_values=jnp.inf)
    )
    lanes = min(_LANES, tile_j)
    cols = -(-n // tile_j) * tile_j
    # (m, cols / 128, 8, 128): every block of the grid lies inside it
    y = jnp.pad(fitness.T, ((0, 0), (0, cols - n)), constant_values=jnp.inf)
    y = jnp.broadcast_to(y.reshape(m, cols // 128, 1, 128), (m, cols // 128, 8, 128))
    packed, count = pl.pallas_call(
        functools.partial(_dominance_pack_kernel, m=m, vregs=lanes // 128),
        grid=(rows // tile_i, cols // tile_j),
        in_specs=[
            pl.BlockSpec((tile_i, m), lambda i, j: (i, 0)),
            pl.BlockSpec((m, tile_j // 128, 8, 128), lambda i, j: (0, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_i // 32, tile_j), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, tile_j), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_words, n), jnp.uint32),
            jax.ShapeDtypeStruct((rows // tile_i, 1, n), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_i, 128 * m), jnp.float32)],
        # the scratch is filled at a row tile's first column tile
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dominance_pack",
    )(x, y)
    return packed, jnp.sum(count, axis=(0, 1))

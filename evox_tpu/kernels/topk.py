"""Blockwise partial-top-k selection (Pallas TPU kernel + XLA fallback).

Top-k-shaped selections are everywhere on the EC hot path: truncation
selection keeps the ``k`` fittest (operators/selection/basic.py
``topk_fit``), DE's current-to-pbest samples from the best ``p`` percent
(``select_rand_pbest``), island migration sends each island's top
``migrate_k`` (workflows/islands.py), ``Algorithm.migrate`` displaces
the worst ``k`` rows, and NSGA-II's environmental truncation fills the
last admitted front by crowding distance
(operators/selection/non_dominate.py). Today those sites pay a full
``argsort``/``lax.top_k`` over ``n`` for a result of size ``k << n``.

This module provides ``partial_topk``: the exact ``k`` smallest values
(and indices) of a vector, computed blockwise —

1. **Per-block top-k** (the Pallas kernel): the input is tiled into
   lane-aligned blocks of ``block_size``; each grid cell ranks its block
   by *comparison counting* — ``rank_i = |{j : v_j < v_i}| + |{j : v_j =
   v_i, j < i}|`` — a loop-free (B, B) VPU compare pass whose tie-break
   makes ranks a permutation (stable, index-ordered ties, matching
   ``lax.top_k``'s tie law), then materializes the block's ``k``
   smallest values and global indices with masked-min extractions over
   the rank one-hot (exact for the ±inf sentinels EC states carry,
   where a one-hot matmul would produce ``inf * 0 = NaN``). No
   in-kernel ``while_loop``, no data-dependent carries — the Mosaic
   trap CLAUDE.md documents never arises because the kernel has no
   loop at all.
2. **Merge** (plain XLA): ``lax.top_k`` over the ``nb * k`` surviving
   candidates — exact, because the global k smallest are each among
   their own block's k smallest.

The candidate layout (block-major, rank-ordered within block) preserves
global index order among equal values, so the merged result is
element-for-element identical to ``lax.top_k(-values, k)`` — asserted
in tests/test_topk.py across duplicates, ±inf sentinels and ragged
tails.

Backend policy: ``use_kernel=None`` resolves through
:func:`default_use_kernel`, which is currently **False on every
backend** — off on non-TPU by design (the kernel targets the TPU memory
system; interpret mode is for testing only), and off on TPU until a chip
run shows it beats ``lax.top_k`` (ROADMAP S4). The kernel compiles for
the v5e (tests/test_chip_compile.py asserts the custom call in the
compiled text); it has not been timed there. Outside its envelope
(``k > block_size``, ``n <= block_size``, ``n >= 2**24``) the call takes
the XLA path even under ``use_kernel=True`` — so whoever checks the
kernel checks for the ``tpu_custom_call``, never just for a result.
Every wired call site threads its own ``use_kernel`` escape hatch.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "partial_topk",
    "partial_topk_reference",
    "default_use_kernel",
]

# 1024 lanes per block: the (B, B) rank-count pass is 1 M compares in
# VMEM (4 MB of f32 intermediates, well under the 16 MB budget) and the
# one-hot materialization is a (B, k) MXU matmul. Larger blocks shrink
# the merge set but grow the O(B^2) pass per element; k <= B is required.
_BLOCK = 1024

# one-hot index matmuls accumulate global indices in f32: exact only
# below 2^24. Larger inputs use the fallback (no EC population today is
# within two orders of magnitude of this).
_MAX_N_KERNEL = 1 << 24


def default_use_kernel() -> bool:
    """Resolve ``use_kernel=None``. False everywhere today: non-TPU
    backends by design (escape hatch off), TPU until the mandatory
    real-chip compile check is recorded (see module docstring)."""
    return False


def _topk_block_kernel(v_ref, out_v_ref, out_i_ref, *, block: int, k_pad: int, k: int):
    """One block: comparison-count ranks, then one-hot matmul the k
    smallest values + global indices into the output tiles."""
    v = v_ref[0]  # (1, B): the leading unit axis is the grid's block axis
    vc = jnp.transpose(v)  # (B, 1): the row-vs-column compare layout
    # rank[i] = #{j: v_j < v_i} + #{j: v_j == v_i, j < i} — a permutation
    # of 0..B-1 (stable ties), so each rank column below is one-hot
    lt = (v < vc).astype(jnp.float32)
    eq = v == vc
    col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)  # i
    row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)  # j
    tie = (eq & (row < col)).astype(jnp.float32)
    rank = jnp.sum(lt + tie, axis=1, keepdims=True)  # (B, 1) f32, exact
    # sel[i, jj] = element i is the block's jj-th smallest, jj < k
    # (Mosaic's iota yields integers only: build it as int32, then cast)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block, k_pad), 1)
    sel = (rank == jj.astype(jnp.float32)) & (jj < k)
    gidx = (
        pl.program_id(0) * block
        + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    ).astype(jnp.float32)
    # masked-min extraction (VPU): each output column has exactly one
    # selected row (ranks are a permutation). NOT a one-hot matmul — a
    # dot would turn the ±inf sentinel values EC states legitimately
    # carry into inf*0 = NaN poison; where+min is exact for any value
    out_v_ref[0] = jnp.min(
        jnp.where(sel, vc, jnp.inf), axis=0, keepdims=True
    )
    out_i_ref[0] = jnp.min(
        jnp.where(sel, gidx, jnp.float32(_MAX_N_KERNEL)), axis=0, keepdims=True
    )


def partial_topk_reference(values: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """XLA fallback with the identical contract: the ``k`` smallest of
    ``values`` with their indices, ascending, ties by lowest index
    (``lax.top_k``'s tie law on the negated input)."""
    neg, idx = jax.lax.top_k(-values, k)
    return -neg, idx


@functools.partial(
    jax.jit, static_argnames=("k", "use_kernel", "interpret", "block_size")
)
def partial_topk(
    values: jax.Array,
    k: int,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    block_size: int = _BLOCK,
) -> Tuple[jax.Array, jax.Array]:
    """The exact ``k`` smallest entries of ``values`` (1-D) and their
    indices, ascending, ties broken by lowest index — element-for-element
    identical to ``lax.top_k(-values, k)`` negated back.

    Args:
        values: ``(n,)`` vector (the minimization-convention fitness).
        k: static selection size, ``1 <= k <= n``.
        use_kernel: run the blockwise Pallas kernel instead of the XLA
            fallback. ``None`` resolves via :func:`default_use_kernel`
            (currently False everywhere — see module docstring). The
            kernel requires ``k <= block_size`` and ``n < 2**24``;
            outside that envelope the call falls back silently (the
            partial-selection shape no longer wins there anyway).
        interpret: run the kernel in interpreter mode (CPU testing).
        block_size: lanes per grid cell (multiple of 128).
    """
    n = values.shape[0]
    if values.ndim != 1:
        raise ValueError(f"partial_topk takes a 1-D vector, got {values.shape}")
    if not 1 <= k <= n:
        raise ValueError(f"k must be in [1, {n}], got {k}")
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if block_size % 128 != 0 or block_size <= 0:
        raise ValueError(f"block_size must be a positive multiple of 128, got {block_size}")
    kernel_fits = k <= block_size and n < _MAX_N_KERNEL and n > block_size
    if not use_kernel or not kernel_fits:
        return partial_topk_reference(values, k)

    values = values.astype(jnp.float32)
    nb = -(-n // block_size)
    pad = nb * block_size - n
    # +inf padding loses every comparison; a tie against a REAL +inf is
    # broken by candidate position, and padded slots sit at higher global
    # indices than every real row, so real sentinels always win the tie
    # (nb, 1, B): Mosaic wants a block's last two dims divisible by
    # (8, 128) or equal to the array's, so each block gets a unit row axis
    v_pad = jnp.pad(values, (0, pad), constant_values=jnp.inf).reshape(
        nb, 1, block_size
    )
    k_pad = -(-k // 128) * 128
    kern = functools.partial(
        _topk_block_kernel, block=block_size, k_pad=k_pad, k=k
    )
    out_v, out_i = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 1, block_size), lambda b: (b, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, k_pad), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, k_pad), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, k_pad), jnp.float32),
        ],
        interpret=interpret,
        name="partial_topk",
    )(v_pad)
    # merge: the global k smallest are each their block's <= k-th
    # smallest, so top_k over the nb*k candidates is exact; block-major,
    # rank-ordered candidates keep equal values in global index order,
    # preserving lax.top_k's lowest-index tie law through the merge
    cand_v = out_v[:, 0, :k].reshape(-1)
    cand_i = out_i[:, 0, :k].reshape(-1)
    neg, pos = jax.lax.top_k(-cand_v, k)
    return -neg, cand_i[pos].astype(jnp.int32)

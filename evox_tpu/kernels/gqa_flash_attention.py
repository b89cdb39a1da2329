"""Forward flash attention for grouped-query heads half a lane tile wide over
a packed row (Pallas TPU kernel): the query heads of a group read their one
key-value head from a copy fetched once, a query block's scores never leave
the chip, and the key blocks no query of the block may attend are never
visited.

The member model of ``problems/lm`` (the ``lfm2_moe`` family) attends with
``heads`` query heads and ``kv_heads`` key-value heads of ``head_dim`` (32, 8
and 64), query head ``h`` reading key-value head ``h // (heads / kv_heads)``,
causally and within a token's document of the packed row; ``q`` and ``k``
come normed and rotated. Its plain body (``problems/lm/model.py``
``attend_gqa_plain``) writes the ``(T, T)`` float32 scores of every member
and head to HBM: at a row of 8,192 tokens, 17.2 GB for one pair of members,
more than the chip has.

A head of 64 is half a lane tile, and nothing narrower than a tile can be
cut out of an operand by a block index. So a grid cell takes one member, one
**pair** of key-value heads (one 128-lane tile of ``k`` and of ``v``, the
member's whole row of it held in VMEM for all the pair's query blocks) and
one block of ``block_q`` queries of the pair's ``2 * group`` query heads
(``2 * group * head_dim`` lanes of ``q``, whole tiles). For each query head
the kernel keeps, in VMEM, its tile of ``q`` with the head moved to the half
of the tile its key-value head lies in (a lane roll where the two differ)
and the other half zero; then, over the key blocks ``first[i] .. last[i]``
of its query block (scalar-prefetched, a dynamic trip count; the bounds are
``flash_attention``'s, ``flash_block_bounds``), for every head in turn:

- the block's scores as one product of the ``k`` tile with that padded
  ``q``, contracted over all 128 lanes, of which the zeros leave exactly
  ``q_h . k_g`` (on a 128 x 128 systolic array a contraction over 64 costs
  what one over 128 does); held ``(keys, queries)``, scaled, masked (``key
  <= query`` and same document; the mask is made once a key block for all
  the heads) and folded into the head's running maximum, running sum and
  accumulator (online softmax: ``flash_attention.fold_key_block``, the one
  step both kernels run), all float32, in VMEM;
- ``p . v`` as one product with the ``v`` tile, of whose 128 rows the
  head's key-value head's 64 are added to the accumulator (the other 64,
  the neighbour's values under this head's probabilities, are dropped).

It writes only the output, every head's ``accumulator / sum``, transposed
back once for all the heads.

Precision is the plain body's: the operands of the products in the dtype
they come in (bfloat16 in the benchmark; the probabilities cast to it before
``p . v``, unnormalised here, normalised there: the same relative rounding),
float32 accumulation; scores, scale, maximum, sum and accumulator float32;
exact exponential and division. Every query attends exactly the keys the
plain mask gives it.

Operand layouts are the projections' own: ``q`` ``(M, T, heads * head_dim)``,
``k`` and ``v`` ``(M, T, kv_heads * head_dim)``; the output ``(M, T, heads *
head_dim)``, the ``o`` projection's operand.

Timed on the v5e (PERF.md section 6, PR 34, has the numbers and the block
sizes tried).

``gqa_flash_attention`` always runs the kernel (``interpret`` for the CPU,
where any ``head_dim`` and block size goes); which path the model takes is
``model.forward``'s choice, by platform and ``gqa_block_sizes``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import fold_key_block, queries_ahead, row_block

__all__ = ["gqa_flash_attention", "gqa_block_sizes"]

_LANES = 128


def gqa_block_sizes(t: int, heads: int, kv_heads: int, head_dim: int) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for a row of ``t`` tokens, or ``None`` where the
    compiled kernel does not take the shapes: two key-value heads make one
    lane tile (``head_dim`` 64, an even number of them), the query heads
    divide among them, and ``t`` divides into blocks of a multiple of a tile."""
    block = row_block(t)
    fits = 2 * head_dim == _LANES and kv_heads % 2 == 0 and heads % kv_heads == 0
    return (block, block) if fits and block is not None else None


def _gqa_kernel(first_ref, last_ref, dq_ref, dk_ref, q_ref, k_ref, v_ref, o_ref,
                qs_ref, m_ref, l_ref, acc_ref, *, scale: float, block_q: int, block_k: int,
                group: int, head_dim: int):
    """One member, one pair of key-value heads, one block of queries of the
    pair's ``2 * group`` query heads. Scores are held ``(keys, queries)``: the
    maximum and the sum over a query's keys run down the sublanes."""
    i = pl.program_id(2)
    heads, tile = 2 * group, 2 * head_dim
    half = jax.lax.broadcasted_iota(jnp.int32, (block_q, tile), 1) // head_dim
    for h in range(heads):
        g = h // group  # the head's key-value head, which is also its half of the k and v tiles
        q = q_ref[0, :, (h // 2) * tile:(h // 2 + 1) * tile]
        if h % 2 != g:  # the head lies in the other half of its own tile (the chip rotates 32-bit lanes only)
            q = pltpu.roll(q.astype(jnp.float32), head_dim, axis=1).astype(q.dtype)
        qs_ref[h] = jnp.where(half == g, q, jnp.zeros_like(q))
    dq = dq_ref[0]  # (1, block_q)
    ahead = queries_ahead(i, block_q, block_k)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    contract_last = (((1,), (1,)), ((), ()))

    def key_block(j, carry):
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :]
        v = v_ref[0, pl.ds(k0, block_k), :]
        keep = (ahead >= k0) & (dk_ref[pl.ds(k0, block_k), :] == dq)
        for h in range(heads):
            g = h // group
            s = jax.lax.dot_general(k, qs_ref[h], contract_last, preferred_element_type=jnp.float32)
            fold_key_block(s, keep, scale, v, m_ref, l_ref, acc_ref, at=h,
                           rows=slice(g * head_dim, (g + 1) * head_dim))
        return carry

    jax.lax.fori_loop(first_ref[i], last_ref[i] + 1, key_block, 0)
    out = acc_ref[...] / l_ref[...]  # (heads, head_dim, block_q)
    o_ref[0] = out.reshape(heads * head_dim, block_q).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale", "block_q", "block_k", "interpret"))
def gqa_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    doc: jax.Array,
    bounds: Tuple[jax.Array, jax.Array],
    *,
    heads: int,
    kv_heads: int,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    """``softmax(scale * q_h . k_g) . v_g`` with ``g = h // (heads /
    kv_heads)`` over a packed row, causal and within a document, for ``M``
    members; ``(M, T, heads * head_dim)`` in ``q``'s dtype.

    ``q`` ``(M, T, heads * head_dim)``; ``k``, ``v`` ``(M, T, kv_heads *
    head_dim)``; ``doc`` ``(T,)`` each token's document; ``bounds``
    ``flash_block_bounds(doc, block_q, block_k)``. ``kv_heads`` is even and
    ``T`` divides into both blocks. Compiled (``interpret`` False),
    ``head_dim`` is 64 and the blocks are multiples of 128
    (``gqa_block_sizes``). One jitted function, so the layers that call it at
    one shape share one lowering of the kernel."""
    m, t, width = q.shape
    head_dim, group = width // heads, heads // kv_heads
    if (width != heads * head_dim or heads != group * kv_heads or kv_heads % 2
            or k.shape != (m, t, kv_heads * head_dim) or v.shape != k.shape):
        raise ValueError(
            f"gqa_flash_attention: q {q.shape}, k {k.shape}, v {v.shape} are not (M, T, {heads} * d), "
            f"(M, T, {kv_heads} * d) twice, with query heads dividing among an even number of key-value heads"
        )
    if t % block_q or t % block_k:
        raise ValueError(f"gqa_flash_attention: a row of {t} does not divide into blocks {block_q}, {block_k}")
    nq = t // block_q
    first, last = bounds
    doc = doc.astype(jnp.int32)
    tile, wide = 2 * head_dim, 2 * group * head_dim  # a pair of key-value heads, and its query heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, kv_heads // 2, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda a, g, i, *_: (i, 0, 0)),  # the queries' documents
            pl.BlockSpec((t, 1), lambda a, g, i, *_: (0, 0)),  # the keys'
            pl.BlockSpec((1, block_q, wide), lambda a, g, i, *_: (a, i, g)),
            pl.BlockSpec((1, t, tile), lambda a, g, i, *_: (a, 0, g)),
            pl.BlockSpec((1, t, tile), lambda a, g, i, *_: (a, 0, g)),
        ],
        out_specs=pl.BlockSpec((1, block_q, wide), lambda a, g, i, *_: (a, i, g)),
        scratch_shapes=[
            pltpu.VMEM((2 * group, block_q, tile), q.dtype),  # each head's q in its key-value head's half
            pltpu.VMEM((2 * group, 1, block_q), jnp.float32),  # running maximum
            pltpu.VMEM((2 * group, 1, block_q), jnp.float32),  # running sum
            pltpu.VMEM((2 * group, head_dim, block_q), jnp.float32),  # accumulator, (values, queries) a head
        ],
    )
    return pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          group=group, head_dim=head_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, t, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="gqa_flash_attention",
    )(first, last, doc.reshape(nq, 1, block_q), doc.reshape(t, 1), q, k, v)

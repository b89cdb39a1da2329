"""Forward flash attention for MLA's unequal head widths over a packed row
(Pallas TPU kernel): a query block's scores never leave the chip, and the
key blocks no query of the block may attend are never visited.

The member model of ``problems/lm`` attends, per member and head, with
queries and keys of ``qk_nope + qk_rope`` (128 + 64) and values of ``v_head``
(128) dimensions, causally and within a token's document of the packed row.
Its plain body (``problems/lm/model.py`` ``attend_plain``) writes the ``(T,
T)`` float32 scores of every member and head to HBM and passes over them
seven or eight times; on the v5e that was 897 ms of a 1,877 ms generation for
3.5e12 operations (PERF.md, PR 28). Here, for one member, one head and one
block of ``block_q`` queries (a grid cell), the kernel

- holds the member and head's keys and values in VMEM (fetched once for all
  the head's query blocks) and loops over the key blocks ``first[i] ..
  last[i]`` of its query block ``i`` (scalar-prefetched, a dynamic trip
  count): the blocks after the query block and those that end before the
  block's first document begins are not visited;
- makes a block's scores as one product of ``[k_nope, k_rope]`` with
  ``[q_nope, q_rope]`` (the rope parts zero-padded to a lane tile, so the
  two are joined in registers and ``k`` is never concatenated in HBM;
  ``k_rope``, one for all heads, is read as it lies), held ``(keys,
  queries)``; scales and masks them (``key <= query`` and same document) and
  folds them into the running maximum, the running sum and the ``(v_head,
  block_q)`` accumulator (online softmax), all float32, in VMEM;
- writes only the output, ``accumulator / sum`` transposed back, in the
  operands' dtype.

Precision is the plain body's: the operands of the products in the dtype
they come in (bfloat16 in the benchmark; the probabilities cast to it before
``p . v``, unnormalised here, normalised there: the same relative rounding),
float32 accumulation; scores, scale, maximum, sum and accumulator float32;
exact exponential and division. Every query attends exactly the keys the
plain mask gives it: a skipped block is one whose every entry the mask sets
to ``finfo.min``.

Operand layouts are the projections' own, so nothing is transposed or copied
for the kernel but the 64-wide rope parts: ``q_nope`` ``(M, T, H * nope)``,
``kv`` ``(M, T, H * (nope + v))`` with each head's ``k_nope`` then ``v``
(handed in twice, the two halves picked by the block index), ``q_rope`` ``(M,
H, T, rope)``, ``k_rope`` ``(M, T, rope)``; the output ``(M, T, H * v)``.

Timed on the v5e (my chip runs, PR 29; 2 members x 16 heads x 2,048 tokens,
bfloat16, the benchmark's documents, a call): the plain body 5.73 ms; this
kernel 0.63 ms at blocks 512 x 512 (0.65 at 512 x 256, 0.70 at 256 x 256,
1.30 at 128 x 128: small blocks skip more keys and pay more a block); the
same with scores held ``(queries, keys)`` 0.65 ms at 512 x 512 and 0.85 at
256 x 256; jax's ``splash_attention`` (causal mask, segment ids, ``k``
concatenated and all three operands transposed to head-major for it) 0.76 ms.

``flash_attention`` always runs the kernel (``interpret`` for the CPU);
which path the model takes is ``model.forward``'s choice, by platform and
``flash_block_sizes``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_block_bounds", "flash_block_sizes"]

_LANES = 128
_BLOCKS = (512, 256, 128)  # the largest that divides the row: the sweep above


def row_block(t: int) -> Optional[int]:
    """The block, of queries and of keys, for a row of ``t`` tokens: the
    largest of ``_BLOCKS`` that divides it, or ``None``. Shared with
    ``gqa_flash_attention``, whose sweep on the chip chose the same."""
    return next((b for b in _BLOCKS if t % b == 0), None)


def flash_block_sizes(t: int, nope: int, v: int) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for a row of ``t`` tokens, or ``None`` where the
    compiled kernel does not take the shapes: the head's ``k_nope`` and ``v``
    are picked out of ``kv`` by a block index, so both are one lane tile
    wide; ``t`` divides into blocks of a multiple of it."""
    block = row_block(t)
    return None if nope != _LANES or v != _LANES or block is None else (block, block)


def flash_block_bounds(doc: jax.Array, block_q: int, block_k: int) -> Tuple[jax.Array, jax.Array]:
    """For each block of ``block_q`` queries of a row whose tokens' documents
    are ``doc`` ``(T,)``: the first and the last key block that holds a key
    one of its queries attends (``key <= query`` and same document),
    ``(T // block_q,)`` int32 each. Documents lie contiguous in a packed row,
    so every block between the two holds such a key as well."""
    t = doc.shape[0]
    at = jnp.arange(t)
    mask = (at[:, None] >= at[None, :]) & (doc[:, None] == doc[None, :])
    nq, nk = t // block_q, t // block_k
    seen = jnp.any(mask.reshape(nq, block_q, nk, block_k), axis=(1, 3))
    first = jnp.argmax(seen, axis=1)
    last = nk - 1 - jnp.argmax(seen[:, ::-1], axis=1)
    return first.astype(jnp.int32), last.astype(jnp.int32)


def queries_ahead(i, block_q: int, block_k: int) -> jax.Array:
    """``(block_k, block_q)``: how far query ``col`` of query block ``i`` lies
    ahead of row ``row`` of a key block that starts at 0. Key ``k0 + row <=``
    query ``i * block_q + col`` is ``k0 <= ahead``."""
    shape = (block_k, block_q)
    return (i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 0))


def fold_key_block(s, keep, scale: float, v, m_ref, l_ref, acc_ref, at=..., rows=None) -> None:
    """One step of the online softmax, shared by the attention kernels: a key
    block's raw scores ``s`` ``(keys, queries)`` scaled, masked by ``keep`` and
    folded with the block's values ``v`` ``(keys, width)`` into the running
    maximum, the running sum and the ``(width, queries)`` accumulator that lie
    at ``at`` of their float32 scratch; ``rows`` picks the accumulator's rows
    out of ``v^T p`` where ``v`` is wider than the head."""
    s = jnp.where(keep, s * scale, jnp.finfo(jnp.float32).min)
    # a query with no key in this block reads exp(0) = 1 down its column;
    # its own document's first block, which comes later, has a finite
    # maximum, and alpha = exp(finfo.min - maximum) = 0 wipes that out
    m_prev = m_ref[at]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=0, keepdims=True)
    pv = jax.lax.dot_general(v, p.astype(v.dtype), (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_ref[at] = alpha * acc_ref[at] + (pv if rows is None else pv[rows])
    m_ref[at] = m_next


def _flash_kernel(first_ref, last_ref, dq_ref, dk_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_q: int, block_k: int):
    """One member, one head, one block of queries. Scores are held ``(keys,
    queries)``: the maximum and the sum over a query's keys then run down
    the sublanes, elementwise over vector registers, and not across the lanes
    of each."""
    i = pl.program_id(2)
    q = jnp.concatenate([qn_ref[0], qr_ref[0, 0]], axis=1)
    dq = dq_ref[0]  # (1, block_q)
    ahead = queries_ahead(i, block_q, block_k)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    contract_last = (((1,), (1,)), ((), ()))

    def key_block(j, carry):
        k0 = pl.multiple_of(j * block_k, block_k)
        k = jnp.concatenate([kn_ref[0, pl.ds(k0, block_k), :], kr_ref[0, pl.ds(k0, block_k), :]], axis=1)
        s = jax.lax.dot_general(k, q, contract_last, preferred_element_type=jnp.float32)  # (keys, queries)
        keep = (ahead >= k0) & (dk_ref[pl.ds(k0, block_k), :] == dq)
        fold_key_block(s, keep, scale, v_ref[0, pl.ds(k0, block_k), :], m_ref, l_ref, acc_ref)
        return carry

    jax.lax.fori_loop(first_ref[i], last_ref[i] + 1, key_block, 0)
    o_ref[0] = (acc_ref[...] / l_ref[...]).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "block_q", "block_k", "interpret"))
def flash_attention(
    q_nope: jax.Array,
    q_rope: jax.Array,
    kv: jax.Array,
    k_rope: jax.Array,
    doc: jax.Array,
    bounds: Tuple[jax.Array, jax.Array],
    *,
    heads: int,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    """``softmax(scale * (q_nope . k_nope + q_rope . k_rope)) . v`` over a
    packed row, causal and within a document, for ``M`` members and ``heads``
    heads; ``(M, T, heads * v)`` in ``q_nope``'s dtype.

    ``q_nope`` ``(M, T, heads * nope)``; ``q_rope`` ``(M, heads, T, rope)``;
    ``kv`` ``(M, T, heads * (nope + v))``, for each head ``k_nope`` then
    ``v``, ``nope == v``; ``k_rope`` ``(M, T, rope)``, one for all heads;
    ``doc`` ``(T,)`` each token's document; ``bounds``
    ``flash_block_bounds(doc, block_q, block_k)``. ``T`` divides into both
    blocks. Compiled (``interpret`` False), ``nope`` is 128 and the blocks
    are multiples of 128 (``flash_block_sizes``). One jitted function, so the
    layers that call it at one shape share one lowering of the kernel."""
    m, t, width = q_nope.shape
    nope, rope = width // heads, q_rope.shape[-1]
    if kv.shape != (m, t, 2 * width) or q_rope.shape != (m, heads, t, rope) or k_rope.shape != (m, t, rope):
        raise ValueError(
            f"flash_attention: q_nope {q_nope.shape}, q_rope {q_rope.shape}, kv {kv.shape}, "
            f"k_rope {k_rope.shape} are not (M, T, H * d), (M, H, T, r), (M, T, 2 * H * d), (M, T, r)"
        )
    if t % block_q or t % block_k:
        raise ValueError(f"flash_attention: a row of {t} does not divide into blocks {block_q}, {block_k}")
    nq = t // block_q
    first, last = bounds
    doc = doc.astype(jnp.int32)
    # the rope parts to a whole lane tile, so that [nope, rope] is one operand of one product
    wide = -(-rope // _LANES) * _LANES
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, 0), (0, wide - rope)))
    k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, wide - rope)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, heads, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda a, h, i, *_: (i, 0, 0)),  # the queries' documents
            pl.BlockSpec((t, 1), lambda a, h, i, *_: (0, 0)),  # the keys'
            pl.BlockSpec((1, block_q, nope), lambda a, h, i, *_: (a, i, h)),
            pl.BlockSpec((1, 1, block_q, wide), lambda a, h, i, *_: (a, h, i, 0)),
            pl.BlockSpec((1, t, nope), lambda a, h, i, *_: (a, 0, 2 * h)),  # the head's k_nope
            pl.BlockSpec((1, t, wide), lambda a, h, i, *_: (a, 0, 0)),
            pl.BlockSpec((1, t, nope), lambda a, h, i, *_: (a, 0, 2 * h + 1)),  # the head's v
        ],
        out_specs=pl.BlockSpec((1, block_q, nope), lambda a, h, i, *_: (a, i, h)),
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),  # running maximum
            pltpu.VMEM((1, block_q), jnp.float32),  # running sum
            pltpu.VMEM((nope, block_q), jnp.float32),  # accumulator, (v, queries); v == nope
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, t, width), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="flash_attention",
    )(first, last, doc.reshape(nq, 1, block_q), doc.reshape(t, 1), q_nope, q_rope, kv, k_rope, kv)

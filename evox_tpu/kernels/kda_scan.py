"""Kimi Delta Attention's recurrence over a packed row, chunkwise and exact
(Pallas TPU kernel ``kda_scan``, and the same chunk arithmetic as plain XLA).

The gated delta rule, per member and head, with a state ``S`` of ``(keys,
values)`` float32 that is zero at the first token of every document:

    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t

``g <= 0`` per key channel, ``beta`` in (0, 1). Token by token
(``kda_scan_reference``: the tests' oracle and the benchmark reference's form)
the state of every member and head is read and written once a token: 550 GB a
layer and generation at the benchmark's shapes. Here the row is cut into
chunks of ``chunk`` tokens and the state is touched once a chunk; inside a
chunk everything is matrix products. With ``G_i`` the sum of ``g`` up to token
``i`` from the chunk's first token or the first of ``i``'s document, whichever
is later (one float32 product with the chunk's mask: no other document's decay
is ever summed in, so none can round into a difference):

- ``A[i, j] = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])`` and the same with
  ``q_i`` for ``j <= i`` of one document. Only ``G_i - G_j`` with ``i >= j``
  is ever exponentiated (``exp(-G)`` alone overflows): between sub-blocks of
  ``SUB`` tokens the decay is split at the later sub-block's first token (both
  factors at most one, one product a sub-block); inside a sub-block the
  entries are made exactly, a column at a time, elementwise in float32;
- the unit lower triangular system ``(I + diag(beta) strict(A)) u = diag(beta)
  (v - (k exp(G)) S_in)`` is solved once a chunk, in float32: the diagonal
  sub-blocks are inverted by forward substitution in the loop that makes
  their columns, and joined over the four block rows by the finite Neumann
  product ``(I - M)(I + M^2)`` of the strictly block-lower ``M`` (``M^4 = 0``:
  exact);
- ``o = (q exp(G)) S_in + tril(A_qk) u`` and ``S_out = diag(exp(G_last)) S_in
  + (k exp(G_last - G))^T u``.

Documents: pairs of different documents are masked out of ``A``; a token takes
nothing from ``S_in`` once a document has begun inside the chunk; the state
carried out is that of the chunk's last document alone. A row that is no
multiple of the chunk is padded with tokens of a document of their own that
write nothing (``k = v = 0``, ``beta = 0``).

Precision: the operands of the products in the dtype ``q``, ``k``, ``v`` come
in (bfloat16 in the benchmark), float32 accumulation; ``g``, ``G``, ``beta``,
the sub-blocks' exact entries, the triangular solve and the carried state in
float32; the output in the operands' dtype.

The kernel: one grid cell a member and ``HEADS_A_CELL`` heads; each head's ``q``, ``k``, ``v``,
``g`` for the whole row are fetched once into VMEM (layouts are the
projections' own, ``(M, T, H * width)``, the head picked by the block index),
the state ``(values, keys)`` stays in VMEM scratch across the row's chunks,
and only ``o`` is written. ``kda_scan_chunked`` is the same arithmetic
(``_chunk_parts``, ``_chunk_step``) vmapped over members, heads and chunks
with a ``lax.scan`` over the chunks for the state: the path off the TPU, and
what the kernel is timed against (PERF.md section 6, PR 32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_scan", "kda_scan_chunked", "kda_scan_reference", "KDA_CHUNK"]

F32 = jnp.float32
KDA_CHUNK = 64  # tokens a chunk: the state is read and written once for these
SUB = 16  # tokens a sub-block: the entries inside one are made exactly
HEADS_A_CELL = 2  # heads a grid cell of the kernel works on side by side

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NN = (((1,), (0,)), ((), ()))


def _mm(a, b):
    """A float32 product of the triangular solve, at full precision."""
    return jax.lax.dot_general(a, b, _NN, precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)


def _nilpotent_inverse(low, index: int, eye):
    """``(I + low)^-1`` for ``low`` with ``low ** index == 0``: the finite
    Neumann product ``(I + P)(I + P^2)(I + P^4)...`` of ``P = -low``."""
    p = -low
    x, power = eye + p, 2
    while power < index:
        p = _mm(p, p)
        x = x + _mm(x, p)
        power *= 2
    return x


def _chunk_parts(q, k, v, g, beta_row, doc_col, doc_row, prev_doc, sub: int):
    """What a chunk needs besides the incoming state. ``q``, ``k`` ``(C,
    keys)``, ``v`` ``(C, values)`` in the operands' dtype; ``g`` ``(C, keys)``
    float32; ``beta_row``
    ``(1, C)``; ``doc_col`` ``(C, 1)`` and ``doc_row`` ``(1, C)`` the tokens'
    documents; ``prev_doc`` that of the token before the chunk (``(1, 1)``).
    Returns ``w`` and ``q_in`` (what meets the incoming state, zero for tokens
    of a document begun inside the chunk), ``u0``, ``a_qk``, ``k_out`` and
    ``carry`` (how the state is carried out: ``(1, keys)``)."""
    c, dt = q.shape[0], q.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(F32)
    same = doc_col == doc_row
    beta_col = jnp.sum(eye * beta_row, axis=1, keepdims=True)  # (1, C) as (C, 1)
    qf, kf = q.astype(F32), k.astype(F32)
    cum = _mm((same & (row >= col)).astype(F32), g)  # G: g summed over the token's document inside the chunk

    # A_qk and A_kk between sub-blocks: exp(G_i - first) exp(first - G_j) at the later sub-block's
    # first token, both at most 1 (columns from the sub-block's own on come out of the clamp: not used)
    rows = [jnp.zeros((2 * sub, c), F32)]
    for lo in range(sub, c, sub):
        first = cum[lo:lo + 1]
        into = jnp.exp(jnp.minimum(cum[lo:lo + sub] - first, 0.0))  # above 0 only in rows whose every pair is masked
        keys = (kf * jnp.exp(jnp.minimum(first - cum, 0.0))).astype(dt)
        both = jnp.concatenate([qf[lo:lo + sub] * into, kf[lo:lo + sub] * into], axis=0).astype(dt)
        rows.append(jax.lax.dot_general(both, keys, _NT, preferred_element_type=F32))
    between_qk = jnp.concatenate([r[:sub] for r in rows], axis=0)
    between_kk = jnp.concatenate([r[sub:] for r in rows], axis=0)
    # inside a sub-block exactly, column j of every sub-block at a time: rows i >= j. The same loop
    # inverts the sub-blocks of I + low by forward substitution: once column j of low is made, row j
    # of the inverse is final and is taken out of the rows below it.
    blocks = c // sub
    cut = lambda a: a.reshape(blocks, sub, -1)
    q3, k3, cum3, beta3, doc3 = cut(qf), cut(kf), cut(cum), cut(beta_col), cut(doc_col)
    column = (jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, c), 2)
              - sub * jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, c), 0))
    below = jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, 1), 1)
    inside_qk = jnp.zeros((blocks, sub, c), F32)
    inner = (column == below).astype(F32)  # the identity, each sub-block at its own columns
    for j in range(sub):
        decay = k3[:, j:j + 1] * jnp.exp(jnp.minimum(cum3 - cum3[:, j:j + 1], 0.0))
        inside_qk = jnp.where(column == j, jnp.sum(q3 * decay, axis=2, keepdims=True), inside_qk)
        low_j = jnp.where((below > j) & (doc3 == doc3[:, j:j + 1]),
                          beta3 * jnp.sum(k3 * decay, axis=2, keepdims=True), 0.0)
        inner = inner - low_j * inner[:, j:j + 1]
    diagonal = (row // sub) == (col // sub)
    a_qk = jnp.where(same & (row >= col), jnp.where(diagonal, inside_qk.reshape(c, c), between_qk), 0.0)
    between = jnp.where(same & ~diagonal & (row > col), beta_col * between_kk, 0.0)

    # (I + low)^-1 from the sub-blocks' inverses: the block rows by the finite Neumann product
    inner = inner.reshape(c, c)
    outer = _nilpotent_inverse(_mm(inner, between), blocks, eye)
    solve = _mm(outer, inner).astype(dt)

    old = (doc_col == prev_doc).astype(F32)  # (C, 1): the token's document began before the chunk
    into = jnp.exp(cum)
    w = jax.lax.dot_general(solve, (old * beta_col * kf * into).astype(dt), _NN, preferred_element_type=F32)
    u0 = jax.lax.dot_general(solve, (beta_col * v.astype(F32)).astype(dt), _NN, preferred_element_type=F32)
    last = cum[c - 1:c]
    of_last = doc_col == doc_col[c - 1:c]
    k_out = jnp.where(of_last, kf * jnp.exp(last - cum), 0.0).astype(dt)
    carry = jnp.exp(last) * old[c - 1:c]
    return w.astype(dt), (old * qf * into).astype(dt), u0, a_qk.astype(dt), k_out, carry


def _chunk_step(state, w, q_in, u0, a_qk, k_out, carry):
    """One chunk of the recurrence: ``state`` ``(values, keys)`` float32 in,
    the chunk's outputs ``(C, values)`` float32 and the state out."""
    dt = w.dtype
    s = state.astype(dt)
    u = (u0 - jax.lax.dot_general(w, s, _NT, preferred_element_type=F32)).astype(dt)
    o = (jax.lax.dot_general(q_in, s, _NT, preferred_element_type=F32)
         + jax.lax.dot_general(a_qk, u, _NN, preferred_element_type=F32))
    return o, carry * state + jax.lax.dot_general(u, k_out, _TN, preferred_element_type=F32)


def _kda_kernel(prev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, dcol_ref, drow_ref, o_ref, state_ref,
                *, chunk: int, sub: int):
    """One member, ``HEADS_A_CELL`` heads: the row's chunks in order, the
    heads' states in VMEM. The heads of a cell share nothing: a chunk is one
    long chain of dependent products, and a second chain beside it fills the
    units the first leaves waiting."""
    state_ref[...] = jnp.zeros_like(state_ref)
    heads, values, keys = state_ref.shape

    def one_chunk(n, carry):
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        for h in range(heads):
            ks, vs = slice(h * keys, (h + 1) * keys), slice(h * values, (h + 1) * values)
            parts = _chunk_parts(
                q_ref[0, at, ks], k_ref[0, at, ks], v_ref[0, at, vs], g_ref[0, at, ks],
                beta_ref[0, h, pl.ds(n, 1), :], dcol_ref[at, :], drow_ref[n],
                jnp.full((1, 1), prev_ref[n], jnp.int32), sub,
            )
            o, state_ref[h] = _chunk_step(state_ref[h], *parts)
            o_ref[0, at, vs] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[1] // chunk, one_chunk, 0)


def _prepare(q, k, v, g, beta, doc, heads: int, chunk: int):
    """The row padded to whole chunks; ``beta`` a row a chunk; the document
    of the token before each chunk (``prev``)."""
    m, t, _ = q.shape
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (q, k, v, g, beta))
        doc = jnp.concatenate([doc, jnp.full((pad,), -2, doc.dtype)])
    n = (t + pad) // chunk
    doc = doc.astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), doc[chunk - 1:-1:chunk]])
    beta = beta.astype(F32).transpose(0, 2, 1).reshape(m, heads, n, chunk)
    return q, k, v, g.astype(F32), beta, doc, prev, n


def _check(q, k, v, g, beta, doc, heads: int, chunk: int) -> None:
    m, t, width = q.shape
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:2] != (m, t) or beta.shape != (m, t, heads)
            or doc.shape != (t,) or width % heads or v.shape[2] % heads or chunk % SUB):
        raise ValueError(
            f"kda_scan: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}, doc {doc.shape} "
            f"are not (M, T, H * keys) thrice, (M, T, H * values), (M, T, H), (T,) for H = {heads}, "
            f"or the chunk {chunk} is no multiple of {SUB}"
        )


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def kda_scan(q, k, v, g, beta, doc, *, heads: int, chunk: int = KDA_CHUNK, interpret: bool = False):
    """The gated delta rule over a packed row, for ``M`` members and ``heads``
    heads, by the kernel; ``(M, T, heads * values)`` in ``q``'s dtype.

    ``q``, ``k``, ``g`` ``(M, T, heads * keys)`` (``g`` float32, at most 0),
    ``v`` ``(M, T, heads * values)``, ``beta`` ``(M, T, heads)``, ``doc``
    ``(T,)`` each token's document (not negative, contiguous). Compiled
    (``interpret`` False) a head's keys and values are whole lane tiles (128).
    One jitted function: the layers that call it at one shape share one
    lowering."""
    _check(q, k, v, g, beta, doc, heads, chunk)
    m, t, width = q.shape
    keys, values = width // heads, v.shape[2] // heads
    q, k, v, g, beta, doc, prev, n = _prepare(q, k, v, g, beta, doc, heads, chunk)
    tp = n * chunk
    hc = HEADS_A_CELL if heads % HEADS_A_CELL == 0 else 1
    head = lambda w: pl.BlockSpec((1, tp, hc * w), lambda a, h, *_: (a, 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, heads // hc),
        in_specs=[
            head(keys), head(keys), head(values), head(keys),
            pl.BlockSpec((1, hc, n, chunk), lambda a, h, *_: (a, h, 0, 0)),
            pl.BlockSpec((tp, 1), lambda a, h, *_: (0, 0)),
            pl.BlockSpec((n, 1, chunk), lambda a, h, *_: (0, 0, 0)),
        ],
        out_specs=head(values),
        scratch_shapes=[pltpu.VMEM((hc, values, keys), F32)],
    )
    out = pl.pallas_call(
        functools.partial(_kda_kernel, chunk=chunk, sub=SUB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, tp, heads * values), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="kda_scan",
    )(prev, q, k, v, g, beta, doc.reshape(tp, 1), doc.reshape(n, 1, chunk))
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("heads", "chunk"))
def kda_scan_chunked(q, k, v, g, beta, doc, *, heads: int, chunk: int = KDA_CHUNK):
    """The same result by the same chunk arithmetic in plain XLA: the parts of
    every chunk at once, then a scan over the chunks for the state."""
    _check(q, k, v, g, beta, doc, heads, chunk)
    m, t, _ = q.shape
    values = v.shape[2] // heads
    q, k, v, g, beta, doc, prev, n = _prepare(q, k, v, g, beta, doc, heads, chunk)
    split = lambda a: a.reshape(m, n, chunk, heads, -1)  # (M, N, C, H, width)
    parts = functools.partial(_chunk_parts, sub=SUB)
    over_heads = jax.vmap(parts, in_axes=(1, 1, 1, 1, 0, None, None, None))
    over_chunks = jax.vmap(over_heads, in_axes=(0, 0, 0, 0, 1, 0, 0, 0))
    over_members = jax.vmap(over_chunks, in_axes=(0, 0, 0, 0, 0, None, None, None))
    made = over_members(
        split(q), split(k), split(v), split(g), beta[:, :, :, None, :],
        doc.reshape(n, chunk, 1), doc.reshape(n, 1, chunk), prev.reshape(n, 1, 1),
    )  # each (M, N, H, ...)
    step = jax.vmap(jax.vmap(_chunk_step))

    def one_chunk(state, xs):
        o, state = step(state, *xs)
        return state, o

    keys = q.shape[2] // heads
    _, o = jax.lax.scan(one_chunk, jnp.zeros((m, heads, values, keys), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in made))  # o: (N, M, H, C, values)
    return o.transpose(1, 0, 3, 2, 4).reshape(m, n * chunk, heads * values)[:, :t].astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("heads",))
def kda_scan_reference(q, k, v, g, beta, doc, *, heads: int):
    """The recurrence as written, a token at a time in float32: the oracle."""
    m, t, _ = q.shape
    split = lambda a: jnp.moveaxis(a.astype(F32).reshape(m, t, heads, -1), 1, 0)  # (T, M, H, width)
    start = jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])

    def token(state, xs):  # state (M, H, keys, values)
        qt, kt, vt, gt, bt, new = xs
        state = jnp.where(new, 0.0, state) * jnp.exp(gt)[..., None]
        seen = jnp.einsum("mhkv,mhk->mhv", state, kt, precision="highest")
        state = state + bt[..., None, None] * kt[..., None] * (vt - seen)[..., None, :]
        return state, jnp.einsum("mhkv,mhk->mhv", state, qt, precision="highest")

    keys, values = q.shape[2] // heads, v.shape[2] // heads
    _, o = jax.lax.scan(token, jnp.zeros((m, heads, keys, values), F32),
                        (split(q), split(k), split(v), split(g), jnp.moveaxis(beta.astype(F32), 1, 0), start))
    return jnp.moveaxis(o, 0, 1).reshape(m, t, heads * values).astype(q.dtype)

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
is later (the chunk's 0/1 mask times ``g``'s three bfloat16 parts, which sum
to ``g`` exactly: every product exact, the sums float32; no other document's
decay is ever summed in, so none can round into a difference):

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

Heads joined. The chunk arithmetic (``_chunk_parts``, ``_chunk_step``) takes
the rows of ``J`` heads stacked, a head after another (``J`` read from the
shapes), and treats tokens of two heads as it treats tokens of two documents:
masked from each other. Every ``(J C, J C)`` matrix of a chunk (the mask, ``A``,
the sub-blocks' inverses, the triangular solve) is then block diagonal by
head with exact zeros elsewhere, and a product of two of them, or of one with
the heads' stacked rows, gives each head the sums it gives that head alone with
zeros added; the products between sub-blocks also make entries across heads,
which the select that drops other documents' pairs drops. A head's output is
bit for bit what it is whatever the joined head holds, so long as that head's
own entries stay finite (``0 * inf``: they are heads of one member). With ``J
= 2`` and the chunk of 64 the float32 products of the solve, ``G``'s and
``a_qk u`` are ``(128, 128)`` by ``(128, 128)``: whole passes of the chip's
matrix unit where one head's use a quarter.

The kernel: one grid cell a member and ``HEADS_A_CELL`` heads, joined (one
head a cell, ``J = 1``, where the head count is odd); each head's ``q``,
``k``, ``v``, ``g`` for the whole row are fetched once into VMEM (layouts are
the projections' own, ``(M, T, H * width)``, the heads picked by the block
index), the states ``(J, values, keys)`` stay in VMEM scratch across the
row's chunks, and only ``o`` is written. ``kda_scan_chunked`` is the same
arithmetic with ``J = 1``, vmapped over members, heads and chunks with a
``lax.scan`` over the chunks for the state: the path off the TPU, and what
the kernel is timed against (PERF.md section 6, PRs 32 and 35).
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
GROUP = 8  # rows of a sub-block walked together: a float32 vector register's
HEADS_A_CELL = 2  # heads a grid cell of the kernel works on, joined

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NN = (((1,), (0,)), ((), ()))


def _mm(a, b):
    """A float32 product of the triangular solve, at full precision."""
    return jax.lax.dot_general(a, b, _NN, precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)


def _dot(a, b, dims=_NN):
    """A product of operands in their own dtype, summed in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _masked_sums(mask, g):
    """``mask @ g`` in float32 for a mask of 0 and 1 and ``g`` float32: ``g``
    is split exactly into three bfloat16 parts (8 of its 24 bits each), the
    mask is exact in bfloat16, so every product is exact and the three passes
    sum, in float32, the terms the full-precision float32 product sums in
    six; a masked term is an exact zero."""
    hi = g.astype(jnp.bfloat16)
    rest = g - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(F32)).astype(jnp.bfloat16)
    mask = mask.astype(jnp.bfloat16)
    return (_dot(mask, low) + _dot(mask, mid)) + _dot(mask, hi)


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


def _chunk_parts(q, k, v, g, beta_col, doc_col, doc_row, prev_doc, sub: int):
    """What a chunk needs besides the incoming state, for ``J`` heads joined
    (``J`` read from the shapes: the rows over the chunk's ``C`` tokens).
    The heads' rows lie one head after another, ``R = J * C`` of them: ``q``,
    ``k`` ``(R, keys)``, ``v`` ``(R, values)`` in the operands' dtype; ``g``
    ``(R, keys)`` float32; ``beta_col`` ``(R, 1)``; ``doc_row`` (the tokens'
    documents, once a head) ``(1, R)``; ``doc_col`` ``(C, 1)``; ``prev_doc``
    the document of the token before the chunk (``(1, 1)``). Tokens of two
    heads are masked from each other as tokens of two documents are, so every
    ``(R, R)`` matrix is block diagonal by head, its other entries exact
    zeros: a product of two of them, or of one with the heads' stacked rows,
    gives each head the sums it gives that head alone, zeros added.
    Returns ``w`` and ``q_in`` (what meets the incoming state, zero for tokens
    of a document begun inside the chunk), ``u0``, ``a_qk`` ``(R, R)``,
    ``k_out`` and ``carry`` (how the state is carried out: ``(J, 1, keys)``)."""
    c, rows_n, dt = doc_col.shape[0], q.shape[0], q.dtype
    joined, keys_n = rows_n // c, q.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows_n, rows_n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows_n, rows_n), 1)
    eye = (row == col).astype(F32)
    doc_col = jnp.concatenate([doc_col] * joined, axis=0)
    same = doc_col == doc_row
    if joined > 1:
        same &= (row // c) == (col // c)
    qf, kf = q.astype(F32), k.astype(F32)
    cum = _masked_sums(same & (row >= col), g)  # G: g summed over the token's document inside the chunk
    by_head = lambda a: a.reshape(joined, c, a.shape[-1])
    q_h, k_h, cum_h = by_head(qf), by_head(kf), by_head(cum)

    # A_qk and A_kk between sub-blocks: exp(G_i - first) exp(first - G_j) at the later sub-block's
    # first token, both at most 1 (columns from the sub-block's own on come out of the clamp, and
    # columns of another head hold that head's keys at its own reference: neither is used)
    rows = [jnp.zeros((joined, 2 * sub, rows_n), F32)]
    for lo in range(sub, c, sub):
        first = cum_h[:, lo:lo + 1]
        into = jnp.exp(jnp.minimum(cum_h[:, lo:lo + sub] - first, 0.0))  # above 0 only in rows whose every pair is masked
        keys = (k_h * jnp.exp(jnp.minimum(first - cum_h, 0.0))).reshape(rows_n, keys_n).astype(dt)
        both = jnp.concatenate([q_h[:, lo:lo + sub] * into, k_h[:, lo:lo + sub] * into], axis=1)
        both = both.reshape(joined * 2 * sub, keys_n).astype(dt)
        rows.append(_dot(both, keys, _NT).reshape(joined, 2 * sub, rows_n))
    between_qk = jnp.concatenate([r[:, :sub] for r in rows], axis=1).reshape(rows_n, rows_n)
    between_kk = jnp.concatenate([r[:, sub:] for r in rows], axis=1).reshape(rows_n, rows_n)
    # inside a sub-block exactly, column j of every sub-block at a time: rows i >= j. The same loop
    # inverts the sub-blocks of I + low by forward substitution: once column j of low is made, row j
    # of the inverse is final and is taken out of the rows below it. A sub-block's rows are walked in
    # groups of GROUP (a float32 register's rows): a group that ends above the column's first row
    # holds nothing of the column and is left out.
    blocks, groups = rows_n // sub, sub // GROUP
    by_block = lambda a: a.reshape(blocks, sub, a.shape[-1])
    grouped = lambda a: [a.reshape(blocks, groups, GROUP, a.shape[-1])[:, r] for r in range(groups)]
    k_b, cum_b, doc_b = by_block(kf), by_block(cum), by_block(doc_col)
    column = (jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, rows_n), 2)
              - sub * jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, rows_n), 0))
    below = jax.lax.broadcasted_iota(jnp.int32, (blocks, sub, 1), 1)
    q_g, k_g, cum_g, beta_g, doc_g, column_g, below_g = map(grouped, (qf, kf, cum, beta_col, doc_col, column, below))
    inside_qk = [jnp.zeros((blocks, GROUP, rows_n), F32)] * groups
    inner = [(c_r == b_r).astype(F32) for c_r, b_r in zip(column_g, below_g)]  # the identity, each sub-block at its own columns
    for j in range(sub):
        k_j, cum_j, doc_j = k_b[:, j:j + 1], cum_b[:, j:j + 1], doc_b[:, j:j + 1]
        inner_j = inner[j // GROUP][:, j % GROUP:j % GROUP + 1]
        for r in range(j // GROUP, groups):
            decay = k_j * jnp.exp(jnp.minimum(cum_g[r] - cum_j, 0.0))
            inside_qk[r] = jnp.where(column_g[r] == j, jnp.sum(q_g[r] * decay, axis=2, keepdims=True), inside_qk[r])
            low_j = jnp.where((below_g[r] > j) & (doc_g[r] == doc_j),
                              beta_g[r] * jnp.sum(k_g[r] * decay, axis=2, keepdims=True), 0.0)
            inner[r] = inner[r] - low_j * inner_j
    inside_qk, inner = (jnp.stack(a, axis=1).reshape(rows_n, rows_n) for a in (inside_qk, inner))
    diagonal = (row // sub) == (col // sub)
    a_qk = jnp.where(same & (row >= col), jnp.where(diagonal, inside_qk, between_qk), 0.0)
    between = jnp.where(same & ~diagonal & (row > col), beta_col * between_kk, 0.0)

    # (I + low)^-1 from the sub-blocks' inverses: a head's block rows by the finite Neumann product
    outer = _nilpotent_inverse(_mm(inner, between), c // sub, eye)
    solve = _mm(outer, inner).astype(dt)

    old = (doc_col == prev_doc).astype(F32)  # (R, 1): the token's document began before the chunk
    into = jnp.exp(cum)
    solved = _dot(solve, jnp.concatenate([(old * beta_col * kf * into).astype(dt),
                                          (beta_col * v.astype(F32)).astype(dt)], axis=1))
    last = cum_h[:, c - 1:c]
    of_last = doc_col == doc_col[c - 1:c]
    k_out = jnp.where(of_last, (k_h * jnp.exp(last - cum_h)).reshape(rows_n, keys_n), 0.0).astype(dt)
    carry = jnp.exp(last) * old[c - 1:c]
    return solved[:, :keys_n].astype(dt), (old * qf * into).astype(dt), solved[:, keys_n:], a_qk.astype(dt), k_out, carry


def _chunk_step(state, w, q_in, u0, a_qk, k_out, carry):
    """One chunk of the recurrence for the ``J`` heads joined: ``state`` ``(J,
    values, keys)`` float32 in, the chunk's outputs ``(R, values)`` float32
    and the states out. A head's state meets that head's rows alone;
    ``a_qk`` meets the heads' stacked ``u`` in one product."""
    dt = w.dtype
    joined, c = state.shape[0], w.shape[0] // state.shape[0]
    s = state.astype(dt)
    with_state = lambda a: jnp.concatenate([_dot(a[h * c:(h + 1) * c], s[h], _NT) for h in range(joined)], axis=0)
    u = (u0 - with_state(w)).astype(dt)
    o = with_state(q_in) + _dot(a_qk, u)
    return o, carry * state + jnp.stack([_dot(u[h * c:(h + 1) * c], k_out[h * c:(h + 1) * c], _TN) for h in range(joined)])


def _kda_kernel(prev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, dcol_ref, drow_ref, o_ref, state_ref,
                *, chunk: int, sub: int):
    """One member and the cell's heads (``HEADS_A_CELL``, or one where the
    head count is odd): the row's chunks in order, the heads' states in VMEM.
    The heads' rows of a chunk are stacked and go through the chunk arithmetic
    joined: two heads' 64 tokens fill the 128 rows and columns of a product
    that one head's leave a quarter or a half used."""
    state_ref[...] = jnp.zeros_like(state_ref)
    heads, values, keys = state_ref.shape

    def one_chunk(n, carry):
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        stacked = lambda ref, width: jnp.concatenate(
            [ref[0, at, h * width:(h + 1) * width] for h in range(heads)], axis=0)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)).astype(F32)
        beta_col = jnp.concatenate(  # a head's (1, C) as (C, 1)
            [jnp.sum(eye * beta_ref[0, h, pl.ds(n, 1), :], axis=1, keepdims=True) for h in range(heads)], axis=0)
        parts = _chunk_parts(
            stacked(q_ref, keys), stacked(k_ref, keys), stacked(v_ref, values), stacked(g_ref, keys),
            beta_col, dcol_ref[at, :], drow_ref[n], jnp.full((1, 1), prev_ref[n], jnp.int32), sub,
        )
        o, state_ref[...] = _chunk_step(state_ref[...], *parts)
        for h in range(heads):
            o_ref[0, at, h * values:(h + 1) * values] = o[h * chunk:(h + 1) * chunk].astype(o_ref.dtype)
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
            pl.BlockSpec((n, 1, hc * chunk), lambda a, h, *_: (0, 0, 0)),
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
    )(prev, q, k, v, g, beta, doc.reshape(tp, 1), jnp.tile(doc.reshape(n, 1, chunk), (1, 1, hc)))
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
        split(q), split(k), split(v), split(g), beta[..., None],
        doc.reshape(n, chunk, 1), doc.reshape(n, 1, chunk), prev.reshape(n, 1, 1),
    )  # each (M, N, H, ...), of one head joined
    step = jax.vmap(jax.vmap(_chunk_step))

    def one_chunk(state, xs):
        o, state = step(state, *xs)
        return state, o

    keys = q.shape[2] // heads
    _, o = jax.lax.scan(one_chunk, jnp.zeros((m, heads, 1, values, keys), F32),
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

"""Kimi Delta Attention's short convolution, SiLU and L2 norm of one stream as
one pass (Pallas TPU kernel ``kda_conv``).

Between the projection and the scan each of ``q``, ``k``, ``v`` goes through a
depthwise causal convolution of a few taps, SiLU and (``q``, ``k``) an L2 norm
over the head, ``q`` also times ``width ** -0.5``:

    y_t = silu(sum_s w[taps - 1 - s] * u_{t - s} [pos_t >= s]);  y / sqrt(sum_head y^2 + 1e-6)

a tap that reaches before the token's document began (``pos_t < s``) reading
zero. The plain body (``problems/lm/model.py`` ``short_conv`` and the two norm
lines) makes a padded float32 copy of the stream a tap and passes over it
again for the mask, the sum, SiLU, the squares, the scale and the cast: about
31 bytes an element through HBM where the semantics need four (PERF.md section
6, PR 33). Here a grid cell holds one member's whole row of ``LANES_A_CELL``
channels in VMEM, as ``kda_scan`` fetches it, and walks it ``TOKEN_BLOCK``
tokens at a time: the block is cast to float32 once, the tap ``s`` tokens back
is a sublane roll of the block with the previous block's last rows before it
(carried in registers, zero at the row's start: no halo is fetched, nothing is
padded), masked by ``pos >= s``; the member's taps multiply, SiLU, the sum of
squares over each head's lanes, ``rsqrt``, and one rounding to the operands'
dtype at the store. The layouts are the projection's and the scan's own,
``(M, T, heads * width)``: nothing is transposed and no float32 copy is
written.

Precision: float32 between the read and the store, as the plain body; the
stream comes in and goes out in the operands' dtype (bfloat16 in the
benchmark), rounded where the plain body rounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_conv", "NORMALISE"]

F32 = jnp.float32
NORMALISE = (None, "l2", "l2_scaled")  # v, k, q
TOKEN_BLOCK = 512  # tokens a step of the walk along the row
LANES_A_CELL = 512  # channels a grid cell holds the row of
HALO = 16  # rows of the previous block kept before a block: a whole packed tile of a 16-bit dtype
L2_EPS = 1e-6


def _conv_kernel(u_ref, w_ref, pos_ref, o_ref, *, width: int, block: int, normalise):
    """One member, a cell's channels: the row's blocks in order, the last
    ``HALO`` rows of each carried to the next."""
    taps, lanes = w_ref.shape[1], u_ref.shape[2]
    w = [w_ref[0, taps - 1 - s:taps - s, :] for s in range(taps)]  # (1, lanes): the tap s tokens back

    def one_block(n, tail):
        at = pl.ds(pl.multiple_of(n * block, block), block)
        cur = u_ref[0, at, :].astype(F32)
        pos = pos_ref[at, :]  # (block, 1)
        ext = jnp.concatenate([tail, cur], axis=0)
        y = cur * w[0]
        for s in range(1, taps):
            past = pltpu.roll(ext, s, 0)[HALO:]  # row i: the stream s tokens before the block's token i
            y = y + jnp.where(pos >= s, past, 0.0) * w[s]
        y = y * jax.nn.sigmoid(y)
        if normalise is not None:
            heads = []
            for lo in range(0, lanes, width):
                yh = y[:, lo:lo + width]
                yh = yh * jax.lax.rsqrt(jnp.sum(yh * yh, axis=1, keepdims=True) + L2_EPS)
                heads.append(yh * width**-0.5 if normalise == "l2_scaled" else yh)  # the plain body's order
            y = jnp.concatenate(heads, axis=1)
        o_ref[0, at, :] = y.astype(o_ref.dtype)
        return cur[block - HALO:]

    jax.lax.fori_loop(0, u_ref.shape[1] // block, one_block, jnp.zeros((HALO, lanes), F32))


def _check(u, w, pos, width: int, normalise) -> None:
    ok = (u.ndim == 3 and w.ndim == 3 and pos.ndim == 1 and normalise in NORMALISE and width > 0
          and w.shape[0] == u.shape[0] and w.shape[2] == u.shape[2] and pos.shape[0] == u.shape[1]
          and u.shape[2] % width == 0 and 1 <= w.shape[1] <= HALO + 1)
    if not ok:
        raise ValueError(
            f"kda_conv: u {u.shape}, w {w.shape}, pos {pos.shape} are not (M, T, H * width), (M, taps, H * width), "
            f"(T,) for width = {width} and at most {HALO + 1} taps, or normalise {normalise!r} is not one of {NORMALISE}"
        )


@functools.partial(jax.jit, static_argnames=("width", "normalise", "interpret"))
def kda_conv(u, w, pos, *, width: int, normalise=None, interpret: bool = False):
    """A stream's short convolution, SiLU and L2 norm for ``M`` members, by
    the kernel; ``(M, T, heads * width)`` in ``u``'s dtype.

    ``u`` ``(M, T, heads * width)``: the projection's output. ``w`` ``(M,
    taps, heads * width)``: each member's own taps, ``w[:, j]`` the tap
    ``taps - 1 - j`` tokens back (the last on the token itself). ``pos``
    ``(T,)``: each token's position in its document. ``normalise``: ``None``
    (``v``), ``"l2"`` over each head's ``width`` channels (``k``) or
    ``"l2_scaled"``, that times ``width ** -0.5`` (``q``). Compiled
    (``interpret`` False) a head is whole lane tiles wide (128). One jitted
    function: the layers that call it at one shape share one lowering."""
    _check(u, w, pos, width, normalise)
    m, t, channels = u.shape
    block = min(TOKEN_BLOCK, -(-t // HALO) * HALO)
    pad = -t % block
    if pad:  # tokens past the row's end: they follow nothing and are cut off
        u, pos = jnp.pad(u, ((0, 0), (0, pad), (0, 0))), jnp.pad(pos, (0, pad))
    tp = t + pad
    heads = channels // width
    lanes = width * max(h for h in range(1, max(LANES_A_CELL // width, 1) + 1) if heads % h == 0)  # whole heads a cell
    row = lambda rows: pl.BlockSpec((1, rows, lanes), lambda a, c: (a, 0, c))
    out = pl.pallas_call(
        functools.partial(_conv_kernel, width=width, block=block, normalise=normalise),
        grid=(m, channels // lanes),
        in_specs=[row(tp), row(w.shape[1]), pl.BlockSpec((tp, 1), lambda a, c: (0, 0))],
        out_specs=row(tp),
        out_shape=jax.ShapeDtypeStruct((m, tp, channels), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="kda_conv",
    )(u, w.astype(F32), pos.astype(jnp.int32).reshape(tp, 1))
    return out[:, :t]

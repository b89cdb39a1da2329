"""Fused big-policy rollout kernel (Pallas TPU): humanoid-scale episodes
with the whole MLP resident in VMEM.

The humanoid-scale workload (chain_walker: obs=244, act=17, 2-hidden MLP,
dim≈21k) is HBM-bound on the standard scan engine: every env step re-reads
every individual's ~84 KB of policy weights from HBM — ~4 bytes of weight
traffic per 2 flops. The reference's engine shape (brax.py:62-97) has the
same roofline.

This kernel flips the roofline: a tile of 128 individuals' FULL weight
matrices (~10.8 MB f32) is loaded into VMEM once per episode and reused
across all T steps; env state lives as (component, tile) planes; each
layer is a static loop of full-width (rows, 128) VPU fused
multiply-adds (per-individual matvecs cannot use the MXU — every lane
carries different weights). HBM sees one weight read and one fitness
write per env per episode. Termination is a sticky in-kernel done mask
with per-tile early exit: the loop is a ``while_loop`` whose state is
packed into ONE uniform (rows, tile) block — Mosaic rejects mixed-shape
while carries, but a single packed carry compiles; never-terminating
envs can opt out via ``PlaneEnv(terminating=False)`` for the
better-pipelining fixed-T ``fori_loop``.

Layouts:
- weights per layer ``(fan_in, fan_out, n)`` — individual in the lane
  dimension, so ``w[k]`` is a ``(fan_out, tile)`` vreg block;
- or the population whole, as the flat genome ``(dim, n)`` (``genome=``,
  one ``(dim, tile)`` block a grid cell): a leaf whose first row ``rows``
  names is read IN PLACE, ``w[k]`` being the static row range
  ``off + k * fan_out`` to ``off + (k + 1) * fan_out`` of that block, so
  nothing cuts the layer out of the genome and writes it a second time
  before the kernel reads it. :func:`genome_rows` is the rule: a leaf is
  read in place when its first row and its ``fan_out`` are multiples of
  the resident dtype's sublane packing (8 rows of float32, 16 of
  bfloat16) — then ``w[k]`` is the same whole aligned tiles, the same
  vector loads, as in a block of its own; any other leaf is passed as
  its own block as above. For ``mlp_policy`` 244-64-64-17
  (``ravel_pytree`` order ``b0 w0 b1 w1 b2 w2``, rows 0, 64, 15,680,
  15,744, 19,840, 19,857) that is ``b0 w0 b1 w1`` in place, 19,840 of
  20,945 rows, and ``b2 w2`` (``fan_out`` 17) cut;
- env state as a dict of ``(components, n)`` planes (:class:`PlaneEnv`);
- observations assembled in-kernel as one ``(obs_dim, tile)`` block whose
  row order matches the AoS env's observation vector exactly — the same
  genome drives both engines bit-compatibly.

``chain_walker_planes`` re-expresses control/walker.py's physics over
planes; tests/test_kernels_mlp.py pins the kernel to the plane math
exactly and to the scan engine's fitness within float tolerance.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NO_ROWS = ((None, None),)  # ``rows`` of a layer whose leaves both come as blocks

PlaneState = Dict[str, jax.Array]


class PlaneEnv(NamedTuple):
    """An env in plane (component-major) form for the big-policy kernel.

    ``base``: the AoS :class:`EnvSpec` (resets come from it — same draws
    as the scan engine). ``to_planes``: batched AoS state ``(n, ...)`` ->
    dict of ``(components, n)`` arrays. ``obs_planes``: plane state ->
    ``(obs_dim, tile)`` observation block (row order == the AoS obs
    vector). ``step_planes``: ``(state, act (act_dim, tile)) ->
    (state, reward (1, tile), done (1, tile) bool)``.
    """

    base: Any
    to_planes: Callable[[Any], PlaneState]
    obs_planes: Callable[[PlaneState], jax.Array]
    step_planes: Callable[
        [PlaneState, jax.Array], Tuple[PlaneState, jax.Array, jax.Array]
    ]
    # terminating=True: the kernel loop is a while_loop exiting each tile
    # as soon as all of its envs are done. Mosaic rejects mixed-shape
    # while carries, so the state planes are packed into ONE
    # (total_rows, tile) block for the loop and sliced apart each step.
    terminating: bool = True


# ------------------------------------------------------------ chain walker


def chain_walker_planes(**kwargs) -> PlaneEnv:
    """control/walker.py's chain_walker over (component, tile) planes.

    Identical math to the AoS implementation (walker.py:_forces/obs/step),
    with masses in the sublane dimension and individuals in lanes; the
    ``.at[].add`` endpoint scatters become pad-and-add over the mass axis.
    """
    from ..problems.neuroevolution.control.walker import (
        chain_walker,
        walker_config,
    )

    cfg = walker_config(**kwargs)  # same constants as the AoS env, always
    base = chain_walker(**cfg)
    n_masses = cfg["n_masses"]
    act_dim = cfg["act_dim"]
    substeps = cfg["substeps"]
    dt = cfg["dt"]
    rod_length = cfg["rod_length"]
    rod_stiffness = cfg["rod_stiffness"]
    rod_damping = cfg["rod_damping"]
    torque_scale = cfg["torque_scale"]
    ground_stiffness = cfg["ground_stiffness"]
    ground_damping = cfg["ground_damping"]
    friction = cfg["friction"]
    gravity = cfg["gravity"]
    obs_dim = cfg["obs_dim"]
    max_steps = cfg["max_steps"]
    n_links = n_masses - 1
    stand_height = 0.3 * n_links * rod_length
    h = dt / substeps

    def to_planes(state) -> PlaneState:
        pos, vel, prev_a, t = state  # (n, 25, 2), (n, 25, 2), (n, 17), (n,)
        return {
            "px": pos[..., 0].T,  # (25, n)
            "py": pos[..., 1].T,
            "vx": vel[..., 0].T,
            "vy": vel[..., 1].T,
            "pa": prev_a.T,  # (17, n)
            "t": t[None, :].astype(jnp.float32),  # (1, n)
            "done": jnp.zeros((1, pos.shape[0]), dtype=jnp.float32),
        }

    def _pad_ends(f_link):
        """(n_links, tile) per-link force -> per-mass sum: +f on the lower
        endpoint, -f on the upper (walker.py's .at[:-1].add / .at[1:].add)."""
        zero = jnp.zeros_like(f_link[:1])
        return jnp.concatenate([f_link, zero], axis=0) - jnp.concatenate(
            [zero, f_link], axis=0
        )

    def _ground(py, vy):
        # action-independent contact normal force (same split as the AoS
        # engine's _ground — the obs path needs only this)
        depth = jnp.maximum(-py, 0.0)
        contact = (depth > 0.0).astype(py.dtype)
        f_n = ground_stiffness * depth - ground_damping * vy * contact
        return jnp.maximum(f_n, 0.0) * contact

    def _forces(px, py, vx, vy, scaled_act):
        # scaled_act = tanh(act) * torque_scale, hoisted by the caller
        # (substep-invariant); rod directions via one rsqrt instead of
        # sqrt + three divides — mirrors walker.py::_forces exactly
        fx = jnp.zeros_like(px)
        fy = jnp.full_like(py, -gravity)

        dx = px[1:] - px[:-1]
        dy = py[1:] - py[:-1]
        dd = dx * dx + dy * dy + 1e-12
        inv = jax.lax.rsqrt(dd)
        dist = dd * inv
        ux, uy = dx * inv, dy * inv
        rel_v = (vx[1:] - vx[:-1]) * ux + (vy[1:] - vy[:-1]) * uy
        mag = rod_stiffness * (dist - rod_length) + rod_damping * rel_v
        fx = fx + _pad_ends(mag * ux)
        fy = fy + _pad_ends(mag * uy)

        tq = jnp.concatenate(
            [
                scaled_act,
                jnp.zeros(
                    (n_links - act_dim,) + scaled_act.shape[1:],
                    scaled_act.dtype,
                ),
            ],
            axis=0,
        )
        coef = tq * jnp.minimum(inv, 1e6)
        fx = fx + _pad_ends(coef * -uy)
        fy = fy + _pad_ends(coef * ux)

        f_n = _ground(py, vy)
        lim = jnp.abs(vx) * 50.0
        f_t = -jnp.clip(friction * f_n * jnp.sign(vx), -lim, lim)
        return fx + f_t, fy + f_n

    def obs_planes(s: PlaneState) -> jax.Array:
        px, py, vx, vy = s["px"], s["py"], s["vx"], s["vy"]
        rel_x = px - px[:1]
        rel_y = py - py[:1]
        dx = px[1:] - px[:-1]
        dy = py[1:] - py[:-1]
        dd = dx * dx + dy * dy + 1e-12
        inv = jax.lax.rsqrt(dd)  # one rsqrt replaces sqrt + three divides
        dist = dd * inv
        strain = dist * (1.0 / rod_length) - 1.0
        ang_cos = dx * inv
        ang_sin = dy * inv
        rvx = vx[1:] - vx[:-1]
        rvy = vy[1:] - vy[:-1]
        ang_vel = (dx * rvy - dy * rvx) * (inv * inv)
        f_n = _ground(py, vy)  # action-independent part of _forces
        tile = px.shape[-1]
        # interleave (m0x, m0y, m1x, ...) to match pos.reshape(-1)
        rel = jnp.stack([rel_x, rel_y], axis=1).reshape(2 * n_masses, tile)
        vel = jnp.stack([vx, vy], axis=1).reshape(2 * n_masses, tile)
        parts = jnp.concatenate(
            [
                rel,
                vel,
                ang_cos,
                ang_sin,
                ang_vel,
                strain,
                f_n * 1e-2,
                s["pa"],
                py[:1],
                py[-1:],
                vx[:1],
                vy[:1],
            ],
            axis=0,
        )
        k = parts.shape[0]
        if k >= obs_dim:
            return parts[:obs_dim]
        return jnp.concatenate(
            [parts, jnp.zeros((obs_dim - k, tile), parts.dtype)], axis=0
        )

    def step_planes(s: PlaneState, act: jax.Array):
        px, py, vx, vy = s["px"], s["py"], s["vx"], s["vy"]
        ta = jnp.tanh(act)  # substep-invariant: hoisted out of the loop
        scaled_act = ta * torque_scale

        def substep(_, c):
            px, py, vx, vy = c
            fx, fy = _forces(px, py, vx, vy, scaled_act)
            vx = vx + h * fx
            vy = vy + h * fy
            return px + h * vx, py + h * vy, vx, vy

        px, py, vx, vy = jax.lax.fori_loop(
            0, substeps, substep, (px, py, vx, vy)
        )
        com_vx = jnp.mean(vx, axis=0, keepdims=True)  # (1, tile)
        ctrl = 0.01 * jnp.sum(ta * ta, axis=0, keepdims=True)
        reward = com_vx + 1.0 - ctrl
        head_y = py[-1:]
        fell = head_y < stand_height
        mx = jnp.maximum(
            jnp.max(jnp.abs(px), axis=0, keepdims=True),
            jnp.max(jnp.abs(py), axis=0, keepdims=True),
        )
        exploded = ~(jnp.isfinite(mx)) | (mx > 1e3)
        t = s["t"] + 1.0
        done = fell | exploded | (t >= max_steps)
        new = dict(s)
        new.update(px=px, py=py, vx=vx, vy=vy, pa=act, t=t)
        return new, reward, done

    return PlaneEnv(
        base=base,
        to_planes=to_planes,
        obs_planes=obs_planes,
        step_planes=step_planes,
    )


# ------------------------------------------------------------------ kernel


def _mlp_planes(w_refs, b_refs, obs: jax.Array, sizes, linear=()) -> jax.Array:
    """(act_dim, tile) actions; per-individual matvecs as static loops of
    full-width (fan_out, tile) FMAs (weights differ per lane -> no MXU).

    Weight planes may be bf16 (``fused_mlp_rollout(weight_dtype=...)``):
    each slice is widened to f32 at load and the accumulator stays f32.
    The load-byte saving is paid for in widening converts; what bf16
    buys is a 2x per-tile policy budget and half the per-episode HBM
    weight traffic, and it fails the walker cell's ``correct`` (PERF.md
    section 6, the table of limits).

    ``linear``: layer indices whose output skips the tanh — consecutive
    linear layers express a low-rank factorization (a rank-r input layer
    is ``sizes=(obs, r, h, ...), linear=(0,)``), the "fewer MACs"
    lever. Matches ``mlp_policy(linear_layers=...)``.

    A layer's entry in ``w_refs`` / ``b_refs`` is its own block,
    ``(fan_in, fan_out, tile)`` / ``(fan_out, tile)``, or a pair ``(flat
    genome block (dim, tile), first row)``: then ``w[k]`` and the bias
    are static row ranges of that block, read where they lie."""
    h = obs
    n_layers = len(sizes) - 1
    for li in range(n_layers):
        fan_in, fan_out = sizes[li], sizes[li + 1]
        b, w = b_refs[li], w_refs[li]
        if isinstance(b, tuple):  # (flat genome block, the bias's first row)
            b = b[0][b[1] : b[1] + fan_out]
        else:
            b = b[...]
        acc = b.astype(jnp.float32)  # (fan_out, tile)
        for k in range(fan_in):
            if isinstance(w, tuple):  # (flat genome block, w[0]'s first row)
                lo = w[1] + k * fan_out
                wk = w[0][lo : lo + fan_out]
            else:
                wk = w[k]
            acc = acc + h[k : k + 1] * wk.astype(jnp.float32)
        h = acc if (li == n_layers - 1 or li in linear) else jnp.tanh(acc)
    return h


def _rollout_mlp_kernel(
    refs,
    out_ref,
    *,
    T: int,
    sizes: Tuple[int, ...],
    step_planes: Callable,
    obs_planes: Callable,
    state_keys: Tuple[str, ...],
    early_stop: bool,
    linear: Tuple[int, ...] = (),
    rows: Any,
):
    # refs: the flat genome block where ``rows`` says some leaf is read in
    # place, then the weight blocks, the bias blocks (of the leaves that
    # have their own), then the state planes
    refs = list(refs)
    genome_ref = refs.pop(0) if rows != _NO_ROWS * len(rows) else None
    w_refs = [
        refs.pop(0) if r[0] is None else (genome_ref, r[0]) for r in rows
    ]
    b_refs = [
        refs.pop(0) if r[1] is None else (genome_ref, r[1]) for r in rows
    ]
    state_refs = refs
    # state blocks arrive (1, C, tile): drop the episode block dim
    state = {k: r[0] for k, r in zip(state_keys, state_refs)}
    tile = state[state_keys[0]].shape[-1]
    total0 = jnp.zeros((1, tile), dtype=out_ref.dtype)
    done0 = state.pop("done")  # (1, tile) float 0/1

    def body(state, done, total):
        obs = obs_planes(state)
        act = _mlp_planes(w_refs, b_refs, obs, sizes, linear)
        state, reward, step_done = step_planes(state, act)
        total = total + jnp.where(done > 0.5, 0.0, reward)
        done = jnp.maximum(done, step_done.astype(done.dtype))
        return state, done, total

    if early_stop:
        # per-tile early exit. Mosaic rejects MIXED-shape while carries,
        # so the whole loop state is packed into ONE (rows, tile) block
        # and sliced apart each iteration (sublane slices are cheap).
        keys = [k for k in state_keys if k != "done"]
        for k in keys:
            # the packed carry concatenates all planes: a non-uniform
            # dtype would be silently promoted, diverging from the fori
            # branch — make the constraint loud instead
            if state[k].dtype != out_ref.dtype:
                raise TypeError(
                    f"early_stop requires all state planes to be "
                    f"{out_ref.dtype}; plane {k!r} is {state[k].dtype} "
                    "(use terminating=False or cast in to_planes)"
                )
        rows = [state[k].shape[0] for k in keys]
        offs = [0]
        for r in rows:
            offs.append(offs[-1] + r)
        done_off = offs[-1]

        def pack(state, done, total):
            return jnp.concatenate(
                [state[k] for k in keys] + [done, total], axis=0
            )

        def unpack(big):
            st = {
                k: big[o : o + r] for k, o, r in zip(keys, offs[:-1], rows)
            }
            return st, big[done_off : done_off + 1], big[done_off + 1 :]

        def cond(c):
            t, big = c
            return (t < T) & jnp.any(big[done_off : done_off + 1] < 0.5)

        def wbody(c):
            t, big = c
            st, done, total = unpack(big)
            st, done, total = body(st, done, total)
            return t + 1, pack(st, done, total)

        _, big = jax.lax.while_loop(
            cond, wbody, (jnp.int32(0), pack(state, done0, total0))
        )
        total = big[done_off + 1 :]
    else:
        _, _, total = jax.lax.fori_loop(
            0, T, lambda _, c: body(*c), (state, done0, total0)
        )
    out_ref[...] = total.reshape(out_ref.shape)


_VMEM_MARGIN = 8 * 1024 * 1024  # scratch/accumulator slack past residency
_VMEM_CAP = 100 * 2**20  # stay under the chip's VMEM (v5e: 128 MiB)


def genome_rows(offsets, sizes, dtype) -> Tuple[Tuple[Any, Any], ...]:
    """Per layer ``(w's first row, b's first row)`` for the leaves of an
    ``mlp_policy`` genome the kernel reads in place, ``None`` for a leaf
    that is cut out and passed as its own block — the ``rows`` of
    :func:`fused_mlp_rollout`. ``offsets``: the params tree with each
    leaf's first row in the flat genome (``TreeAndVector.offsets``);
    ``dtype``: what the planes are resident as.

    A rule of shapes and offsets alone: in place when the leaf's first row
    and its ``fan_out`` are multiples of the dtype's sublane packing (8
    rows of a 4-byte type to a tile, 16 of a 2-byte one), so that every
    ``w[k]`` is whole aligned tiles and loads as it does from its own
    block. An unaligned row range compiles too (the walker's ``w2[k]``: 17
    rows at 19,857 + 17 k) and gives the same bits; float32 on the chip,
    the kernel alone ran no slower with every leaf in place (PERF.md, PR
    27, which also says what widening the rule waits for)."""
    pack = 32 // jnp.dtype(dtype).itemsize

    def first_row(off, fan_out):
        return off if off % pack == 0 and fan_out % pack == 0 else None

    return tuple(
        (first_row(l["w"], fan_out), first_row(l["b"], fan_out))
        for l, fan_out in zip(offsets, sizes[1:])
    )


def _vmem_plan(weights, biases, tile: int, genome=None) -> Tuple[int, int]:
    """``(resident bytes per grid cell, vmem_limit_bytes)`` for the fused
    kernel: one tile of every block (each layer's weight/bias planes, or
    the flat genome and the leaves cut out of it) is VMEM-resident,
    Pallas double-buffers the blocks across grid cells, and the Mosaic
    scoped-vmem budget is raised to twice the residency plus margin
    (capped below the chip's VMEM). The single source of truth for both
    the ``pallas_call`` compiler params and
    :func:`fused_rollout_analysis`'s headroom report."""
    blocks = [x for x in (genome, *weights, *biases) if x is not None]
    per_cell = sum(
        math.prod(x.shape[:-1]) * tile * x.dtype.itemsize for x in blocks
    )
    return per_cell, min(2 * per_cell + _VMEM_MARGIN, _VMEM_CAP)


def fused_rollout_analysis(
    weights: Tuple[jax.Array, ...],
    biases: Tuple[jax.Array, ...],
    tile: int = _LANES,
    weight_dtype: Any = None,
    params: Any = None,
) -> dict:
    """Static VMEM-residency report for :func:`fused_mlp_rollout` — the
    kernel half of the roofline analytics layer (core/xla_cost.py covers
    the XLA-visible FLOPs/bytes; Mosaic's VMEM budget is invisible to
    HLO cost analysis, so it is accounted here from the same arithmetic
    the kernel's ``CompilerParams`` uses).

    Pure host-side arithmetic on shapes/dtypes (no compile, no
    callbacks): the per-grid-cell resident weight/bias bytes, the
    double-buffered requirement, the ``vmem_limit_bytes`` the kernel
    will request, and the headroom between them. Negative headroom means
    the cap clipped the request — the compile will fail or thrash; shrink
    ``tile`` or narrow ``weight_dtype`` (bf16 halves residency).

    ``params``: one member's ``mlp_policy`` params tree (arrays or
    shapes). With it the report is of the flat-genome call a workflow
    makes for that tree (:func:`genome_rows` at the resident dtype):
    ``rows_in_place``, the genome's rows the kernel reads where they lie,
    ``rows_cut``, those cut out into blocks of their own first, and the
    residency of the whole genome block plus the cut leaves."""
    dtype = jnp.dtype(
        weight_dtype if weight_dtype is not None else weights[0].dtype
    )
    genome, rows = None, {}
    if params is not None:
        from ..utils.common import leaf_offsets

        offsets, dim = leaf_offsets(params)
        sizes = (weights[0].shape[0],) + tuple(w.shape[1] for w in weights)
        in_place = genome_rows(offsets, sizes, dtype)
        # what the kernel is handed: the leaves cut out, and the genome
        # whole where any leaf is read in place
        weights = tuple(w for r, w in zip(in_place, weights) if r[0] is None)
        biases = tuple(b for r, b in zip(in_place, biases) if r[1] is None)
        cut = sum(math.prod(x.shape[:-1]) for x in (*weights, *biases))
        rows = {"rows_in_place": dim - cut, "rows_cut": cut}
        if cut < dim:
            genome = jax.ShapeDtypeStruct((dim, tile), dtype)
    per_cell, limit = _vmem_plan(
        [jax.ShapeDtypeStruct(w.shape, dtype) for w in weights],
        [jax.ShapeDtypeStruct(b.shape, dtype) for b in biases],
        tile,
        genome,
    )
    return {
        "tile": tile,
        "weight_dtype": str(dtype),
        "resident_bytes_per_cell": per_cell,
        "double_buffered_bytes": 2 * per_cell,
        "vmem_limit_bytes": limit,
        "vmem_cap_bytes": _VMEM_CAP,
        "headroom_bytes": limit - 2 * per_cell,
        **rows,
    }


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "sizes", "step_planes", "obs_planes", "tile", "episodes",
        "early_stop", "interpret", "weight_dtype", "linear", "rows",
    ),
)
def fused_mlp_rollout(
    weights: Tuple[jax.Array, ...],
    biases: Tuple[jax.Array, ...],
    init_state: PlaneState,
    T: int,
    sizes: Tuple[int, ...],
    step_planes: Callable,
    obs_planes: Callable,
    tile: int = _LANES,
    episodes: int = 1,
    early_stop: bool = True,
    interpret: bool = False,
    weight_dtype: Any = None,
    linear: Tuple[int, ...] = (),
    genome: Any = None,
    rows: Any = None,
) -> jax.Array:
    """Total episode reward per env, fully fused, weights VMEM-resident.

    Args:
        weights: per layer ``(fan_in, fan_out, n)`` (individual = lane);
            ``None`` for a layer read in place from ``genome``.
        biases: per layer ``(fan_out, n)``; ``None`` likewise.
        init_state: dict of ``(episodes * n,)``-env plane arrays, each
            ``(C, episodes * n)``, EPISODE-MAJOR along the env axis. Must
            contain a ``"done"`` plane (float 0/1) consumed as the initial
            done mask.
        T / sizes: horizon and MLP layer sizes (obs, h1, ..., act).
        tile: individuals per grid cell (multiple of 128; default 128 —
            the f32 VMEM budget for the default walker shape; bf16
            residency fits 256).
        weight_dtype: VMEM residency dtype for the weight/bias planes
            (e.g. ``jnp.bfloat16``); None keeps the input dtype. The MLP
            accumulator is always f32 and all env math stays f32 — only
            the resident policy planes narrow. At humanoid scale the
            inner loop re-streams the weight planes from VMEM every env
            step, so bf16 both halves that bandwidth (the kernel's
            roofline) and doubles the per-tile policy budget.
        linear: layer indices with no tanh after them (low-rank
            factorized layers — see :func:`_mlp_planes`).
        genome: the population as the flat genome ``(dim, n)``, individual
            = lane, with ``rows``; ``None`` when every leaf comes as its
            own block.
        rows: per layer ``(w's first row, b's first row)`` in ``genome``
            for a leaf read in place, ``None`` for one passed in
            ``weights`` / ``biases`` (:func:`genome_rows`, module
            docstring). Same loads, same order of multiply and add:
            bit-identical to the per-layer call.

    Returns:
        ``(episodes * n,)`` total rewards, episode-major (always f32).
    """
    if tile % _LANES != 0:
        raise ValueError(f"tile must be a multiple of {_LANES}, got {tile}")
    n_layers = len(sizes) - 1
    assert len(weights) == n_layers and len(biases) == n_layers
    # mirror mlp_policy(linear_layers=...): a typo'd (or negative) index
    # would be silently ignored by _mlp_planes' loop and the user would
    # train a different architecture than they asked for
    if not set(linear) <= set(range(n_layers)):
        raise ValueError(
            f"linear {sorted(set(linear))} out of range for {n_layers} "
            "layers (negative indices not supported)"
        )
    if rows is None:
        rows = _NO_ROWS * n_layers
    if rows == _NO_ROWS * n_layers:
        genome = None  # nothing to read in place: no block of it
    elif genome is None:
        raise ValueError(f"rows {rows} name leaves of a genome, and none came")
    if any(
        (x is None) == (r is None)
        for pair, leaves in zip(rows, zip(weights, biases))
        for r, x in zip(pair, leaves)
    ):
        raise ValueError(
            "a leaf comes in weights/biases or has its first row of genome "
            f"in rows, one of the two (rows {rows})"
        )
    # every array the kernel is handed a block of, member last: the genome
    # where some leaf is read in place, then the leaves that come as their own
    planes = [x for x in (genome, *weights, *biases) if x is not None]
    if weight_dtype is not None:
        planes = [x.astype(weight_dtype) for x in planes]
    n = planes[0].shape[-1]
    pad = (-n) % tile
    n_pad = n + pad
    if pad:
        planes = [
            jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) for x in planes
        ]
        init_state = {
            k: jnp.pad(
                v.reshape(v.shape[0], episodes, n), ((0, 0), (0, 0), (0, pad))
            ).reshape(v.shape[0], episodes * n_pad)
            for k, v in init_state.items()
        }
        # padded envs must not keep the while_loop alive
        d = init_state["done"].reshape(1, episodes, n_pad)
        init_state["done"] = d.at[:, :, n:].set(1.0).reshape(1, episodes * n_pad)
    state_3d = {
        k: v.reshape(v.shape[0], episodes, n_pad).transpose(1, 0, 2)
        for k, v in sorted(init_state.items())
    }  # (episodes, C, n_pad)
    state_keys = tuple(state_3d)
    blocks = n_pad // tile

    kernel = functools.partial(
        _rollout_mlp_kernel,
        T=T,
        sizes=sizes,
        step_planes=step_planes,
        obs_planes=obs_planes,
        state_keys=state_keys,
        early_stop=early_stop,
        linear=linear,
        rows=rows,
    )

    def wrapped(*refs):
        kernel(refs[:-1], refs[-1])

    # whole in every dimension but the last, one tile of members a cell
    p_specs = [
        pl.BlockSpec(
            x.shape[:-1] + (tile,),
            lambda b, e, lead=(0,) * (x.ndim - 1): lead + (b,),
        )
        for x in planes
    ]
    s_specs = [
        pl.BlockSpec(
            (1, state_3d[k].shape[1], tile), lambda b, e: (e, 0, b)
        )
        for k in state_keys
    ]
    kwargs = {}
    if not interpret:
        # the weight blocks are double-buffered across grid cells; the
        # default 16 MB scoped-vmem budget is too small for the resident
        # weights — raise it (v5e VMEM is far larger than the default cap)
        _, vmem_limit = _vmem_plan(planes, (), tile)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit
        )
    out_dtype = jnp.float32  # the documented reward-sum contract
    total = pl.pallas_call(
        wrapped,
        # episodes INNERMOST: consecutive grid steps differing only in the
        # episode index revisit unchanged weight/bias blocks, so Pallas
        # elides their re-fetch — the resident policy tile is DMA'd once
        # per block regardless of episode count
        grid=(blocks, episodes),
        in_specs=p_specs + s_specs,
        # 3-D output (episodes, 1, n_pad): Mosaic's lowering constrains
        # only the LAST TWO block dims (divisible by (8, 128) or equal to
        # the array dims); a 2-D (episodes, n_pad) array with block
        # (1, tile) violates that whenever episodes > 1 — a latent
        # multi-episode compile failure the CPU interpret tests never saw
        out_specs=pl.BlockSpec((1, 1, tile), lambda b, e: (e, 0, b)),
        out_shape=jax.ShapeDtypeStruct((episodes, 1, n_pad), out_dtype),
        interpret=interpret,
        # the name a trace knows the custom call by (the benchmark's
        # kernel_event_pattern matches it)
        name="fused_mlp_rollout",
        **kwargs,
    )(*planes, *state_3d.values())
    return total[:, 0, :n].reshape(episodes * n)

"""Fused policy-rollout kernel (Pallas TPU): the whole episode in VMEM.

The scan-based rollout (problems/neuroevolution/rollout.py) is bound not
by FLOPs but by fusion boundaries: each of the T environment steps
round-trips the carry (env state, observations, hidden activations)
through HBM, so at pendulum scale the chip runs at a few percent of VPU
peak. This kernel runs the ENTIRE episode for a tile of environments
inside one Pallas program — policy weights, env state and activations
stay resident in VMEM across all T steps; HBM sees one theta read and one
fitness write per environment, total.

Scope: the MLP policy from ``flat_mlp_policy`` flat genomes and envs
expressed in SoA form over component arrays. Built-ins: ``pendulum_soa``
(chip_smoke.py's pendulum phase), ``cartpole_soa``, ``mountain_car_soa`` and
``acrobot_soa`` — terminating envs run under a sticky in-kernel done
mask with the standard engine's frozen-episode reward accounting, so
fitness matches both ``early_exit`` modes of the generic engine (which
remains the default; this kernel is the opt-in fast path, strongest on
never-terminating or long-surviving episodes).

CPU interpret-mode tests (tests/test_kernels.py) pin the kernel to the
scan rollout's numerics; no benchmark cell times it yet (PERF.md section
7, row 6). The wiring into :class:`PolicyRolloutProblem` (the ``fused_env=``
constructor parameter) lives in problems/neuroevolution/rollout.py.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# environments in SoA form: state is a dict of per-env component arrays
SoAState = Dict[str, jax.Array]

_LANES = 128  # TPU vreg lane width
_PAD_KEY = "__pad__"  # reserved state plane marking padded (dead) lanes


class SoAEnv(NamedTuple):
    """An :class:`~...control.envs.EnvSpec` re-expressed over SoA component
    planes, for the fused kernel. ``base`` keeps the AoS spec (used for
    reset — so the fused path draws the *same* initial states as the scan
    path and the numerics-pinning tests can compare them directly);
    ``to_soa`` converts a batched AoS state ``(n, ...)`` into the dict of
    ``(n,)`` component arrays that ``step_soa``/``obs_soa`` operate on.
    ``step_soa`` returns ``(state, reward, done)`` — terminating envs get
    a sticky in-kernel done mask (rewards after termination are dropped,
    exactly like the standard engine's frozen-episode accounting);
    never-terminating envs return a constant-False plane that the
    compiler eliminates."""

    base: Any  # EnvSpec
    to_soa: Callable[[Any], SoAState]
    obs_soa: Callable[[SoAState], Tuple[jax.Array, ...]]
    step_soa: Callable[
        [SoAState, Tuple[jax.Array, ...]],
        Tuple[SoAState, jax.Array, jax.Array],
    ]
    # terminating=True runs the kernel loop as a while_loop that exits a
    # tile as soon as ALL of its envs are done (per-tile early exit —
    # finer than the generic engine's global all-done test); False keeps
    # the fori_loop, which pipelines better when episodes never end
    terminating: bool = True


def pendulum_reset_soa(key: jax.Array, n: int) -> SoAState:
    """Matches control/envs.pendulum reset ranges (batched)."""
    k1, k2 = jax.random.split(key)
    return {
        "th": jax.random.uniform(k1, (n,), minval=-jnp.pi, maxval=jnp.pi),
        "thdot": jax.random.uniform(k2, (n,), minval=-1.0, maxval=1.0),
    }


def pendulum_obs_soa(s: SoAState) -> Tuple[jax.Array, ...]:
    return (jnp.cos(s["th"]), jnp.sin(s["th"]), s["thdot"])


def pendulum_step_soa(s: SoAState, a: Tuple[jax.Array, ...]):
    """One step on (tile,) component arrays; identical math to
    control/envs.pendulum (envs.py:76-101)."""
    max_speed, max_torque, dt, g = 8.0, 2.0, 0.05, 10.0
    th, thdot = s["th"], s["thdot"]
    u = jnp.clip(a[0], -max_torque, max_torque)
    norm_th = ((th + jnp.pi) % (2 * jnp.pi)) - jnp.pi
    cost = norm_th**2 + 0.1 * thdot**2 + 0.001 * u**2
    thdot = thdot + (3.0 * g / 2.0 * jnp.sin(th) + 3.0 * u) * dt
    thdot = jnp.clip(thdot, -max_speed, max_speed)
    never_done = jnp.zeros_like(th, dtype=bool)
    return {"th": th + thdot * dt, "thdot": thdot}, -cost, never_done


def pendulum_soa(max_steps: int = 200) -> SoAEnv:
    """The built-in pendulum :class:`SoAEnv` instance."""
    from ..problems.neuroevolution.control.envs import pendulum

    return SoAEnv(
        base=pendulum(max_steps=max_steps),
        to_soa=lambda s: {"th": s[..., 0], "thdot": s[..., 1]},
        obs_soa=pendulum_obs_soa,
        step_soa=pendulum_step_soa,
        terminating=False,
    )


def cartpole_soa(max_steps: int = 500) -> SoAEnv:
    """control/envs.cartpole over SoA planes (terminating: uses the
    kernel's sticky done mask). Identical math to envs.py:35-71."""
    from ..problems.neuroevolution.control.envs import cartpole

    gravity, masscart, masspole = 9.8, 1.0, 0.1
    total_mass = masscart + masspole
    length = 0.5
    polemass_length = masspole * length
    force_mag = 10.0
    tau = 0.02
    theta_limit = 12 * 2 * jnp.pi / 360
    x_limit = 2.4

    def obs_soa(s):
        return (s["x"], s["xd"], s["th"], s["thd"])

    def step_soa(s, a):
        # arithmetic select (2c-1 maps {0,1} -> {-1,+1}): scalar-branch
        # jnp.where on the episode blocks trips a Mosaic replicated-layout
        # bug ("invalid relayout: non-singleton logical dimension")
        go_right = (a[1] > a[0]).astype(a[0].dtype)
        force = force_mag * (2.0 * go_right - 1.0)
        x, x_dot, th, th_dot = s["x"], s["xd"], s["th"], s["thd"]
        costh, sinth = jnp.cos(th), jnp.sin(th)
        temp = (force + polemass_length * th_dot**2 * sinth) / total_mass
        thacc = (gravity * sinth - costh * temp) / (
            length * (4.0 / 3.0 - masspole * costh**2 / total_mass)
        )
        xacc = temp - polemass_length * thacc * costh / total_mass
        x = x + tau * x_dot
        x_dot = x_dot + tau * xacc
        th = th + tau * th_dot
        th_dot = th_dot + tau * thacc
        done = (jnp.abs(x) > x_limit) | (jnp.abs(th) > theta_limit)
        new = {"x": x, "xd": x_dot, "th": th, "thd": th_dot}
        # 1.0 written as a data-derived value: a pure constant splat here
        # is the one reward form that trips the Mosaic relayout bug
        reward = 1.0 + 0.0 * x
        return new, reward, done

    return SoAEnv(
        base=cartpole(max_steps=max_steps),
        to_soa=lambda s: {
            "x": s[..., 0], "xd": s[..., 1], "th": s[..., 2], "thd": s[..., 3]
        },
        obs_soa=obs_soa,
        step_soa=step_soa,
    )


def mountain_car_soa(max_steps: int = 999) -> SoAEnv:
    """control/envs.mountain_car over SoA planes (envs.py:106-127)."""
    from ..problems.neuroevolution.control.envs import mountain_car

    power = 0.0015

    def obs_soa(s):
        return (s["pos"], s["vel"])

    def step_soa(s, a):
        pos, vel = s["pos"], s["vel"]
        force = jnp.clip(a[0], -1.0, 1.0)
        vel = vel + force * power - 0.0025 * jnp.cos(3.0 * pos)
        vel = jnp.clip(vel, -0.07, 0.07)
        pos = jnp.clip(pos + vel, -1.2, 0.6)
        # arithmetic selects (see cartpole_soa: Mosaic replicated-layout)
        at_wall = ((pos <= -1.2) & (vel < 0)).astype(vel.dtype)
        vel = vel * (1.0 - at_wall)
        done = pos >= 0.45
        reward = 100.0 * done.astype(pos.dtype) - 0.1 * force**2
        return {"pos": pos, "vel": vel}, reward, done

    return SoAEnv(
        base=mountain_car(max_steps=max_steps),
        to_soa=lambda s: {"pos": s[..., 0], "vel": s[..., 1]},
        obs_soa=obs_soa,
        step_soa=step_soa,
    )


def acrobot_soa(max_steps: int = 500) -> SoAEnv:
    """control/envs.acrobot over SoA planes (envs.py:132-179); the
    3-logit argmax becomes nested elementwise selects (first-max wins,
    like jnp.argmax)."""
    from ..problems.neuroevolution.control.envs import acrobot

    dt = 0.2
    l1 = m1 = m2 = 1.0
    lc1 = lc2 = 0.5
    I1 = I2 = 1.0
    g = 9.8

    def obs_soa(s):
        t1, t2 = s["t1"], s["t2"]
        return (
            jnp.cos(t1), jnp.sin(t1), jnp.cos(t2), jnp.sin(t2),
            s["td1"], s["td2"],
        )

    def step_soa(s, a):
        # arithmetic argmax->torque (see cartpole_soa: Mosaic
        # replicated-layout); first-max wins like jnp.argmax
        c0 = ((a[0] >= a[1]) & (a[0] >= a[2])).astype(a[0].dtype)
        inner = (a[1] < a[2]).astype(a[0].dtype)  # 0 -> torque 0, 1 -> +1
        torque = -c0 + (1.0 - c0) * inner
        t1, t2, td1, td2 = s["t1"], s["t2"], s["td1"], s["td2"]
        d1 = (
            m1 * lc1**2
            + m2 * (l1**2 + lc2**2 + 2 * l1 * lc2 * jnp.cos(t2))
            + I1
            + I2
        )
        d2 = m2 * (lc2**2 + l1 * lc2 * jnp.cos(t2)) + I2
        phi2 = m2 * lc2 * g * jnp.cos(t1 + t2 - jnp.pi / 2.0)
        phi1 = (
            -m2 * l1 * lc2 * td2**2 * jnp.sin(t2)
            - 2 * m2 * l1 * lc2 * td2 * td1 * jnp.sin(t2)
            + (m1 * lc1 + m2 * l1) * g * jnp.cos(t1 - jnp.pi / 2.0)
            + phi2
        )
        tdd2 = (
            torque + d2 / d1 * phi1 - m2 * l1 * lc2 * td1**2 * jnp.sin(t2) - phi2
        ) / (m2 * lc2**2 + I2 - d2**2 / d1)
        tdd1 = -(d2 * tdd2 + phi1) / d1
        td1 = jnp.clip(td1 + dt * tdd1, -4 * jnp.pi, 4 * jnp.pi)
        td2 = jnp.clip(td2 + dt * tdd2, -9 * jnp.pi, 9 * jnp.pi)
        t1 = t1 + dt * td1
        t2 = t2 + dt * td2
        done = -jnp.cos(t1) - jnp.cos(t2 + t1) > 1.0
        reward = done.astype(t1.dtype) - 1.0  # 0 when done, else -1
        return {"t1": t1, "t2": t2, "td1": td1, "td2": td2}, reward, done

    return SoAEnv(
        base=acrobot(max_steps=max_steps),
        to_soa=lambda s: {
            "t1": s[..., 0], "t2": s[..., 1],
            "td1": s[..., 2], "td2": s[..., 3],
        },
        obs_soa=obs_soa,
        step_soa=step_soa,
    )


def _mlp_act(
    theta_ref,
    obs: Tuple[jax.Array, ...],
    obs_dim: int,
    hidden: int,
    act_dim: int,
) -> Tuple[jax.Array, ...]:
    """(tile,) actions from per-env flat genomes resident in VMEM.

    ``theta_ref`` is the TRANSPOSED genome tile ``(dim, tile)``: each
    genome component is one sublane row, so every access below is a
    full-lane ``(tile,)`` VPU vector — static loops over the (small)
    obs/hidden indices, no in-kernel reshapes or lane gathers. Genome
    layout matches ``flat_mlp_policy`` (policy.py): w1 row-major, b1,
    w2 row-major, b2.
    """
    n1 = obs_dim * hidden
    n2 = n1 + hidden
    n3 = n2 + hidden * act_dim
    h = [theta_ref[n1 + j] for j in range(hidden)]  # start from b1
    for k in range(obs_dim):
        for j in range(hidden):
            h[j] = h[j] + obs[k] * theta_ref[k * hidden + j]
    th = [jnp.tanh(hj) for hj in h]
    acts = []
    for i in range(act_dim):
        a = theta_ref[n3 + i]  # b2[i]
        for j in range(hidden):
            a = a + th[j] * theta_ref[n2 + j * act_dim + i]
        acts.append(a)
    return tuple(acts)


def _rollout_kernel(
    theta_ref,
    state_refs,
    out_ref,
    *,
    T: int,
    obs_dim: int,
    hidden: int,
    act_dim: int,
    step_soa: Callable,
    obs_soa: Callable,
    state_keys: Tuple[str, ...],
    early_stop: bool,
):
    # drop the leading episode-block dim: every per-env value in the body
    # is then a uniform 2-D (rows, 128) block, same rank as the theta
    # slices — mixed-rank broadcasts here trip Mosaic relayout bugs on
    # some step functions ("non-singleton logical dimension is
    # replicated")
    state = {k: r[0] for k, r in zip(state_keys, state_refs)}
    total0 = jnp.zeros_like(state[state_keys[0]])
    # sticky float done mask, seeded from the padding plane so padded
    # lanes never hold the early-exit while_loop open (a zero-state
    # padded env may never terminate on its own, e.g. mountain_car)
    done0 = state.pop(_PAD_KEY)

    def body(state, done, total):
        obs = obs_soa(state)
        a = _mlp_act(theta_ref, obs, obs_dim, hidden, act_dim)
        state, reward, step_done = step_soa(state, a)
        # frozen-episode accounting, same as the standard engine: the
        # terminating step's reward counts, later ones don't. Same-shape
        # where operands: a scalar branch here trips a Mosaic relayout
        # bug ("non-singleton logical dimension is replicated") on the
        # episode blocks.
        total = total + jnp.where(done > 0.5, jnp.zeros_like(reward), reward)
        done = jnp.maximum(done, step_done.astype(done.dtype))
        return state, done, total

    if early_stop:
        # per-tile early exit: uniform-shape vector carries compile fine
        # (it is MIXED-shape while carries that crash Mosaic)
        def cond(c):
            t, _, done, _ = c
            return (t < T) & jnp.any(done < 0.5)

        def wbody(c):
            t, state, done, total = c
            state, done, total = body(state, done, total)
            return t + 1, state, done, total

        _, _, _, total = jax.lax.while_loop(
            cond, wbody, (jnp.int32(0), state, done0, total0)
        )
    else:
        _, _, total = jax.lax.fori_loop(
            0, T, lambda _, c: body(*c), (state, done0, total0)
        )
    out_ref[0] = total


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "obs_dim", "hidden", "act_dim", "step_soa", "obs_soa", "tile",
        "episodes", "early_stop", "interpret",
    ),
)
def fused_rollout(
    theta: jax.Array,
    init_state: SoAState,
    T: int,
    obs_dim: int = 3,
    hidden: int = 16,
    act_dim: int = 1,
    step_soa: Callable = pendulum_step_soa,
    obs_soa: Callable = pendulum_obs_soa,
    tile: int = 2048,
    episodes: int = 1,
    early_stop: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Total episode reward per environment, fully fused.

    Args:
        theta: ``(n, dim)`` flat MLP genomes (one row per individual).
            Layout per ``flat_mlp_policy`` (policy.py).
        init_state: SoA env state dict of ``(episodes * n,)`` arrays,
            EPISODE-MAJOR (all of episode 0's envs, then episode 1's...).
        T: fixed episode length.
        obs_dim / hidden / act_dim: MLP shape.
        step_soa / obs_soa: the env's SoA step/observation functions (any
            jax-traceable elementwise math over the component arrays).
        tile: environments per Pallas grid cell; theta tile must fit VMEM
            (tile x dim x 4 bytes, default 2048 x 81 ≈ 660 KB).
        episodes: episodes per individual. The grid is 2-D
            ``(n/tile, episodes)`` with episodes innermost: every episode
            row maps to the same genome block, and because consecutive
            grid steps then revisit an unchanged theta index, Pallas
            elides the re-fetch — theta streams from HBM once per genome
            block regardless of episode count (and no ``jnp.repeat``-ed
            copy ever materializes).

    Returns:
        ``(episodes * n,)`` total rewards, episode-major.
    """
    if tile % (8 * _LANES) != 0:
        raise ValueError(f"tile must be a multiple of {8 * _LANES}, got {tile}")
    n, dim = theta.shape
    expect_dim = obs_dim * hidden + hidden + hidden * act_dim + act_dim
    if dim != expect_dim:
        raise ValueError(
            f"theta dim {dim} != flat MLP size {expect_dim} for "
            f"({obs_dim} -> {hidden} -> {act_dim})"
        )
    if jax.tree.leaves(init_state)[0].shape[0] != episodes * n:
        raise ValueError(
            f"init_state has {jax.tree.leaves(init_state)[0].shape[0]} envs, "
            f"expected episodes*n = {episodes * n}"
        )
    if _PAD_KEY in init_state:
        raise ValueError(f"state key {_PAD_KEY!r} is reserved")
    pad = (-n) % tile
    n_pad = n + pad
    init_state = dict(init_state)
    # padding plane: 1.0 on padded lanes; seeds the kernel's done mask so
    # padded (zero-state) envs can't hold the early-exit loop open
    init_state[_PAD_KEY] = jnp.zeros((episodes * n,), dtype=theta.dtype)
    if pad:
        theta = jnp.pad(theta, ((0, pad), (0, 0)))
        # pad each episode segment so segments stay tile-aligned; the
        # padding plane gets 1.0 in the padded tail of every segment
        init_state = {
            k: jnp.pad(
                v.reshape(episodes, n),
                ((0, 0), (0, pad)),
                constant_values=1.0 if k == _PAD_KEY else 0.0,
            ).reshape(-1)
            for k, v in init_state.items()
        }
    # every per-env quantity becomes a full (sublane, lane) = (8k, 128m)
    # tile: genome components are (rows, LANES) planes of a 3-D theta
    # block, env state components are matching 2-D tiles — all kernel ops
    # are full-width VPU instructions (1-D (tile,) values waste 7/8
    # sublanes)
    rows_pop = n_pad // _LANES
    rows_tile = tile // _LANES
    blocks = rows_pop // rows_tile
    theta_t = theta.T.reshape(dim, rows_pop, _LANES)
    state_3d = {
        k: v.reshape(episodes, rows_pop, _LANES)
        for k, v in sorted(init_state.items())
    }
    state_keys = tuple(state_3d)
    kernel = functools.partial(
        _rollout_kernel,
        T=T,
        obs_dim=obs_dim,
        hidden=hidden,
        act_dim=act_dim,
        step_soa=step_soa,
        obs_soa=obs_soa,
        state_keys=state_keys,
        early_stop=early_stop,
    )

    def wrapped(theta_ref, *state_refs_and_out):
        kernel(theta_ref, state_refs_and_out[:-1], state_refs_and_out[-1])

    total = pl.pallas_call(
        wrapped,
        # episodes INNERMOST: consecutive grid steps that differ only in
        # the episode index keep the same theta block, so Pallas's
        # revisiting pipeline elides the redundant HBM fetch — theta
        # streams once per genome block instead of once per episode
        grid=(blocks, episodes),
        in_specs=[
            pl.BlockSpec((dim, rows_tile, _LANES), lambda b, e: (0, b, 0))
        ]
        + [
            pl.BlockSpec((1, rows_tile, _LANES), lambda b, e: (e, b, 0))
            for _ in state_keys
        ],
        out_specs=pl.BlockSpec((1, rows_tile, _LANES), lambda b, e: (e, b, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (episodes, rows_pop, _LANES), theta.dtype
        ),
        interpret=interpret,
        name="fused_rollout",
    )(theta_t, *state_3d.values())
    total = total.reshape(episodes, n_pad)[:, :n]
    return total.reshape(episodes * n)

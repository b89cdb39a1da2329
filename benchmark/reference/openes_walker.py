"""Plain reference of the ``openes_walker`` configuration.

OpenES (Salimans et al. 2017: mirrored sampling, centred-rank shaping, plain
SGD on the centre) on a planar chain walker driven by a tanh MLP, written in
straightforward ``jax.numpy`` at float32 with ``highest`` matmul precision: no
kernel, no cache, members in blocks so that a chip holds them. It imports
nothing of the program and draws everything from the seed.

What it shares with the program is the semantics, and the order in which keys
are split, because the random draws are part of the semantics:

- workflow: ``k_algo, k_prob = split(key(seed))``;
- OpenES: ``key, noise_key = split(k_algo)`` at init; each generation
  ``key, k = split(key)``, ``half = normal(k, (pop/2, dim))``, population
  ``centre + sigma * [half; -half]``; fitness to maximise is negated, shaped
  to ranks in [-0.5, 0.5]; ``grad = half.T @ (s[:pop/2] - s[pop/2:]) / (pop *
  sigma)``; ``centre -= lr * grad``;
- problem: every member and every generation starts from the same reset,
  ``reset(split(fold_in(k_prob, 0), 1)[0])``; an episode ends when the head
  falls under the stand height, the state explodes, or at ``episode_len``;
  fitness is the sum of rewards up to the end;
- genome layout: for each layer its bias, then its weights row-major
  ``(fan_in, fan_out)``.

Departures: none known. The member's episode runs the full horizon with a
sticky ``done`` mask where the program leaves a tile early; the sums agree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WALKER = dict(
    n_masses=25, substeps=5, dt=0.01, rod_length=0.2, rod_stiffness=2000.0,
    rod_damping=4.0, torque_scale=8.0, ground_stiffness=3000.0,
    ground_damping=10.0, friction=1.0, gravity=9.8,
)


def _key(seed: int):
    seed = int(seed)
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)


def _layers(sizes):
    """Offsets of each layer's bias and weights in the genome."""
    out, at = [], 0
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        out.append((at, at + fan_out, fan_in, fan_out))
        at += fan_out + fan_in * fan_out
    return out, at


def _policy(genomes, obs, sizes):
    """tanh MLP, one genome a row: ``(B, dim), (B, obs) -> (B, act)``."""
    layers, _ = _layers(sizes)
    h = obs
    for i, (b0, w0, fan_in, fan_out) in enumerate(layers):
        b = genomes[:, b0:w0]
        w = genomes[:, w0 : w0 + fan_in * fan_out].reshape(-1, fan_in, fan_out)
        h = jnp.einsum("bi,bio->bo", h, w, precision="highest") + b
        if i < len(layers) - 1:
            h = jnp.tanh(h)
    return h


def _ground(pos, vel):
    c = WALKER
    depth = jnp.maximum(-pos[..., 1], 0.0)
    contact = (depth > 0.0).astype(pos.dtype)
    f_n = c["ground_stiffness"] * depth - c["ground_damping"] * vel[..., 1] * contact
    return jnp.maximum(f_n, 0.0) * contact


def _links(pos):
    d = pos[:, 1:] - pos[:, :-1]
    dd = jnp.sum(d * d, axis=-1) + 1e-12
    inv = jax.lax.rsqrt(dd)
    return d, dd, inv


def _forces(pos, vel, torque, act_dim):
    """Force on each unit mass: gravity, rod springs, joint torques, ground."""
    c = WALKER
    d, dd, inv = _links(pos)
    u = d * inv[..., None]
    rel_v = jnp.sum((vel[:, 1:] - vel[:, :-1]) * u, axis=-1)
    mag = c["rod_stiffness"] * (dd * inv - c["rod_length"]) + c["rod_damping"] * rel_v
    perp = jnp.stack([-u[..., 1], u[..., 0]], axis=-1)
    tq = jnp.pad(torque, ((0, 0), (0, d.shape[1] - act_dim)))
    f_link = mag[..., None] * u + (tq * jnp.minimum(inv, 1e6))[..., None] * perp
    zero = jnp.zeros_like(f_link[:, :1])
    f = jnp.concatenate([f_link, zero], axis=1) - jnp.concatenate([zero, f_link], axis=1)
    f_n = _ground(pos, vel)
    vx = vel[..., 0]
    f_t = -jnp.clip(c["friction"] * f_n * jnp.sign(vx), -jnp.abs(vx) * 50.0, jnp.abs(vx) * 50.0)
    return f + jnp.stack([f_t, f_n - c["gravity"]], axis=-1)


def _obs(pos, vel, prev_a, obs_dim):
    c = WALKER
    d, dd, inv = _links(pos)
    rel_v = vel[:, 1:] - vel[:, :-1]
    ang_vel = (d[..., 0] * rel_v[..., 1] - d[..., 1] * rel_v[..., 0]) * (inv * inv)
    b = pos.shape[0]
    parts = jnp.concatenate(
        [
            (pos - pos[:, :1]).reshape(b, -1),
            vel.reshape(b, -1),
            d[..., 0] * inv,
            d[..., 1] * inv,
            ang_vel,
            dd * inv * (1.0 / c["rod_length"]) - 1.0,
            _ground(pos, vel) * 1e-2,
            prev_a,
            jnp.stack([pos[:, 0, 1], pos[:, -1, 1], vel[:, 0, 0], vel[:, 0, 1]], axis=-1),
        ],
        axis=-1,
    )
    k = parts.shape[-1]
    return parts[:, :obs_dim] if k >= obs_dim else jnp.pad(parts, ((0, 0), (0, obs_dim - k)))


def _reset(k_prob, dtype):
    c = WALKER
    k_eps = jax.random.split(jax.random.fold_in(k_prob, 0), 1)[0]
    k1, k2 = jax.random.split(k_eps)
    idx = jnp.arange(c["n_masses"], dtype=jnp.float32)
    base = jnp.stack(
        [
            0.3 * c["rod_length"] * jnp.where(idx % 2 == 0, 1.0, -1.0),
            0.02 + idx * c["rod_length"] * jnp.sqrt(1.0 - 0.09),
        ],
        axis=-1,
    )
    pos = base + 0.01 * jax.random.normal(k1, base.shape)
    vel = 0.01 * jax.random.normal(k2, base.shape)
    return pos.astype(dtype), vel.astype(dtype)


def rollout(genomes, pos0, vel0, config):
    """Fitness of a block of genomes: ``(B, dim) -> (B,)``."""
    c = WALKER
    sizes = tuple(config["policy_sizes"])
    obs_dim, act_dim, horizon = sizes[0], sizes[-1], int(config["episode_len"])
    b, dtype = genomes.shape[0], genomes.dtype
    stand = 0.3 * (c["n_masses"] - 1) * c["rod_length"]
    h = c["dt"] / c["substeps"]

    def step(carry, _):
        pos, vel, prev_a, done, total, t = carry
        action = _policy(genomes, _obs(pos, vel, prev_a, obs_dim), sizes)
        tanh_a = jnp.tanh(action)
        p, v = pos, vel
        for _ in range(c["substeps"]):
            v = v + h * _forces(p, v, tanh_a * c["torque_scale"], act_dim)
            p = p + h * v
        reward = jnp.mean(v[..., 0], axis=-1) + 1.0 - 0.01 * jnp.sum(tanh_a**2, axis=-1)
        flat = p.reshape(b, -1)
        exploded = jnp.any(~jnp.isfinite(flat), axis=-1) | (jnp.max(jnp.abs(flat), axis=-1) > 1e3)
        ends = (p[:, -1, 1] < stand) | exploded | (t + 1 >= horizon)
        total = total + jnp.where(done, 0.0, reward).astype(jnp.float32)
        keep = done[:, None, None]
        carry = (
            jnp.where(keep, pos, p),
            jnp.where(keep, vel, v),
            jnp.where(done[:, None], prev_a, action),
            done | ends,
            total,
            t + 1,
        )
        return carry, None

    carry0 = (
        jnp.broadcast_to(pos0, (b,) + pos0.shape),
        jnp.broadcast_to(vel0, (b,) + vel0.shape),
        jnp.zeros((b, act_dim), dtype),
        jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.float32),
        jnp.int32(0),
    )
    return jax.lax.scan(step, carry0, None, length=horizon)[0][4]


def _centred_ranks(x):
    n = x.shape[0]
    ranks = jnp.zeros((n,), jnp.float32).at[jnp.argsort(x)].set(jnp.arange(n, dtype=jnp.float32))
    return ranks / (n - 1) - 0.5


def follow(config: dict, traffic: dict, seed: int, generations: list, precision: str = "float32",
           program: list = (), block: int = 4096) -> list:
    """From the seed through the generations asked for. One snapshot for each:
    ``{"generation", "center", "center_before"}``. ``precision="bfloat16"`` is the control:
    the same computation with every array and product in bfloat16.

    ``program``: the snapshots under comparison. Where it holds one of the
    generation before, a generation starts from that snapshot's centre and
    not from the reference's own: the comparison is then of one chunk at a
    time, each from a state that was itself compared, and does not measure
    how fast two trajectories that differ in the last digits drift apart.
    The keys follow from the seed alone either way."""
    dtype = jnp.dtype(precision)
    pop, sigma, lr = int(traffic["pop"]), float(config["noise_stdev"]), float(config["learning_rate"])
    dim = _layers(tuple(config["policy_sizes"]))[1]
    half_n = pop // 2
    block = min(block, half_n)
    if half_n % block:
        raise ValueError(f"half the population ({half_n}) is not a multiple of the block ({block})")
    key = _key(seed)
    k_algo, k_prob = jax.random.split(key)
    akey, _ = jax.random.split(k_algo)
    centre = float(config["center_init_std"]) * jax.random.normal(jax.random.fold_in(key, 1), (dim,))
    pos0, vel0 = _reset(k_prob, dtype)

    @jax.jit
    def fitness_block(centre, half, start, sign):
        rows = jax.lax.dynamic_slice_in_dim(half, start, block, axis=0)
        genomes = (centre + sigma * sign * rows).astype(dtype)
        return rollout(genomes, pos0, vel0, config)

    @jax.jit
    def update(centre, half, fitness):
        shaped = _centred_ranks(-fitness).astype(dtype)
        diff = shaped[:half_n] - shaped[half_n:]
        grad = jnp.einsum(
            "pd,p->d", half.astype(dtype), diff, precision="highest",
            preferred_element_type=jnp.float32,
        ) / (pop * sigma)
        return centre - lr * grad

    snaps = []
    given = {int(s["generation"]): s for s in program}
    for step in range(1, max(generations) + 1):
        if step - 1 in given:
            centre = jnp.asarray(given[step - 1]["center"], jnp.float32)
        akey, k = jax.random.split(akey)
        half = jax.random.normal(k, (half_n, dim))
        parts = [
            fitness_block(centre, half, start, sign)
            for sign in (1.0, -1.0)
            for start in range(0, half_n, block)
        ]
        before = np.asarray(centre)
        centre = update(centre, half, jnp.concatenate(parts))
        if step in generations:
            snaps.append({"generation": step, "center": np.asarray(centre), "center_before": before})
        del half, parts
    return snaps


def numbers(config: dict, program: list, reference: list) -> dict:
    """The numbers compared, one for each step followed: the distance of the
    program's centre from the reference's, as a share of the step the
    reference took (1.0: the centre did not move, or moved the other way by
    as much). And whether the generation counter counts the steps."""
    out = {}
    for k, (got, want) in enumerate(zip(program, reference), 1):
        c_got, c_want = (np.asarray(s["center"], np.float64) for s in (got, want))
        # the step the reference took in its last generation before the snapshot
        moved = np.linalg.norm(c_want - np.asarray(want["center_before"], np.float64))
        out[f"step{k}_center_err"] = float(np.linalg.norm(c_got - c_want) / max(moved, 1e-30))
        out[f"step{k}_generation_off"] = float(abs(int(got["generation"]) - int(want["generation"])))
    return out

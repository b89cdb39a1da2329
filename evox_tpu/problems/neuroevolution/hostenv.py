"""Host-side environment problems — external simulators driven from inside
jit via ordered ``io_callback`` (the EnvPool pattern, reference
src/evox/problems/neuroevolution/reinforcement_learning/env_pool.py:41-78).

The device side stays one compiled ``lax.while_loop``: policy inference for
the whole population is a single vmapped MXU program per step, and only
(action -> obs/reward/done) crosses the host boundary. One env per
individual, exactly the EnvPool contract.

``NumpyCartPoleVec`` is a dependency-free vectorized host env (numpy
CartPole-v1 dynamics) so the path is testable and usable without EnvPool;
``envpool_make`` wraps the real EnvPool when that package is present.

NOTE: host callbacks need a runtime that can call back into this Python
process — a local CPU or a directly attached accelerator — just as the
reference's host problems require a local runtime.
"""

from __future__ import annotations

from typing import Callable, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from ...core.problem import Problem


class HostVectorEnv(Protocol):
    """Batched host environment: ``num_envs`` parallel episodes."""

    num_envs: int
    obs_dim: int

    def reset(self, seed: int) -> np.ndarray:  # (num_envs, obs_dim)
        ...

    def step(
        self, actions: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """-> (obs, reward, terminated, truncated), each (num_envs, ...)."""
        ...


class NumpyCartPoleVec:
    """Vectorized CartPole-v1 in numpy (standard Gym dynamics). Already-done
    envs freeze (their state, reward 0) like EnvPool's default behavior."""

    obs_dim = 4
    act_dim = 2

    def __init__(self, num_envs: int, max_steps: int = 500):
        self.num_envs = num_envs
        self.max_steps = max_steps
        self._s = np.zeros((num_envs, 4))
        self._done = np.zeros((num_envs,), dtype=bool)
        self._t = 0

    def reset(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(int(seed))
        self._s = rng.uniform(-0.05, 0.05, size=(self.num_envs, 4))
        self._done[:] = False
        self._t = 0
        return self._s.astype(np.float32)

    def step(self, actions: np.ndarray):
        force = np.where(actions[:, 1] > actions[:, 0], 10.0, -10.0)
        x, x_dot, th, th_dot = self._s.T
        cos, sin = np.cos(th), np.sin(th)
        temp = (force + 0.05 * th_dot**2 * sin) / 1.1
        thacc = (9.8 * sin - cos * temp) / (0.5 * (4.0 / 3.0 - 0.1 * cos**2 / 1.1))
        xacc = temp - 0.05 * thacc * cos / 1.1
        new = np.stack(
            [x + 0.02 * x_dot, x_dot + 0.02 * xacc, th + 0.02 * th_dot, th_dot + 0.02 * thacc],
            axis=1,
        )
        live = ~self._done
        self._s = np.where(live[:, None], new, self._s)
        self._t += 1
        reward = live.astype(np.float32)
        terminated = (np.abs(self._s[:, 0]) > 2.4) | (np.abs(self._s[:, 2]) > 0.2095)
        truncated = np.full((self.num_envs,), self._t >= self.max_steps)
        self._done |= terminated | truncated
        return (
            self._s.astype(np.float32),
            reward,
            terminated,
            truncated,
        )


class EnvPoolAdapter:
    """Adapt an EnvPool gymnasium-API batch env to :class:`HostVectorEnv`.

    EnvPool's gymnasium interface returns ``(obs, info)`` from ``reset()``
    and ``(obs, reward, terminated, truncated, info)`` from ``step()`` —
    this strips the infos and exposes the 4-tuple contract
    :class:`HostEnvProblem` consumes. EnvPool fixes its RNG seed at
    construction (``envpool.make(..., seed=...)``), so the per-evaluation
    ``seed`` argument only triggers a reset; pass ``seed`` through
    ``env_options`` for reproducible streams.

    ``action_transform`` maps the policy's raw ``(num_envs, act_dim)``
    output to what the env expects — e.g. ``lambda a: a.argmax(-1)`` for
    discrete action spaces (reference env_pool.py:41-78 hands policy
    output straight to EnvPool, which only works for continuous spaces).
    """

    def __init__(self, env, num_envs: int, action_transform=None):
        self._env = env
        self._action_transform = action_transform
        self.num_envs = num_envs
        self.obs_dim = int(np.prod(env.observation_space.shape))
        self._warned_seed = False

    def reset(self, seed: int) -> np.ndarray:
        if not self._warned_seed:
            self._warned_seed = True
            import warnings

            warnings.warn(
                "EnvPoolAdapter ignores the per-evaluation seed (EnvPool "
                "fixes its RNG at construction): every generation replays "
                "the same episode stream. Pass seed= through env_options "
                "at envpool_make() for a chosen stream.",
                stacklevel=2,
            )
        obs, _info = self._env.reset()
        return np.asarray(obs, dtype=np.float32).reshape(self.num_envs, -1)

    def step(self, actions: np.ndarray):
        if self._action_transform is not None:
            actions = self._action_transform(actions)
        obs, reward, terminated, truncated, _info = self._env.step(actions)
        return (
            np.asarray(obs, dtype=np.float32).reshape(self.num_envs, -1),
            np.asarray(reward, dtype=np.float32),
            np.asarray(terminated, dtype=bool),
            np.asarray(truncated, dtype=bool),
        )


def envpool_make(
    env_name: str,
    num_envs: int,
    action_transform: Optional[Callable] = None,
    **env_options,
) -> HostVectorEnv:
    """Construct a real EnvPool env (optional dependency), adapted to the
    :class:`HostVectorEnv` protocol.

    Seeding: EnvPool fixes its RNG at construction, so per-evaluation
    seeds are ignored (a one-time warning fires on first reset) — pass
    ``seed=`` here via ``env_options`` to pick the episode stream."""
    try:
        import envpool
    except ImportError as e:
        raise ImportError(
            "envpool is not installed; use NumpyCartPoleVec or another "
            "HostVectorEnv implementation"
        ) from e
    env = envpool.make(
        env_name, num_envs=num_envs, env_type="gymnasium", **env_options
    )
    return EnvPoolAdapter(env, num_envs, action_transform)


class HostEnvProblem(Problem):
    """Evaluate a population by stepping a :class:`HostVectorEnv` (one env
    per individual) from inside jit.

    Args:
        policy: jittable ``(params, obs) -> action`` for one individual.
        env: the host vector env; ``env.num_envs`` must equal pop size.
        cap_episode_length: hard step cap (None = run until all done).
    """

    def __init__(
        self,
        policy: Callable,
        env: HostVectorEnv,
        cap_episode_length: Optional[int] = None,
    ):
        self.policy = policy
        self.env = env
        self.num_envs = env.num_envs
        self.cap = cap_episode_length
        n = self.num_envs
        self._step_sds = (
            jax.ShapeDtypeStruct((n, env.obs_dim), jnp.float32),  # obs
            jax.ShapeDtypeStruct((n,), jnp.float32),  # reward
            jax.ShapeDtypeStruct((n,), jnp.bool_),  # terminated
            jax.ShapeDtypeStruct((n,), jnp.bool_),  # truncated
        )

    def init(self, key=None):
        return key if key is not None else jax.random.PRNGKey(0)

    def _host_reset(self, seed) -> np.ndarray:
        return np.asarray(self.env.reset(int(seed)), dtype=np.float32)

    def _host_step(self, actions):
        obs, r, term, trunc = self.env.step(np.asarray(actions))
        return (
            np.asarray(obs, dtype=np.float32),
            np.asarray(r, dtype=np.float32),
            np.asarray(term, dtype=bool),
            np.asarray(trunc, dtype=bool),
        )

    def evaluate(self, state, pop):
        key, k_seed = jax.random.split(state)
        seed = jax.random.randint(k_seed, (), 0, jnp.iinfo(jnp.int32).max)
        obs0 = io_callback(
            self._host_reset,
            jax.ShapeDtypeStruct((self.num_envs, self.env.obs_dim), jnp.float32),
            seed,
            ordered=True,
        )
        batched_policy = jax.vmap(self.policy)

        def cond(carry):
            i, done, _, _ = carry
            alive = ~jnp.all(done)
            if self.cap is not None:
                return (i < self.cap) & alive
            return alive

        def body(carry):
            i, done, total, obs = carry
            actions = batched_policy(pop, obs)
            obs, reward, term, trunc = io_callback(
                self._host_step, self._step_sds, actions, ordered=True
            )
            total = total + jnp.where(done, 0.0, reward)
            return i + 1, done | term | trunc, total, obs

        _, _, total, _ = jax.lax.while_loop(
            cond,
            body,
            (
                jnp.int32(0),
                jnp.zeros((self.num_envs,), dtype=bool),
                jnp.zeros((self.num_envs,)),
                obs0,
            ),
        )
        return total, key

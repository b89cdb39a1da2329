"""API-conformant fakes of the optional dependencies (brax, envpool).

The real packages are not part of this build's baked environment; these
fakes reproduce exactly the API surface our adapters consume so the
adapter code paths (`control/brax_adapter.py::brax_env`,
`hostenv.py::envpool_make`/`EnvPoolAdapter`) execute in CI instead of
living behind import guards. The fake dynamics are
simple but real (a damped torque pendulum for brax, Gym CartPole-v1
physics for envpool), so golden tests can pin adapter output against an
EnvSpec/HostVectorEnv built directly on the same math.
"""

from __future__ import annotations

import sys
import types
from typing import NamedTuple

import numpy as np


# --------------------------------------------------------------- fake brax
class FakeBraxState(NamedTuple):
    """Mimics brax.envs.State: a pytree carrying obs/reward/done plus the
    physics state (brax keeps it in `pipeline_state`; the adapter never
    touches it, only threads it through)."""

    pipeline_state: object  # (2,) [theta, theta_dot]
    obs: object  # (3,)
    reward: object  # ()
    done: object  # () float 0/1, brax convention


def _fake_brax_module():
    import jax
    import jax.numpy as jnp

    class FakePendulumEnv:
        """Damped torque pendulum with brax's env API: reset(key)->State,
        step(State, action)->State, observation_size/action_size."""

        observation_size = 3
        action_size = 1

        def __init__(self, backend: str):
            self.backend = backend

        def _obs(self, q):
            return jnp.stack([jnp.sin(q[0]), jnp.cos(q[0]), q[1]])

        def reset(self, key):
            q = 0.1 * jax.random.normal(key, (2,))
            return FakeBraxState(
                pipeline_state=q,
                obs=self._obs(q),
                reward=jnp.zeros(()),
                done=jnp.zeros(()),
            )

        def step(self, state, action):
            q = state.pipeline_state
            torque = jnp.clip(action[0], -2.0, 2.0)
            th_dot = 0.95 * q[1] + 0.05 * (torque - jnp.sin(q[0]))
            th = q[0] + 0.05 * th_dot
            q = jnp.stack([th, th_dot])
            reward = -(th * th + 0.1 * th_dot * th_dot + 0.001 * torque * torque)
            done = (jnp.abs(th_dot) > 8.0).astype(jnp.float32)
            return FakeBraxState(
                pipeline_state=q, obs=self._obs(q), reward=reward, done=done
            )

    def get_environment(env_name: str, backend: str = "generalized"):
        if env_name != "fake_pendulum":
            raise KeyError(env_name)
        return FakePendulumEnv(backend)

    brax = types.ModuleType("brax")
    brax_envs = types.ModuleType("brax.envs")
    brax_envs.get_environment = get_environment
    brax_envs.State = FakeBraxState
    brax.envs = brax_envs
    return brax, brax_envs


def install_fake_brax(monkeypatch):
    brax, brax_envs = _fake_brax_module()
    monkeypatch.setitem(sys.modules, "brax", brax)
    monkeypatch.setitem(sys.modules, "brax.envs", brax_envs)
    return brax_envs


# ------------------------------------------------------------- fake envpool
class _Space(NamedTuple):
    shape: tuple


class FakeEnvPoolCartPole:
    """EnvPool gymnasium-interface batch CartPole: reset() -> (obs, info),
    step(actions) -> (obs, reward, terminated, truncated, info). Dynamics
    are the exact NumpyCartPoleVec math so a golden test can compare."""

    def __init__(self, num_envs: int, seed: int = 0, max_steps: int = 500):
        from evox_tpu.problems.neuroevolution.hostenv import NumpyCartPoleVec

        self._inner = NumpyCartPoleVec(num_envs, max_steps=max_steps)
        self._seed = seed
        self.observation_space = _Space(shape=(4,))
        self.action_space = _Space(shape=())

    def reset(self):
        obs = self._inner.reset(self._seed)
        return obs, {}

    def step(self, actions):
        actions = np.asarray(actions)
        if actions.ndim == 1:  # discrete int actions -> inner's logit form
            logits = np.zeros((actions.shape[0], 2), dtype=np.float32)
            logits[np.arange(actions.shape[0]), actions.astype(int)] = 1.0
            actions = logits
        obs, r, term, trunc = self._inner.step(actions)
        return obs, r, term, trunc, {}


def _fake_envpool_module():
    envpool = types.ModuleType("envpool")

    def make(env_name: str, num_envs: int, env_type: str = "gymnasium", **opts):
        assert env_type == "gymnasium"
        if env_name != "FakeCartPole-v1":
            raise KeyError(env_name)
        return FakeEnvPoolCartPole(num_envs, **opts)

    envpool.make = make
    return envpool


def install_fake_envpool(monkeypatch):
    envpool = _fake_envpool_module()
    monkeypatch.setitem(sys.modules, "envpool", envpool)
    return envpool

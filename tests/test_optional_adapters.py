"""Optional-dependency adapters actually executed:
brax_env and envpool_make construct, roll out end-to-end, and match
EnvSpec/HostVectorEnv-level goldens built on the same dynamics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.problems.neuroevolution import PolicyRolloutProblem, flat_mlp_policy
from evox_tpu.problems.neuroevolution.control.envs import EnvSpec

from tests._fake_optional_deps import (
    FakeBraxState,
    install_fake_brax,
    install_fake_envpool,
)


def test_brax_env_rollout_matches_envspec_golden(monkeypatch):
    """brax_env wraps a brax-API env into an EnvSpec whose rollouts are
    identical to a hand-built EnvSpec on the same dynamics."""
    install_fake_brax(monkeypatch)
    from evox_tpu.problems.neuroevolution.control.brax_adapter import brax_env

    env = brax_env("fake_pendulum", backend="positional", max_steps=30)
    assert env.obs_dim == 3 and env.act_dim == 1 and not env.discrete

    # golden: the same pendulum math written directly as an EnvSpec
    def g_reset(key):
        q = 0.1 * jax.random.normal(key, (2,))
        return q

    def g_obs(q):
        return jnp.stack([jnp.sin(q[0]), jnp.cos(q[0]), q[1]])

    def g_step(q, action):
        torque = jnp.clip(action[0], -2.0, 2.0)
        th_dot = 0.95 * q[1] + 0.05 * (torque - jnp.sin(q[0]))
        th = q[0] + 0.05 * th_dot
        q = jnp.stack([th, th_dot])
        reward = -(th * th + 0.1 * th_dot * th_dot + 0.001 * torque * torque)
        return q, reward, jnp.abs(th_dot) > 8.0

    golden = EnvSpec(
        reset=g_reset, obs=g_obs, step=g_step,
        obs_dim=3, act_dim=1, discrete=False, max_steps=30,
    )

    apply, dim = flat_mlp_policy(3, 8, 1)
    pop = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (5, dim))
    kw = dict(num_episodes=2, stochastic_reset=False)
    p_brax = PolicyRolloutProblem(apply, env, **kw)
    p_gold = PolicyRolloutProblem(apply, golden, **kw)
    f_brax, _ = p_brax.evaluate(p_brax.init(jax.random.PRNGKey(9)), pop)
    f_gold, _ = p_gold.evaluate(p_gold.init(jax.random.PRNGKey(9)), pop)
    np.testing.assert_allclose(np.asarray(f_brax), np.asarray(f_gold),
                               rtol=1e-6, atol=1e-6)
    assert np.std(np.asarray(f_brax)) > 0  # distinct policies score apart


def test_brax_env_terminate_on_done_false(monkeypatch):
    """terminate_on_done=False: episodes run the full horizon."""
    install_fake_brax(monkeypatch)
    from evox_tpu.problems.neuroevolution.control.brax_adapter import brax_env

    env = brax_env("fake_pendulum", max_steps=7, terminate_on_done=False)
    state = env.reset(jax.random.PRNGKey(0))
    assert isinstance(state, FakeBraxState)
    state, reward, done = env.step(state, jnp.ones((1,)))
    assert done is False  # constant: XLA eliminates the branch


@pytest.mark.skipif(
    __import__("importlib.util", fromlist=["util"]).find_spec("brax") is not None,
    reason="real brax installed",
)
def test_brax_env_missing_dep_message():
    with pytest.raises(ImportError, match="brax is not installed"):
        from evox_tpu.problems.neuroevolution.control.brax_adapter import brax_env

        brax_env("whatever")


def test_envpool_make_matches_numpy_cartpole_golden(monkeypatch):
    """envpool_make adapts the EnvPool gymnasium API to HostVectorEnv and
    matches HostEnvProblem on the same CartPole dynamics driven directly."""
    install_fake_envpool(monkeypatch)
    from evox_tpu.problems.neuroevolution.hostenv import (
        HostEnvProblem,
        NumpyCartPoleVec,
        envpool_make,
    )

    n = 8
    seed = 1234
    env_pool = envpool_make(
        "FakeCartPole-v1", num_envs=n,
        action_transform=lambda a: np.argmax(a, axis=-1),
        seed=seed, max_steps=60,
    )
    assert env_pool.num_envs == n and env_pool.obs_dim == 4

    apply, dim = flat_mlp_policy(4, 8, 2)
    pop = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (n, dim))

    p_pool = HostEnvProblem(apply, env_pool, cap_episode_length=60)
    f_pool, _ = p_pool.evaluate(p_pool.init(jax.random.PRNGKey(2)), pop)

    # golden: the same dynamics via NumpyCartPoleVec, seeded identically
    class SeededCartPole(NumpyCartPoleVec):
        def reset(self, _seed):
            return super().reset(seed)

    env_gold = SeededCartPole(n, max_steps=60)
    p_gold = HostEnvProblem(apply, env_gold, cap_episode_length=60)
    f_gold, _ = p_gold.evaluate(p_gold.init(jax.random.PRNGKey(2)), pop)
    np.testing.assert_allclose(np.asarray(f_pool), np.asarray(f_gold),
                               rtol=1e-6, atol=1e-6)
    assert float(np.max(np.asarray(f_pool))) > 1.0  # episodes actually ran


@pytest.mark.skipif(
    __import__("importlib.util", fromlist=["util"]).find_spec("envpool") is not None,
    reason="real envpool installed",
)
def test_envpool_missing_dep_message():
    from evox_tpu.problems.neuroevolution.hostenv import envpool_make

    with pytest.raises(ImportError, match="envpool is not installed"):
        envpool_make("CartPole-v1", num_envs=4)

"""On-device policy-rollout problem — the neuroevolution engine.

Mirrors the reference's Brax problem structure (reference src/evox/problems/
neuroevolution/reinforcement_learning/brax.py:45-97: double-vmapped policy
over (pop, episodes), ``lax.while_loop`` episode loop stepping all envs until
everyone is done or ``max_episode_length``, reward masked by done,
``reduce_fn`` over episodes) — but generalized over any pure ``EnvSpec``
(our JAX control envs, or any external pure-JAX physics env wrapped into a
``(reset, obs, step)`` triple).

The reference's host-side rollout helpers are re-expressed as on-device
pytree state threaded through ``evaluate``:

- :class:`CapEpisode` (reference gym.py:267-281) — the episode-length cap
  becomes a *traced* while_loop bound updated from the measured mean episode
  length, so later generations stop early once policies die fast.
- :class:`ObsNormalizer` (reference gym.py:20-56) — running observation
  statistics; observations are normalized with the stats at evaluation start
  and the moments observed during the rollout are merged afterwards.

TPU-first: the entire evaluation is one jit region; under the workflow mesh
the pop axis of the weight batch is sharded, so each chip rolls out only its
population shard — the north-star workload shape (SURVEY.md §6).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.instrument import DECODE, LAYOUT, RESET, ROLLOUT_KERNEL, scope
from ...core.problem import Problem
from .control.envs import EnvSpec


class CapEpisode:
    """Adaptive episode-length cap (reference gym.py:267-281): cap rollouts
    at twice the measured mean episode length — pure pytree state."""

    def __init__(self, init_cap: int = 100):
        self.init_cap = init_cap

    def init(self) -> jax.Array:
        return jnp.asarray(self.init_cap, dtype=jnp.int32)

    def update(self, cap: jax.Array, episode_lengths: jax.Array) -> jax.Array:
        del cap  # the new cap depends only on the measured lengths
        return jnp.maximum((2.0 * jnp.mean(episode_lengths)).astype(jnp.int32), 1)

    def get(self, cap: jax.Array) -> jax.Array:
        return cap


class ObsNormalizer:
    """Running observation statistics (reference gym.py:20-56 ``Normalizer``)
    as a pure pytree: ``state = (count, mean, m2)``."""

    def __init__(self, obs_dim: int, clip: float = 10.0):
        self.obs_dim = obs_dim
        self.clip = clip

    def init(self):
        return (
            jnp.zeros(()),
            jnp.zeros((self.obs_dim,)),
            jnp.zeros((self.obs_dim,)),
        )

    def update(self, state, obs_batch: jax.Array):
        """Welford batch update from a (..., obs_dim) batch of observations."""
        b = obs_batch.reshape(-1, self.obs_dim)
        n = b.shape[0]
        return self.merge_moments(
            state,
            jnp.asarray(float(n)),
            jnp.sum(b, axis=0),
            jnp.sum(b * b, axis=0),
        )

    def merge_moments(self, state, cnt, s1, s2):
        """Merge raw moments (count, sum, sum-of-squares) into the running
        (count, mean, m2) state (Chan's parallel update)."""
        count, mean, m2 = state
        safe_cnt = jnp.maximum(cnt, 1.0)
        b_mean = s1 / safe_cnt
        # clamp: the raw sum-of-squares form can cancel to small negatives
        # in f32 when |mean| >> stddev, which would NaN the sqrt downstream
        b_m2 = jnp.maximum(s2 - safe_cnt * b_mean * b_mean, 0.0)
        new_count = count + cnt
        delta = b_mean - mean
        new_mean = jnp.where(
            cnt > 0, mean + delta * cnt / jnp.maximum(new_count, 1.0), mean
        )
        new_m2 = jnp.where(
            cnt > 0,
            m2 + b_m2 + delta * delta * count * cnt / jnp.maximum(new_count, 1.0),
            m2,
        )
        return (new_count, new_mean, new_m2)

    def normalize(self, state, obs: jax.Array) -> jax.Array:
        count, mean, m2 = state
        var = jnp.where(count > 1, jnp.maximum(m2, 0.0) / jnp.maximum(count - 1, 1.0), 1.0)
        return jnp.clip(
            (obs - mean) / jnp.sqrt(var + 1e-8), -self.clip, self.clip
        )


class Trajectory(NamedTuple):
    """A single rollout's full trace (see :meth:`PolicyRolloutProblem.
    visualize`). All arrays are time-major with length ``max_episode_length``;
    steps after episode end are frozen (state repeats, reward 0, done True)."""

    states: Any  # (T, ...) raw env states — whatever the env's pytree is
    obs: jax.Array  # (T, obs_dim)
    actions: jax.Array  # (T, act_dim)
    rewards: jax.Array  # (T,)
    dones: jax.Array  # (T,) bool
    length: jax.Array  # () int32 — number of live steps


class RolloutState(NamedTuple):
    key: jax.Array
    cap: Any  # int32 cap when CapEpisode is enabled, else None
    norm: Any  # (count, mean, m2) when ObsNormalizer is enabled, else None


class PolicyRolloutProblem(Problem):
    """Evaluate a population of policy parameters by environment rollouts.

    Args:
        policy: ``(params, obs) -> action`` pure function (e.g.
            ``apply`` from :func:`~evox_tpu.problems.neuroevolution.policy.
            mlp_policy`, or a flax module's ``apply``).
        env: an :class:`EnvSpec`.
        num_episodes: episodes per individual; fitness = ``reduce_fn`` over
            episode returns.
        max_episode_length: cap on environment steps (defaults to the env's).
        reduce_fn: e.g. ``jnp.mean`` (default) over the episode axis.
        stochastic_reset: draw fresh episode seeds every evaluation (the
            reference's behavior); set False for a fixed evaluation seed
            (lower-variance ES gradients).
        cap_episode: a :class:`CapEpisode` to adapt the episode-length cap
            from the measured mean episode length across generations.
        obs_normalizer: an :class:`ObsNormalizer`; observations are
            normalized before the policy sees them and the running stats are
            updated from every (not-yet-done) step of every rollout.
        early_exit: True (default) rolls out in a ``lax.while_loop`` that
            stops as soon as every episode is done. Set False for envs that
            never terminate early (e.g. pendulum): the rollout becomes a
            ``lax.scan`` unrolled by ``unroll``, trading the per-iteration
            loop overhead for straight-line code XLA can pipeline — a real
            throughput win at large populations. Incompatible with
            ``cap_episode`` (the cap is a traced bound). Ignored by the
            ``fused_env`` engine, which picks its own loop form: per-tile
            early-exit while_loop for terminating envs, fixed-horizon
            fori for never-terminating ones (``SoAEnv.terminating``;
            same fitness either way).
        unroll: scan unroll factor for the ``early_exit=False`` path.
        fused_env: an :class:`~evox_tpu.kernels.rollout.SoAEnv` — switches
            ``evaluate`` to the fused Pallas rollout kernel
            (:func:`~evox_tpu.kernels.rollout.fused_rollout`): the whole
            episode runs inside one kernel with genomes, env state and
            activations resident in VMEM (one theta read + one fitness
            write of HBM traffic per env, vs one carry round-trip per
            step for the scan engine). Terminating envs are handled by a
            sticky in-kernel done mask with the standard engine's
            frozen-episode reward accounting, so fitness matches both
            ``early_exit`` engine modes. Requires no
            ``cap_episode``/``obs_normalizer`` and a flat ``(pop, dim)``
            population in :func:`flat_mlp_policy` layout. Initial states
            come from ``fused_env.base.reset`` with the same keys as the
            standard engines, so all engines are numerics-compatible
            (pinned by tests/test_kernels.py). Built-ins:
            ``pendulum_soa``, ``cartpole_soa``, ``mountain_car_soa``,
            ``acrobot_soa`` (kernels/rollout.py).
        fused_tile: environments per Pallas grid cell (multiple of 1024).
        fused_interpret: run the kernel in interpreter mode (None = auto:
            interpret on the CPU backend, compiled elsewhere).
        fused_planes: a :class:`~evox_tpu.kernels.rollout_mlp.PlaneEnv` —
            switches ``evaluate`` to the BIG-POLICY fused kernel
            (:func:`~evox_tpu.kernels.rollout_mlp.fused_mlp_rollout`):
            a tile of individuals' full MLP weights stays resident in
            VMEM across the whole episode, with per-tile early exit on
            termination. Population must be an ``mlp_policy`` params
            tree (pass the ``TreeAndVector`` adapter's ``batched_to_tree``
            as a workflow pop transform, as usual). For humanoid-scale
            policies where per-step weight re-reads dominate
            (the walker cell: PERF.md sections 4 and 5). Where that
            transform is the workflow's only one, the workflow hands
            over the undecoded batch as well (``evaluate_genome``) and the kernel reads each leaf whose
            first row in the genome and whose ``fan_out`` are multiples
            of the resident dtype's sublane packing (8 rows of float32,
            16 of bfloat16) in place, out of the flat ``(dim, n)``
            genome; only the other leaves are cut out of it first
            (``kernels.rollout_mlp.genome_rows``; for 244-64-64-17 the
            17-wide output layer). Bit-identical fitness either way. Any
            other chain of transforms, or ``evaluate`` called with a
            tree: every layer cut and laid out as its own block.
        fused_planes_tile: individuals per grid cell (multiple of 128).
        fused_planes_dtype: VMEM residency dtype for the policy planes in
            the big-policy kernel (e.g. ``jnp.bfloat16`` — halves the
            kernel's VMEM-bandwidth roofline and doubles the per-tile
            policy budget; accumulation and env math stay f32). None
            keeps f32 residency.
        fused_planes_linear: layer indices with no tanh after them, matching
            the policy's ``mlp_policy(linear_layers=...)`` — expresses
            low-rank factorized layers in the big-policy kernel (the
            fewer-MACs lever).
    """

    def __init__(
        self,
        policy: Callable,
        env: EnvSpec,
        num_episodes: int = 4,
        max_episode_length: Optional[int] = None,
        reduce_fn: Callable = jnp.mean,
        stochastic_reset: bool = True,
        cap_episode: Optional[CapEpisode] = None,
        obs_normalizer: Optional[ObsNormalizer] = None,
        early_exit: bool = True,
        unroll: int = 4,
        fused_env: Optional["SoAEnv"] = None,
        fused_tile: int = 2048,
        fused_interpret: Optional[bool] = None,
        fused_planes: Optional["PlaneEnv"] = None,
        fused_planes_tile: int = 128,
        fused_planes_dtype: Any = None,
        fused_planes_linear: Tuple[int, ...] = (),
    ):
        self.policy = policy
        self.env = env
        self.num_episodes = num_episodes
        self.max_len = max_episode_length or env.max_steps
        self.reduce_fn = reduce_fn
        self.stochastic_reset = stochastic_reset
        self.cap_episode = cap_episode
        self.obs_normalizer = obs_normalizer
        if not early_exit and cap_episode is not None:
            raise ValueError("early_exit=False cannot be combined with cap_episode")
        self.early_exit = early_exit
        self.unroll = unroll
        if fused_env is not None:
            if cap_episode is not None or obs_normalizer is not None:
                raise ValueError(
                    "fused_env cannot be combined with cap_episode or "
                    "obs_normalizer"
                )
            self._check_fused_base(fused_env.base, "fused_env")
        if fused_planes is not None:
            if fused_env is not None:
                raise ValueError("pass fused_env OR fused_planes, not both")
            if cap_episode is not None or obs_normalizer is not None:
                raise ValueError(
                    "fused_planes cannot be combined with cap_episode or "
                    "obs_normalizer"
                )
            self._check_fused_base(fused_planes.base, "fused_planes")
        self.fused_env = fused_env
        self.fused_tile = fused_tile
        self.fused_interpret = fused_interpret
        self.fused_planes = fused_planes
        self.fused_planes_tile = fused_planes_tile
        self.fused_planes_dtype = fused_planes_dtype
        self.fused_planes_linear = tuple(int(i) for i in fused_planes_linear)
        self._fused_policy_checked = False

    def _check_fused_base(self, base, name: str) -> None:
        """A fused spec built over a *different* env than the constructor's
        ``env`` would silently evaluate a different workload than the scan
        engine (T/obs_dim/act_dim come from ``self.env``, step math from the
        fused spec) — refuse the mismatch up front."""
        if base is self.env:
            return
        for attr in ("obs_dim", "act_dim", "max_steps"):
            if getattr(base, attr, None) != getattr(self.env, attr):
                raise ValueError(
                    f"{name}.base disagrees with env on {attr!r} "
                    f"({getattr(base, attr, None)} vs "
                    f"{getattr(self.env, attr)}); build the fused spec "
                    "over the same EnvSpec passed as env"
                )

    def _check_fused_policy(self, dim: int, hidden: int) -> None:
        """One-time concrete probe: ``self.policy`` must agree with the
        kernel's flat-MLP math, else evolution would silently optimize a
        different network than the ``policy`` the user later deploys."""
        import numpy as np

        from ...kernels.rollout import _mlp_act

        obs_dim, act_dim = self.env.obs_dim, self.env.act_dim
        rng = np.random.default_rng(0)
        # evaluate has usually been jit-traced by the workflow at this
        # point; the probe must still produce CONCRETE values, so force
        # compile-time evaluation of this constant-only computation
        with jax.ensure_compile_time_eval():
            theta = jnp.asarray(rng.normal(size=(dim,)), dtype=jnp.float32)
            obs = jnp.asarray(rng.normal(size=(obs_dim,)), dtype=jnp.float32)
            want = _mlp_act(
                theta[:, None], tuple(obs[k : k + 1] for k in range(obs_dim)),
                obs_dim, hidden, act_dim,
            )
            want = np.asarray(jnp.concatenate(want))
            got = np.asarray(self.policy(theta, obs)).reshape(-1)
        if got.shape != want.shape or not np.allclose(got, want, atol=1e-5):
            raise ValueError(
                "fused_env requires the policy to be the flat tanh MLP the "
                "kernel implements (use flat_mlp_policy); the supplied "
                "policy disagrees with the kernel math on a probe input"
            )
        self._fused_policy_checked = True

    def init(self, key=None) -> RolloutState:
        return RolloutState(
            key=key if key is not None else jax.random.PRNGKey(0),
            cap=self.cap_episode.init() if self.cap_episode else None,
            norm=self.obs_normalizer.init() if self.obs_normalizer else None,
        )

    def _evaluate_fused(
        self, state: RolloutState, pop: Any
    ) -> Tuple[jax.Array, RolloutState]:
        """Fused-kernel engine: same key/reset/reduce semantics as the scan
        engine, the episode loop replaced by one Pallas program per env
        tile (kernels/rollout.py)."""
        from ...kernels.rollout import fused_rollout

        key = state.key
        if self.stochastic_reset:
            key, k_eps = jax.random.split(key)
        else:
            k_eps = jax.random.fold_in(key, 0)
        pop = jnp.asarray(pop)
        pop_size, dim = pop.shape
        ep = self.num_episodes
        obs_dim, act_dim = self.env.obs_dim, self.env.act_dim
        hidden, rem = divmod(dim - act_dim, obs_dim + 1 + act_dim)
        if rem:
            raise ValueError(
                f"population dim {dim} is not a flat_mlp_policy genome for "
                f"obs_dim={obs_dim}, act_dim={act_dim}"
            )
        if not self._fused_policy_checked:
            self._check_fused_policy(dim, hidden)

        # same episode seeds/reset draws as the scan engine (common random
        # numbers across the population), then AoS -> SoA component planes,
        # EPISODE-MAJOR so the kernel re-reads one theta per episode block
        # instead of a jnp.repeat-ed copy
        with scope(RESET):
            ep_keys = jax.random.split(k_eps, ep)
            env_state0 = jax.vmap(self.fused_env.base.reset)(ep_keys)  # (ep, ...)
            env_flat = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[:, None], (ep, pop_size) + x.shape[1:]
                ).reshape((ep * pop_size,) + x.shape[1:]),
                env_state0,
            )
            soa0 = self.fused_env.to_soa(env_flat)
        interpret = self.fused_interpret
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        with scope(ROLLOUT_KERNEL):
            totals = fused_rollout(
                pop,
                soa0,
                T=int(self.max_len),
                obs_dim=obs_dim,
                hidden=hidden,
                act_dim=act_dim,
                step_soa=self.fused_env.step_soa,
                obs_soa=self.fused_env.obs_soa,
                tile=self.fused_tile,
                episodes=ep,
                early_stop=self.fused_env.terminating,
                interpret=interpret,
            )
        # (ep, pop) episode-major -> (pop, ep) so reduce_fn sees the same
        # axis convention as the scan engine
        fitness = self.reduce_fn(totals.reshape(ep, pop_size).T, axis=-1)
        return fitness, RolloutState(key=key, cap=state.cap, norm=state.norm)

    @property
    def evaluate_genome(self):
        """``Problem.evaluate_genome``: the big-policy kernel's engine can
        read the flat genome; the other engines have no such path."""
        return self._evaluate_fused_planes if self.fused_planes is not None else None

    def _evaluate_fused_planes(
        self, state: RolloutState, pop: Any, genome: Any = None, adapter: Any = None
    ) -> Tuple[jax.Array, RolloutState]:
        """Big-policy kernel engine (kernels/rollout_mlp.py): whole MLP
        resident in VMEM, per-tile early exit. ``pop`` must be an
        ``mlp_policy`` params tree (list of {"w", "b"} layers, batched on
        the leading axis). With ``genome`` (the ``(n, dim)`` batch ``pop``
        was decoded from by ``adapter``) the kernel is handed the genome
        whole and only the leaves it cannot read in place are cut out."""
        from ...kernels.rollout_mlp import fused_mlp_rollout, genome_rows

        key = state.key
        if self.stochastic_reset:
            key, k_eps = jax.random.split(key)
        else:
            k_eps = jax.random.fold_in(key, 0)
        if not (
            isinstance(pop, (list, tuple))
            and all(isinstance(l, dict) and {"w", "b"} <= set(l) for l in pop)
        ):
            raise ValueError(
                "fused_planes expects an mlp_policy params tree "
                "(list of {'w', 'b'} layers)"
            )
        sizes = pop[0]["w"].shape[1:2] + tuple(l["w"].shape[2] for l in pop)
        if genome is None:
            flat = rows = None
            with scope(LAYOUT):
                weights = tuple(l["w"].transpose(1, 2, 0) for l in pop)  # (in, out, n)
                biases = tuple(l["b"].T for l in pop)  # (out, n)
        else:
            offsets = adapter.offsets
            resident = self.fused_planes_dtype
            rows = genome_rows(
                offsets, sizes, genome.dtype if resident is None else resident
            )
            with scope(LAYOUT):
                # (dim, n), the member in the lane dimension: a bitcast of
                # how XLA lays the batch out anyway. Behind a barrier, or
                # the fusion that writes the batch out (ask's) takes the
                # transpose as its root and, in a trace, evaluate's name
                flat = jax.lax.optimization_barrier(genome).T
            with scope(DECODE):
                # rows off + k * fan_out + j of the genome are w[k, j]
                weights = tuple(
                    None if r[0] is not None else
                    flat[o["w"] : o["w"] + i * j].reshape(i, j, -1)
                    for r, o, i, j in zip(rows, offsets, sizes, sizes[1:])
                )
                biases = tuple(
                    None if r[1] is not None else flat[o["b"] : o["b"] + j]
                    for r, o, j in zip(rows, offsets, sizes[1:])
                )
        if sizes[0] != self.env.obs_dim or sizes[-1] != self.env.act_dim:
            raise ValueError(
                f"policy sizes {sizes} do not match env "
                f"({self.env.obs_dim} -> {self.env.act_dim})"
            )
        if not self._fused_policy_checked:
            self._check_fused_planes_policy(pop, sizes)
        pop_size = pop[0]["b"].shape[0]
        ep = self.num_episodes

        with scope(RESET):
            ep_keys = jax.random.split(k_eps, ep)
            env_state0 = jax.vmap(self.fused_planes.base.reset)(ep_keys)
            env_flat = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[:, None], (ep, pop_size) + x.shape[1:]
                ).reshape((ep * pop_size,) + x.shape[1:]),
                env_state0,
            )
            planes0 = self.fused_planes.to_planes(env_flat)
        interpret = self.fused_interpret
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        with scope(ROLLOUT_KERNEL):
            totals = fused_mlp_rollout(
                weights,
                biases,
                planes0,
                T=int(self.max_len),
                sizes=sizes,
                step_planes=self.fused_planes.step_planes,
                obs_planes=self.fused_planes.obs_planes,
                tile=self.fused_planes_tile,
                episodes=ep,
                early_stop=self.fused_planes.terminating,
                interpret=interpret,
                weight_dtype=self.fused_planes_dtype,
                linear=self.fused_planes_linear,
                genome=flat,
                rows=rows,
            )
        fitness = self.reduce_fn(totals.reshape(ep, pop_size).T, axis=-1)
        return fitness, RolloutState(key=key, cap=state.cap, norm=state.norm)

    def _check_fused_planes_policy(self, pop: Any, sizes) -> None:
        """One-time concrete probe: ``self.policy`` must agree with the
        kernel's tanh-MLP plane math on the params tree layout."""
        import numpy as np

        from ...kernels.rollout_mlp import _mlp_planes

        rng = np.random.default_rng(0)
        with jax.ensure_compile_time_eval():
            params = [
                {
                    "w": jnp.asarray(
                        rng.normal(size=(sizes[i], sizes[i + 1])) * 0.3,
                        dtype=jnp.float32,
                    ),
                    "b": jnp.asarray(
                        rng.normal(size=(sizes[i + 1],)), dtype=jnp.float32
                    ),
                }
                for i in range(len(sizes) - 1)
            ]
            obs = jnp.asarray(rng.normal(size=(sizes[0],)), dtype=jnp.float32)
            w_refs = [l["w"][:, :, None] for l in params]  # (in, out, 1)
            b_refs = [l["b"][:, None] for l in params]  # (out, 1)
            want = np.asarray(
                _mlp_planes(
                    w_refs,
                    b_refs,
                    obs[:, None],
                    tuple(sizes),
                    self.fused_planes_linear,
                )
            ).reshape(-1)
            got = np.asarray(self.policy(params, obs)).reshape(-1)
        if got.shape != want.shape or not np.allclose(
            got, want, atol=1e-4, rtol=1e-4
        ):
            raise ValueError(
                "fused_planes requires the policy to be the tanh MLP the "
                "kernel implements (use mlp_policy); the supplied policy "
                "disagrees with the kernel math on a probe input"
            )
        self._fused_policy_checked = True

    def evaluate(self, state: RolloutState, pop: Any) -> Tuple[jax.Array, RolloutState]:
        if self.fused_planes is not None:
            return self._evaluate_fused_planes(state, pop)
        if self.fused_env is not None:
            return self._evaluate_fused(state, pop)
        key = state.key
        if self.stochastic_reset:
            key, k_eps = jax.random.split(key)
        else:
            k_eps = jax.random.fold_in(key, 0)
        pop_size = jax.tree.leaves(pop)[0].shape[0]
        ep_keys = jax.random.split(k_eps, self.num_episodes)

        # env state batch: (pop, episodes, ...) — same episode seeds across
        # the population for common random numbers
        env_state0 = jax.vmap(self.env.reset)(ep_keys)  # (ep, ...)
        env_state0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (pop_size,) + x.shape), env_state0
        )  # (pop, ep, ...)

        batched_policy = jax.vmap(  # over episodes
            jax.vmap(self.policy, in_axes=(None, 0)), in_axes=(0, 0)
        )  # params: (pop,...), obs: (pop, ep, obs_dim)

        if self.cap_episode is not None:
            max_len = jnp.minimum(
                jnp.asarray(self.max_len, jnp.int32), self.cap_episode.get(state.cap)
            )
        else:
            max_len = jnp.asarray(self.max_len, jnp.int32)

        obs_dim = self.env.obs_dim
        moments0 = (jnp.zeros(()), jnp.zeros((obs_dim,)), jnp.zeros((obs_dim,)))

        def cond(carry):
            t, _, done, _, _, _ = carry
            return (t < max_len) & ~jnp.all(done)

        def body(carry):
            t, env_state, done, total, ep_len, moments = carry
            o = jax.vmap(jax.vmap(self.env.obs))(env_state)
            if self.obs_normalizer is not None:
                cnt, s1, s2 = moments
                live = (~done).astype(o.dtype)[..., None]  # (pop, ep, 1)
                moments = (
                    cnt + jnp.sum(live),
                    s1 + jnp.sum(o * live, axis=(0, 1)),
                    s2 + jnp.sum(o * o * live, axis=(0, 1)),
                )
                o = self.obs_normalizer.normalize(state.norm, o)
            actions = batched_policy(pop, o)
            new_state, reward, step_done = jax.vmap(jax.vmap(self.env.step))(
                env_state, actions
            )
            total = total + jnp.where(done, 0.0, reward)
            ep_len = ep_len + (~done).astype(jnp.int32)
            # freeze finished episodes' states so the loop is a no-op there
            env_state = jax.tree.map(
                lambda old, new: jnp.where(
                    done.reshape(done.shape + (1,) * (new.ndim - 2)), old, new
                ),
                env_state,
                new_state,
            )
            return t + 1, env_state, done | step_done, total, ep_len, moments

        done0 = jnp.zeros((pop_size, self.num_episodes), dtype=bool)
        total0 = jnp.zeros((pop_size, self.num_episodes))
        len0 = jnp.zeros((pop_size, self.num_episodes), dtype=jnp.int32)
        carry0 = (jnp.int32(0), env_state0, done0, total0, len0, moments0)
        if self.early_exit:
            _, _, _, total, ep_len, moments = jax.lax.while_loop(
                cond, body, carry0
            )
        else:
            # fixed trip count: straight-line scan XLA can software-pipeline
            _, _, _, total, ep_len, moments = jax.lax.scan(
                lambda c, _: (body(c), None),
                carry0,
                length=int(self.max_len),
                unroll=self.unroll,
            )[0]
        fitness = self.reduce_fn(total, axis=-1)

        cap = state.cap
        if self.cap_episode is not None:
            cap = self.cap_episode.update(cap, ep_len)
        norm = state.norm
        if self.obs_normalizer is not None:
            norm = self.obs_normalizer.merge_moments(norm, *moments)
        return fitness, RolloutState(key=key, cap=cap, norm=norm)

    def visualize(
        self,
        params: Any,
        key: Optional[jax.Array] = None,
        state: Optional[RolloutState] = None,
    ) -> Trajectory:
        """Roll out ONE policy and return its full :class:`Trajectory`.

        The policy-inspection analog of the reference's ``visualize``
        (reference brax.py:99-133 renders HTML, gym.py:383-426 collects
        frames): with pure-JAX envs there is no renderer to call, so the
        trace itself — env states, observations, actions, rewards — is the
        artifact; pipe it into ``vis_tools`` plots or any custom renderer.
        Observation normalization uses the running stats in ``state`` (pass
        the post-training problem state to see what the policy actually saw).
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        env_state0 = self.env.reset(key)

        def scan_step(carry, _):
            env_state, done = carry
            o = self.env.obs(env_state)
            o_in = (
                self.obs_normalizer.normalize(state.norm, o)
                if self.obs_normalizer is not None and state is not None
                else o
            )
            action = self.policy(params, o_in)
            new_state, reward, step_done = self.env.step(env_state, action)
            new_state = jax.tree.map(
                lambda old, new: jnp.where(done, old, new), env_state, new_state
            )
            out = (env_state, o, action, jnp.where(done, 0.0, reward), done)
            return (new_state, done | step_done), out

        (_, _), (states, obs, actions, rewards, dones) = jax.lax.scan(
            scan_step, (env_state0, jnp.asarray(False)), length=self.max_len
        )
        return Trajectory(
            states=states,
            obs=obs,
            actions=actions,
            rewards=rewards,
            dones=dones,
            length=jnp.sum(~dones).astype(jnp.int32),
        )

"""OpenAI Evolution Strategy (Salimans et al. 2017, arXiv:1703.03864).

Capability parity with reference src/evox/algorithms/so/es_variants/open_es.py
(mirrored sampling, optional optax optimizer), functional TPU-native state.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from jax.sharding import PartitionSpec as P

from ....core.algorithm import Algorithm
from ....core.instrument import GRADIENT, NOISE, PERTURB, UPDATE, scope
from ....core.struct import PyTreeNode, field
from .common import make_optimizer


class OpenESState(PyTreeNode):
    # center/optimizer replicate. The (pop, dim) noise batch is NOT
    # stored: tell regenerates it from noise_key (counter-based PRNG is
    # deterministic, so ask and tell see bit-identical noise) — at
    # north-star scale the stored batch would be the dominant state
    # buffer (pop=65536 x dim=20945 = 5.5 GB), and dropping it is what
    # lets the humanoid-scale workload run at the BASELINE.md population
    # on one chip.
    center: jax.Array = field(sharding=P())
    opt_state: tuple = field(sharding=P())
    noise_key: jax.Array = field(sharding=P())
    key: jax.Array = field(sharding=P())


class OpenES(Algorithm):
    def __init__(
        self,
        center_init,
        pop_size: int,
        learning_rate: float = 0.05,
        noise_stdev: float = 0.02,
        optimizer=None,
        mirrored_sampling: bool = True,
    ):
        assert pop_size > 0 and learning_rate > 0 and noise_stdev > 0
        if mirrored_sampling:
            assert pop_size % 2 == 0, "mirrored sampling needs an even pop_size"
        self.center_init = jnp.asarray(center_init, dtype=jnp.float32)
        self.dim = self.center_init.shape[0]
        self.pop_size = pop_size
        self.learning_rate = learning_rate
        self.noise_stdev = noise_stdev
        self.mirrored = mirrored_sampling
        self.optimizer = make_optimizer(optimizer, learning_rate)
        # traced learning-rate multiplier on the optimizer's updates: the
        # optimizer's own learning rate is baked into its optax closure at
        # construction (not bindable as a traced hyperparameter), so
        # fleet/multi-level hyperparameter adaptation rebinds THIS knob
        # instead (workflows/tenancy.py hyperparams, workflows/
        # multilevel.py HyperSpec). The 1.0 default compiles to the exact
        # pre-knob program (the multiply is skipped statically below).
        self.lr_scale = 1.0

    def init(self, key: jax.Array) -> OpenESState:
        key, k = jax.random.split(key)
        return OpenESState(
            center=self.center_init,
            opt_state=self.optimizer.init(self.center_init),
            noise_key=k,
            key=key,
        )

    @scope(NOISE)
    def _noise(self, k: jax.Array) -> jax.Array:
        if self.mirrored:
            half = jax.random.normal(k, (self.pop_size // 2, self.dim))
            return jnp.concatenate([half, -half], axis=0)
        return jax.random.normal(k, (self.pop_size, self.dim))

    def ask(self, state: OpenESState) -> Tuple[jax.Array, OpenESState]:
        key, k = jax.random.split(state.key)
        # the regenerated batch is a jit transient: under a mesh its
        # sharding comes from GSPMD propagating backward from the
        # workflow's shard_pop constraint on the emitted population (and
        # from the sharded fitness in tell's contraction) rather than
        # from a state-field annotation as before
        noise = self._noise(k)
        with scope(PERTURB):
            pop = state.center + self.noise_stdev * noise
        return pop, state.replace(noise_key=k, key=key)

    def tell(self, state: OpenESState, fitness: jax.Array) -> OpenESState:
        # minimize: estimated gradient of E[f] wrt center; noise is
        # regenerated from the paired ask's key (bit-identical values, no
        # persistent (pop, dim) buffer — see OpenESState). Mirrored
        # sampling folds: noise.T @ f == half.T @ (f_pos - f_neg), so the
        # dominant transient is (pop/2, dim), not (pop, dim).
        with scope(GRADIENT):
            if self.mirrored:
                half = jax.random.normal(
                    state.noise_key, (self.pop_size // 2, self.dim)
                )
                m = self.pop_size // 2
                grad = half.T @ (fitness[:m] - fitness[m:])
            else:
                noise = jax.random.normal(
                    state.noise_key, (self.pop_size, self.dim)
                )
                grad = noise.T @ fitness
            grad = grad / (self.pop_size * self.noise_stdev)
        with scope(UPDATE):
            updates, opt_state = self.optimizer.update(
                grad, state.opt_state, state.center
            )
            if not (isinstance(self.lr_scale, float) and self.lr_scale == 1.0):
                # only reached when lr_scale was rebound (a traced tenant /
                # multi-level hyperparameter, or an explicit non-1 float)
                updates = jax.tree.map(lambda u: u * self.lr_scale, updates)
            center = optax.apply_updates(state.center, updates)
        return state.replace(center=center, opt_state=opt_state)

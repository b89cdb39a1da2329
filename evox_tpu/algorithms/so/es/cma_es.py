"""CMA-ES family (Hansen, "The CMA Evolution Strategy: A Tutorial",
arXiv:1604.00772).

Capability parity with reference src/evox/algorithms/so/es_variants/cma_es.py
(CMAES, SepCMAES, IPOP/BIPOP restarts), TPU-first design choices:

- the full generation (ask + tell) is pure and jit/scan-compatible;
- eigendecomposition of C is *lazy*: performed every ``decomp_per_iter``
  generations inside ``lax.cond`` (both per the tutorial's amortization rule
  and because ``eigh`` is the one op here that does not love the MXU);
- restarts: jit-compatible in-place restart on stagnation (same pop size,
  static shapes) plus a host-level :class:`RestartCMAESDriver` implementing
  true IPOP/BIPOP population growth (a new pop size means a new compiled
  program on TPU, so growth lives outside jit by design — unlike the
  reference, which also keeps pop_size fixed inside its IPOP `tell` and is
  noted buggy there, SURVEY.md §2.4).

The reference warns its eigh is numerically hardware-sensitive (cma_es.py
:40-44); here f32 ``jnp.linalg.eigh`` is used as it is — no host offload
or f64 (the convergence tests of tests/test_so_es.py run on it; dense
CMA-ES has no benchmark cell yet: PERF.md section 7, row 7).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ....core.algorithm import Algorithm
from jax.sharding import PartitionSpec as P
from ....core.distributed import POP_AXIS
from ....core.struct import PyTreeNode, field
# recombination_weights aliased: CMAES.__init__ has a parameter of that name
from .common import (
    bounded_sigma_step,
    capped_mu_weights,
    check_dense_scale,
    clamp_step_size,
    recombination_weights as _stable_weights,
    safe_eigh,
    sorted_selection_moments,
    weights_at_ranks,
)


def _default_pop_size(dim: int) -> int:
    return 4 + math.floor(3 * math.log(dim))


class CMAESState(PyTreeNode):
    mean: jax.Array = field(sharding=P())
    sigma: jax.Array = field(sharding=P())
    pc: jax.Array = field(sharding=P())
    ps: jax.Array = field(sharding=P())
    C: jax.Array = field(sharding=P())
    B: jax.Array = field(sharding=P())
    D: jax.Array = field(sharding=P())
    z: jax.Array = field(sharding=P(POP_AXIS), storage=True)  # standardized samples of the current generation
    iteration: jax.Array = field(sharding=P())
    key: jax.Array = field(sharding=P())


class CMAES(Algorithm):
    def __init__(
        self,
        center_init,
        init_stdev: float,
        pop_size: Optional[int] = None,
        recombination_weights=None,
        cm: float = 1.0,
        decomp_per_iter: Optional[int] = None,
        sigma_floor: float = 1e-20,
        sigma_ceiling: float = 1e20,
        cond_cap: float = 1e14,
        eigh_max_dim: Optional[int] = 4096,
        dense_budget_elems: Optional[int] = 2**26,
    ):
        assert init_stdev > 0
        # numeric guards (es/common.py): identity for healthy trajectories,
        # rails for multiplicative sigma collapse/explosion and for a
        # drifted/indefinite covariance reaching eigh
        self.sigma_floor = sigma_floor
        self.sigma_ceiling = sigma_ceiling
        self.cond_cap = cond_cap
        self.eigh_max_dim = eigh_max_dim
        self.center_init = jnp.asarray(center_init, dtype=jnp.float32)
        self.dim = int(self.center_init.shape[0])
        self.init_stdev = float(init_stdev)
        self.pop_size = pop_size or _default_pop_size(self.dim)
        # scale guard (es/common.py): the dense track stalls/OOMs past the
        # single-device wall — refuse eagerly with the sep/low-rank handoff
        # named in the error instead of compiling a program that never ends
        check_dense_scale(
            self.dim, self.pop_size, eigh_max_dim, dense_budget_elems, "CMAES"
        )
        self.cm = cm
        n, lam = self.dim, self.pop_size

        if recombination_weights is None:
            mu = lam // 2
            # f32-stable log-rank weights (es/common.py): log1p raw form +
            # logsumexp normalization, identical to the classic
            # log((lam+1)/2) - log(rank) form up to fp rounding at small mu
            # and correct (no underflow-to-0 tails) at mu ~ 1e6
            w = _stable_weights(mu, (lam + 1) / 2)
        else:
            w = jnp.asarray(recombination_weights, dtype=jnp.float32)
            mu = int(w.shape[0])
        self.mu = mu
        self.weights = w
        self.mueff = float(jnp.sum(w) ** 2 / jnp.sum(w**2))

        me = self.mueff
        self.cc = (4 + me / n) / (n + 4 + 2 * me / n)
        self.cs = (me + 2) / (n + me + 5)
        self.c1 = 2 / ((n + 1.3) ** 2 + me)
        self.cmu = min(1 - self.c1, 2 * (me - 2 + 1 / me) / ((n + 2) ** 2 + me))
        self.damps = 1 + 2 * max(0.0, math.sqrt((me - 1) / (n + 1)) - 1) + self.cs
        self.chiN = math.sqrt(n) * (1 - 1 / (4 * n) + 1 / (21 * n**2))
        if decomp_per_iter is None:
            decomp_per_iter = max(1, round(1 / ((self.c1 + self.cmu) * n * 10)))
        self.decomp_per_iter = decomp_per_iter

    # ------------------------------------------------------------------ api
    def init(self, key: jax.Array) -> CMAESState:
        n = self.dim
        return CMAESState(
            mean=self.center_init,
            sigma=jnp.asarray(self.init_stdev, dtype=jnp.float32),
            pc=jnp.zeros((n,)),
            ps=jnp.zeros((n,)),
            C=jnp.eye(n),
            B=jnp.eye(n),
            D=jnp.ones((n,)),
            z=jnp.zeros((self.pop_size, n)),
            iteration=jnp.zeros((), dtype=jnp.int32),
            key=key,
        )

    def ask(self, state: CMAESState) -> Tuple[jax.Array, CMAESState]:
        key, k = jax.random.split(state.key)
        z = jax.random.normal(k, (self.pop_size, self.dim))
        # x_i = mean + sigma * B (D ⊙ z_i)   — batched as one matmul (MXU)
        y = (z * state.D) @ state.B.T
        pop = state.mean + state.sigma * y
        return pop, state.replace(z=z, key=key)

    def tell(self, state: CMAESState, fitness: jax.Array) -> CMAESState:
        n = self.dim
        order = jnp.argsort(fitness)
        z_sorted = state.z[order][: self.mu]
        y_sorted = (z_sorted * state.D) @ state.B.T
        y_w = self.weights @ y_sorted
        mean = state.mean + self.cm * state.sigma * y_w

        # invsqrtC @ y_w == B z_w because y = B D z
        z_w = self.weights @ z_sorted
        ps = (1 - self.cs) * state.ps + math.sqrt(
            self.cs * (2 - self.cs) * self.mueff
        ) * (state.B @ z_w)
        it = state.iteration + 1
        ps_norm = jnp.linalg.norm(ps)
        hsig = ps_norm / jnp.sqrt(1 - (1 - self.cs) ** (2 * it.astype(jnp.float32))) < (
            1.4 + 2 / (n + 1)
        ) * self.chiN
        hsig = hsig.astype(jnp.float32)
        pc = (1 - self.cc) * state.pc + hsig * math.sqrt(
            self.cc * (2 - self.cc) * self.mueff
        ) * y_w

        rank_mu = (y_sorted * self.weights[:, None]).T @ y_sorted
        C = (
            (1 - self.c1 - self.cmu) * state.C
            + self.c1
            * (jnp.outer(pc, pc) + (1 - hsig) * self.cc * (2 - self.cc) * state.C)
            + self.cmu * rank_mu
        )
        sigma = clamp_step_size(
            state.sigma * jnp.exp(self.cs / self.damps * (ps_norm / self.chiN - 1)),
            self.sigma_floor,
            self.sigma_ceiling,
        )

        B, D = jax.lax.cond(
            it % self.decomp_per_iter == 0,
            lambda: self._decompose(C),
            lambda: (state.B, state.D),
        )
        return state.replace(
            mean=mean, sigma=sigma, pc=pc, ps=ps, C=C, B=B, D=D, iteration=it,
        )

    def _decompose(self, C: jax.Array):
        return safe_eigh(C, self.cond_cap, max_dim=self.eigh_max_dim)


class SepCMAESState(PyTreeNode):
    mean: jax.Array = field(sharding=P())
    sigma: jax.Array = field(sharding=P())
    pc: jax.Array = field(sharding=P())
    ps: jax.Array = field(sharding=P())
    C: jax.Array = field(sharding=P())  # diagonal of the covariance
    z: jax.Array = field(sharding=P(POP_AXIS), storage=True)
    iteration: jax.Array = field(sharding=P())
    key: jax.Array = field(sharding=P())


class SepCMAES(Algorithm):
    """Separable (diagonal-covariance) CMA-ES — O(d) memory, for very high
    dimension (Ros & Hansen 2008). Reference cma_es.py:200-253.

    Low-memory sharded track (PR 10): ``tell`` is expressed through
    weighted per-candidate moments (``pop_moments``/``tell_with_moments``)
    so :class:`~evox_tpu.core.distributed.ShardedES` can run the rank-µ
    and path updates as psum-of-partial-sums over a POP-sharded sample
    matrix — no device ever gathers the full ``(pop, dim)`` population.
    The replicated path uses the identical decomposition (sorted-selection
    moments), so the two differ only by floating-point summation order."""

    pop_shard_capable = True  # ShardedES protocol (core/distributed.py)
    sharded_pop_fields = ("z",)

    def __init__(
        self,
        center_init,
        init_stdev: float,
        pop_size: Optional[int] = None,
        mu: Optional[int] = None,
        sigma_floor: float = 1e-20,
        sigma_ceiling: float = 1e20,
    ):
        assert init_stdev > 0
        self.sigma_floor = sigma_floor
        self.sigma_ceiling = sigma_ceiling
        self.center_init = jnp.asarray(center_init, dtype=jnp.float32)
        self.dim = int(self.center_init.shape[0])
        self.init_stdev = float(init_stdev)
        self.pop_size = pop_size or _default_pop_size(self.dim)
        n, lam = self.dim, self.pop_size
        # mu: optional large-population parent cap (es/common.py
        # capped_mu_weights — restores mueff = O(mu) at pop ~ 1e5-1e6)
        mu, w = capped_mu_weights(lam, mu)
        self.mu, self.weights = mu, w
        me = float(jnp.sum(w) ** 2 / jnp.sum(w**2))
        self.mueff = me
        self.cc = (4 + me / n) / (n + 4 + 2 * me / n)
        self.cs = (me + 2) / (n + me + 5)
        # separable variant: covariance learning rate scaled up by (n+2)/3
        # (Ros & Hansen 2008) — additionally capped at 1.0: past
        # mueff ~ (n+2)^2 the scaled rate exceeds 1, turning the
        # (1 - c1 - cmu) decay factor NEGATIVE and collapsing C to its
        # floor within generations (observed at pop=1e6). At total rate 1
        # the covariance is fully re-estimated from the current
        # generation's mu ~ 5e5 samples — statistically sound at that
        # sample count, and the cap is inactive at conventional λ.
        self.ccov = min(
            1.0,
            (n + 2) / 3 * min(
                1.0,
                2 * (me - 2 + 1 / me) / ((n + 2) ** 2 + me)
                + 2 / ((n + 1.3) ** 2 + me),
            ),
        )
        self.c1 = self.ccov * 2 / ((n + 1.3) ** 2 + me) / (
            2 / ((n + 1.3) ** 2 + me) + min(1.0, 2 * (me - 2 + 1 / me) / ((n + 2) ** 2 + me))
        )
        self.cmu = self.ccov - self.c1
        self.damps = 1 + 2 * max(0.0, math.sqrt((me - 1) / (n + 1)) - 1) + self.cs
        self.chiN = math.sqrt(n) * (1 - 1 / (4 * n) + 1 / (21 * n**2))

    def init(self, key: jax.Array) -> SepCMAESState:
        n = self.dim
        return SepCMAESState(
            mean=self.center_init,
            sigma=jnp.asarray(self.init_stdev, dtype=jnp.float32),
            pc=jnp.zeros((n,)),
            ps=jnp.zeros((n,)),
            C=jnp.ones((n,)),
            z=jnp.zeros((self.pop_size, n)),
            iteration=jnp.zeros((), dtype=jnp.int32),
            key=key,
        )

    def ask(self, state: SepCMAESState) -> Tuple[jax.Array, SepCMAESState]:
        key, k = jax.random.split(state.key)
        z = jax.random.normal(k, (self.pop_size, self.dim))
        pop = state.mean + state.sigma * jnp.sqrt(state.C) * z
        return pop, state.replace(z=z, key=key)

    # ----------------------------------------- sharded low-memory protocol
    # (core/distributed.py ShardedES). `ask_rows` is the per-shard sampling
    # law — each device draws only its own (pop/n_shards, dim) block from a
    # fold_in-derived stream; `pop_moments` + `tell_with_moments` split the
    # update at the reduction boundary so the sharded path psums (dim,)
    # partial sums instead of gathering the population.

    def ask_rows(self, state: SepCMAESState, key: jax.Array, n_rows: int):
        z = jax.random.normal(key, (n_rows, self.dim))
        pop = state.mean + state.sigma * jnp.sqrt(state.C) * z
        return pop, {"z": z}

    def rank_weights(self, ranks: jax.Array) -> jax.Array:
        return weights_at_ranks(self.weights, ranks, self.mu)

    def pop_moments(self, rows, weights: jax.Array):
        z = rows["z"]
        return {"zw": weights @ z, "zzw": weights @ (z**2)}

    def tell_with_moments(
        self, state: SepCMAESState, moments, fitness: jax.Array
    ) -> SepCMAESState:
        n = self.dim
        z_w = moments["zw"]
        D = jnp.sqrt(state.C)
        # y = z * D rowwise, so the weighted sums factor: y_w = z_w * D and
        # sum_i w_i y_i^2 = zzw * C — the (dim,)-sized moments are all the
        # population information the update needs
        y_w = z_w * D
        rank_mu = moments["zzw"] * state.C
        mean = state.mean + state.sigma * y_w
        ps = (1 - self.cs) * state.ps + math.sqrt(
            self.cs * (2 - self.cs) * self.mueff
        ) * z_w
        it = state.iteration + 1
        ps_norm = jnp.linalg.norm(ps)
        hsig = ps_norm / jnp.sqrt(1 - (1 - self.cs) ** (2 * it.astype(jnp.float32))) < (
            1.4 + 2 / (n + 1)
        ) * self.chiN
        hsig = hsig.astype(jnp.float32)
        pc = (1 - self.cc) * state.pc + hsig * math.sqrt(
            self.cc * (2 - self.cc) * self.mueff
        ) * y_w
        C = (
            (1 - self.c1 - self.cmu) * state.C
            + self.c1 * (pc**2 + (1 - hsig) * self.cc * (2 - self.cc) * state.C)
            + self.cmu * rank_mu
        )
        C = jnp.maximum(C, 1e-20)
        # bounded CSA step (es/common.py): at mueff ~ 1e5 the raw exponent
        # is O(sqrt(mueff)) on any slope — identity at conventional λ
        sigma = bounded_sigma_step(
            state.sigma,
            self.cs / self.damps * (ps_norm / self.chiN - 1),
            self.sigma_floor,
            self.sigma_ceiling,
        )
        return state.replace(mean=mean, sigma=sigma, pc=pc, ps=ps, C=C, iteration=it)

    def tell(self, state: SepCMAESState, fitness: jax.Array) -> SepCMAESState:
        moments, _ = sorted_selection_moments(self, state, fitness)
        return self.tell_with_moments(state, moments, fitness)


class _RestartCMAES(CMAES):
    """CMA-ES with jit-compatible in-place restart on stagnation: when the
    best-fitness spread over the current generation collapses below
    ``stagnation_tol`` (or sigma explodes/vanishes), strategy state resets
    and the mean re-samples uniformly in ``restart_bounds``. Shapes (and
    pop size) stay static — see module docstring for why growth is host-side.
    """

    def __init__(self, *args, stagnation_tol: float = 1e-12,
                 restart_bounds: Tuple[float, float] = (-1.0, 1.0), **kwargs):
        super().__init__(*args, **kwargs)
        self.stagnation_tol = stagnation_tol
        self.restart_bounds = restart_bounds

    def tell(self, state: CMAESState, fitness: jax.Array) -> CMAESState:
        new_state = super().tell(state, fitness)
        spread = jnp.max(fitness) - jnp.min(fitness)
        degenerate = (
            (spread < self.stagnation_tol)
            | (new_state.sigma < 1e-16)
            | (new_state.sigma > 1e16)
            | ~jnp.isfinite(new_state.sigma)
        )

        def restart(s: CMAESState) -> CMAESState:
            key, k = jax.random.split(s.key)
            lo, hi = self.restart_bounds
            mean = jax.random.uniform(k, (self.dim,), minval=lo, maxval=hi)
            fresh = self.init(key)
            return fresh.replace(mean=mean, iteration=s.iteration)

        return jax.lax.cond(degenerate, restart, lambda s: s, new_state)


class IPOPCMAES(_RestartCMAES):
    """Restart-CMA-ES (static pop size inside jit; use
    :class:`RestartCMAESDriver` for true IPOP population doubling)."""


class BIPOPCMAES(_RestartCMAES):
    """Restart-CMA-ES (static pop size inside jit; use
    :class:`RestartCMAESDriver` with ``bipop=True`` for the two-regime
    budget schedule)."""


class RestartCMAESDriver:
    """Host-level IPOP/BIPOP driver (Auger & Hansen 2005; Hansen 2009).

    Runs CMA-ES to stagnation, then restarts with a doubled population
    (IPOP) or alternates large/small-pop regimes (BIPOP). Each pop size is a
    separate compiled program — the TPU-honest way to grow λ, since XLA
    shapes are static.

    Usage::

        driver = RestartCMAESDriver(center_init, init_stdev, evaluate_fn)
        best_x, best_f = driver.run(key, max_restarts=5, gens_per_run=200)
    """

    def __init__(self, center_init, init_stdev, evaluate_fn, bipop: bool = False,
                 base_pop_size: Optional[int] = None):
        self.center_init = jnp.asarray(center_init, dtype=jnp.float32)
        self.init_stdev = init_stdev
        self.evaluate_fn = evaluate_fn
        self.bipop = bipop
        self.base_pop_size = base_pop_size or _default_pop_size(self.center_init.shape[0])

    def run(self, key: jax.Array, max_restarts: int = 5, gens_per_run: int = 200):
        best_x, best_f = None, jnp.inf
        large_pop = self.base_pop_size
        # BIPOP budget accounting (Hansen 2009): pick the regime with the
        # smaller spent evaluation budget; only large-regime runs double λ.
        budget_large, budget_small = 0, 0
        for restart in range(max_restarts):
            key, k_init, k_regime = jax.random.split(key, 3)
            small_regime = self.bipop and restart > 0 and budget_small < budget_large
            if small_regime:
                u = float(jax.random.uniform(k_regime))
                ratio = (large_pop / self.base_pop_size) ** (u**2)
                lam = max(4, int(self.base_pop_size * ratio) // 2 * 2)
            else:
                if restart > 0:
                    large_pop *= 2  # IPOP growth, large regime only
                lam = large_pop
            algo = CMAES(self.center_init, self.init_stdev, pop_size=lam)
            state = algo.init(k_init)

            @jax.jit
            def gen(state):
                pop, state = algo.ask(state)
                fit = self.evaluate_fn(pop)
                state = algo.tell(state, fit)
                return state, pop, fit

            gens_done = 0
            for _ in range(gens_per_run):
                state, pop, fit = gen(state)
                gens_done += 1
                i = jnp.argmin(fit)
                if fit[i] < best_f:
                    best_f, best_x = fit[i], pop[i]
                spread = jnp.max(fit) - jnp.min(fit)
                if spread < 1e-12 or not jnp.isfinite(state.sigma):
                    break
            if small_regime:
                budget_small += gens_done * lam
            else:
                budget_large += gens_done * lam
        return best_x, best_f

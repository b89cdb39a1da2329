"""On-device surrogate models for pre-screening expensive evaluations.

The reference ships a gaussian-process operator layer as a gpjax extra
(reference: src/evox/operators/gaussian_process/regression.py — SURVEY §1
layer 3) that exists only host-side and is never wired into a workflow.
This module is the TPU-native analog the ROADMAP item 5 arc needs: a
fixed-capacity **paired (candidate, fitness) archive ring** plus two
interchangeable surrogate models behind one ``fit``/``predict(mean,
uncertainty)`` interface, all pure jittable math — zero host callbacks
(pinned by tests/test_no_host_callbacks.py), so they run identically in
``wf.step`` loops and the fused ``wf.run`` ``fori_loop``. Consumed by
:class:`~evox_tpu.workflows.surrogate.SurrogateWorkflow`, which spends
these cheap on-device FLOPs to cut TRUE evaluations per unit of
convergence (the compute-for-samples trade of "Fast Population-Based RL
on a Single Machine", PAPERS.md).

Models:

- :class:`GPSurrogate` — an exact GP (RBF kernel, one Cholesky solve,
  f32 throughout). Kernel scale/amplitude come from masked data
  statistics (mean pairwise distance / fitness variance), so ``fit`` is
  deterministic and one dense ``(capacity, capacity)`` factorization —
  MXU-friendly, and **capacity-bounded** by the dense-scale guard
  discipline (algorithms/so/es/common.py ``check_dense_scale``):
  capacities past ``max_capacity`` raise :class:`GPCapacityError` naming
  the :class:`EnsembleSurrogate` handoff instead of silently compiling
  an O(capacity³) program.
- :class:`EnsembleSurrogate` — a deep ensemble of small MLPs trained
  with optax adam on the (masked, standardized) archive; the ensemble
  mean is the prediction and the de-standardized member disagreement
  (std over members) is the uncertainty. Scales past the GP's dense
  budget; uncertainty is epistemic-by-disagreement (Lakshminarayanan et
  al. 2017's recipe), which is exactly the health signal the workflow's
  fallback predicates consume.

Every state here is a frozen :class:`~evox_tpu.core.struct.PyTreeNode`
with the repo's sharding/storage annotations (capacity-leading buffers
annotated ``P(POP_AXIS)`` so a meshed workflow shards the archive rows;
candidates are ``storage=True`` — bf16-storage-compatible under a
``DtypePolicy`` — while fitness and every factorization product carry
the explicit ``storage=False`` must-stay-f32 opt-out), enforced by
tests/test_state_contracts.py.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from ..core.distributed import POP_AXIS
from ..core.struct import PyTreeNode, field
from ..utils.ring import ring_scatter_indices

__all__ = [
    "ArchiveState",
    "SurrogateArchive",
    "GPCapacityError",
    "GPModelState",
    "GPSurrogate",
    "EnsembleModelState",
    "EnsembleSurrogate",
    "spearman_correlation",
]


# ------------------------------------------------------------------ archive


class ArchiveState(PyTreeNode):
    """Paired (candidate, fitness) ring — the EvalMonitor ring discipline
    (monitors/eval_monitor.py ``_update_device_history``) extended to
    store the candidates alongside their TRUE fitness, because that pair
    is the surrogate's training set. ``count`` is the total writes ever;
    slot ``count % capacity`` is the next write target, so once full the
    oldest pairs are overwritten (the model tracks the search's moving
    neighborhood instead of averaging over stale basins)."""

    # candidates may rest at storage width between generations (the model
    # upcasts to f32 at fit time); fitness is the ranking signal and
    # stays f32 (explicit must-stay opt-out)
    x: jax.Array = field(sharding=P(POP_AXIS), storage=True)  # (capacity, dim)
    y: jax.Array = field(sharding=P(POP_AXIS), storage=False)  # (capacity,) f32
    count: jax.Array = field(sharding=P())  # () int32 total writes ever


class SurrogateArchive:
    """Fixed-capacity on-device archive of evaluated (candidate, fitness)
    pairs. All methods are pure jittable math at fixed shapes.

    Args:
        capacity: ring size. Must be at least the widest batch a single
            ``update`` can write (the workflow enforces ``capacity >=
            ask width`` so one generation's scatter never collides with
            itself inside the ring).
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)

    def init(self, dim: int, dtype: Any = jnp.float32) -> ArchiveState:
        return ArchiveState(
            x=jnp.zeros((self.capacity, dim), dtype=dtype),
            y=jnp.full((self.capacity,), jnp.inf, dtype=jnp.float32),
            count=jnp.zeros((), dtype=jnp.int32),
        )

    def update(
        self,
        astate: ArchiveState,
        x: jax.Array,
        y: jax.Array,
        mask: jax.Array,
    ) -> ArchiveState:
        """Append the ``mask``-selected rows of ``(x, y)`` at the ring
        head. Masked-out rows scatter to an out-of-range index and are
        dropped (``mode="drop"``), so the write is one fixed-shape
        scatter regardless of how many rows this generation truly
        evaluated — no retrace as the screened count changes."""
        if x.shape[0] > self.capacity:
            raise ValueError(
                f"batch of {x.shape[0]} rows exceeds archive capacity "
                f"{self.capacity}; a single update's scatter would "
                "collide with itself inside the ring — size the archive "
                "to at least the widest evaluated batch"
            )
        idx, count = ring_scatter_indices(
            astate.count, mask, self.capacity
        )  # shared ring discipline (utils/ring.py)
        return ArchiveState(
            x=astate.x.at[idx].set(x.astype(astate.x.dtype), mode="drop"),
            y=astate.y.at[idx].set(y.astype(astate.y.dtype), mode="drop"),
            count=count,
        )

    def fill(self, astate: ArchiveState) -> jax.Array:
        """() int32 — how many slots hold real pairs."""
        return jnp.minimum(astate.count, self.capacity)

    def valid_mask(self, astate: ArchiveState) -> jax.Array:
        """(capacity,) bool — which slots hold real pairs. Because the
        ring only ever overwrites the oldest slot, the first
        ``min(count, capacity)`` slots are exactly the live ones."""
        return jnp.arange(self.capacity) < self.fill(astate)


# ------------------------------------------------------------- rank health


def spearman_correlation(
    a: jax.Array, b: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Masked Spearman rank correlation between two (n,) vectors — the
    health signal deciding whether the surrogate's ORDERING can be
    trusted (screening only consumes the order, never the values).
    Masked-out rows are pushed to the tail of both rankings and excluded
    from the correlation. Fewer than 3 valid rows returns 1.0 (no
    evidence is not evidence of lying — the warmup gate, not this
    predicate, owns the under-filled regime). Jittable, fixed shapes."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if mask is None:
        mask = jnp.ones(a.shape, dtype=bool)
    mask = mask & jnp.isfinite(a) & jnp.isfinite(b)
    n = jnp.sum(mask.astype(jnp.float32))
    # double argsort = dense ranks; masked rows ranked last (inf key)
    rank = lambda v: jnp.argsort(  # noqa: E731
        jnp.argsort(jnp.where(mask, v, jnp.inf))
    ).astype(jnp.float32)
    ra, rb = rank(a), rank(b)
    n_safe = jnp.maximum(n, 1.0)
    ma = jnp.sum(jnp.where(mask, ra, 0.0)) / n_safe
    mb = jnp.sum(jnp.where(mask, rb, 0.0)) / n_safe
    da = jnp.where(mask, ra - ma, 0.0)
    db = jnp.where(mask, rb - mb, 0.0)
    cov = jnp.sum(da * db)
    denom = jnp.sqrt(jnp.sum(da**2) * jnp.sum(db**2))
    corr = cov / jnp.maximum(denom, 1e-12)
    return jnp.where(n < 3, jnp.float32(1.0), jnp.clip(corr, -1.0, 1.0))


# ------------------------------------------------------------------ GP model


class GPCapacityError(RuntimeError):
    """The exact GP's dense ``(capacity, capacity)`` Cholesky exceeds its
    budget — same refusal discipline as the CMA dense-scale guard
    (algorithms/so/es/common.py ``EighScaleError``): fail loudly at
    construction naming the handoff, never compile an O(capacity³)
    program by accident."""


class GPModelState(PyTreeNode):
    """A fitted exact-GP posterior, cached so ``predict`` is one kernel
    cross-covariance + two triangular solves. Everything is f32
    (explicit ``storage=False`` opt-outs): the Cholesky factor and the
    solve vector are exactly the quantities half precision destroys."""

    x: jax.Array = field(sharding=P(POP_AXIS), storage=False)  # (cap, dim) f32
    chol: jax.Array = field(sharding=P(POP_AXIS), storage=False)  # (cap, cap)
    alpha: jax.Array = field(sharding=P(POP_AXIS), storage=False)  # (cap,)
    y_mean: jax.Array = field(sharding=P())  # () masked mean of y
    lengthscale2: jax.Array = field(sharding=P())  # () squared RBF scale
    amplitude: jax.Array = field(sharding=P())  # () kernel variance


class GPSurrogate:
    """Exact Gaussian-process surrogate: RBF kernel, one f32 Cholesky.

    Deterministic ``fit`` (no optimizer loop): the RBF lengthscale is
    the masked mean pairwise squared distance of the archived candidates
    (the median heuristic's cheap cousin) and the amplitude is the
    masked fitness variance, both recomputed per fit so the kernel
    tracks the search's moving scale. Dead archive rows are neutralized
    by a huge diagonal noise term (their posterior weight underflows to
    ~0), which keeps ``fit`` one fixed-shape program regardless of fill.
    This deliberately deviates from the reference's gpjax layer
    (optimizer-fitted hyperparameters, host-side): screening consumes
    the ORDER of the predictions, for which the data-statistic kernel is
    accurate and 50x cheaper — documented in PARITY row 60;
    :class:`~evox_tpu.operators.gaussian_process.regression.
    GPRegression` keeps the optimizer-fitted reference-parity API for
    host-side use.

    Args:
        noise: observation noise floor added to the kernel diagonal.
        max_capacity: dense-scale bound — archives past this raise
            :class:`GPCapacityError` naming the ensemble handoff.
    """

    kind = "gp"

    def __init__(self, noise: float = 1e-4, max_capacity: int = 2048):
        self.noise = float(noise)
        self.max_capacity = int(max_capacity)

    def check_capacity(self, capacity: int) -> None:
        if capacity > self.max_capacity:
            raise GPCapacityError(
                f"GPSurrogate: archive capacity {capacity} exceeds "
                f"max_capacity={self.max_capacity} — the exact GP is one "
                f"dense ({capacity}, {capacity}) Cholesky per refit "
                "(O(capacity^3)). Use EnsembleSurrogate for large "
                "archives, or raise max_capacity to override."
            )

    def init_model(self, capacity: int, dim: int) -> GPModelState:
        """An untrained (prior-only) model: zero-mean predictions with
        the prior amplitude as uncertainty. The workflow's warmup gate
        keeps screening off until the first real ``fit``."""
        self.check_capacity(capacity)
        return GPModelState(
            x=jnp.zeros((capacity, dim), dtype=jnp.float32),
            chol=jnp.eye(capacity, dtype=jnp.float32),
            alpha=jnp.zeros((capacity,), dtype=jnp.float32),
            y_mean=jnp.zeros((), dtype=jnp.float32),
            lengthscale2=jnp.ones((), dtype=jnp.float32),
            amplitude=jnp.ones((), dtype=jnp.float32),
        )

    @staticmethod
    def _sq_dists(a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)

    def fit(
        self,
        model: GPModelState,
        x: jax.Array,
        y: jax.Array,
        mask: jax.Array,
        key: Optional[jax.Array] = None,
    ) -> GPModelState:
        """Refit the posterior on the masked archive. ``key`` is accepted
        (and unused — the fit is deterministic) so both model kinds share
        one call signature. Jittable, fixed shapes."""
        del key
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        mask = mask & jnp.isfinite(y)
        fmask = mask.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(fmask), 1.0)
        y_mean = jnp.sum(jnp.where(mask, y, 0.0)) / n
        yc = jnp.where(mask, y - y_mean, 0.0)
        amplitude = jnp.maximum(
            jnp.sum(jnp.where(mask, (y - y_mean) ** 2, 0.0)) / n, 1e-8
        )
        d2 = self._sq_dists(x, x)
        pair_w = fmask[:, None] * fmask[None, :]
        ls2 = jnp.maximum(
            jnp.sum(d2 * pair_w) / jnp.maximum(jnp.sum(pair_w), 1.0), 1e-8
        )
        K = amplitude * jnp.exp(-0.5 * d2 / ls2)
        # dead rows get a huge diagonal: their posterior weight ~0, and
        # the factorization stays one fixed-shape program at any fill
        noise_vec = self.noise * amplitude + jnp.where(mask, 0.0, 1e8)
        L = jnp.linalg.cholesky(K + jnp.diag(noise_vec))
        alpha = jax.scipy.linalg.cho_solve((L, True), yc)
        return GPModelState(
            x=x,
            chol=L,
            alpha=alpha,
            y_mean=y_mean,
            lengthscale2=ls2,
            amplitude=amplitude,
        )

    def predict(
        self, model: GPModelState, x_test: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """(mean, uncertainty) at ``x_test`` (t, dim) — posterior mean and
        posterior standard deviation."""
        x_test = jnp.asarray(x_test, jnp.float32)
        Ks = model.amplitude * jnp.exp(
            -0.5 * self._sq_dists(x_test, model.x) / model.lengthscale2
        )
        mean = Ks @ model.alpha + model.y_mean
        v = jax.scipy.linalg.solve_triangular(model.chol, Ks.T, lower=True)
        var = jnp.clip(model.amplitude - jnp.sum(v**2, axis=0), 1e-12)
        return mean, jnp.sqrt(var)


# ------------------------------------------------------------ ensemble model


class EnsembleModelState(PyTreeNode):
    """A fitted deep ensemble: member-stacked MLP params plus the
    (masked) input/output standardization the members were trained
    under. Member axis leads every param leaf — that is the ENSEMBLE
    axis, never the population axis, so everything is ``P()`` per the
    state-layout convention."""

    params: Any = field(sharding=P())  # member-stacked MLP weights
    x_mean: jax.Array = field(sharding=P())  # (dim,)
    x_scale: jax.Array = field(sharding=P())  # (dim,)
    y_mean: jax.Array = field(sharding=P())  # ()
    y_scale: jax.Array = field(sharding=P())  # ()


class EnsembleSurrogate:
    """Deep-ensemble MLP surrogate trained with optax adam.

    ``n_members`` independently initialized MLPs (dim → hidden → hidden
    → 1, tanh) are trained on the standardized masked archive for
    ``fit_steps`` full-batch adam steps inside one ``lax.scan`` —
    jittable, fixed shapes, vmapped over the member axis. ``predict``
    returns the de-standardized ensemble mean and the member
    DISAGREEMENT (std over members) as uncertainty — the epistemic
    signal the fallback predicates consume: far from the archive the
    members extrapolate differently and the disagreement blows up.
    """

    kind = "ensemble"

    def __init__(
        self,
        n_members: int = 4,
        hidden: int = 32,
        fit_steps: int = 150,
        learning_rate: float = 1e-2,
    ):
        if n_members < 2:
            raise ValueError(
                f"n_members must be >= 2 (disagreement needs a spread), "
                f"got {n_members}"
            )
        self.n_members = int(n_members)
        self.hidden = int(hidden)
        self.fit_steps = int(fit_steps)
        self.opt = optax.adam(learning_rate)

    # -- MLP plumbing (member axis handled by vmap) ------------------------
    def _init_params(self, key: jax.Array, dim: int):
        k1, k2, k3 = jax.random.split(key, 3)
        h = self.hidden
        s1 = 1.0 / jnp.sqrt(jnp.float32(max(dim, 1)))
        s2 = 1.0 / jnp.sqrt(jnp.float32(h))
        return {
            "w1": jax.random.normal(k1, (dim, h), jnp.float32) * s1,
            "b1": jnp.zeros((h,), jnp.float32),
            "w2": jax.random.normal(k2, (h, h), jnp.float32) * s2,
            "b2": jnp.zeros((h,), jnp.float32),
            "w3": jax.random.normal(k3, (h, 1), jnp.float32) * s2,
            "b3": jnp.zeros((1,), jnp.float32),
        }

    @staticmethod
    def _forward(params, x: jax.Array) -> jax.Array:
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        h = jnp.tanh(h @ params["w2"] + params["b2"])
        return (h @ params["w3"] + params["b3"])[:, 0]

    def init_model(self, capacity: int, dim: int) -> EnsembleModelState:
        del capacity  # the ensemble has no dense-capacity bound
        keys = jax.random.split(jax.random.PRNGKey(0), self.n_members)
        return EnsembleModelState(
            params=jax.vmap(lambda k: self._init_params(k, dim))(keys),
            x_mean=jnp.zeros((dim,), jnp.float32),
            x_scale=jnp.ones((dim,), jnp.float32),
            y_mean=jnp.zeros((), jnp.float32),
            y_scale=jnp.ones((), jnp.float32),
        )

    def fit(
        self,
        model: EnsembleModelState,
        x: jax.Array,
        y: jax.Array,
        mask: jax.Array,
        key: jax.Array,
    ) -> EnsembleModelState:
        """Retrain every member from a fresh ``key``-derived init on the
        masked, standardized archive (full retrain per refit: the
        archive is small and a warm start would anchor the ensemble to a
        stale basin). Jittable, fixed shapes."""
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        dim = x.shape[1]
        mask = mask & jnp.isfinite(y)
        fmask = mask.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(fmask), 1.0)
        x_mean = jnp.sum(jnp.where(mask[:, None], x, 0.0), axis=0) / n
        x_var = jnp.sum(
            jnp.where(mask[:, None], (x - x_mean) ** 2, 0.0), axis=0
        ) / n
        x_scale = jnp.sqrt(jnp.maximum(x_var, 1e-8))
        y_mean = jnp.sum(jnp.where(mask, y, 0.0)) / n
        y_var = jnp.sum(jnp.where(mask, (y - y_mean) ** 2, 0.0)) / n
        y_scale = jnp.sqrt(jnp.maximum(y_var, 1e-8))
        xs = (x - x_mean) / x_scale
        ys = jnp.where(mask, (y - y_mean) / y_scale, 0.0)

        def train_member(k):
            params = self._init_params(k, dim)

            def loss_fn(p):
                pred = self._forward(p, xs)
                return jnp.sum(fmask * (pred - ys) ** 2) / n

            def step(carry, _):
                p, opt_state = carry
                loss, g = jax.value_and_grad(loss_fn)(p)
                updates, opt_state = self.opt.update(g, opt_state)
                p = optax.apply_updates(p, updates)
                return (p, opt_state), loss

            (params, _), _ = jax.lax.scan(
                step, (params, self.opt.init(params)), length=self.fit_steps
            )
            return params

        keys = jax.random.split(key, self.n_members)
        return EnsembleModelState(
            params=jax.vmap(train_member)(keys),
            x_mean=x_mean,
            x_scale=x_scale,
            y_mean=y_mean,
            y_scale=y_scale,
        )

    def predict(
        self, model: EnsembleModelState, x_test: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """(mean, uncertainty): de-standardized ensemble mean and member
        disagreement (std over members)."""
        xs = (jnp.asarray(x_test, jnp.float32) - model.x_mean) / model.x_scale
        preds = jax.vmap(lambda p: self._forward(p, xs))(model.params)
        mean = jnp.mean(preds, axis=0) * model.y_scale + model.y_mean
        disagreement = jnp.std(preds, axis=0) * model.y_scale
        return mean, disagreement

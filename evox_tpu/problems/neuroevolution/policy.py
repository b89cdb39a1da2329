"""Tiny pure-JAX policy networks for neuroevolution.

The reference's examples pair its rollout problems with user-supplied flax
modules; these helpers give the same ergonomics with zero dependencies: an
``(init_params, apply)`` pair whose params form an ordinary pytree, ready for
:class:`~evox_tpu.utils.TreeAndVector` and the workflow's ``pop_transforms``.

TPU note: small layers deliberately avoid ``obs @ w`` — under the rollout's
per-individual vmap that becomes a huge batch of tiny matmuls, which XLA:TPU
pads onto the MXU at enormous cost. The broadcast-multiply-reduce form
lowers to plain VPU elementwise work.
Wide layers (where the matmul genuinely fills MXU tiles) keep ``@``; the
per-layer choice is automatic (see ``mlp_policy``'s ``use_matmul``).
Custom policies used with :class:`PolicyRolloutProblem` should follow suit.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp


def flat_mlp_policy(
    obs_dim: int, hidden: int, act_dim: int = 1
) -> Tuple[Callable, int]:
    """One-hidden-layer tanh MLP over a FLAT genome vector.

    Returns ``(apply, dim)`` where ``apply(theta, obs) -> action`` consumes
    a ``(dim,)`` genome laid out ``[w1 row-major, b1, w2 row-major, b2]``
    — the layout the fused Pallas rollout kernel
    (:func:`~evox_tpu.kernels.rollout.fused_rollout`) reads directly, so a
    population evolved against this policy can switch between the scan and
    fused engines with bit-compatible genomes. ES algorithms consume the
    flat ``(pop, dim)`` population with no tree transform at all.

    Uses the VPU broadcast-multiply-reduce form (module docstring).
    """
    n1 = obs_dim * hidden
    n2 = n1 + hidden
    n3 = n2 + hidden * act_dim
    dim = n3 + act_dim

    def apply(theta: jax.Array, obs: jax.Array) -> jax.Array:
        w1 = theta[:n1].reshape(obs_dim, hidden)
        b1 = theta[n1:n2]
        w2 = theta[n2:n3].reshape(hidden, act_dim)
        b2 = theta[n3:]
        h = jnp.tanh(jnp.sum(obs[..., :, None] * w1, axis=-2) + b1)
        return jnp.sum(h[..., :, None] * w2, axis=-2) + b2

    return apply, dim


def mlp_policy(
    layer_sizes: Sequence[int],
    activation: Callable = jnp.tanh,
    final_activation: Callable | None = None,
    use_matmul: bool | None = None,
    linear_layers: Sequence[int] = (),
) -> Tuple[Callable, Callable]:
    """Build an MLP ``(init_params, apply)`` pair.

    ``init_params(key) -> params`` initializes Lecun-normal weights;
    ``apply(params, obs) -> action`` is pure and vmap/jit friendly.
    ``use_matmul``: per-layer by default — ``@`` for layers wide enough to
    fill MXU tiles, broadcast-multiply-reduce for the tiny layers where a
    per-individual batched matmul pads catastrophically (module docstring).
    Force with True/False.
    ``linear_layers``: indices of layers with NO activation after them.
    Two consecutive layers with the first linear express a low-rank
    factorized weight (``layer_sizes=(obs, r, h, act), linear_layers=(0,)``
    is a rank-r input layer) — same obs/act at a fraction of the MACs and
    genome dim; the fused kernel mirrors this via
    ``fused_mlp_rollout(linear=...)``.
    """
    sizes = tuple(int(s) for s in layer_sizes)
    if len(sizes) < 2:
        raise ValueError("layer_sizes needs at least (in, out)")
    linear_set = frozenset(int(i) for i in linear_layers)
    # a typo'd (or negative) index would be silently ignored by BOTH this
    # policy and the fused kernel's identical loop — the consistency probe
    # would pass while the user trains a different architecture
    if not linear_set <= set(range(len(sizes) - 1)):
        raise ValueError(
            f"linear_layers {sorted(linear_set)} out of range for "
            f"{len(sizes) - 1} layers (negative indices not supported)"
        )
    # MXU tiles are 128x128; a (fan_in, fan_out) this small occupies a
    # fraction of one tile per individual, so the VPU form wins
    layer_matmul = tuple(
        use_matmul
        if use_matmul is not None
        else (fi >= 64 and fo >= 64)
        for fi, fo in zip(sizes[:-1], sizes[1:])
    )

    def init_params(key: jax.Array):
        params = []
        for k, (fan_in, fan_out) in zip(
            jax.random.split(key, len(sizes) - 1), zip(sizes[:-1], sizes[1:])
        ):
            w = jax.random.normal(k, (fan_in, fan_out)) / jnp.sqrt(fan_in)
            params.append({"w": w, "b": jnp.zeros((fan_out,))})
        return params

    def apply(params, obs: jax.Array) -> jax.Array:
        h = obs
        for i, layer in enumerate(params):
            if layer_matmul[i]:
                h = h @ layer["w"] + layer["b"]
            else:
                # broadcast-multiply-reduce == h @ w, but VPU-friendly
                # under per-individual vmap (see module docstring)
                h = jnp.sum(h[..., :, None] * layer["w"], axis=-2) + layer["b"]
            if i in linear_set:
                pass
            elif i < len(params) - 1:
                h = activation(h)
            elif final_activation is not None:
                h = final_activation(h)
        return h

    return init_params, apply

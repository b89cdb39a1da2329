"""A population that is never a ``(pop, dim)`` array: one shared centre and,
for each member, a low-rank perturbation of every matrix in it.

``Algorithm.ask`` may return a :class:`LowRankPopulation` in place of a batch
of candidates when a member is too large to be a row of one (a model of 1e9
parameters: 64 dense members would be 216 GB). The problem applies the
perturbation inside its forward pass.

The law (mirrored sampling, ``pairs = pop // 2``; the members' order is that
of ``OpenES``: the ``+`` half, then the ``-`` half, so members ``p`` and
``p + pairs`` are the two signs of pair ``p``). For the leaf with index ``l``
in ``jax.tree.leaves(center)``:

- two axes, ``(d_in, d_out)``: ``Z = normal(fold_in(fold_in(noise_key, l), p),
  (d_in + d_out, rank))``, ``A_p = Z[:d_in]``, ``B_p = Z[d_in:]``; member ``i``
  of pair ``p`` uses ``W + sign_i * sigma / sqrt(rank) * A_p @ B_p.T``, so
  ``x @ W_i = x @ W + sign_i * sigma / sqrt(rank) * (x @ A_p) @ B_p.T``: the
  base product is shared by all members;
- three axes, ``(n, d_in, d_out)`` (stacked matrices, the experts of a
  layer): the same for each of the ``n`` matrices, ``Z`` of shape
  ``(n, d_in + d_out, rank)``;
- fewer axes (norm gains, biases): the member uses the centre's.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .struct import PyTreeNode, field

__all__ = ["LowRankPopulation", "lowrank_factors", "tree_factors"]


def lowrank_factors(
    noise_key: jax.Array, leaf_index: int, shape: Tuple[int, ...], pairs: int, rank: int
) -> Optional[Tuple[jax.Array, jax.Array]]:
    """``(A, B)`` of every pair for one leaf, ``(pairs, [n,] d_in, rank)`` and
    ``(pairs, [n,] d_out, rank)``; None for a leaf that is not perturbed."""
    if len(shape) not in (2, 3):
        return None
    d_in, d_out = shape[-2:]
    keys = jax.vmap(lambda p: jax.random.fold_in(jax.random.fold_in(noise_key, leaf_index), p))(
        jnp.arange(pairs)
    )
    z = jax.vmap(lambda k: jax.random.normal(k, shape[:-2] + (d_in + d_out, rank)))(keys)
    return z[..., :d_in, :], z[..., d_in:, :]


def tree_factors(noise_key: jax.Array, center: Any, pairs: int, rank: int) -> Any:
    """The factors of every leaf of ``center``, in a tree of its structure:
    ``(A, B)`` where the leaf is perturbed, ``None`` where it is not."""
    leaves, treedef = jax.tree.flatten(center)
    return jax.tree.unflatten(
        treedef,
        [lowrank_factors(noise_key, l, leaf.shape, pairs, rank) for l, leaf in enumerate(leaves)],
    )


class LowRankPopulation(PyTreeNode):
    """What ``LowRankOpenES.ask`` hands ``evaluate``. No leaf has a
    population axis: ``center`` is shared, ``factors`` has a pair axis.

    ``center``: the centre, its matrices in the dtype the forward pass
    multiplies with. ``factors``: ``tree_factors`` of it (float32).
    ``scale``: ``sigma / sqrt(rank)``. ``signs``: ``(pop,)``, +1 then -1.
    ``noise_key``: what the factors were drawn from.
    """

    has_population_axis = False  # a ``"pop"`` mesh has nothing to shard (StdWorkflow refuses)

    center: Any
    factors: Any
    scale: jax.Array
    signs: jax.Array
    noise_key: jax.Array
    pop_size: int = field(static=True, default=0)

    def materialise(self) -> Any:
        """Every member's dense tree, each leaf with a leading population
        axis: for tests and for problems small enough to hold it."""
        pairs = self.pop_size // 2

        def dense(leaf, fac):
            leaf = jnp.asarray(leaf, jnp.float32)
            if fac is None:
                return jnp.broadcast_to(leaf, (self.pop_size,) + leaf.shape)
            a, b = fac
            delta = self.scale * jnp.einsum("p...ir,p...or->p...io", a, b, precision="highest")
            signs = self.signs.reshape((2, pairs) + (1,) * leaf.ndim)
            return (leaf + signs * delta).reshape((self.pop_size,) + leaf.shape)

        leaves, treedef = jax.tree.flatten(self.center)
        facs = treedef.flatten_up_to(self.factors)
        return jax.tree.unflatten(treedef, [dense(l, f) for l, f in zip(leaves, facs)])

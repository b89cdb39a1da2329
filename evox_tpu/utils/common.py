"""Common utilities (reference: src/evox/utils/common.py).

- ``TreeAndVector``: flatten neural-net param pytrees to flat genomes and
  back (batched), the neuroevolution adapter (reference common.py:157-219).
- fitness shaping: ``rank_based_fitness`` centered ranks (common.py:135-139).
- ``parse_opt_direction``: min/max → ±1 per objective (common.py:222-245).
- pairwise distances + ``dominate_relation`` (common.py:35-107).
- ``min_by``, ``compose`` (common.py:15-24, 110-121).
"""

from __future__ import annotations

import itertools
import math
from typing import Any, Callable, Sequence, Union

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


def leaf_offsets(tree: Any) -> tuple:
    """``(offsets, dim)``: ``tree`` with each leaf replaced by the index of
    its first element in the flat genome ``ravel_pytree`` makes of it
    (leaves in ``jax.tree.flatten`` order, each raveled row-major), and the
    genome's length. Host arithmetic on shapes."""
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [math.prod(jnp.shape(leaf)) for leaf in leaves]
    starts = list(itertools.accumulate(sizes, initial=0))
    return treedef.unflatten(starts[:-1]), starts[-1]


class TreeAndVector:
    """Bidirectional adapter between a parameter pytree and a flat genome.

    ``to_vector``/``to_tree`` convert a single pytree; ``batched_to_tree``/
    ``batched_to_vector`` convert arrays with a leading population axis,
    suitable as workflow candidate transforms. ``offsets`` states where
    each leaf lies in the genome (:func:`leaf_offsets`): a problem that can
    read leaves out of the undecoded batch (``Problem.evaluate_genome``)
    takes the layout from it, not from a guess at the tree's order.
    """

    def __init__(self, dummy_input: Any):
        flat, self._unravel = ravel_pytree(dummy_input)
        self.dim = flat.shape[0]
        self.dtype = flat.dtype
        self.offsets, _ = leaf_offsets(dummy_input)

    def to_vector(self, tree: Any) -> jax.Array:
        flat, _ = ravel_pytree(tree)
        return flat

    def to_tree(self, vector: jax.Array) -> Any:
        return self._unravel(vector)

    def batched_to_vector(self, trees: Any) -> jax.Array:
        return jax.vmap(self.to_vector)(trees)

    def batched_to_tree(self, vectors: jax.Array) -> Any:
        return jax.vmap(self.to_tree)(vectors)

    # pickling: the unravel closure is rebuilt from a dummy tree
    def __getstate__(self):
        zeros = self._unravel(jnp.zeros((self.dim,), dtype=self.dtype))
        return {"dummy": jax.device_get(zeros)}

    def __setstate__(self, state):
        self.__init__(state["dummy"])


def parse_opt_direction(opt_direction: Union[str, Sequence[str]]) -> jax.Array:
    """Map ``"min"``/``"max"`` (or a per-objective list) to a ±1 vector.

    Workflows multiply fitness by this so algorithms always minimize.
    """
    if isinstance(opt_direction, str):
        opt_direction = [opt_direction]
    signs = []
    for d in opt_direction:
        if d == "min":
            signs.append(1.0)
        elif d == "max":
            signs.append(-1.0)
        else:
            raise ValueError(f"opt_direction must be 'min' or 'max', got {d!r}")
    return jnp.asarray(signs, dtype=jnp.float32)


def rank_based_fitness(fitness: jax.Array) -> jax.Array:
    """Centered-rank fitness shaping in [-0.5, 0.5] (OpenAI-ES style)."""
    n = fitness.shape[0]
    ranks = jnp.empty_like(fitness).at[jnp.argsort(fitness)].set(jnp.arange(n, dtype=fitness.dtype))
    return ranks / (n - 1) - 0.5


def standardise(fitness: jax.Array) -> jax.Array:
    """Fitness shaped to zero mean and unit variance over the population (the
    z-score of low-rank ES; a population of equal fitness shapes to zeros)."""
    centred = fitness - jnp.mean(fitness)
    return centred / jnp.maximum(jnp.sqrt(jnp.mean(centred * centred)), 1e-12)


def min_by(values: Sequence[jax.Array], keys: Sequence[jax.Array]):
    """Select the value whose key is minimal across several batches."""
    values = jnp.concatenate([jnp.atleast_1d(v) if v.ndim <= 1 else v for v in values])
    keys = jnp.concatenate([jnp.atleast_1d(k) for k in keys])
    i = jnp.argmin(keys)
    return values[i], keys[i]


def compose(*functions: Callable) -> Callable:
    """Left-to-right function composition: ``compose(f, g)(x) == g(f(x))``."""

    def composed(x):
        for f in functions:
            x = f(x)
        return x

    return composed


# -- pairwise distances ------------------------------------------------------

def pairwise_euclidean_dist(x: jax.Array, y: jax.Array) -> jax.Array:
    """(n, d), (m, d) → (n, m) Euclidean distances, MXU-friendly formulation."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True)
    sq = x2 - 2.0 * (x @ y.T) + y2.T
    return jnp.sqrt(jnp.maximum(sq, 0.0))


def pairwise_manhattan_dist(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def pairwise_chebyshev_dist(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def cos_dist(x: jax.Array, y: jax.Array) -> jax.Array:
    """(n, d), (m, d) → (n, m) cosine similarity (matmul on the MXU)."""
    xn = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    yn = y / jnp.linalg.norm(y, axis=1, keepdims=True)
    return xn @ yn.T


def dominate_relation(x: jax.Array, y: jax.Array) -> jax.Array:
    """Boolean (n, m) matrix: ``out[i, j]`` iff ``x[i]`` Pareto-dominates ``y[j]``.

    Minimization convention (reference: utils/common.py:94-97). Formulated
    as a static loop over the (small) objective axis so every compare is an
    (n, n) pass with the population in the TPU lane dimension — the
    broadcast-compare form puts m in the lanes and wastes most of them.
    """
    le = jnp.ones((x.shape[0], y.shape[0]), dtype=jnp.bool_)
    lt = jnp.zeros((x.shape[0], y.shape[0]), dtype=jnp.bool_)
    for k in range(x.shape[1]):
        xk = x[:, k][:, None]
        yk = y[:, k][None, :]
        le &= xk <= yk
        lt |= xk < yk
    return le & lt


def new_key(key: jax.Array):
    """Split a key, returning (carry_key, use_key)."""
    k1, k2 = jax.random.split(key)
    return k1, k2


def frames2gif(frames, save_path: str, duration: float = 0.1) -> None:
    """Write a list of (H, W, 3) uint8 frames to an animated GIF (reference
    utils/common.py:248-261). Uses imageio when present, else PIL."""
    import numpy as _np

    arrs = [_np.asarray(f, dtype=_np.uint8) for f in frames]
    try:
        import imageio

        with imageio.get_writer(save_path, mode="I", duration=duration) as w:
            for a in arrs:
                w.append_data(a)
        return
    except ImportError:
        pass
    from PIL import Image

    imgs = [Image.fromarray(a) for a in arrs]
    imgs[0].save(
        save_path,
        save_all=True,
        append_images=imgs[1:],
        duration=int(duration * 1000),
        loop=0,
    )

"""Basic parent-selection operators (reference:
src/evox/operators/selection/{tournament,roulette_wheel,topk_fit,
uniform_random,find_pbest}.py). All are pure functions of (key, pop, fitness).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ...core.instrument import MATING, scope
from ...kernels.topk import partial_topk


@scope(MATING)
def tournament(
    key: jax.Array,
    pop: jax.Array,
    fitness: jax.Array,
    n_round: Optional[int] = None,
    tournament_size: int = 2,
    best_fn: Callable = jnp.argmin,
) -> jax.Array:
    """Single-fitness tournament selection → selected population.

    Draws ``n_round`` (default: pop size) tournaments of ``tournament_size``
    uniformly-random contestants; winner by ``best_fn`` over fitness.
    """
    n = pop.shape[0]
    n_round = n if n_round is None else n_round
    contestants = jax.random.randint(key, (n_round, tournament_size), 0, n)
    winner_col = jax.vmap(lambda c: best_fn(fitness[c]))(contestants)
    winners = contestants[jnp.arange(n_round), winner_col]
    return pop[winners]


@scope(MATING)
def tournament_multifit(
    key: jax.Array,
    pop: jax.Array,
    fitnesses: jax.Array,
    n_round: Optional[int] = None,
    tournament_size: int = 2,
) -> jax.Array:
    """Tournament with lexicographic multi-key fitness ``(n, k)``: winner is
    the lexicographically smallest fitness row (reference tournament.py
    multi-fitness form)."""
    n = pop.shape[0]
    n_round = n if n_round is None else n_round
    contestants = jax.random.randint(key, (n_round, tournament_size), 0, n)

    def pick(c):
        fs = fitnesses[c]  # (t, k)
        order = jnp.lexsort(tuple(fs[:, j] for j in reversed(range(fs.shape[1]))))
        return c[order[0]]

    winners = jax.vmap(pick)(contestants)
    return pop[winners]


def roulette_wheel(
    key: jax.Array,
    pop: jax.Array,
    fitness: jax.Array,
    n: Optional[int] = None,
) -> jax.Array:
    """Fitness-proportionate selection (minimization: lower fitness → higher
    probability, via max-shift inversion as in reference roulette_wheel.py:7).
    """
    num = pop.shape[0] if n is None else n
    weight = jnp.max(fitness) - fitness + 1e-9
    idx = jax.random.choice(key, pop.shape[0], (num,), p=weight / jnp.sum(weight))
    return pop[idx]


def topk_fit(
    pop: jax.Array,
    fitness: jax.Array,
    topk: int,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """Keep the ``topk`` fittest (reference topk_fit.py:41).

    ``use_kernel``: route through the blockwise Pallas partial-selection
    kernel (kernels/topk.py) instead of a full ``lax.top_k`` over ``n``
    — identical output (values, order, tie law). ``None`` = backend
    default (currently off everywhere; see kernels/topk.py)."""
    fit, idx = partial_topk(
        fitness, topk, use_kernel=use_kernel, interpret=interpret
    )
    return pop[idx], fit


def uniform_rand(key: jax.Array, pop: jax.Array, n: int) -> jax.Array:
    """Select ``n`` individuals uniformly with replacement (uniform_random.py:18)."""
    idx = jax.random.randint(key, (n,), 0, pop.shape[0])
    return pop[idx]


def select_rand_pbest(
    key: jax.Array,
    percent: float,
    pop: jax.Array,
    fitness: jax.Array,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """For each individual, pick a random member of the best ``percent``
    fraction of the population (DE current-to-pbest; reference find_pbest.py).

    The best-``p%`` set is a textbook partial selection (``top <<
    n``) — ``use_kernel`` routes it through kernels/topk.py, identical
    result (``None`` = backend default, currently off)."""
    n = pop.shape[0]
    top = max(1, int(n * percent))
    _, best_idx = partial_topk(
        fitness, top, use_kernel=use_kernel, interpret=interpret
    )
    choice = jax.random.randint(key, (n,), 0, top)
    return pop[best_idx[choice]]

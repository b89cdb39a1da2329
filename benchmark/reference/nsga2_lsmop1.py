"""Plain reference of the ``nsga2_lsmop1`` configuration.

NSGA-II (Deb et al. 2002) on LSMOP1 (Cheng et al. 2017), in straightforward
``jax.numpy`` at float32: binary tournament on (rank, crowding), simulated
binary crossover and polynomial mutation (both with index 20), parents and
offspring merged, and the next population the best by (rank, crowding on the
front that is cut). It imports nothing of the program and draws everything
from the seed.

The Pareto rank of a point is one more than the highest rank among the
points that dominate it. Sorted lexicographically, every dominator of a point
comes before it, so the ranks follow from one sweep over the sorted points in
blocks: no dominance matrix and no peeling of fronts.

What it shares with the program is the semantics, and the order in which keys
are split, because the random draws are part of the semantics:

- workflow: ``k_algo, _ = split(key(seed))``; NSGA-II: ``key, k = split(
  k_algo)``, population ``uniform(k, (pop, d)) * (ub - lb) + lb``;
- generation 1 evaluates the parents, ranks all of them and takes the
  crowding distance over all of them (not front by front);
- a later generation: ``key, k_mate, k_var = split(key, 3)``; contestants
  ``randint(k_mate, (pop, 2), 0, pop)``, the winner the lexicographically
  smaller (rank, -crowding), the first on a tie; ``k1, k2 = split(k_var)``;
  crossover over consecutive pairs with ``uniform(k1, (pop/2, d))``;
  ``k3, k4 = split(k2)``; a gene mutates where ``uniform(k3) < 1/d`` by
  ``uniform(k4)``; offspring clipped to the bounds;
- survivors in the order (rank, crowding descending on the cut front, index);
  the fronts above the cut keep index order. Their crowding for the next
  tournament is again taken over all survivors at once.

Departures from Deb et al., which are the program's and kept here: crowding
for mating over the whole population, not front by front; a span of an
objective under 1e-12 counts as 1e-12.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NK = 5  # subcomponents in each variable group (the suite's n_k)


def _key(seed: int):
    seed = int(seed)
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)


def _groups(d: int, m: int):
    """Lengths of the suite's variable groups (its chaos series, eq. 6)."""
    c = [3.8 * 0.1 * (1 - 0.1)]
    for _ in range(1, m):
        c.append(3.8 * c[-1] * (1 - c[-1]))
    c = np.asarray(c, np.float32)
    return [int(x) for x in np.floor(c / np.sum(c) * np.float32(d - (m - 1)) / NK)]


def lsmop1(x, m: int, dtype=jnp.float32):
    """LSMOP1: linear linkage, sphere inner function, linear front."""
    x = x.astype(dtype)
    n, d = x.shape
    i = jnp.arange(m, d + 1, dtype=jnp.float32).astype(dtype)
    xs = (1.0 + i / d) * x[:, m - 1 :] - 10.0 * x[:, :1]
    g, at = [], 0
    for length in _groups(d, m):
        part = xs[:, at : at + NK * length].reshape(n, NK, length)
        g.append(jnp.sum(jnp.sum(part * part, axis=-1) / max(length, 1), axis=-1) / NK)
        at += NK * length
    g = jnp.stack(g, axis=1)
    xf = x[:, : m - 1]
    ones = jnp.ones((n, 1), dtype)
    cum = jnp.cumprod(jnp.concatenate([ones, xf], axis=1), axis=1)[:, ::-1]
    rev = jnp.concatenate([ones, 1.0 - xf[:, ::-1]], axis=1)
    return ((1.0 + g) * cum * rev).astype(jnp.float32)


def _dominates(a, b):
    """``a (A, m)`` dominates ``b (B, m)`` -> ``(A, B)`` bool (minimisation)."""
    le = jnp.all(a[:, None, :] <= b[None, :, :], axis=-1)
    lt = jnp.any(a[:, None, :] < b[None, :, :], axis=-1)
    return le & lt


def pareto_ranks(fitness, block: int = 1024):
    """Rank 0 for the non-dominated, else 1 + the highest rank of a dominator."""
    n, m = fitness.shape
    order = jnp.lexsort(tuple(fitness[:, j] for j in reversed(range(m))))
    pad = (-n) % block
    f = jnp.concatenate([fitness[order], jnp.full((pad, m), jnp.inf)], axis=0)
    total = n + pad

    def one_block(b, rank):
        start = b * block
        mine = jax.lax.dynamic_slice_in_dim(f, start, block, axis=0)
        earlier = jnp.arange(total) < start
        dom = _dominates(f, mine) & earlier[:, None]
        floor = jnp.max(jnp.where(dom, rank[:, None] + 1, 0), axis=0)
        inside = _dominates(mine, mine)

        def settle(carry):
            r, _ = carry
            new = jnp.maximum(floor, jnp.max(jnp.where(inside, r[:, None] + 1, 0), axis=0))
            return new, jnp.any(new != r)

        r, _ = jax.lax.while_loop(lambda c: c[1], settle, (floor, jnp.bool_(True)))
        return jax.lax.dynamic_update_slice_in_dim(rank, r, start, axis=0)

    rank = jax.lax.fori_loop(0, total // block, one_block, jnp.zeros((total,), jnp.int32))
    return jnp.zeros((n,), jnp.int32).at[order].set(rank[:n])


def crowding(fitness, mask):
    """Crowding distance over the rows of ``mask``; -inf outside it, +inf at
    the ends of each objective."""
    n, _ = fitness.shape
    count = jnp.sum(mask)
    pos = jnp.arange(n)

    def one(fv):
        fv = jnp.where(mask, fv, jnp.inf)
        order = jnp.argsort(fv)
        s = fv[order]
        last = jnp.maximum(count - 1, 0)
        span = jnp.maximum(s[last] - s[0], 1e-12)
        inner = (s[2:] - s[:-2]) / span
        dist = jnp.concatenate([jnp.full((1,), jnp.inf), inner, jnp.full((1,), jnp.inf)])
        dist = jnp.where(pos == last, jnp.inf, dist)
        dist = jnp.where(pos >= count, -jnp.inf, dist)
        dist = jnp.nan_to_num(dist, nan=0.0, posinf=jnp.inf, neginf=-jnp.inf)
        return jnp.zeros((n,)).at[order].set(dist)

    return jnp.sum(jax.vmap(one)(fitness.T), axis=0)


def _offspring(key, population, rank, crowd, lb, ub, dtype):
    n, d = population.shape
    key, k_mate, k_var = jax.random.split(key, 3)
    pairs = jax.random.randint(k_mate, (n, 2), 0, n)
    a, b = pairs[:, 0], pairs[:, 1]
    b_wins = (rank[b] < rank[a]) | ((rank[b] == rank[a]) & (-crowd[b] < -crowd[a]))
    pool = population[jnp.where(b_wins, b, a)].astype(dtype)
    k1, k2 = jax.random.split(k_var)
    p1, p2 = pool[0::2], pool[1::2]
    u = jax.random.uniform(k1, (n // 2, d)).astype(dtype)
    beta = jnp.where(u <= 0.5, (2.0 * u) ** (1.0 / 21.0), (1.0 / (2.0 * (1.0 - u))) ** (1.0 / 21.0))
    c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
    c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
    x = jnp.stack([c1, c2], axis=1).reshape(n, d)
    k3, k4 = jax.random.split(k2)
    site = jax.random.uniform(k3, (n, d)) < (1.0 / d)
    u = jax.random.uniform(k4, (n, d)).astype(dtype)
    lo, hi = lb.astype(dtype), ub.astype(dtype)
    span = hi - lo
    down, up = (x - lo) / span, (hi - x) / span
    left = (2.0 * u + (1.0 - 2.0 * u) * (1.0 - down) ** 21.0) ** (1.0 / 21.0) - 1.0
    right = 1.0 - (2.0 * (1.0 - u) + 2.0 * (u - 0.5) * (1.0 - up) ** 21.0) ** (1.0 / 21.0)
    moved = x + jnp.where(u <= 0.5, left, right) * span
    return key, jnp.clip(jnp.where(site, moved, x), lo, hi).astype(jnp.float32)


def follow(config: dict, traffic: dict, seed: int, generations: list, precision: str = "float32",
           program: list = ()) -> list:
    """From the seed through the generations asked for. One snapshot for each:
    ``{"generation", "population", "fitness", "rank"}``.
    ``precision="bfloat16"`` is the control: evaluation and variation in
    bfloat16, selection on what they give.

    ``program``: the snapshots under comparison. Where it holds one of the
    generation before, a generation starts from that snapshot's population,
    in its order, and from nothing else of it: the objectives are evaluated
    anew here, and rank and crowding taken from them. The comparison is then
    of one chunk at a time, each from rows that were themselves compared.
    Followed from the seed alone, one
    survivor that differs shifts every later row's index, the tournament's
    draws then pick other parents, and the third generation differs in a
    tenth of its rows (PERF.md, Findings PR 25). The keys follow from the
    seed alone either way."""
    dtype = jnp.dtype(precision)
    d, m, pop = int(config["d"]), int(config["m"]), int(traffic["pop"])
    lb = jnp.zeros((d,))
    ub = jnp.ones((d,)).at[m - 1 :].set(10.0)
    k_algo, _ = jax.random.split(_key(seed))
    key, k = jax.random.split(k_algo)

    @jax.jit
    def first(k):
        population = jax.random.uniform(k, (pop, d)) * (ub - lb) + lb
        fitness = lsmop1(population, m, dtype)
        return population, fitness, pareto_ranks(fitness), crowding(fitness, jnp.ones((pop,), bool))

    @jax.jit
    def generation(key, population, fitness, rank, crowd):
        key, off = _offspring(key, population, rank, crowd, lb, ub, dtype)
        merged = jnp.concatenate([population, off], axis=0)
        merged_fit = jnp.concatenate([fitness, lsmop1(off, m, dtype)], axis=0)
        ranks = pareto_ranks(merged_fit)
        cut = jnp.sort(ranks)[pop - 1]
        order = jnp.lexsort((-crowding(merged_fit, ranks == cut), ranks))[:pop]
        fit = merged_fit[order]
        return key, merged[order], fit, ranks[order], crowding(fit, jnp.ones((pop,), bool))

    @jax.jit
    def adopt(population):
        fitness = lsmop1(population, m, dtype)
        return fitness, pareto_ranks(fitness), crowding(fitness, jnp.ones((pop,), bool))

    population, fitness, rank, crowd = first(k)
    snaps = []
    given = {int(s["generation"]): s for s in program}
    for step in range(1, max(generations) + 1):
        if step - 1 in given:
            population = jnp.asarray(given[step - 1]["population"], jnp.float32)
            fitness, rank, crowd = adopt(population)
        if step > 1:
            key, population, fitness, rank, crowd = generation(key, population, fitness, rank, crowd)
        if step in generations:
            snaps.append(
                {
                    "generation": step,
                    "population": np.asarray(population),
                    "fitness": np.asarray(fitness),
                    "rank": np.asarray(rank),
                }
            )
    return snaps


def numbers(config: dict, program: list, reference: list) -> dict:
    """The numbers compared, for each step followed. Rows are matched through
    four fixed random projections (a row that differs in its last digits still
    finds its partner; two different rows never meet).

    - ``population_mismatch``: share of the program's rows with no partner
      among the reference's: a wrong offspring, or a wrong survivor.
    - ``fitness_err``: over the matched rows, the largest gap between the
      program's objectives and the reference's, as a share of 1 + the
      reference's size.
    - ``rank_mismatch``: share of the matched rows whose Pareto rank differs.
    """
    from scipy.spatial import cKDTree

    out = {}
    d = int(config["d"])
    proj = np.random.default_rng(20170217).standard_normal((d, 4)) / np.sqrt(d)
    for k, (got, want) in enumerate(zip(program, reference), 1):
        # a row that is not finite finds no partner
        p_got = np.nan_to_num(np.asarray(got["population"], np.float64) @ proj, nan=1e9, posinf=1e9, neginf=-1e9)
        p_want = np.nan_to_num(np.asarray(want["population"], np.float64) @ proj, nan=-1e9, posinf=-1e9, neginf=1e9)
        dist, idx = cKDTree(p_want).query(p_got, k=1)
        hit = dist < 1e-3
        out[f"step{k}_population_mismatch"] = float(1.0 - np.mean(hit))
        f_got = np.asarray(got["fitness"], np.float64)[hit]
        f_want = np.asarray(want["fitness"], np.float64)[idx[hit]]
        gap = np.nan_to_num(np.abs(f_got - f_want) / (1.0 + np.abs(f_want)), nan=1.0, posinf=1.0)
        out[f"step{k}_fitness_err"] = float(np.max(gap)) if gap.size else 1.0
        r_got, r_want = np.asarray(got["rank"])[hit], np.asarray(want["rank"])[idx[hit]]
        out[f"step{k}_rank_mismatch"] = float(np.mean(r_got != r_want)) if r_got.size else 1.0
        out[f"step{k}_generation_off"] = float(abs(int(got["generation"]) - int(want["generation"])))
    return out

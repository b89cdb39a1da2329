"""Faults planted under the timed path, to show that ``correct`` comes out
false: used by ``benchmark/control.py`` on the chip and by the tests under
``tests/benchmark_checks/``. The benchmark's own runs never plant one.

Each takes what a builder built and returns it broken:

- ``unchanged``: a step that returns its state as it got it;
- ``half_batch``: the second half of the batch left out of evaluation, the
  mean of the rest put in its place;
- ``altered``: the answers of evaluation altered where they are produced
  (the first objective of every 64th member halved).

"The exchange between chips left out" has no planting here: no cell on a
mesh stands (PERF.md, Open questions, row 1, says what a planting has to be
and which number has to catch it).
"""

from __future__ import annotations


class _Problem:
    """The program's problem with ``evaluate`` post-processed."""

    def __init__(self, inner, after):
        self._inner, self._after = inner, after

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def evaluate(self, state, pop):
        fitness, state = self._inner.evaluate(state, pop)
        return self._after(fitness), state


def unchanged(built):
    built.wf.run = lambda state, n, **_: state
    return built


def half_batch(built):
    import jax.numpy as jnp

    def after(fitness):
        n = fitness.shape[0]
        rest = jnp.mean(fitness[: n // 2], axis=0, keepdims=True)
        keep = (jnp.arange(n) < n // 2).reshape((n,) + (1,) * (fitness.ndim - 1))
        return jnp.where(keep, fitness, rest)

    built.wf.problem = _Problem(built.wf.problem, after)
    return built


def altered(built):
    import jax.numpy as jnp

    def after(fitness):
        n = fitness.shape[0]
        hit = jnp.arange(n) % 64 == 0
        if fitness.ndim == 1:
            return jnp.where(hit, 0.5 * fitness, fitness)
        return fitness.at[:, 0].set(jnp.where(hit, 0.5 * fitness[:, 0], fitness[:, 0]))

    built.wf.problem = _Problem(built.wf.problem, after)
    return built


FAULTS = {
    "unchanged": unchanged,
    "half_batch": half_batch,
    "altered": altered,
}

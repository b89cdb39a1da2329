"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/experts``:
the held experts' grouped products (gate, up, down of each block of sorted rows), without the low-rank terms
(benchmark/lib/lm_scopes.py says how nested scopes are told apart)."""

from benchmark.lib import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "experts")

"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/router``:
scores, top-k, the sort of assignments by expert, tokens gathered and results put back by token, without the experts' products
(benchmark/lib/lm_scopes.py says how nested scopes are told apart)."""

from benchmark.lib import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "router")

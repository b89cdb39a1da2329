"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/head_loss``:
the final norm, the head's product a pair at a time, and the log-likelihood, without the low-rank terms
(benchmark/lib/lm_scopes.py says how nested scopes are told apart)."""

from benchmark.lib import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "head_loss")

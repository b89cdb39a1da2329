"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/attention``:
norms, projections, RoPE, scores, softmax and output of every layer's latent attention, without the low-rank terms
(benchmark/lib/lm_scopes.py says how nested scopes are told apart)."""

from benchmark.lib import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "attention")

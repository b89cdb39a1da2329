"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/conv``:
every gated short convolution layer's norm, input projection, gates, taps and output projection,
without the low-rank terms (benchmark/lib/lm_lfm2_scopes.py)."""

from benchmark.lib import lm_lfm2_scopes


def read(ctx):
    return lm_lfm2_scopes.part_ms(ctx, "conv")

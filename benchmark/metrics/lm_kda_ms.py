"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/kda``:
every KDA layer's norm, projections, short convolutions, L2 norms, decay and beta, output norm, gate and output,
without the recurrence (``lm/kda_scan``) and the low-rank terms (benchmark/lib/lm_hybrid_scopes.py)."""

from benchmark.lib import lm_hybrid_scopes


def read(ctx):
    return lm_hybrid_scopes.part_ms(ctx, "kda")

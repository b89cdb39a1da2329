"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/kda/lm/kda_scan``:
the delta rule's recurrence over the row in every KDA layer, kernel or XLA body, with the running sums of g
it starts from (benchmark/lib/lm_hybrid_scopes.py)."""

from benchmark.lib import lm_hybrid_scopes


def read(ctx):
    return lm_hybrid_scopes.part_ms(ctx, "kda_scan")

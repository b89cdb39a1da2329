"""Layer: evaluate. Device time a generation under ``evox.evaluate/lm/lowrank``:
every member's sign * scale * (x A) B^T, wherever it is added: what the perturbation costs the forward pass
(benchmark/lib/lm_scopes.py says how nested scopes are told apart)."""

from benchmark.lib import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "lowrank")

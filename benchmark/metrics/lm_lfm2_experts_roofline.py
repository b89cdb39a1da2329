"""Layer: kernels. The held experts' grouped products' share of their
roofline in a model of the ``lfm2_moe`` family: the least time the chip could
take for them, ``max(flops / peak flops, bytes / peak bandwidth)`` from shapes
and traffic alone (benchmark/lib/work_lm_lfm2.py, at the expected 4 * 16 / 64
held choices a token; ``lm_experts_roofline`` reads the ``deepseek_v3``
family's keys), over the device time under ``lm/experts``. Operations bound
it. A run that routes more or fewer rows to held experts than expected does
more or less work than is counted."""

from benchmark.lib import lm_scopes, peaks, work_lm_lfm2


def read(ctx):
    ns = lm_scopes.part_ns(ctx, "experts")
    if ns is None or "layer_types" not in ctx.config:
        return None
    least_s = work_lm_lfm2.experts_least_seconds(
        ctx.config, ctx.traffic, ctx.window["evals"] / ctx.chips, peaks.peaks(ctx.device_kind)
    )
    return 100.0 * least_s / (ns / 1e9)

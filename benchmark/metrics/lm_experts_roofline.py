"""Layer: kernels. The held experts' grouped products' share of their
roofline: the least time the chip could take for them, ``max(flops / peak
flops, bytes / peak bandwidth)`` from shapes and traffic alone
(benchmark/lib/work_lm.py, at the expected 1.5 held choices a token), over
the device time under ``lm/experts``. Operations bound it. A run that routes
more or fewer rows to held experts than expected does more or less work than
is counted."""

from benchmark.lib import lm_scopes, peaks, work_lm


def read(ctx):
    ns = lm_scopes.part_ns(ctx, "experts")
    if ns is None or "moe_intermediate_size" not in ctx.config:
        return None
    least_s = work_lm.experts_least_seconds(
        ctx.config, ctx.traffic, ctx.window["evals"] / ctx.chips, peaks.peaks(ctx.device_kind)
    )
    return 100.0 * least_s / (ns / 1e9)

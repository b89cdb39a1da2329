"""Layer: kernels. The KDA recurrence's share of its roofline: the least time
the chip could take for it, ``max(flops / peak flops, bytes / peak
bandwidth)`` from shapes and traffic alone (benchmark/lib/work_lm_hybrid.py: a
token, head and KDA layer 7 * 128 * 128 operations; q, k, v, o once in the
operands' dtype, g and beta once in float32), over the device time under
``lm/kda_scan``. Bandwidth bounds it. The counts are of the semantics: what a
chunked form computes besides (a chunk's triangular system, its running sums)
is time and not work."""

from benchmark.lib import lm_hybrid_scopes, peaks, work_lm_hybrid


def read(ctx):
    ns = lm_hybrid_scopes.part_ns(ctx, "kda_scan")
    if ns is None or "linear_attn_config" not in ctx.config:
        return None
    least_s = work_lm_hybrid.kda_scan_least_seconds(
        ctx.config, ctx.traffic, ctx.window["evals"] / ctx.chips, peaks.peaks(ctx.device_kind)
    )
    return 100.0 * least_s / (ns / 1e9)

"""Layer: kernels. The grouped-query attention kernel's share of its
roofline: the least time the chip could take for the attention layers'
scores, softmax and ``p . v``, ``max(flops / peak flops, bytes / peak
bandwidth)`` from shapes and traffic alone (benchmark/lib/work_lm_lfm2.py: a
token, query head and attended key ``2 * (64 + 64)`` operations at the keys a
query is expected to attend in a packed row, the row's cut of its last
document counted; q, k, v in and o out once in the operands' dtype), over the device time of the kernel's own events. Operations bound it.
The counts are of the semantics: the same whatever key blocks a kernel visits
or skips, and a contraction padded to a lane tile is time and not work."""

from benchmark.lib import lm_lfm2_scopes, peaks, work_lm_lfm2


def read(ctx):
    ns = lm_lfm2_scopes.gqa_kernel_ns(ctx)
    if ns is None or "layer_types" not in ctx.config:
        return None
    least_s = work_lm_lfm2.gqa_kernel_least_seconds(
        ctx.config, ctx.traffic, ctx.window["evals"] / ctx.chips, peaks.peaks(ctx.device_kind)
    )
    return 100.0 * least_s / (ns / 1e9)

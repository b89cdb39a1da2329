"""Layer: whole step. The member model's operations the traced stretch
completed per second, over the chips' peak: the window's evaluations times the
operations of one member's forward pass (benchmark/lib/work_lm.py: shapes and
traffic alone, 1.5 held choices a token, attention at the documents' expected
lengths), over the length of the traced stretch and chips times peak FLOP/s.
The search's ask and tell, the low-rank terms and every other overhead count
as time and not as work."""

from benchmark.lib import peaks, work_lm


def read(ctx):
    if "moe_intermediate_size" not in ctx.config or not ctx.events:
        return None
    flops = work_lm.lm_flops_per_eval(ctx.config, ctx.traffic) * ctx.window["evals"]
    per_s = flops / (ctx.stretch_ns / 1e9)
    return 100.0 * per_s / (ctx.chips * peaks.peaks(ctx.device_kind)["flops_per_s"])

"""Layer: whole step. The member model's operations the traced stretch
completed per second, over the chips' peak, for a model of two layer kinds:
the window's evaluations times the operations of one member's forward pass
(benchmark/lib/work_lm_hybrid.py: KDA and MLA layers each by their own count,
by the configuration's pattern; scores at the documents' expected lengths;
held experts at 8 * 16 / 256 choices a token), over the length of the traced
stretch and chips times peak FLOP/s. The search's ask and tell, the low-rank
terms and every other overhead count as time and not as work."""

from benchmark.lib import peaks, work_lm_hybrid


def read(ctx):
    if "linear_attn_config" not in ctx.config or not ctx.events:
        return None
    flops = work_lm_hybrid.lm_flops_per_eval(ctx.config, ctx.traffic) * ctx.window["evals"]
    per_s = flops / (ctx.stretch_ns / 1e9)
    return 100.0 * per_s / (ctx.chips * peaks.peaks(ctx.device_kind)["flops_per_s"])

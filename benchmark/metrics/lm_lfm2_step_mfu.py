"""Layer: whole step. The member model's operations the traced stretch
completed per second, over the chips' peak, for a model of the ``lfm2_moe``
family: the window's evaluations times the operations of one member's forward
pass (benchmark/lib/work_lm_lfm2.py: convolution and attention layers each by
their own count, by the configuration's ``layer_types``; scores at the keys a
query is expected to attend in a packed row, its cut counted; held experts at 4 * 16 / 64 choices a token),
over the length of the traced stretch and chips times peak FLOP/s. The
search's ask and tell, the low-rank terms and every other overhead count as
time and not as work."""

from benchmark.lib import peaks, work_lm_lfm2


def read(ctx):
    if "layer_types" not in ctx.config or not ctx.events:
        return None
    flops = work_lm_lfm2.lm_flops_per_eval(ctx.config, ctx.traffic) * ctx.window["evals"]
    per_s = flops / (ctx.stretch_ns / 1e9)
    return 100.0 * per_s / (ctx.chips * peaks.peaks(ctx.device_kind)["flops_per_s"])

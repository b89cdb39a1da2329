"""Layer: whole step. The member model's flops the traced stretch completed
per second, over the chips' peak: the window's evaluations times the
configuration's flops an evaluation (benchmark/lib/work.py), over the length
of the traced stretch (the first chunk's start to the last chunk's end, on
the trace's clock) and chips times peak FLOP/s. It bounds a claim when a
later PR takes the kernel off the path and the kernel's roofline falls
silent."""

from benchmark.lib import peaks, work


def read(ctx):
    if "policy_sizes" not in ctx.config or not ctx.events:
        return None
    flops = work.rollout_flops_per_eval(ctx.config) * ctx.window["evals"] / (ctx.stretch_ns / 1e9)
    return 100.0 * flops / (ctx.chips * peaks.peaks(ctx.device_kind)["flops_per_s"])

"""Layer: whole step. For a configuration with no member model: the bytes a
generation's semantics force through HBM whatever implements it
(benchmark/lib/work.py: parents read, offspring written, offspring read by
evaluate, survivors written) at the peak bandwidth, over the traced
stretch's time a generation (the first chunk's start to the last chunk's
end, on the trace's clock). A floor, so it reads low; it is the share of the
whole step that bounds a claim in such a cell."""

from benchmark.lib import peaks, work


def read(ctx):
    if "d" not in ctx.config or not ctx.events:
        return None
    least_s = work.generation_hbm_bytes(ctx.config, int(ctx.traffic["pop"])) / (
        ctx.chips * peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (ctx.stretch_ns / 1e9 / ctx.window["generations"])

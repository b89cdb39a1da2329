"""Layer: kernels. The rollout kernel's share of its roofline on the fullest
device: the least time the chip could take for the rollouts that device ran,
``max(flops / peak flops, bytes / peak bandwidth)`` from the configuration's
shapes alone (benchmark/lib/work.py), over the device time of the kernel's
events in the trace (``kernel_event_pattern`` of the configuration).

Bytes bound it: 83,780 B a member is 102 ns at 819 GB/s, against 50 ns for
its 9.82 MFLOP at 197 TFLOP/s. The work is counted at the full horizon for
every member; a tile that leaves early does less, so the share overstates by
what early exits save."""

from benchmark.lib import peaks, trace as tr, work


def kernel_ns(ctx):
    """Device time of the kernel's events on the fullest device, or None."""
    pattern = ctx.config.get("kernel_event_pattern")
    if not pattern:
        return None
    return tr.matching_ns(ctx.events, pattern) or None


def read(ctx):
    ns = kernel_ns(ctx)
    if ns is None:
        return None
    peak = peaks.peaks(ctx.device_kind)
    evals = ctx.window["evals"] / ctx.chips  # what one device ran
    least_s = evals * max(
        work.rollout_flops_per_eval(ctx.config) / peak["flops_per_s"],
        work.rollout_bytes_per_eval(ctx.config) / peak["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ns / 1e9)

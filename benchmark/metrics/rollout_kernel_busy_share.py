"""Layer: kernels. The rollout kernel's device time as a share of the fullest
device's busy time in the traced stretch."""

from benchmark.metrics.rollout_kernel_roofline import kernel_ns


def read(ctx):
    ns = kernel_ns(ctx)
    return None if ns is None else 100.0 * ns / ctx.busy_ns

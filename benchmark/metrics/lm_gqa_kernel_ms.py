"""Layer: kernels. Device time a generation of the events the
``gqa_flash_attention`` kernel's ``pallas_call`` names: the grouped-query
layers' scores, softmax and ``p . v`` (benchmark/lib/lm_lfm2_scopes.py). A
part of ``lm_attention_ms``, which also holds the layer's norms, projections
and RoPE."""

from benchmark.lib import lm_lfm2_scopes


def read(ctx):
    ns = lm_lfm2_scopes.gqa_kernel_ns(ctx)
    if ns is None or not ctx.window["generations"]:
        return None
    return ns / 1e6 / ctx.window["generations"]

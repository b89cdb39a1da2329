"""Pallas TPU kernels for hot operators.

XLA's default lowerings handle most of the framework well; these kernels
cover the cases where they don't. Each kernel ships with a pure-XLA
fallback of identical semantics, and is unit-tested against the fallback
in interpret mode so the CPU mesh CI exercises the kernel body too. Where
measurement shows the fallback already at the hardware roofline (see each
kernel's docstring), the fallback stays the default.

- ``dominance``: the bit-packed Pareto-dominance build; ``packed_dominance``
  chooses the ``dominance_pack`` kernel by backend (the TPU's), the XLA
  build (``packed_dominance_reference``) elsewhere.
- ``topk``: blockwise partial top-k selection.
- ``rollout``, ``rollout_mlp``: whole-episode policy rollouts (small and
  VMEM-resident-weights policies).
- ``flash_attention``: forward flash attention for the language model's
  MLA heads (keys 192 wide, values 128) over a packed row of documents; its
  fallback and reference is ``problems/lm/model.py`` ``attend_plain``, and
  the caller chooses by platform and shape (``flash_block_sizes``).
- ``gqa_flash_attention``: the same for grouped-query heads half a lane tile
  wide (64): a grid cell takes a pair of key-value heads, one tile of ``k``
  and of ``v`` fetched once for the eight query heads that read them; its
  fallback and reference is ``attend_gqa_plain``; the caller chooses by
  platform and shape (``gqa_block_sizes``).
- ``kda_scan``: Kimi Delta Attention's gated delta rule over a packed row,
  chunkwise and exact, the state in VMEM across the row's chunks; its
  fallback is the same chunk arithmetic in XLA (``kda_scan_chunked``), its
  oracle the token-by-token recurrence (``kda_scan_reference``); the caller
  chooses by platform and head width.
- ``kda_conv``: what lies between KDA's projections and that scan, for one of
  ``q``, ``k``, ``v``: the short convolution, SiLU and the head's L2 norm as
  one pass that reads the projection once and writes the scan's operand once;
  its fallback and reference is ``problems/lm/model.py`` ``short_conv`` with
  the norm lines of ``kda``; the caller chooses by the same rule.
"""

from .dominance import packed_dominance, packed_dominance_reference
from .flash_attention import flash_attention, flash_block_bounds, flash_block_sizes
from .gqa_flash_attention import gqa_block_sizes, gqa_flash_attention
from .kda_conv import kda_conv
from .kda_scan import kda_scan, kda_scan_chunked, kda_scan_reference
from .topk import default_use_kernel, partial_topk, partial_topk_reference
from .rollout import (
    SoAEnv,
    acrobot_soa,
    cartpole_soa,
    fused_rollout,
    mountain_car_soa,
    pendulum_soa,
)
from .rollout_mlp import (
    PlaneEnv,
    chain_walker_planes,
    fused_mlp_rollout,
    fused_rollout_analysis,
)

__all__ = [
    "packed_dominance",
    "packed_dominance_reference",
    "flash_attention",
    "flash_block_bounds",
    "flash_block_sizes",
    "gqa_block_sizes",
    "gqa_flash_attention",
    "kda_conv",
    "kda_scan",
    "kda_scan_chunked",
    "kda_scan_reference",
    "default_use_kernel",
    "partial_topk",
    "partial_topk_reference",
    "SoAEnv",
    "acrobot_soa",
    "cartpole_soa",
    "fused_rollout",
    "mountain_car_soa",
    "pendulum_soa",
    "PlaneEnv",
    "chain_walker_planes",
    "fused_mlp_rollout",
    "fused_rollout_analysis",
]

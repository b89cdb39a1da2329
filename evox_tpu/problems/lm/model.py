"""The member model of :class:`~evox_tpu.problems.lm.TokenLMProblem`: a
decoder-only language model whose layers mix tokens by latent attention
(MLA), by Kimi Delta Attention (KDA, a gated delta-rule linear attention), by
a gated short convolution or by grouped-query attention with a QK norm, each
layer of the kind the configuration's pattern gives it, over a mixture of
experts (``deepseek_v3``: every layer MLA with RoPE; ``kimi_linear``: KDA and
unrotated MLA; ``lfm2_moe``: gated short convolutions and grouped-query
attention, no shared expert), evaluated for every member of a low-rank
population at once.

The equations (each departure from the published modelling code is listed in
the benchmark configuration's ``assumed``). Pre-norm residual blocks, ``h = x
+ mixer(norm(x))``, ``y = h + mlp(norm(h))``, RMSNorm, a final norm, an untied
head.

- MLA, per token: ``q = x Wq`` as ``heads`` of ``qk_nope + qk_rope``. ``x
  Wkva`` is ``kv_lora_rank + qk_rope`` wide: the first ``kv_lora_rank``
  through RMSNorm give ``c``, the rest are ``k_rope``, one for all heads.
  RoPE (``rope_theta``, no scaling, the two halves of the rope dimensions
  paired, positions counted from the start of the token's document) on
  ``q_rope`` and ``k_rope``; with ``mla_use_nope`` none: those dimensions are
  used as they come. ``c Wkvb`` gives for each head ``k_nope`` and
  ``v``. ``k = [k_nope, k_rope]``; scores ``q.k / sqrt(qk_nope + qk_rope)``,
  causal and within a document only, softmax in float32; the heads' outputs
  through ``Wo``. Two bodies, one result (``forward`` chooses by what it can
  observe, no option): on the TPU backend, where the row divides into blocks
  of 128 and ``qk_nope`` and ``v`` are 128 wide, ``kernels.flash_attention``
  (a query block's scores, running maximum, running sum and output
  accumulator stay in VMEM in float32; key blocks after the query block or
  wholly of earlier documents are not visited; only the output is written);
  elsewhere ``attend_plain``, which makes every member's and head's ``(T,
  T)`` scores in HBM and is the kernel's reference in the tests.
- KDA, per token, on the normed ``xn``: ``q~ = xn Wq``, ``k~ = xn Wk``, ``v~ =
  xn Wv``, ``heads * head_dim`` wide each. A short convolution on each of the
  three (depthwise, causal, ``short_conv_kernel_size`` taps, then SiLU): ``u_t
  = silu(sum_j w[:, j] * u~_{t - (taps - 1) + j})``, a tap that reaches before
  the token's document began reading zero. ``w`` is a leaf of two axes
  ``(channels, taps)``, so the search perturbs it, and being no ``x @ W`` each
  member's ``w + sign * scale * A_p B_p^T`` is formed. Per head ``q <- q /
  sqrt(sum q^2 + 1e-6) / sqrt(head_dim)``, ``k <- k / sqrt(sum k^2 + 1e-6)``.
  The decay, per head and key channel: ``g = -exp(A_log[h]) * softplus((xn Wfa)
  Wfb + dt_bias) <= 0``; ``beta = sigmoid(xn Wb)`` a head. The delta rule, the
  state ``S`` ``(head_dim, head_dim)`` zero at the first token of every
  document: ``S' = diag(exp(g_t)) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t -
  S'^T k_t)^T``, ``o_t = S_t^T q_t``. Then per head ``rmsnorm(o) *
  sigmoid((xn Wga) Wgb)`` and ``Wo``. The recurrence is computed chunkwise and
  exactly (``kernels/kda_scan.py`` says how): on the TPU backend, where a
  head is a whole lane tile wide, by the ``kda_scan`` kernel (the state stays
  in VMEM across the row's chunks; a grid cell's two heads go through the
  chunk arithmetic joined, their tokens masked from each other as two
  documents' are, so that its products are 128 wide: each head's result is
  what it is alone), elsewhere by the same chunk arithmetic, a head at a
  time, in plain XLA; ``forward`` chooses by what it can observe, no option. By the
  same rule the way from each projection to the scan's operand (convolution,
  SiLU, L2 norm, the rounding) is one pass of the ``kda_conv`` kernel on the
  TPU backend and ``short_conv`` with the norms as float32 passes elsewhere.
- Gated short convolution (``lfm2_moe``'s ``conv`` layers), per token, on the
  normed ``xn``: ``[B, C, u] = xn W_in`` (``W_in`` ``(hidden, 3 hidden)``, a
  third each in that order); ``z = B * u``; ``c_t = sum_j w[:, j] * z_{t -
  (taps - 1) + j}``, depthwise and causal over ``conv_L_cache`` taps, the last
  on the token itself, no bias, a tap that reaches before the token's
  document began reading zero; ``(C * c) W_out``. No activation anywhere in
  it. ``w`` ``(hidden, taps)`` is a leaf of two axes, perturbed a member at a
  time as KDA's convolutions are (``member_taps``). Plain XLA on every
  backend.
- Grouped-query attention (``lfm2_moe``'s ``full_attention`` layers): ``q = xn
  Wq`` as ``num_attention_heads`` heads, ``k = xn Wk`` and ``v = xn Wv`` as
  ``num_key_value_heads``, of ``head_dim``; ``q`` and ``k`` through an RMS
  norm over each head (gains ``q_norm``, ``k_norm``, one a dimension, shared
  by the heads); RoPE over the whole head (the two halves paired, positions
  counted from the start of the token's document) on both; query head ``h``
  reads key-value head ``h // (heads / kv heads)``; scores ``q.k /
  sqrt(head_dim)``, causal and within a document, softmax in float32; the
  heads' outputs through ``Wo``. No bias. Two bodies, chosen as MLA's are: on
  the TPU backend, at heads of 64 in pairs of key-value heads and a row that
  divides into blocks of 128, ``kernels.gqa_flash_attention`` (a pair of
  key-value heads is fetched once for the query heads that read it);
  elsewhere ``attend_gqa_plain``.
- The dense layers' MLP and the shared experts (one MLP of width
  ``n_shared_experts * moe_intermediate_size``; a family with none has no such
  MLP): ``down(silu(gate x) * up x)``.
- Router: ``s = sigmoid(x Wr)`` in float32 over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the correction bias);
  weights ``routed_scaling_factor * s_e / sum of the chosen s`` (``lfm2_moe``:
  ``/ (sum + 1e-6)``, ``router_eps``). The layer's
  output is the shared MLP (where there is one) plus the weighted sum over the chosen experts
  **that are held here** (``experts_held``, a range): the layer routes over
  all the experts and computes its own experts' part of the result; what the
  absent experts would add is left out and no token is dropped. On one chip
  it runs without its exchange. The held assignments are sorted by expert
  once; each expert's rows go through its MLP a block at a time and are put
  back by token there and then, each block's results, rounded to the
  operands' dtype, weighted in float32 and added to their tokens' float32
  sums: only held rows are ever moved (``held_experts``).
- Fitness: mean next-token negative log-likelihood over the held rows of the
  vocabulary, every position but the first of each document.
- Precision: the operands of every matrix product in the dtype of the
  centre's matrices as ``ask`` cast them (bfloat16 in the benchmark), float32
  accumulation; norms, softmax, router scores and loss in float32. In
  attention the scores, their scale, the maximum, the sum and the output
  accumulator are float32 in both bodies; the probabilities are cast to the
  operands' dtype as the operand of ``p.v`` (normalised in the plain body,
  unnormalised in the kernel, which divides the accumulator by the sum at the
  end: the same relative rounding), and the output once more. In KDA the
  convolutions, the L2 norms, ``g`` and its running sums, ``beta``, the
  chunk's triangular solve, the carried state and the output norm and gate
  are float32; ``q``, ``k``, ``v`` and every operand of the scan's products
  are in the operands' dtype. In the gated convolution the gates' products,
  the taps' sum and ``C * c`` are float32 from the projection's rounded
  values, rounded once more as the operand of ``W_out``. In grouped-query
  attention the QK norms and RoPE are float32, ``q`` and ``k`` rounded once
  after them; the rest as in MLA.

Members: the population's two halves are the two signs of ``pairs``
perturbations (``core/lowrank.py``). Activations are laid out ``(pairs, 2,
tokens, width)``; every product is the shared base product over all members'
tokens plus each member's ``sign * scale * (x A_p) B_p^T``.

Parameters: ``init_params`` gives the tree. The index of a leaf in
``jax.tree.leaves`` of it is the leaf index of the perturbation law.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.instrument import (
    LM_ATTENTION,
    LM_CONV,
    LM_EMBED,
    LM_EXPERTS,
    LM_FORWARD,
    LM_HEAD_LOSS,
    LM_KDA,
    LM_KDA_SCAN,
    LM_LOWRANK,
    LM_MLP,
    LM_ROUTER,
    scope,
)
from ...kernels.flash_attention import flash_attention, flash_block_bounds, flash_block_sizes
from ...kernels.gqa_flash_attention import gqa_block_sizes, gqa_flash_attention
from ...kernels.kda_conv import kda_conv
from ...kernels.kda_scan import KDA_CHUNK, kda_scan, kda_scan_chunked

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The model's shapes, under the ``deepseek_v3`` family's names.
    ``n_routed_experts`` is the router's width; ``experts_held`` the range
    ``[lo, hi)`` of experts this chip holds; ``vocab_size`` the rows of the
    vocabulary held here; ``layers`` the depth held here, its first
    ``first_k_dense_replace`` layers dense. ``layer_kinds``: how each layer
    held mixes tokens, ``"mla"``, ``"kda"``, ``"conv"`` (gated short
    convolution) or ``"gqa"`` (grouped-query attention with QK norm) (empty:
    every layer MLA). ``mla_use_nope``: MLA does not rotate (``rope_theta`` is
    then unused). ``kda_num_heads``, ``kda_head_dim``, ``kda_conv_size``: KDA's
    sizes. ``num_key_value_heads``, ``head_dim``: the grouped-query layers'
    (their query heads are ``num_attention_heads``); ``conv_size``: the gated
    convolution's taps. ``n_shared_experts`` 0: an expert layer is its routed
    part alone. ``router_eps``: added to the sum of the chosen scores before
    the weights are divided by it."""

    hidden_size: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    routed_scaling_factor: float
    first_k_dense_replace: int
    layers: int
    vocab_size: int
    rms_norm_eps: float = 1e-5
    rope_theta: Optional[float] = None
    init_std: float = 0.02
    layer_kinds: Tuple[str, ...] = ()
    mla_use_nope: bool = False
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_size: int = 0
    num_key_value_heads: int = 0
    head_dim: int = 0
    conv_size: int = 0
    router_eps: float = 0.0

    @classmethod
    def from_dict(cls, config: dict) -> "LMConfig":
        """From a configuration file's keys: the published ``config.json``'s
        names of a family, told apart by ``model_type``, beside ``layers``
        (the depth held) and ``experts_held``. ``deepseek_v3``:
        ``n_routed_experts`` the experts held beside
        ``n_routed_experts_published``. ``kimi_linear``: ``num_experts`` the
        experts held beside ``num_experts_published``, and
        ``linear_attn_config``, whose layer numbers count from 1 and of which
        the first ``layers`` are held. ``lfm2_moe``: ``num_experts`` likewise,
        and ``layers_held``, the range ``[lo, hi)`` of ``layer_types`` (which
        counts from 0) held here: ``layers`` of them, those under
        ``num_dense_layers`` dense, the expert layers among them of the two
        kinds in the published proportion."""
        family = config.get("model_type", "deepseek_v3")
        if family not in _FAMILY_KEYS:
            raise ValueError(f"model_type {family!r} is not one of {sorted(_FAMILY_KEYS)}")
        own = {"experts_held", "rope_theta", "layer_kinds", "mla_use_nope", "num_key_value_heads", "head_dim",
               "conv_size", "router_eps"}
        names = {f.name for f in dataclasses.fields(cls) if not f.name.startswith("kda_")} - own
        keys = {name: _FAMILY_KEYS[family].get(name, name) for name in names}
        given = {name: config[key] for name, key in keys.items() if key in config}
        lo, hi = (int(v) for v in config["experts_held"])
        if hi - lo != int(given["n_routed_experts"]):
            raise ValueError(
                f"experts_held {lo}..{hi} is not {keys['n_routed_experts']}={given['n_routed_experts']} experts"
            )
        given["n_routed_experts"] = int(config[keys["n_routed_experts"] + "_published"])
        layers = int(config["layers"])
        if family == "lfm2_moe":
            return cls(experts_held=(lo, hi), **{**_LFM2_ABSENT, **given}, **_lfm2_layers(config, layers))
        kinds, kda = ("mla",) * layers, {}
        if family == "kimi_linear":
            for key, want in _KIMI_ROUTER.items():  # what ``route`` does
                if config[key] != want:
                    raise ValueError(f"kimi_linear: {key}={config[key]!r}, the router here does {want!r}")
            linear = config["linear_attn_config"]
            where = {**{int(l): "mla" for l in linear["full_attn_layers"]},
                     **{int(l): "kda" for l in linear["kda_layers"]}}
            kinds = tuple(where[l] for l in range(1, layers + 1))
            kda = dict(kda_num_heads=int(linear["num_heads"]), kda_head_dim=int(linear["head_dim"]),
                       kda_conv_size=int(linear["short_conv_kernel_size"]))
        nope = bool(config.get("mla_use_nope", False))
        return cls(
            experts_held=(lo, hi), layer_kinds=kinds, mla_use_nope=nope,
            # a rotating family states its theta: no other model's stands in for it
            rope_theta=None if nope else float(config["rope_theta"]),
            **given, **kda,
        )

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def expert_layers(self) -> int:
        return self.layers - self.first_k_dense_replace

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds or ("mla",) * self.layers

    @property
    def kda_layers(self) -> int:
        return sum(kind == "kda" for kind in self.kinds)

    @property
    def conv_layers(self) -> int:
        return sum(kind == "conv" for kind in self.kinds)


# a field of LMConfig under another family's published name
_FAMILY_KEYS = {
    "deepseek_v3": {},
    "kimi_linear": {"n_routed_experts": "num_experts", "num_experts_per_tok": "num_experts_per_token",
                    "n_shared_experts": "num_shared_experts"},
    "lfm2_moe": {"n_routed_experts": "num_experts", "rms_norm_eps": "norm_eps"},
}
_KIMI_ROUTER = {"moe_renormalize": True, "moe_router_activation_func": "sigmoid", "use_grouped_topk": True,
                "num_expert_group": 1, "topk_group": 1}
# what ``route`` (with ``router_eps``) and ``gated_conv`` do
_LFM2_STATED = {"norm_topk_prob": True, "use_expert_bias": True, "conv_bias": False}
# MLA's sizes and the shared expert, which the family does not have
_LFM2_ABSENT = dict(qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0, kv_lora_rank=0, n_shared_experts=0)
_LFM2_KINDS = {"conv": "conv", "full_attention": "gqa"}


def _lfm2_layers(config: dict, layers: int) -> dict:
    """The ``lfm2_moe`` family's own fields: the kinds of the layers held
    (``layers_held`` of ``layer_types``), how many of them are dense, the
    mixers' sizes, RoPE's base and the router's ``1e-6``."""
    for key, want in _LFM2_STATED.items():
        if config[key] != want:
            raise ValueError(f"lfm2_moe: {key}={config[key]!r}, the model here does {want!r}")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"lfm2_moe: rope_type {rope['rope_type']!r}, the model here rotates plainly")
    types, dense = list(config["layer_types"]), int(config["num_dense_layers"])
    first, last = (int(v) for v in config["layers_held"])
    if not 0 <= first < last <= len(types) or last - first != layers:
        raise ValueError(f"lfm2_moe: layers_held {first}..{last} is not layers={layers} of the {len(types)} layer_types")
    kinds = tuple(_LFM2_KINDS[kind] for kind in types[first:last])
    sparse = kinds[max(dense - first, 0):]
    if types.count("full_attention") * len(sparse) != sparse.count("gqa") * len(types):
        raise ValueError(
            f"lfm2_moe: the expert layers held {sparse} break the pattern: {types.count('full_attention')} of "
            f"{len(types)} published layers are full_attention"
        )
    heads = int(config["num_attention_heads"])
    return dict(
        layer_kinds=kinds, first_k_dense_replace=len(kinds) - len(sparse), rope_theta=float(rope["rope_theta"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or int(config["hidden_size"]) // heads),
        conv_size=int(config["conv_L_cache"]), router_eps=1e-6,
    )


def param_shapes(cfg: LMConfig) -> dict:
    """The parameter tree with a shape in each leaf's place."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    attn = {
        "norm": (d,),
        "q": (d, h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
        "kva": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": (cfg.kv_lora_rank,),
        "kvb": (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (h * cfg.v_head_dim, d),
    }
    kh, kd, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size
    kda = {
        "norm": (d,),
        "q": (d, kh * kd), "k": (d, kh * kd), "v": (d, kh * kd),
        "q_conv": (kh * kd, taps), "k_conv": (kh * kd, taps), "v_conv": (kh * kd, taps),
        "f_a": (d, kd), "f_b": (kd, kh * kd),  # the decay's low-rank projection
        "A_log": (kh,), "dt_bias": (kh * kd,),
        "beta": (d, kh),
        "g_a": (d, kd), "g_b": (kd, kh * kd),  # the output gate's
        "o_norm": (kd,),
        "o": (kh * kd, d),
    }

    conv = {  # ``in_proj``: the gates B and C and the stream u, a third each in that order
        "norm": (d,), "in_proj": (d, 3 * d), "taps": (d, cfg.conv_size), "out_proj": (d, d),
    }
    gh, gd = cfg.num_key_value_heads, cfg.head_dim
    gqa = {
        "norm": (d,),
        "q": (d, h * gd), "k": (d, gh * gd), "v": (d, gh * gd),
        "q_norm": (gd,), "k_norm": (gd,),  # one gain a dimension of the head, shared by the heads
        "o": (h * gd, d),
    }
    mixers = {"mla": ("attn", attn), "kda": ("kda", kda), "conv": ("conv", conv), "gqa": ("gqa", gqa)}

    def mlp(width, stack=()):
        return {"gate": stack + (d, width), "up": stack + (d, width), "down": stack + (width, d)}

    layers = []
    for l, kind in enumerate(cfg.kinds):
        name, mixer = mixers[kind]
        layer = {"mlp_norm": (d,), name: dict(mixer)}
        if l < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            layer["router"] = (d, cfg.n_routed_experts)
            layer["router_bias"] = (cfg.n_routed_experts,)
            if cfg.n_shared_experts:
                layer["shared"] = mlp(cfg.n_shared_experts * cfg.moe_intermediate_size)
            layer["experts"] = mlp(cfg.moe_intermediate_size, (cfg.n_held,))
        layers.append(layer)
    return {
        "embed": (cfg.vocab_size, d),
        "layers": layers,
        "final_norm": (d,),
        "head": (d, cfg.vocab_size),
    }


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and all(isinstance(n, int) for n in x)


def init_params(cfg: LMConfig, key: jax.Array) -> dict:
    """Seeded float32 parameters, leaf ``l`` (its index among the leaves) from
    ``fold_in(key, l)``: a leaf of two or three axes ``init_std * normal``
    (KDA's convolutions among them), norm gains one, the router's correction
    bias zero, KDA's ``A_log = log(uniform(1, 16))`` and ``dt_bias`` the
    inverse softplus of a ``dt`` log-uniform in 0.001 to 0.1."""
    paths, treedef = jax.tree.flatten_with_path(param_shapes(cfg), is_leaf=_is_shape)
    leaves = []
    for l, (path, shape) in enumerate(paths):
        name, k = getattr(path[-1], "key", None), jax.random.fold_in(key, l)
        if len(shape) >= 2:
            leaves.append(cfg.init_std * jax.random.normal(k, shape, F32))
        elif name == "router_bias":
            leaves.append(jnp.zeros(shape, F32))
        elif name == "A_log":
            leaves.append(jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0)))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(0.001), math.log(0.1)))
            leaves.append(dt + jnp.log(-jnp.expm1(-dt)))
        else:
            leaves.append(jnp.ones(shape, F32))
    return jax.tree.unflatten(treedef, leaves)


# ------------------------------------------------------------------ pieces

_SIGNS = (1.0, -1.0)  # the two members of a pair, along the axis of size 2


def _blocks(n: int, want: int) -> int:
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(b for b in range(1, max(1, min(n, want)) + 1) if n % b == 0)


def rmsnorm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """Float32 in, float32 out."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _add_lowrank(y: jax.Array, x: jax.Array, fac: Optional[tuple], scale: jax.Array) -> jax.Array:
    """``y + sign * scale * (x A_p) B_p^T`` for ``x`` of ``(pairs, 2, T, d_in)``."""
    if fac is None:
        return y
    a, b = fac
    with scope(LM_LOWRANK):
        xa = jnp.einsum("pstd,pdr->pstr", x, a.astype(x.dtype), preferred_element_type=F32)
        xa = xa * (scale * jnp.asarray(_SIGNS, F32))[None, :, None, None]
        return y + jnp.einsum(
            "pstr,por->psto", xa.astype(x.dtype), b.astype(x.dtype), preferred_element_type=F32
        )


def linear(x, w, fac, scale, out_dtype) -> jax.Array:
    """Every member's ``x @ W_i``: one product over all members' tokens and
    the low-rank term."""
    y = jnp.einsum("pstd,do->psto", x, w, preferred_element_type=F32)
    return _add_lowrank(y, x, fac, scale).astype(out_dtype)


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x``: ``(..., T, heads, rope)``; ``cos``/``sin``: ``(T, rope / 2)``."""
    x = x.astype(F32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attend_plain(cfg: LMConfig, mask: jax.Array, q, q_rope, kv, k_rope) -> jax.Array:
    """The plain body: the scores of all the block's members and heads, ``(m,
    heads, T, T)`` float32, made, masked and normalised in passes through
    HBM. ``q`` ``(m, T, heads, nope + rope)`` of which the nope part is read;
    ``q_rope`` ``(m, T, heads, rope)`` and ``k_rope`` ``(m, T, rope)`` after
    RoPE; ``kv`` ``(m, T, heads, nope + v)``. Returns ``(m, T, heads * v)``."""
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    s = jnp.einsum("mqhd,mkhd->mhqk", q[..., :dn], kv[..., :dn], preferred_element_type=F32)
    s = s + jnp.einsum("mqhd,mkd->mhqk", q_rope, k_rope, preferred_element_type=F32)
    s = jnp.where(mask, s * (1.0 / math.sqrt(dn + dr)), jnp.finfo(F32).min)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("mhqk,mkhd->mqhd", w.astype(q.dtype), kv[..., dn:], preferred_element_type=F32)
    return o.astype(q.dtype).reshape(q.shape[:2] + (-1,))


def attend_flash(cfg: LMConfig, doc: jax.Array, bounds: tuple, block_sizes: tuple,
                 q, q_rope, kv, k_rope) -> jax.Array:
    """The same, by ``kernels.flash_attention``: a query block's scores stay
    on the chip, the key blocks outside ``bounds`` are not visited."""
    m, t, h, _ = q.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    return flash_attention(
        q[..., :dn].reshape(m, t, h * dn), q_rope.transpose(0, 2, 1, 3), kv.reshape(m, t, -1), k_rope,
        doc, bounds, heads=h, scale=1.0 / math.sqrt(dn + dr),
        block_q=block_sizes[0], block_k=block_sizes[1], interpret=jax.default_backend() != "tpu",
    )


def _flash_blocks(cfg: LMConfig, t: int) -> Optional[tuple]:
    """The kernel's ``(block_q, block_k)`` where it runs: on the TPU backend,
    at head widths and a row length it takes. Elsewhere ``None``: the plain
    body."""
    if jax.default_backend() != "tpu":
        return None
    return flash_block_sizes(t, cfg.qk_nope_head_dim, cfg.v_head_dim)


def attend_gqa_plain(cfg: LMConfig, mask: jax.Array, q, k, v) -> jax.Array:
    """The grouped-query layers' plain body: the scores of all the block's
    members and heads, ``(m, kv heads, group, T, T)`` float32, through HBM.
    ``q`` ``(m, T, heads * head_dim)``, ``k`` and ``v`` ``(m, T, kv heads *
    head_dim)``, ``q`` and ``k`` normed and rotated; query head ``h`` reads
    key-value head ``h // group``. Returns ``(m, T, heads * head_dim)``."""
    m, t, _ = q.shape
    gh, gd = cfg.num_key_value_heads, cfg.head_dim
    k, v = k.reshape(m, t, gh, gd), v.reshape(m, t, gh, gd)
    s = jnp.einsum("mqgjd,mkgd->mgjqk", q.reshape(m, t, gh, -1, gd), k, preferred_element_type=F32)
    s = jnp.where(mask, s * (1.0 / math.sqrt(gd)), jnp.finfo(F32).min)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("mgjqk,mkgd->mqgjd", w.astype(q.dtype), v, preferred_element_type=F32)
    return o.astype(q.dtype).reshape(q.shape)


def attend_gqa_flash(cfg: LMConfig, doc: jax.Array, bounds: tuple, block_sizes: tuple, q, k, v) -> jax.Array:
    """The same, by ``kernels.gqa_flash_attention``."""
    return gqa_flash_attention(
        q, k, v, doc, bounds, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        scale=1.0 / math.sqrt(cfg.head_dim), block_q=block_sizes[0], block_k=block_sizes[1],
        interpret=jax.default_backend() != "tpu",
    )


def _gqa_blocks(cfg: LMConfig, t: int) -> Optional[tuple]:
    """The grouped-query kernel's ``(block_q, block_k)`` where it runs: on the
    TPU backend, at head counts and widths and a row length it takes.
    Elsewhere ``None``: the plain body."""
    if jax.default_backend() != "tpu":
        return None
    return gqa_block_sizes(t, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)


def gqa_attention(cfg: LMConfig, p, f, scale, x, attend, rotate, block_pairs: int) -> jax.Array:
    """``gqa(norm(x))``, a block of pairs at a time: ``q`` as
    ``num_attention_heads`` heads and ``k``, ``v`` as ``num_key_value_heads``
    of ``head_dim``; ``q`` and ``k`` through an RMS norm over each head (one
    gain a dimension, shared by the heads) and RoPE over the whole head, in
    float32, rounded once; ``attend``: ``attend_gqa_plain`` or
    ``attend_gqa_flash`` with the row's mask or bounds bound."""
    pairs, _, t, _ = x.shape
    dt = x.dtype
    h, gh, gd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    bp = _blocks(pairs, block_pairs)
    m = 2 * bp
    split = lambda a: a.reshape((pairs // bp, bp) + a.shape[1:])

    def block(args):
        xb, fb = args
        xn = rmsnorm(xb, p["norm"], cfg.rms_norm_eps).astype(dt)
        q = linear(xn, p["q"], fb["q"], scale, dt).reshape(m, t, h, gd)
        k = linear(xn, p["k"], fb["k"], scale, dt).reshape(m, t, gh, gd)
        v = linear(xn, p["v"], fb["v"], scale, dt).reshape(m, t, gh * gd)
        q = rotate(rmsnorm(q, p["q_norm"], cfg.rms_norm_eps)).astype(dt).reshape(m, t, h * gd)
        k = rotate(rmsnorm(k, p["k_norm"], cfg.rms_norm_eps)).astype(dt).reshape(m, t, gh * gd)
        o = attend(q, k, v)
        return linear(o.reshape(bp, 2, t, h * gd), p["o"], fb["o"], scale, dt)

    return jax.lax.map(block, (split(x), jax.tree.map(split, f))).reshape(x.shape)


def attention(cfg: LMConfig, p, f, scale, x, attend, rotate, block_pairs: int) -> jax.Array:
    """``attn(norm(x))``, a block of pairs at a time (the plain body's scores
    of all members at once would be ``pop * heads * T * T`` floats).
    ``attend``: ``attend_plain`` or ``attend_flash`` with the row's mask or
    bounds bound. ``rotate``: RoPE at the row's positions, or the identity
    where MLA does not rotate."""
    pairs, _, t, _ = x.shape
    dt = x.dtype
    h, dn, dr, dv, dl = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    bp = _blocks(pairs, block_pairs)
    m = 2 * bp
    split = lambda a: a.reshape((pairs // bp, bp) + a.shape[1:])

    def block(args):
        xb, fb = args
        xn = rmsnorm(xb, p["norm"], cfg.rms_norm_eps).astype(dt)
        q = linear(xn, p["q"], fb["q"], scale, dt).reshape(m, t, h, dn + dr)
        kva = linear(xn, p["kva"], fb["kva"], scale, F32)
        c = rmsnorm(kva[..., :dl], p["kv_norm"], cfg.rms_norm_eps).astype(dt)
        kv = linear(c, p["kvb"], fb["kvb"], scale, dt).reshape(m, t, h, dn + dv)
        q_rope = rotate(q[..., dn:]).astype(dt)
        k_rope = rotate(kva[..., dl:].reshape(m, t, 1, dr)).astype(dt)[:, :, 0]
        o = attend(q, q_rope, kv, k_rope)
        return linear(o.reshape(bp, 2, t, h * dv), p["o"], fb["o"], scale, dt)

    return jax.lax.map(block, (split(x), jax.tree.map(split, f))).reshape(x.shape)


def member_taps(w, fac, scale) -> jax.Array:
    """Each member's own ``w + sign * scale * A_p B_p^T`` of a convolution's
    ``(channels, taps)``: ``(pairs, 2, channels, taps)`` float32, or ``(1, 1,
    channels, taps)`` where there are no factors."""
    w = w.astype(F32)[None, None]
    if fac is None:
        return w
    with scope(LM_LOWRANK):
        delta = jnp.einsum("pcr,pjr->pcj", *fac, preferred_element_type=F32)
        return w + (scale * jnp.asarray(_SIGNS, F32))[None, :, None, None] * delta[:, None]


def short_conv(u, w, fac, scale, reach) -> jax.Array:
    """KDA's short convolution and SiLU on one stream. ``u`` ``(pairs, 2, T,
    channels)``; ``w`` ``(channels, taps)``, depthwise, its last tap on the
    token itself; ``reach`` ``(taps, T)``: whether the tap ``s`` tokens back
    lies in the token's document. Each member's own ``w + sign * scale * A_p
    B_p^T`` is formed: the leaf is no ``x @ W``. Float32."""
    w = member_taps(w, fac, scale)
    return jax.nn.silu(_taps(u.astype(F32), w, reach))


def _taps(u, w, reach) -> jax.Array:
    """``sum_back w[..., taps - 1 - back] * u_{t - back}`` over the row, a tap
    outside ``reach`` reading zero. ``u`` ``(pairs, 2, T, channels)`` float32;
    ``w`` each member's taps as ``member_taps`` forms them."""
    t, taps = u.shape[2], w.shape[-1]
    # one expand each, then whole slices of a leading axis: an index and a new axis a tap (``reach[back][:, None]``)
    # lower to reshape pairs that MLIR merges under a fused location, which XLA names after the scope it lies in,
    # so that a named scope would change more of the program than its metadata
    keep = reach[:, :, None]  # (taps, T, 1)
    w = jnp.moveaxis(w, -1, 0)[:, :, :, None]  # (taps, pairs or 1, 2 or 1, 1, channels)
    y = 0.0
    for back in range(taps):
        past = jnp.pad(u, ((0, 0), (0, 0), (back, 0), (0, 0)))[:, :, :t]
        y = y + jnp.where(keep[back], past, 0.0) * w[taps - 1 - back]
    return y


def gated_conv(cfg: LMConfig, p, f, scale, x, reach) -> tuple:
    """``conv(norm(x))`` of the chunk's pairs, and four sums of squares, ``(2,
    2)``: of the mixer's output and of its normed input, each over every token
    and over the tokens a tap of which reaches before their document began
    (``conv_gain`` is the roots of the two ratios). ``[B, C, u] = xn W_in``, a
    third each; ``c`` the short depthwise causal convolution of ``B * u`` (each
    member's own taps, none reaching before the token's document: ``reach``);
    ``(C * c) W_out``. No activation. The gates' products, the taps' sum and
    ``C * c`` are float32 from the projection's rounded values, rounded once
    more as the operand of ``W_out``."""
    d, dt = x.shape[-1], x.dtype
    first = ~reach[-1]  # the tokens whose farthest tap the mask zeroes

    def squares(a):
        by_token = jnp.sum(jnp.square(a.astype(F32)), axis=(0, 1, 3))
        return jnp.stack([jnp.sum(by_token), jnp.sum(jnp.where(first, by_token, 0.0))])

    xn = rmsnorm(x, p["norm"], cfg.rms_norm_eps).astype(dt)
    bcu = linear(xn, p["in_proj"], f["in_proj"], scale, dt).astype(F32)
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    y = c * _taps(b * u, member_taps(p["taps"], f["taps"], scale), reach)
    out = linear(y.astype(dt), p["out_proj"], f["out_proj"], scale, dt)
    return out, jnp.stack([squares(out), squares(xn)])


def _kda_kernel(cfg: LMConfig) -> bool:
    """Whether KDA runs its kernels (``kda_conv`` between the projections and
    the scan, ``kda_scan``): on the TPU backend, a head a whole lane tile
    wide. Elsewhere ``short_conv`` and the chunk arithmetic in XLA."""
    return jax.default_backend() == "tpu" and cfg.kda_head_dim % 128 == 0


def kda(cfg: LMConfig, p, f, scale, x, scan, conv, reach, block_pairs: int) -> tuple:
    """``kda(norm(x))``, a block of pairs at a time, and the mean of
    ``exp(g)``. ``scan``: the recurrence over the row (``kda_scan`` or
    ``kda_scan_chunked`` with the row's documents bound). ``conv``: ``kda_conv``
    with the row's positions bound, which takes a projection as ``linear``
    leaves it to the scan's operand in one pass, or ``None``: ``short_conv``
    over ``reach`` and the L2 norms in float32, a pass each."""
    pairs, _, t, _ = x.shape
    dt = x.dtype
    h, dk = cfg.kda_num_heads, cfg.kda_head_dim
    bp = _blocks(pairs, block_pairs)
    m = 2 * bp
    split = lambda a: a.reshape((pairs // bp, bp) + a.shape[1:])
    heads = lambda a: a.reshape(bp, 2, t, h, dk)

    def block(args):
        xb, fb = args
        xn = rmsnorm(xb, p["norm"], cfg.rms_norm_eps).astype(dt)
        streams = [(linear(xn, p[n], fb[n], scale, dt), p[n + "_conv"], fb[n + "_conv"]) for n in ("q", "k", "v")]
        if conv is None:
            q, k, v = (heads(short_conv(u, w, fac, scale, reach)) for u, w, fac in streams)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        else:
            # the kernel's layouts: the stream as ``linear`` leaves it, a member's taps a row each
            own = lambda w, fac: jnp.broadcast_to(member_taps(w, fac, scale), (bp, 2) + w.shape).reshape((m,) + w.shape)
            q, k, v = (
                conv(u.reshape(m, t, h * dk), own(w, fac).transpose(0, 2, 1), width=dk, normalise=norm)
                for (u, w, fac), norm in zip(streams, ("l2_scaled", "l2", None))
            )
        low = lambda a, b, out: linear(linear(xn, p[a], fb[a], scale, dt), p[b], fb[b], scale, out)
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(heads(low("f_a", "f_b", F32) + p["dt_bias"]))
        beta = jax.nn.sigmoid(linear(xn, p["beta"], fb["beta"], scale, F32))
        with scope(LM_KDA_SCAN):
            flat = lambda a, to: a.astype(to).reshape(m, t, h * dk)
            o = scan(flat(q, dt), flat(k, dt), flat(v, dt), flat(g, F32), beta.reshape(m, t, h))
        o = rmsnorm(heads(o), p["o_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(heads(low("g_a", "g_b", F32)))
        out = linear(o.astype(dt).reshape(bp, 2, t, h * dk), p["o"], fb["o"], scale, dt)
        return out, jnp.mean(jnp.exp(g))

    out, kept = jax.lax.map(block, (split(x), jax.tree.map(split, f)))
    return out.reshape(x.shape), jnp.mean(kept)


def mlp(p, f, scale, xn, block_pairs: int) -> jax.Array:
    """``down(silu(gate x) * up x)``, a block of pairs at a time."""
    pairs, dt = xn.shape[0], xn.dtype
    bp = _blocks(pairs, block_pairs)
    split = lambda a: a.reshape((pairs // bp, bp) + a.shape[1:])

    def block(args):
        xb, fb = args
        g = linear(xb, p["gate"], fb["gate"], scale, F32)
        u = linear(xb, p["up"], fb["up"], scale, F32)
        return linear((jax.nn.silu(g) * u).astype(dt), p["down"], fb["down"], scale, dt)

    out = jax.lax.map(block, (split(xn), jax.tree.map(split, f)))
    return out.reshape(xn.shape)


def route(cfg: LMConfig, p, f, scale, xn) -> tuple:
    """The router over all the experts: for each token its chosen experts
    ``(…, k)`` and their weights."""
    z = linear(xn, p["router"], f["router"], scale, F32)
    s = jax.nn.sigmoid(z)
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    if cfg.router_eps:  # a family's own renormalisation
        total = total + cfg.router_eps
    w = cfg.routed_scaling_factor * chosen / total
    return idx, w


def held_experts(cfg: LMConfig, p, f, scale, xn, idx, w, block_rows: int) -> tuple:
    """The weighted sum over each token's chosen experts that are held here,
    the held experts' loads ``(n_held,)`` and the rows the loop moved.

    The assignments are sorted by expert once (those of absent experts last;
    within an expert by token, each token at most once), each held expert's
    rows go through its MLP in blocks of ``block_rows`` (a loop whose trip
    count is the blocks there are: nothing is padded to a capacity and nothing
    is dropped), and each block's results are weighted and added to their
    tokens' float32 sums where they are made: nothing between the sort and
    the layer's output has a row for every assignment. ``moved``: the blocks
    times ``block_rows``, the rows gathered, computed and put back (the held
    rows and what fills each expert's last block)."""
    pairs, _, t, d = xn.shape
    dt, k, eh = xn.dtype, cfg.num_experts_per_tok, cfg.n_held
    lo, hi = cfg.experts_held
    n = pairs * 2 * t
    held = (idx >= lo) & (idx < hi)
    key = jnp.where(held, idx - lo, eh).astype(jnp.int32).reshape(n * k)
    experts, fe = p["experts"], f["experts"]
    r = fe["gate"][0].shape[-1]
    signs = jnp.asarray(_SIGNS, F32)
    xc = xn.reshape(n, d)

    # one stable sort by expert; each assignment's token and weight travel with its key
    _, tok_sorted, w_sorted = jax.lax.sort(
        (key, jnp.arange(n * k, dtype=jnp.int32) // k, w.reshape(n * k)), num_keys=1, is_stable=True
    )
    tok_sorted, w_sorted = (jnp.pad(v, (0, block_rows)) for v in (tok_sorted, w_sorted))
    counts = jnp.sum(key[:, None] == jnp.arange(eh)[None, :], axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    blocks = (counts + block_rows - 1) // block_rows
    ends = jnp.cumsum(blocks)
    at = jnp.arange(block_rows, dtype=jnp.int32)

    def one_block(b, out):
        e = jnp.sum(b >= ends).astype(jnp.int32)  # the expert whose block this is
        done = (b - (ends[e] - blocks[e])) * block_rows  # rows of it in its earlier blocks
        off = starts[e] + done
        tok = jax.lax.dynamic_slice_in_dim(tok_sorted, off, block_rows)
        xb = xc[tok]
        # each row's own pair, with its sign and the scale
        own = jax.nn.one_hot(tok // (2 * t), pairs, dtype=F32) * (scale * signs[(tok // t) % 2])[:, None]

        def product(xin, w_e, fac):
            y = jnp.einsum("nd,do->no", xin, w_e, preferred_element_type=F32)
            with scope(LM_LOWRANK):
                a, bb = (jax.lax.dynamic_index_in_dim(v, e, axis=1, keepdims=False) for v in fac)
                a = a.transpose(1, 0, 2).reshape(a.shape[1], pairs * r).astype(dt)
                bb = bb.transpose(0, 2, 1).reshape(pairs * r, bb.shape[1]).astype(dt)
                xa = jnp.einsum("nd,dq->nq", xin, a, preferred_element_type=F32)
                xa = (xa.reshape(-1, pairs, r) * own[:, :, None]).reshape(-1, pairs * r)
                return y + jnp.einsum("nq,qo->no", xa.astype(dt), bb, preferred_element_type=F32)

        with scope(LM_EXPERTS):
            w_e = {name: jax.lax.dynamic_index_in_dim(v, e, keepdims=False) for name, v in experts.items()}
            g = product(xb, w_e["gate"], fe["gate"])
            u = product(xb, w_e["up"], fe["up"])
            y = product((jax.nn.silu(g) * u).astype(dt), w_e["down"], fe["down"]).astype(dt)
        # The rows past the expert's last are the next expert's (or absent
        # experts'), computed with this expert's weights: they go to a row
        # past ``n``, which the add drops. A zero weight would not do: the add
        # would still move those tokens' sums, name a token twice where it is
        # a live row of this block too, and spoil it with a row not finite.
        row = jnp.where(at < counts[e] - done, tok, n)
        wb = jax.lax.dynamic_slice_in_dim(w_sorted, off, block_rows)
        return out.at[row].add((y.astype(F32) * wb[:, None]).reshape((block_rows,) + out.shape[1:]), mode="drop")

    # A token's sum is kept as ``(d / lanes, lanes)``, whole tiles of the
    # chip's memory lying together, not one row of a matrix, which is a sublane
    # of each of ``d / 128`` tiles: the chip's scatter moves it in well under
    # half the time (PERF.md section 6, PR 31).
    lanes = math.gcd(d, 128)
    out = jax.lax.fori_loop(0, ends[-1], one_block, jnp.zeros((n, d // lanes, lanes), F32))
    # the sums are put into rows here, once: unheld, the compiler carries their
    # shape on into the residual stream, the norms and the head, which it slows
    out = jax.lax.optimization_barrier(out.astype(dt).reshape(xn.shape))
    return out, counts, ends[-1] * block_rows


def expert_layer(cfg: LMConfig, p, f, scale, xn, blocks: dict) -> tuple:
    """``(shared MLP, held experts' part, loads, moved)`` of an expert layer
    for the normed ``xn``; the layer's output is the sum of the first two, or
    the second alone where the model has no shared expert (``None``)."""
    shared = None
    if "shared" in p:
        with scope(LM_MLP):
            shared = mlp(p["shared"], f["shared"], scale, xn, blocks["shared_block_pairs"])
    with scope(LM_ROUTER):
        idx, w = route(cfg, p, f, scale, xn)
        routed, loads, moved = held_experts(cfg, p, f, scale, xn, idx, w, blocks["expert_block_rows"])
    return shared, routed, loads, moved


# How the forward pass is cut so that it fits. ``chunk_pairs``: the pairs that
# go through the whole model together (their tokens are the rows of every base
# product and of the experts' sort); within a chunk, the pairs a block of
# attention (of either kind), of KDA, of the dense MLP and of the shared MLP
# takes (the gated convolution takes the chunk whole); the rows of a block of
# an expert's product.
DEFAULT_BLOCKS = {
    "chunk_pairs": 4,
    "attn_block_pairs": 1,
    "kda_block_pairs": 1,
    "dense_block_pairs": 1,
    "shared_block_pairs": 4,
    "expert_block_rows": 512,
}


@scope(LM_FORWARD)
def forward(cfg: LMConfig, center, factors, scale, ids, doc, pos, n_probe: int,
            blocks: dict = DEFAULT_BLOCKS) -> dict:
    """Every member's loss on one packed row of tokens.

    ``center``/``factors``/``scale``: a ``LowRankPopulation``'s. ``ids``,
    ``doc``, ``pos``: ``(T,)`` token ids, each token's document and its
    position in it. Returns ``losses`` ``(pairs, 2)``, ``probe`` (pair 0's
    float32 logits at the last ``n_probe`` positions, ``(2, n_probe, vocab)``),
    per expert layer ``held`` (assignments that landed on held experts),
    ``moved`` (the rows the experts' loop gathered, computed and put back: the
    held rows and what fills each expert's last block, over the chunks) and
    ``imbalance`` (largest held expert's load over the mean), and
    ``attn_blocks``: the key blocks attention's kernel visits for this row's
    documents over those of a dense causal pass (1 where the plain body runs:
    the whole row is its one block); per KDA layer ``kda_retention`` (the mean
    of ``exp(g)`` over members, tokens, heads and channels: how much of the
    state a token keeps) and ``kda_boundary_chunks`` (the scan's chunks in
    which a document starts over its chunks: how often the reset inside a
    chunk runs); per gated convolution layer ``conv_gain`` (the root mean
    square of the mixer's output over that of its normed input, over members,
    tokens and channels, and the same over the first ``taps - 1`` tokens of
    the documents alone, where the mask of the taps acts: ``(layers, 2)``)."""
    t = ids.shape[0]
    dt = center["embed"].dtype
    pairs = jax.tree.leaves(factors)[0].shape[0]
    cp = _blocks(pairs, blocks["chunk_pairs"])
    signs = jnp.asarray(_SIGNS, F32)
    grouped = "gqa" in cfg.kinds  # a family's attention is of one kind: MLA, or grouped-query heads

    if cfg.mla_use_nope:
        rotate = lambda a: a
    else:
        half = (cfg.head_dim if grouped else cfg.qk_rope_head_dim) // 2
        freq = cfg.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
        angle = pos.astype(F32)[:, None] * freq[None, :]
        rotate = functools.partial(_rope, cos=jnp.cos(angle), sin=jnp.sin(angle))
    at = jnp.arange(t)
    flash = _gqa_blocks(cfg, t) if grouped else _flash_blocks(cfg, t)
    if flash is None:
        mask = (at[:, None] >= at[None, :]) & (doc[:, None] == doc[None, :])
        attend = functools.partial(attend_gqa_plain if grouped else attend_plain, cfg, mask)
        attn_blocks = jnp.ones((), F32)  # the whole row is one block
    else:
        first, last = flash_block_bounds(doc, *flash)
        attend = functools.partial(attend_gqa_flash if grouped else attend_flash, cfg, doc, (first, last), flash)
        # a query attends itself, so ``last`` is the diagonal's block: a dense causal pass visits 0 .. last
        attn_blocks = jnp.sum(last - first + 1).astype(F32) / jnp.sum(last + 1)
    if cfg.conv_layers:
        conv_reach = pos[None, :] >= jnp.arange(cfg.conv_size)[:, None]
    boundary_chunks = 0.0
    if cfg.kda_layers:
        reach = pos[None, :] >= jnp.arange(cfg.kda_conv_size)[:, None]
        scan = (functools.partial(kda_scan, interpret=jax.default_backend() != "tpu") if _kda_kernel(cfg)
                else kda_scan_chunked)
        scan = functools.partial(scan, doc=doc, heads=cfg.kda_num_heads, chunk=KDA_CHUNK)
        conv = (functools.partial(kda_conv, pos=pos, interpret=jax.default_backend() != "tpu") if _kda_kernel(cfg)
                else None)
        starts = jnp.pad(pos == 0, (0, -t % KDA_CHUNK)).reshape(-1, KDA_CHUNK)
        boundary_chunks = jnp.mean(jnp.any(starts, axis=1).astype(F32))
    target = jnp.roll(ids, -1)
    counted = (at + 1 < t) & (jnp.roll(doc, -1) == doc)  # the next token is of this document
    weight = counted.astype(F32) / jnp.maximum(jnp.sum(counted), 1)

    def chunk(fac):
        with scope(LM_EMBED):
            x = jnp.broadcast_to(center["embed"][ids].astype(F32), (cp, 2, t, cfg.hidden_size))
            if fac["embed"] is not None:
                a, b = fac["embed"]
                with scope(LM_LOWRANK):
                    xa = a[:, ids] * scale  # (pairs, T, r)
                    delta = jnp.einsum("ptr,pdr->ptd", xa.astype(dt), b.astype(dt), preferred_element_type=F32)
                    x = x + signs[None, :, None, None] * delta[:, None]
            x = x.astype(dt)

        loads, moved, kept, squares = [], [], [], []
        for kind, p, f in zip(cfg.kinds, center["layers"], fac["layers"]):
            if kind == "kda":
                with scope(LM_KDA):
                    mixed, retention = kda(cfg, p["kda"], f["kda"], scale, x, scan, conv, reach,
                                           blocks["kda_block_pairs"])
                    x = x + mixed
                kept.append(retention)
            elif kind == "conv":
                with scope(LM_CONV):
                    mixed, square = gated_conv(cfg, p["conv"], f["conv"], scale, x, conv_reach)
                    x = x + mixed
                squares.append(square)
            elif kind == "gqa":
                with scope(LM_ATTENTION):
                    x = x + gqa_attention(cfg, p["gqa"], f["gqa"], scale, x, attend, rotate,
                                          blocks["attn_block_pairs"])
            else:
                with scope(LM_ATTENTION):
                    x = x + attention(cfg, p["attn"], f["attn"], scale, x, attend, rotate,
                                      blocks["attn_block_pairs"])
            with scope(LM_MLP):
                xn = rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps).astype(dt)
            if "mlp" in p:
                with scope(LM_MLP):
                    x = x + mlp(p["mlp"], f["mlp"], scale, xn, blocks["dense_block_pairs"])
            else:
                shared, routed, load, rows = expert_layer(cfg, p, f, scale, xn, blocks)
                with scope(LM_ROUTER):
                    x = x + routed if shared is None else x + shared + routed
                loads.append(load)
                moved.append(rows)

        with scope(LM_HEAD_LOSS):
            xn = rmsnorm(x, center["final_norm"], cfg.rms_norm_eps).astype(dt)

            def pair(args):
                xb, fb = args
                logits = linear(xb[None], center["head"], jax.tree.map(lambda v: v[None], fb), scale, F32)[0]
                nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                    logits, target[None, :, None], axis=-1
                )[..., 0]
                return jnp.sum(nll * weight, axis=-1)

            losses = jax.lax.map(pair, (xn, fac["head"]))
            probe = linear(
                xn[:1, :, t - n_probe :], center["head"],
                jax.tree.map(lambda v: v[:1], fac["head"]), scale, F32,
            )[0]
        loads = jnp.stack(loads) if loads else jnp.zeros((0, cfg.n_held), jnp.int32)
        moved = jnp.stack(moved) if moved else jnp.zeros((0,), jnp.int32)
        kept = jnp.stack(kept) if kept else jnp.zeros((0,), F32)
        return losses, probe, loads, moved, kept, squares

    split = lambda a: a.reshape((pairs // cp, cp) + a.shape[1:])
    losses, probe, loads, moved, kept, squares = jax.lax.map(chunk, jax.tree.map(split, factors))
    # a layer's sums of squares over the chunks: (output, input) x (every token, the documents' first)
    squares = [jnp.sum(square, axis=0) for square in squares]
    with scope(LM_ROUTER):
        loads = jnp.sum(loads, axis=0)  # (expert layers, held experts)
        imbalance = jnp.max(loads, axis=-1) / jnp.maximum(jnp.mean(loads.astype(F32), axis=-1), 1.0)
    return {
        "losses": losses.reshape(pairs, 2),
        "probe": probe[0],  # pair 0 is the first of the first chunk
        "held": jnp.sum(loads, axis=-1).astype(jnp.int32),
        "moved": jnp.sum(moved, axis=0).astype(jnp.int32),
        "imbalance": imbalance.astype(F32),
        "attn_blocks": attn_blocks,
        "kda_retention": jnp.mean(kept, axis=0),
        "kda_boundary_chunks": jnp.full((cfg.kda_layers,), boundary_chunks, F32),
        "conv_gain": (jnp.stack([jnp.sqrt(out / within) for out, within in squares]) if squares
                      else jnp.zeros((0, 2), F32)),
    }

"""Work counts of a token language model of the ``lfm2_moe`` family (gated
short convolutions and grouped-query attention by ``layer_types``, experts
with no shared one) under low-rank OpenES: the operations and bytes its
semantics need, from the configuration's shapes and the traffic alone (never
the program), so that a share of a peak counts the same work whatever
implements it. ``work_lm.py`` counts the ``deepseek_v3`` family and
``work_lm_hybrid.py`` the ``kimi_linear``.

Counted per token of one member's forward pass; a multiply-accumulate is two
operations. A convolution layer: its two projections (``hidden x 3 hidden``
and ``hidden x hidden``), the two gates' products and the taps. An attention
layer: q, k, v, o and the scores, causal and within a document, at the keys a
query is expected to attend in a packed row of the traffic's documents, the
row's cut of its last document counted (``attended_keys_per_token``: 1,940.5 at
this cell's traffic, where ``work_lm.expected_attended``, which leaves the cut
out, gives 2,421: at a median of 2,048 in rows of 8,192 the cut is a fifth):
for each query head and attended key ``2 * head_dim`` operations for the
score and as many for ``p . v``. The routed
experts at the expected share of a token's choices that lands on held experts
(``held / published * top k``: 1.0 of 4 at 16 of 64). The low-rank terms,
norms, RoPE, softmax and the search's own ask and tell are left out.
"""

from __future__ import annotations

import functools
import math

import numpy as np


def layers_held(config: dict) -> list:
    """``(kind, dense)`` of each layer held: ``layer_types`` counts from 0."""
    first, last = config["layers_held"]
    return [(config["layer_types"][l], l < int(config["num_dense_layers"])) for l in range(first, last)]


def head_dim(config: dict) -> int:
    return int(config["hidden_size"]) // int(config["num_attention_heads"])


def held_choices_per_token(config: dict) -> float:
    """Expected choices of a token that land on held experts under a uniform
    router."""
    return (float(config["num_experts_per_tok"]) * float(config["num_experts"])
            / float(config["num_experts_published"]))


def attended_keys_per_token(traffic: dict) -> float:
    """The expected number of keys a query attends (itself included) in one
    packed row: documents of length ``clip(round(L), doc_len_min, seq_len)``,
    ``L`` log-normal, lie end to end from the row's start and the row's end
    cuts the last. ``A(t)``, the expected pairs of a query and a key it
    attends in the ``t`` tokens left of a row, obeys ``A(t) = sum_{l < t} P(l)
    (l (l + 1) / 2 + A(t - l)) + P(L >= t) t (t + 1) / 2``; this is ``A(T) /
    T``, exact for the traffic's law."""
    return _attended(float(traffic["doc_len_median"]), float(traffic["doc_len_sigma"]),
                     int(traffic["doc_len_min"]), int(traffic["seq_len"]))


@functools.lru_cache(maxsize=None)
def _attended(median: float, sigma: float, lo: int, t_row: int) -> float:
    cdf = lambda x: 0.5 * (1.0 + math.erf((math.log(x) - math.log(median)) / sigma / math.sqrt(2.0)))
    # below[l] = P(length <= l): rounding puts (l - 0.5, l + 0.5) on l, the clip all below ``lo`` on ``lo``
    below = np.array([0.0 if l < lo else cdf(l + 0.5) for l in range(t_row)] + [1.0])
    pmf = np.diff(below, prepend=0.0)
    pairs = np.arange(t_row + 1) * (np.arange(t_row + 1) + 1) / 2.0
    a = np.zeros(t_row + 1)
    for t in range(1, t_row + 1):
        a[t] = pmf[1:t] @ (pairs[1:t] + a[t - 1:0:-1]) + (1.0 - below[t - 1]) * pairs[t]
    return float(a[t_row] / t_row)


def gqa_scores_flops_per_token(config: dict, traffic: dict) -> float:
    """Scores and ``p . v`` of one attention layer for one token, all query
    heads, at the expected number of attended keys."""
    return 2.0 * int(config["num_attention_heads"]) * 2 * head_dim(config) * attended_keys_per_token(traffic)


def gqa_kernel_bytes_per_token(config: dict, operand_bytes: int = 2) -> int:
    """What one attention layer's scores, softmax and ``p . v`` have to move
    for one token: q in and o out at the query heads' width, k and v in at the
    key-value heads', once each in the operands' dtype."""
    wide = int(config["num_attention_heads"]) * head_dim(config)
    narrow = int(config["num_key_value_heads"]) * head_dim(config)
    return (2 * wide + 2 * narrow) * operand_bytes


def lm_flops_per_token(config: dict, traffic: dict) -> dict:
    """The parts of a token's forward pass, in operations: ``conv`` (every
    convolution layer's projections, gates and taps), ``attention`` (the
    attention layers' projections and scores), ``gqa_scores`` (the scores and
    ``p . v`` alone, a part of ``attention``), ``dense_mlp``, ``router``,
    ``experts``, ``head``, and their ``total`` (``gqa_scores`` counted once)."""
    d = int(config["hidden_size"])
    wide = int(config["num_attention_heads"]) * head_dim(config)
    narrow = int(config["num_key_value_heads"]) * head_dim(config)
    held = layers_held(config)
    n_conv = sum(kind == "conv" for kind, _ in held)
    n_attn = len(held) - n_conv
    n_dense = sum(dense for _, dense in held)
    n_sparse = len(held) - n_dense
    conv = 2.0 * (d * 3 * d + d * d) + 2.0 * d * int(config["conv_L_cache"]) + 2.0 * d
    projections = 2.0 * (d * wide + 2 * d * narrow + wide * d)
    scores = gqa_scores_flops_per_token(config, traffic)
    out = {
        "conv": n_conv * conv,
        "attention": n_attn * (projections + scores),
        "dense_mlp": 2.0 * n_dense * 3 * d * int(config["intermediate_size"]),
        "router": 2.0 * n_sparse * d * int(config["num_experts_published"]),
        "experts": n_sparse * held_choices_per_token(config) * 2 * 3 * d * int(config["moe_intermediate_size"]),
        "head": 2.0 * d * int(config["vocab_size"]),
    }
    out["total"] = sum(out.values())
    out["gqa_scores"] = n_attn * scores
    return out


def lm_flops_per_eval(config: dict, traffic: dict) -> float:
    """One member's evaluation: its tokens times a token's operations."""
    tokens = int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    return tokens * lm_flops_per_token(config, traffic)["total"]


def gqa_kernel_least_seconds(config: dict, traffic: dict, evals: float, peak: dict) -> float:
    """The least time the chip could take for the attention layers' scores,
    softmax and ``p . v`` of ``evals`` evaluations: ``max(flops / peak flops,
    bytes / peak bandwidth)``. Operations bound it at this cell's row. The
    count is of the semantics: the same whatever blocks a kernel visits or
    skips."""
    tokens = evals * int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    layers = sum(kind != "conv" for kind, _ in layers_held(config))
    flops = tokens * layers * gqa_scores_flops_per_token(config, traffic)
    moved = tokens * layers * gqa_kernel_bytes_per_token(config)
    return max(flops / peak["flops_per_s"], moved / peak["hbm_bytes_per_s"])


def experts_least_seconds(config: dict, traffic: dict, evals: float, peak: dict) -> float:
    """The least time the chip could take for the held experts' products of
    ``evals`` evaluations: ``max(flops / peak flops, bytes / peak
    bandwidth)``, as ``work_lm.experts_least_seconds`` counts the
    ``deepseek_v3`` family's. Bytes, for each generation and expert layer:
    the held experts' weights read once in the operands' precision (2
    bytes), each routed row read and written once at the hidden width (2
    bytes each way). Operations bound it at this cell's size. The rows that
    fill an expert's last block are time and not work."""
    d, width = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    tokens = evals * int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    expert_layers = sum(not dense for _, dense in layers_held(config))
    rows = tokens * held_choices_per_token(config) * expert_layers
    flops = rows * 2 * 3 * d * width
    generations = evals / float(traffic["pop"])
    weights = generations * expert_layers * int(config["num_experts"]) * 3 * d * width * 2
    return max(flops / peak["flops_per_s"], (weights + rows * 2 * d * 2) / peak["hbm_bytes_per_s"])

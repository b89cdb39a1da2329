"""Work counts of a token language model whose layers are of two kinds (KDA
and MLA, by the configuration's ``linear_attn_config``) under low-rank OpenES:
the operations and bytes its semantics need, from the configuration's shapes
and the traffic alone (never the program), so that a share of a peak counts
the same work whatever implements it. Reads the ``kimi_linear`` family's key
names; ``work_lm.py`` counts the ``deepseek_v3`` family, every layer MLA.

Counted per token of one member's forward pass; a multiply-accumulate is two
operations. A KDA layer: its projections (q, k, v, o, the two low-rank gates,
beta), the three short convolutions, and the delta rule's recurrence as its
semantics state it, a token, head and layer ``6 * keys * values`` (two
matrix-vector products and one rank-1 update) plus the decay's ``keys *
values``: what a chunked form adds to that (the chunk's triangular system) is
overhead, not work. An MLA layer as ``work_lm`` counts it, the scores at
``work_lm.expected_attended``. The routed experts at the expected share of a
token's choices that lands on held experts (``held / published * top k``: 0.5
of 8 at 16 of 256). The low-rank terms, norms, softmax and the search's own
ask and tell are left out.
"""

from __future__ import annotations

from benchmark.lib.work_lm import expected_attended


def layer_kinds(config: dict) -> list:
    """``"kda"`` or ``"mla"`` for each layer held (the pattern counts from 1)."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    return ["kda" if l in kda else "mla" for l in range(1, int(config["layers"]) + 1)]


def held_choices_per_token(config: dict) -> float:
    """Expected choices of a token that land on held experts under a uniform
    router."""
    return (float(config["num_experts_per_token"]) * float(config["num_experts"])
            / float(config["num_experts_published"]))


def kda_scan_flops_per_token(config: dict) -> int:
    """The recurrence of one KDA layer for one token, all heads."""
    linear = config["linear_attn_config"]
    return 7 * int(linear["num_heads"]) * int(linear["head_dim"]) ** 2


def kda_scan_bytes_per_token(config: dict, operand_bytes: int = 2) -> int:
    """What the recurrence of one KDA layer has to move for one token: q, k,
    v in and o out once in the operands' dtype, g and beta once in float32."""
    linear = config["linear_attn_config"]
    heads, width = int(linear["num_heads"]), int(linear["head_dim"])
    return heads * (4 * width * operand_bytes + 4 * width + 4)


def lm_flops_per_token(config: dict, traffic: dict) -> dict:
    """The parts of a token's forward pass, in operations: ``kda``
    (projections, convolutions and recurrence of every KDA layer), ``kda_scan``
    (the recurrences alone, a part of ``kda``), ``attention`` (the MLA layers'
    projections and scores), ``dense_mlp``, ``shared`` (with the router),
    ``experts``, ``head``, and their ``total`` (``kda_scan`` counted once)."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr, dv, dl = (int(config[k]) for k in
                      ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    linear = config["linear_attn_config"]
    wide, low = int(linear["num_heads"]) * int(linear["head_dim"]), int(linear["head_dim"])
    kinds = layer_kinds(config)
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    dense = int(config["first_k_dense_replace"])
    expert_layers = len(kinds) - dense
    kda_projections = 4 * d * wide + 2 * (d * low + low * wide) + d * int(linear["num_heads"])
    convolutions = 3 * wide * int(linear["short_conv_kernel_size"])
    mla_projections = d * h * (dn + dr) + d * (dl + dr) + dl * h * (dn + dv) + h * dv * d
    scores = h * (dn + dr + dv) * expected_attended(traffic)
    expert = 2 * 3 * d * int(config["moe_intermediate_size"])
    out = {
        "kda": n_kda * (2.0 * (kda_projections + convolutions) + kda_scan_flops_per_token(config)),
        "attention": 2.0 * n_mla * (mla_projections + scores),
        "dense_mlp": 2.0 * dense * 3 * d * int(config["intermediate_size"]),
        "shared": 2.0 * expert_layers * (
            3 * d * int(config["num_shared_experts"]) * int(config["moe_intermediate_size"])
            + d * int(config["num_experts_published"])
        ),
        "experts": expert_layers * held_choices_per_token(config) * expert,
        "head": 2.0 * d * int(config["vocab_size"]),
    }
    out["total"] = sum(out.values())
    out["kda_scan"] = float(n_kda * kda_scan_flops_per_token(config))
    return out


def lm_flops_per_eval(config: dict, traffic: dict) -> float:
    """One member's evaluation: its tokens times a token's operations."""
    tokens = int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    return tokens * lm_flops_per_token(config, traffic)["total"]


def kda_scan_least_seconds(config: dict, traffic: dict, evals: float, peak: dict) -> float:
    """The least time the chip could take for the KDA layers' recurrences of
    ``evals`` evaluations: ``max(flops / peak flops, bytes / peak
    bandwidth)``. Bandwidth bounds it at these shapes."""
    tokens = evals * int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    layers = layer_kinds(config).count("kda")
    flops = tokens * layers * kda_scan_flops_per_token(config)
    moved = tokens * layers * kda_scan_bytes_per_token(config)
    return max(flops / peak["flops_per_s"], moved / peak["hbm_bytes_per_s"])

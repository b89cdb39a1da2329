"""Work counts of the token language model under low-rank OpenES: the
operations and bytes its semantics need, from the configuration's shapes and
the traffic alone (never the program), so that a share of a peak counts the
same work whatever implements it.

Counted per token of one member's forward pass; a multiply-accumulate is two
operations. Attention's scores are counted causal and within a document, at
the documents' expected lengths under the traffic's distribution
(``expected_attended``). The routed experts are counted at the expected share
of a token's choices that lands on held experts (``held / published *
num_experts_per_tok``: 1.5 of 6 at 16 of 64). The low-rank terms, norms,
softmax and the search's own ask and tell are left out: they are overhead of
the method, not the member model's work.
"""

from __future__ import annotations

import math


def _normal_cdf(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def expected_attended(traffic: dict) -> float:
    """The expected number of keys a query attends to (itself included):
    for a token drawn uniformly from a long packing of documents whose
    lengths are log-normal (median, sigma, clipped to ``doc_len_min`` ..
    ``seq_len``), ``E[L (L + 1) / 2] / E[L]``. The cut of the last document
    at the row's end is left out (it shortens, so this overstates a little)."""
    mu, s = math.log(float(traffic["doc_len_median"])), float(traffic["doc_len_sigma"])
    lo, hi = float(traffic["doc_len_min"]), float(traffic["seq_len"])

    def partial(k: int, a: float, b: float) -> float:
        # E[L^k; a < L < b] of the log-normal
        if a >= b:
            return 0.0
        shift = mu + k * s * s
        za = -math.inf if a <= 0 else (math.log(a) - shift) / s
        zb = math.inf if b == math.inf else (math.log(b) - shift) / s
        return math.exp(k * mu + 0.5 * k * k * s * s) * (_normal_cdf(zb) - _normal_cdf(za))

    def moment(k: int) -> float:
        below = _normal_cdf((math.log(lo) - mu) / s)
        above = 1.0 - _normal_cdf((math.log(hi) - mu) / s)
        return lo**k * below + partial(k, lo, hi) + hi**k * above

    return 0.5 * (moment(2) / moment(1) + 1.0)


def held_choices_per_token(config: dict) -> float:
    """Expected choices of a token that land on held experts under a uniform
    router."""
    return (
        float(config["num_experts_per_tok"]) * float(config["n_routed_experts"])
        / float(config["n_routed_experts_published"])
    )


def expert_flops_per_row(config: dict) -> int:
    """One token through one routed expert: gate, up, down."""
    return 2 * 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"])


def lm_flops_per_token(config: dict, traffic: dict) -> dict:
    """The parts of a token's forward pass, in operations: ``attention``
    (projections and scores), ``dense_mlp``, ``shared`` (with the router),
    ``experts``, ``head``, and their ``total``."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr, dv, dl = (int(config[k]) for k in
                      ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    layers, dense = int(config["layers"]), int(config["first_k_dense_replace"])
    projections = d * h * (dn + dr) + d * (dl + dr) + dl * h * (dn + dv) + h * dv * d
    scores = h * (dn + dr + dv) * expected_attended(traffic)
    out = {
        "attention": 2.0 * layers * (projections + scores),
        "dense_mlp": 2.0 * dense * 3 * d * int(config["intermediate_size"]),
        "shared": 2.0 * (layers - dense) * (
            3 * d * int(config["n_shared_experts"]) * int(config["moe_intermediate_size"])
            + d * int(config["n_routed_experts_published"])
        ),
        "experts": (layers - dense) * held_choices_per_token(config) * expert_flops_per_row(config),
        "head": 2.0 * d * int(config["vocab_size"]),
    }
    out["total"] = sum(out.values())
    return out


def lm_flops_per_eval(config: dict, traffic: dict) -> float:
    """One member's evaluation: its tokens times a token's operations."""
    tokens = int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    return tokens * lm_flops_per_token(config, traffic)["total"]


def experts_least_seconds(config: dict, traffic: dict, evals: float, peak: dict) -> float:
    """The least time the chip could take for the held experts' products of
    ``evals`` evaluations: ``max(flops / peak flops, bytes / peak
    bandwidth)``. Bytes, for each generation and expert layer: the held
    experts' weights read once in the operands' precision (2 bytes), each
    routed row read and written once at the hidden width (2 bytes each way).
    Operations bound it at this cell's size."""
    tokens = evals * int(traffic["rows_per_member"]) * int(traffic["seq_len"])
    expert_layers = int(config["layers"]) - int(config["first_k_dense_replace"])
    rows = tokens * held_choices_per_token(config) * expert_layers
    flops = rows * expert_flops_per_row(config)
    generations = evals / float(traffic["pop"])
    weights = (generations * expert_layers * int(config["n_routed_experts"])
               * 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"]) * 2)
    rows_bytes = rows * 2 * int(config["hidden_size"]) * 2
    return max(flops / peak["flops_per_s"], (weights + rows_bytes) / peak["hbm_bytes_per_s"])

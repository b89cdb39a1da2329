"""Low-rank OpenES over a small language model with sparse experts: the tiny
cut of one of the benchmark's three language-model configurations (hidden 64,
8 experts of which 2 are held here, top 2, 5 layers, 32 held rows of a
vocabulary of 256), built through the constructor the benchmark's builders use
(``LMConfig.from_dict``, which tells the families apart by ``model_type``).

    python examples/lowrank_es_lm.py                  # moonlight_16b_a3b_es: latent attention (MLA) in every layer
    python examples/lowrank_es_lm.py kimi_linear      # kimi_linear_48b_a3b_es: 3 KDA layers to 1 MLA without RoPE
    python examples/lowrank_es_lm.py lfm2             # lfm2_24b_a2b_es: 3 gated short convolutions to 1 grouped-query attention

No member is ever a row of a population: ``ask`` hands ``evaluate`` a
perturbation spec, and the forward pass adds each member's ``sign * sigma *
(x A_p) B_p^T`` to the shared product. The tokens are uniform noise, so the
loss cannot fall below ``log(32)``; the script shows the path, not learning.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import LowRankOpenES
from evox_tpu.problems.lm import LMConfig, TokenLMProblem, init_params
from evox_tpu.utils import standardise

ROOT = Path(__file__).resolve().parents[1]
WIDTHS = {
    "hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "intermediate_size": 96, "moe_intermediate_size": 32,
    "experts_held": [0, 2], "vocab_size": 32,
}
# a family: its configuration file, and the cut under that family's own key names
FAMILIES = {
    "moonlight": ("moonlight_16b_a3b_es", {
        **WIDTHS, "n_routed_experts": 2, "n_routed_experts_published": 8, "num_experts_per_tok": 2}),
    "kimi_linear": ("kimi_linear_48b_a3b_es", {
        **WIDTHS, "num_experts": 2, "num_experts_published": 8, "num_experts_per_token": 2,
        "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
                               "head_dim": 16, "short_conv_kernel_size": 4}}),
    # 4 query heads on 2 key-value heads of 16; the file's own layers 1 to 5 of the published pattern
    "lfm2": ("lfm2_24b_a2b_es", {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 96,
        "moe_intermediate_size": 32, "experts_held": [0, 2], "vocab_size": 32, "num_experts": 2,
        "num_experts_published": 8, "num_experts_per_tok": 2}),
}

if __name__ == "__main__":
    name, tiny = FAMILIES[sys.argv[1] if len(sys.argv) > 1 else "moonlight"]
    config = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    cfg = LMConfig.from_dict({**config, **tiny})
    pop, key = 16, jax.random.PRNGKey(0)
    algo = LowRankOpenES(
        lambda: init_params(cfg, jax.random.fold_in(key, 1)), pop,
        learning_rate=config["learning_rate"], noise_stdev=config["noise_stdev"],
        rank=config["rank"], compute_dtype=jnp.bfloat16,
    )
    problem = TokenLMProblem(cfg, pop, seq_len=64, doc_len_median=12, doc_len_min=4,
                             blocks={"expert_block_rows": 16})
    wf = StdWorkflow(algo, problem, opt_direction="min", fit_transforms=(standardise,))
    state = wf.init(key)
    for _ in range(3):
        state = wf.run(state, 1)
        held, moved = state.prob.held, state.prob.moved
        print(f"generation {int(state.generation)}: mean loss {float(state.prob.losses.mean()):.4f}, "
              f"held assignments a layer {held.tolist()}, "
              f"rows moved over held {[round(float(v), 2) for v in moved / jnp.maximum(held, 1)]}, "
              f"imbalance {[round(float(v), 2) for v in state.prob.imbalance]}"
              + (f", KDA layers keep {[round(float(v), 3) for v in state.prob.kda_retention]} of their state a token"
                 if cfg.kda_layers else "")
              + (f", convolution layers' gain {[float(f'{float(v):.3g}') for v in state.prob.conv_gain[:, 0]]}"
                 if cfg.conv_layers else ""))

"""What the token language model's tests share: each family's tiny cut, the
traffic and blocks they run at, the ``body`` fixture, the workflow and its
snapshots, and the laws every family is held to against its plain reference
(``benchmark/reference``).

A family's laws sit in a test file of their own (``test_token_lm_<family>.py``):
each case builds and compiles a run loop or a ``forward``, and under
``--dist loadfile`` one file is one worker's unit, so the families run on
different workers. The laws that compile little stay in ``test_token_lm.py``,
and so does the KDA kernels' law through ``forward``: xdist hands out the
files with the most cases first, so a long law beside many short ones starts
at once, where a file of a few long cases starts last."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_48b_a3b_es as ref_kimi
from benchmark.reference import lfm2_24b_a2b_es as ref_lfm2
from benchmark.reference import moonlight_16b_a3b_es as ref
from benchmark.reference import ouro_2_6b_es as ref_ouro
from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import LowRankOpenES
from evox_tpu.core.lowrank import tree_factors
from evox_tpu.problems.lm import LMConfig, TokenLMProblem, init_params
from evox_tpu.problems.lm import model as lm
from evox_tpu.utils import standardise

# hidden 64, 2 heads, 8 experts of which 2 held, top 2, 5 layers, vocabulary
# 256 of which 32 held: the cut tests/benchmark_checks/conftest.py enters
TINY = dict(
    hidden_size=64, num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=24, intermediate_size=96, moe_intermediate_size=32,
    n_shared_experts=2, n_routed_experts=2, n_routed_experts_published=8, experts_held=[0, 2],
    num_experts_per_tok=2, routed_scaling_factor=2.446, first_k_dense_replace=1, layers=5,
    vocab_size=32, rms_norm_eps=1e-5, rope_theta=50000, init_std=0.02, rank=1,
    noise_stdev=0.001, learning_rate=0.0005, probe_positions=64,
)
# the same cut of the kimi_linear family (tests/benchmark_checks/test_kimi_linear_cell.py enters it): KDA of 2
# heads x 16 with 4 taps in layers 1, 2, 3, 5 as the pattern counts them, MLA unrotated in layer 4, one shared expert
TINY_KIMI = dict(
    model_type="kimi_linear", hidden_size=64, num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=24, intermediate_size=96, moe_intermediate_size=32, num_shared_experts=1,
    num_experts=2, num_experts_published=8, experts_held=[0, 2], num_experts_per_token=2,
    routed_scaling_factor=2.446, first_k_dense_replace=1, layers=5, vocab_size=32, rms_norm_eps=1e-5,
    init_std=0.02, mla_use_nope=True, moe_renormalize=True, moe_router_activation_func="sigmoid",
    use_grouped_topk=True, num_expert_group=1, topk_group=1,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=2, head_dim=16,
                            short_conv_kernel_size=4),
    rank=1, noise_stdev=0.001, learning_rate=0.0005, probe_positions=64,
)
# the cut of the lfm2_moe family (tests/benchmark_checks/test_lfm2_cell.py enters it): layers 1 to 5 of the pattern
# counted from 0 (conv + dense MLP; attention, conv, conv, conv + experts), 4 query heads on 2 key-value heads of 16,
# 3 taps, no shared expert
TINY_LFM2 = dict(
    model_type="lfm2_moe", hidden_size=64, num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    conv_bias=False, intermediate_size=96, moe_intermediate_size=32, num_experts=2, num_experts_published=8,
    experts_held=[0, 2], num_experts_per_tok=2, routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
    num_dense_layers=2, layer_types=["conv", "conv", "full_attention", "conv"] * 2, layers=5, layers_held=[1, 6],
    vocab_size=32, norm_eps=1e-5, rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, init_std=0.02,
    rank=1, noise_stdev=0.001, learning_rate=0.0005, probe_positions=64,
)
# the cut of the ouro family (tests/benchmark_checks/test_ouro_cell.py enters it): 2 layers held of the 48, run 4
# times, 4 heads of 16 on 4 key-value heads, sandwich norms, the exit gate
TINY_OURO = dict(
    model_type="ouro", hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, hidden_act="silu", num_hidden_layers=48, layer_types=["full_attention"] * 48, layers=2,
    layers_held=[0, 2], total_ut_steps=4, early_exit_threshold=1, vocab_size=32, rms_norm_eps=1e-6,
    rope_theta=1000000, rope_scaling=None, use_sliding_window=False, tie_word_embeddings=False, init_std=0.02,
    rank=1, noise_stdev=0.001, learning_rate=0.0005, probe_positions=64,
)
FAMILIES = {"deepseek_v3": (TINY, ref), "kimi_linear": (TINY_KIMI, ref_kimi), "lfm2_moe": (TINY_LFM2, ref_lfm2),
            "ouro": (TINY_OURO, ref_ouro)}
TRAFFIC = dict(pop=8, seq_len=48, doc_len_median=12, doc_len_sigma=1.0, doc_len_min=4,
               rows_per_member=1)
BLOCKS = {"expert_block_rows": 8, "chunk_pairs": 2, "attn_block_pairs": 2, "kda_block_pairs": 2}
SEED = 2**33 + 5


@pytest.fixture(params=("plain", "kernel"))
def body(request, monkeypatch):
    """The two bodies of the token mixers: the plain ones, which the CPU
    backend takes (attention's scores in HBM, KDA's chunk arithmetic in XLA),
    and the kernels (``flash_attention``, ``gqa_flash_attention``, ``kda_scan``,
    interpreted here), which the TPU backend takes at shapes they accept; the choice is steered
    here, not by an option. The scan's chunks are 16 tokens here, so that a
    row of 48 carries its state across two chunk ends."""
    monkeypatch.setattr(lm, "KDA_CHUNK", 16)
    if request.param == "kernel":
        monkeypatch.setattr(lm, "_flash_blocks", lambda cfg, t: (8, 8))
        monkeypatch.setattr(lm, "_gqa_blocks", lambda cfg, t: (8, 8))
        monkeypatch.setattr(lm, "_kda_kernel", lambda cfg: True)
    return request.param


def _workflow(config=TINY, compute_dtype=None, rank=1):
    cfg = LMConfig.from_dict(config)
    key = ref._key(SEED)
    algo = LowRankOpenES(
        init_params(cfg, jax.random.fold_in(key, 1)), TRAFFIC["pop"], rank=rank,
        learning_rate=config["learning_rate"], noise_stdev=config["noise_stdev"],
        compute_dtype=compute_dtype,
    )
    problem = TokenLMProblem(cfg, TRAFFIC["pop"], TRAFFIC["seq_len"], doc_len_median=12,
                             doc_len_min=4, blocks=BLOCKS)
    wf = StdWorkflow(algo, problem, opt_direction="min", fit_transforms=(standardise,))
    return wf, key


def _snapshots(wf, key, steps=2):
    # steady from the start: one compiled run loop serves every call
    state, snaps = wf.init(key).replace(first_step=False), []
    for _ in range(steps):
        state = wf.run(state, 1)
        snaps.append({
            "generation": int(state.generation),
            "center": [np.asarray(v) for v in jax.tree.leaves(state.algo.center)],
            "fitness": np.asarray(state.algo.fitness),
            "losses": np.asarray(state.prob.losses),
            "probe": np.asarray(state.prob.probe),
            "held": np.asarray(state.prob.held),
            "kda_retention": np.asarray(state.prob.kda_retention),
            "conv_gain": np.asarray(state.prob.conv_gain),
            "exit_pdf": np.asarray(state.prob.exit_pdf),
            "loop_gain": np.asarray(state.prob.loop_gain),
        })
    return snaps


def _forward(cfg, center, ids, doc, pos, pairs=2):
    factors = tree_factors(jax.random.PRNGKey(4), center, pairs, 1)
    return lm.forward(cfg, center, factors, jnp.float32(1e-3), ids, doc, pos, 8, {**lm.DEFAULT_BLOCKS, **BLOCKS})


# (rank, layers, steps) of the float32 law: rank 2 at three layers for the two older families; the third runs its
# five layers only, the looped family its two four times (each case compiles a run loop)
FLOAT32_CASES = {
    "deepseek_v3": [(1, 5, 2), (2, 3, 1)],
    "kimi_linear": [(1, 5, 2), (2, 3, 1)],
    "lfm2_moe": [(1, 5, 2)],
    "ouro": [(1, 2, 2)],
}


def program_agrees_with_the_reference_in_float32(family, rank, layers, steps, built=None):
    """Logits, losses, routing and centre (for ``kimi_linear`` the hybrid
    pattern at five layers, and what the KDA layers keep of their state; for
    ``lfm2_moe`` the convolutions' gains; for ``ouro`` the exit gate's
    probabilities and the steps' gains), through ``StdWorkflow.run``, a
    second generation from the first's centre. ``built``: the workflow of
    this configuration, where the caller already holds it."""
    tiny, reference = FAMILIES[family]
    config = dict(tiny, rank=rank, layers=layers)
    wf, key = built or _workflow(config, rank=rank)
    snaps = _snapshots(wf, key, steps)
    generations = list(range(1, steps + 1))
    want = reference.follow(config, TRAFFIC, SEED, generations, program=snaps)
    for got, w in zip(snaps, want):
        np.testing.assert_allclose(got["losses"], w["losses"], rtol=0, atol=2e-6)
        np.testing.assert_allclose(got["probe"], w["probe"], rtol=0, atol=2e-5)
        np.testing.assert_array_equal(got["held"], w.get("held", np.zeros((0,))))  # a dense model holds no expert
        assert w["center_step"] > 0 and w["center_diff"] < 1e-5 * w["center_step"]
    numbers = reference.numbers(config, snaps, want)
    assert all(numbers[f"step{k}_generation_off"] == 0 for k in generations)
    assert max(numbers[f"step{k}_logit_err"] for k in generations) < 1e-5
    if family == "kimi_linear":
        assert snaps[0]["kda_retention"].shape == ({5: 4, 3: 3}[layers],)  # layer 4 of the pattern is MLA
        assert max(numbers[f"step{k}_retention_err"] for k in generations) < 1e-5
        assert np.all((0 < snaps[0]["kda_retention"]) & (snaps[0]["kda_retention"] < 1))
    if family == "lfm2_moe":
        assert snaps[0]["conv_gain"].shape == (4, 2) and np.all(snaps[0]["conv_gain"] > 0)
        # float32 sums of squares in another order than the reference's
        assert max(numbers[f"step{k}_conv_gain_err"] for k in generations) < 1e-5
    if family == "ouro":
        assert snaps[0]["exit_pdf"].shape == snaps[0]["loop_gain"].shape == (4,)
        assert max(numbers[f"step{k}_exit_err"] for k in generations) < 1e-6
        assert max(numbers[f"step{k}_loop_gain_err"] for k in generations) < 1e-5


def bfloat16_operands_stay_near_the_reference(family):
    tiny, reference = FAMILIES[family]
    wf, key = _workflow(tiny, compute_dtype=jnp.bfloat16)
    snaps = _snapshots(wf, key, steps=1)
    numbers = reference.numbers(tiny, snaps, reference.follow(tiny, TRAFFIC, SEED, [1], program=snaps))
    assert numbers["step1_logit_err"] < 0.02 and numbers["step1_center_err"] < 1e-5


def a_token_sees_only_its_document_and_its_past(family):
    """The prefix property: the logits at a position do not change when a
    later token, or a token of an earlier document, changes. In a KDA layer
    that is the convolution's taps and the scan's state, both reset where
    document 1 starts, inside the scan's first chunk of 16."""
    cfg = LMConfig.from_dict(FAMILIES[family][0])
    center = init_params(cfg, jax.random.PRNGKey(1))
    t = 24
    ids = jax.random.randint(jax.random.PRNGKey(2), (t,), 0, cfg.vocab_size)
    doc = jnp.asarray([0] * 10 + [1] * 14)
    pos = jnp.concatenate([jnp.arange(10), jnp.arange(14)])
    probe = jax.jit(lambda ids: _forward(cfg, center, ids, doc, pos)["probe"])
    changed = lambda at: ids.at[at].set((ids[at] + 1) % cfg.vocab_size)
    base = probe(ids)  # the last 8 positions, of document 1
    later = probe(changed(t - 1))
    np.testing.assert_array_equal(base[:, :-1], later[:, :-1])
    assert not np.array_equal(base[:, -1], later[:, -1])
    np.testing.assert_array_equal(base, probe(changed(3)))  # document 0 is not seen from document 1
    assert not np.array_equal(base, probe(changed(12)))

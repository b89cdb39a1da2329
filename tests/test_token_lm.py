"""The token language model under low-rank OpenES, at a tiny size on the CPU:
the program against the plain references (``benchmark/reference``) on seeded
weights for the three families (``deepseek_v3``: every layer MLA with RoPE;
``kimi_linear``: KDA and unrotated MLA by the pattern; ``lfm2_moe``: gated
short convolutions and grouped-query attention, no shared expert), the properties the
member model has to have, and ``LowRankOpenES`` against ``OpenES`` on
materialised members."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_48b_a3b_es as ref_kimi
from benchmark.reference import lfm2_24b_a2b_es as ref_lfm2
from benchmark.reference import moonlight_16b_a3b_es as ref
from benchmark.reference import ouro_2_6b_es as ref_ouro
from evox_tpu import StdWorkflow
from evox_tpu.algorithms.so.es import LowRankOpenES, OpenES
from evox_tpu.core.lowrank import LowRankPopulation, tree_factors
from evox_tpu.problems.lm import LMConfig, TokenLMProblem, init_params, packed_row
from evox_tpu.problems.lm import model as lm
from evox_tpu.utils import standardise

ROOT = Path(__file__).resolve().parents[1]
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


# rank 2 at three layers for the two older families; the third runs its five layers only, the looped family its
# two four times (each case compiles a run loop)
@pytest.mark.parametrize("rank,layers,steps,family", [
    *((1, 5, 2, family) for family in FAMILIES if family != "ouro"), (2, 3, 1, "deepseek_v3"),
    (2, 3, 1, "kimi_linear"), (1, 2, 2, "ouro")])
def test_program_agrees_with_the_reference_in_float32(rank, layers, steps, family, body):
    """Logits, losses, routing and centre (for ``kimi_linear`` the hybrid
    pattern at five layers, and what the KDA layers keep of their state; for
    ``lfm2_moe`` the convolutions' gains; for ``ouro`` the exit gate's
    probabilities and the steps' gains), through ``StdWorkflow.run``, a
    second generation from the first's centre."""
    tiny, reference = FAMILIES[family]
    config = dict(tiny, rank=rank, layers=layers)
    wf, key = _workflow(config, rank=rank)
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


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bfloat16_operands_stay_near_the_reference(family, body):
    tiny, reference = FAMILIES[family]
    wf, key = _workflow(tiny, compute_dtype=jnp.bfloat16)
    snaps = _snapshots(wf, key, steps=1)
    numbers = reference.numbers(tiny, snaps, reference.follow(tiny, TRAFFIC, SEED, [1], program=snaps))
    assert numbers["step1_logit_err"] < 0.02 and numbers["step1_center_err"] < 1e-5


def _forward(cfg, center, ids, doc, pos, pairs=2):
    factors = tree_factors(jax.random.PRNGKey(4), center, pairs, 1)
    return lm.forward(cfg, center, factors, jnp.float32(1e-3), ids, doc, pos, 8, {**lm.DEFAULT_BLOCKS, **BLOCKS})


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_token_sees_only_its_document_and_its_past(family, body):
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


def _row(t=48, seed=2, vocab=32):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (t,), 0, vocab)
    lengths = [3, 1, 2, 19, 23]  # starts inside a tap's reach of each other, and inside the scan's chunks of 16
    doc = jnp.repeat(jnp.arange(len(lengths)), jnp.asarray(lengths), total_repeat_length=t)
    pos = jnp.concatenate([jnp.arange(n) for n in lengths])
    return ids, doc, pos


@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
def test_forward_with_kdas_kernels_is_forward_with_the_plain_bodies(dtype, monkeypatch):
    """The hybrid cut through ``forward`` twice: as the CPU backend takes it
    (``short_conv``, the norms, the chunk arithmetic in XLA) and as the TPU
    backend does (``kda_conv`` and ``kda_scan``, interpreted here): the
    losses, the probe and what the KDA layers keep of their state agree within
    what the reference is held to."""
    monkeypatch.setattr(lm, "KDA_CHUNK", 16)
    cfg = LMConfig.from_dict(TINY_KIMI)
    center = init_params(cfg, jax.random.PRNGKey(1))
    if dtype == "bfloat16":
        center = jax.tree.map(lambda v: v.astype(jnp.bfloat16) if v.ndim >= 2 else v, center)
    ids, doc, pos = _row()
    run = lambda: jax.jit(lambda ids: _forward(cfg, center, ids, doc, pos))(ids)
    plain = run()
    seen = []
    conv = lm.kda_conv
    monkeypatch.setattr(lm, "kda_conv", lambda *a, **k: (seen.append(k["normalise"]), conv(*a, **k))[1])
    monkeypatch.setattr(lm, "_kda_kernel", lambda cfg: True)
    kernels = run()
    assert seen == ["l2_scaled", "l2", None] * cfg.kda_layers  # q, k, v of every KDA layer, once a traced block
    tol = {"float32": (2e-6, 2e-5, 1e-6), "bfloat16": (0.02, 0.05, 1e-3)}[dtype]
    np.testing.assert_allclose(kernels["losses"], plain["losses"], rtol=0, atol=tol[0])
    np.testing.assert_allclose(kernels["probe"], plain["probe"], rtol=0, atol=tol[1])
    np.testing.assert_allclose(kernels["kda_retention"], plain["kda_retention"], rtol=0, atol=tol[2])
    np.testing.assert_array_equal(kernels["held"], plain["held"])


def test_a_model_without_kda_layers_never_reaches_kdas_kernels(monkeypatch):
    """Moonlight's path: a configuration whose pattern has no KDA layer lowers
    to the same text whether KDA's kernels would be chosen or not, and with
    ``kda_conv`` and ``kda_scan`` taken away."""
    cfg = LMConfig.from_dict(TINY)
    center = init_params(cfg, jax.random.PRNGKey(1))
    ids, doc, pos = _row()
    lower = lambda: jax.jit(lambda ids: _forward(cfg, center, ids, doc, pos)).lower(ids).as_text()
    base = lower()

    def gone(*a, **k):
        raise AssertionError("a KDA kernel was reached")

    monkeypatch.setattr(lm, "_kda_kernel", lambda cfg: True)
    monkeypatch.setattr(lm, "kda_conv", gone)
    monkeypatch.setattr(lm, "kda_scan", gone)
    assert lower() == base


def test_attn_blocks_is_the_visited_share_of_a_dense_causal_pass(body):
    """The counter against a count from the dense mask: key blocks that hold
    a key some query of the block attends, over the blocks at or under the
    diagonal. The plain body makes the whole row as one block."""
    cfg = LMConfig.from_dict(TINY)
    center = init_params(cfg, jax.random.PRNGKey(1))
    t, b = 48, 8
    ids, doc, pos = packed_row(jax.random.PRNGKey(3), t, cfg.vocab_size, 6.0, 1.0, 2)
    got = float(_forward(cfg, center, ids, doc, pos)["attn_blocks"])
    at, d = np.arange(t), np.asarray(doc)
    mask = (at[:, None] >= at[None, :]) & (d[:, None] == d[None, :])
    seen = mask.reshape(t // b, b, t // b, b).any(axis=(1, 3)).sum()
    assert seen < 21  # some block under the diagonal is skipped in this row
    assert got == pytest.approx(seen / 21 if body == "kernel" else 1.0)
    wf, key = _workflow()
    state = wf.run(wf.init(key).replace(first_step=False), 1)
    assert 0.0 < float(state.prob.attn_blocks) <= 1.0


# family, the key that counts the experts held, the router's width, a share, the choices a token
SHARES = {
    "8_experts_top_2": ("deepseek_v3", "n_routed_experts", 8, 2, 2),
    "256_experts_top_8": ("kimi_linear", "num_experts", 256, 64, 8),
    "64_experts_top_4_no_shared_expert": ("lfm2_moe", "num_experts", 64, 16, 4),
}


@pytest.mark.parametrize("case", list(SHARES))
def test_the_shares_of_an_expert_layer_add_up(case):
    """The outputs of the expert layer for every share of the experts (0-1,
    2-3, 4-5 and 6-7 of 8; four shares of 64 of a 256-wide router at top 8;
    experts 0-15, 16-31, 32-47, 48-63 of a 64-wide router at top 4, the
    published proportion of ``lfm2_24b_a2b_es``), the shared MLP counted once
    (``lfm2_moe`` has none to count: the routed parts alone add up), sum to
    the uncut reference's layer."""
    family, held_key, width, share, top = SHARES[case]
    tiny, reference = FAMILIES[family]
    top_key = "num_experts_per_token" if family == "kimi_linear" else "num_experts_per_tok"
    tiny = {**tiny, held_key + "_published": width, top_key: top}
    whole = {**tiny, held_key: width, "experts_held": [0, width]}
    full = reference._init(whole, jax.random.PRNGKey(7))["layers"][1]
    for mixer in ("attn", "kda", "conv", "gqa"):
        full.pop(mixer, None)
    pairs, t, d = 2, 16, tiny["hidden_size"]
    xn = jax.random.normal(jax.random.PRNGKey(8), (pairs, 2, t, d))
    no_noise = lambda tree: jax.tree.map(
        lambda v: (jnp.zeros((pairs,) + v.shape[:-2] + (v.shape[-2], 1)),
                   jnp.zeros((pairs,) + v.shape[:-2] + (v.shape[-1], 1))) if v.ndim >= 2 else None,
        tree,
    )
    total = 0.0
    for lo in range(0, width, share):
        cfg = LMConfig.from_dict({**tiny, held_key: share, "experts_held": [lo, lo + share]})
        p = dict(full, experts=jax.tree.map(lambda v: v[lo : lo + share], full["experts"]))
        shared, routed, loads, _ = lm.expert_layer(
            cfg, p, no_noise(p), jnp.float32(0.0), xn, {**lm.DEFAULT_BLOCKS, **BLOCKS}
        )
        total = total + routed
        assert int(jnp.sum(loads)) > 0 and (shared is None) == (family == "lfm2_moe")
    if shared is not None:
        total = total + shared

    # the uncut layer, plainly: every chosen expert of all of them
    x = xn.reshape(-1, d)
    score = jax.nn.sigmoid(x @ full["router"])
    _, idx = jax.lax.top_k(score + full["router_bias"], top)
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    renorm = jnp.sum(chosen, axis=-1, keepdims=True) + (1e-6 if family == "lfm2_moe" else 0.0)
    weight = tiny["routed_scaling_factor"] * chosen / renorm
    want = ref._swiglu(x, full["shared"]) if "shared" in full else 0.0
    for e in range(width):
        mine = jnp.sum(jnp.where(idx == e, weight, 0), axis=-1)
        want = want + mine[:, None] * ref._swiglu(x, jax.tree.map(lambda v: v[e], full["experts"]))
    np.testing.assert_allclose(total.reshape(-1, d), want, rtol=0, atol=1e-5)


# ---------------------------------------------------------------- the routed path
# 24 tokens (one pair of 12), 8 experts, two choices a token, blocks of 8 rows;
# experts 2, 3, 4 held unless a case says otherwise
ROUTED = dict(TINY, hidden_size=16, moe_intermediate_size=8, n_routed_experts=3, experts_held=[2, 5])
ROUTED_T, ROUTED_ROWS = 12, 8


def _choices(chosen: dict, rest=(0, 1)) -> np.ndarray:
    """``(24, 2)`` expert ids: ``chosen[token]`` where given, else ``rest``."""
    idx = np.tile(np.asarray(rest, np.int32), (2 * ROUTED_T, 1))
    for token, pair in chosen.items():
        idx[token] = pair
    return idx


HELD_CASES = {
    # expert 3 is held and nobody chooses it
    "an_expert_with_no_row": (ROUTED, _choices({t: (2, 4) for t in range(0, 24, 3)})),
    # expert 2: 8 rows, one full block; expert 3: 9 rows, a block and one row; expert 4: none
    "a_full_block_and_one_row_more": (
        ROUTED, _choices({**{t: (2, 7) for t in range(8)}, **{t: (6, 3) for t in range(10, 19)}})),
    "one_row_in_all": (ROUTED, _choices({17: (5, 4)})),
    "every_choice_absent": (ROUTED, _choices({t: (5, 7) for t in range(0, 24, 2)})),
    "every_choice_held": (
        dict(ROUTED, n_routed_experts=8, experts_held=[0, 8]),
        np.stack([np.arange(24) % 8, (np.arange(24) * 3 + 1) % 8], axis=1).astype(np.int32)),
    # The double count: expert 2 has rows 1, 5, 9 and its one block's tail holds the first five
    # rows of expert 3, tokens 5, 6, 7, 9, 11: tokens 5 and 9 are live rows of that block AND tail
    # rows of it, and live rows again in expert 3's own block, which comes next.
    "a_token_live_in_a_block_and_in_its_tail": (
        ROUTED, _choices({1: (2, 0), 5: (3, 2), 9: (2, 3), 6: (3, 7), 7: (1, 3), 11: (3, 4), 20: (4, 3)})),
}


def _routed_case(config, idx, dtype=jnp.float32):
    cfg = LMConfig.from_dict(config)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    experts = ref._init(dict(config, n_routed_experts=cfg.n_held), keys[0])["layers"][1]["experts"]
    xn = jax.random.normal(keys[1], (1, 2, ROUTED_T, cfg.hidden_size)).astype(dtype)
    w = jax.random.uniform(keys[2], idx.shape, minval=0.2, maxval=1.5)
    p = {"experts": jax.tree.map(lambda v: v.astype(dtype), experts)}
    f = {"experts": jax.tree.map(lambda v: (jnp.zeros((1,) + v.shape[:-1] + (1,)),
                                             jnp.zeros((1, v.shape[0], v.shape[2], 1))), experts)}
    return cfg, p, f, xn, jnp.asarray(idx), w


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_held_experts_is_the_plain_weighted_sum_over_the_held_experts(case, monkeypatch):
    """``held_experts`` against a float32 loop over the held experts with
    masks, the loads and ``moved`` exactly; and, block by block, each add
    names the block's live rows and no other row of the sums (the held rows
    are put back once each), and whatever it tells the compiler of its rows
    (ascending, none named twice) is true: a claim no sum on the CPU would
    test, its scatter being serial."""
    config, idx = HELD_CASES[case]
    cfg, p, f, xn, idx, w = _routed_case(config, idx)
    lo, hi = cfg.experts_held
    got, loads, moved = jax.jit(
        lambda *a: lm.held_experts(cfg, *a, ROUTED_ROWS))(p, f, jnp.float32(0.0), xn, idx, w)

    x = xn.reshape(-1, cfg.hidden_size)
    want = jnp.zeros_like(x)
    for e in range(lo, hi):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        want = want + mine[:, None] * ref._swiglu(x, jax.tree.map(lambda v: v[e - lo], p["experts"]))
    np.testing.assert_allclose(got.reshape(x.shape), want, rtol=0, atol=1e-6)
    counts = np.asarray([(np.asarray(idx) == e).sum() for e in range(lo, hi)])
    np.testing.assert_array_equal(loads, counts)
    assert int(moved) == int(np.sum(-(-counts // ROUTED_ROWS)) * ROUTED_ROWS)

    bind, seen = jax.lax.scatter_add_p.bind, []

    def honest(operand, rows, updates, **params):
        if not isinstance(rows, jax.core.Tracer):  # the call on values; its dispatch binds once more, traced
            at = np.asarray(rows).reshape(-1)
            assert not params["indices_are_sorted"] or np.all(np.diff(at) >= 0), at
            assert not params["unique_indices"] or len(set(at.tolist())) == at.size, at
            seen.append(int(np.sum(at < operand.shape[0])))
        return bind(operand, rows, updates, **params)

    monkeypatch.setattr(jax.lax.scatter_add_p, "bind", honest)
    with jax.disable_jit():  # the loop runs block by block, every operation on its values
        eager, _, _ = lm.held_experts(cfg, p, f, jnp.float32(0.0), xn, idx, w, ROUTED_ROWS)
    monkeypatch.undo()
    assert sum(seen) == counts.sum() and len(seen) * ROUTED_ROWS == int(moved)  # each held row put back once
    np.testing.assert_allclose(eager, got, rtol=0, atol=1e-6)


# the three families' routers, (width, choices): Moonlight's, the hybrid cell's, the third cell's
ROUTERS = {"deepseek_v3": (64, 6), "kimi_linear": (256, 8), "lfm2_moe": (64, 4)}
ROUTER_T = 20


@jax.jit
def _scores(p, f, xn):
    return jax.nn.sigmoid(lm.linear(xn, p["router"], f["router"], jnp.float32(0.5), jnp.float32))


def _router_case(width: int, k: int, case: str, eps: float = 0.0):
    """A router of ``width`` experts choosing ``k`` over ``2 * ROUTER_T``
    tokens, with low-rank factors. ``"no_bias"``: a zero correction bias;
    ``"bias"``: a nonzero one; ``"ties"``: experts 8 to 28 are seven triples
    with one router column and one bias each (ties in ``s`` and in ``s +
    b``), and experts 0 to 3 take biases of about 4, all four among every
    token's chosen, with expert 1's and 3's set so that ``s + b`` ties
    exactly, though ``s`` differs, with expert 0's for token 0 and with
    expert 2's for token 1."""
    cfg = dataclasses.replace(
        LMConfig.from_dict(dict(TINY, n_routed_experts_published=width, num_experts_per_tok=k)), router_eps=eps)
    keys = jax.random.split(jax.random.PRNGKey(width + k), 4)
    d = cfg.hidden_size
    xn = jax.random.normal(keys[0], (1, 2, ROUTER_T, d))
    router = np.array(jax.random.normal(keys[1], (d, width)) * 0.5)
    bias = np.zeros(width, np.float32) if case == "no_bias" else np.array(jax.random.normal(keys[2], (width,)) * 0.05)
    if case == "ties":
        for e in range(8, 29, 3):
            router[:, e + 1] = router[:, e + 2] = router[:, e]
            bias[e + 1] = bias[e + 2] = bias[e]
    f = {"router": tree_factors(keys[3], {"r": jnp.zeros((d, width))}, 1, 1)["r"]}
    p = {"router": jnp.asarray(router), "router_bias": jnp.asarray(bias)}
    if case == "ties":
        s = np.asarray(_scores(p, f, xn)).reshape(-1, width)
        for token, (lo, hi) in enumerate([(0, 1), (2, 3)]):
            bias[lo] = 4.0
            v = np.float32(s[token, lo] + bias[lo])
            near = np.float32(v - s[token, hi])
            fits = [b for b in (near, np.nextafter(near, np.float32(9)), np.nextafter(near, np.float32(-9)))
                    if np.float32(s[token, hi] + b) == v]
            assert fits, "no bias ties the two"
            bias[hi] = fits[0]
        p["router_bias"] = jnp.asarray(bias)
    return cfg, p, f, xn


@pytest.mark.parametrize("eps", [0.0, 1e-6])
@pytest.mark.parametrize("case", ["no_bias", "bias", "ties"])
@pytest.mark.parametrize("family", list(ROUTERS))
def test_route_is_top_k_and_the_chosen_scores_bit_for_bit(family, case, eps):
    """``route``'s ``(idx, w)`` are, bit for bit, the formula: ``lax.top_k``
    of ``s + b`` (descending, ties to the lower expert), the chosen ``s`` read
    at those experts, their float32 sum in that order, ``router_eps`` and the
    scaling, whatever the bias and wherever ``s + b`` ties."""
    cfg, p, f, xn = _router_case(*ROUTERS[family], case, eps)

    @jax.jit
    def formula(p, f, xn):
        s = _scores(p, f, xn)
        _, idx = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        if eps:
            total = total + eps
        return idx, cfg.routed_scaling_factor * chosen / total

    got = jax.jit(lambda p, f, xn: lm.route(cfg, p, f, jnp.float32(0.5), xn))(p, f, xn)
    want = formula(p, f, xn)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(np.asarray(got[1]).view(np.uint32), np.asarray(want[1]).view(np.uint32))
    if case == "ties":  # the planted ties are among the chosen and the lower expert comes first
        idx = np.asarray(got[0]).reshape(-1, cfg.num_experts_per_tok)
        for token, (lo, hi) in enumerate([(0, 1), (2, 3)]):
            at = list(idx[token])
            assert lo in at and hi in at and at.index(lo) + 1 == at.index(hi), at


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


def test_the_routed_path_sorts_once_and_keeps_no_row_for_every_assignment():
    """The law of the routed path: one ``sort``; nothing it makes is as
    large as ``n * k`` rows of width ``hidden``, however it is shaped;
    ``moved`` is the blocks of the loop
    times their rows, between ``held`` and ``held`` plus what can fill each
    held expert's last block."""
    cfg, p, f, xn, _, _ = _routed_case(ROUTED, _choices({}), jnp.bfloat16)
    f["router"], p["router"] = None, jax.random.normal(jax.random.PRNGKey(12), (cfg.hidden_size, 8)).astype(xn.dtype)
    p["router_bias"] = jnp.zeros((8,))
    idx, w = lm.route(cfg, p, f, jnp.float32(0.0), xn)
    fn = lambda p, f, xn, idx, w: lm.held_experts(cfg, p, f, jnp.float32(1e-3), xn, idx, w, ROUTED_ROWS)
    n, k, d = 2 * ROUTED_T, cfg.num_experts_per_tok, cfg.hidden_size
    eqns = [e for j in _sub_jaxprs(jax.make_jaxpr(fn)(p, f, xn, idx, w).jaxpr) for e in j.eqns]
    assert sum(e.primitive.name == "sort" for e in eqns) == 1
    wide = [v.aval.shape for e in eqns for v in e.outvars if np.prod(v.aval.shape) >= n * k * d]
    assert not wide, wide
    _, loads, moved = fn(p, f, xn, idx, w)
    held = int(jnp.sum(loads))
    assert held > 0 and int(moved) == int(jnp.sum(-(-loads // ROUTED_ROWS)) * ROUTED_ROWS)
    assert held <= int(moved) <= held + cfg.n_held * (ROUTED_ROWS - 1)

    wf, key = _workflow()
    state = wf.run(wf.init(key).replace(first_step=False), 1)
    held, moved = np.asarray(state.prob.held), np.asarray(state.prob.moved)
    chunks = TRAFFIC["pop"] // 2 // BLOCKS["chunk_pairs"]
    fill = chunks * TINY["n_routed_experts"] * (BLOCKS["expert_block_rows"] - 1)
    assert np.all(held > 0) and np.all(moved % BLOCKS["expert_block_rows"] == 0)
    assert np.all(held <= moved) and np.all(moved <= held + fill)


@pytest.mark.parametrize("family", list(ROUTERS))
def test_route_gathers_nothing(family):
    """The law of the router: each chosen score is read by a compare and
    select over the experts, never by a gather of scalars (which the chip
    runs as a serial loop); so ``route``'s jaxpr, its low-rank term's
    included, holds no ``gather``."""
    cfg, p, f, xn = _router_case(*ROUTERS[family], "bias")
    jaxpr = jax.make_jaxpr(lambda p, f, xn: lm.route(cfg, p, f, jnp.float32(0.5), xn))(p, f, xn).jaxpr
    names = {e.primitive.name for j in _sub_jaxprs(jaxpr) for e in j.eqns}
    assert "gather" not in names, names


def test_packed_row_packs_documents_until_the_row_is_full():
    ids, doc, pos = packed_row(jax.random.PRNGKey(5), 256, 32, 12.0, 1.0, 4)
    doc, pos = np.asarray(doc), np.asarray(pos)
    assert ids.shape == (256,) and int(ids.min()) >= 0 and int(ids.max()) < 32
    assert doc[0] == 0 and np.all(np.diff(doc) >= 0) and np.all(np.diff(doc) <= 1)
    starts = np.flatnonzero(np.diff(doc, prepend=-1))
    assert np.all(pos[starts] == 0) and np.all(np.diff(pos)[np.diff(doc) == 0] == 1)
    assert np.all(np.diff(starts)[:-1] >= 4)  # no document under the least length


def test_lowrank_openes_agrees_with_openes_on_materialised_members(monkeypatch):
    """On a small dense problem where each member's dense genome is
    materialised from the same factors: same fitness, same centre after tell
    (``OpenES`` at ``learning_rate * sigma``: its 1 / sigma stays in the step)."""
    pop, sigma, lr, rank = 8, 0.05, 0.3, 2
    center = {"w": jax.random.normal(jax.random.PRNGKey(0), (5, 3)), "b": jnp.ones((3,))}
    target = jax.random.normal(jax.random.PRNGKey(1), (pop, 18))
    low = LowRankOpenES(center, pop, learning_rate=lr, noise_stdev=sigma, rank=rank)
    state = low.init(jax.random.PRNGKey(2))
    spec, state = low.ask(state)
    assert isinstance(spec, LowRankPopulation)
    members = spec.materialise()
    flat = jnp.concatenate([members["b"], members["w"].reshape(pop, -1)], axis=1)  # leaves' order
    fitness = standardise(jnp.sum((flat - target) ** 2, axis=1))
    told = low.tell(state, fitness)

    centre = jnp.concatenate([center["b"], center["w"].reshape(-1)])
    half = (flat[: pop // 2] - centre) / sigma  # OpenES's unit noise: members are centre + sigma * noise
    dense = OpenES(centre, pop, learning_rate=lr * sigma, noise_stdev=sigma)
    dstate = dense.init(jax.random.PRNGKey(3))
    monkeypatch.setattr(jax.random, "normal", lambda key, shape, *a, **k: half)
    dpop, dstate = dense.ask(dstate)
    dtold = dense.tell(dstate, fitness)
    monkeypatch.undo()
    np.testing.assert_allclose(dpop, flat, rtol=0, atol=1e-6)  # the same members, so the same fitness
    got = jnp.concatenate([told.center["b"], told.center["w"].reshape(-1)])
    np.testing.assert_allclose(got, dtold.center, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(told.center["b"], center["b"])  # vectors stay at the centre
    assert float(jnp.max(jnp.abs(told.center["w"] - center["w"]))) > 1e-3


def test_the_spec_has_no_population_axis_and_a_mesh_says_so():
    from evox_tpu import create_mesh

    wf, key = _workflow()
    spec, _ = wf.algorithm.ask(wf.algorithm.init(key))
    pairs = TRAFFIC["pop"] // 2
    assert all(v.shape[0] == pairs for v in jax.tree.leaves(spec.factors))
    meshed = StdWorkflow(wf.algorithm, wf.problem, opt_direction="min",
                         fit_transforms=(standardise,), mesh=create_mesh())
    with pytest.raises(ValueError, match="no population axis"):
        meshed.step(meshed.init(key))


def test_problem_refuses_a_dense_population():
    wf, key = _workflow()
    with pytest.raises(TypeError, match="LowRankPopulation"):
        wf.problem.evaluate(wf.problem.init(key), jnp.zeros((8, 4)))


# configuration: the catalog row's numbers, the keys cut, their values here, what ``published`` says,
# the parameters held, and words the deployment has to say
PUBLISHED = {
    "moonlight_16b_a3b_es": (
        {
            "hidden_size": 2048, "intermediate_size": 11264, "moe_intermediate_size": 1408,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
            "num_attention_heads": 16, "num_key_value_heads": 16, "num_experts_per_tok": 6,
            "n_shared_experts": 2, "num_hidden_layers": 27, "first_k_dense_replace": 1,
            "routed_scaling_factor": 2.446, "rope_theta": 50000, "rms_norm_eps": 1e-05,
            "q_lora_rank": None, "n_group": 1, "topk_group": 1, "max_position_embeddings": 8192,
        },
        ["layers", "n_routed_experts", "vocab_size"], (5, 16, 20480),
        {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840, "parameters": "16B, 3B active"},
        845_308_672, ("shared by 4 chips", "the vocabulary by 8", "pipeline stages"),
    ),
    "kimi_linear_48b_a3b_es": (
        {
            "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
            "intermediate_size": 9216, "kv_lora_rank": 512,
            "linear_attn_config": {
                "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                "num_heads": 32, "short_conv_kernel_size": 4,
            },
            "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
            "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
            "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
            "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
            "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
            "rope_theta": 10000, "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
            "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        },
        ["layers", "num_experts", "vocab_size"], (5, 16, 20480),
        {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840, "parameters": "48B, 3B active"},
        828_926_848, ("shared by 16 chips", "the vocabulary by 8", "pipeline stages"),
    ),
}


PUBLISHED["lfm2_24b_a2b_es"] = (
    {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 10, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
    },
    ["layers", "num_experts", "vocab_size"], (5, 16, 16384),
    {"num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536, "parameters": "24B, 2B active"},
    821_606_784, ("shared by 4 chips", "rows 0 to 16383 of the 65536", "pipeline stages", "layers 1 to 5 of 40"),
)


PUBLISHED["ouro_2_6b_es"] = (
    {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152,
    },
    ["layers"], (12,),
    {"num_hidden_layers": 48, "total_ut_steps": 4, "vocab_size": 49152, "parameters": "2.6B"},
    817_991_681, ("four pipeline stages", "stage 1", "total_ut_steps = 4", "every head is here"),
)


@pytest.mark.parametrize("name", list(PUBLISHED))
def test_configuration_keeps_the_published_widths(name):
    """The configuration file holds every number of the catalog row's config
    at its published value, but for the keys it lists under ``reduced``; it
    says what deployment the cut stands for and how many parameters are held."""
    published, reduced, cut, said, held, words = PUBLISHED[name]
    config = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == reduced and tuple(config[k] for k in reduced) == cut
    assert config["published"] == said and all(w in config["deployment"] for w in words)
    cfg = LMConfig.from_dict(config)
    shapes = jax.tree.leaves(lm.param_shapes(cfg), is_leaf=lm._is_shape)
    assert sum(int(np.prod(s)) for s in shapes) == config["parameters_held"] == held
    assert set(config["limits"]) == set(config["limits_why"])
    if cfg.expert_layers:
        assert (cfg.n_routed_experts, cfg.n_held) == (said[reduced[1]], 16)  # the router keeps its width


def test_the_hybrid_configuration_is_read_by_its_own_keys():
    config = json.loads((ROOT / "benchmark/configs/kimi_linear_48b_a3b_es.json").read_text())
    cfg = LMConfig.from_dict(config)
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda") and cfg.kda_layers == 4
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size) == (32, 128, 4)
    assert cfg.mla_use_nope and cfg.rope_theta is None  # the file's theta is not used: nothing rotates
    assert (cfg.num_experts_per_tok, cfg.n_shared_experts, cfg.first_k_dense_replace) == (8, 1, 1)
    moon = LMConfig.from_dict(json.loads((ROOT / "benchmark/configs/moonlight_16b_a3b_es.json").read_text()))
    assert moon.kinds == ("mla",) * 5 and moon.kda_layers == 0 and moon.rope_theta == 50000 and not moon.mla_use_nope
    # what ``route`` does is checked, not assumed; a rotating family states its theta; a family is one of two
    for key, other in (("moe_renormalize", False), ("moe_router_activation_func", "softmax"),
                       ("use_grouped_topk", False), ("num_expert_group", 8), ("topk_group", 4)):
        with pytest.raises(ValueError, match=key):
            LMConfig.from_dict({**config, key: other})
    with pytest.raises(KeyError, match="rope_theta"):
        LMConfig.from_dict({k: v for k, v in TINY.items() if k != "rope_theta"})
    with pytest.raises(ValueError, match="model_type"):
        LMConfig.from_dict({**config, "model_type": "llama"})
    with pytest.raises(KeyError):  # a layer held that the pattern does not name
        LMConfig.from_dict({**config, "linear_attn_config": {**config["linear_attn_config"], "kda_layers": [1, 2]}})


REFUSED = {
    # the expert layers held are conv, conv, conv: no attention layer of the period's one in four
    "held_layers_break_the_pattern": ({"layers_held": [2, 7], "num_dense_layers": 4}, "break the pattern"),
    "held_layers_are_not_the_depth": ({"layers_held": [1, 5]}, "layers_held"),
    "held_layers_outside_the_model": ({"layers_held": [36, 41]}, "layers_held"),
    "experts_held_disagrees_with_num_experts": ({"experts_held": [0, 8]}, "experts_held"),
    "a_router_that_does_not_renormalise": ({"norm_topk_prob": False}, "norm_topk_prob"),
    "a_router_without_its_bias": ({"use_expert_bias": False}, "use_expert_bias"),
    "a_convolution_with_a_bias": ({"conv_bias": True}, "conv_bias"),
    "a_scaled_rope": ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_type"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_from_dict_refuses_an_lfm2_moe_file_it_would_misread(case):
    config = json.loads((ROOT / "benchmark/configs/lfm2_24b_a2b_es.json").read_text())
    change, said = REFUSED[case]
    with pytest.raises(ValueError, match=said):
        LMConfig.from_dict({**config, **change})


def test_the_lfm2_configuration_is_read_by_its_own_keys():
    config = json.loads((ROOT / "benchmark/configs/lfm2_24b_a2b_es.json").read_text())
    cfg = LMConfig.from_dict(config)
    assert cfg.kinds == ("conv", "gqa", "conv", "conv", "conv") and cfg.conv_layers == 4 and cfg.kda_layers == 0
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.conv_size) == (32, 8, 64, 3)
    assert cfg.first_k_dense_replace == 1 and cfg.expert_layers == 4  # of the two dense layers, layer 1 is held
    assert (cfg.n_shared_experts, cfg.router_eps, cfg.rope_theta, cfg.rms_norm_eps) == (0, 1e-6, 1e6, 1e-5)
    shapes = lm.param_shapes(cfg)
    assert [sorted(set(layer) - {"mlp_norm"}) for layer in shapes["layers"]] == [
        ["conv", "mlp"], ["experts", "gqa", "router", "router_bias"], *[["conv", "experts", "router", "router_bias"]] * 3]
    count = lambda tree: sum(int(np.prod(v)) for v in jax.tree.leaves(tree, is_leaf=lm._is_shape))
    assert count(shapes["layers"][0]["conv"]) == 16_783_360 + 2048  # ISSUE 34's reckoning, and the norm's gains
    assert count(shapes["layers"][1]["gqa"]) == 10_485_760 + 128 + 2048
    assert count(shapes["layers"][0]["mlp"]) == 72_351_744 and count(shapes["layers"][1]["experts"]) == 150_994_944
    # the other families' models have none of this: their routers divide by the plain sum, beside a shared expert
    moon = LMConfig.from_dict(json.loads((ROOT / "benchmark/configs/moonlight_16b_a3b_es.json").read_text()))
    assert (moon.router_eps, moon.conv_layers, moon.num_key_value_heads) == (0.0, 0, 0) and moon.n_shared_experts == 2


def test_the_gated_convolution_is_its_equations_member_by_member():
    """``gated_conv`` against the layer written out for each member on its
    dense weights (the taps' ``w + sign * scale * A B^T`` among them): ``[B,
    C, u] = xn W_in``, the three taps of ``B * u`` with none reaching before
    the document's start, ``(C * c) W_out``; the two members of a pair differ;
    the sums of squares are the output's and the normed input's, over every
    token and over the documents' first two."""
    cfg = LMConfig.from_dict(TINY_LFM2)
    pairs, t, d, scale = 2, 12, cfg.hidden_size, 0.3
    p = init_params(cfg, jax.random.PRNGKey(31))["layers"][0]["conv"]
    p["norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(32), (d,))
    f = tree_factors(jax.random.PRNGKey(33), p, pairs, 1)
    x = jax.random.normal(jax.random.PRNGKey(34), (pairs, 2, t, d))
    pos = jnp.asarray([0, 1, 2, 3, 4, 0, 1, 0, 1, 2, 3, 4])
    reach = pos[None, :] >= jnp.arange(cfg.conv_size)[:, None]
    got, squares = lm.gated_conv(cfg, p, f, jnp.float32(scale), x, reach)
    assert not np.allclose(got[:, 0], got[:, 1])
    total_in = 0.0
    for pair in range(pairs):
        for i, sign in enumerate((1.0, -1.0)):
            dense = {k: v + sign * scale * f[k][0][pair] @ f[k][1][pair].T for k, v in p.items() if v.ndim == 2}
            xn = np.asarray(lm.rmsnorm(x[pair, i], p["norm"], cfg.rms_norm_eps), np.float64)
            b, c, u = np.split(xn @ np.asarray(dense["in_proj"], np.float64), 3, axis=1)
            z, y = b * u, np.zeros((t, d))
            for at in range(t):
                for j in range(cfg.conv_size):
                    back = cfg.conv_size - 1 - j
                    if pos[at] >= back:
                        y[at] += np.asarray(dense["taps"][:, j], np.float64) * z[at - back]
            # float32 against float64, at values of some tens: the perturbation is large here
            np.testing.assert_allclose(got[pair, i], (c * y) @ np.asarray(dense["out_proj"], np.float64),
                                       rtol=1e-5, atol=1e-5)
            total_in += float(np.sum(xn * xn))
    first = np.asarray(pos) < cfg.conv_size - 1  # the documents' first tokens, where the mask of the taps acts
    xn = lm.rmsnorm(x, p["norm"], cfg.rms_norm_eps)
    np.testing.assert_allclose(squares, [[float(jnp.sum(got * got)), float(jnp.sum(got[:, :, first] ** 2))],
                                         [total_in, float(jnp.sum(xn[:, :, first] ** 2))]], rtol=1e-5)


@pytest.mark.parametrize("factors", ("with_factors", "without_factors"))
@pytest.mark.parametrize("via", ("short_conv", "kda_conv"))
def test_the_convolutions_lowrank_perturbation_is_the_materialised_members(via, factors):
    """``short_conv`` with factors equals, member by member, the convolution
    with that member's dense ``w + sign * scale * A B^T``; a tap before the
    document's start reads zero. So does the ``kda_conv`` kernel (interpreted)
    on the taps ``member_taps`` forms for it: the two members of a pair
    differ, and without factors every member has the centre's taps."""
    pairs, t, channels, taps, scale = 3, 12, 8, 4, 0.3
    keys = jax.random.split(jax.random.PRNGKey(21), 4)
    u = jax.random.normal(keys[0], (pairs, 2, t, channels))
    w = jax.random.normal(keys[1], (channels, taps))
    fac = (jax.random.normal(keys[2], (pairs, channels, 1)), jax.random.normal(keys[3], (pairs, taps, 1)))
    if factors == "without_factors":
        fac, scale = None, 0.0
    pos = jnp.asarray([0, 1, 2, 3, 4, 0, 1, 0, 1, 2, 3, 4])
    reach = pos[None, :] >= jnp.arange(taps)[:, None]
    if via == "short_conv":
        got = lm.short_conv(u, w, fac, jnp.float32(scale), reach)
    else:
        own = jnp.broadcast_to(lm.member_taps(w, fac, jnp.float32(scale)), (pairs, 2, channels, taps))
        got = lm.kda_conv(u.reshape(pairs * 2, t, channels), own.reshape(pairs * 2, channels, taps).transpose(0, 2, 1),
                          pos, width=channels, interpret=True).reshape(u.shape)
        assert (fac is None) == bool(np.array_equal(own[:, 0], own[:, 1]))  # the two signs' taps differ
    for p in range(pairs):
        for i, sign in enumerate((1.0, -1.0)):
            dense = w + (sign * scale * fac[0][p] @ fac[1][p].T if fac else 0.0)
            want = np.zeros((t, channels))
            for at in range(t):
                for j in range(taps):
                    back = taps - 1 - j
                    if pos[at] >= back:
                        want[at] += np.asarray(dense[:, j] * u[p, i, at - back])
            np.testing.assert_allclose(got[p, i], jax.nn.silu(want), rtol=0, atol=1e-5)


def test_work_counts_of_the_cell():
    from benchmark.lib import work_lm

    config = json.loads((ROOT / "benchmark/configs/moonlight_16b_a3b_es.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/closed_pop64_seq2048_g1.json").read_text())
    parts = work_lm.lm_flops_per_token(config, traffic)
    assert work_lm.held_choices_per_token(config) == 1.5
    assert parts["dense_mlp"] == 2 * 3 * 2048 * 11264 and parts["head"] == 2 * 2048 * 20480
    assert parts["experts"] == 4 * 1.5 * 2 * 3 * 2048 * 1408
    assert 16 <= work_lm.expected_attended(traffic) <= 1024.5
    # without documents a causal row of T tokens attends (T + 1) / 2 keys on average
    whole = dict(traffic, doc_len_median=1e9, doc_len_min=2048)
    assert work_lm.expected_attended(whole) == pytest.approx(1024.5)
    assert parts["total"] == pytest.approx(sum(v for k, v in parts.items() if k != "total"))


def test_work_counts_of_the_lfm2_cell():
    """460 MFLOP a token to three digits, by part (ISSUE 34's reckoning, with
    the scores at the keys a query attends once the row cuts its last
    document: 1,940.5, not the 2,421 of ``work_lm.expected_attended``)."""
    from benchmark.lib import work_lm, work_lm_lfm2 as work

    config = json.loads((ROOT / "benchmark/configs/lfm2_24b_a2b_es.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/closed_pop16_seq8192_g1.json").read_text())
    parts = work.lm_flops_per_token(config, traffic)
    assert work.layers_held(config) == [("conv", True), ("full_attention", False), *[("conv", False)] * 3]
    assert work.held_choices_per_token(config) == 1.0 and work.head_dim(config) == 64
    assert parts["conv"] == 4 * (2 * (2048 * 6144 + 2048 * 2048) + 2 * 2048 * 3 + 2 * 2048)
    attended = work.attended_keys_per_token(traffic)
    assert attended == pytest.approx(1940.5, rel=1e-4) and work_lm.expected_attended(traffic) > 1.24 * attended
    # a row of one document attends (T + 1) / 2 keys a query; documents much shorter than the row are hardly cut
    assert work.attended_keys_per_token(dict(traffic, doc_len_median=1e9, doc_len_min=8192)) == pytest.approx(4096.5)
    short = dict(traffic, doc_len_median=64, doc_len_sigma=0.5)
    assert work.attended_keys_per_token(short) == pytest.approx(work_lm.expected_attended(short), rel=0.01)
    assert parts["gqa_scores"] == pytest.approx(2 * 32 * (64 + 64) * attended)
    assert parts["attention"] == pytest.approx(2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + parts["gqa_scores"])
    assert parts["dense_mlp"] == 2 * 3 * 2048 * 11776 and parts["head"] == 2 * 2048 * 16384
    assert parts["experts"] == 4 * 1.0 * 2 * 3 * 2048 * 1536 and parts["router"] == 4 * 2 * 2048 * 64
    rounded = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert rounded == {"conv": 134.3, "attention": 36.9, "dense_mlp": 144.7, "router": 1.0, "experts": 75.5,
                       "head": 67.1, "total": 459.5, "gqa_scores": 15.9}
    assert parts["total"] == pytest.approx(sum(v for k, v in parts.items() if k not in ("total", "gqa_scores")))
    assert work.lm_flops_per_eval(config, traffic) * 16 == pytest.approx(6.02e13, rel=2e-3)
    # the kernel's floor is operations: 2.1e12 a generation, 10.6 ms at 197 TFLOP/s; its bytes 1.6 ms at 819 GB/s
    assert work.gqa_kernel_bytes_per_token(config) == (2 * 2048 + 2 * 512) * 2 == 10_240
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.gqa_kernel_least_seconds(config, traffic, 16, peak) == pytest.approx(0.010577, rel=1e-3)
    assert 16 * 8192 * 10_240 / 819e9 < 0.0105


def test_work_counts_of_the_hybrid_cell():
    """711 MFLOP a token to three digits, by part (ISSUE 32's reckoning)."""
    from benchmark.lib import work_lm_hybrid as work

    config = json.loads((ROOT / "benchmark/configs/kimi_linear_48b_a3b_es.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/closed_pop64_seq2048_g1.json").read_text())
    parts = work.lm_flops_per_token(config, traffic)
    assert work.layer_kinds(config) == ["kda", "kda", "kda", "mla", "kda"]
    assert work.held_choices_per_token(config) == 0.5
    projections = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    assert work.kda_scan_flops_per_token(config) == 7 * 32 * 128 * 128
    assert parts["kda"] == 4 * (projections + 2 * 3 * 4096 * 4 + 7 * 32 * 128 * 128)
    assert parts["kda_scan"] == 4 * 7 * 32 * 128 * 128
    assert parts["dense_mlp"] == 2 * 3 * 2304 * 9216 and parts["head"] == 2 * 2304 * 20480
    assert parts["shared"] == 4 * 2 * (3 * 2304 * 1024 + 2304 * 256)
    assert parts["experts"] == 4 * 0.5 * 2 * 3 * 2304 * 1024
    rounded = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert rounded == {"kda": 330.8, "attention": 69.0, "dense_mlp": 127.4, "shared": 61.3, "experts": 28.3,
                       "head": 94.4, "total": 711.2, "kda_scan": 14.7}
    assert parts["total"] == pytest.approx(sum(v for k, v in parts.items() if k not in ("total", "kda_scan")))
    assert work.lm_flops_per_eval(config, traffic) * 64 == pytest.approx(9.32e13, rel=2e-3)
    # the scan's floor is bandwidth: 49,280 B a token and layer, 31.5 ms a generation at 819 GB/s
    assert work.kda_scan_bytes_per_token(config) == 32 * (4 * 128 * 2 + 4 * 128 + 4) == 49_280
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.kda_scan_least_seconds(config, traffic, 64, peak) == pytest.approx(0.03155, rel=1e-3)


def test_work_counts_of_the_ouro_cell():
    """5.30 GFLOP a token, by part: 48 layer applications of 102.8 MFLOP and
    the scores at the 418.2 keys a query attends in this traffic's rows; the
    head once; the gate at every step."""
    from benchmark.lib import work_lm_ouro as work

    config = json.loads((ROOT / "benchmark/configs/ouro_2_6b_es.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/closed_pop16_seq2048_g1.json").read_text())
    parts = work.lm_flops_per_token(config, traffic)
    assert work.layer_applications(config) == 48
    attended = work.attended_keys_per_token(traffic)
    assert attended == pytest.approx(418.2, rel=1e-4)
    assert work.attention_scores_flops_per_token(config, traffic) == pytest.approx(2 * 16 * (128 + 128) * attended)
    layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    assert layer / 1e6 == pytest.approx(102.8, abs=0.05)
    assert parts["layers"] == pytest.approx(48 * layer + parts["scores"]) and parts["head"] == 2 * 2048 * 49152
    assert parts["gate"] == 4 * 2 * 2048
    assert parts["total"] == pytest.approx(sum(v for k, v in parts.items() if k not in ("total", "scores")))
    assert parts["total"] == pytest.approx(5.298e9, rel=1e-3)
    assert work.lm_flops_per_eval(config, traffic) * 16 == pytest.approx(1.736e14, rel=1e-3)
    # the kernel's floor is bytes: 16,384 B a token and application, 31.5 ms a generation at 819 GB/s
    assert work.attention_kernel_bytes_per_token(config) == (2 * 2048 + 2 * 2048) * 2 == 16_384
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.attention_least_seconds(config, traffic, 16, peak) == pytest.approx(16 * 2048 * 48 * 16_384 / 819e9)
    assert work.attention_least_seconds(config, traffic, 16, peak) == pytest.approx(0.03146, rel=1e-3)

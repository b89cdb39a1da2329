"""The looped member model (``ouro``: the held layers run ``total_ut_steps``
times over one set of weights, sandwich norms, an exit gate) at a tiny size
on the CPU, against the plain reference (``benchmark/reference/ouro_2_6b_es.py``):
any number of steps agrees, a program that leaves out a step or the step's
norm is caught by the cell's limits, the exit gate's probabilities are a
distribution over the steps, every step applies the same perturbed weights,
and the configuration is read by its own keys or refused."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from benchmark.reference import ouro_2_6b_es as reference
from evox_tpu.core.lowrank import tree_factors
from evox_tpu.problems.lm import LMConfig, init_params
from evox_tpu.problems.lm import model as lm
from _token_lm import BLOCKS, SEED, TINY_OURO, TRAFFIC, _snapshots, _workflow

ROOT = Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "benchmark/configs/ouro_2_6b_es.json").read_text())


def _against_reference(program_config: dict, reference_config: dict = TINY_OURO) -> tuple:
    """One generation of the program through ``StdWorkflow.run`` and the
    reference's of ``reference_config``; the snapshots and the numbers."""
    wf, key = _workflow(program_config)
    snaps = _snapshots(wf, key, steps=1)
    want = reference.follow(reference_config, TRAFFIC, SEED, [1], program=snaps)
    return snaps[0], want[0], reference.numbers(reference_config, snaps, want)


@pytest.mark.parametrize("steps", (1, 4))
def test_any_number_of_steps_agrees_with_the_reference(steps):
    """In float32, losses, logits, the exit gate's probabilities and the
    steps' gains, one step as four; each within the cell's limits."""
    config = dict(TINY_OURO, total_ut_steps=steps)
    got, want, numbers = _against_reference(config, config)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=0, atol=2e-6)
    np.testing.assert_allclose(got["probe"], want["probe"], rtol=0, atol=2e-5)
    np.testing.assert_allclose(got["exit_pdf"], want["exit_pdf"], rtol=0, atol=1e-6)
    np.testing.assert_allclose(got["loop_gain"], want["loop_gain"], rtol=1e-5)
    assert got["exit_pdf"].shape == (steps,) and harness.compare(numbers, CONFIG["limits"])[1] is True


def _without_the_step_norm(monkeypatch):
    end = lm.step_end
    monkeypatch.setattr(lm, "step_end", lambda cfg, center, fac, scale, y: (y, end(cfg, center, fac, scale, y)[1]))
    return TINY_OURO


FAULTS = {
    "one_step_fewer": lambda monkeypatch: dict(TINY_OURO, total_ut_steps=3),
    "the_steps_norm_left_out": _without_the_step_norm,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_program_that_leaves_out_a_step_or_its_norm_is_not_correct(fault, monkeypatch):
    """Against the reference of four steps each with its norm: the losses
    leave the float32 tolerance, and the cell's limits call it not correct."""
    got, want, numbers = _against_reference(FAULTS[fault](monkeypatch))
    assert np.max(np.abs(got["losses"] - want["losses"])) > 1e-3
    compared, correct = harness.compare(numbers, CONFIG["limits"])
    assert correct is False, compared


def _row(t=48, vocab=32):
    ids = jax.random.randint(jax.random.PRNGKey(2), (t,), 0, vocab)
    lengths = [3, 1, 2, 19, 23]
    doc = jnp.repeat(jnp.arange(len(lengths)), jnp.asarray(lengths), total_repeat_length=t)
    pos = jnp.concatenate([jnp.arange(n) for n in lengths])
    return ids, doc, pos


def _forward(cfg, center, factors, scale):
    ids, doc, pos = _row(vocab=cfg.vocab_size)
    return jax.jit(lambda c, f: lm.forward(cfg, c, f, jnp.float32(scale), ids, doc, pos, 8,
                                           {**lm.DEFAULT_BLOCKS, **BLOCKS}))(center, factors)


def test_exit_pdf_is_a_distribution_over_the_steps():
    """``p(t) = lambda_t prod_{j<t} (1 - lambda_j)``, the last step the
    rest: every share in [0, 1], the shares sum to 1; a gate pushed to exit
    puts nearly all of it on the first step, one pushed to stay on the last."""
    cfg = LMConfig.from_dict(TINY_OURO)
    center = init_params(cfg, jax.random.PRNGKey(1))
    factors = tree_factors(jax.random.PRNGKey(4), center, 2, 1)
    pdf = np.asarray(_forward(cfg, center, factors, 1e-3)["exit_pdf"])
    assert pdf.shape == (4,) and np.all((0 <= pdf) & (pdf <= 1)) and pdf.sum() == pytest.approx(1.0, abs=1e-6)
    for bias, at in ((30.0, 0), (-30.0, 3)):
        pushed = _forward(cfg, dict(center, exit_bias=jnp.full((1,), bias)), factors, 1e-3)["exit_pdf"]
        assert float(pushed[at]) > 0.999


def test_every_step_applies_the_same_perturbed_weights():
    """The first member through the program with its factors is that member's
    materialised weights through the program with no perturbation: every one
    of the four steps ran on the same perturbed matrices."""
    cfg = LMConfig.from_dict(TINY_OURO)
    center = init_params(cfg, jax.random.PRNGKey(1))
    factors = tree_factors(jax.random.PRNGKey(4), center, 2, 1)
    scale = 0.05  # large, so that a step on other weights would show
    leaves, treedef = jax.tree.flatten(center)
    facs = treedef.flatten_up_to(factors)
    dense = jax.tree.unflatten(treedef, [w if f is None else w + scale * f[0][0] @ f[1][0].T
                                         for w, f in zip(leaves, facs)])
    got = _forward(cfg, center, factors, scale)
    want = _forward(cfg, dense, factors, 0.0)
    np.testing.assert_allclose(got["losses"][0, 0], want["losses"][0, 0], rtol=0, atol=2e-6)
    np.testing.assert_allclose(got["probe"][0], want["probe"][0], rtol=0, atol=2e-5)
    assert abs(float(got["losses"][0, 0] - _forward(cfg, center, factors, 0.0)["losses"][0, 0])) > 1e-4


def test_the_ouro_configuration_is_read_by_its_own_keys():
    cfg = LMConfig.from_dict(CONFIG)
    assert cfg.kinds == ("mha",) * 12 and cfg.total_ut_steps == 4 and cfg.expert_layers == 0
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.intermediate_size, cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (5632, 49152, 1e6, 1e-6)
    shapes = lm.param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(v)) for v in jax.tree.leaves(tree, is_leaf=lm._is_shape))
    assert sorted(shapes["layers"][0]) == ["mha", "mlp", "mlp_norm", "mlp_post_norm"]
    assert count(shapes["layers"][0]) == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416  # a layer's sum
    assert (shapes["exit_gate"], shapes["exit_bias"]) == ((2048, 1), (1,))
    # the other families' models have none of this
    lfm2 = LMConfig.from_dict(json.loads((ROOT / "benchmark/configs/lfm2_24b_a2b_es.json").read_text()))
    assert lfm2.total_ut_steps == 0 and "exit_gate" not in lm.param_shapes(lfm2)


REFUSED = {
    "a_threshold_under_one": ({"early_exit_threshold": 0.9}, "early_exit_threshold"),
    "a_sliding_window": ({"use_sliding_window": True}, "use_sliding_window"),
    "a_scaled_rope": ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    "a_tied_head": ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    "another_activation": ({"hidden_act": "gelu"}, "hidden_act"),
    "held_layers_are_not_the_depth": ({"layers_held": [0, 11]}, "layers_held"),
    "held_layers_outside_the_model": ({"layers_held": [40, 52]}, "layers_held"),
    "no_step": ({"total_ut_steps": 0}, "steps"),
    "a_held_layer_of_another_kind": ({"layer_types": ["sliding_attention"] + ["full_attention"] * 47}, "full_attention"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_from_dict_refuses_an_ouro_file_it_would_misread(case):
    change, said = REFUSED[case]
    with pytest.raises(ValueError, match=said):
        LMConfig.from_dict({**CONFIG, **change})

"""The ``deepseek_v3`` family's member model (every layer MLA with RoPE, the
Moonlight cell's) against its plain reference (``benchmark/reference``) at
the family's tiny cut, under both bodies of the token mixers: the laws of
``_token_lm.py``, and the attention kernel's count of the key blocks it
visits."""

from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from _token_lm import (
    FLOAT32_CASES,
    TINY,
    _forward,
    _workflow,
    a_token_sees_only_its_document_and_its_past,
    bfloat16_operands_stay_near_the_reference,
    body,  # noqa: F401  (the fixture)
    program_agrees_with_the_reference_in_float32,
)
from evox_tpu.problems.lm import LMConfig, init_params, packed_row

FAMILY = "deepseek_v3"


@functools.cache
def _tiny_cut(body: str) -> tuple:
    """The tiny cut's workflow under ``body``, built once a body: the float32
    law's first case and the ``attn_blocks`` law run the same program, so
    its run loop compiles once. Keyed by the body because a jitted function
    keeps its first trace whatever the ``body`` fixture patches later."""
    return _workflow()


@pytest.mark.parametrize("rank,layers,steps", FLOAT32_CASES[FAMILY])
def test_program_agrees_with_the_reference_in_float32(rank, layers, steps, body):  # noqa: F811
    built = _tiny_cut(body) if (rank, layers) == (TINY["rank"], TINY["layers"]) else None
    program_agrees_with_the_reference_in_float32(FAMILY, rank, layers, steps, built)


def test_bfloat16_operands_stay_near_the_reference(body):  # noqa: F811
    bfloat16_operands_stay_near_the_reference(FAMILY)


def test_a_token_sees_only_its_document_and_its_past(body):  # noqa: F811
    a_token_sees_only_its_document_and_its_past(FAMILY)


def test_attn_blocks_is_the_visited_share_of_a_dense_causal_pass(body):  # noqa: F811
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
    wf, key = _tiny_cut(body)
    state = wf.run(wf.init(key).replace(first_step=False), 1)
    assert 0.0 < float(state.prob.attn_blocks) <= 1.0

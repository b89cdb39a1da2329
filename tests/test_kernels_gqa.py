"""``kernels/gqa_flash_attention.py`` in interpreter mode on the CPU against
the plain body it replaces on the chip (``problems/lm/model.py``
``attend_gqa_plain``): the same output for every layout of documents in the
row, query head ``h`` reading key-value head ``h // group``, and against a
loop over the heads written out, which shares no reshape with either."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.kernels import flash_block_bounds, gqa_block_sizes, gqa_flash_attention
from evox_tpu.problems.lm import model as lm

LFM2 = (8, 2, 64)  # the benchmark's head width and group of four, a quarter of its heads
SMALL = (4, 2, 16)  # the tiny cut's: 4 query heads on 2 key-value heads of 16
WIDE = (12, 4, 8)  # groups of three, two pairs of key-value heads


def _docs(*lengths):
    return jnp.asarray(np.repeat(np.arange(len(lengths)), lengths), jnp.int32)


def _dense_mask(doc):
    at = np.arange(doc.shape[0])
    doc = np.asarray(doc)
    return (at[:, None] >= at[None, :]) & (doc[:, None] == doc[None, :])


def _operands(t, shape, dtype, members=2, seed=0):
    heads, kv_heads, width = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = lambda k, n: jax.random.normal(k, (members, t, n * width), jnp.float32).astype(dtype)
    return normal(keys[0], heads), normal(keys[1], kv_heads), normal(keys[2], kv_heads)


def _cfg(shape):
    return types.SimpleNamespace(num_attention_heads=shape[0], num_key_value_heads=shape[1], head_dim=shape[2])


def _both(doc, shape, blocks, dtype, seed=0):
    operands = _operands(doc.shape[0], shape, dtype, seed=seed)
    want = lm.attend_gqa_plain(_cfg(shape), jnp.asarray(_dense_mask(doc)), *operands)
    got = lm.attend_gqa_flash(_cfg(shape), doc, flash_block_bounds(doc, *blocks), blocks, *operands)
    assert got.shape == want.shape and got.dtype == want.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


LAYOUTS = {
    "one_document_fills_the_row": (_docs(64), (16, 16)),
    "many_short_documents": (_docs(*[4] * 16), (16, 16)),
    "single_tokens": (_docs(*[1] * 32), (8, 16)),
    "boundary_inside_a_block": (_docs(10, 14, 40), (16, 16)),
    "boundary_on_a_block_edge": (_docs(16, 32, 16), (16, 16)),
    "several_blocks_a_document": (_docs(5, 100, 23), (16, 16)),
    "query_blocks_wider_than_key_blocks": (_docs(10, 14, 40), (32, 8)),
    "key_blocks_wider_than_query_blocks": (_docs(10, 14, 40), (8, 32)),
    "one_block": (_docs(7, 9), (16, 16)),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_agrees_with_the_plain_body_in_float32(layout):
    doc, blocks = LAYOUTS[layout]
    got, want = _both(doc, SMALL, blocks, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("dtype,atol", ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)))
def test_kernel_at_the_benchmark_widths(dtype, atol):
    """Heads of 64, four to a key-value head, blocks of 128: the widths the
    compiled kernel takes, three documents over a row of 256."""
    got, want = _both(_docs(100, 60, 96), LFM2, (128, 128), dtype, seed=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("shape", (SMALL, WIDE), ids=("group_of_2", "group_of_3_two_pairs"))
def test_query_head_h_reads_key_value_head_h_over_group(shape):
    """Head by head with no reshape: the softmax of ``q_h . k_(h // group)``
    over the keys the mask keeps, times ``v_(h // group)``."""
    heads, kv_heads, width = shape
    doc = _docs(10, 14, 24)
    q, k, v = _operands(48, shape, jnp.float32, seed=3)
    got = gqa_flash_attention(q, k, v, doc, flash_block_bounds(doc, 16, 16), heads=heads, kv_heads=kv_heads,
                              scale=width**-0.5, block_q=16, block_k=16, interpret=True)
    mask = _dense_mask(doc)
    for h in range(heads):
        g = h // (heads // kv_heads)
        qh, kg, vg = (np.asarray(a[..., i * width:(i + 1) * width], np.float64) for a, i in ((q, h), (k, g), (v, g)))
        s = np.where(mask, np.einsum("mqd,mkd->mqk", qh, kg) * width**-0.5, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want = np.einsum("mqk,mkd->mqd", p / p.sum(axis=-1, keepdims=True), vg)
        np.testing.assert_allclose(got[..., h * width:(h + 1) * width], want, rtol=0, atol=1e-5)


def test_kernel_with_bfloat16_operands_at_small_widths():
    """The two bodies round the probabilities at different points (normalised
    there, unnormalised here), to the same relative error."""
    got, want = _both(_docs(10, 14, 40), SMALL, (16, 16), jnp.bfloat16, seed=2)
    assert np.median(np.abs(got - want) / (np.abs(want) + 1e-3)) < 0.02
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)


def test_block_sizes_are_the_shapes_the_compiled_kernel_takes():
    assert gqa_block_sizes(8192, 32, 8, 64) == (512, 512)
    assert gqa_block_sizes(384, 32, 8, 64) == (128, 128)
    assert gqa_block_sizes(48, 32, 8, 64) is None  # the row does not divide into lane tiles
    assert gqa_block_sizes(8192, 4, 2, 16) is None  # the tiny cut's heads: two are no lane tile
    assert gqa_block_sizes(8192, 32, 7, 64) is None  # key-value heads go in pairs
    assert gqa_block_sizes(8192, 32, 6, 64) is None  # query heads divide among them


def test_kernel_refuses_operands_that_are_not_its_layout():
    q, k, v = _operands(32, SMALL, jnp.float32)
    doc = _docs(32)
    call = lambda q, k, v, bq=16, kv_heads=2: gqa_flash_attention(
        q, k, v, doc, flash_block_bounds(doc, 16, 16), heads=4, kv_heads=kv_heads, scale=0.25,
        block_q=bq, block_k=16, interpret=True)
    with pytest.raises(ValueError, match="are not"):
        call(q, k, v[..., :16])  # v narrower than k
    with pytest.raises(ValueError, match="are not"):
        call(q, k[..., :16], v[..., :16], kv_heads=1)  # one key-value head is half a tile
    with pytest.raises(ValueError, match="does not divide"):
        call(q, k, v, bq=24)

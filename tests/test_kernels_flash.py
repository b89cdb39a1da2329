"""``kernels/flash_attention.py`` in interpreter mode on the CPU against the
plain body it replaces on the chip (``problems/lm/model.py`` ``attend_plain``):
the same output for every layout of documents in the row, and loop bounds that
leave out exactly the key blocks the mask would have emptied."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.kernels import flash_attention, flash_block_bounds, flash_block_sizes
from evox_tpu.problems.lm import model as lm
from evox_tpu.problems.lm import packed_row

MLA = (128, 64, 128)  # the benchmark's head: qk 192, v 128
SMALL = (16, 8, 16)  # qk 24, v 16


def _docs(*lengths):
    return jnp.asarray(np.repeat(np.arange(len(lengths)), lengths), jnp.int32)


def _dense_mask(doc):
    at = np.arange(doc.shape[0])
    doc = np.asarray(doc)
    return (at[:, None] >= at[None, :]) & (doc[:, None] == doc[None, :])


def _operands(t, heads, widths, dtype, members=2, seed=0):
    dn, dr, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return (normal(keys[0], (members, t, heads, dn + dr)), normal(keys[1], (members, t, heads, dr)),
            normal(keys[2], (members, t, heads, dn + dv)), normal(keys[3], (members, t, dr)))


def _both(doc, heads, widths, blocks, dtype, seed=0):
    cfg = types.SimpleNamespace(qk_nope_head_dim=widths[0], qk_rope_head_dim=widths[1])
    operands = _operands(doc.shape[0], heads, widths, dtype, seed=seed)
    want = lm.attend_plain(cfg, jnp.asarray(_dense_mask(doc)), *operands)
    got = lm.attend_flash(cfg, doc, flash_block_bounds(doc, *blocks), blocks, *operands)
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
    got, want = _both(doc, 2, SMALL, blocks, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("dtype,atol", ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)))
def test_kernel_at_the_benchmark_widths(dtype, atol):
    """Query and key width 192, value width 128, blocks of 128: the widths
    the compiled kernel takes, three documents over two blocks."""
    got, want = _both(_docs(100, 60, 96), 2, MLA, (128, 128), dtype, seed=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_kernel_with_bfloat16_operands_at_small_widths():
    """The tolerance of ``test_bfloat16_operands_stay_near_the_reference``:
    the two bodies round the probabilities at different points (normalised
    there, unnormalised here), to the same relative error."""
    got, want = _both(_docs(10, 14, 40), 2, SMALL, (16, 16), jnp.bfloat16, seed=2)
    assert np.median(np.abs(got - want) / (np.abs(want) + 1e-3)) < 0.02
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)


@pytest.mark.parametrize("seed,median,blocks", ((0, 12.0, (16, 16)), (1, 40.0, (32, 16)), (2, 96.0, (16, 64)),
                                                (3, 384.0, (128, 128))))
def test_skipped_blocks_are_exactly_the_wholly_masked(seed, median, blocks):
    """Over a packed row's documents the loop's bounds visit a key block if
    and only if the dense mask keeps an entry of it."""
    t, (bq, bk) = 512, blocks
    _, doc, _ = packed_row(jax.random.PRNGKey(seed), t, 32, median, 1.0, 4)
    first, last = (np.asarray(v) for v in flash_block_bounds(doc, bq, bk))
    seen = _dense_mask(doc).reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    at = np.arange(t // bk)
    np.testing.assert_array_equal((at[None, :] >= first[:, None]) & (at[None, :] <= last[:, None]), seen)
    assert first.dtype == np.int32 and np.all(last == ((np.arange(t // bq) + 1) * bq - 1) // bk)


def test_block_sizes_are_the_shapes_the_compiled_kernel_takes():
    assert flash_block_sizes(2048, 128, 128) == (512, 512)
    assert flash_block_sizes(384, 128, 128) == (128, 128)
    assert flash_block_sizes(48, 128, 128) is None  # the row does not divide into lane tiles
    assert flash_block_sizes(2048, 16, 16) is None  # the tiny cut's heads
    assert flash_block_sizes(2048, 128, 64) is None


def test_kernel_refuses_operands_that_are_not_its_layout():
    q, q_rope, kv, k_rope = _operands(32, 2, SMALL, jnp.float32)
    doc = _docs(32)
    call = lambda *a, **k: flash_attention(*a, doc, flash_block_bounds(doc, 16, 16), heads=2, scale=0.2,
                                           block_q=k.get("bq", 16), block_k=16, interpret=True)
    with pytest.raises(ValueError, match="are not"):
        call(q[..., :16].reshape(2, 32, 32), q_rope, kv.reshape(2, 32, -1), k_rope)  # q_rope not head-major
    with pytest.raises(ValueError, match="does not divide"):
        call(q[..., :16].reshape(2, 32, 32), q_rope.transpose(0, 2, 1, 3), kv.reshape(2, 32, -1), k_rope, bq=24)

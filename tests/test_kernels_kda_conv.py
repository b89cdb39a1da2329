"""KDA's fused convolution, SiLU and L2 norm (``kernels/kda_conv.py``,
interpreted here) against the plain body it replaces on the chip:
``problems/lm/model.py`` ``short_conv`` and the two norm lines, on rows whose
documents start wherever a tap can be asked to stop."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evox_tpu.problems.lm import model as lm

kc = importlib.import_module("evox_tpu.kernels.kda_conv")  # the package exports the function under the module's name

TAPS = 4
# each token's position in its document, as lengths of documents; a first entry (n,) a row that begins n tokens into one
ROWS = {
    "one_document_from_token_0": [40],
    "starts_inside_the_first_three_tokens": [1, 1, 38],
    "documents_of_1_2_3_tokens_one_after_another": [1, 2, 3, 1, 2, 3, 1, 1, 2, 24],
    "no_start_at_all": [(7,), 40],
    "a_start_at_a_blocks_first_and_last_token": [16, 15, 1, 16],  # with the walk's blocks at 16
    "a_row_no_multiple_of_the_block": [9, 28],  # 37 tokens in blocks of 16: padded, cut off
}


def _pos(lengths):
    first = lengths[0][0] if isinstance(lengths[0], tuple) else 0
    lengths = [n for n in lengths if not isinstance(n, tuple)]
    pos = np.concatenate([np.arange(n) for n in lengths])
    pos[: lengths[0]] += first
    return jnp.asarray(pos, jnp.int32)


def _inputs(lengths, members=2, heads=3, width=8, dtype=jnp.float32, seed=0):
    pos = _pos(lengths)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    u = jax.random.normal(keys[0], (members, pos.shape[0], heads * width)).astype(dtype)
    w = jax.random.normal(keys[1], (members, TAPS, heads * width))
    return u, w, pos


def _plain(u, w, pos, width, normalise):
    """The body off the TPU, a member at a time through ``short_conv`` with
    that member's dense taps, then ``kda``'s two norm lines; float32."""
    m, t, channels = u.shape
    reach = pos[None, :] >= jnp.arange(w.shape[1])[:, None]
    y = jnp.stack([lm.short_conv(u[i][None, None], w[i].T, None, None, reach)[0, 0] for i in range(m)])
    y = y.reshape(m, t, channels // width, width)
    if normalise is not None:
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    if normalise == "l2_scaled":
        y = y * width**-0.5
    return y.reshape(m, t, channels)


@pytest.fixture
def sized(monkeypatch):
    """The kernel's sizes set for one test (``kda_conv`` reads them where it
    is traced, so what it traced before and after is dropped)."""
    def to(**sizes):
        for name, value in sizes.items():
            monkeypatch.setattr(kc, name, value)
        kc.kda_conv.clear_cache()

    yield to
    kc.kda_conv.clear_cache()


@pytest.fixture
def blocks_of_16(sized):
    """The walk along the row in blocks of 16 tokens, so that a short row
    carries its last rows across several block ends."""
    sized(TOKEN_BLOCK=16)


@pytest.mark.parametrize("normalise", kc.NORMALISE, ids=str)
@pytest.mark.parametrize("row", list(ROWS))
def test_the_kernel_is_the_plain_body(row, normalise, blocks_of_16):
    u, w, pos = _inputs(ROWS[row])
    got = kc.kda_conv(u, w, pos, width=8, normalise=normalise, interpret=True)
    want = _plain(u, w, pos, 8, normalise)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("normalise", kc.NORMALISE, ids=str)
def test_bfloat16_operands_are_the_float32_body_rounded_once(normalise, blocks_of_16):
    u, w, pos = _inputs(ROWS["documents_of_1_2_3_tokens_one_after_another"], dtype=jnp.bfloat16, seed=1)
    got = kc.kda_conv(u, w, pos, width=8, normalise=normalise, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _plain(u, w, pos, 8, normalise)  # float32 arithmetic on the bfloat16 stream
    once = np.asarray(want.astype(jnp.bfloat16), np.float32)
    ulp = np.maximum(np.abs(once), 2.0**-126) * 2.0**-7  # at most one step of bfloat16's 8 bits
    assert np.all(np.abs(np.asarray(got, np.float32) - once) <= ulp)
    assert np.mean(np.asarray(got, np.float32) == once) > 0.99


def test_the_kernels_own_block_and_several_cells_of_lanes(sized):
    """The block the chip walks in (512 tokens) over a row of two and a part,
    and heads in more than one grid cell."""
    sized(LANES_A_CELL=16)
    u, w, pos = _inputs([511, 1, 2, 514, 190, 1], heads=4, seed=2)
    got = kc.kda_conv(u, w, pos, width=8, normalise="l2", interpret=True)
    np.testing.assert_allclose(got, _plain(u, w, pos, 8, "l2"), rtol=0, atol=2e-6)


def test_a_token_is_read_by_itself_and_the_next_three_of_its_document(blocks_of_16):
    u, w, pos = _inputs([21, 27])
    run = lambda u: np.asarray(kc.kda_conv(u, w, pos, width=8, normalise="l2_scaled", interpret=True))
    base = run(u)
    for at, last in ((5, 8), (14, 17), (19, 20), (30, 33), (47, 47)):  # 19: document 1 starts at 21 and reads nothing of it
        moved = np.any(run(u.at[:, at].multiply(-1.5)) != base, axis=(0, 2))
        assert np.array_equal(np.flatnonzero(moved), np.arange(at, last + 1)), (at, np.flatnonzero(moved))


@pytest.mark.parametrize("case", ("width", "taps_members", "taps_channels", "pos", "normalise", "too_many_taps", "rank"))
def test_shapes_that_are_not_the_kernels_are_refused(case):
    u, w, pos = _inputs([16])
    bad = {
        "width": lambda: kc.kda_conv(u, w, pos, width=5, interpret=True),
        "taps_members": lambda: kc.kda_conv(u, w[:1], pos, width=8, interpret=True),
        "taps_channels": lambda: kc.kda_conv(u, w[:, :, :8], pos, width=8, interpret=True),
        "pos": lambda: kc.kda_conv(u, w, pos[:8], width=8, interpret=True),
        "normalise": lambda: kc.kda_conv(u, w, pos, width=8, normalise="l1", interpret=True),
        "too_many_taps": lambda: kc.kda_conv(u, jnp.zeros((2, 18, 24)), pos, width=8, interpret=True),
        "rank": lambda: kc.kda_conv(u[0], w, pos, width=8, interpret=True),
    }
    with pytest.raises(ValueError, match="kda_conv"):
        bad[case]()

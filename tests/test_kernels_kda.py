"""KDA's chunked scan (``kernels/kda_scan.py``) against the token-by-token
recurrence: the kernel (interpreted here) and the same chunk arithmetic in
plain XLA, on rows whose documents start wherever a chunk's reset path can be
asked to handle them."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ks = importlib.import_module("evox_tpu.kernels.kda_scan")  # the package exports the function under the module's name

CHUNK = 16  # one sub-block a chunk; the kernel's own 64 runs four
BODIES = {
    "kernel": lambda *a, **k: ks.kda_scan(*a, interpret=True, **k),
    "xla": ks.kda_scan_chunked,
}
# documents' lengths, in a row cut into chunks of 16
ROWS = {
    "one_document": (48,),
    "a_start_at_a_chunks_first_token": (16, 32),
    "a_start_inside_a_chunk": (21, 27),
    "two_starts_in_one_chunk": (18, 3, 4, 23),
    "a_document_a_token": (30, 1, 1, 1, 15),
    "a_row_no_multiple_of_the_chunk": (18, 3, 4, 16),
    "a_row_shorter_than_a_chunk": (5, 6),
}


def _inputs(lengths, members=2, heads=2, width=8, dtype=jnp.float32, seed=0):
    t = sum(lengths)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: (a.reshape(members, t, heads, width)
                      / jnp.linalg.norm(a.reshape(members, t, heads, width), axis=-1, keepdims=True)
                      ).reshape(members, t, heads * width)
    q = unit(jax.random.normal(keys[0], (members, t, heads * width))) * width**-0.5
    k = unit(jax.random.normal(keys[1], (members, t, heads * width)))
    v = jax.random.normal(keys[2], (members, t, heads * width))
    # a token keeps between nearly all and e^-4.5 of a channel's state: sums over a chunk pass exp's range downwards
    g = -jnp.exp(jax.random.uniform(keys[3], (members, t, heads * width), minval=-6.0, maxval=1.5))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (members, t, heads)))
    doc = jnp.repeat(jnp.arange(len(lengths)), jnp.asarray(lengths), total_repeat_length=t)
    return [a.astype(dtype) for a in (q, k, v)] + [g, beta, doc.astype(jnp.int32)], heads


@pytest.mark.parametrize("body", list(BODIES))
@pytest.mark.parametrize("row", list(ROWS))
def test_chunked_scan_is_the_recurrence(row, body):
    args, heads = _inputs(ROWS[row])
    want = ks.kda_scan_reference(*args, heads=heads)
    got = BODIES[body](*args, heads=heads, chunk=CHUNK)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("body", list(BODIES))
def test_sub_blocks_and_block_rows_join_exactly(body):
    """Chunks of four sub-blocks (the kernel's own 64): the decay split at a
    sub-block's first token and the block rows of the triangular solve."""
    args, heads = _inputs((70, 9, 40, 11), width=16, seed=1)
    want = ks.kda_scan_reference(*args, heads=heads)
    got = BODIES[body](*args, heads=heads, chunk=ks.KDA_CHUNK)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("body", list(BODIES))
def test_strong_decay_neither_overflows_nor_loses_the_near_past(body):
    """``g`` of -30 a token: ``exp(-G)`` would overflow inside a sub-block;
    only differences are exponentiated, so the result stays the recurrence's
    (to the float32 rounding of a running sum near -600: 6e-5 of a factor)."""
    args, heads = _inputs((40, 24), seed=2)
    args[3] = jnp.where(jnp.arange(64)[None, :, None] % 3 == 0, -30.0, args[3])
    want = ks.kda_scan_reference(*args, heads=heads)
    got = BODIES[body](*args, heads=heads, chunk=ks.KDA_CHUNK)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-5)


@pytest.mark.parametrize("body", list(BODIES))
def test_a_token_takes_nothing_from_another_document_or_the_future(body):
    args, heads = _inputs((21, 27))
    base = BODIES[body](*args, heads=heads, chunk=CHUNK)
    for at, same_until in ((5, 5), (30, 30)):  # a change in document 0, and one in document 1
        changed = [a.at[:, at].multiply(-1.5) if i < 3 else a for i, a in enumerate(args)]
        got = BODIES[body](*changed, heads=heads, chunk=CHUNK)
        np.testing.assert_array_equal(got[:, :same_until], base[:, :same_until])
        assert not np.array_equal(got[:, at], base[:, at])
        if at < 21:  # document 1 does not see document 0
            np.testing.assert_array_equal(got[:, 21:], base[:, 21:])


@pytest.mark.parametrize("body", list(BODIES))
def test_bfloat16_operands_stay_near_the_recurrence(body):
    args, heads = _inputs((70, 9, 49), width=16, dtype=jnp.bfloat16, seed=3)
    want = ks.kda_scan_reference(*[a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a for a in args],
                                 heads=heads)
    got = BODIES[body](*args, heads=heads, chunk=ks.KDA_CHUNK)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() < 0.03 * np.abs(want).max()


def test_shapes_that_are_not_the_scans_are_refused():
    args, heads = _inputs((16,))
    with pytest.raises(ValueError, match="kda_scan"):
        ks.kda_scan_chunked(*args, heads=heads, chunk=24)
    with pytest.raises(ValueError, match="kda_scan"):
        ks.kda_scan_chunked(args[0], args[1][:, :8], *args[2:], heads=heads)

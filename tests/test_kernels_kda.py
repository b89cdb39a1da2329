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
    "five_chunks": (30, 3, 47),
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


@pytest.mark.parametrize("heads", [1, 2, 3, 4])
def test_the_kernel_joins_two_heads_or_takes_one(heads):
    """An even head count runs the chunk arithmetic on a grid cell's two
    heads joined (2: one cell, 4: two), an odd one a head at a time (the
    form the XLA path runs): both are the recurrence, at the kernel's own
    chunk of four sub-blocks."""
    args, _ = _inputs((70, 9, 40, 11), heads=heads, width=16, seed=4)
    want = ks.kda_scan_reference(*args, heads=heads)
    got = ks.kda_scan(*args, heads=heads, chunk=ks.KDA_CHUNK, interpret=True)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("body", list(BODIES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_head_takes_nothing_from_its_cells_other_head(body, dtype):
    """The joined products add the other head's entries times exact zeros, or
    make entries that a select drops: whatever head 1 (and 3) holds, large
    finite values included (keys stay of unit length, as the model's are: the
    triangular system's entries are what overflow first), heads 0 and 2 come
    out bit for bit the same."""
    heads, width = 4, 16
    args, _ = _inputs((70, 9, 40, 11), heads=heads, width=width, dtype=dtype, seed=5)
    base = BODIES[body](*args, heads=heads, chunk=ks.KDA_CHUNK)
    odd = (jnp.arange(heads * width) // width) % 2 == 1  # the lanes of heads 1 and 3
    q, k, v, g, beta, doc = args
    fresh, _ = _inputs((70, 9, 40, 11), heads=heads, width=width, dtype=dtype, seed=6)
    changed = [jnp.where(odd, 30.0 * fresh[0], q), jnp.where(odd, -fresh[1], k), jnp.where(odd, 1e4 * fresh[2], v),
               jnp.where(odd, 3.0 * fresh[3], g), jnp.where(jnp.arange(heads) % 2 == 1, fresh[4], beta), doc]
    got = BODIES[body](*changed, heads=heads, chunk=ks.KDA_CHUNK)
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    np.testing.assert_array_equal(np.asarray(got)[..., ~odd], np.asarray(base)[..., ~odd])
    assert not np.array_equal(np.asarray(got)[..., odd], np.asarray(base)[..., odd])


@pytest.mark.parametrize("decay", [(-30.0, -30.0), (-30.0, -1e-6), (-1.0, -1e-3), (-1e-3, -1e-6), (-1e-6, -1e-6)],
                         ids=lambda d: f"{d[0]:g}_to_{d[1]:g}")
def test_three_part_sums_are_the_full_precision_products(decay):
    """``G`` by three bfloat16 passes against the float32 product at
    ``HIGHEST``, on two heads' rows under one mask. The parts' products are
    exact and their float32 sums nearly so (8-bit terms); the float32 product
    rounds its running sum once a term, up to 63 times half an ulp of it: the
    two differ by that (8 half-ulps of the sum of |g| seen), the three-part
    one the nearer to the sum in float64. A masked term adds exactly zero."""
    low, high = np.log(-decay[0]), np.log(-decay[1])
    g = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(7), (128, 24), minval=min(low, high) - 0.05, maxval=max(low, high)))
    doc = np.repeat(np.arange(3), (50, 30, 48))
    at = np.arange(128)
    mask = (doc[:, None] == doc[None, :]) & (at[:, None] >= at[None, :]) & (at[:, None] // 64 == at[None, :] // 64)
    got = np.asarray(ks._masked_sums(jnp.asarray(mask), g), np.float64)
    full = np.asarray(ks._mm(jnp.asarray(mask, jnp.float32), g), np.float64)
    exact = mask.astype(np.float64) @ np.asarray(g, np.float64)
    scale = mask.astype(np.float64) @ np.abs(np.asarray(g, np.float64))
    assert np.all(np.abs(got - full) <= 16 * 2.0**-24 * scale)
    assert np.abs(got - exact).max() <= np.abs(full - exact).max()
    poisoned = jnp.where((doc == 1)[:, None], -1e30, g)  # another document's decay is never summed in
    again = np.asarray(ks._masked_sums(jnp.asarray(mask), poisoned))
    np.testing.assert_array_equal(again[doc != 1], np.asarray(ks._masked_sums(jnp.asarray(mask), g))[doc != 1])


def test_shapes_that_are_not_the_scans_are_refused():
    args, heads = _inputs((16,))
    with pytest.raises(ValueError, match="kda_scan"):
        ks.kda_scan_chunked(*args, heads=heads, chunk=24)
    with pytest.raises(ValueError, match="kda_scan"):
        ks.kda_scan_chunked(args[0], args[1][:, :8], *args[2:], heads=heads)

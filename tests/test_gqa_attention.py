"""Causal and short-window attention over grouped-query heads
(``parallel/gqa.py``) against a brute-force numpy softmax: the outputs,
the key taps at the window's edge, causality and the rows of a batch
bit for bit, and the counts of visited keys against the tiles the two
functions really step over."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import gqa

G, HG, D = 2, 4, 16          # 8 query heads over 2 key-value heads
SCALE = D ** -0.5


def _qkv(seed, b_sz, length, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b_sz, length, G, HG, D), dtype),
            jax.random.normal(ks[1], (b_sz, length, G, D), dtype),
            jax.random.normal(ks[2], (b_sz, length, G, D), dtype))


def _brute(q, k, v, window=None):
    """Every (query, key) score of a route, masked, in float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    length = q.shape[1]
    t, s = np.arange(length)[:, None], np.arange(length)[None, :]
    keys = s <= t
    if window:
        keys &= s > t - window
    scores = np.einsum("bqghd,bkgd->bghqk", q, k) * SCALE
    scores = np.where(keys, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bghqk,bkgd->bqghd", p, v), keys


def _run(kind, q, k, v, **sizes):
    with jax.default_matmul_precision("highest"):
        if kind == "window":
            return jax.jit(lambda *a: gqa.window_attention(
                *a, scale=SCALE, **sizes))(q, k, v)
        return jax.jit(lambda *a: gqa.causal_attention(
            *a, scale=SCALE, **sizes))(q, k, v)


CASES = [("causal", 48, dict(block=8, chunk=16)),
         ("causal", 40, dict(block=8, chunk=16)),     # a last part chunk
         ("causal", 8, dict(block=256, chunk=1024)),  # shorter than a block
         ("window", 48, dict(window=8, block=8, rows=16)),
         ("window", 40, dict(window=5, block=8, rows=16)),
         ("window", 24, dict(window=8, block=8, rows=2048)),
         ("window", 8, dict(window=8, block=128))]


@pytest.mark.parametrize("kind,length,sizes", CASES)
def test_attention_matches_a_brute_force_softmax(kind, length, sizes):
    q, k, v = _qkv(0, 2, length)
    out, n_keys, first = _run(kind, q, k, v, **sizes)
    want, keys = _brute(q, k, v, sizes.get("window"))
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(n_keys, np.broadcast_to(
        keys.sum(-1), n_keys.shape))
    np.testing.assert_array_equal(first, np.broadcast_to(
        keys.argmax(-1), first.shape))



@pytest.mark.parametrize("groups,per", [(4, 5), (2, 5)])
def test_causal_attention_at_five_query_heads_a_group(groups, per):
    """The fifth model's grouping, 20 query heads over 4 key-value heads
    (and 10 over 2 at its toy size): no code of the function changed for
    it; the numbers are the brute force's."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 40, groups, per, D))
    k = jax.random.normal(ks[1], (2, 40, groups, D))
    v = jax.random.normal(ks[2], (2, 40, groups, D))
    out, n_keys, _ = _run("causal", q, k, v, block=8, chunk=16)
    want, keys = _brute(q, k, v)
    assert out.shape == (2, 40, groups, per, D)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(n_keys, np.broadcast_to(
        keys.sum(-1), n_keys.shape))

def test_key_taps_are_exact_at_the_windows_edge():
    q, k, v = _qkv(1, 1, 32)
    _, n_keys, first = _run("window", q, k, v, window=8, block=8, rows=16)
    # the window counts the query's own position: t = 7 is the last
    # query that sees key 0, t = 8 the first that sees eight from key 1
    assert list(np.asarray(n_keys[0, 6:10])) == [7, 8, 8, 8]
    assert list(np.asarray(first[0, 6:10])) == [0, 0, 1, 2]
    _, n_keys, first = _run("causal", q, k, v, block=8, chunk=16)
    assert list(np.asarray(n_keys[0, [0, 15, 16, 31]])) == [1, 16, 17, 32]
    assert not np.asarray(first).any()


@pytest.mark.parametrize("kind,sizes", [
    ("causal", dict(block=8, chunk=16)),
    ("window", dict(window=8, block=8, rows=16))])
def test_a_later_token_and_a_neighbour_route_change_nothing(kind, sizes):
    """Bit for bit: outputs at t <= 20 after every token past 20 changed,
    and a route's outputs after the other route of the batch changed (a
    window does not reach into the row before it, nor into padding)."""
    q, k, v = _qkv(2, 2, 48)
    base, _, _ = _run(kind, q, k, v, **sizes)
    q2, k2, v2 = _qkv(3, 2, 48)
    later = [a.at[:, 21:].set(b[:, 21:]) for a, b in ((q, q2), (k, k2),
                                                      (v, v2))]
    out, _, _ = _run(kind, *later, **sizes)
    np.testing.assert_array_equal(out[:, :21], base[:, :21])
    assert not np.array_equal(out[:, 21:], base[:, 21:])
    other = [a.at[0].set(b[0]) for a, b in ((q, q2), (k, k2), (v, v2))]
    out, _, _ = _run(kind, *other, **sizes)
    np.testing.assert_array_equal(out[1], base[1])
    assert not np.array_equal(out[0], base[0])


@pytest.mark.parametrize("kind,sizes", [
    ("causal", dict(block=8, chunk=16)),
    ("window", dict(window=8, block=8, rows=16))])
def test_bfloat16_products_accumulate_in_float32(kind, sizes):
    q, k, v = _qkv(4, 1, 48, jnp.bfloat16)
    out, _, _ = _run(kind, q, k, v, **sizes)
    assert out.dtype == jnp.bfloat16
    want, _ = _brute(q, k, v, sizes.get("window"))
    gap = np.linalg.norm(np.asarray(out, np.float64) - want) \
        / np.linalg.norm(want)
    assert gap < 0.01


@pytest.mark.parametrize("length,block,chunk", [
    (48, 8, 16), (40, 8, 16), (26624, 256, 1024), (15104, 256, 1024),
    (1280, 256, 1024)])
def test_visited_keys_are_the_tiles_stepped_over(length, block, chunk):
    """Each block of queries times the whole chunks up to its last key;
    a window layer two blocks of keys a query."""
    b, c = gqa.causal_chunk(length, block, chunk)
    want = sum(b * c * -(-((i + 1) * b) // c) for i in range(length // b))
    assert gqa.causal_visited(length, block, chunk) == want
    needed = length * (length + 1) // 2
    assert needed <= want <= needed + length * (b + c)
    assert gqa.window_visited(length, 128) == 2 * min(128, length) * length
    assert gqa.window_visited(8, 128) == 2 * 8 * 8


def test_the_chunk_does_not_shrink_with_the_lengths_divisors():
    # 15,104 = 59 x 256 shares only 256 with 2,048; the keys are padded
    assert gqa.causal_chunk(15104, 256, 1024) == (256, 1024)
    assert gqa.causal_chunk(512, 256, 1024) == (256, 512)
    assert gqa.causal_chunk(8, 256, 1024) == (8, 8)


@pytest.mark.parametrize("kind,length,sizes,match", [
    ("window", 48, dict(window=9, block=8), "window of 9 keys"),
    ("window", 44, dict(window=8, block=8), "multiple of 8"),
    ("causal", 44, dict(block=8, chunk=16), "multiple of 8")])
def test_shapes_that_do_not_tile_are_refused(kind, length, sizes, match):
    q, k, v = _qkv(5, 1, length)
    with pytest.raises(ValueError, match=match):
        _run(kind, q, k, v, **sizes)

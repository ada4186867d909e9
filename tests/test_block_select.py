"""Block selection (``routest_tpu/parallel/select.py``): the two-stage
choice against a brute-force numpy choice, the forced blocks, ties, and
that no compressed key leaks the future."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import select

SIZES = dict(window=4, stride=2, block=8)
PICK = dict(top=6, block=8, init=1, local=16)
G, HG, D = 2, 2, 16


def _qk(seed, length):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (length, G, HG, D)),
            jax.random.normal(kk, (length, G, D)),
            jax.random.normal(kv, (length, G, D)))


def _brute_choice(q, k, top=6, window=4, stride=2, block=8, init=1,
                  local=16):
    """(L, G, M) bool by loops, float64."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    length = q.shape[0]
    n_blk = length // block
    n_comp = (length - window) // stride + 1
    kc = np.stack([k[stride * j:stride * j + window].mean(0)
                   for j in range(n_comp)])
    out = np.zeros((length, G, n_blk), bool)
    for t in range(length):
        vis = [j for j in range(n_comp) if stride * j + window - 1 <= t]
        for g in range(G):
            a = np.zeros(n_comp)
            for h in range(HG):
                if vis:
                    s = kc[vis, g] @ q[t, g, h] / np.sqrt(D)
                    p = np.exp(s - s.max())
                    a[vis] += p / p.sum()
            per = block // stride
            score = np.full(n_blk, -np.inf)
            for m in range(t // block + 1):
                js = [j for j in range(per * m - 1, per * m + per)
                      if 0 <= j < n_comp]
                score[m] = max(a[j] for j in js)
                if m < init or block * m + block - 1 >= t - (local - 1):
                    score[m] = np.inf
            order = sorted(range(t // block + 1),
                           key=lambda m: (-score[m], m))
            out[t, g, order[:top]] = True
    return out


def _choice(q, k, t_pos):
    kc = select.compress_keys(k, SIZES["window"], SIZES["stride"])
    with jax.default_matmul_precision("highest"):
        scores, n_vis = select.block_scores(q[t_pos], kc, t_pos,
                                            scale=D ** -0.5, **SIZES)
    return select.choose_blocks(scores, t_pos, **PICK), n_vis


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_choice_is_the_brute_force_choice(seed):
    q, k, _ = _qk(seed, 96)
    t_pos = jnp.arange(96, dtype=jnp.int32)
    got, n_vis = _choice(q, k, t_pos)
    want = _brute_choice(q, k)
    np.testing.assert_array_equal(np.asarray(got).transpose(1, 0, 2), want)
    np.testing.assert_array_equal(
        n_vis, [max(0, (t - 3) // 2 + 1) for t in range(96)])
    picked = want.sum(-1)
    assert picked[95].tolist() == [6, 6] and picked[0].tolist() == [1, 1]


def test_compressed_keys_are_window_means():
    _, k, _ = _qk(3, 32)
    kc = np.asarray(select.compress_keys(k, 4, 2))
    assert kc.shape == (16, G, D)
    for j in range(15):
        np.testing.assert_allclose(kc[j], np.asarray(k[2 * j:2 * j + 4])
                                   .mean(0), atol=1e-6)
    assert (kc[15] == 0).all()          # would reach past the end


def test_forced_blocks_are_the_first_and_the_local_window():
    t_pos = jnp.array([0, 7, 8, 40, 95], jnp.int32)
    forced = np.asarray(select.forced_blocks(t_pos, 12, 8, 1, 16))
    assert forced[0].nonzero()[0].tolist() == [0]
    assert forced[2].nonzero()[0].tolist() == [0, 1]
    # t = 40: keys 25..40 lie in blocks 3, 4, 5
    assert forced[3].nonzero()[0].tolist() == [0, 3, 4, 5]
    assert forced[4].nonzero()[0].tolist() == [0, 10, 11]
    # at the published sizes a query has 33 or 34 forced blocks
    far = np.asarray(select.forced_blocks(jnp.array([46957, 9000, 8191]),
                                          734, 64, 1, 2048))
    assert far.sum(-1).tolist() == [34, 34, 33]


def test_ties_go_to_the_lower_block_and_the_future_is_never_chosen():
    t_pos = jnp.array([95, 50], jnp.int32)
    flat = jnp.zeros((G, 2, 12), jnp.float32)
    got = np.asarray(select.choose_blocks(flat, t_pos, **PICK))
    # t = 95: forced 0, 10, 11; the other three are the lowest free ones
    assert got[0, 0].nonzero()[0].tolist() == [0, 1, 2, 3, 10, 11]
    # t = 50: forced 0, 4, 5, 6; blocks past 6 never
    assert got[1, 1].nonzero()[0].tolist() == [0, 1, 2, 4, 5, 6]


def _attend(q, k, v, lengths, rows_at, dense_len=48):
    with jax.default_matmul_precision("highest"):
        return select.block_sparse_attention(
            q, k, v, lengths, rows_at, scale=D ** -0.5, dense_len=dense_len,
            top=6, block=8, window=4, stride=2, init=1, local=16, q_block=8,
            chunk=16)


def test_attention_is_the_softmax_over_the_chosen_blocks_keys():
    q, k, v = _qk(4, 96)
    lengths, rows_at = jnp.array([96]), jnp.array([[5, 60, 94]])
    out, n_keys, n_vis, blocks = _attend(q[None], k[None], v[None], lengths,
                                         rows_at)
    chosen = _brute_choice(q, k)
    np.testing.assert_array_equal(np.asarray(blocks[0]),
                                  chosen[np.asarray(rows_at[0])])
    qn, kn, vn = (np.asarray(x, np.float64) for x in (q, k, v))
    for t in (0, 17, 60, 95):
        for g in range(G):
            keys = [s for s in range(t + 1) if chosen[t, g, s // 8]]
            assert int(n_keys[0, t, g]) == len(keys)
            for h in range(HG):
                s = kn[keys, g] @ qn[t, g, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vn[keys, g]
                np.testing.assert_allclose(out[0, t, g, h], want, atol=1e-5)


def test_a_route_below_dense_len_sees_every_causal_key():
    q, k, v = _qk(5, 96)
    two = [jnp.stack([x, x]) for x in (q, k, v)]
    out, n_keys, _, blocks = _attend(*two, jnp.array([96, 40]),
                                     jnp.array([[5, 60, 94], [3, 20, 38]]))
    np.testing.assert_array_equal(n_keys[1, :40, 0], np.arange(1, 41))
    assert int(n_keys[0, 95, 0]) < 96
    assert np.asarray(blocks[1, 2, 0]).nonzero()[0].tolist() == [0, 1, 2, 3,
                                                                 4]
    # a length class wholly below dense_len skips the first stage
    short = [x[:, :40] for x in two]
    out_s, n_keys_s, _, _ = _attend(*short, jnp.array([40, 40]),
                                    jnp.array([[3, 20, 38]] * 2))
    np.testing.assert_allclose(out_s[1], out[1, :40], atol=1e-6)


@pytest.mark.parametrize("changed", [41, 77])
def test_no_compressed_key_leaks_the_future(changed):
    """A change to token t' leaves every output at t < t' bit-equal."""
    q, k, v = _qk(6, 96)
    lengths, rows_at = jnp.array([96]), jnp.array([[5, 30, 40]])
    base = _attend(q[None], k[None], v[None], lengths, rows_at)
    q2, k2, v2 = (x.at[changed:].set(x[changed:] * -3.0 + 1.0)
                  for x in (q, k, v))
    other = _attend(q2[None], k2[None], v2[None], lengths, rows_at)
    np.testing.assert_array_equal(base[0][0, :changed],
                                  other[0][0, :changed])
    np.testing.assert_array_equal(base[1][0, :changed],
                                  other[1][0, :changed])
    np.testing.assert_array_equal(base[3], other[3])
    assert not np.array_equal(base[0][0, changed:], other[0][0, changed:])

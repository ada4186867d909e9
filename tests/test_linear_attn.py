"""The chunked decayed linear attention against the token-by-token
recurrence it rewrites (``routest_tpu/parallel/linear_attn.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import linear_attn as la

H, D = 4, 8


def _qkv(seed, b_sz, length, heads=H, d=D):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b_sz, length, heads, d)) for k in keys]


def _chunked(q, k, v, log_lam, lengths, chunk):
    """Padded to whole chunks, at ``highest``: float32 throughout."""
    length = q.shape[1]
    c = min(chunk, length)
    pad = -(-length // c) * c - length

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))

    with jax.default_matmul_precision("highest"):
        out, state = la.chunked(padded(q), padded(k), padded(v), log_lam,
                                lengths, D ** -0.5, chunk=chunk)
    return out[:, :length], state


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("layer", [0, 9, 31])
def test_chunked_is_the_recurrence(chunk, layer):
    """Chunks of 8 and 16, and a route shorter than a chunk (64)."""
    q, k, v = _qkv(layer, 2, 40)
    lengths = jnp.array([40, 19])
    log_lam = la.log_decay(H, layer, 32)
    want, want_state = la.recurrent(q, k, v, log_lam, lengths, D ** -0.5)
    got, state = _chunked(q, k, v, log_lam, lengths, chunk)
    live = (np.arange(40)[None] < np.asarray(lengths)[:, None])[..., None,
                                                               None]
    np.testing.assert_allclose(np.asarray(got) * live,
                               np.asarray(want) * live, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)


def test_padding_and_a_neighbour_change_nothing():
    q, k, v = _qkv(3, 2, 32)
    lengths = jnp.array([21, 32])
    log_lam = la.log_decay(H, 9, 32)
    out, state = _chunked(q, k, v, log_lam, lengths, 8)
    # other tokens past route 0's end, another neighbour
    q2, k2, v2 = (x.at[0, 21:].set(7.0).at[1].set(-x[1]) for x in (q, k, v))
    out2, state2 = _chunked(q2, k2, v2, log_lam, lengths, 8)
    np.testing.assert_array_equal(out[0, :21], out2[0, :21])
    np.testing.assert_array_equal(state[0], state2[0])
    # the route alone, unpadded
    alone, alone_state = _chunked(q[:1, :21], k[:1, :21], v[:1, :21],
                                  log_lam, jnp.array([21]), 8)
    np.testing.assert_allclose(out[0, :21], alone[0], atol=1e-6)
    np.testing.assert_allclose(state[0], alone_state[0], atol=1e-6)


def test_an_empty_slot_keeps_a_zero_state():
    q, k, v = _qkv(4, 2, 16)
    _, state = _chunked(q, k, v, la.log_decay(H, 9, 32), jnp.array([16, 0]),
                        8)
    assert float(jnp.abs(state[1]).max()) == 0.0
    assert float(jnp.abs(state[0]).max()) > 0.0


def test_published_decays_and_both_extreme_heads_finite_at_4k_tokens():
    lam = np.exp(np.asarray(la.log_decay(32, 9, 32)))
    assert abs(lam[0] - 0.5506) < 1e-3 and abs(lam[-1] - 0.9972) < 1e-4
    assert (np.diff(lam) > 0).all()
    # the fastest and the slowest head of the published layer 9
    log_lam = la.log_decay(32, 9, 32)[jnp.array([0, 31])]
    q, k, v = _qkv(5, 1, 4096, heads=2, d=16)
    out, state = la.chunked(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                            v.astype(jnp.bfloat16), log_lam,
                            jnp.array([4096]), 0.25, chunk=256)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(state).all())
    want, want_state = la.recurrent(
        *(x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k, v)),
        log_lam, jnp.array([4096]), 0.25)
    gap = (np.linalg.norm(np.asarray(out, np.float32) - np.asarray(want))
           / np.linalg.norm(np.asarray(want)))
    assert gap < 0.01, gap
    assert (np.linalg.norm(np.asarray(state) - np.asarray(want_state))
            / np.linalg.norm(np.asarray(want_state))) < 1e-4


def test_a_length_that_is_not_whole_chunks_is_refused():
    q, k, v = _qkv(6, 1, 20)
    with pytest.raises(ValueError):
        la.chunked(q, k, v, la.log_decay(H, 0, 32), jnp.array([20]), 1.0,
                   chunk=8)
    assert la.chunk_count(47104, 256) == 184 and la.chunk_count(5, 8) == 1

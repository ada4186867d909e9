"""Dense causal attention over two-part keys (``parallel/latent.py``)
against a brute-force oracle; its kernel form (``select``'s fused step
under ``interpret=True``, a 192-wide value) against its XLA form; and
the pure function that chooses between the two."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import gqa, latent, select


def _arrays(dtype, routes, length, heads, d, d_r, d_v, seed=0):
    """Queries, keys and values scaled so that a logit has standard
    deviation about 2 at ``scale`` = (d + d_r) ** -0.5."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    gain = 2.0 ** 0.5

    def draw(key, shape, g=gain):
        return (g * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return (draw(ks[0], (routes, length, heads, d)),
            draw(ks[1], (routes, length, heads, d_r)),
            draw(ks[2], (routes, length, heads, d)),
            draw(ks[3], (routes, length, d_r)),
            draw(ks[4], (routes, length, heads, d_v), 1.0))


def _run(q, q_shared, k, k_shared, v, block, chunk, scale):
    length = q.shape[1]
    block = min(block, length)
    padded = latent.padded_keys(length, block, chunk)
    widen = ((0, 0), (0, padded - length))

    def q_fn(b, t0):
        return (jax.lax.dynamic_slice_in_dim(q[b], t0, block, 0),
                jax.lax.dynamic_slice_in_dim(q_shared[b], t0, block, 0))

    return jax.jit(lambda k, ks, v: latent.causal_attention(
        q_fn, jnp.pad(k, widen + ((0, 0), (0, 0))),
        jnp.pad(ks, widen + ((0, 0),)), jnp.pad(v, widen + ((0, 0), (0, 0))),
        length=length, scale=scale, block=block, chunk=chunk))(k, k_shared, v)


def _oracle(q, q_shared, k, k_shared, v, scale):
    s = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
         + jnp.einsum("bqhd,bkd->bhqk", q_shared, k_shared)) * scale
    length = q.shape[1]
    seen = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("length,block,chunk", [
    (96, 8, 16), (72, 8, 16), (40, 8, 64), (24, 32, 16), (64, 16, 16)])
def test_causal_attention_is_the_brute_force_softmax(length, block, chunk):
    """Lengths that are and are not whole chunks, a chunk longer than
    the route, a block longer than the route: float32 to rounding, the
    taps exactly ``t + 1`` keys from key 0."""
    arrays = _arrays(jnp.float32, 2, length, 2, 8, 4, 12)
    scale = 12 ** -0.5
    with jax.default_matmul_precision("highest"):
        out, n_keys, first = _run(*arrays, block, chunk, scale)
        want = _oracle(*arrays, scale)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(
        n_keys, np.tile(np.arange(length) + 1, (2, 1)))
    assert not np.asarray(first).any()


def test_keys_not_padded_to_whole_chunks_are_refused():
    q, q_shared, k, k_shared, v = _arrays(jnp.float32, 1, 40, 2, 8, 4, 12)
    assert latent.padded_keys(40, 8, 16) == 48
    assert latent.padded_keys(26112, 256, 1024) == 26624
    assert latent.padded_keys(2816, 256, 1024) == 3072
    with pytest.raises(ValueError, match="chunks of 16"):
        latent.causal_attention(lambda b, t0: (q[b, :8], q_shared[b, :8]),
                                k, k_shared, v, length=40, scale=1.0,
                                block=8, chunk=16)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_form_is_the_xla_form(monkeypatch, dtype):
    """Heads of 128 + 64 wide keys and 192-wide values, two routes of
    1,024 tokens in blocks of 256 over tiles of 512 keys: with a chunk
    of the kernel's key tile both forms add the same terms in the same
    order."""
    arrays = _arrays(jnp.dtype(dtype), 2, 1024, 4, 128, 64, 192, seed=2)
    scale = 192 ** -0.5
    want, n_want, _ = _run(*arrays, 256, 512, scale)
    assert latent.latent_path(4, 1024, 256, 512, 128, 64, 192,
                              arrays[0].dtype) == "xla"     # this is a CPU
    monkeypatch.setattr(latent, "latent_path", lambda *a, **kw: "fused")
    monkeypatch.setattr(latent, "_attend_fused", functools.partial(
        select._attend_fused, interpret=True, head_tile=2))
    got, n_got, first = _run(*arrays, 256, 512, scale)
    assert got.shape == (2, 1024, 4, 192) and got.dtype == arrays[0].dtype
    tol = 2e-6 if dtype == "float32" else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_array_equal(n_got, n_want)
    assert not np.asarray(first).any()


# ── the choice ───────────────────────────────────────────────────────

CELL = dict(heads=64, d=128, d_shared=64, d_v=192, dtype=jnp.bfloat16)
CELL_CLASSES = [26112, 17152, 13312, 10752, 8960, 6400, 5120, 2816]


@pytest.mark.parametrize("length", CELL_CLASSES)
def test_the_cells_length_classes_take_the_kernel_on_a_tpu(length):
    def path(backend, **changes):
        a = dict(CELL, **changes)
        return latent.latent_path(a["heads"], length, 256, 1024, a["d"],
                                  a["d_shared"], a["d_v"], a["dtype"],
                                  backend=backend)

    assert path("tpu") == "fused" and path("cpu") == "xla"
    assert path("tpu", dtype=jnp.float32) == "xla"
    assert path("tpu", heads=4) == "xla" and path("tpu", d=96) == "xla"
    assert path("tpu", d_v=128) == "fused" and path("tpu", d_v=100) == "xla"
    # whole tiles of 1,024 keys, chunk for chunk what the plan counts
    assert latent.padded_keys(length, 256, 1024) % 1024 == 0
    assert gqa.causal_chunk(length, 256, 1024) == (256, 1024)


def test_a_toy_width_stays_in_xla_everywhere():
    assert latent.latent_path(4, 96, 8, 16, 16, 8, 24, jnp.bfloat16,
                              backend="tpu") == "xla"

"""The state-space scan (``parallel/ssd.py``) at toy widths on the CPU:
the recurrence as written, its chunked rewrite in XLA and the kernel in
interpret mode give the same outputs and the same final states; ragged
routes in one batch; a padded position (``dt = 0``) writes nothing into
the state, so the state carried out is the one at the route's last real
token; each group of heads reads its own B and C; the form is chosen
from shapes, dtype and backend alone; the convolution is causal."""

import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import ssd

B, L, H, P, G, N, C = 3, 32, 16, 16, 2, 16, 8
LENGTHS = np.array([32, 19, 5])


def _inputs(seed=0, lengths=LENGTHS, length=L):
    rng = np.random.default_rng(seed)
    live = np.arange(length)[None] < lengths[:, None]
    b_sz = len(lengths)
    x = rng.normal(size=(b_sz, length, H, P)).astype(np.float32)
    b = rng.normal(size=(b_sz, length, G, N)).astype(np.float32)
    c = rng.normal(size=(b_sz, length, G, N)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                            size=(b_sz, length, H))).astype(np.float32)
    dt = np.where(live[..., None], dt, 0.0).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, size=H).astype(np.float32)
    d = rng.normal(size=H).astype(np.float32)
    return x, dt, a, b, c, d, live


def _fused(x, dt, a, b, c, d):
    """The kernel in interpret mode over the conv's lane layout."""
    b_sz, length = x.shape[:2]
    xbc = np.concatenate([x.reshape(b_sz, length, H * P),
                          b.reshape(b_sz, length, G * N),
                          c.reshape(b_sz, length, G * N)], -1)
    cum = np.cumsum((dt * a).reshape(b_sz, length // C, C, H),
                    2).reshape(b_sz, length, H)
    tiles = jnp.asarray([ssd.b_c_group(g * ssd.HEAD_TILE, H, G)
                         for g in range(H // ssd.HEAD_TILE)], jnp.int32)
    y, s = ssd._scan_fused(jnp.asarray(xbc), jnp.asarray(dt).transpose(0, 2, 1),
                           jnp.asarray(cum).transpose(0, 2, 1), jnp.asarray(d),
                           tiles, heads=H, groups=G, state=N, chunk=C,
                           interpret=True)
    return np.asarray(y).reshape(b_sz, length, H, P), np.asarray(s)


@pytest.fixture(scope="module")
def three_forms():
    x, dt, a, b, c, d, live = _inputs()
    rec = ssd.recurrent(x, dt, a, b, c, d)
    chk = ssd.chunked(x, dt, a, b, c, d, chunk=C)
    return (x, dt, a, b, c, d, live), rec, chk, _fused(x, dt, a, b, c, d)


@pytest.mark.parametrize("form", ["chunked", "fused"])
def test_each_form_is_the_recurrence(three_forms, form):
    (_, _, _, _, _, _, live), (y_r, s_r), chk, fused = three_forms
    y, s = chk if form == "chunked" else fused
    scale = float(np.abs(y_r).max())
    # the real positions' outputs and every final state
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_r)[live],
                               atol=2e-6 * scale)
    np.testing.assert_allclose(s, s_r, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["recurrent", "chunked", "fused"])
def test_the_state_is_the_one_at_the_last_real_token(three_forms, form):
    (x, dt, a, b, c, d, _), rec, chk, fused = three_forms
    s = np.asarray({"recurrent": rec, "chunked": chk, "fused": fused}[form][1])
    for r, n in enumerate(LENGTHS):
        _, alone = ssd.recurrent(x[r:r + 1, :n], dt[r:r + 1, :n], a,
                                 b[r:r + 1, :n], c[r:r + 1, :n], d)
        np.testing.assert_allclose(s[r], np.asarray(alone)[0], rtol=1e-5,
                                   atol=1e-6)


def test_a_padded_position_writes_nothing_into_the_state():
    """What follows a route's end, however large, leaves its state as it
    was; a route's real outputs do not see it either."""
    x, dt, a, b, c, d, live = _inputs(1)
    noisy = np.where(live[..., None, None], x, 1e3 * x)
    y0, s0 = ssd.chunked(x, dt, a, b, c, d, chunk=C)
    y1, s1 = ssd.chunked(noisy, dt, a, b, c, d, chunk=C)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(y0)[live], np.asarray(y1)[live])
    # with dt left on at the padding the state goes on: what the
    # planted fault ``dt_on_padding`` relies on being seen
    dt_on = np.full_like(dt, 0.1)
    _, s2 = ssd.chunked(x, dt_on, a, b, c, d, chunk=C)
    assert not np.allclose(np.asarray(s2)[1], np.asarray(s0)[1])


def test_routes_in_one_batch_are_independent():
    x, dt, a, b, c, d, _ = _inputs(2)
    y, s = ssd.chunked(x, dt, a, b, c, d, chunk=C)
    for r in range(len(LENGTHS)):
        y1, s1 = ssd.chunked(x[r:r + 1], dt[r:r + 1], a, b[r:r + 1],
                             c[r:r + 1], d, chunk=C)
        np.testing.assert_allclose(np.asarray(y)[r], np.asarray(y1)[0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s)[r], np.asarray(s1)[0],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["recurrent", "chunked", "fused"])
def test_a_group_of_heads_reads_its_own_b_and_c(form):
    """Changing group 1's B and C changes heads 8-15 alone; with
    ``b_c_group`` reversed every form reads the other group."""
    x, dt, a, b, c, d, _ = _inputs(3)
    b2, c2 = b.copy(), c.copy()
    b2[:, :, 1] += 1.0
    c2[:, :, 1] -= 1.0

    def run(b, c):
        if form == "recurrent":
            return ssd.recurrent(x, dt, a, b, c, d)
        if form == "chunked":
            return ssd.chunked(x, dt, a, b, c, d, chunk=C)
        return _fused(x, dt, a, b, c, d)

    y0, s0 = (np.asarray(v) for v in run(b, c))
    y1, s1 = (np.asarray(v) for v in run(b2, c2))
    first = slice(0, H // G)
    np.testing.assert_array_equal(y0[:, :, first], y1[:, :, first])
    np.testing.assert_array_equal(s0[:, first], s1[:, first])
    assert not np.allclose(s0[:, H // G:], s1[:, H // G:])
    swapped = (b[:, :, ::-1], c[:, :, ::-1])
    real = ssd.b_c_group
    try:
        ssd.b_c_group = lambda h, heads, groups: (groups - 1) - h // (
            heads // groups)
        y2, s2 = (np.asarray(v) for v in run(b, c))
    finally:
        ssd.b_c_group = real
    y3, s3 = (np.asarray(v) for v in run(*swapped))
    np.testing.assert_allclose(s2, s3, rtol=1e-5, atol=1e-6)


def test_no_exponent_overflows_at_the_fastest_decay():
    """``dt A`` of -16 a token over whole chunks: every decay underflows
    to what float32 makes of it, nothing is inf or nan."""
    x, dt, a, b, c, d, _ = _inputs(4)
    dt = np.full_like(dt, 1.0)
    a = np.full_like(a, -16.0)
    for y, s in (ssd.chunked(x, dt, a, b, c, d, chunk=C),
                 _fused(x, dt, a, b, c, d)):
        assert np.isfinite(np.asarray(y)).all()
        assert np.isfinite(np.asarray(s)).all()
    y_r, s_r = ssd.recurrent(x, dt, a, b, c, d)
    y, s = ssd.chunked(x, dt, a, b, c, d, chunk=C)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), rtol=1e-5,
                               atol=1e-5)


def test_bfloat16_products_stay_near_the_float32_recurrence():
    x, dt, a, b, c, d, _ = _inputs(5)
    y_r, s_r = ssd.recurrent(x, dt, a, b, c, d)
    bf = [jnp.asarray(v, jnp.bfloat16) for v in (x, b, c)]
    y, s = ssd.chunked(bf[0], dt, a, bf[1], bf[2], d, chunk=C)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    gap = (np.linalg.norm(np.asarray(s) - np.asarray(s_r))
           / np.linalg.norm(np.asarray(s_r)))
    assert gap < 0.02


def test_the_scan_reads_the_convolutions_lanes():
    """``scan`` over one array of the lanes [x | B | C] is ``chunked``
    over its parts."""
    x, dt, a, b, c, d, _ = _inputs(6)
    xbc = np.concatenate([x.reshape(B, L, H * P), b.reshape(B, L, G * N),
                          c.reshape(B, L, G * N)], -1)
    y, s = ssd.scan(jnp.asarray(xbc), dt, a, d, heads=H, groups=G, state=N,
                    chunk=C)
    y_c, s_c = ssd.chunked(x, dt, a, b, c, d, chunk=C)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(y_c).reshape(B, L, H * P))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_c))


@pytest.mark.parametrize("shape,dtype,backend,want", [
    ((32, 128, 256, 2, 128), jnp.bfloat16, "tpu", "fused"),   # the cell's
    ((32, 128, 256, 2, 128), jnp.bfloat16, "cpu", "xla"),
    ((32, 128, 256, 2, 128), jnp.float32, "tpu", "xla"),
    ((16, 8, 16, 2, 8), jnp.bfloat16, "tpu", "xla"),          # toy widths
    ((24, 128, 256, 2, 128), jnp.bfloat16, "tpu", "xla"),     # 12 a group
    ((32, 128, 256, 2, 64), jnp.bfloat16, "tpu", "xla"),      # half lanes
])
def test_the_form_is_chosen_from_shapes_dtype_and_backend(shape, dtype,
                                                          backend, want):
    heads, p, n, groups, chunk = shape
    assert ssd.ssd_path(heads, p, n, dtype, backend, groups=groups,
                        chunk=chunk) == want


def test_the_convolution_is_causal_with_its_bias():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 12, 5)).astype(np.float32)
    w = rng.normal(size=(4, 5)).astype(np.float32)
    bias = rng.normal(size=(5,)).astype(np.float32)
    out = np.asarray(ssd.causal_conv(x, w, bias))
    for t in range(12):
        want = bias + sum(w[k] * x[:, t - 3 + k] for k in range(4)
                          if t - 3 + k >= 0)
        np.testing.assert_allclose(out[:, t], want, rtol=1e-5, atol=1e-6)
    # a later token moves no earlier output
    x2 = x.copy()
    x2[:, 7] += 5.0
    out2 = np.asarray(ssd.causal_conv(x2, w, bias))
    np.testing.assert_array_equal(out[:, :7], out2[:, :7])


def test_chunk_count():
    assert ssd.chunk_count(14848, 128) == 116
    assert ssd.chunk_count(1024, 128) == 8
    assert ssd.chunk_count(40, 128) == 1

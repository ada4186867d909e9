"""Dense causal attention over two-part keys (``parallel/latent.py``)
against a brute-force oracle; its kernel (``latent._causal_fused`` under
``interpret=True``, a 192-wide value) against the brute force and its
XLA form; the tables its grid and the scorer's counters read; and the
pure function that chooses between the two forms."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import gqa, latent, select


def _arrays(dtype, routes, length, heads, d, d_r, d_v, seed=0):
    """Queries, keys and values (B, L, H, *) scaled so that a logit has
    standard deviation about 2 at ``scale`` = (d + d_r) ** -0.5."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    gain = 2.0 ** 0.5

    def draw(key, shape, g=gain):
        return (g * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return (draw(ks[0], (routes, length, heads, d)),
            draw(ks[1], (routes, length, heads, d_r)),
            draw(ks[2], (routes, length, heads, d)),
            draw(ks[3], (routes, length, d_r)),
            draw(ks[4], (routes, length, heads, d_v), 1.0))


def _laid_out(q, q_shared, k, k_shared, v, block, chunk, fill=0.0):
    """The arrays as the layer's expansions write them: by head, the
    keys and values padded to whole tiles with ``fill``."""
    length = q.shape[1]
    widen = ((0, 0), (0, latent.padded_keys(length, min(block, length),
                                            chunk) - length))
    pad = functools.partial(jnp.pad, constant_values=fill)
    return (q.transpose(0, 2, 1, 3), q_shared.transpose(0, 2, 1, 3),
            pad(k, widen + ((0, 0), (0, 0))).transpose(0, 2, 1, 3),
            pad(k_shared, widen + ((0, 0),)),
            pad(v, widen + ((0, 0), (0, 0))).transpose(0, 2, 3, 1))


def _run(q, q_shared, k, k_shared, v, block, chunk, scale, fill=0.0):
    length = q.shape[1]
    return jax.jit(functools.partial(
        latent.causal_attention, length=length, scale=scale, block=block,
        chunk=chunk))(*_laid_out(q, q_shared, k, k_shared, v, block, chunk,
                                 fill))


def _oracle(q, q_shared, k, k_shared, v, scale):
    f32 = [x.astype(jnp.float32) for x in (q, q_shared, k, k_shared, v)]
    q, q_shared, k, k_shared, v = f32
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
    q, q_shared, k, k_shared, v = _laid_out(
        *_arrays(jnp.float32, 1, 40, 2, 8, 4, 12), 8, 16)
    assert latent.padded_keys(40, 8, 16) == 48
    assert latent.padded_keys(26112, 256, 1024) == 26624
    assert latent.padded_keys(2816, 256, 1024) == 3072
    with pytest.raises(ValueError, match="chunks of 16"):
        latent.causal_attention(q, q_shared, k[:, :, :40], k_shared[:, :40],
                                v[..., :40], length=40, scale=1.0, block=8,
                                chunk=16)


def _kernel(monkeypatch):
    """``causal_attention`` with its fused form, interpreted, two heads a
    group: what runs on the chip, here."""
    monkeypatch.setattr(latent, "latent_path", lambda *a, **kw: "fused")
    monkeypatch.setattr(latent, "_causal_fused", functools.partial(
        latent._causal_fused, interpret=True, head_tile=2))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_form_is_the_xla_form(monkeypatch, dtype):
    """Heads of 128 + 64 wide keys and 192-wide values, two routes of
    1,024 tokens in blocks of 256 over tiles of 512 keys: both forms
    multiply the same pairs, the kernel's interior tiles in one piece
    where the XLA form takes them a block of keys at a time."""
    arrays = _arrays(jnp.dtype(dtype), 2, 1024, 4, 128, 64, 192, seed=2)
    scale = 192 ** -0.5
    want, n_want, _ = _run(*arrays, 256, 512, scale)
    assert latent.latent_path(4, 1024, 256, 512, 128, 64, 192,
                              arrays[0].dtype) == "xla"     # this is a CPU
    _kernel(monkeypatch)
    got, n_got, first = _run(*arrays, 256, 512, scale)
    assert got.shape == (2, 1024, 4, 192) and got.dtype == arrays[0].dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    else:   # the probabilities rescaled at other points before their
        # rounding to bfloat16: a rounding or two apart, and as near the
        # float32 softmax as the XLA form is
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(_oracle(*arrays, scale))
        assert np.abs(got - want).max() <= 2 ** -6
        assert np.linalg.norm(got - exact) <= 1.05 * np.linalg.norm(
            want - exact)
    np.testing.assert_array_equal(n_got, n_want)
    assert not np.asarray(first).any()


# At toy widths with a 1.5-lane value (d 128, shared 64, dv 192, two
# heads a group): blocks of 128 queries over tiles of 512 keys, so that
# block i's diagonal falls at key 128 (i + 1) inside its tile; 640
# tokens: the padded keys are 1,024, two tiles, the second mostly past
# the routes.
@pytest.mark.parametrize("dtype,tol", [("float32", 5e-6), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("length", [640, 512, 128])
def test_the_kernel_is_the_brute_force_softmax(monkeypatch, dtype, tol,
                                               length):
    arrays = _arrays(jnp.dtype(dtype), 2, length, 4, 128, 64, 192, seed=5)
    scale = 192 ** -0.5
    _kernel(monkeypatch)
    with jax.default_matmul_precision("highest"):
        got, n_keys, first = _run(*arrays, 128, 512, scale)
        want = _oracle(*arrays, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)
    np.testing.assert_array_equal(
        n_keys, np.tile(np.arange(length) + 1, (2, 1)))
    assert not np.asarray(first).any()


def test_padded_keys_and_the_future_never_reach_the_kernel(monkeypatch):
    """Two routes in one step: the keys and values past the routes are
    1e30, which any score with them would carry to inf and the softmax
    to NaN, and a change to the routes' last token or to route 0 leaves
    the earlier outputs and route 1 alone."""
    arrays = _arrays(jnp.float32, 2, 640, 4, 128, 64, 192, seed=7)
    scale = 192 ** -0.5
    _kernel(monkeypatch)
    out, _, _ = _run(*arrays, 128, 512, scale, fill=1e30)
    assert np.isfinite(np.asarray(out)).all()
    q, q_shared, k, k_shared, v = arrays
    later = (q, q_shared, k.at[:, -1].add(3.0), k_shared, v.at[:, -1].set(9.0))
    moved, _, _ = _run(*later, 128, 512, scale, fill=1e30)
    np.testing.assert_array_equal(moved[:, :-1], out[:, :-1])
    other = (q, q_shared, k.at[0].multiply(-1.0), k_shared, v)
    moved, _, _ = _run(*other, 128, 512, scale, fill=1e30)
    np.testing.assert_array_equal(moved[1], out[1])
    assert not np.array_equal(moved[0], out[0])


# ── the grid and the counters ────────────────────────────────────────

CELL = dict(heads=64, d=128, d_shared=64, d_v=192, dtype=jnp.bfloat16)
CELL_CLASSES = [26112, 17152, 13312, 10752, 8960, 6400, 5120, 2816]
# the cell's plan: (routes, padded length) a step
CELL_STEPS = [(1, 26112), (1, 17152), (1, 13312), (1, 10752), (2, 8960),
              (1, 6400), (2, 5120), (1, 2816)]


@pytest.mark.parametrize("routes,length,block,chunk", [
    (2, 640, 128, 512), (1, 96, 8, 16), (3, 40, 8, 64), (1, 24, 32, 16),
    (1, 26112, 256, 1024)])
def test_the_grid_is_the_causal_triangle(routes, length, block, chunk):
    """Every (route, block, tile) whose tile holds a key at or before
    the block's last, in order, the diagonal last; nothing past it."""
    grid = latent.causal_grid(routes, length, block, chunk)
    b, c = gqa.causal_chunk(length, block, chunk)
    want = [(r, i, j) for r in range(routes) for i in range(length // b)
            for j in range(length // c + 1) if j * c < (i + 1) * b]
    assert [tuple(row) for row in grid] == want
    assert grid.dtype == np.int32
    # one route's pairs: the blocks' keys up to their own, brute force
    pairs = sum(min(c, (i + 1) * b - j * c) * b
                for r, i, j in want if r == 0)
    assert latent.visited(length, block, chunk) == pairs == b * b * sum(
        range(1, length // b + 1))
    steps = latent.grid_steps(routes, length, block, chunk, heads=16)
    assert steps["diagonal"] == 2 * routes * (length // b)
    assert steps["interior"] + steps["diagonal"] == 2 * len(grid)


def test_the_cells_layer_has_no_empty_step_and_cuts_the_diagonal():
    """ISSUE 40's reckoning of the cell's pass: 25,016 working grid steps
    a layer (8 groups of heads), none empty where the parent's grid held
    22,720, and the pairs at 256-key granularity on the diagonal."""
    steps = [latent.grid_steps(r, n, 256, 1024, CELL["heads"])
             for r, n in CELL_STEPS]
    assert sum(s["interior"] + s["diagonal"] for s in steps) == 25016
    assert sum(s["diagonal"] for s in steps) == 8 * sum(
        r * n // 256 for r, n in CELL_STEPS)
    lengths = [2590, 3966, 5109, 6255, 7502, 8945, 10728, 13135, 16923,
               25908]
    needed = sum(n * (n + 1) // 2 for n in lengths)
    visited = sum(r * latent.visited(n, 256, 1024) for r, n in CELL_STEPS)
    chunks = sum(r * gqa.causal_visited(n, 256, 1024) for r, n in CELL_STEPS)
    assert round(visited / needed, 4) == 1.0601
    assert round(chunks / needed, 4) == 1.1158       # the parent's


# K-EXAONE's full layers keep the chunk count of the grouped form (its
# counter): the cell's classes, 256-query blocks over 1,024-key chunks.
@pytest.mark.parametrize("length,want", [
    (26624, 368050176), (15104, 121896960), (11008, 66322432),
    (7168, 29360128), (5120, 15728640), (3328, 7340032), (2304, 3932160),
    (1280, 1572864)])
def test_the_grouped_form_counts_what_it_did(length, want):
    assert gqa.causal_visited(length, 256, 1024) == want


def test_the_kernel_is_latents_own_and_selects_kernel_stays():
    """``latent`` no longer reaches the selecting kernel, whose path and
    name ``route-lm-score``'s shapes still get."""
    assert "_attend_fused" not in vars(latent)
    assert latent.KERNEL == "latent_attention_step"
    assert select.attention_path(128, 256, 2048, 128, 64, 128, jnp.bfloat16,
                                 backend="tpu") == "fused"
    import inspect
    name = inspect.signature(select._attend_fused).parameters["name"]
    assert name.default == "selected_attention_step"


# ── the choice ───────────────────────────────────────────────────────


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
    # whole tiles of 1,024 keys, whole blocks of 256 queries in a tile
    assert latent.padded_keys(length, 256, 1024) % 1024 == 0
    assert gqa.causal_chunk(length, 256, 1024) == (256, 1024)


def test_a_toy_width_stays_in_xla_everywhere():
    assert latent.latent_path(4, 96, 8, 16, 16, 8, 24, jnp.bfloat16,
                              backend="tpu") == "xla"

"""The window step of ``windowed_attention`` as a kernel (the Pallas
kernel, here under ``interpret=True``) against the XLA body it stands in
for; the pure function that chooses between the two; and what the model
says of the choice."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import select

D_V = 128
LENGTH, ROUTES, HEADS, BLOCK = 1024, 2, 2, 256
TILES = dict(q_tile=128, head_tile=2)     # the least tile: whole lanes
WIDTHS = {"192+64": (192, 64), "128+64": (128, 64)}


def _scale(d, d_shared):
    return 1.0 / np.sqrt(d + d_shared)


@functools.lru_cache(maxsize=None)
def _arrays(dtype, widths, length=LENGTH, seed=0):
    """Queries, both key parts and values of two routes, scaled so that
    a logit has standard deviation about 2."""
    d, d_shared = WIDTHS[widths]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    gain = (2.0 / _scale(d, d_shared) / np.sqrt(d + d_shared)) ** 0.5

    def draw(key, shape, g=gain):
        return (g * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return (draw(ks[0], (ROUTES, length, HEADS, d)),
            draw(ks[1], (ROUTES, length, HEADS, d_shared)),
            draw(ks[2], (ROUTES, length, HEADS, d)),
            draw(ks[3], (ROUTES, length, d_shared)),
            draw(ks[4], (ROUTES, length, HEADS, D_V), 1.0))


def _q_fn(q, q_shared, block):
    def q_fn(b, t0):
        return (jax.lax.dynamic_slice_in_dim(q[b], t0, block, 0),
                jax.lax.dynamic_slice_in_dim(q_shared[b], t0, block, 0))
    return q_fn


def _whole(arrays, window, form, monkeypatch, block=BLOCK, tiles=TILES):
    """``windowed_attention`` itself with the choice answered for it:
    the kernel interpreted at a toy tiling, or the XLA body."""
    q, q_shared, k, k_shared, v = arrays
    monkeypatch.setattr(select, "window_path", lambda *a, **kw: form)
    monkeypatch.setattr(select, "_window_fused", functools.partial(
        select._window_fused, interpret=True, **tiles))
    out, n_keys, first = select.windowed_attention(
        _q_fn(q, q_shared, min(block, k.shape[1])), k, k_shared, v,
        window=window, scale=_scale(k.shape[-1], k_shared.shape[-1]),
        block=block)
    return (np.asarray(out, np.float32), np.asarray(n_keys),
            np.asarray(first))


_BOTH = {}


def _both(dtype, widths, window, monkeypatch):
    """Both forms over two routes, computed once a case."""
    key = (dtype, widths, window)
    if key not in _BOTH:
        arrays = _arrays(jnp.dtype(dtype), widths)
        _BOTH[key] = (_whole(arrays, window, "fused", monkeypatch),
                      _whole(arrays, window, "xla", monkeypatch))
    return _BOTH[key]


def _tol(dtype):
    # float32: the accumulate order; bfloat16: one ulp of values of a
    # few units (the kernel rounds its probabilities against a running
    # max, the XLA body against the span's)
    return 1e-5 if dtype == "float32" else 2 ** -5


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("window", [513, 129, 2])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_window_kernel_is_the_xla_body(dtype, widths, window, where,
                                       monkeypatch):
    """Two routes a step; the first block (its window cut at position
    0), one in the middle and the last."""
    (got, _, _), (want, _, _) = _both(dtype, widths, window, monkeypatch)
    i = {"first": 0, "middle": LENGTH // BLOCK // 2,
         "last": LENGTH // BLOCK - 1}[where]
    rows = slice(i * BLOCK, (i + 1) * BLOCK)
    assert np.isfinite(got[:, rows]).all()
    np.testing.assert_allclose(got[:, rows], want[:, rows], atol=_tol(dtype),
                               rtol=_tol(dtype))
    # the second route is not the first
    assert np.abs(want[0, rows] - want[1, rows]).max() > 0.1


@pytest.mark.parametrize("window", [513, 129, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_both_forms_report_the_same_keys_exactly(dtype, window, monkeypatch):
    (_, n_got, first_got), (_, n_want, first_want) = _both(
        dtype, "192+64", window, monkeypatch)
    np.testing.assert_array_equal(n_got, n_want)
    np.testing.assert_array_equal(first_got, first_want)
    t = np.arange(LENGTH)
    np.testing.assert_array_equal(n_want[0], np.minimum(t + 1, window))
    np.testing.assert_array_equal(first_want[1],
                                  np.maximum(t - window + 1, 0))


@pytest.mark.parametrize("length,block", [(256, 128), (512, 512), (640, 128)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_route_shorter_than_one_span(dtype, length, block, monkeypatch):
    """The span is cut to the length: the kernel fetches the whole route
    for every tile of queries."""
    arrays = tuple(a[:, :length] for a in _arrays(jnp.dtype(dtype),
                                                  "192+64"))
    assert length <= 128 + 512        # a tile's span at a window of 513
    tiles = dict(q_tile=128, head_tile=1)
    got, n_got, first_got = _whole(arrays, 513, "fused", monkeypatch, block,
                                   tiles)
    want, n_want, first_want = _whole(arrays, 513, "xla", monkeypatch, block,
                                      tiles)
    np.testing.assert_allclose(got, want, atol=_tol(dtype), rtol=_tol(dtype))
    np.testing.assert_array_equal(n_got, n_want)
    np.testing.assert_array_equal(first_got, first_want)
    assert n_want[0, -1] == min(length, 513)


@pytest.mark.parametrize("q_tile,head_tile", [(256, 2), (128, 2), (128, 1)])
def test_every_tiling_gives_the_same(q_tile, head_tile, monkeypatch):
    """A tile of queries as large as the block or half of it, one head a
    step or two: what the chip's sweep chooses among. The softmax is
    over one span either way, so only the span's extent differs."""
    arrays = _arrays(jnp.dtype("float32"), "192+64")
    got, _, _ = _whole(arrays, 129, "fused", monkeypatch, tiles=dict(
        q_tile=q_tile, head_tile=head_tile))
    (_, _, _), (want, _, _) = _both("float32", "192+64", 129, monkeypatch)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [513, 129, 2])
@pytest.mark.parametrize("form", ["fused", "xla"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_key_outside_the_window_adds_no_mass(dtype, form, window,
                                               monkeypatch):
    """Values are 1 but for a few planted keys worth 1,000: a query
    whose window holds none of them reads 1, which says that no mass
    came from outside (a leak of a thousandth of the mass reads 2)."""
    q, q_shared, k, k_shared, v = _arrays(jnp.dtype(dtype), "192+64")
    planted = np.zeros(LENGTH, bool)
    planted[[3, 130, 131, 700]] = True
    v = jnp.where(jnp.asarray(planted)[None, :, None, None], 1000.0,
                  1.0).astype(v.dtype) * jnp.ones_like(v)
    out, _, _ = _whole((q, q_shared, k, k_shared, v), window, form,
                       monkeypatch)
    t = np.arange(LENGTH)
    sees = np.array([planted[max(0, i - window + 1):i + 1].any() for i in t])
    assert sees.any() and not sees.all()
    np.testing.assert_allclose(out[:, ~sees], 1.0,
                               atol=2e-6 if dtype == "float32" else 2 ** -7)
    assert (out[:, sees] > 1.01).mean() > 0.9     # and they do see theirs


@pytest.mark.parametrize("queries,length,change", [
    (96, 1024, {}), (128, 1000, {}), (256, 1024, dict(q_tile=192)),
    (128, 1024, dict(head_tile=4))])
def test_the_kernel_refuses_what_is_not_whole_tiles(queries, length, change):
    q, q_shared, k, k_shared, v = (a[:, :length] for a in _arrays(
        jnp.dtype("float32"), "192+64"))
    args = (jnp.concatenate([q[0, :queries], q_shared[0, :queries]], -1),
            k.transpose(0, 2, 3, 1), k_shared.transpose(0, 2, 1),
            v.transpose(0, 2, 1, 3), jnp.int32(0), jnp.int32(0))
    with pytest.raises(ValueError, match="not whole"):
        select._window_fused(*args, window=9, scale=1.0, interpret=True,
                             **dict(dict(q_tile=128, head_tile=2), **change))


# ── the choice ───────────────────────────────────────────────────────

CELL = dict(heads=64, d=192, d_shared=64, d_v=128, dtype=jnp.bfloat16)
CELL_CLASSES = [26624, 15360, 11264, 9216, 6144, 4608, 3072, 1536]


@pytest.mark.parametrize("length", CELL_CLASSES)
def test_the_cells_length_classes_take_the_window_kernel_on_a_tpu(length):
    span = select.window_span(length, 512, 513)
    assert span == 1024 and length % 512 == 0
    assert select.window_path(block=512, span=span, backend="tpu",
                              **CELL) == "fused"
    assert select.window_path(block=512, span=span, backend="cpu",
                              **CELL) == "xla"


@pytest.mark.parametrize("change", [
    dict(dtype=jnp.float32), dict(heads=4), dict(heads=2), dict(d=128),
    dict(d=24), dict(d_shared=8), dict(d_v=16), dict(d_v=64), dict(block=8),
    dict(block=128), dict(span=96), dict(span=1024 + 64), dict(span=4096),
    dict(backend="gpu"), dict(backend="cpu")])
def test_what_does_not_tile_keeps_the_xla_body(change):
    args = dict(CELL, block=512, span=1024, backend="tpu")
    assert select.window_path(**args) == "fused"
    assert select.window_path(**dict(args, **change)) == "xla"


def test_without_a_backend_named_the_window_choice_asks_jax():
    assert jax.default_backend() == "cpu"
    assert select.window_path(block=512, span=1024, **CELL) == "xla"


@pytest.mark.parametrize("length,block,window,want", [
    (26624, 512, 513, 1024), (1536, 512, 513, 1024), (512, 512, 513, 512),
    (26624, 512, 514, 1536), (26624, 512, 2, 1024), (26624, 512, 1, 512),
    (96, 8, 9, 16), (5, 5, 9, 5)])
def test_the_span_is_the_whole_blocks_a_window_touches(length, block, window,
                                                       want):
    assert select.window_span(length, block, window) == want


@pytest.mark.parametrize("t0,window,length,want", [
    (0, 513, 26624, 0), (256, 513, 26624, 0), (512, 513, 26624, 0),
    (768, 513, 26624, 256), (26368, 513, 26624, 25856),
    (768, 514, 26624, 128), (768, 2, 26624, 640), (768, 129, 26624, 640),
    (256, 513, 512, 0), (1280, 513, 1536, 768)])
def test_the_one_span_of_keys_a_tile_of_queries_fetches(t0, window, length,
                                                        want):
    """256 queries from ``t0`` on: the span starts ``window - 1`` keys
    before them, rounded down to a whole lane, never before the route
    nor so late that it would end past it; every key of every query's
    window lies in it."""
    back = -(-(window - 1) // 128) * 128
    n_k = min(256 + back, length)
    first = int(select._window_first_key(jnp.int32(t0), back, n_k, length))
    assert first == want and first % 128 == 0
    assert 0 <= first and first + n_k <= length
    assert first <= max(0, t0 - window + 1) and t0 + 255 < first + n_k


# ── what the model says of it ────────────────────────────────────────


def test_the_model_names_the_window_step_and_counts_its_blocks():
    from _route_lm_toys import F32, model
    from benchmark import run as R
    from routest_tpu.models.route_lm import RouteLM

    toy = model("RouteLM", F32)
    assert toy.window_steps(96) == ("xla", 12)      # blocks of 8, the CPU
    assert toy.window_steps(5) == ("xla", 1)
    assert toy.step_attrs(96)["window"] == "xla"
    _, cfg, _ = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"),
                            "route-lm-score")
    real = RouteLM.from_config(cfg)
    assert real.length_quantum == 512       # the plan is what it was
    assert [real.window_steps(n)[1] for n in CELL_CLASSES] == [
        n // 512 for n in CELL_CLASSES]
    a = real.attention_sizes("sliding_attention")
    assert (a.heads, a.d_nope, a.d_rope, a.d_v, a.window) == (
        CELL["heads"], CELL["d"], CELL["d_shared"], CELL["d_v"], 513)
    # here the backend is the CPU; on a TPU the same shapes take the kernel
    assert {real.window_steps(n)[0] for n in CELL_CLASSES} == {"xla"}
    steps = [(26624, 1), (15360, 1), (11264, 1), (9216, 2), (6144, 2),
             (4608, 3), (3072, 3), (1536, 3)]
    assert sum(real.window_steps(n)[1] * r for n, r in steps) == 218

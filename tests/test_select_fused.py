"""The fused online-softmax step of ``selected_attention`` (the Pallas
kernel, here under ``interpret=True``) against the XLA step it stands
in for, at small shapes that tile; the pure function that chooses
between the two; and what the scorer says of the choice."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import select

D, D_SHARED, D_V = 128, 64, 128
LENGTH, ROUTES = 2048, 2
SCALE = 1.0 / np.sqrt(D + D_SHARED)


def _arrays(dtype, heads, block, seed=0):
    """Keys and values of two routes and one block of queries, scaled
    so that a logit has standard deviation about 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    gain = (2.0 / SCALE / np.sqrt(D + D_SHARED)) ** 0.5

    def draw(key, shape, g=gain):
        return (g * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return (draw(ks[0], (block, heads, D)),
            draw(ks[1], (block, heads, D_SHARED)),
            draw(ks[2], (ROUTES, LENGTH, heads, D)),
            draw(ks[3], (ROUTES, LENGTH, D_SHARED)),
            draw(ks[4], (ROUTES, LENGTH, heads, D_V), 1.0))


def _mask(kind, block, i, chunk):
    """(block, LENGTH) bool for block ``i``. ``causal``: what a route no
    longer than ``top_k`` gets. ``selected``: the selector's cut over
    random scores. ``gaps``: every second query sees nothing in every
    second chunk, the first one among them."""
    t_pos = i * block + jnp.arange(block, dtype=jnp.int32)
    causal = jnp.arange(LENGTH, dtype=jnp.int32)[None, :] <= t_pos[:, None]
    if kind == "causal":
        return causal
    if kind == "selected":
        scores = jax.random.normal(jax.random.PRNGKey(7), (block, LENGTH))
        return select.top_k_mask(scores, t_pos, 96)
    in_even_chunk = (jnp.arange(LENGTH) // chunk) % 2 == 0
    blind = (jnp.arange(block) % 2 == 1)[:, None] & in_even_chunk[None, :]
    keys = causal & ~blind
    return keys.at[:, chunk].set(True)      # every query sees something


def _both(q, q_shared, k, k_shared, v, keys, i, chunk, head_tile=2,
          key_tile=512):
    block = q.shape[0]
    b = jnp.int32(1)
    seen_to = (i + 1) * block
    want = select._attend_xla(q, q_shared, k, k_shared, v, keys, b,
                              -(-seen_to // chunk), chunk=chunk, scale=SCALE)
    got = select._attend_fused(
        q, q_shared, k.transpose(0, 2, 1, 3), k_shared,
        v.transpose(0, 2, 1, 3), keys, b,
        jnp.int32(-(-seen_to // key_tile)), scale=SCALE, key_tile=key_tile,
        head_tile=head_tile, interpret=True)
    return (np.asarray(got, np.float32),
            np.asarray(want.astype(v.dtype), np.float32))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("chunk", [512, 1024])
@pytest.mark.parametrize("where", ["first", "late"])
@pytest.mark.parametrize("mask", ["causal", "selected", "gaps"])
def test_fused_step_is_the_xla_step(mask, where, chunk, dtype):
    """Equal to the rounding of the accumulate order: with a chunk of
    the kernel's key tile both add the same terms in the same order;
    with a larger chunk XLA rounds its probabilities to ``v.dtype``
    against the running max of twice as many keys."""
    block, heads = 32, 4
    i = 0 if where == "first" else LENGTH // block - 3
    if mask == "gaps" and where == "first":
        i = chunk // block + 2          # blind in its first chunk, not all
    q, q_shared, k, k_shared, v = _arrays(jnp.dtype(dtype), heads, block)
    keys = _mask(mask, block, i, chunk)
    got, want = _both(q, q_shared, k, k_shared, v, keys, i, chunk)
    assert np.isfinite(got).all()
    same_order = chunk == 512           # the key tile _both takes
    if dtype == "float32":
        tol = 2e-6 if same_order else 2e-5
    else:                               # one bfloat16 ulp of values ~1
        tol = 1e-6 if same_order else 2 ** -6
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("step", ["fused", "xla"])
def test_a_masked_key_adds_no_mass(step, dtype):
    """Values are 1 at the keys every query sees and 0 at the masked
    ones, among them two whole chunks, the first one too: an output of
    1 says that the sum of the probabilities holds the seen keys' mass
    and no other (``exp(NEG - NEG) = 1`` a masked key would add 512)."""
    block, heads, chunk = 16, 2, 512
    q, q_shared, k, k_shared, _ = _arrays(jnp.dtype(dtype), heads, block, 3)
    seen = (jax.random.uniform(jax.random.PRNGKey(5), (LENGTH,)) < 0.3) \
        & ((jnp.arange(LENGTH) // chunk) % 2 == 1)
    keys = jnp.broadcast_to(seen[None, :], (block, LENGTH))
    v = jnp.broadcast_to(seen[None, :, None, None].astype(q.dtype),
                         (ROUTES, LENGTH, heads, D_V))
    i = LENGTH // block - 1
    got, want = _both(q, q_shared, k, k_shared, v, keys, i, chunk)
    out = got if step == "fused" else want
    np.testing.assert_allclose(out, 1.0,
                               atol=2e-6 if dtype == "float32" else 2 ** -7)


def test_one_head_tile_or_several_give_the_same():
    q, q_shared, k, k_shared, v = _arrays(jnp.bfloat16, 4, 64, 1)
    keys = _mask("selected", 64, 20, 512)
    one, _ = _both(q, q_shared, k, k_shared, v, keys, 20, 512, head_tile=4)
    two, _ = _both(q, q_shared, k, k_shared, v, keys, 20, 512, head_tile=2)
    np.testing.assert_array_equal(one, two)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_larger_key_tile_is_the_xla_step_at_its_chunk(dtype):
    q, q_shared, k, k_shared, v = _arrays(jnp.dtype(dtype), 4, 32)
    i = LENGTH // 32 - 5
    keys = _mask("gaps", 32, i, 1024)
    got, want = _both(q, q_shared, k, k_shared, v, keys, i, 1024,
                      key_tile=1024)
    tol = 2e-6 if dtype == "float32" else 1e-6
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_query_blind_to_whole_tiles_adds_nothing_there(dtype):
    """The last block of queries over four tiles: every second query
    sees no key in the first two, no query any in the third, all their
    own keys in the fourth. A masked score is -inf and the running max
    starts at the finite NEG, so a query that has seen nothing yet adds
    exp(-inf) = 0 and keeps its max, sum and accumulator, and the fourth
    tile's real max scales them by exp(NEG - max) = 0; the XLA step
    multiplies by the mask instead."""
    block, chunk = 32, 512
    i = LENGTH // block - 1
    q, q_shared, k, k_shared, v = _arrays(jnp.dtype(dtype), 4, block, 4)
    tile = jnp.arange(LENGTH) // chunk
    odd = (jnp.arange(block) % 2 == 1)[:, None]
    keys = (_mask("causal", block, i, chunk) & (tile != 2)[None, :]
            & ~(odd & (tile < 2)[None, :]))
    assert not keys[1::2, :2 * chunk].any() and keys[::2, :2 * chunk].all()
    assert not keys[:, 2 * chunk:3 * chunk].any()
    got, want = _both(q, q_shared, k, k_shared, v, keys, i, chunk)
    assert np.isfinite(got).all()
    tol = 2e-6 if dtype == "float32" else 1e-6
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_two_calls_in_one_program_lower_one_kernel():
    """Jitted: the two full layers of a step program call the kernel at
    the same shapes and share one trace and lowering of it, one
    ``tpu_custom_call`` in the program lowered for a TPU (the function
    unwrapped lowers two)."""
    heads, block, length = 8, 32, 1024
    q, q_shared, k, k_shared, v = _arrays(jnp.bfloat16, heads, block)
    args = (q, q_shared, k[:, :length].transpose(0, 2, 1, 3),
            k_shared[:, :length], v[:, :length].transpose(0, 2, 1, 3),
            _mask("causal", block, 30, 512)[:, :length])

    def calls(attend):
        def two(*a):
            step = functools.partial(attend, scale=SCALE, key_tile=512)
            return (step(*a, jnp.int32(0), jnp.int32(2))
                    + step(*a, jnp.int32(1), jnp.int32(1)))

        text = jax.jit(two).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        return text.count("tpu_custom_call")

    assert calls(select._attend_fused) == 1
    assert calls(select._attend_fused.__wrapped__) == 2


# ── the choice ───────────────────────────────────────────────────────

CELL = dict(heads=128, d=128, d_shared=64, d_v=128, dtype=jnp.bfloat16)
CELL_CLASSES = [26624, 15360, 11264, 9216, 6144, 4608, 3072, 1536]


@pytest.mark.parametrize("length", CELL_CLASSES)
def test_the_cells_length_classes_take_the_kernel_on_a_tpu(length):
    block, chunk = select.block_and_chunk(length, 256, 2048)
    assert length % chunk == 0
    assert select.key_tile_for(chunk) == (512 if length in (4608, 1536)
                                      else 1024)
    assert select.attention_path(block=block, chunk=chunk, backend="tpu",
                                 **CELL) == "fused"
    assert select.attention_path(block=block, chunk=chunk, backend="cpu",
                                 **CELL) == "xla"


@pytest.mark.parametrize("change", [
    dict(dtype=jnp.float32), dict(heads=4), dict(d=16), dict(d_shared=8),
    dict(d_v=16), dict(block=8), dict(chunk=16), dict(chunk=256),
    dict(backend="gpu")])
def test_what_does_not_tile_keeps_the_xla_step(change):
    args = dict(CELL, block=256, chunk=2048, backend="tpu")
    assert select.attention_path(**args) == "fused"
    assert select.attention_path(**dict(args, **change)) == "xla"


def test_without_a_backend_named_the_choice_asks_jax():
    assert jax.default_backend() == "cpu"
    assert select.attention_path(block=256, chunk=2048, **CELL) == "xla"


@pytest.mark.parametrize("length,block,chunk,want", [
    (26624, 256, 2048, 728),    # 8 blocks a chunk: 8 (1 + ... + 13)
    (1536, 256, 2048, 12),      # the chunk cut to 512: 2 (1 + 2 + 3)
    (96, 8, 16, 42),            # the toy size: 2 (1 + ... + 6)
    (5, 8, 16, 5)])             # one block of 5, chunks of gcd(5, 16) = 1
def test_chunk_steps_are_the_loops(length, block, chunk, want):
    assert select.chunk_steps(length, block, chunk) == want


def test_selected_attention_on_the_cpu_is_the_xla_step_at_tileable_shapes():
    """The whole function, selector and all, at widths the kernel would
    take on a TPU: here it takes the XLA step and must agree with the
    kernel's answer for a block."""
    heads, block, length = 8, 32, 1024
    q, q_shared, k, k_shared, v = _arrays(jnp.bfloat16, heads, length, 2)
    k, k_shared, v = k[:1, :length], k_shared[:1, :length], v[:1, :length]

    def q_fn(b, t0):
        return (jax.lax.dynamic_slice_in_dim(q, t0, block, 0),
                jax.lax.dynamic_slice_in_dim(q_shared, t0, block, 0))

    out, n_keys, first = select.selected_attention(
        q_fn, k, k_shared, v, None, None, top_k=length, scale=SCALE,
        block=block, chunk=512)
    assert (np.asarray(n_keys[0]) == np.arange(length) + 1).all()
    assert (np.asarray(first) == 0).all()
    i = length // block - 1
    got = select._attend_fused(
        *q_fn(0, i * block), k.transpose(0, 2, 1, 3), k_shared,
        v.transpose(0, 2, 1, 3), _mask("causal", block, i, 512)[:, :length],
        jnp.int32(0), jnp.int32(2), scale=SCALE, key_tile=512,
        interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got.transpose(1, 0, 2), np.float32),
        np.asarray(out[0, i * block:], np.float32))


# ── what the scorer says of it ───────────────────────────────────────


def test_the_scorer_names_the_step_and_counts_its_chunks():
    from _route_lm_toys import F32, TOYS, highest, model, params
    from routest_tpu.obs import get_registry, get_tracer
    from routest_tpu.serve import seq_score

    def chunks():
        family = get_registry().get("rtpu_seq_attention_chunks_total")
        return ({k[0]: c.value for k, c in family.items()} if family
                else {})

    m = model("RouteLM", F32)
    scorer = seq_score.RouteScorer(m, params("RouteLM", F32, 0),
                                   max_step_tokens=96, max_classes=2)
    ids, lengths, rows_at = TOYS["RouteLM"].routes(4, [40, 17, 30, 9])
    before = chunks()
    highest(scorer.score)(jnp.asarray(ids), jnp.asarray(lengths),
                          jnp.asarray(rows_at))
    after = chunks()
    plan = scorer.plan(lengths)
    n_full = sum(a == "full_attention" for a, _ in m.layer_kinds())
    want = sum(select.chunk_steps(s.length, m.select_block, m.key_chunk)
               * len(s.routes) * n_full for s in plan)
    assert want > 0 and set(after) <= {"fused", "xla"}
    assert sum(after.values()) - sum(before.values()) == want
    assert after.get("fused", 0.0) == before.get("fused", 0.0)   # toy, CPU
    spans = get_tracer().buffer.snapshot()
    root = [s for s in spans if s["name"] == "seq.score_pass"][-1]
    steps = [s for s in spans if s["name"] == "seq.step"
             and s["parent_id"] == root["span_id"]]
    assert len(steps) == len(plan)
    assert [s["attrs"]["attention"] for s in steps] == ["xla"] * len(plan)
    for s, step in zip(steps, plan):
        assert m.selected_steps(step.length) == (
            s["attrs"]["attention"],
            select.chunk_steps(step.length, m.select_block, m.key_chunk))

"""The held experts' grouped product (``parallel/expert.py``
``grouped_experts``): its two forms — the Pallas kernels, here under
``interpret=True`` at a toy tiling, and ``ragged_dot`` — against each
other and against a dense per-expert reference, over the layouts that
can go wrong; the pure function that chooses between the forms; the
rows the layout makes the product visit; and a route's terms being its
own whatever else the step holds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import expert

N_EXPERTS, K = 16, 4
TILE, CHUNK = 16, 64        # the toy tiling: whole bfloat16 sublanes
WIDTHS = {"5120x1536": (1280, 384),     # the two models' width pairs,
          "6144x2048": (384, 128)}      # scaled down to whole lanes


@functools.lru_cache(maxsize=None)
def _experts(widths, held, dtype, seed=0):
    d, m = WIDTHS[widths]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)

    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    return {"w_gate": draw(ks[0], (held, d, m), d),
            "w_up": draw(ks[1], (held, d, m), d),
            "w_down": draw(ks[2], (held, m, d), m)}


def _tokens(widths, tokens, dtype, seed=1):
    d, _ = WIDTHS[widths]
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (tokens, d), jnp.float32).astype(dtype)
    chosen = jnp.argsort(jax.random.uniform(kc, (tokens, N_EXPERTS)),
                         -1)[:, :K].astype(jnp.int32)
    weights = jax.random.uniform(kw, (tokens, K), minval=0.1, maxval=0.5)
    return x, chosen, weights


def _dense(x, chosen, weights, p, share, valid):
    """Every held assignment by its own expert's matrices, one row at a
    time, in ``gated_mlp``'s precision; the sum over a token's slots in
    float64."""
    y = np.zeros(x.shape, np.float64)
    n = np.zeros((share.count,), np.int64)
    for t in range(x.shape[0]):
        if valid is not None and not bool(valid[t]):
            continue
        for j in range(chosen.shape[1]):
            e = int(chosen[t, j]) - share.first
            if 0 <= e < share.count:
                term = expert.gated_mlp(x[t:t + 1], p["w_gate"][e],
                                        p["w_up"][e], p["w_down"][e])[0]
                y[t] += float(weights[t, j]) * np.asarray(term, np.float64)
                n[e] += 1
    return y, n


def _run(form, monkeypatch, x, chosen, weights, p, share, valid=None,
         chunk=CHUNK, tile=TILE):
    """``grouped_experts`` itself with the choice answered for it: the
    kernels interpreted at the toy tiling, or ``ragged_dot``."""
    monkeypatch.setattr(expert, "expert_path", lambda *a, **kw: form)
    monkeypatch.setattr(expert, "CHUNK_ROWS", chunk)
    monkeypatch.setattr(expert, "ROW_TILE", tile)
    monkeypatch.setattr(expert, "_product_fused", functools.partial(
        expert._product_fused, interpret=True))
    y, counts = jax.jit(lambda *a: expert.grouped_experts(
        *a, share, valid))(x, chosen, weights, p)
    return np.asarray(y), np.asarray(counts)


def _case(name):
    """(widths, tokens, share, chosen → chosen, valid, chunk) of a named
    layout; the share is experts 2 .. 2 + held - 1 of 16."""
    tokens, held, valid, chunk, widths = 40, 6, None, CHUNK, "6144x2048"
    change = lambda c: c                                    # noqa: E731
    if name == "an_expert_with_no_rows":
        change = lambda c: jnp.where(c == 4, 9, c)          # noqa: E731
    elif name == "one_expert_holds_every_row":
        change = lambda c: jnp.full_like(c, 3).at[:, 1:].set(  # noqa: E731
            jnp.arange(8, 8 + K - 1)[None])
    elif name == "counts_that_are_no_multiples_of_the_tile":
        tokens = 37
    elif name == "padded_tokens_left_out":
        valid = jnp.arange(tokens) % 5 != 2
    elif name == "widths_5120x1536":
        widths, tokens = "5120x1536", 24
    elif name == "widths_6144x2048":
        tokens = 56
    elif name == "several_chunks_the_last_part_full":
        tokens, chunk = 90, 80
    elif name == "an_experts_rows_over_two_chunks":
        # 50 tokens all on expert 3: four tiles of 16, chunks of 32
        tokens, chunk = 50, 32
        change = lambda c: jnp.full_like(c, 3).at[:, 1:].set(  # noqa: E731
            jnp.arange(10, 10 + K - 1)[None])
    elif name == "every_slot_of_every_token_held":
        held = N_EXPERTS
    first = 0 if held == N_EXPERTS else 2
    return (widths, tokens, expert.ExpertShare(N_EXPERTS, first, held),
            change, valid, chunk)


CASES = ["an_expert_with_no_rows", "one_expert_holds_every_row",
         "counts_that_are_no_multiples_of_the_tile",
         "padded_tokens_left_out", "widths_5120x1536", "widths_6144x2048",
         "several_chunks_the_last_part_full",
         "an_experts_rows_over_two_chunks",
         "every_slot_of_every_token_held"]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", CASES)
def test_both_forms_against_each_other_and_the_dense_reference(
        name, dtype, monkeypatch):
    widths, tokens, share, change, valid, chunk = _case(name)
    dt = jnp.dtype(dtype)
    p = _experts(widths, share.count, dt)
    x, chosen, weights = _tokens(widths, tokens, dt)
    chosen = change(chosen)
    with jax.default_matmul_precision("highest"):
        want, n = _dense(x, chosen, weights, p, share, valid)
        fused, c_fused = _run("fused", monkeypatch, x, chosen, weights, p,
                              share, valid, chunk)
        xla, c_xla = _run("xla", monkeypatch, x, chosen, weights, p, share,
                          valid, chunk)
    np.testing.assert_array_equal(c_fused, n)
    np.testing.assert_array_equal(c_xla, n)
    # bfloat16: the hidden rows are rounded to bfloat16 in every form,
    # from float32 sums in another order: an ulp of a few values a row
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(fused, want, atol=tol)
    np.testing.assert_allclose(xla, want, atol=tol)
    np.testing.assert_allclose(fused, xla, atol=tol)
    # no assignment is lost and none is invented
    if name == "every_slot_of_every_token_held":
        assert n.sum() == tokens * K
    if name == "an_expert_with_no_rows":
        assert n[4 - share.first] == 0 and n.sum() > 0
    if name == "one_expert_holds_every_row":
        assert n[3 - share.first] == tokens == n.sum()
    if name == "padded_tokens_left_out":
        assert not fused[~np.asarray(valid)].any()
        assert not xla[~np.asarray(valid)].any()


def test_an_experts_rows_really_lie_over_two_chunks_and_tiles_part_empty():
    """The layouts the cases above are named for, from the counts: 50
    rows of one expert are four tiles of 16 over chunks of 32; 37 tokens
    leave every expert's last tile part empty."""
    assert expert.rows_visited(np.asarray([50]), TILE).tolist() == [64]
    _, tokens, share, change, _, _ = _case(
        "counts_that_are_no_multiples_of_the_tile")
    _, chosen, _ = _tokens("6144x2048", tokens, jnp.float32)
    counts = np.bincount(np.asarray(chosen).reshape(-1),
                         minlength=N_EXPERTS)[2:2 + share.count]
    assert (counts % TILE != 0).all() and (counts > 0).all()
    _, tokens, share, _, _, chunk = _case(
        "several_chunks_the_last_part_full")
    _, chosen, _ = _tokens("6144x2048", tokens, jnp.float32)
    counts = np.bincount(np.asarray(chosen).reshape(-1),
                         minlength=N_EXPERTS)[2:2 + share.count]
    rows = int(expert.rows_visited(counts, TILE).sum())
    assert rows > 2 * chunk and rows % chunk != 0


@pytest.mark.parametrize("counts,tile,want", [
    ([0, 1, 16, 17], 16, [0, 16, 16, 32]),
    ([0, 1, 16, 17], 1, [0, 1, 16, 17]),
    ([300, 0, 256], 128, [384, 0, 256])])
def test_rows_visited_rounds_each_expert_up_to_whole_tiles(counts, tile,
                                                           want):
    assert expert.rows_visited(np.asarray(counts), tile).tolist() == want
    assert expert.rows_visited(jnp.asarray(counts), tile).tolist() == want


@pytest.mark.parametrize("chunk,t,want", [
    (8192, 26624, 512),         # the cells' steps: 4,608 tokens and up
    (8192, 4608, 512),
    (8192, 4096, 512),          # an eighth of the sum's rows, no more
    (8192, 4095, 256),
    (8192, 1100, 128),
    (8192, 300, 32),            # under a tile of the kernels
    (37 * 128, 2400, 296),      # a chunk that is all a short step holds
    (80, 90, 10), (64, 40, 4), (64, 5, 1)])
def test_a_scatter_add_of_the_combine_stays_the_plain_one(chunk, t, want):
    """At most an eighth of the sum's rows and ``COMBINE_ROWS``, and a
    divisor of the chunk, so that no piece reaches past its end."""
    piece = expert.combine_rows(chunk, t)
    assert piece == want
    assert chunk % piece == 0 and piece <= expert.COMBINE_ROWS
    assert piece == 1 or 8 * piece <= t


@pytest.mark.parametrize("d,m,dtype,backend,want", [
    (5120, 1536, "bfloat16", "tpu", "fused"),
    (6144, 2048, "bfloat16", "tpu", "fused"),
    (6144, 2048, "bfloat16", "cpu", "xla"),
    (6144, 2048, "bfloat16", "gpu", "xla"),
    (6144, 2048, "float32", "tpu", "xla"),
    (64, 32, "bfloat16", "tpu", "xla"),          # toy widths: no lanes
    (6144, 2000, "bfloat16", "tpu", "xla"),
    (6100, 2048, "bfloat16", "tpu", "xla"),
    (2 ** 20, 2048, "bfloat16", "tpu", "xla"),   # no whole-depth block fits
])
def test_expert_path_is_a_pure_function_of_widths_dtype_and_backend(
        d, m, dtype, backend, want):
    assert expert.expert_path(d, m, jnp.dtype(dtype), backend) == want
    assert expert.row_tile_of(want) == (expert.ROW_TILE if want == "fused"
                                        else 1)


def test_expert_path_defaults_to_jaxs_own_backend():
    assert jax.default_backend() == "cpu"
    assert expert.expert_path(6144, 2048, jnp.bfloat16) == "xla"


def test_the_column_tiles_of_the_two_models_fit_and_divide():
    for d, m in ((5120, 1536), (6144, 2048)):
        up, down = expert._col_tile(m, d, 2), expert._col_tile(d, m, 1)
        assert m % up == 0 and d % down == 0 and up % 128 == 0 == down % 128
        assert 2 * d * up * 2 <= expert._BLOCK_BYTES
        assert m * down * 2 <= expert._BLOCK_BYTES
        # two sets of blocks, a tile of rows twice and the results
        assert 2 * expert._BLOCK_BYTES + 4 * expert.ROW_TILE * d * 2 \
            + 3 * expert.ROW_TILE * max(up, down) * 4 < expert._VMEM_BYTES


@pytest.mark.parametrize("form", ["fused", "xla"])
def test_a_routes_terms_are_its_own_bit_for_bit(form, monkeypatch):
    """The rows of one route alone, in a step with others before and
    after it, and in a step whose other tokens are padding: the same
    float32 bits, because a token's held terms are added in expert order
    whatever tiles and chunks the step's other tokens put them in."""
    share = expert.ExpertShare(N_EXPERTS, 2, 9)
    p = _experts("6144x2048", 9, jnp.dtype("float32"))
    x, chosen, weights = _tokens("6144x2048", 70, jnp.float32)
    mine = slice(23, 52)
    alone, _ = _run(form, monkeypatch, x[mine], chosen[mine], weights[mine],
                    p, share)
    among, _ = _run(form, monkeypatch, x, chosen, weights, p, share)
    valid = (jnp.arange(70) >= 23) & (jnp.arange(70) < 52)
    padded, _ = _run(form, monkeypatch, x, chosen, weights, p, share, valid,
                     chunk=32)
    np.testing.assert_array_equal(among[mine], alone)
    np.testing.assert_array_equal(padded[mine], alone)
    assert alone.any()


def test_moe_share_hands_on_what_grouped_experts_gives():
    """``moe_share`` calls ``grouped_experts(x, chosen, weights, experts,
    share, valid)``: the call the benchmark's planted fault patches."""
    seen = {}

    def none(x, chosen, weights, experts, share, valid=None, tile=None):
        seen["args"] = (x.shape, chosen.shape, weights.dtype, share, valid)
        return (jnp.zeros(x.shape, jnp.float32),
                jnp.zeros((share.count,), jnp.int32))

    share = expert.ExpertShare(N_EXPERTS, 0, 4)
    p = dict(_experts("6144x2048", 4, jnp.dtype("float32")))
    d, m = WIDTHS["6144x2048"]
    p["router"] = jnp.zeros((d, N_EXPERTS))
    p["bias"] = jnp.arange(N_EXPERTS, dtype=jnp.float32)
    p["shared"] = {k: v[0] for k, v in p.items() if k.startswith("w_")}
    x = jnp.ones((5, d))
    real = expert.grouped_experts
    expert.grouped_experts = none
    try:
        y, taps = expert.moe_share(p, x, K, share)
    finally:
        expert.grouped_experts = real
    assert seen["args"][:2] == ((5, d), (5, K)) and seen["args"][3] == share
    np.testing.assert_allclose(y, expert.gated_mlp(
        x, p["shared"]["w_gate"], p["shared"]["w_up"],
        p["shared"]["w_down"]))
    assert not np.asarray(taps["counts"]).any()

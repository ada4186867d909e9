"""The radix top-k of the selector as a kernel (``_top_k_fused``, here
under ``interpret=True``) against its XLA form ``_top_k_xla``: the same
set of keys, bit for bit; the pure function that chooses between the
two; and what the scorer counts of the choice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.parallel import select

TOP_K = 96


def _fused_mask(scores, t_pos, top_k=TOP_K):
    """``top_k_mask``'s fused form: the kernel's k-th values and last
    ties through the shared last line."""
    n_k = scores.shape[1]
    s_pos = jnp.arange(n_k, dtype=jnp.int32)[None, :]
    causal = s_pos <= t_pos[:, None]
    kth, last = select._top_k_fused(scores, t_pos, top_k=top_k,
                                    interpret=True)
    return select._cut_at(select._sortable(scores, causal), causal, s_pos,
                          kth[:, None], last[:, None])


def _scores(kind, n_q, n_k, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "floats":
        return rng.normal(size=(n_q, n_k)).astype(np.float32)
    if kind == "ties":                   # a handful of values: cuts
        return rng.integers(-2, 3, (n_q, n_k)).astype(np.float32)
    if kind == "zeros":                  # all equal, both zeros
        return np.where(rng.random((n_q, n_k)) < 0.5, 0.0,
                        -0.0).astype(np.float32)
    # the selector's own: sums of relu(q . k) * w, many of them exactly 0
    q = rng.normal(size=(n_q, 4, 16)).astype(np.float32)
    k = rng.normal(size=(n_k, 16)).astype(np.float32)
    w = np.abs(rng.normal(size=(n_q, 4))).astype(np.float32)
    return np.array(select.selector_scores(q, w, k))


def _rows(kind, n_k, t0, rows, chunk=256, seed=0):
    """``rows`` queries from position ``t0`` as ``selected_attention``
    hands a block over: scores over the chunks that hold a causal key,
    ``-inf`` in every column after them."""
    t_pos = (t0 + np.arange(rows)).astype(np.int32)
    scores = _scores(kind, rows, n_k, seed)
    n_chunks = -(-(t0 + rows) // chunk)
    scores[:, n_chunks * chunk:] = -np.inf
    return jnp.asarray(scores), jnp.asarray(t_pos)


def _block(kind, n_k, block, i, chunk=256, seed=0):
    """Block ``i`` of ``block`` queries."""
    return _rows(kind, n_k, i * block, block, chunk, seed)


def _same(scores, t_pos, top_k=TOP_K):
    want = np.asarray(select._top_k_xla(scores, t_pos, top_k))
    got = np.asarray(_fused_mask(scores, t_pos, top_k))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.asarray(t_pos) + 1, top_k))
    return got


# start (every row has no more than top_k keys, or a few more), middle,
# end of a route of 1,024 keys in blocks of 32 queries
@pytest.mark.parametrize("i", [0, 2, 3, 17, 31])
@pytest.mark.parametrize("kind", ["floats", "ties", "zeros", "selector"])
def test_the_kernel_takes_the_xla_forms_keys(kind, i):
    _same(*_block(kind, 1024, 32, i))


# widths of several pieces (TOPK_PIECE columns, or the largest divisor of
# the width in whole lanes): 4,096 in four pieces of 1,024, 4,608 in nine
# of 512. Two tiles of 16 rows from t0; a tile counts the pieces up to its
# own last causal column.
MANY_PIECES = [
    (4096, 992),     # both tiles in the first piece: three never read
    (4096, 1008),    # tile 0 ends in piece 0, tile 1 in piece 1
    (4096, 2040),    # tile 0's rows straddle a piece boundary
    (4096, 4064),    # the route's end: every piece
    (4608, 496),     # tile 0 ends in piece 0, tile 1 in piece 1
    (4608, 2552),    # tile 0's rows straddle a piece boundary
    (4608, 4576),    # the route's end: every piece
]


@pytest.mark.parametrize("n_k,piece", [(4096, 1024), (4608, 512),
                                       (1024, 1024)])
def test_a_tile_reads_the_pieces_that_hold_its_causal_columns(n_k, piece):
    """Every piece up to the one that holds the tile's last position, and
    none after it: past it every key is below any candidate, so reading
    more pieces would change no answer, only the time."""
    t_max = np.arange(n_k)
    n = np.asarray(select._causal_pieces(jnp.asarray(t_max), n_k, piece))
    assert ((n - 1) * piece <= t_max).all() and (t_max < n * piece).all()


@pytest.mark.parametrize("n_k,t0", MANY_PIECES)
@pytest.mark.parametrize("kind", ["floats", "ties", "selector"])
def test_the_kernel_counts_each_tiles_pieces_up_to_its_causal_extent(
        kind, n_k, t0):
    piece = n_k // (4 if n_k == 4096 else 9)
    assert np.gcd(n_k, select.TOPK_PIECE) == piece
    scores, t_pos = _rows(kind, n_k, t0, 2 * select.TOPK_ROWS, seed=t0)
    _same(scores, t_pos)


def test_forced_ties_at_the_kth_value_are_cut_at_the_lower_positions():
    """Every row has 40 keys above 0 and the rest tie at 0: the cut
    among the ties is by position, in every tile."""
    block, n_k, i = 16, 512, 20
    t_pos = jnp.asarray(i * block + np.arange(block), jnp.int32)
    scores = np.zeros((block, n_k), np.float32)
    rng = np.random.default_rng(3)
    for r in range(block):
        scores[r, rng.choice(i * block, 40, replace=False)] = \
            rng.random(40) + 1.0
    got = _same(jnp.asarray(scores), t_pos)
    zeros = (scores == 0) & (np.arange(n_k)[None] <= np.asarray(t_pos)[:, None])
    for r in range(block):
        taken = np.flatnonzero(got[r] & zeros[r])
        assert len(taken) == TOP_K - 40
        assert list(taken) == list(np.flatnonzero(zeros[r])[:TOP_K - 40])


def test_exact_zeros_and_negative_zeros_are_one_value():
    block, n_k = 16, 512
    t_pos = jnp.asarray(300 + np.arange(block), jnp.int32)
    scores = np.where(np.arange(n_k)[None] % 2 == 0, 0.0, -0.0)
    scores = np.broadcast_to(scores, (block, n_k)).astype(np.float32)
    got = _same(jnp.asarray(scores), t_pos)
    # all tie: the lowest TOP_K positions, zeros of both signs alike
    assert (got[:, :TOP_K].all() and not got[:, TOP_K:].any())


def test_rows_with_no_more_keys_than_top_k_take_all_of_them():
    """A tile whose rows all have t + 1 <= top_k runs no search; a tile
    that straddles top_k searches for the rows that need it."""
    block, n_k = 16, 512
    for t0 in (0, TOP_K - 16, TOP_K - 8):
        t_pos = jnp.asarray(t0 + np.arange(block), jnp.int32)
        scores = jnp.asarray(_scores("floats", block, n_k, t0))
        got = _same(scores, t_pos)
        short = np.asarray(t_pos) + 1 <= TOP_K
        causal = np.arange(n_k)[None] <= np.asarray(t_pos)[:, None]
        np.testing.assert_array_equal(got[short], causal[short])


def test_minus_inf_after_the_causal_extent_is_never_selected():
    """The buffer past a block's last causal chunk is ``-inf`` and past
    each row's own position the scores are large: neither is taken."""
    block, n_k, i = 16, 1024, 12
    scores, t_pos = _block("floats", n_k, block, i, chunk=256)
    t = np.asarray(t_pos)
    big = np.where(np.arange(n_k)[None] > t[:, None], 1e30, 0.0)
    got = _same(scores + jnp.asarray(big, jnp.float32), t_pos)
    assert not got[:, t.max() + 1:].any()


def test_no_more_columns_than_top_k():
    """``top_k_mask`` returns every causal key before any form runs; the
    kernel alone, handed such scores, takes every causal key too."""
    block, n_k = 16, 128
    t_pos = jnp.asarray(np.arange(block) * 8, jnp.int32)
    scores = jnp.asarray(_scores("floats", block, n_k))
    causal = np.arange(n_k)[None] <= np.asarray(t_pos)[:, None]
    np.testing.assert_array_equal(
        np.asarray(select.top_k_mask(scores, t_pos, 200)), causal)
    np.testing.assert_array_equal(
        np.asarray(_fused_mask(scores, t_pos, 200)), causal)


def test_top_k_mask_takes_the_kernel_where_the_choice_says_fused(
        monkeypatch):
    """Through ``top_k_mask`` itself, the kernel's form chosen: the same
    mask as the XLA form."""
    calls = []
    fused = select._top_k_fused

    def interpreted(*a, **kw):
        calls.append(a[0].shape)
        return fused(*a, interpret=True, **kw)

    monkeypatch.setattr(select, "topk_path", lambda *a, **kw: "fused")
    monkeypatch.setattr(select, "_top_k_fused", interpreted)
    scores, t_pos = _block("selector", 1024, 32, 20)
    want = np.asarray(select._top_k_xla(scores, t_pos, TOP_K))
    np.testing.assert_array_equal(
        np.asarray(select.top_k_mask(scores, t_pos, TOP_K)), want)
    assert calls == [(32, 1024)]


def test_two_calls_in_one_program_lower_one_kernel():
    """Jitted: the two full layers of a step program call the kernel at
    the same shapes and share one trace and lowering of it."""
    scores, t_pos = _block("floats", 1024, 32, 20)

    def calls(topk):
        def two(s, t):
            return topk(s, t, top_k=TOP_K)[0] + topk(2 * s, t,
                                                     top_k=TOP_K)[0]

        text = jax.jit(two).trace(scores, t_pos).lower(
            lowering_platforms=("tpu",)).as_text()
        return text.count("tpu_custom_call")

    assert calls(select._top_k_fused) == 1
    assert calls(select._top_k_fused.__wrapped__) == 2


def test_shapes_that_do_not_tile_are_refused_by_the_kernel():
    with pytest.raises(ValueError, match="whole tiles"):
        select._top_k_fused(jnp.zeros((12, 256)), jnp.arange(12),
                            top_k=8, interpret=True)
    with pytest.raises(ValueError, match="whole tiles"):
        select._top_k_fused(jnp.zeros((16, 200)), jnp.arange(16),
                            top_k=8, interpret=True)


# ── the choice ───────────────────────────────────────────────────────

# route-lm-score's selecting classes: a block's (256, L) scores
CELL_SELECTING = [26624, 15360, 11264, 9216, 6144, 4608, 3072]
# route-lm-sala-long's block scores: (2 groups x 128 queries, L / 64)
SALA_BLOCKS = [140, 216, 284, 364, 480, 736]


@pytest.mark.parametrize("length", CELL_SELECTING)
def test_the_cells_selector_scores_take_the_kernel_on_a_tpu(length):
    assert select.topk_path(256, length, jnp.float32, "tpu") == "fused"
    assert select.topk_path(256, length, jnp.float32, "cpu") == "xla"


@pytest.mark.parametrize("n_blocks", SALA_BLOCKS)
def test_the_block_choice_of_the_second_model_keeps_the_xla_form(n_blocks):
    assert select.topk_path(2 * 128, n_blocks, jnp.float32, "tpu") == "xla"


@pytest.mark.parametrize("length", CELL_SELECTING + [1536])
def test_the_blocks_that_select_at_the_cells_lengths(length):
    """``selected_attention``'s blocks of 256 queries over float32
    scores: all of them select above ``top_k`` (2,048), none below."""
    path, n = select.topk_blocks(length, 256, 2048, "tpu")
    assert (path, n) == ("fused", length // 256 if length > 2048 else 0)
    assert select.topk_blocks(length, 256, 2048, "cpu")[0] == "xla"


def test_named_rows_keep_the_xla_form():
    # selected_rows: route-lm-score's four named rows of a route
    assert select.topk_path(4, 26624, jnp.float32, "tpu") == "xla"


@pytest.mark.parametrize("rows,cols,dtype", [
    (256, 26624, jnp.bfloat16), (12, 26624, jnp.float32),
    (256, 3000, jnp.float32), (256, 2 * select.TOPK_MAX_COLS, jnp.float32)])
def test_what_does_not_tile_keeps_the_xla_form(rows, cols, dtype):
    assert select.topk_path(rows, cols, dtype, "tpu") == "xla"


def test_without_a_backend_named_the_choice_asks_jax():
    assert jax.default_backend() == "cpu"
    assert select.topk_path(256, 26624, jnp.float32) == "xla"


def test_a_planted_fault_of_the_selection_stays_on_the_timed_path():
    """``benchmark.faults_seq.recent_selected`` patches
    ``select.top_k_mask``; ``selected_attention`` calls it by its module
    name, so under the fault a query's keys are its most recent
    ``top_k``: as many, the first of them ``t - top_k + 1``."""
    from benchmark.faults_seq import recent_selected

    heads, block, length, top_k = 2, 8, 64, 12
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(length, heads, 8)), jnp.float32)
    qs = jnp.asarray(rng.normal(size=(length, heads, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, length, heads, 8)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(1, length, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, length, heads, 8)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(length, 2, 8)), jnp.float32)
    wi = jnp.asarray(rng.random((length, 2)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(1, length, 8)), jnp.float32)

    def run():
        return select.selected_attention(
            lambda b, t0: (jax.lax.dynamic_slice_in_dim(q, t0, block, 0),
                           jax.lax.dynamic_slice_in_dim(qs, t0, block, 0)),
            k, ks, v,
            lambda b, t0: (jax.lax.dynamic_slice_in_dim(qi, t0, block, 0),
                           jax.lax.dynamic_slice_in_dim(wi, t0, block, 0)),
            ki, top_k=top_k, scale=0.3, block=block, chunk=16)

    t = np.arange(length)
    _, n_keys, first = run()
    assert (np.asarray(n_keys[0]) == np.minimum(t + 1, top_k)).all()
    assert (np.asarray(first[0])[t >= top_k] != t[t >= top_k] - top_k + 1
            ).any()                             # the learned choice
    with recent_selected():
        _, n_keys, first = run()
    assert (np.asarray(n_keys[0]) == np.minimum(t + 1, top_k)).all()
    np.testing.assert_array_equal(np.asarray(first[0]),
                                  np.maximum(t - top_k + 1, 0))


# ── what the scorer counts ───────────────────────────────────────────


def test_the_model_counts_the_blocks_that_select_by_form():
    from _route_lm_toys import F32, model
    from routest_tpu.serve import seq_score

    m = model("RouteLM", F32)
    top_k = m.attention_sizes("full_attention").top_k
    plan = seq_score.plan_pass([96, 33, 70, 12], m.length_quantum, 128, 8)
    counted = [c for c in m.pass_counts(plan, [], 211)
               if c[0] == "topk_blocks"]
    want = sum(s.length // m.select_block * len(s.routes) * 2 for s in plan
               if s.length > top_k)
    assert want > 0 and any(s.length <= top_k for s in plan)
    assert sum(c[2] for c in counted) == want
    assert {c[1]["path"] for c in counted} == {"xla"}            # the CPU
    assert m.topk_blocks(16) == ("xla", 0)
    assert m.topk_blocks(96) == ("xla", 12)
    assert "topk_blocks" in seq_score._COUNTERS
    family = seq_score._seq_metrics()["topk_blocks"]
    assert family.name == "rtpu_seq_topk_blocks_total"


@pytest.mark.parametrize("length", [26624, 3072])
def test_the_cells_model_names_the_kernel_for_its_selecting_blocks(
        monkeypatch, length):
    """At route-lm-score's own sizes, the choice asked as on a TPU."""
    import json
    import os

    from routest_tpu.models import route_lm

    as_tpu = select.topk_path
    monkeypatch.setattr(select, "topk_path",
                        lambda rows, cols, dtype, backend="":
                        as_tpu(rows, cols, dtype, "tpu"))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "dots3-note-prev-ep8.json")) as f:
        m = route_lm.RouteLM.from_config(json.load(f))
    assert m.topk_blocks(length) == ("fused", length // 256)
    assert m.topk_blocks(1536) == ("fused", 0)     # never selects

"""The pieces under the route-sequence language model, each against the
plain reference or a dense oracle: the share of the expert layer (the
parts all shares give, the shared expert counted once, add up to the
uncut layer), the grouped product's layout, the selector's exact top-k
with ties, the window, and the scorer's length ladder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import (F32, TOYS, expert_layer, highest, model, params,
                            program)
from benchmark.reference import dots3_ref as ref
from routest_tpu.parallel import expert, select
from routest_tpu.serve import seq_score

D, E, K = 64, 16, 4


def _cut(p, first, count):
    out = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][first:first + count]
    return out


@pytest.mark.parametrize("n_shares", [1, 2, 4])
def test_the_parts_of_all_shares_add_up_to_the_uncut_layer(n_shares):
    """Every share routes over all 16 experts and adds its own experts'
    terms; the shared expert, which every chip computes alike, is
    counted once."""
    p = expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(9), (50, D))
    whole, _, _ = ref.moe(p, x, K, (0, E))
    shared = ref.gated_mlp(x, p["shared"])
    count = E // n_shares
    total = jnp.zeros_like(whole)
    for s in range(n_shares):
        share = expert.ExpertShare(E, s * count, count)
        y, taps = highest(jax.jit(lambda q, x, share=share: expert.moe_share(
            q, x, K, share)))(_cut(p, s * count, count), x)
        # the reference, given the same share, gives the same part
        want, _, fullest = ref.moe(_cut(p, s * count, count), x, K,
                                   (s * count, count))
        assert int(fullest) == int(taps["counts"].max())
        np.testing.assert_allclose(y, want, atol=2e-5)
        total = total + (y - shared)
        assert int(taps["counts"].sum()) <= 50 * K
    np.testing.assert_allclose(total + shared, whole, atol=5e-5)


def test_routing_is_over_all_experts_and_the_bias_moves_only_the_choice():
    p = expert_layer(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, D))
    chosen, w = highest(expert.route_top_k)(x, p["router"], p["bias"], K)
    want_c, want_w = ref.route(p, x, K)
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    plain, _ = highest(expert.route_top_k)(x, p["router"],
                                           jnp.zeros((E,)), K)
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    prob = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision="highest"))
    picked = jnp.take_along_axis(prob, chosen, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)


@pytest.mark.parametrize("tile,chunk,tokens", [
    (4, 32, 50), (16, 64, 50), (64, 64, 7), (1, 16, 50)])
def test_grouped_product_with_uneven_and_empty_groups(tile, chunk, tokens,
                                                      monkeypatch):
    """Tiles smaller and larger than the groups, chunks that cut an
    expert's rows in two, an expert nobody chose, padded tokens left
    out."""
    monkeypatch.setattr(expert, "row_tile_of", lambda path: tile)
    monkeypatch.setattr(expert, "CHUNK_ROWS", chunk)
    p = expert_layer(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, D))
    chosen, w = expert.route_top_k(x, p["router"], p["bias"], K)
    chosen = jnp.where(chosen == 5, 6, chosen)       # nobody takes 5
    valid = jnp.arange(tokens) < tokens - 3
    share = expert.ExpertShare(E, 2, 9)              # experts 2..10
    y, counts = highest(jax.jit(lambda *a: expert.grouped_experts(
        *a, share, valid)))(x, chosen, w, _cut(p, 2, 9))
    want = np.zeros((tokens, D), np.float32)
    n = np.zeros(9, np.int64)
    for t in range(tokens - 3):
        for j in range(K):
            e = int(chosen[t, j])
            if 2 <= e < 11:
                term = ref.gated_mlp(x[t:t + 1], {k: p[k][e] for k in (
                    "w_gate", "w_up", "w_down")})[0]
                want[t] += float(w[t, j]) * np.asarray(term)
                n[e - 2] += 1
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_array_equal(counts, n)
    assert counts[3] == 0


# ── the selector ─────────────────────────────────────────────────────


def _reference_mask(scores, t_pos, top_k):
    return np.asarray(ref.selected_keys(
        jnp.asarray(scores), jnp.asarray(t_pos),
        jnp.arange(scores.shape[1]), top_k))


@pytest.mark.parametrize("case", ["floats", "ties", "zeros", "short"])
def test_top_k_mask_is_the_exact_top_k_with_ties_to_the_lower_key(case):
    rng = np.random.default_rng(5)
    n_q, n_k, top_k = 24, 70, 16
    t_pos = np.sort(rng.integers(0, n_k, n_q)).astype(np.int32)
    t_pos[:3] = [0, 5, top_k - 1]            # fewer keys than top_k
    if case == "floats":
        scores = rng.normal(size=(n_q, n_k)).astype(np.float32)
    elif case == "ties":                     # a handful of values
        scores = rng.integers(-2, 3, (n_q, n_k)).astype(np.float32)
    elif case == "zeros":                    # all equal, both zeros
        scores = np.where(rng.random((n_q, n_k)) < 0.5, 0.0,
                          -0.0).astype(np.float32)
    else:                                    # no more keys than top_k
        n_k = top_k
        t_pos = np.minimum(t_pos, n_k - 1)
        scores = rng.normal(size=(n_q, n_k)).astype(np.float32)
    got = np.asarray(jax.jit(lambda s, t: select.top_k_mask(s, t, top_k))(
        scores, t_pos))
    np.testing.assert_array_equal(got, _reference_mask(scores, t_pos, top_k))
    np.testing.assert_array_equal(got.sum(-1),
                                  np.minimum(t_pos + 1, top_k))


def test_ties_cut_at_the_lower_positions():
    scores = np.zeros((1, 40), np.float32)
    scores[0, [7, 30]] = 1.0
    got = np.asarray(select.top_k_mask(jnp.asarray(scores),
                                       jnp.asarray([35]), 6))
    assert list(np.flatnonzero(got[0])) == [0, 1, 2, 3, 7, 30]


@pytest.mark.parametrize("window,block,length", [
    (9, 8, 48), (8, 8, 48), (1, 8, 24), (17, 8, 40), (9, 16, 16),
    (513, 16, 48)])
def test_windowed_attention_against_the_dense_mask(window, block, length):
    h, d, dr, dv = 2, 8, 4, 6
    ks = jax.random.split(jax.random.PRNGKey(window), 5)
    q = jax.random.normal(ks[0], (2, length, h, d))
    qs = jax.random.normal(ks[1], (2, length, h, dr))
    k = jax.random.normal(ks[2], (2, length, h, d))
    kshared = jax.random.normal(ks[3], (2, length, dr))
    v = jax.random.normal(ks[4], (2, length, h, dv))
    blk = min(block, length)

    def q_fn(b, t0):
        return (jax.lax.dynamic_slice_in_dim(q[b], t0, blk, 0),
                jax.lax.dynamic_slice_in_dim(qs[b], t0, blk, 0))

    out, n_keys, first = highest(jax.jit(
        lambda: select.windowed_attention(q_fn, k, kshared, v,
                                          window=window, scale=0.3,
                                          block=block)))()
    pos = jnp.arange(length)
    keys = ref.window_keys(pos, pos, window)
    for b in range(2):
        kk = jnp.concatenate([k[b], jnp.broadcast_to(
            kshared[b][:, None], (length, h, dr))], -1)
        qq = jnp.concatenate([q[b], qs[b]], -1)
        want = ref.attend(qq, kk, v[b], keys, 0.3)
        np.testing.assert_allclose(out[b], want, atol=2e-5)
    np.testing.assert_array_equal(n_keys[0], keys.sum(-1))
    np.testing.assert_array_equal(first[0], jnp.argmax(keys, -1))


def test_window_needs_whole_blocks():
    with pytest.raises(ValueError, match="multiple"):
        select.windowed_attention(None, jnp.zeros((1, 20, 1, 4)), None,
                                  jnp.zeros((1, 20, 1, 4)), window=3,
                                  scale=1.0, block=8)


# ── the scorer's plan ────────────────────────────────────────────────

CELL_LENGTHS = [1024, 1096, 1492, 1884, 2295, 2739, 3231, 3787, 4430, 5192,
                6124, 7309, 8903, 11246, 15303, 26384]


@pytest.mark.parametrize("max_classes", [1, 3, 6, 8, 16])
def test_ladder_holds_every_route_and_more_classes_pad_less(max_classes):
    ladder = seq_score.length_ladder(CELL_LENGTHS, 512, max_classes)
    assert len(ladder) <= max_classes and ladder == sorted(ladder)
    assert all(c % 512 == 0 for c in ladder) and ladder[-1] >= 26384

    def padded(k):
        plan = seq_score.plan_pass(CELL_LENGTHS, 512, 32768, k)
        rows = np.concatenate([s.routes[s.routes >= 0] for s in plan])
        assert sorted(rows) == list(range(16))       # each route once
        assert all(s.length * len(s.routes) <= 32768 for s in plan)
        assert sum(s.real_tokens for s in plan) == sum(CELL_LENGTHS)
        return sum(s.padded_tokens for s in plan)

    assert padded(max_classes) <= padded(max(1, max_classes - 1))


def test_ladder_is_the_least_padding_by_brute_force():
    import itertools

    lengths = [3, 5, 6, 11, 12, 20, 31]
    cands = sorted({-(-n // 4) * 4 for n in lengths})
    best = min(sum(min(c for c in combo if c >= n) - n for n in lengths)
               for combo in itertools.combinations(cands, 3)
               if combo[-1] >= max(lengths))
    ladder = seq_score.length_ladder(lengths, 4, 3)
    got = sum(min(c for c in ladder if c >= n) - n for n in lengths)
    assert got == best


def test_scorer_pass_spans_counters_and_results():
    from routest_tpu.obs import get_registry, get_tracer

    m, p = model("RouteLM", F32), params("RouteLM", F32, 0)
    scorer = seq_score.RouteScorer(m, p, max_step_tokens=96, max_classes=2)
    ids, lengths, rows_at = TOYS["RouteLM"].routes(4, [40, 17, 30, 9])
    before = {k[0]: c.value for k, c in (get_registry().get(
        "rtpu_seq_tokens_total").items() if get_registry().get(
            "rtpu_seq_tokens_total") else [])}
    res = highest(scorer.score)(jnp.asarray(ids), jnp.asarray(lengths),
                                jnp.asarray(rows_at))
    plan = scorer.plan(lengths)
    alone = highest(program("RouteLM", F32))(p, ids[1:2, :24], lengths[1:2],
                                             rows_at[1:2])
    np.testing.assert_allclose(res.lse[1, :17], alone["lse"][0, :17],
                               atol=2e-5)
    np.testing.assert_allclose(res.loglik[1], alone["loglik"][0], rtol=1e-5)
    spans = get_tracer().buffer.snapshot()
    root = [s for s in spans if s["name"] == "seq.score_pass"][-1]
    kids = [s for s in spans if s["parent_id"] == root["span_id"]]
    steps = [s for s in kids if s["name"] == "seq.step"]
    assert len(steps) == len(plan) == root["attrs"]["steps"]
    assert [s["name"] for s in kids][-1] == "seq.wait"
    assert sum(s["attrs"]["real_tokens"] for s in steps) == 96
    assert {"length_class", "routes", "real_tokens",
            "padded_tokens"} <= set(steps[0]["attrs"])
    tokens = {k[0]: c.value for k, c in get_registry().get(
        "rtpu_seq_tokens_total").items()}
    assert tokens["real"] - before.get("real", 0.0) == 96
    assert tokens["padded"] - before.get("padded", 0.0) == sum(
        s.padded_tokens for s in plan)
    share = get_registry().get(
        "rtpu_seq_held_assignment_share").items()[0][1].value
    assert 0.3 < share < 0.7                 # 8 of 16 experts are held
    keys = get_registry().get(
        "rtpu_seq_selected_keys_per_query").items()[0][1].value
    assert 1.0 <= keys <= TOYS["RouteLM"].config["index_topk"]

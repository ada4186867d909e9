"""One scorer for every route-sequence model: what ``RouteScorer`` asks
of a model (``serve/seq_score.py``) is met by ``RouteLM``, by
``RouteLMSala``, by ``RouteLMKExaone``, by ``RouteLMGigaChat``, whose
prediction modules' columns come through the same tap tables, and by
``RouteLMFalconH1``, whose state-space layers' states do; ``RouteLM``'s and
``RouteLMSala``'s plans and result tables are what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _route_lm_toys as toys
from _route_lm_toys import F32, model, program
from routest_tpu.serve import seq_score
from routest_tpu.serve.seq_score import RouteScorer, plan_pass

LENGTHS = [96, 33, 70]
TOYS = {name: toys.TOYS[cls] for name, cls in (
    ("dots3", "RouteLM"), ("sala", "RouteLMSala"),
    ("kexaone", "RouteLMKExaone"), ("gigachat", "RouteLMGigaChat"),
    ("falcon", "RouteLMFalconH1"))}
dots3, sala, kexaone, gigachat, falcon = TOYS.values()


def _scorer(toy, **kw):
    m, p = model(toy.name, F32), toys.params(toy.name, F32, 0)
    return m, p, RouteScorer(m, p, **kw)


FIRST_PASS = {}      # model → the seq.step spans of its compiling pass


def _last_pass(names=("seq.step",)):
    """The spans of those names that the last recorded pass left, in
    the order they finished."""
    from routest_tpu.obs import get_tracer

    spans = get_tracer().buffer.snapshot()
    (root,) = [s for s in spans if s["name"] == "seq.score_pass"][-1:]
    mine = {root["span_id"]} | {s["span_id"] for s in spans
                                if s["parent_id"] == root["span_id"]}
    return root, [s for s in spans
                  if s["parent_id"] in mine and s["name"] in names]


@pytest.fixture(scope="module")
def scorers():
    """One scorer a model for the whole file: its step programs compile
    once, in a first pass here, with the compiles counted."""
    from routest_tpu.core.cache import count_compiles

    count_compiles()
    out = {}
    for name, toy in TOYS.items():
        out[name] = m, params, scorer = _scorer(toy, max_step_tokens=128)
        scorer.score(*(jnp.asarray(a) for a in toy.routes(4, LENGTHS)))
        FIRST_PASS[name] = _last_pass()[1]
    return out


@pytest.fixture(scope="module", params=["dots3", "sala", "kexaone",
                                        "gigachat", "falcon"])
def scored(request, scorers):
    toy = TOYS[request.param]
    m, params, scorer = scorers[request.param]
    ids, lengths, rows_at = (jnp.asarray(a) for a in toy.routes(4, LENGTHS))
    return request.param, m, params, scorer, (ids, lengths, rows_at), \
        scorer.score(ids, lengths, rows_at)


def test_a_pass_through_the_scorer_is_the_model_route_by_route(scored):
    name, m, params, _, (ids, lengths, rows_at), scores = scored
    q = m.length_quantum
    for r, n in enumerate(int(v) for v in lengths):
        padded = -(-n // q) * q
        alone = program(TOYS[name].name, F32)(
            params, jnp.pad(ids[r:r + 1, :n], ((0, 0), (0, padded - n))),
            lengths[r:r + 1], rows_at[r:r + 1])
        np.testing.assert_allclose(scores.lse[r, :n], alone["lse"][0, :n],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(scores.loglik[r], alone["loglik"][0],
                                   rtol=1e-5)
        np.testing.assert_allclose(scores.rows[r], alone["rows"][0],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(scores.taps["n_keys"][:, r, :n],
                                      alone["n_keys"][:, 0, :n])


def test_the_tables_are_the_models_tap_tables(scored):
    name, m, _, _, (ids, _, rows_at), scores = scored
    want = m.tap_tables(ids.shape[0], ids.shape[1], rows_at.shape[1])
    assert set(scores.taps) == set(want)
    for tap, (shape, dtype, axis, unit) in want.items():
        assert scores.taps[tap].shape == shape, tap
        assert scores.taps[tap].dtype == dtype, tap
        if axis is not None:
            assert shape[axis] == -(-ids.shape[1] // unit)
    assert set(want) == {
        "dots3": {"n_keys", "first_key", "chosen", "selected"},
        "sala": {"n_keys", "n_visible", "blocks", "state"},
        "kexaone": {"n_keys", "first_key", "chosen", "mtp_next_logit",
                    "mtp_lse", "mtp_loglik"},
        "gigachat": {"n_keys", "first_key", "chosen", "mtp_next_logit",
                     "mtp_lse", "mtp_loglik"},
        "falcon": {"n_keys", "first_key", "state"}}[name]


def test_the_modules_column_reaches_the_caller_over_the_table(scorers):
    """Device arrays over the table's rows, as the first column's: a
    per-position tap follows the padded length, the per-route one has
    no such axis; both are the model's own answers for the route
    alone."""
    m, params, scorer = scorers["kexaone"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in kexaone.routes(4, LENGTHS))
    taps = scorer.score(ids, lengths, rows_at).taps
    assert isinstance(taps["mtp_loglik"], jax.Array)
    assert taps["mtp_lse"].shape == (1, 3, 96)
    assert taps["mtp_loglik"].shape == (1, 3)
    tables = m.tap_tables(3, 96, 3)
    assert tables["mtp_loglik"][2] is None and tables["mtp_lse"][2] == 2
    for r, n in enumerate(int(v) for v in lengths):
        padded = -(-n // 8) * 8
        alone = program(kexaone.name, F32)(
            params, jnp.pad(ids[r:r + 1, :n], ((0, 0), (0, padded - n))),
            lengths[r:r + 1], rows_at[r:r + 1])
        for tap in ("mtp_next_logit", "mtp_lse"):
            np.testing.assert_allclose(taps[tap][0, r, :n - 1],
                                       alone[tap][0, 0, :n - 1], rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(taps["mtp_loglik"][0, r],
                                   alone["mtp_loglik"][0, 0], rtol=1e-5)
        # the last two positions have no arc after next
        assert not np.asarray(taps["mtp_next_logit"][0, r, n - 2:]).any()


def test_route_lms_plan_and_tables_are_what_they_were(scorers):
    """The numbers of the scorer before the protocol: a quantum of
    lcm(select_block, window_block), the same ladder, the same four tap
    tables with the same shapes."""
    m, _, scorer = scorers["dots3"]
    assert scorer.quantum == 8 == int(np.lcm(m.select_block, m.window_block))
    plan = scorer.plan(np.asarray(LENGTHS))
    assert [(s.length, list(s.routes)) for s in plan] == [
        (s.length, list(s.routes))
        for s in plan_pass(LENGTHS, 8, 128, 8)]
    assert [s.length for s in plan] == [96, 72, 40]
    tables = scorer._empty_tables(4, 96, 3)
    assert {k: v.shape for k, v in tables.items()} == {
        "next_logit": (4, 96), "lse": (4, 96), "loglik": (4,),
        "rows": (4, 3, 128), "n_keys": (5, 4, 96), "first_key": (5, 4, 96),
        "chosen": (4, 4, 96, 4), "selected": (2, 4, 3, 96)}
    assert tables["selected"].dtype == jnp.bool_
    assert m.step_attrs(96) == {"attention": "xla", "window": "xla",
                                "mixers": "full=xla,sliding=xla",
                                "experts": "xla"}


def test_the_real_models_quanta():
    from benchmark import run as R
    from routest_tpu.models.route_lm import RouteLM
    from routest_tpu.models.route_lm_sala import RouteLMSala

    manifest = R.load_json(R.REPO, "BENCHMARK.json")
    _, cfg, mix = R.load_cell(manifest, "route-lm-score")
    assert RouteLM.from_config(cfg).length_quantum == 512
    _, cfg, mix = R.load_cell(manifest, "route-lm-sala-long")
    m = RouteLMSala.from_config(cfg)
    plan = plan_pass(mix["lengths"], m.length_quantum,
                     mix["max_step_tokens"], 8)
    assert [s.length for s in plan] == [47104, 30720, 23296, 18176, 13824,
                                        8960]
    assert all(len(s.routes) == 1 for s in plan)
    assert sum(s.padded_tokens for s in plan) == 543        # 0.38%
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone

    _, cfg, mix = R.load_cell(manifest, "route-lm-kexaone-mixed")
    m = RouteLMKExaone.from_config(cfg)
    assert m.length_quantum == 256
    plan = plan_pass(mix["lengths"], m.length_quantum,
                     mix["max_step_tokens"], mix["max_classes"])
    assert [(len(s.routes), s.length) for s in plan] == [
        (1, 26624), (1, 15104), (2, 11008), (2, 7168), (3, 5120), (3, 3328),
        (4, 2304), (4, 1280)]
    assert sum(s.padded_tokens for s in plan) == 11880      # 10.1%
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat

    _, cfg, mix = R.load_cell(manifest, "route-lm-gigachat-dense")
    m = RouteLMGigaChat.from_config(cfg)
    assert m.length_quantum == 256
    plan = plan_pass(mix["lengths"], m.length_quantum,
                     mix["max_step_tokens"], mix["max_classes"])
    assert [(len(s.routes), s.length) for s in plan] == [
        (1, 26112), (1, 17152), (1, 13312), (1, 10752), (2, 8960), (1, 6400),
        (2, 5120), (1, 2816)]
    assert sum(s.padded_tokens for s in plan) == 3643       # 3.48%
    from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1

    _, cfg, mix = R.load_cell(manifest, "route-lm-falcon-hybrid")
    m = RouteLMFalconH1.from_config(cfg)
    assert m.length_quantum == 256
    plan = plan_pass(mix["lengths"], m.length_quantum,
                     mix["max_step_tokens"], mix["max_classes"])
    assert [(len(s.routes), s.length) for s in plan] == [
        (1, 14848), (1, 9728), (2, 7936), (2, 5632), (2, 4608), (4, 3584),
        (4, 2560), (4, 1536)]
    assert sum(s.padded_tokens for s in plan) == 8843       # 9.65%


@pytest.fixture
def registry():
    from routest_tpu.obs import MetricsRegistry
    from routest_tpu.obs import registry as reg_mod

    old, seq_score._metrics = reg_mod._default_registry, None
    reg_mod._default_registry = MetricsRegistry()
    yield reg_mod._default_registry
    reg_mod._default_registry, seq_score._metrics = old, None


def _family(registry, name):
    return {labels: child.value
            for labels, child in registry.get(name).items()}


def test_sala_counters_and_span_attributes(registry, scorers):
    from routest_tpu.obs import get_tracer

    m, params, scorer = scorers["sala"]
    ids, lengths, rows_at = (jnp.asarray(a) for a in sala.routes(4, LENGTHS))
    scores = scorer.score(ids, lengths, rows_at)
    tokens = _family(registry, "rtpu_seq_tokens_total")
    assert tokens[("real",)] == sum(LENGTHS)
    keys = _family(registry, "rtpu_seq_sparse_keys_total")
    real = np.arange(96)[None] < np.asarray(lengths)[:, None]
    chosen = float(np.where(real[None, :, :, None],
                            np.asarray(scores.taps["n_keys"]), 0).sum())
    assert keys[("chosen",)] == chosen > 0
    assert keys[("visited",)] > chosen
    # linear layers x (12 + 9 + 5) chunks of 8 of the padded lengths
    assert _family(registry, "rtpu_seq_linear_chunks_total")[()] == 2 * 26
    blocks = _family(registry, "rtpu_seq_sparse_blocks_per_query")[()]
    assert 1.0 < blocks <= 6.0
    assert _family(registry, "rtpu_seq_attention_chunks_total") == {}
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-3:]
    assert all(s["attrs"]["mixers"] == "sparse=xla,linear=xla"
               for s in steps)


def test_route_lm_counters_are_what_they_were(registry, scorers):
    m, params, scorer = scorers["dots3"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in dots3.routes(4, LENGTHS))
    scorer.score(ids, lengths, rows_at)
    chunks = _family(registry, "rtpu_seq_attention_chunks_total")
    want = sum(m.selected_steps(s.length)[1] * len(s.routes) * 2
               for s in scorer.plan(np.asarray(LENGTHS)))
    assert chunks == {("xla",): want}
    # three sliding layers, blocks of 8 queries, one route a step
    plan = scorer.plan(np.asarray(LENGTHS))
    assert _family(registry, "rtpu_seq_window_blocks_total") == {
        ("xla",): 3 * sum(s.length // 8 * len(s.routes) for s in plan)}
    assert m.pass_counts(plan, [], sum(LENGTHS))[1] == (
        "window_blocks", {"path": "xla"}, 3 * 96 // 8)
    from routest_tpu.obs import get_tracer
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-len(plan):]
    assert [(s["attrs"]["attention"], s["attrs"]["window"]) for s in steps] \
        == [("xla", "xla")] * len(plan)
    assert _family(registry, "rtpu_seq_expert_load_max_over_mean")[()] >= 1.0
    assert 0 < _family(registry, "rtpu_seq_held_assignment_share")[()] <= 1.0
    # four expert layers a step; ragged_dot multiplies the held rows alone
    assert _family(registry, "rtpu_seq_expert_blocks_total") == {
        ("xla",): 4 * len(plan)}
    rows = _family(registry, "rtpu_seq_expert_rows_total")
    assert rows[("visited",)] == rows[("held",)] > 0
    assert all(s["attrs"]["experts"] == "xla" for s in steps)
    assert _family(registry, "rtpu_seq_selected_keys_per_query")[()] > 1.0
    assert _family(registry, "rtpu_seq_sparse_keys_total") == {}


def test_route_lm_counts_its_selecting_blocks_by_the_form_of_the_top_k(
        registry, scorers):
    """Blocks of queries of the full layers that ran the selection, from
    the plan: a toy route is longer than its ``top_k`` of 16, and on the
    CPU every one takes the XLA form."""
    m, params, scorer = scorers["dots3"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in dots3.routes(4, LENGTHS))
    scorer.score(ids, lengths, rows_at)
    plan = scorer.plan(np.asarray(LENGTHS))
    want = sum(s.length // 8 * len(s.routes) * 2 for s in plan)
    assert _family(registry, "rtpu_seq_topk_blocks_total") == {
        ("xla",): want}
    assert want == 2 * (96 + 40 + 72) // 8


def test_kexaone_counters_and_span_attributes(registry, scorers):
    from routest_tpu.obs import get_tracer
    from routest_tpu.parallel import gqa

    m, params, scorer = scorers["kexaone"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in kexaone.routes(4, LENGTHS))
    scorer.score(ids, lengths, rows_at)
    assert _family(registry, "rtpu_seq_tokens_total")[("real",)] == sum(
        LENGTHS)
    keys = _family(registry, "rtpu_seq_gqa_keys_total")
    # four sliding layers see min(t + 1, 8) keys, the full layer t + 1
    # and the module's block t + 1 over a route's n - 1 positions
    seen = lambda n, cap: sum(min(t + 1, cap) for t in range(n))  # noqa: E731
    assert keys[("window", "needed")] == 4 * sum(seen(n, 8) for n in LENGTHS)
    assert keys[("full", "needed")] == sum(seen(n, n) + seen(n - 1, n)
                                           for n in LENGTHS)
    plan = scorer.plan(np.asarray(LENGTHS))
    assert [s.length for s in plan] == [96, 72, 40]
    assert keys[("window", "visited")] == 4 * sum(
        gqa.window_visited(s.length, 8) for s in plan)
    assert keys[("full", "visited")] == 2 * sum(
        gqa.causal_visited(s.length, 8, 16) for s in plan)
    assert keys[("window", "visited")] > keys[("window", "needed")]
    assert keys[("full", "visited")] > keys[("full", "needed")]
    assert _family(registry, "rtpu_seq_mtp_positions_total")[()] == sum(
        n - 2 for n in LENGTHS)
    assert _family(registry, "rtpu_seq_expert_load_max_over_mean")[()] >= 1.0
    # 8 of 16 experts held: about half of the assignments land here
    assert 0.3 < _family(registry,
                         "rtpu_seq_held_assignment_share")[()] < 0.7
    # four trunk expert blocks and the module's, three steps
    assert _family(registry, "rtpu_seq_expert_blocks_total") == {
        ("xla",): 5 * 3}
    rows = _family(registry, "rtpu_seq_expert_rows_total")
    assert rows[("visited",)] == rows[("held",)] > 0
    assert _family(registry, "rtpu_seq_attention_chunks_total") == {}
    assert _family(registry, "rtpu_seq_window_blocks_total") == {}
    assert _family(registry, "rtpu_seq_sparse_keys_total") == {}
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-3:]
    assert all(s["attrs"]["mixers"] == "full=xla,window=xla"
               and s["attrs"]["mtp"] == "1"
               and s["attrs"]["experts"] == "xla" for s in steps)


def test_gigachat_counters_and_span_attributes(registry, scorers):
    from routest_tpu.obs import get_tracer
    from routest_tpu.parallel import latent

    m, params, scorer = scorers["gigachat"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in gigachat.routes(4, LENGTHS))
    scores = scorer.score(ids, lengths, rows_at)
    assert _family(registry, "rtpu_seq_tokens_total")[("real",)] == sum(
        LENGTHS)
    keys = _family(registry, "rtpu_seq_latent_keys_total")
    # five trunk blocks see t + 1 keys, the module's block t + 1 over a
    # route's n - 1 positions
    tri = lambda n: n * (n + 1) // 2                        # noqa: E731
    assert keys[("needed",)] == sum(5 * tri(n) + tri(n - 1) for n in LENGTHS)
    plan = scorer.plan(np.asarray(LENGTHS))
    assert [s.length for s in plan] == [96, 72, 40]
    # the pairs the kernel's grid multiplies: every block of 8 queries
    # times the keys up to its own, 8 x 8 x (1 + 2 + ... + L / 8)
    assert keys[("visited",)] == 6 * sum(
        latent.visited(s.length, 8, 16) for s in plan) == 6 * sum(
        64 * tri(s.length // 8) for s in plan)
    assert keys[("visited",)] > keys[("needed",)]
    # its grid steps from the same tables: one diagonal a block of
    # queries, the whole tiles of 16 keys before it interior; one group
    # of heads at the toy's 4
    tiles = _family(registry, "rtpu_seq_latent_tiles_total")
    assert tiles[("diagonal",)] == 6 * sum(
        len(s.routes) * s.length // 8 for s in plan)
    assert tiles[("interior",)] + tiles[("diagonal",)] == 6 * sum(
        len(latent.causal_grid(len(s.routes), s.length, 8, 16))
        for s in plan)
    assert tiles[("interior",)] == 6 * sum(
        len(s.routes) * sum(i // 2 for i in range(s.length // 8))
        for s in plan)
    assert _family(registry, "rtpu_seq_mtp_positions_total")[()] == sum(
        n - 2 for n in LENGTHS)
    # the tokens one of whose chosen experts lies in group 0 (experts
    # 0-3; 0-1 are held), from the table's taps; all: four trunk expert
    # blocks' n and the module's n - 1
    chosen = np.asarray(scores.taps["chosen"])              # (5, R, W, k)
    real = (np.arange(96)[None, None] < np.asarray(lengths)[None, :, None]
            - (np.arange(5) == 4)[:, None, None])
    hits = _family(registry, "rtpu_seq_expert_group_tokens_total")
    assert hits[("held_group",)] == ((chosen // 4 == 0).any(-1) & real).sum()
    assert hits[("all",)] == real.sum() == 5 * sum(LENGTHS) - len(LENGTHS)
    assert 0.2 < hits[("held_group",)] / hits[("all",)] < 0.6
    assert _family(registry, "rtpu_seq_expert_load_max_over_mean")[()] >= 1.0
    # 2 of 32 experts held
    assert 0.02 < _family(registry,
                          "rtpu_seq_held_assignment_share")[()] < 0.12
    assert _family(registry, "rtpu_seq_expert_blocks_total") == {
        ("xla",): 5 * 3}
    rows = _family(registry, "rtpu_seq_expert_rows_total")
    assert rows[("visited",)] == rows[("held",)] > 0
    assert _family(registry, "rtpu_seq_gqa_keys_total") == {}
    assert _family(registry, "rtpu_seq_attention_chunks_total") == {}
    assert _family(registry, "rtpu_seq_sparse_keys_total") == {}
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-3:]
    assert all(s["attrs"]["mixers"] == "latent=xla"
               and s["attrs"]["mtp"] == "1" and s["attrs"]["groups"] == "8/4"
               and s["attrs"]["experts"] == "xla" for s in steps)


def test_falcon_counters_span_attributes_and_states(registry, scorers):
    """The attention's keys as K-EXAONE's full layers count them, the
    scan's chunk steps by form, and each block's state at a route's last
    real token: the state of the route alone, whatever its step."""
    from routest_tpu.obs import get_tracer
    from routest_tpu.parallel import gqa

    m, params, scorer = scorers["falcon"]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in falcon.routes(4, LENGTHS))
    scores = scorer.score(ids, lengths, rows_at)
    assert _family(registry, "rtpu_seq_tokens_total")[("real",)] == sum(
        LENGTHS)
    keys = _family(registry, "rtpu_seq_gqa_keys_total")
    tri = lambda n: n * (n + 1) // 2                        # noqa: E731
    plan = scorer.plan(np.asarray(LENGTHS))
    assert [s.length for s in plan] == [96, 72, 40]
    assert keys[("full", "needed")] == 3 * sum(tri(n) for n in LENGTHS)
    assert keys[("full", "visited")] == 3 * sum(
        gqa.causal_visited(s.length, 8, 16) for s in plan)
    assert set(keys) == {("full", "needed"), ("full", "visited")}
    # three blocks x (12 + 9 + 5) chunks of 8, the XLA form here
    assert _family(registry, "rtpu_seq_ssm_chunks_total") == {
        ("xla",): 3 * 26}
    assert _family(registry, "rtpu_seq_linear_chunks_total") == {}
    assert _family(registry, "rtpu_seq_expert_blocks_total") == {}
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-3:]
    assert all(s["attrs"]["mixers"] == "ssm=xla,attn=xla" for s in steps)
    for r, n in enumerate(int(v) for v in lengths):
        padded = -(-n // 8) * 8
        alone = program(falcon.name, F32)(
            params, jnp.pad(ids[r:r + 1, :n], ((0, 0), (0, padded - n))),
            lengths[r:r + 1], rows_at[r:r + 1])
        np.testing.assert_allclose(scores.taps["state"][:, r],
                                   alone["state"][:, 0], rtol=1e-4,
                                   atol=1e-6)


def test_the_other_two_models_emit_no_gqa_or_module_counters(registry,
                                                             scorers):
    for name, toy in (("dots3", dots3), ("sala", sala)):
        _, _, scorer = scorers[name]
        ids, lengths, rows_at = (jnp.asarray(a)
                                 for a in toy.routes(4, LENGTHS))
        scorer.score(ids, lengths, rows_at)
    assert _family(registry, "rtpu_seq_gqa_keys_total") == {}
    assert _family(registry, "rtpu_seq_mtp_positions_total") == {}
    assert _family(registry, "rtpu_seq_latent_keys_total") == {}
    assert _family(registry, "rtpu_seq_latent_tiles_total") == {}
    assert _family(registry, "rtpu_seq_expert_group_tokens_total") == {}
    assert _family(registry, "rtpu_seq_ssm_chunks_total") == {}


# ── a pass accounts for its own time (ISSUE 37) ─────────────────────


@pytest.fixture
def ledger(monkeypatch):
    """A goodput ledger of the test's own as the process's."""
    from routest_tpu.obs import MetricsRegistry, efficiency

    mine = efficiency.GoodputLedger(registry=MetricsRegistry())
    monkeypatch.setattr(efficiency, "_ledger", mine)
    return mine


@pytest.mark.parametrize("name", sorted(TOYS))
def test_a_pass_leaves_one_timed_wait_a_step_in_dispatch_order(
        name, scorers, ledger):
    _, _, scorer = scorers[name]
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in TOYS[name].routes(4, LENGTHS))
    scorer.score(ids, lengths, rows_at)
    plan = scorer.plan(np.asarray(LENGTHS))
    root, waits = _last_pass(("seq.wait.step",))
    want = [{"length_class": s.length, "routes": int((s.routes >= 0).sum()),
             "real_tokens": s.real_tokens, "padded_tokens": s.padded_tokens}
            for s in plan]
    assert [{k: w["attrs"][k] for k in want[0]} for w in waits] == want
    assert all(w["attrs"]["device_ms"] > 0.0 for w in waits)
    total = sum(w["attrs"]["device_ms"] for w in waits)
    assert root["attrs"]["device_ms"] == pytest.approx(total)
    assert 0.0 < total <= root["duration_ms"]
    # every wait lies inside the pass's one seq.wait
    (wait,) = _last_pass(("seq.wait",))[1]
    assert {w["parent_id"] for w in waits} == {wait["span_id"]}
    assert sum(w["duration_ms"] for w in waits) <= wait["duration_ms"]
    # the host's account of the pass, as far as this machine gives one
    assert root["attrs"]["cpu_ms"] > 0.0 and root["attrs"]["gc_ms"] >= 0.0
    # one ledger record a step: tokens, launched tokens, the class
    seq = ledger.snapshot()["programs"]["seq_score"]
    assert seq["calls"] == len(plan)
    assert seq["rows"] == sum(LENGTHS)
    assert seq["padded_rows"] == sum(s.length * len(s.routes) for s in plan)
    assert seq["device_s"] == pytest.approx(total / 1e3, rel=1e-3)
    assert {b: (w["rows"], w["padded"]) for b, w in seq["buckets"].items()} \
        == {s.length: (s.real_tokens, s.length * len(s.routes))
            for s in plan}


@pytest.mark.parametrize("name", sorted(TOYS))
def test_with_the_tracer_off_a_pass_waits_once_and_reports_nothing(
        name, scorers, ledger, monkeypatch):
    from routest_tpu.obs import Tracer, configure_tracer, get_tracer

    _, _, scorer = scorers[name]
    args = [jnp.asarray(a) for a in TOYS[name].routes(4, LENGTHS)]
    waited, block = [], jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(1) or block(x))
    old = get_tracer()
    off = configure_tracer(Tracer(enabled=False))
    try:
        scores = scorer.score(*args)
    finally:
        configure_tracer(old)
    assert len(waited) == 1 and off.buffer.snapshot() == []
    assert ledger.snapshot()["programs"]["seq_score"]["calls"] == 0
    on = scorer.score(*args)
    np.testing.assert_array_equal(scores.loglik, on.loglik)
    assert len(waited) == 1 + len(scorer.plan(np.asarray(LENGTHS))) + 1


@pytest.mark.parametrize("name", sorted(TOYS))
def test_the_steps_that_compiled_say_so_and_no_later_one_does(
        name, scorers, ledger):
    first = FIRST_PASS[name]
    assert len(first) == 3
    assert all(s["attrs"]["compile_ms"] > 0.0 for s in first)
    assert all(s["attrs"]["compile_ms"] <= s["duration_ms"] for s in first)
    _, _, scorer = scorers[name]
    scorer.score(*(jnp.asarray(a) for a in TOYS[name].routes(4, LENGTHS)))
    assert not any("compile_ms" in s["attrs"] for s in _last_pass()[1])


def test_a_pass_that_compiled_reports_no_device_seconds(ledger):
    """Its waits began long after its first steps ended."""
    _, _, scorer = _scorer(dots3, max_step_tokens=128)
    args = [jnp.asarray(a) for a in dots3.routes(4, [16])]
    scorer.score(*args)
    assert ledger.snapshot()["programs"]["seq_score"]["calls"] == 0
    (wait,) = _last_pass(("seq.wait.step",))[1]
    assert wait["attrs"]["device_ms"] > 0.0
    scorer.score(*args)
    assert ledger.snapshot()["programs"]["seq_score"]["calls"] == 1


class _NoStats:
    """A model whose steps of the middle class hand back no stats."""

    def __init__(self, model, silent):
        self._model, self._silent = model, silent

    def __getattr__(self, name):
        return getattr(self._model, name)

    def step_stats(self, out, lengths):
        if out["lse"].shape[1] in self._silent:
            return {}
        return self._model.step_stats(out, lengths)

    def pass_counts(self, steps, stats, real):
        return []


@pytest.mark.parametrize("silent,want", [
    ((72,), [(96, 96, 1), (72, 103, 2)]),       # timed with the next one
    ((40,), [(96, 96, 1), (72, 70, 1), (40, 33, 1)]),   # a tail: the tables
    ((96, 72, 40), [(96, 199, 3)])])
def test_a_step_with_nothing_to_wait_on_is_timed_with_the_next(
        silent, want, ledger):
    m, params = model("RouteLM", F32), toys.params("RouteLM", F32, 0)
    scorer = RouteScorer(_NoStats(m, silent), params, max_step_tokens=128)
    args = [jnp.asarray(a) for a in dots3.routes(4, LENGTHS)]
    scorer.score(*args)
    scorer.score(*args)
    root, waits = _last_pass(("seq.wait.step",))
    assert [(w["attrs"]["length_class"], w["attrs"]["real_tokens"],
             w["attrs"]["routes"]) for w in waits] == want
    assert sum(w["attrs"]["real_tokens"] for w in waits) == sum(LENGTHS)
    assert root["attrs"]["device_ms"] == pytest.approx(
        sum(w["attrs"]["device_ms"] for w in waits))

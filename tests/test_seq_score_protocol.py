"""One scorer for every route-sequence model: what ``RouteScorer`` asks
of a model (``serve/seq_score.py``) is met by ``RouteLM``, by
``RouteLMSala`` and by ``RouteLMKExaone``, whose prediction module's
column comes through the same tap tables; ``RouteLM``'s and
``RouteLMSala``'s plans and result tables are what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _route_lm_kexaone_toy as kexaone
import _route_lm_sala_toy as sala
import _route_lm_toy as dots3
from routest_tpu.serve import seq_score
from routest_tpu.serve.seq_score import RouteScorer, plan_pass

LENGTHS = [96, 33, 70]
TOYS = {"dots3": dots3, "sala": sala, "kexaone": kexaone}


def _scorer(toy, **kw):
    m = toy.model()
    params = jax.jit(m.init)(jax.random.PRNGKey(0))
    return m, params, RouteScorer(m, params, **kw)


@pytest.fixture(scope="module")
def scorers():
    """One scorer a model for the whole file: its step programs compile
    once."""
    return {name: _scorer(toy, max_step_tokens=128)
            for name, toy in TOYS.items()}


@pytest.fixture(scope="module", params=["dots3", "sala", "kexaone"])
def scored(request, scorers):
    toy = TOYS[request.param]
    m, params, scorer = scorers[request.param]
    ids, lengths, rows_at = (jnp.asarray(a) for a in toy.routes(4, LENGTHS))
    return request.param, m, params, scorer, (ids, lengths, rows_at), \
        scorer.score(ids, lengths, rows_at)


def test_a_pass_through_the_scorer_is_the_model_route_by_route(scored):
    _, m, params, _, (ids, lengths, rows_at), scores = scored
    q = m.length_quantum
    for r, n in enumerate(int(v) for v in lengths):
        padded = -(-n // q) * q
        alone = jax.jit(m.apply)(
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
                    "mtp_lse", "mtp_loglik"}}[name]


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
        alone = jax.jit(m.apply)(
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
                                "mixers": "full=xla,sliding=xla"}


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
    assert _family(registry, "rtpu_seq_selected_keys_per_query")[()] > 1.0
    assert _family(registry, "rtpu_seq_sparse_keys_total") == {}


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
    assert _family(registry, "rtpu_seq_attention_chunks_total") == {}
    assert _family(registry, "rtpu_seq_window_blocks_total") == {}
    assert _family(registry, "rtpu_seq_sparse_keys_total") == {}
    steps = [s for s in get_tracer().buffer.snapshot()
             if s["name"] == "seq.step"][-3:]
    assert all(s["attrs"]["mixers"] == "full=xla,window=xla"
               and s["attrs"]["mtp"] == "1" for s in steps)


def test_the_other_two_models_emit_no_gqa_or_module_counters(registry,
                                                             scorers):
    for name, toy in (("dots3", dots3), ("sala", sala)):
        _, _, scorer = scorers[name]
        ids, lengths, rows_at = (jnp.asarray(a)
                                 for a in toy.routes(4, LENGTHS))
        scorer.score(ids, lengths, rows_at)
    assert _family(registry, "rtpu_seq_gqa_keys_total") == {}
    assert _family(registry, "rtpu_seq_mtp_positions_total") == {}

"""What every route-sequence backbone owes, checked once over the toy
registry (``_route_lm_toys.py``): the whole model against its plain
float32 reference, output by output and route by route, and its taps;
bfloat16 within the limits its entry states; a route's outputs are its
own; the parameter count and the configuration's published keys at
the cell's widths; the artifact's round trip and its share gate. A
model ``train/checkpoint`` can load and the registry lacks fails here:
a new backbone is a registry entry and the tests of its own
mechanisms."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import (F32, TOYS, by_layer, close, highest, model,
                            params, pick, program, rel, taps_equal)
from routest_tpu.core.dtypes import BF16_POLICY
from routest_tpu.train.checkpoint import (_route_lm_types, load_route_lm,
                                          save_route_lm)

TAPS = ("n_keys", "first_key", "n_visible", "selected", "blocks", "chosen")
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "published_configs.json")) as f:
    PUBLISHED = json.load(f)    # each cell's source's config.json


@functools.lru_cache(maxsize=None)
def table(name):
    """The float32 toy's routes, the program's answers and the
    reference's, route by route: made once a module."""
    toy = TOYS[name]
    p = params(name, F32, toy.seed)
    ids, lengths, rows_at = toy.routes(*toy.table)
    out = highest(program(name, F32))(p, ids, lengths, rows_at)
    kw = {} if toy.blocks is None else {"blocks": toy.blocks}
    want = [toy.forward(p, ids[b, :n], rows_at[b], **kw)
            for b, n in enumerate(lengths)]
    return (ids, lengths, rows_at), out, want


def test_every_route_sequence_model_has_a_toy():
    assert set(_route_lm_types()) == set(TOYS)


@pytest.mark.parametrize("name,b,what", [
    (name, b, what) for name, toy in TOYS.items()
    for b in range(len(toy.table[1])) for what in toy.outputs])
def test_float32_is_the_reference(name, b, what):
    (_, lengths, _), out, want = table(name)
    TOYS[name].matches(out, want[b], b, int(lengths[b]), what)


@pytest.mark.parametrize("name,b", [(name, b) for name, toy in TOYS.items()
                                    for b in range(len(toy.table[1]))])
def test_the_taps_are_the_references(name, b):
    (_, lengths, _), out, want = table(name)
    taps_equal(out, want[b], b, TAPS)
    TOYS[name].laws(want[b], int(lengths[b]))


BF16 = [(name, lengths) for name, toy in TOYS.items() if toy.bf16
        for lengths in toy.bf16["tables"]]


@pytest.mark.parametrize("name,lengths", BF16, ids=[
    f"{name}-{'_'.join(map(str, lengths))}" for name, lengths in BF16])
def test_bfloat16_stays_within_stated_limits(name, lengths):
    """bfloat16 parameters and activations against the float32
    reference on the same (bfloat16-valued) weights: the gaps the cell
    compares, at a toy width."""
    toy = TOYS[name]
    spec = toy.bf16
    p = params(name, BF16_POLICY, spec["seed"])
    ids, lengths, rows_at = toy.routes(spec["routes"], lengths)
    out = program(name, BF16_POLICY)(p, ids, lengths, rows_at)
    assert all(out[k].dtype == jnp.float32 for k in toy.outputs
               if k.endswith("lse"))
    for r, n in enumerate(int(v) for v in lengths):
        want = toy.forward(p, ids[r, :n], rows_at[r])
        taps_equal(out, want, r, ("n_keys",))
        for what, limit in spec["limits"].items():
            if what == "chosen":
                agree = [(np.asarray(out["chosen"][i, r, :len(w)])[:, :, None]
                          == w[:, None, :]).any(-1).mean()
                         for i, w in enumerate(want["chosen"])]
                assert min(agree) > limit
            elif what == "blocks":
                for i, w in enumerate(want["blocks"]):
                    got = np.asarray(out["blocks"][i, r])[
                        tuple(slice(0, s) for s in w.shape)]
                    assert (w & ~got).sum() <= limit * w.sum()
            else:
                for g, w in by_layer(what, pick(out, what, r, n),
                                     want[what]):
                    assert rel(g, w) < limit, what


@pytest.mark.parametrize("how", ["in_a_table", "alone", "wider"])
@pytest.mark.parametrize("name", list(TOYS))
def test_a_routes_outputs_are_its_own(name, how):
    """Each route beside the others in reverse order, or alone, with
    rubbish past its end, in the same padded length: the same bits,
    taps and all, where the entry says so; beside the others in a
    padded length 4 quanta wider (another chunking of the same
    softmax), and elsewhere: within the entry's tolerance."""
    toy = TOYS[name]
    p, run = params(name, F32, toy.seed), highest(program(name, F32))
    ids, lengths, rows_at = toy.routes(toy.own_seeds.get(how, toy.table[0]),
                                       toy.table[1])
    base = run(p, ids, lengths, rows_at)
    routes, width = ids.shape
    if how == "wider":
        width += 4 * model(name, F32).length_quantum
    junk = np.full((routes, width), 5, np.int32)
    junk[:, :ids.shape[1]] = np.where(
        np.arange(ids.shape[1])[None] < lengths[:, None], ids, 5)
    if how == "alone":
        placed = [(run(p, junk[r:r + 1], lengths[r:r + 1], rows_at[r:r + 1]),
                   0) for r in range(routes)]
    else:
        got = run(p, junk[::-1], lengths[::-1], rows_at[::-1])
        placed = [(got, routes - 1 - r) for r in range(routes)]
    exact = how in toy.own_exact
    for r, (got, g) in enumerate(placed):
        n = int(lengths[r])
        for what in toy.outputs:
            a, b = pick(got, what, g, n), pick(base, what, r, n)
            if exact:
                np.testing.assert_array_equal(a, b, err_msg=what)
            else:
                close(a, b, toy.own.get(what, toy.own["*"]), what)
        for tap in ("n_keys", "first_key") + (("chosen",) if exact else ()):
            if tap in base:
                np.testing.assert_array_equal(got[tap][:, g, :n - 1],
                                              base[tap][:, r, :n - 1])


WITH_MODULE = [name for name, toy in TOYS.items()
               if "mtp_lse" in toy.outputs]


@pytest.mark.parametrize("name", WITH_MODULE)
def test_a_share_without_the_prediction_module_holds_and_taps_none(name):
    toy = TOYS[name]
    bare = toy.model(share=dict(toy.config["share"], mtp_held=False))
    assert len(bare.block_kinds()) == 5
    assert "mtp" not in jax.eval_shape(bare.init, jax.random.PRNGKey(0))
    assert set(bare.tap_tables(4, 96, 3)) == {"n_keys", "first_key",
                                              "chosen"}


@pytest.mark.parametrize("name", WITH_MODULE)
def test_the_modules_column_looks_no_further_than_the_next_token(name):
    """With every token past t + 1 = 41 changed, the first column's
    distribution stands up to t = 41 and the module's up to t = 40 (it
    has read ``id_41``); the module's at 41 has read ``id_42`` and
    moves."""
    (ids, lengths, rows_at), out, _ = table(name)
    changed = np.array(ids)
    changed[0, 42:] = (changed[0, 42:] + 7) % TOYS[name].config["vocab_size"]
    again = highest(program(name, F32))(params(name, F32, TOYS[name].seed),
                                        changed, lengths, rows_at)
    np.testing.assert_array_equal(again["lse"][0, :42], out["lse"][0, :42])
    np.testing.assert_array_equal(again["mtp_lse"][0, 0, :41],
                                  out["mtp_lse"][0, 0, :41])
    np.testing.assert_array_equal(again["mtp_next_logit"][0, 0, :40],
                                  out["mtp_next_logit"][0, 0, :40])
    assert again["mtp_lse"][0, 0, 41] != out["mtp_lse"][0, 0, 41]
    assert again["lse"][0, 42] != out["lse"][0, 42]


@pytest.mark.parametrize("name", list(TOYS))
def test_parameter_count_at_the_published_widths(name):
    toy = TOYS[name]
    cfg = toy.cell()
    m = _route_lm_types()[name].from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)

    def size(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    facts = {"parameters": size(shapes),
             "vectors": size([x for x in leaves if x.ndim == 1]),
             "bytes": sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in leaves)}
    facts["matrices"] = facts["parameters"] - facts["vectors"]
    for path in toy.count:
        if isinstance(path, tuple):          # a part of the tree
            facts[path] = size(functools.reduce(lambda t, k: t[k], path,
                                                shapes))
    assert {k: facts[k] for k in toy.count} == toy.count
    counted = toy.counted(cfg)
    assert {k: facts[k] for k in counted} == counted
    assert cfg.get("parameters_held", facts["parameters"]) \
        == facts["parameters"]
    assert all(x.dtype == jnp.bfloat16 for x in leaves if x.ndim > 1)
    assert all(m.sizes[k] == v for k, v in cfg["published"].items())
    for attr, want in toy.attrs.items():
        got = getattr(m, attr)
        assert (got() if callable(got) else got) == want, attr


@pytest.mark.parametrize("name", list(TOYS))
def test_the_configuration_keeps_every_published_key(name):
    toy = TOYS[name]
    cfg, row = toy.cell(), PUBLISHED[toy.config_file]
    assert cfg["source"] == row["source"]
    for key, value in row["config"].items():
        assert cfg[key] == toy.cut.get(key, value), key
    assert cfg["reduced"] == list(toy.cut)
    assert cfg["published"] == {k: row["config"][k] for k in toy.cut}
    assert cfg["share"] == toy.cell_share
    assert all(cfg[k] for k in ("assumed", "deployment", "guarantees",
                                "not_built"))
    assert toy.floors(cfg)


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """An artifact of each float32 toy, written once a module."""
    root = tmp_path_factory.mktemp("route_lm")

    @functools.lru_cache(maxsize=None)
    def save(name):
        m, p = model(name, F32), params(name, F32, TOYS[name].seed)
        path = str(root / f"{name}.msgpack")
        save_route_lm(path, m, p)
        return m, p, path
    return save


@pytest.mark.parametrize("name", list(TOYS))
def test_artifact_round_trip_returns_the_model_it_was_saved_from(name,
                                                                 saved):
    """The artifact carries the model, not the toy's block sizes."""
    m, p, path = saved(name)
    m2, p2 = load_route_lm(path, expect_share=m.share_header())
    fields = {f.name for f in dataclasses.fields(m)}
    assert dataclasses.replace(m2, **{
        k: v for k, v in TOYS[name].config.items() if k in fields}) == m
    a, b = jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(p2)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.asarray(x).dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), y)
    (ids, lengths, rows_at), out, _ = table(name)
    again = highest(program(name, F32))(p2, ids, lengths, rows_at)
    for what in out:
        np.testing.assert_array_equal(again[what], out[what])


@pytest.mark.parametrize("name,key,value", [
    (name, key, value) for name, toy in TOYS.items()
    for key, value in toy.refuse.items()])
def test_artifact_of_another_share_is_refused(name, key, value, saved):
    with pytest.raises(ValueError, match=key):
        load_route_lm(saved(name)[2], expect_share={key: value})


@pytest.mark.parametrize("name", list(TOYS))
def test_artifact_whose_arrays_are_not_its_headers_share_is_refused(
        name, tmp_path):
    toy = TOYS[name]
    liar, p = toy.liar(model(name, F32), params(name, F32, toy.seed))
    path = str(tmp_path / "liar.msgpack")
    save_route_lm(path, liar, p)
    with pytest.raises(ValueError, match="not the share"):
        load_route_lm(path)

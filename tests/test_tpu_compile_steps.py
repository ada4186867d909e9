"""Whole step programs of the route-sequence models at the cells'
published widths, compiled for a TPU v5e that is described and not
attached (``jax.experimental.topologies``): the kernels each step must
hold and the memory it must fit in, at no chip time. Apart from
``test_tpu_compile.py`` so that the two run side by side. Skipped where
the TPU compiler cannot describe the topology."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from _v5e import _on, v5e  # noqa: F401  (v5e: a fixture)


# The longest and the shortest step of the two sparse-expert
# route-sequence models at the cells' own configurations (one route of
# 26,624 tokens; three of 1,536 and four of 1,280), the longest step and
# a shared one of the group-limited one (one route of 26,112 tokens
# beside 9.83 GiB of parameters; two of 8,960), and a step shorter
# than any the cells hold (one route of 1,280: under 4,096 tokens a
# piece of the combine is less than COMBINE_ROWS), every path function
# answering as it does on the chip: the held experts' grouped
# product is the two kernels, an expert's matrices are read by block
# index (the loop this replaced sliced w_gate[e], w_up[e], w_down[e] out
# of the stacks a tile at a time), and the combine's scatter-adds stay
# the plain ones (a larger one sorts its indices and permutes its rows
# first: PERF.md §6).
@pytest.mark.parametrize("config,module,cls,blocks,routes,length", [
    ("dots3-note-prev-ep8", "route_lm", "RouteLM", 4, 1, 26624),
    ("k-exaone-236b-ep8", "route_lm_kexaone", "RouteLMKExaone", 5, 1, 26624),
    ("dots3-note-prev-ep8", "route_lm", "RouteLM", 4, 3, 1536),
    ("k-exaone-236b-ep8", "route_lm_kexaone", "RouteLMKExaone", 5, 4, 1280),
    ("k-exaone-236b-ep8", "route_lm_kexaone", "RouteLMKExaone", 5, 1, 1280),
    ("gigachat3.1-702b-ep16", "route_lm_gigachat", "RouteLMGigaChat", 5, 1,
     26112),
    ("gigachat3.1-702b-ep16", "route_lm_gigachat", "RouteLMGigaChat", 5, 2,
     8960)])
def test_a_step_holds_the_grouped_expert_kernels(v5e, monkeypatch, config,
                                                 module, cls, blocks, routes,
                                                 length):
    import importlib
    import json

    from routest_tpu.parallel import expert, latent, select

    def on_the_chip(fn):
        return lambda *a, **kw: fn(*a, **{**kw, "backend": "tpu"})

    monkeypatch.setattr(expert, "expert_path",
                        on_the_chip(expert.expert_path))
    monkeypatch.setattr(select, "attention_path",
                        on_the_chip(select.attention_path))
    monkeypatch.setattr(select, "window_path",
                        on_the_chip(select.window_path))
    monkeypatch.setattr(latent, "latent_path",
                        on_the_chip(latent.latent_path))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    model = getattr(importlib.import_module("routest_tpu.models." + module),
                    cls).from_config(cfg)
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert expert.expert_path(d, m, jnp.bfloat16) == "fused"

    def on(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(model.apply).lower(
        _on(v5e, jax.eval_shape(model.init, jax.random.PRNGKey(0))),
        on((routes, length)), on((routes,)), on((routes, 3))).compile()
    text = compiled.as_text()
    # the step fits beside its parameters: the chip gives a program
    # 15.75 GiB; the fourth model's longest step, whose queries are made
    # once a layer for the one dense kernel, is held to 15.0 (13.03 when
    # its kernel came: PERF.md §4)
    memory = compiled.memory_analysis()
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            + memory.output_size_in_bytes) < 15.75 * 2 ** 30
    if cls == "RouteLMGigaChat":
        assert (memory.temp_size_in_bytes
                + memory.argument_size_in_bytes) < 15.0 * 2 ** 30
    for kernel in ("grouped_expert_product_up", "grouped_expert_product_down"):
        calls = re.findall(rf"%{kernel}[.\d]* = \S+ custom-call\(.*"
                           r"tpu_custom_call", text)
        assert len(calls) == blocks, (kernel, len(calls))
    # the dense causal blocks of the model that has them run the fused
    # step under its own name, ONE kernel a block (the module's among
    # them) and no loop over blocks of queries round it
    dense = re.findall(r"%latent_attention_step[.\d]* = \S+ custom-call\(.*"
                       r"tpu_custom_call", text)
    assert len(dense) == (blocks + 1 if cls == "RouteLMGigaChat" else 0)
    assert not [line for line in text.split("\n")
                if ".mla.full" in line and "while/body" in line]
    inside = [line for line in text.split("\n") if ".moe.experts/" in line]
    whole = re.compile(rf"\[(1,)?({d},{m}|{m},{d})\]")
    sliced = [line for line in inside if "dynamic-slice(" in line
              and whole.search(line.split(" dynamic-slice(")[0])]
    assert not sliced, sliced[0][:300]
    # one sort an expert block, the assignments' argsort: no scatter-add
    # of the combine went the sorted way
    assert sum(" sort(" in line for line in inside) == blocks
    assert not [line for line in inside if " scatter(" in line
                and "indices_are_sorted=true" in line]


# The fifth model's longest step (one route of 14,848 tokens) and its
# largest batched one (two of 7,936) whole, the scan picked as on the
# chip: one scan kernel a block, reading the convolution's output where
# it lies, and the step beside its 7.03 GiB of parameters under 15.0 GiB
# (8.0 when the model came: PERF.md §4).
@pytest.mark.parametrize("routes,length", [(1, 14848), (2, 7936)])
def test_the_hybrid_step_fits_and_holds_one_scan_kernel_a_block(
        v5e, monkeypatch, routes, length):
    import json

    from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1
    from routest_tpu.parallel import ssd

    real = ssd.ssd_path
    monkeypatch.setattr(ssd, "ssd_path", lambda *a, **kw: real(
        *a, **{**kw, "backend": "tpu"}))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "falcon-h1-34b-l0-7.json")) as f:
        model = RouteLMFalconH1.from_config(json.load(f))
    assert model.step_attrs(length) == {"mixers": "ssm=fused,attn=xla"}

    def on(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e)

    compiled = jax.jit(model.apply).lower(
        _on(v5e, jax.eval_shape(model.init, jax.random.PRNGKey(0))),
        on((routes, length)), on((routes,)), on((routes, 4))).compile()
    memory = compiled.memory_analysis()
    assert (memory.temp_size_in_bytes
            + memory.argument_size_in_bytes) < 15.0 * 2 ** 30
    calls = re.findall(r"%ssd_scan_step[.\d]* = \(.*\) custom-call\((.*)",
                       compiled.as_text())
    assert len(calls) == 8
    # x, B and C are three views of one operand, the conv's output
    for args in calls:
        operands = [a.strip() for a in args.split(")")[0].split(",")]
        assert operands[2] == operands[3] == operands[4], operands

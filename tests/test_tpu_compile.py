"""The main path's device programs, compiled for a TPU v5e that is
described and not attached (``jax.experimental.topologies``): what the
chip's compiler refuses fails here, on the CPU, at no chip time.

Interpret mode cannot show these: a Pallas tile that does not fit VMEM,
a slice not aligned to the tiling, a program that does not fit HBM. A
compile that passes is not a chip run — ``chip_smoke.py`` is that.
Skipped where the TPU compiler cannot describe the topology.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _v5e import _on, v5e  # noqa: F401  (v5e: a fixture)
from routest_tpu.models.eta_mlp import EtaMLP
from routest_tpu.ops import fused_eta_forward, pack_eta_params
from routest_tpu.ops.fused_mlp import MAX_TILE, MAX_TILE_F32


@functools.lru_cache(maxsize=None)
def _model(n_q: int):
    """The shipped width (13->256->256->128), seeded weights."""
    model = EtaMLP(quantiles=(0.1, 0.5, 0.9) if n_q else ())
    return model, model.init(jax.random.PRNGKey(0))


# The serving buckets' largest (4,096) and one slice of od-score. The
# f32 variant's HIGHEST-precision matmuls take three times as long to
# compile, so it is held to the larger batch: the per-tile program is
# the same at both, only the grid differs.
@pytest.mark.parametrize("dtype,n_q,batch", [
    (dtype, n_q, batch)
    for dtype in ("bf16", "int8", "f32") for n_q in (0, 3)
    for batch in (4096, 131072) if dtype != "f32" or batch == 131072])
def test_fused_kernel_compiles_for_v5e(v5e, dtype, n_q, batch):
    model, params = _model(n_q)
    packed = _on(v5e, pack_eta_params(model, params, dtype=dtype))
    x = jax.ShapeDtypeStruct((batch, 12), jnp.float32, sharding=v5e)
    compiled = fused_eta_forward.lower(packed, x, n_q=n_q).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpret


def test_xla_scorer_compiles_for_v5e(v5e):
    """The 4,096-row serving bucket as ``EtaService._aot_score`` lowers
    it: jit with the input slab donated."""
    model, params = _model(3)
    x = jax.ShapeDtypeStruct((4096, 12), np.float32, sharding=v5e)
    compiled = jax.jit(model.apply_quantiles, donate_argnums=(1,)).lower(
        _on(v5e, params), x).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_table_scan_program_compiles_for_v5e(v5e, monkeypatch):
    """od-score's pass as ``benchmark/drivers/table_scan.py`` builds it:
    a ``fori_loop`` over 131,072-row slices of a resident (R, 12) table
    through ``jax.jit(model.apply_quantiles)``, the answers written in
    place. On a TPU ``eta_path`` takes the kernel there (here it sees
    the CPU, so the test answers for it). The slice has to reach the
    kernel in the table's own feature-major layout and leave it the same
    way: no array of the loop is wider than the tables' padded 16
    columns, and nothing of the weights' packing runs per slice."""
    import re

    from routest_tpu.models import eta_mlp

    real = eta_mlp.eta_path
    monkeypatch.setattr(eta_mlp, "eta_path",
                        lambda backend, *rest: real("tpu", *rest))
    model, params = _model(3)
    size, n_slices = 131072, 8
    rows = size * n_slices

    def score_pass(params, feats, answers):
        forward = jax.jit(lambda x: model.apply_quantiles(params, x))

        def score_slice(i, answers):
            x = jax.lax.dynamic_slice_in_dim(feats, i * size, size, 0)
            return jax.lax.dynamic_update_slice_in_dim(
                answers, forward(x), i * size, 0)

        return jax.lax.fori_loop(0, n_slices, score_slice, answers)

    text = jax.jit(score_pass, donate_argnums=(2,)).lower(
        _on(v5e, params),
        jax.ShapeDtypeStruct((rows, 12), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((rows, 3), jnp.float32, sharding=v5e),
    ).compile().as_text()
    body = [c for c in text.split("\n\n")
            if "custom-call(" in c and "eta_mlp_fused" in c]
    assert len(body) == 1, "one computation, the loop's body, holds the kernel"
    ops = re.findall(r" = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", body[0])
    for dtype, dims, op in ops:
        dims = [int(d) for d in dims.split(",") if d]
        if size in dims:      # a slice: features, answers, or wider?
            assert max(d for d in dims if d != size) <= 16, (op, dtype, dims)
    # the slice's own copy, the kernel, the in-place write: nothing else
    # of array size (XLA's prefetches of the biases apart)
    work = [op for dtype, dims, op in ops if dims and op not in (
        "get-tuple-element", "bitcast", "parameter", "copy-start",
        "copy-done")]
    assert sorted(work) == ["custom-call", "dynamic-update-slice", "fusion"], work


def test_oversized_tile_is_refused_by_the_kernel_not_by_mosaic(v5e):
    """The kernel unrolls its tile chain by chain, so a larger tile is a
    longer program (22 s of Mosaic at 32,768 rows, three times that in
    f32) for no gain past 8,192; its own bound names the limit before
    the compiler is asked."""
    model, params = _model(3)
    packed = _on(v5e, pack_eta_params(model, params))
    x = jax.ShapeDtypeStruct((131072, 12), jnp.float32, sharding=v5e)
    with pytest.raises(ValueError, match=f"{MAX_TILE}"):
        fused_eta_forward.lower(packed, x, n_q=3, tile=2 * MAX_TILE)
    packed_f32 = _on(v5e, pack_eta_params(model, params, dtype="f32"))
    with pytest.raises(ValueError, match=f"{MAX_TILE_F32}"):
        fused_eta_forward.lower(packed_f32, x, n_q=3, tile=2 * MAX_TILE_F32)


# The route-sequence model's full layers at the cell's widths: the
# longest length class (one route) and the shortest (three routes, the
# chunk cut to 512 keys).
@pytest.mark.parametrize("routes,length", [(1, 26624), (3, 1536)])
def test_selected_attention_step_compiles_for_v5e(v5e, routes, length):
    from routest_tpu.parallel import select

    heads, block, d, d_shared, d_v = 128, 256, 128, 64, 128
    block, chunk = select.block_and_chunk(length, block, 2048)
    assert select.attention_path(heads, block, chunk, d, d_shared, d_v,
                                 jnp.bfloat16, backend="tpu") == "fused"

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(functools.partial(
        select._attend_fused, scale=192 ** -0.5,
        key_tile=select.key_tile_for(chunk))).lower(
        on((block, heads, d)), on((block, heads, d_shared)),
        on((routes, heads, length, d)), on((routes, length, d_shared)),
        on((routes, heads, length, d_v)), on((block, length), jnp.bool_),
        on((), jnp.int32), on((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpret


# Its full layers' radix top-k at a block's (256, L) float32 scores: the
# longest selecting class, one whose columns are not whole 1,024-column
# pieces, and the shortest.
@pytest.mark.parametrize("length", [26624, 4608, 3072])
def test_radix_top_k_step_compiles_for_v5e(v5e, length):
    from routest_tpu.parallel import select

    assert select.topk_path(256, length, jnp.float32, backend="tpu") \
        == "fused"
    compiled = jax.jit(functools.partial(
        select._top_k_fused, top_k=2048)).lower(
        jax.ShapeDtypeStruct((256, length), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((256,), jnp.int32, sharding=v5e)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # Mosaic, not interpret
    assert "radix_top_k_step" in text


# Its sliding layers' window step at the published widths (64 heads, key
# parts of 192 + 64, values of 128, a window of 513): the same two
# classes. The keys come with the length last, as the layer's expansion
# writes them.
@pytest.mark.parametrize("routes,length", [(1, 26624), (3, 1536)])
def test_windowed_attention_step_compiles_for_v5e(v5e, routes, length):
    from routest_tpu.parallel import select

    heads, block, d, d_shared, d_v, window = 64, 512, 192, 64, 128, 513
    assert select.window_path(
        heads, block, select.window_span(length, block, window), d, d_shared,
        d_v, jnp.bfloat16, backend="tpu") == "fused"

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(functools.partial(
        select._window_fused, window=window,
        scale=(d + d_shared) ** -0.5)).lower(
        on((block, heads, d + d_shared)), on((routes, heads, d, length)),
        on((routes, d_shared, length)), on((routes, heads, length, d_v)),
        on((), jnp.int32), on((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # Mosaic, not interpret
    # its own name in the trace: the full layers' roofline sums every
    # operation named ``selected_attention_step…``
    assert "windowed_attention_step" in text
    assert "selected_attention_step" not in text


# The fourth model's dense causal step at the cell's widths (64 heads,
# keys of 128 + 64, values of 192), one kernel a layer over the causal
# triangle: the longest class (one route of 26,112 tokens), a batched
# one (two of 8,960) and the shortest (one of 2,816).
@pytest.mark.parametrize("routes,length", [(1, 26112), (2, 8960), (1, 2816)])
def test_latent_attention_step_compiles_for_v5e(v5e, routes, length):
    from routest_tpu.parallel import latent

    heads, block, d, d_shared, d_v = 64, 256, 128, 64, 192
    assert latent.latent_path(heads, length, block, 1024, d, d_shared, d_v,
                              jnp.bfloat16, backend="tpu") == "fused"
    padded = latent.padded_keys(length, block, 1024)

    def on(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)

    compiled = jax.jit(functools.partial(
        latent._causal_fused, block=block, tile=1024,
        scale=192 ** -0.5)).lower(
        on((routes, heads, length, d)), on((routes, heads, length, d_shared)),
        on((routes, heads, padded, d)), on((routes, padded, d_shared)),
        on((routes, heads, d_v, padded))).compile()
    text = compiled.as_text()
    # one kernel, named for the roofline that sums it
    assert len(re.findall(r"%latent_attention_step[.\d]* = \S+ custom-call\(",
                          text)) == 1
    assert "tpu_custom_call" in text and "selected_attention_step" not in text


# The fifth model's state-space scan at the cell's widths (32 heads of
# 128, two groups of a state of 256, chunks of 128), one kernel a block
# over the conv's lanes: the longest class (one route of 14,848 tokens)
# and the widest (four of 1,536).
@pytest.mark.parametrize("routes,length", [(1, 14848), (4, 1536)])
def test_ssd_scan_step_compiles_for_v5e(v5e, routes, length):
    from routest_tpu.parallel import ssd

    heads, p, groups, n = 32, 128, 2, 256
    assert ssd.ssd_path(heads, p, n, jnp.bfloat16, "tpu", groups=groups,
                        chunk=128) == "fused"

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(functools.partial(
        ssd._scan_fused, heads=heads, groups=groups, state=n,
        chunk=128)).lower(
        on((routes, length, heads * p + 2 * groups * n), jnp.bfloat16),
        on((routes, heads, length)), on((routes, heads, length)),
        on((heads,)), on((heads // ssd.HEAD_TILE,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssd_scan_step[.\d]* = \(.*\) custom-call\(",
                          text)) == 1
    assert "tpu_custom_call" in text

"""Fused ETA-MLP inference kernel (Pallas, TPU).

This kernel runs the whole forward — feature expansion, normalization,
the matmul chain, and the ``pace·dist + overhead`` epilogue — in ONE
``pallas_call`` named ``eta_mlp_fused``, so no activation ever
round-trips HBM.

**Who runs it.** ``models/eta_mlp.eta_path`` chooses it from what the
model can see — a TPU backend, bfloat16 compute, hidden widths that are
multiples of 128, a batch that is a whole number of tiles and at least
``FUSED_MIN_ROWS`` — inside ``EtaMLP.apply`` / ``apply_quantiles``;
everything else (the CPU, a float32 policy, toy widths, small or
symbolic batches) runs the XLA body, which is also the kernel's oracle
(``tests/test_ops_fused.py``) and the only differentiable form: the
kernel has no VJP and ``jax.grad`` through it raises, so training,
evaluation and export call ``apply_xla`` / ``apply_quantiles_xla`` by
name. ``serve/ml_service.py``'s ``ROUTEST_FUSED=1`` still forces the
kernel at every batch on one TPU device, in any dtype variant.

**Bandwidth accounting** (physical, not logical). The core entry
``fused_eta_forward_t`` takes the features as ``(12, B)`` f32 — rows on
lanes — and returns ``(n_q, B)``: blocks ``(12, tile)`` in and
``(n_q, tile)`` out, both lane-dense, 48 + 4·n_q = 60 B a row through
HBM (64 + 16 with the sublane padding to 16 and 4 rows). That is the
layout a resident table has anyway: XLA stores ``f32[R, 12]`` on a TPU
feature-major, padded 12→16, so for a slice of such a table ``x.T`` and
the result's ``.T`` are bitcasts and the slice reaches the kernel
through its own 6.3 MB copy and nothing else. The XLA body moves 2.5-3
KB a row at that size (bf16 ``[131072, 256]`` activations handed from
fusion to fusion at 610-650 GB/s: ledger, PR 32). The row-major form
this file had until PR 33 — ``(tile, 12)`` blocks in, ``(tile, n_q)``
out — moved 512 B a row each way in HBM's (8, 128) tiling and would
have cost a re-tiling copy of every slice; ``fused_eta_forward`` keeps
that signature as a wrapper (``x.T`` → core → ``.T``).

**History of its readings on a v5e.** PR 21, row-major I/O, 128-lane
padding of the expansion and the heads, one dependent chain a
2,048-row tile: 164.66 M preds/s against XLA's 205.96 M at 131,072
rows (single readings, no ledger line) — a loss, and XLA served. PR 33,
this form, inside od-score's own loop over 131,072-row slices: 1.93 ns
a row against the XLA body's 5.24, the kernel alone 1.61 (its nine MXU
passes a row take 1.5 at peak); the cell 197.0 → 555.6 M rows/s
(builder's runs; ``PERF.md`` §5-§6 has the tile sweep, the split by
phase and the crossover by slice size: the kernel wins from 4,096 rows,
× 2.2-2.7).

Design notes:

- the batch is tiled over the grid on the LANE axis; a grid step works
  its tile as independent chains of ``SUB`` rows, ``CHAINS`` of them in
  lockstep, so that one chain's gelu (VPU) can be scheduled against the
  next one's product (MXU);
- feature expansion builds a ``(48, rows)`` f32 block on sublanes in
  ``EtaMLP._expand``'s own order: the categorical rows copied, the
  weekday/hour one-hots by sublane iota against the broadcast row (no
  gathers, no relayouts), then the three scalar bases; the normalizer
  is applied in f32 before the cast, exactly as ``_expand`` does —
  nothing is folded into the weights, so the first product's bf16
  operands are the XLA path's;
- the first product contracts that block's sublane axis
  (``featsᵀ · w0``, a transposed-left ``dot_general``); from there the
  activations are row-major ``(rows, width)`` against stationary
  weights, bf16 operands, f32 accumulation, f32 bias, tanh-form gelu in
  f32 (``jax.nn.gelu``'s default), cast to bf16 for the next product;
- the heads come back feature-major (``w_lastᵀ · hᵀ``, the A·Bᵀ form),
  so softplus runs over 8 sublanes and not 128 lanes, the cumulative
  sums are row adds in f32, and ``pace · dist + overhead`` meets
  ``dist`` in the layout it arrived in;
- ``pack_eta_params`` is ``jnp`` and, at widths that tile, casts and
  bitcasts only: called inside a traced loop over slices, XLA hoists
  all of it out of the loop (``tests/test_tpu_compile.py`` holds that).

Compute-dtype variants (``RTPU_KERNEL_DTYPE``, or the ``dtype=`` arg of
``pack_eta_params``): ``bf16`` (default — MXU-native matmuls),
``f32`` (full-precision matmuls, parity/debug), and ``int8`` —
weights quantized per output column to int8 at pack time (4× less
weight HBM traffic) and dequantized in VMEM to bf16 before the dot.
EVERY variant accumulates in float32 (``preferred_element_type``);
activations and the epilogue stay f32. ``eta_path`` only ever chooses
bf16: int8 is a control that fails the benchmark's limit.

The quantile epilogue is non-crossing by construction regardless of
dtype, since the cumsum of softplus-positive increments is monotone
whatever error quantization put into the increments themselves.

Semantics are identical to ``EtaMLP.apply`` on the 12-feature ABI
(SURVEY.md Appendix B, ``Flaskr/ml.py:35-48``): unknown categories hit
zero weight rows, distance is clamped non-negative, two softplus heads
combine as ``eta = pace · distance + overhead``.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from routest_tpu.data.features import N_FEATURES

# Rows of one grid step, rows of one independent chain inside it, and how
# many chains advance in lockstep (``_kernel``). From the sweep on a v5e
# inside od-score's own loop (PERF.md §5, PR 33): a chain alone reads
# 2.62 ns a row at 256 rows and two in lockstep 2.13; from 512 rows x 4
# everything between 4,096 and 16,384 rows a step lies within 3% (1.90-
# 1.99), the products alone being 1.77. Fixed here; no option reads them.
TILE = 8192
SUB = 512
CHAINS = 4
# The tile is unrolled chain by chain, so a larger one is only a longer
# program (Mosaic: 4 s at 8,192 rows, 22 s at 32,768, three times that
# in f32, whose multi-pass products unroll longer): refused by name
# before the compiler is asked (tests/test_tpu_compile.py).
MAX_TILE = 16384
MAX_TILE_F32 = 4096

# The in-kernel expanded feature block is (K_ROWS, rows): the 42 bases
# in EtaMLP._expand's own order (8 categorical, 7 weekday, 24 hour,
# dist_n, log1p(dist), age_n) on sublanes, then zero rows up to a
# multiple of the bf16 sublane tile. Layer 0's weights need no
# re-rowing; the kernel appends their zero rows.
K_ROWS = 48
_N_CAT, _N_WD, _N_HR = 8, 7, 24
_ROW_WD = _N_CAT
_ROW_HR = _ROW_WD + _N_WD
_ROW_DIST = _ROW_HR + _N_HR      # then log1p(dist), age
_N_NORM = 4     # packed["scalars"]: the normalizer, then the head biases

Packed = Dict[str, List[jax.Array]]

# Compute-dtype variants (RTPU_KERNEL_DTYPE / pack_eta_params(dtype=)).
_DTYPE_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "f32": "float32", "fp32": "float32", "float32": "float32",
    "int8": "int8",
}


def resolve_kernel_dtype(model=None, dtype=None) -> str:
    """Canonical kernel compute-dtype name: explicit ``dtype`` arg, then
    ``RTPU_KERNEL_DTYPE``, then the model policy's compute dtype. An
    unknown name raises — kernel selection must stay LOUD (the serving
    layer logs ``fused_kernel_unavailable`` and falls back to XLA), not
    silently serve a different precision than the operator asked for."""
    raw = dtype or os.environ.get("RTPU_KERNEL_DTYPE")
    if not raw:
        if model is not None:
            raw = np.dtype(model.policy.compute_dtype).name
        else:
            raw = "bfloat16"
    name = _DTYPE_ALIASES.get(str(raw).strip().lower())
    if name is None:
        raise ValueError(
            f"RTPU_KERNEL_DTYPE={raw!r} is not a kernel variant "
            f"(choose from bf16 / f32 / int8)")
    return name


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_to(a: jax.Array, rows: int, cols: int) -> jax.Array:
    if a.shape == (rows, cols):
        return a    # no op in the traced program where the widths tile
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def pack_eta_params(model, params, dtype: str = None) -> Packed:
    """EtaMLP params → kernel-layout weights (a jit-friendly pytree).

    Written with ``jnp`` so that ``params`` may be tracers
    (``EtaMLP.apply_quantiles`` packs inside the traced function). Where
    every hidden width is a multiple of 128 the pack is casts and
    bitcasts only, which XLA hoists out of a loop over slices; other
    widths pad up with zero rows/cols, exact no-ops through gelu.
    Weights keep ``(d_in, d_out)``; layer 0 keeps its 42 rows (the
    kernel appends the zero rows of its feature block). ``"b"`` holds
    the hidden layers' biases as ``(1, d_out)``; ``"scalars"`` the
    normalizer's four numbers (distance and age mean / std: the kernel
    applies them in f32 before the cast, as ``_expand`` does) followed
    by the head biases.

    ``dtype`` selects the compute variant (``resolve_kernel_dtype``):
    bf16/f32 store the weights in that dtype; int8 stores them quantized
    per OUTPUT column (symmetric, scale = max|col|/127 — per-column
    because a whole-layer scale lets one outlier column crush the
    resolution of every other) with f32 scales under ``"scale"``.
    Biases are always f32 — they add into the f32 accumulator.
    """
    layers = params["layers"]
    norm = params["norm"]
    variant = resolve_kernel_dtype(model, dtype)
    compute = jnp.bfloat16 if variant == "bfloat16" else jnp.float32
    last = len(layers) - 1

    ws: List[jax.Array] = []
    bs: List[jax.Array] = []
    scales: List[jax.Array] = []
    for i, layer in enumerate(layers):
        w = jnp.asarray(layer["w"], jnp.float32)
        b = jnp.asarray(layer["b"], jnp.float32)
        d_in, d_out = w.shape
        wp = _pad_to(w, _round_up(d_in, 128) if i else d_in,
                     d_out if i == last else _round_up(d_out, 128))
        if i < last:
            bs.append(_pad_to(b[None, :], 1, wp.shape[1]))
        if variant == "int8":
            s = jnp.abs(wp).max(axis=0, keepdims=True) / 127.0
            s = jnp.where(s < 1e-12, 1.0, s)  # all-zero (padding) columns
            ws.append(jnp.rint(wp / s).astype(jnp.int8))
            scales.append(s)
        else:
            ws.append(wp.astype(compute))
    mean = jnp.asarray(norm["mean"], jnp.float32)
    std = jnp.asarray(norm["std"], jnp.float32)
    packed: Packed = {
        "w": ws, "b": bs,
        "scalars": jnp.concatenate(
            [jnp.stack([mean[10], std[10], mean[11], std[11]]),
             jnp.asarray(layers[-1]["b"], jnp.float32)])}
    if variant == "int8":
        packed["scale"] = scales
    return packed


def _expand_t(x, scalars_ref):
    """(12, rows) f32 ABI features, feature-major → the (K_ROWS, rows)
    f32 block of ``EtaMLP._expand``'s bases and the clamped distance
    (1, rows). One-hots by sublane iota against the broadcast weekday /
    hour row (an out-of-range value matches no row, as ``one_hot``);
    the normalizer in f32, as ``_expand`` applies it."""
    rows = x.shape[1]
    wd = x[8:9].astype(jnp.int32)
    hr = x[9:10].astype(jnp.int32)
    dist = jnp.maximum(x[10:11], 0.0)
    dist_n = (dist - scalars_ref[0]) / scalars_ref[1]
    age_n = (x[11:12] - scalars_ref[2]) / scalars_ref[3]
    wd_row = jnp.where((wd >= 0) & (wd < _N_WD), wd + _ROW_WD, -1)
    hr_row = jnp.where((hr >= 0) & (hr < _N_HR), hr + _ROW_HR, -1)
    # the three scalar bases live in the last two 8-row groups
    cut = _ROW_DIST // 8 * 8

    def one_hots(lo, hi):
        r = lo + jax.lax.broadcasted_iota(jnp.int32, (hi - lo, rows), 0)
        return r, ((r == wd_row) | (r == hr_row)).astype(jnp.float32)

    _, one_hot = one_hots(_N_CAT, cut)
    r, tail = one_hots(cut, K_ROWS)
    tail = jnp.where(r == _ROW_DIST, dist_n,
                     jnp.where(r == _ROW_DIST + 1, jnp.log1p(dist),
                               jnp.where(r == _ROW_DIST + 2, age_n, tail)))
    feats = jnp.concatenate([x[0:_N_CAT], one_hot, tail], axis=0)
    return feats, dist


def _heads_t(out_t, dist, n_q: int):
    """(head rows, rows) raw heads, feature-major → (n_q | 1, rows) ETA
    minutes: softplus over the 8 head sublanes, the two cumulative sums
    as row adds (non-crossing by construction in every dtype variant:
    sums of softplus-positive increments)."""
    sp = jax.nn.softplus(out_t)
    n = max(n_q, 1)     # the point model: one pace head, one overhead
    pace, over = sp[0:1], sp[n:n + 1]
    etas = [pace * dist + over]
    for q in range(1, n):
        pace = pace + sp[q:q + 1]
        over = over + sp[n + q:n + q + 1]
        etas.append(pace * dist + over)
    return jnp.concatenate(etas, axis=0)


def _kernel(n_layers: int, compute, n_q: int, quant: bool, sub: int,
            scalars_ref, x_ref, *refs) -> None:
    """One grid step: a (12, tile) block of feature-major rows → the
    (n_q | 1, tile) block of answers, every intermediate in VMEM.

    refs = w_0, b_0[, s_0], …, w_{n-1}[, s_{n-1}], out_ref (the head
    layer has no bias ref: its biases are in ``scalars_ref``; ``quant``
    adds the int8 scales, and weights dequantize in VMEM to the compute
    dtype, so HBM only ever moves int8 weights).

    The tile is worked as independent chains of ``sub`` rows, ``CHAINS``
    of them in lockstep: expand → (product → f32 bias → f32 gelu → cast)
    per hidden layer → heads → epilogue. One chain's gelu (VPU) has no
    dependency on the next one's product (MXU), so the scheduler can
    overlap them. The first product contracts the feature-major block's
    sublane axis (``featsᵀ · w0``); from there activations are row-major
    ``(sub, width)`` against stationary weights; the heads come back
    feature-major (``w_lastᵀ · hᵀ``, the A·Bᵀ form) so that softplus,
    the cumulative sums and ``pace · dist + overhead`` run on 8
    sublanes and meet ``dist`` in the layout it arrived in.
    """
    *refs, out_ref = refs
    tile = x_ref.shape[1]
    # The MXU multiplies float32 operands in bfloat16 passes at default
    # precision, so the f32 variant asks for HIGHEST on every dot — or
    # its "full-precision" answers carry bf16-class error on the chip
    # (the interpreter on a CPU never showed this).
    precision = (jax.lax.Precision.HIGHEST if compute == jnp.float32
                 else None)
    refs = iter(refs)
    ws, bs = [], []
    for i in range(n_layers):
        w = next(refs)[:]
        if i < n_layers - 1:
            bs.append(next(refs)[:])
        if quant:
            # Dequantize in VMEM: int8 weights stream from HBM at a
            # quarter of the f32 bill. The dot still runs in the compute
            # dtype with f32 accumulation.
            w = w.astype(jnp.float32) * next(refs)[:]
        if i == 0:   # the feature block's zero rows, appended in f32
            w = w.astype(jnp.float32)
            w = jnp.concatenate(
                [w, jnp.zeros((K_ROWS - w.shape[0], w.shape[1]), w.dtype)], 0)
        ws.append(w.astype(compute))
    # the head biases as a column, from SMEM scalars
    n_heads = ws[-1].shape[1]
    r = jax.lax.broadcasted_iota(jnp.int32, (n_heads, 1), 0)
    b_heads = jnp.zeros((n_heads, 1), jnp.float32)
    for k in range(n_heads):
        b_heads = jnp.where(r == k, scalars_ref[_N_NORM + k], b_heads)

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                                   preferred_element_type=jnp.float32)

    n_sub = tile // sub
    for g in range(0, n_sub, CHAINS):
        cols = [slice(c * sub, (c + 1) * sub)
                for c in range(g, min(g + CHAINS, n_sub))]
        expanded = [_expand_t(x_ref[:, s], scalars_ref) for s in cols]
        acts = [f.astype(compute) for f, _ in expanded]
        for i in range(n_layers - 1):
            # layer 0 contracts the feature-major block's sublane axis
            outs = [dot(a, ws[i], ((1 if i else 0,), (0,))) for a in acts]
            acts = [jax.nn.gelu(o + bs[i]).astype(compute) for o in outs]
        act_axis = 0 if n_layers == 1 else 1
        for s, a, (_, dist) in zip(cols, acts, expanded):
            out_t = dot(ws[-1], a, ((0,), (act_axis,))) + b_heads
            out_ref[:, s] = _heads_t(out_t, dist, n_q)


def _no_derivative(*_):
    raise TypeError(
        "eta_mlp_fused is inference-only (no VJP): differentiate "
        "EtaMLP.apply_xla / apply_quantiles_xla instead")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_eta_forward_t(packed: Packed, x_t: jax.Array, n_q: int = 0,
                        tile: Optional[int] = None, interpret: bool = False
                        ) -> jax.Array:
    """The kernel's core entry, in the table's own layout: ``x_t``
    (12, B) f32 ABI features with rows on lanes → (n_q, B) f32 minutes
    per quantile ((1, B) for the point model). 48 + 4·n_q bytes a row
    through HBM, both blocks lane-dense. ``B`` pads up to the tile,
    which is ``TILE`` (or the variant's largest) unless given."""
    ws, bs = packed["w"], packed["b"]
    scales = packed.get("scale")
    quant = scales is not None
    # int8 variant: dequantized matmuls run in bf16 (MXU-native);
    # otherwise the packed weight dtype IS the compute dtype.
    compute = jnp.bfloat16 if quant else ws[0].dtype
    max_tile = MAX_TILE_F32 if compute == jnp.float32 else MAX_TILE
    tile = min(TILE, max_tile) if tile is None else tile
    if not 0 < tile <= max_tile or tile % 128:
        raise ValueError(
            f"fused_eta_forward: tile={tile} must be a multiple of 128 in "
            f"[128, {max_tile}] for {jnp.dtype(compute).name} compute "
            f"(a larger tile is only a longer program)")
    n_out = n_q if n_q else 1
    b_rows = x_t.shape[1]
    if b_rows == 0:
        # A zero-row batch would make the grid degenerate.
        return jnp.zeros((n_out, 0), jnp.float32)
    tile = min(tile, _round_up(b_rows, 128))
    sub = math.gcd(tile, SUB)
    b_pad = _round_up(b_rows, tile)
    x_t = x_t.astype(jnp.float32)
    if b_pad != b_rows:   # never where the batch divides the tile
        x_t = jnp.pad(x_t, ((0, 0), (0, b_pad - b_rows)))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0, 0),
                            memory_space=pltpu.VMEM)

    operands = []
    for i, w in enumerate(ws):
        operands.append(w)
        if i < len(bs):
            operands.append(bs[i])
        if quant:
            operands.append(scales[i])
    n_layers = len(ws)
    flops = 2 * b_pad * sum(w.shape[0] * w.shape[1] for w in ws)
    out = pl.pallas_call(
        functools.partial(_kernel, n_layers, compute, n_q, quant, sub),
        name="eta_mlp_fused",
        grid=(b_pad // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((N_FEATURES, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM)]
        + [whole(a) for a in operands],
        out_specs=pl.BlockSpec((n_out, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_out, b_pad), jnp.float32),
        # Mosaic's default scoped VMEM (16 MiB) holds four 512-row
        # chains with room: no vmem_limit_bytes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            # what the kernel moves: the two lane-dense tables and one
            # stream of the weights in their STORED dtype
            bytes_accessed=4 * b_pad * (N_FEATURES + n_out) + sum(
                a.size * a.dtype.itemsize for a in operands),
            # gelu per hidden unit + softplus over the head sublanes
            transcendentals=b_pad * sum(w.shape[1] for w in ws),
        ),
        interpret=interpret,
    )(packed["scalars"], x_t, *operands)
    return out[:, :b_rows]


fused_eta_forward_t.defvjp(_no_derivative, _no_derivative)


@functools.partial(jax.jit, static_argnames=("n_q", "tile", "interpret"))
def fused_eta_forward(packed: Packed, x: jax.Array, *, n_q: int = 0,
                      tile: Optional[int] = None,
                      interpret: bool = False) -> jax.Array:
    """(B, 12) ABI features → (B,) ETA minutes — or (B, n_q) per-quantile
    minutes for a quantile model — via the fused kernel: the row-major
    wrapper of :func:`fused_eta_forward_t` (``x.T`` → core → ``.T``).

    ``interpret=True`` runs the Pallas interpreter (any backend) — used by
    the CPU test suite; compiled mode requires a TPU. The compute
    variant (bf16 / f32 / int8-weight, see ``pack_eta_params``) is
    carried by the packed pytree itself.
    """
    out = fused_eta_forward_t(packed, x.T, n_q, tile, interpret).T
    return out if n_q else out[:, 0]

"""Fused ETA-MLP inference kernel (Pallas, TPU).

This kernel runs the whole forward — feature expansion, normalization,
the matmul chain, and the ``pace·dist + overhead`` epilogue — in ONE
``pallas_call``, so no activation ever round-trips HBM.

**XLA serves; the kernel is opt-in.** SURVEY.md §7.1's rule is "a
Pallas kernel is justified only if XLA fails to fuse — benchmark
first". No benchmark cell times this kernel, so ``serve/ml_service.py``
serves the XLA path unless ``ROUTEST_FUSED=1`` forces the kernel on a
TPU; its one chip reading is a loss at 131,072 rows (``PERF.md`` §6,
PR 21).

Bandwidth accounting (physical, not logical): TPU HBM stores f32
arrays in (8, 128) tiles with the minor dim padded to 128 lanes, so
the (B, 12) input and (B, 1|n_q) output each stream ~512 B/row
REGARDLESS of their logical width — narrowing the blocks does not
change that floor (the XLA path reads the identical padded input).
What the narrow layout does buy: the old version's two extra
whole-batch passes are gone (an explicit zeros+set pad to 128 logical
lanes — one write + one re-read — and a 128-lane output broadcast),
and when the batch divides the tile the input pad-copy is skipped
entirely, so the kernel's HBM bill is one input read + one output
write. The kernel's structural edge over XLA remains keeping every
inter-layer activation in VMEM (XLA spills ~3 KB/row of bf16
activations for this trunk at large batches); its structural
overheads remain the 42→128 MXU row padding (~35% extra matmul FLOPs,
irrelevant while bandwidth-bound) and Mosaic serializing the per-tile
VPU expansion against the MXU chain, which XLA overlaps across tiles.

Design notes:

- the batch is tiled over the grid; per tile, every intermediate lives
  in VMEM and only the (tile, 12) input block and (tile, 1|n_q) output
  block touch HBM (one lane-padded stream each way, no extra passes);
- feature expansion is pure VPU arithmetic — lane-index comparisons build
  the weekday/hour one-hots in place (no gathers, no lane relayouts);
- the train-time normalizer is an affine map feeding a linear layer, so
  ``pack_eta_params`` folds it into the layer-0 weights/bias at pack time:
  zero runtime cost and serving can never skew from training normalization
  (the same guarantee ``EtaMLP._expand`` enforces with in-pytree stats);
- matmuls run on the MXU in the model policy's compute dtype (bfloat16)
  with float32 accumulation.

Compute-dtype variants (``RTPU_KERNEL_DTYPE``, or the ``dtype=`` arg of
``pack_eta_params``): ``bf16`` (default — MXU-native matmuls),
``f32`` (full-precision matmuls, parity/debug), and ``int8`` —
weights quantized per output column to int8 at pack time (4× less
weight HBM traffic; min int8 tile is (32, 128) and every padded weight
dim is a multiple of 128, so the layout is tile-legal) and dequantized
in VMEM to bf16 before the dot. EVERY variant accumulates in float32
(``preferred_element_type``); activations and the epilogue stay f32.

The quantile epilogue is fused in-kernel: the 2·Q raw heads go through
softplus once, then ONE constant-matrix dot computes both cumulative
sums (the same block-triangular trick as ``eta_mlp.quantile_heads``) —
non-crossing by construction regardless of dtype, since the cumsum of
softplus-positive increments is monotone whatever error quantization
put into the increments themselves.

Semantics are identical to ``EtaMLP.apply`` on the 12-feature ABI
(SURVEY.md Appendix B, ``Flaskr/ml.py:35-48``): unknown categories hit
zero weight rows, distance is clamped non-negative, two softplus heads
combine as ``eta = pace · distance + overhead``. Parity is enforced by
``tests/test_ops_fused.py`` against the XLA path, which remains the
reference implementation and the fallback wherever Pallas is unavailable
(``serve/ml_service.py`` degrades automatically).

Inference-only by design: training uses the differentiable XLA path, so
no custom VJP is defined here.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from routest_tpu.data.features import N_FEATURES

# Largest batch tile the kernel accepts. Every intermediate of a tile
# lives in VMEM (several (tile, 256) f32/bf16 activations plus the
# lane-padded input and output blocks): for a TPU v5e, 4096 rows compile
# in the bf16 and int8 variants and 8192 is refused by Mosaic with
# ``RESOURCE_EXHAUSTED ... memory space vmem``; the f32 variant's
# multi-pass matmuls need more and stop at 2048
# (tests/test_tpu_compile.py holds both sides). Checked here so no
# caller can ask for a tile the compiler refuses.
MAX_TILE = 4096
MAX_TILE_F32 = 2048

# Lane layout of the in-kernel expanded feature vector (width = LANES).
# Chosen so every region starts where VPU masks are cheap; the 32-wide
# weekday slot (7 real + 25 zero weight rows) keeps hour at a lane
# boundary. Order differs from EtaMLP._expand's concat — pack_eta_params
# permutes the trained layer-0 rows to match.
LANES = 128
_CAT = (0, 8)        # weather(4) + traffic(4), copied straight from x
_WD = (8, 40)        # weekday one-hot, lane 8+w
_HR = (40, 64)       # hour one-hot, lane 40+h
_DIST = 64           # raw distance_km (normalizer folded into weights)
_LOGD = 65           # log1p(distance_km)
_AGE = 66            # raw driver_age (normalizer folded into weights)

# EtaMLP._expand's row order in the trained layer-0 weight matrix.
_ROW_CAT = (0, 8)
_ROW_WD = (8, 15)
_ROW_HR = (15, 39)
_ROW_DIST, _ROW_LOGD, _ROW_AGE = 39, 40, 41

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


def pack_eta_params(model, params, dtype: str = None) -> Packed:
    """EtaMLP params → kernel-layout weights (a jit-friendly pytree).

    Layer 0 is re-rowed to the kernel's lane layout with the normalizer
    folded in: ``(d - mean)/std`` feeding a linear layer is the same as
    scaling the weight row by ``1/std`` and shifting the bias by
    ``-mean/std · row``. All dims pad up to multiples of 128 (MXU tiles);
    padding rows/cols are zero so they are exact no-ops through gelu.

    ``dtype`` selects the compute variant (``resolve_kernel_dtype``):
    bf16/f32 store the weights in that dtype; int8 stores them quantized
    per OUTPUT column (symmetric, scale = max|col|/127 — per-column
    because a whole-layer scale lets one outlier column crush the
    resolution of every other) with f32 scales under ``"scale"``.
    Biases are always f32 — they add into the f32 accumulator.
    """
    layers = params["layers"]
    norm = params["norm"]
    mean = np.asarray(norm["mean"], np.float32)
    std = np.asarray(norm["std"], np.float32)
    variant = resolve_kernel_dtype(model, dtype)
    compute = jnp.bfloat16 if variant == "bfloat16" else jnp.float32

    ws: List[jax.Array] = []
    bs: List[jax.Array] = []
    scales: List[jax.Array] = []
    for i, layer in enumerate(layers):
        w = np.asarray(layer["w"], np.float32)
        b = np.asarray(layer["b"], np.float32)
        d_in, d_out = w.shape
        if i == 0:
            wp = np.zeros((LANES, _round_up(d_out, 128)), np.float32)
            wp[_CAT[0]:_CAT[1], :d_out] = w[_ROW_CAT[0]:_ROW_CAT[1]]
            wp[_WD[0]:_WD[0] + (_ROW_WD[1] - _ROW_WD[0]), :d_out] = \
                w[_ROW_WD[0]:_ROW_WD[1]]
            wp[_HR[0]:_HR[0] + (_ROW_HR[1] - _ROW_HR[0]), :d_out] = \
                w[_ROW_HR[0]:_ROW_HR[1]]
            wp[_DIST, :d_out] = w[_ROW_DIST] / std[10]
            wp[_LOGD, :d_out] = w[_ROW_LOGD]
            wp[_AGE, :d_out] = w[_ROW_AGE] / std[11]
            bp = np.zeros((1, wp.shape[1]), np.float32)
            bp[0, :d_out] = (b
                             - (mean[10] / std[10]) * w[_ROW_DIST]
                             - (mean[11] / std[11]) * w[_ROW_AGE])
        else:
            wp = np.zeros((_round_up(d_in, 128), _round_up(d_out, 128)), np.float32)
            wp[:d_in, :d_out] = w
            bp = np.zeros((1, wp.shape[1]), np.float32)
            bp[0, :d_out] = b
        if variant == "int8":
            s = np.abs(wp).max(axis=0) / 127.0
            s[s < 1e-12] = 1.0  # all-zero (padding) columns: exact zeros
            ws.append(jnp.asarray(np.rint(wp / s), jnp.int8))
            scales.append(jnp.asarray(s[None, :], jnp.float32))
        else:
            ws.append(jnp.asarray(wp, compute))
        bs.append(jnp.asarray(bp, jnp.float32))
    packed: Packed = {"w": ws, "b": bs}
    if variant == "int8":
        packed["scale"] = scales
    return packed


def _kernel(n_layers: int, compute, n_q: int, quant: bool,
            x_ref, *refs) -> None:
    """One batch tile: expand → matmul chain → eta, all in VMEM.

    refs = w_0, b_0[, s_0], …, w_{n-1}, b_{n-1}[, s_{n-1}], out_ref
    (``quant`` adds the per-column int8 scales; weights dequantize in
    VMEM to the compute dtype, so HBM only ever moves int8 weights).
    ``n_q == 0`` is the 2-head point model; ``n_q > 0`` fuses the
    quantile epilogue too (``EtaMLP.apply_quantiles``): one softplus
    over the padded head lanes, then ONE constant-matrix dot per head
    family computes the cumulative sums (MXU-shaped — K is the padded
    128-lane head dim) ⇒ non-crossing quantiles with no per-head
    unrolled lane slicing and no extra HBM pass for the band.

    The tile arrives in its natural (tile, 12) ABI width and leaves as
    (tile, 1) / (tile, n_q); minor-dim lane padding means HBM still
    moves ~512 B/row each way (see the module docstring's accounting),
    but the earlier version's extra pad/broadcast passes are gone and
    every intermediate stays in VMEM. The widen-to-128 below is a
    VMEM-only lane relayout.
    """
    out_ref = refs[-1]
    x = x_ref[:]  # (tile, 12) f32: the raw ABI features
    tile = x.shape[0]

    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
    wd = x[:, 8:9].astype(jnp.int32)
    hr = x[:, 9:10].astype(jnp.int32)
    dist = jnp.maximum(x[:, 10:11], 0.0)
    age = x[:, 11:12]

    # Widen to the kernel lane layout (VMEM-only), then build the
    # expanded features via lane masks — pure VPU, no gathers. Lanes
    # 12:128 of xw are zero, so the lane<8 select keeps the one-hots.
    xw = jnp.concatenate(
        [x, jnp.zeros((tile, LANES - x.shape[1]), x.dtype)], axis=1)
    xfull = (
        jnp.where(lane < _CAT[1], xw, 0.0)
        + ((lane >= _WD[0]) & (lane < _WD[1])
           & (lane - _WD[0] == wd)).astype(jnp.float32)
        + ((lane >= _HR[0]) & (lane < _HR[1])
           & (lane - _HR[0] == hr)).astype(jnp.float32)
        + jnp.where(lane == _DIST, dist, 0.0)
        + jnp.where(lane == _LOGD, jnp.log1p(dist), 0.0)
        + jnp.where(lane == _AGE, age, 0.0)
    )

    h = xfull.astype(compute)
    # The MXU multiplies float32 operands in bfloat16 passes at default
    # precision, so the f32 variant asks for HIGHEST on every dot — or
    # its "full-precision" answers carry bf16-class error on the chip
    # (the interpreter on a CPU never showed this).
    precision = (jax.lax.Precision.HIGHEST if compute == jnp.float32
                 else None)
    stride = 3 if quant else 2
    for i in range(n_layers):
        w_ref, b_ref = refs[stride * i], refs[stride * i + 1]
        if quant:
            # Dequantize in VMEM: int8 weights stream from HBM at a
            # quarter of the f32 bill; per-column f32 scales broadcast
            # over the rows. The dot still runs in the compute dtype
            # with f32 accumulation.
            s_ref = refs[stride * i + 2]
            w = (w_ref[:].astype(jnp.float32) * s_ref[:]).astype(compute)
        else:
            w = w_ref[:]
        out = jnp.dot(h, w, preferred_element_type=jnp.float32,
                      precision=precision)
        out = out + b_ref[:]
        if i < n_layers - 1:
            h = jax.nn.gelu(out).astype(compute)
    if n_q == 0:
        pace = jax.nn.softplus(out[:, 0:1])
        overhead = jax.nn.softplus(out[:, 1:2])
        out_ref[:] = pace * dist + overhead
    else:
        # Fused epilogue, MXU form: softplus over the whole padded head
        # block (the VPU processes 128 lanes per cycle either way), then
        # one triangular-matrix dot per head family computes the
        # cumulative sums. The triangular selectors are built in-kernel
        # from iota (Pallas kernels may not capture array constants);
        # rows ≥ 2·n_q are zero, so the softplus(0) on padding lanes
        # never contributes.
        d_head = out.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (d_head, n_q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (d_head, n_q), 1)
        pace_m = ((row <= col) & (row < n_q)).astype(jnp.float32)
        over_m = ((row - n_q <= col) & (row >= n_q)
                  & (row < 2 * n_q)).astype(jnp.float32)
        sp = jax.nn.softplus(out)
        pace = jnp.dot(sp, pace_m, preferred_element_type=jnp.float32,
                       precision=precision)
        overhead = jnp.dot(sp, over_m, preferred_element_type=jnp.float32,
                           precision=precision)
        out_ref[:] = pace * dist + overhead


@functools.partial(jax.jit, static_argnames=("n_q", "tile", "interpret"))
def fused_eta_forward(packed: Packed, x: jax.Array, *, n_q: int = 0,
                      tile: int = 2048, interpret: bool = False) -> jax.Array:
    """(B, 12) ABI features → (B,) ETA minutes — or (B, n_q) per-quantile
    minutes for a quantile model — via the fused kernel.

    ``interpret=True`` runs the Pallas interpreter (any backend) — used by
    the CPU test suite; compiled mode requires a TPU. The compute
    variant (bf16 / f32 / int8-weight, see ``pack_eta_params``) is
    carried by the packed pytree itself.
    """
    ws, bs = packed["w"], packed["b"]
    scales = packed.get("scale")
    quant = scales is not None
    # int8 variant: dequantized matmuls run in bf16 (MXU-native);
    # otherwise the packed weight dtype IS the compute dtype.
    compute = jnp.bfloat16 if quant else ws[0].dtype
    max_tile = MAX_TILE_F32 if compute == jnp.float32 else MAX_TILE
    if not 0 < tile <= max_tile or tile % 8:
        raise ValueError(
            f"fused_eta_forward: tile={tile} must be a multiple of 8 in "
            f"[8, {max_tile}] for {jnp.dtype(compute).name} compute "
            f"(larger tiles do not fit VMEM)")
    n_layers = len(ws)
    b_rows = x.shape[0]
    if b_rows == 0:
        # A zero-row batch would make the tile (and grid) degenerate —
        # _round_up(0, 0) divides by zero. Nothing to score; match the
        # XLA path's rank ((B,) point, (B, n_q) quantile).
        return jnp.zeros((0, n_q) if n_q else (0,), jnp.float32)
    tile = min(tile, _round_up(b_rows, 8))
    b_pad = _round_up(b_rows, tile)

    # Row padding only, and none at all when the batch divides the tile
    # (serving buckets do): the kernel then reads
    # the caller's buffer directly instead of paying a pad-copy pass.
    if b_pad == b_rows:
        xp = x.astype(jnp.float32)
    else:
        xp = jnp.zeros((b_pad, N_FEATURES), jnp.float32)
        xp = xp.at[:b_rows].set(x.astype(jnp.float32))

    wb_specs = []
    operands = []
    for i, (w, b) in enumerate(zip(ws, bs)):
        wb_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        wb_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        operands.extend((w, b))
        if quant:
            s = scales[i]
            wb_specs.append(pl.BlockSpec(s.shape, lambda i: (0, 0),
                                         memory_space=pltpu.VMEM))
            operands.append(s)

    n_out = n_q if n_q else 1
    flops = 2 * b_pad * sum(w.shape[0] * w.shape[1] for w in ws)
    if n_q:
        # Fused epilogue: two (d_head, n_q) constant dots + the
        # multiply-add per quantile.
        flops += 2 * b_pad * (2 * ws[-1].shape[1] * n_q + n_q)
    # Physical traffic: minor dims pad to 128 lanes in HBM's (8, 128)
    # f32 tiling, so input and output each move b_pad*128*4 bytes; the
    # weight bill is the STORED dtype (1 byte/elem for int8 + its f32
    # scales), which is the whole point of the quantized variant.
    bytes_accessed = 2 * b_pad * LANES * 4 + sum(
        w.size * w.dtype.itemsize for w in ws)
    if quant:
        bytes_accessed += sum(s.size * 4 for s in scales)
    out = pl.pallas_call(
        functools.partial(_kernel, n_layers, compute, n_q, quant),
        grid=(b_pad // tile,),
        in_specs=[pl.BlockSpec((tile, N_FEATURES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] + wb_specs,
        out_specs=pl.BlockSpec((tile, n_out), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed,
            # gelu per hidden lane + softplus over the (padded) head
            # lanes of the fused epilogue (2 for the point model).
            transcendentals=b_pad * (sum(w.shape[1] for w in ws[:-1])
                                     + (ws[-1].shape[1] if n_q else 2)),
        ),
        interpret=interpret,
    )(xp, *operands)
    if n_q:
        return out[:b_rows, :n_q]
    return out[:b_rows, 0]

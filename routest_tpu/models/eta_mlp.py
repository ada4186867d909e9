"""ETA regressor: an MLP over the 12-feature encoding.

Replaces the reference's pickled XGBoost booster (``Flaskr/ml.py`` —
batch-size-1 CPU tree walks) with a model whose inference is pure MXU
matmuls, trivially batched and sharded over the mesh data axis.
SURVEY.md §7.3 item 2 motivates the MLP-first choice (``models/gbdt.py``
is the tensorized tree-ensemble alternative for tree-model parity).

The external contract stays the reference's 12 features (Appendix B), but
internally the model expands them into TPU-friendly bases and applies a
physical inductive bias:

- ``weekday``/``hour`` scalars → one-hots (7 + 24): travel-time structure
  over hours (rush peaks, night discount) is sharp and non-monotone —
  one-hot bases capture it where a scalar input forces the net to carve
  step functions out of gelus;
- two heads: predicted **pace** (min/km) and **overhead** (min), combined
  as ``eta = pace · distance + overhead`` — ETAs are near-affine in
  distance with context-dependent slope, so the net only has to learn the
  slope/intercept surfaces.

Parameters are a plain pytree (dict), so pjit/optax/orbax all apply
directly. The feature normalizer (training-set mean/std for the scalar
columns) lives inside the params pytree and is applied under
stop_gradient — serving can never skew from training-time normalization.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import DEFAULT_POLICY, Policy
from routest_tpu.data.features import N_FEATURES

Params = Dict

_N_HOURS = 24
_N_WEEKDAYS = 7
# internal width: weather(4) + traffic(4) + weekday_oh(7) + hour_oh(24)
# + [dist_norm, log_dist, age_norm]
_INTERNAL_FEATURES = 4 + 4 + _N_WEEKDAYS + _N_HOURS + 3


def _cumsum_matrix(n_q: int) -> np.ndarray:
    """(2Q, 2Q) block-diagonal upper-triangular ones: ``sp @ M`` computes
    BOTH head cumsums (pace cols 0..Q-1, overhead cols Q..2Q-1) in one
    matmul. ``cumsum`` along a tiny axis lowers to a reduce-window /
    scan that XLA cannot fuse with the surrounding elementwise graph;
    a constant-matrix dot fuses, runs on the MXU, and is exactly the
    same sum (ones-matrix matmul adds the identical terms)."""
    tri = np.triu(np.ones((n_q, n_q), np.float32))
    m = np.zeros((2 * n_q, 2 * n_q), np.float32)
    m[:n_q, :n_q] = tri
    m[n_q:, n_q:] = tri
    return m


def quantile_heads(out: jax.Array, dist_km: jax.Array,
                   n_q: int) -> jax.Array:
    """Fused non-crossing quantile epilogue: raw head outputs
    (…, 2Q) + distance (…,) → per-quantile ETA minutes (…, Q).

    pace/overhead for quantile 0 are softplus-positive; each later
    quantile adds a softplus-positive increment (cumulative sum), so
    ``eta[:, i] <= eta[:, i+1]`` for every input and parameter setting —
    crossing quantiles are unrepresentable. The cumulative sums run as
    ONE constant-matrix matmul (``_cumsum_matrix``) so the whole
    epilogue is softplus → dot → multiply-add: three fusable ops instead
    of two scans. ``quantile_heads_unfused`` is the scan-form oracle the
    parity tests compare against."""
    sp = jax.nn.softplus(out[..., : 2 * n_q])
    cum = sp @ jnp.asarray(_cumsum_matrix(n_q), sp.dtype)
    return cum[..., :n_q] * dist_km[..., None] + cum[..., n_q:]


def quantile_heads_unfused(out: jax.Array, dist_km: jax.Array,
                           n_q: int) -> jax.Array:
    """Reference (pre-fusion) epilogue: explicit ``jnp.cumsum`` per head
    family. Semantics oracle for :func:`quantile_heads` — kept for the
    parity tests and the serving-kernel bench's fused-vs-unfused rows;
    serving always runs the fused form."""
    pace = jnp.cumsum(jax.nn.softplus(out[..., :n_q]), axis=-1)
    overhead = jnp.cumsum(jax.nn.softplus(out[..., n_q:2 * n_q]), axis=-1)
    return pace * dist_km[..., None] + overhead


@dataclasses.dataclass(frozen=True)
class EtaMLP:
    """Configured model; ``init``/``apply`` are pure functions of params.

    ``quantiles`` (empty by default = point model) turns the two heads
    into 2·Q quantile heads: per quantile a (pace, overhead) pair, with
    pace/overhead parameterized as a positive base plus cumulative
    softplus increments across the quantile axis — so predicted ETA
    quantiles are non-crossing *by construction*, not by regularization.
    The reference's model family is a point regressor (``Flaskr/ml.py``);
    calibrated uncertainty is an additive capability of this framework.
    """

    hidden: Tuple[int, ...] = (256, 256, 128)
    n_features: int = N_FEATURES
    policy: Policy = DEFAULT_POLICY
    quantiles: Tuple[float, ...] = ()

    def __post_init__(self) -> None:
        q = self.quantiles
        if q:
            if list(q) != sorted(q) or len(set(q)) != len(q):
                raise ValueError(f"quantiles must be strictly increasing: {q}")
            if not all(0.0 < v < 1.0 for v in q):
                raise ValueError(f"quantiles must lie in (0, 1): {q}")
            if 0.5 not in q:
                # apply() serves the median as THE eta (the reference ABI
                # is a single number); a head set without it has no
                # defensible point estimate.
                raise ValueError(f"quantiles must include 0.5: {q}")

    @property
    def n_heads(self) -> int:
        return 2 * max(1, len(self.quantiles))

    @classmethod
    def from_config(cls, cfg, policy: Policy = DEFAULT_POLICY) -> "EtaMLP":
        """Build from a core.config.ModelConfig (the env-layered path)."""
        return cls(hidden=tuple(cfg.hidden), policy=policy)

    def init(self, key: jax.Array,
             norm_mean: Optional[np.ndarray] = None,
             norm_std: Optional[np.ndarray] = None) -> Params:
        # point model: (pace, overhead); quantile model: Q pairs
        dims = (_INTERNAL_FEATURES,) + tuple(self.hidden) + (self.n_heads,)
        params: Params = {"layers": []}
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            key, sub = jax.random.split(key)
            scale = jnp.sqrt(2.0 / d_in)
            params["layers"].append(
                {
                    "w": jax.random.normal(sub, (d_in, d_out), self.policy.param_dtype) * scale,
                    "b": jnp.zeros((d_out,), self.policy.param_dtype),
                }
            )
        mean = np.zeros((self.n_features,), np.float32) if norm_mean is None else norm_mean
        std = np.ones((self.n_features,), np.float32) if norm_std is None else norm_std
        # Stats are stored for all 12 ABI columns (stable artifact shape) but
        # ``_expand`` only consumes indices 10-11 (distance, age) — the
        # categorical/ordinal columns become one-hots instead. The std floor
        # guards constant columns (e.g. all-same driver_age) from 1/ε blowup.
        std = np.where(np.asarray(std) < 1e-3, 1.0, std)
        params["norm"] = {
            "mean": jnp.asarray(mean, self.policy.param_dtype),
            "std": jnp.asarray(std, self.policy.param_dtype),
        }
        return params

    def _expand(self, params: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """ABI features (B,12) → internal bases (B,42) + distance_km (B,)."""
        norm = jax.lax.stop_gradient(params["norm"])
        cat = x[..., 0:8]
        weekday = x[..., 8].astype(jnp.int32)
        hour = x[..., 9].astype(jnp.int32)
        # Clamp distance once: a negative distance from a malformed request
        # must not produce a negative ETA downstream.
        dist_km = jnp.maximum(x[..., 10], 0.0)
        age = x[..., 11]
        wd_oh = jax.nn.one_hot(weekday, _N_WEEKDAYS, dtype=x.dtype)
        hr_oh = jax.nn.one_hot(hour, _N_HOURS, dtype=x.dtype)
        dist_n = (dist_km - norm["mean"][10]) / norm["std"][10]
        age_n = (age - norm["mean"][11]) / norm["std"][11]
        log_dist = jnp.log1p(dist_km)
        feats = jnp.concatenate(
            [cat, wd_oh, hr_oh,
             dist_n[..., None], log_dist[..., None], age_n[..., None]],
            axis=-1,
        )
        return feats, dist_km

    def _trunk(self, params: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Shared forward: raw head outputs (B, n_heads) f32 + distance."""
        # named scopes: metadata only (an instruction's ``op_name``, an
        # xplane's ``tf_op``), so a device trace names the layers
        with jax.named_scope("eta.expand"):
            feats, dist_km = self._expand(params, x)
            h = feats.astype(self.policy.compute_dtype)
        layers = params["layers"]
        for i, layer in enumerate(layers[:-1]):
            with jax.named_scope(f"eta.layer{i}"):
                w = layer["w"].astype(self.policy.compute_dtype)
                b = layer["b"].astype(self.policy.compute_dtype)
                h = jax.nn.gelu(h @ w + b)
        last = layers[-1]
        with jax.named_scope("eta.heads"):
            out = h @ last["w"].astype(self.policy.compute_dtype) + last[
                "b"].astype(self.policy.compute_dtype)
            out = out.astype(self.policy.output_dtype)
            dist_km = dist_km.astype(self.policy.output_dtype)
        return out, dist_km

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        """(B, 12) ABI features → (B,) ETA minutes. bf16 trunk, f32 out.

        For a quantile model this is the median head — the reference ABI's
        single number (``Flaskr/ml.py:53``). Runs the path
        :func:`eta_path` names; :meth:`apply_xla` is the XLA body."""
        if self.quantiles:
            q50 = self.quantiles.index(0.5)
            return self.apply_quantiles(params, x)[..., q50]
        if self._path(x) == "fused":
            return self._fused(params, x)[:, 0]
        return self.apply_xla(params, x)

    def apply_xla(self, params: Params, x: jax.Array) -> jax.Array:
        """:meth:`apply` as plain XLA, whatever the backend and size: the
        differentiable form (training, export) and the kernel's oracle."""
        if self.quantiles:
            q50 = self.quantiles.index(0.5)
            return self.apply_quantiles_xla(params, x)[..., q50]
        out, dist_km = self._trunk(params, x)
        with jax.named_scope("eta.heads"):
            pace = jax.nn.softplus(out[..., 0])       # min/km, positive
            overhead = jax.nn.softplus(out[..., 1])   # min, positive
            return pace * dist_km + overhead

    def apply_quantiles(self, params: Params, x: jax.Array) -> jax.Array:
        """(B, 12) → (B, Q) ETA minutes per quantile, non-crossing.

        pace/overhead for quantile 0 are softplus-positive; each later
        quantile adds a softplus-positive increment (cumulative sum), so
        ``eta[:, i] <= eta[:, i+1]`` holds for every input and parameter
        setting — crossing quantiles are unrepresentable. Runs the path
        :func:`eta_path` names: the fused kernel (``ops/fused_mlp.py``)
        for large bfloat16 batches on a TPU, :meth:`apply_quantiles_xla`
        everywhere else.
        """
        if self.quantiles and self._path(x) == "fused":
            return self._fused(params, x)
        return self.apply_quantiles_xla(params, x)

    def apply_quantiles_xla(self, params: Params, x: jax.Array) -> jax.Array:
        """:meth:`apply_quantiles` as plain XLA: the differentiable form
        and the kernel's oracle. The epilogue runs in the fused matmul
        form (:func:`quantile_heads`) — same sums, one fusable dot
        instead of two scans."""
        if not self.quantiles:
            raise ValueError("apply_quantiles on a point model; "
                             "construct EtaMLP(quantiles=...)")
        n_q = len(self.quantiles)
        out, dist_km = self._trunk(params, x)
        with jax.named_scope("eta.heads"):
            return quantile_heads(out, dist_km, n_q)

    def _path(self, x: jax.Array) -> str:
        rows = x.shape[0] if x.ndim == 2 else 0
        return eta_path(jax.default_backend(), self.policy.compute_dtype,
                        self.hidden, rows)

    def _fused(self, params: Params, x: jax.Array) -> jax.Array:
        """(B, 12) → (B, Q | 1) through the kernel. The table's layout is
        feature-major on a TPU, so both transposes are bitcasts there;
        the weights are packed in the traced program (loop-invariant)."""
        from routest_tpu.ops.fused_mlp import (fused_eta_forward_t,
                                               pack_eta_params)

        packed = pack_eta_params(self, params, dtype="bfloat16")
        with jax.named_scope("eta.fused"):
            return fused_eta_forward_t(packed, x.T, len(self.quantiles)).T


# Rows from which EtaMLP takes the fused kernel. On a v5e the kernel beat
# the XLA body at every slice size measured, 4,096 to 131,072 rows, by
# x 2.2-2.7 (PERF.md §5, the crossover table of PR 33), so no crossover
# sets this: it is the smallest batch of whole kernel tiles, which keeps
# the serving buckets (8-4,096 rows, host-bound) on the XLA body.
FUSED_MIN_ROWS = 8192


def eta_path(backend: str, compute_dtype, hidden, n_rows) -> str:
    """The forward :class:`EtaMLP` runs at these shapes: ``"fused"``
    (one Pallas kernel, ``ops/fused_mlp.py``) on a TPU at bfloat16
    compute where every hidden width tiles the MXU and the batch is a
    whole number of kernel tiles and large enough for the kernel to
    win; ``"xla"`` everywhere else (the CPU, a float32 policy, toy
    widths, small or symbolic batches)."""
    if (backend != "tpu" or jnp.dtype(compute_dtype) != jnp.bfloat16
            or not hidden or any(h % 128 for h in hidden)
            or not isinstance(n_rows, int) or n_rows < FUSED_MIN_ROWS):
        return "xla"
    from routest_tpu.ops.fused_mlp import TILE   # pallas: 0.7 s to import

    return "xla" if n_rows % TILE else "fused"


def fit_normalizer(features: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Mean/std over the training features. ``init`` replaces near-zero
    stds (constant columns) with 1.0 so unseen categories can't explode."""
    return features.mean(axis=0).astype(np.float32), features.std(axis=0).astype(np.float32)

"""Checkpointing & model-artifact IO.

The reference's entire persistence story for the model is "lazily unpickle
``xgb_eta_model.pkl``, path overridable via ``ETA_MODEL_PATH``"
(``Flaskr/ml.py:6-21``; SURVEY.md §5.4). Here:

- training checkpoints (params + optimizer state + step) go through Orbax;
- the *serving artifact* is a single msgpack file (flax serialization) of
  the params pytree plus a small JSON header with the model config — no
  pickle, loadable without trusting the file;
- ``ETA_MODEL_PATH`` still points at the serving artifact, for env parity.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from flax import serialization

from routest_tpu.models.eta_mlp import EtaMLP, Params

MAGIC = b"RTPU1\n"
ARTIFACT_VERSION = 2
QUANTILE_ARTIFACT_VERSION = 3


def _write_artifact(path: str, magic: bytes, header: dict,
                    blob: bytes) -> None:
    """Shared artifact writer: magic prefix + one-line JSON header +
    binary blob — the layout every artifact family speaks (see
    :func:`_read_artifact`).

    Written temp-then-rename: hot-reload watchers (the ETA service's and
    the road router's) stat these paths on live traffic, so a reader
    must never observe a half-written file — os.replace makes the swap
    atomic on POSIX."""
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # pid alone is not unique enough: two threads in one process (e.g.
    # concurrent trainers in tests) would interleave writes to the same
    # temp file before os.replace.
    tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(magic)
            f.write(json.dumps(header).encode() + b"\n")
            f.write(blob)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _params_blob(params) -> bytes:
    """Params pytree → msgpack bytes (host copies, no device refs)."""
    return serialization.msgpack_serialize(
        jax.tree_util.tree_map(np.asarray, params))


def _read_artifact(path: str, magic: bytes, fmt: str, versions,
                   kind: str, retrain_hint: str):
    """Shared artifact reader: magic prefix + one-line JSON header +
    binary blob, with format/version validation. All three artifact
    families (eta msgpack, road-GNN msgpack, StableHLO export) speak
    this layout; keeping ONE reader keeps their error contracts in sync.
    Returns (header, blob)."""
    with open(path, "rb") as f:
        if f.read(len(magic)) != magic:
            raise ValueError(f"{path}: not a {kind}")
        header = json.loads(f.readline().decode())
        blob = f.read()
    if header.get("format") != fmt:
        raise ValueError(f"{path}: unknown artifact format "
                         f"{header.get('format')}")
    if header.get("version") not in versions:
        expected = "/".join(f"v{v}" for v in versions)
        raise ValueError(
            f"{path}: artifact version {header.get('version')} is "
            f"incompatible (expects {expected}); {retrain_hint}")
    return header, blob


def save_model(path: str, model: EtaMLP, params: Params) -> None:
    """Serving artifact: MAGIC + json header line + msgpack params."""
    header_dict = {
        "format": "routest_tpu.eta_mlp",
        # v2: internal one-hot expansion + [pace, overhead] heads
        # (first layer is 42-wide, output is 2-wide). v1 artifacts
        # (12-wide input, 1 head) are incompatible and rejected on load.
        # v3 = v2 + quantile heads (output 2·Q-wide); point models keep
        # writing v2 so older builds load them unchanged.
        "version": ARTIFACT_VERSION,
        "hidden": list(model.hidden),
        "n_features": model.n_features,
        "compute_dtype": np.dtype(model.policy.compute_dtype).name,
    }
    if model.quantiles:
        header_dict["version"] = QUANTILE_ARTIFACT_VERSION
        header_dict["quantiles"] = list(model.quantiles)
    _write_artifact(path, MAGIC, header_dict, _params_blob(params))


def load_model(path: str) -> Tuple[EtaMLP, Params]:
    header, blob = _read_artifact(
        path, MAGIC, "routest_tpu.eta_mlp",
        (ARTIFACT_VERSION, QUANTILE_ARTIFACT_VERSION),
        kind="routest_tpu model artifact",
        retrain_hint="retrain via scripts/train_eta.py")
    version = header.get("version")
    quantiles = tuple(header.get("quantiles", ()))
    if version == QUANTILE_ARTIFACT_VERSION and not quantiles:
        raise ValueError(f"{path}: v{QUANTILE_ARTIFACT_VERSION} artifact "
                         f"missing its quantiles header")
    import jax.numpy as jnp

    from routest_tpu.core.dtypes import DEFAULT_POLICY
    import dataclasses as _dc

    compute = header.get("compute_dtype", "bfloat16")
    policy = _dc.replace(DEFAULT_POLICY, compute_dtype=jnp.dtype(compute).type)
    model = EtaMLP(hidden=tuple(header["hidden"]), n_features=header["n_features"],
                   policy=policy, quantiles=quantiles)
    params = serialization.msgpack_restore(blob)
    params = jax.tree_util.tree_map(lambda x: np.asarray(x), params)
    return model, params


EXPORT_MAGIC = b"RTPUX1\n"
EXPORT_VERSION = 1


def export_serving_fn(path: str, model: EtaMLP, params: Params,
                      platforms: Tuple[str, ...] = ("cpu", "tpu")) -> None:
    """AOT-export the serving forward as serialized StableHLO.

    The msgpack artifact (``save_model``) needs this package's model
    code to rebuild the forward; this artifact does not — the traced
    computation with the params baked in as constants IS the file, with
    a symbolic batch dimension so one export covers every batch bucket.
    That pins the serving numerics against model-code drift (the
    deployed function can't change when ``eta_mlp.py`` does) and drops
    the Python model from the serving dependency chain — the TPU-native
    analog of exporting the reference's pickled booster to a
    self-contained format. Multi-platform by default: the same file
    serves the CPU conftest backend and the TPU.

    Layout mirrors ``save_model``: EXPORT_MAGIC + JSON header line
    (n_features / quantiles / platforms — what the serving layer needs
    without executing anything) + the StableHLO bytes.
    """
    from jax import export as jax_export

    quantiles = tuple(getattr(model, "quantiles", ()) or ())
    # the XLA body by name: the artifact has to run on every platform it
    # lists, and a Mosaic kernel runs on one
    forward = model.apply_quantiles_xla if quantiles else model.apply_xla
    host_params = jax.tree_util.tree_map(np.asarray, params)

    def fn(x):
        return forward(host_params, x)

    (batch,) = jax_export.symbolic_shape("b")
    spec = jax.ShapeDtypeStruct((batch, model.n_features), np.float32)
    exported = jax_export.export(jax.jit(fn), platforms=tuple(platforms))(spec)
    if "tpu_custom_call" in exported.mlir_module():
        raise ValueError("the exported program holds a Mosaic kernel")
    _write_artifact(path, EXPORT_MAGIC, {
        "format": "routest_tpu.eta_stablehlo",
        "version": EXPORT_VERSION,
        "n_features": model.n_features,
        "quantiles": list(quantiles),
        "platforms": list(platforms),
        "hidden": list(model.hidden),  # informational; not needed to run
    }, exported.serialize())


class ExportedServingModel:
    """A deserialized AOT export, shaped like a model for the serving
    layer: ``n_features``/``quantiles`` attributes + ``__call__``.
    No params pytree exists — weights are constants inside the program."""

    def __init__(self, call, header: dict) -> None:
        self._call = call
        self.header = header
        self.n_features = int(header["n_features"])
        self.quantiles = tuple(header.get("quantiles", ()))
        self.hidden = tuple(header.get("hidden", ()))

    @property
    def call(self):
        """The raw traceable program — what the serving layer hands to
        ``jax.jit`` for per-bucket AOT compiles (with mesh shardings
        when a runtime is present)."""
        return self._call

    def __call__(self, x):
        return self._call(x)


def backend_platforms(backend: Optional[str] = None) -> Tuple[str, ...]:
    """jax backend name → the export-platform names it can execute.
    Vocabularies differ on GPU: ``jax.default_backend()`` says "gpu",
    exports say "cuda"/"rocm"."""
    backend = backend or jax.default_backend()
    if backend == "gpu":
        return ("cuda", "rocm")
    return (backend,)


def load_exported_serving_fn(path: str) -> ExportedServingModel:
    """Deserialize an ``export_serving_fn`` artifact. Raises ValueError
    for wrong magic/format/version (same contract as ``load_model``)."""
    from jax import export as jax_export

    header, blob = _read_artifact(
        path, EXPORT_MAGIC, "routest_tpu.eta_stablehlo", (EXPORT_VERSION,),
        kind="routest_tpu AOT export",
        retrain_hint="re-export via scripts/export_model.py")
    exported = jax_export.deserialize(blob)
    runnable = backend_platforms()
    if not any(p in exported.platforms for p in runnable):
        raise ValueError(
            f"{path}: exported for platforms {list(exported.platforms)}, "
            f"but the running backend is {jax.default_backend()}; "
            f"re-export with --platforms {','.join(runnable)}")
    # Same contract as EtaMLP.__post_init__: a quantile head must carry
    # the median, or every per-request ``q.index(0.5)`` in the serving
    # layer would raise (500s) instead of the graceful (None, None)
    # degrade. Reject the foreign/hand-edited artifact at load time.
    quantiles = header.get("quantiles") or []
    if quantiles and 0.5 not in quantiles:
        raise ValueError(
            f"{path}: quantile export lacks the 0.5 median "
            f"(quantiles={quantiles}); serving requires it")
    return ExportedServingModel(exported.call, header)


def default_model_path(cfg=None) -> str:
    """Resolution order: explicit ModelConfig.model_path (set from
    ETA_MODEL_PATH by ``load_config``), then the env var directly, then the
    in-repo artifact location (mirrors ``Flaskr/ml.py:6-9`` behavior)."""
    if cfg is not None and getattr(cfg, "model_path", None):
        return cfg.model_path
    return os.getenv("ETA_MODEL_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "artifacts",
        "eta_mlp.msgpack",
    )


# ── Road-GNN serving artifact ─────────────────────────────────────────────
#
# Same MAGIC + header + msgpack layout as the ETA artifact, different
# format tag. The header carries a fingerprint of the TRAINING graph's
# node set (count + coordinate checksum): the model's message passing is
# anchored to node embeddings, so serving it over a different node set
# would silently produce garbage — the router refuses mismatched graphs
# and falls back to free-flow physics.

GNN_ARTIFACT_VERSION = 1


def graph_fingerprint(node_coords: np.ndarray, senders: np.ndarray,
                      receivers: np.ndarray, length_m: np.ndarray) -> dict:
    """Nodes AND edges: the GNN's aggregation depends on the topology it
    was trained over, so an edge-set drift (not just a node drift) must
    also fail the serving-compatibility check."""
    import zlib

    def crc(a, dtype):
        return int(zlib.crc32(np.ascontiguousarray(
            np.asarray(a, dtype)).tobytes()))

    return {
        "n_nodes": int(np.asarray(node_coords).shape[0]),
        "coords_crc32": crc(node_coords, np.float32),
        "n_edges": int(len(senders)),
        "edges_crc32": crc(senders, np.int32) ^ crc(receivers, np.int32)
        ^ crc(length_m, np.float32),
    }


def save_gnn(path: str, model, params, graph: dict) -> None:
    _write_artifact(path, MAGIC, {
        "format": "routest_tpu.road_gnn",
        "version": GNN_ARTIFACT_VERSION,
        "hidden": int(model.hidden),
        "n_rounds": int(model.n_rounds),
        "n_nodes": int(model.n_nodes),
        "compute_dtype": np.dtype(model.policy.compute_dtype).name,
        "graph": graph_fingerprint(
            graph["node_coords"], graph["senders"], graph["receivers"],
            graph["length_m"]),
    }, _params_blob(params))


def load_gnn(path: str):
    """→ (RoadGNN, params, graph fingerprint dict)."""
    from routest_tpu.models.gnn import RoadGNN

    header, blob = _read_artifact(
        path, MAGIC, "routest_tpu.road_gnn", (GNN_ARTIFACT_VERSION,),
        kind="routest_tpu model artifact",
        retrain_hint="retrain via scripts/train_gnn.py")
    import jax.numpy as jnp

    from routest_tpu.core.dtypes import DEFAULT_POLICY

    compute = header.get("compute_dtype", "bfloat16")
    policy = dataclasses.replace(DEFAULT_POLICY,
                                 compute_dtype=jnp.dtype(compute).type)
    model = RoadGNN(n_nodes=header["n_nodes"], hidden=header["hidden"],
                    n_rounds=header["n_rounds"], policy=policy)
    params = serialization.msgpack_restore(blob)
    params = jax.tree_util.tree_map(np.asarray, params)
    # Feature-ABI gate: an artifact trained against an older
    # edge_feature_array layout would pass the graph fingerprint and
    # then shape-crash inside apply ON THE REQUEST PATH. The message
    # MLP's input width pins the trained feature count; reject here so
    # the router's loader degrades to the next pricer instead.
    from routest_tpu.models.gnn import N_EDGE_FEATURES

    f_in = int(params["msg"][0]["w"].shape[0]) - 2 * int(header["hidden"])
    if f_in != N_EDGE_FEATURES:
        raise ValueError(
            f"{path}: trained with {f_in} edge features, this build uses "
            f"{N_EDGE_FEATURES}; retrain via scripts/train_gnn.py")
    return model, params, header.get("graph") or {}


def default_gnn_path() -> str:
    """``ROAD_GNN_PATH`` env override, then the in-repo artifact."""
    return os.getenv("ROAD_GNN_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "artifacts",
        "road_gnn.msgpack",
    )


# ── Route-transformer serving artifact ────────────────────────────────────

TRANSFORMER_ARTIFACT_VERSION = 1


def save_transformer(path: str, model, params, graph: dict,
                     seq_len: int) -> None:
    """Route-transformer leg-cost artifact — same fingerprinting contract
    as the road GNN: the router serves it only when its training graph
    matches the routable (post-bridge) graph. ``seq_len`` (the trained
    route length) is recorded so serving can chunk longer tours into
    in-distribution windows."""
    _write_artifact(path, MAGIC, {
        "format": "routest_tpu.route_transformer",
        "version": TRANSFORMER_ARTIFACT_VERSION,
        "d_model": int(model.d_model),
        "n_heads": int(model.n_heads),
        "n_layers": int(model.n_layers),
        "d_mlp": int(model.d_mlp),
        "seq_len": int(seq_len),
        "graph": graph_fingerprint(
            graph["node_coords"], graph["senders"], graph["receivers"],
            graph["length_m"]),
    }, _params_blob(params))


def load_transformer(path: str):
    """→ (RouteTransformer, params, meta) where meta carries the graph
    fingerprint and the trained ``seq_len``."""
    from routest_tpu.models.route_transformer import RouteTransformer

    header, blob = _read_artifact(
        path, MAGIC, "routest_tpu.route_transformer",
        (TRANSFORMER_ARTIFACT_VERSION,),
        kind="routest_tpu model artifact",
        retrain_hint="retrain via scripts/train_transformer.py")
    model = RouteTransformer(d_model=header["d_model"],
                             n_heads=header["n_heads"],
                             n_layers=header["n_layers"],
                             d_mlp=header["d_mlp"])
    params = serialization.msgpack_restore(blob)
    params = jax.tree_util.tree_map(np.asarray, params)
    # Same feature-ABI gate as load_gnn: the embed matrix pins the
    # trained edge-feature count.
    f_in = int(params["embed"]["w"].shape[0])
    if f_in != model.n_features:
        raise ValueError(
            f"{path}: trained with {f_in} edge features, this build uses "
            f"{model.n_features}; retrain via scripts/train_transformer.py")
    return model, params, {"graph": header.get("graph") or {},
                           "seq_len": int(header.get("seq_len", 24))}


def default_transformer_path() -> str:
    """``ROUTE_TRANSFORMER_PATH`` env override, then the in-repo artifact."""
    return os.getenv("ROUTE_TRANSFORMER_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "artifacts",
        "route_transformer.msgpack",
    )


# ── Route-sequence language model artifact ────────────────────────────────

ROUTE_LM_ARTIFACT_VERSION = 1


def _route_lm_types():
    """Model type in an artifact's header → class. ``RouteLM`` is what
    a header without the key holds."""
    from routest_tpu.models.route_lm import RouteLM
    from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone
    from routest_tpu.models.route_lm_sala import RouteLMSala

    return {"RouteLM": RouteLM, "RouteLMSala": RouteLMSala,
            "RouteLMKExaone": RouteLMKExaone,
            "RouteLMGigaChat": RouteLMGigaChat,
            "RouteLMFalconH1": RouteLMFalconH1}


def save_route_lm(path: str, model, params) -> None:
    """Route-LM serving artifact: the header carries the model's type,
    the published sizes it was built from, the share of the deployment
    these parameters are (``RouteLM``: layers, experts and vocabulary
    rows held, chips a layer; ``RouteLMKExaone`` and ``RouteLMGigaChat``:
    the same and whether the prediction module is held; ``RouteLMSala``:
    a run of layers from ``layers_first`` on; ``RouteLMFalconH1``: a run
    of blocks and the vocabulary's rows over ``vocab_chips``) and the
    dtype policy; the
    blob is the params pytree (bfloat16 leaves travel as they are)."""
    _write_artifact(path, MAGIC, {
        "format": "routest_tpu.route_lm",
        "version": ROUTE_LM_ARTIFACT_VERSION,
        "model": type(model).__name__,
        "sizes": dict(model.sizes),
        "share": model.share_header(),
        "param_dtype": np.dtype(model.policy.param_dtype).name,
        "compute_dtype": np.dtype(model.policy.compute_dtype).name,
    }, _params_blob(params))


def load_route_lm(path: str, expect_share: Optional[dict] = None):
    """→ (the model the header names, params). The parameters of a
    share are only that share's: where the loader states the share it
    serves (``expect_share``: any key of the model's ``share_header``)
    and the artifact's disagrees, or where the arrays are not the
    header's share, the load raises — as the feature-count gates of the
    other artifacts do."""
    import jax.numpy as jnp

    from routest_tpu.core.dtypes import Policy

    header, blob = _read_artifact(
        path, MAGIC, "routest_tpu.route_lm", (ROUTE_LM_ARTIFACT_VERSION,),
        kind="routest_tpu model artifact",
        retrain_hint="write it again with save_route_lm")
    share = header["share"]
    for key, want in (expect_share or {}).items():
        if share.get(key) != want:
            raise ValueError(
                f"{path}: the artifact holds {key}={share.get(key)}, this "
                f"replica serves {key}={want}; load the artifact of its "
                f"own share")
    types = _route_lm_types()
    kind = header.get("model", "RouteLM")
    if kind not in types:
        raise ValueError(f"{path}: no route-sequence model {kind!r} here "
                         f"(known: {sorted(types)})")
    model = types[kind](
        sizes=header["sizes"], **share,
        policy=Policy(param_dtype=jnp.dtype(header["param_dtype"]).type,
                      compute_dtype=jnp.dtype(header["compute_dtype"]).type))
    params = serialization.msgpack_restore(blob)
    params = jax.tree_util.tree_map(np.asarray, params)
    layers = params["layers"]
    if isinstance(layers, dict):     # msgpack keeps a list as a dict
        layers = [layers[str(i)] for i in range(len(layers))]
        params["layers"] = layers
    if not model.holds(params):
        raise ValueError(
            f"{path}: the arrays are not the share the header states "
            f"({len(layers)} layers, {params['embed'].shape[0]} vocabulary "
            f"rows against {share})")
    return model, params


# ── Orbax training checkpoints ────────────────────────────────────────────

def save_checkpoint(ckpt_dir: str, step: int, state) -> None:
    import orbax.checkpoint as ocp

    path = os.path.join(os.path.abspath(ckpt_dir), f"step_{step:08d}")
    ckptr = ocp.StandardCheckpointer()
    host_state = jax.tree_util.tree_map(np.asarray, state)
    ckptr.save(path, host_state, force=True)
    ckptr.wait_until_finished()


def latest_checkpoint_step(ckpt_dir: str) -> Optional[Tuple[int, str]]:
    """Newest COMPLETE checkpoint as ``(step, path)``. A crash mid-save
    leaves Orbax tmp dirs (``step_N.orbax-checkpoint-tmp-*``) behind —
    exactly the scenario resume exists for — so only cleanly-named
    numeric steps count. The step number is parsed here, the one place
    that owns the ``step_%08d`` naming scheme."""
    if not os.path.isdir(ckpt_dir):
        return None
    best: Optional[Tuple[int, str]] = None
    for d in os.listdir(ckpt_dir):
        if not d.startswith("step_"):
            continue
        suffix = d[len("step_"):]
        if not suffix.isdigit():
            continue  # tmp/incomplete entries
        step = int(suffix)
        if best is None or step > best[0]:
            best = (step, d)
    return (best[0], os.path.join(ckpt_dir, best[1])) if best else None


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    found = latest_checkpoint_step(ckpt_dir)
    return found[1] if found else None


def restore_checkpoint(path: str, target):
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    host_target = jax.tree_util.tree_map(np.asarray, target)
    return ckptr.restore(path, host_target)

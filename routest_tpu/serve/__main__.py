"""Dev server entry point: ``python -m routest_tpu.serve``.

Equivalent of the reference's ``app.py`` dev entry (Flask dev server on
:5000); honors the same PORT env var. If no model artifact exists yet, a
quick synthetic training run materializes one so the service comes up
fully functional out of the box. Boot status goes through the
structured ``JsonLogger`` like every other event in the stack — the
bare-print era is closed by ``tests/test_no_bare_print.py``.
"""

from __future__ import annotations

import os

from routest_tpu.core.config import load_config
from routest_tpu.serve.app import create_app
from routest_tpu.train.checkpoint import default_model_path
from routest_tpu.utils.logging import get_logger

_log = get_logger("routest_tpu.serve.boot")


def ensure_model(path: str) -> None:
    if os.path.exists(path):
        return
    _log.info("model_bootstrap_started", path=path,
              reason="no artifact; training a quick synthetic model")
    from routest_tpu.core.config import TrainConfig
    from routest_tpu.data.synthetic import generate_dataset, train_eval_split
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.train.checkpoint import save_model
    from routest_tpu.train.loop import fit

    train, ev = train_eval_split(generate_dataset(200_000, seed=0))
    model = EtaMLP()
    result = fit(model, train, ev, TrainConfig(epochs=15))
    save_model(path, model, result.state.params)
    _log.info("model_bootstrap_finished", path=path,
              eval_rmse_min=round(result.eval_rmse, 2))


def main() -> None:
    if os.environ.get("ROUTEST_FORCE_CPU") == "1":
        # One switch for "CPU backend with 8 virtual devices" — what the
        # tests, the CPU fleet overlays (fleet/placement.slice_env) and
        # the bench harnesses boot replicas with, so sharding paths run
        # without hardware. Plain JAX_PLATFORMS=cpu gives one device.
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    # Persistent XLA cache: server restarts and sibling replicas skip the
    # first-compile cost of the serving buckets and the road solver.
    from routest_tpu.core.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    if cache_dir:
        _log.info("compile_cache_enabled", dir=cache_dir)
    config = load_config()
    ensure_model(default_model_path(config.model))
    # Production serving shards the OD batch over every visible device
    # (the BASELINE.json north star is a *pjit-sharded* inference server,
    # not a single-chip one). ROUTEST_MESH: "auto" (default) = mesh when
    # >1 REAL accelerator — virtual CPU device counts (ROUTEST_FORCE_CPU
    # sets 8 for sharding validation) are pure overhead on one physical
    # core, measured 2x worse single-row p95; "1" forces the mesh on any
    # multi-device backend (sharding-path validation); "0" disables.
    runtime = None
    mesh_pref = os.environ.get("ROUTEST_MESH", "auto")
    if mesh_pref != "0":
        import jax

        from routest_tpu.core.mesh import MeshRuntime

        devices = jax.devices()
        want = mesh_pref == "1" or jax.default_backend() not in ("cpu",)
        if want and len(devices) > 1:
            runtime = MeshRuntime.create(config.mesh)
            _log.info("mesh_serving", data_shards=runtime.n_data,
                      devices=len(devices))
    from routest_tpu.serve.ml_service import EtaService

    eta = EtaService(config.serve,
                     model_path=default_model_path(config.model),
                     runtime=runtime)
    if config.serve.reload_sec > 0:
        # EtaService started the watcher itself (it owns the lifecycle);
        # just surface it on the boot line.
        _log.info("hot_reload_watcher", interval_s=config.serve.reload_sec)
    app = create_app(config, eta_service=eta)
    # Binary wire channel: when RTPU_WIRE=1 armed the app's wire
    # handlers, expose them on a raw multiplexed TCP socket too (the
    # gateway's preferred transport; HTTP negotiation stays available
    # either way). Derived port keeps autoscaled replicas on random
    # HTTP ports addressable: channel = http_port + offset.
    from routest_tpu.core.config import load_wire_config

    wire_cfg = load_wire_config()
    wire_server = None
    if wire_cfg.enabled and wire_cfg.channel and app.wire_handlers:
        from routest_tpu.serve.wirechannel import WireChannelServer

        wire_port = wire_cfg.port or (config.serve.port
                                      + wire_cfg.port_offset)
        wire_server = WireChannelServer(
            app.wire_handlers, config.serve.host, wire_port,
            max_frame_bytes=int(wire_cfg.max_frame_mb * 1024 * 1024))
        try:
            wire_server.start()   # logs wire_channel_listening itself
        except OSError as e:
            # A derived-port collision must not kill the worker: the
            # HTTP negotiation path still serves wire frames, and the
            # gateway falls back to it per request.
            _log.warning("wire_channel_bind_failed", port=wire_port,
                         error=str(e))
            wire_server = None
    # HTTP/1.1 keep-alive: werkzeug defaults to 1.0 (connection-per-
    # request), which taxes every call with TCP setup + a fresh handler
    # thread. Persistent connections cut the serving tail roughly in half
    # under concurrent load.
    from werkzeug.serving import WSGIRequestHandler

    from routest_tpu.serve.wsgi import run_with_graceful_shutdown

    WSGIRequestHandler.protocol_version = "HTTP/1.1"
    _log.info("serve_listening", host=config.serve.host,
              port=config.serve.port)
    # SIGTERM/SIGINT drain: stop accepting, finish in-flight handlers,
    # then exit — the single-replica analog of the fleet's drain path
    # (a supervisor TERM must not kill a worker mid-request).
    run_with_graceful_shutdown(app, config.serve.host, config.serve.port)
    if wire_server is not None:
        wire_server.stop()
    _log.info("serve_stopped")


if __name__ == "__main__":
    main()

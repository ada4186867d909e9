"""The serving application: the reference's full HTTP ABI, TPU-backed.

Every endpoint of the reference Flask service (SURVEY.md Appendix A,
``Flaskr/routes.py``) plus Laravel's ``GET /api/locations``, mounted at
``/api``. Differences under the hood:

- route optimization runs on-device (``optimize.engine``) instead of ORS;
- ETA prediction goes through the dynamic batcher to a jit-compiled MLP;
- persistence/SSE default to hermetic in-memory backends, switching to
  PostgREST/Redis when the reference's env vars are configured;
- health keeps the degraded-not-down contract (always HTTP 200,
  ``Flaskr/routes.py:339-363``) and adds TPU gauges (preds/sec, batch
  fill, devices) under ``checks.tpu`` (SURVEY.md §5.5).
"""

from __future__ import annotations

import datetime as dt
import math
import os
import time
from typing import Optional

import numpy as np

from werkzeug.wrappers import Response

from routest_tpu.core.config import Config, load_config, load_wire_config
from routest_tpu.core.mesh import chip_peaks
from routest_tpu.data.locations import locations_table
from routest_tpu.obs import get_registry
from routest_tpu.obs.ledger import record_change
from routest_tpu.optimize.engine import (MAX_BATCH_PROBLEMS, _parse_problem,
                                         optimize_route,
                                         optimize_route_batch, travel_matrix)
from routest_tpu.serve import sim
from routest_tpu.serve import auth as auth_mod
from routest_tpu.serve.auth import AuthService, mount_auth
from routest_tpu.serve.bus import make_bus, sse_stream
from routest_tpu.serve.deadline import DeadlineExceeded
from routest_tpu.serve.ml_service import EtaService
from routest_tpu.serve import wirecodec
from routest_tpu.serve.store import StoreUnavailable, make_store
from routest_tpu.serve.wsgi import App, get_json, json_response
from routest_tpu.utils.logging import get_logger

_log = get_logger("routest_tpu.serve")

_m_dispatch_requests = get_registry().counter(
    "rtpu_dispatch_requests_total",
    "POST /api/dispatch solves accepted, by problem mode.", ("mode",))


def _obj(value) -> dict:
    """A client-supplied field that SHOULD be an object, defensively:
    non-dict values (fuzz-reachable on every nested field) degrade to {}
    so handlers fall into their normal missing-field defaults instead of
    AttributeError 500s."""
    return value if isinstance(value, dict) else {}


class ServerState:
    """Everything the handlers share."""

    def __init__(self, config: Config, eta: EtaService, store, bus,
                 sim_tick_range=(2.0, 5.0), auth: Optional[AuthService] = None,
                 mailer=None) -> None:
        self.config = config
        self.eta = eta
        self.store = store
        self.bus = bus
        self.sim_tick_range = sim_tick_range
        self.auth = auth if auth is not None else AuthService(
            required=os.environ.get("ROUTEST_AUTH") == "require")
        self.mailer = mailer
        self.started = time.time()
        self.live = None  # LiveTrafficService when RTPU_LIVE=1
        # tile-probe cache: (checked_at, result) — see health()
        self._tiles_cache = (0.0, None)


def create_app(config: Optional[Config] = None,
               eta_service: Optional[EtaService] = None,
               store=None, bus=None,
               sim_tick_range=(2.0, 5.0),
               auth: Optional[AuthService] = None,
               mailer=None) -> App:
    config = config or load_config()
    if mailer is None:
        from routest_tpu.serve.mail import make_mailer

        mailer = make_mailer()
    if eta_service is not None:
        eta = eta_service
    else:
        from routest_tpu.train.checkpoint import default_model_path

        eta = EtaService(config.serve,
                         model_path=default_model_path(config.model))
    store = store if store is not None else make_store(
        config.serve.supabase_url, config.serve.supabase_service_key
    )
    bus = bus if bus is not None else make_bus(config.serve.redis_url)
    state = ServerState(config, eta, store, bus, sim_tick_range, auth,
                        mailer=mailer)

    app = App()
    app.state = state  # for tests / introspection
    mount_auth(app, state.auth, mailer=state.mailer)

    # Standard identity gauges (rtpu_build_info + process start time) on
    # the process registry every /api/metrics exposition includes.
    from routest_tpu.obs import register_build_info

    register_build_info()

    # SLO engine: per-route burn-rate objectives over THIS app's
    # request-stats registry plus the store dependency, ticking on a
    # daemon thread so alert edges (and their postmortem bundles) fire
    # even when nobody polls /api/slo. The flight recorder subscribes
    # to page edges and carries the engine's state in every bundle.
    from routest_tpu.obs.recorder import get_recorder
    from routest_tpu.obs.slo import build_replica_engine

    recorder = get_recorder()

    # Change ledger (docs/OBSERVABILITY.md "Change ledger & incident
    # correlation"): arm this replica's blast-radius context, fan local
    # events out on the fleet bus (and ingest the fleet's), and hand
    # the ledger to the recorder so every bundle ranks suspects.
    from routest_tpu.obs.ledger import (get_change_ledger,
                                        replica_label as _replica_label)

    app.change_ledger = get_change_ledger()
    app.change_ledger.set_context(
        replica=_replica_label(),
        version=os.environ.get("RTPU_VERSION") or None)
    if app.change_ledger.enabled:
        app.change_ledger.attach_bus(state.bus)
    recorder.register_change_ledger(app.change_ledger)

    app.slo = None
    if config.slo.enabled:
        app.slo = build_replica_engine(app.request_stats.registry,
                                       config.slo)
        app.slo.on_page.append(recorder.on_slo_page)
        recorder.register_slo_engine(app.slo)
        if config.slo.tick_s > 0:
            app.slo.start()

    # Metric timeline (docs/OBSERVABILITY.md "Metric timeline"): the
    # request-stats registry AND the process registry ticked into
    # bounded multi-resolution rings behind /api/timeline, with the
    # anomaly watcher comparing each fresh window against the trailing
    # baseline. Bundles embed the timeline (register_timeline), so a
    # postmortem answers "when did it start".
    from routest_tpu.obs import get_registry as _get_registry
    from routest_tpu.obs.timeline import AnomalyWatcher, TimelineStore

    app.timeline = None
    app.watcher = None
    timeline_cfg = getattr(config, "timeline", None)
    if timeline_cfg is not None and timeline_cfg.enabled:
        app.timeline = TimelineStore(
            [app.request_stats.registry, _get_registry()],
            timeline_cfg, component="replica")
        recorder.register_timeline(app.timeline)
        if timeline_cfg.watch:
            app.watcher = AnomalyWatcher(app.timeline, timeline_cfg,
                                         recorder).attach()
        app.timeline.start()

    # Triggered on-path profiling (docs/OBSERVABILITY.md "Triggered
    # profiling"): armed by the SLO engine's upward edges (warn→page)
    # and POST /api/debug/profile, budgeted per process.
    from routest_tpu.obs.profiler import TriggeredProfiler

    app.profiler = None
    profile_cfg = getattr(config, "profile", None)
    if profile_cfg is not None and profile_cfg.enabled:
        app.profiler = TriggeredProfiler(profile_cfg, recorder,
                                         component="replica")
        if app.slo is not None:
            app.slo.on_warn.append(app.profiler.on_slo_edge)

    # Live traffic (RTPU_LIVE=1, docs/ARCHITECTURE.md "Live traffic"):
    # probe-stream ingest → per-edge congestion state → periodic metric
    # refresh on the road router. Armed asynchronously — the router
    # build on a metro extract must not stall /up.
    app.live = None
    state.live = None
    live_cfg = getattr(config, "live", None)
    if live_cfg is not None and live_cfg.enabled:
        from routest_tpu.live.service import LiveTrafficService

        app.live = LiveTrafficService(state.bus, live_cfg)
        state.live = app.live
        app.live.start()

    # Dispatch workload (docs/ARCHITECTURE.md "Dispatch dataflow"):
    # concurrent POST /api/dispatch VRP problems merge into one padded
    # device batch (dispatch/batcher.py); confirmed dispatches register
    # their corridor (dispatch/registry.py); on live metric flips the
    # re-optimization loop re-solves exactly the degraded plans and
    # pushes plan_update events over the SSE bus (dispatch/reopt.py).
    app.dispatch = None
    state.dispatch = None
    dispatch_cfg = getattr(config, "dispatch", None)
    if dispatch_cfg is not None and dispatch_cfg.enabled:
        from types import SimpleNamespace

        from routest_tpu.dispatch import (DispatchBatcher, DispatchRegistry,
                                          ReoptLoop)
        from routest_tpu.data import geo as _geo

        def _live_epoch() -> int:
            live = state.live
            if live is not None and live.router is not None:
                return int(live.router.live_epoch)
            return 0

        def _corridor_matrix(latlon, speed_mps=None):
            """(N+1, 2) lat/lon → (N+1, N+1) float32 travel SECONDS
            under the CURRENT metric: road-router shortest paths priced
            by the live leg models when the live router is armed,
            great-circle × car road factor otherwise. One unit
            everywhere, so a dispatch's baseline cost and its re-priced
            corridor cost stay comparable across metric flips."""
            latlon = np.asarray(latlon, np.float32)
            car = _geo.profile_for_vehicle("car")
            speed = float(speed_mps or dispatch_cfg.speed_mps
                          or _geo.PROFILE_SPEED_MPS[car])
            live = state.live
            if live is not None and live.ready and live.router is not None:
                legs = live.router.route_legs(latlon)
                return np.asarray(legs.duration_matrix(), np.float32)
            import jax.numpy as _jnp

            dist_m = np.asarray(_geo.distance_matrix_m(
                _jnp.asarray(latlon), _geo.PROFILE_ROAD_FACTOR[car]))
            return (dist_m / speed).astype(np.float32)

        def _sim_restart(rec) -> None:
            """plan_update → re-target the driver sim at the NEW stop
            order, replaying deterministically under the dispatch's
            stored sim_seed (None keeps the reference's random gait)."""
            if rec.latlon is None \
                    or not rec.driver_details.get("driver_name") \
                    or not rec.driver_details.get("vehicle_type"):
                return
            order = list(rec.plan.get("optimized_order") or []) \
                + list(rec.plan.get("spill_lane") or [])
            coords = [[float(rec.latlon[0][1]), float(rec.latlon[0][0])]]
            coords += [[float(rec.latlon[j + 1][1]),
                        float(rec.latlon[j + 1][0])] for j in order]
            coords.append(list(coords[0]))
            speed = float(rec.driver_details.get("speed_mps") or 1.0)
            data = {
                "route_details": {
                    "geometry": {"coordinates": coords},
                    "properties": {
                        "summary": {
                            "duration": round(rec.baseline_cost, 1),
                            "distance": round(rec.baseline_cost * speed, 1),
                            "trips": rec.plan.get("n_trips", 1),
                        },
                        "destinations": rec.destinations or [],
                    },
                },
                "driver_details": rec.driver_details,
            }
            sim.start_simulation(data, state.bus.publish,
                                 state.sim_tick_range, seed=rec.sim_seed)

        _d_registry = DispatchRegistry(max_active=dispatch_cfg.max_active)
        _d_batcher = DispatchBatcher(max_rows=dispatch_cfg.max_rows,
                                     window_s=dispatch_cfg.window_s,
                                     epoch_fn=_live_epoch)
        _d_reopt = None
        if dispatch_cfg.reopt:
            _d_reopt = ReoptLoop(
                _d_registry, _d_batcher, state.bus.publish,
                _live_epoch, _corridor_matrix,
                degrade_ratio=dispatch_cfg.degrade_ratio,
                poll_s=dispatch_cfg.reopt_poll_s,
                sim_restart=_sim_restart)
            if dispatch_cfg.reopt_poll_s > 0:
                _d_reopt.start()
        state.dispatch = SimpleNamespace(
            cfg=dispatch_cfg, registry=_d_registry, batcher=_d_batcher,
            reopt=_d_reopt, matrix_fn=_corridor_matrix,
            epoch_fn=_live_epoch, sim_restart=_sim_restart)
        app.dispatch = state.dispatch

    # Device efficiency (docs/OBSERVABILITY.md "Device efficiency &
    # goodput"): the goodput ledger is always-on accounting inside the
    # batchers; here the replica arms the throughput-regression
    # watchdog against the committed battery curve. A missing or
    # foreign-backend artifact degrades to ledger-only — surfaced via
    # /api/health and /api/efficiency, never silently.
    from routest_tpu.core.config import load_efficiency_config
    from routest_tpu.obs.efficiency import EfficiencyWatchdog, get_ledger

    app.efficiency = None
    eff_cfg = load_efficiency_config()
    if eff_cfg.enabled and eff_cfg.watchdog:
        app.efficiency = EfficiencyWatchdog(eff_cfg, recorder=recorder)
        if app.efficiency.arm():
            app.efficiency.start()

    # ── optimization ────────────────────────────────────────────────────

    @app.route("/api/request_route", methods=("POST",))
    def request_route(request):
        data = get_json(request)
        response = optimize_route(data or {})
        if not response:
            return {"error": "no response acquired from the optimizer."}, 400
        if isinstance(response, dict) and response.get("error"):
            return response, 400
        return response, 200

    @app.route("/api/optimize_route", methods=("POST",))
    def optimize_route_endpoint(request):
        payload = get_json(request) or {}
        result = optimize_route(payload)
        if isinstance(result, dict) and result.get("error"):
            return result, 400

        # Optional ML ETA — computed before persisting, as the reference
        # does (``Flaskr/routes.py:96-116``).
        if payload.get("use_ml_eta"):
            props = result.setdefault("properties", {}) or {}
            summary = _obj(props.get("summary"))
            ctx = _obj(payload.get("context"))
            try:
                age = float(_obj(payload.get("driver_details"))
                            .get("driver_age", 30) or 30)
            except (TypeError, ValueError):
                age = 30.0
            try:
                distance_m = float(summary.get("distance") or 0)
            except (TypeError, ValueError):
                distance_m = 0.0
            eta_min, eta_iso, eta_bands = state.eta.predict_eta_quantiles(
                weather=ctx.get("weather", "Sunny"),
                traffic=ctx.get("traffic", "Low"),
                distance_m=distance_m,
                pickup_time=dt.datetime.now(),
                driver_age=age,
            )
            if eta_min is not None:
                props["eta_minutes_ml"] = eta_min
                props["eta_completion_time_ml"] = eta_iso
                # Additive: calibrated uncertainty band when the serving
                # model has quantile heads (point models add nothing).
                for level, val in eta_bands.items():
                    props[f"eta_minutes_ml_{level}"] = round(val, 4)

        # Best-effort persistence: failures are logged, never fatal
        # (``Flaskr/routes.py:118-125``).
        try:
            req_id = _persist(state, payload, result)
            if req_id:
                result.setdefault("properties", {})["request_id"] = req_id
                result["properties"]["saved"] = True
                # Write-behind: the rows are journaled, not yet durable
                # at the backend — surface that honestly (the id is
                # still valid; the journal replays on recovery).
                if getattr(state.store, "degraded", False):
                    result["properties"]["degraded"] = True
        except Exception as e:
            _log.error("persist_failed", error=str(e),
                       store=state.store.kind)

        return result, 200

    @app.route("/api/optimize_route_batch", methods=("POST",))
    def optimize_route_batch_endpoint(request):
        """Batch route optimization — additive ABI.

        ``{"items": [<optimize_route bodies>], "use_ml_eta": bool}`` →
        ``{"count": N, "items": [<Feature or {"error"}>]}``. All
        multi-stop problems solve in ONE vmapped device call
        (``optimize/vrp.solve_host_batch``); with ``use_ml_eta`` every
        successful route's ETA scores in ONE model batch. Per-item
        errors come back in place; nothing here persists (batch scoring
        is an analysis surface, not dispatch — use the single endpoint
        to dispatch + save a route).
        """
        body = get_json(request) or {}
        items = body.get("items")
        if not isinstance(items, list) or not items:
            return {"error": "items must be a non-empty list"}, 400
        if len(items) > MAX_BATCH_PROBLEMS:
            return {"error": f"batch too large (max {MAX_BATCH_PROBLEMS} "
                             f"problems)"}, 400
        if not all(isinstance(it, dict) for it in items):
            return {"error": "every item must be an optimize_route body"}, 400
        results = optimize_route_batch(items)

        if body.get("use_ml_eta"):
            ok = [(i, r) for i, r in enumerate(results)
                  if isinstance(r, dict) and "error" not in r]
            if ok:
                ctx = _obj(body.get("context"))
                try:
                    minutes, iso = state.eta.predict_eta_batch(
                        weather=[ctx.get("weather", "Sunny")] * len(ok),
                        traffic=[ctx.get("traffic", "Low")] * len(ok),
                        distance_m=[
                            float((r["properties"].get("summary") or {})
                                  .get("distance") or 0) for _, r in ok],
                        pickup_time=None,
                        driver_age=[
                            float((items[i].get("driver_details") or {})
                                  .get("driver_age", 30) or 30)
                            for i, _ in ok],
                    )
                except DeadlineExceeded:
                    raise  # 504: the whole batch's budget is gone
                except Exception as e:
                    _log.error("batch_eta_failed", error=str(e))
                    minutes = None
                if minutes is not None:
                    for (i, r), m, ts in zip(ok, minutes, iso):
                        if math.isfinite(m):
                            r["properties"]["eta_minutes_ml"] = round(
                                float(m), 4)
                            r["properties"]["eta_completion_time_ml"] = str(ts)
        return {"count": len(items), "items": results}, 200

    # ── binary wire path (docs/API.md "Binary wire format") ───────────
    # Content-type-negotiated alternative representation of the two hot
    # endpoints: ``application/x-rtpu-wire`` frames in, frames out, the
    # SAME answers as JSON bit-for-bit (the prober's ``wire`` parity
    # kind holds the two to that continuously). ONE implementation per
    # endpoint serves both transports — the HTTP negotiation branch
    # below and the persistent gateway channel (serve/wirechannel.py)
    # call these handlers, which speak raw frame bytes →
    # (status, frame bytes). Transport-level failures (413/429/504,
    # gateway sheds) remain JSON; only request-level outcomes use
    # error frames.
    wire_cfg = load_wire_config()
    app.wire_config = wire_cfg
    _wire_max = int(wire_cfg.max_frame_mb * 1024 * 1024)

    def _wire_eta(payload):
        try:
            frame = wirecodec.decode_eta_request(
                payload, max_bytes=_wire_max, max_rows=131_072)
        except wirecodec.WireError as e:
            return 400, wirecodec.encode_error_frame(
                400, f"malformed batch: {e}")
        try:
            result = state.eta.predict_eta_wire(
                frame.columns["features"], frame.columns["pickup_ms"],
                blob=frame.payload("features"))
        except DeadlineExceeded:
            raise  # → 504 via the transport layer, not a 503
        except Exception as e:
            _log.error("predict_wire_failed", error=str(e))
            result = None
        if result is None:
            return 503, wirecodec.encode_error_frame(
                503, "model unavailable")
        minutes, completion_ms, bands = result
        return 200, wirecodec.encode_eta_response(minutes, completion_ms,
                                                  bands)

    def _wire_matrix(payload):
        try:
            body = wirecodec.decode_matrix_request(payload,
                                                   max_bytes=_wire_max)
        except wirecodec.WireError as e:
            return 400, wirecodec.encode_error_frame(400, str(e))
        result = travel_matrix(body)
        if "error" in result:
            return 400, wirecodec.encode_error_frame(400, result["error"])
        return 200, wirecodec.encode_matrix_response(result)

    # Path → wire handler; the worker boot hands this dict to the
    # channel server. Empty while the path is disabled: HTTP
    # negotiation answers 415 and no channel listener ever starts.
    app.wire_handlers = (
        {"/api/predict_eta_batch": _wire_eta, "/api/matrix": _wire_matrix}
        if wire_cfg.enabled else {})
    if wire_cfg.enabled:
        record_change("wire.enable",
                      detail={"paths": sorted(app.wire_handlers),
                              "channel": wire_cfg.channel})

    def _wire_negotiated(request, path):
        """None when the request is not wire content-type, else the
        finished binary (or 415) Response."""
        ct = (request.content_type or "").split(";", 1)[0].strip().lower()
        if ct != wirecodec.WIRE_CONTENT_TYPE:
            return None
        fn = app.wire_handlers.get(path)
        if fn is None:
            return json_response(
                {"error": "binary wire format disabled on this replica "
                          "(RTPU_WIRE=1 enables it)"}, 415)
        status, frame = fn(request.get_data())
        return Response(frame, status=status,
                        content_type=wirecodec.WIRE_CONTENT_TYPE)

    @app.route("/api/matrix", methods=("POST",))
    def matrix_endpoint(request):
        """Travel matrix — additive ABI (the ORS capability the
        reference rents per optimize request, ``Flaskr/utils.py:97-103``,
        exposed as a first-class API). ``{"points": [{"lat","lon"}, …],
        "road_graph": bool, "sources"/"destinations": [idx], ...}`` →
        ``{"distances_m": S×D, "durations_s": S×D}``; road matrices are
        street-network shortest paths priced by the live leg models,
        with unreachable pairs null. Also speaks the binary wire format
        by content-type (docs/API.md "Binary wire format")."""
        wired = _wire_negotiated(request, "/api/matrix")
        if wired is not None:
            return wired
        result = travel_matrix(get_json(request) or {})
        if "error" in result:
            return result, 400
        return result, 200

    # ── prediction ─────────────────────────────────────────────────────

    @app.route("/api/predict_eta", methods=("POST",))
    def predict_eta(request):
        body = get_json(request) or {}
        summary = _obj(body.get("summary"))
        try:
            distance_m = float(summary.get("distance") or 0)
            driver_age = float(body.get("driver_age", 30) or 30)
        except (TypeError, ValueError):
            return {"error": "distance/driver_age must be numeric"}, 400
        # Same type rule the batch endpoint enforces: categorical fields
        # must be strings (an unhashable dict would blow up featurization).
        for name in ("weather", "traffic"):
            if not isinstance(body.get(name, ""), str):
                return {"error": f"{name} must be a string"}, 400
        eta_min, eta_iso, eta_bands = state.eta.predict_eta_quantiles(
            weather=body.get("weather", "Sunny"),
            traffic=body.get("traffic", "Low"),
            distance_m=distance_m,
            pickup_time=body.get("pickup_time") or dt.datetime.now().isoformat(),
            driver_age=driver_age,
        )
        if eta_min is None:
            return {"error": "model unavailable"}, 503
        out = {"eta_minutes_ml": eta_min, "eta_completion_time_ml": eta_iso}
        for level, val in eta_bands.items():  # additive uncertainty band
            out[f"eta_minutes_ml_{level}"] = round(val, 4)
        return out, 200

    @app.route("/api/predict_eta_batch", methods=("POST",))
    def predict_eta_batch(request):
        """Batched ETA scoring — the serving-side 10k preds/sec path.

        Additive to the reference ABI (its ``/predict_eta`` is one row
        per request, ``Flaskr/routes.py:365-383``). Accepts either form:

        - columnar (fast path): ``{"distance_m": [..N..], "weather":
          [..]|str, "traffic": [..]|str, "driver_age": [..]|num,
          "pickup_time": [..]|iso}`` — scalars broadcast to N;
        - row-shaped: ``{"items": [{summary:{distance}, weather, traffic,
          pickup_time, driver_age}, ...]}`` (each item = the single-row
          request body).

        Response: ``{"count": N, "eta_minutes_ml": [..],
        "eta_completion_time_ml": [..]}`` / 503 when no model serves.
        Also speaks the binary wire format by content-type
        (docs/API.md "Binary wire format").
        """
        wired = _wire_negotiated(request, "/api/predict_eta_batch")
        if wired is not None:
            return wired
        body = get_json(request) or {}
        try:
            if "items" in body:
                items = body["items"]
                if not isinstance(items, list) or not items:
                    return {"error": "items must be a non-empty list"}, 400
                if len(items) > 131_072:  # O(1), BEFORE any per-row work
                    return {"error": "batch too large (max 131072 rows)"}, 400
                distance = [float(((it.get("summary") or {}).get("distance"))
                                  or it.get("distance_m") or 0)
                            for it in items]
                # `or` (not .get default) so explicit nulls coerce to the
                # defaults exactly like the columnar form / single endpoint
                weather = [it.get("weather") or "Sunny" for it in items]
                traffic = [it.get("traffic") or "Low" for it in items]
                age = [float(it.get("driver_age", 30) or 30) for it in items]
                pickup = [it.get("pickup_time") for it in items]
            else:
                distance = body.get("distance_m")
                if not isinstance(distance, list) or not distance:
                    return {"error": "distance_m must be a non-empty list "
                                     "(or send items=[...])"}, 400
                if len(distance) > 131_072:  # O(1), BEFORE per-row work
                    return {"error": "batch too large (max 131072 rows)"}, 400
                distance = [float(d or 0) for d in distance]
                n = len(distance)

                def col(name, default):
                    v = body.get(name, default)
                    if isinstance(v, list):
                        if len(v) != n:
                            raise ValueError(
                                f"{name} has {len(v)} entries, expected {n}")
                        return v
                    return [v] * n  # scalar broadcasts

                weather = [w or "Sunny" for w in col("weather", "Sunny")]
                traffic = [t or "Low" for t in col("traffic", "Low")]
                age = [float(a or 30) for a in col("driver_age", 30.0)]
                pickup = col("pickup_time", None)
            # Bad entry TYPES are client errors: catch them here as 400,
            # not downstream as a 503 that reads like a model outage.
            for name, vals in (("weather", weather), ("traffic", traffic)):
                for v in vals:
                    if not isinstance(v, str):
                        raise ValueError(f"{name} entries must be strings")
            for p in pickup:
                if p is not None and not isinstance(p, str):
                    raise ValueError("pickup_time entries must be ISO strings")
        except (TypeError, ValueError, AttributeError) as e:
            # AttributeError: non-dict items / summary ("items": ["foo"])
            return {"error": f"malformed batch: {e}"}, 400
        try:
            minutes, iso, bands = state.eta.predict_eta_batch(
                weather=weather, traffic=traffic, distance_m=distance,
                pickup_time=pickup, driver_age=age, return_quantiles=True)
        except DeadlineExceeded:
            raise  # → 504 via the WSGI layer, not a 503 "model outage"
        except Exception as e:
            _log.error("predict_batch_failed", error=str(e))
            minutes = None
        if minutes is None:
            return {"error": "model unavailable"}, 503
        # Non-finite rows serialize as null in BOTH columns (NaN is
        # invalid JSON; its timestamp is NaT) — the batch-shaped analog
        # of the single-row (None, None) contract. Serialization is
        # vectorized (np.round + tolist) with the per-element fallback
        # only on rows that actually carry NaN: the per-row python loop
        # was the single largest cost of serving quantile bands (a
        # measured ~18 ms per 4096-row response vs ~5 ms vectorized —
        # most of the old point-vs-quantile throughput gap lived HERE,
        # not in the model's extra heads; a CPU reading).
        minutes = np.asarray(minutes, np.float64)
        finite = np.isfinite(minutes)
        all_finite = bool(finite.all())
        rounded = np.round(minutes, 4)
        out = {"count": len(distance)}
        if all_finite:
            out["eta_minutes_ml"] = rounded.tolist()
            out["eta_completion_time_ml"] = np.asarray(iso).tolist()
        else:
            out["eta_minutes_ml"] = [float(m) if ok else None
                                     for m, ok in zip(rounded, finite)]
            out["eta_completion_time_ml"] = [str(s) if ok else None
                                             for s, ok in zip(iso, finite)]
        for level, vals in bands.items():  # additive uncertainty columns
            # null where the MEDIAN row is null, and also where the band
            # value itself is non-finite (NaN/Inf are invalid JSON).
            vals = np.asarray(vals, np.float64)
            ok_col = finite & np.isfinite(vals)
            col = np.round(vals, 4)
            out[f"eta_minutes_ml_{level}"] = (
                col.tolist() if bool(ok_col.all())
                else [float(v) if ok else None
                      for v, ok in zip(col, ok_col)])
        return out, 200

    @app.route("/api/predict", methods=("POST",))
    def predict_alias(request):
        """The Laravel-proxy contract (BASELINE.json north star: "the
        Laravel backend's predict endpoint proxies to a pjit-sharded JAX
        inference server"): ONE endpoint a proxy can point at, accepting
        either the single-row ``/api/predict_eta`` body or the batch
        forms, dispatched on shape. ``request.get_data`` is cached by
        werkzeug, so delegating re-parses safely."""
        body = get_json(request) or {}
        if "items" in body or isinstance(body.get("distance_m"), list):
            return predict_eta_batch(request)
        return predict_eta(request)

    # ── dispatch ───────────────────────────────────────────────────────

    @app.route("/api/dispatch", methods=("POST",))
    def dispatch_endpoint(request):
        """Batched VRP dispatch — the paper's workload as a first-class
        API (docs/API.md "Dispatch").

        Geographic mode (reference-shaped body): ``{"source_point",
        "destination_points": [{lat, lon, payload}, …],
        "driver_details", "time_windows": [[open_s, close_s|null],
        …]?, "confirm": bool?, "sim_seed": int?}`` — stops price into
        travel seconds under the current metric and solve through the
        shared dispatch batcher (time-window + demand-spillover VRP).

        Matrix mode (prober/bench surface): ``{"matrix": (N+1)×(N+1),
        "demands": [N], "capacity", "max_distance",
        "time_windows"?}`` — the caller brings the cost matrix, so the
        served plan is directly comparable against a host re-solve of
        the SAME matrix (the dispatch probe's oracle check).

        ``{"complete": "<dispatch_id>"}`` retires an active dispatch.

        Concurrent requests merge into ONE padded device batch; with
        ``confirm`` the plan registers for live re-optimization
        (``plan_update`` over SSE on corridor degradation) and — when
        the body carries a driver — starts the driver simulation.
        """
        svc = state.dispatch
        if svc is None:
            return {"error": "dispatch disabled (RTPU_DISPATCH=0)"}, 503
        body = get_json(request) or {}

        done = body.get("complete")
        if done is not None:
            if not isinstance(done, str):
                return {"error": "complete must be a dispatch id"}, 400
            if not svc.registry.complete(done):
                return {"error": "not found"}, 404
            return {"status": "completed", "dispatch_id": done}, 200

        seed = body.get("sim_seed")
        if seed is not None and not isinstance(seed, int):
            return {"error": "sim_seed must be an integer"}, 400

        if "matrix" in body:
            parsed = _parse_matrix_dispatch(body, svc.cfg.max_stops)
        else:
            parsed = _parse_geo_dispatch(body, svc.cfg.max_stops)
        if "error" in parsed:
            return parsed, 400

        from routest_tpu.dispatch import DispatchProblem, plan_cost

        mode = parsed["mode"]
        if mode == "geographic":
            speed = parsed["speed"]
            matrix = svc.matrix_fn(parsed["latlon"], speed_mps=speed)
            max_cost = parsed["max_dist"] / speed  # meters → seconds
        else:
            matrix = parsed["matrix"]
            max_cost = parsed["max_cost"]
        problem = DispatchProblem(matrix, parsed["demands"],
                                  parsed["capacity"], max_cost,
                                  parsed["tw_open"], parsed["tw_close"])
        try:
            plan = svc.batcher.solve([problem])[0]
        except TimeoutError:
            return {"error": "dispatch solver saturated; retry"}, 503
        _m_dispatch_requests.labels(mode=mode).inc()
        cost = plan_cost(matrix, plan)
        out = {"mode": mode, "plan": plan,
               "cost": round(float(cost), 3), "epoch": svc.epoch_fn()}

        if body.get("confirm"):
            driver = dict(parsed.get("driver_details") or {})
            if mode == "geographic":
                driver.setdefault("speed_mps", round(speed, 3))
            rec = svc.registry.register(
                channel=driver.get("driver_name"),
                latlon=parsed.get("latlon"),
                demands=parsed["demands"],
                capacity=parsed["capacity"], max_cost=max_cost,
                plan=plan, baseline_cost=cost, epoch=out["epoch"],
                tw_open=parsed["tw_open"], tw_close=parsed["tw_close"],
                sim_seed=seed, driver_details=driver,
                destinations=parsed.get("destinations"))
            out["dispatch_id"] = rec.id
            out["channel"] = rec.channel
            svc.sim_restart(rec)  # no-op without a named driver
        return out, 200

    @app.route("/api/dispatch", methods=("GET",))
    def dispatch_state(request):
        # Dispatch surface state: active registry, batcher merge
        # stats, re-optimization loop snapshot — the bench's and an
        # operator's one-stop coherency view.
        svc = state.dispatch
        if svc is None:
            return {"enabled": False}, 200
        out = {"enabled": True, "epoch": svc.epoch_fn(),
               "registry": svc.registry.snapshot(),
               "batcher": svc.batcher.stats()}
        if svc.reopt is not None:
            out["reopt"] = svc.reopt.snapshot()
        return out, 200

    # ── live tracking ──────────────────────────────────────────────────

    @app.route("/api/confirm_route", methods=("POST",))
    def confirm_route(request):
        data = get_json(request)
        if not data or "route_details" not in data or "driver_details" not in data:
            return {"error": "driver_details and route_details required"}, 400
        # Validate the structure the simulator dereferences up front —
        # a daemon thread dying on KeyError would 200 then go silent.
        route = _obj(data["route_details"])
        driver = _obj(data["driver_details"])
        coords = _obj(route.get("geometry")).get("coordinates")
        summary = _obj(route.get("properties")).get("summary")
        if not isinstance(coords, list) or not coords or not isinstance(summary, dict):
            return {"error": "route_details must carry geometry.coordinates and properties.summary"}, 400
        if not driver.get("driver_name") or not driver.get("vehicle_type"):
            return {"error": "driver_details must carry driver_name and vehicle_type"}, 400
        if "destinations" not in _obj(route.get("properties")):
            return {"error": "route_details.properties.destinations required"}, 400
        # Optional deterministic replay: a caller-supplied sim_seed
        # makes the tick jitter (and therefore the publish cadence)
        # bit-identical across runs — scenario tooling and tests lean
        # on it; unseeded requests keep the reference's random gait.
        seed = data.get("sim_seed")
        if seed is not None and not isinstance(seed, int):
            return {"error": "sim_seed must be an integer"}, 400
        sim.start_simulation(data, state.bus.publish, state.sim_tick_range,
                             seed=seed)
        # Dispatch citizenship: a confirmed reference-shaped route also
        # registers for live re-optimization when the body carries
        # enough of the problem to re-solve (lat/lon stops + finite
        # constraints); the optional sim_seed rides along so a
        # re-dispatch sim restart replays deterministically. Bodies
        # without re-solvable structure keep the reference behavior.
        out = {"status": "route simulation initialized."}
        svc = state.dispatch
        if svc is not None:
            try:
                rec = _register_confirmed_route(svc, data, seed)
            except Exception as e:  # best-effort: never fail the confirm
                rec = None
                _log.debug("dispatch_register_skipped",
                           error=f"{type(e).__name__}: {e}")
            if rec is not None:
                out["dispatch_id"] = rec.id
        return out, 200

    @app.route("/api/update_tracker", methods=("POST",))
    def update_tracker(request):
        data = get_json(request)
        if not data:
            return {"error": "no data provided in the publish request."}, 400
        try:
            event = sim.format_sse_data(data)
        except (KeyError, ValueError, TypeError, OverflowError) as e:
            # TypeError: right fields, wrong types (a dict where the ISO
            # pickup_time string belongs); OverflowError: timedelta on an
            # infinite/huge duration — all the same client error.
            return {"error": f"malformed tracker payload: {e}"}, 400
        state.bus.publish(str(data.get("route_id")), event)
        return {"status": "published"}, 200

    @app.route("/api/probe", methods=("POST",))
    def probe(request):
        """Probe-observation ingest over HTTP — the loadgen-facing twin
        of the bus-native probe stream. The handler only PUBLISHES to
        the probe channel; every replica (this one included) folds the
        event through its own bus subscription, so HTTP- and bus-
        sourced probes take one code path into the estimator and the
        whole fleet sees every observation exactly once."""
        data = get_json(request)
        if not data:
            return {"error": "no probe data provided."}, 400
        obs = data.get("obs") if isinstance(data.get("obs"), list) \
            else data.get("observations")
        if not isinstance(obs, list) or not obs:
            return {"error": "obs must be a non-empty list of "
                             "[edge_id, speed_mps] pairs"}, 400
        if len(obs) > 4096:
            return {"error": "probe batch too large (max 4096)"}, 400
        for o in obs:
            if (not isinstance(o, (list, tuple)) or len(o) != 2
                    or not isinstance(o[0], int)
                    or not isinstance(o[1], (int, float))):
                return {"error": "each observation must be "
                                 "[edge_id, speed_mps]"}, 400
        channel = (state.live.cfg.channel if state.live is not None
                   else os.environ.get("RTPU_LIVE_CHANNEL",
                                       "rtpu.probes"))
        event = {"t": float(data.get("t") or time.time()),
                 "driver": str(data.get("driver") or "http"),
                 "obs": [[int(e), float(s)] for e, s in obs]}
        # Cross-region replication tag (live/bridge.py): an HTTP-
        # sourced frame that already crossed a bridge keeps its origin
        # stamp, so republishing it here cannot re-enter the ring.
        if data.get("origin_region") is not None:
            event["origin_region"] = str(data["origin_region"])
        if data.get("hour") is not None:
            try:
                event["hour"] = int(data["hour"]) % 24
            except (TypeError, ValueError):
                return {"error": "hour must be an integer"}, 400
        state.bus.publish(channel, event)
        return {"status": "published", "count": len(obs)}, 200

    @app.route("/api/live", methods=("GET",))
    def live_state(request):
        """Live-traffic surface: ingest/customizer/retrain state, the
        serving metric epoch, and — with ``?metric=1`` — the blended
        per-edge seconds themselves (the array the bench's scipy
        oracle re-solves against)."""
        if state.live is None:
            return {"enabled": False}, 200
        out = state.live.snapshot()
        if request.args.get("metric") and state.live.router is not None:
            metric = state.live.router.live_metric_export()
            if metric is not None:
                out["edge_time_s"] = [round(float(v), 4) for v in metric]
                out["n_edges"] = len(metric)
        return out, 200

    @app.route("/api/realtime_feed", methods=("GET",))
    def realtime_feed(request):
        channel = request.args.get("channel", "sse")
        try:
            max_events = int(request.args["max_events"]) \
                if "max_events" in request.args else None
        except ValueError:
            max_events = None
        # SSE resume: EventSource sends Last-Event-ID on reconnect;
        # buses with a replay ring (in-memory) resume from it, others
        # (Redis pub/sub has no history) just start live.
        last_id = None
        raw_lei = (request.headers.get("Last-Event-ID")
                   or request.args.get("last_event_id"))
        if raw_lei:
            try:
                last_id = int(raw_lei)
            except ValueError:
                last_id = None
        try:
            subscription = state.bus.subscribe(channel,
                                               last_event_id=last_id)
        except TypeError:
            subscription = state.bus.subscribe(channel)
        return Response(
            sse_stream(subscription, max_events=max_events),
            mimetype="text/event-stream",
            headers={"Cache-Control": "no-cache", "X-Accel-Buffering": "no"},
        )

    # ── history ────────────────────────────────────────────────────────

    @app.route("/api/history", methods=("GET",))
    def history(request):
        try:
            limit = int(request.args.get("limit", 20))
        except ValueError:
            limit = 20
        limit = max(1, min(limit, 100))
        # Additive filter: ?engine=ml|default narrows server-side (the
        # dashboard's ML badge filter otherwise pages through everything).
        engine = request.args.get("engine")
        if engine is not None and engine not in ("ml", "default"):
            return {"error": "engine must be 'ml' or 'default'"}, 400
        try:
            rows = state.store.list_history(limit, engine=engine)
        except StoreUnavailable:
            # Degraded-mode read: the store's circuit breaker is open —
            # fail FAST with an explicit marker instead of stacking
            # timeouts against a dead backend (docs/ROBUSTNESS.md).
            return {"items": [], "degraded": True}, 200
        except Exception as e:
            return {"error": f"history fetch failed: {e}"}, 500

        items = []
        for rr in rows:
            res = rr.get("route_results") or []
            first = res[0] if res else {}
            stops = rr.get("stops") or {}
            dest_ids = stops.get("destination_ids") or []
            items.append({
                "request_id": rr["id"],
                "created_at": rr.get("request_time"),
                "origin_id": rr.get("origin_id"),
                "dest_count": len(dest_ids),
                "total_distance": first.get("total_distance"),
                "total_duration": first.get("total_duration"),
                "optimized": bool(first.get("optimized_order") or []),
                "engine": rr.get("engine") or "default",
                "vehicle_id": rr.get("vehicle_id"),
                "eta_minutes_ml": first.get("eta_minutes_ml"),
                "eta_completion_time_ml": first.get("eta_completion_time_ml"),
            })
        return {"items": items}, 200

    @app.route("/api/history/<req_id>", methods=("GET",))
    def history_detail(request, req_id):
        try:
            row = state.store.get_request(req_id)
        except StoreUnavailable:
            return {"error": "store degraded; retry later",
                    "degraded": True}, 503
        except Exception as e:
            return {"error": f"history fetch failed: {e}"}, 500
        if row is None:
            return {"error": "not found"}, 404
        results = row.get("route_results") or []
        return {
            "request": {
                "id": row["id"],
                "origin_id": row.get("origin_id"),
                "stops": row.get("stops") or {},
                "status": row.get("status"),
                "request_time": row.get("request_time"),
                "engine": row.get("engine") or "default",
                "vehicle_id": row.get("vehicle_id"),
                "driver_age": row.get("driver_age"),
            },
            "result": results[0] if results else None,
        }, 200

    @app.route("/api/history/<req_id>", methods=("DELETE",))
    def delete_history(request, req_id):
        # The one destructive route: bearer-gated when ROUTEST_AUTH=require
        # (the reference never gated it; SURVEY.md §2.2 notes its auth
        # scaffold is bypassed at runtime).
        if state.auth.required and state.auth.user_from_request(request) is None:
            return auth_mod.UNAUTHENTICATED
        try:
            deleted = state.store.delete_request(req_id)
        except StoreUnavailable:
            return {"error": "store degraded; retry later",
                    "degraded": True}, 503
        except Exception as e:
            return {"error": f"delete failed: {e}"}, 500
        if not deleted:
            return {"error": "not found"}, 404
        return Response("", 204)

    # ── meta ───────────────────────────────────────────────────────────

    @app.route("/api/locations", methods=("GET",))
    def locations(request):
        # Laravel parity (``routes/api.php:7-9``): plain array of rows.
        return locations_table(), 200

    # ── pages (the map-app capability, served hermetically) ────────────
    # Same layout as the reference frontend: "/" = MVP point-to-point map
    # (app/page.js), "/ui" = dispatch dashboard (app/ui/page.jsx),
    # "/health" = status page (app/health/page.jsx).

    _static_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "static")
    _pages = {}
    for _name in ("dashboard", "mvp", "health"):
        with open(os.path.join(_static_dir, _name + ".html"), "rb") as f:
            _pages[_name] = f.read()  # immutable assets: read once, serve cached
    # Front-end logic modules as real shipped files so CI can execute
    # the exact served bytes (tests/test_dashboard_logic.py via
    # utils/minijs.py) — the reference splits these between page
    # components (app/ui/page.jsx) and lib/ (lib/classify.js).
    _lib_dir = os.path.join(_static_dir, "lib")
    _lib_files = {}
    for _name in sorted(os.listdir(_lib_dir)):
        if _name.endswith(".js"):
            with open(os.path.join(_lib_dir, _name), "rb") as f:
                _lib_files[_name] = f.read()

    @app.route("/lib/<name>", methods=("GET",))
    def lib_js(request, name):
        body = _lib_files.get(name)
        if body is None:
            return {"error": "not found"}, 404
        return Response(body, mimetype="text/javascript")

    @app.route("/", methods=("GET",))
    def mvp_page(request):
        return Response(_pages["mvp"], mimetype="text/html")

    @app.route("/ui", methods=("GET",))
    def dashboard(request):
        return Response(_pages["dashboard"], mimetype="text/html")

    @app.route("/health", methods=("GET",))
    def health_page(request):
        return Response(_pages["health"], mimetype="text/html")

    @app.route("/api/ping", methods=("GET",))
    def ping(request):
        return {"ok": True, "service": "route-optimizer"}, 200

    @app.route("/up", methods=("GET",))
    def up(request):
        # Laravel's stock health endpoint (reference bootstrap/app.php:12):
        # plain HTTP 200, no body contract beyond "the app is up".
        return Response(b"OK", mimetype="text/html")

    @app.route("/api/version", methods=("GET",))
    def version_info(request):
        # Change-delivery identity (docs/ROBUSTNESS.md "Safe change
        # delivery"): which build and which model BYTES this replica is
        # serving, cheap enough to poll — the rollout controller's
        # version-skew view and the gateway's /api/autoscale `versions`
        # section read it.
        from routest_tpu.obs import build_info

        eta = state.eta
        return {
            "version_label": os.environ.get("RTPU_VERSION"),
            "build": build_info(),
            "model": {
                "available": eta.available,
                "generation": eta.generation,
                "fingerprint": eta.fingerprint,
                "path": eta.model_path,
                "kernel": eta.kernel,
                "quantiles": list(eta.quantiles),
                "loaded_unix": eta.loaded_unix,
            },
        }, 200

    @app.route("/api/metrics", methods=("GET",))
    def metrics(request):
        # TPU-era observability (SURVEY.md §5.5): per-route latency
        # percentiles + batcher gauges, additive to the reference ABI,
        # plus the unified process registry (batcher stage histograms,
        # store/netbus op latencies, train metrics) — ISSUE 2's one-API
        # view. ?format=prometheus renders the same data in the
        # exposition format every scraper speaks.
        from routest_tpu.obs import get_registry

        snapshot = {
            "http": app.request_stats.snapshot(),
            "batcher": state.eta.stats,
        }
        if request.args.get("format") == "prometheus":
            text = _prometheus_text(snapshot) + \
                get_registry().prometheus_text()
            return Response(text, 200,
                            mimetype="text/plain; version=0.0.4")
        snapshot["registry"] = get_registry().snapshot()
        return snapshot, 200

    @app.route("/api/trace", methods=("GET",))
    def trace_dump(request):
        # Span flight recorder (bounded ring; RTPU_OBS_* knobs): raw
        # span JSON by default; ?format=chrome emits Trace Event JSON
        # loadable in chrome://tracing / Perfetto; ?trace_id= narrows to
        # one request's tree; ?limit=N tails the buffer.
        from routest_tpu.obs import to_chrome_trace
        from routest_tpu.obs.trace import get_tracer

        buf = get_tracer().buffer
        spans = buf.snapshot(trace_id=request.args.get("trace_id") or None)
        raw_limit = request.args.get("limit", "")
        if raw_limit.isdigit():
            spans = spans[-int(raw_limit):]
        import json as _json

        payload = (to_chrome_trace(spans)
                   if request.args.get("format") == "chrome"
                   else {"count": len(spans), "dropped": buf.dropped,
                         "spans": spans})
        # default=str: span attrs are caller-supplied (numpy scalars,
        # exceptions) — a dump endpoint must render them, not 500.
        return Response(_json.dumps(payload, default=str), 200,
                        mimetype="application/json")

    @app.route("/api/slo", methods=("GET",))
    def slo_state(request):
        # Burn-rate alert surface (docs/OBSERVABILITY.md "SLOs &
        # burn-rate alerts"): per-objective state machine, fast/slow
        # burns, remaining error budget. A request forces a fresh tick
        # so the answer reflects NOW, not the last ticker wakeup.
        if app.slo is None:
            return {"enabled": False}, 200
        app.slo.tick()
        return app.slo.snapshot(), 200

    @app.route("/api/efficiency", methods=("GET",))
    def efficiency_state(request):
        # Device goodput surface (docs/OBSERVABILITY.md "Device
        # efficiency & goodput"): per-program real/padded/cached row
        # totals, live per-bucket goodput windows, and the watchdog's
        # pin/verdict state. A request forces a fresh watchdog tick so
        # the verdicts reflect NOW, not the last ticker wakeup.
        out = {"enabled": get_ledger().enabled,
               "ledger": get_ledger().snapshot()}
        wd = app.efficiency
        if wd is None:
            out["watchdog"] = {"armed": False,
                               "status": "disabled"
                               if not eff_cfg.watchdog else "unarmed"}
        else:
            if wd.armed:
                wd.tick()
            out["watchdog"] = wd.snapshot()
        return out, 200

    @app.route("/api/changes", methods=("GET",))
    def changes_query(request):
        # Change-ledger surface (docs/OBSERVABILITY.md "Change ledger
        # & incident correlation"): newest-first state-change events
        # with label filtering — ?kind= substring, ?replica=/?version=
        # /?region=/?bucket= exact, ?since= unix cut, ?limit= cap.
        def _num(name):
            raw = request.args.get(name)
            if not raw:
                return None
            try:
                return float(raw)
            except ValueError:
                return None

        limit = _num("limit")
        out = app.change_ledger.query(
            kind=request.args.get("kind") or None,
            replica=request.args.get("replica") or None,
            version=request.args.get("version") or None,
            region=request.args.get("region") or None,
            bucket=request.args.get("bucket") or None,
            since=_num("since"),
            limit=int(limit) if limit else None)
        out["ledger"] = app.change_ledger.snapshot()
        return out, 200

    @app.route("/api/incidents", methods=("GET",))
    def incidents_query(request):
        # Incident roll-up (docs/OBSERVABILITY.md "Change ledger &
        # incident correlation"): recent flight-recorder pages, each
        # with the suspect changes ranked against its paging scope.
        from routest_tpu.obs.recorder import get_recorder as _get_rec

        incidents = _get_rec().incidents_snapshot()
        return {"enabled": app.change_ledger.enabled,
                "count": len(incidents), "incidents": incidents}, 200

    @app.route("/api/timeline", methods=("GET",))
    def timeline_query(request):
        # Metric history (docs/OBSERVABILITY.md "Metric timeline"):
        # windowed deltas/percentiles from the bounded in-process
        # rings. ?family= substring-filters, ?window= trims to the
        # trailing seconds, ?step= picks the covering resolution.
        if app.timeline is None:
            return {"enabled": False}, 200

        def _num(name):
            raw = request.args.get(name)
            if not raw:
                return None
            try:
                return float(raw)
            except ValueError:
                return None

        out = app.timeline.query(
            family=request.args.get("family") or None,
            window_s=_num("window"), step_s=_num("step"))
        out["enabled"] = True
        if app.watcher is not None:
            out["watcher"] = app.watcher.snapshot()
        return out, 200

    @app.route("/api/debug/profile", methods=("POST",))
    def debug_profile(request):
        # Manual on-path profile trigger (docs/OBSERVABILITY.md
        # "Triggered profiling"): arms a bounded stack-sample capture;
        # the result lands as a flight-recorder bundle (profile.folded
        # + profile.json). 202 armed / 409 when a capture is already
        # running or the per-process budget is spent.
        if app.profiler is None:
            return {"error": "profiler disabled"}, 503
        body = get_json(request) or {}
        duration = body.get("duration_s")
        if duration is not None and not isinstance(duration, (int, float)):
            return {"error": "duration_s must be a number"}, 400
        armed = app.profiler.arm("manual_api", {"source": "api"},
                                 duration_s=duration)
        return ({"armed": armed, "profiler": app.profiler.snapshot()},
                202 if armed else 409)

    @app.route("/api/debug/probe_subgraph", methods=("GET",))
    def probe_subgraph(request):
        # The blackbox prober's oracle feed (docs/OBSERVABILITY.md
        # "Synthetic probing & correctness SLOs"): the road graph's
        # edge topology in graph edge order — the SAME order
        # /api/live?metric=1 exports its per-edge seconds in — plus
        # the probe waypoints' snapped node indices and snap
        # distances, so an external scipy Dijkstra can re-derive the
        # served answers exactly. Fetched once at prober arm time;
        # bounded by RTPU_PROBER_SUBGRAPH_MAX_EDGES (a metro-scale
        # graph is armed out-of-band, not shipped per request).
        from routest_tpu.core.config import load_prober_config
        from routest_tpu.optimize import road_router as _rr

        router = _rr._default_router
        if router is None:
            return {"error": "no road router built"}, 503
        n_edges = int(len(router.senders))
        max_edges = load_prober_config().subgraph_max_edges
        if n_edges > max_edges:
            return {"error": f"graph too large to export ({n_edges} "
                             f"edges > RTPU_PROBER_SUBGRAPH_MAX_EDGES="
                             f"{max_edges})"}, 413
        latlon = []
        for raw in request.args.getlist("wp"):
            lat, sep, lon = raw.partition(",")
            try:
                if not sep:
                    raise ValueError(raw)
                latlon.append((float(lat), float(lon)))
            except ValueError:
                return {"error": f"malformed wp {raw!r}: want "
                                 "lat,lon"}, 400
        out = {
            "nodes": int(router.n_nodes),
            "edges": n_edges,
            "senders": np.asarray(router.senders).tolist(),
            "receivers": np.asarray(router.receivers).tolist(),
            "snapped": [],
            "snap_m": [],
        }
        if latlon:
            from routest_tpu.data.road_graph import haversine_np

            pts = np.asarray(latlon, np.float32)
            snapped = np.asarray(router.snap(pts), np.int64)
            snap_m = haversine_np(
                pts[:, 0].astype(np.float64),
                pts[:, 1].astype(np.float64),
                router.coords[snapped, 0], router.coords[snapped, 1])
            out["snapped"] = snapped.tolist()
            out["snap_m"] = [round(float(v), 3) for v in snap_m]
        return out, 200

    @app.route("/api/debug/snapshot", methods=("POST",))
    def debug_snapshot(request):
        # Manual postmortem trigger (same bundle the automatic
        # triggers write). force=True: an operator asking for evidence
        # bypasses the crash-loop rate limit; the disk bounds hold.
        from routest_tpu.obs.recorder import get_recorder as _gr

        rec = _gr()
        bundle = rec.trigger("manual_api", {"source": "api"}, force=True)
        if bundle is None:
            return {"error": "recorder disabled or bundle write failed",
                    "recorder": rec.snapshot()}, 503
        return {"bundle": bundle, "recorder": rec.snapshot()}, 200

    @app.route("/api/health", methods=("GET",))
    def health(request):
        t0 = time.time()
        bus_ok = state.bus.ping()
        bus_res = {"status": "ok" if bus_ok else "error",
                   "latency_ms": int((time.time() - t0) * 1000),
                   "backend": state.bus.kind}
        t0 = time.time()
        store_ok = state.store.ping()
        store_res = {"status": "ok" if store_ok else "error",
                     "latency_ms": int((time.time() - t0) * 1000),
                     "backend": state.store.kind}
        # Degraded-mode visibility: breaker state + journal depth when
        # the store is wrapped in the resilience layer (always, via
        # make_store). A store with journaled writes is "degraded", not
        # "ok" — readers must know history may lag.
        resilience = getattr(state.store, "resilience", None)
        if resilience is not None:
            store_res["resilience"] = resilience()
            if store_ok and getattr(state.store, "degraded", False):
                store_res["status"] = "degraded"
        # The routing engine is in-process now: report it with a trivial
        # self-check instead of probing ORS over the internet.
        engine_res = {"status": "ok" if state.eta is not None else "error",
                      "latency_ms": 0, "engine": "jax-tpu"}
        # Device topology (fleet placement): how many chips THIS
        # replica actually owns, mesh axis shapes when the batch is
        # sharded, and the placement slice label — the rollout health
        # gate and an operator's skew check read it here.
        if state.eta is not None:
            engine_res["mesh"] = state.eta.mesh_info()
        # Road-router gauge (only when a router has been built — probing
        # would otherwise build the 2k graph on a health check): which
        # leg pricers are live, over what graph.
        from routest_tpu.optimize import road_router as _rr

        if _rr._default_router is not None:
            r = _rr._default_router
            engine_res["road_router"] = {
                "nodes": int(r.n_nodes),
                "edges": int(len(r.senders)),
                "leg_cost_model": r.leg_cost_model,
                "transformer": bool(r.has_transformer),
                **r.solver_info,
            }
        # Device-efficiency gauge: the goodput watchdog's armed state.
        # A degraded watchdog (missing/foreign-backend artifact) is the
        # LOUD surface the ledger-only fallback promises — it shows up
        # here, not just behind /api/efficiency.
        if get_ledger().enabled or app.efficiency is not None:
            engine_res["efficiency"] = (
                app.efficiency.health() if app.efficiency is not None
                else {"ledger": get_ledger().enabled,
                      "watchdog": "disabled"})
        # Live-traffic gauge: armed/ready state + estimator coverage +
        # serving metric epoch (absent entirely when RTPU_LIVE is off —
        # the frozen-world health shape is unchanged).
        if state.live is not None:
            live_snap = state.live.snapshot()
            engine_res["live"] = {
                "ready": live_snap.get("ready", False),
                "epoch": live_snap.get("epoch", 0),
                "edges_observed": live_snap.get(
                    "ingest", {}).get("edges_observed", 0),
                "confidence_mean": live_snap.get(
                    "ingest", {}).get("confidence_mean", 0.0),
                "flips": live_snap.get(
                    "customize", {}).get("flips", 0),
                **({"error": live_snap["error"]}
                   if live_snap.get("error") else {}),
            }
        model_res = {"status": "ok" if state.eta.available else "degraded",
                     "generation": state.eta.generation,
                     "fingerprint": state.eta.fingerprint,
                     # Scoring-artifact identity (mirrors the
                     # road_router block): kernel path, compute dtype,
                     # AOT buckets, win-bucket provenance.
                     "scoring": state.eta.scoring_info(),
                     **({"error": state.eta.load_error}
                        if state.eta.load_error else {})}

        parts = (bus_res["status"], store_res["status"], engine_res["status"],
                 model_res["status"])
        overall = "ok" if all(s == "ok" for s in parts) else "degraded"

        import jax

        payload = {
            "backend": True,
            "checks": {
                "engine": engine_res,
                "redis": bus_res,
                "supabase": store_res,
                "model": model_res,
                "tpu": {
                    "devices": [str(d) for d in jax.devices()],
                    "memory": _device_memory(jax),
                    "batcher": state.eta.stats,
                    "uptime_s": int(time.time() - state.started),
                    **_tpu_roofline(jax),
                },
            },
            "db": store_ok,
            "osrm": engine_res["status"] in ("ok", "degraded"),
            "redis": bus_ok,
            "tiles": _tiles_status(state),
            "status": overall,
            "version": state.config.serve.version,
        }
        return payload, 200  # always 200: degraded-not-down

    _warm_optimizer()
    return app


def _tiles_status(state: ServerState):
    """The reference's health route actually fetches a map tile from
    OSM/Carto (``frontend/map-app/app/api/health/route.js:36-49``).
    The built-in dashboard renders a dependency-free SVG basemap, so
    with no tile server configured the honest answer is ``"static"``
    rather than a hardcoded ``true``; when ``ROUTEST_TILE_URL`` names a
    tile endpoint (e.g. a self-hosted ``/0/0/0.png``) it is probed for
    real, cached for 30 s so health polls don't hammer it."""
    url = os.environ.get("ROUTEST_TILE_URL")
    if not url:
        return "static"
    now = time.time()
    checked, result = state._tiles_cache
    if result is not None and now - checked < 30.0:
        return result
    import http.client
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=2.0) as resp:
            ok = 200 <= resp.status < 400
    except (urllib.error.URLError, http.client.HTTPException,
            OSError, ValueError):
        # URLError: unreachable; HTTPException: a server speaking
        # non-HTTP (BadStatusLine etc.) — health stays degraded-not-down
        ok = False
    state._tiles_cache = (now, ok)
    return ok


def _prometheus_text(snapshot: dict) -> str:
    """metrics snapshot → Prometheus exposition format (text/plain
    0.0.4). Route labels are sanitized; numeric leaves only."""

    def esc(v: str) -> str:
        return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")

    lines = [
        "# HELP routest_http_uptime_seconds Server uptime.",
        "# TYPE routest_http_uptime_seconds gauge",
        f"routest_http_uptime_seconds "
        f"{snapshot['http'].get('uptime_s', 0)}",
    ]
    route_keys = ("count", "errors", "mean_ms", "p50_ms", "p95_ms", "p99_ms")
    for key in route_keys:
        metric = f"routest_http_route_{key}"
        kind = "counter" if key in ("count", "errors") else "gauge"
        lines.append(f"# TYPE {metric} {kind}")
        for route, s in sorted(snapshot["http"].get("routes", {}).items()):
            if key in s:
                lines.append(
                    f'{metric}{{route="{esc(route)}"}} {s[key]}')
    lines.append("# TYPE routest_batcher gauge")
    for key, val in sorted(snapshot.get("batcher", {}).items()):
        if isinstance(val, bool):
            val = int(val)
        if isinstance(val, (int, float)):
            lines.append(f'routest_batcher{{stat="{esc(key)}"}} {val}')
    return "\n".join(lines) + "\n"


def _device_memory(jax) -> dict:
    """Per-device HBM residency gauge (SURVEY.md §5.5 — "HBM residency"
    is one of the TPU gauges the health contract promises). CPU backends
    do not implement memory_stats(); report what exists, never fail
    health over a gauge."""
    out = {}
    try:
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            used = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            if used is None:
                continue
            entry = {"bytes_in_use": int(used)}
            if limit:
                entry["bytes_limit"] = int(limit)
                entry["utilization"] = round(used / limit, 4)
            out[str(d)] = entry
    except Exception as e:
        # Gauge-only: health must never fail over missing memory stats
        # (CPU backends) — but the miss is loggable.
        _log.debug("device_memory_unavailable",
                   error=f"{type(e).__name__}: {e}")
    return out


def _tpu_roofline(jax) -> dict:
    """Chip identity + the peak-table row that utilization figures are
    computed against: readable from the serving surface, not
    reconstructed by a reviewer."""
    device = jax.devices()[0]
    out: dict = {"device_kind": device.device_kind}
    if device.platform != "cpu":
        # An accelerator the peak table does not know is reported, not
        # omitted: health stays up and says which row is missing.
        try:
            out["peak_tflops_bf16"], out["peak_hbm_gbps"] = chip_peaks(
                device.device_kind)
        except ValueError as e:
            out["peaks_error"] = f"{type(e).__name__}: {e}"
            _log.warning("chip_peaks_unavailable", error=out["peaks_error"])
    return out


def _warm_optimizer() -> None:
    """Pre-compile the optimize-route shapes customers actually send.

    ``greedy_vrp``/geometry jit per destination count; without this the
    first request at each count pays the XLA compile inline (round 1's
    load test: optimize p95 ~700 ms vs p50 29 ms). The jitted functions
    are module-level, so the compile cache is process-wide — repeated
    ``create_app`` calls (tests) warm once. Shapes: 1 (point-to-point),
    3 (typical), 10 (the UI's max stops). Opt out with
    ``ROUTEST_WARM_BUCKETS=0``.
    """
    if os.environ.get("ROUTEST_WARM_BUCKETS", "1") == "0":
        return
    t0 = time.time()
    for n in (1, 3, 10):
        optimize_route({
            "source_point": {"lat": 14.5836, "lon": 121.0409},
            "destination_points": [
                {"lat": 14.55 + 0.002 * i, "lon": 121.05, "payload": 1}
                for i in range(n)],
            "driver_details": {"vehicle_type": "car",
                               "vehicle_capacity": 9e9,
                               "maximum_distance": 9e9},
        })
    get_logger("routest_tpu.serve").info(
        "optimizer_warmed", shapes=[1, 3, 10],
        seconds=round(time.time() - t0, 2))


def _persist(state: ServerState, payload: dict, feature: dict) -> Optional[str]:
    """Write request+result rows (``Flaskr/routes.py:134-182`` shape)."""
    meta = payload.get("meta") or {}
    driver = payload.get("driver_details") or {}
    req_row = {
        "origin_id": meta.get("origin_id"),
        "stops": {
            "destination_ids": meta.get("destination_ids") or [],
            "destination_points": payload.get("destination_points") or [],
        },
        "status": "completed",
        "engine": "ml" if payload.get("use_ml_eta") else "default",
        "vehicle_id": driver.get("driver_name"),
        "driver_age": driver.get("driver_age"),
    }
    request_id = state.store.insert_request(req_row)

    props = (feature or {}).get("properties", {}) or {}
    summary = props.get("summary", {}) or {}
    state.store.insert_result({
        "request_id": request_id,
        "total_distance": float(summary.get("distance") or 0),
        "total_duration": float(summary.get("duration") or 0),
        "optimized_order": props.get("optimized_order") or [],
        "legs": props.get("segments", []) or [],
        "geometry": feature.get("geometry") or None,
        "eta_minutes_ml": props.get("eta_minutes_ml"),
        "eta_completion_time_ml": props.get("eta_completion_time_ml"),
    })
    return request_id


def _parse_windows(body: dict, n: int):
    """``time_windows``: list of N ``[open_s, close_s|null]`` pairs →
    (tw_open, tw_close) float32 arrays, (None, None) when absent, or
    ``{"error"}``. A null/absent close means "no deadline" (the solver's
    NO_WINDOW sentinel); non-finite values are client errors — a NaN
    window would poison the on-device feasibility mask."""
    raw = body.get("time_windows")
    if raw is None:
        return None, None
    from routest_tpu.optimize.vrp import NO_WINDOW

    if not isinstance(raw, list) or len(raw) != n:
        return {"error": f"time_windows must be a list of {n} "
                         "[open_s, close_s] pairs"}, None
    opens, closes = [], []
    for tw in raw:
        if not isinstance(tw, (list, tuple)) or len(tw) != 2:
            return {"error": "each time window must be "
                             "[open_s, close_s]"}, None
        o, c = tw
        try:
            o = float(o or 0)
            c = NO_WINDOW if c is None else float(c)
        except (TypeError, ValueError):
            return {"error": "time window bounds must be numeric"}, None
        if not (math.isfinite(o) and (c == NO_WINDOW or math.isfinite(c))):
            return {"error": "time window bounds must be finite"}, None
        opens.append(o)
        closes.append(min(c, NO_WINDOW))
    return (np.asarray(opens, np.float32), np.asarray(closes, np.float32))


def _parse_matrix_dispatch(body: dict, max_stops: int) -> dict:
    """Matrix-mode dispatch body → problem fields or ``{"error"}``."""
    matrix = body.get("matrix")
    if not isinstance(matrix, list) or len(matrix) < 2:
        return {"error": "matrix must be a square cost matrix "
                         "(row/col 0 = depot) with at least one stop"}
    n = len(matrix) - 1
    if n > max_stops:
        return {"error": f"too many stops (max {max_stops})"}
    try:
        m = np.asarray(matrix, np.float32)
    except ValueError:
        return {"error": "matrix must be numeric and square"}
    if m.shape != (n + 1, n + 1) or not np.isfinite(m).all():
        return {"error": "matrix must be numeric, square and finite"}
    demands = body.get("demands")
    if not isinstance(demands, list) or len(demands) != n:
        return {"error": f"demands must be a list of {n} numbers"}
    try:
        dem = np.asarray([float(d or 0) for d in demands], np.float32)
        capacity = float(body.get("capacity", 9e12))
        max_cost = float(body.get("max_distance", 9e12))
    except (TypeError, ValueError):
        return {"error": "demands/capacity/max_distance must be numeric"}
    if not (np.isfinite(dem).all() and math.isfinite(capacity)
            and math.isfinite(max_cost)):
        return {"error": "demands/capacity/max_distance must be finite"}
    tw_open, tw_close = _parse_windows(body, n)
    if isinstance(tw_open, dict):
        return tw_open
    return {"mode": "matrix", "matrix": m, "demands": dem,
            "capacity": capacity, "max_cost": max_cost,
            "tw_open": tw_open, "tw_close": tw_close, "latlon": None,
            "driver_details": _obj(body.get("driver_details")),
            "destinations": None}


def _parse_geo_dispatch(body: dict, max_stops: int) -> dict:
    """Geographic dispatch body → problem fields or ``{"error"}``.
    Shares the optimizer's reference-body validation, so a malformed
    dispatch fails exactly like a malformed optimize_route."""
    p = _parse_problem(body)
    if "error" in p:
        return p
    if len(p["destinations"]) > max_stops:
        return {"error": f"too many stops (max {max_stops})"}
    tw_open, tw_close = _parse_windows(body, len(p["destinations"]))
    if isinstance(tw_open, dict):
        return tw_open
    return {"mode": "geographic", "latlon": p["latlon"],
            "demands": p["demands"], "capacity": p["cap"],
            "max_dist": p["max_dist"], "speed": p["speed"],
            "tw_open": tw_open, "tw_close": tw_close,
            "driver_details": p["driver_details"],
            "destinations": p["destinations"]}


def _register_confirmed_route(svc, data: dict, seed):
    """Best-effort: register a confirm_route body's route as an active
    dispatch so the re-optimization loop watches its corridor. Needs
    lat/lon on every destination and finite constraints; returns None
    (caller keeps reference behavior) when the body can't support a
    re-solve. The confirmed stop ORDER is the baseline plan."""
    from routest_tpu.dispatch import plan_cost

    route = _obj(data["route_details"])
    driver = dict(_obj(data["driver_details"]))
    props = _obj(route.get("properties"))
    dests = props.get("destinations")
    if not isinstance(dests, list) or not dests:
        return None
    coords = _obj(route.get("geometry")).get("coordinates")
    try:
        origin = [float(coords[0][1]), float(coords[0][0])]  # lonlat row
        latlon = np.asarray(
            [origin] + [[float(d["lat"]), float(d["lon"])] for d in dests],
            np.float32)
        demands = np.asarray(
            [float(_obj(d).get("payload", 0) or 0) for d in dests],
            np.float32)
        capacity = float(driver.get("vehicle_capacity", 9e12))
        max_dist = float(driver.get("maximum_distance", 9e12))
    except (KeyError, TypeError, ValueError, IndexError):
        return None
    if not (np.isfinite(latlon).all() and np.isfinite(demands).all()
            and math.isfinite(capacity) and math.isfinite(max_dist)):
        return None
    from routest_tpu.data import geo as _geo

    profile = _geo.profile_for_vehicle(
        str(driver.get("vehicle_type") or "car").lower().strip())
    speed = float(svc.cfg.speed_mps or _geo.PROFILE_SPEED_MPS[profile])
    driver.setdefault("speed_mps", round(speed, 3))
    matrix = svc.matrix_fn(latlon, speed_mps=speed)
    plan = {"trips": [list(range(len(dests)))],
            "optimized_order": list(range(len(dests))),
            "n_trips": 1, "spill_lane": [], "spilled": [],
            "penalty": 0.0, "unroutable": []}
    return svc.registry.register(
        channel=driver.get("driver_name"), latlon=latlon,
        demands=demands, capacity=capacity, max_cost=max_dist / speed,
        plan=plan, baseline_cost=plan_cost(matrix, plan),
        epoch=svc.epoch_fn(), sim_seed=seed, driver_details=driver,
        destinations=dests, source="confirm_route")

"""Continuous GNN refresh: recent observation window → verified swap.

Periodically re-fits the road-GNN congestion head on the estimator's
recent observation window and writes the artifact atomically
(``save_gnn`` → temp-then-rename); the serving router's
fingerprint-gated hot reload picks the new mtime up on its next
request and lands it through the VERIFIED swap
(``RoadRouter._verify_gnn_swap`` — finiteness + divergence gates, the
road-side twin of PR 7's ETA golden-batch gate). The trainer never
touches a router directly: the artifact file IS the interface, so the
same trainer runs in-replica, in a sidecar, or in a bench driver.

Training shape (the ``loss_weights`` split in ``models/gnn.py``):
every graph edge carries messages (the aggregation the model serves
under), but the loss reads only window-observed edges — targets are
each observed edge's window-mean seconds at its last observed hour.
Warm start: parameters continue from the previous cycle (or the
current artifact when fingerprints match), so a few dozen steps per
cycle track a drifting world instead of re-learning it.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from routest_tpu.live.state import CongestionState

_metrics = None


def _trainer_metrics():
    global _metrics
    if _metrics is None:
        from routest_tpu.obs import get_registry

        reg = get_registry()
        _metrics = {
            "runs": reg.counter(
                "rtpu_live_retrain_total",
                "Continuous-retrain cycles, by result "
                "(saved / skipped / rejected / failed).", ("result",)),
            "dur": reg.histogram(
                "rtpu_live_retrain_seconds",
                "One retrain cycle: window build + steps + save."),
            "phase": reg.histogram(
                "rtpu_live_retrain_phase_seconds",
                "One phase of a retrain cycle (aggregate / upload / "
                "steps / apply / save): its span's own duration.",
                ("phase",)),
        }
    return _metrics


@contextlib.contextmanager
def _phase(phase: str, **attrs) -> Iterator:
    """One child span of ``live.retrain``, its duration observed into
    ``rtpu_live_retrain_phase_seconds{phase}`` as well, so that
    ``/metrics`` and the timeline show one number. The span's own
    duration is the one taken; only where none was recorded (tracer
    off, trace unsampled) does the histogram get this clock's."""
    from routest_tpu.obs import trace_span

    t0 = time.perf_counter()
    with trace_span("live.retrain." + phase, **attrs) as span:
        yield span
    ms = getattr(span, "duration_ms", None)
    _trainer_metrics()["phase"].labels(phase=phase).observe(
        time.perf_counter() - t0 if ms is None else ms / 1000.0)


class ContinuousTrainer:
    """Periodic re-fit of the road-GNN on the observation window.

    A trainer's graph is fixed at construction, so what a cycle's batch
    holds of it is handed to the device once, by the first cycle that
    trains, and stays there between cycles: the arcs' ends, lengths and
    speed limits, the node coordinates, the message weights, the
    layout's slabs and the ``(E, 13)`` feature table: about 90 bytes
    an arc (a TPU keeps the table column by column, 16 columns), 0.25
    GB at 2.7 M arcs. Every cycle held as much for the length of its
    steps anyway; a replica that trains beside a router now holds it
    between cycles too. A cycle sends its window's targets, loss
    weights and hours (12 bytes an arc) and rewrites the table's hour
    columns on the device, in place. A cycle that raises drops the
    resident state, and the next one rebuilds it from the host's."""

    def __init__(self, router, state: CongestionState,
                 artifact_path: Optional[str] = None, *,
                 steps: int = 40, lr: float = 1e-3,
                 min_obs: int = 256, hidden: int = 64,
                 seed: int = 0) -> None:
        from routest_tpu.train.checkpoint import default_gnn_path

        self._router = router
        self._state = state
        self._path = (artifact_path or getattr(router, "_gnn_path", None)
                      or default_gnn_path())
        self.steps = int(steps)
        self.lr = float(lr)
        self.min_obs = int(min_obs)
        self.hidden = int(hidden)
        self.seed = int(seed)
        self._graph = router.graph_dict()
        self._layout = None
        self._static: Optional[Dict[str, np.ndarray]] = None
        # the device's copy of what _static and _layout hold of a batch
        self._resident: Optional[Dict] = None
        self._model = None
        self._params = None
        self._opt = None
        self._opt_state = None
        self._step_fn = None
        self._apply_fn = None
        self._hours_fn = None
        self.cycles = 0
        self.last_result: Dict = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ── model bring-up ────────────────────────────────────────────────

    def _ensure_model(self) -> None:
        if self._model is not None:
            return
        import jax

        from routest_tpu.core.dtypes import F32_POLICY
        from routest_tpu.models.gnn import RoadGNN

        # Warm start from the live artifact when it belongs to THIS
        # graph — continuity is what makes few-step cycles converge.
        try:
            from routest_tpu.train.checkpoint import load_gnn

            model, params, fp = load_gnn(self._path)
            if fp == self._router._fingerprint:
                import dataclasses

                self._model = dataclasses.replace(model,
                                                  policy=F32_POLICY)
                self._params = params
        except Exception:  # rtpulint: disable=broad-except-unlogged -- warm-start is best-effort: any load failure falls back to fresh init
            self._model = None  # fresh init below; reason irrelevant
        if self._model is None:
            self._model = RoadGNN(n_nodes=len(self._graph["node_coords"]),
                                  hidden=self.hidden, n_rounds=2,
                                  policy=F32_POLICY)
            self._params = self._model.init(
                jax.random.PRNGKey(self.seed))

    def _ensure_step(self) -> None:
        if self._step_fn is not None:
            return
        import jax
        import optax

        from routest_tpu.models.gnn import set_hour_columns

        self._opt = optax.adamw(self.lr, weight_decay=1e-4)
        self._opt_state = self._opt.init(self._params)
        model, opt = self._model, self._opt

        @jax.jit
        def step(params, opt_state, coords, batch, loss_weights):
            loss, grads = jax.value_and_grad(model.loss)(
                params, coords, batch, loss_weights=loss_weights)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        self._step_fn = step
        self._apply_fn = jax.jit(model.apply)
        self._hours_fn = jax.jit(set_hour_columns, donate_argnums=0)

    def _ensure_layout(self, root) -> None:
        """Once per trainer: the graph's ``GraphLayout`` where it gets
        one (``models/gnn.graph_layout`` decides from its degrees), under
        which the train step holds no scatter-add, and the static arrays
        in the order the device is handed them: the layout's, else the
        graph's own."""
        if self._static is not None:
            return
        from routest_tpu.models.gnn import graph_layout

        g = self._graph
        t0 = time.perf_counter()
        lay = graph_layout(g["senders"], g["receivers"],
                           len(g["node_coords"]))
        static = {k: np.asarray(g[k]) for k in (
            "senders", "receivers", "length_m", "speed_limit",
            "road_class", "node_coords")}
        if lay is not None:
            for k in ("length_m", "speed_limit", "road_class"):
                static[k] = static[k][lay.arc_order]
            static["senders"], static["receivers"] = (lay.senders,
                                                      lay.receivers)
            static["node_coords"] = static["node_coords"][lay.node_order]
            root.set_attr("layout_build_ms",
                          round((time.perf_counter() - t0) * 1e3, 3))
        self._layout, self._static = lay, static

    def _upload_static(self) -> int:
        """Hands the device what no window changes, in the order of
        ``_static``; returns the bytes sent. The feature table goes up
        with hour 0 in its hour columns: every cycle rewrites them."""
        import jax
        import jax.numpy as jnp

        from routest_tpu.models.gnn import edge_feature_array, hour_table

        g = self._static
        host = {
            "senders": np.asarray(g["senders"], np.int32),
            "receivers": np.asarray(g["receivers"], np.int32),
            "edge_feats": edge_feature_array(
                g["length_m"], g["speed_limit"], g["road_class"], 0),
            "length_m": np.asarray(g["length_m"], np.float32),
            "speed_limit": np.asarray(g["speed_limit"], np.float32),
            "coords": np.asarray(g["node_coords"], np.float32),
            "hour_table": hour_table(),
            "slabs": self._layout and self._layout.slabs}
        self._resident = jax.tree_util.tree_map(jnp.asarray, host)
        self._resident["weights"] = jnp.ones((len(g["senders"]),),
                                             jnp.float32)
        return sum(a.nbytes for a in jax.tree_util.tree_leaves(host))

    # ── one cycle ─────────────────────────────────────────────────────

    def run_once(self) -> Dict:
        """One retrain cycle; returns a result dict, never raises.

        The cycle is one ``live.retrain`` span with five sequential
        children (aggregate / upload / steps / apply / save). A child
        ends where the host already is — nothing synchronises for the
        tracer's sake — so an upload still in flight when ``upload``
        closes is absorbed by ``steps``. Where it is recorded the root
        also carries the host's account of the cycle (``obs/host.py``:
        ``psi_cpu_ms``, ``steal_ms``, ``nivcsw``, ``gc_ms`` …) and
        ``compile_ms`` where a program compiled or came from the
        persistent cache meanwhile (the first cycle's step)."""
        from routest_tpu.core.cache import compile_seconds
        from routest_tpu.obs import host, trace_span
        from routest_tpu.utils.logging import get_logger

        with trace_span("live.retrain") as root:
            before = host.begin(root)
            compiled = compile_seconds()
            try:
                result, self.last_result = self._cycle(root)
            except Exception as e:
                # the cycle may have died between donating the feature
                # table and getting it back
                self._resident = None
                get_logger("routest_tpu.live").error(
                    "live_retrain_failed", error=f"{type(e).__name__}: {e}")
                result, self.last_result = "failed", {
                    "trained": False, "reason": f"{type(e).__name__}: {e}"}
            _trainer_metrics()["runs"].labels(result=result).inc()
            root.set_attr("result", result)
            compiled = compile_seconds() - compiled
            if compiled > 0.0:
                root.set_attr("compile_ms", 1e3 * compiled)
            host.end(root, before)
            return self.last_result

    def _cycle(self, root) -> Tuple[str, Dict]:
        """The cycle proper: (result label, result dict)."""
        import jax.numpy as jnp

        from routest_tpu.models.gnn import GraphBatch
        from routest_tpu.utils.logging import get_logger

        t0 = time.perf_counter()
        with _phase("aggregate"):
            win = self._state.window()
            n_obs = len(win["edge"])
            root.set_attr("observations", n_obs)
            if n_obs < self.min_obs:
                return "skipped", {
                    "trained": False,
                    "reason": f"window {n_obs} < min_obs {self.min_obs}"}
            self._ensure_layout(root)
            g, lay = self._static, self._layout
            root.set_attr("layout",
                          "segment_sum" if lay is None else "dense")
            # the window's arc ids, in the order the arrays are in
            edge = (win["edge"] if lay is None
                    else lay.arc_rank[win["edge"]])
            E = len(g["senders"])
            root.set_attr("edges", E)
            # Per-edge window aggregation: mean observed seconds, last
            # observed hour (the window is oldest-first, so a plain
            # index write leaves the LAST occurrence standing).
            sums = np.zeros(E, np.float64)
            counts = np.zeros(E, np.float64)
            np.add.at(sums, edge, win["time_s"])
            np.add.at(counts, edge, 1.0)
            observed = counts > 0
            targets = np.zeros(E, np.float32)
            targets[observed] = (sums[observed]
                                 / counts[observed]).astype(np.float32)
            hours = np.full(E, time.localtime().tm_hour, np.int32)
            hours[edge] = win["hour"]
            # what the window changed: all a cycle sends once the
            # static arrays are on the device
            changed = {"targets": targets,
                       "loss_w": observed.astype(np.float32),
                       "hours": hours}
        with _phase("upload") as span:
            self._ensure_model()
            self._ensure_step()
            resident = self._resident is not None
            sent = sum(a.nbytes for a in changed.values())
            if not resident:
                sent += self._upload_static()
            span.set_attr("static_resident", resident)
            span.set_attr("bytes", sent)
            dev = self._resident
            changed = {k: jnp.asarray(a) for k, a in changed.items()}
            # the table is donated: until the program hands the new one
            # back the resident state holds none
            dev["edge_feats"] = self._hours_fn(
                dev.pop("edge_feats"), changed.pop("hours"),
                dev["hour_table"])
            batch = GraphBatch(
                senders=dev["senders"], receivers=dev["receivers"],
                edge_feats=dev["edge_feats"], length_m=dev["length_m"],
                speed_limit=dev["speed_limit"], targets=changed["targets"],
                weights=dev["weights"], layout=dev["slabs"])
            loss_w, coords = changed["loss_w"], dev["coords"]
        root.set_attr("steps", self.steps)
        with _phase("steps", steps=self.steps):
            params, opt_state = self._params, self._opt_state
            loss = float("nan")
            for _ in range(self.steps):
                params, opt_state, loss = self._step_fn(
                    params, opt_state, coords, batch, loss_w)
            loss = float(loss)
            if not np.isfinite(loss):
                return "rejected", {"trained": False,
                                    "reason": f"non-finite loss {loss}"}
        with _phase("apply") as span:
            pred = np.asarray(self._apply_fn(params, coords, batch))
            span.set_attr("bytes", pred.nbytes)
            if not np.isfinite(pred).all():
                return "rejected", {
                    "trained": False,
                    "reason": "non-finite predictions after fit"}
        with _phase("save") as span:
            # Accept the cycle: carry the optimizer state forward and
            # land the artifact atomically (the router verifies again,
            # independently, before ITS generation flips).
            self._params, self._opt_state = params, opt_state
            from routest_tpu.train.checkpoint import save_gnn

            # the graph as the router gave it: the artifact's
            # fingerprint is the router's, whatever order trained it
            save_gnn(self._path, self._model, params, self._graph)
            span.set_attr("bytes", os.path.getsize(self._path))
        dur = time.perf_counter() - t0
        self.cycles += 1
        _trainer_metrics()["dur"].observe(dur)
        obs_rmse = float(np.sqrt(np.mean(
            (pred[observed] - targets[observed]) ** 2)))
        last = {
            "trained": True, "observations": n_obs,
            "edges_labeled": int(observed.sum()),
            "loss": round(loss, 3),
            "window_rmse_s": round(obs_rmse, 3),
            "train_s": round(dur, 3), "path": self._path}
        get_logger("routest_tpu.live").info("live_retrain_saved", **last)
        return "saved", last

    def start(self, interval_s: float = 30.0) -> None:
        def run() -> None:
            while not self._stop.wait(interval_s):
                self.run_once()

        self._thread = threading.Thread(target=run, name="live-trainer",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)

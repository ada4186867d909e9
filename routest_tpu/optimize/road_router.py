"""Road-graph shortest-path routing on device.

The reference outsources real road routing to ORS/OSRM SaaS
(``Flaskr/utils.py:55,97,151``); this framework's base engine
approximates legs with great-circle polylines × road factor
(``optimize/engine.py``). This module closes that gap on-device
(SURVEY.md §7.3 item 5 — "road network without ORS"): legs are true
shortest paths over a road graph, with geometry that follows the
street network and durations from the graph's per-edge travel times.

The solver is a **batched multi-source Bellman-Ford relaxation**
expressed as XLA control flow: per iteration, every edge proposes
``dist[s] + w`` to its receiver and a scatter-min folds the proposals —
one ``lax.while_loop`` whose body is two gathers and a scatter over the
(S, N) distance table. That maps the irregular graph problem onto the
TPU's strength (wide vectorized updates, no per-node host loops) and
vmaps/shards along the source axis like every other batch in this
framework. Predecessors are recovered after convergence with one more
edge sweep (an edge lies on a shortest path iff it is *tight*:
``dist[s] + w == dist[r]``), keeping the hot loop free of argmin
bookkeeping.

Path *reconstruction* (walking predecessors into polylines) is
host-side — it is O(path length) pointer chasing on tiny data, exactly
the kind of work that does not belong on the accelerator.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from routest_tpu.data.road_graph import (
    _CLASS_SPEED_MPS,
    generate_road_graph,
    haversine_np,
)
from routest_tpu.optimize.hierarchy import (
    HierarchicalIndex,
    hier_cache_path,
    hier_min_nodes,
    relax_from,
    tight_pred,
)
from routest_tpu.obs.efficiency import get_ledger
from routest_tpu.obs.ledger import record_change
from routest_tpu.obs.trace import trace_span
from routest_tpu.utils.logging import get_logger

_INF = jnp.float32(3e38)

_metrics = None


def _router_metrics():
    """Process-registry families for the router hot path, created
    lazily (importing the obs registry at module import would make the
    optimizer depend on serving wiring). Phase labels: ``snap``
    (lat/lon → node), ``solve`` (the fused device program incl. fetch),
    ``matrix`` (device duration table), ``walk`` (host predecessor
    walk per leg) — histogram exemplars link a slow solve to its trace
    id like every other stage histogram."""
    global _metrics
    if _metrics is None:
        from routest_tpu.obs import get_registry

        reg = get_registry()
        _metrics = {
            "phase": reg.histogram(
                "rtpu_router_phase_seconds",
                "Road-router request-path phase latency.", ("phase",)),
            "info": reg.gauge(
                "rtpu_router_overlay_info",
                "Overlay build stats by level and stat.",
                ("level", "stat")),
            "build": reg.gauge(
                "rtpu_router_overlay_build_seconds",
                "Overlay precompute seconds by level.", ("level",)),
            "swaps": reg.counter(
                "rtpu_road_model_swaps_total",
                "Road-GNN hot-swap attempts, by result "
                "(accepted / rejected / removed).", ("result",)),
            "model_gen": reg.gauge(
                "rtpu_road_model_generation",
                "Generation id of the live road-GNN leg pricer "
                "(monotonic per process; bumps on every swap)."),
            "batch_dispatches": reg.counter(
                "rtpu_router_batch_dispatches_total",
                "Merged solve dispatches through the router batcher."),
            "batch_rows": reg.counter(
                "rtpu_router_batch_rows_total",
                "Source rows solved through merged dispatches."),
            "batch_merged": reg.counter(
                "rtpu_router_batch_merged_requests_total",
                "Requests that shared a dispatch with at least one "
                "other request."),
        }
    return _metrics


@functools.partial(jax.jit, static_argnames=("n_rounds",))
def _time_table(bf_senders: jax.Array, pred: jax.Array, time_bf: jax.Array,
                dist: jax.Array, *, n_rounds: int) -> jax.Array:
    """(S, N) travel seconds along every shortest-path tree, on device.

    Matrix consumers need durations for every (source, node) pair; the
    host-side predecessor walk is O(path length) PER PAIR — seconds of
    pointer chasing at metro scale. Pointer doubling turns the whole
    table into ``n_rounds = ceil(log2(N))`` rounds of two (S, N)
    gathers: each round, every node's accumulated time and parent jump
    twice as far up its tree. Sums re-associate (tree order instead of
    walk order), so values match the walk to f32 rounding, not
    bitwise. Unreachable nodes (no predecessor, infinite distance)
    come back INF like the distance table."""
    rows = jnp.arange(pred.shape[0])[:, None]
    has_pred = pred >= 0
    safe = jnp.maximum(pred, 0)
    parent = jnp.where(has_pred, bf_senders[safe],
                       jnp.arange(pred.shape[1])[None, :])
    acc = jnp.where(has_pred, time_bf[safe], 0.0)

    # Fixed point after ceil(log2(tree depth)) rounds — the street-graph
    # diameter, typically far below the n_rounds=log2(N) bound; exit as
    # soon as every pointer reaches its root (one cheap compare per
    # round vs. the gathers it saves).
    def keep_going(state):
        _, _, changed, i = state
        return changed & (i < n_rounds)

    def body(state):
        acc, parent, _, i = state
        new_parent = parent[rows, parent]
        return (acc + acc[rows, parent], new_parent,
                jnp.any(new_parent != parent), i + 1)

    acc, parent, _, _ = jax.lax.while_loop(
        keep_going, body,
        (acc, parent, jnp.asarray(True), jnp.zeros((), jnp.int32)))
    # A predecessor CYCLE (possible with zero-length-edge ties — the
    # case _walk defends against) must surface as unreachable like the
    # walk does, not as a plausible partial sum. "Still moving" is NOT
    # a sufficient test: an even-length cycle squares to a spurious
    # fixed point where its nodes become their own parents. The sound
    # invariant: a finished chain ends at a TRUE root — a node with no
    # predecessor. Anything whose final parent still has a predecessor
    # sits in (or chains into) a cycle.
    bad_root = jnp.take_along_axis(has_pred, parent, axis=1)
    return jnp.where((dist < 1e37) & ~bad_root, acc, jnp.inf)

# Flat-relaxation sweeps run over hierarchy distances before
# predecessor recovery: the overlay's re-associated sums round a few
# ulps away from the sweep's own ``dist[s] + w`` assignments; an
# UNROLLED sweep re-anchors ties near-bitwise (values are already
# exact, so this is O(1), not O(diameter)). The sweeps now run on the
# CONTRACTED graph (chain interiors are synthesized from the fill
# structure, not relaxed in), and since the input values are exact the
# single default sweep re-anchors every node whose assignment matters
# — tight_edges' min-slack + 1 cm merge slack absorbs the one-op
# rounding that remains. Each sweep is a full (S, Nc)×Ec pass (~40 ms
# at 250k on one core), a first-order term in metro warm latency.
def _polish_sweeps() -> int:
    try:
        return max(1, int(os.environ.get("ROUTEST_POLISH_SWEEPS", "1")))
    except ValueError:
        return 1


@functools.partial(jax.jit, static_argnames=("n_nodes", "max_iters"))
def _bellman_ford(senders: jax.Array, receivers: jax.Array, w: jax.Array,
                  sources: jax.Array, *, n_nodes: int,
                  max_iters: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(S,) source nodes → (S, N) distances, (S, N) predecessor edges,
    and a scalar bool: True iff the loop CONVERGED (a sweep changed
    nothing) rather than exhausting ``max_iters`` — the caller must not
    trust distances when it is False.

    Edge arrays MUST be sorted by receiver: the sweep folds proposals
    with ``segment_min(indices_are_sorted=True)``, which benchmarks 1.6×
    faster than the equivalent scatter-min on TPU at metro scale (50k
    nodes / 243k edges: 1.13 s vs 1.81 s for a 16-source batch).
    Returned predecessor ids index the SORTED edge order — the caller
    maps them back through its sort permutation.

    The sweep and recovery primitives live in ``optimize/hierarchy.py``
    (``relax_from`` / ``tight_pred``) — the partition overlay composes
    the same kernels with a different initial table.
    """
    n_src = sources.shape[0]
    dist0 = jnp.full((n_src, n_nodes), _INF).at[
        jnp.arange(n_src), sources].set(0.0)
    dist, converged = relax_from(senders, receivers, w, dist0,
                                 n_nodes=n_nodes, max_iters=max_iters)
    pred = tight_pred(senders, receivers, w, dist, sources, n_nodes=n_nodes)
    return dist, pred, converged


def _road_swap_divergence() -> float:
    """Verified road-GNN hot-swap bound (median absolute edge-seconds
    divergence from the live pricer; 0 disables the compare — the
    finiteness gate always holds). Mirrors ``RTPU_SWAP_MAX_DIV`` on the
    ETA model (docs/ROBUSTNESS.md "Safe change delivery")."""
    try:
        return float(os.environ.get("RTPU_ROAD_SWAP_MAX_DIV", "600"))
    except ValueError:
        return 600.0


class _LiveMetric:
    """One immutable live-traffic metric generation (docs/ARCHITECTURE
    "Live traffic"): the blended per-edge travel seconds, the
    customized time-metric overlay (when the router has one), and the
    fused solve for it. Built OFF-PATH by ``install_live_metric`` and
    installed with a single reference flip — requests snapshot
    ``router._live`` once, so a flip can never tear a solve."""

    __slots__ = ("epoch", "gen", "time_s", "d_time_bf", "hier", "solve",
                 "aot", "route", "installed_unix", "timings")

    def __init__(self, epoch: int, time_s: np.ndarray, d_time_bf,
                 hier, solve, aot: Dict[int, object], route: bool,
                 timings: Dict, gen: int = 0) -> None:
        self.epoch = int(epoch)
        # Router-internal monotonic install counter: the route
        # fastlane keys on (epoch, gen) so even a caller that reuses
        # an epoch number (two customizer instances both starting at
        # 1) can never alias two different metrics onto one cache key.
        self.gen = int(gen)
        self.time_s = time_s
        self.d_time_bf = d_time_bf
        self.hier = hier
        self.solve = solve
        self.aot = aot
        self.route = route
        self.installed_unix = time.time()
        self.timings = timings


def _batcher_config() -> Tuple[bool, int, float]:
    """(enabled, max merged rows, window seconds) for the solve
    batcher (``ROUTEST_ROUTER_BATCH`` on/off,
    ``ROUTEST_ROUTER_BATCH_MAX``, ``ROUTEST_ROUTER_BATCH_WINDOW_MS``)."""
    raw = os.environ.get("ROUTEST_ROUTER_BATCH", "1").strip().lower()
    enabled = raw not in ("0", "off", "false", "no")
    try:
        max_rows = max(1, int(os.environ.get(
            "ROUTEST_ROUTER_BATCH_MAX", "32")))
    except ValueError:
        max_rows = 32
    try:
        window_ms = float(os.environ.get(
            "ROUTEST_ROUTER_BATCH_WINDOW_MS", "0"))
    except ValueError:
        window_ms = 0.0
    return enabled, max_rows, max(0.0, window_ms) / 1000.0


class _BatchEntry:
    __slots__ = ("sources", "live", "key", "event", "dist", "pred", "error",
                 "dispatch_rows", "dispatch_requests", "t_q")

    def __init__(self, sources: np.ndarray, live, key) -> None:
        self.sources = sources
        self.live = live
        self.key = key
        self.event = threading.Event()
        self.dist = self.pred = None
        self.error: Optional[BaseException] = None
        # Stamped by _dispatch: how big the merged device dispatch that
        # carried this entry actually was (trace provenance — a slow
        # solve span says whether it rode a 1-row or a 32-row merge).
        self.dispatch_rows = 0
        self.dispatch_requests = 0
        # Enqueue stamp for the goodput ledger's queue/compute split.
        self.t_q = time.monotonic()


class _SolveBatcher:
    """Cross-request solve coalescing: concurrent :meth:`shortest`
    callers whose metric generation matches merge into ONE padded
    device dispatch. The solver's source axis is batched by design, so
    merged results are bitwise what lone solves return — the merge only
    amortizes dispatch + fetch, the way the ETA ``DynamicBatcher``
    amortizes scoring (docs/ARCHITECTURE.md "Serving").

    Zero added latency by construction with the default 0 ms window: a
    lone request dispatches immediately; arrivals during an in-flight
    solve queue and drain as the NEXT merged batch (the natural-
    batching regime — occupancy grows exactly when the device is the
    bottleneck). ``window_s > 0`` adds a fixed pre-drain wait for
    benchmarking forced batch shapes.

    Requests under different live-metric epochs never share a dispatch
    (their edge weights differ); the leader drains one epoch group per
    round and keeps going until the queue is empty, so mixed-epoch
    bursts around a metric flip drain in arrival order."""

    def __init__(self, router: "RoadRouter", max_rows: int,
                 window_s: float) -> None:
        self._router = router
        self.max_rows = int(max_rows)
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._queue: List[_BatchEntry] = []
        self._busy = False
        self._dispatches = 0
        self._rows = 0
        self._requests = 0
        self._merged_requests = 0
        self._max_occupancy = 0

    def stats(self) -> Dict:
        with self._lock:
            d = max(1, self._dispatches)
            return {"max_rows": self.max_rows,
                    "window_ms": round(self.window_s * 1000, 3),
                    "dispatches": self._dispatches,
                    "rows": self._rows,
                    "requests": self._requests,
                    "merged_requests": self._merged_requests,
                    "max_occupancy": self._max_occupancy,
                    "mean_rows_per_dispatch": round(self._rows / d, 3)}

    def solve(self, sources: np.ndarray, live):
        """One caller's solve through the merge queue, traced: the span
        records how many rows rode the merged dispatch that carried it
        (``dispatch_rows``/``merged_requests``) — the provenance a
        tail-sampled slow route trace needs to say whether the solve
        was a lone dispatch or amortized across a merge."""
        with trace_span("router.batch_solve", rows=len(sources)) as span:
            entry = self._solve_entry(sources, live)
            span.set_attr("dispatch_rows", entry.dispatch_rows)
            span.set_attr("merged_requests", entry.dispatch_requests)
            return entry.dist, entry.pred

    def _solve_entry(self, sources: np.ndarray, live) -> "_BatchEntry":
        key = live.epoch if (live is not None and live.route) else 0
        entry = _BatchEntry(sources, live if key else None, key)
        with self._lock:
            self._queue.append(entry)
            self._requests += 1
            leader = not self._busy
            if leader:
                self._busy = True
        if not leader:
            if not entry.event.wait(120.0):
                raise TimeoutError("router solve batcher wedged")
            if entry.error is not None:
                raise entry.error
            return entry
        drain_error: Optional[BaseException] = None
        try:
            if self.window_s > 0:
                time.sleep(self.window_s)
            while True:
                with self._lock:
                    if not self._queue:
                        # Clearing the flag and observing the empty
                        # queue must be ONE atomic step: an arrival in
                        # between would otherwise wait on a leader that
                        # already left.
                        self._busy = False
                        break
                    k0 = self._queue[0].key
                    batch: List[_BatchEntry] = []
                    rest: List[_BatchEntry] = []
                    rows = 0
                    for it in self._queue:
                        if (it.key == k0
                                and rows + len(it.sources) <= self.max_rows):
                            batch.append(it)
                            rows += len(it.sources)
                        else:
                            rest.append(it)
                    self._queue = rest
                    self._dispatches += 1
                    self._rows += rows
                    self._max_occupancy = max(self._max_occupancy, rows)
                    if len(batch) > 1:
                        self._merged_requests += len(batch)
                m = _router_metrics()
                m["batch_dispatches"].inc()
                m["batch_rows"].inc(rows)
                if len(batch) > 1:
                    m["batch_merged"].inc(len(batch))
                self._dispatch(batch)
        except BaseException as e:  # drain-loop bug: fail loudly, not hung
            drain_error = e
            raise
        finally:
            if drain_error:
                with self._lock:
                    # Never leave the flag stuck: if the drain loop
                    # itself died, surviving queue entries error out
                    # rather than hang their threads.
                    leftovers = list(self._queue)
                    self._queue = []
                    self._busy = False
            else:
                leftovers = []
            for it in leftovers:
                if not it.event.is_set():
                    it.error = drain_error
                    it.event.set()
        if entry.error is not None:
            raise entry.error
        return entry

    def _dispatch(self, batch: List[_BatchEntry]) -> None:
        merged = (batch[0].sources if len(batch) == 1
                  else np.concatenate([it.sources for it in batch]))
        queue_s = max(0.0, time.monotonic() - min(it.t_q for it in batch))
        t0 = time.perf_counter()
        try:
            dist, pred = self._router._solve_rows(merged, batch[0].live)
        except BaseException as e:  # propagate to every merged caller
            for it in batch:
                it.error = e
                it.event.set()
            return
        # _solve_rows pads the source axis to the next pow2 — that IS
        # the launched batch the goodput ledger accounts against.
        n = len(merged)
        bucket = 1 << max(0, n - 1).bit_length()
        get_ledger().record(
            "route_solve", real_rows=n, padded_rows=bucket, bucket=bucket,
            queue_s=queue_s, compute_s=time.perf_counter() - t0)
        pos = 0
        for it in batch:
            m = len(it.sources)
            it.dist = dist[pos:pos + m]
            it.pred = pred[pos:pos + m]
            it.dispatch_rows = len(merged)
            it.dispatch_requests = len(batch)
            pos += m
            it.event.set()


class RoadRouter:
    """Routable road network: snap → batched shortest paths → polylines."""

    def __init__(self, graph: Optional[Dict[str, np.ndarray]] = None,
                 n_nodes: int = 2048, seed: int = 0,
                 use_gnn: bool = True,
                 gnn_path: Optional[str] = None,
                 use_transformer: bool = True,
                 transformer_path: Optional[str] = None) -> None:
        g = graph if graph is not None else generate_road_graph(
            n_nodes=n_nodes, seed=seed)
        self.coords = np.asarray(g["node_coords"], np.float32)   # (N, 2)
        senders = np.asarray(g["senders"], np.int32)
        receivers = np.asarray(g["receivers"], np.int32)
        length = np.asarray(g["length_m"], np.float32)
        road_class = np.asarray(g["road_class"], np.int32)
        speed_limit = np.asarray(
            g.get("speed_limit", _CLASS_SPEED_MPS[road_class]), np.float32)
        n_edges_raw = len(senders)
        senders, receivers, length, road_class, speed_limit = \
            self._bridge_components(senders, receivers, length, road_class,
                                    speed_limit)
        self._was_bridged = len(senders) != n_edges_raw
        # GNN compatibility is checked against the POST-bridge graph —
        # the edge set messages actually aggregate over at serving time.
        # Training must therefore run on the same bridged arrays
        # (``graph_dict()``), which makes learned costs work on real OSM
        # extracts too: bridging is deterministic, so trainer and server
        # agree on the fingerprint.
        from routest_tpu.train.checkpoint import graph_fingerprint

        self._fingerprint = graph_fingerprint(
            self.coords, senders, receivers, length)
        self.senders, self.receivers = senders, receivers
        self.length_m = length
        self.road_class = road_class
        self.speed_limit = speed_limit
        # Fallback leg pricing: free-flow physics (length / speed limit +
        # intersection overhead). Deliberately NOT the data generator's
        # congestion formula — the request path must not depend on the
        # synthetic ground truth it is supposed to predict.
        self.freeflow_time_s = (
            length / np.maximum(self.speed_limit, 0.1) + 4.0
        ).astype(np.float32)
        self.time_s = self.freeflow_time_s  # back-compat alias
        self.n_nodes = len(self.coords)
        # Bellman-Ford needs ≥ diameter sweeps; a kNN street grid's hop
        # diameter is O(√N) — 4√N is a comfortable first bound, and the
        # loop exits early once converged. ``shortest`` re-runs with the
        # exact N-1 bound if this heuristic is ever exhausted.
        self.max_iters = int(4 * np.sqrt(self.n_nodes)) + 8
        # Device-resident graph arrays: uploaded once, not per request.
        # Original edge order (the GNN's training/feature order):
        self._d_senders = jnp.asarray(self.senders)
        self._d_receivers = jnp.asarray(self.receivers)
        self._d_length = jnp.asarray(self.length_m)
        self._d_speed = jnp.asarray(self.speed_limit)
        # Receiver-sorted copies for the shortest-path sweep (segment_min
        # with indices_are_sorted — see _bellman_ford); predecessor ids
        # come back in this order and map through _bf_perm.
        self._bf_perm = np.argsort(self.receivers, kind="stable").astype(np.int32)
        self._bf_senders = jnp.asarray(self.senders[self._bf_perm])
        self._bf_receivers = jnp.asarray(self.receivers[self._bf_perm])
        self._bf_length = jnp.asarray(self.length_m[self._bf_perm])
        # Metro-scale graphs route through the two-level partition
        # overlay (``optimize/hierarchy.py``): the flat sweep's
        # iteration count is the graph's hop diameter, which crosses
        # from "fine" to "seconds per solve" around tens of thousands
        # of nodes. The overlay answers the same queries exactly in
        # O(cells-across) sweeps after a one-time batched precompute.
        self._hier: Optional[HierarchicalIndex] = None
        hmin = hier_min_nodes()
        if hmin and self.n_nodes >= hmin:
            cache = hier_cache_path(self._fingerprint)
            if cache and os.path.exists(cache):
                self._hier = HierarchicalIndex.load(
                    cache, fingerprint=self._fingerprint)
            if self._hier is None:
                self._hier = HierarchicalIndex.build(
                    self.coords, self.senders, self.receivers,
                    self.length_m, cache_path=cache,
                    fingerprint=self._fingerprint)
        self._aot: Dict[int, object] = {}
        self._aot_compile_s = 0.0
        if self._hier is not None:
            # Overlay query + polish sweeps + predecessor recovery
            # fused into ONE jitted program: a warm solve is a single
            # dispatch + fetch instead of three dispatches; it also
            # collapses three per-bucket compiles into one.
            self._overlay_solve = self._make_overlay_solve(self._hier)
            # AOT-compile the query entry per (graph, overlay) shape at
            # init (``jit(...).lower().compile()``): warm latency then
            # excludes dispatch/trace overhead and the FIRST request of
            # a replica's life stops paying the multi-second trace +
            # compile (4.8 s recorded at 250k). With the persistent XLA
            # compile cache on, the executable round-trips disk across
            # processes, so a fleet boot pays it once per machine.
            t0 = time.perf_counter()
            L = self._hier.n_levels
            for bucket in self._aot_buckets():
                spec = (jnp.zeros((L, bucket), jnp.int32),
                        jnp.zeros((L + 1, bucket, 2), jnp.int32),
                        jnp.zeros((L + 1, bucket, 2), jnp.float32),
                        jnp.zeros((bucket,), jnp.int32))
                self._aot[bucket] = self._overlay_solve.lower(
                    *spec).compile()
            self._aot_compile_s = round(time.perf_counter() - t0, 3)
            self._publish_overlay_metrics()
        # Cross-request solve batching (concurrent request_route /
        # matrix traffic shares compiled dispatches) + the route-level
        # fastlane (Zipf-skewed OD traffic mostly skips the solver).
        enabled, max_rows, window_s = _batcher_config()
        self._solve_batcher: Optional[_SolveBatcher] = (
            _SolveBatcher(self, max_rows, window_s) if enabled else None)
        from routest_tpu.optimize.route_cache import (RouteCache,
                                                      route_cache_config)

        rc_on, rc_bytes, rc_ttl = route_cache_config()
        self._route_cache: Optional[RouteCache] = (
            RouteCache(rc_bytes, rc_ttl) if rc_on else None)
        # Learned leg costs: load the trained road-GNN when its training
        # graph fingerprint matches this router's node set.
        self._hour_times: Dict[int, np.ndarray] = {}
        self._gnn_lock = threading.Lock()
        # Learned leg models hot-reload like the ETA model: each request
        # entry point stats the artifact and re-runs the fingerprint-
        # gated loader when the file changed — a retrained GNN or
        # transformer goes live without a restart. Mtimes are recorded
        # even for rejected artifacts so a bad file isn't re-parsed on
        # every request.
        from routest_tpu.train.checkpoint import (default_gnn_path,
                                                  default_transformer_path)

        self._gnn_path = ((gnn_path or default_gnn_path())
                          if use_gnn else None)
        self._transformer_path = (
            (transformer_path or default_transformer_path())
            if use_transformer else None)
        self._gnn_mtime_ns: Optional[int] = None
        self._transformer_mtime_ns: Optional[int] = None
        self._gnn = None
        self._transformer = None
        # Live-traffic metric (routest_tpu/live): installed by the
        # customizer, snapshotted once per request batch. None = frozen
        # world (free-flow / GNN pricing, distance-metric routing).
        self._live: Optional[_LiveMetric] = None
        self._live_installs = 0  # monotonic; part of the route-cache key
        self._live_lock = threading.Lock()  # serializes installs only
        # Serializes reloads only — model loading happens OUTSIDE the
        # cache lock so a retrain never stalls concurrent requests.
        self._reload_lock = threading.Lock()
        self._model_gen = 0  # bumped per swap: stale cache writes discard
        self._maybe_reload_models()

    @staticmethod
    def _aot_buckets() -> List[int]:
        """Source-bucket sizes to AOT-compile at init.
        ``ROUTEST_ROUTER_AOT``: "auto" (default — the serving
        point-to-point bucket and the bench/matrix 16-waypoint bucket),
        "off"/"0" to disable, or a comma list of waypoint counts
        (rounded up to their power-of-two buckets)."""
        raw = os.environ.get("ROUTEST_ROUTER_AOT", "auto").strip().lower()
        if raw in ("", "0", "off", "false", "no"):
            return []
        if raw == "auto":
            return [2, 16]
        out = set()
        for tok in raw.split(","):
            tok = tok.strip()
            if tok.isdigit() and int(tok) > 0:
                out.add(1 << max(0, (int(tok) - 1).bit_length()))
        return sorted(out)

    def _publish_overlay_metrics(self) -> None:
        """Overlay build stats → the process registry: per-level
        ``rtpu_router_overlay_info{level, stat}`` gauges plus
        ``rtpu_router_overlay_build_seconds{level}`` — the provenance a
        dashboard (or a postmortem bundle) reads without a /api/health
        round trip."""
        if self._hier is None:
            return
        m = _router_metrics()
        for lvl in self._hier.stats.get("levels", []):
            level = str(lvl.get("level", 1))
            for stat in ("n_cells", "c_max", "b_max", "n_overlay_nodes",
                         "n_overlay_edges", "clique_edges_kept",
                         "clique_edges_pruned"):
                if stat in lvl:
                    m["info"].labels(level=level, stat=stat).set(lvl[stat])
            m["build"].labels(level=level).set(lvl.get("build_s", 0.0))
        m["info"].labels(level="top", stat="n_overlay_nodes").set(
            self._hier.stats.get("top_nodes", 0))
        m["info"].labels(level="top", stat="n_overlay_edges").set(
            self._hier.stats.get("top_edges", 0))

    @property
    def leg_cost_model(self) -> str:
        """"gnn" when learned per-edge times serve requests, else
        "freeflow"."""
        return "gnn" if self._gnn is not None else "freeflow"

    @property
    def solver_info(self) -> Dict:
        """Which shortest-path regime serves this graph, with the
        overlay's build stats when the partition hierarchy is active —
        ONE shape shared by the health gauge and the scale benchmark.
        ``overlay.levels`` carries the per-level breakdown,
        ``overlay.loaded_from_cache``/``cache_version`` the provenance,
        ``aot_buckets`` the solve shapes compiled at init."""
        if self._hier is not None:
            from routest_tpu.optimize.hierarchy import _CACHE_VERSION

            info = {"solver": "hierarchy",
                    "overlay": dict(self._hier.stats)}
            info["overlay"].setdefault("loaded_from_cache", False)
            info["overlay"]["cache_version"] = _CACHE_VERSION
            info["hub_labels"] = self._hier._labels is not None
            info["aot_buckets"] = sorted(self._aot)
            if self._aot:
                info["aot_compile_s"] = self._aot_compile_s
        else:
            info = {"solver": "flat_bf", "max_iters_bound": self.max_iters}
        # Routing fast-path provenance: the solve batcher's
        # merged-dispatch stats and the route fastlane's hit/byte
        # counters, for health.
        if self._solve_batcher is not None:
            info["batch"] = self._solve_batcher.stats()
        if self._route_cache is not None:
            info["route_cache"] = self._route_cache.stats()
        if self._live is not None:
            info["live"] = self.live_info
        return info

    # ── live traffic: metric install / flip ───────────────────────────

    @property
    def live_epoch(self) -> int:
        """Metric generation currently serving (0 = no live metric)."""
        live = self._live
        return live.epoch if live is not None else 0

    @property
    def live_info(self) -> Optional[Dict]:
        """Health/bench view of the installed live metric."""
        live = self._live
        if live is None:
            return None
        return {"epoch": live.epoch, "route_metric": live.route,
                "installed_unix": round(live.installed_unix, 3),
                **live.timings}

    def live_metric_export(self) -> Optional[np.ndarray]:
        """The (E,) blended edge seconds the live generation serves —
        what the bench's scipy oracle re-solves against."""
        live = self._live
        return None if live is None else live.time_s

    def install_live_metric(self, time_s: np.ndarray, epoch: int, *,
                            route: bool = True) -> Dict:
        """Build and atomically flip to a new live metric generation.

        ``time_s`` is the blended per-edge travel seconds (original
        edge order). Everything expensive — overlay customization
        (``HierarchicalIndex.customize``: partition + contraction
        reused, boundary tables re-priced), the fused solve's
        trace/compile for the AOT buckets — happens BEFORE the flip, on
        the caller's (customizer) thread, so requests keep solving the
        previous generation with zero blip and the flip itself is one
        reference assignment. ``route=False`` installs the metric for
        leg PRICING only (ETAs shift, chosen routes stay on the
        distance metric). Raises on a bad metric or a failed
        customization — the previous generation keeps serving.
        """
        time_s = np.array(time_s, np.float32, copy=True)
        if time_s.shape != self.length_m.shape:
            raise ValueError(
                f"live metric has {time_s.shape} entries, graph has "
                f"{self.length_m.shape}")
        # Same physical floor as every learned pricer: no edge beats
        # free-flow at an arterial ceiling, and non-finite/absurd
        # estimates degrade to physics instead of poisoning the metric.
        bad = ~np.isfinite(time_s) | (time_s <= 0)
        if bad.any():
            time_s[bad] = self.freeflow_time_s[bad]
        np.maximum(time_s, self.length_m / 16.7, out=time_s)
        timings: Dict = {}
        hier_live = solve = None
        aot: Dict[int, object] = {}
        d_time_bf = jnp.asarray(time_s[self._bf_perm])
        if self._hier is not None and route:
            t0 = time.perf_counter()
            hier_live = self._hier.customize(time_s)
            timings["customize_s"] = round(time.perf_counter() - t0, 3)
            timings["full_build_s"] = self._hier.stats.get("build_s", 0.0)
            solve = self._make_overlay_solve(hier_live)
            t0 = time.perf_counter()
            L = hier_live.n_levels
            for bucket in self._aot_buckets():
                spec = (jnp.zeros((L, bucket), jnp.int32),
                        jnp.zeros((L + 1, bucket, 2), jnp.int32),
                        jnp.zeros((L + 1, bucket, 2), jnp.float32),
                        jnp.zeros((bucket,), jnp.int32))
                aot[bucket] = solve.lower(*spec).compile()
            timings["aot_s"] = round(time.perf_counter() - t0, 3)
        with self._live_lock:
            self._live_installs += 1
            live = _LiveMetric(epoch, time_s, d_time_bf, hier_live,
                               solve, aot, route, timings,
                               gen=self._live_installs)
            self._live = live
        from routest_tpu.live import set_metric_epoch

        set_metric_epoch(live.epoch)
        get_logger("routest.road").info(
            "live_metric_installed", epoch=live.epoch, route=route,
            **timings)
        return dict(timings, epoch=live.epoch)

    def _make_overlay_solve(self, hier: HierarchicalIndex):
        """Fused overlay query + CONTRACTED-graph polish/predecessor
        recovery + exact chain synthesis — one jitted program, one
        dispatch per warm solve (``HierarchicalIndex.full_solve_fn``).
        Shared by the distance overlay (init) and every live-metric
        generation (customizer — the customized index carries its own
        re-priced contracted weights and fill offsets). Polish sweeps
        no longer couple to the contraction cap: chain interiors are
        synthesized from the fill structure, not relaxed in."""
        return jax.jit(hier.full_solve_fn(_polish_sweeps()))

    def graph_dict(self) -> Dict[str, np.ndarray]:
        """The (post-bridge) routable graph — the EXACT arrays serving
        aggregates over, and therefore the arrays the GNN must train on
        (``scripts/train_gnn.py`` consumes this; the saved artifact's
        fingerprint then matches ``_load_gnn``'s check)."""
        return {
            "node_coords": self.coords,
            "senders": self.senders,
            "receivers": self.receivers,
            "length_m": self.length_m,
            "road_class": self.road_class,
            "speed_limit": self.speed_limit,
        }

    def _load_leg_model(self, loader, resolved: str, tag: str):
        """Shared load-and-fingerprint-gate for learned leg-cost
        artifacts (road GNN, route transformer). The artifact is
        optional by design (same contract as the ETA model's
        ``(None, None)`` fallback, ``Flaskr/ml.py:25-26``): any failure
        degrades to the next pricer down, never an error. Returns
        (model, params, meta) or None; ``meta`` may be the fingerprint
        itself or a dict carrying it under "graph"."""
        try:
            model, params, meta = loader(resolved)
        except FileNotFoundError:
            return None
        except Exception as e:  # corrupt/foreign artifact: degrade, log
            get_logger("routest.road").warning(
                f"{tag}_artifact_unusable", path=resolved,
                error=f"{type(e).__name__}: {e}")
            return None
        fp = meta.get("graph", meta) if isinstance(meta, dict) else meta
        if fp != self._fingerprint:
            # Expected whenever a custom/test graph is routed; debug only.
            get_logger("routest.road").debug(
                f"{tag}_graph_mismatch", path=resolved,
                artifact=fp, router=self._fingerprint)
            return None
        from routest_tpu.core.dtypes import backend_compute_policy

        # Leg pricers serve per request: on the CPU fallback backend,
        # bf16 compute is emulation — same swap the ETA service applies.
        return backend_compute_policy(model), params, meta

    def _load_gnn(self, path: str):
        from routest_tpu.train.checkpoint import load_gnn

        loaded = self._load_leg_model(load_gnn, path, "road_gnn")
        if loaded is None:
            return None
        model, params, _meta = loaded
        return model, params

    @property
    def has_transformer(self) -> bool:
        return self._transformer is not None

    @staticmethod
    def _mtime_ns(path: Optional[str]) -> Optional[int]:
        if not path:
            return None
        try:
            return os.stat(path).st_mtime_ns
        except OSError:
            return None

    def _maybe_reload_models(self) -> None:
        """Reload the GNN / transformer when their artifact files changed
        (two stats per call — cheap enough to run per request). Same
        degradation contract as initial load: a rejected replacement
        simply isn't served; a DELETED artifact stops serving (pricing
        falls down the stack, matching a fresh process's behavior).
        Artifacts are written atomically (``_write_artifact``'s
        temp-then-rename), so a changed mtime always means a complete
        file. Deserialization runs outside the cache lock — only the
        final reference swap (and the generation bump that invalidates
        in-flight cache writes) holds it; a second thread arriving
        mid-reload just serves the current models."""
        if not (self._gnn_path or self._transformer_path):
            return
        if not self._reload_lock.acquire(blocking=False):
            return  # another request is already reloading
        try:
            m = self._mtime_ns(self._gnn_path)
            if self._gnn_path and m != self._gnn_mtime_ns:
                new_gnn = (self._load_gnn(self._gnn_path)
                           if m is not None else None)
                # Verified hot-swap (the continuous-retrain landing
                # zone, docs/ARCHITECTURE.md "Live traffic"): when a
                # model is already serving, a REPLACEMENT artifact must
                # score the graph finitely and stay within the
                # divergence bound before the generation flips — a
                # corrupt/degenerate retrain keeps the old pricer
                # serving. A DELETED artifact still stops serving
                # (matches a fresh process), and the first-ever install
                # only needs finiteness.
                accept, verdict = self._verify_gnn_swap(new_gnn, m)
                swaps = _router_metrics()["swaps"]
                if accept:
                    with self._gnn_lock:
                        self._gnn = new_gnn
                        self._gnn_mtime_ns = m
                        self._model_gen += 1
                        self._hour_times.clear()
                        gen = self._model_gen
                    swaps.labels(result=verdict.pop("result",
                                                    "accepted")).inc()
                    _router_metrics()["model_gen"].set(gen)
                    record_change("model.road_swap",
                                  detail={"generation": gen,
                                          "path": self._gnn_path})
                    get_logger("routest.road").info(
                        "road_model_swapped", generation=gen,
                        path=self._gnn_path, **verdict)
                else:
                    with self._gnn_lock:
                        # Remember the bad mtime so the artifact is not
                        # re-verified on every request until it changes.
                        self._gnn_mtime_ns = m
                    swaps.labels(result="rejected").inc()
                    get_logger("routest.road").warning(
                        "road_model_swap_rejected", path=self._gnn_path,
                        **verdict)
            m = self._mtime_ns(self._transformer_path)
            if self._transformer_path and m != self._transformer_mtime_ns:
                new_tf = (self._load_transformer(self._transformer_path)
                          if m is not None else None)
                with self._gnn_lock:
                    self._transformer = new_tf
                    self._transformer_mtime_ns = m
        finally:
            self._reload_lock.release()

    def _verify_gnn_swap(self, new_gnn, mtime_ns) -> Tuple[bool, Dict]:
        """Golden-graph gate for a road-GNN replacement → ``(accept,
        verdict)``. ``new_gnn`` None accepts as a removal (file deleted
        → pricing falls down the stack) unless a model is live and the
        file still EXISTS (an unloadable overwrite must not take down a
        working pricer). A loadable replacement scores the whole edge
        set at the current hour: any non-finite output rejects, and —
        when a model is already serving — a median absolute divergence
        beyond ``RTPU_ROAD_SWAP_MAX_DIV`` edge-seconds rejects too."""
        with self._gnn_lock:
            cur = self._gnn
        if new_gnn is None:
            if cur is not None and mtime_ns is not None:
                return False, {"reason": "replacement failed to load"}
            return True, {"result": "removed" if mtime_ns is None
                          else "accepted"}
        import datetime as _dt

        from routest_tpu.models.gnn import GraphBatch, edge_feature_array

        hour = _dt.datetime.now().hour
        model, params = new_gnn
        e = len(self.length_m)
        batch = GraphBatch(
            senders=self._d_senders, receivers=self._d_receivers,
            edge_feats=jnp.asarray(edge_feature_array(
                self.length_m, self.speed_limit, self.road_class, hour)),
            length_m=self._d_length, speed_limit=self._d_speed,
            targets=jnp.zeros((e,), jnp.float32),
            weights=jnp.ones((e,), jnp.float32))
        try:
            pred = np.asarray(
                model.apply(params, jnp.asarray(self.coords), batch),
                np.float32)
        except Exception as exc:
            return False, {"reason": "verification forward failed: "
                                     f"{type(exc).__name__}: {exc}"}
        if not np.isfinite(pred).all():
            return False, {"reason": "non-finite edge predictions",
                           "bad_edges": int((~np.isfinite(pred)).sum())}
        bound = _road_swap_divergence()
        if cur is not None and bound > 0:
            pred_f = np.maximum(pred, self.length_m / 16.7)
            cur_f = self.edge_time_s(hour)  # live pricer, same floor
            div = float(np.median(np.abs(pred_f - cur_f)))
            if div > bound:
                return False, {"reason": "divergence beyond bound",
                               "divergence_s": round(div, 2),
                               "bound_s": bound}
            return True, {"divergence_s": round(div, 3), "bound_s": bound}
        return True, {}

    def _load_transformer(self, path: str):
        """(model, params, trained_seq_len) when a fingerprint-compatible
        route-transformer artifact exists, else None."""
        from routest_tpu.train.checkpoint import load_transformer

        loaded = self._load_leg_model(load_transformer, path,
                                      "route_transformer")
        if loaded is None:
            return None
        model, params, meta = loaded
        return model, params, int(meta.get("seq_len", 24))

    def edge_time_s(self, hour: int) -> np.ndarray:
        """(E,) per-edge car travel seconds at the given hour-of-day.

        GNN-predicted when the trained artifact matches this graph
        (cached per hour — 24 small tables max), free-flow physics
        otherwise. This is the on-device replacement for the reference's
        "ask ORS how long this leg takes" (``Flaskr/utils.py:97-109``).
        """
        h = int(hour) % 24
        # ONE consistent snapshot of (model, cache, generation): a
        # concurrent hot-reload can null self._gnn between a bare check
        # and a later read, and its cache clear must invalidate THIS
        # call's eventual write (stale-generation writes are discarded).
        with self._gnn_lock:
            gnn = self._gnn
            gen = self._model_gen
            cached = self._hour_times.get(h)
        if gnn is None:
            return self.freeflow_time_s
        if cached is not None:
            return cached
        from routest_tpu.models.gnn import GraphBatch, edge_feature_array

        model, params = gnn
        e = len(self.length_m)
        batch = GraphBatch(
            senders=self._d_senders,
            receivers=self._d_receivers,
            edge_feats=jnp.asarray(edge_feature_array(
                self.length_m, self.speed_limit, self.road_class, h)),
            length_m=self._d_length,
            speed_limit=self._d_speed,
            targets=jnp.zeros((e,), jnp.float32),
            weights=jnp.ones((e,), jnp.float32),
        )
        try:
            pred = np.asarray(
                model.apply(params, jnp.asarray(self.coords), batch),
                np.float32)
        except Exception as e:
            # A loaded-but-unusable artifact (foreign shapes, backend
            # quirk) must degrade to physics, not 500 the request path;
            # drop it so the cost is paid once, not per request.
            get_logger("routest.road").error(
                "road_gnn_apply_failed", error=f"{type(e).__name__}: {e}")
            with self._gnn_lock:
                if self._model_gen == gen:
                    self._gnn = None
                    self._model_gen += 1
                    self._hour_times.clear()
            return self.freeflow_time_s
        # Physical floor: no edge is faster than free-flow at an
        # arterial ceiling — guards against a degenerate prediction
        # pricing an edge at ~0 s and distorting every route through it.
        pred = np.maximum(pred, self.length_m / 16.7)  # 60 km/h cap
        with self._gnn_lock:
            if self._model_gen == gen:  # don't poison a reloaded cache
                self._hour_times[h] = pred
        return pred

    def _bridge_components(self, senders, receivers, length, road_class,
                           speed_limit):
        """kNN graphs can come out disconnected; bridge every component to
        the largest with an edge between their closest node pair so every
        snap target is reachable. Pure numpy union-find — scipy is a test
        oracle here, not a runtime dependency."""
        n = len(self.coords)
        parent = np.arange(n)

        def find(a: int) -> int:
            root = a
            while parent[root] != root:
                root = parent[root]
            while parent[a] != root:  # path compression
                parent[a], a = root, parent[a]
            return root

        for s, r in zip(senders, receivers):
            ra, rb = find(int(s)), find(int(r))
            if ra != rb:
                parent[rb] = ra
        labels_raw = np.fromiter((find(i) for i in range(n)), np.int64, n)
        _, labels = np.unique(labels_raw, return_inverse=True)
        n_comp = int(labels.max()) + 1
        if n_comp <= 1:
            return senders, receivers, length, road_class, speed_limit
        sizes = np.bincount(labels)
        main = int(np.argmax(sizes))
        add_s, add_r = [], []
        main_nodes = np.flatnonzero(labels == main)
        for comp in range(n_comp):
            if comp == main:
                continue
            nodes = np.flatnonzero(labels == comp)
            d = haversine_np(
                self.coords[nodes, 0][:, None], self.coords[nodes, 1][:, None],
                self.coords[main_nodes, 0][None, :],
                self.coords[main_nodes, 1][None, :])
            i, j = np.unravel_index(np.argmin(d), d.shape)
            add_s.append(nodes[i])
            add_r.append(main_nodes[j])
        add_s = np.asarray(add_s, np.int32)
        add_r = np.asarray(add_r, np.int32)
        bridge_len = (haversine_np(
            self.coords[add_s, 0], self.coords[add_s, 1],
            self.coords[add_r, 0], self.coords[add_r, 1]) * 1.2).astype(np.float32)
        bridge_class = np.full(len(add_s), 1, np.int32)  # collector
        bridge_speed = np.full(len(add_s), _CLASS_SPEED_MPS[1], np.float32)
        return (np.concatenate([senders, add_s, add_r]),
                np.concatenate([receivers, add_r, add_s]),
                np.concatenate([length, bridge_len, bridge_len]),
                np.concatenate([road_class, bridge_class, bridge_class]),
                np.concatenate([speed_limit, bridge_speed, bridge_speed]))

    def snap(self, latlon: np.ndarray) -> np.ndarray:
        """(M, 2) lat/lon → (M,) nearest graph node ids."""
        latlon = np.asarray(latlon, np.float32)
        d = haversine_np(latlon[:, 0][:, None], latlon[:, 1][:, None],
                          self.coords[None, :, 0], self.coords[None, :, 1])
        return np.argmin(d, axis=1).astype(np.int32)

    def shortest(self, source_nodes: np.ndarray,
                 live: Optional[_LiveMetric] = None):
        """(S,) nodes → ((S, N) distances m, (S, N) predecessor edge ids).

        The source axis is padded to power-of-two buckets (duplicating
        source 0) so varying waypoint counts reuse one compiled program
        instead of recompiling the while_loop on the request path — the
        same bucket trick as the serving batcher. Concurrent callers
        whose metric generation matches merge into ONE device dispatch
        through the solve batcher (``_SolveBatcher``) — the row axis is
        batched by construction, so merged results are bitwise what a
        lone solve returns.

        With ``live`` (a snapshot of ``self._live`` taken ONCE by the
        caller, so one request batch never straddles a flip) and its
        route metric armed, the solve runs over the live travel-TIME
        metric instead of meters: distances come back in seconds, and
        predecessor trees are time-shortest (``route_legs_batch``
        recovers leg meters along those trees separately).
        """
        source_nodes = np.asarray(source_nodes, np.int32)
        batcher = self._solve_batcher
        if batcher is not None and 0 < len(source_nodes) <= batcher.max_rows:
            return batcher.solve(source_nodes, live)
        # Direct path (batcher off, or oversized request): still a
        # padded device launch the goodput ledger must see.
        n = len(source_nodes)
        t0 = time.perf_counter()
        out = self._solve_rows(source_nodes, live)
        if n > 0:
            bucket = 1 << max(0, n - 1).bit_length()
            get_ledger().record(
                "route_solve", real_rows=n, padded_rows=bucket,
                bucket=bucket, compute_s=time.perf_counter() - t0,
                oversized=batcher is not None and n > batcher.max_rows)
        return out

    def _solve_rows(self, source_nodes: np.ndarray,
                    live: Optional[_LiveMetric] = None):
        """The real dispatch body behind :meth:`shortest` (the batcher
        calls this with merged rows)."""
        source_nodes = np.asarray(source_nodes, np.int32)
        n_src = len(source_nodes)
        bucket = 1 << max(0, (n_src - 1)).bit_length()
        padded = np.full(bucket, source_nodes[0] if n_src else 0, np.int32)
        padded[:n_src] = source_nodes
        if live is not None and live.route:
            t0 = time.perf_counter()
            if live.hier is not None:
                p_cells, seed_pos, seed_val = live.hier.prep_sources(padded)
                solve = live.aot.get(bucket, live.solve)
                dist, pred = jax.device_get(solve(
                    p_cells, seed_pos, seed_val, jnp.asarray(padded)))
                _router_metrics()["phase"].labels(phase="solve").observe(
                    time.perf_counter() - t0)
                # full_solve_fn already returns ORIGINAL edge ids.
                return dist[:n_src], pred[:n_src]
            # Flat graphs re-dispatch the SAME compiled program with
            # the time weights as arguments — a metric flip costs
            # zero recompiles here.
            dist, pred, converged = jax.device_get(_bellman_ford(
                self._bf_senders, self._bf_receivers, live.d_time_bf,
                jnp.asarray(padded),
                n_nodes=self.n_nodes, max_iters=self.max_iters))
            if not bool(converged):
                dist, pred, _ = jax.device_get(_bellman_ford(
                    self._bf_senders, self._bf_receivers,
                    live.d_time_bf, jnp.asarray(padded),
                    n_nodes=self.n_nodes, max_iters=self.n_nodes))
            _router_metrics()["phase"].labels(phase="solve").observe(
                time.perf_counter() - t0)
            pred = pred[:n_src]
            pred = np.where(pred >= 0, self._bf_perm[np.maximum(pred, 0)],
                            -1)
            return dist[:n_src], pred
        if self._hier is not None:
            # Overlay path: exact distances in O(top-cells-across)
            # sweeps (or one hub-label fold), polish + predecessor
            # recovery on the CONTRACTED graph, and exact chain
            # synthesis back to full-graph rows — all one fused
            # program returning ORIGINAL edge predecessors.
            # Convergence is guaranteed by construction (the overlay
            # loop's bound is its exact node count), so no exhaustion
            # re-run exists. Buckets AOT-compiled at init dispatch the
            # ready executable directly.
            t0 = time.perf_counter()
            p_cells, seed_pos, seed_val = self._hier.prep_sources(padded)
            solve = self._aot.get(bucket, self._overlay_solve)
            dist, pred = jax.device_get(solve(
                p_cells, seed_pos, seed_val, jnp.asarray(padded)))
            _router_metrics()["phase"].labels(phase="solve").observe(
                time.perf_counter() - t0)
            return dist[:n_src], pred[:n_src]
        # ONE batched device_get for (dist, pred, converged): separate
        # np.asarray fetches would each block on their own transfer.
        t0 = time.perf_counter()
        dist, pred, converged = jax.device_get(_bellman_ford(
            self._bf_senders, self._bf_receivers, self._bf_length,
            jnp.asarray(padded),
            n_nodes=self.n_nodes, max_iters=self.max_iters))
        if not bool(converged):
            # The O(√N) diameter heuristic was exhausted while distances
            # were still improving (possible on long chains, e.g. after
            # component bridging, or user-supplied path-like graphs).
            # Silently-wrong distances are never acceptable: re-run with
            # the exact N-1 Bellman-Ford bound.
            get_logger("routest.road").warning(
                "bellman_ford_bound_exhausted", heuristic=self.max_iters,
                exact=self.n_nodes, n_sources=n_src)
            dist, pred, converged = jax.device_get(_bellman_ford(
                self._bf_senders, self._bf_receivers, self._bf_length,
                jnp.asarray(padded),
                n_nodes=self.n_nodes, max_iters=self.n_nodes))
        _router_metrics()["phase"].labels(phase="solve").observe(
            time.perf_counter() - t0)
        pred = pred[:n_src]
        # sorted-edge ids → original edge ids (RoadLegs/_walk index the
        # original arrays, which also carry the GNN's per-edge times)
        pred = np.where(pred >= 0, self._bf_perm[np.maximum(pred, 0)], -1)
        return dist[:n_src], pred

    def _meters_along(self, pred: np.ndarray,
                      metric_rows: np.ndarray) -> np.ndarray:
        """(S, N) meters accumulated along the given predecessor trees
        (pointer doubling — the ``_time_table`` machinery with lengths
        as the per-edge cost). Live-metric solves are time-shortest, so
        leg DISTANCES must be recovered along those trees rather than
        read from the solve's own (seconds) table."""
        m = len(pred)
        bucket = 1 << max(0, (m - 1)).bit_length()
        pad = [(0, bucket - m), (0, 0)]
        n_rounds = max(1, (max(self.n_nodes - 1, 1)).bit_length())
        meters = np.asarray(_time_table(
            self._d_senders, jnp.asarray(np.pad(pred, pad, mode="edge")),
            self._d_length,
            jnp.asarray(np.pad(metric_rows, pad, mode="edge")),
            n_rounds=n_rounds))[:m]
        # Same unreachable sentinel as the distance solve (3e38, finite)
        # so downstream consumers see one convention either way.
        return np.where(np.isfinite(meters), meters,
                        np.float32(3e38)).astype(np.float32)

    def _walk(self, pred_row: np.ndarray, source: int, target: int) -> List[int]:
        """Predecessor edges → node sequence source..target (host-side)."""
        path = [int(target)]
        node = int(target)
        for _ in range(self.n_nodes):
            if node == source:
                break
            e = int(pred_row[node])
            if e < 0:
                return []  # unreachable
            node = int(self.senders[e])
            path.append(node)
        if node != source:
            # Iteration budget exhausted without reaching the source — a
            # predecessor cycle (possible with degenerate zero-length
            # edges). Unreachable beats a garbage path.
            return []
        return path[::-1]

    def route_legs(self, points_latlon: np.ndarray,
                   time_scale: float = 1.0,
                   hour: Optional[int] = None) -> "RoadLegs":
        """Legs between M waypoints over the road graph.

        One batched shortest-path solve up front (all M sources at once —
        the device-friendly part); per-leg predecessor walks, durations,
        and polylines are LAZY and memoized, because the VRP consumes the
        full (M, M) distance matrix but the response only renders the ~M
        legs of the solved trips. ``time_scale`` maps free-flow car times
        to the vehicle profile. ``hour`` (0-23, pickup hour) selects the
        learned congestion regime when the GNN is active; None prices at
        noon off-peak.
        """
        return self.route_legs_batch([(points_latlon, time_scale, hour)])[0]

    def route_legs_batch(self, problems) -> List["RoadLegs"]:
        """Traced entry: the ``router.route_legs`` span carries the
        per-request provenance the PR 10–12 fast paths added — route-
        cache hits/misses/waits, hub-labels vs top-BF solver path,
        serving metric epoch, road-model generation — so a tail-sampled
        slow route trace says WHICH path it took. Body in
        :meth:`_route_legs_batch_traced`."""
        with trace_span("router.route_legs",
                        problems=len(problems)) as span:
            return self._route_legs_batch_traced(problems, span)

    def _route_legs_batch_traced(self, problems, span) -> List["RoadLegs"]:
        """Many waypoint sets → one :class:`RoadLegs` each, sharing as
        FEW device solves as memory allows.

        ``problems``: list of ``(points_latlon, time_scale, hour)``
        triples (``route_legs``'s arguments — the single path IS the
        one-problem batch, so the two can never diverge). The
        shortest-path solver is batched over sources by design, so
        problems concatenate along the source axis and split back as
        row slices — each source row's distances are computed
        independently, so results are bitwise identical to
        per-problem solves. Groups are sized so one fetch (dist f32 +
        pred i32 rows over every node) stays under ~64 MB:
        serving-default graphs take a single call, metro graphs chunk
        instead of materializing a (ΣM, N) table.

        Problems first consult the route fastlane
        (``optimize/route_cache.py``): a cached identical problem —
        same waypoint bytes, time scale, hour, live-metric epoch and
        road-model generation — skips snap AND solve entirely, and
        concurrent identical problems collapse onto one solve
        (singleflight). Only the uncached remainder reaches the
        grouped solves below.
        """
        self._maybe_reload_models()  # once for the whole batch
        pts_list = [np.asarray(p, np.float32) for p, _, _ in problems]
        counts = [len(p) for p in pts_list]
        # ONE live-metric snapshot for the whole batch: every problem in
        # it prices (and, with the route metric armed, routes) against
        # the same metric generation — a concurrent flip affects only
        # later batches, never tears this one.
        live = self._live
        out: List[Optional[RoadLegs]] = [None] * len(problems)
        cache = self._route_cache
        keys: List = [None] * len(problems)
        aliases: List[Tuple[int, int]] = []        # (idx, lead idx)
        waits: List[Tuple[int, object]] = []       # (idx, flight)
        solve_idx: List[int] = list(range(len(problems)))
        if cache is not None:
            epoch = ((live.epoch, live.gen) if live is not None
                     else (0, 0))
            gen = self._model_gen
            my_leads: Dict = {}
            solve_idx = []
            for i, pts in enumerate(pts_list):
                _, time_scale, hour = problems[i]
                eff_hour = 12 if hour is None else int(hour) % 24
                key = (pts.tobytes(), len(pts), float(time_scale),
                       eff_hour, epoch, gen)
                keys[i] = key
                lead = my_leads.get(key)
                if lead is not None:
                    # duplicate inside this batch: share the lead's
                    # legs (waiting on our own flight would deadlock)
                    aliases.append((i, lead))
                    continue
                state, val = cache.lookup(key)
                if state == "hit":
                    out[i] = val
                elif state == "wait":
                    waits.append((i, val))
                else:
                    my_leads[key] = i
                    solve_idx.append(i)

        # Trace provenance: which solver regime, metric generation, and
        # cache outcome served THIS batch (the attrs a tail-sampled
        # slow trace needs to say which path it took).
        span.set_attr(
            "solver",
            "hub_labels" if (self._hier is not None
                             and self._hier._labels is not None)
            else ("overlay_top_bf" if self._hier is not None
                  else "flat_bf"))
        span.set_attr("metric_epoch",
                      live.epoch if live is not None else 0)
        span.set_attr("model_generation", self._model_gen)
        if cache is None:
            span.set_attr("route_cache", "off")
        else:
            span.set_attr("route_cache_hits",
                          sum(1 for o in out if o is not None))
            span.set_attr("route_cache_misses", len(solve_idx))
            span.set_attr("route_cache_waits", len(waits))
            span.set_attr("route_cache_aliases", len(aliases))

        try:
            if solve_idx:
                self._solve_problems(problems, pts_list, counts,
                                     solve_idx, live, out,
                                     copy_rows=cache is not None)
        except BaseException as e:
            if cache is not None:
                for i in solve_idx:
                    cache.abort(keys[i], e)
            raise
        if cache is not None:
            for i in solve_idx:
                legs = out[i]
                cache.commit(keys[i], legs, legs.nbytes())
        for i, lead in aliases:
            out[i] = out[lead]
        if waits:
            # Respect the request budget like the ETA fast lane: a
            # parked waiter must not outlive its deadline waiting on a
            # slow leader.
            from routest_tpu.serve.deadline import current_deadline

            dl = current_deadline()
            budget = (None if dl is None
                      else max(0.0, dl - time.monotonic()))
            for i, flight in waits:
                out[i] = cache.wait(flight, budget)
        return out

    def _solve_problems(self, problems, pts_list, counts, solve_idx,
                        live, out, *, copy_rows: bool) -> None:
        """Snap + grouped solves + :class:`RoadLegs` construction for
        the selected problem indices (the cache-miss remainder).
        ``copy_rows`` detaches each problem's rows from the group
        solve's big arrays so a cached entry can never pin a whole
        (Σrows, N) result."""
        sel_counts = [counts[i] for i in solve_idx]
        offsets = np.concatenate([[0], np.cumsum(sel_counts)])
        all_pts = np.concatenate([pts_list[i] for i in solve_idx], axis=0)
        # snap() materializes an (M, N) haversine table — chunk its row
        # axis too, or a full road batch on a country-scale graph would
        # build the multi-GB host tensor the solve grouping avoids.
        t0 = time.perf_counter()
        snap_chunk = max(1, (16 << 20) // max(self.n_nodes, 1))
        all_nodes = np.concatenate([
            self.snap(all_pts[i:i + snap_chunk])
            for i in range(0, len(all_pts), snap_chunk)])
        _router_metrics()["phase"].labels(phase="snap").observe(
            time.perf_counter() - t0)
        # First/last mile: the request point is rarely ON the network;
        # charge the point↔snapped-node gap into every leg (at collector
        # free-flow for the duration) so far-off-network points see
        # physically sensible totals instead of intra-graph-only paths.
        all_snap = haversine_np(
            all_pts[:, 0], all_pts[:, 1],
            self.coords[all_nodes, 0],
            self.coords[all_nodes, 1]).astype(np.float32)

        budget = _legs_batch_row_budget(self.n_nodes)
        groups: List[List[int]] = []
        cur: List[int] = []
        rows = 0
        for j, m in enumerate(sel_counts):
            if cur and rows + m > budget:
                groups.append(cur)
                cur, rows = [], 0
            cur.append(j)
            rows += m
        if cur:
            groups.append(cur)

        def _rows(a, lo, hi):
            return a[lo:hi].copy() if copy_rows else a[lo:hi]

        for g in groups:
            sel = np.concatenate([np.arange(offsets[j], offsets[j + 1])
                                  for j in g])
            dist, pred = self.shortest(all_nodes[sel], live=live)
            meters = (self._meters_along(pred, dist)
                      if live is not None and live.route else None)
            pos = 0
            for j in g:
                i = solve_idx[j]
                m = sel_counts[j]
                _, time_scale, hour = problems[i]
                eff_hour = 12 if hour is None else int(hour) % 24
                if live is not None:
                    # Live pricing: the legs' per-edge seconds ARE the
                    # installed metric — route solves, leg durations and
                    # the oracle-facing export stay coherent by
                    # construction (hour blending happens at flip time).
                    time_arr = live.time_s
                    cost_model = f"live+{self.leg_cost_model}"
                else:
                    time_arr = self.edge_time_s(eff_hour)
                    cost_model = self.leg_cost_model
                out[i] = RoadLegs(
                    self, pts_list[i],
                    all_nodes[offsets[j]:offsets[j + 1]],
                    _rows(dist, pos, pos + m), _rows(pred, pos, pos + m),
                    all_snap[offsets[j]:offsets[j + 1]],
                    time_scale, time_arr,
                    cost_model, hour=eff_hour,
                    meters_rows=(_rows(meters, pos, pos + m)
                                 if meters is not None else None))
                pos += m


_SNAP_SPEED_MPS = 8.3  # first/last-mile charged at collector free-flow


def _legs_batch_row_budget(n_nodes: int) -> int:
    """Max source rows per grouped batch solve: bounds each dist f32 +
    pred i32 fetch to ~64 MB whatever the graph size (clamped so tiny
    graphs still group generously and huge ones keep ≥16 rows)."""
    return max(16, min(512, (64 << 20) // (8 * max(n_nodes, 1))))


class RoadLegs:
    """Lazy, memoized per-leg view over one batched shortest-path solve."""

    def __init__(self, router: RoadRouter, points: np.ndarray,
                 nodes: np.ndarray, dist: np.ndarray, pred: np.ndarray,
                 snap_m: np.ndarray, time_scale: float,
                 time_s: Optional[np.ndarray] = None,
                 cost_model: str = "freeflow",
                 hour: int = 12,
                 meters_rows: Optional[np.ndarray] = None) -> None:
        self._r = router
        self._hour = hour
        self._points = points
        self._nodes = nodes
        self._pred = pred
        self._snap_m = snap_m
        self._time_scale = time_scale
        self._time_s = time_s if time_s is not None else router.freeflow_time_s
        self.cost_model = cost_model
        # Live-metric solves are TIME-shortest: ``dist`` rows are
        # seconds and ``meters_rows`` carries the meters recovered
        # along those trees — the VRP/ABI distance fields must stay in
        # meters whatever metric chose the paths.
        self._live_metric = meters_rows is not None
        m = len(points)
        # Full matrix (the VRP input): graph distance + first/last mile.
        phys = meters_rows if meters_rows is not None else dist
        self.dist_m = phys[np.arange(m)[:, None], nodes[None, :]] \
            + snap_m[:, None] + snap_m[None, :]
        np.fill_diagonal(self.dist_m, 0.0)
        self._dist_rows = dist            # (M, N): duration_matrix masks by it
        self._dur_rows: Optional[np.ndarray] = None
        self._memo: Dict[Tuple[int, int], Tuple[float, float, list]] = {}
        self._cost_memo: Dict[Tuple[int, int], Tuple[float, float]] = {}

    def nbytes(self) -> int:
        """Resident bytes a cached entry pins (the route fastlane's
        byte-budget input) — the (M, N) solve rows dominate."""
        n = self._pred.nbytes + self._dist_rows.nbytes + self.dist_m.nbytes
        if self._dur_rows is not None:
            n += self._dur_rows.nbytes
        return int(n)

    def _walk_cost(self, i: int, j: int):
        """Memoized shared core: (node_seq, distance_m, duration_s) for
        leg i→j — ONE place owns the predecessor walk and the duration
        formula so the cost-only and geometry accessors can never price
        a leg differently. ``node_seq`` is [] when unreachable."""
        cached = self._cost_memo.get((i, j))
        if cached is not None:
            return cached
        t0 = time.perf_counter()
        node_seq = self._r._walk(self._pred[i], int(self._nodes[i]),
                                 int(self._nodes[j]))
        _router_metrics()["phase"].labels(phase="walk").observe(
            time.perf_counter() - t0)
        if not node_seq:
            out = ([], float("inf"), float("inf"))
        else:
            # pred[i][b] is by construction the edge that enters b here
            dur = self._time_scale * (
                float(sum(self._time_s[int(self._pred[i][b])]
                          for b in node_seq[1:]))
                + (self._snap_m[i] + self._snap_m[j]) / _SNAP_SPEED_MPS)
            out = (node_seq, float(self.dist_m[i, j]), float(dur))
        self._cost_memo[(i, j)] = out
        return out

    def reprice_trips(self, trips) -> Dict[Tuple[int, int], float]:
        """Route-context leg durations from the route transformer.

        ``trips`` is the solved assignment (lists of destination indices,
        ``solve_host`` form). Each trip's legs concatenate into ONE edge
        sequence (origin → stops → origin) and the transformer re-prices
        every edge with route context — per-leg times then depend on
        where in the tour the leg sits, which per-edge pricers (GNN,
        free-flow) cannot express. Returns ``{(i, j): duration_s}`` per
        leg, or ``{}`` when no transformer artifact serves this graph /
        any leg is unwalkable (callers keep base pricing — the same
        graceful-degradation contract as every model here).

        Trips in the solved assignment are stop-disjoint, so (i, j) leg
        keys cannot collide across trips. For ALTERNATIVE orders over
        the same stops use :meth:`reprice_orders` (list-shaped, no keys).
        """
        per_trip = self._reprice([[int(s) for s in t] for t in trips])
        if per_trip is None:
            return {}
        out: Dict[Tuple[int, int], float] = {}
        for legs in per_trip:
            out.update(legs)
        return out

    def reprice_orders(self, orders):
        """Transformer durations for CANDIDATE single-trip orders:
        list of stop-index orders → list of total route seconds (None
        per order when unavailable). One batched forward prices every
        candidate, so alternatives stay comparable with the
        transformer-priced main summary."""
        per_trip = self._reprice([[int(s) for s in o] for o in orders])
        if per_trip is None:
            return [None] * len(orders)
        return [sum(d for _, d in legs.items()) for legs in per_trip]

    def _reprice(self, trips):
        """Shared core: list of trips (stop-index lists) → list of
        ``{(i, j): duration_s}`` per trip, or None when the transformer
        is unavailable / any leg is unwalkable.

        Tours longer than the artifact's trained ``seq_len`` are CHUNKED
        into seq_len windows with window-local positions — exactly the
        training distribution (each training route starts at position 0
        and is ≤ seq_len legs) — so long metro tours never push the
        model out of its validated envelope, and attention cost stays
        O(seq_len²) per window instead of O(tour²).
        """
        t = self._r._transformer
        if t is None or not trips:
            return None
        if self._live_metric:
            # The transformer was trained on the frozen world (free-flow
            # features, no live context); letting it re-price legs would
            # silently overwrite the live-blended durations the metric
            # flip just installed. Base (live) pricing stands.
            return None
        from routest_tpu.models.gnn import edge_feature_array

        model, params, seq_len = t
        r = self._r
        # (trip index, leg key, edge ids) per leg, in tour order.
        trip_legs: list = []
        for trip in trips:
            seq = [0] + [s + 1 for s in trip] + [0]
            legs = []
            for a, b in zip(seq[:-1], seq[1:]):
                if a == b:
                    continue
                node_seq, _m, _s = self._walk_cost(a, b)
                if not node_seq:
                    return None  # unwalkable leg: keep base pricing
                legs.append(((a, b),
                             [int(self._pred[a][n]) for n in node_seq[1:]]))
            trip_legs.append(legs)

        # Flatten every trip's edge sequence into seq_len windows.
        windows: list = []   # (trip_idx, [edge ids])
        for ti, legs in enumerate(trip_legs):
            edges = [e for _, leg_edges in legs for e in leg_edges]
            for start in range(0, len(edges), seq_len):
                windows.append((ti, edges[start: start + seq_len]))
        if not windows:
            return [dict() for _ in trip_legs]
        s_max = max(len(w) for _, w in windows)
        feats = np.zeros((len(windows), s_max, model.n_features), np.float32)
        freeflow = np.zeros((len(windows), s_max), np.float32)
        mask = np.zeros((len(windows), s_max), np.float32)
        for wi, (_, edges) in enumerate(windows):
            e_ids = np.asarray(edges, np.int64)
            k = len(e_ids)
            feats[wi, :k] = edge_feature_array(
                r.length_m[e_ids], r.speed_limit[e_ids],
                r.road_class[e_ids], self._hour)
            freeflow[wi, :k] = r.freeflow_time_s[e_ids]
            mask[wi, :k] = 1.0
        import jax.numpy as jnp

        try:
            pred = np.asarray(model.apply(
                params, jnp.asarray(feats), jnp.asarray(freeflow),
                jnp.arange(s_max), key_mask=jnp.asarray(mask)), np.float32)
        except Exception as e:  # degrade to base pricing, drop the model
            get_logger("routest.road").error(
                "route_transformer_apply_failed",
                error=f"{type(e).__name__}: {e}")
            with r._gnn_lock:
                r._transformer = None
            return None

        # Stitch window predictions back into per-trip edge streams.
        stream: Dict[int, list] = {ti: [] for ti in range(len(trip_legs))}
        for wi, (ti, edges) in enumerate(windows):
            stream[ti].extend(pred[wi, : len(edges)].tolist())
        out: list = []
        for ti, legs in enumerate(trip_legs):
            flat = stream[ti]
            offset = 0
            priced: Dict[Tuple[int, int], float] = {}
            for (a, b), edges in legs:
                k = len(edges)
                e_ids = np.asarray(edges, np.int64)
                # Same physical floor as the GNN pricer: no edge beats
                # free-flow at an arterial ceiling.
                leg_pred = np.maximum(
                    np.asarray(flat[offset: offset + k], np.float32),
                    r.length_m[e_ids] / 16.7)
                offset += k
                priced[(a, b)] = float(self._time_scale * (
                    float(leg_pred.sum())
                    + (self._snap_m[a] + self._snap_m[b]) / _SNAP_SPEED_MPS))
            out.append(priced)
        return out

    def cost(self, i: int, j: int) -> Tuple[float, float]:
        """(distance_m, duration_s) for waypoint leg i→j WITHOUT
        building the polyline — for callers pricing many pairs none of
        which may render (matrix responses, candidate orders). Same
        memoized walk core as :meth:`leg`, so the two can never
        disagree; a later ``leg`` call only adds the geometry pass."""
        if i == j:
            return 0.0, 0.0
        _, dist_m, dur = self._walk_cost(i, j)
        return dist_m, dur

    def duration_matrix(self) -> np.ndarray:
        """(M, M) leg seconds for EVERY waypoint pair in one device
        dispatch. The per-pair walk in :meth:`cost` is O(path length)
        host pointer chasing — fine for a handful of response legs,
        seconds for a full matrix at metro scale. Here the whole
        (M, N) time table accumulates on device via pointer doubling
        (``_time_table``) and the matrix is one gather; values match
        the walk to f32 rounding (sums re-associate). Computed lazily,
        once per solve."""
        if self._dur_rows is None:
            t0 = time.perf_counter()
            r = self._r
            n_rounds = max(1, (max(r.n_nodes - 1, 1)).bit_length())
            # Same bucket trick as shortest(): pad the waypoint axis to
            # a power of two (repeating the last row) so varying M reuses
            # one compiled table program instead of recompiling per count.
            m = len(self._pred)
            bucket = 1 << max(0, (m - 1)).bit_length()
            pad = [(0, bucket - m), (0, 0)]
            self._dur_rows = np.asarray(_time_table(
                r._d_senders,
                jnp.asarray(np.pad(self._pred, pad, mode="edge")),
                jnp.asarray(self._time_s),
                jnp.asarray(np.pad(self._dist_rows, pad, mode="edge")),
                n_rounds=n_rounds))[:m]
            _router_metrics()["phase"].labels(phase="matrix").observe(
                time.perf_counter() - t0)
        dur = self._dur_rows[:, self._nodes].astype(np.float64)
        dur = self._time_scale * (
            dur + (self._snap_m[:, None] + self._snap_m[None, :])
            / _SNAP_SPEED_MPS)
        np.fill_diagonal(dur, 0.0)
        return dur

    def leg(self, i: int, j: int) -> Tuple[float, float, List[List[float]]]:
        """(distance_m, duration_s, [[lon, lat], …]) for waypoint leg i→j."""
        if i == j:
            return 0.0, 0.0, []
        key = (i, j)
        if key in self._memo:
            return self._memo[key]
        node_seq, dist_m, dur = self._walk_cost(i, j)
        if not node_seq:
            out = (float("inf"), float("inf"), [])
        else:
            poly = [[float(self._r.coords[n, 1]), float(self._r.coords[n, 0])]
                    for n in node_seq]
            # endpoints: exact request coordinates, not snapped nodes
            poly.insert(0, [float(self._points[i, 1]), float(self._points[i, 0])])
            poly.append([float(self._points[j, 1]), float(self._points[j, 0])])
            # plain python floats: np.float32 would survive into the JSON
            # serializer (json.dumps rejects it)
            out = (dist_m, dur, poly)
        self._memo[key] = out
        return out


_default_router: Optional[RoadRouter] = None
_default_lock = threading.Lock()


def default_router() -> RoadRouter:
    """Process-wide router: a real OSM extract when ``ROAD_GRAPH_OSM``
    points at one (``data/osm.py``), else the generated Metro Manila
    network. A bad extract degrades to the generator with a log line
    rather than taking down routing."""
    import os

    global _default_router
    with _default_lock:
        if _default_router is None:
            osm_path = os.environ.get("ROAD_GRAPH_OSM")
            if osm_path:
                from routest_tpu.data.osm import load_osm

                try:
                    _default_router = RoadRouter(graph=load_osm(osm_path))
                except Exception as e:
                    get_logger("routest.road").error(
                        "osm_extract_unusable", path=osm_path,
                        error=f"{type(e).__name__}: {e}")
            if _default_router is None:
                _default_router = RoadRouter()
        return _default_router

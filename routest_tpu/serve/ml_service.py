"""ETA inference service: request-coalescing dynamic batcher → one jit call.

The reference runs one CPU tree-walk per HTTP request
(``Flaskr/ml.py:51-53`` — batch size 1, no batching layer at all). The
10k preds/sec target (BASELINE.json) is won here: concurrent requests
coalesce into one device batch, padded to a small set of bucket sizes so
XLA compiles each shape once (SURVEY.md §7.3 item 4).

Failure semantics mirror the reference: a missing/broken model artifact
makes ``predict`` return ``(None, None)`` and the caller degrades
gracefully (route still served without ML fields; ``/predict_eta``
surfaces 503).
"""

from __future__ import annotations

import datetime as dt
import hashlib
import itertools
import math
import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from routest_tpu.core.config import ServeConfig
from routest_tpu.core.mesh import MeshRuntime, pad_rows
from routest_tpu.data.features import encode_requests
from routest_tpu.models.eta_mlp import EtaMLP, Params, eta_path
from routest_tpu.obs import get_registry
from routest_tpu.obs.efficiency import get_ledger
from routest_tpu.obs.export import maybe_device_trace
from routest_tpu.obs.ledger import record_change
from routest_tpu.obs.trace import trace_span
from routest_tpu.serve.deadline import DeadlineExceeded
from routest_tpu.train.checkpoint import default_model_path, load_model


class _ServingState:
    """One immutable bundle of everything a prediction needs — model,
    batcher, quantile levels. Readers snapshot ``self._serving`` ONCE
    per request and use only the snapshot, so a hot-reload (which swaps
    the single attribute) can never hand a request the OLD batcher's
    output shape with the NEW model's quantile metadata (a torn read
    that would mis-index or mis-label the row).

    ``generation`` is a process-unique id for this serving state (one
    ``next()`` of the module counter per successful bring-up). The
    fast-lane prediction cache keys on it, so a hot-reload makes every
    cached prediction of the OLD model unreachable the instant the
    snapshot flips — cache coherency falls out of the same one-flip
    design that prevents torn reads."""

    __slots__ = ("model", "batcher", "quantiles", "generation")

    def __init__(self, model, batcher, quantiles,
                 generation: int = -1) -> None:
        self.model = model
        self.batcher = batcher
        self.quantiles = tuple(quantiles or ())
        self.generation = generation


_EMPTY_SERVING = _ServingState(None, None, ())

# Model-generation counter: every serving state that goes live anywhere
# in the process (startup, hot-reload replacement) draws a fresh id.
_GENERATION = itertools.count()

# Change-delivery observability (docs/ROBUSTNESS.md "Safe change
# delivery"): every verified-swap verdict counts here, and the gauge
# tracks the LIVE generation so version skew across a fleet is readable
# from /api/metrics without parsing logs.
_m_swaps = get_registry().counter(
    "rtpu_model_swaps_total",
    "Model hot-swap attempts, by result (accepted / rejected).",
    ("result",))
_m_generation = get_registry().gauge(
    "rtpu_model_generation",
    "Generation id of the live serving model (monotonic per process).")
# Scoring-artifact observability: one observation per AOT bucket compile
# at bring-up. The per-bucket COUNT doubles as the "no compile after
# startup" assertion — if it ever grows while serving, a customer request
# paid a compile.
_m_aot_compile = get_registry().histogram(
    "rtpu_replica_aot_compile_seconds",
    "AOT compile of the score program per batch bucket "
    "(jit().lower().compile() at serving bring-up).", ("bucket",))
_m_cold_start = get_registry().gauge(
    "rtpu_replica_cold_start_seconds",
    "Service-construction-to-ready wall time of the live serving state "
    "(model load + AOT bucket compiles + self-check + warmup).")


def _artifact_fingerprint(path: str) -> Optional[str]:
    """Content fingerprint of the serving artifact (sha256, short) —
    the identity the rollout controller and ``/api/version`` report, so
    'which bytes is r3 actually serving?' has a one-line answer."""
    try:
        digest = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                digest.update(chunk)
        return digest.hexdigest()[:16]
    except OSError:
        return None


_GOLDEN_BATCH: Optional[np.ndarray] = None


def golden_batch() -> np.ndarray:
    """Deterministic verification rows spanning the feature domain.

    Every (weather × traffic) category pair appears twice, with
    weekday/hour/distance/driver-age swept across their ranges — the
    fixed batch a replacement artifact must score finitely (and close
    to the live model, see ``swap_max_divergence``) before a hot-swap
    flips the serving generation. Encoded once per process; the rows
    are plain model inputs, so the same batch verifies MLP, quantile,
    GBDT, and AOT-export artifacts alike (shared 12-feature ABI)."""
    global _GOLDEN_BATCH
    if _GOLDEN_BATCH is None:
        from routest_tpu.data.features import (TRAFFIC_CATEGORIES,
                                               WEATHER_CATEGORIES)

        combos = [(w, t) for w in WEATHER_CATEGORIES
                  for t in TRAFFIC_CATEGORIES]
        n = 2 * len(combos)
        _GOLDEN_BATCH = encode_requests(
            weather=[w for w, _ in combos] * 2,
            traffic=[t for _, t in combos] * 2,
            weekday=[i % 7 for i in range(n)],
            hour=[(7 * i) % 24 for i in range(n)],
            distance_km=[0.5 + (i % 12) * 2.5 for i in range(n)],
            driver_age=[20.0 + (i % 8) * 5.0 for i in range(n)],
        )
    return _GOLDEN_BATCH


class _InReload(threading.local):
    flag = False


_in_reload = _InReload()


def _parse_pickup_single(pickup_time) -> dt.datetime:
    """Single-row pickup parsing (reference semantics, ``Flaskr/ml.py``):
    ISO string → datetime (offset preserved), datetime passes through,
    anything else → now. Both single-row entry points share this so the
    completion timestamp keeps the caller's offset regardless of which
    model family serves."""
    if isinstance(pickup_time, str):
        try:
            return dt.datetime.fromisoformat(pickup_time)
        except ValueError:
            return dt.datetime.now()
    if isinstance(pickup_time, dt.datetime):
        return pickup_time
    return dt.datetime.now()


def _band_label(level: float) -> str:
    """Quantile level → response-field suffix: 0.1 → "p10", 0.975 →
    "p97.5" — exact and collision-free where percent-rounding would fold
    0.015 and 0.025 into the same key."""
    return f"p{level * 100:.10g}"


class _Pending:
    """One waiter. Rows live in ONE of two places: the batcher's staging
    slab (``slab=True``, located by ``offset``) — the zero-copy fast
    path — or the waiter's own array (``rows``), the fallback for
    oversized submissions and slab overflow."""

    __slots__ = ("rows", "slab", "offset", "n", "event", "result", "error",
                 "deadline", "t_q")

    def __init__(self, rows: Optional[np.ndarray] = None,
                 deadline: Optional[float] = None, *,
                 n: Optional[int] = None, offset: int = 0) -> None:
        self.rows = rows          # fallback path only (slab entries: None)
        self.slab = rows is None
        self.offset = offset      # row offset inside the staging slab
        self.n = len(rows) if rows is not None else int(n or 0)
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        # Absolute time.monotonic() deadline captured from the ambient
        # request context at submit; None = no budget.
        self.deadline = deadline
        # Enqueue stamp: the goodput ledger's queue-vs-compute split
        # charges each launch the oldest rider's wait.
        self.t_q = time.monotonic()


class _WindowController:
    """Adaptive flush window (Clipper-style AIMD goal, EWMA-rate form):
    pick the wait the CURRENT arrival rate justifies instead of a fixed
    one. At low rates, waiting buys nothing — no peer will arrive inside
    any reasonable window — so the window collapses to ``min_wait``
    (latency mode). At high rates the window grows toward ``max_wait``,
    sized to fill the largest bucket the rate can fill within the cap
    (throughput mode; in practice ``max_batch`` triggers first and the
    window is only the backstop). The rate estimate is a time-constant
    EWMA of rows/s over submit arrivals — bursty thread schedules decay
    smoothly instead of whipsawing the window."""

    __slots__ = ("buckets", "max_wait", "min_wait", "tau", "rate", "_last")

    def __init__(self, buckets: Sequence[int], max_wait_s: float,
                 min_wait_s: float = 0.0, tau_s: float = 0.5) -> None:
        self.buckets = tuple(buckets)
        self.max_wait = max_wait_s
        self.min_wait = min(min_wait_s, max_wait_s)
        self.tau = tau_s
        self.rate = 0.0           # rows/s, EWMA
        self._last: Optional[float] = None

    def observe(self, n_rows: int, now: float) -> None:
        if self._last is None:
            self._last = now
            self.rate = 0.0
            return
        dt = max(now - self._last, 1e-6)
        self._last = now
        # Time-constant EWMA: weight of the new sample grows with the
        # gap, so a long idle stretch decays the rate toward the new
        # (low) instantaneous value instead of remembering a burst.
        w = 1.0 - math.exp(-dt / self.tau)
        self.rate += w * (n_rows / dt - self.rate)

    def window_s(self, flush_s: float = 0.0) -> float:
        """The wait the current rate justifies, in seconds.

        ``flush_s`` is the batcher's EWMA flush duration: once arrivals
        come faster than flushes complete (``rate × flush_s ≥ 1``),
        waiting ~one flush duration coalesces at zero marginal latency
        — the flush slot is busy for that long anyway, and flushing
        every lone row instead just multiplies per-dispatch overhead
        (measured: 26% throughput LOSS on the all-unique closed-loop
        workload without this floor)."""
        if self.max_wait <= 0:
            return self.min_wait
        fillable = self.rate * self.max_wait
        busy = self.rate * max(flush_s, 0.0) >= 1.0
        # Latency mode: traffic so light that neither the cap window nor
        # an in-progress flush would supply a peer to batch with —
        # waiting is pure added latency.
        if fillable < self.buckets[0] and not busy:
            return self.min_wait
        # Throughput mode: wait long enough to fill the largest bucket
        # the rate can fill inside the cap, floored at one flush
        # duration when the batcher is saturated.
        bucket = max((b for b in self.buckets if b <= fillable),
                     default=self.buckets[0])
        want = bucket / self.rate if self.rate > 0 else self.max_wait
        if busy:
            want = max(want, flush_s)
        return min(self.max_wait, max(want, self.min_wait))


class DynamicBatcher:
    """Coalesce concurrent scoring requests into bucket-padded device calls.

    Requests enqueue feature rows and block; a flusher drains the queue
    whenever ``max_batch`` rows are waiting or the oldest request has
    waited ``max_wait_ms``. Flushing happens on the caller thread that
    triggers the condition — no dedicated thread, no idle spinning.
    """

    def __init__(self, score_fn, buckets: Sequence[int], max_batch: int,
                 max_wait_ms: float, align: int = 1,
                 hard_cap_s: float = 60.0, adaptive: bool = False,
                 min_wait_ms: float = 0.0) -> None:
        self._score = score_fn
        # Waiter give-up bound: a submit with no request deadline still
        # cannot wait past this — a wedged flush thread (device hang)
        # must surface as DeadlineExceeded, not pin the waiter forever.
        self._hard_cap_s = hard_cap_s
        # ``align`` = mesh data-shard count: every device batch must divide
        # evenly across the data axis, so bucket sizes round up to multiples.
        self._align = max(1, align)
        self._buckets = sorted(
            {((b + self._align - 1) // self._align) * self._align for b in buckets}
        )
        self._max_batch = max_batch
        # Drain cap: flush shapes must stay bucketed even when an
        # operator sets max_batch above the largest bucket (the bucket
        # list is fixed while RTPU_MAX_BATCH is env-configurable).
        self._drain_cap = min(max_batch, self._buckets[-1])
        self._max_wait = max_wait_ms / 1000.0
        self._lock = threading.Lock()
        self._queue: List[_Pending] = []
        self._queued_rows = 0
        self._flushing = False
        # Zero-copy staging: submits write rows straight into a
        # preallocated slab (capacity = the largest bucket); a flush
        # detaches the slab, pads IN PLACE, and hands a view to the
        # device — no per-flush np.concatenate, no pad allocation.
        # Allocated lazily at first submit (feature width unknown until
        # then); ``_spare`` recycles the one detached slab a flush can
        # have in flight at a time.
        self._slab: Optional[np.ndarray] = None
        self._spare: Optional[np.ndarray] = None
        self._staged = 0
        # Adaptive flush window (off by default: direct constructions —
        # tests, embedders — keep the fixed-window contract; EtaService
        # wires it from ServeConfig.adaptive_wait).
        self._ctrl = (_WindowController(self._buckets, self._max_wait,
                                        min_wait_ms / 1000.0)
                      if adaptive else None)
        # EWMA flush duration feeding the adaptive controller's
        # saturation floor (rate × flush ≥ 1 → waiting is free).
        self._flush_ewma_s = 0.0
        self.stats = {"flushes": 0, "rows": 0, "max_batch_seen": 0,
                      "zero_copy_flushes": 0}
        # Unified-registry view of the batching stages (ISSUE 2): until
        # now queue wait vs. assembly vs. device compute were
        # indistinguishable from outside — these histograms + the stage
        # spans in submit()/_flush() are what the next perf PRs read.
        reg = get_registry()
        self._m_queue_wait = reg.histogram(
            "rtpu_batcher_queue_wait_seconds",
            "Submit-to-result wait inside the dynamic batcher.")
        self._m_flush = reg.histogram(
            "rtpu_batcher_flush_seconds",
            "One drain: assembly + pad + device compute.")
        self._m_compute = reg.histogram(
            "rtpu_batcher_device_compute_seconds",
            "Device scoring call per flush, by pad bucket.", ("bucket",))
        self._m_fill = reg.histogram(
            "rtpu_batcher_fill_ratio", "Real rows / padded bucket rows.",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        self._m_rows = reg.counter(
            "rtpu_batcher_rows_total", "Rows scored through the batcher.")
        self._m_flushes = reg.counter(
            "rtpu_batcher_flushes_total", "Batcher drains executed.")
        self._m_expired = reg.counter(
            "rtpu_batcher_expired_total",
            "Requests whose deadline expired inside the batcher: "
            "dropped at drain time (stage=drain) or abandoned by their "
            "waiter (stage=wait). Expired rows never reach the device.",
            ("stage",))
        self._m_window = reg.gauge(
            "rtpu_batcher_wait_window_ms",
            "Flush window currently in force (adaptive controller or "
            "the fixed max_wait_ms).")
        self._m_window.set(max_wait_ms)
        self._m_zero_copy = reg.counter(
            "rtpu_batcher_zero_copy_flushes_total",
            "Flushes assembled in place from the staging slab "
            "(no concatenate/pad allocation).")

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        # oversized: exact shape, rounded up to the shard multiple
        return ((n + self._align - 1) // self._align) * self._align

    def _stage_locked(self, rows: np.ndarray, deadline) -> _Pending:
        """Lock held: place the rows. Fast path writes them straight
        into the staging slab (ONE copy, into memory the device batch
        will be a view of); fallback (oversized rows, slab full under a
        flush in flight, unexpected shape) keeps the waiter's own array
        for the concatenate path."""
        n = len(rows)
        cap = self._buckets[-1]
        if getattr(rows, "ndim", 0) == 2 and n <= cap - self._staged:
            if self._slab is None:
                self._slab = np.empty((cap, rows.shape[1]), np.float32)
            if self._slab.shape[1] == rows.shape[1]:
                offset = self._staged
                self._slab[offset:offset + n] = rows
                self._staged += n
                return _Pending(deadline=deadline, n=n, offset=offset)
        return _Pending(rows, deadline=deadline)

    def _repack_locked(self, src: np.ndarray) -> None:
        """Lock held: re-pack every queued slab entry into a dense
        prefix of the CURRENT slab, reading each entry's rows from
        ``src`` (the old slab after a drain detached it, or the current
        one after a mid-queue withdrawal left a hole). Queue order ==
        offset order, so one forward pass suffices; same-buffer moves
        are always downward (numpy buffers overlapping assignments)."""
        dst = 0
        for p in self._queue:
            if not p.slab:
                continue
            if src is not self._slab or p.offset != dst:
                self._slab[dst:dst + p.n] = src[p.offset:p.offset + p.n]
                p.offset = dst
            dst += p.n
        self._staged = dst

    def _withdraw_locked(self, pending: _Pending) -> bool:
        """Lock held: remove a still-queued entry (deadline give-up)."""
        if pending not in self._queue:
            return False
        self._queue.remove(pending)
        self._queued_rows -= pending.n
        if pending.slab and self._slab is not None:
            self._repack_locked(self._slab)
        return True

    def submit(self, rows: np.ndarray) -> np.ndarray:
        from routest_tpu.serve.deadline import current_deadline

        t_submit = time.perf_counter()
        t_mono = time.monotonic()
        req_deadline = current_deadline()
        # Waiter give-up point: the request's own deadline when it has
        # one, else the batcher's hard cap. Without this, a wedged
        # flush thread (device hang) pinned every waiter in a 1 ms spin
        # forever.
        give_up_at = t_mono + self._hard_cap_s
        if req_deadline is not None:
            give_up_at = min(give_up_at, req_deadline)
        with trace_span("batcher.queue_wait", rows=len(rows)) as qs:
            with self._lock:
                pending = self._stage_locked(rows, req_deadline)
                self._queue.append(pending)
                self._queued_rows += pending.n
                if self._ctrl is not None:
                    self._ctrl.observe(pending.n, t_mono)
                    wait_s = self._ctrl.window_s(self._flush_ewma_s)
                    if wait_s <= 0.0 and (self._flushing
                                          or len(self._queue) > 1):
                        # Queue-depth feedback: latency mode only when
                        # the batcher is IDLE. With a flush in flight
                        # (or peers queued) an immediate drain would
                        # fragment batches into lone-row flushes —
                        # floor the wait at one flush duration so we
                        # drain alongside our peers instead.
                        wait_s = min(max(self._flush_ewma_s, 0.0005),
                                     self._max_wait)
                    self._m_window.set(wait_s * 1000.0)
                else:
                    wait_s = self._max_wait
                should_flush = (self._queued_rows >= self._max_batch
                                and not self._flushing)
            # A flush exception here may belong to OTHER requests' rows
            # (the capped drain can exclude ours); our own failure
            # arrives via pending.error below, so never re-raise from
            # the shared flush.
            # A zero adaptive window is latency mode: drain NOW instead
            # of sleeping one spin tick first — at low arrival rates the
            # batch is this request alone either way.
            if should_flush or wait_s <= 0.0:
                self._flush_quietly()
            deadline = time.monotonic() + wait_s
            spin = 0.001
            while True:
                # Oldest-waiter timeout: whoever wakes first drains the
                # queue. After the deadline, short waits keep a flush in
                # flight on another thread from being hot-spun against —
                # escalating (1 → 50 ms) so a wedged flush costs wakeups,
                # not a pinned core — and ``give_up_at`` bounds the whole
                # wait: past it the entry is withdrawn and the waiter
                # raises DeadlineExceeded.
                now = time.monotonic()
                if now >= give_up_at and not pending.event.is_set():
                    with self._lock:
                        self._withdraw_locked(pending)
                    if not pending.event.is_set():
                        qs.set_attr("expired", True)
                        self._m_expired.labels(stage="wait").inc()
                        self._m_queue_wait.observe(
                            time.perf_counter() - t_submit)
                        raise DeadlineExceeded(
                            f"batcher wait exceeded "
                            f"{(now - t_mono) * 1000:.0f} ms budget")
                remaining = deadline - now
                if remaining <= 0:
                    remaining = spin
                    spin = min(spin * 2, 0.05)
                wait = max(min(remaining, give_up_at - now + 0.001), 0.001)
                if pending.event.wait(timeout=wait):
                    break
                if time.monotonic() >= give_up_at:
                    continue  # give up (loop top) rather than start a
                              # flush this waiter can no longer wait for
                self._flush_quietly()
            qs.set_attr("flushed_inline", should_flush)
        self._m_queue_wait.observe(time.perf_counter() - t_submit)
        if pending.error is not None:
            # A dead device must surface as an error on EVERY waiter, not
            # only the thread that happened to run the flush — silent NaN
            # fills would 200 with all-null columns while the TPU is down.
            raise pending.error
        assert pending.result is not None
        return pending.result

    def _flush_quietly(self) -> None:
        """Run a flush whose exceptions belong to the affected waiters
        (delivered via their ``pending.error``), not to this caller."""
        try:
            self._flush()
        except Exception as e:
            from routest_tpu.utils.logging import get_logger

            get_logger("routest_tpu.serve").debug(
                "batcher_flush_failed", error=f"{type(e).__name__}: {e}")

    def _flush(self) -> None:
        from routest_tpu.chaos import inject as chaos_inject

        while True:
            expired: List[_Pending] = []
            batch_slab: Optional[np.ndarray] = None
            with self._lock:
                if self._flushing or not self._queue:
                    return
                # Deadline drop at drain time: an entry whose budget
                # expired while queued is withdrawn BEFORE batch
                # assembly — the device batch must never contain rows
                # nobody is waiting for (its waiter gets 504 below).
                now = time.monotonic()
                keep = []
                for p in self._queue:
                    if p.deadline is not None and now >= p.deadline:
                        expired.append(p)
                        self._queued_rows -= p.n
                    else:
                        keep.append(p)
                if expired:
                    self._queue[:] = keep
                    if any(p.slab for p in expired) and self._slab is not None:
                        self._repack_locked(self._slab)
                if not self._queue:
                    batch: List[_Pending] = []
                    taken = cnt = 0
                else:
                    self._flushing = True
                    # Drain at most max_batch rows (whole requests): with
                    # submissions pre-chunked to the largest bucket, every
                    # flush shape stays bucketed — unbounded drains
                    # compiled a fresh XLA executable per novel
                    # concatenated size.
                    taken = cnt = 0
                    for p in self._queue:
                        if cnt and taken + p.n > self._drain_cap:
                            break
                        taken += p.n
                        cnt += 1
                    batch = self._queue[:cnt]  # O(k) slice, not O(n) pops
                    del self._queue[:cnt]
                    self._queued_rows -= taken
                    if batch and all(p.slab for p in batch):
                        # Zero-copy drain: the batch IS the slab's
                        # [0:taken] prefix (offsets are assigned in
                        # queue order). Detach it, install the spare,
                        # and move any leftover staged rows across so
                        # queued entries always reference the live slab.
                        batch_slab = self._slab
                        self._slab = (self._spare if self._spare is not None
                                      else np.empty_like(batch_slab))
                        self._spare = None
                        self._repack_locked(batch_slab)
                    elif batch:
                        # Mixed batch (slab-overflow fallback entries
                        # interleaved): materialize the slab rows and
                        # take the concatenate path; leftovers re-pack.
                        for p in batch:
                            if p.slab:
                                p.rows = self._slab[
                                    p.offset:p.offset + p.n].copy()
                                p.slab = False
                        if self._slab is not None:
                            self._repack_locked(self._slab)
            for p in expired:
                p.error = DeadlineExceeded("expired in batch queue")
                p.event.set()
            if expired:
                self._m_expired.labels(stage="drain").inc(len(expired))
            if not batch:
                return
            try:
                t_flush = time.perf_counter()
                queue_s = max(0.0, time.monotonic()
                              - min(p.t_q for p in batch))
                with trace_span("batcher.flush", requests=cnt) as fs:
                    n = taken
                    bucket = self._bucket(n)
                    fs.set_attr("rows", n)
                    fs.set_attr("bucket", bucket)
                    fs.set_attr("zero_copy", batch_slab is not None)
                    with trace_span("batcher.pad", rows=n, bucket=bucket,
                                    pad_rows=bucket - n):
                        if batch_slab is not None:
                            # Pad in place: zero the tail rows of the
                            # detached slab and hand the device a VIEW —
                            # no concatenate, no pad allocation.
                            if bucket > n:
                                batch_slab[n:bucket] = 0.0
                            padded = batch_slab[:bucket]
                        else:
                            padded = pad_rows(
                                np.concatenate([p.rows for p in batch],
                                               axis=0), bucket)
                    t_dev = time.perf_counter()
                    with trace_span("batcher.device_compute", rows=n,
                                    bucket=bucket) as ds:
                        # Chaos fault point: an injected error here is
                        # indistinguishable from a dead device — every
                        # waiter in this batch must surface it. A
                        # ``skew`` fault returns a magnitude applied to
                        # the scored outputs below: a silently-wrong
                        # device, which nothing in-process can notice
                        # (the blackbox prober's target fault).
                        skew = chaos_inject("device.compute")
                        # xplane capture budget permitting, a sampled
                        # flush also records the device trace that
                        # explains it (one trace id across both).
                        with maybe_device_trace(ds):
                            out = self._score(padded)
                            preds = np.asarray(out)[:n]
                        # Where the flush actually ran (health's
                        # ``checks.tpu.batcher.last_flush``): a mesh
                        # replica must show the bucket split over its
                        # whole slice, not resident on one device.
                        sharding = getattr(out, "sharding", None)
                        if sharding is not None:
                            self.stats["last_flush"] = {
                                "bucket": bucket,
                                "devices": sorted(
                                    d.id for d in sharding.device_set),
                                "rows_per_device": int(
                                    sharding.shard_shape(out.shape)[0])}
                        if skew:
                            preds = preds + skew
                    if batch_slab is not None and \
                            np.shares_memory(preds, batch_slab):
                        # A host score_fn may hand back a view of its
                        # input; the slab is about to be recycled, so
                        # waiters must own their rows.
                        preds = preds.copy()
                    compute_s = time.perf_counter() - t_dev
                    self._m_compute.labels(bucket=bucket).observe(
                        compute_s)
                get_ledger().record(
                    "eta_score", real_rows=n, padded_rows=bucket,
                    bucket=bucket, queue_s=queue_s, compute_s=compute_s,
                    oversized=n > self._buckets[-1])
                flush_dur = time.perf_counter() - t_flush
                self._m_flush.observe(flush_dur)
                self._flush_ewma_s += 0.3 * (flush_dur - self._flush_ewma_s)
                self._m_fill.observe(n / bucket if bucket else 1.0)
                self._m_rows.inc(n)
                self._m_flushes.inc()
                self.stats["flushes"] += 1
                self.stats["rows"] += n
                if batch_slab is not None:
                    self.stats["zero_copy_flushes"] += 1
                    self._m_zero_copy.inc()
                self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"], n)
                offset = 0
                for p in batch:
                    p.result = preds[offset: offset + p.n]
                    offset += p.n
                    p.event.set()
            except Exception as e:
                for p in batch:
                    p.error = e
                    p.event.set()
                raise
            finally:
                with self._lock:
                    self._flushing = False
                    # Slab-rotation safety (the AOT entry DONATES its
                    # input): the detached slab re-enters circulation
                    # only HERE, after the flush's device call fully
                    # consumed its copy — an in-flight donated buffer
                    # is never rewritten (fuzzed in
                    # test_scoring_artifact.py).
                    if batch_slab is not None and self._spare is None:
                        self._spare = batch_slab
                    more = self._queued_rows >= self._drain_cap
            if not more:
                return


class EtaService:
    """Model lifecycle + prediction API for the serving layer."""

    def __init__(self, cfg: Optional[ServeConfig] = None,
                 model_path: Optional[str] = None,
                 runtime: Optional[MeshRuntime] = None) -> None:
        cfg = cfg or ServeConfig()
        self._t_construct = time.perf_counter()
        self._cfg = cfg
        self._runtime = runtime
        self._model: Optional[EtaMLP] = None
        self._params: Optional[Params] = None
        self._error: Optional[str] = None
        # Scoring-artifact introspection (scoring_info() / health):
        # which compute path serves, at what dtype, with which buckets
        # AOT-compiled.
        self.kernel_dtype: Optional[str] = None
        self._aot_buckets: Tuple[int, ...] = ()
        self._path = model_path or default_model_path()
        self._loaded_mtime_ns = self._artifact_mtime_ns()
        self._reload_lock = threading.Lock()
        self.fingerprint: Optional[str] = None
        self.loaded_unix: Optional[float] = None
        self._load(self._path)
        self._batcher: Optional[DynamicBatcher] = None
        self._serving = _EMPTY_SERVING
        # Fast lane (serve/fastlane.py): per-row prediction cache +
        # singleflight consulted in _predict_rows before the batcher.
        # None when both features are configured off.
        self._fastlane = None
        if cfg.fastlane_cache or cfg.fastlane_singleflight:
            from routest_tpu.serve.fastlane import FastLane

            self._fastlane = FastLane(
                capacity=cfg.fastlane_cache_size,
                ttl_s=cfg.fastlane_cache_ttl_s,
                cache=cfg.fastlane_cache,
                singleflight=cfg.fastlane_singleflight,
                max_rows=cfg.fastlane_max_rows)
        # which forward path serves: xla | pallas_fused |
        # xla+pallas_fused(>=N) | xla_tp | stablehlo_aot[_sharded]
        self.kernel = "xla"
        # Hot-reload watcher (cfg.reload_sec > 0): the SERVICE owns it,
        # so embedders constructing EtaService directly get it too —
        # not only `python -m routest_tpu.serve`. Suppressed inside a
        # reload's own replacement construction (the parent watches).
        if cfg.reload_sec > 0 and not _in_reload.flag:
            self._watcher_stop = self.start_reload_watcher(cfg.reload_sec)
        # Warm the native encoder now: its first use triggers a g++
        # build (content-cached), which must happen at startup, not
        # inside the first customer request's batcher flush.
        from routest_tpu import native

        native.available()
        if self.available:
            from routest_tpu.train.checkpoint import ExportedServingModel

            if isinstance(self._model, ExportedServingModel):
                # AOT export: the traced program IS the artifact
                # (weights baked in as constants). A mesh runtime no
                # longer gets refused: the serialized program compiles
                # UNDER a jit with the mesh's batch sharding — the same
                # artifact a multi-chip mesh fans out, compiled with
                # shardings (ROADMAP item 2's contract). Per-bucket AOT
                # compiles happen here exactly like the msgpack path.
                exported = self._model
                from routest_tpu.utils.logging import get_logger

                if os.environ.get("ROUTEST_FUSED") == "1":
                    get_logger("routest_tpu.serve").warning(
                        "fused_kernel_ignored",
                        reason="AOT exports run their serialized program "
                               "as-is; ROUTEST_FUSED has no effect")
                self.kernel_dtype = "export"

                def direct_score(x: np.ndarray) -> np.ndarray:
                    # Shape-polymorphic single-device call: the fallback
                    # for non-bucket shapes on every export path.
                    return exported(np.asarray(x, np.float32))

                if runtime is not None and cfg.serve_aot:
                    sharding = runtime.batch_sharding()
                    jitted = jax.jit(exported.call,
                                     in_shardings=(sharding,),
                                     donate_argnums=(0,))
                    score = self._aot_score(jitted, (), sharding,
                                            direct_score,
                                            align=runtime.n_data)
                    if score is not None:
                        self.kernel = "stablehlo_aot_sharded"
                        self._finish_init(score, align=runtime.n_data)
                        return
                    # Loud degrade (e.g. an export whose recorded device
                    # count cannot execute on this mesh): the artifact
                    # still serves single-device rather than not at all.
                    get_logger("routest_tpu.serve").warning(
                        "aot_mesh_incompatible",
                        reason="exported program would not compile under "
                               "the mesh's shardings; serving single-"
                               "device (re-export on this topology)")
                score = None
                if cfg.serve_aot:
                    jitted = jax.jit(exported.call, donate_argnums=(0,))
                    score = self._aot_score(jitted, (), None, direct_score)
                self.kernel = "stablehlo_aot"
                self._finish_init(score or direct_score, align=1)
                return
            # Quantile models score ALL heads per row — (B, Q) through the
            # batcher — so one device call serves both the median (the
            # reference ABI's single eta) and the uncertainty band (its
            # non-crossing construction is fused into the score program,
            # models/eta_mlp.quantile_heads).
            forward = (self._model.apply_quantiles if self.quantiles
                       else self._model.apply)
            if runtime is not None:
                # over a mesh the batch is sharded, and a Mosaic kernel
                # cannot be partitioned: the XLA body by name
                forward = (self._model.apply_quantiles_xla if self.quantiles
                           else self._model.apply_xla)
            apply_jit = jax.jit(forward)
            if self.kernel_dtype is None and hasattr(self._model, "policy"):
                self.kernel_dtype = np.dtype(
                    self._model.policy.compute_dtype).name
            # load_model returns host numpy arrays; pin them on device once
            # or every scoring call re-uploads the whole param tree.
            if runtime is not None:
                if os.environ.get("ROUTEST_FUSED") == "1":
                    from routest_tpu.utils.logging import get_logger

                    get_logger("routest_tpu.serve").warning(
                        "fused_kernel_ignored",
                        reason="ROUTEST_FUSED=1 is single-device only; "
                               "mesh serving uses the sharded XLA path")
                score = self._maybe_tp_score(runtime)
                if score is None:  # replicated weights, batch-sharded
                    params = runtime.replicate(self._params)

                    def score(x: np.ndarray) -> np.ndarray:
                        return apply_jit(
                            params, runtime.shard_batch(jax.numpy.asarray(x)))

                    if cfg.serve_aot:
                        # Shard-ready AOT: compile each bucket WITH the
                        # mesh's batch sharding (params replicated) —
                        # the same compiled artifact multi-chip serving
                        # fans out, per ROADMAP item 2.
                        aot = self._aot_score(
                            jax.jit(forward, donate_argnums=(1,)),
                            (params,), runtime.batch_sharding(), score,
                            align=runtime.n_data)
                        score = aot or score
            else:
                params = jax.device_put(self._params)

                def jit_score(x: np.ndarray) -> np.ndarray:
                    return apply_jit(params, x)

                score = jit_score
                if cfg.serve_aot:
                    aot = self._aot_score(
                        jax.jit(forward, donate_argnums=(1,)),
                        (params,), None, jit_score)
                    score = aot or score
                self.kernel = self._chosen_kernel()
                score = self._maybe_fused_score(score)
            self._finish_init(
                score, align=runtime.n_data if runtime is not None else 1)

    def _aot_score(self, jitted, leading: tuple, x_sharding, fallback,
                   align: int = 1):
        """Per-bucket AOT serving entry: ``jit().lower().compile()`` the
        full score program for every (align-rounded) batch bucket NOW,
        so no bucket ever pays trace+compile — or the jit call's python
        dispatch — on a customer request. The input argument is DONATED
        (``jitted`` is built with ``donate_argnums`` on the slab arg):
        the device copy of the batcher's staging slab is consumed by the
        computation, so XLA reuses its buffer for outputs/temporaries
        instead of allocating fresh — no defensive copy exists anywhere
        on the path (the numpy slab itself is never aliased by the
        device: the host→device transfer is the one copy, and the slab
        is detached from the queue for the whole flush, so donation can
        never rewrite rows a waiter still owns). Backends that cannot
        donate (CPU XLA) silently decline; the compile-time warning is
        filtered because it is the EXPECTED outcome there.

        Returns a score fn dispatching exact bucket shapes to their
        compiled executables (anything else → ``fallback``), or None if
        any bucket refuses to compile (the caller keeps the jit path).
        """
        import warnings

        buckets = sorted({((b + align - 1) // align) * align
                          for b in self._cfg.batch_buckets})
        n_feat = self._model.n_features
        table = {}
        try:
            for b in buckets:
                if x_sharding is not None:
                    spec = jax.ShapeDtypeStruct((b, n_feat), np.float32,
                                                sharding=x_sharding)
                else:
                    spec = jax.ShapeDtypeStruct((b, n_feat), np.float32)
                t0 = time.perf_counter()
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    table[b] = jitted.lower(*leading, spec).compile()
                _m_aot_compile.labels(bucket=b).observe(
                    time.perf_counter() - t0)
        except Exception as e:
            from routest_tpu.utils.logging import get_logger

            get_logger("routest_tpu.serve").warning(
                "aot_compile_unavailable", bucket=locals().get("b"),
                error=f"{type(e).__name__}: {e}")
            return None

        def score(x: np.ndarray) -> np.ndarray:
            exe = table.get(len(x))
            if exe is None:
                return fallback(x)
            x = np.ascontiguousarray(x, np.float32)
            if x_sharding is not None:
                x = jax.device_put(x, x_sharding)
            return exe(*leading, x)

        self._aot_buckets = tuple(buckets)
        return score

    def _finish_init(self, score, align: int) -> None:
        """Shared serving bring-up: batcher, one-row self-check, bucket
        warmup. Used by the jit/TP/fused paths and the AOT-export path."""
        cfg = self._cfg
        self._score = score
        self._batcher = DynamicBatcher(
            score, cfg.batch_buckets, cfg.max_batch, cfg.max_wait_ms,
            align=align, adaptive=getattr(cfg, "adaptive_wait", False),
            min_wait_ms=getattr(cfg, "min_wait_ms", 0.0),
        )
        # Self-check: an artifact can deserialize fine yet be unusable
        # (e.g. stale layer shapes). Run one dummy row now so breakage
        # surfaces in health as model:degraded instead of per-request
        # 503s with health claiming ok.
        try:
            probe = np.zeros((1, self._model.n_features), np.float32)
            if not np.isfinite(self._batcher.submit(probe)).all():
                raise ValueError("self-check produced non-finite output")
        except Exception as e:
            self._error = f"model self-check failed: {type(e).__name__}: {e}"
            self._model = None
            self._params = None
            self._batcher = None
            self.kernel = "xla"  # nothing is serving; don't claim fused
            self.kernel_dtype = None
            self._aot_buckets = ()
            # drop the score closure too — it captures the device-pinned
            # param tree and would hold device memory forever
            self._score = None
            self._serving = _EMPTY_SERVING
        else:
            self._serving = _ServingState(self._model, self._batcher,
                                          self.quantiles,
                                          generation=next(_GENERATION))
            self.loaded_unix = time.time()
            # A replacement built for verification is NOT live yet; its
            # parent flips the gauge if (and only if) the swap lands.
            if not _in_reload.flag:
                _m_generation.set(self._serving.generation)
            self._warm_buckets()
            if not _in_reload.flag:
                _m_cold_start.set(time.perf_counter() - self._t_construct)

    def _warm_buckets(self) -> None:
        """Compile EVERY batch bucket at startup.

        Round 1 warmed only the smallest bucket; the first customer
        request to hit a larger one paid its XLA compile inline (load
        test p95 was 512 ms against a p50 of 9 ms). Opt out with
        ``ROUTEST_WARM_BUCKETS=0`` when fast process startup matters
        more than first-request latency. Warming is an optimization: a
        failure here (e.g. the biggest bucket exhausting device memory)
        logs and falls back to lazy inline compiles — it must never tear
        down a model the self-check just proved serviceable.
        """
        if os.environ.get("ROUTEST_WARM_BUCKETS", "1") == "0":
            return
        from routest_tpu.utils.logging import get_logger

        t0 = time.time()
        for bucket in self._batcher._buckets:
            try:
                zeros = np.zeros((bucket, self._model.n_features), np.float32)
                np.asarray(self._score(zeros))
            except Exception as e:
                get_logger("routest_tpu.serve").warning(
                    "bucket_warm_failed", bucket=bucket,
                    error=f"{type(e).__name__}: {e}")
        get_logger("routest_tpu.serve").info(
            "batch_buckets_warmed", buckets=list(self._batcher._buckets),
            seconds=round(time.time() - t0, 2))

    def _maybe_tp_score(self, runtime: MeshRuntime):
        """Tensor-parallel serving when the mesh has a real ``model``
        axis (``RTPU_MESH_MODEL>1``) — weights sharded Megatron-style
        over it, batch over ``data`` (SURVEY.md §2.4 TP row). Returns
        None (→ replicated fallback) when the axis is 1, the artifact is
        not an MLP (the GBDT path gathers, not matmuls), or the trunk
        widths don't divide the axis — TP is an opt-in optimization,
        never a dependency."""
        from routest_tpu.models.eta_mlp import EtaMLP as _EtaMLP

        tp = runtime.mesh.shape[runtime.model_axis]
        if tp <= 1 or not isinstance(self._model, _EtaMLP):
            return None
        try:
            from routest_tpu.parallel.tensor import (make_tp_apply,
                                                     shard_tp_params)

            tp_apply = make_tp_apply(self._model, runtime.mesh)
            params = shard_tp_params(self._params, self._model, runtime.mesh)
        except ValueError as e:
            from routest_tpu.utils.logging import get_logger

            get_logger("routest_tpu.serve").warning(
                "tp_serving_unavailable", error=str(e))
            return None

        def score(x: np.ndarray) -> np.ndarray:
            return tp_apply(params, runtime.shard_batch(jax.numpy.asarray(x)))

        self.kernel = "xla_tp"
        return score

    def _chosen_kernel(self) -> str:
        """What ``EtaMLP`` runs at this replica's buckets on one device
        (``models/eta_mlp.eta_path``): ``xla``, ``pallas_fused``, or
        ``xla+pallas_fused(>=N)`` where only the buckets from N rows up
        take the kernel."""
        if not isinstance(self._model, EtaMLP):   # an imported booster
            return "xla"
        buckets = sorted(self._cfg.batch_buckets)
        fused = [b for b in buckets
                 if eta_path(jax.default_backend(),
                             self._model.policy.compute_dtype,
                             self._model.hidden, b) == "fused"]
        if not fused:
            return "xla"
        if len(fused) == len(buckets):
            return "pallas_fused"
        return f"xla+pallas_fused(>={fused[0]})"

    def _maybe_fused_score(self, fallback):
        """``ROUTEST_FUSED=1`` on a TPU forces the fused Pallas kernel
        (``ops/fused_mlp.py``) at its own default tile for every batch;
        anything else serves ``fallback``.

        Probed eagerly with one row: the operator forced the kernel, so
        a pack/compile failure (unexpected param shapes, Mosaic
        regressions) raises — serving XLA under that setting would hide
        it. Off the TPU the switch is ignored with a
        ``fused_kernel_ignored`` warning.
        """
        if os.environ.get("ROUTEST_FUSED") != "1":
            return fallback
        if jax.default_backend() != "tpu":
            # Compiled Mosaic needs a TPU; interpreter mode would "work"
            # but orders of magnitude slower — never serve it.
            from routest_tpu.utils.logging import get_logger

            get_logger("routest_tpu.serve").warning(
                "fused_kernel_ignored",
                reason=f"ROUTEST_FUSED=1 needs the TPU backend, "
                       f"have {jax.default_backend()}; serving XLA")
            return fallback
        from routest_tpu.ops import (fused_eta_forward, pack_eta_params,
                                     resolve_kernel_dtype)

        variant = resolve_kernel_dtype(self._model)
        packed = jax.device_put(
            pack_eta_params(self._model, self._params, dtype=variant))
        n_q = len(self.quantiles)

        def fused(x: np.ndarray) -> np.ndarray:
            return fused_eta_forward(packed, jax.numpy.asarray(x), n_q=n_q)

        probe = np.zeros((1, self._model.n_features), np.float32)
        if not np.isfinite(np.asarray(fused(probe))).all():
            raise ValueError("fused kernel probe produced non-finite output")
        self.kernel = "pallas_fused"
        self.kernel_dtype = variant
        return fused

    def _load(self, path: str) -> None:
        # Chaos fault point: a bad deploy's first observable failure is
        # often the artifact load itself — seeded injection here makes
        # that scenario replayable (``model.load:error=1.0@1`` fails
        # exactly one load). An injected fault degrades exactly like a
        # corrupt file: load_error set, old model (if any) keeps serving.
        from routest_tpu.chaos import ChaosError
        from routest_tpu.chaos import inject as chaos_inject

        try:
            chaos_inject("model.load")
        except ChaosError as e:
            self._error = f"chaos injected at model.load: {e}"
            return
        self.fingerprint = _artifact_fingerprint(path)
        # AOT export? Sniff the magic so a .stablehlo artifact gets a
        # real error from ITS loader instead of "not a msgpack artifact".
        try:
            from routest_tpu.train.checkpoint import (EXPORT_MAGIC,
                                                      load_exported_serving_fn)

            with open(path, "rb") as f:
                is_export = f.read(len(EXPORT_MAGIC)) == EXPORT_MAGIC
            if is_export:
                self._model = load_exported_serving_fn(path)
                self._params = None  # weights are constants in the program
                return
        except FileNotFoundError:
            pass  # fall through: load_model reports the missing path
        except Exception as e:
            self._error = f"{type(e).__name__}: {e}"
            return
        try:
            self._model, self._params = load_model(path)
            from routest_tpu.core.dtypes import backend_compute_policy

            self._model = backend_compute_policy(self._model)
            return
        except Exception as e:
            first_error = f"{type(e).__name__}: {e}"
        # ETA_MODEL_PATH may point at the reference's actual model family:
        # an XGBoost regressor exported to XGBoost's JSON format
        # (``Flaskr/ml.py:11-21`` unpickles the same trees). Serve it via
        # the tensorized GBDT path — same 12-feature ABI, batched on
        # device instead of row-at-a-time CPU walks.
        try:
            from routest_tpu.models.gbdt import load_xgboost_eta

            self._model, self._params = load_xgboost_eta(path)
        except Exception:  # rtpulint: disable=broad-except-unlogged -- the primary loader's error (first_error) is what health surfaces
            self._error = first_error

    def _artifact_mtime_ns(self) -> Optional[int]:
        try:
            return os.stat(self._path).st_mtime_ns
        except OSError:
            return None

    def reload_if_changed(self) -> bool:
        """Hot-reload the serving artifact when its file changed.

        The reference's only way to pick up a new model is a process
        restart (the pickle loads once, ``Flaskr/ml.py:11-21``); here a
        changed ``ETA_MODEL_PATH`` file swaps in WITHOUT dropping
        requests: a complete replacement service (model + batcher, self-
        checked and bucket-warmed) is built off to the side, then the
        references flip — in-flight requests finish on the old batcher's
        closures, new requests land on the new one. A broken replacement
        (missing/corrupt/failed self-check) keeps the old model serving
        and returns False. Returns True only after a successful swap.
        """
        with self._reload_lock:
            mtime = self._artifact_mtime_ns()
            if mtime is None or mtime == self._loaded_mtime_ns:
                return False
            from routest_tpu.utils.logging import get_logger

            log = get_logger("routest_tpu.serve")
            _in_reload.flag = True
            try:
                fresh = EtaService(self._cfg, model_path=self._path,
                                   runtime=self._runtime)
            finally:
                _in_reload.flag = False
            if not fresh.available:
                _m_swaps.labels(result="rejected").inc()
                log.warning("model_reload_rejected", path=self._path,
                            fingerprint=fresh.fingerprint,
                            error=fresh.load_error)
                # remember the bad mtime: don't rebuild-and-reject on
                # every poll until the file changes again
                self._loaded_mtime_ns = mtime
                return False
            # Golden-batch gate: a deserializable, self-check-passing
            # artifact can still be wrong (truncated weights that load,
            # a layer scaled by a bad export). Score the fixed golden
            # rows off-path and reject non-finite or wildly divergent
            # outputs BEFORE the generation flips — the live model
            # never stops serving during any of this.
            ok, verdict = self._verify_swap(fresh)
            if not ok:
                _m_swaps.labels(result="rejected").inc()
                log.warning("model_swap_rejected", path=self._path,
                            fingerprint=fresh.fingerprint, **verdict)
                self._loaded_mtime_ns = mtime
                return False
            # ONE reference flip makes the swap atomic for readers (they
            # snapshot _serving once per request); the individual fields
            # are updated too for stats/health introspection.
            self._serving = fresh._serving
            self._model = fresh._model
            self._params = fresh._params
            self._batcher = fresh._batcher
            self._score = fresh._score
            self.kernel = fresh.kernel
            self.kernel_dtype = fresh.kernel_dtype
            self._aot_buckets = fresh._aot_buckets
            self._error = None
            self._loaded_mtime_ns = fresh._loaded_mtime_ns
            self.fingerprint = fresh.fingerprint
            self.loaded_unix = fresh.loaded_unix
            _m_swaps.labels(result="accepted").inc()
            _m_generation.set(self._serving.generation)
            record_change("model.swap",
                          detail={"generation": self._serving.generation,
                                  "fingerprint": self.fingerprint,
                                  "path": self._path})
            # Cache coherency on reload: correctness already holds (the
            # new snapshot carries a new generation, so old keys can
            # never match) — this drop is memory hygiene, freeing the
            # dead generation's entries immediately instead of waiting
            # for LRU/TTL.
            if self._fastlane is not None:
                self._fastlane.invalidate()
            log.info("model_reloaded", path=self._path, kernel=self.kernel,
                     generation=self._serving.generation,
                     fingerprint=self.fingerprint, **verdict)
            return True

    def _verify_swap(self, fresh: "EtaService") -> Tuple[bool, dict]:
        """Score the golden batch on the REPLACEMENT service →
        ``(accept, verdict-detail)``. Two gates: every output finite,
        and — when the live model is comparable (same output shape;
        a point→quantile upgrade is a deliberate structural change and
        skips it) — median absolute divergence within
        ``swap_max_divergence`` minutes. Both run entirely off-path on
        the replacement's own batcher."""
        cfg = self._cfg
        if not getattr(cfg, "swap_verify", True):
            return True, {"verified": False}
        golden = golden_batch()
        try:
            new = fresh._predict_rows(fresh._serving, golden)
        except Exception as e:
            return False, {"reason": "golden batch scoring failed: "
                                     f"{type(e).__name__}: {e}"}
        if new is None:
            return False, {"reason": "golden batch produced no output"}
        new = np.asarray(new, np.float64)
        finite = np.isfinite(new).reshape(len(new), -1).all(axis=1)
        if not finite.all():
            return False, {"reason": "non-finite golden outputs",
                           "bad_rows": int((~finite).sum()),
                           "rows": int(len(new))}
        bound = float(getattr(cfg, "swap_max_divergence", 0.0) or 0.0)
        serving = self._serving
        if bound > 0 and serving.batcher is not None:
            try:
                old = self._predict_rows(serving, golden)
            except Exception:  # rtpulint: disable=broad-except-unlogged -- live model unscoreable: the finiteness gate alone decides the swap
                old = None  # live model unscoreable: finiteness decides
            if old is not None:
                old = np.asarray(old, np.float64)
                if old.shape == new.shape and bool(np.isfinite(old).all()):
                    div = float(np.median(np.abs(new - old)))
                    if div > bound:
                        return False, {"reason": "divergence beyond bound",
                                       "divergence": round(div, 3),
                                       "bound": bound}
                    return True, {"divergence": round(div, 4),
                                  "bound": bound}
        return True, {}

    def start_reload_watcher(self, interval_s: float) -> threading.Event:
        """Poll the artifact mtime every ``interval_s`` seconds on a
        daemon thread (``ROUTEST_RELOAD_SEC`` wires this in serve boot).
        Returns the stop event."""
        stop = threading.Event()

        def watch() -> None:
            while not stop.wait(interval_s):
                try:
                    self.reload_if_changed()
                except Exception as e:  # never kill the watcher
                    from routest_tpu.utils.logging import get_logger

                    get_logger("routest_tpu.serve").error(
                        "model_reload_failed",
                        error=f"{type(e).__name__}: {e}")

        threading.Thread(target=watch, name="eta-reload-watcher",
                         daemon=True).start()
        return stop

    @property
    def available(self) -> bool:
        return self._model is not None

    @property
    def generation(self) -> int:
        """Generation id of the LIVE serving snapshot (-1 = nothing
        serving). The fast-lane cache keys on it; the rollout controller
        reads it through ``/api/version`` to prove a swap landed."""
        return self._serving.generation

    @property
    def model_path(self) -> str:
        return self._path

    @property
    def quantiles(self) -> Tuple[float, ...]:
        """Quantile levels the serving model predicts; () for point models
        (including the GBDT path)."""
        if self._model is None:
            return ()
        return tuple(getattr(self._model, "quantiles", ()) or ())

    @property
    def load_error(self) -> Optional[str]:
        return self._error

    def scoring_info(self) -> dict:
        """The scoring artifact's identity card (health's model block,
        mirroring the road_router block): which compute path serves
        (kernel), at what dtype, and which buckets are AOT-compiled."""
        return {
            "kernel": self.kernel,
            "dtype": self.kernel_dtype,
            "aot": bool(self._aot_buckets),
            "aot_buckets": list(self._aot_buckets),
        }

    def mesh_info(self) -> dict:
        """The replica's device topology at a glance (health's
        ``checks.engine.mesh``): how many devices this process actually
        owns (the placement overlay's pinning, verified — not what the
        plan intended), the mesh axis shapes when batch sharding is on,
        and the placement slice label the supervisor stamped."""
        import jax

        devices = jax.devices()
        info: dict = {
            "devices": len(devices),
            "device_ids": [d.id for d in devices],
            "device_kind": devices[0].device_kind,
            "platform": jax.default_backend(),
            "sharded": self._runtime is not None,
        }
        # The chip mask the placement overlay set, as this process sees
        # it: masked processes each number their chips from 0, so the
        # mask is what tells one-chip replicas of a host apart.
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible is not None:
            info["visible_chips"] = visible
        label = os.environ.get("RTPU_FLEET_PLACEMENT_LABEL")
        if label:
            info["placement"] = label
        if self._runtime is not None:
            info["axis_shapes"] = {
                str(name): int(self._runtime.mesh.shape[name])
                for name in self._runtime.mesh.axis_names}
        return info

    def predict_batch(self, rows: np.ndarray) -> Optional[np.ndarray]:
        return self._predict_rows(self._serving, rows)

    def _predict_rows(self, serving: _ServingState,
                      rows: np.ndarray,
                      blob=None) -> Optional[np.ndarray]:
        """Score rows against ONE serving snapshot (hot-reload-safe:
        callers must pair the result with the SAME snapshot's quantile
        metadata). The fast lane is consulted first: cached rows never
        reach the batcher, novel rows coalesce with identical in-flight
        ones, and only the remainder costs a device slot."""
        batcher = serving.batcher
        if batcher is None:
            return None
        rows = np.asarray(rows, np.float32)
        # Host-side non-finite containment: a NaN/Inf input row (a
        # client sending "NaN" distances) must neither poison its
        # batch-mates nor abort the jit under jax_debug_nans — the
        # device only ever sees finite rows. Bad rows score as a finite
        # placeholder and their outputs are stamped back to NaN, which
        # the response layer already serializes as null.
        bad = ~np.isfinite(rows).all(axis=1)
        if bad.any():
            rows = np.where(bad[:, None], np.float32(0.0), rows)
            blob = None  # rewritten rows no longer match the wire bytes
        fl = self._fastlane
        if fl is not None and fl.accepts(len(rows)):
            from routest_tpu.live import metric_epoch

            # Cache key = (model generation, live-metric epoch): a
            # metric flip retires every cached prediction the same way
            # a model swap does, so no served number outlives either
            # kind of change. Epoch is 0 (one stable key) while live
            # traffic is off. The span carries the per-request
            # provenance — which model generation/metric epoch served
            # these rows and how many came from cache — so a
            # tail-sampled slow trace says WHICH path it took.
            epoch = metric_epoch()
            with trace_span("fastlane.predict", rows=len(rows),
                            model_generation=serving.generation,
                            metric_epoch=epoch) as fspan:
                preds = fl.predict(
                    rows, (serving.generation, epoch),
                    lambda miss: self._submit_chunked(batcher, miss),
                    span=fspan, blob=blob)
        else:
            preds = self._submit_chunked(batcher, rows)
        if bad.any() and preds is not None:
            preds = np.array(preds, np.float64, copy=True)  # never mutate
            preds[bad] = np.nan                  # a cached/shared buffer
        return preds

    @staticmethod
    def _submit_chunked(batcher: DynamicBatcher,
                        rows: np.ndarray) -> np.ndarray:
        # Chunk oversize batches to the largest compile bucket: arbitrary
        # row counts would each compile a fresh executable (a client
        # sweeping sizes = recompile storm + unbounded jit cache).
        cap = batcher._buckets[-1]
        if len(rows) <= cap:
            return batcher.submit(rows)
        return np.concatenate([
            batcher.submit(rows[i: i + cap])
            for i in range(0, len(rows), cap)])

    def predict_eta_minutes(
        self, *, weather: str, traffic: str, distance_m: float,
        pickup_time, driver_age: float = 30.0,
    ) -> Tuple[Optional[float], Optional[str]]:
        """Reference-signature single prediction (``Flaskr/ml.py:23``):
        returns (eta_minutes, completion_iso) or (None, None)."""
        # ONE snapshot for both scoring and quantile metadata: a
        # concurrent hot-reload must not pair the old batcher's output
        # shape with the new model's quantile levels.
        serving = self._serving
        if serving.batcher is None:
            return None, None
        pickup_dt = _parse_pickup_single(pickup_time)

        rows = encode_requests(
            weather=[weather], traffic=[traffic],
            weekday=[pickup_dt.weekday()], hour=[pickup_dt.hour],
            distance_km=[float(distance_m or 0) / 1000.0],
            driver_age=[float(driver_age or 30.0)],
        )
        try:
            preds = self._predict_rows(serving, rows)
        except DeadlineExceeded:
            raise  # 504, not "model unavailable": the budget ran out
        except Exception:  # rtpulint: disable=broad-except-unlogged -- degrade contract: a scoring failure serves the route without ML fields
            return None, None
        if preds is None:
            return None, None
        row = np.atleast_1d(preds[0])
        q = serving.quantiles
        # Finiteness policy (shared with predict_eta_quantiles): the row
        # is servable iff its MEDIAN is finite — a degenerate tail head
        # must not turn a servable point estimate into "model
        # unavailable".
        median = float(row[q.index(0.5)] if q else row[0])
        if not np.isfinite(median):
            return None, None
        eta_ts = (pickup_dt + dt.timedelta(minutes=median)).isoformat()
        return median, eta_ts

    def predict_eta_quantiles(
        self, *, weather: str, traffic: str, distance_m: float,
        pickup_time, driver_age: float = 30.0,
    ) -> Tuple[Optional[float], Optional[str], dict]:
        """Single prediction plus the uncertainty band: (eta_median,
        completion_iso, {"p10": …, "p90": …}). The dict is empty for
        point models — callers add response fields only when the serving
        model actually calibrates them."""
        if not self.quantiles:
            eta, iso = self.predict_eta_minutes(
                weather=weather, traffic=traffic, distance_m=distance_m,
                pickup_time=pickup_time, driver_age=driver_age)
            return eta, iso, {}
        pickup_dt = _parse_pickup_single(pickup_time)
        try:
            minutes, _iso, bands = self.predict_eta_batch(
                weather=[weather], traffic=[traffic], distance_m=[distance_m],
                pickup_time=pickup_dt, driver_age=[driver_age],
                return_quantiles=True)
        except DeadlineExceeded:
            raise  # budget expiry must surface as 504, not a null field
        except Exception:  # rtpulint: disable=broad-except-unlogged -- degrade contract: a scoring failure serves the route without ML fields
            # Same degrade-gracefully contract as predict_eta_minutes: a
            # scoring failure is (None, None), never an exception — the
            # route response must still be served without ML fields.
            return None, None, {}
        if minutes is None or not np.isfinite(minutes[0]):
            return None, None, {}
        # Completion stamp via the SINGLE-ROW formula, not the batch
        # path's datetime64 string: the response format (sub-second
        # precision, preserved UTC offset) must not change just because
        # the serving artifact gained quantile heads.
        eta_minutes = float(minutes[0])
        iso = (pickup_dt + dt.timedelta(minutes=eta_minutes)).isoformat()
        # Non-finite band entries are dropped, not serialized: the point
        # estimate stands on its own (NaN/Inf would also be invalid JSON).
        return (eta_minutes, iso,
                {k: float(v[0]) for k, v in bands.items()
                 if np.isfinite(v[0])})

    def predict_eta_batch(
        self, *, weather: Sequence[str], traffic: Sequence[str],
        distance_m: Sequence[float], pickup_time,
        driver_age: Sequence[float], return_quantiles: bool = False,
    ):
        """Batched scoring: N OD pairs → (minutes (N,), completion ISO (N,)).

        The serving-side half of the 10k preds/sec north star
        (BASELINE.json): the reference scores one row per HTTP request
        (``Flaskr/routes.py:365-383``); here one request carries a whole
        OD batch straight into the device batcher. ``pickup_time`` may be
        a single ISO string (shared by the batch) or a sequence of N.
        Returns (None, None) when no model is serving.

        With ``return_quantiles=True`` a third element is returned: a
        dict of per-level minute arrays (``{"p10": (N,), "p90": (N,)}``),
        empty for point models. Minutes are always the median for
        quantile models.
        """
        serving = self._serving  # one snapshot: scoring + metadata
        if serving.batcher is None:
            return (None, None, {}) if return_quantiles else (None, None)
        n = len(distance_m)
        if isinstance(pickup_time, (str, dt.datetime)) or pickup_time is None:
            pickup_time = [pickup_time] * n

        def parse(p):
            # Shared single-row semantics, then keep offset-local WALL
            # time (drop tzinfo for datetime64): the single-row path
            # encodes hour/weekday from the wall clock as sent, and the
            # two endpoints must featurize the identical row identically.
            return _parse_pickup_single(p).replace(tzinfo=None)

        pickups = [parse(p) for p in pickup_time]
        rows = encode_requests(
            weather=list(weather), traffic=list(traffic),
            weekday=[p.weekday() for p in pickups],
            hour=[p.hour for p in pickups],
            distance_km=[float(d or 0) / 1000.0 for d in distance_m],
            driver_age=[float(a or 30.0) for a in driver_age],
        )
        preds = self._predict_rows(serving, rows)
        if preds is None:
            return (None, None, {}) if return_quantiles else (None, None)
        preds = np.asarray(preds, np.float64)
        q = serving.quantiles
        bands: dict = {}
        if q:
            minutes = preds[:, q.index(0.5)]
            if return_quantiles:
                bands = {_band_label(level): preds[:, i]
                         for i, level in enumerate(q) if level != 0.5}
        else:
            minutes = preds
        # Vectorized completion stamps: datetime64 arithmetic beats a
        # per-row datetime+timedelta loop ~50x at batch sizes that matter.
        base = np.asarray([np.datetime64(p, "ms") for p in pickups])
        completion = base + (minutes * 60_000.0).astype("timedelta64[ms]")
        iso = np.datetime_as_string(completion, unit="s")
        return (minutes, iso, bands) if return_quantiles else (minutes, iso)

    def predict_eta_wire(self, features: np.ndarray,
                         pickup_ms: np.ndarray, blob=None):
        """Binary-wire batched scoring: pre-encoded (N, 12) float32
        features + (N,) int64 pickup epoch-ms → ``(minutes (N,) f64,
        completion_ms (N,) i64, bands {label: (N,) f64})``, or None
        when no model is serving.

        Zero per-row Python: the client featurized with the same
        ``encode_requests`` the JSON path uses, so scoring feeds the
        model bit-identical rows, and the completion math below is the
        SAME float64 expression as the JSON path's datetime64
        arithmetic (``ms + int64(minutes * 60_000.0)``) — the two
        content-types answer bitwise-identically by construction.
        NaN-minute rows stamp the datetime64 NaT sentinel
        (``wirecodec.COMPLETION_NAT``). ``blob`` is the request
        frame's raw feature bytes, threaded to the fast lane so cache
        keys slice from the socket buffer instead of re-serializing."""
        serving = self._serving  # one snapshot: scoring + metadata
        if serving.batcher is None:
            return None
        preds = self._predict_rows(serving, features, blob=blob)
        if preds is None:
            return None
        preds = np.asarray(preds, np.float64)
        q = serving.quantiles
        bands: dict = {}
        if q:
            minutes = preds[:, q.index(0.5)]
            bands = {_band_label(level): preds[:, i]
                     for i, level in enumerate(q) if level != 0.5}
        else:
            minutes = preds
        pickup_ms = np.asarray(pickup_ms, np.int64)
        from routest_tpu.serve.wirecodec import COMPLETION_NAT

        finite = np.isfinite(minutes)
        completion_ms = np.full(minutes.shape, COMPLETION_NAT, np.int64)
        if finite.any():
            # float→int truncation toward zero, exactly what the JSON
            # path's float64→timedelta64[ms] astype performs.
            completion_ms[finite] = (
                pickup_ms[finite]
                + (minutes[finite] * 60_000.0).astype(np.int64))
        return minutes, completion_ms, bands

    @property
    def stats(self) -> dict:
        base = {"available": self.available, "error": self._error,
                "kernel": self.kernel, "generation": self.generation,
                "fingerprint": self.fingerprint}
        if self._batcher is not None:
            base.update(self._batcher.stats)
        if self._fastlane is not None:
            base["fastlane"] = self._fastlane.snapshot()
        return base

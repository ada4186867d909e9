"""Route-level fastlane: epoch-keyed solved-route cache + singleflight.

The ETA fast lane (``serve/fastlane.py``) proved the pattern on
predictions: Zipf-skewed traffic re-asks the same questions, and the
cheapest answer is the one already computed. Routing traffic has the
same shape — loadgen measured a 0.97 hit rate on ETA keys over the
same OD vocabulary — but every repeated ``request_route`` still paid a
full snap + device solve + predecessor fetch. This module caches the
SOLVED leg set (the :class:`~routest_tpu.optimize.road_router.RoadLegs`
behind a request) keyed by::

    (waypoint fingerprint, time_scale, hour,
     live metric epoch, road-model generation)

- **Exact invalidation, no TTL races**: the live-metric epoch
  (``routest_tpu.live.metric_epoch`` — bumped by every
  ``install_live_metric`` flip) and the router's model generation
  (bumped by every verified road-GNN swap) are IN the key, so no
  cached route can outlive either flip — the same coherency contract
  the prediction cache carries.
  TTL is a freshness backstop on top, not the correctness mechanism.
- **Byte-budgeted LRU**: a cached solve pins (M, N) predecessor and
  distance rows — megabytes per entry at metro scale — so the budget
  is bytes, not entries (``ROUTEST_ROUTE_CACHE_MB``).
- **Singleflight**: N concurrent identical OD requests cost ONE solve;
  followers park on an event and read the leader's legs (the PR-4
  pattern). A leader failure propagates to every waiter and caches
  nothing.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

_metrics = None


def _cache_metrics():
    global _metrics
    if _metrics is None:
        from routest_tpu.obs import get_registry

        reg = get_registry()
        _metrics = {
            "hits": reg.counter(
                "rtpu_route_cache_hits_total",
                "Route problems served from the route fastlane."),
            "misses": reg.counter(
                "rtpu_route_cache_misses_total",
                "Route problems that had to be solved."),
            "coalesced": reg.counter(
                "rtpu_route_cache_coalesced_total",
                "Route problems served by waiting on another request's "
                "in-flight solve (singleflight)."),
            "evictions": reg.counter(
                "rtpu_route_cache_evictions_total",
                "Route-cache entries evicted by the byte-budget LRU."),
            "bytes": reg.gauge(
                "rtpu_route_cache_bytes", "Route-cache resident bytes."),
            "entries": reg.gauge(
                "rtpu_route_cache_entries", "Live route-cache entries."),
        }
    return _metrics


def route_cache_config() -> Tuple[bool, int, float]:
    """(enabled, byte budget, ttl seconds) from the env knobs
    (``ROUTEST_ROUTE_CACHE`` on/off, ``ROUTEST_ROUTE_CACHE_MB``,
    ``ROUTEST_ROUTE_CACHE_TTL_S``)."""
    raw = os.environ.get("ROUTEST_ROUTE_CACHE", "1").strip().lower()
    enabled = raw not in ("0", "off", "false", "no")
    try:
        budget_mb = float(os.environ.get("ROUTEST_ROUTE_CACHE_MB", "256"))
    except ValueError:
        budget_mb = 256.0
    try:
        ttl_s = float(os.environ.get("ROUTEST_ROUTE_CACHE_TTL_S", "300"))
    except ValueError:
        ttl_s = 300.0
    return enabled, int(budget_mb * 1e6), ttl_s


class _Flight:
    """One in-progress solve other threads can wait on."""

    __slots__ = ("event", "value", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None


class RouteCache:
    """Byte-budgeted LRU + TTL + singleflight over solved leg sets.

    The protocol is split (unlike ``FastLane.predict``) because the
    router solves MANY problems per call and wants cache misses from
    one request batch grouped into shared device solves:

    - :meth:`lookup` classifies a key → ``("hit", legs)``,
      ``("wait", flight)`` or ``("lead", flight)``;
    - the caller solves every lead, then :meth:`commit`\\ s (or
      :meth:`abort`\\ s on failure);
    - ``("wait", flight)`` resolves with :meth:`wait`.
    """

    WAIT_HARD_CAP_S = 120.0

    def __init__(self, budget_bytes: int = 256_000_000,
                 ttl_s: float = 300.0) -> None:
        self.budget_bytes = int(budget_bytes)
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        # key -> (stored_monotonic, nbytes, legs)
        self._cache: "OrderedDict[Tuple, Tuple[float, int, object]]" = \
            OrderedDict()
        self._bytes = 0
        self._inflight: Dict[Tuple, _Flight] = {}
        self._hits = self._misses = self._coalesced = self._evictions = 0

    # ── bookkeeping ───────────────────────────────────────────────────

    def stats(self) -> dict:
        with self._lock:
            total = self._hits + self._misses + self._coalesced
            return {
                "entries": len(self._cache),
                "bytes": self._bytes,
                "budget_bytes": self.budget_bytes,
                "ttl_s": self.ttl_s,
                "hits": self._hits,
                "misses": self._misses,
                "coalesced": self._coalesced,
                "evictions": self._evictions,
                "hit_rate": round((self._hits + self._coalesced)
                                  / total, 4) if total else 0.0,
            }

    def invalidate(self) -> None:
        """Drop everything (hygiene only — correctness comes from the
        epoch/generation halves of the key)."""
        with self._lock:
            self._cache.clear()
            self._bytes = 0
            m = _cache_metrics()
            m["bytes"].set(0)
            m["entries"].set(0)

    # ── the protocol ──────────────────────────────────────────────────

    def lookup(self, key: Tuple):
        """→ ("hit", legs) | ("wait", flight) | ("lead", flight)."""
        m = _cache_metrics()
        now = time.monotonic()
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                stored, nbytes, legs = hit
                if self.ttl_s <= 0 or now - stored <= self.ttl_s:
                    self._cache.move_to_end(key)
                    self._hits += 1
                    m["hits"].inc()
                    return "hit", legs
                del self._cache[key]
                self._bytes -= nbytes
            flight = self._inflight.get(key)
            if flight is not None:
                self._coalesced += 1
                m["coalesced"].inc()
                return "wait", flight
            flight = _Flight()
            self._inflight[key] = flight
            self._misses += 1
            m["misses"].inc()
            return "lead", flight

    def commit(self, key: Tuple, legs, nbytes: int) -> None:
        """Leader publishes its solved legs; waiters wake; the LRU
        evicts from the cold end until the byte budget holds. Entries
        bigger than the whole budget publish to waiters but skip the
        cache (they would evict everything for one key)."""
        m = _cache_metrics()
        now = time.monotonic()
        with self._lock:
            flight = self._inflight.pop(key, None)
            if nbytes <= self.budget_bytes:
                old = self._cache.pop(key, None)
                if old is not None:
                    self._bytes -= old[1]
                self._cache[key] = (now, int(nbytes), legs)
                self._bytes += int(nbytes)
                self._evict_locked()
            m["bytes"].set(self._bytes)
            m["entries"].set(len(self._cache))
        if flight is not None:
            flight.value = legs
            flight.event.set()

    def _evict_locked(self) -> None:
        m = _cache_metrics()
        while self._bytes > self.budget_bytes and self._cache:
            _, (_, nb, _) = self._cache.popitem(last=False)
            self._bytes -= nb
            self._evictions += 1
            m["evictions"].inc()

    def abort(self, key: Tuple, error: BaseException) -> None:
        """Leader failed: nothing cached, every waiter gets the error,
        the next request solves fresh."""
        with self._lock:
            flight = self._inflight.pop(key, None)
        if flight is not None:
            flight.error = error
            flight.event.set()

    def wait(self, flight: _Flight, deadline_s: Optional[float] = None):
        budget = self.WAIT_HARD_CAP_S if deadline_s is None \
            else min(self.WAIT_HARD_CAP_S, deadline_s)
        if not flight.event.wait(budget):
            raise TimeoutError(
                "route-fastlane wait exceeded the request budget")
        if flight.error is not None:
            raise flight.error
        return flight.value

"""Topology-aware fleet placement: device inventory → replica slices.

Until now the fleet layer was device-blind: the supervisor spawned N
copies of one chip and the gateway assumed every replica had equal
capacity, so the compute side's multi-chip serving paths (mesh batch
shardings, DP/TP scoring) had no fleet that could
actually *spend* more than one chip. This module is the missing map
from "what does this host have" to "what do we boot":

- :func:`detect_inventory` — how many chips, on what platform. The
  operator override (``RTPU_FLEET_CHIPS``) wins; a forced-CPU virtual
  device count (``XLA_FLAGS --xla_force_host_platform_device_count``)
  is honored next so placement shape is testable without hardware;
  otherwise JAX is asked in a short-lived child process — never in the
  caller, because a chip belongs to one process at a time and the fleet
  parent must leave every chip free for the replicas it spawns.
- :func:`candidate_layouts` — the ways ``chips`` devices can be carved
  into replica slices (8 → 8×1, 4×2, 2×4, 1×8; odd counts get a mixed
  remainder slice: 6 → …, 4+2; every chip is owned by exactly one
  slice).
- :func:`plan_placement` — pick one. ``RTPU_FLEET_PLACEMENT`` forces
  (``replica`` = all 1-chip, ``mesh`` = one big slice, ``NxK`` or a
  ``4,2,1`` list = exactly that); ``auto`` compares candidate layouts
  by predicted throughput — from the *measured* per-chip curve in
  ``artifacts/fleet_chips.json`` when one exists (provenance recorded
  on the plan, PR-10 selection-table style), else from a simple
  mesh-efficiency model (``RTPU_FLEET_PLACEMENT_EFF`` per added chip).
  On a CPU backend auto never multiplies virtual devices — they
  time-share one host and a mesh over them is pure overhead (measured
  2× worse single-row p95), so auto yields plain 1-chip replicas with
  empty overlays and the boot behaves exactly as before this module
  existed.

Each slice carries the per-replica env overlay that pins its devices —
the PR-7 overlay machinery is the actuation path, so a monitor respawn
or a rolling restart reuses the SAME overlay and a replica can never
silently wander onto another replica's chips. Capacity units (predicted
throughput normalized to one chip) ride along to the gateway's weighted
router and the autoscaler's capacity-weighted pressure signals.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from routest_tpu.utils.logging import get_logger

_log = get_logger("routest_tpu.fleet.placement")

# The env key every slice stamps: replicas surface it in
# ``/api/health`` ``checks.engine.mesh.placement`` so an operator can
# see which slice a process believes it owns.
PLACEMENT_LABEL_ENV = "RTPU_FLEET_PLACEMENT_LABEL"

_FORCE_COUNT_RE = re.compile(
    r"--xla_force_host_platform_device_count=(\d+)")


@dataclasses.dataclass(frozen=True)
class DeviceInventory:
    """What the host has: ``chips`` devices on ``platform``
    (``cpu`` | ``tpu`` | ``gpu``), and where the answer came from
    (``env`` | ``xla_flags`` | ``jax`` | ``default``)."""

    platform: str
    chips: int
    source: str


_DEVICE_QUERY = ("import jax; d = jax.devices(); "
                 "print(d[0].platform, len(d))")


def _query_devices(env: Mapping[str, str]) -> Tuple[str, int]:
    """(platform, device count) as JAX reports them in a child process
    that exits — and so releases the chips — before this returns."""
    proc = subprocess.run([sys.executable, "-c", _DEVICE_QUERY],
                          env=dict(env), capture_output=True, text=True,
                          timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device detection failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-500:]}")
    platform, count = proc.stdout.split()[-2:]
    return platform, int(count)


def detect_inventory(
        env: Optional[Mapping[str, str]] = None) -> DeviceInventory:
    """Enumerate local devices WITHOUT importing JAX in this process:
    env overrides answer first, else a child process asks JAX. A failed
    detection raises — planning "1 chip, cpu" on a host whose chips
    could not be seen would boot replicas on the wrong device."""
    env = env if env is not None else os.environ
    raw = env.get("RTPU_FLEET_CHIPS")
    if raw:
        try:
            chips = int(raw)
            if chips > 0:
                platform = env.get("RTPU_FLEET_PLATFORM") or (
                    "cpu" if env.get("ROUTEST_FORCE_CPU") == "1" else "tpu")
                return DeviceInventory(platform, chips, "env")
        except ValueError:
            _log.warning("bad_chips_override", value=raw)
    if env.get("ROUTEST_FORCE_CPU") == "1" or env.get(
            "JAX_PLATFORMS", "").strip() == "cpu":
        m = _FORCE_COUNT_RE.search(env.get("XLA_FLAGS", ""))
        if m:
            return DeviceInventory("cpu", int(m.group(1)), "xla_flags")
        return DeviceInventory("cpu", 1, "default")
    platform, chips = _query_devices(env)
    return DeviceInventory(platform, chips, "jax")


@dataclasses.dataclass(frozen=True)
class ReplicaSlice:
    """One replica's share of the host: ``chips`` devices (by id), the
    env overlay that pins them, and the capacity units (predicted
    preds/s normalized to a 1-chip replica) the gateway weights by."""

    chips: int
    device_ids: Tuple[int, ...]
    label: str
    env: Mapping[str, str]
    capacity: float

    def as_dict(self) -> dict:
        return {"chips": self.chips, "device_ids": list(self.device_ids),
                "label": self.label, "capacity": round(self.capacity, 3),
                "env": dict(self.env)}


@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    platform: str
    total_chips: int
    layout: str                       # "8x1" | "2x4" | "4+2" | "host"
    slices: Tuple[ReplicaSlice, ...]
    source: str                       # forced | auto_measured | auto_model…
    predicted_rate: float             # capacity units summed

    @property
    def capacity_units(self) -> float:
        return sum(s.capacity for s in self.slices)

    def growth_slice(self, index: int) -> ReplicaSlice:
        """The slice an autoscaler scale-up should spawn: the plan's
        repeating unit (modal chip count), pinned round-robin over the
        inventory — growth past the physical chip count oversubscribes
        devices rather than reverting to an unpinned 1-chip replica."""
        counts = [s.chips for s in self.slices] or [1]
        k = max(set(counts), key=counts.count)
        start = (index * k) % max(1, self.total_chips)
        ids = tuple((start + j) % max(1, self.total_chips)
                    for j in range(k))
        label = f"g{index}:{k}chip"
        cap = next((s.capacity for s in self.slices if s.chips == k),
                   float(k))
        return ReplicaSlice(k, ids, label,
                            slice_env(self.platform, k, ids, label), cap)

    def as_dict(self) -> dict:
        return {"platform": self.platform, "total_chips": self.total_chips,
                "layout": self.layout, "source": self.source,
                "predicted_rate": round(self.predicted_rate, 3),
                "capacity_units": round(self.capacity_units, 3),
                "slices": [s.as_dict() for s in self.slices]}


def candidate_layouts(chips: int) -> List[Tuple[int, ...]]:
    """Every way to carve ``chips`` devices into slices of one uniform
    size (plus a remainder slice when the size does not divide): each
    layout is a tuple of per-slice chip counts covering every chip
    exactly once. 8 → (1,)*8, (2,2,2,2), (4,4), (8,); 6 includes
    (4, 2); 3 → (1,1,1), (2,1), (3,)."""
    chips = max(1, int(chips))
    seen = set()
    out: List[Tuple[int, ...]] = []
    for per in range(1, chips + 1):
        n, rem = divmod(chips, per)
        layout = tuple([per] * n + ([rem] if rem else []))
        if layout not in seen:
            seen.add(layout)
            out.append(layout)
    return out


# x,y,z extent of the chips one masked process owns, by slice width —
# what ran on a v5e 2x2 host (PR 21): four one-chip processes at once,
# and two two-chip processes over the consecutive ids (0,1) and (2,3)
# that ``plan_placement`` hands out ("2,1,1" and the pairs (0,2), (1,3)
# were refused). Other widths get the mask alone until a chip run shows
# their bounds; a slice that is the whole host needs none.
_TPU_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def slice_env(platform: str, chips: int, device_ids: Sequence[int],
              label: str) -> Dict[str, str]:
    """The per-replica env overlay that makes a worker own exactly its
    slice. CPU slices get a virtual device count (the shape-pinning
    path: ``XLA_FLAGS --xla_force_host_platform_device_count``); GPU
    slices mask with ``CUDA_VISIBLE_DEVICES``; TPU slices mask with
    ``TPU_VISIBLE_CHIPS`` and tell libtpu that this process is a whole
    topology of its own (process bounds 1,1,1 and the slice's chip
    bounds) — with the mask alone, every process after the first fails
    on libtpu's multi-process lockfile. Multi-chip slices
    force the serving mesh on (``ROUTEST_MESH=1``) with
    ``RTPU_MESH_DATA`` = the slice width so the batch shards over
    exactly the owned devices."""
    ids = ",".join(str(i) for i in device_ids)
    env: Dict[str, str] = {PLACEMENT_LABEL_ENV: label,
                           "RTPU_FLEET_SLICE_CHIPS": str(chips)}
    if platform == "cpu":
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
        env["ROUTEST_FORCE_CPU"] = "1"
    elif platform == "gpu":
        env["CUDA_VISIBLE_DEVICES"] = ids
    else:  # tpu
        env["TPU_VISIBLE_CHIPS"] = ids
        bounds = _TPU_CHIP_BOUNDS.get(chips)
        if bounds:
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    env["RTPU_MESH_DATA"] = str(chips)
    env["ROUTEST_MESH"] = "1" if chips > 1 else "0"
    return env


# ── throughput models (what the auto comparison scores with) ─────────

def model_rate(chips: int, mesh_eff: float) -> float:
    """Predicted per-replica rate in 1-chip units under the built-in
    model: each chip added to a mesh keeps ``mesh_eff`` of its ideal
    contribution (ICI collectives + pad waste grow with the slice), so
    a k-chip replica delivers ``k·mesh_eff^(k-1)`` units. With eff < 1
    more 1-chip replicas always win on modeled throughput — a bigger
    slice must EARN its place through the measured curve (or an
    explicit ``RTPU_FLEET_PLACEMENT`` override)."""
    return chips * (mesh_eff ** max(0, chips - 1))


def measured_rates(record_path: str,
                   platform: Optional[str] = None
                   ) -> Optional[Dict[int, float]]:
    """chips → preds/s from a recorded ``fleet_chips.json``
    curve, or None when absent/unreadable (LOUDLY: a corrupt record
    must not silently change placement). With ``platform``, a record
    measured on a DIFFERENT backend is refused — a CPU-virtual curve
    says nothing about real-chip scaling, so TPU placement falls back
    to the model until the battery re-records there."""
    try:
        with open(record_path) as f:
            record = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as e:
        _log.warning("placement_record_unreadable", path=record_path,
                     error=f"{type(e).__name__}: {e}")
        return None
    recorded_backend = (record.get("host") or {}).get("backend")
    if platform and recorded_backend and recorded_backend != platform:
        _log.info("placement_record_backend_mismatch",
                  path=record_path, recorded=recorded_backend,
                  platform=platform)
        return None
    rates: Dict[int, float] = {}
    for row in record.get("curve") or []:
        try:
            chips, rate = int(row["chips"]), float(row["preds_per_s"])
        except (KeyError, TypeError, ValueError):
            continue
        if chips > 0 and rate > 0:
            rates[chips] = rate
    return rates or None


def _interp_rate(chips: int, rates: Dict[int, float]) -> float:
    """Rate for a slice width the record didn't measure: linear in
    chips between the nearest measured widths (flat past the ends)."""
    if chips in rates:
        return rates[chips]
    ks = sorted(rates)
    lo = max((k for k in ks if k < chips), default=None)
    hi = min((k for k in ks if k > chips), default=None)
    if lo is None:
        return rates[hi] * chips / hi
    if hi is None:
        return rates[lo] * chips / lo
    frac = (chips - lo) / (hi - lo)
    return rates[lo] + frac * (rates[hi] - rates[lo])


def parse_layout_spec(spec: str, chips: int) -> Optional[Tuple[int, ...]]:
    """``"2x4"`` → (4, 4); ``"4,2,1"`` → (4, 2, 1). None when the spec
    is not a layout (``auto``/``replica``/``mesh`` handled upstream).
    A spec that names more chips than the inventory is refused loudly —
    an operator typo must not oversubscribe silently."""
    spec = spec.strip().lower()
    m = re.fullmatch(r"(\d+)x(\d+)", spec)
    if m:
        n, per = int(m.group(1)), int(m.group(2))
        layout: Tuple[int, ...] = tuple([per] * n)
    elif re.fullmatch(r"\d+(,\d+)*", spec):
        layout = tuple(int(v) for v in spec.split(","))
    else:
        return None
    if not layout or any(v <= 0 for v in layout):
        return None
    if sum(layout) > chips:
        raise ValueError(
            f"placement spec {spec!r} names {sum(layout)} chips; "
            f"host has {chips}")
    return layout


def plan_placement(inventory: DeviceInventory, *,
                   replicas: Optional[int] = None,
                   spec: str = "auto",
                   mesh_eff: float = 0.92,
                   record_path: str = "artifacts/fleet_chips.json",
                   ) -> PlacementPlan:
    """Turn an inventory into a placement plan.

    ``replicas`` caps the slice count for forced-``replica``/CPU-auto
    plans (the ``RTPU_FLEET_REPLICAS`` contract: an operator who asked
    for 2 replicas gets 2). ``spec`` is ``RTPU_FLEET_PLACEMENT``:
    ``auto`` (compare layouts), ``replica``, ``mesh``, ``NxK``, or an
    explicit comma list. ``mesh_eff``/``record_path`` feed the auto
    comparison (measured beats modeled)."""
    chips = max(1, inventory.chips)
    platform = inventory.platform
    spec = (spec or "auto").strip().lower()

    def build(layout: Tuple[int, ...], source: str,
              rate_fn) -> PlacementPlan:
        if platform != "cpu" and sum(layout) > chips:
            # A chip belongs to one process: a second replica pinned to
            # an owned chip cannot start. Refuse the plan instead.
            raise ValueError(
                f"placement {layout} needs {sum(layout)} {platform} "
                f"chips; host has {chips}")
        slices: List[ReplicaSlice] = []
        next_id = 0
        base_rate = rate_fn(1)
        for i, k in enumerate(layout):
            ids = tuple(range(next_id, next_id + k))
            next_id += k
            label = f"s{i}:{k}chip"
            cap = rate_fn(k) / base_rate if base_rate > 0 else float(k)
            slices.append(ReplicaSlice(
                k, ids, label, slice_env(platform, k, ids, label), cap))
        if len(set(layout)) == 1:
            name = f"{len(layout)}x{layout[0]}"
        else:
            name = "+".join(str(k) for k in layout)
        return PlacementPlan(platform, chips, name, tuple(slices),
                             source, sum(s.capacity for s in slices))

    rates = measured_rates(record_path, platform) if record_path else None

    def rate_fn(k: int) -> float:
        if rates:
            return _interp_rate(k, rates)
        return model_rate(k, mesh_eff)

    explicit = parse_layout_spec(spec, chips) if spec not in (
        "auto", "replica", "mesh") else None
    if explicit is not None:
        return build(explicit, "spec", rate_fn)
    if spec == "replica":
        n = replicas if replicas else chips
        return build(tuple([1] * max(1, n)), "replica", rate_fn)
    if spec == "mesh":
        return build((chips,), "mesh", rate_fn)
    if spec != "auto":
        raise ValueError(f"unknown RTPU_FLEET_PLACEMENT {spec!r} "
                         "(auto | replica | mesh | NxK | k,k,…)")

    # auto. CPU virtual devices time-share one host: never multiply
    # them — plain 1-chip replicas with EMPTY overlays, so a default
    # boot is byte-identical to the pre-placement era.
    if platform == "cpu":
        n = max(1, replicas if replicas else 1)
        slices = tuple(
            ReplicaSlice(1, (), f"s{i}:host",
                         {PLACEMENT_LABEL_ENV: f"s{i}:host"}, 1.0)
            for i in range(n))
        return PlacementPlan(platform, chips, "host", slices,
                             "auto_host", float(n))
    # ``replicas`` caps the slice count (the RTPU_FLEET_REPLICAS
    # contract: an operator who asked for N processes gets at most N —
    # the planner then spends the chips WITHIN that, e.g. 8 chips at
    # replicas=2 compares 2×4 against 1×8, not 8×1).
    layouts = [lo for lo in candidate_layouts(chips)
               if not replicas or len(lo) <= replicas]
    if not layouts:
        layouts = [tuple([chips])]
    best = None
    for layout in layouts:
        plan = build(layout,
                     "auto_measured" if rates else "auto_model", rate_fn)
        # Higher predicted rate wins; ties prefer MORE replicas
        # (process isolation: one crash takes out one batcher).
        key = (plan.predicted_rate, len(plan.slices))
        if best is None or key > best[0]:
            best = (key, plan)
    plan = best[1]
    _log.info("placement_planned", platform=platform, chips=chips,
              layout=plan.layout, source=plan.source,
              predicted_rate=round(plan.predicted_rate, 2))
    return plan


def plan_from_env(env: Optional[Mapping[str, str]] = None,
                  replicas: Optional[int] = None) -> PlacementPlan:
    """The fleet entry point's one-call path: detect + plan from the
    ``RTPU_FLEET_PLACEMENT*`` env knobs."""
    env = env if env is not None else os.environ

    def _num(name: str, default: float) -> float:
        raw = env.get(name)
        if not raw:
            return default
        try:
            return float(raw)
        except ValueError:
            _log.warning("bad_placement_knob", name=name, value=raw)
            return default

    inventory = detect_inventory(env)
    return plan_placement(
        inventory,
        replicas=replicas,
        spec=env.get("RTPU_FLEET_PLACEMENT", "auto"),
        mesh_eff=_num("RTPU_FLEET_PLACEMENT_EFF", 0.92),
        record_path=env.get("RTPU_FLEET_PLACEMENT_RECORD",
                            "artifacts/fleet_chips.json"))

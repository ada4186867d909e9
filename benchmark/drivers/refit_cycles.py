"""Back-to-back live refit cycles through the program's own entry,
``ContinuousTrainer.run_once``, over a window of probe traversals that
is refilled before each cycle.

The trainer reads three things of a router: ``graph_dict()``,
``_fingerprint`` and ``_gnn_path``. It is handed the smallest object
with those three: a ``RoadRouter`` of this size would first build its
overlay, which this traffic measures none of.

Set-up builds the one trainer, drives its first cycle (which compiles)
with a recorder around its step that keeps the first steps' losses and
states, and hands the same trainer to the window. ``check`` follows
those first steps with the plain reference.
"""

from __future__ import annotations

import os
import time
import types
from typing import Dict, List

import numpy as np

from benchmark import compare, counts, graphgen, seeds, trace, traffic
from benchmark.reference import road_gnn_ref as ref

ANNOTATIONS = ("refill-window", "cycle", "window")


class _PinnedClock:
    """``time`` as the trainer's module sees it, with the hour pinned so
    that a seed gives the same batch at any hour of the day."""

    def __init__(self, hour: int) -> None:
        self._hour = int(hour)
        self.perf_counter = time.perf_counter

    def localtime(self, *_):
        return types.SimpleNamespace(tm_hour=self._hour)


class _StepRecorder:
    """Passes the trainer's step through and keeps what the first
    ``keep`` calls were given and returned (device arrays; no sync)."""

    def __init__(self, inner, keep: int) -> None:
        self.inner, self.keep = inner, keep
        self.first_params = None
        self.outputs: List = []

    def __call__(self, params, opt_state, *rest):
        out = self.inner(params, opt_state, *rest)
        if self.first_params is None:
            self.first_params = params
        if len(self.outputs) < self.keep:
            self.outputs.append(out)
        return out


def _adam_mu(opt_state):
    found = [s for s in opt_state if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError("expected one Adam state in the optimizer state")
    return found[0].mu


class Driver:
    def __init__(self, run) -> None:
        import jax

        from routest_tpu.live import trainer as trainer_mod
        from routest_tpu.live.state import CongestionState

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        self.graph = graphgen.road_graph(
            cfg["n_nodes"], cfg["n_arcs"], seeds.sub_seed(run.seed, "graph"),
            cfg["bbox"])
        g = self.graph
        path = os.path.join(run.scratch, "road_gnn_refit.msgpack")
        router_like = types.SimpleNamespace(
            graph_dict=lambda: g, _fingerprint=None, _gnn_path=path)
        trainer_mod.time = _PinnedClock(cfg["pinned_hour"])
        self.state = CongestionState(
            g["length_m"] / np.maximum(g["speed_limit"], 0.1),
            window=cfg["observation_window"])
        self.init_seed = seeds.sub_seed(run.seed, "gnn-init")
        self.trainer = trainer_mod.ContinuousTrainer(
            router_like, self.state, seed=self.init_seed)
        t = self.trainer
        stated = (cfg["steps_per_cycle"], cfg["learning_rate"],
                  cfg["min_obs"], cfg["hidden"])
        if (t.steps, t.lr, t.min_obs, t.hidden) != stated:
            raise ValueError(
                f"the trainer's defaults {(t.steps, t.lr, t.min_obs, t.hidden)}"
                f" are not the configuration's {stated}")
        self.probes = traffic.ProbeSource(run.seed, g, mix)
        self._clock = 0.0
        self.durations: List[float] = []
        self.failed = 0
        self.elapsed = 0.0

        # the first cycle: compiles, and is what the reference follows
        self.first_window = self._refill()
        t._ensure_model()
        t._ensure_step()
        self.steps_compared = int(mix["compared_steps"])
        recorder = _StepRecorder(t._step_fn, self.steps_compared)
        t._step_fn = recorder
        try:
            result = t.run_once()
        finally:
            t._step_fn = recorder.inner
        if result.get("trained") is not True:
            raise RuntimeError(f"the first refit cycle failed: {result}")
        self.first = jax.device_get({
            "losses": [o[2] for o in recorder.outputs],
            "mu": _adam_mu(recorder.outputs[0][1]),
            "before": recorder.first_params,
            "after": recorder.outputs[-1][0]})

    def _refill(self):
        """One window of probes, folded batch by batch as a publisher
        sends them (one hour stamp a batch)."""
        edge, hour, seconds = self.probes.window()
        per = int(self.mix["probes_per_batch"])
        for lo in range(0, len(edge), per):
            self._clock += 1.0
            self.state.fold(edge[lo:lo + per], seconds[lo:lo + per],
                            t=self._clock, hour=int(hour[lo]))
        return edge, hour, seconds

    def window(self, seconds: float) -> None:
        t_start = time.perf_counter()
        with trace.annotate("window"):
            while True:
                with trace.annotate("refill-window"):
                    self._refill()
                t0 = time.perf_counter()
                with trace.annotate("cycle"):
                    result = self.trainer.run_once()
                now = time.perf_counter()
                if result.get("trained") is True:
                    self.durations.append(now - t0)
                else:
                    self.failed += 1
                if now - t_start >= seconds:
                    break
        self.elapsed = time.perf_counter() - t_start

    @property
    def attempted(self) -> int:
        return len(self.durations) + self.failed

    def end_to_end(self) -> Dict[str, float]:
        steps = self.cfg["steps_per_cycle"] * len(self.durations)
        return {"gnn_edges_per_s":
                self.cfg["n_arcs"] * steps / self.elapsed}

    def counts(self) -> Dict:
        steps = self.cfg["steps_per_cycle"] * len(self.durations)
        return {"steps": steps, "cycles": len(self.durations),
                "flops": steps * counts.gnn_train_step_flops(self.cfg),
                "module": self.mix["step_module"],
                "window_s": self.elapsed}

    def release(self) -> None:
        """Drop the trainer and its state before the reference runs."""
        self.trainer = None
        self.state = None

    def follow(self, dtype_name: str = "float32",
               matmul_precision: str = "") -> Dict:
        """The reference's readings of the first cycle's first steps
        (in a lower precision: the control)."""
        return ref.follow(self.cfg, self.graph, self.first_window,
                          self.init_seed, self.steps_compared,
                          dtype_name=dtype_name,
                          matmul_precision=matmul_precision)

    def numbers(self) -> Dict[str, float]:
        """What the program's first steps read against the reference."""
        return gaps(self.program_readings(), self.follow())

    def program_readings(self) -> Dict:
        import jax

        b1 = self.cfg["adam"]["b1"]
        grads = jax.tree_util.tree_map(lambda m: m / (1.0 - b1),
                                       self.first["mu"])
        change = jax.tree_util.tree_map(
            lambda a, b: a - b, self.first["after"], self.first["before"])
        return {"losses": [float(x) for x in self.first["losses"]],
                "grad_norms": ref.leaf_norms(grads),
                "change_norms": ref.leaf_norms(change)}

    def check(self) -> List[compare.Check]:
        return compare.with_limits(self.numbers(), self.mix["limits"])


def gaps(got: Dict, want: Dict) -> Dict[str, float]:
    """loss_gap: widest relative gap of a step's loss. grad_gap and
    update_gap: by the worst leaf, the gap between the two norms against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger. Leaves whose reference gradient is under a thousandth of
    the median leaf's move by round-off alone and are left out of
    update_gap."""
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(got["losses"], want["losses"]))
    if len(got["losses"]) != len(want["losses"]):
        loss_gap = float("inf")
    g_med = float(np.median(list(want["grad_norms"].values())))
    c_med = float(np.median(list(want["change_norms"].values())))
    grad_gap = max(compare.norm_gap(got["grad_norms"][k], w, g_med)
                   for k, w in want["grad_norms"].items())
    moved = [k for k, w in want["grad_norms"].items() if w >= 1e-3 * g_med]
    update_gap = max(compare.norm_gap(got["change_norms"][k],
                                      want["change_norms"][k], c_med)
                     for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}

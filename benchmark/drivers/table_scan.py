"""Closed-loop passes over a resident table, slice by slice.

Each pass runs the program's scorer (``load_model`` →
``model.apply_quantiles`` under ``jax.jit``) over successive slices of
the resident feature table and writes the resident answer table in
place; a pass ends in ``block_until_ready``. The window ends with the
pass in which the time ran out.

A pass is ONE device program, a ``fori_loop`` over the slices (as
``bench.measure`` loops on the device). Dispatched slice by slice the
pass was 5% shorter, but the runtime keeps the host only some 20 ms
ahead of the device, so every 100 ms stall of a shared host's CPU
starved the chip and half of the runs on such a host lost 0.3-1.3%
(PERF.md, PR 24): a yardstick for the scorer's kernels has to be
deaf to that.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, List

import numpy as np

from benchmark import compare, counts, trace, traffic
from benchmark.reference import eta_mlp_ref

ANNOTATIONS = ("pass", "window")
UNWRITTEN = -1.0        # ETAs are positive: a row never scored shows


class Driver:
    def __init__(self, run) -> None:
        import jax
        import jax.numpy as jnp

        from routest_tpu.train.checkpoint import load_model

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        self._artifact = os.path.join(run.repo, cfg["artifact"])
        model, params = load_model(self._artifact)
        if (list(model.hidden) != list(cfg["hidden"])
                or list(model.quantiles) != list(cfg["quantiles"])
                or np.dtype(model.policy.compute_dtype).name
                != cfg["compute_dtype"]):
            raise ValueError("the artifact is not the configuration's model")
        params = jax.device_put(params)
        forward = jax.jit(lambda x: model.apply_quantiles(params, x))

        self.rows = cfg["n_stops"] ** 2
        self.slice_rows = min(int(mix["rows_per_slice"]), self.rows)
        if self.rows % self.slice_rows:
            raise ValueError("rows_per_slice has to divide the table")
        n_q, size = len(cfg["quantiles"]), self.slice_rows

        n_slices = self.rows // size

        @functools.partial(jax.jit, donate_argnums=(1,))
        def score_pass(feats, answers):
            def score_slice(i, answers):
                x = jax.lax.dynamic_slice_in_dim(feats, i * size, size, 0)
                return jax.lax.dynamic_update_slice_in_dim(
                    answers, forward(x), i * size, 0)

            return jax.lax.fori_loop(0, n_slices, score_slice, answers)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def clear(answers):
            return jnp.full_like(answers, UNWRITTEN)

        self._score_pass = score_pass
        self.feats = traffic.od_table(run.seed, cfg)
        self.answers = clear(jnp.zeros((self.rows, n_q), jnp.float32))
        # warm-up: one whole pass (compiles the one shape the window
        # uses), then the answers are cleared so that what is compared
        # is what the timed passes wrote
        self._pass()
        self.answers = clear(self.answers)
        self.answers.block_until_ready()
        self.durations: List[float] = []
        self.elapsed = 0.0

    def _pass(self) -> None:
        self.answers = self._score_pass(self.feats, self.answers)
        self.answers.block_until_ready()

    def window(self, seconds: float) -> None:
        t_start = time.perf_counter()
        with trace.annotate("window"):
            while True:
                t0 = time.perf_counter()
                with trace.annotate("pass"):
                    self._pass()
                now = time.perf_counter()
                self.durations.append(now - t0)
                if now - t_start >= seconds:
                    break
        self.elapsed = time.perf_counter() - t_start

    @property
    def attempted(self) -> int:
        return len(self.durations)

    failed = 0

    def end_to_end(self) -> Dict[str, float]:
        return {
            "od_rows_per_s": self.rows * len(self.durations) / self.elapsed,
            "od_pass_p95_ms": float(np.percentile(self.durations, 95)) * 1e3,
        }

    def counts(self) -> Dict:
        rows = self.rows * len(self.durations)
        return {"rows": rows,
                "flops": rows * counts.eta_flops_per_row(self.cfg),
                "bytes": (rows * counts.eta_bytes_per_row(self.cfg)
                          + len(self.durations)
                          * counts.eta_weight_bytes(self.cfg)),
                "module": self.mix["scoring_module"],
                "window_s": self.elapsed}

    def release(self) -> None:
        """Nothing to free: the two tables are what is compared."""

    def numbers(self, answers_precision: str = "") -> Dict[str, float]:
        _, params = eta_mlp_ref.read_artifact(self._artifact)
        return eta_mlp_ref.table_gaps(
            params, self.feats, self.answers, len(self.cfg["quantiles"]),
            block_rows=int(self.mix.get("reference_block_rows", 1 << 20)),
            answers_precision=answers_precision)

    def check(self) -> List[compare.Check]:
        return compare.with_limits(self.numbers(), self.mix["limits"])

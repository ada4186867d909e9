"""Closed-loop passes over a resident table of route histories.

The program's table-scoring entry (``routest_tpu.serve.seq_score
.RouteScorer``) is handed the table as device arrays and scores every
route once a pass; a pass ends in the entry's own sync. The window ends
with the pass in which the time ran out. The weights are the model's
seeded init, drawn on the device in bfloat16; the driver calls the entry
and nothing private.

``correct``: after the window the plain float32 reference
(``benchmark/reference/dots3_ref.py``) recomputes every route of the
last timed pass, one route at a time, and what that pass wrote is
compared with it: the next-arc logits, the log-sum-exps and the named
logit rows by a relative gap (the norm of the difference over the norm
of the reference's, the worst route), the log-likelihoods, and the key
sets and chosen experts that the program reports beside them.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import compare, counts_seq, seeds, trace, traffic_seq
from benchmark.reference import dots3_ref

ANNOTATIONS = ("pass", "window")


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


class Gaps:
    """The compared numbers, gathered route by route."""

    def __init__(self) -> None:
        self.worst = {"logit_gap": 0.0, "lse_gap": 0.0, "rows_gap": 0.0,
                      "loglik_gap": 0.0}
        self.experts = [0.0, 0.0]        # agreeing, all
        self.selected = [0.0, 0.0]
        self.key_sets = [0.0, 0.0]       # differing, all

    def add(self, got: Dict, want: Dict, sliding: List[int]) -> None:
        n = len(want["lse"])
        for name, key in (("logit_gap", "next_logit"), ("lse_gap", "lse"),
                          ("rows_gap", "rows")):
            self.worst[name] = max(self.worst[name],
                                   rel_gap(got[key], want[key]))
        ll_got, ll_want = float(got["loglik"]), float(want["loglik"])
        self.worst["loglik_gap"] = max(
            self.worst["loglik_gap"],
            abs(ll_got - ll_want) / max(abs(ll_want), 1e-30)
            if np.isfinite(ll_got) else float("inf"))
        for g, w in zip(got["chosen"], want["chosen"]):
            g, w = np.asarray(g), np.asarray(w)
            same = (g[:, :, None] == w[:, None, :]).any(-1).sum()
            self.experts[0] += float(same)
            self.experts[1] += float(w.size)
        for g, w in zip(got["selected"], want["selected"]):
            g, w = np.asarray(g, bool), np.asarray(w, bool)
            self.selected[0] += float((g & w).sum())
            self.selected[1] += float(w.sum())
        for l, (g_n, w_n, g_f, w_f) in enumerate(zip(
                got["n_keys"], want["n_keys"], got["first_key"],
                want["first_key"])):
            differ = np.asarray(g_n) != np.asarray(w_n)
            if l in sliding:
                differ = differ | (np.asarray(g_f) != np.asarray(w_f))
            self.key_sets[0] += float(differ.sum())
            self.key_sets[1] += float(n)

    def result(self) -> Dict[str, float]:
        out = dict(self.worst)
        out["expert_gap"] = 1.0 - self.experts[0] / max(self.experts[1], 1.0)
        out["selected_gap"] = (1.0 - self.selected[0]
                               / max(self.selected[1], 1.0))
        out["key_set_gap"] = self.key_sets[0] / max(self.key_sets[1], 1.0)
        return out


class Driver:
    def __init__(self, run) -> None:
        # the program's entry first: a commit without it fails here,
        # before anything is built
        from routest_tpu.models.route_lm import RouteLM
        from routest_tpu.serve.seq_score import RouteScorer

        import jax
        import jax.numpy as jnp

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        if traffic_seq.route_lengths(mix) != list(mix["lengths"]):
            raise ValueError("the mix's lengths are not its quantiles")
        model = RouteLM.from_config(cfg)
        for name in ("param_dtype", "compute_dtype", "output_dtype"):
            if np.dtype(getattr(model.policy, name)).name != cfg[name]:
                raise ValueError(f"the model's {name} is not the "
                                 f"configuration's")
        self.share = (model.experts_first, model.experts_held)
        params = jax.jit(model.init)(jax.random.PRNGKey(
            seeds.sub_seed(run.seed, "weights")))
        self.scorer = RouteScorer(
            model, params, max_step_tokens=int(mix["max_step_tokens"]))
        self.params = self.scorer.params
        self.table = traffic_seq.route_table(run.seed, cfg, mix)
        self.ids = jnp.asarray(self.table["ids"])
        self.lengths = jnp.asarray(self.table["lengths"])
        self.rows_at = jnp.asarray(self.table["rows_at"])
        self.plan = self.scorer.plan(self.table["lengths"])
        self.scores = None
        # warm-up: one whole pass compiles every shape the window uses
        self._pass()
        self.scores = None
        self.durations: List[float] = []
        self.elapsed = 0.0

    def _pass(self) -> None:
        self.scores = self.scorer.score(self.ids, self.lengths, self.rows_at,
                                        plan=self.plan)

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
        return {"od_rows_per_s": (len(self.table["lengths"])
                                  * len(self.durations) / self.elapsed)}

    def counts(self) -> Dict:
        import jax.numpy as jnp

        passes = len(self.durations)
        first, count = self.share
        chosen = self.scores.taps["chosen"]              # (n_moe, R, W, k)
        real = (jnp.arange(chosen.shape[2])[None, :]
                < self.lengths[:, None])[None, :, :, None]
        held = float(jnp.sum(real & (chosen >= first)
                             & (chosen < first + count)))
        return {"passes": passes, "routes": len(self.table["lengths"]),
                "tokens_real": sum(s.real_tokens for s in self.plan),
                "tokens_padded": sum(s.padded_tokens for s in self.plan),
                "steps": len(self.plan), "held_assignments": held,
                "flops": passes * counts_seq.pass_flops(
                    self.cfg, self.table["lengths"], held),
                "window_s": self.elapsed}

    def release(self) -> None:
        """The scorer and its programs go; the parameters (what the
        reference reads) and the last pass's answers stay."""
        self.scorer = None

    # ── the comparison ──────────────────────────────────────────────

    def program_routes(self) -> List[Dict]:
        """What the last pass wrote, route by route, in the form of the
        reference's answers."""
        s, out = self.scores, []
        taps = {k: np.asarray(v) for k, v in s.taps.items()}
        next_logit, lse = np.asarray(s.next_logit), np.asarray(s.lse)
        loglik, rows = np.asarray(s.loglik), np.asarray(s.rows)
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append({"next_logit": next_logit[r, :n], "lse": lse[r, :n],
                        "loglik": float(loglik[r]), "rows": rows[r],
                        "chosen": list(taps["chosen"][:, r, :n]),
                        "n_keys": list(taps["n_keys"][:, r, :n]),
                        "first_key": list(taps["first_key"][:, r, :n]),
                        "selected": list(taps["selected"][:, r, :, :n])})
        return out

    def reference(self, precision: str = "") -> List[Dict]:
        """The plain reference's answers for every route of the table,
        as host arrays; ``precision`` names the control's."""
        blocks = dots3_ref.Blocks(**self.mix["reference_blocks"])
        out = []
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            got = dots3_ref.forward(
                self.params, self.cfg, self.table["ids"][r, :n], self.share,
                list(self.table["rows_at"][r]), blocks=blocks,
                precision=precision or None)
            out.append({k: ([np.asarray(x) for x in v]
                            if isinstance(v, list) else np.asarray(v))
                        for k, v in got.items()})
        return out

    def gaps(self, got: List[Dict], want: List[Dict]) -> Dict[str, float]:
        sliding = [l for l, (a, _) in enumerate(
            dots3_ref.layer_kinds(self.cfg)) if a == "sliding_attention"]
        gaps = Gaps()
        for g, w in zip(got, want):
            gaps.add(g, w, sliding)
        return gaps.result()

    def numbers(self, answers_precision: str = "") -> Dict[str, float]:
        """The compared numbers of the last timed pass. With
        ``answers_precision`` set, the answers compared are the
        reference's own at that lower precision (the control) and the
        program's are not read."""
        got = (self.reference(answers_precision) if answers_precision
               else self.program_routes())
        return self.gaps(got, self.reference())

    def check(self) -> List[compare.Check]:
        return compare.with_limits(self.numbers(), self.mix["limits"])

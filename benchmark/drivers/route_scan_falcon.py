"""Closed-loop passes over a resident table of route histories, scored
by the fifth route-sequence model (``RouteLMFalconH1``, configuration
``falcon-h1-34b-l0-7``) through the same table-scoring entry as
``route_scan.py`` drives: the window, the warm-up and the end-to-end
metric are that driver's; this one builds the hybrid model, counts its
work and compares with its reference.

``correct``: after the window the plain float32 reference
(``benchmark/reference/falcon_h1_ref.py``) recomputes every route of
the last timed pass, one route at a time, the state-space mixer token
by token, and what that pass wrote is compared with it: the four gaps
of ``route_scan.Gaps.worst`` (next-arc logits, log-sum-exps, named
rows, log-likelihood), ``state_gap`` (each state-space layer's state at
the route's last real token, relative, the worst layer and route) and
``key_set_gap`` (the share of (block, token) whose number of keys seen
or first key differs from the reference's: exactly 0).
"""

from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1  # noqa: I001
# (the program's entry first: a commit without it fails here, at once)

from typing import Dict, List

import numpy as np

from benchmark import counts_falcon, seeds, traffic_seq
from benchmark.drivers import route_scan
from benchmark.reference import falcon_h1_ref
from benchmark.reference.sala_ref import Blocks

ANNOTATIONS = route_scan.ANNOTATIONS
rel_gap = route_scan.rel_gap


def gaps(got: List[Dict], want: List[Dict]) -> Dict[str, float]:
    """The compared numbers over the table's routes."""
    worst = dict(route_scan.Gaps().worst, state_gap=0.0)
    key_sets = [0.0, 0.0]                        # differing, all
    for g, w in zip(got, want):
        for name, key in (("logit_gap", "next_logit"), ("lse_gap", "lse"),
                          ("rows_gap", "rows")):
            worst[name] = max(worst[name], rel_gap(g[key], w[key]))
        ll_g, ll_w = float(g["loglik"]), float(w["loglik"])
        worst["loglik_gap"] = max(
            worst["loglik_gap"],
            abs(ll_g - ll_w) / max(abs(ll_w), 1e-30)
            if np.isfinite(ll_g) else float("inf"))
        for s_g, s_w in zip(g["state"], w["state"]):
            worst["state_gap"] = max(worst["state_gap"], rel_gap(s_g, s_w))
        for n_g, n_w, f_g, f_w in zip(g["n_keys"], w["n_keys"],
                                      g["first_key"], w["first_key"]):
            differ = ((np.asarray(n_g) != np.asarray(n_w))
                      | (np.asarray(f_g) != np.asarray(f_w)))
            key_sets[0] += float(differ.sum())
            key_sets[1] += float(differ.size)
    return dict(worst, key_set_gap=key_sets[0] / max(key_sets[1], 1.0))


class Driver(route_scan.Driver):
    def __init__(self, run) -> None:
        from routest_tpu.serve.seq_score import RouteScorer

        import jax
        import jax.numpy as jnp

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        if traffic_seq.route_lengths(mix) != list(mix["lengths"]):
            raise ValueError("the mix's lengths are not its quantiles")
        model = RouteLMFalconH1.from_config(cfg)
        for name in ("param_dtype", "compute_dtype", "output_dtype"):
            if np.dtype(getattr(model.policy, name)).name != cfg[name]:
                raise ValueError(f"the model's {name} is not the "
                                 f"configuration's")
        params = jax.jit(model.init)(jax.random.PRNGKey(
            seeds.sub_seed(run.seed, "weights")))
        self.scorer = RouteScorer(
            model, params, max_step_tokens=int(mix["max_step_tokens"]),
            max_classes=int(mix["max_classes"]))
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

    def counts(self) -> Dict:
        passes = len(self.durations)
        return {"passes": passes, "routes": len(self.table["lengths"]),
                "tokens_real": sum(s.real_tokens for s in self.plan),
                "tokens_padded": sum(s.padded_tokens for s in self.plan),
                "steps": len(self.plan),
                "flops": passes * counts_falcon.pass_flops(
                    self.cfg, self.table["lengths"]),
                "window_s": self.elapsed}

    # ── the comparison ──────────────────────────────────────────────

    def program_routes(self) -> List[Dict]:
        s, out = self.scores, []
        taps = {k: np.asarray(v) for k, v in s.taps.items()}
        next_logit, lse = np.asarray(s.next_logit), np.asarray(s.lse)
        loglik, rows = np.asarray(s.loglik), np.asarray(s.rows)
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append({"next_logit": next_logit[r, :n], "lse": lse[r, :n],
                        "loglik": float(loglik[r]), "rows": rows[r],
                        "n_keys": list(taps["n_keys"][:, r, :n]),
                        "first_key": list(taps["first_key"][:, r, :n]),
                        "state": list(taps["state"][:, r])})
        return out

    def reference(self, precision: str = "") -> List[Dict]:
        blocks = Blocks(**self.mix["reference_blocks"])
        out = []
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append(falcon_h1_ref.forward(
                self.params, self.cfg, self.table["ids"][r, :n],
                list(self.table["rows_at"][r]), blocks=blocks,
                precision=precision or None))
        return out

    def gaps(self, got: List[Dict], want: List[Dict]) -> Dict[str, float]:
        return gaps(got, want)

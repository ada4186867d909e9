"""Closed-loop passes over a resident table of route histories, scored
by the second route-sequence model (``RouteLMSala``, configuration
``minicpm-sala-l9-16``) through the same table-scoring entry as
``route_scan.py`` drives: the window, the warm-up and the end-to-end
metric are that driver's; this one builds the other model and compares
with the other reference.

``correct``: after the window the plain float32 reference
(``benchmark/reference/sala_ref.py``) recomputes every route of the
last timed pass, one route at a time, and what that pass wrote is
compared with it: the four gaps of ``route_scan.Gaps.worst`` (next-arc
logits, log-sum-exps, named rows, log-likelihood), ``block_set_gap``
(the share of the reference's chosen blocks, at the named rows of the
sparse layers, that the program did not choose), ``key_set_gap`` (the
share of (sparse layer, token, key-value head) whose number of keys
seen, or whose number of visible compressed keys, differs: exactly 0)
and ``state_gap`` (each linear layer's state at the route's last token,
relative, the worst layer and route).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark import counts_sala, seeds, traffic_seq
from benchmark.drivers import route_scan
from benchmark.reference import sala_ref

ANNOTATIONS = route_scan.ANNOTATIONS
rel_gap = route_scan.rel_gap


def gaps(got: List[Dict], want: List[Dict]) -> Dict[str, float]:
    """The compared numbers over the table's routes."""
    worst = dict(route_scan.Gaps().worst, state_gap=0.0)
    blocks, key_sets = [0.0, 0.0], [0.0, 0.0]     # missed | differing, all
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
        for b_g, b_w in zip(g["blocks"], w["blocks"]):
            b_g, b_w = np.asarray(b_g, bool), np.asarray(b_w, bool)
            blocks[0] += float((b_w & ~b_g).sum())
            blocks[1] += float(b_w.sum())
        for n_g, n_w, v_g, v_w in zip(g["n_keys"], w["n_keys"],
                                      g["n_visible"], w["n_visible"]):
            differ = (np.asarray(n_g) != np.asarray(n_w)) | (
                np.asarray(v_g) != np.asarray(v_w))[:, None]
            key_sets[0] += float(differ.sum())
            key_sets[1] += float(differ.size)
    out = dict(worst)
    out["block_set_gap"] = blocks[0] / max(blocks[1], 1.0)
    out["key_set_gap"] = key_sets[0] / max(key_sets[1], 1.0)
    return out


class Driver(route_scan.Driver):
    def __init__(self, run) -> None:
        # the program's entry first: a commit without it fails here,
        # before anything is built
        from routest_tpu.models.route_lm_sala import RouteLMSala
        from routest_tpu.serve.seq_score import RouteScorer

        import jax
        import jax.numpy as jnp

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        if traffic_seq.route_lengths(mix) != list(mix["lengths"]):
            raise ValueError("the mix's lengths are not its quantiles")
        model = RouteLMSala.from_config(cfg)
        for name in ("param_dtype", "compute_dtype", "output_dtype"):
            if np.dtype(getattr(model.policy, name)).name != cfg[name]:
                raise ValueError(f"the model's {name} is not the "
                                 f"configuration's")
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

    def counts(self) -> Dict:
        import jax.numpy as jnp

        passes = len(self.durations)
        n_keys = self.scores.taps["n_keys"]              # (n_sp, R, W, G)
        real = (jnp.arange(n_keys.shape[2])[None, :]
                < self.lengths[:, None])[None, :, :, None]
        chosen = float(jnp.sum(jnp.where(real, n_keys, 0)
                               .astype(jnp.float32)))
        return {"passes": passes, "routes": len(self.table["lengths"]),
                "tokens_real": sum(s.real_tokens for s in self.plan),
                "tokens_padded": sum(s.padded_tokens for s in self.plan),
                "steps": len(self.plan), "chosen_keys": chosen,
                "flops": passes * counts_sala.pass_flops(
                    self.cfg, self.table["lengths"], chosen),
                "window_s": self.elapsed}

    # ── the comparison ──────────────────────────────────────────────

    def program_routes(self) -> List[Dict]:
        s, out = self.scores, []
        taps = {k: np.asarray(v) for k, v in s.taps.items()}
        next_logit, lse = np.asarray(s.next_logit), np.asarray(s.lse)
        loglik, rows = np.asarray(s.loglik), np.asarray(s.rows)
        block = self.cfg["sparse"]["block_size"]
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append({"next_logit": next_logit[r, :n], "lse": lse[r, :n],
                        "loglik": float(loglik[r]), "rows": rows[r],
                        "n_keys": list(taps["n_keys"][:, r, :n]),
                        "n_visible": list(taps["n_visible"][:, r, :n]),
                        "blocks": list(
                            taps["blocks"][:, r, :, :, :-(-n // block)]),
                        "state": list(taps["state"][:, r])})
        return out

    def reference(self, precision: str = "") -> List[Dict]:
        blocks = sala_ref.Blocks(**self.mix["reference_blocks"])
        out = []
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append(sala_ref.forward(
                self.params, self.cfg, self.table["ids"][r, :n],
                list(self.table["rows_at"][r]), blocks=blocks,
                precision=precision or None))
        return out

    def gaps(self, got: List[Dict], want: List[Dict]) -> Dict[str, float]:
        return gaps(got, want)

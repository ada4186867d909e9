"""Closed-loop passes over a resident table of route histories, scored
by the third route-sequence model (``RouteLMKExaone``, configuration
``k-exaone-236b-ep8``) through the same table-scoring entry as
``route_scan.py`` drives: the window, the warm-up and the end-to-end
metric are that driver's; this one builds the other model, reads its
second likelihood column and compares with the other reference.

``correct``: after the window the plain float32 reference
(``benchmark/reference/kexaone_ref.py``) recomputes every route of the
last timed pass, one route at a time, and what that pass wrote is
compared with it: the four gaps of ``route_scan.Gaps.worst`` (next-arc
logits, log-sum-exps, named rows, log-likelihood); ``mtp_logit_gap``,
``mtp_lse_gap`` and ``mtp_loglik_gap`` (the prediction module's column
over a route's n - 1 positions, the same forms); ``expert_gap`` (the
share of the (token, slot) choices of the expert blocks, the module's
among them, that the reference did not make) and ``key_set_gap`` (the
share of (block, token) whose number of keys seen or, in a sliding
layer, whose first key differs: exactly 0).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark import counts_kexaone, seeds, traffic_seq
from benchmark.drivers import route_scan
from benchmark.reference import kexaone_ref
from benchmark.reference.dots3_ref import Blocks

ANNOTATIONS = route_scan.ANNOTATIONS
rel_gap = route_scan.rel_gap


def gaps(got: List[Dict], want: List[Dict], sliding: List[int]) -> Dict:
    """The compared numbers over the table's routes; ``sliding``: the
    rows of ``n_keys`` / ``first_key`` that are sliding layers."""
    first = route_scan.Gaps()
    worst = {"mtp_logit_gap": 0.0, "mtp_lse_gap": 0.0, "mtp_loglik_gap": 0.0}
    for g, w in zip(got, want):
        first.add(dict(g, selected=[]), dict(w, selected=[]), sliding)
        for name, key in (("mtp_logit_gap", "mtp_next_logit"),
                          ("mtp_lse_gap", "mtp_lse")):
            worst[name] = max(worst[name], rel_gap(g[key], w[key]))
        ll_g, ll_w = float(g["mtp_loglik"]), float(w["mtp_loglik"])
        worst["mtp_loglik_gap"] = max(
            worst["mtp_loglik_gap"],
            abs(ll_g - ll_w) / max(abs(ll_w), 1e-30)
            if np.isfinite(ll_g) else float("inf"))
    out = first.result()
    del out["selected_gap"]             # this model selects nothing
    return dict(out, **worst)


class Driver(route_scan.Driver):
    def __init__(self, run) -> None:
        # the program's entry first: a commit without it fails here,
        # before anything is built
        from routest_tpu.models.route_lm_kexaone import RouteLMKExaone
        from routest_tpu.serve.seq_score import RouteScorer

        import jax
        import jax.numpy as jnp

        cfg, mix = run.config, run.mix
        self.cfg, self.mix = cfg, mix
        if traffic_seq.route_lengths(mix) != list(mix["lengths"]):
            raise ValueError("the mix's lengths are not its quantiles")
        model = RouteLMKExaone.from_config(cfg)
        for name in ("param_dtype", "compute_dtype", "output_dtype"):
            if np.dtype(getattr(model.policy, name)).name != cfg[name]:
                raise ValueError(f"the model's {name} is not the "
                                 f"configuration's")
        if not model.mtp_held:
            raise ValueError("the cell compares the prediction module's "
                             "column; the configuration holds none")
        self.share = (model.experts_first, model.experts_held)
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
        """The base driver's, with the module's positions: its block is
        the last row of ``chosen`` and holds a route's n - 1 tokens."""
        import jax.numpy as jnp

        passes = len(self.durations)
        first, count = self.share
        chosen = self.scores.taps["chosen"]         # (blocks, R, W, k)
        at = jnp.arange(chosen.shape[2])[None, None, :]
        short = (jnp.arange(chosen.shape[0]) == chosen.shape[0] - 1)
        real = (at < (self.lengths[None, :, None]
                      - short[:, None, None]))[..., None]
        held = float(jnp.sum(real & (chosen >= first)
                             & (chosen < first + count)))
        lengths = self.table["lengths"]
        return {"passes": passes, "routes": len(lengths),
                "tokens_real": sum(s.real_tokens for s in self.plan),
                "tokens_padded": sum(s.padded_tokens for s in self.plan),
                "steps": len(self.plan), "held_assignments": held,
                "mtp_positions": int(np.maximum(lengths - 2, 0).sum()),
                "flops": passes * counts_kexaone.pass_flops(
                    self.cfg, lengths, held),
                "window_s": self.elapsed}

    # ── the comparison ──────────────────────────────────────────────

    def program_routes(self) -> List[Dict]:
        """What the last pass wrote, route by route, in the form of the
        reference's answers: the module's rows cut to its n - 1
        positions."""
        s, out = self.scores, []
        taps = {k: np.asarray(v) for k, v in s.taps.items()}
        next_logit, lse = np.asarray(s.next_logit), np.asarray(s.lse)
        loglik, rows = np.asarray(s.loglik), np.asarray(s.rows)

        def by_block(tap, r, n):
            return ([tap[i, r, :n] for i in range(len(tap) - 1)]
                    + [tap[-1, r, :n - 1]])

        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append({
                "next_logit": next_logit[r, :n], "lse": lse[r, :n],
                "loglik": float(loglik[r]), "rows": rows[r],
                "mtp_next_logit": taps["mtp_next_logit"][0, r, :n - 1],
                "mtp_lse": taps["mtp_lse"][0, r, :n - 1],
                "mtp_loglik": float(taps["mtp_loglik"][0, r]),
                "chosen": by_block(taps["chosen"], r, n),
                "n_keys": by_block(taps["n_keys"], r, n),
                "first_key": by_block(taps["first_key"], r, n)})
        return out

    def reference(self, precision: str = "") -> List[Dict]:
        blocks = Blocks(**self.mix["reference_blocks"])
        out = []
        for r, n in enumerate(int(v) for v in self.table["lengths"]):
            out.append(kexaone_ref.forward(
                self.params, self.cfg, self.table["ids"][r, :n], self.share,
                list(self.table["rows_at"][r]), blocks=blocks,
                precision=precision or None))
        return out

    def gaps(self, got: List[Dict], want: List[Dict]) -> Dict[str, float]:
        sliding = [l for l, (a, _) in enumerate(
            kexaone_ref.layer_kinds(self.cfg))
            if a == kexaone_ref.SLIDING]
        return gaps(got, want, sliding)

"""Scoring a resident table of route histories with the route-sequence
language model: the table-scoring entry that takes and returns device
arrays.

A caller holds ``ids`` (R, L_max) and ``lengths`` (R,) on the device
and asks for every route's next-arc logits, log-sum-exps and
log-likelihood, and for whole logit rows at the positions it names.
:class:`RouteScorer` plans the pass from the lengths alone
(:func:`plan_pass`): routes are grouped into a **length ladder** — a
few padded lengths, chosen from the lengths given so that the fewest
padded tokens are computed — and each class is cut into device steps of
at most ``max_step_tokens`` padded tokens. A step is one jitted program
(gather the step's routes out of the table, the model, scatter the
results into the result tables, which are donated), so a pass compiles
one program per class. Steps are dispatched without waiting; a pass
ends in one sync.

Spans: ``seq.score_pass`` (root) with one ``seq.step`` child per step
(the host's dispatch of it; attrs ``length_class``, ``routes``,
``real_tokens``, ``padded_tokens``, ``attention``: the online-softmax
step its full layers run, ``fused`` or ``xla``) and ``seq.wait`` (the
sync). As every recorded span they are ``TraceAnnotation``s too.
Counters: ``rtpu_seq_tokens_total{kind=real|padded}`` and
``rtpu_seq_attention_chunks_total{path=fused|xla}`` (the full layers'
steps over chunks of keys) from the plan; and, read
from the device once a pass after its sync, ``rtpu_seq_expert_tokens
{stat=max|mean}`` (tokens per held expert per step and layer),
``rtpu_seq_expert_load_max_over_mean``, ``rtpu_seq_held_assignment_
share`` (the share of a step's k·T assignments that land on held
experts) and ``rtpu_seq_selected_keys_per_query``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

_metrics = None


def _seq_metrics():
    global _metrics
    if _metrics is None:
        from routest_tpu.obs import get_registry

        reg = get_registry()
        _metrics = {
            "tokens": reg.counter(
                "rtpu_seq_tokens_total",
                "Tokens of scored routes (real) and of the padding "
                "computed beside them (padded).", ("kind",)),
            "chunks": reg.counter(
                "rtpu_seq_attention_chunks_total",
                "Online-softmax steps over chunks of keys that the full "
                "layers of the dispatched steps ran, by the form of the "
                "step (fused: the Pallas kernel; xla).", ("path",)),
            "expert_tokens": reg.gauge(
                "rtpu_seq_expert_tokens",
                "Tokens a held expert got in one step of one expert "
                "layer: the last pass's max and mean.", ("stat",)),
            "load": reg.gauge(
                "rtpu_seq_expert_load_max_over_mean",
                "Fullest held expert over the mean one, averaged over "
                "the last pass's steps and expert layers."),
            "held_share": reg.gauge(
                "rtpu_seq_held_assignment_share",
                "Share of the last pass's token-expert assignments that "
                "landed on held experts."),
            "selected": reg.gauge(
                "rtpu_seq_selected_keys_per_query",
                "Mean keys a query of a selecting layer saw, last pass."),
        }
    return _metrics


class Step(NamedTuple):
    length: int                 # the class: padded length of its routes
    routes: np.ndarray          # (B,) table rows; -1 = an empty slot
    real_tokens: int

    @property
    def padded_tokens(self) -> int:
        return self.length * len(self.routes) - self.real_tokens


def length_ladder(lengths: Sequence[int], quantum: int,
                  max_classes: int) -> List[int]:
    """At most ``max_classes`` padded lengths, multiples of ``quantum``,
    such that padding every route to the least class that holds it
    computes the fewest padded tokens (a dynamic programme over the
    sorted lengths)."""
    need = sorted({-(-int(n) // quantum) * quantum for n in lengths})
    count = {c: 0 for c in need}
    for n in lengths:
        count[-(-int(n) // quantum) * quantum] += 1
    n_c = len(need)
    if n_c <= max_classes:
        return need
    # best[j][k]: least padded tokens for the first j candidates in k
    # classes, the last class being need[j - 1]
    inf = float("inf")
    best = [[inf] * (max_classes + 1) for _ in range(n_c + 1)]
    back = [[0] * (max_classes + 1) for _ in range(n_c + 1)]
    best[0][0] = 0.0
    for j in range(1, n_c + 1):
        for k in range(1, max_classes + 1):
            for i in range(j):
                cost = best[i][k - 1] + sum(
                    (need[j - 1] - need[m]) * count[need[m]]
                    for m in range(i, j))
                if cost < best[j][k]:
                    best[j][k], back[j][k] = cost, i
    k = min(range(1, max_classes + 1), key=lambda q: best[n_c][q])
    ladder, j = [], n_c
    while j > 0:
        ladder.append(need[j - 1])
        j, k = back[j][k], k - 1
    return sorted(ladder)


def plan_pass(lengths: Sequence[int], quantum: int, max_step_tokens: int,
              max_classes: int) -> List[Step]:
    """The steps of one pass over routes of these lengths, longest class
    first. A class's routes are spread evenly over its steps; a last
    step that has a slot too many holds an empty route there."""
    lengths = np.asarray(lengths, np.int64)
    ladder = length_ladder(lengths, quantum, max_classes)
    klass = np.searchsorted(ladder, lengths)
    steps = []
    for c in reversed(range(len(ladder))):
        rows = np.flatnonzero(klass == c)
        if not len(rows):
            continue
        per_step = max(1, max_step_tokens // ladder[c])
        n_steps = -(-len(rows) // per_step)
        width = -(-len(rows) // n_steps)
        for s in range(n_steps):
            mine = rows[s * width:(s + 1) * width]
            slots = np.full((width,), -1, np.int64)
            slots[:len(mine)] = mine
            steps.append(Step(int(ladder[c]), slots,
                              int(lengths[mine].sum())))
    return steps


class SeqScores(NamedTuple):
    """Device arrays over the table's rows: ``next_logit`` and ``lse``
    (R, L_max) float32, ``loglik`` (R,), ``rows`` (R, P, vocab_held) the
    logit rows at the named positions, and ``taps``: what the model's
    ``apply`` reports beside them, laid out over the table the same
    way."""
    next_logit: object
    lse: object
    loglik: object
    rows: object
    taps: Dict


class RouteScorer:
    def __init__(self, model, params, max_step_tokens: int = 32768,
                 max_classes: int = 8) -> None:
        import jax

        self.model = model
        self.params = jax.device_put(params)
        self.max_step_tokens = int(max_step_tokens)
        self.max_classes = int(max_classes)
        self.quantum = int(np.lcm(model.select_block, model.window_block))
        self._step = jax.jit(self._run_step, static_argnums=(6,),
                             donate_argnums=(5,))

    @classmethod
    def from_artifact(cls, path: str, expect_share: Optional[Dict] = None,
                      **kw) -> "RouteScorer":
        from routest_tpu.train.checkpoint import load_route_lm

        model, params = load_route_lm(path, expect_share)
        return cls(model, params, **kw)

    def plan(self, lengths) -> List[Step]:
        """The pass's steps from the routes' lengths (one small read
        where they live on the device)."""
        return plan_pass(np.asarray(lengths), self.quantum,
                         self.max_step_tokens, self.max_classes)

    # ── one step, one program ───────────────────────────────────────

    def _run_step(self, params, ids, lengths, rows_at, routes, tables,
                  length: int):
        import jax.numpy as jnp

        n_rows, width = ids.shape
        live = routes >= 0
        src = jnp.where(live, routes, 0)
        take = min(length, width)
        step_ids = jnp.pad(ids[src, :take], ((0, 0), (0, length - take)))
        step_len = jnp.where(live, lengths[src], 0)
        at = jnp.minimum(rows_at[src], length - 1)
        out = self.model.apply(params, step_ids, step_len, at)
        dst = jnp.where(live, routes, n_rows)          # dropped
        new = dict(tables)
        for name in ("next_logit", "lse"):
            new[name] = tables[name].at[dst, :take].set(
                out[name][:, :take], mode="drop")
        for name in ("n_keys", "first_key", "chosen"):    # layers first
            if name in out:
                new[name] = tables[name].at[:, dst, :take].set(
                    out[name][:, :, :take], mode="drop")
        if "selected" in out:
            new["selected"] = tables["selected"].at[:, dst, :, :take].set(
                out["selected"][..., :take], mode="drop")
        new["loglik"] = tables["loglik"].at[dst].set(out["loglik"],
                                                     mode="drop")
        new["rows"] = tables["rows"].at[dst].set(out["rows"], mode="drop")
        stats = {}
        if "counts" in out:
            stats["counts"] = out["counts"]
        if "selected" in out:        # the model has selecting layers
            kinds = self.model.layer_kinds()
            full = [l for l, (a, _) in enumerate(kinds)
                    if a == "full_attention"]
            real = (jnp.arange(length)[None, :] < step_len[:, None])
            stats["selected_keys"] = jnp.sum(
                jnp.where(real[None], out["n_keys"][jnp.asarray(full)], 0))
        return new, stats

    def _empty_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        import jax.numpy as jnp

        m = self.model
        kinds = m.layer_kinds()
        n_moe = sum(1 for _, f in kinds if f == "moe")
        n_full = sum(1 for a, _ in kinds if a == "full_attention")
        k = int(m.sizes["num_experts_per_tok"])
        t = {"next_logit": jnp.zeros((n_rows, width), jnp.float32),
             "lse": jnp.zeros((n_rows, width), jnp.float32),
             "loglik": jnp.zeros((n_rows,), jnp.float32),
             "rows": jnp.zeros((n_rows, n_named, m.vocab_held), jnp.float32),
             "n_keys": jnp.zeros((len(kinds), n_rows, width), jnp.int32),
             "first_key": jnp.zeros((len(kinds), n_rows, width), jnp.int32)}
        if n_moe:
            t["chosen"] = jnp.zeros((n_moe, n_rows, width, k), jnp.int32)
        if n_full:
            t["selected"] = jnp.zeros((n_full, n_rows, n_named, width), bool)
        return t

    # ── a pass ──────────────────────────────────────────────────────

    def score(self, ids, lengths, rows_at,
              plan: Optional[List[Step]] = None) -> SeqScores:
        """Score every route of the table once. ``ids`` (R, L_max) int32,
        ``lengths`` (R,), ``rows_at`` (R, P) int32: device arrays.
        ``plan`` is ``self.plan(lengths)`` where the caller kept it."""
        import jax
        import jax.numpy as jnp

        from routest_tpu.obs import trace_span

        plan = plan if plan is not None else self.plan(lengths)
        real = sum(s.real_tokens for s in plan)
        padded = sum(s.padded_tokens for s in plan)
        with trace_span("seq.score_pass", routes=int(ids.shape[0]),
                        steps=len(plan), real_tokens=real,
                        padded_tokens=padded):
            tables = self._empty_tables(ids.shape[0], ids.shape[1],
                                        rows_at.shape[1])
            stats = []
            for step in plan:
                with trace_span("seq.step", length_class=step.length,
                                routes=int((step.routes >= 0).sum()),
                                real_tokens=step.real_tokens,
                                padded_tokens=step.padded_tokens,
                                attention=self.model.selected_steps(
                                    step.length)[0]):
                    tables, st = self._step(
                        self.params, ids, lengths, rows_at,
                        jnp.asarray(step.routes, jnp.int32), tables,
                        step.length)
                    stats.append(st)
            with trace_span("seq.wait"):
                jax.block_until_ready(tables)
            self._count(plan, jax.device_get(stats), real, padded)
        taps = {k: tables[k] for k in ("n_keys", "first_key", "chosen",
                                       "selected") if k in tables}
        return SeqScores(tables["next_logit"], tables["lse"],
                         tables["loglik"], tables["rows"], taps)

    def _count(self, plan, stats, real: int, padded: int) -> None:
        m = _seq_metrics()
        m["tokens"].labels(kind="real").inc(real)
        m["tokens"].labels(kind="padded").inc(padded)
        n_full = sum(1 for a, _ in self.model.layer_kinds()
                     if a == "full_attention")
        for step in plan:
            path, chunks = self.model.selected_steps(step.length)
            m["chunks"].labels(path=path).inc(
                chunks * len(step.routes) * n_full)
        counts = [np.asarray(s["counts"], np.float64) for s in stats
                  if "counts" in s]
        if counts:
            per_layer = np.concatenate(counts, 0)       # (steps·layers, E)
            means = per_layer.mean(1)
            busy = means > 0
            m["expert_tokens"].labels(stat="max").set(per_layer.max())
            m["expert_tokens"].labels(stat="mean").set(per_layer.mean())
            if busy.any():
                m["load"].set(float(np.mean(
                    per_layer[busy].max(1) / means[busy])))
            k = int(self.model.sizes["num_experts_per_tok"])
            n_moe = counts[0].shape[0]
            m["held_share"].set(per_layer.sum() / max(1, k * real * n_moe))
        picked = [float(s["selected_keys"]) for s in stats
                  if "selected_keys" in s]
        if picked:
            m["selected"].set(sum(picked) / max(1, real * n_full))

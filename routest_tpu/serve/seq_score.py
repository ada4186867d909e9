"""Scoring a resident table of route histories with a route-sequence
language model: the table-scoring entry that takes and returns device
arrays. One scorer for every such model (``models/route_lm.RouteLM``,
the ``dots3-note-prev`` architecture, ``models/route_lm_sala
.RouteLMSala``, the ``MiniCPM-SALA`` one, ``models/route_lm_kexaone
.RouteLMKExaone``, the ``K-EXAONE-236B-A23B`` one, and
``models/route_lm_gigachat.RouteLMGigaChat``, the
``GigaChat3.1-702B-A36B`` one — these two have a prediction module
that gives a second likelihood column, the arc after next, as taps
``mtp_next_logit``, ``mtp_lse`` and ``mtp_loglik`` — and
``models/route_lm_falcon_h1.RouteLMFalconH1``, the
``Falcon-H1-34B-Instruct`` one).

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

**What the scorer asks of a model** (nothing else is read off it):

- ``apply(params, ids (B, L), lengths (B,), rows_at (B, P))`` → a dict
  with ``next_logit`` and ``lse`` (B, L) float32, ``loglik`` (B,),
  ``rows`` (B, P, vocab_held) and the model's own taps; a route's
  outputs depend on nothing but its own tokens;
- ``vocab_held``; ``length_quantum``: padded lengths are its multiples;
- ``tap_tables(n_rows, width, n_named)`` → for each tap of ``apply``
  that is kept over the table: (shape, dtype, axis, unit). The table's
  axis 1 is the route (``apply``'s too); ``axis`` is the one that
  follows the padded length, an entry of it ``unit`` tokens (``None``:
  no such axis: a value a route, as a linear layer's last state or a
  prediction module's log-likelihood);
- ``step_attrs(length)`` → attributes of the ``seq.step`` span (which
  path each mixer runs at this padded length);
- ``step_stats(out, lengths)`` → small device values of one step, and
  ``pass_counts(steps, stats, real_tokens)`` → [(family, labels,
  value)] for the families of :func:`_seq_metrics`, from the plan and
  from the steps' stats, which are fetched once a pass after its sync.
  The stats are also what a step's device time is read from: a step's
  program hands them back when it ends, the device runs the queued
  steps in order, so the host waits on them one step after another
  once all are dispatched. A step whose ``step_stats`` is empty has
  nothing to wait on and is timed with the next step that has (a tail
  of such steps with the pass's result tables): one span for the
  group, its tokens and routes summed, under the class of its first
  step.

Spans: ``seq.score_pass`` (root) with one ``seq.step`` child per step
(the host's dispatch of it; attrs ``length_class``, ``routes``,
``real_tokens``, ``padded_tokens``, ``mixers`` and, for ``RouteLM``,
``attention`` and ``window``: the online-softmax step its full layers
run and the window step its sliding layers run, each ``fused`` or
``xla``; for a model with expert layers ``experts``: the form of the
held experts' grouped product, ``fused`` or ``xla``; ``compile_ms`` where the dispatch compiled or fetched its
program: ``core/cache.compile_seconds`` grew while the span was open)
and ``seq.wait`` (the sync). Where the pass is recorded, ``seq.wait``
holds one ``seq.wait.step`` a timed step, in dispatch order, with the
step's ``length_class``, ``routes``, ``real_tokens``, ``padded_tokens``
and ``device_ms``: the step's completion less the later of the step
before's completion and its own dispatch, on the host's
``perf_counter``; each is reported once to the goodput ledger
(``obs/efficiency.py``) as program ``seq_score``, bucket = the class,
rows = tokens (real against launched), so ``/api/efficiency`` holds
tokens per device-second by length class; a pass that compiled reports
none (the host reached its waits long after the first steps ended, so
its ``device_ms`` are upper bounds). The root then carries
``device_ms``, their sum (what of a pass no step accounts for is the
rest), and the host's account of the pass (``obs/host.py``:
``psi_cpu_ms``, ``steal_ms``, ``nivcsw``, ``gc_ms`` …). A pass that is
not recorded (tracer off, trace unsampled) waits once and reports
none of this. As every recorded span they are
``TraceAnnotation``s too. Counters: ``rtpu_seq_tokens_total{kind=real|
padded}`` from the plan, for every model. ``RouteLM``:
``rtpu_seq_attention_chunks_total{path=fused|xla}`` (the full layers'
steps over chunks of keys), ``rtpu_seq_topk_blocks_total{path=fused|
xla}`` (the full layers' blocks of queries that ran the selection, by
``parallel/select.topk_path`` at a block's scores) and
``rtpu_seq_window_blocks_total{path=fused|xla}`` (the sliding layers'
blocks of queries) from the plan, as
``rtpu_seq_expert_blocks_total{path=fused|xla}`` (expert blocks x
steps, by ``parallel/expert.expert_path`` at the model's widths); and,
read from the device
once a pass after its sync, ``rtpu_seq_expert_tokens{stat=max|mean}``
(tokens per held expert per step and layer), ``rtpu_seq_expert_load_
max_over_mean``, ``rtpu_seq_held_assignment_share`` (the share of a
step's k·T assignments that land on held experts),
``rtpu_seq_expert_rows_total{kind=held|visited}`` (assignments on held
experts, and the rows of the tiles their layout gives them: the rule
``expert.rows_visited`` over those counts, on the host: no kernel's) and
``rtpu_seq_selected_keys_per_query``. ``RouteLMSala``:
``rtpu_seq_sparse_keys_total{kind=chosen|visited}`` (keys in chosen
blocks at or before the query, from the device; keys the second stage
multiplied, from the plan), ``rtpu_seq_sparse_blocks_per_query`` (the
first over the real (query, group) pairs and the block's keys) and
``rtpu_seq_linear_chunks_total`` (steps of the linear mixers' scans).
``RouteLMKExaone``: the expert gauges and counters as ``RouteLM`` (the
module's block among the expert layers), ``rtpu_seq_gqa_keys_total{layer=window|
full, kind=needed|visited}`` (needed: the keys each real query saw,
from the device; visited: the keys of the blocks and chunks the
dispatched programs multiplied, padding and beyond-the-diagonal parts
included, from the plan) and ``rtpu_seq_mtp_positions_total`` (positions
that got a likelihood term for the arc after next). ``seq.step`` carries
``mtp`` (1 where the module ran) beside ``mixers``.
``RouteLMGigaChat``: the expert gauges and counters and
``rtpu_seq_mtp_positions_total`` as ``RouteLMKExaone``,
``rtpu_seq_latent_keys_total{kind=needed|visited}`` (its dense causal
latent-attention blocks, the module's among them: ``t + 1`` keys a real
query, from the device; the pairs the kernel's grid multiplies, from
the plan), ``rtpu_seq_latent_tiles_total{kind=interior|diagonal}`` (its
grid steps, from the same tables) and ``rtpu_seq_expert_group_tokens_total{
kind=held_group|all}`` (real tokens of the expert blocks one of whose
chosen experts lies in the held experts' routing group, from the
device's ``chosen`` taps; all of them). ``seq.step`` carries ``mixers``
(``latent=fused`` or ``latent=xla``: the form of the dense softmax,
``parallel/latent.latent_path``), ``mtp``, ``experts`` and ``groups``
(the router's ``n_group/topk_group``, ``8/4``).
``RouteLMFalconH1``: ``rtpu_seq_gqa_keys_total{layer=full, kind=needed|
visited}`` as ``RouteLMKExaone``'s full layers (its attention, one a
block) and ``rtpu_seq_ssm_chunks_total{path=fused|xla}`` (routes x
chunks x state-space blocks of the dispatched steps, by the form
``parallel/ssd.ssd_path`` names at the step's shapes, from the plan).
``seq.step`` carries ``mixers`` (``ssm=fused,attn=xla`` or
``ssm=xla,attn=xla``); the taps ``state`` are each state-space block's
state at a route's last real token.
"""

from __future__ import annotations

import time
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
            "topk_blocks": reg.counter(
                "rtpu_seq_topk_blocks_total",
                "Blocks of queries whose selection the full layers of the "
                "dispatched steps ran, by the form of the radix top-k "
                "(fused: the Pallas kernel; xla).", ("path",)),
            "window_blocks": reg.counter(
                "rtpu_seq_window_blocks_total",
                "Blocks of queries that the sliding layers of the "
                "dispatched steps ran, by the form of the window step "
                "(fused: the Pallas kernel; xla).", ("path",)),
            "expert_blocks": reg.counter(
                "rtpu_seq_expert_blocks_total",
                "Expert blocks that the dispatched steps ran, by the "
                "form of the held experts' grouped product (fused: the "
                "Pallas kernels; xla: ragged_dot).", ("path",)),
            "expert_rows": reg.counter(
                "rtpu_seq_expert_rows_total",
                "Rows of the held experts' grouped product: assignments "
                "on held experts (held), and rows of the tiles its layout "
                "gives them by rule, an expert's last tile's padding "
                "included (visited).", ("kind",)),
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
            "sparse_keys": reg.counter(
                "rtpu_seq_sparse_keys_total",
                "Keys of the block-selecting layers: in chosen blocks at "
                "or before the query (chosen), and multiplied by the "
                "second stage, masked or not (visited).", ("kind",)),
            "sparse_blocks": reg.gauge(
                "rtpu_seq_sparse_blocks_per_query",
                "Chosen keys a (query, group) of a block-selecting layer "
                "saw, in blocks, last pass."),
            "linear_chunks": reg.counter(
                "rtpu_seq_linear_chunks_total",
                "Steps of the linear mixers' chunked scans that the "
                "dispatched steps ran."),
            "gqa_keys": reg.counter(
                "rtpu_seq_gqa_keys_total",
                "Keys of the plain grouped-query layers, window or full: "
                "seen by real queries (needed), and multiplied by the "
                "dispatched programs, masked or padded or not "
                "(visited).", ("layer", "kind")),
            "mtp_positions": reg.counter(
                "rtpu_seq_mtp_positions_total",
                "Positions whose arc after next a prediction module "
                "scored."),
            "latent_keys": reg.counter(
                "rtpu_seq_latent_keys_total",
                "Keys of the dense causal latent-attention blocks: seen "
                "by real queries (needed), and multiplied by the "
                "dispatched programs, masked or padded or not "
                "(visited).", ("kind",)),
            "latent_tiles": reg.counter(
                "rtpu_seq_latent_tiles_total",
                "Grid steps of the dense causal latent-attention kernel "
                "at the dispatched steps' shapes, a group of heads each: "
                "whole tiles of keys, unmasked (interior), and a block "
                "of queries' last tile, masked (diagonal).", ("kind",)),
            "expert_group_tokens": reg.counter(
                "rtpu_seq_expert_group_tokens_total",
                "Real tokens of the expert blocks under group-limited "
                "routing: those one of whose chosen experts lies in the "
                "held experts' routing group (held_group), and all of "
                "them (all).", ("kind",)),
            "ssm_chunks": reg.counter(
                "rtpu_seq_ssm_chunks_total",
                "Chunk steps of the state-space scans that the dispatched "
                "steps ran (routes x chunks x state-space blocks), by the "
                "form of the scan (fused: the Pallas kernel; xla).",
                ("path",)),
        }
    return _metrics


_COUNTERS = ("tokens", "chunks", "topk_blocks", "window_blocks",
             "expert_blocks", "expert_rows", "sparse_keys", "linear_chunks",
             "gqa_keys", "mtp_positions", "latent_keys", "latent_tiles",
             "expert_group_tokens", "ssm_chunks")


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


def _wait_steps(steps, stats, tables, report: bool) -> float:
    """Wait on the dispatched steps one after another, in the order the
    device runs them; one ``seq.wait.step`` span and, with ``report``,
    one goodput-ledger record a timed step (the module's text says
    which steps are timed together). ``steps``: (span attributes, host
    time of dispatch) a step. Returns the summed device milliseconds."""
    import jax

    from routest_tpu.obs import trace_span
    from routest_tpu.obs.efficiency import get_ledger

    total, done, group = 0.0, None, []
    for i, (step, st) in enumerate(zip(steps, stats)):
        group.append(step)
        if not jax.tree_util.tree_leaves(st):
            if i < len(stats) - 1:
                continue
            st = tables
        attrs = dict(group[0][0])
        for name in ("routes", "real_tokens", "padded_tokens"):
            attrs[name] = sum(a[name] for a, _ in group)
        with trace_span("seq.wait.step", **attrs) as span:
            jax.block_until_ready(st)
            now = time.perf_counter()
            began = group[0][1] if done is None else max(done, group[0][1])
            device_ms = 1e3 * (now - began)
            span.set_attr("device_ms", device_ms)
        if report:
            get_ledger().record(
                "seq_score", real_rows=attrs["real_tokens"],
                padded_rows=attrs["real_tokens"] + attrs["padded_tokens"],
                bucket=attrs["length_class"], compute_s=device_ms / 1e3)
        total, done, group = total + device_ms, now, []
    return total


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
        self.quantum = int(model.length_quantum)
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
        for name, (_, _, axis, unit) in self.model.tap_tables(
                n_rows, width, rows_at.shape[1]).items():
            where = [slice(None)] * out[name].ndim
            if axis is not None:
                where[axis] = slice(0, -(-take // unit))
            value = out[name][tuple(where)]
            where[1] = dst
            new[name] = tables[name].at[tuple(where)].set(value, mode="drop")
        new["loglik"] = tables["loglik"].at[dst].set(out["loglik"],
                                                     mode="drop")
        new["rows"] = tables["rows"].at[dst].set(out["rows"], mode="drop")
        return new, self.model.step_stats(out, step_len)

    def _empty_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        import jax.numpy as jnp

        t = {"next_logit": jnp.zeros((n_rows, width), jnp.float32),
             "lse": jnp.zeros((n_rows, width), jnp.float32),
             "loglik": jnp.zeros((n_rows,), jnp.float32),
             "rows": jnp.zeros((n_rows, n_named, self.model.vocab_held),
                               jnp.float32)}
        for name, (shape, dtype, _, _) in self.model.tap_tables(
                n_rows, width, n_named).items():
            t[name] = jnp.zeros(shape, dtype)
        return t

    # ── a pass ──────────────────────────────────────────────────────

    def score(self, ids, lengths, rows_at,
              plan: Optional[List[Step]] = None) -> SeqScores:
        """Score every route of the table once. ``ids`` (R, L_max) int32,
        ``lengths`` (R,), ``rows_at`` (R, P) int32: device arrays.
        ``plan`` is ``self.plan(lengths)`` where the caller kept it."""
        import jax
        import jax.numpy as jnp

        from routest_tpu.core.cache import compile_seconds
        from routest_tpu.obs import host, trace_span

        plan = plan if plan is not None else self.plan(lengths)
        real = sum(s.real_tokens for s in plan)
        padded = sum(s.padded_tokens for s in plan)
        with trace_span("seq.score_pass", routes=int(ids.shape[0]),
                        steps=len(plan), real_tokens=real,
                        padded_tokens=padded) as root:
            before = host.begin(root)
            tables = self._empty_tables(ids.shape[0], ids.shape[1],
                                        rows_at.shape[1])
            stats, steps = [], []       # steps: (attrs, dispatched at)
            fresh = False               # a step of this pass compiled
            for step in plan:
                attrs = {"length_class": step.length,
                         "routes": int((step.routes >= 0).sum()),
                         "real_tokens": step.real_tokens,
                         "padded_tokens": step.padded_tokens}
                with trace_span("seq.step", **attrs,
                                **self.model.step_attrs(step.length)) as span:
                    compiled = compile_seconds()
                    tables, st = self._step(
                        self.params, ids, lengths, rows_at,
                        jnp.asarray(step.routes, jnp.int32), tables,
                        step.length)
                    stats.append(st)
                    steps.append((attrs, time.perf_counter()))
                    compiled = compile_seconds() - compiled
                    if compiled > 0.0:
                        span.set_attr("compile_ms", 1e3 * compiled)
                        fresh = True
            with trace_span("seq.wait"):
                if root.sampled:
                    root.set_attr("device_ms", _wait_steps(
                        steps, stats, tables, report=not fresh))
                jax.block_until_ready(tables)
            self._count(plan, jax.device_get(stats), real, padded)
            host.end(root, before)
        taps = {k: v for k, v in tables.items()
                if k not in ("next_logit", "lse", "loglik", "rows")}
        return SeqScores(tables["next_logit"], tables["lse"],
                         tables["loglik"], tables["rows"], taps)

    def _count(self, plan, stats, real: int, padded: int) -> None:
        m = _seq_metrics()
        m["tokens"].labels(kind="real").inc(real)
        m["tokens"].labels(kind="padded").inc(padded)
        for family, labels, value in self.model.pass_counts(plan, stats,
                                                            real):
            child = m[family].labels(**labels) if labels else m[family]
            (child.inc if family in _COUNTERS else child.set)(value)

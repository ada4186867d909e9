"""The expert layer: top-k routed experts with a shared one, computed for
the share of the experts that this chip holds — and, for an ``expert``
mesh axis with one expert a device, the all-to-all exchange.

**The share** (:func:`route_top_k`, :func:`grouped_experts`,
:func:`moe_share`). A layer of ``n_experts`` routed experts is divided
over chips; this chip holds the experts ``first .. first + count - 1``
(:class:`ExpertShare`). It routes every token over ALL experts
(sigmoid scores in float32, top-k of score + correction bias — where
the router has groups, within the groups :func:`keep_groups` keeps —,
weights renormalised over the chosen, no capacity and no drops), and
adds, for each token, the terms of the chosen experts it holds and the
shared expert. What the experts held elsewhere would add is left out:
on one chip the layer runs without its exchange, and the parts that all
shares give, with the shared expert counted once, add up to the whole
layer (``tests/test_route_lm_share.py``).

The held experts' product is a grouped one with uneven groups, one
dataflow in three steps (:func:`grouped_experts`). **Sort**: the (token,
slot) assignments that land here are sorted by expert once a layer, and
each expert's rows are laid out from a multiple of the row tile on, so
that a tile of rows belongs to one expert (an expert wastes at most one
part-tile: :func:`rows_visited`). **Grouped product**: the layout is
walked in chunks of a fixed number of rows (a dynamic trip count: no
capacity, so no bound on the held rows but the number of assignments,
and memory of one chunk): a chunk's rows are gathered once, multiplied
by ``w_gate`` and ``w_up`` of the experts that own them with
``silu(gate) * up`` cast to the rows' dtype, then by ``w_down`` with the
assignment's float32 weight on the finished float32 row. Two forms of
the product, chosen by :func:`expert_path` from widths, dtype and
backend alone: ``fused``, two Pallas kernels over row tiles x column
tiles that read each expert's matrices from the ``(count, D, M)`` stacks
by block index (``grouped_expert_product_up`` / ``_down`` in a trace),
and ``xla``, ``jax.lax.ragged_dot`` over the same layout (the CPU,
float32, toy widths; the kernels' oracle). **Combine**: the chunk's rows
are scatter-added into the float32 sum a few hundred at a time, the
sum and the rows held lane by lane (:func:`lane_rows`) until the layer
hands the sum on; a token's held terms are added in expert order
whatever else the step holds.

**The exchange** (:func:`make_moe_apply`, with :func:`moe_apply_dense`
as its oracle): Switch-style top-1 routing with a capacity over an
``expert`` mesh axis, one expert a device. Tokens are sharded over the
axis; each device packs up to ``capacity`` tokens per destination
expert into a fixed (E, C, D) buffer (overflow is dropped and reported),
one ``all_to_all`` brings every expert its tokens, the expert MLP runs,
a second ``all_to_all`` takes the results home. It has run on virtual
CPU devices only; the top-k share above has no exchange yet (ROADMAP
M3).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from routest_tpu.core.smap import shard_map

Params = Dict


def init_moe_params(key: jax.Array, n_experts: int, d_model: int,
                    d_hidden: int) -> Params:
    """Router + stacked expert FFNs (leading axis = expert)."""
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(d_model)
    s2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.normal(kr, (d_model, n_experts)) * s1,
        "w1": jax.random.normal(k1, (n_experts, d_model, d_hidden)) * s1,
        "b1": jnp.zeros((n_experts, d_hidden)),
        "w2": jax.random.normal(k2, (n_experts, d_hidden, d_model)) * s2,
        "b2": jnp.zeros((n_experts, d_model)),
    }


def shard_moe_params(params: Params, mesh: Mesh,
                     expert_axis: str = "expert") -> Params:
    """Experts to their devices; the router is replicated."""
    ex = NamedSharding(mesh, P(expert_axis))
    rep = NamedSharding(mesh, P())
    return {k: jax.device_put(v, rep if k == "router" else ex)
            for k, v in params.items()}


def _expert_ffn(w1, b1, w2, b2, x):
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def moe_apply_dense(params: Params, tokens: jax.Array) -> jax.Array:
    """Single-device oracle: every token through its argmax expert, no
    capacity limit. The EP layer must match this wherever no token
    overflowed."""
    logits = tokens @ params["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    choice = jnp.argmax(logits, axis=-1)                       # (B,)
    outs = jax.vmap(_expert_ffn, in_axes=(0, 0, 0, 0, None))(
        params["w1"], params["b1"], params["w2"], params["b2"], tokens)
    # outs: (E, B, D); pick each token's expert, scale by its gate
    picked = jnp.take_along_axis(
        outs, choice[None, :, None], axis=0)[0]                # (B, D)
    gate = jnp.take_along_axis(gates, choice[:, None], axis=1)
    return picked * gate


def make_moe_apply(mesh: Mesh, expert_axis: str = "expert",
                   capacity_factor: float = 2.0):
    """jitted (params, tokens) → (outputs, aux) with experts sharded over
    ``expert_axis`` and tokens sharded over the same axis.

    ``aux``: dict with ``load_balance_loss`` (scalar) and
    ``dropped_frac`` (scalar fraction of tokens past capacity, whose
    output is zero).
    """
    n_exp = mesh.shape[expert_axis]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"router": P(), "w1": P(expert_axis), "b1": P(expert_axis),
                   "w2": P(expert_axis), "b2": P(expert_axis)},
                  P(expert_axis)),
        out_specs=(P(expert_axis), P()))
    def run(params, tokens):
        b_local, d = tokens.shape
        capacity = max(1, int(capacity_factor * b_local / n_exp))

        logits = tokens @ params["router"]                  # (b, E)
        gates = jax.nn.softmax(logits, axis=-1)
        choice = jnp.argmax(logits, axis=-1)                # (b,)
        gate = jnp.take_along_axis(gates, choice[:, None], axis=1)[:, 0]

        # position of each token within its expert's capacity window
        one_hot = jax.nn.one_hot(choice, n_exp, dtype=jnp.int32)  # (b, E)
        # already zero outside each token's chosen column, so the row sum
        # IS the token's slot index within its expert
        pos_in_expert = (jnp.cumsum(one_hot, axis=0) - 1) * one_hot
        slot = pos_in_expert.sum(axis=1)                    # (b,)
        keep = slot < capacity                              # overflow drops

        # pack: dispatch[e, c] = token routed to expert e at slot c
        dispatch = jnp.zeros((n_exp, capacity, d), tokens.dtype)
        src = jnp.where(keep, choice, 0)
        slot_c = jnp.clip(slot, 0, capacity - 1)
        dispatch = dispatch.at[src, slot_c].add(
            tokens * keep[:, None].astype(tokens.dtype))

        # (dest_expert, C, D) → every device receives its expert's slab
        # from all source devices: (n_source, C, D)
        arriving = jax.lax.all_to_all(dispatch, expert_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        local = jax.tree_util.tree_map(lambda a: a[0], (
            params["w1"], params["b1"], params["w2"], params["b2"]))
        out = _expert_ffn(*local, arriving.reshape(-1, d))
        out = out.reshape(n_exp, capacity, d)
        # route results back to the tokens' home devices
        returned = jax.lax.all_to_all(out, expert_axis, split_axis=0,
                                      concat_axis=0, tiled=True)

        # unpack: token i's output sits at returned[choice[i], slot[i]]
        gathered = returned[src, slot_c]                    # (b, D)
        y = gathered * (gate * keep.astype(gate.dtype))[:, None]

        # Switch load-balance loss: E · Σ_e (frac tokens to e)(mean prob e),
        # psum'd so every shard reports the GLOBAL value.
        frac = one_hot.astype(jnp.float32).mean(axis=0)
        prob = gates.mean(axis=0)
        lbl = n_exp * jnp.sum(
            jax.lax.pmean(frac, expert_axis)
            * jax.lax.pmean(prob, expert_axis))
        dropped = jax.lax.pmean(1.0 - keep.mean(), expert_axis)
        return y, {"load_balance_loss": lbl, "dropped_frac": dropped}

    return jax.jit(run)


# ── the share of a top-k layer that one chip holds ───────────────────


class ExpertShare(NamedTuple):
    """The experts ``first .. first + count - 1`` of ``n_experts``."""
    n_experts: int
    first: int
    count: int


def keep_groups(score: jax.Array, n_group: int, topk_group: int):
    """Group-limited routing's cut: the experts in ``n_group`` groups of
    consecutive ones, a group scored by the sum of its two largest
    ``score``s, the ``topk_group`` best groups kept (ties to the lower
    group) and the scores of the rest set to 0. ``score`` (T, E)."""
    t, n = score.shape
    grouped = score.reshape(t, n_group, n // n_group)
    best_two = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, kept = jax.lax.top_k(best_two, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], 1)
    return jnp.where(keep[:, :, None], grouped, 0.0).reshape(t, n)


def route_top_k(x: jax.Array, router: jax.Array, bias: jax.Array,
                top_k: int, scaling: float = 1.0, n_group: int = 1,
                topk_group: int = 1):
    """Every token over all experts: ``p = sigmoid(x @ router)`` in
    float32, chosen = top-k of ``p + bias`` (ties to the lower expert)
    — with ``n_group`` above 1 of what :func:`keep_groups` leaves of it
    —, weights ``p / sum over the chosen`` times ``scaling``.
    → (chosen (T, k) int32, weights (T, k) float32)."""
    prob = jax.nn.sigmoid(jnp.matmul(
        x, router, preferred_element_type=jnp.float32))
    score = prob + bias.astype(jnp.float32)
    if n_group > 1:
        score = keep_groups(score, n_group, topk_group)
    _, chosen = jax.lax.top_k(score, top_k)
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen.astype(jnp.int32), (
        picked / picked.sum(-1, keepdims=True) * scaling)


def gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, gate_mult: float = 1.0,
              down_mult: float = 1.0) -> jax.Array:
    """``(silu(gate_mult x w_gate) * x w_up) w_down * down_mult``:
    products in the arrays' dtype, accumulated and returned in float32;
    a multiplier of 1 is no operation of the program."""
    gate = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
    up = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    if gate_mult != 1.0:
        gate = gate * gate_mult
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jnp.matmul(hidden, w_down, preferred_element_type=jnp.float32)
    return out if down_mult == 1.0 else out * down_mult


# ── the held experts' grouped product ─────────────────────────────────

ROW_TILE = 128              # rows of a tile of the kernels: one expert's
CHUNK_ROWS = 8192           # rows of the layout multiplied at a time
COMBINE_ROWS = 512          # most rows of one scatter-add of the combine
_LANES = 128                # (readings of the three: PERF.md §6, PR 38)
_SUBLANES = 8
_BLOCK_BYTES = 16 * 2 ** 20  # an expert's whole-depth blocks of one step
_VMEM_BYTES = 96 * 2 ** 20


def _col_tile(n: int, depth: int, matrices: int, itemsize: int = 2,
              by_lanes: bool = False) -> int:
    """Output columns of one grid step: the most whole lanes that divide
    ``n`` whose ``matrices`` blocks of the whole ``depth`` fit
    ``_BLOCK_BYTES`` (twice that is in VMEM: the next expert's blocks
    arrive while this one's are multiplied); 0 where none does.
    ``by_lanes``: the output is written as rows of (n / 128, 128), so a
    tile is whole groups of 8 lanes' worth, or all of ``n``."""
    step = _LANES * _SUBLANES if by_lanes else _LANES
    fits = [c for c in list(range(step, n, step)) + [n] if n % c == 0
            and matrices * depth * c * itemsize <= _BLOCK_BYTES]
    return max(fits, default=0)


def lane_rows(d: int):
    """(groups, lanes) of a row of ``d`` numbers laid out lane by lane:
    as (T, groups, lanes) a token's float32 row lies in one piece of
    the device's memory (as (T, d) eight tokens' rows are interleaved
    128 numbers at a time), which is what makes a row of the combine's
    scatter-add cost a quarter (PERF.md §6, PR 38)."""
    lanes = math.gcd(d, _LANES)
    return d // lanes, lanes


def expert_path(d: int, m: int, dtype, backend: str = "") -> str:
    """The grouped product :func:`grouped_experts` runs for experts of
    ``d`` x ``m``: ``"fused"`` (the Pallas kernels) on a TPU where
    bfloat16 rows tile and a whole-depth block of an expert fits,
    ``"xla"`` (``ragged_dot``) everywhere else. ``backend`` defaults to
    JAX's own."""
    tiles = (d % _LANES == 0 and m % _LANES == 0
             and _col_tile(m, d, 2) > 0
             and _col_tile(d, m, 1, by_lanes=True) > 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def row_tile_of(path: str) -> int:
    """Rows an expert's part of the layout is rounded up to: the
    kernels' tile, or single rows for ``ragged_dot``."""
    return ROW_TILE if path == "fused" else 1


def rows_visited(counts, row_tile: int):
    """Rows of the layout each expert owns, the padding of its last tile
    included: what the grouped product multiplies for ``counts`` held
    rows (numpy or JAX integers)."""
    return -(-counts // row_tile) * row_tile


def _n_live(tiles):
    """The chunk's live tiles: the last entry of ``tiles``."""
    return tiles[tiles.shape[0] - 1]


def _live_tile(j, tiles):
    """No tile beyond the chunk's last live one: a grid step past it
    fetches nothing new and computes nothing."""
    return jnp.minimum(j, _n_live(tiles) - 1)


def _up_kernel(tiles_ref, x_ref, wg_ref, wu_ref, h_ref):
    @pl.when(pl.program_id(1) < _n_live(tiles_ref))
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h_ref[...] = (jax.nn.silu(gate) * up).astype(h_ref.dtype)


def _down_kernel(tiles_ref, h_ref, wd_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < _n_live(tiles_ref))
    def _():
        o_ref[...] = (jnp.dot(
            h_ref[...], wd_ref[...], preferred_element_type=jnp.float32)
            * w_ref[...]).reshape(o_ref.shape)        # rows by lanes


def _grouped_call(kernel, name: str, rows, stacks, extra, tiles, out_dtype,
                  *, row_tile: int, col_tile: int, by_lanes: bool,
                  interpret: bool):
    """``rows`` (C, depth) against the ``stacks`` (count, depth, n), a
    tile of ``row_tile`` rows by the matrices of the expert ``tiles``
    names for it: a grid over column tiles (outer) and row tiles, so
    that an expert's whole-depth blocks, read from the stacks by block
    index, stay in VMEM while its tiles of rows pass. ``extra``: (C, 1)
    arrays that go with the rows. → (C, n), or ``by_lanes`` (C, n / 128,
    128): :func:`lane_rows`."""
    c, depth = rows.shape
    n = stacks[0].shape[-1]
    if c % row_tile or n % col_tile:
        raise ValueError(f"{c} rows in tiles of {row_tile}, {n} columns in "
                         f"tiles of {col_tile}: not whole tiles")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // col_tile, c // row_tile),
        in_specs=(
            [pl.BlockSpec((row_tile, depth),
                          lambda i, j, t: (_live_tile(j, t), 0))]
            + [pl.BlockSpec((None, depth, col_tile),
                            lambda i, j, t: (t[_live_tile(j, t)], 0, i))
               for _ in stacks]
            + [pl.BlockSpec((row_tile, 1),
                            lambda i, j, t: (_live_tile(j, t), 0))
               for _ in extra]),
        out_specs=(pl.BlockSpec((row_tile, col_tile // _LANES, _LANES),
                                lambda i, j, t: (_live_tile(j, t), i, 0))
                   if by_lanes else
                   pl.BlockSpec((row_tile, col_tile),
                                lambda i, j, t: (_live_tile(j, t), i))))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (c, n // _LANES, _LANES) if by_lanes else (c, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name, interpret=interpret,
    )(tiles, rows, *stacks, *extra)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def _product_fused(xs, w, tiles, experts: Params, *, row_tile: int,
                   interpret: bool = False):
    """A chunk's rows through their experts as two kernels: ``xs`` (C,
    D), ``w`` (C,) float32, ``tiles`` (C / row_tile + 1,) int32: the
    expert of each tile of rows, then the number of live tiles → (C, D /
    128, 128) float32 (:func:`lane_rows`), the rows of dead tiles
    unwritten. Jitted, so that a step program traces and lowers the two
    kernels once and not once an expert block."""
    d, m = experts["w_gate"].shape[1:]
    size = jnp.dtype(xs.dtype).itemsize
    hidden = _grouped_call(
        _up_kernel, "grouped_expert_product_up", xs,
        (experts["w_gate"], experts["w_up"]), (), tiles, xs.dtype,
        row_tile=row_tile, col_tile=_col_tile(m, d, 2, size), by_lanes=False,
        interpret=interpret)
    return _grouped_call(
        _down_kernel, "grouped_expert_product_down", hidden,
        (experts["w_down"],), (w[:, None],), tiles, jnp.float32,
        row_tile=row_tile, col_tile=_col_tile(d, m, 1, size, by_lanes=True),
        by_lanes=True, interpret=interpret)


def _product_xla(xs, w, sizes, experts: Params):
    """The same chunk through ``ragged_dot``: ``sizes`` (count,) the
    rows of the chunk each expert owns, in the layout's order; the rows
    by lanes as the kernels give them."""
    def dot(rows, stack):
        return jax.lax.ragged_dot(rows, stack, sizes,
                                  preferred_element_type=jnp.float32)

    hidden = (jax.nn.silu(dot(xs, experts["w_gate"]))
              * dot(xs, experts["w_up"])).astype(xs.dtype)
    out = dot(hidden, experts["w_down"]) * w[:, None]
    return out.reshape((out.shape[0],) + lane_rows(out.shape[1]))


def combine_rows(chunk: int, t: int) -> int:
    """Rows of one scatter-add of the combine into a sum of ``t`` rows:
    the most that divide the chunk, up to ``COMBINE_ROWS`` and up to an
    eighth of the sum's rows: of more than an eighth of a (T, D) sum's
    rows XLA sorts the indices and permutes the rows first (PERF.md §6,
    PR 38; no sum laid out lane by lane was seen to go that way)."""
    most = max(1, min(COMBINE_ROWS, t // 8))
    return max(p for p in range(1, most + 1) if chunk % p == 0)


def grouped_experts(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                    experts: Params, share: ExpertShare,
                    valid: Optional[jax.Array] = None):
    """The held experts' terms: ``sum over the chosen e held here of
    weights_e * E_e(x)`` for every token, as float32 (T, D), and the
    number of tokens each held expert got, (count,) int32. ``experts``
    holds ``w_gate``, ``w_up`` (count, D, M) and ``w_down`` (count, M,
    D); ``valid`` (T,) leaves padded tokens out."""
    t, d = x.shape
    k = chosen.shape[1]
    n_held = share.count
    path = expert_path(d, experts["w_gate"].shape[-1], x.dtype)
    tile = row_tile_of(path)
    local = chosen - share.first
    held = (local >= 0) & (local < n_held)
    if valid is not None:
        held = held & valid[:, None]
    local = jnp.where(held, local, n_held).reshape(-1)     # (T·k,)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :], 0,
                     dtype=jnp.int32)       # (a scatter-add takes 1 ms)
    # the layout: expert e owns the rows end[e] - owned[e] .. end[e] - 1,
    # the first counts[e] of them assignments, from row0[e] of ``order``
    owned = rows_visited(counts, tile)
    end = jnp.cumsum(owned)
    row0 = jnp.cumsum(counts) - counts
    most = -(-(t * k + n_held * (tile - 1)) // tile) * tile
    chunk = min(-(-CHUNK_ROWS // tile) * tile, most)
    piece = combine_rows(chunk, t)
    at = jnp.arange(chunk, dtype=jnp.int32)
    flat_w = weights.reshape(-1)
    stacks = {name: experts[name] for name in ("w_gate", "w_up", "w_down")}

    def one_chunk(i, y):
        lo = i * chunk
        n_rows = jnp.clip(end[-1] - lo, 0, chunk)       # whole tiles
        e = jnp.searchsorted(end, lo + at, side="right").astype(jnp.int32)
        own = jnp.minimum(e, n_held - 1)
        within = lo + at - (end[own] - owned[own])
        live = (e < n_held) & (within < counts[own])
        flat = order[jnp.clip(row0[own] + within, 0, t * k - 1)]
        token = flat // k
        w = jnp.where(live, flat_w[flat], 0.0)
        if path == "fused":
            tiles = jnp.concatenate([own[::tile], n_rows[None] // tile])
            out = _product_fused(x[token], w, tiles, stacks, row_tile=tile)
        else:
            sizes = (jnp.clip(end - lo, 0, chunk)
                     - jnp.clip(end - owned - lo, 0, chunk))
            out = _product_xla(x[token], w, sizes, stacks)
        # a token's held terms arrive in expert order; rows that are no
        # assignment (a tile's padding, a dead tile) are dropped. Rows
        # by lanes, and a few hundred a scatter-add: XLA sorts the
        # indices of a larger one and permutes its rows first
        to = jnp.where(live, token, t)

        def add_piece(j, y):
            return y.at[jax.lax.dynamic_slice_in_dim(to, j * piece, piece)
                        ].add(jax.lax.dynamic_slice_in_dim(
                            out, j * piece, piece), mode="drop")

        return jax.lax.fori_loop(0, (n_rows + piece - 1) // piece,
                                 add_piece, y)

    y = jax.lax.fori_loop(0, (end[-1] + chunk - 1) // chunk, one_chunk,
                          jnp.zeros((t,) + lane_rows(d), jnp.float32))
    return y.reshape(t, d), counts


def moe_share(params: Params, x: jax.Array, top_k: int, share: ExpertShare,
              scaling: float = 1.0, valid: Optional[jax.Array] = None,
              scope: str = "moe", groups: Tuple[int, int] = (1, 1)):
    """This chip's part of the layer for tokens ``x`` (T, D): the held
    experts' terms plus the shared expert, float32. ``params``:
    ``router`` (D, n_experts), ``bias`` (n_experts,), the held experts'
    ``w_gate`` / ``w_up`` / ``w_down``, and ``shared`` (the shared
    expert's three matrices); ``groups``: the router's (``n_group``,
    ``topk_group``), named to it only where there is more than one
    group. → (y, {"chosen", "counts"})."""
    limited = ({} if groups[0] == 1 else
               {"n_group": groups[0], "topk_group": groups[1]})
    with jax.named_scope(scope + ".route"):
        chosen, weights = route_top_k(x, params["router"], params["bias"],
                                      top_k, scaling, **limited)
    with jax.named_scope(scope + ".experts"):
        y, counts = grouped_experts(x, chosen, weights, params, share, valid)
    with jax.named_scope(scope + ".shared"):
        s = params["shared"]
        y = y + gated_mlp(x, s["w_gate"], s["w_up"], s["w_down"])
    return y, {"chosen": chosen, "counts": counts}

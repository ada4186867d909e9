"""The expert layer: top-k routed experts with a shared one, computed for
the share of the experts that this chip holds — and, for an ``expert``
mesh axis with one expert a device, the all-to-all exchange.

**The share** (:func:`route_top_k`, :func:`grouped_experts`,
:func:`moe_share`). A layer of ``n_experts`` routed experts is divided
over chips; this chip holds the experts ``first .. first + count - 1``
(:class:`ExpertShare`). It routes every token over ALL experts
(sigmoid scores in float32, top-k of score + correction bias, weights
renormalised over the chosen, no capacity and no drops), and adds, for
each token, the terms of the chosen experts it holds and the shared
expert. What the experts held elsewhere would add is left out: on one
chip the layer runs without its exchange, and the parts that all shares
give, with the shared expert counted once, add up to the whole layer
(``tests/test_route_lm_share.py``).

The held experts' product is a grouped one with uneven groups: the
(token, slot) assignments that land here are sorted by expert, each
expert's group is cut into tiles of ``tile`` rows, and one loop runs
over the tiles that exist (a dynamic trip count: no capacity, so no
bound on a group but the number of tokens): gather the tile's tokens,
the gated MLP with that expert's weights, scatter-add the weighted rows
into a float32 sum. A tile holds one expert's tokens only, so a short
group costs one mostly empty tile; ``tile`` follows the tokens an
expert can expect.

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
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
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


def route_top_k(x: jax.Array, router: jax.Array, bias: jax.Array,
                top_k: int, scaling: float = 1.0):
    """Every token over all experts: ``p = sigmoid(x @ router)`` in
    float32, chosen = top-k of ``p + bias`` (ties to the lower expert),
    weights ``p / sum over the chosen`` times ``scaling``.
    → (chosen (T, k) int32, weights (T, k) float32)."""
    prob = jax.nn.sigmoid(jnp.matmul(
        x, router, preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(prob + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen.astype(jnp.int32), (
        picked / picked.sum(-1, keepdims=True) * scaling)


def gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array) -> jax.Array:
    """``(silu(x w_gate) * x w_up) w_down``: products in the arrays'
    dtype, accumulated and returned in float32."""
    gate = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
    up = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.matmul(hidden, w_down, preferred_element_type=jnp.float32)


def expert_tile(tokens: int, top_k: int, n_experts: int) -> int:
    """Rows of a tile: the tokens an expert can expect of ``tokens``,
    as a power of two between 128 (below it the MXU idles) and 512."""
    expect = max(1, tokens * top_k // n_experts)
    return min(512, max(128, 1 << (expect - 1).bit_length()))


def grouped_experts(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                    experts: Params, share: ExpertShare,
                    valid: Optional[jax.Array] = None,
                    tile: Optional[int] = None):
    """The held experts' terms: ``sum over the chosen e held here of
    weights_e * E_e(x)`` for every token, as float32 (T, D), and the
    number of tokens each held expert got, (count,) int32. ``experts``
    holds ``w_gate``, ``w_up`` (count, D, M) and ``w_down`` (count, M,
    D); ``valid`` (T,) leaves padded tokens out."""
    t, d = x.shape
    k = chosen.shape[1]
    n_held = share.count
    tile = tile or expert_tile(t, k, share.n_experts)
    local = chosen - share.first
    held = (local >= 0) & (local < n_held)
    if valid is not None:
        held = held & valid[:, None]
    local = jnp.where(held, local, n_held).reshape(-1)     # (T·k,)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(1)[:n_held]
    tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    row0 = jnp.cumsum(counts) - counts
    offs = jnp.arange(tile, dtype=jnp.int32)

    def one_tile(j, y):
        e = jnp.searchsorted(tile_end, j, side="right").astype(jnp.int32)
        within = (j - (tile_end[e] - tiles[e])) * tile + offs
        live = within < counts[e]
        flat = order[jnp.clip(row0[e] + within, 0, t * k - 1)]
        token, slot = flat // k, flat % k
        w = jnp.where(live, weights[token, slot], 0.0)
        out = gated_mlp(x[token], experts["w_gate"][e], experts["w_up"][e],
                        experts["w_down"][e])
        return y.at[token].add(out * w[:, None])

    y = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                          jnp.zeros((t, d), jnp.float32))
    return y, counts


def moe_share(params: Params, x: jax.Array, top_k: int, share: ExpertShare,
              scaling: float = 1.0, valid: Optional[jax.Array] = None,
              scope: str = "moe"):
    """This chip's part of the layer for tokens ``x`` (T, D): the held
    experts' terms plus the shared expert, float32. ``params``:
    ``router`` (D, n_experts), ``bias`` (n_experts,), the held experts'
    ``w_gate`` / ``w_up`` / ``w_down``, and ``shared`` (the shared
    expert's three matrices). → (y, {"chosen", "counts"})."""
    with jax.named_scope(scope + ".route"):
        chosen, weights = route_top_k(x, params["router"], params["bias"],
                                      top_k, scaling)
    with jax.named_scope(scope + ".experts"):
        y, counts = grouped_experts(x, chosen, weights, params, share, valid)
    with jax.named_scope(scope + ".shared"):
        s = params["shared"]
        y = y + gated_mlp(x, s["w_gate"], s["w_up"], s["w_down"])
    return y, {"chosen": chosen, "counts": counts}

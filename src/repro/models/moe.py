"""Mixture-of-Experts FFN (olmoe-1b-7b, deepseek-moe-16b, deepseek-v2-lite).

Two dispatches, chosen by ``MoEConfig.dropless``.

Dropless, over the experts this chip holds (deepseek-v2-lite; the first
``n_held`` of the router's): a float32 softmax over all the router's
experts and a greedy top-k; the (token,
expert) pairs whose expert is held are sorted by expert to the front, and
three grouped matmuls (``jax.lax.ragged_dot``) compute a SwiGLU for each of
them, however many land on one expert.  Pairs routed to experts held
elsewhere form no group and are not computed: the layer passes on the part
of the result that its own experts give, as an expert-parallel rank does
before the exchange between chips (which one chip does not run).

Sort-based capacity dispatch (MegaBlocks/MaxText style) — never materializes
the (T, E, C) one-hot of GShard:

  1. top-k routing over (T, E) gate probs;
  2. flat (T*k,) assignments sorted by expert id (argsort — XLA sort);
  3. rank within expert via searchsorted; tokens beyond the per-expert
     capacity C are DROPPED (residual connection carries them — standard);
  4. gather tokens into an (E, C, d) buffer (experts sharded over `model`),
     per-expert SwiGLU FFN as one batched einsum, weighted scatter-add back.

Shared experts (DeepSeekMoE) are a plain dense SwiGLU applied to every token.
The router adds the Switch-style load-balancing auxiliary loss.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig, ShardingPolicy
from repro.models import layers as L
from repro.models.sharding import Shard

__all__ = ["init_moe", "moe_specs", "apply_moe", "router_capacity"]

from jax.sharding import PartitionSpec as P


def router_capacity(moe: MoEConfig, n_tokens: int) -> int:
    """Per-expert capacity for a token block of size n_tokens."""
    ideal = n_tokens * moe.top_k / moe.n_experts
    cap = int(moe.capacity_factor * ideal + 0.5)
    return max(cap, moe.top_k)


def init_moe(key, cfg: ArchConfig):
    moe = cfg.moe
    assert moe is not None
    d, f, e = cfg.d_model, moe.d_expert, moe.n_experts
    kg, k1, k2, k3, ks = jax.random.split(key, 5)
    scale_in, scale_out = d ** -0.5, f ** -0.5
    h = moe.held
    p = {
        "router": (jax.random.normal(kg, (d, e)) * scale_in).astype(jnp.float32),
        "wi_gate": (jax.random.normal(k1, (h, d, f)) * scale_in).astype(L.DTYPE),
        "wi_up": (jax.random.normal(k2, (h, d, f)) * scale_in).astype(L.DTYPE),
        "wo": (jax.random.normal(k3, (h, f, d)) * scale_out).astype(L.DTYPE),
    }
    if moe.n_shared > 0:
        p["shared"] = L.init_mlp(ks, cfg, d_ff=moe.n_shared * moe.d_expert)
    return p


def moe_specs(cfg: ArchConfig, policy: ShardingPolicy):
    moe = cfg.moe
    m = policy.model_axis
    dp = policy.dp_axes if policy.fsdp else None
    p = {
        "router": P(None, None),
        "wi_gate": P(m, dp, None),
        "wi_up": P(m, dp, None),
        "wo": P(m, None, dp),
    }
    if moe.n_shared > 0:
        p["shared"] = L.mlp_specs(cfg, policy)
    return p


def _expert_ffn(params, xb):
    """xb: (D, E, C, d) -> (D, E, C, d); batched SwiGLU over the expert dim."""
    g = jnp.einsum("gecd,edf->gecf", xb, params["wi_gate"])
    u = jnp.einsum("gecd,edf->gecf", xb, params["wi_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xb.dtype) * u
    return jnp.einsum("gecf,efd->gecd", h, params["wo"])


def _route(moe: MoEConfig, xt, router):
    """float32 softmax over the router's experts and its greedy top-k:
    (probs (..., E), gate weights (..., k), expert ids (..., k))."""
    logits = jnp.einsum(
        "...d,de->...e", xt.astype(jnp.float32), router.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, moe.top_k)
    if moe.renormalize:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return probs, gate_w, gate_e


def _aux_loss(moe: MoEConfig, probs, gate_e):
    """Switch load balancing: E * sum_e f_e * p_e over every token."""
    e = moe.n_experts
    me = probs.reshape(-1, e).mean(axis=0)
    counts = jnp.zeros((e,), jnp.float32).at[gate_e.reshape(-1)].add(1.0)
    fe = counts / gate_e.size
    return moe.aux_loss_weight * e * jnp.sum(fe * me)


def _held_experts(moe: MoEConfig, params, xt, gate_w, gate_e):
    """xt (t, d); gate (t, k) -> (t, d) float32: for every (token, expert)
    pair whose expert this chip holds, the gate weight times the expert's
    SwiGLU of the token, summed per token.  Nothing is dropped."""
    t, d = xt.shape
    held = moe.held
    ids = gate_e.reshape(-1)
    mine = ids < held
    # held pairs sorted by expert to the front; the rest form no group
    group = jnp.minimum(ids, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    rows = order // moe.top_k  # the token of each sorted pair
    xs = xt[rows]
    g = jax.lax.ragged_dot(xs, params["wi_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["wi_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    y = jax.lax.ragged_dot(h, params["wo"], sizes)
    # rows in no group are left undefined by the grouped matmul: select
    w = gate_w.reshape(-1)[order]
    y = jnp.where(mine[order][:, None], y.astype(jnp.float32) * w[:, None],
                  0.0)
    return jnp.zeros((t, d), jnp.float32).at[rows].add(y)


def _apply_dropless(cfg: ArchConfig, params, x):
    moe = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        probs, gate_w, gate_e = _route(moe, xt, params["router"])
        aux = _aux_loss(moe, probs, gate_e)
    with jax.named_scope("moe.experts"):
        y = _held_experts(moe, params, xt, gate_w, gate_e)
    y = y.astype(x.dtype).reshape(b, s, d)
    if moe.n_shared > 0:
        with jax.named_scope("moe.shared"):
            y = y + L.apply_mlp(cfg, params["shared"], x)
    return y, aux


def apply_moe(
    cfg: ArchConfig,
    shard: Shard,
    params,
    x,
    capacity: Optional[int] = None,
):
    """x: (b, s, d) -> (y, aux_loss).

    Dispatch is PER DATA SHARD (tokens viewed as (D, T_local, d)): slot
    buffers shard (dp, model) so expert compute is fully local — without
    this, capacity slots cannot shard over dp and every device computes the
    global expert load (16x waste; see EXPERIMENTS.md §Perf iteration 1).

    A dropless layer (``MoEConfig.dropless``) has no capacity and no
    per-shard dispatch: see the module docstring.
    """
    moe = cfg.moe
    if moe.dropless:
        return _apply_dropless(cfg, params, x)
    b, s, d = x.shape
    t = b * s
    e, k = moe.n_experts, moe.top_k
    nd = shard.n_data_shards()
    if t % nd:
        nd = 1
    tl = t // nd  # tokens per dp shard
    cap = capacity if capacity is not None else router_capacity(moe, tl)

    xt = shard.moe_tokens(x.reshape(nd, tl, d))
    # probs (D, tl, e); gate weights and ids (D, tl, k)
    probs, gate_w, gate_e = _route(moe, xt, params["router"])
    aux = _aux_loss(moe, probs, gate_e)

    # -- sort-based dispatch, vectorized over the dp-shard dim
    flat_e = gate_e.reshape(nd, tl * k)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tl), k)[None], (nd, tl * k)
    )
    flat_w = gate_w.reshape(nd, tl * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    st = jnp.take_along_axis(flat_t, order, axis=-1)
    sw = jnp.take_along_axis(flat_w, order, axis=-1)
    # rank within expert group (per shard)
    group_start = jax.vmap(
        lambda row: jnp.searchsorted(row, row, side="left")
    )(se)
    rank = jnp.arange(tl * k)[None] - group_start
    valid = rank < cap
    slot = se * cap + jnp.where(valid, rank, 0)  # (D, tl*k) in [0, e*cap)

    def scatter_row(slots, vals, valid_row, dtype):
        buf = jnp.zeros((e * cap,), dtype)
        return buf.at[slots].set(
            jnp.where(valid_row, vals, jnp.zeros((), dtype)), mode="drop"
        )

    slot_tok = jax.vmap(
        lambda sl, v, ok: scatter_row(sl, v.astype(jnp.int32), ok, jnp.int32)
    )(slot, st, valid)
    slot_w = jax.vmap(
        lambda sl, v, ok: scatter_row(sl, v, ok, jnp.float32)
    )(slot, sw, valid)
    slot_live = jax.vmap(
        lambda sl, v, ok: scatter_row(sl, v, ok, jnp.float32)
    )(slot, valid.astype(jnp.float32), valid)

    # gather tokens into (D, E, C, d), experts sharded over model
    xb = jnp.take_along_axis(xt, slot_tok[..., None], axis=1)
    xb = xb * slot_live[..., None].astype(xt.dtype)
    xb = shard.moe_buffer(xb.reshape(nd, e, cap, d))
    yb = _expert_ffn(params, xb)
    yb = shard.moe_buffer(yb).reshape(nd, e * cap, d)

    yw = yb.astype(jnp.float32) * (slot_w * slot_live)[..., None]
    out = jax.vmap(
        lambda toks, vals: jnp.zeros((tl, d), jnp.float32).at[toks].add(vals)
    )(slot_tok, yw)
    y = shard.moe_tokens(out.astype(x.dtype)).reshape(b, s, d)

    if moe.n_shared > 0:
        y = y + L.apply_mlp(cfg, params["shared"], x)
    return y, aux

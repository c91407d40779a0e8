"""Zamba2-7B hybrid: Mamba2 backbone + ONE shared-weight attention block
applied after every ``attn_every``-th SSM block.

81 layers with attn_every=6 -> 13 segments of (6 mamba + shared attn) + 3
trailing mamba blocks.  The shared block's weights are reused at every
application (the Zamba trick: attention quality at ~1/13 of the weight
cost); each application keeps its OWN KV cache.

Layout: outer lax.scan over the 13 segments (shared-attn weights are loop
invariant), inner lax.scan over the 6 stacked mamba blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShardingPolicy
from repro.models import layers as L
from repro.models import ssm, transformer
from repro.models.sharding import Shard

__all__ = [
    "segment_layout",
    "init_zamba",
    "zamba_specs",
    "apply_zamba",
    "zamba_decode_state_shape",
    "apply_zamba_decode",
]


def segment_layout(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_segments, seg_len, n_trailing)."""
    k = cfg.hybrid.attn_every
    n_seg = cfg.n_layers // k
    trailing = cfg.n_layers - n_seg * k
    return n_seg, k, trailing


def init_zamba(key, cfg: ArchConfig):
    n_seg, seg, trailing = segment_layout(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    seg_keys = jax.random.split(k1, n_seg * seg).reshape(n_seg, seg, 2)
    blocks = jax.vmap(
        jax.vmap(lambda kk: ssm.init_mamba2_block(kk, cfg))
    )(seg_keys)
    p = {
        "mamba_segments": blocks,  # leaves (n_seg, seg, ...)
        "shared_attn": transformer.init_block(k2, cfg),
    }
    if trailing:
        tk = jax.random.split(k3, trailing)
        p["mamba_trailing"] = jax.vmap(
            lambda kk: ssm.init_mamba2_block(kk, cfg)
        )(tk)
    return p


def zamba_specs(cfg: ArchConfig, policy: ShardingPolicy):
    n_seg, seg, trailing = segment_layout(cfg)
    mspec = ssm.mamba2_block_specs(cfg, policy)
    stack2 = jax.tree.map(lambda s: P(None, None, *s), mspec)
    p = {
        "mamba_segments": stack2,
        "shared_attn": transformer.block_specs(cfg, policy),
    }
    if trailing:
        p["mamba_trailing"] = jax.tree.map(lambda s: P(None, *s), mspec)
    return p


def apply_zamba(cfg: ArchConfig, shard: Shard, params, x, positions):
    """x: (b, s, d).  Returns y (final SSM states are discarded in training)."""
    n_seg, seg, trailing = segment_layout(cfg)

    def mamba_scan(x, stacked):
        def body(h, lp):
            h, _ = ssm.apply_mamba2_block(cfg, shard, lp, h)
            return h, None

        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        y, _ = jax.lax.scan(body, x, stacked)
        return y

    def segment(h, seg_params):
        h = mamba_scan(h, seg_params)
        h = transformer.apply_block(
            cfg, shard, params["shared_attn"], h, positions
        )
        return h, None

    segment = jax.checkpoint(segment, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(segment, x, params["mamba_segments"])
    if trailing:
        x = mamba_scan(x, params["mamba_trailing"])
    return x


def apply_zamba_prefill(cfg: ArchConfig, shard: Shard, params, x, positions,
                        max_len: int):
    """Prompt pass that captures decode state (SSM states + conv tails +
    per-application shared-attn KV caches).  Returns (y, state)."""
    n_seg, seg, trailing = segment_layout(cfg)
    b, s, _ = x.shape
    state = init_zamba_decode_state(cfg, b, max_len)

    def mamba_scan(h, stacked):
        def body(h, lp):
            h, st = ssm.apply_mamba2_block(cfg, shard, lp, h)
            return h, st

        return jax.lax.scan(body, h, stacked)

    def segment(h, xs):
        seg_params, ck, cv = xs
        h, sts = mamba_scan(h, seg_params)
        # shared attention with KV capture
        h_in = shard.activation(h)
        h1 = L.apply_norm(cfg, params["shared_attn"]["ln1"], h_in)
        q, k, v = L.qkv_project(cfg, params["shared_attn"]["attn"], h1, positions, shard)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), 0, axis=1)
        ctx = transformer.chunked_gqa_attend(q, k, v, causal=True)
        h = h_in + L.attn_out(cfg, params["shared_attn"]["attn"], ctx, shard)
        h2 = L.apply_norm(cfg, params["shared_attn"]["ln2"], h)
        h = h + L.apply_mlp(cfg, params["shared_attn"]["mlp"], h2)
        return h, (sts, ck, cv)

    x, (sts, nk, nv) = jax.lax.scan(
        segment, x, (params["mamba_segments"], state["attn_k"], state["attn_v"])
    )
    new_state = dict(state)
    new_state.update(
        seg_ssm=sts["ssm"], seg_conv=sts["conv"], attn_k=nk, attn_v=nv
    )
    if trailing:
        x, tst = mamba_scan(x, params["mamba_trailing"])
        new_state.update(trail_ssm=tst["ssm"], trail_conv=tst["conv"])
    return x, new_state


def zamba_decode_state_shape(cfg: ArchConfig, batch: int, max_len: int):
    n_seg, seg, trailing = segment_layout(cfg)
    st = ssm.mamba2_state_shape(cfg, batch)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "seg_ssm": (n_seg, seg) + st["ssm"],
        "seg_conv": (n_seg, seg) + st["conv"],
        "attn_k": (n_seg, batch, max_len, kv, hd),
        "attn_v": (n_seg, batch, max_len, kv, hd),
    }
    if trailing:
        shapes["trail_ssm"] = (trailing,) + st["ssm"]
        shapes["trail_conv"] = (trailing,) + st["conv"]
    return shapes


def init_zamba_decode_state(cfg: ArchConfig, batch: int, max_len: int):
    shapes = zamba_decode_state_shape(cfg, batch, max_len)
    dt = {"seg_ssm": jnp.float32, "seg_conv": L.DTYPE,
          "attn_k": L.DTYPE, "attn_v": L.DTYPE,
          "trail_ssm": jnp.float32, "trail_conv": L.DTYPE}
    return {k: jnp.zeros(v, dt[k]) for k, v in shapes.items()}


def apply_zamba_decode(cfg: ArchConfig, shard: Shard, params, x, state,
                       cache_len, positions):
    """x: (b, 1, d).  Returns (y, new_state)."""
    n_seg, seg, trailing = segment_layout(cfg)

    def mamba_steps(h, stacked_params, ssm_st, conv_st):
        def body(h, xs):
            lp, s_ssm, s_conv = xs
            h, new = ssm.apply_mamba2_decode(
                cfg, shard, lp, h, {"ssm": s_ssm, "conv": s_conv}
            )
            return h, (new["ssm"], new["conv"])

        h, (new_ssm, new_conv) = jax.lax.scan(
            body, h, (stacked_params, ssm_st, conv_st)
        )
        return h, new_ssm, new_conv

    # the shared attention's stacked caches ride in the carry, one slot per
    # segment, written in place (as in lm.decode_step)
    def segment(carry, xs):
        h, k, v, slot = carry
        seg_params, s_ssm, s_conv = xs
        h, new_ssm, new_conv = mamba_steps(h, seg_params, s_ssm, s_conv)
        h, k, v = transformer.apply_block_decode(
            cfg, shard, params["shared_attn"], h, k, v, slot, cache_len,
            positions,
        )
        return (h, k, v, slot + 1), (new_ssm, new_conv)

    (x, new_k, new_v, _), (new_ssm, new_conv) = jax.lax.scan(
        segment,
        (x, state["attn_k"], state["attn_v"], jnp.int32(0)),
        (params["mamba_segments"], state["seg_ssm"], state["seg_conv"]),
    )
    new_state = dict(state)
    new_state.update(
        seg_ssm=new_ssm, seg_conv=new_conv, attn_k=new_k, attn_v=new_v
    )
    if trailing:
        x, t_ssm, t_conv = mamba_steps(
            x, params["mamba_trailing"], state["trail_ssm"], state["trail_conv"]
        )
        new_state.update(trail_ssm=t_ssm, trail_conv=t_conv)
    return x, new_state

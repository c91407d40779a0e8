"""Unified model API over all 10 assigned architectures.

    params                = init_params(key, cfg)
    specs                 = param_specs(cfg, policy)          # same pytree of PartitionSpec
    loss, metrics         = train_loss(cfg, shard, params, batch)
    logits, state         = prefill(cfg, shard, params, batch, max_len)
    logits, state         = decode_step(cfg, shard, params, state, token, cache_len)

Batches (built by repro.data.pipeline / launch.input_specs):
    dense/moe/ssm/hybrid train: {tokens (B,S) i32, labels (B,S) i32}
    vlm train:   + {patch_embeds (B, P, frontend_dim)}   (P text slots replaced)
    audio train: {frames (B,S,frontend_dim), tokens (B,S//8), labels (B,S//8)}
    decode:      {token (B,1) i32} + cache state + cache_len
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShardingPolicy
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models import whisper as W
from repro.models import xlstm as X
from repro.models import zamba as Z
from repro.models.sharding import Shard

__all__ = [
    "init_params",
    "param_specs",
    "train_loss",
    "init_decode_state",
    "decode_state_specs",
    "prefill",
    "decode_step",
    "count_params",
    "active_params",
]

DEC_SEQ_RATIO = 8  # audio: decoder length = seq_len // 8


# ---------------------------------------------------------------------------
# xLSTM segmentation: blocks grouped into segments ending with an sLSTM
# ---------------------------------------------------------------------------

def _xlstm_layout(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_segments, mlstm_per_segment, trailing_mlstm)."""
    sl = sorted(cfg.ssm.slstm_layers)
    if not sl:
        return 0, 0, cfg.n_layers
    seg_len = sl[0] + 1
    expect = tuple(seg_len * (i + 1) - 1 for i in range(len(sl)))
    if tuple(sl) != expect:
        raise ValueError(
            f"slstm_layers {sl} must be uniformly spaced ends of segments"
        )
    n_seg = len(sl)
    trailing = cfg.n_layers - n_seg * seg_len
    if trailing < 0:
        raise ValueError("slstm layout exceeds n_layers")
    return n_seg, seg_len - 1, trailing


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig):
    cfg.validate()
    ke, kb, kn, kx = jax.random.split(key, 4)
    if cfg.family == "audio":
        return W.init_whisper(key, cfg)

    p: dict[str, Any] = {"embed": L.init_embedding(ke, cfg)}
    if cfg.family == "vlm":
        p["projector"] = {
            "w": (
                jax.random.normal(kx, (cfg.frontend_dim, cfg.d_model))
                * cfg.frontend_dim ** -0.5
            ).astype(L.DTYPE)
        }

    if cfg.mla is not None:
        p.update(MLA.init_stack(kb, cfg))
    elif cfg.family in ("dense", "vlm"):
        keys = jax.random.split(kb, cfg.n_layers)
        p["blocks"] = jax.vmap(lambda k: T.init_block(k, cfg))(keys)
    elif cfg.family == "moe":
        n_moe = cfg.n_layers - (1 if cfg.moe.first_layer_dense else 0)
        keys = jax.random.split(kb, n_moe)

        def init_moe_block(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            return {
                "ln1": L.init_norm(cfg),
                "attn": L.init_attention(k1, cfg),
                "ln2": L.init_norm(cfg),
                "moe": M.init_moe(k2, cfg),
            }

        p["blocks"] = jax.vmap(init_moe_block)(keys)
        if cfg.moe.first_layer_dense:
            p["dense_block"] = T.init_block(kx, cfg)
    elif cfg.family == "ssm":  # xlstm
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        if n_seg:
            mk = jax.random.split(kb, n_seg * m_per).reshape(n_seg, m_per, 2)
            p["mlstm_segments"] = jax.vmap(
                jax.vmap(lambda k: X.init_mlstm_block(k, cfg))
            )(mk)
            sk = jax.random.split(kn, n_seg)
            p["slstm_blocks"] = jax.vmap(lambda k: X.init_slstm_block(k, cfg))(sk)
        if trailing:
            tk = jax.random.split(kx, trailing)
            p["mlstm_trailing"] = jax.vmap(
                lambda k: X.init_mlstm_block(k, cfg)
            )(tk)
    elif cfg.family == "hybrid":
        p.update(Z.init_zamba(kb, cfg))
    else:
        raise ValueError(f"unknown family {cfg.family}")

    p["final_norm"] = L.init_norm(cfg)
    return p


def param_specs(cfg: ArchConfig, policy: ShardingPolicy):
    if cfg.family == "audio":
        return W.whisper_specs(cfg, policy)
    stack = lambda spec: jax.tree.map(lambda s: P(None, *s), spec)
    p: dict[str, Any] = {"embed": L.embedding_specs(cfg, policy)}
    dp = policy.dp_axes if policy.fsdp else None
    if cfg.family == "vlm":
        p["projector"] = {"w": P(None, dp)}
    if cfg.mla is not None:
        p.update(MLA.stack_specs(cfg, policy))
    elif cfg.family in ("dense", "vlm"):
        p["blocks"] = stack(T.block_specs(cfg, policy))
    elif cfg.family == "moe":
        mspec = {
            "ln1": L.norm_specs(cfg),
            "attn": L.attention_specs(cfg, policy),
            "ln2": L.norm_specs(cfg),
            "moe": M.moe_specs(cfg, policy),
        }
        p["blocks"] = stack(mspec)
        if cfg.moe.first_layer_dense:
            p["dense_block"] = T.block_specs(cfg, policy)
    elif cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        ms = X.mlstm_block_specs(cfg, policy)
        if n_seg:
            p["mlstm_segments"] = jax.tree.map(lambda s: P(None, None, *s), ms)
            p["slstm_blocks"] = stack(X.slstm_block_specs(cfg, policy))
        if trailing:
            p["mlstm_trailing"] = stack(ms)
    elif cfg.family == "hybrid":
        p.update(Z.zamba_specs(cfg, policy))
    p["final_norm"] = L.norm_specs(cfg)
    return p


# ---------------------------------------------------------------------------
# forward (training)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, shard: Shard, params, batch):
    """Returns (x (b,s,d), positions (s,), loss_mask (b,s) or None)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params["embed"], tokens)
    if cfg.family == "vlm":
        pe = jnp.einsum(
            "bpf,fd->bpd", batch["patch_embeds"].astype(L.DTYPE),
            params["projector"]["w"],
        )
        x = jnp.concatenate([pe, x], axis=1)
        b, s, _ = x.shape
        mask = jnp.concatenate(
            [
                jnp.zeros((b, cfg.n_patches), jnp.float32),
                jnp.ones((b, s - cfg.n_patches), jnp.float32),
            ],
            axis=1,
        )
        return x, jnp.arange(s), mask
    return x, jnp.arange(x.shape[1]), None


def _backbone(cfg: ArchConfig, shard: Shard, params, x, positions):
    """Residual-stream pass through the stacked blocks.  Returns (y, aux)."""
    aux = jnp.float32(0.0)
    ckpt = lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.nothing_saveable
    )
    if cfg.mla is not None:
        x, _, aux = MLA.forward(cfg, shard, params, x, positions,
                                keep_cache=False, wrap=ckpt)
    elif cfg.family in ("dense", "vlm"):

        def body(h, lp):
            return T.apply_block(cfg, shard, lp, h, positions), None

        x, _ = jax.lax.scan(ckpt(body), x, params["blocks"])
    elif cfg.family == "moe":
        if cfg.moe.first_layer_dense:
            x = T.apply_block(cfg, shard, params["dense_block"], x, positions)

        def body(h, lp):
            h = shard.activation(h)
            h1 = L.apply_norm(cfg, lp["ln1"], h)
            q, k, v = L.qkv_project(cfg, lp["attn"], h1, positions, shard)
            ctx = T.chunked_gqa_attend(q, k, v, causal=True)
            h = h + L.attn_out(cfg, lp["attn"], ctx, shard)
            h2 = L.apply_norm(cfg, lp["ln2"], h)
            y, a = M.apply_moe(cfg, shard, lp["moe"], h2)
            return h + y, a

        x, auxs = jax.lax.scan(ckpt(body), x, params["blocks"])
        aux = aux + auxs.sum()
    elif cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)

        def mbody(h, lp):
            h, _ = X.apply_mlstm_block(cfg, shard, lp, h)
            return h, None

        if n_seg:

            def segment(h, seg):
                mparams, sparams = seg
                h, _ = jax.lax.scan(ckpt(mbody), h, mparams)
                h, _ = X.apply_slstm_block(cfg, shard, sparams, h)
                return h, None

            x, _ = jax.lax.scan(
                ckpt(segment), x,
                (params["mlstm_segments"], params["slstm_blocks"]),
            )
        if trailing:
            x, _ = jax.lax.scan(ckpt(mbody), x, params["mlstm_trailing"])
    elif cfg.family == "hybrid":
        x = Z.apply_zamba(cfg, shard, params, x, positions)
    else:
        raise ValueError(cfg.family)
    return x, aux


def train_loss(cfg: ArchConfig, shard: Shard, params, batch):
    """Mean next-token cross entropy (+ MoE aux).  Returns (loss, metrics)."""
    if cfg.family == "audio":
        enc = W.encode(cfg, shard, params, batch["frames"])
        logits = W.decode_train(cfg, shard, params, batch["tokens"], enc)
        logits = shard.logits(logits)
        loss = L.softmax_xent(logits, batch["labels"])
        return loss, {"loss": loss, "aux": jnp.float32(0.0)}

    x, positions, mask = _embed_inputs(cfg, shard, params, batch)
    x, aux = _backbone(cfg, shard, params, x, positions)
    x = L.apply_norm(cfg, params["final_norm"], x)
    if cfg.family == "vlm":
        # only text positions produce logits/loss
        x = x[:, cfg.n_patches :]
        mask = None
    logits = L.unembed(cfg, params["embed"], x)
    logits = shard.logits(logits)
    xent = L.softmax_xent(logits, batch["labels"], mask)
    loss = xent + aux
    return loss, {"loss": xent, "aux": aux}


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_len: int):
    """Zero-initialized cache/state pytree (jnp arrays)."""
    shapes = decode_state_shapes(cfg, batch, max_len)
    return jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def decode_state_shapes(cfg: ArchConfig, batch: int, max_len: int):
    """ShapeDtypeStruct pytree of the decode state (dry-run friendly)."""
    sds = jax.ShapeDtypeStruct
    kv, hd, ld = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    if cfg.mla is not None:
        # one latent and one rope key per position and layer
        return {"kv": sds(MLA.cache_shape(cfg, batch, max_len), L.DTYPE)}
    if cfg.family in ("dense", "vlm", "moe"):
        n_cached = ld
        return {
            "k": sds((n_cached, batch, max_len, kv, hd), L.DTYPE),
            "v": sds((n_cached, batch, max_len, kv, hd), L.DTYPE),
        }
    if cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        m = X.mlstm_state_shape(cfg, batch)
        s = X.slstm_state_shape(cfg, batch)
        out = {}
        if n_seg:
            out["m_c"] = sds((n_seg, m_per) + m["c"], jnp.float32)
            out["m_n"] = sds((n_seg, m_per) + m["n"], jnp.float32)
            out["m_m"] = sds((n_seg, m_per) + m["m"], jnp.float32)
            out["m_conv"] = sds((n_seg, m_per) + m["conv"], L.DTYPE)
            out["s_c"] = sds((n_seg,) + s["c"], jnp.float32)
            out["s_n"] = sds((n_seg,) + s["n"], jnp.float32)
            out["s_m"] = sds((n_seg,) + s["m"], jnp.float32)
            out["s_h"] = sds((n_seg,) + s["h"], jnp.float32)
        if trailing:
            out["t_c"] = sds((trailing,) + m["c"], jnp.float32)
            out["t_n"] = sds((trailing,) + m["n"], jnp.float32)
            out["t_m"] = sds((trailing,) + m["m"], jnp.float32)
            out["t_conv"] = sds((trailing,) + m["conv"], L.DTYPE)
        return out
    if cfg.family == "hybrid":
        shapes = Z.zamba_decode_state_shape(cfg, batch, max_len)
        dt = {
            "seg_ssm": jnp.float32, "seg_conv": L.DTYPE,
            "attn_k": L.DTYPE, "attn_v": L.DTYPE,
            "trail_ssm": jnp.float32, "trail_conv": L.DTYPE,
        }
        return {k: sds(v, dt[k]) for k, v in shapes.items()}
    if cfg.family == "audio":
        shapes = W.whisper_cache_shape(cfg, batch, max_len)
        return {k: sds(v, L.DTYPE) for k, v in shapes.items()}
    raise ValueError(cfg.family)


def decode_state_specs(cfg: ArchConfig, policy: ShardingPolicy,
                       batch_shardable: bool = True):
    """PartitionSpec pytree matching decode_state_shapes.

    ``batch_shardable=False`` (e.g. long_500k batch=1): the batch dim is
    replicated and long-context caches shard their SEQ dim over dp instead.
    """
    dp = policy.dp_axes if batch_shardable else None
    m = policy.model_axis
    if policy.kv_seq_shard and not batch_shardable:
        # batch=1 long-context: cache seq over dp (+ kv heads over model)
        kv_spec = P(None, None, policy.dp_axes,
                    m if policy.shard_kv_heads else None, None)
    elif policy.kv_seq_shard:
        kv_spec = P(None, dp, m, None, None)
    elif policy.shard_kv_heads:
        kv_spec = P(None, dp, None, m, None)
    else:
        kv_spec = P(None, dp, None, None, None)
    if cfg.mla is not None:
        return {"kv": P(None, dp, None, None)}
    if cfg.family in ("dense", "vlm", "moe"):
        return {"k": kv_spec, "v": kv_spec}
    if cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        out = {}
        # mLSTM state: shard dv over model (heads are few)
        if n_seg:
            out["m_c"] = P(None, None, dp, None, None, m)
            out["m_n"] = P(None, None, dp, None, None)
            out["m_m"] = P(None, None, dp, None)
            out["m_conv"] = P(None, None, dp, None, m)
            out["s_c"] = P(None, dp, None, m)
            out["s_n"] = P(None, dp, None, m)
            out["s_m"] = P(None, dp, None, m)
            out["s_h"] = P(None, dp, None, m)
        if trailing:
            out["t_c"] = P(None, dp, None, None, m)
            out["t_n"] = P(None, dp, None, None)
            out["t_m"] = P(None, dp, None)
            out["t_conv"] = P(None, dp, None, m)
        return out
    if cfg.family == "hybrid":
        if policy.kv_seq_shard and not batch_shardable:
            # batch=1 long-context: seq over dp, kv heads over model
            att = P(None, None, policy.dp_axes, m, None)
        elif policy.kv_seq_shard:
            att = P(None, dp, m, None, None)
        else:
            att = P(None, dp, None, m, None)
        return {
            "seg_ssm": P(None, None, dp, m, None, None),
            "seg_conv": P(None, None, dp, None, m),
            "attn_k": att,
            "attn_v": att,
            "trail_ssm": P(None, dp, m, None, None),
            "trail_conv": P(None, dp, None, m),
        }
    if cfg.family == "audio":
        kv_spec2 = (
            P(None, dp, m, None, None)
            if policy.kv_seq_shard
            else P(None, dp, None, m, None)
        )
        return {k: kv_spec2 for k in ("self_k", "self_v", "cross_k", "cross_v")}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def decode_step(cfg: ArchConfig, shard: Shard, params, state, token,
                cache_len):
    """One-token step.  token (b,1) i32; cache_len scalar i32 (= number of
    tokens already in the cache).  Returns (logits (b,1,V), new_state)."""
    if cfg.family == "audio":
        return W.decode_step(
            cfg, shard, params, state, token, cache_len, cross_len=cache_len
        )
    x = L.embed_tokens(params["embed"], token)
    positions = cache_len + jnp.zeros((1,), jnp.int32)
    if cfg.mla is not None:
        x, kv = MLA.decode(cfg, shard, params, x, state["kv"], cache_len,
                           positions)
        state = {"kv": kv}
    elif cfg.family in ("dense", "vlm", "moe"):

        def body(h, xs):
            if cfg.family == "moe":
                lp, ck, cv = xs
                h1 = L.apply_norm(cfg, lp["ln1"], h)
                q, k, v = L.qkv_project(cfg, lp["attn"], h1, positions, shard)
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.astype(ck.dtype), cache_len, axis=1
                )
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.astype(cv.dtype), cache_len, axis=1
                )
                ck, cv = shard.cache(ck), shard.cache(cv)
                ctx = T.decode_attend(q, ck, cv, cache_len + 1)
                h = h + L.attn_out(cfg, lp["attn"], ctx, shard)
                h2 = L.apply_norm(cfg, lp["ln2"], h)
                y, _ = M.apply_moe(cfg, shard, lp["moe"], h2)
                return h + y, (ck, cv)
            lp, ck, cv = xs
            h, ck, cv = T.apply_block_decode(
                cfg, shard, lp, h, ck, cv, cache_len, positions
            )
            return h, (ck, cv)

        blocks = params["blocks"]
        if cfg.family == "moe" and cfg.moe.first_layer_dense:
            # dense layer 0 holds cache slot 0
            h, k0, v0 = T.apply_block_decode(
                cfg, shard, params["dense_block"], x,
                state["k"][0], state["v"][0], cache_len, positions,
            )
            x = h
            xs = (blocks, state["k"][1:], state["v"][1:])
            x, (nk, nv) = jax.lax.scan(body, x, xs)
            new_k = jnp.concatenate([k0[None], nk], axis=0)
            new_v = jnp.concatenate([v0[None], nv], axis=0)
        else:
            x, (new_k, new_v) = jax.lax.scan(
                body, x, (blocks, state["k"], state["v"])
            )
        state = {"k": new_k, "v": new_v}
    elif cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        new_state = dict(state)

        def mbody(h, xs):
            lp, c, n, m, conv = xs
            h, ns = X.apply_mlstm_decode(
                cfg, shard, lp, h, {"c": c, "n": n, "m": m, "conv": conv}
            )
            return h, (ns["c"], ns["n"], ns["m"], ns["conv"])

        if n_seg:

            def segment(h, xs):
                mparams, sparams, mc, mn, mm, mconv, sc, sn, sm, sh = xs
                h, (nc, nn, nm, nconv) = jax.lax.scan(
                    mbody, h, (mparams, mc, mn, mm, mconv)
                )
                h, ss = X.apply_slstm_decode(
                    cfg, shard, sparams, h,
                    {"c": sc, "n": sn, "m": sm, "h": sh},
                )
                return h, (nc, nn, nm, nconv, ss["c"], ss["n"], ss["m"], ss["h"])

            x, outs = jax.lax.scan(
                segment, x,
                (
                    params["mlstm_segments"], params["slstm_blocks"],
                    state["m_c"], state["m_n"], state["m_m"], state["m_conv"],
                    state["s_c"], state["s_n"], state["s_m"], state["s_h"],
                ),
            )
            (new_state["m_c"], new_state["m_n"], new_state["m_m"],
             new_state["m_conv"], new_state["s_c"], new_state["s_n"],
             new_state["s_m"], new_state["s_h"]) = outs
        if trailing:
            x, (tc, tn, tm, tconv) = jax.lax.scan(
                mbody, x,
                (params["mlstm_trailing"], state["t_c"], state["t_n"],
                 state["t_m"], state["t_conv"]),
            )
            new_state.update(t_c=tc, t_n=tn, t_m=tm, t_conv=tconv)
        state = new_state
    elif cfg.family == "hybrid":
        x, state = Z.apply_zamba_decode(
            cfg, shard, params, x, state, cache_len, positions
        )
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], x)
    return shard.logits(logits), state


# ---------------------------------------------------------------------------
# prefill (dense/vlm/moe families; state-carrying families return states)
# ---------------------------------------------------------------------------

def prefill(cfg: ArchConfig, shard: Shard, params, batch, max_len: int):
    """Process a prompt, build the decode state.  Returns (last_logits, state).

    Implemented for serving-scale use on the dense/moe/vlm families (KV is
    written at [0, s)); SSM/hybrid prefill runs the chunked forms and keeps
    final states.  The prefill_32k dry-run cells lower THIS function.
    """
    x, positions, _ = _embed_inputs(cfg, shard, params, batch)
    b, s, _ = x.shape
    if cfg.mla is not None:
        x, kv, _ = MLA.forward(cfg, shard, params, x, positions,
                               keep_cache=True)
        pad = [(0, 0)] * kv.ndim
        pad[2] = (0, max_len - s)
        state = {"kv": jnp.pad(kv.astype(L.DTYPE), pad)}
    elif cfg.family in ("dense", "vlm", "moe"):
        state = init_decode_state(cfg, b, max_len)

        def body(h, xs):
            lp, ck, cv = xs
            h = shard.activation(h)
            h1 = L.apply_norm(cfg, lp["ln1"], h)
            q, k, v = L.qkv_project(cfg, lp["attn"], h1, positions, shard)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), 0, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), 0, axis=1
            )
            ctx = T.chunked_gqa_attend(q, k, v, causal=True)
            h = h + L.attn_out(cfg, lp["attn"], ctx, shard)
            if cfg.family == "moe" and "moe" in lp:
                h2 = L.apply_norm(cfg, lp["ln2"], h)
                y, _ = M.apply_moe(cfg, shard, lp["moe"], h2)
                h = h + y
            elif cfg.parallel_block:
                h = h + L.apply_mlp(cfg, lp["mlp"], h1)
            else:
                h2 = L.apply_norm(cfg, lp["ln2"], h)
                h = h + L.apply_mlp(cfg, lp["mlp"], h2)
            return h, (ck, cv)

        if cfg.family == "moe" and cfg.moe.first_layer_dense:
            # dense layer 0 with explicit KV capture into cache slot 0
            lp0 = params["dense_block"]
            h1 = L.apply_norm(cfg, lp0["ln1"], x)
            q0, k0, v0 = L.qkv_project(cfg, lp0["attn"], h1, positions, shard)
            ck0 = jax.lax.dynamic_update_slice_in_dim(
                state["k"][0], k0.astype(state["k"].dtype), 0, axis=1
            )
            cv0 = jax.lax.dynamic_update_slice_in_dim(
                state["v"][0], v0.astype(state["v"].dtype), 0, axis=1
            )
            ctx0 = T.chunked_gqa_attend(q0, k0, v0, causal=True)
            x = x + L.attn_out(cfg, lp0["attn"], ctx0, shard)
            h2 = L.apply_norm(cfg, lp0["ln2"], x)
            x = x + L.apply_mlp(cfg, lp0["mlp"], h2)
            xs = (params["blocks"], state["k"][1:], state["v"][1:])
            x, (nk, nv) = jax.lax.scan(body, x, xs)
            state = {"k": jnp.concatenate([ck0[None], nk]),
                     "v": jnp.concatenate([cv0[None], nv])}
        else:
            x, (nk, nv) = jax.lax.scan(
                body, x, (params["blocks"], state["k"], state["v"])
            )
            state = {"k": nk, "v": nv}
    elif cfg.family == "ssm":
        n_seg, m_per, trailing = _xlstm_layout(cfg)
        state = init_decode_state(cfg, b, max_len)

        def mbody(h, lp):
            h, st = X.apply_mlstm_block(cfg, shard, lp, h)
            return h, st

        if n_seg:

            def segment(h, seg):
                mparams, sparams = seg
                h, mst = jax.lax.scan(mbody, h, mparams)
                h, ss = X.apply_slstm_block(cfg, shard, sparams, h)
                return h, (mst, ss)

            x, (mst, ss) = jax.lax.scan(
                segment, x, (params["mlstm_segments"], params["slstm_blocks"])
            )
            state.update(m_c=mst["c"], m_n=mst["n"], m_m=mst["m"],
                         m_conv=mst["conv"],
                         s_c=ss["c"], s_n=ss["n"], s_m=ss["m"], s_h=ss["h"])
        if trailing:
            x, tst = jax.lax.scan(mbody, x, params["mlstm_trailing"])
            state.update(t_c=tst["c"], t_n=tst["n"], t_m=tst["m"],
                         t_conv=tst["conv"])
    elif cfg.family == "hybrid":
        x, state = Z.apply_zamba_prefill(
            cfg, shard, params, x, positions, max_len
        )
    else:
        raise NotImplementedError(cfg.family)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], x[:, -1:])
    return shard.logits(logits), state


# ---------------------------------------------------------------------------
# parameter counting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params(cfg: ArchConfig) -> int:
    import math

    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def active_params(cfg: ArchConfig) -> int:
    """Active parameters per token (MoE: only top_k + shared experts; where
    the chip holds some of the router's experts, the expected top_k x held
    / n_experts of them)."""
    total = count_params(cfg)
    if cfg.moe is None:
        return total
    moe = cfg.moe
    d = cfg.d_model
    per_expert = 3 * d * moe.d_expert
    n_moe_layers = cfg.n_layers - (1 if moe.first_layer_dense else 0)
    active = moe.top_k * moe.held / moe.n_experts
    inactive = n_moe_layers * (moe.held - active) * per_expert
    return total - round(inactive)

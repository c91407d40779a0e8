"""Sharding-aware model primitives.

Design rules:
* pure-functional: ``init_*`` returns a params pytree; ``*_specs`` returns a
  PartitionSpec pytree with IDENTICAL structure (checked in tests).
* compute dtype bf16, params bf16, reductions fp32 (norms / softmax / loss).
* TP follows Megatron conventions: attention column-parallel in heads
  (or head_dim for archs whose head count doesn't divide the axis), FFN
  column+row parallel, vocab column-parallel.
* FSDP shards the embed/ffn input dim over the dp axes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShardingPolicy, YarnConfig

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------

def _dp(policy: ShardingPolicy):
    """The axis (tuple) parameters get FSDP-sharded over, or None."""
    return policy.dp_axes if policy.fsdp else None


def dim_shardable(dim: int, axis_size: int) -> bool:
    return axis_size > 0 and dim % axis_size == 0


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), DTYPE)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), DTYPE)
    return p


def norm_specs(cfg: ArchConfig):
    p = {"scale": P(None)}
    if cfg.norm == "layernorm":
        p["bias"] = P(None)
    return p


def apply_norm(cfg: ArchConfig, params, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embedding
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor (hf modeling_deepseek ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attention_factor(yarn: Optional[YarnConfig]) -> float:
    """What YaRN multiplies the softmax scale by: mscale(mscale_all_dim)
    squared, or 1."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def rope_freqs(head_dim: int, theta: float,
               yarn: Optional[YarnConfig] = None) -> jax.Array:
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    # YaRN: the frequencies that turn fewer than beta_fast times over the
    # original context are divided by ``factor``; a linear ramp between the
    # correction dims of beta_fast and beta_slow blends the two
    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[YarnConfig] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, yarn)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv: int
    head_dim: int


def init_attention(key, cfg: ArchConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    dims = AttnDims(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, dims.n_heads, dims.head_dim)) * scale).astype(DTYPE),
        "wk": (jax.random.normal(k2, (d, dims.n_kv, dims.head_dim)) * scale).astype(DTYPE),
        "wv": (jax.random.normal(k3, (d, dims.n_kv, dims.head_dim)) * scale).astype(DTYPE),
        "wo": (jax.random.normal(k4, (dims.n_heads, dims.head_dim, d)) * scale).astype(DTYPE),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((dims.n_heads, dims.head_dim), DTYPE)
        p["bk"] = jnp.zeros((dims.n_kv, dims.head_dim), DTYPE)
        p["bv"] = jnp.zeros((dims.n_kv, dims.head_dim), DTYPE)
    if cfg.attn_out_bias:
        p["bo"] = jnp.zeros((d,), DTYPE)
    return p


def attention_specs(cfg: ArchConfig, policy: ShardingPolicy):
    m = policy.model_axis
    dp = _dp(policy)
    if policy.attn_mode == "heads":
        # padded-head mode: the PARAM head count doesn't divide the axis —
        # keep weights replicated on heads; the padded ACTIVATION shards.
        h_ax = None if policy.attn_pad_heads else m
        q_spec = P(dp, h_ax, None)
        kv_spec = P(dp, m if policy.shard_kv_heads else None, None)
        o_spec = P(h_ax, None, dp)
        bq = P(h_ax, None)
        bkv = P(m if policy.shard_kv_heads else None, None)
    else:  # head_dim sharding (e.g. qwen2-0.5b: 14 heads, 16-way axis)
        q_spec = P(dp, None, m)
        kv_spec = P(dp, None, m)
        o_spec = P(None, m, dp)
        bq = P(None, m)
        bkv = P(None, m)
    p = {"wq": q_spec, "wk": kv_spec, "wv": kv_spec, "wo": o_spec}
    if cfg.qkv_bias:
        p["bq"], p["bk"], p["bv"] = bq, bkv, bkv
    if cfg.attn_out_bias:
        p["bo"] = P(None)
    return p


def _pad_head_axis(w, axis: int, target: int, n_kv: int):
    """Zero-pad a weight's head axis to ``target`` PER KV GROUP (functional
    head padding: params keep the true head count; padded heads have zero
    weights so they contribute nothing through wo, but the head dim divides
    the model axis).

    Padding must preserve the head->kv-group mapping used by repeat_kv
    (heads are blocked group-major), so each group's block pads
    independently: (.., KV, H/KV, ..) -> pad -> (.., KV, target/KV, ..).
    """
    n = w.shape[axis]
    if target <= n:
        return w
    group = n // n_kv
    new_group = target // n_kv
    shape = w.shape
    wg = w.reshape(shape[:axis] + (n_kv, group) + shape[axis + 1 :])
    pads = [(0, 0)] * wg.ndim
    pads[axis + 1] = (0, new_group - group)
    wg = jnp.pad(wg, pads)
    return wg.reshape(shape[:axis] + (target,) + shape[axis + 1 :])


def qkv_project(cfg: ArchConfig, params, x, positions=None, shard=None):
    """x: (b, s, d) -> q (b,s,H[,pad],hd), k,v (b,s,KV,hd), RoPE applied."""
    pad = shard.policy.attn_pad_heads if shard is not None else 0
    kv = params["wk"].shape[1]
    wq = _pad_head_axis(params["wq"], 1, pad, kv) if pad else params["wq"]
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        bq = _pad_head_axis(params["bq"], 0, pad, kv) if pad else params["bq"]
        q = q + bq
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.use_rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if shard is not None:
        q = shard.heads(q)
    return q, k, v


def repeat_kv(k, n_heads: int):
    """(b, s, KV, hd) -> (b, s, H, hd).  A replicated->sharded slice under
    GSPMD (no reshape of a sharded head dim, which tiles badly when
    KV < model-axis size)."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


def gqa_attend(q, k, v, causal: bool, logit_softcap: float = 0.0,
               q_offset: jax.Array | int = 0):
    """Reference GQA attention (XLA path — the dry-run lowers this; the
    Pallas kernel in repro.kernels.flash_attention is the TPU-target twin).

    q: (b, sq, H, hd); k, v: (b, skv, KV, hd).  H % KV == 0.
    ``q_offset``: absolute position of q[0] (for causal masking vs a cache).
    """
    b, sq, h, hd = q.shape
    kf = repeat_kv(k, h)
    vf = repeat_kv(v, h)
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bshd->bhqs", q * scale, kf).astype(jnp.float32)
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv := k.shape[1])[None, :]
        mask = qpos >= kpos  # (sq, skv)
        logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, vf)
    return out


def attn_out(cfg: ArchConfig, params, ctx, shard=None):
    pad = shard.policy.attn_pad_heads if shard is not None else 0
    wo = (
        _pad_head_axis(params["wo"], 0, pad, cfg.n_kv_heads)
        if pad
        else params["wo"]
    )
    y = jnp.einsum("bshk,hkd->bsd", ctx, wo)
    if cfg.attn_out_bias:
        y = y + params["bo"]
    return y


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None,
             d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in, scale_out = d ** -0.5, f ** -0.5
    if cfg.activation == "swiglu":
        p = {
            "wi_gate": (jax.random.normal(k1, (d, f)) * scale_in).astype(DTYPE),
            "wi_up": (jax.random.normal(k2, (d, f)) * scale_in).astype(DTYPE),
            "wo": (jax.random.normal(k3, (f, d)) * scale_out).astype(DTYPE),
        }
    else:  # gelu
        p = {
            "wi_up": (jax.random.normal(k2, (d, f)) * scale_in).astype(DTYPE),
            "wo": (jax.random.normal(k3, (f, d)) * scale_out).astype(DTYPE),
        }
    if cfg.mlp_bias:
        p["bi"] = jnp.zeros((f,), DTYPE)
        p["bo"] = jnp.zeros((d,), DTYPE)
    return p


def mlp_specs(cfg: ArchConfig, policy: ShardingPolicy):
    m = policy.model_axis
    dp = _dp(policy)
    if cfg.activation == "swiglu":
        p = {"wi_gate": P(dp, m), "wi_up": P(dp, m), "wo": P(m, dp)}
    else:
        p = {"wi_up": P(dp, m), "wo": P(m, dp)}
    if cfg.mlp_bias:
        p["bi"] = P(m)
        p["bo"] = P(None)
    return p


def apply_mlp(cfg: ArchConfig, params, x):
    if cfg.activation == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"])
        u = jnp.einsum("bsd,df->bsf", x, params["wi_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = jnp.einsum("bsd,df->bsf", x, params["wi_up"])
        if cfg.mlp_bias:
            u = u + params["bi"]
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bsf,fd->bsd", h, params["wo"])
    if cfg.mlp_bias:
        y = y + params["bo"]
    return y


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ArchConfig):
    p = {
        "tokens": (
            jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(DTYPE)
    }
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["unembed"] = (
            jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
            * cfg.d_model ** -0.5
        ).astype(DTYPE)
    return p


def embedding_specs(cfg: ArchConfig, policy: ShardingPolicy):
    m = policy.model_axis if policy.shard_vocab else None
    dp = _dp(policy)
    p = {"tokens": P(m, dp)}
    if not cfg.tie_embeddings:
        p["unembed"] = P(dp, m)
    return p


def embed_tokens(params, tokens):
    return jnp.take(params["tokens"], tokens, axis=0)


def unembed(cfg: ArchConfig, params, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["tokens"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross entropy in fp32; labels already shifted."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

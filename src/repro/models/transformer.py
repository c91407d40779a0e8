"""Dense decoder-only transformer (internvl2 backbone, command-r-plus,
qwen2-0.5b, qwen2.5-14b, granite-34b) with:

* GQA / MQA attention, optional QKV bias, optional parallel attn+FFN block
  (Cohere), RMSNorm or LayerNorm, SwiGLU or GELU FFN;
* layer stacking via ``lax.scan`` + per-layer remat (keeps HLO small and
  compile time flat in depth);
* query-chunked attention on the XLA path so prefill at 32k never
  materializes a full (sq, skv) score tensor (the Pallas flash kernel is the
  TPU-target twin — same math, see repro.kernels.flash_attention);
* sequence-parallel activation constraints between blocks (policy.seq_shard).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShardingPolicy
from repro.models import layers as L
from repro.models.sharding import Shard

__all__ = [
    "init_block",
    "block_specs",
    "apply_block",
    "chunked_gqa_attend",
    "decode_attend",
]


# ---------------------------------------------------------------------------
# attention with query chunking (XLA path)
# ---------------------------------------------------------------------------

def chunked_gqa_attend(
    q, k, v, causal: bool, logit_softcap: float = 0.0, q_chunk: int = 512,
    q_offset: int = 0,
):
    """Full-row attention computed one query chunk at a time via lax.scan.

    Peak transient memory is O(b * h * q_chunk * skv) fp32 instead of
    O(b * h * sq * skv); numerics identical to the direct path (softmax rows
    are complete — no online rescaling needed).
    """
    b, sq, h, hd = q.shape
    if sq <= 2 * q_chunk or sq % q_chunk:
        return L.gqa_attend(q, k, v, causal, logit_softcap, q_offset)
    n_chunks = sq // q_chunk
    qc = q.reshape(b, n_chunks, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, args):
        i, qi = args
        out = L.gqa_attend(
            qi, k, v, causal, logit_softcap, q_offset=i * q_chunk + q_offset
        )
        return carry, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def decode_attend(q, k_cache, v_cache, cache_len, logit_softcap: float = 0.0):
    """One-token attention against a (possibly sharded) KV cache.

    q: (b, 1, H, hd); caches: (b, S_max, KV, hd); cache_len: scalar — number
    of valid positions (the new token's K/V already written at cache_len-1).
    Positions >= cache_len are masked.  When the cache's seq dim is sharded,
    GSPMD turns the row-softmax into a distributed (flash-decode style)
    max/sum combine.
    """
    b, sq, h, hd = q.shape
    _, smax, kv, _ = k_cache.shape
    kf = L.repeat_kv(k_cache, h)
    vf = L.repeat_kv(v_cache, h)
    logits = jnp.einsum(
        "bqhd,bshd->bhqs", q * hd ** -0.5, kf
    ).astype(jnp.float32)
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    mask = jnp.arange(smax)[None, None, None, :] < cache_len
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, vf)
    return out


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ArchConfig):
    ka, km, k1, k2 = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(ka, cfg),
        "mlp": L.init_mlp(km, cfg),
    }
    if not cfg.parallel_block:
        p["ln2"] = L.init_norm(cfg)
    return p


def block_specs(cfg: ArchConfig, policy: ShardingPolicy):
    p = {
        "ln1": L.norm_specs(cfg),
        "attn": L.attention_specs(cfg, policy),
        "mlp": L.mlp_specs(cfg, policy),
    }
    if not cfg.parallel_block:
        p["ln2"] = L.norm_specs(cfg)
    return p


def apply_block(
    cfg: ArchConfig,
    shard: Shard,
    params,
    x,
    positions,
    q_chunk: int = 512,
):
    """Training/prefill block.  x: (b, s, d)."""
    x = shard.activation(x)
    h1 = L.apply_norm(cfg, params["ln1"], x)
    h1_full = shard.full_seq(h1)  # all-gather seq if sequence-parallel
    q, k, v = L.qkv_project(cfg, params["attn"], h1_full, positions, shard)
    ctx = chunked_gqa_attend(
        q, k, v, causal=True, logit_softcap=cfg.logit_softcap, q_chunk=q_chunk
    )
    attn_y = L.attn_out(cfg, params["attn"], ctx, shard)
    # full-seq pins around weight matmuls (Megatron-SP order): the INPUT
    # gather makes forward weight contractions full-seq-local and — because
    # with_sharding_constraint pins the COTANGENT too — the output pin keeps
    # dy full-seq, so weight grads never psum over the model axis.  Gated by
    # policy.sp_weightgrad_fix (§Perf iterations 4-6).
    attn_y = shard.mm_boundary(attn_y)
    attn_y = shard.activation(attn_y)
    if cfg.parallel_block:
        mlp_y = shard.mm_boundary(L.apply_mlp(cfg, params["mlp"], h1_full))
        return x + attn_y + shard.activation(mlp_y)
    x = x + attn_y
    h2 = L.apply_norm(cfg, params["ln2"], x)
    mlp_y = shard.mm_boundary(
        L.apply_mlp(cfg, params["mlp"], shard.mm_input(h2))
    )
    return x + shard.activation(mlp_y)


def apply_block_decode(
    cfg: ArchConfig,
    shard: Shard,
    params,
    x,
    k_cache,
    v_cache,
    slot,
    cache_len,
    positions,
    ffn=None,
):
    """Single-token decode block.  x: (b, 1, d).

    ``k_cache``/``v_cache`` are the stacked caches of every layer,
    (n, b, S_max, kv, hd), carried whole through the caller's layer scan;
    ``slot`` is this layer's index in them.  The token's K/V is written in
    place at (slot, cache_len) and the block attends over the slot's
    ``cache_len + 1`` items, so no layer's cache is sliced out or restacked.
    ``ffn(params, h)`` is the feed-forward (default: the block's MLP).
    Returns (x_out, k_cache, v_cache).
    """
    h1 = L.apply_norm(cfg, params["ln1"], x)
    q, k, v = L.qkv_project(cfg, params["attn"], h1, positions, shard)
    at = (slot, 0, cache_len, 0, 0)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k[None].astype(k_cache.dtype), at
    )
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v[None].astype(v_cache.dtype), at
    )
    ck = shard.cache(jax.lax.dynamic_index_in_dim(k_cache, slot, keepdims=False))
    cv = shard.cache(jax.lax.dynamic_index_in_dim(v_cache, slot, keepdims=False))
    ctx = decode_attend(q, ck, cv, cache_len + 1, cfg.logit_softcap)
    attn_y = L.attn_out(cfg, params["attn"], ctx, shard)
    if ffn is None:
        ffn = lambda p, h: L.apply_mlp(cfg, p["mlp"], h)  # noqa: E731
    if cfg.parallel_block:
        return x + attn_y + ffn(params, h1), k_cache, v_cache
    x = x + attn_y
    h2 = L.apply_norm(cfg, params["ln2"], x)
    return x + ffn(params, h2), k_cache, v_cache

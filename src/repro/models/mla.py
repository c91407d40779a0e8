"""Multi-head latent attention (DeepSeek-V2) and the MoE stack it serves in.

Per position, one down-projection gives a latent ``c`` of ``kv_lora_rank``
(RMS-normed) and one rope key ``k_pe`` of ``qk_rope_head_dim`` shared by
all heads; each head's key is ``[c @ W_UK, k_pe]`` and its value
``c @ W_UV``.  The query is a plain projection to ``qk_nope_head_dim +
qk_rope_head_dim`` per head.  The cache holds ``[c, k_pe]`` per position
and layer: ``(L, b, S, kv_lora_rank + qk_rope_head_dim)``.

* Prefill and training use the expanded form: keys and values up-projected
  for every position, then ordinary causal attention.
* Decode is absorbed: ``W_UK`` is folded into the query (``q_nope @
  W_UK^T``, a query in latent space), the scores are taken against the
  latent cache directly, the weighted sum stays in latent space, and
  ``W_UV`` is applied once after attending; no step up-projects the
  context.

Rope follows hf ``modeling_deepseek``: each (even, odd) pair of the rope
dims is de-interleaved (evens first) before ``rotate_half``, at the
frequencies of YaRN (``layers.rope_freqs``); the softmax scale is
``(qk_nope + qk_rope) ** -0.5`` times YaRN's attention factor.

The stack: a dense layer 0 (SwiGLU) and MoE layers after it
(``moe.apply_moe``), all with this attention.  Named scopes ``mla.project``
and ``mla.attend`` mark the attention's device ops, beside the expert
layer's ``moe.*``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShardingPolicy
from repro.models import layers as L
from repro.models import moe as M
from repro.models.sharding import Shard

__all__ = [
    "init_attention",
    "attention_specs",
    "project",
    "attend_expanded",
    "attend_absorbed",
    "init_stack",
    "stack_specs",
    "forward",
    "decode",
    "cache_shape",
]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
                     m.v_head_dim)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(L.DTYPE)

    return {
        "wq": normal(k1, (d, h, dn + dr), d),
        "wkv_a": normal(k2, (d, r + dr), d),
        "kv_norm": L.init_norm(cfg, r),
        "wk_b": normal(k3, (r, h, dn), r),
        "wv_b": normal(k4, (r, h, dv), r),
        "wo": normal(k5, (h, dv, d), h * dv),
    }


def attention_specs(cfg: ArchConfig, policy: ShardingPolicy):
    """Heads over the model axis; the latent projection is replicated."""
    m, dp = policy.model_axis, L._dp(policy)
    return {
        "wq": P(dp, m, None),
        "wkv_a": P(dp, None),
        "kv_norm": L.norm_specs(cfg),
        "wk_b": P(None, m, None),
        "wv_b": P(None, m, None),
        "wo": P(m, None, dp),
    }


def _rope(cfg: ArchConfig, x, positions):
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return L.apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def softmax_scale(cfg: ArchConfig) -> float:
    m = cfg.mla
    return ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
            * L.yarn_attention_factor(cfg.rope_scaling))


def project(cfg: ArchConfig, p, x, positions):
    """x (b, s, d) -> q_nope (b, s, H, nope), q_pe (b, s, H, rope) roped,
    and the cache's entry (b, s, kv_lora_rank + rope): the normed latent
    and the roped shared key."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c = L.apply_norm(cfg, p["kv_norm"], kv[..., : m.kv_lora_rank])
    k_pe = _rope(cfg, kv[..., None, m.kv_lora_rank :], positions)[:, :, 0]
    return q_nope, _rope(cfg, q_pe, positions), jnp.concatenate([c, k_pe], -1)


def _softmax(logits, mask):
    """Scores (f32, already scaled) -> weights, masked where ``mask`` is
    false; the decode path's scores are float32 too, so that the two forms
    round alike."""
    return jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)


def attend_expanded(cfg: ArchConfig, p, q_nope, q_pe, latent):
    """Causal attention over the keys and values up-projected from
    ``latent`` (b, s, r + rope): (b, s, H, v_head_dim)."""
    r = cfg.mla.kv_lora_rank
    c, k_pe = latent[..., :r], latent[..., r:]
    k_nope = jnp.einsum("bsr,rhn->bshn", c, p["wk_b"])
    v = jnp.einsum("bsr,rhv->bshv", c, p["wv_b"])
    logits = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhp,bsp->bhqs", q_pe, k_pe,
                           preferred_element_type=jnp.float32))
    s = latent.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = _softmax(logits * softmax_scale(cfg), causal[None, None])
    return jnp.einsum("bhqs,bshv->bqhv", w.astype(v.dtype), v)


def attend_absorbed(cfg: ArchConfig, p, q_nope, q_pe, cache, length):
    """One query (b, 1, H, ·) against the latent cache (b, S, r + rope),
    positions >= ``length`` masked: (b, 1, H, v_head_dim)."""
    r = cfg.mla.kv_lora_rank
    q = jnp.concatenate(
        [jnp.einsum("bqhn,rhn->bqhr", q_nope, p["wk_b"]), q_pe], -1)
    logits = jnp.einsum("bqhc,bsc->bhqs", q, cache,
                        preferred_element_type=jnp.float32)
    mask = jnp.arange(cache.shape[1])[None, None, None, :] < length
    w = _softmax(logits * softmax_scale(cfg), mask)
    # over the whole entry, so that the cache is read as it lies; the rope
    # key's columns of the result are dropped
    ctx = jnp.einsum("bhqs,bsc->bqhc", w.astype(cache.dtype), cache)
    return jnp.einsum("bqhr,rhv->bqhv", ctx[..., :r], p["wv_b"])


def _out(p, ctx):
    return jnp.einsum("bshv,hvd->bsd", ctx, p["wo"])


# ---------------------------------------------------------------------------
# blocks and the stack
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, dense: bool):
    ka, kf = jax.random.split(key)
    p = {"ln1": L.init_norm(cfg), "attn": init_attention(ka, cfg),
         "ln2": L.init_norm(cfg)}
    if dense:
        p["mlp"] = L.init_mlp(kf, cfg)
    else:
        p["moe"] = M.init_moe(kf, cfg)
    return p


def init_stack(key, cfg: ArchConfig):
    """{"dense_block": layer 0, "blocks": the MoE layers, stacked}."""
    k0, kb = jax.random.split(key)
    keys = jax.random.split(kb, cfg.n_layers - 1)
    return {
        "dense_block": _init_block(k0, cfg, dense=True),
        "blocks": jax.vmap(lambda k: _init_block(k, cfg, dense=False))(keys),
    }


def stack_specs(cfg: ArchConfig, policy: ShardingPolicy):
    def block(ffn):
        return {"ln1": L.norm_specs(cfg), "attn": attention_specs(cfg, policy),
                "ln2": L.norm_specs(cfg), **ffn}

    moe = block({"moe": M.moe_specs(cfg, policy)})
    return {
        "dense_block": block({"mlp": L.mlp_specs(cfg, policy)}),
        "blocks": jax.tree.map(lambda s: P(None, *s), moe),
    }


def _ffn(cfg: ArchConfig, shard: Shard, lp, h):
    if "mlp" in lp:
        return L.apply_mlp(cfg, lp["mlp"], h), jnp.float32(0.0)
    return M.apply_moe(cfg, shard, lp["moe"], h)


def _block(cfg: ArchConfig, shard: Shard, lp, x, positions):
    """Training/prefill block: (x, cache entry (b, s, r + rope), aux)."""
    x = shard.activation(x)
    h1 = L.apply_norm(cfg, lp["ln1"], x)
    with jax.named_scope("mla.project"):
        q_nope, q_pe, latent = project(cfg, lp["attn"], h1, positions)
    with jax.named_scope("mla.attend"):
        x = x + _out(lp["attn"],
                     attend_expanded(cfg, lp["attn"], q_nope, q_pe, latent))
    y, aux = _ffn(cfg, shard, lp, L.apply_norm(cfg, lp["ln2"], x))
    return x + y, latent, aux


def _block_decode(cfg: ArchConfig, shard: Shard, lp, x, cache, cache_len,
                  positions):
    """One-token block: writes the token's entry at ``cache_len`` and
    attends over ``cache_len + 1`` positions.  Returns (x, cache)."""
    h1 = L.apply_norm(cfg, lp["ln1"], x)
    with jax.named_scope("mla.project"):
        q_nope, q_pe, latent = project(cfg, lp["attn"], h1, positions)
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, latent.astype(cache.dtype), cache_len, axis=1)
    with jax.named_scope("mla.attend"):
        x = x + _out(lp["attn"], attend_absorbed(
            cfg, lp["attn"], q_nope, q_pe, cache, cache_len + 1))
    y, _ = _ffn(cfg, shard, lp, L.apply_norm(cfg, lp["ln2"], x))
    return x + y, cache


def forward(cfg: ArchConfig, shard: Shard, params, x, positions,
            keep_cache: bool, wrap=lambda f: f):
    """The stack over x (b, s, d): (y, cache entries (L, b, s, r + rope)
    when ``keep_cache`` else None, aux loss).  ``wrap`` wraps the scanned
    MoE block (remat for training)."""
    x, lat0, aux = _block(cfg, shard, params["dense_block"], x, positions)

    def body(h, lp):
        h, lat, a = _block(cfg, shard, lp, h, positions)
        return h, (lat if keep_cache else None, a)

    x, (lats, auxs) = jax.lax.scan(wrap(body), x, params["blocks"])
    cache = jnp.concatenate([lat0[None], lats]) if keep_cache else None
    return x, cache, aux + auxs.sum()


def decode(cfg: ArchConfig, shard: Shard, params, x, cache, cache_len,
           positions):
    """One token through the stack against ``cache`` (L, b, S, r + rope):
    (y, updated cache)."""
    x, c0 = _block_decode(cfg, shard, params["dense_block"], x, cache[0],
                          cache_len, positions)

    def body(h, xs):
        lp, c = xs
        return _block_decode(cfg, shard, lp, h, c, cache_len, positions)

    x, cs = jax.lax.scan(body, x, (params["blocks"], cache[1:]))
    return x, jnp.concatenate([c0[None], cs])


def cache_shape(cfg: ArchConfig, batch: int, max_len: int):
    m = cfg.mla
    return (cfg.n_layers, batch, max_len, m.kv_lora_rank + m.qk_rope_head_dim)

"""Plain float32 forward pass of Qwen2 (hf ``Qwen/Qwen2-0.5B``).

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no KV cache, no batching tricks, every position of every
row computed from the tokens alone, layer by layer in a ``lax.scan``.  It
imports nothing of the program; it reads the plain weight layout of
``qwen2_0_5b_weights.py``.

Per layer, as published: x += o_proj(attn(rope(q_proj(n1)), rope(k_proj(n1)),
v_proj(n1))) with n1 = RMSNorm(x) and biases on q, k and v; then
x += down(silu(gate(n2)) * up(n2)) with n2 = RMSNorm(x).  Grouped-query
attention shares each of the KV heads among H / KV query heads; RoPE
rotates the two halves of each head (rotate_half) at base ``rope_theta``;
the output head is the tied embedding.  Departures from the published
model: none in the arithmetic; the weights are random (see the weights
module), and the forward pass keeps float32 where the published model
runs bfloat16.

``control_weights`` makes the control: every matrix rounded to int8 (or
fp8 e4m3) with one scale per output channel, the step below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools

import numpy as np


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h


@functools.lru_cache(maxsize=None)
def _forward_fn(shape_key: tuple, first: int):
    import jax
    import jax.numpy as jnp

    m = dict(shape_key)
    d, H, KV, hd = m["d"], m["H"], m["KV"], m["hd"]
    eps, theta = m["eps"], m["theta"]

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale

    def rope(x, pos):
        half = hd // 2
        freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
        ang = pos[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(x, w):
        b, s, _ = x.shape
        pos = jnp.arange(s, dtype=jnp.float32)
        n1 = rms(x, w["ln1"])
        q = (n1 @ w["wq"] + w["bq"]).reshape(b, s, H, hd)
        k = (n1 @ w["wk"] + w["bk"]).reshape(b, s, KV, hd)
        v = (n1 @ w["wv"] + w["bv"]).reshape(b, s, KV, hd)
        q = jax.vmap(rope, (0, None))(q, pos)
        k = jax.vmap(rope, (0, None))(k, pos)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, s, H * hd) @ w["wo"]
        n2 = rms(x, w["ln2"])
        x = x + (jax.nn.silu(n2 @ w["w_gate"]) * (n2 @ w["w_up"])) \
            @ w["w_down"]
        return x, None

    names = ("ln1", "ln2", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
             "w_gate", "w_up", "w_down")

    def forward(w, tokens):
        f32 = lambda a: a.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            embed = f32(w["embed"])
            x = embed[tokens]
            layers = {n: w[n] for n in names}
            x, _ = jax.lax.scan(
                lambda h, lw: layer(h, jax.tree.map(f32, lw)), x, layers)
            x = rms(x[:, first:], f32(w["final_norm"]))
            return x @ embed.T

    return jax.jit(forward)


def logits(w, tokens, cfg: dict, first: int):
    """float32 logits ``(rows, positions - first, vocab)`` at positions
    ``first`` onwards of ``tokens`` (rows, positions)."""
    d, H, KV, hd = _dims(cfg)
    key = (("d", d), ("H", H), ("KV", KV), ("hd", hd),
           ("eps", float(cfg["rms_norm_eps"])),
           ("theta", float(cfg["rope_theta"])))
    return _forward_fn(key, int(first))(w, tokens)


def _quantize(w, kind: str):
    """Round a (..., fan_in, fan_out) matrix to ``kind`` with one scale per
    output channel, and return it dequantized in float32."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if kind == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if kind == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown control precision {kind!r}")


def control_weights(w, kind: str):
    """The weights with every matrix rounded to ``kind`` (norm scales and
    biases, which a weight-only scheme keeps, stay as they are)."""
    out = dict(w)
    for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        out[n] = _quantize(w[n], kind)
    # the embedding is the output head too: one scale per vocabulary row
    out["embed"] = _quantize(w["embed"].T, kind).T
    return out


def served_gaps(ref_logits, served) -> np.ndarray:
    """How far below the reference's best logit each served token lies."""
    ref = np.asarray(ref_logits, dtype=np.float64)
    tok = np.asarray(served)
    best = ref.max(-1)
    picked = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return best - picked

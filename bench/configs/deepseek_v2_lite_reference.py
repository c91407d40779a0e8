"""Plain float32 forward pass of DeepSeek-V2-Lite (hf
``deepseek-ai/DeepSeek-V2-Lite``, ``modeling_deepseek.py``), as one chip's
share of the configuration's EP8 deployment.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no cache, no batching tricks, every position of every row
computed from the tokens alone.  It imports nothing of the program; it
reads the flat weight layout of ``deepseek_v2_lite_weights.py`` and
upcasts the weights to float32 one layer at a time inside its scan (the
bfloat16 weights fill half the chip; float32 copies of all of them would
not fit beside them).

Per layer, as published: x += o_proj(attn(n1)) with n1 = RMSNorm(x), in
the expanded form of multi-head latent attention: q = q_proj(n1) split
into q_nope and q_pe per head; [c, k_pe] = kv_a_proj_with_mqa(n1);
[k_nope, v] = kv_b_proj(RMSNorm(c)) per head; q_pe and k_pe (one key
shared by all heads) roped, each (even, odd) pair de-interleaved before
``rotate_half`` (``apply_rotary_pos_emb``), at the YaRN frequencies and
magnitude of ``DeepseekV2YarnRotaryEmbedding`` written out below; scores
times q_head_dim**-0.5 x mscale(mscale_all_dim)**2; causal softmax.  Then
x += ffn(RMSNorm(x)): layer 0 a SwiGLU MLP; the later layers the
DeepSeekMoE layer: a float32 softmax over all 64 router outputs, greedy
top-6, no renormalisation (``norm_topk_prob`` false,
``routed_scaling_factor`` 1), plus the shared experts (one SwiGLU of width
n_shared x f).  Of the routed experts, this chip's share: every held
expert computed for every token, weighted by its gate where it is among
the token's top 6 and by 0 elsewhere; what the experts of the other
chips would add is left out, as in the program.  The head is untied.
Departures from the published model: none in the arithmetic; the weights
are random (see the weights module), and the forward pass keeps float32
where the published model runs bfloat16.

``control_weights`` makes the control: every matrix but the router's
rounded to int8 (or fp8 e4m3) with one scale per output channel, the step
below the configuration's bfloat16 (a weight-only scheme keeps the router,
which picks the experts), held in bfloat16 so that it fits beside the
weights.
"""

from __future__ import annotations

import functools
import math

import numpy as np

_ATTN = ("ln1", "ln2", "wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo")
_MOE = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


def _yarn(cfg) -> tuple:
    """(inverse frequencies, cos/sin magnitude, softmax scale), as
    ``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention`` compute
    them."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def get_mscale(scale, mscale):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (factor * base ** exps)
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    magnitude = (get_mscale(factor, rs["mscale"])
                 / get_mscale(factor, rs["mscale_all_dim"]))
    m = get_mscale(factor, rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq.astype(np.float32), magnitude, scale


def _dims(cfg) -> tuple:
    ep = cfg["expert_parallel"]
    return (("H", cfg["num_attention_heads"]), ("r", cfg["kv_lora_rank"]),
            ("dn", cfg["qk_nope_head_dim"]), ("dr", cfg["qk_rope_head_dim"]),
            ("dv", cfg["v_head_dim"]), ("k", cfg["num_experts_per_tok"]),
            ("first", ep["rank"] * cfg["n_routed_experts"]),
            ("eps", float(cfg["rms_norm_eps"])))


@functools.lru_cache(maxsize=None)
def _forward_fn(shape_key: tuple, yarn_key: tuple, first_pos: int):
    import jax
    import jax.numpy as jnp

    m = dict(shape_key)
    H, r, dn, dr, dv, k = (m[n] for n in ("H", "r", "dn", "dr", "dv", "k"))
    inv_freq, magnitude, scale = yarn_key
    inv_freq = np.asarray(inv_freq, np.float32)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m["eps"]) * w

    def rope(x, pos):
        # x (b, s, h, dr): pairs de-interleaved, then rotate_half
        b, s, h, d = x.shape
        x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
        freqs = pos[:, None] * inv_freq[None, :]
        emb = jnp.concatenate([freqs, freqs], -1)
        cos = (jnp.cos(emb) * magnitude)[None, :, None, :]
        sin = (jnp.sin(emb) * magnitude)[None, :, None, :]
        rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
        return x * cos + rot * sin

    def attention(x, w):
        b, s, d = x.shape
        pos = jnp.arange(s, dtype=jnp.float32)
        q = (x @ w["wq"].reshape(d, -1)).reshape(b, s, H, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        ckv = x @ w["wkv_a"]
        c, k_pe = rms(ckv[..., :r], w["kv_norm"]), ckv[..., r:]
        k_nope = (c @ w["wk_b"].reshape(r, -1)).reshape(b, s, H, dn)
        v = (c @ w["wv_b"].reshape(r, -1)).reshape(b, s, H, dv)
        q_pe = rope(q_pe, pos)
        k_pe = jnp.broadcast_to(rope(k_pe[:, :, None], pos), (b, s, H, dr))
        qs = jnp.concatenate([q_nope, q_pe], -1)
        ks = jnp.concatenate([k_nope, k_pe], -1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qs, ks) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return att.reshape(b, s, H * dv) @ w["wo"].reshape(H * dv, d)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def moe(x, w):
        probs = jax.nn.softmax(x @ w["router"], -1)  # (b, s, 64)
        top, ids = jax.lax.top_k(probs, k)
        n = w["e_gate"].shape[0]
        held = m["first"] + jnp.arange(n)
        # the gate of each held expert: its probability where it is among
        # the token's top k, else 0 (top k weights are not renormalised)
        gate = jnp.sum(jnp.where(ids[..., None] == held, top[..., None], 0.0),
                       axis=-2)  # (b, s, n)
        h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, w["e_gate"])) \
            * jnp.einsum("bsd,edf->bsef", x, w["e_up"])
        routed = jnp.einsum("bsef,efd->bsd", h * gate[..., None],
                            w["e_down"])
        return routed + swiglu(x, w["s_gate"], w["s_up"], w["s_down"])

    def layer(x, w, ffn):
        x = x + attention(rms(x, w["ln1"]), w)
        return x + ffn(rms(x, w["ln2"]), w)

    def dense_ffn(x, w):
        return swiglu(x, w["w_gate"], w["w_up"], w["w_down"])

    def forward(w, tokens):
        f32 = lambda a: a.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            x = f32(w["embed"][tokens])
            dense = {n: f32(w[f"dense.{n}"])
                     for n in _ATTN + ("w_gate", "w_up", "w_down")}
            x = layer(x, dense, dense_ffn)
            stacked = {n: w[n] for n in _ATTN + _MOE}
            x, _ = jax.lax.scan(
                lambda h, lw: (layer(h, jax.tree.map(f32, lw), moe), None),
                x, stacked)
            x = rms(x[:, first_pos:], f32(w["final_norm"]))
            return x @ f32(w["unembed"])

    return jax.jit(forward)


def logits(w, tokens, cfg: dict, first: int):
    """float32 logits ``(rows, positions - first, vocab)`` at positions
    ``first`` onwards of ``tokens`` (rows, positions)."""
    inv_freq, magnitude, scale = _yarn(cfg)
    yarn_key = (tuple(float(f) for f in inv_freq), float(magnitude),
                float(scale))
    return _forward_fn(_dims(cfg), yarn_key, int(first))(w, tokens)


def _round(w, kind: str):
    """Round a (fan_in, fan_out) matrix to ``kind`` with one scale per
    output channel, in float32."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    if kind == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if kind == "fp8":
        import jax

        scale = jnp.maximum(amax, 1e-30) / 448.0
        # the fp8 values held in their own type between two fusions: within
        # one, the TPU compiler keeps the convert's result at a higher
        # precision and rounds nothing
        q = jax.lax.optimization_barrier((w / scale).astype(jnp.float8_e4m3fn))
        return q.astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {kind!r}")


@functools.lru_cache(maxsize=None)
def _quantizer(shape: tuple, dtype: str, n_in: int, n_out: int, kind: str):
    """A jitted rounding of one weight of ``shape``: its last ``n_in``
    + ``n_out`` axes a matrix, any leading (stacking) axes mapped one
    slice at a time, so that the float32 temporaries stay a layer's."""
    import jax
    import jax.numpy as jnp

    lead = len(shape) - n_in - n_out
    fan_in = int(np.prod(shape[lead:lead + n_in]))

    def one(w):
        q = _round(w.astype(jnp.float32).reshape(fan_in, -1), kind)
        return q.reshape(w.shape).astype(w.dtype)

    def rnd(w):
        f = one
        for _ in range(lead):
            f = functools.partial(jax.lax.map, f)
        return f(w)

    return jax.jit(rnd)


def _quantize(w, name: str, kind: str):
    n_in, n_out = _FAN[name.rsplit(".", 1)[-1]]
    return _quantizer(tuple(w.shape), str(w.dtype), n_in, n_out, kind)(w)


# (fan-in axes, fan-out axes) at the end of each matrix
_FAN = {"wq": (1, 2), "wkv_a": (1, 1), "wk_b": (1, 2), "wv_b": (1, 2),
        "wo": (2, 1), "w_gate": (1, 1), "w_up": (1, 1), "w_down": (1, 1),
        "e_gate": (1, 1), "e_up": (1, 1), "e_down": (1, 1), "s_gate": (1, 1),
        "s_up": (1, 1), "s_down": (1, 1), "unembed": (1, 1)}


def control_weights(w, kind: str):
    """The weights with every matrix but the router rounded to ``kind``
    and held in their own type (bfloat16, which carries fp8 and int8
    values times a scale with little more rounding), so that they fit
    beside the weights; norm scales and the router stay as they are, and
    the embedding, a lookup, is rounded per row."""
    out = dict(w)
    for name in w:
        if name.rsplit(".", 1)[-1] in _FAN:
            out[name] = _quantize(w[name], name, kind)
    out["embed"] = _quantize(w["embed"].T, "unembed", kind).T
    return out


def served_gaps(ref_logits, served) -> np.ndarray:
    """How far below the reference's best logit each served token lies."""
    ref = np.asarray(ref_logits, dtype=np.float64)
    tok = np.asarray(served)
    best = ref.max(-1)
    picked = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return best - picked

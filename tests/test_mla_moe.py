"""DeepSeek-V2-Lite's mechanisms at tiny sizes on seeded random weights:
latent attention (absorbed decode against the expanded form), YaRN rope
against a transcription of the source's formulas, the dropless held-expert
layer (its expert-parallel shares, drops, renormalisation), and prefill
then decode through the latent cache against the full forward pass."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, get_config, reduced_config
from repro.models import Shard, decode_step, init_params, prefill
from repro.models import layers as L
from repro.models import lm as LM
from repro.models import mla as MLA
from repro.models.moe import apply_moe, init_moe

KEY = jax.random.PRNGKey(0)
ARCHS = ["deepseek-v2-lite", "deepseek-v2-lite-ep8"]


@pytest.fixture
def float32(monkeypatch):
    """The model's parameters, activations and cache in float32."""
    monkeypatch.setattr(L, "DTYPE", jnp.float32)


def _tiny(arch="deepseek-v2-lite"):
    return reduced_config(get_config(arch))


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def test_absorbed_decode_equals_expanded_attention(float32):
    cfg = _tiny()
    p = MLA.init_attention(jax.random.PRNGKey(1), cfg)
    s, smax = 12, 20
    x = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model))
    q_nope, q_pe, latent = MLA.project(cfg, p, x, jnp.arange(s))
    want = MLA.attend_expanded(cfg, p, q_nope, q_pe, latent)
    # the cache past the attended length holds garbage, which the mask hides
    cache = jnp.concatenate(
        [latent, 1e3 * jnp.ones((2, smax - s, latent.shape[-1]))], axis=1)
    for t in (0, 5, s - 1):
        got = MLA.attend_absorbed(cfg, p, q_nope[:, t:t + 1], q_pe[:, t:t + 1],
                                  cache, t + 1)
        # the same sums in another order: float32 rounding only
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, t]), rtol=1e-4,
                                   atol=1e-5)


def test_rope_pairs_are_deinterleaved_before_rotate_half(float32):
    """modeling_deepseek's layout: (x0, x1), (x2, x3), ... become the
    halves (x0, x2, ...) and (x1, x3, ...) of rotate_half."""
    cfg = _tiny()
    dr = cfg.mla.qk_rope_head_dim
    x = jax.random.normal(KEY, (1, 3, 1, dr))
    pos = jnp.arange(3)
    got = MLA._rope(cfg, x, pos)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want = L.apply_rope(halves, pos, cfg.rope_theta, cfg.rope_scaling)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _source_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                 mscale_all_dim):
    """DeepseekV2YarnRotaryEmbedding and DeepseekV2Attention, line by line
    (hf modeling_deepseek.py), in float64 numpy."""

    def yarn_find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
        low = math.floor(yarn_find_correction_dim(low_rot, dim, base,
                                                  max_pos))
        high = math.ceil(yarn_find_correction_dim(high_rot, dim, base,
                                                  max_pos))
        return max(low, 0), min(high, dim - 1)

    def yarn_get_mscale(scale=1, mscale=1):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    def yarn_linear_ramp_mask(min, max, dim):
        if min == max:
            max += 0.001
        return np.clip((np.arange(dim) - min) / (max - min), 0, 1)

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    low, high = yarn_find_correction_range(beta_fast, beta_slow, dim, base,
                                           orig)
    inv_freq_mask = 1.0 - yarn_linear_ramp_mask(low, high, dim // 2)
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    cos_sin = yarn_get_mscale(factor, mscale) / yarn_get_mscale(
        factor, mscale_all_dim)
    m = yarn_get_mscale(factor, mscale_all_dim) if mscale_all_dim else 1.0
    return inv_freq, cos_sin, m * m


@pytest.mark.parametrize("mscale", [0.707, 1.0])
def test_yarn_matches_the_source(mscale):
    cfg = get_config("deepseek-v2-lite")
    y = dataclasses.replace(cfg.rope_scaling, mscale=mscale)
    dim = cfg.mla.qk_rope_head_dim
    inv_freq, cos_sin, attn = _source_yarn(
        dim, cfg.rope_theta, y.factor, y.original_max_position_embeddings,
        y.beta_fast, y.beta_slow, y.mscale, y.mscale_all_dim)
    # float32 powers and quotients against float64
    np.testing.assert_allclose(np.asarray(L.rope_freqs(dim, cfg.rope_theta, y)),
                               inv_freq, rtol=1e-6)
    assert L.yarn_attention_factor(y) == pytest.approx(attn, rel=1e-12)
    # the published settings: cos/sin unscaled, softmax scale x ~1.590
    if mscale == 0.707:
        assert cos_sin == 1.0
        assert attn == pytest.approx((0.1 * 0.707 * math.log(40) + 1) ** 2)
        assert MLA.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * attn)
    # rope at a few positions, with the magnitude cos_sin
    x = jax.random.normal(KEY, (1, 4, 1, dim))
    pos = np.array([0, 1, 4095, 40_000])
    got = np.asarray(L.apply_rope(x, jnp.asarray(pos), cfg.rope_theta, y))
    ang = pos[:, None] * inv_freq[None, :]
    cos, sin = (np.cos(ang) * cos_sin)[None, :, None], (np.sin(ang)
                                                         * cos_sin)[None, :, None]
    x1, x2 = np.split(np.asarray(x, np.float64), 2, axis=-1)
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # float32 angles at position 40,000: |angle| up to 4e4, so a relative
    # 6e-8 of it moves cos and sin by up to 2.4e-3
    np.testing.assert_allclose(got, want, atol=5e-3)


# ---------------------------------------------------------------------------
# the held-expert layer
# ---------------------------------------------------------------------------

def _moe_cfg(**moe):
    cfg = reduced_config(get_config("deepseek-v2-lite"))
    base = MoEConfig(n_experts=64, top_k=6, d_expert=16, n_shared=2,
                     first_layer_dense=True, renormalize=False,
                     dropless=True)
    return dataclasses.replace(cfg, d_model=32,
                               moe=dataclasses.replace(base, **moe))


def _f64(a):
    return np.asarray(a, np.float64)


def _swiglu(x, gate, up, down):
    g = x @ _f64(gate)
    return (g / (1 + np.exp(-g)) * (x @ _f64(up))) @ _f64(down)


def _shared(p, x):
    xt = _f64(x).reshape(-1, x.shape[-1])
    sh = p["shared"]
    return _swiglu(xt, sh["wi_gate"], sh["wi_up"], sh["wo"]).reshape(x.shape)


def _naive(cfg, p, x):
    """Per token: softmax over every router output, top k, the held
    experts' weighted SwiGLUs (float64 numpy) plus the shared experts."""
    moe = cfg.moe
    xt = _f64(x).reshape(-1, x.shape[-1])
    logits = xt @ _f64(p["router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[: moe.top_k]
        w = probs[t, top]
        if moe.renormalize:
            w = w / w.sum()
        for e, we in zip(top, w):
            if e < moe.held:
                out[t] += we * _swiglu(xt[t], p["wi_gate"][e],
                                       p["wi_up"][e], p["wo"][e])
    return out.reshape(x.shape) + _shared(p, x)


def test_eight_shares_sum_to_the_uncut_layer(float32):
    """Each of 8 chips holds 8 of the 64 experts and passes on its own
    experts' part plus the shared experts; the shares, with the shared
    experts counted once, add up to the whole layer.  Rank r's share is
    the layer that holds the first 8 experts of a router whose outputs are
    rolled by 8 r (the same experts, numbered from rank r's first)."""
    full = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(3), full)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, full.d_model))
    whole, _ = apply_moe(full, Shard.local(), p, x)
    np.testing.assert_allclose(np.asarray(whole), _naive(full, p, x),
                               rtol=1e-4, atol=1e-5)
    shared = _shared(p, x)
    parts = []
    cfg = _moe_cfg(n_held=8)
    for rank in range(8):
        held = slice(8 * rank, 8 * rank + 8)
        share = {**p, "router": jnp.roll(p["router"], -8 * rank, axis=1),
                 "wi_gate": p["wi_gate"][held], "wi_up": p["wi_up"][held],
                 "wo": p["wo"][held]}
        y, _ = apply_moe(cfg, Shard.local(), share, x)
        np.testing.assert_allclose(np.asarray(y), _naive(cfg, share, x),
                                   rtol=1e-4, atol=1e-5)
        parts.append(np.asarray(y) - shared)
    # float32 sums in another order
    np.testing.assert_allclose(sum(parts) + shared, np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_held_layer_drops_nothing_and_does_not_renormalise(float32):
    cfg = _moe_cfg(n_held=8)
    p = init_moe(jax.random.PRNGKey(5), cfg)
    # every token's largest router output is expert 3's: 48 tokens on one
    # expert, six times what an even split gives an expert here
    router = p["router"].at[0].set(0.0).at[0, 3].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 12, cfg.d_model))
    x = x.at[..., 0].set(6.0)
    p = {**p, "router": router}
    y, _ = apply_moe(cfg, Shard.local(), p, x)
    want = _naive(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ p["router"], -1)
    assert bool((jnp.argmax(probs, -1) == 3).all())
    # the top 6 weights sum to less than 1, and the output keeps them so
    top = jax.lax.top_k(probs, cfg.moe.top_k)[0].sum(-1)
    assert float(top.max()) < 0.999
    renorm = _naive(dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, renormalize=True)), p, x)
    assert np.abs(renorm - want).max() > 1e-3


# ---------------------------------------------------------------------------
# the model: prefill then decode through the latent cache
# ---------------------------------------------------------------------------

def _forward_and_served(cfg, params, tokens, prompt):
    """Logits of the full forward pass at positions prompt - 1 onwards, and
    those of prefill(prompt) then decode_step over the rest."""
    shard = Shard.local()

    @jax.jit
    def forward(params, tokens):
        x, pos, _ = LM._embed_inputs(cfg, shard, params, {"tokens": tokens})
        xb, _ = LM._backbone(cfg, shard, params, x, pos)
        return L.unembed(cfg, params["embed"],
                         L.apply_norm(cfg, params["final_norm"], xb))

    step = jax.jit(lambda p, s, t, c: decode_step(cfg, shard, p, s, t, c))
    logits, state = jax.jit(lambda p, t: prefill(
        cfg, shard, p, {"tokens": t}, max_len=24))(params, tokens[:, :prompt])
    got = [logits[:, -1]]
    for i in range(prompt, tokens.shape[1]):
        logits, state = step(params, state, tokens[:, i:i + 1], jnp.int32(i))
        got.append(logits[:, -1])
    f = lambda a: np.asarray(a, np.float64)
    return (f(forward(params, tokens)[:, prompt - 1:]),
            f(jnp.stack(got, axis=1)))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_match_the_forward_pass(float32, arch):
    """In float32, where the expanded and the absorbed forms are the same
    sums in another order.  (In bfloat16 they round differently, and the
    router may then pick another expert near a tie; the served bfloat16
    path is held to the float32 reference on the chip by the benchmark's
    check.)"""
    cfg = _tiny(arch)
    tokens = jax.random.randint(KEY, (2, 14), 0, cfg.vocab_size)
    params = init_params(KEY, cfg)
    assert params["blocks"]["moe"]["wi_gate"].dtype == jnp.float32
    want, got = _forward_and_served(cfg, params, tokens, 8)
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std())


def test_decode_program_carries_the_named_scopes():
    cfg = _tiny("deepseek-v2-lite-ep8")
    params = jax.eval_shape(lambda: init_params(KEY, cfg))
    state = LM.decode_state_shapes(cfg, 2, 16)
    sds = jax.ShapeDtypeStruct
    text = jax.jit(
        lambda p, s, t, c: decode_step(cfg, Shard.local(), p, s, t, c)
    ).lower(params, state, sds((2, 1), jnp.int32),
            sds((), jnp.int32)).as_text(debug_info=True)
    for scope in ("mla.project", "mla.attend", "moe.route", "moe.experts",
                  "moe.shared"):
        assert scope in text, scope


def test_latent_cache_is_one_entry_per_position_and_layer():
    cfg = get_config("deepseek-v2-lite-ep8")
    shapes = LM.decode_state_shapes(cfg, 8, 1152)
    assert {k: v.shape for k, v in shapes.items()} == {
        "kv": (27, 8, 1152, 576)}
    assert shapes["kv"].dtype == jnp.bfloat16

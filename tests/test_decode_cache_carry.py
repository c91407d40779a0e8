"""The decode step's in-place cache carry against the per-layer formulation.

``lm.decode_step`` (and the hybrid family's shared attention) carries the
stacked KV caches whole through the layer scan and writes each layer's one
entry in place.  The reference here is the formulation it replaced: each
layer's cache sliced out as a scan input, written with
``dynamic_update_slice_in_dim``, attended over and restacked as a scan
output.  Same operations in the same order, so the logits and the state
agree bit for bit, step after step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import Shard, decode_step, init_decode_state, init_params
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import transformer as T

B, MAX_LEN, START, STEPS = 2, 16, 5, 4


def _ref_block(cfg, shard, p, x, ck, cv, cache_len, positions, ffn):
    """One layer on its own (b, S_max, kv, hd) cache slice."""
    h1 = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.qkv_project(cfg, p["attn"], h1, positions, shard)
    ck = jax.lax.dynamic_update_slice_in_dim(
        ck, k.astype(ck.dtype), cache_len, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cv, v.astype(cv.dtype), cache_len, axis=1)
    ck, cv = shard.cache(ck), shard.cache(cv)
    ctx = T.decode_attend(q, ck, cv, cache_len + 1, cfg.logit_softcap)
    attn_y = L.attn_out(cfg, p["attn"], ctx, shard)
    if cfg.parallel_block:
        return x + attn_y + ffn(p, h1), ck, cv
    x = x + attn_y
    return x + ffn(p, L.apply_norm(cfg, p["ln2"], x)), ck, cv


def _ref_decode(cfg, shard, params, state, token, cache_len):
    """The per-layer formulation: caches as scan inputs and outputs."""
    x = L.embed_tokens(params["embed"], token)
    positions = cache_len + jnp.zeros((1,), jnp.int32)

    def ffn(p, h):
        if "moe" in p:
            return M.apply_moe(cfg, shard, p["moe"], h)[0]
        return L.apply_mlp(cfg, p["mlp"], h)

    def block(p, h, ck, cv):
        return _ref_block(cfg, shard, p, h, ck, cv, cache_len, positions, ffn)

    def mamba(h, xs):
        p, ssm, conv = xs
        h, ns = SSM.apply_mamba2_decode(
            cfg, shard, p, h, {"ssm": ssm, "conv": conv})
        return h, (ns["ssm"], ns["conv"])

    if cfg.family == "hybrid":
        def segment(h, xs):
            p, ssm, conv, ck, cv = xs
            h, (ssm, conv) = jax.lax.scan(mamba, h, (p, ssm, conv))
            h, ck, cv = block(params["shared_attn"], h, ck, cv)
            return h, (ssm, conv, ck, cv)

        new = dict(state)
        x, (new["seg_ssm"], new["seg_conv"], new["attn_k"],
            new["attn_v"]) = jax.lax.scan(segment, x, (
                params["mamba_segments"], state["seg_ssm"],
                state["seg_conv"], state["attn_k"], state["attn_v"]))
        if "trail_ssm" in state:
            x, (new["trail_ssm"], new["trail_conv"]) = jax.lax.scan(
                mamba, x, (params["mamba_trailing"], state["trail_ssm"],
                           state["trail_conv"]))
    else:
        def layer(h, xs):
            p, ck, cv = xs
            h, ck, cv = block(p, h, ck, cv)
            return h, (ck, cv)

        k, v = state["k"], state["v"]
        if cfg.family == "moe" and cfg.moe.first_layer_dense:
            x, k0, v0 = block(params["dense_block"], x, k[0], v[0])
            x, (k, v) = jax.lax.scan(layer, x, (params["blocks"], k[1:], v[1:]))
            k = jnp.concatenate([k0[None], k])
            v = jnp.concatenate([v0[None], v])
        else:
            x, (k, v) = jax.lax.scan(layer, x, (params["blocks"], k, v))
        new = {"k": k, "v": v}
    x = L.apply_norm(cfg, params["final_norm"], x)
    return shard.logits(L.unembed(cfg, params["embed"], x)), new


@pytest.mark.parametrize("arch", [
    "qwen2-0.5b",           # dense
    "command-r-plus-104b",  # dense, parallel attention and MLP
    "olmoe-1b-7b",          # moe
    "deepseek-moe-16b",     # moe with a dense first layer in slot 0
    "internvl2-76b",        # vlm
    "zamba2-7b",            # hybrid: the shared attention's caches
])
def test_decode_carry_matches_per_layer_reference(arch):
    cfg = reduced_config(get_config(arch))
    key = jax.random.PRNGKey(7)
    params = init_params(key, cfg)
    shard = Shard.local()
    zeros = init_decode_state(cfg, B, MAX_LEN)
    # a filled cache, so that every step attends over real entries
    keys = jax.random.split(key, len(jax.tree.leaves(zeros)))
    state = jax.tree.unflatten(jax.tree.structure(zeros), [
        jax.random.normal(k, z.shape, jnp.float32).astype(z.dtype)
        for k, z in zip(keys, jax.tree.leaves(zeros))])

    step = jax.jit(lambda p, s, t, c: decode_step(cfg, shard, p, s, t, c))
    ref = jax.jit(lambda p, s, t, c: _ref_decode(cfg, shard, p, s, t, c))
    tok = jnp.ones((B, 1), jnp.int32)
    got, want = state, state
    for c in range(START, START + STEPS):
        lg, got = step(params, got, tok, jnp.int32(c))
        lw, want = ref(params, want, tok, jnp.int32(c))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)

"""DeepSeek-V2-Lite's reference and check, on the CPU at tiny sizes.

The float32 reference forward pass against ``repro.models`` (``prefill``,
then ``decode_step`` through the latent cache) on the same weights, all in
float32, where the program's expanded prefill and absorbed decode are the
reference's sums in another order.  Then ``correct`` must come out false
for the control (the reference with fp8 weights choosing the tokens) and
for a decode step that leaves its latent cache unchanged, with the rest of
a run driven as on the chip (the harness's look for a chip skipped).
"""

import dataclasses

import numpy as np
import pytest

from harness import runner, spec

import tiny

CELL = "deepseek-v2-lite.chat"
CFG = spec.load_json("configs/deepseek-v2-lite.json")

# every width cut, the router's 64 outputs, top 6 and the held share kept
TINY = dict(CFG, hidden_size=64, intermediate_size=96, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=24, vocab_size=300)


def _arch(cfg):
    from repro.configs import MLAConfig, get_config

    base = get_config(CFG["deployment"]["arch"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mla=MLAConfig(cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"]),
        moe=dataclasses.replace(base.moe,
                                d_expert=cfg["moe_intermediate_size"]))


@pytest.fixture(scope="module")
def weights():
    return spec.load_module(CFG["weights"])


def test_weights_match_the_program_layout(weights):
    import jax

    from repro.models import init_params

    ours = weights.to_engine(weights.make(7, TINY), TINY)
    theirs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                _arch(TINY)))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(theirs)


def test_arch_is_the_programs_model(weights):
    arch = weights.arch(CFG)
    assert (arch.moe.n_experts, arch.moe.held, arch.moe.top_k) == (64, 8, 6)
    with pytest.raises(ValueError, match="configuration states"):
        weights.arch(dict(CFG, kv_lora_rank=256))
    with pytest.raises(ValueError, match="states moe.renormalize=True"):
        weights.arch(dict(CFG, norm_topk_prob=True))


def test_prefill_and_decode_match_the_reference(monkeypatch, weights):
    import jax
    import jax.numpy as jnp

    from repro.models import Shard, decode_step, prefill
    from repro.models import layers as L

    monkeypatch.setattr(L, "DTYPE", jnp.float32)  # the latent cache too
    ref = spec.load_module(CFG["reference"])
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       weights.make(7, TINY))
    params = weights.to_engine(w32, TINY)
    cfg = _arch(TINY)
    tokens = np.random.default_rng(0).integers(0, TINY["vocab_size"],
                                               (2, 12)).astype(np.int32)
    prompt = 8
    want = np.asarray(ref.logits(w32, tokens, TINY, prompt - 1))
    shard = Shard.local()
    logits, state = prefill(cfg, shard, params,
                            {"tokens": tokens[:, :prompt]}, max_len=16)
    got = [np.asarray(logits[:, -1])]
    for i in range(prompt, tokens.shape[1]):
        logits, state = decode_step(cfg, shard, params, state,
                                    jnp.asarray(tokens[:, i:i + 1]),
                                    jnp.int32(i))
        got.append(np.asarray(logits[:, -1]))
    got = np.stack(got, axis=1)
    # float32 sums in other orders (and the program's precision is the
    # backend's default, the reference's the highest): rounding only; a
    # wrong rope layout, scale, mask, expert or cache entry moves the
    # logits by a good part of their spread
    scale = float(np.std(want))
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_control_weights_round_matrices_only(weights):
    w = weights.make(7, TINY)
    ref = spec.load_module(CFG["reference"])
    for kind, step in (("int8", 1 / 127), ("fp8", 1 / 8)):
        low = ref.control_weights(w, kind)
        assert np.array_equal(np.asarray(low["router"]),
                              np.asarray(w["router"]))
        assert np.array_equal(np.asarray(low["kv_norm"]),
                              np.asarray(w["kv_norm"]))
        for name in ("e_up", "wq", "wo", "unembed"):
            a = np.asarray(w[name], np.float32)
            b = np.asarray(low[name], np.float32)
            assert b.dtype == a.dtype and b.shape == a.shape
            assert 0 < np.abs(a - b).max() <= (step + 2 ** -8) * np.abs(
                a).max()


def _run(driver=None, seed=2**31 + 5):
    cell = spec.load_cell(CELL).replace(**tiny.overrides(CELL))
    return runner.run_cell(cell, seed=seed, seconds=1.0, traced=False,
                           start=0.0, require_chip=False, driver=driver)


class _Control:
    """The cell's driver with the control in the program's place."""

    def __init__(self, control):
        self.mod = spec.load_module("drivers/serve.py")
        self.control = control

    def __getattr__(self, attr):
        return getattr(self.mod, attr)

    def check(self, st, run):
        return self.mod.check(st, run, control=self.control)


def test_fp8_control_is_not_correct(monkeypatch):
    tiny.serve_this_model(monkeypatch, CELL)
    res = _run(driver=_Control("fp8"))
    assert res["correct"] is False, res["checks"]
    checks = res["checks"]
    assert checks["served_logit_gap"]["value"] > checks["served_logit_gap"][
        "limit"]


def test_stale_latent_cache_is_not_correct(monkeypatch):
    """A decode step that serves its token but returns the latent cache it
    was given: later tokens attend to a context without the generated
    ones."""
    from repro.serving import ReplicatedServingEngine

    tiny.serve_this_model(monkeypatch, CELL)
    real_init = ReplicatedServingEngine.__init__

    def init(self, sc):
        real_init(self, sc)
        decode = self._decode

        def stale_decode(p, state, tok, pos):
            out, _ = decode(p, state, tok, pos)
            return out, state

        self._decode = stale_decode

    monkeypatch.setattr(ReplicatedServingEngine, "__init__", init)
    res = _run()
    assert res["correct"] is False, res["checks"]

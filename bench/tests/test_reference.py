"""The plain references against the program, on the CPU at tiny sizes.

qwen2: the float32 reference forward pass against ``repro.models``
(``prefill``, then ``decode_step`` through the KV cache) on the same
weights, held in float32 so that only the program's bfloat16 KV cache
rounds.  The planner: the float64 reference of the engine's initial plan
against the program's numpy float64 sojourn sweep, which must agree to
rounding.
"""

import numpy as np
import pytest

from harness import spec

TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 300, "rms_norm_eps": 1e-6,
        "rope_theta": 1_000_000.0, "tie_word_embeddings": True}


def _arch(cfg):
    from repro.configs import get_config
    import dataclasses

    return dataclasses.replace(
        get_config("qwen2-0.5b"), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"])


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    weights = spec.load_module("configs/qwen2_0_5b_weights.py")
    w = weights.make(7, TINY)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    return w, w32, weights.to_engine(w32, TINY)


def test_weights_match_the_program_layout(model):
    import jax

    from repro.models import init_params

    w, _, _ = model
    weights = spec.load_module("configs/qwen2_0_5b_weights.py")
    ours = weights.to_engine(w, TINY)
    theirs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                _arch(TINY)))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(ours) == shapes(theirs)


def test_prefill_and_decode_match_the_reference(model):
    import jax
    import jax.numpy as jnp

    from repro.models import Shard, decode_step, prefill

    w, w32, params = model
    ref = spec.load_module("configs/qwen2_0_5b_reference.py")
    cfg = _arch(TINY)
    tokens = np.random.default_rng(0).integers(0, TINY["vocab_size"],
                                               (2, 12)).astype(np.int32)
    prompt = 8
    want = np.asarray(ref.logits(w32, tokens, TINY, prompt - 1))
    shard = Shard.local()
    logits, state = prefill(cfg, shard, params, {"tokens": tokens[:, :prompt]},
                            max_len=16)
    got = [np.asarray(logits[:, -1])]
    for i in range(prompt, tokens.shape[1]):
        logits, state = decode_step(cfg, shard, params, state,
                                    jnp.asarray(tokens[:, i:i + 1]),
                                    jnp.int32(i))
        got.append(np.asarray(logits[:, -1]))
    got = np.stack(got, axis=1)  # (rows, 5, vocab)
    scale = float(np.std(want))
    # prefill runs in float32 end to end: rounding only
    np.testing.assert_allclose(got[:, 0], want[:, 0], atol=1e-4 * scale)
    # decode reads keys and values back from the bfloat16 cache (relative
    # rounding 2**-9 per entry), which moves the logits by well under 5 %
    # of their spread; a wrong position, mask or layout moves them by O(1)
    err = np.abs(got[:, 1:] - want[:, 1:]).max()
    assert err < 0.05 * scale, (err, scale)
    assert np.array_equal(got.argmax(-1), want.argmax(-1))
    # the reference at one more position is the same forward pass
    longer = np.asarray(ref.logits(w32, tokens, TINY, 0))
    np.testing.assert_allclose(longer[:, prompt - 1:], want, rtol=1e-5,
                               atol=1e-5 * scale)


def test_control_weights_round_matrices_only(model):
    w, _, _ = model
    ref = spec.load_module("configs/qwen2_0_5b_reference.py")
    for kind, step in (("int8", 1 / 127), ("fp8", 1 / 8)):
        low = ref.control_weights(w, kind)
        assert np.array_equal(np.asarray(low["bq"]), np.asarray(w["bq"]))
        a = np.asarray(w["w_up"], np.float32)
        b = np.asarray(low["w_up"])
        amax = np.abs(a).max(axis=-2, keepdims=True)
        assert 0 < np.abs(a - b).max() <= step * amax.max()


def test_planner_reference_matches_the_program_numpy_sweep():
    from repro.core import (ClusterSpec, Objective, ShiftedExponential,
                            SimulatedPlanner)
    import repro.core.simulator as simulator

    ref = spec.load_module("configs/planner_reference.py")
    dep = {"n_server_groups": 12, "plan_trials": 300,
           "service_law": {"delta": 0.02, "mu": 50.0, "work_per_token": 0.01}}
    traffic = {"batch_size": 4, "prompt_len": 40, "gen_tokens": 10,
               "utilization": 0.6}
    seen = []
    real = simulator.sweep_sojourn

    def recording(*a, **k):
        seen.append(real(*a, **k))
        return seen[-1]

    simulator.sweep_sojourn = recording
    try:
        plan = SimulatedPlanner(n_trials=300, seed=11, backend="numpy").plan(
            ClusterSpec(n_workers=12, dist=ShiftedExponential(0.02, 50.0)),
            Objective(metric="mean", utilization=0.6, job_load=2.0))
    finally:
        simulator.sweep_sojourn = real
    want = ref.sweep(dep, traffic, 11)
    np.testing.assert_allclose(seen[0].samples[0], want, rtol=1e-12)
    assert plan.n_batches == ref.plan_choice(want, 12)
    assert ref.splits(12) == list(seen[0].splits)

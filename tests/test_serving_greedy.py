"""The engine's one-program-per-call host path against plain JAX.

``ReplicatedServingEngine`` folds the greedy pick into its jitted prefill
and decode programs and draws a job's prompts in one jitted program.  For a
dense and a state-space family of the reduced engine, the served tokens
must equal a direct loop of jitted ``prefill``/``decode_step`` with an
eager argmax, and each job's prompts must be bitwise the per-request draw
``randint(fold_in(prompt_key, request_id))``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import decode_step, prefill
from repro.serving import ReplicatedServingEngine, ServeEngineConfig

PROMPT_LEN, GEN_TOKENS, BATCH, N_REQUESTS = 8, 5, 3, 7
ARCHS = ["qwen2-0.5b", "xlstm-350m"]


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    """An engine with every job's request ids and prompts recorded, and
    what it served."""
    engine = ReplicatedServingEngine(ServeEngineConfig(
        arch=request.param, n_server_groups=4, n_batches=2, batch_size=BATCH,
        prompt_len=PROMPT_LEN, gen_tokens=GEN_TOKENS, max_len=16,
        utilization=0.5, seed=11))
    jobs, real = [], engine._generate

    def generate(prompts):
        jobs.append(np.asarray(prompts))
        return real(prompts)

    engine._generate = generate
    ids = []
    real_job = engine._generate_for_job

    def generate_for_job(job):
        ids.append([r.request_id for r in job.requests])
        return real_job(job)

    engine._generate_for_job = generate_for_job
    stats = {s.request_id: s for s in engine.serve(N_REQUESTS)}
    return engine, list(zip(ids, jobs)), stats


def _direct_greedy(engine):
    """Jitted prefill and decode steps with the pick made eagerly: prompts
    -> tokens."""
    cfg, sc = engine.cfg, engine.sc
    step0 = jax.jit(lambda p, b: prefill(cfg, engine.shard, p, b,
                                         max_len=sc.max_len))
    step = jax.jit(lambda p, s, t, c: decode_step(cfg, engine.shard, p, s,
                                                  t, c))

    def generate(prompts):
        logits, state = step0(engine.params, {"tokens": jnp.asarray(prompts)})
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = [tok]
        for i in range(sc.gen_tokens - 1):
            logits, state = step(engine.params, state, tok,
                                 jnp.int32(sc.prompt_len + i))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            out.append(tok)
        return np.asarray(jnp.concatenate(out, axis=1))

    return generate


def test_tokens_equal_a_direct_greedy_loop(served):
    engine, jobs, stats = served
    assert sum(len(ids) for ids, _ in jobs) == N_REQUESTS
    direct = _direct_greedy(engine)
    for ids, prompts in jobs:
        want = direct(prompts)
        for k, rid in enumerate(ids):
            assert stats[rid].tokens.shape == (GEN_TOKENS,)
            np.testing.assert_array_equal(stats[rid].tokens, want[k])


def test_prompt_draw_equals_the_per_request_draw(served):
    engine, jobs, _ = served
    assert len({len(ids) for ids, _ in jobs}) > 1  # a partial job too
    for ids, prompts in jobs:
        rows = np.stack([np.asarray(jax.random.randint(
            jax.random.fold_in(engine._prompt_key, rid), (PROMPT_LEN,), 0,
            engine.cfg.vocab_size)) for rid in ids])
        assert prompts.dtype == rows.dtype
        np.testing.assert_array_equal(prompts, rows)

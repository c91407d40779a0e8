"""Traffic driver ``serve``: the replicated serving engine under load.

Set-up builds ``ReplicatedServingEngine`` from the configuration's
deployment and the traffic's shapes, puts the benchmark's own weights
(made from the seed, ``config.weights``) in place of the engine's, and
serves one batch so that every program of the window is compiled.  The
window calls ``engine.serve(requests_per_call)`` again and again and ends
at the first call boundary after ``seconds``.

The check draws ``check_requests`` served requests from the seed, runs the
configuration's float32 reference once over each prompt with its served
tokens, and reads how far below the reference's best logit each served
token lies.  It also holds the engine's initial plan, made in set-up by
the planner's sojourn sweep, against the configuration's float64 planner
reference: the mean sojourn of every split, and the split chosen.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from harness import spec
from harness.runner import Check, sub_seed

SEED_TAG_WEIGHTS = 0x3E16
SEED_TAG_ENGINE = 0xE461
SEED_TAG_CHECK = 0xC4EC


class State:
    pass


def _engine_config(cfg: dict, tr: dict, engine_seed: int):
    from repro.serving import ServeEngineConfig

    dep = cfg["deployment"]
    return ServeEngineConfig(
        arch=dep["arch"], reduced=dep["reduced"],
        execute_model=dep["execute_model"],
        n_server_groups=dep["n_server_groups"],
        planner_mode=dep["planner_mode"], sim_backend=dep["sim_backend"],
        plan_initial=dep["plan_initial"], tuner=dep["tuner"],
        delta=dep["service_law"]["delta"], mu=dep["service_law"]["mu"],
        utilization=tr["utilization"], batch_size=tr["batch_size"],
        prompt_len=tr["prompt_len"], gen_tokens=tr["gen_tokens"],
        max_len=tr["max_len"], arrival_kind=tr["arrival_kind"],
        queue_discipline=tr["queue_discipline"], max_wait=math.inf,
        seed=engine_seed,
    )


def _assert_shapes(engine, want) -> None:
    """The engine must serve the model the configuration file states:
    ``want``, its weights module's ``arch``, field by field."""
    got = engine.cfg
    off = [f.name for f in dataclasses.fields(want)
           if getattr(got, f.name) != getattr(want, f.name)]
    if off:
        def show(c):
            return ", ".join(f"{n}={getattr(c, n)!r}" for n in off)

        raise ValueError(f"engine serves {show(got)}, configuration states "
                         f"{show(want)}")


def _planned_engine(st, sc):
    """The engine, built with its initial plan's sweep recorded."""
    import repro.core.simulator as simulator
    from repro.serving import ReplicatedServingEngine

    real = simulator.sweep_sojourn
    sweeps = []

    def recording(*args, **kwargs):
        sweeps.append(real(*args, **kwargs))
        return sweeps[-1]

    simulator.sweep_sojourn = recording
    try:
        engine = ReplicatedServingEngine(sc)
    finally:
        simulator.sweep_sojourn = real
    st.plan_samples = (np.asarray(sweeps[-1].samples[0], dtype=np.float64)
                       if sweeps else None)
    st.plan_b = engine.plan.n_batches
    return engine


def setup(run):
    import jax

    cfg, tr = run.cell.config, run.cell.traffic
    st = State()
    st.cfg, st.tr = cfg, tr
    st.engine_seed = sub_seed(run.seed, SEED_TAG_ENGINE)
    engine = _planned_engine(st, _engine_config(cfg, tr, st.engine_seed))
    weights = spec.load_module(cfg["weights"])
    _assert_shapes(engine, weights.arch(cfg))
    st.weights = weights.make(sub_seed(run.seed, SEED_TAG_WEIGHTS), cfg)
    engine.params = weights.to_engine(st.weights, cfg)
    st.engine = engine
    # what the engine fed the model and what it generated, per job
    st.jobs, st.prompts = [], []
    real_job, real_gen = engine._generate_for_job, engine._generate

    def generate_for_job(job):
        st.jobs.append([r.request_id for r in job.requests])
        return real_job(job)

    def generate(prompts):
        st.prompts.append(prompts)
        return real_gen(prompts)

    engine._generate_for_job = generate_for_job
    engine._generate = generate
    st.calls = {"prefill": 0, "decode": 0}
    for name in ("prefill", "decode"):
        _count_calls(st, engine, name)
    jax.block_until_ready(engine.serve(tr["batch_size"])[0].tokens)
    st.jobs.clear()
    st.prompts.clear()
    st.stats = []
    return st


def _count_calls(st, engine, name: str) -> None:
    """Count the engine's prefill and decode calls, inside a span each."""
    import jax

    attr = f"_{name}"
    real = getattr(engine, attr)
    label = f"bench.{name}"

    def counted(*args):
        st.calls[name] += 1
        with jax.profiler.TraceAnnotation(label):
            return real(*args)

    setattr(engine, attr, counted)


def window(st, run, seconds: float) -> None:
    import jax

    tr = st.tr
    calls0 = dict(st.calls)
    t_end = time.perf_counter() + seconds
    tokens = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.serve"):
            stats = st.engine.serve(tr["requests_per_call"])
        st.stats.extend(stats)
        for s in stats:
            run.attempted += 1
            ok = not s.dropped and s.tokens.shape == (tr["gen_tokens"],)
            run.failed += not ok
            tokens += s.tokens.size if ok else 0
        if time.perf_counter() >= t_end:
            break
    run.counts["tokens"] = tokens
    run.facts.update(
        prefill_calls=st.calls["prefill"] - calls0["prefill"],
        decode_calls=st.calls["decode"] - calls0["decode"],
        batch=tr["batch_size"], prompt_len=tr["prompt_len"],
        gen_tokens=tr["gen_tokens"], model=st.cfg)


def finish(st) -> None:
    import jax

    # the program's state goes before the reference runs; the weights the
    # benchmark made stay for it
    st.prompts = [np.asarray(p) for p in st.prompts]
    st.engine = None
    jax.clear_caches()


def sampled(st, run) -> list:
    """(prompt, served tokens) of requests drawn from the seed."""
    by_id = {s.request_id: s for s in st.stats}
    rows = {}
    for prompts, ids in zip(st.prompts, st.jobs):
        for k, rid in enumerate(ids):
            rows[rid] = prompts[k]
    served = sorted(rid for rid, s in by_id.items()
                    if rid in rows and s.tokens.size)
    rng = np.random.default_rng(sub_seed(run.seed, SEED_TAG_CHECK))
    take = min(st.tr["check_requests"], len(served))
    pick = sorted(rng.choice(len(served), size=take, replace=False))
    return [(rows[served[i]], by_id[served[i]].tokens) for i in pick]


def _tokens(pairs):
    prompts = np.stack([p for p, _ in pairs]).astype(np.int32)
    served = np.stack([t for _, t in pairs]).astype(np.int32)
    # the logits at prompt_len - 1 + j chose served token j
    inputs = np.concatenate([prompts, served[:, :-1]], axis=1)
    return inputs, served, prompts.shape[1] - 1


def plan_readings(st, control=None) -> dict:
    """The widest relative gap of a split's mean sojourn between the
    engine's initial-plan sweep (or, with ``control``, the reference
    computed in bfloat16, one precision below the sweep's float32) and the
    float64 reference; and whether the split chosen differs from the
    reference's where the reference scores it worse by more than that
    gap's limit (a float32 sweep may pick either of two near-tied splits)."""
    ref = spec.load_module(st.cfg["planner_reference"])
    dep, n = st.cfg["deployment"], st.cfg["deployment"]["n_server_groups"]
    want = ref.sweep(dep, st.tr, st.engine_seed)
    if control is None:
        got, got_b = st.plan_samples, st.plan_b
    else:
        import ml_dtypes

        got = ref.sweep(dep, st.tr, st.engine_seed, dtype=ml_dtypes.bfloat16)
        got_b = ref.plan_choice(got, n)
    if got is None or got.shape != want.shape:
        return {"plan_mean_gap": math.inf, "plans_differ": 1.0}
    means = want.mean(axis=-1)
    gap = float(np.max(np.abs(got.mean(axis=-1) - means) / means))
    if got_b not in ref.splits(n):
        return {"plan_mean_gap": gap, "plans_differ": 1.0}
    worse = means[ref.splits(n).index(got_b)] / means.min() - 1.0
    differ = (got_b != ref.plan_choice(want, n)
              and worse > st.tr["limits"]["plan_mean_gap"])
    return {"plan_mean_gap": gap, "plans_differ": float(differ)}


def readings(st, run, control=None) -> dict:
    """``control`` names the model's control (``fp8``, ``int8``); with it
    the plan's control, bfloat16, takes the sweep's place too."""
    ref = spec.load_module(st.cfg["reference"])
    pairs = sampled(st, run)
    inputs, served, first = _tokens(pairs)
    block = st.tr["check_block"]

    def logits(weights):
        # a few rows at a time, so that long prompts fit beside the weights
        return np.concatenate([
            np.asarray(ref.logits(weights, inputs[i:i + block], st.cfg,
                                  first))
            for i in range(0, len(inputs), block)])

    want = logits(st.weights)
    if control is not None:
        served = logits(ref.control_weights(st.weights, control)).argmax(-1)
    gap = float(np.max(ref.served_gaps(want, served)))
    return {"served_logit_gap": gap, "requests_compared": len(pairs),
            **plan_readings(st, control)}


def check(st, run, control=None) -> list[Check]:
    limits = st.tr["limits"]
    got = readings(st, run, control)
    if got["requests_compared"] < 1:
        run.failed += 1
    return [Check(name, got[name], limits[name]) for name in limits]

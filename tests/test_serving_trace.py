"""Profiler spans of the serving engine's host path, and the stable names of
its device programs.

A tiny reduced engine serves one call under ``jax.profiler`` on the CPU; the
trace is read back with ``jax.profiler.ProfileData``.  Each completed job
must show one ``repro.job``, ``repro.prompts``, ``repro.prefill`` and
``repro.fetch`` span and ``gen_tokens - 1`` ``repro.decode`` spans, all
inside the call's ``repro.serve`` span, and launch one device program per
prompt draw, prefill, decode step and fetch.
"""

import glob
import os
from collections import Counter, defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import ReplicatedServingEngine, ServeEngineConfig
from repro.serving.engine import PREFIX

GEN_TOKENS = 20
N_REQUESTS = 6
BATCH = 2
PER_JOB = {"repro.job": 1, "repro.prompts": 1, "repro.prefill": 1,
           "repro.decode": GEN_TOKENS - 1, "repro.fetch": 1}


def _engine():
    return ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=4, n_batches=2, batch_size=BATCH, prompt_len=8,
        gen_tokens=GEN_TOKENS, max_len=32, utilization=0.5, seed=5))


def _host_events(log_dir):
    """(spans: name -> sorted [(start, end)], count of every host event's
    name)."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans, names = defaultdict(list), Counter()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names[ev.name] += 1
                if ev.name.startswith(PREFIX):
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    for ivs in spans.values():
        ivs.sort()
    return dict(spans), names


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced call, and the same call untraced on a twin engine."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    engine = _engine()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        traced = engine.serve(N_REQUESTS)
    untraced = _engine().serve(N_REQUESTS)
    spans, names = _host_events(log_dir)
    return engine, traced, untraced, spans, names


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_one_serve_span_per_call(served):
    _, traced, _, spans, _ = served
    assert len(spans["repro.serve"]) == 1
    assert sum(s.tokens.size > 0 for s in traced) == N_REQUESTS


def test_span_counts_per_job(served):
    _, _, _, spans, _ = served
    jobs = spans["repro.job"]
    assert len(jobs) == N_REQUESTS // BATCH
    for job in jobs:
        got = {name: sum(_inside(iv, job) for iv in spans[name])
               for name in PER_JOB}
        assert got == PER_JOB
    for name, n in PER_JOB.items():
        assert len(spans[name]) == n * len(jobs), name


@pytest.mark.parametrize("name", sorted(PER_JOB))
def test_spans_nest_inside_serve(served, name):
    _, _, _, spans, _ = served
    serve, = spans["repro.serve"]
    assert spans[name]
    assert all(_inside(iv, serve) for iv in spans[name])


def test_prompt_prefill_decode_fetch_spans_in_order(served):
    """Within a job: prompts, prefill, a decode per further token, then
    fetch; the greedy pick has no span, it runs inside prefill and decode."""
    _, _, _, spans, _ = served
    assert "repro.sample" not in spans
    model = sorted((s, name) for name in ("repro.prompts", "repro.prefill",
                                          "repro.decode", "repro.fetch")
                   for s, _ in spans[name])
    want = (["repro.prompts", "repro.prefill"]
            + ["repro.decode"] * (GEN_TOKENS - 1) + ["repro.fetch"])
    assert [n for _, n in model] == want * (N_REQUESTS // BATCH)


def test_device_programs_per_job(served):
    """At most ``gen_tokens + 8`` program launches a job: the prompt draw,
    prefill, a decode step per further token and the fetch's concatenation
    are one launch each (an eager greedy pick would be about a dozen a
    token, and an eager concatenate of more than 16 tokens several)."""
    _, _, _, spans, names = served
    launches = sum(n for name, n in names.items()
                   if name.endswith("Executable::Execute"))
    jobs = len(spans["repro.job"])
    assert launches == jobs * (GEN_TOKENS + 2)
    assert launches / jobs <= GEN_TOKENS + 8


def test_tracing_leaves_tokens_unchanged(served):
    _, traced, untraced, _, _ = served
    assert [s.request_id for s in traced] == [s.request_id for s in untraced]
    for a, b in zip(traced, untraced):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_programs_have_stable_names(served):
    engine, _, _, _, names = served
    assert {"PjitFunction(serve_prefill)",
            "PjitFunction(serve_decode)",
            "PjitFunction(serve_prompts)",
            "PjitFunction(serve_join)"} <= names.keys()
    prompts = jnp.zeros((BATCH, 8), jnp.int32)
    text = engine._prefill.lower(engine.params,
                                 {"tokens": prompts}).as_text()
    assert "@jit_serve_prefill" in text


def _kernel_jaxprs():
    from repro.kernels.coded.kernel import combine_pallas
    from repro.kernels.sojourn_sweep.kernel import (
        coded_cells_pallas,
        sojourn_cells_pallas,
    )

    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    return {
        "coded_combine": lambda: jax.make_jaxpr(
            lambda a, b: combine_pallas(a, b, interpret=True))(
                sds((4, 3), f32), sds((3, 8), f32)),
        "coded_cells": lambda: jax.make_jaxpr(
            lambda t, k: coded_cells_pallas(t, k, interpret=True))(
                sds((2, 8, 4), f32), sds((2,), i32)),
        "sojourn_cells": lambda: jax.make_jaxpr(
            lambda *a: sojourn_cells_pallas(*a, interpret=True))(
                sds((8,), f32), sds((2, 8, 4), f32), sds((2, 8, 4), f32),
                sds((1,), i32), sds((2, 1), f32), sds((1, 8), bool),
                sds((2,), i32)),
    }


@pytest.mark.parametrize("name", ["coded_combine", "coded_cells",
                                  "sojourn_cells"])
def test_pallas_kernels_are_named(name):
    assert f"name={name}" in str(_kernel_jaxprs()[name]())

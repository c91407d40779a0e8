"""The trace reduction on a small trace recorded on a TPU v5e chip, and
the operation and byte counts against hand counts.

``data/tiny_v5e.xplane.pb``: three ``bench.step`` spans, each dispatching
a 512x512 bfloat16 matmul-and-sum program and a 50-step loop program (the
loop program ran a fourth time after the last span)."""

import os

import pytest

from harness import spec, tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tracing.from_file(DATA)


def test_modules_and_spans(trace):
    progs = trace.programs()
    assert sorted(len(d) for d in progs.values()) == [3, 4]
    assert {m[2] for m in trace.modules} == {"jit__lambda"}
    assert len(trace.spans["bench.step"]) == 3
    assert trace.n_devices == 1
    # the loop program is the longer one, about 23 us a run
    loop = max(progs.values(), key=sum)
    assert len(loop) == 4 and all(20e-6 < d < 30e-6 for d in loop)


def test_busy_union_and_idle_share(trace):
    durations = sum(e - s for s, e, _, _ in trace.modules)
    busy = trace.busy()
    # the executions do not overlap, so the union is their sum
    assert len(busy) == len(trace.modules)
    assert sum(e - s for s, e in busy) == pytest.approx(durations, rel=1e-12)
    start, end = trace.modules[0][0], trace.modules[-1][1]
    covered = tracing.covered(busy, start, end)
    assert covered == pytest.approx(durations, rel=1e-12)
    idle = tracing.gaps(busy, start, end)
    assert len(idle) == len(busy) - 1
    assert sum(e - s for s, e in idle) + covered == pytest.approx(end - start)
    # overlapping intervals merge
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.covered([(0, 3), (5, 6)], 2, 5.5) == pytest.approx(1.5)


def test_idle_gaps_named_by_span(trace):
    start, end = trace.modules[0][0], trace.modules[-1][1]
    trace.spans["bench.window"] = [(start, end)]
    out = tracing.breakdown(trace)
    names = {n for n, _ in out["idle_gaps"]}
    assert names <= {"bench.step", "outside any span"}
    total = sum(t for _, t in out["idle_gaps"])
    assert total == pytest.approx(
        (end - start) - tracing.covered(trace.busy(), start, end))
    # a gap inside a span is named after it
    s, e = trace.spans["bench.step"][1]
    assert tracing.innermost_span(trace.spans, 0.5 * (s + e)) == "bench.step"
    assert out["device_ops"][0][0].startswith("jit__lambda/")
    idle = 100.0 * total / (end - start)
    assert tracing.idle_share(trace, "bench.step") == pytest.approx(idle)
    assert tracing.idle_share(trace, "bench.serve") is None
    del trace.spans["bench.window"]


def test_program_by_calls(trace):
    name, durs = tracing.program_by_calls(trace, 3)
    assert len(durs) == 3 and name.startswith("jit__lambda(")
    assert tracing.program_by_calls(trace, 5) == (None, [])


def test_self_times_nest():
    events = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (4.0, 5.0, "b"),
              (12.0, 13.0, "c")]
    got = tracing._self_times(events)
    assert got[(0.0, "while")] == pytest.approx(7.0)
    assert got[(1.0, "a")] == pytest.approx(2.0)
    assert got[(12.0, "c")] == pytest.approx(1.0)


def test_dense_decoder_counts_by_hand():
    counts = spec.load_module("counts/dense_decoder.py")
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "vocab_size": 10, "attention_bias": True}
    # head size 4: q, o 8x8; k, v 8x4; gate, up, down 8x16
    assert counts.matmul_weights_per_layer(cfg) == 64 + 64 + 32 + 32 + 384
    # + q/k/v biases (8 + 4 + 4) and two norm scales (16) per layer,
    # the 10x8 embedding and the final norm
    assert counts.parameters(cfg) == 2 * (576 + 16 + 16) + 80 + 8
    assert counts.parameters(dict(cfg, attention_bias=False)) == (
        2 * (576 + 16) + 80 + 8)
    # batch 3 at context 5: every weight, and K and V of 5 positions
    assert counts.decode_bytes(cfg, 3, 5) == 2 * (1304 + 2 * 2 * 3 * 5 * 4)
    # the same weights, and K and V of 5 prompt positions written
    assert counts.prefill_bytes(cfg, 3, 5) == counts.decode_bytes(cfg, 3, 5)
    assert counts.flops_per_token(cfg, 5, True) == 2 * 2 * 576 + \
        4 * 2 * 5 * 8 + 2 * 8 * 10
    assert counts.prefill_flops(cfg, 1, 3) == 2 * 2 * 576 * 3 + \
        4 * 2 * 8 * 6 + 2 * 8 * 10
    assert counts.job_contexts(4, 3) == [5, 6]
    # the published qwen2-0.5b shapes give its 494,032,768 parameters
    q = spec.load_json("configs/qwen2-0.5b.json")
    assert counts.parameters(q) == 494_032_768

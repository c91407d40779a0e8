"""The serving engine's ``repro.`` spans read from a trace recorded on a TPU
v5e chip (``harness/program_spans.py``), against hand counts.

``data/tiny_engine_v5e.xplane.pb``: a reduced serving engine serving three
jobs of 2 requests and 8 greedy tokens, with the engine's own ``repro.``
spans (``record_engine_trace.py`` says how it was made).
``data/tiny_v5e.xplane.pb`` holds no span of the program."""

import os
from collections import Counter

import pytest

from harness import program_spans, spec, tracing

import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
JOBS, GEN_TOKENS = 3, 8
READINGS = ("decode_dispatch_ms", "job_host_ms", "device_programs_per_job",
            "device_idle.dispatch", "device_idle.fetch", "device_idle.engine")


@pytest.fixture(scope="module")
def engine():
    return program_spans.from_file(
        os.path.join(DATA, "tiny_engine_v5e.xplane.pb"))


def test_nested_spans_cut_into_innermost_pieces():
    spans = {"outer": [(0.0, 10.0)], "a": [(0.0, 2.0), (5.0, 6.0)],
             "b": [(3.0, 4.0)], "bench.window": [(-1.0, 11.0)],
             "late": [(12.0, 13.0)]}
    assert program_spans.innermost_pieces(spans) == [
        (0.0, 2.0, "a"), (2.0, 3.0, "outer"), (3.0, 4.0, "b"),
        (4.0, 5.0, "outer"), (5.0, 6.0, "a"), (6.0, 10.0, "outer"),
        (12.0, 13.0, "late")]


def test_silent_without_program_spans():
    """A trace with only the benchmark's spans reads nothing, and raises
    nothing."""
    trace = tracing.from_file(os.path.join(DATA, "tiny_v5e.xplane.pb"))
    trace.spans["bench.window"] = [(trace.modules[0][0],
                                    trace.modules[-1][1])]
    assert program_spans.readings(trace) == {}
    assert program_spans.device_offset(trace) == 0.0


def test_program_spans_and_names_are_read(engine):
    want = {"repro.serve": 1, "repro.job": JOBS, "repro.prompts": JOBS,
            "repro.prefill": JOBS, "repro.decode": JOBS * (GEN_TOKENS - 1),
            "repro.sample": JOBS * GEN_TOKENS, "repro.fetch": JOBS,
            "bench.prefill": JOBS, "bench.decode": JOBS * (GEN_TOKENS - 1),
            "bench.serve": 1, "bench.window": 1}
    assert {n: len(v) for n, v in engine.spans.items()} == want
    # the harness's own reduction keeps the benchmark's spans alone
    plain = tracing.from_file(os.path.join(DATA, "tiny_engine_v5e.xplane.pb"))
    assert {n for n in plain.spans} == {n for n in want
                                        if n.startswith("bench.")}
    names = Counter(m[2] for m in engine.modules)
    assert names["jit_serve_prefill"] == JOBS
    assert names["jit_serve_decode"] == JOBS * (GEN_TOKENS - 1)
    assert "jit__lambda" not in names
    ops = tracing.breakdown(engine)["device_ops"]
    assert any(n.startswith("jit_serve_decode/") for n, _ in ops)


def test_device_offset_puts_no_decode_before_its_dispatch(engine):
    off = program_spans.device_offset(engine)
    assert 0.0 <= off <= 3e-3
    name, _ = tracing.program_by_calls(engine,
                                       len(engine.spans["repro.decode"]))
    assert name.startswith("jit_serve_decode(")
    execs = sorted(s for s, _, _, full in engine.modules if full == name)
    gaps = [x + off - s for (s, _), x in zip(engine.spans["repro.decode"],
                                            execs)]
    # the smallest such shift: the tightest execution starts with its span
    assert off > 0.0 and min(gaps) == pytest.approx(0.0, abs=1e-12)
    busy = program_spans.aligned_busy(engine)
    assert [b - a for a, b in busy] == pytest.approx(
        [b - a for a, b in engine.busy()])
    assert busy[0][0] == pytest.approx(engine.busy()[0][0] + off)


def _idle_by_brute_force(trace):
    """Idle seconds by innermost span, from every elementary interval of
    the span and gap boundaries and the midpoint rule."""
    start, end = trace.window()
    idle = tracing.gaps(program_spans.aligned_busy(trace), start, end)
    cuts = sorted({t for ivs in trace.spans.values() for iv in ivs
                   for t in iv} | {t for iv in idle for t in iv})
    out = Counter()
    for gs, ge in idle:
        inner = [gs] + [t for t in cuts if gs < t < ge] + [ge]
        for a, b in zip(inner, inner[1:]):
            out[tracing.innermost_span(trace.spans, 0.5 * (a + b))] += b - a
    return out


@pytest.mark.parametrize("name", sorted(program_spans.IDLE_SPANS))
def test_idle_shares_match_hand_counts(engine, name):
    start, end = engine.window()
    brute = _idle_by_brute_force(engine)
    want = 100.0 * sum(brute[n] for n in program_spans.IDLE_SPANS[name]) / (
        end - start)
    assert program_spans.readings(engine)[name] == pytest.approx(
        want, rel=1e-9, abs=1e-9)


def test_idle_shares_sum_to_the_aligned_idle_share(engine):
    start, end = engine.window()
    got = program_spans.readings(engine)
    shares = sum(got[n] for n in program_spans.IDLE_SPANS)
    named = {n for ns in program_spans.IDLE_SPANS.values() for n in ns}
    rest = 100.0 * sum(
        t for n, t in program_spans.idle_by_span(engine).items()
        if n not in named) / (end - start)
    aligned = 100.0 * (1.0 - tracing.covered(
        program_spans.aligned_busy(engine), start, end) / (end - start))
    assert shares + rest == pytest.approx(aligned, abs=1e-9)
    assert aligned == pytest.approx(
        tracing.idle_share(engine, "bench.serve"), abs=0.1)
    gaps = program_spans.idle_gaps(engine, top=100)
    assert sum(t for _, t in gaps) == pytest.approx(
        aligned / 100.0 * (end - start), abs=1e-12)
    # the host dispatches nearly all the time in a tiny engine
    assert got["device_idle.dispatch"] > 50.0


def test_host_readings_match_hand_counts(engine):
    sp, got = engine.spans, program_spans.readings(engine)
    assert sorted(got) == sorted(READINGS)
    mean = lambda ivs: sum(e - s for s, e in ivs) / len(ivs)  # noqa: E731
    assert got["decode_dispatch_ms"] == pytest.approx(
        1e3 * (mean(sp["repro.decode"]) + mean(sp["repro.sample"])))
    # the model spans are disjoint, and all inside the one serve span
    (s0, e0), = sp["repro.serve"]
    model = sum(e - s for n in program_spans.MODEL for s, e in sp[n])
    assert got["job_host_ms"] == pytest.approx(
        1e3 * ((e0 - s0) - model) / JOBS)
    # per job: prefill, 7 decode steps, and the prompt draw, greedy picks
    # and final concatenate as small programs
    assert len(engine.modules) == 339
    assert got["device_programs_per_job"] == 339 / JOBS


def test_engine_spans_tool_reads_a_tiny_traced_window(monkeypatch):
    """``checks/engine_spans.py`` on the CPU at a tiny size: the program's
    spans are read; no device plane exists, so no program is counted."""
    name = "qwen2-0.5b.chat"
    tiny.serve_this_model(monkeypatch, name)
    cell = spec.load_cell(name).replace(**tiny.overrides(name))
    tool = spec.load_module("checks/engine_spans.py")
    driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    row = tool.traced_window(driver, cell, seed=2**31 + 5, seconds=1.0)
    got = row["readings"]
    assert sorted(got) == sorted(READINGS)
    assert got["decode_dispatch_ms"] > 0.0 and got["job_host_ms"] > 0.0
    assert got["device_programs_per_job"] == 0.0
    assert row["device_offset_ms"] == 0.0
    assert sum(got[n] for n in program_spans.IDLE_SPANS) <= 100.0 + 1e-9
    assert {n for n, _ in row["idle_gaps"]} & {"repro.decode", "repro.sample"}

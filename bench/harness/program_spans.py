"""The serving engine's own spans in a profiler trace, on the device's clock.

The engine (``repro.serving.engine``) opens ``jax.profiler.TraceAnnotation``
spans named ``repro.serve``, ``repro.job``, ``repro.prompts``,
``repro.prefill``, ``repro.decode``, ``repro.sample`` and ``repro.fetch``.
``tracing.from_file`` keeps only the benchmark's ``bench.`` spans;
``from_file`` here reads the same ``.xplane.pb`` and adds the program's.

On a TPU v5e the device timeline in the trace can run ahead of the host's
(a program's execution appears to start before the host span that
dispatched it).  ``device_offset`` measures that skew from the engine's
``repro.decode`` spans, and the idle shares by span read the device's busy
time shifted by it (``aligned_busy``).

``readings`` gives six numbers of one traced window, ``{}`` where the
trace holds no span of the program:

* ``decode_dispatch_ms``: mean host ms of a ``repro.decode`` span plus
  that of a ``repro.sample`` span (one decode step's dispatch);
* ``job_host_ms``: host ms per ``repro.job`` in ``repro.serve`` that no
  ``repro.prefill``, ``repro.decode``, ``repro.sample`` or ``repro.fetch``
  span covers (the event loop, the tuner's telemetry, the prompt draw);
* ``device_programs_per_job``: program executions per device that start
  in the window (aligned clock), over the ``repro.job`` spans;
* ``device_idle.dispatch``, ``device_idle.fetch``, ``device_idle.engine``:
  per cent of the window in which the device was idle (aligned clock)
  while the host's innermost span was one of ``IDLE_SPANS[name]``.
"""

from __future__ import annotations

from collections import defaultdict

from . import tracing

PREFIX = "repro."
MODEL = ("repro.prefill", "repro.decode", "repro.sample", "repro.fetch")
IDLE_SPANS = {
    "device_idle.dispatch": ("repro.prefill", "repro.decode", "repro.sample",
                             "bench.prefill", "bench.decode"),
    "device_idle.fetch": ("repro.fetch",),
    "device_idle.engine": ("repro.serve", "repro.job", "repro.prompts"),
}


def from_file(path: str) -> tracing.Trace:
    """``tracing.from_file`` with the program's ``repro.`` spans added."""
    from jax.profiler import ProfileData

    trace = tracing.from_file(path)
    spans = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        s = ev.start_ns * 1e-9
                        spans[ev.name].append((s, s + ev.duration_ns * 1e-9))
    trace.spans.update({n: sorted(ivs) for n, ivs in spans.items()})
    return trace


def device_offset(trace: tracing.Trace, span: str = "repro.decode") -> float:
    """Seconds by which the device's timeline runs ahead of the host's: the
    smallest shift that starts no execution of the program that ran once
    per ``span`` before its own dispatch span (the k-th execution pairs
    with the k-th span).  0.0 where the trace holds no such span or
    program."""
    spans = trace.spans.get(span)
    if not spans:
        return 0.0
    name, _ = tracing.program_by_calls(trace, len(spans))
    if name is None:
        return 0.0
    execs = sorted(s for s, _, _, full in trace.modules if full == name)
    return max(0.0, max(s - x for (s, _), x in zip(spans, execs)))


def aligned_busy(trace: tracing.Trace) -> list[tuple[float, float]]:
    """``trace.busy()`` on the host's clock (shifted by ``device_offset``)."""
    off = device_offset(trace)
    return [(s + off, e + off) for s, e in trace.busy()]


def innermost_pieces(spans: dict, skip=("bench.window",)) -> list:
    """Disjoint ``(start, end, name)`` pieces of time, each named after the
    innermost span that covers it (spans opened on one thread nest); time
    in no span is left out."""
    out, stack, t = [], [], float("-inf")

    def close(until):
        nonlocal t
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    # outer spans first where two start together
    for s, e, name in sorted(((s, e, n) for n, ivs in spans.items()
                              if n not in skip for s, e in ivs),
                             key=lambda x: (x[0], -x[1])):
        close(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((e, name))
    close(float("inf"))
    return out


def idle_by_span(trace: tracing.Trace) -> dict:
    """Seconds of the window in which the device was idle (on the aligned
    clock), by the innermost span the host was in (``outside any span``
    where it was in none)."""
    start, end = trace.window()
    idle = tracing.gaps(aligned_busy(trace), start, end)
    out: dict = defaultdict(float)
    # pieces and gaps are both sorted and disjoint: merge them in one pass
    pieces, i = innermost_pieces(trace.spans), 0
    for gs, ge in idle:
        out["outside any span"] += ge - gs
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, name = pieces[j]
            part = max(0.0, min(pe, ge) - max(ps, gs))
            out[name] += part
            out["outside any span"] -= part
            j += 1
    return dict(out)


def readings(trace: tracing.Trace) -> dict:
    """The six numbers of the module docstring, or ``{}`` where the trace
    holds no ``repro.job`` span."""
    sp = trace.spans
    jobs = sp.get("repro.job")
    if not jobs:
        return {}
    out = {}
    if sp.get("repro.decode") and sp.get("repro.sample"):
        out["decode_dispatch_ms"] = 1e3 * sum(
            sum(e - s for s, e in sp[n]) / len(sp[n])
            for n in ("repro.decode", "repro.sample"))
    model = tracing.union(iv for n in MODEL for iv in sp.get(n, []))
    host = sum(e - s - tracing.covered(model, s, e)
               for s, e in tracing.union(sp.get("repro.serve", [])))
    out["job_host_ms"] = 1e3 * host / len(jobs)
    start, end = trace.window()
    off = device_offset(trace)
    n = sum(start <= s + off <= end for s, _, _, _ in trace.modules)
    out["device_programs_per_job"] = n / max(trace.n_devices, 1) / len(jobs)
    idle = idle_by_span(trace)
    for name, names in IDLE_SPANS.items():
        out[name] = 100.0 * sum(idle.get(n, 0.0) for n in names) / (end - start)
    return out


def idle_gaps(trace: tracing.Trace, top: int = 10) -> list:
    """``tracing.breakdown``'s ``idle_gaps`` on the aligned clock, charged
    to the innermost span of the program or the benchmark."""
    idle = {n: t for n, t in idle_by_span(trace).items() if t > 0}
    return [[n, t] for n, t in sorted(idle.items(), key=lambda kv: -kv[1])
            [:top]]

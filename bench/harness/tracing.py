"""Profiler trace capture and its reduction to intervals.

A traced window runs under ``jax.profiler`` with the Python tracer off.
The reduction reads the ``.xplane.pb`` it writes with nothing but
``jax.profiler.ProfileData``:

* device plane ``/device:TPU:<i>``: line ``XLA Modules`` (one event per
  program execution, named ``<jit name>(<fingerprint>)``) and line
  ``XLA Ops`` (one event per HLO operation; a ``while`` op spans its body);
* host plane ``/host:CPU``: every event whose name starts with ``bench.``
  is a span the benchmark opened with ``jax.profiler.TraceAnnotation``.

On a TPU v5e the device timeline in the trace runs about 1 ms ahead of
the host's (a program's execution appears to start before the host span
that dispatched it), so short gaps near a span's edge can be named after
the neighbouring span.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    """Intervals (seconds, on the trace's own clock) of one traced window."""

    modules: list  # (start, end, program name, program name with fingerprint)
    op_self: dict  # "<program>/<HLO op>" -> self seconds on the device
    spans: dict  # span name -> [(start, end), ...]
    n_devices: int

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        (start, end), = self.spans[name]
        return start, end

    def busy(self) -> list[tuple[float, float]]:
        """Union of the device's program executions."""
        return union([(s, e) for s, e, _, _ in self.modules])

    def programs(self) -> dict:
        """Full program name -> list of execution durations (seconds)."""
        out = defaultdict(list)
        for s, e, _, full in self.modules:
            out[full].append(e - s)
        return dict(out)


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, start: float, end: float) -> float:
    """Length of the part of [start, end] that disjoint ``intervals`` cover."""
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in intervals)


def gaps(intervals, start: float, end: float) -> list[tuple[float, float]]:
    """The parts of [start, end] that disjoint sorted ``intervals`` leave."""
    out, t = [], start
    for s, e in intervals:
        if e <= start or s >= end:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return out


def innermost_span(spans: dict, t: float, skip=("bench.window",)) -> str:
    """Name of the shortest benchmark span that contains time ``t``."""
    best, best_len = "outside any span", float("inf")
    for name, ivs in spans.items():
        if name in skip:
            continue
        for s, e in ivs:
            if s <= t <= e and e - s < best_len:
                best, best_len = name, e - s
    return best


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the enclosed block into ``log_dir`` (Python tracer off)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _self_times(events) -> dict:
    """Self time of each event of one line whose events nest, keyed by
    (start, name)."""
    out: dict = defaultdict(float)
    stack: list[list] = []  # [end, key, child time, duration]
    for start, end, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= start:
            top = stack.pop()
            out[top[1]] += top[3] - top[2]
        if stack:
            stack[-1][2] += end - start
        stack.append([end, (start, name), 0.0, end - start])
    for top in stack:
        out[top[1]] += top[3] - top[2]
    return dict(out)


def _op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def _program_of(modules, starts, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1]:
        return modules[i][2]
    return "?"


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_file(max(paths, key=os.path.getmtime))


def from_file(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, spans = [], defaultdict(list)
    op_events: list = []
    n_devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            n_devices += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        full = ev.name
                        modules.append((s, s + ev.duration_ns * 1e-9,
                                        _FINGERPRINT.sub("", full), full))
                elif line.name == "XLA Ops":
                    op_events.append([
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         _op_name(ev.name))
                        for ev in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans[ev.name].append((s, s + ev.duration_ns * 1e-9))
    modules.sort()
    starts = [m[0] for m in modules]
    op_self: dict = defaultdict(float)
    for events in op_events:
        for (start, name), t in _self_times(events).items():
            op_self[f"{_program_of(modules, starts, start)}/{name}"] += t
    for ivs in spans.values():
        ivs.sort()
    return Trace(modules=modules, op_self=dict(op_self),
                 spans=dict(spans), n_devices=n_devices)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device operations by self time, and idle time in the window
    summed by the innermost benchmark span the host was in."""
    start, end = trace.window()
    ops = sorted(trace.op_self.items(), key=lambda kv: -kv[1])[:top]
    idle: dict = defaultdict(float)
    for s, e in gaps(trace.busy(), start, end):
        idle[innermost_span(trace.spans, 0.5 * (s + e))] += e - s
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in idle_top]}


def idle_share(trace: Trace | None, span: str):
    """Per cent of the traced window in which no program ran on the
    device, or None where the window holds no ``span`` (the cell drives
    some other path)."""
    if trace is None or span not in trace.spans:
        return None
    start, end = trace.window()
    return 100.0 * (1.0 - covered(trace.busy(), start, end) / (end - start))


def program_by_calls(trace: Trace, n_calls: int, exclude=()) -> tuple:
    """(full name, durations) of the program that ran exactly ``n_calls``
    times in the trace, the one with the most device time where several
    did; ``(None, [])`` when none did."""
    best, best_t = (None, []), -1.0
    for name, durs in trace.programs().items():
        if name in exclude or len(durs) != n_calls:
            continue
        if sum(durs) > best_t:
            best, best_t = (name, durs), sum(durs)
    return best

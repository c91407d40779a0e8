"""One run of one cell: set-up, measured window, metrics, check, result.

A traffic driver (``bench/drivers/<driver>.py``) provides

* ``setup(run) -> state``: builds the system under test from the seed and
  warms up every shape the window uses (all of it counts as ``setup_s``);
* ``window(state, run, seconds)``: the closed loop of the measured window,
  recording counts in ``run.counts``, and what the per-layer readers need
  in ``run.facts``;
* ``finish(state)``: frees the program's device state;
* ``check(state, run) -> [Check]``: compares what the timed path produced
  with the configuration's plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time

from . import spec, tracing
from .clock import CompileClock

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit (a run is correct when every
    value is at most its limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    traced: bool
    setup_s: float = math.nan
    window_s: float = math.nan
    counts: dict = dataclasses.field(default_factory=dict)
    facts: dict = dataclasses.field(default_factory=dict)
    trace: tracing.Trace | None = None
    peaks: dict | None = None
    attempted: int = 0
    failed: int = 0


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed that depends on every bit of the run's ``seed`` (of
    any size) and on ``tag``, one stream per use."""
    import numpy as np

    return int(np.random.SeedSequence([seed, tag]).generate_state(1)[0]
               & 0x7FFFFFFF)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """Keep JAX's persistent cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), and keep every program
    there, however fast it compiled."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(spec.ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(cell: spec.Cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU visible (platform {devs[0].platform!r})")
        if len(devs) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, "
                         f"{len(devs)} visible")
    return devs


def peaks_for(kind: str) -> dict:
    table = spec.load_json("harness/peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/harness/"
                       "peaks.json; add its published peaks")
    return table[kind]


def device_record(devs, n_used: int) -> dict:
    peak = [((d.memory_stats() or {}).get("peak_bytes_in_use")) for d in
            devs[:n_used]]
    peak = [p for p in peak if p is not None]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peak) if peak else None,
    }


def _metric_values(entries, run: Run) -> dict:
    out = {}
    for m in entries:
        reader = spec.load_module(f"metrics/{m['name']}.py")
        value = reader.read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             start: float, require_chip: bool = True,
             driver=None) -> dict:
    """Run the cell once; return the result (not yet printed)."""
    import jax

    devs = devices_for(cell, require_chip)
    clock = CompileClock()
    run = Run(cell=cell, seed=seed, seconds=seconds, traced=traced)
    if require_chip:
        run.peaks = peaks_for(devs[0].device_kind)
    if driver is None:
        driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    state = driver.setup(run)
    window_len = (min(seconds, cell.traffic["trace_seconds"]) if traced
                  else seconds)
    before = clock.snapshot()
    run.setup_s = time.perf_counter() - start
    log(f"setup_s {run.setup_s!r} (compile clock {before['seconds']!r} s, "
        f"persistent-cache hits {before['cache_hits']})")
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with tracing.capture(TRACE_DIR):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                driver.window(state, run, window_len)
            run.window_s = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        driver.window(state, run, window_len)
        run.window_s = time.perf_counter() - t0
    after = clock.snapshot()
    in_window = {k: after[k] - before[k]
                 for k in ("lowerings", "backend_compiles", "cache_hits")}
    run.facts["compiles_in_window"] = in_window
    log(f"compiles in window: {in_window['backend_compiles']} backend "
        f"compiles, {in_window['lowerings']} lowerings, "
        f"{in_window['cache_hits']} cache hits "
        f"({run.window_s!r} s window)")
    device = device_record(devs, cell.chips)
    if traced:
        t0 = time.perf_counter()
        run.trace = tracing.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        w0, w1 = run.trace.window()
        busy = tracing.covered(run.trace.busy(), w0, w1)
        device["busy_s"] = busy / max(run.trace.n_devices, 1)
        device["window_s"] = w1 - w0
        log(f"trace read in {time.perf_counter() - t0!r} s")
    metrics = _metric_values(
        cell.per_layer if traced else cell.end_to_end, run)
    driver.finish(state)
    gc.collect()
    t0 = time.perf_counter()
    checks = driver.check(state, run)
    log(f"check took {time.perf_counter() - t0!r} s")
    result = {
        "correct": bool(run.failed == 0 and all(c.ok for c in checks)),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = tracing.breakdown(run.trace)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


def main(workload: str, seed: int, seconds: float, traced: bool,
         start: float) -> int:
    try:
        cell = spec.load_cell(workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"bench: {e}")
        return 2
    use_compile_cache()
    try:
        result = run_cell(cell, seed, seconds, traced, start)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 2
    print(json.dumps(result), flush=True)
    return 0

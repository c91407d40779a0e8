"""The serving engine's spans in one traced window of a cell, per seed.

    python3 bench/checks/engine_spans.py --workload qwen2-0.5b.chat \
        --seeds 1,2 [--seconds 5] [--out <dir>]

For each seed it sets the cell up as a run does, drives the traffic's
traced window under the profiler (inside a ``bench.window`` span, as a
run with ``--trace 1``), and reads the trace with the engine's own
``repro.`` spans (``harness/program_spans.py``): the device clock's
offset, the six readings of ``program_spans.readings``, the window's idle
share and its idle time by the host's innermost span on the aligned
clock.  It prints one JSON line per seed, and with ``--out`` writes them
all to ``<dir>/<cell>.json``.  Like a run, it refuses to run
without a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import program_spans, runner, spec, tracing  # noqa: E402


def traced_window(driver, cell, seed: int, seconds: float) -> dict:
    """One seed: set-up, a traced window, and what its trace reads."""
    import jax

    run = runner.Run(cell=cell, seed=seed, seconds=seconds, traced=True)
    st = driver.setup(run)
    log_dir = tempfile.mkdtemp()
    try:
        with tracing.capture(log_dir):
            with jax.profiler.TraceAnnotation("bench.window"):
                driver.window(st, run, seconds)
        driver.finish(st)
        path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        trace = program_spans.from_file(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return {"seed": seed,
            "device_offset_ms": 1e3 * program_spans.device_offset(trace),
            "readings": program_spans.readings(trace),
            "idle_share": tracing.idle_share(trace, "bench.serve"),
            "idle_gaps": program_spans.idle_gaps(trace)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: the traffic's "
                         "trace_seconds)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runner.use_compile_cache()
    try:
        runner.devices_for(cell, require_chip=True)
    except runner.NoChip as e:
        print(f"engine_spans: {e}", file=sys.stderr)
        return 2
    driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    seconds = args.seconds or cell.traffic["trace_seconds"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(traced_window(driver, cell, seed, seconds))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.workload}.json"), "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings behind a cell's correctness limits, many seeds in one process.

    python3 bench/checks/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 5] [--out chiprun_out/readings]

For each seed it sets the cell up as a run does, drives a short window at
the cell's own load, frees the program's state and reads the numbers the
check compares: the program's (the lower readings, over ``--seeds``) and,
for ``--control-seeds``, the control's, the reference computed one
precision below the configuration's in the program's place (for the
bfloat16 model the traffic's stated control, fp8 weights, or those named
by ``--controls``; for the float32 plan sweep, bfloat16): the upper
readings.  It prints one JSON line per seed and the largest program
reading and smallest control reading of each number.  Like a run, it
refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import runner, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="",
                    help="model controls to read, e.g. int8,fp8")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=os.path.join(spec.ROOT, "chiprun_out",
                                                  "readings"))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runner.use_compile_cache()
    try:
        runner.devices_for(cell, require_chip=True)
    except runner.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(args.out, exist_ok=True)
    rows = []
    for seed in sorted(set(seeds) | controls):
        run = runner.Run(cell=cell, seed=seed, seconds=args.seconds,
                         traced=False)
        t0 = time.perf_counter()
        st = driver.setup(run)
        driver.window(st, run, args.seconds)
        driver.finish(st)
        row = {"seed": seed, "attempted": run.attempted,
               "failed": run.failed}
        if seed in seeds:
            row["program"] = driver.readings(st, run)
        for name in (args.controls.split(",") if seed in controls else []):
            row[f"control {name}".strip()] = driver.readings(
                st, run, name or cell.traffic["control"])
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        del st
    parts = sorted({p for r in rows for p in r
                    if p == "program" or p.startswith("control")})
    summary = {}
    for part in parts:
        for name in sorted({k for r in rows for k in r.get(part, {})}):
            vals = [r[part][name] for r in rows if part in r]
            summary[f"{part}: {name}"] = {
                "largest": max(vals), "smallest": min(vals), "all": vals}
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    with open(os.path.join(args.out, f"{args.workload}.json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

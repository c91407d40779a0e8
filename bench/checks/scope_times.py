"""Device time of the served programs by the model's named scopes.

    python3 bench/checks/scope_times.py --workload deepseek-v2-lite.chat \
        --seed 1 [--seconds 5] [--out <dir>]

It sets the cell up as a run does, compiles the engine's
``jit_serve_prefill`` and ``jit_serve_decode`` once more to read each
HLO op's ``op_name`` metadata (where ``jax.named_scope`` leaves
``mla.project``, ``mla.attend``, ``moe.route``, ``moe.experts`` and
``moe.shared``), drives the traffic's traced window under the profiler,
and sums each program's device self time by scope, per execution, with
the costliest ops of each scope.  Ops in no scope are ``other``.  It
prints one JSON line, and with ``--out`` writes it to
``<dir>/<cell>.scopes.json``.  Like a run, it refuses to run without a
TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import runner, spec, tracing  # noqa: E402

SCOPES = ("mla.project", "mla.attend", "moe.route", "moe.experts",
          "moe.shared")
_OP = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict:
    """HLO op name -> the first of ``SCOPES`` in its op_name, or other."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP.match(line)
        if m:
            out[m.group(1)] = next((s for s in SCOPES if s in m.group(2)),
                                   "other")
    return out


def _jitted(fn):
    """The jitted program behind the driver's counting wrapper."""
    for cell in fn.__closure__ or ():
        if callable(cell.cell_contents) and hasattr(cell.cell_contents,
                                                    "lower"):
            return cell.cell_contents
    return fn


def by_scope(trace, program: str, scopes: dict, top: int = 5) -> dict:
    runs = sum(1 for _, _, name, _ in trace.modules if name == program)
    ms, ops = defaultdict(float), defaultdict(list)
    for key, sec in trace.op_self.items():
        prog, op = key.split("/", 1)
        if prog != program:
            continue
        scope = scopes.get(op, "other")
        ms[scope] += 1e3 * sec / max(runs, 1)
        ops[scope].append((1e3 * sec / max(runs, 1), op))
    return {"executions": runs,
            "ms_per_execution": dict(sorted(ms.items(), key=lambda kv: -kv[1])),
            "top_ops_ms": {s: sorted(v, reverse=True)[:top]
                           for s, v in ops.items()}}


def program_scopes(st) -> dict:
    """{program: {HLO op: scope}} of the engine's ``jit_serve_prefill``
    and ``jit_serve_decode``, compiled once more at the traffic's batch."""
    import jax

    engine = st.engine
    prompts = engine._prompts(engine._prompt_key,
                              jax.numpy.arange(st.tr["batch_size"]))
    prefill, decode = _jitted(engine._prefill), _jitted(engine._decode)
    tok, state = prefill(engine.params, {"tokens": prompts})
    return {name: op_scopes(prog.lower(*args).compile().as_text())
            for name, prog, args in (
                ("jit_serve_prefill", prefill,
                 (engine.params, {"tokens": prompts})),
                ("jit_serve_decode", decode,
                 (engine.params, state, tok, engine._positions[0])))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runner.use_compile_cache()
    try:
        runner.devices_for(cell, require_chip=True)
    except runner.NoChip as e:
        print(f"scope_times: {e}", file=sys.stderr)
        return 2
    import jax

    driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    run = runner.Run(cell=cell, seed=args.seed, seconds=0.0, traced=True)
    st = driver.setup(run)
    tr = cell.traffic
    scopes = program_scopes(st)
    log_dir = tempfile.mkdtemp()
    try:
        with tracing.capture(log_dir):
            with jax.profiler.TraceAnnotation("bench.window"):
                driver.window(st, run, args.seconds or tr["trace_seconds"])
        path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        trace = tracing.from_file(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    row = {"workload": args.workload, "seed": args.seed,
           **{name: by_scope(trace, name, s) for name, s in scopes.items()}}
    print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.workload}.scopes.json"),
                  "w") as f:
            json.dump(row, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

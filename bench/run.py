"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  Its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``), the traffic's driver
(``bench/drivers/<driver>.py``) and each of its metrics
(``bench/metrics/<metric>.py``) are found by name, so a new cell or metric
is new files and entries, never an edit.

The run loads, warms up every shape the cell uses (``setup_s``), measures
for ``--seconds`` (or, with ``--trace 1``, the traffic's shorter traced
window under the profiler), checks what the timed path produced against
the configuration's plain reference, and prints one JSON line last on
stdout.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

import time

_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "src"))

from harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return runner.main(args.workload, args.seed, args.seconds,
                       bool(args.trace), start=_START)


if __name__ == "__main__":
    sys.exit(main())

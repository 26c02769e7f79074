#!/usr/bin/env python3
"""One benchmark run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload xlstm_350m.local --seed 7 --seconds 10 --trace 0

Makes the cell's weights and inputs from ``--seed``, warms up every shape the
window uses (counted as set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON object
as the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the lower-precision control in place of "
                         "the program (sets the upper readings of the limits)")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    devices = harness.accelerator(cell.chips)
    if devices is None:
        return 2
    try:
        harness.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, devices, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t0=T0, control=args.control)
    for line in result.check_lines():
        print(line, file=sys.stderr)
    print(json.dumps(result.line()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

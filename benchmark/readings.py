"""``python -m benchmark.readings --workload <cell> --seeds a,b,c
[--control 1]``: the numbers the ``correct`` limits are set from.

For each seed, in one process: new weights from the seed, a short window
of the cell's own traffic at its own rate (long enough to finish the
mix's longest requests), and the numbers a run compares — for the
program and, with ``--control 1``, for the int8 forward put in the
program's place at the same prompts and tokens.  Not part of a benchmark
run; PERF.md records what it read.
"""

from __future__ import annotations

import argparse
import sys

from benchmark import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    harness.load_runner(cell).readings(cell, devices, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())

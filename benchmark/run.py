"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, one JSON line last on stdout.

Set-up (weights from the seed, the engine or executor, every program the
window will use) is timed from the start of the process; then the window
measures for ``--seconds``; then, outside it, the cell's output is
compared with the plain reference.  With ``--trace 0`` the line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
and the device's busy time from the profiler's trace.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    try:  # the system under test: without it there is nothing to measure
        import distributed_llm_scheduler_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e}); "
              "no result", file=sys.stderr)
        return 4
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    runner = harness.load_runner(cell)
    harness.log(f"cell {cell.name}: config {cell.config_name}, traffic "
                f"{cell.traffic_name}, {cell.chips} chip(s), seed "
                f"{args.seed}, {args.seconds} s, trace {args.trace}")
    line = runner.run(cell, devices, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=_T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

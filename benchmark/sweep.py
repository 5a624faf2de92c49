"""``python -m benchmark.sweep --workload <cell> --rates r1,r2,...``:
find the highest rate a served cell sustains, once, on the chip.

One process, one engine; per rate one window of ``--seconds`` at the same
seed.  A rate is sustained when the backlog (requests due and not yet
retired) at the end of the window is no larger than at mid-window.  The
cell's ``rate_rps`` is then written, by hand, as 0.8 x the highest
sustained rate.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import harness, stats
from benchmark.runners import serve as S
from benchmark.traffic import open_loop


def backlog(records, t):
    return sum(1 for r in records if r["due"] <= t
               and (r["t_retire"] is None or r["t_retire"] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260927)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    ref = harness.load_reference(cell.config)
    weights = ref.make_params(cell.config, args.seed)
    engine = S.build_engine(cell.config, devices[0], weights)
    S.warm_up(engine, cell.config, cell.traffic, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        arrivals = open_loop.generate(cell.traffic, rate, args.seconds,
                                      args.seed, rid_prefix=f"s{i}_")
        out = S.serve(engine, cell.config, arrivals, seed=args.seed,
                      slo_ttft_s=3600.0, drain_s=120.0, seconds=args.seconds)
        rec, t0 = out["records"], out["t0"]
        row = {
            "rate_rps": rate, "n": len(rec),
            "failed": sum(r["failed"] for r in rec),
            "backlog_mid": backlog(rec, t0 + args.seconds / 2),
            "backlog_end": backlog(rec, t0 + args.seconds),
            "drain_s": out["t_end"] - t0 - args.seconds,
            "out_tok_s": stats.tokens_in_window(
                rec, t0, t0 + args.seconds) / args.seconds,
        }
        for f in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            vals = [r[f] for r in rec if r[f] is not None]
            row[f + "_p50"] = stats.percentile(vals, 50)
            row[f + "_p90"] = stats.percentile(vals, 90)
        row["late"] = out["late"]
        print("SWEEP " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

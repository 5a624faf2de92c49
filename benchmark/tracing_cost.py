"""``python -m benchmark.tracing_cost --workload <cell> --seed <n>
--seconds <s>``: what the program's own ``Tracer`` costs when it is
attached, on the cell's own path, with the profiler never on.

Placed-DAG cell, one process: a window of steps with no tracer, a window
of steps that all record into ONE ``Tracer`` (so a cost that grows with
the tracer's length shows as a slower last third), and a window with no
tracer again; each window ``--seconds`` long.  Served cell, one process:
the cell's window served twice with the same seed, by an engine built
without a tracer and then by one built with it; ``tpot_ms_mean`` of
each, as a run computes it.  Not part of a benchmark run; PERF.md
records what it read.  One JSON line last on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from typing import Any, Dict, List

from benchmark import harness

CLOCK = time.perf_counter


def _thirds(ms: List[float]) -> Dict[str, Any]:
    k = max(len(ms) // 3, 1)
    return {"steps": len(ms), "p50_ms": statistics.median(ms),
            "first_third_p50_ms": statistics.median(ms[:k]),
            "last_third_p50_ms": statistics.median(ms[-k:]),
            "max_ms": max(ms)}


def dag(cell: harness.Cell, devices: List[Any], seed: int,
        seconds: float) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from benchmark.runners import dag as runner
    from benchmark.traffic import closed_loop
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    ref = harness.load_reference(cell.config)
    with jax.default_device(devices[0]):
        weights = ref.make_params(cell.config, seed)
        ids = jnp.asarray(closed_loop.input_ids(
            cell.traffic, int(cell.config["vocab_size"]), seed))
    graph_dag, schedule, backend = runner.build(
        cell.config, cell.traffic, devices)
    for kw in ({}, {"warmup": False}):
        jax.block_until_ready(backend.execute(
            graph_dag.graph, schedule, weights, ids, **kw).output)
    gc.collect()

    def window(tracer: Any) -> List[float]:
        ms, t0 = [], CLOCK()
        while CLOCK() - t0 < seconds:
            a = CLOCK()
            rep = backend.execute(graph_dag.graph, schedule, weights, ids,
                                  warmup=False, trace=tracer)
            jax.block_until_ready(rep.output)
            ms.append((CLOCK() - a) * 1e3)
        return ms

    tracer = Tracer(clock=CLOCK)
    before, traced, after = window(None), window(tracer), window(None)
    untraced = statistics.median(before + after)
    out = {"untraced_before": _thirds(before), "traced": _thirds(traced),
           "untraced_after": _thirds(after), "events": len(tracer.events),
           "events_per_step": len(tracer.events) / len(traced),
           "traced_over_untraced":
               statistics.median(traced) / untraced}
    harness.log(f"tracer attached to execute(): {out}")
    return out


def serve(cell: harness.Cell, devices: List[Any], seed: int,
          seconds: float) -> Dict[str, Any]:
    from benchmark.runners import serve as runner
    from benchmark.traffic import open_loop
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    ref = harness.load_reference(cell.config)
    weights = ref.make_params(cell.config, seed)
    arrivals = open_loop.generate(
        cell.traffic, float(cell.params["rate_rps"]), seconds, seed)
    out: Dict[str, Any] = {}
    for name in ("untraced", "traced"):
        tracer = Tracer(clock=CLOCK) if name == "traced" else None
        engine = runner.build_engine(cell.config, devices[0], weights, tracer)
        runner.warm_up(engine, cell.config, cell.traffic, seed)
        if tracer is not None:
            tracer.events.clear()
        gc.collect()
        served = runner.serve(
            engine, cell.config, arrivals, seed=seed,
            slo_ttft_s=float(cell.params["slo_ttft_s"]),
            drain_s=float(cell.params["drain_s"]), seconds=seconds)
        tpot = [r["tpot_ms"] for r in served["records"]
                if r["tpot_ms"] is not None]
        out[name] = {
            "tpot_ms_mean": statistics.fmean(tpot), "n": len(tpot),
            "failed": sum(r["failed"] for r in served["records"]),
            "events": len(tracer.events) if tracer is not None else 0}
        harness.log(f"{name}: {out[name]}")
        served.pop("fe").engine = None
        del engine, served
        gc.collect()
    out["traced_over_untraced"] = (out["traced"]["tpot_ms_mean"]
                                   / out["untraced"]["tpot_ms_mean"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    kind = {"dag": dag, "serve": serve}[cell.config["runner"]]
    out = kind(cell, devices, args.seed, args.seconds)
    print(json.dumps(dict(out, workload=cell.name, seed=args.seed,
                          seconds=args.seconds,
                          device=harness.device_block(devices))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

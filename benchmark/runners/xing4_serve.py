"""Runner for the Xing4.0 served cells: ``runners/serve.py``'s path —
``build_paged_decode_dag`` -> scheduler -> ``DeviceBackend.
paged_decode_engine`` -> ``ServingFrontend`` on the wall clock — with
this family's model config and a **pinned schedule**.

The arrival offsets, prompt lengths, output lengths and their order come
from ``open_loop.generate`` called with the traffic file's own
``schedule_seed``, never with ``--seed``: every seed offers the same
requests at the same offsets, so the spread between runs is the
system's (PERF.md, PR 26: with requests that differ 4 x in length the
order alone moved ``tpot_ms_mean`` 6-7%).  ``--seed`` makes the weights
and, through ``prompt_token_ids``, the tokens.  Serving, warm-up, the
token check, the verdicts and the trace reduction are ``serve.py``'s.
"""

from __future__ import annotations

import dataclasses
import gc
from typing import Any, Dict, List

from .. import harness, stats
from ..traffic import open_loop
from . import serve as base

CLOCK = base.CLOCK


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.xing4 import Xing4Config

    return Xing4Config.from_hf(config, dtype=jnp.dtype(config["dtype"]))


def build_engine(config: Dict[str, Any], device: Any, weights: Dict[str, Any],
                 tracer: Any = None):
    """The engine as ``cmd_serve`` builds it, at the file's geometry."""
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.kv_pages import PagePool
    from distributed_llm_scheduler_tpu.obs.metrics import MetricsRegistry

    geo = config["engine"]
    mcfg = model_config(config)
    impl = geo.get("attention_impl")
    ddag = build_paged_decode_dag(
        mcfg, slots=geo["slots"], page_size=geo["page_size"],
        n_pages=geo["n_pages"], pages_per_seq=geo["pages_per_seq"],
        attention_impl=impl,
    )
    cluster = Cluster.from_jax_devices([device])
    schedule = get_scheduler(geo["scheduler"]).schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=geo["n_pages"], page_size=geo["page_size"])
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, schedule, mcfg, weights, pool, slots=geo["slots"],
        pages_per_seq=geo["pages_per_seq"], seg_steps=geo["seg_steps"],
        trace=tracer, metrics=MetricsRegistry(), clock=CLOCK,
        attention_impl=impl, chunk_tokens=geo.get("chunk_tokens"),
    )


def schedule(traffic: Dict[str, Any], rate_rps: float,
             seconds: float) -> List[Any]:
    """The cell's arrivals: a function of the traffic file alone."""
    return open_loop.generate(traffic, rate_rps, seconds,
                              int(traffic["schedule_seed"]))


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: bool = False) -> Dict[str, Any]:
    """``serve.check_tokens`` with the reference compiled for the mix's
    longest request (rounded up to its query block), not for the slot's
    whole capacity: the cost of the float32 forward follows that length."""
    t, geo = cell.traffic, dict(cell.config["engine"])
    need = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    block = max(geo["page_size"], 1024)
    geo["pages_per_seq"] = min(
        geo["pages_per_seq"], -(-need // block) * block // geo["page_size"])
    sized = dataclasses.replace(cell, config=dict(cell.config, engine=geo))
    return base.check_tokens(sized, weights, served, seed, control=control)


def run(cell: harness.Cell, devices: List[Any], *, seed: int, seconds: float,
        trace: bool, t_start: float) -> str:
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    config, traffic, params = cell.config, cell.traffic, cell.params
    model_config(config)    # a program without the family fails here, at once
    counter = harness.CompileCounter()
    ref = harness.load_reference(config)
    weights = ref.make_params(config, seed)
    tracer = Tracer(clock=CLOCK) if trace else None
    engine = build_engine(config, devices[0], weights, tracer)
    harness.log(f"attention impl resolved to "
                f"{engine.resolved_attention_impl!r}")
    base.warm_up(engine, config, traffic, seed)
    arrivals = schedule(traffic, float(params["rate_rps"]), seconds)
    setup = counter.snapshot()
    if tracer is not None:
        tracer.events.clear()
    gc.collect()
    gc.freeze()
    setup_s = CLOCK() - t_start
    harness.log(f"set-up {setup_s:.2f} s: {setup}")

    slice_ = harness.TraceSlice(cell.root, cell.name,
                                float(params["trace_seconds"]), trace, CLOCK)
    window = {}

    def hook(now: float) -> None:   # the first tick opens the window
        slice_.poll(now, window.setdefault("end", now + seconds))

    served = base.serve(engine, config, arrivals, seed=seed,
                        slo_ttft_s=float(params["slo_ttft_s"]),
                        drain_s=float(params["drain_s"]), seconds=seconds,
                        tick_hook=hook)
    slice_.finish()
    in_window = counter.snapshot()["compiles"] - setup["compiles"]
    t0, records = served["t0"], served["records"]
    n_tok = stats.tokens_in_window(records, t0, t0 + seconds)
    failed = sum(1 for r in records if r["failed"])
    harness.log(
        f"window: {len(records)} requests due, {failed} failed, {n_tok} "
        f"tokens in {seconds} s; run ended {served['t_end'] - t0:.2f} s "
        f"after window start; generator lateness {served['late']}; "
        f"compilations in window+drain: {in_window}")
    for f in ("ttft_ms", "tpot_ms"):
        vals = sorted(r[f] for r in records if r[f] is not None)
        harness.log(f"{f}: n={len(vals)} mean={sum(vals) / max(len(vals), 1):.1f} "
                    + " ".join(f"p{q}={stats.percentile(vals, q):.1f}"
                               for q in (50, 75, 90))
                    + f" sorted={[round(v, 1) for v in vals]}")
    device = harness.device_block(devices)
    spans = list(tracer.events) if tracer is not None else []

    # the program's state goes before the reference comes
    served.pop("fe").engine = None
    del engine
    gc.unfreeze()
    gc.collect()
    check = check_tokens(cell, weights, served, seed)
    harness.log(f"reference check: {check}")
    verdicts = base.decide(cell, served, check, in_window)

    read_rows = stats.closed_before(records, slice_.t_before)
    ctx: Dict[str, Any] = {
        "config": config, "traffic": traffic, "records": read_rows,
        "seconds": seconds, "t0": t0, "spans": spans, "trace": slice_.trace,
        "device_kind": device["kind"], "n_devices": 1,
        "values": {"setup_s": setup_s, "window_tok_s": n_tok / seconds},
    }
    breakdown = base.add_trace(ctx, slice_, device) if trace else None
    defs = cell.per_layer if trace else cell.end_to_end
    return harness.result_line(
        correct=all(v["ok"] for v in verdicts), attempted=len(records),
        failed=failed, metrics=harness.read_metrics(defs, ctx),
        device=device, breakdown=breakdown,
    )


def readings(cell: harness.Cell, devices: List[Any], args: Any) -> None:
    """For ``benchmark.readings``: per seed, in one process, the numbers a
    run compares — the program's and, with ``--control 1``, the int8
    forward's at the same prompts; the schedule is the cell's own."""
    import json

    ref = harness.load_reference(cell.config)
    for i, seed in enumerate(args.seeds):
        # an engine a seed: the float32 forward does not fit beside the
        # pools, so the engine goes before the check (as in ``run``)
        weights = ref.make_params(cell.config, seed)
        engine = build_engine(cell.config, devices[0], weights)
        base.warm_up(engine, cell.config, cell.traffic, seed)
        arrivals = [
            open_loop.Request(f"k{i}_{a.rid}", a.t, a.prompt_len,
                              a.max_new_tokens)
            for a in schedule(cell.traffic, float(cell.params["rate_rps"]),
                              args.seconds)]
        out = base.serve(engine, cell.config, arrivals, seed=seed,
                         slo_ttft_s=3600.0, drain_s=240.0,
                         seconds=args.seconds)
        out.pop("fe").engine = None
        del engine
        gc.collect()
        row = {"seed": seed, "n": len(out["records"]),
               "failed": sum(r["failed"] for r in out["records"]),
               "program": check_tokens(cell, weights, out, seed)}
        if args.control:
            row["control"] = check_tokens(cell, weights, out, seed,
                                          control=True)
        print("READING " + json.dumps(row), flush=True)
        del weights, out
        gc.collect()
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)


def sweep(argv=None) -> int:
    """``python -m benchmark.runners.xing4_serve --workload <cell> --rates
    r1,r2,...``: ``benchmark.sweep`` for this runner's cells — one engine,
    per rate one window of the cell's pinned schedule at that rate; a rate
    is sustained when the backlog at the end of the window is no larger
    than at mid-window.  Not part of a benchmark run."""
    import argparse
    import json

    from ..sweep import backlog

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=20260928)
    ap.add_argument("--prompt-bounds", default=None,
                    help="lo,hi in place of the traffic file's, to size a mix")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.prompt_bounds:
        lo, hi = (int(x) for x in args.prompt_bounds.split(","))
        cell.traffic["prompt_len"].update(lo=lo, hi=hi)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    weights = harness.load_reference(cell.config).make_params(
        cell.config, args.seed)
    engine = build_engine(cell.config, devices[0], weights)
    base.warm_up(engine, cell.config, cell.traffic, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        arrivals = [
            open_loop.Request(f"s{i}_{a.rid}", a.t, a.prompt_len,
                              a.max_new_tokens)
            for a in schedule(cell.traffic, rate, args.seconds)]
        out = base.serve(engine, cell.config, arrivals, seed=args.seed,
                         slo_ttft_s=3600.0, drain_s=240.0,
                         seconds=args.seconds)
        rec, t0 = out["records"], out["t0"]
        row = {"rate_rps": rate, "n": len(rec),
               "failed": sum(r["failed"] for r in rec),
               "backlog_mid": backlog(rec, t0 + args.seconds / 2),
               "backlog_end": backlog(rec, t0 + args.seconds),
               "drain_s": out["t_end"] - t0 - args.seconds,
               "out_tok_s": stats.tokens_in_window(
                   rec, t0, t0 + args.seconds) / args.seconds}
        for f in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            vals = [r[f] for r in rec if r[f] is not None]
            row[f + "_p50"] = stats.percentile(vals, 50)
            row[f + "_p90"] = stats.percentile(vals, 90)
        row["late"] = out["late"]
        print("SWEEP " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(sweep())

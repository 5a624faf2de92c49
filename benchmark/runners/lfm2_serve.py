"""Runner for the LFM2 served cells: ``runners/xing4_serve.py``'s path
and pinned schedule (``schedule_seed`` in the traffic file makes the
arrivals, ``--seed`` the weights and tokens) with this family's model
config — ``runners/nemotron_serve.py``'s shape, the other family with a
state a slot.

The family has state layers, so the engine prefills every prompt through
the chunk program — a short one as one padded chunk — and the window
compiles two programs, the segment and the chunk: ``serve.warm_up`` (the
mix's shortest and longest prompt) drives both.  The engine is handed the
reference's own arrays (the chip never holds a second 10.5 GB), its
pools go before the check, and the reference is compiled for the
request's length rounded up to a power of two
(``laguna_serve.reference_rows``).

Serving, the verdicts every served cell shares and the trace reduction
are ``serve.py``'s; the schedule is ``xing4_serve.py``'s; the sample and
its check ``laguna_serve.py``'s.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from .. import harness, stats
from . import serve as base
from .laguna_serve import _renamed, check_tokens
from .xing4_serve import schedule

CLOCK = base.CLOCK
#: the reference's controls a reading reports beside the program's
#: numbers: int8 everywhere, and float32 with the conv layers' carried
#: rows lost at every chunk boundary and every 64 decoded tokens
CONTROLS = {"control": True, "control_conv_state_lost": "conv_state_lost"}


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.lfm2 import Lfm2Config

    return Lfm2Config.from_hf(config, dtype=jnp.dtype(config["dtype"]))


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
    plan = get_scheduler(geo["scheduler"]).schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=geo["n_pages"], page_size=geo["page_size"])
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, mcfg, weights, pool, slots=geo["slots"],
        pages_per_seq=geo["pages_per_seq"], seg_steps=geo["seg_steps"],
        trace=tracer, metrics=MetricsRegistry(), clock=CLOCK,
        attention_impl=impl, chunk_tokens=geo.get("chunk_tokens"),
    )


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
    arrivals = schedule(traffic, float(params["rate_rps"]), seconds)
    base.warm_up(engine, config, traffic, seed)
    setup = counter.snapshot()
    harness.log(f"prefill / decode program classes compiled: the segment "
                f"and {sorted(map(str, engine._prefill_store))}")
    if tracer is not None:
        tracer.events.clear()
    gc.collect()
    gc.freeze()
    setup_s = CLOCK() - t_start
    harness.log(f"set-up {setup_s:.2f} s: {setup}")

    slice_ = harness.TraceSlice(cell.root, cell.name,
                                float(params["trace_seconds"]), trace, CLOCK)
    window = {}
    peak = {"pages": 0, "slots": 0}

    def hook(now: float) -> None:   # the first tick opens the window
        peak["pages"] = max(peak["pages"], engine.pool.used_pages)
        peak["slots"] = max(peak["slots"], engine.slots - engine.free_slots)
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
    snap = engine.metrics.snapshot()
    counted = {k: v["value"] for k, v in snap["counters"].items()
               if k.startswith(("ssm.", "decode.chunk", "decode.state"))}
    counted.update({k: v["p50"] for k, v in snap["histograms"].items()
                    if k.startswith(("conv.", "moe.", "decode.page_pool"))})
    harness.log(
        f"window: {len(records)} requests due, {failed} failed, {n_tok} "
        f"tokens in {seconds} s; run ended {served['t_end'] - t0:.2f} s "
        f"after window start; generator lateness {served['late']}; "
        f"compilations in window+drain: {in_window}; most pages in use "
        f"{peak['pages']} of {engine.pool.n_pages - 1}, most slots "
        f"{peak['slots']} of {engine.slots}; engine counters and medians "
        f"{counted}")
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
    run compares — the program's and, with ``--control 1``, each of
    :data:`CONTROLS`' forwards' at the same prompts; the schedule is the
    cell's own."""
    import json

    ref = harness.load_reference(cell.config)
    arrivals = schedule(cell.traffic, float(cell.params["rate_rps"]),
                        args.seconds)
    for i, seed in enumerate(args.seeds):
        # an engine a seed: the float32 forward does not fit beside the
        # pools, so the engine goes before the check (as in ``run``)
        weights = ref.make_params(cell.config, seed)
        engine = build_engine(cell.config, devices[0], weights)
        base.warm_up(engine, cell.config, cell.traffic, seed)
        out = base.serve(
            engine, cell.config, _renamed(arrivals, f"k{i}_"), seed=seed,
            slo_ttft_s=3600.0, drain_s=240.0, seconds=args.seconds)
        out.pop("fe").engine = None
        del engine
        gc.collect()
        row = {"seed": seed, "n": len(out["records"]),
               "failed": sum(r["failed"] for r in out["records"]),
               "program": check_tokens(cell, weights, out, seed)}
        for name, control in CONTROLS.items() if args.control else ():
            row[name] = check_tokens(cell, weights, out, seed,
                                     control=control)
        print("READING " + json.dumps(row), flush=True)
        del weights, out
        gc.collect()
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)


def _widths(spans: List[Dict[str, Any]], steps: int, lo: float,
            hi: float) -> List[float]:
    """Slots a step of the ``segment`` spans that began in ``[lo, hi]``:
    ``conv_slots`` (slot-steps of the segment) over ``steps``."""
    return [float(e["args"]["conv_slots"]) / steps for e in spans
            if e.get("type") == "span" and e.get("name") == "segment"
            and "conv_slots" in e.get("args", {}) and lo <= e["t0"] <= hi]


def sweep(argv=None) -> int:
    """``python -m benchmark.runners.lfm2_serve --workload <cell>
    --rates r1,r2,...``: one engine, per rate one window of the cell's
    pinned schedule at that rate, drained to empty before the next; a
    rate is sustained when the backlog at the end of the window is no
    larger than at mid-window.  The width of the step is read PER RATE:
    the engine gets a registry of its own for each (``slots_stepped_p50``
    is ``conv_slots_stepped`` as a run at that rate alone would print it,
    ramp and drain included), and the ``segment`` spans give the median
    inside the window and the mean over its last ``trace_seconds`` (what
    ``conv_slots_traced`` reads).  ``--output-len lo,hi`` sweeps another
    output range at the same prompts (``max_total`` and the slots' pages
    follow it; the pool stays).  The span tracer the widths are read
    from costs the host ~5% at 128 slots, so a row's ``tpot_ms_mean`` reads
    that much over a run's.  Not part of a benchmark run."""
    import argparse
    import json

    from distributed_llm_scheduler_tpu.obs.metrics import MetricsRegistry
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    from ..sweep import backlog

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=20261003)
    ap.add_argument("--output-len", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    config, traffic = cell.config, cell.traffic
    if args.output_len:
        lo, hi = (int(v) for v in args.output_len.split(","))
        traffic = dict(traffic, output_len=dict(
            traffic["output_len"], lo=lo, hi=hi),
            max_total=int(traffic["prompt_len"]["hi"]) + hi)
        geo = dict(config["engine"])
        geo["pages_per_seq"] = -(-traffic["max_total"] // geo["page_size"])
        config = dict(config, engine=geo)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    weights = harness.load_reference(config).make_params(config, args.seed)
    tracer = Tracer(clock=CLOCK)
    engine = build_engine(config, devices[0], weights, tracer)
    base.warm_up(engine, config, traffic, args.seed)
    steps, tail = config["engine"]["seg_steps"], float(
        cell.params["trace_seconds"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        peak = {"pages": 0, "slots": 0}
        engine.metrics = MetricsRegistry()
        tracer.events.clear()

        def hook(_now: float) -> None:
            peak["pages"] = max(peak["pages"], engine.pool.used_pages)
            peak["slots"] = max(peak["slots"],
                                engine.slots - engine.free_slots)

        out = base.serve(
            engine, config,
            _renamed(schedule(traffic, rate, args.seconds), f"s{i}_"),
            seed=args.seed, slo_ttft_s=3600.0, drain_s=240.0,
            seconds=args.seconds, tick_hook=hook)
        rec, t0 = out["records"], out["t0"]
        t1 = t0 + args.seconds
        row = {"rate_rps": rate, "n": len(rec),
               "output_len": [traffic["output_len"]["lo"],
                              traffic["output_len"]["hi"]],
               "failed": sum(r["failed"] for r in rec),
               "backlog_mid": backlog(rec, t0 + args.seconds / 2),
               "backlog_end": backlog(rec, t1),
               "drain_s": out["t_end"] - t1,
               "pages_peak": peak["pages"], "slots_peak": peak["slots"],
               "out_tok_s": stats.tokens_in_window(
                   rec, t0, t1) / args.seconds}
        for f in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            vals = [r[f] for r in rec if r[f] is not None]
            row[f + "_p50"] = stats.percentile(vals, 50)
            row[f + "_p90"] = stats.percentile(vals, 90)
        row["tpot_ms_mean"] = float(np.mean(
            [r["tpot_ms"] for r in rec if r["tpot_ms"] is not None]))
        row["late"] = out["late"]
        row["slots_stepped_p50"] = engine.metrics.snapshot()[
            "histograms"].get("conv.slots_stepped", {}).get("p50")
        spans = list(tracer.events)
        inside = _widths(spans, steps, t0, t1)
        last = _widths(spans, steps, t1 - tail, t1)
        row["slots_window_p50"] = stats.percentile(inside, 50)
        row["slots_last_mean"] = sum(last) / len(last) if last else None
        print("SWEEP " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(sweep())

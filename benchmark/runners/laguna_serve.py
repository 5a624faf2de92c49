"""Runner for the Laguna served cells: ``runners/xing4_serve.py``'s path
and pinned schedule (``schedule_seed`` in the traffic file makes the
arrivals, ``--seed`` the weights and tokens) with this family's model
config, over a mix whose requests differ a hundredfold in length.

Two things follow from the mix and are spelled out here.  A prompt no
longer than a chunk is prefilled whole, by a program of its own length:
the schedule is a function of the traffic file alone, so the warm-up
walks it and compiles every such length before the window.  And the
reference is compiled for the request's length rounded up to a power of
two, not for the mix's longest: a 33k-token float32 forward costs a
hundred times a 2k-token one.

Serving, the verdicts every served cell shares and the trace reduction
are ``serve.py``'s; the schedule is ``xing4_serve.py``'s.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from .. import harness, stats
from ..traffic import open_loop
from . import serve as base
from .xing4_serve import schedule

CLOCK = base.CLOCK
#: the shortest length the reference is compiled for
MIN_REFERENCE_ROWS = 2048


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.laguna import LagunaConfig

    geo = config["engine"]
    return LagunaConfig.from_hf(
        config, dtype=jnp.dtype(config["dtype"]),
        ring_rows=int(geo["ring_pages"]) * int(geo["page_size"]))


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


def warm_up(engine: Any, config: Dict[str, Any], traffic: Dict[str, Any],
            seed: int, arrivals: List[Any]) -> None:
    """``serve.warm_up`` (the mix's shortest and longest prompt: the
    segment, the chunk program and a whole-prompt program) and then one
    request for every other prompt of the schedule that is prefilled
    whole — no longer than a chunk — a length a program."""
    base.warm_up(engine, config, traffic, seed)
    chunk = int(config["engine"]["chunk_tokens"])
    whole = sorted({a.prompt_len for a in arrivals if a.prompt_len <= chunk}
                   - {int(traffic["prompt_len"]["lo"])})
    if not whole:
        return
    out = base.serve(
        engine, config,
        [open_loop.Request(f"warmp{p}", 0.0, p, 2) for p in whole],
        seed=seed, slo_ttft_s=3600.0, drain_s=600.0, seconds=600.0)
    if any(r["failed"] for r in out["records"]):
        raise RuntimeError("warm-up requests did not complete")
    harness.log(f"warmed {len(whole)} whole-prompt lengths: {whole}")


def reference_rows(n_tokens: int, cap: int) -> int:
    """The length the reference is compiled for to judge a request of
    ``n_tokens``: the next power of two, within the slot's capacity."""
    return min(cap, max(MIN_REFERENCE_ROWS, 1 << (n_tokens - 1).bit_length()))


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: bool = False) -> Dict[str, Any]:
    """``serve.check_tokens`` — the sample's served tokens (the longest
    request always in it) teacher-forced through the plain reference,
    the widest and the mean gap — with the reference compiled for each
    request's own length (:func:`reference_rows`)."""
    ref = harness.load_reference(cell.config)
    done = [r for r in served["records"] if not r["failed"]]
    if not done:
        return {"n_requests": 0, "n_tokens": 0, "gap_max": float("inf"),
                "gap_mean": float("inf"), "distinct_share": 0.0}
    rng = open_loop._rng(seed, 5)
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_served"])
    rest = [r for r in done if r is not longest]
    k = min(int(cell.params["check_requests"]) - 1, len(rest))
    sample = [longest] + [rest[i] for i in rng.permutation(len(rest))[:k]]
    geo = cell.config["engine"]
    cap = geo["pages_per_seq"] * geo["page_size"]
    gaps, distinct, t0 = [], [], CLOCK()
    for r in sample:
        prompt = open_loop.prompt_token_ids(
            r["rid"], r["prompt_len"], int(cell.config["vocab_size"]), seed)[0]
        toks = served["tokens"][r["rid"]]
        seq = np.concatenate([prompt, toks])
        gaps.append(ref.served_gaps(
            weights, cell.config, seq, r["prompt_len"], len(toks),
            reference_rows(len(seq), cap), control=control))
        distinct.append(len(set(toks.tolist())) / len(toks))
    g = np.concatenate(gaps)
    return {"n_requests": len(sample), "n_tokens": int(g.size),
            "gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "flips": int((g > 0).sum()),
            "distinct_share": float(np.mean(distinct)),
            "lengths": [r["prompt_len"] + r["n_served"] for r in sample],
            "seconds": CLOCK() - t0}


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
    warm_up(engine, config, traffic, seed, arrivals)
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
    pages = {"peak": 0}

    def hook(now: float) -> None:   # the first tick opens the window
        pages["peak"] = max(pages["peak"], engine.pool.used_pages)
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
        f"compilations in window+drain: {in_window}; most pages in use "
        f"{pages['peak']} of {engine.pool.n_pages - 1}")
    for f in ("ttft_ms", "tpot_ms"):
        vals = sorted(r[f] for r in records if r[f] is not None)
        harness.log(f"{f}: n={len(vals)} mean={sum(vals) / max(len(vals), 1):.1f} "
                    + " ".join(f"p{q}={stats.percentile(vals, q):.1f}"
                               for q in (50, 75, 90))
                    + f" sorted={[round(v, 1) for v in vals]}")
    harness.log("requests by arrival (at s, prompt, out, ttft_ms, tpot_ms): "
                + str([(round(a.t, 2), r["prompt_len"], r["n_served"],
                        r["ttft_ms"] and round(r["ttft_ms"]),
                        r["tpot_ms"] and round(r["tpot_ms"], 1))
                       for a, r in zip(arrivals, records)]))
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


def _renamed(arrivals: List[Any], prefix: str) -> List[Any]:
    return [open_loop.Request(prefix + a.rid, a.t, a.prompt_len,
                              a.max_new_tokens) for a in arrivals]


def readings(cell: harness.Cell, devices: List[Any], args: Any) -> None:
    """For ``benchmark.readings``: per seed, in one process, the numbers a
    run compares — the program's and, with ``--control 1``, the int8
    forward's at the same prompts; the schedule is the cell's own."""
    import json

    ref = harness.load_reference(cell.config)
    arrivals = schedule(cell.traffic, float(cell.params["rate_rps"]),
                        args.seconds)
    for i, seed in enumerate(args.seeds):
        # an engine a seed: the float32 forward does not fit beside the
        # pools, so the engine goes before the check (as in ``run``)
        weights = ref.make_params(cell.config, seed)
        engine = build_engine(cell.config, devices[0], weights)
        warm_up(engine, cell.config, cell.traffic, seed, arrivals)
        out = base.serve(
            engine, cell.config, _renamed(arrivals, f"k{i}_"), seed=seed,
            slo_ttft_s=3600.0, drain_s=240.0, seconds=args.seconds)
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
    """``python -m benchmark.runners.laguna_serve --workload <cell> --rates
    r1,r2,...``: one engine, per rate one window of the cell's pinned
    schedule at that rate; a rate is sustained when the backlog at the
    end of the window is no larger than at mid-window.  Not part of a
    benchmark run."""
    import argparse
    import json

    from ..sweep import backlog

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=20260930)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    weights = harness.load_reference(cell.config).make_params(
        cell.config, args.seed)
    engine = build_engine(cell.config, devices[0], weights)
    rates = [float(r) for r in args.rates.split(",")]
    warm_up(engine, cell.config, cell.traffic, args.seed,
            [a for r in rates for a in schedule(cell.traffic, r, args.seconds)])
    for i, rate in enumerate(rates):
        peak = {"pages": 0}

        def hook(_now: float) -> None:
            peak["pages"] = max(peak["pages"], engine.pool.used_pages)

        out = base.serve(
            engine, cell.config,
            _renamed(schedule(cell.traffic, rate, args.seconds), f"s{i}_"),
            seed=args.seed, slo_ttft_s=3600.0, drain_s=240.0,
            seconds=args.seconds, tick_hook=hook)
        rec, t0 = out["records"], out["t0"]
        row = {"rate_rps": rate, "n": len(rec),
               "failed": sum(r["failed"] for r in rec),
               "backlog_mid": backlog(rec, t0 + args.seconds / 2),
               "backlog_end": backlog(rec, t0 + args.seconds),
               "drain_s": out["t_end"] - t0 - args.seconds,
               "pages_peak": peak["pages"],
               "out_tok_s": stats.tokens_in_window(
                   rec, t0, t0 + args.seconds) / args.seconds}
        for f in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            vals = [r[f] for r in rec if r[f] is not None]
            row[f + "_p50"] = stats.percentile(vals, 50)
            row[f + "_p90"] = stats.percentile(vals, 90)
        row["tpot_ms_mean"] = float(np.mean(
            [r["tpot_ms"] for r in rec if r["tpot_ms"] is not None]))
        row["late"] = out["late"]
        print("SWEEP " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(sweep())

"""Runner for the Ouro served cells: ``runners/laguna_serve.py``'s path
and pinned schedule (``schedule_seed`` in the traffic file makes the
arrivals, ``--seed`` the weights and tokens) with this family's model
config and reference.

What is this runner's own: the reference's stacked layers are made once a
check and dropped after it (they are one more copy of 4.9 GB of weights,
and the next engine's pools need the room); the reference is compiled for
each request's own length rounded up to a power of two; ``readings``
judges two controls, the int8 forward and the float32 forward with its
last pass left out; the sweep prints what the pool did (pages in use,
chunks refused their pages, requests preempted) and can scan ``init``
groups for one under which greedy decoding does not settle on one token.

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
MIN_REFERENCE_ROWS = 256
CONTROLS = ("int8", "passes_3")


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.ouro import OuroConfig

    return OuroConfig.from_hf(config, dtype=jnp.dtype(config["dtype"]))


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


def reference_rows(n_tokens: int, cap: int) -> int:
    """The length the reference is compiled for to judge a request of
    ``n_tokens``: the next power of two, within the slot's capacity."""
    return min(cap, max(MIN_REFERENCE_ROWS, 1 << (n_tokens - 1).bit_length()))


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: Any = False) -> Dict[str, Any]:
    """``serve.check_tokens`` — the sample's served tokens (the longest
    request always in it) teacher-forced through the plain reference,
    the widest and the mean gap — with the reference compiled for each
    request's own length (:func:`reference_rows`) over layers stacked
    once for the whole sample."""
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
    layers = ref.stack_layers(weights, cell.config)
    gaps, distinct, t0 = [], [], CLOCK()
    for r in sample:
        prompt = open_loop.prompt_token_ids(
            r["rid"], r["prompt_len"], int(cell.config["vocab_size"]), seed)[0]
        toks = served["tokens"][r["rid"]]
        seq = np.concatenate([prompt, toks])
        gaps.append(ref.served_gaps(
            weights, cell.config, seq, r["prompt_len"], len(toks),
            reference_rows(len(seq), cap), control=control, layers=layers))
        distinct.append(len(set(toks.tolist())) / len(toks))
    del layers
    gc.collect()
    g = np.concatenate(gaps)
    return {"n_requests": len(sample), "n_tokens": int(g.size),
            "gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "flips": int((g > 0).sum()),
            "distinct_share": float(np.mean(distinct)),
            "lengths": [r["prompt_len"] + r["n_served"] for r in sample],
            "seconds": CLOCK() - t0}


def _pool_counts(engine: Any) -> Dict[str, Any]:
    """What admission by need did: chunks refused their pages by the
    banker's rule, requests preempted, the share of the pool in use."""
    snap = engine.metrics.snapshot()
    return {
        "chunk_stalls": snap["counters"].get(
            "decode.chunk_stalls", {}).get("value", 0),
        "preempted": sum(1 for r in engine.reqlog.records()
                         if r.state == "preempted"),
        "pool_used_share": snap["histograms"].get(
            "decode.page_pool_used_share", {}),
    }


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
        f"{pages['peak']} of {engine.pool.n_pages - 1}; "
        f"{_pool_counts(engine)}")
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
    run compares — the program's and, with ``--control 1``, each
    control's (:data:`CONTROLS`) at the same prompts; the schedule is the
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
        for control in CONTROLS if args.control else ():
            row[f"control_{control}"] = check_tokens(
                cell, weights, out, seed, control=control)
        print("READING " + json.dumps(row), flush=True)
        del weights, out
        gc.collect()
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)


def sweep(argv=None) -> int:
    """``python -m benchmark.runners.ouro_serve --workload <cell>`` with
    ``--rates r1,r2,...``: one engine, per rate one window of the cell's
    pinned schedule at that rate; a rate is sustained when the backlog at
    the end of the window is no larger than at mid-window and no request
    was preempted or failed.  With ``--scan '[{...}, ...]'``: the init
    scan — per entry new weights with the entry laid over the
    configuration's ``init`` group and a burst of ``--burst`` requests;
    reads the distinct tokens a request.  Neither is part of a benchmark
    run."""
    import argparse
    import json

    import jax

    from ..sweep import backlog

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--scan", default=None)
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--scan-prompt", type=int, default=256)
    ap.add_argument("--scan-out", type=int, default=384)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=20261002)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    ref = harness.load_reference(cell.config)
    t0 = CLOCK()
    weights = ref.make_params(cell.config, args.seed)
    engine = build_engine(cell.config, devices[0], weights)
    base.warm_up(engine, cell.config, cell.traffic, args.seed)
    harness.log(f"engine warm after {CLOCK() - t0:.1f} s")
    for i, init in enumerate(json.loads(args.scan) if args.scan else ()):
        config = dict(cell.config, init={**cell.config["init"], **init})
        engine.weights = weights = None
        gc.collect()
        engine.weights = weights = jax.device_put(
            ref.make_params(config, args.seed))
        engine.rebind_obs(clock=CLOCK)
        burst = [open_loop.Request(f"i{i}_{j}", 0.0, args.scan_prompt,
                                   args.scan_out) for j in range(args.burst)]
        t0 = CLOCK()
        out = base.serve(engine, config, burst, seed=args.seed,
                         slo_ttft_s=3600.0, drain_s=600.0, seconds=600.0)
        toks = list(out["tokens"].values())
        print("SCAN " + json.dumps({
            "init": init, "seconds": CLOCK() - t0,
            "failed": sum(r["failed"] for r in out["records"]),
            "distinct_share": [round(len(set(t.tolist())) / len(t), 3)
                               for t in toks],
            "tpot_ms_p50": stats.percentile(
                [r["tpot_ms"] for r in out["records"]
                 if r["tpot_ms"] is not None], 50)}), flush=True)
    for i, rate in enumerate(
            float(r) for r in (args.rates.split(",") if args.rates else ())):
        engine.rebind_obs(clock=CLOCK)
        peak = {"pages": 0}

        def hook(_now: float) -> None:
            peak["pages"] = max(peak["pages"], engine.pool.used_pages)

        out = base.serve(
            engine, cell.config,
            _renamed(schedule(cell.traffic, rate, args.seconds), f"s{i}_"),
            seed=args.seed, slo_ttft_s=3600.0, drain_s=240.0,
            seconds=args.seconds, tick_hook=hook)
        rec, t0 = out["records"], out["t0"]
        toks = list(out["tokens"].values())
        row = {"rate_rps": rate, "n": len(rec),
               "failed": sum(r["failed"] for r in rec),
               "backlog_mid": backlog(rec, t0 + args.seconds / 2),
               "backlog_end": backlog(rec, t0 + args.seconds),
               "drain_s": out["t_end"] - t0 - args.seconds,
               "pages_peak": peak["pages"], **_pool_counts(engine),
               "distinct_share": float(np.mean(
                   [len(set(t.tolist())) / len(t) for t in toks])),
               "out_tok_s": stats.tokens_in_window(
                   rec, t0, t0 + args.seconds) / args.seconds}
        for f in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            vals = [r[f] for r in rec if r[f] is not None]
            row[f + "_p50"] = stats.percentile(vals, 50)
            row[f + "_p90"] = stats.percentile(vals, 90)
        row["tpot_ms_mean"] = float(np.mean(
            [r["tpot_ms"] for r in rec if r["tpot_ms"] is not None]))
        row["step_interval_ms"] = engine.metrics.snapshot()[
            "histograms"].get("decode.step_interval_ms", {})
        row["late"] = out["late"]
        print("SWEEP " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(sweep())

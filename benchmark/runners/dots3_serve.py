"""Runner for the dots3 served cells: ``runners/xing4_serve.py``'s path
and pinned schedule (``schedule_seed`` in the traffic file makes the
arrivals, ``--seed`` the weights and tokens) with this family's model
config, and one more compared number: the share of the rows the
reference's full layers select that the served decode steps read too.

Serving, warm-up, the verdicts every served cell shares and the trace
reduction are ``serve.py``'s; the schedule is ``xing4_serve.py``'s.  The
files that were there take no family's config but their own, so the
engine, the check, the rate sweep and the readings are spelled out here.
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
#: requests of the checked sample whose full-layer selections are
#: compared (the longest is the first)
SELECTION_REQUESTS = 2


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.dots3 import Dots3Config

    geo = config["engine"]
    return Dots3Config.from_hf(
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


class Selections:
    """What the SERVED path selected, taken off the engine's
    ``stats_probe`` seam during the window: per request and decode
    position, the rows each full layer's attention read (``dsa_idx`` of
    ``jit_seg``, after ``_dsa_index``, the exact top-k and before the
    page-table gather).  The position ``prompt_len - 1`` is the chunk
    program's and is not here."""

    def __init__(self) -> None:
        self.rows: Dict[str, Dict[int, np.ndarray]] = {}

    def __call__(self, stats, rids, lengths, owed) -> None:
        idx = stats.get("dsa_idx")       # (steps, full layers, slots, k)
        if idx is None:
            return
        for s, erid in enumerate(rids):
            if erid is None or owed[s] <= 0:
                continue
            mine = self.rows.setdefault(str(erid).split("#p")[0], {})
            for j in range(min(int(owed[s]), idx.shape[0])):
                mine[int(lengths[s]) + j] = idx[j, :, s].copy()

    def masks(self, rid: str, first: int, n: int, cap: int):
        """``(seen (n,) bool, picked (full layers, n, cap) bool)`` for
        the queries at positions ``first .. first + n - 1``."""
        mine = self.rows.get(rid, {})
        seen = np.array([first + i in mine for i in range(n)], bool)
        picked = None
        for i in np.flatnonzero(seen):
            idx = mine[first + i]
            if picked is None:
                picked = np.zeros((idx.shape[0], n, cap), bool)
            for layer, row in enumerate(idx):
                picked[layer, i, row[row >= 0]] = True
        return seen, picked


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: bool = False) -> Dict[str, Any]:
    """``serve.check_tokens`` — the sample's served tokens against the
    plain reference, compiled for the mix's longest request — and, for
    the first :data:`SELECTION_REQUESTS` of the sample, the overlap of
    the rows the reference's full layers select at the decoded positions
    with the rows the served path read there (``served["selections"]``;
    with ``control`` the int8 forward's): rows both hold over the larger
    of the two counts, so rows read beyond the reference's count lower
    it too."""
    ref = harness.load_reference(cell.config)
    done = [r for r in served["records"] if not r["failed"]]
    if not done:
        return {"n_requests": 0, "n_tokens": 0, "gap_max": float("inf"),
                "gap_mean": float("inf"), "distinct_share": 0.0,
                "selection_overlap": 0.0}
    rng = open_loop._rng(seed, 5)
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_served"])
    rest = [r for r in done if r is not longest]
    k = min(int(cell.params["check_requests"]) - 1, len(rest))
    sample = [longest] + [rest[i] for i in rng.permutation(len(rest))[:k]]
    t, geo = cell.traffic, cell.config["engine"]
    need = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    block = max(geo["page_size"], ref.Q_BLOCK)
    cap = min(geo["pages_per_seq"] * geo["page_size"],
              -(-need // block) * block)
    gaps, distinct, both, theirs, mine, t0 = [], [], 0, 0, 0, CLOCK()
    for j, r in enumerate(sample):
        prompt = open_loop.prompt_token_ids(
            r["rid"], r["prompt_len"], int(cell.config["vocab_size"]), seed)[0]
        toks = served["tokens"][r["rid"]]
        seq = np.concatenate([prompt, toks])
        select = j < SELECTION_REQUESTS
        out = ref.served_gaps(
            weights, cell.config, seq, r["prompt_len"], len(toks), cap,
            control=control, selections=select)
        if select:
            g, picked, judged = out
            # the decode steps' queries: the first served token came of
            # the prompt's last row, in the chunk program
            seen = np.arange(len(toks)) > 0
            if not control:
                seen, judged = served["selections"].masks(
                    r["rid"], r["prompt_len"] - 1, len(toks), cap)
            if seen.any():
                both += int((picked & judged)[:, seen].sum())
                theirs += int(picked[:, seen].sum())
                mine += int(judged[:, seen].sum())
            gaps.append(g)
        else:
            gaps.append(out)
        distinct.append(len(set(toks.tolist())) / len(toks))
    g = np.concatenate(gaps)
    return {"n_requests": len(sample), "n_tokens": int(g.size),
            "gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "flips": int((g > 0).sum()),
            "distinct_share": float(np.mean(distinct)),
            "selection_overlap": both / max(theirs, mine, 1),
            "selected_rows_checked": theirs,
            "seconds": CLOCK() - t0}


def decide(cell: harness.Cell, served: Dict[str, Any],
           check: Dict[str, Any], compiles: int) -> List[Dict[str, Any]]:
    low = float(cell.params["limits"]["selection_overlap_min"])
    return base.decide(cell, served, check, compiles) + [harness.compared(
        "selection_overlap", check["selection_overlap"], low,
        check["selection_overlap"] >= low)]


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
    engine.stats_probe = selections = Selections()
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
    ticks: List[float] = []

    def hook(now: float) -> None:   # the first tick opens the window
        ticks.append(now)
        slice_.poll(now, window.setdefault("end", now + seconds))

    served = base.serve(engine, config, arrivals, seed=seed,
                        slo_ttft_s=float(params["slo_ttft_s"]),
                        drain_s=float(params["drain_s"]), seconds=seconds,
                        tick_hook=hook)
    slice_.finish()
    served["selections"] = selections
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
    # what a run that reads high is traced back to: a tick that stalled,
    # and which requests were in the system then
    stalls = sorted(((b - a, a - t0) for a, b in zip(ticks, ticks[1:])),
                    reverse=True)[:3]
    harness.log("longest engine ticks (s, at s after window start): "
                + str([(round(d, 3), round(at, 2)) for d, at in stalls]))
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
    verdicts = decide(cell, served, check, in_window)

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
    for i, seed in enumerate(args.seeds):
        # an engine a seed: the float32 forward does not fit beside the
        # pools, so the engine goes before the check (as in ``run``)
        weights = ref.make_params(cell.config, seed)
        engine = build_engine(cell.config, devices[0], weights)
        base.warm_up(engine, cell.config, cell.traffic, seed)
        engine.stats_probe = selections = Selections()
        out = base.serve(
            engine, cell.config, _renamed(schedule(
                cell.traffic, float(cell.params["rate_rps"]), args.seconds),
                f"k{i}_"),
            seed=seed, slo_ttft_s=3600.0, drain_s=240.0, seconds=args.seconds)
        out["selections"] = selections
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
    """``python -m benchmark.runners.dots3_serve --workload <cell> --rates
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
    ap.add_argument("--seed", type=int, default=20260928)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    weights = harness.load_reference(cell.config).make_params(
        cell.config, args.seed)
    engine = build_engine(cell.config, devices[0], weights)
    base.warm_up(engine, cell.config, cell.traffic, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        out = base.serve(
            engine, cell.config,
            _renamed(schedule(cell.traffic, rate, args.seconds), f"s{i}_"),
            seed=args.seed, slo_ttft_s=3600.0, drain_s=240.0,
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

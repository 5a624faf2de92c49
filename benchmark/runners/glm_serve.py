"""Runner for the GLM served cells: ``runners/xing4_serve.py``'s path and
pinned schedule (``schedule_seed`` in the traffic file makes the
arrivals, ``--seed`` the weights and tokens) with this family's model
config — served with its multi-token-prediction module as the self-draft,
there being no other way to serve it — and three more compared numbers,
all of what the timed window itself produced: the drafts the served
steps verified, read off the engine's ``stats_probe`` seam with the
tokens (ids only), teacher-forced through the reference DRAFT module
(``draft_logit_gap_mean``), and the served acceptance rate against the
reference's own at the same positions (``mtp_accept_rate_diff``).

Serving, warm-up, the verdicts every served cell shares and the trace
reduction are ``serve.py``'s; the schedule is ``xing4_serve.py``'s.  The
files that were there take no family's config but their own, so the
engine, the check, the rate sweep and the readings are spelled out here
(ROADMAP D14).  ``--scan`` is the init scan of PERF.md section 4.
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
#: a request whose last tokens repeat with a period under this has
#: collapsed into a cycle
CYCLE_PERIOD = 64


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.glm4_lite import Glm4LiteConfig

    return Glm4LiteConfig.from_hf(config, dtype=jnp.dtype(config["dtype"]))


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


class Drafts:
    """What the SERVED steps verified, taken off the engine's
    ``stats_probe`` seam during the window: per request, the start length
    ``L`` of every decode step it ran and the draft that step verified
    (``mtp_drafts`` of ``jit_seg``'s one readback, beside the counts the
    host folds from).  The first draft of a request is the chunk
    program's; it is verified by a step and so is here."""

    def __init__(self) -> None:
        self.at: Dict[str, Dict[int, int]] = {}

    def __call__(self, stats, rids, lengths, owed) -> None:
        counts, drafts = stats.get("mtp_counts"), stats.get("mtp_drafts")
        if counts is None:
            return
        for s, erid in enumerate(rids):
            if erid is None or owed[s] <= 0:
                continue
            mine = self.at.setdefault(str(erid).split("#p")[0], {})
            L = int(lengths[s])
            for n, d in zip(counts[s], drafts[s]):
                if n:
                    mine[L] = int(d)
                    L += int(n)


def cycles(tokens: np.ndarray) -> bool:
    """Whether the last 4 x CYCLE_PERIOD tokens repeat with a period
    under :data:`CYCLE_PERIOD`."""
    tail = np.asarray(tokens)[-4 * CYCLE_PERIOD:]
    return any(len(tail) > p and (tail[p:] == tail[:-p]).all()
               for p in range(1, CYCLE_PERIOD))


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: bool = False) -> Dict[str, Any]:
    """The sample's served tokens AND verified drafts against the plain
    reference, compiled for the mix's longest request: every served
    token (accepted draft or not) teacher-forced through the reference
    main model; every verified draft through the reference draft module;
    the share of drafts that were the token served next against the
    reference's own agreement at the same positions."""
    ref = harness.load_reference(cell.config)
    done = [r for r in served["records"] if not r["failed"]]
    empty = {"n_requests": 0, "n_tokens": 0, "gap_max": float("inf"),
             "gap_mean": float("inf"), "distinct_share": 0.0, "n_drafts": 0,
             "draft_gap_mean": float("inf"), "accept_served": 0.0,
             "accept_ref": 0.0, "accept_diff": float("inf"), "cycling": 0}
    if not done:
        return empty
    rng = open_loop._rng(seed, 5)
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_served"])
    rest = [r for r in done if r is not longest]
    k = min(int(cell.params["check_requests"]) - 1, len(rest))
    sample = [longest] + [rest[i] for i in rng.permutation(len(rest))[:k]]
    t, geo = cell.traffic, cell.config["engine"]
    need = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    block = max(geo["page_size"], ref.Q_BLOCK)
    cap = min(-(-geo["pages_per_seq"] * geo["page_size"] // block) * block,
              -(-need // block) * block)
    out: Dict[str, List[np.ndarray]] = {}
    distinct, cycling, t0 = [], 0, CLOCK()
    for r in sample:
        prompt = open_loop.prompt_token_ids(
            r["rid"], r["prompt_len"], int(cell.config["vocab_size"]), seed)[0]
        toks = served["tokens"][r["rid"]]
        got = ref.served_check(
            weights, cell.config, np.concatenate([prompt, toks]),
            r["prompt_len"], len(toks), cap,
            drafts=served["drafts"].at.get(r["rid"], {}), control=control)
        for name, v in got.items():
            out.setdefault(name, []).append(v)
        distinct.append(len(set(toks.tolist())) / len(toks))
        cycling += cycles(toks)
    g = np.concatenate(out["gaps"])
    check = dict(empty, n_requests=len(sample), n_tokens=int(g.size),
                 gap_max=float(g.max()), gap_mean=float(g.mean()),
                 flips=int((g > 0).sum()),
                 distinct_share=float(np.mean(distinct)), cycling=cycling)
    if "draft_gaps" in out:
        dg = np.concatenate(out["draft_gaps"])
        a_s = float(np.concatenate(out["served_accepts"]).mean())
        a_r = float(np.concatenate(out["ref_accepts"]).mean())
        check.update(n_drafts=int(dg.size), draft_gap_mean=float(dg.mean()),
                     accept_served=a_s, accept_ref=a_r,
                     accept_diff=abs(a_s - a_r))
    check["seconds"] = CLOCK() - t0
    return check


def decide(cell: harness.Cell, served: Dict[str, Any],
           check: Dict[str, Any], compiles: int) -> List[Dict[str, Any]]:
    lim = cell.params["limits"]
    return base.decide(cell, served, check, compiles) + [
        harness.compared("drafts_checked", check["n_drafts"],
                         lim["min_drafts_checked"],
                         check["n_drafts"] >= lim["min_drafts_checked"]),
        harness.compared("draft_logit_gap_mean", check["draft_gap_mean"],
                         lim["draft_gap_mean"],
                         check["draft_gap_mean"] <= lim["draft_gap_mean"]),
        harness.compared("mtp_accept_rate_diff", check["accept_diff"],
                         lim["accept_diff_max"],
                         check["accept_diff"] <= lim["accept_diff_max"]),
    ]


def _mtp(engine: Any) -> Dict[str, float]:
    """The engine's own draft counters, as a run logs them."""
    c = engine.metrics.snapshot()["counters"]
    v = c.get("mtp.drafts_verified", {}).get("value", 0)
    a = c.get("mtp.drafts_accepted", {}).get("value", 0)
    return {"drafts_verified": v, "drafts_accepted": a,
            "accept_rate": a / max(v, 1)}


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
    engine.stats_probe = drafts = Drafts()
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
    served["drafts"] = drafts
    in_window = counter.snapshot()["compiles"] - setup["compiles"]
    t0, records = served["t0"], served["records"]
    n_tok = stats.tokens_in_window(records, t0, t0 + seconds)
    failed = sum(1 for r in records if r["failed"])
    harness.log(
        f"window: {len(records)} requests due, {failed} failed, {n_tok} "
        f"tokens in {seconds} s; run ended {served['t_end'] - t0:.2f} s "
        f"after window start; generator lateness {served['late']}; "
        f"compilations in window+drain: {in_window}; drafts {_mtp(engine)}")
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
        engine.stats_probe = drafts = Drafts()
        out = base.serve(
            engine, cell.config, _renamed(schedule(
                cell.traffic, float(cell.params["rate_rps"]), args.seconds),
                f"k{i}_"),
            seed=seed, slo_ttft_s=3600.0, drain_s=240.0, seconds=args.seconds)
        out["drafts"] = drafts
        mtp = _mtp(engine)
        out.pop("fe").engine = None
        del engine
        gc.collect()
        row = {"seed": seed, "n": len(out["records"]),
               "failed": sum(r["failed"] for r in out["records"]),
               "mtp": mtp,
               "program": check_tokens(cell, weights, out, seed)}
        if args.control:
            row["control"] = check_tokens(cell, weights, out, seed,
                                          control=True)
        print("READING " + json.dumps(row), flush=True)
        del weights, out
        gc.collect()
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)


def _hist(engine: Any, name: str) -> Any:
    return engine.metrics.snapshot()["histograms"].get(name, {}).get("p50")


def main(argv=None) -> int:
    """``python -m benchmark.runners.glm_serve --workload <cell>`` with
    ``--rates r1,r2,...``: ``benchmark.sweep`` for this runner's cells —
    one engine, per rate one window of the cell's pinned schedule at that
    rate; a rate is sustained when the backlog at the end of the window
    is no larger than at mid-window.  With ``--scan '[{...}, ...]'``: the
    init scan — one engine, per entry new weights with the entry laid
    over the configuration's ``init`` group and a burst of ``--burst``
    requests (``--scan-prompt`` tokens in, ``--scan-out`` out); reads the
    acceptance, the distinct tokens a request, the requests that cycle
    and the experts touched.  Neither is part of a benchmark run."""
    import argparse
    import json

    import jax

    from ..sweep import backlog

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--scan", default=None)
    ap.add_argument("--burst", type=int, default=32)
    ap.add_argument("--scan-prompt", type=int, default=512)
    ap.add_argument("--scan-out", type=int, default=768)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=20260929)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.require_chip(cell.chips)
    ref = harness.load_reference(cell.config)
    weights = ref.make_params(cell.config, args.seed)
    engine = build_engine(cell.config, devices[0], weights)
    base.warm_up(engine, cell.config, cell.traffic, args.seed)
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
            "init": init, **_mtp(engine), "seconds": CLOCK() - t0,
            "failed": sum(r["failed"] for r in out["records"]),
            "distinct_share": float(np.mean(
                [len(set(t.tolist())) / len(t) for t in toks])),
            "cycling": int(sum(cycles(t) for t in toks)),
            "experts_touched_share": _hist(
                engine, "moe.experts_touched_share"),
            "tpot_ms_p50": stats.percentile(
                [r["tpot_ms"] for r in out["records"]
                 if r["tpot_ms"] is not None], 50)}), flush=True)
    for i, rate in enumerate(
            float(r) for r in (args.rates.split(",") if args.rates else ())):
        engine.rebind_obs(clock=CLOCK)
        out = base.serve(
            engine, cell.config,
            _renamed(schedule(cell.traffic, rate, args.seconds), f"s{i}_"),
            seed=args.seed, slo_ttft_s=3600.0, drain_s=240.0,
            seconds=args.seconds)
        rec, t0 = out["records"], out["t0"]
        toks = list(out["tokens"].values())
        row = {"rate_rps": rate, "n": len(rec),
               "cycling": int(sum(cycles(t) for t in toks)),
               "distinct_share": float(np.mean(
                   [len(set(t.tolist())) / len(t) for t in toks])),
               "failed": sum(r["failed"] for r in rec),
               "backlog_mid": backlog(rec, t0 + args.seconds / 2),
               "backlog_end": backlog(rec, t0 + args.seconds),
               "drain_s": out["t_end"] - t0 - args.seconds,
               "out_tok_s": stats.tokens_in_window(
                   rec, t0, t0 + args.seconds) / args.seconds,
               "accept_rate": _mtp(engine)["accept_rate"],
               "tokens_per_step_p50": _hist(engine, "mtp.tokens_per_step")}
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

    sys.exit(main())

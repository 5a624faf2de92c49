"""Runner for served cells: the paged engine behind ``ServingFrontend``
on the wall clock, driven by open-loop arrivals.

The engine is built exactly as the program's ``serve`` command builds it
(``build_paged_decode_dag`` -> scheduler -> ``DeviceBackend.
paged_decode_engine`` -> ``ServingFrontend``); only the sizes are the
configuration file's, because the command hard-codes a toy geometry.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness, stats
from ..traffic import open_loop

CLOCK = time.perf_counter


def model_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    return GPT2Config(
        vocab_size=int(config["vocab_size"]),
        n_positions=int(config["n_positions"]),
        n_embd=int(config["n_embd"]), n_layer=int(config["n_layer"]),
        n_head=int(config["n_head"]), dtype=jnp.dtype(config["dtype"]),
        ln_eps=float(config["layer_norm_epsilon"]),
    )


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


def serve(engine: Any, config: Dict[str, Any], arrivals: List[Any], *,
          seed: int, slo_ttft_s: float, drain_s: float, seconds: float,
          tick_hook: Any = None) -> Dict[str, Any]:
    """One front-end over ``engine`` serving ``arrivals`` to completion.

    Stamps each request's first token itself, on its own clock, at the
    first tick after which the engine holds a token for it (by then the
    fold has forced the readback: at most one tick late, never early),
    and notes how late each arrival was injected."""
    from distributed_llm_scheduler_tpu.obs.slo import SLOPolicy
    from distributed_llm_scheduler_tpu.serve.frontend import ServingFrontend
    from distributed_llm_scheduler_tpu.serve.loadgen import Arrival

    fe = ServingFrontend(
        engine,
        [Arrival(a.rid, a.t, a.prompt_len, a.max_new_tokens, a.priority)
         for a in arrivals],
        SLOPolicy(ttft_s=slo_ttft_s),
        admission=config["engine"]["admission"],
        prompt_seed=seed, prompt_fn=open_loop.prompt_token_ids,
    )
    first: Dict[str, float] = {}
    injected: Dict[str, float] = {}
    state = {"tick_start": None, "n_seen": 0}

    def on_tick(f: Any) -> None:
        now = CLOCK()
        if len(f._reqs) > state["n_seen"]:
            started = state["tick_start"] or f.t0
            for rid in list(f._reqs)[state["n_seen"]:]:
                injected[rid] = started
            state["n_seen"] = len(f._reqs)
        for erid, req in f._inflight.items():
            rid = req.a.rid
            if rid not in first and engine._tokens.get(erid):
                first[rid] = now
        if len(f.results) > len(first):
            for rid in f.results:
                first.setdefault(rid, now)
        if tick_hook is not None:
            tick_hook(now)
        state["tick_start"] = CLOCK()

    fe.run(deadline=seconds + drain_s, on_tick=on_tick)
    t_end = CLOCK()
    t0 = fe.t0
    rows = fe.request_rows()
    records = stats.request_records(arrivals, rows, first, t0, t_end)
    tokens = {rid: np.asarray(t) for rid, t in fe.results.items()}
    for r in records:
        # the tokens handed back, not the log's count, are what was served
        r["n_served"] = len(tokens.get(r["rid"], ()))
        r["failed"] = r["failed"] or r["n_served"] != r["max_new_tokens"]
    return {
        "fe": fe, "t0": t0, "t_end": t_end, "records": records,
        "tokens": tokens,
        "late": stats.lateness_ms(
            (t0 + a.t, injected[a.rid]) for a in arrivals
            if a.rid in injected),
        "pages_leaked": int(fe.report()["pages_leaked"]),
    }


def warm_up(engine: Any, config: Dict[str, Any], traffic: Dict[str, Any],
            seed: int) -> None:
    """Every program the window will drive, through the window's own
    path: two requests, the mix's shortest and longest prompt, a few
    segments each, all due at once."""
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    steps = 2 * int(config["engine"]["seg_steps"]) + 1
    reqs = [open_loop.Request("warm0", 0.0, int(lo), steps),
            open_loop.Request("warm1", 0.0, int(hi), steps)]
    out = serve(engine, config, reqs, seed=seed, slo_ttft_s=3600.0,
                drain_s=600.0, seconds=600.0)
    if any(r["failed"] for r in out["records"]):
        raise RuntimeError("warm-up requests did not complete")


def check_tokens(cell: harness.Cell, weights: Dict[str, Any],
                 served: Dict[str, Any], seed: int,
                 control: bool = False) -> Dict[str, Any]:
    """The sample's served tokens against the plain reference: the widest
    and the mean gap by which a served token's reference logit lies
    below that position's best.  Run after the engine is freed."""
    ref = harness.load_reference(cell.config)
    done = [r for r in served["records"] if not r["failed"]]
    if not done:
        return {"n_requests": 0, "n_tokens": 0, "gap_max": float("inf"),
                "gap_mean": float("inf"), "distinct_share": 0.0}
    rng = open_loop._rng(seed, 5)
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_served"])
    rest = [r for r in done if r is not longest]
    k = min(int(cell.params["check_requests"]) - 1, len(rest))
    sample = [longest] + [rest[i] for i in
                          rng.permutation(len(rest))[:k]]
    geo = cell.config["engine"]
    cap = geo["pages_per_seq"] * geo["page_size"]
    gaps, distinct, t0 = [], [], CLOCK()
    for r in sample:
        prompt = open_loop.prompt_token_ids(
            r["rid"], r["prompt_len"], int(cell.config["vocab_size"]), seed)[0]
        toks = served["tokens"][r["rid"]]
        seq = np.concatenate([prompt, toks])
        gaps.append(ref.served_gaps(
            weights, cell.config, seq, r["prompt_len"], len(toks), cap,
            control=control))
        distinct.append(len(set(toks.tolist())) / len(toks))
    g = np.concatenate(gaps)
    return {"n_requests": len(sample), "n_tokens": int(g.size),
            "gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "flips": int((g > 0).sum()),
            "distinct_share": float(np.mean(distinct)),
            "seconds": CLOCK() - t0}


def decide(cell: harness.Cell, served: Dict[str, Any],
           check: Dict[str, Any], compiles: int) -> List[Dict[str, Any]]:
    """Every number compared, beside its limit."""
    lim = cell.params["limits"]
    wrong = sum(1 for r in served["records"]
                if r["t_retire"] is not None
                and r["n_served"] != r["max_new_tokens"])
    return [
        harness.compared("requests_with_wrong_token_count", wrong, 0,
                         wrong == 0),
        harness.compared("pages_leaked", served["pages_leaked"], 0,
                         served["pages_leaked"] == 0),
        harness.compared("compilations_in_window", compiles, 0,
                         compiles == 0),
        harness.compared("served_tokens_checked", check["n_tokens"],
                         lim["min_tokens_checked"],
                         check["n_tokens"] >= lim["min_tokens_checked"]),
        harness.compared("served_logit_gap_max", check["gap_max"],
                         lim["gap_max"], check["gap_max"] <= lim["gap_max"]),
        harness.compared("served_logit_gap_mean", check["gap_mean"],
                         lim["gap_mean"],
                         check["gap_mean"] <= lim["gap_mean"]),
    ]


def run(cell: harness.Cell, devices: List[Any], *, seed: int, seconds: float,
        trace: bool, t_start: float) -> str:
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    config, traffic, params = cell.config, cell.traffic, cell.params
    counter = harness.CompileCounter()
    ref = harness.load_reference(config)
    weights = ref.make_params(config, seed)
    tracer = Tracer(clock=CLOCK) if trace else None
    engine = build_engine(config, devices[0], weights, tracer)
    harness.log(f"attention impl resolved to "
                f"{engine.resolved_attention_impl!r}")
    warm_up(engine, config, traffic, seed)
    arrivals = open_loop.generate(traffic, float(params["rate_rps"]),
                                  seconds, seed)
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

    served = serve(engine, config, arrivals, seed=seed,
                   slo_ttft_s=float(params["slo_ttft_s"]),
                   drain_s=float(params["drain_s"]), seconds=seconds,
                   tick_hook=hook)
    slice_.finish()   # the run has ended: only now is the trace parsed
    in_window = counter.snapshot()["compiles"] - setup["compiles"]
    t0 = served["t0"]
    records = served["records"]
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
    verdicts = decide(cell, served, check, in_window)

    # a traced run reads latencies only from before the profiler came on
    read_rows = stats.closed_before(records, slice_.t_before)
    if trace:
        harness.log("traced run: row metrics from "
                    f"{sum(r['ttft_ms'] is not None for r in read_rows)} "
                    "first tokens and "
                    f"{sum(r['tpot_ms'] is not None for r in read_rows)} "
                    "retirements before the profiler came on, "
                    f"{(slice_.t_before or t0) - t0:.2f} s into the window")
    ctx: Dict[str, Any] = {
        "config": config, "traffic": traffic, "records": read_rows,
        "seconds": seconds, "t0": t0, "spans": spans, "trace": slice_.trace,
        "device_kind": device["kind"], "n_devices": 1,
        "values": {"setup_s": setup_s, "window_tok_s": n_tok / seconds},
    }
    breakdown = None
    if trace:
        breakdown = add_trace(ctx, slice_, device)
    defs = cell.per_layer if trace else cell.end_to_end
    return harness.result_line(
        correct=all(v["ok"] for v in verdicts), attempted=len(records),
        failed=failed, metrics=harness.read_metrics(defs, ctx),
        device=device, breakdown=breakdown,
    )


def add_trace(ctx: Dict[str, Any], slice_: harness.TraceSlice,
              device: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Busy time, top ops and idle by host activity into ``device`` and
    the breakdown; the slice's ``segment`` spans into the context."""
    from .. import xplane

    trace = ctx["trace"]
    if trace is None:
        return None
    host = [(e["name"], e["t0"], e["t1"]) for e in ctx["spans"]
            if e.get("type") == "span" and e.get("track") == "decode"
            and e.get("t1") is not None
            and e["t1"] >= slice_.t_start and e["t0"] <= slice_.t_stop]
    ctx["slice_segments"] = [(a, b) for n, a, b in host if n == "segment"
                             and a >= slice_.t_start and b <= slice_.t_stop]
    ctx["slice"] = (slice_.t_start, slice_.t_stop)
    summary = xplane.summarize(
        trace, slice_.t_stop - slice_.t_start, host, slice_.t_sync,
        n_devices=ctx["n_devices"])
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    for plane in xplane.device_planes(trace)[:ctx["n_devices"]]:
        mods = xplane.self_times(xplane.line_events(plane, xplane.MODULES_LINE))
        harness.log(f"trace: modules on {plane['name']}: "
                    f"{xplane.top(mods, 6, 1e-9)}")
    harness.log(f"trace: busy {summary['busy_s']:.3f} s of "
                f"{summary['window_s']:.3f} s; device events span "
                f"{summary.get('device_span_s')} s; longest gap "
                f"{summary.get('longest_gap_ms')} ms")
    return {"device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"]}


def readings(cell: harness.Cell, devices: List[Any], args: Any) -> None:
    """For ``benchmark.readings``: per seed, in one process, the numbers
    a run compares — the program's and, with ``--control 1``, the int8
    forward's at the same prompts."""
    import json

    ref = harness.load_reference(cell.config)
    engine = None
    for i, seed in enumerate(args.seeds):
        weights = ref.make_params(cell.config, seed)
        if engine is None:
            engine = build_engine(cell.config, devices[0], weights)
            warm_up(engine, cell.config, cell.traffic, seed)
        engine.weights = weights
        arrivals = open_loop.generate(
            cell.traffic, float(cell.params["rate_rps"]), args.seconds,
            seed, rid_prefix=f"k{i}_")
        out = serve(engine, cell.config, arrivals, seed=seed,
                      slo_ttft_s=3600.0, drain_s=120.0, seconds=args.seconds)
        row = {"seed": seed, "n": len(out["records"]),
               "failed": sum(r["failed"] for r in out["records"]),
               "program": check_tokens(cell, weights, out, seed)}
        if args.control:
            row["control"] = check_tokens(cell, weights, out, seed,
                                            control=True)
        print("READING " + json.dumps(row), flush=True)
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)

"""Runner for placed-DAG cells: one forward step of the model cut into
tasks, placed by a policy, executed by ``DeviceBackend.execute`` with its
default arguments (the planned per-task path) — closed loop, one step in
flight, each step ended on the harness's clock by
``block_until_ready`` of its output.  Time per step is the whole window,
from its start to the end of its last step, over the steps in it.
"""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any, Dict, List

from .. import harness
from ..traffic import closed_loop
from .serve import model_config

CLOCK = time.perf_counter


def build(config: Dict[str, Any], traffic: Dict[str, Any],
          devices: List[Any]):
    """Graph, schedule and backend as the program's ``execute`` command
    builds them, at the files' sizes."""
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag

    dag = build_gpt2_dag(
        model_config(config), batch=int(traffic["batch"]), seq_len=int(traffic["seq_len"]),
        microbatches=int(traffic["microbatches"]),
    )
    cluster = Cluster.from_jax_devices(devices)
    schedule = get_scheduler(traffic["policy"]).schedule(dag.graph, cluster)
    return dag, schedule, DeviceBackend(cluster)


def check_output(cell: harness.Cell, weights: Dict[str, Any], ids: Any,
                 output: Any, control: bool = False) -> Dict[str, Any]:
    """The step's logits for the whole batch against the reference."""
    ref = harness.load_reference(cell.config)
    t0 = CLOCK()
    rows = int(cell.traffic["batch"]) // int(cell.traffic["microbatches"])
    out = ref.forward_distance(weights, cell.config, ids, output, rows,
                               control=control)
    return dict(out, seconds=CLOCK() - t0)


def decide(cell: harness.Cell, check: Dict[str, Any], compiles: int,
           steps: int) -> List[Dict[str, Any]]:
    lim = cell.params["limits"]
    return [
        harness.compared("steps_completed", steps, 1, steps >= 1),
        harness.compared("compilations_in_window", compiles, 0,
                         compiles == 0),
        harness.compared("logits_finite", int(check["finite"]), 1,
                         check["finite"]),
        harness.compared("logits_max_abs_diff", check["max_abs"],
                         lim["max_abs"], check["max_abs"] <= lim["max_abs"]),
        harness.compared("logits_rel_frobenius", check["rel_fro"],
                         lim["rel_fro"], check["rel_fro"] <= lim["rel_fro"]),
        harness.compared("top1_logit_gap_mean", check["top1_gap_mean"],
                         lim["top1_gap_mean"],
                         check["top1_gap_mean"] <= lim["top1_gap_mean"]),
    ]


def run(cell: harness.Cell, devices: List[Any], *, seed: int, seconds: float,
        trace: bool, t_start: float) -> str:
    import jax
    import jax.numpy as jnp

    config, traffic, params = cell.config, cell.traffic, cell.params
    counter = harness.CompileCounter()
    ref = harness.load_reference(config)
    with jax.default_device(devices[0]):
        weights = ref.make_params(config, seed)
        ids = jnp.asarray(closed_loop.input_ids(
            traffic, int(config["vocab_size"]), seed))
    dag, schedule, backend = build(config, traffic, devices)
    # set-up drives the first steps: one that warms every task's program,
    # then one through the window's own call, so that whatever that call
    # does once it does before the window
    for kw in ({}, {"warmup": False}):
        first = backend.execute(dag.graph, schedule, weights, ids, **kw)
        jax.block_until_ready(first.output)
        del first
    setup = counter.snapshot()
    gc.collect()
    setup_s = CLOCK() - t_start
    harness.log(f"set-up {setup_s:.2f} s: {setup}")

    t0 = CLOCK()
    slice_ = harness.TraceSlice(cell.root, cell.name,
                                float(params["trace_seconds"]), trace, CLOCK)
    step_ms: List[float] = []
    spans: List[Any] = []   # the harness's own: the program's tracer costs
    #                         three steps' time per step when it is on
    reports: List[Dict[str, Any]] = []
    output = None
    t_last = t0
    while True:
        a = CLOCK()
        if a - t0 >= seconds:
            break
        slice_.poll(a, t0 + seconds)
        a = CLOCK()
        rep = backend.execute(dag.graph, schedule, weights, ids,
                              warmup=False)
        output = jax.block_until_ready(rep.output)
        b = t_last = CLOCK()
        step_ms.append((b - a) * 1e3)
        spans.append(("execute (host dispatch of one step)", a, b))
        reports.append({
            "dispatch_overhead_s": rep.dispatch_overhead_s,
            "n_dispatches": rep.n_dispatches,
            "transfer_edges": rep.transfer_edges,
            "transfer_bytes": rep.transfer_bytes,
            "planned": rep.planned,
        })
    loaded = slice_.finish()
    # time per step is the whole window over all its steps: whatever
    # stalls inside or between steps (the loop's own bookkeeping, a
    # garbage collection, a slow step) is in the figure
    window = t_last - t0
    step_mean_ms = window * 1e3 / len(step_ms)
    step_p50_ms = statistics.median(step_ms)
    in_window = counter.snapshot()["compiles"] - setup["compiles"]
    slowest = sorted(range(len(step_ms)), key=step_ms.__getitem__)[-3:]
    harness.log(
        f"window: {len(step_ms)} steps in {window:.4f} s = "
        f"{step_mean_ms:.3f} ms a step; single steps p50 "
        f"{step_p50_ms:.3f} min {min(step_ms):.3f} max "
        f"{max(step_ms):.3f} ms, {window - sum(step_ms) / 1e3:.3f} s of "
        f"the window between steps; slowest steps (index, ms) "
        f"{[(i, round(step_ms[i], 1)) for i in slowest]}; last report "
        f"{reports[-1]}; compilations in window: {in_window}")
    device = harness.device_block(devices)

    check = check_output(cell, weights, ids, output)
    harness.log(f"reference check: {check}")
    verdicts = decide(cell, check, in_window, len(step_ms))

    ctx: Dict[str, Any] = {
        "config": config, "traffic": traffic, "seconds": seconds,
        "reports": reports,
        "spans": spans,
        "trace": loaded, "device_kind": device["kind"],
        "n_devices": len(devices),
        "values": {"setup_s": setup_s, "dag_step_ms": step_mean_ms,
                   "dag_step_ms_p50": step_p50_ms},
    }
    breakdown = None
    if trace and loaded is not None:
        from .. import xplane

        host = [s for s in spans if s[2] >= slice_.t_start]
        summary = xplane.summarize(
            loaded, slice_.t_stop - slice_.t_start, host, slice_.t_sync,
            n_devices=len(devices))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        mods = xplane.self_times(xplane.line_events(
            xplane.device_planes(loaded)[0], xplane.MODULES_LINE))
        harness.log(f"trace: modules: {xplane.top(mods, 8, 1e-9)}")
        harness.log(f"trace: busy per device {summary['busy_s_per_device']}"
                    f" of {summary['window_s']:.3f} s")
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    defs = cell.per_layer if trace else cell.end_to_end
    return harness.result_line(
        correct=all(v["ok"] for v in verdicts), attempted=len(step_ms),
        failed=0, metrics=harness.read_metrics(defs, ctx),
        device=device, breakdown=breakdown,
    )


def readings(cell: harness.Cell, devices: List[Any], args: Any) -> None:
    """For ``benchmark.readings``: per seed, the first step's logits
    against the reference, and with ``--control 1`` the int8 forward's."""
    import json

    import jax
    import jax.numpy as jnp

    ref = harness.load_reference(cell.config)
    dag, schedule, backend = build(cell.config, cell.traffic, devices)
    for seed in args.seeds:
        with jax.default_device(devices[0]):
            weights = ref.make_params(cell.config, seed)
            ids = jnp.asarray(closed_loop.input_ids(
                cell.traffic, int(cell.config["vocab_size"]), seed))
        rep = backend.execute(dag.graph, schedule, weights, ids)
        out = jax.block_until_ready(rep.output)
        row = {"seed": seed,
               "program": check_output(cell, weights, ids, out)}
        if args.control:
            row["control"] = check_output(cell, weights, ids, out,
                                            control=True)
        print("READING " + json.dumps(row), flush=True)
        del rep, out, weights
    print("DEVICE " + json.dumps(harness.device_block(devices)), flush=True)

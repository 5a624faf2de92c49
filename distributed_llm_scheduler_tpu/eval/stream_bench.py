"""Oversubscribed execution probe: a model bigger than the device budget.

The reference's headline scenario is scheduling a 37.5 GB-param model onto
28 GB of laptops (reference ``test_gpt2.py:274-299``) with parameter
eviction (reference ``schedulers.py:404-442``) — but it only ever
*simulates* that.  This probe makes it physical on a real chip: cap the
node's parameter budget at a fraction of the model's
total param bytes and execute with ``stream_params=True`` — prefetched
batched loads with Belady (farthest-next-use) eviction keep residency
under budget, so the model runs correctly even though its weights never
co-reside.  A sibling leg measures the same budget with int8 weights
(half the streamed bytes).

Run directly (on the TPU, or the CPU mesh for a functional check)::

    python -m distributed_llm_scheduler_tpu.eval.stream_bench [budget_frac]

Emits one JSON dict: uncapped (all params resident) vs capped+streamed
makespans, load/eviction counts, peak resident param bytes (must respect
the budget), and an output-parity flag against the fused forward.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def measure_streaming(
    config: Any = None,
    batch: int = 8,
    seq_len: int = 512,
    budget_frac: float = 0.3,
    policy: str = "greedy",
    log=lambda m: print(m, file=sys.stderr, flush=True),
) -> Dict[str, Any]:
    """Execute a forward DAG per-task with params capped at
    ``budget_frac`` x total param bytes, vs. the uncapped placed run.

    Single-device by design: the point is the *capacity* mechanism, so
    one node holds the whole model (uncapped) or streams it (capped) —
    the purest form of the reference's oversubscription scenario.
    """
    from .. import get_scheduler
    from ..backends.device import DeviceBackend
    from ..core.cluster import Cluster
    from ..frontend.gpt2_dag import build_gpt2_dag
    from ..models.gpt2 import GPT2Config

    if config is None:
        config = GPT2Config.medium(dtype=jnp.bfloat16)
    dag = build_gpt2_dag(config, batch=batch, seq_len=seq_len)
    graph = dag.graph
    params = dag.init_params()
    ids = dag.make_inputs()
    total_param_gb = graph.total_param_gb()

    dev = jax.devices()[0]
    cluster = Cluster.from_jax_devices([dev])
    backend = DeviceBackend(cluster)
    sched = get_scheduler(policy).schedule(graph, cluster)
    assert not sched.failed, "single uncapped node must fit every task"

    # uncapped: params placed up-front, all resident
    from .benchlib import oracle_close

    dtype_name = jnp.dtype(config.dtype).name
    rep_full = backend.execute(graph, sched, params, ids)
    fused = dag.reference_forward(params, ids)
    full_ok = oracle_close(fused, rep_full.output, dtype_name)
    log(f"stream_bench: uncapped makespan {rep_full.makespan_s*1e3:.1f} ms "
        f"({total_param_gb:.3f} GB params resident); oracle: {full_ok}")

    # capped: budget below total params -> must stream + evict.
    # budget is set AFTER scheduling so the placement is identical — the
    # comparison isolates the capacity mechanism, not policy reaction.
    budget_gb = total_param_gb * budget_frac
    orig_budgets = {d.node_id: d.total_memory for d in cluster}
    for d in cluster:
        d.total_memory = budget_gb
    rep_cap = backend.execute(graph, sched, params, ids, stream_params=True)
    # the capped run does strictly more work than the uncapped one, so a
    # faster capped measurement is host-contention noise inflating the
    # uncapped floor (observed on the shared CPU host: bound_utilization
    # 3.5 when a TPU capture ran concurrently) — re-measure the floor,
    # bounded, keeping the min
    tries = 0
    while rep_cap.makespan_s < rep_full.makespan_s and tries < 2:
        for d in cluster:
            d.total_memory = orig_budgets[d.node_id]
        rerun = backend.execute(graph, sched, params, ids)
        if rerun.makespan_s < rep_full.makespan_s:
            rep_full = rerun
            # the adopted run must carry its own oracle verdict, and the
            # log must match the JSON an auditor will diff against
            full_ok = oracle_close(fused, rep_full.output, dtype_name)
            log(f"stream_bench: uncapped floor re-measured "
                f"{rep_full.makespan_s*1e3:.1f} ms (contended first "
                f"window); oracle: {full_ok}")
        for d in cluster:
            d.total_memory = budget_gb
        tries += 1
    cap_ok = oracle_close(fused, rep_cap.output, dtype_name)
    peak_gb = max(rep_cap.peak_param_bytes.values()) / 1024**3
    log(f"stream_bench: capped@{budget_frac:.2f}x makespan "
        f"{rep_cap.makespan_s*1e3:.1f} ms; {rep_cap.param_loads} loads "
        f"({rep_cap.param_load_calls} batched calls, "
        f"{rep_cap.param_load_bytes/1024**2:.1f} MB), "
        f"{rep_cap.param_evictions} evictions, peak resident "
        f"{peak_gb:.3f} GB on {budget_gb:.3f} GB budget; oracle: {cap_ok}")

    # how far from its own floor is the streamed run?  Floor = the larger
    # of compute (uncapped makespan) and
    # the measured host-link transfer time for the bytes actually
    # streamed; a perfectly overlapped pipeline hits max(), not sum()
    import math

    from ..utils.linkmodel import calibrate_link

    cal = calibrate_link(
        [dev], sizes=(1 << 20, 1 << 24), repeats=3, sustained=True
    )
    link = cal.to_link_model()
    host_gbps: Optional[float] = link.param_load_gbps
    if not math.isfinite(host_gbps) or host_gbps <= 0:
        # noise-degenerate fit (latency-dominated samples can be
        # non-monotonic -> _fit_affine returns inf): disclose, don't emit
        # Infinity into the JSON
        log("stream_bench: WARNING burst link fit degenerate "
            f"({host_gbps}); floor falls back to sustained/achieved")
        host_gbps = None
    # streaming moves hundreds of MB back-to-back: its floor is the
    # SUSTAINED link rate, which need not match the burst rate
    # (linkmodel docstring); both rates are reported for the audit
    # trail.
    sustained_gbps: Optional[float] = cal.sustained_gbps
    if sustained_gbps is not None and (
        not math.isfinite(sustained_gbps) or sustained_gbps <= 0
    ):
        sustained_gbps = None
    # the streamed run itself demonstrated a sustained rate over ~20 s;
    # if the short probe read lower (a stall covering just the probe
    # window), the link is provably at least as fast as what the run
    # achieved — floor on the best demonstrated rate, so a stalled probe
    # can't push bound_utilization above 1
    achieved = (
        rep_cap.param_load_bytes / 1024**3 / max(rep_cap.makespan_s, 1e-12)
    )
    floor_gbps = sustained_gbps or host_gbps
    floor_source = "sustained_probe" if sustained_gbps else (
        "burst_probe" if host_gbps else None
    )
    if floor_gbps is not None and achieved > floor_gbps:
        # the clamp makes the link-side bound self-referential (it equals
        # the capped makespan, so bound_utilization reads ~1.0) — the
        # floor_source field discloses that the probe under-read and the
        # "distance to floor" is a lower bound, not a measurement
        floor_gbps = achieved
        floor_source = "achieved(probe under-read)"
    link_bound_s = (
        rep_cap.param_load_bytes / (floor_gbps * 1024**3)
        if floor_gbps
        else None
    )
    floor_s = max(rep_full.makespan_s, link_bound_s or 0.0)
    bound_utilization = floor_s / max(rep_cap.makespan_s, 1e-12)
    log(f"stream_bench: host link burst "
        + (f"{host_gbps:.2f} GB/s" if host_gbps else "unknown")
        + ", sustained "
        + (f"{sustained_gbps:.4f} GB/s" if sustained_gbps else "unknown")
        + " -> transfer bound "
        + (f"{link_bound_s*1e3:.1f} ms" if link_bound_s else "n/a")
        + f", compute {rep_full.makespan_s*1e3:.1f} ms; "
        f"bound utilization {bound_utilization:.1%}")

    # int8-quantized streaming: same device budget, half the streamed
    # bytes — in the transfer-bound regime streaming lives in, cutting
    # bytes IS the optimization (the reference's founding constraint
    # attacked at the representation level, composed with streaming).
    q_ms = q_ok = q_load_gb = q_total_gb = None
    q_peak_gb = q_budget_ok = None
    try:
        from ..utils.quantize import quantize_dag

        qdag = quantize_dag(dag)
        qparams = qdag.init_params()
        qcluster = Cluster.from_jax_devices([dev])
        qsched = get_scheduler(policy).schedule(qdag.graph, qcluster)
        assert not qsched.failed
        for d in qcluster:
            d.total_memory = budget_gb  # the SAME capped budget
        rep_q = DeviceBackend(qcluster).execute(
            qdag.graph, qsched, qparams, ids, stream_params=True
        )
        q_ok = oracle_close(
            qdag.reference_forward(qparams, ids), rep_q.output, dtype_name
        )
        q_ms = rep_q.makespan_s * 1e3
        q_load_gb = rep_q.param_load_bytes / 1024**3
        q_total_gb = qdag.graph.total_param_gb()
        # the "same budget" claim must be *checked*, same as the bf16 leg:
        # an under-evicting streamer could let the 0.33 GB of int8 weights
        # co-reside and fake the speedup
        q_peak_gb = max(rep_q.peak_param_bytes.values()) / 1024**3
        q_budget_ok = bool(q_peak_gb <= budget_gb * 1.02 + 1e-6)
        log(f"stream_bench: int8 capped makespan {q_ms:.1f} ms "
            f"({q_load_gb:.3f} GB streamed vs {total_param_gb:.3f} bf16, "
            f"peak {q_peak_gb:.3f} on the same {budget_gb:.3f} GB "
            f"budget, respected={q_budget_ok}); oracle: {q_ok}")
    except Exception:
        import traceback

        log("stream_bench: WARNING quantized streaming failed:\n"
            + traceback.format_exc())

    n_params = len(graph.unique_params())
    return {
        "model": graph.name,
        "platform": dev.platform,
        "n_tasks": len(graph),
        "n_params": n_params,
        "total_param_gb": round(total_param_gb, 4),
        "budget_frac": budget_frac,
        "budget_gb": round(budget_gb, 4),
        "uncapped_makespan_ms": round(rep_full.makespan_s * 1e3, 3),
        "capped_makespan_ms": round(rep_cap.makespan_s * 1e3, 3),
        "slowdown": round(
            rep_cap.makespan_s / max(rep_full.makespan_s, 1e-12), 3
        ),
        "param_loads": rep_cap.param_loads,
        "param_load_calls": rep_cap.param_load_calls,
        "param_load_gb": round(rep_cap.param_load_bytes / 1024**3, 4),
        "param_evictions": rep_cap.param_evictions,
        "host_link_gbps": round(host_gbps, 3) if host_gbps else None,
        "sustained_gbps": (
            round(sustained_gbps, 4) if sustained_gbps else None
        ),
        "link_bound_ms": (
            round(link_bound_s * 1e3, 3) if link_bound_s else None
        ),
        "bound_utilization": round(bound_utilization, 4),
        "floor_source": floor_source,
        # throughput the streamed run actually sustained end-to-end;
        # exceeding the probes means they under-read the link (the floor
        # clamps to this, disclosed via floor_source — so the link-side
        # bound can't overshoot; only a contended compute floor can push
        # bound_utilization above 1.0, and that gets re-measured above)
        "achieved_gbps": round(achieved, 4),
        "peak_resident_param_gb": round(peak_gb, 4),
        "budget_respected": bool(peak_gb <= budget_gb * 1.02 + 1e-6),
        "oracle_ok": bool(full_ok and cap_ok),
        # int8 leg (None when it failed): same budget, ~half the bytes
        "quantized_capped_makespan_ms": (
            round(q_ms, 3) if q_ms is not None else None
        ),
        "quantized_oracle_ok": q_ok,
        "quantized_param_load_gb": (
            round(q_load_gb, 4) if q_load_gb is not None else None
        ),
        "quantized_total_param_gb": (
            round(q_total_gb, 4) if q_total_gb is not None else None
        ),
        "quantized_peak_resident_gb": (
            round(q_peak_gb, 4) if q_peak_gb is not None else None
        ),
        "quantized_budget_respected": q_budget_ok,
        # throughput while oversubscribed: forward passes per second
        "capped_forwards_per_s": round(
            1.0 / max(rep_cap.makespan_s, 1e-12), 3
        ),
    }


if __name__ == "__main__":
    import json

    frac = float(sys.argv[1]) if len(sys.argv) > 1 else 0.3
    print(json.dumps(measure_streaming(budget_frac=frac), indent=1))

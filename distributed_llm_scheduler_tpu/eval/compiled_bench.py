"""Whole-program compiled-path bench: one launch per run, and faster.

The tentpole claim behind :mod:`..backends.compiled_schedule` is
mechanical and falsifiable: lowering the ENTIRE placed run into one
jitted program (per-device compute under a mesh-index switch,
cross-device edges as in-program ``ppermute``) must

* keep outputs bit-identical to the planned interpreted path,
* cut host launches per run to O(devices) — input-leaf staging puts
  plus ONE program launch, never O(tasks),
* cut host dispatch wall at least ``--min-overhead-reduction`` (default
  5x) vs the planned path,
* not lose makespan to the segmented runner (the previous production
  rung): compiled makespan <= segmented * (1 + ``--makespan-slack``).

Measured on a medium-structured multi-device DAG (24 layers,
microbatches=8, vocab_shards=8 by default — the BENCH_MEDIUM shape with
tiny tensor dims) placed across the 8-virtual-device CPU mesh, so the
cross-device edges are real ``ppermute`` hops, not a degenerate
single-chip program.

Usage::

    JAX_PLATFORMS=cpu python -m distributed_llm_scheduler_tpu.eval.compiled_bench

The module forces ``--xla_force_host_platform_device_count=8`` before
JAX initializes, so no accelerator is needed (and none is used).
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) benchmark harness: wall time IS the measured quantity

import os

from ..utils.config import env_str

# must be set before jax initializes its backend (conftest.py does the
# same for tests); harmless if jax is already up — we then require the
# caller to have provided the mesh
_flags = env_str("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import argparse
import dataclasses
import json
import statistics
import sys
import time
from typing import Any, Dict

import jax
import numpy as np

from ..backends.device import DeviceBackend
from ..core.cluster import Cluster
from ..sched.policies import get_scheduler
from .benchlib import spread_stats


def _bit_identical(a: Any, b: Any) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


def run_compiled_bench(
    n_layer: int = 24,
    batch: int = 8,
    seq_len: int = 8,
    microbatches: int = 8,
    vocab_shards: int = 8,
    policy: str = "roundrobin",
    samples: int = 3,
    reps: int = 1,
    log=None,
) -> Dict[str, Any]:
    """Measure planned / segmented / compiled on one multi-device
    schedule; return the report dict.  Gates are *evaluated* here but
    enforced by the caller."""
    from ..frontend.gpt2_dag import build_gpt2_dag
    from ..models.gpt2 import GPT2Config
    from ..utils.costmodel import _fence_rtt

    cfg = dataclasses.replace(GPT2Config.tiny(), n_layer=n_layer)
    dag = build_gpt2_dag(
        cfg, batch=batch, seq_len=seq_len,
        microbatches=microbatches, vocab_shards=vocab_shards,
    )
    graph = dag.graph
    params = dag.init_params()
    ids = dag.make_inputs()

    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    backend = DeviceBackend(cluster)
    schedule = get_scheduler(policy).schedule(graph, cluster)
    if schedule.failed:
        raise RuntimeError(
            f"policy {policy!r} failed to place "
            f"{len(schedule.failed)} tasks; bench needs a full plan"
        )

    # one fence-RTT calibration shared by every leg (the bench.py hoist,
    # same rationale: per-execute probes would dominate these short legs)
    rtt = _fence_rtt(backend._fence_device())

    legs = {
        "planned": dict(),
        "segmented": dict(segments=True, planned=False),
        "compiled": dict(compiled=True),
    }
    results: Dict[str, Dict[str, Any]] = {}
    outputs: Dict[str, Any] = {}
    for name, kw in legs.items():
        t0 = time.perf_counter()
        # warmup execute compiles; timed samples reuse the caches
        rep = backend.execute(
            graph, schedule, params, ids, fence_rtt=rtt, **kw
        )
        outputs[name] = rep.output
        mk, ov = [], []
        for _ in range(samples):
            r = backend.execute(
                graph, schedule, params, ids, warmup=False, reps=reps,
                fence_rtt=rtt, **kw
            )
            mk.append(r.makespan_s)
            ov.append(r.dispatch_overhead_s)
            rep = r
        results[name] = {
            "makespan_ms": statistics.median(mk) * 1e3,
            "dispatch_overhead_ms": statistics.median(ov) * 1e3,
            "spread": spread_stats(mk),
            "n_dispatches": rep.n_dispatches,
            "transfer_edges": rep.transfer_edges,
            "wall_s": time.perf_counter() - t0,
        }
        if log:
            log(
                f"  {name}: makespan {results[name]['makespan_ms']:.2f} ms, "
                f"host dispatch "
                f"{results[name]['dispatch_overhead_ms']:.2f} ms "
                f"({rep.n_dispatches} launches, median of {samples})"
            )

    bit_identical = _bit_identical(
        outputs["planned"], outputs["compiled"]
    ) and _bit_identical(outputs["planned"], outputs["segmented"])
    if log:
        log(f"  bit-identical outputs (planned vs segmented vs compiled): "
            f"{bit_identical}")

    n_input_leaves = len(jax.tree_util.tree_leaves(ids))
    return {
        "bench": "compiled_schedule_bench",
        "platform": jax.devices()[0].platform,
        "n_devices": len(cluster.devices),
        "n_tasks": len(graph.topo_order),
        "n_input_leaves": n_input_leaves,
        "policy": policy,
        "fence_rtt_ms": rtt * 1e3,
        "config": {
            "n_layer": n_layer, "batch": batch, "seq_len": seq_len,
            "microbatches": microbatches, "vocab_shards": vocab_shards,
            "samples": samples, "reps": reps,
        },
        "legs": results,
        "bit_identical": bit_identical,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="whole-program compiled execution bench + gates"
    )
    ap.add_argument("--samples", type=int, default=3)
    # reps=1 is deliberate: on the CPU PJRT client, re-enqueueing the
    # same executable while its previous execution is still in flight
    # BLOCKS the host, so a multi-rep compiled leg measures device
    # compute, not host dispatch.  Each sample ends with a fence, so
    # every single-rep launch is a clean enqueue — for all legs equally.
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--policy", default="roundrobin")
    ap.add_argument("--n-layer", type=int, default=24)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument(
        "--min-overhead-reduction", type=float, default=5.0,
        help="required host-dispatch-wall reduction factor, compiled vs "
        "planned (the tentpole's >=5x claim)",
    )
    ap.add_argument(
        "--makespan-slack", type=float, default=0.05,
        help="compiled makespan may exceed segmented by at most this "
        "fraction (timer noise allowance on shared CI hosts)",
    )
    ap.add_argument(
        "--launch-epsilon", type=int, default=1,
        help="host launches per run must be <= n_devices + this",
    )
    ap.add_argument("--out", default=None, help="write JSON report here")
    args = ap.parse_args(argv)

    # a host-overhead measurement on the virtual CPU mesh by design:
    # pinned so it never lands on an attached accelerator
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < 8:
        print(
            "compiled_bench: need 8 CPU devices "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before python starts)",
            file=sys.stderr,
        )
        return 2

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    log("compiled bench: medium-structured DAG on 8-device CPU mesh")
    report = run_compiled_bench(
        n_layer=args.n_layer, seq_len=args.seq_len, policy=args.policy,
        samples=args.samples, reps=args.reps, log=log,
    )

    legs = report["legs"]
    ok = True
    planned_ov = legs["planned"]["dispatch_overhead_ms"]
    compiled_ov = legs["compiled"]["dispatch_overhead_ms"]
    factor = planned_ov / compiled_ov if compiled_ov > 0 else float("inf")
    if factor < args.min_overhead_reduction:
        log(
            f"GATE FAIL: compiled dispatch wall {compiled_ov:.2f} ms is "
            f"only {factor:.1f}x below planned {planned_ov:.2f} ms "
            f"(need >= {args.min_overhead_reduction:.1f}x)"
        )
        ok = False
    launches = legs["compiled"]["n_dispatches"]
    budget = report["n_devices"] + args.launch_epsilon
    if launches > budget:
        log(
            f"GATE FAIL: compiled path issued {launches} host launches "
            f"> n_devices + eps = {budget}"
        )
        ok = False
    seg_mk = legs["segmented"]["makespan_ms"]
    comp_mk = legs["compiled"]["makespan_ms"]
    if comp_mk > seg_mk * (1.0 + args.makespan_slack):
        log(
            f"GATE FAIL: compiled makespan {comp_mk:.2f} ms exceeds "
            f"segmented {seg_mk:.2f} ms by more than "
            f"{args.makespan_slack:.0%}"
        )
        ok = False
    if not report["bit_identical"]:
        log("GATE FAIL: compiled outputs are not bit-identical to planned")
        ok = False
    report["gates"] = {
        "min_overhead_reduction": args.min_overhead_reduction,
        "overhead_reduction_factor": round(factor, 2),
        "makespan_slack": args.makespan_slack,
        "launch_epsilon": args.launch_epsilon,
        "passed": ok,
    }
    if ok:
        log(
            f"GATES PASS: {factor:.1f}x dispatch reduction, "
            f"{launches} launches <= {budget}, compiled {comp_mk:.2f} ms "
            f"<= segmented {seg_mk:.2f} ms (+{args.makespan_slack:.0%}), "
            f"bit_identical={report['bit_identical']}"
        )

    text = json.dumps(report, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Host-dispatch-overhead microbench: planned fast path vs legacy loop.

The tentpole claim behind :mod:`..backends.dispatch_plan` is mechanical
and falsifiable: on a DAG with flagship *structure* (12 layers,
microbatches=8, vocab_shards=8 — 921 tasks) but tiny tensor dims, host
dispatch overhead dominates wall time, and the pre-planned path must cut
it.  This module measures ``DeviceReport.dispatch_overhead_s`` (host wall
inside the dispatch loop, fence excluded) for four configurations on the
8-virtual-device CPU mesh:

* ``legacy``          — the per-task ``_run`` loop (``planned=False``)
* ``planned``         — plan-then-dispatch, one launch a task
                        (``coalesce=False``; donation on where supported)
* ``coalesce``        — ``execute()``'s default: planned + fused
                        multi-task launches, every run, donation on
* ``coalesce_nodonate`` — planned + coalesced with donation off: the pure
                        dispatch-overhead configuration (donation trades
                        a little host time for peak-memory savings, so it
                        is excluded from the primary gate)

Each leg is sampled ``--samples`` times (min quoted; full spread kept via
:func:`..eval.benchlib.spread_stats`) with ``--reps`` amortized reps per
sample.  Two gates, both asserted in CI:

* ``coalesce_nodonate`` must reduce host dispatch wall by at least
  ``--min-reduction`` (default 0.40) vs ``legacy``;
* ``planned`` (per-task launches, donation on) must still beat ``legacy`` by at
  least ``--min-reduction-default`` (default 0.15).

Bit-identity is checked alongside: a ``keep_outputs`` run of the
coalesced path must reproduce every task output of the legacy loop
bit-for-bit (``optimization_barrier`` between coalesced members makes
this exact, not approximate).

Usage::

    JAX_PLATFORMS=cpu python -m distributed_llm_scheduler_tpu.eval.dispatch_bench

The module forces ``--xla_force_host_platform_device_count=8`` before JAX
initializes, so no accelerator is needed (and none is used).
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
import sys
import time
from typing import Any, Dict, Optional

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


def run_dispatch_bench(
    n_layer: int = 12,
    batch: int = 8,
    seq_len: int = 8,
    microbatches: int = 8,
    vocab_shards: int = 8,
    policy: str = "greedy",
    samples: int = 5,
    reps: int = 3,
    check_outputs: bool = True,
    log=None,
) -> Dict[str, Any]:
    """Measure all four dispatch configurations; return the report dict.

    Gates are *evaluated* here (``reduction`` fields) but enforced by the
    caller — tests and the CLI choose their own thresholds.
    """
    from ..frontend.gpt2_dag import build_gpt2_dag
    from ..models.gpt2 import GPT2Config

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
            f"{len(schedule.failed)} tasks; microbench needs a full plan"
        )

    legs = {
        "legacy": dict(planned=False),
        "planned": dict(coalesce=False),
        "coalesce": dict(),
        "coalesce_nodonate": dict(donate=False),
    }
    results: Dict[str, Dict[str, Any]] = {}
    for name, kw in legs.items():
        t0 = time.perf_counter()
        vals = []
        rep = None
        for _ in range(samples):
            rep = backend.execute(
                graph, schedule, params, ids, reps=reps, **kw
            )
            vals.append(rep.dispatch_overhead_s)
        results[name] = {
            "dispatch_overhead_ms": min(vals) * 1e3,
            "spread": spread_stats(vals),
            "n_dispatches": rep.n_dispatches,
            "dispatch_phases_ms": {
                k: v * 1e3 for k, v in rep.dispatch_phases.items()
            },
            "transfer_edges": rep.transfer_edges,
            "wall_s": time.perf_counter() - t0,
        }
        if log:
            log(
                f"  {name}: {min(vals)*1e3:.1f} ms host dispatch "
                f"({rep.n_dispatches} launches, {samples}x min)"
            )

    base = results["legacy"]["dispatch_overhead_ms"]
    for name in ("planned", "coalesce", "coalesce_nodonate"):
        results[name]["reduction_vs_legacy"] = (
            1.0 - results[name]["dispatch_overhead_ms"] / base
            if base > 0 else 0.0
        )

    bit_identical: Optional[bool] = None
    if check_outputs:
        rl = backend.execute(
            graph, schedule, params, ids, planned=False, keep_outputs=True
        )
        rc = backend.execute(
            graph, schedule, params, ids, keep_outputs=True
        )
        bit_identical = set(rl.task_outputs) == set(rc.task_outputs) and all(
            _bit_identical(rl.task_outputs[t], rc.task_outputs[t])
            for t in rl.task_outputs
        )
        if log:
            log(f"  bit-identical outputs (legacy vs coalesced): {bit_identical}")

    return {
        "bench": "dispatch_microbench",
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "n_tasks": len(graph.topo_order),
        "policy": policy,
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
        description="planned-vs-legacy host dispatch overhead microbench"
    )
    ap.add_argument("--samples", type=int, default=5)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--policy", default="greedy")
    ap.add_argument("--n-layer", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument(
        "--min-reduction", type=float, default=0.40,
        help="required reduction for coalesce_nodonate vs legacy",
    )
    ap.add_argument(
        "--min-reduction-default", type=float, default=0.15,
        help="required reduction for planned (defaults) vs legacy",
    )
    ap.add_argument("--out", default=None, help="write JSON report here")
    args = ap.parse_args(argv)

    # a host-overhead measurement on the virtual CPU mesh by design:
    # pinned so it never lands on an attached accelerator
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < 8:
        print(
            "dispatch_bench: need 8 CPU devices "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before python starts)",
            file=sys.stderr,
        )
        return 2

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    log("dispatch microbench: flagship-structured DAG on 8-device CPU mesh")
    report = run_dispatch_bench(
        n_layer=args.n_layer, seq_len=args.seq_len, policy=args.policy,
        samples=args.samples, reps=args.reps, log=log,
    )

    legs = report["legs"]
    fast = legs["coalesce_nodonate"]["reduction_vs_legacy"]
    dflt = legs["planned"]["reduction_vs_legacy"]
    ok = True
    if fast < args.min_reduction:
        log(
            f"GATE FAIL: coalesce_nodonate reduced dispatch wall by "
            f"{fast:.1%} < required {args.min_reduction:.0%}"
        )
        ok = False
    if dflt < args.min_reduction_default:
        log(
            f"GATE FAIL: planned (defaults) reduced dispatch wall by "
            f"{dflt:.1%} < required {args.min_reduction_default:.0%}"
        )
        ok = False
    if report["bit_identical"] is False:
        log("GATE FAIL: coalesced outputs are not bit-identical to legacy")
        ok = False
    report["gates"] = {
        "min_reduction": args.min_reduction,
        "min_reduction_default": args.min_reduction_default,
        "passed": ok,
    }
    if ok:
        log(
            f"GATES PASS: coalesce_nodonate -{fast:.1%}, "
            f"planned -{dflt:.1%}, bit_identical={report['bit_identical']}"
        )

    text = json.dumps(report, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

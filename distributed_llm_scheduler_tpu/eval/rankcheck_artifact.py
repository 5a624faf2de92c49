"""Assemble RANKCHECK_r{N}.json: the flagship and separating legs.

The two legs answer different questions:

* ``flagship`` — the bench's own configuration (GPT-2 small mb8+vs8
  fused, compute-tied on the CPU mesh), with the two-anchor in-situ
  calibration (``run_rank_check(anchor_calibrate=True)``): does the
  replay's cost model, once grounded against a busy host, rank the
  policies the way reality does?  The r4 leg predicted a 1.7% spread
  where reality spread 37% — the quiet-host microbenchmarks under-charge
  staging ~30x under load (fitted: ~1 GB/s vs ~30 GB/s quiet).
* ``separating`` — the transfer-bound stress DAG where the sim predicts
  separation from first principles, so rank agreement is asserted with
  no tie escape and no calibration.

Run under the virtual mesh::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m distributed_llm_scheduler_tpu.eval.rankcheck_artifact 5
"""

from __future__ import annotations

import json
import os
import sys

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def flagship_leg() -> dict:
    from ..core.fusion import fuse_linear_chains
    from ..frontend.gpt2_dag import build_gpt2_dag
    from ..models.gpt2 import GPT2Config
    from .rankcheck import run_rank_check

    dag = build_gpt2_dag(
        GPT2Config.small(), batch=8, seq_len=128, microbatches=8,
        vocab_shards=8,
    )
    graph = fuse_linear_chains(dag.graph)
    return run_rank_check(
        graph, dag.init_params(), dag.make_inputs(),
        policies=("roundrobin", "critical", "pipeline", "pack", "greedy"),
        hbm_cap_gb=4.0, measure_repeats=5, anchor_calibrate=True, log=log,
    )


def separating_leg() -> dict:
    import jax

    from ..core.cluster import Cluster
    from ..frontend.stress_dag import build_transfer_stress_dag
    from .rankcheck import run_rank_check

    dag = build_transfer_stress_dag(chains=6, length=6, edge_mb=8.0)
    cluster = Cluster.from_jax_devices(jax.devices()[:4], hbm_cap_gb=4.0)
    return run_rank_check(
        dag.graph, dag.init_params(), dag.make_inputs(),
        policies=("roundrobin", "critical", "dfs", "greedy", "pipeline"),
        cluster=cluster, measure_repeats=5, log=log,
    )


def main(argv) -> int:
    import jax

    # both legs are virtual-CPU-mesh measurements by design: pinned so
    # they never land on an attached accelerator
    jax.config.update("jax_platforms", "cpu")

    if not argv or not argv[0].isdigit():
        print(__doc__, file=sys.stderr)
        return 2
    round_n = int(argv[0])
    if len(jax.devices()) < 8:
        print("rankcheck_artifact needs the 8-device mesh "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return 2
    out = {
        "round": round_n,
        "note": (
            "Two legs: 'flagship' = the bench configuration with "
            "two-anchor in-situ calibration (anchors in-sample, other "
            "policies and the ordering out-of-sample); 'separating' = "
            "the transfer-bound stress config where the sim predicts "
            "separation uncalibrated."
        ),
        "flagship": flagship_leg(),
        "separating": separating_leg(),
    }
    path = os.path.join(REPO_ROOT, f"RANKCHECK_r{round_n:02d}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    log(f"rankcheck_artifact: wrote {path}")
    ok = True
    for leg in ("flagship", "separating"):
        d = out[leg]
        ok &= bool(d["winner_agreement"]) and d["kendall_tau"] >= 0.8
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Native C++ engine: exact-parity tests against the pure-Python policies.

Every natively-implemented policy must emit bit-identical schedules (per-node
task lists, global assignment order, completed/failed sets) to its Python
twin across the synthetic workload families and the real GPT-2 DAG, including
memory-constrained regimes that trigger failures and MRU eviction.
"""

from __future__ import annotations

import pytest

from distributed_llm_scheduler_tpu.core.cluster import (
    Cluster,
    estimate_cluster_memory_needed,
)
from distributed_llm_scheduler_tpu.frontend.generators import (
    generate_llm_dag,
    generate_pipeline_dag,
    generate_random_dag,
)
from distributed_llm_scheduler_tpu.native import POLICY_IDS, available
from distributed_llm_scheduler_tpu.sched.native import NativeScheduler
from distributed_llm_scheduler_tpu.sched.policies import (
    ALL_SCHEDULERS,
    get_scheduler,
)

pytestmark = pytest.mark.skipif(
    not available(), reason="native engine unavailable (no g++?)"
)

NATIVE_POLICIES = sorted(POLICY_IDS)


def make_graphs():
    return [
        generate_llm_dag(num_layers=4, num_heads=4, seed=7),
        generate_llm_dag(num_layers=8, num_heads=2, seed=11),
        generate_random_dag(num_tasks=60, seed=7),
        generate_pipeline_dag(num_stages=5, tasks_per_stage=4, seed=7),
    ]


def assert_same_schedule(py, nat, label):
    assert nat.completed == py.completed, f"{label}: completed sets differ"
    assert nat.failed == py.failed, f"{label}: failed sets differ"
    assert nat.per_node == py.per_node, f"{label}: per-node lists differ"
    assert nat.assignment_order == py.assignment_order, (
        f"{label}: assignment order differs"
    )


@pytest.mark.parametrize("policy", NATIVE_POLICIES)
@pytest.mark.parametrize("regime", [1.0, 0.8, 0.5])
def test_parity_synthetic(policy, regime):
    for graph in make_graphs():
        graph.freeze()
        total = estimate_cluster_memory_needed(graph) * regime
        for n_nodes in (2, 4):
            py = ALL_SCHEDULERS[policy]().schedule(
                graph, Cluster.heterogeneous(total, n_nodes)
            )
            nat = NativeScheduler(policy).schedule(
                graph, Cluster.heterogeneous(total, n_nodes)
            )
            assert_same_schedule(
                py, nat, f"{policy}/{graph.name}/n{n_nodes}/r{regime}"
            )


@pytest.mark.parametrize("policy", NATIVE_POLICIES)
def test_parity_gpt2(policy):
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=64)
    graph = dag.graph
    py = ALL_SCHEDULERS[policy]().schedule(graph, Cluster.laptops())
    nat = NativeScheduler(policy).schedule(graph, Cluster.laptops())
    assert_same_schedule(py, nat, f"{policy}/gpt2")


def test_parity_under_failures():
    """A cluster too small for the DAG: failure handling must match too."""
    graph = generate_llm_dag(num_layers=6, num_heads=4, seed=3)
    # 1.0 GB nodes: the largest activations exceed a whole node, so even
    # MRU's eviction cannot save everything — all policies must fail tasks
    for policy in NATIVE_POLICIES:
        py = ALL_SCHEDULERS[policy]().schedule(graph, Cluster.uniform(2, 1.0))
        nat = NativeScheduler(policy).schedule(graph, Cluster.uniform(2, 1.0))
        assert_same_schedule(py, nat, f"{policy}/too-small")
        assert py.failed, f"{policy}: fixture should actually trigger failures"


def test_get_scheduler_native_prefix():
    s = get_scheduler("native:mru")
    assert isinstance(s, NativeScheduler)
    assert s.name == "native:mru"


def test_env_upgrade(monkeypatch):
    monkeypatch.setenv("DLS_NATIVE", "1")
    assert isinstance(get_scheduler("heft"), NativeScheduler)
    assert isinstance(get_scheduler("pipeline"), NativeScheduler)


def test_native_rejects_unknown_policy():
    with pytest.raises(ValueError, match="no native implementation"):
        NativeScheduler("no-such-policy")


def test_parity_pipeline_repack_ties():
    """Regression: the parked-group repack's tie-break (equal param-union
    loads -> prefer the LATER device) must match between Python and C++.
    flagship-shaped graph with equal-size shard groups hits exact float
    ties during the repack (caught diverging in review, round 2)."""
    from test_pipeline_rebalance import flagship_shaped_graph

    graph = flagship_shaped_graph(n_layers=6, n_shards=2, mb=2)
    for policy in ("pipeline", "pack"):
        py = ALL_SCHEDULERS[policy]().schedule(graph, Cluster.uniform(4, 100.0))
        nat = NativeScheduler(policy).schedule(graph, Cluster.uniform(4, 100.0))
        assert_same_schedule(py, nat, f"{policy}/repack-ties")


def test_parity_with_out_bytes():
    """Graphs whose tasks carry true output sizes (pre-flight out_bytes)
    must still schedule identically: the engine's event ordering charges
    cross-node transfers at TaskGraph.output_gb, not the activation proxy
    (the two diverge exactly when out_bytes is set)."""
    from distributed_llm_scheduler_tpu.core.cluster import DeviceState

    graph = generate_llm_dag(num_layers=6, num_heads=3, seed=5)
    # true outputs much smaller than activation footprints: transfer
    # charges shrink, which reshuffles event order and refine's search
    for i, tid in enumerate(graph.task_ids()):
        graph[tid].out_bytes = (i % 7 + 1) * 1_000_000
    cluster = Cluster([DeviceState(f"core_{i}", 8.0) for i in range(4)])
    for policy in ("pipeline", "pack", "refine", "heft"):
        py = get_scheduler(policy).schedule(graph, cluster)
        nat = NativeScheduler(policy).schedule(graph, cluster)
        assert_same_schedule(py, nat, f"{policy}+out_bytes")


@pytest.mark.parametrize("seed", [3, 17, 29, 41, 53])
def test_refine_parity_fuzz(seed):
    """Fuzz the refine twin: random graphs + heterogeneous speeds + tight
    memory hit different basin-hop trajectories (the RNG stream interacts
    with feasibility), so each seed exercises fresh tie-break paths."""
    import random as pyrandom

    from distributed_llm_scheduler_tpu.core.cluster import DeviceState

    r = pyrandom.Random(seed)
    graph = generate_random_dag(num_tasks=40 + seed, seed=seed)
    cluster = Cluster([
        DeviceState(f"n{i}", 3.0 + 2.0 * r.random(),
                    compute_speed=0.7 + 0.6 * r.random())
        for i in range(r.randrange(2, 6))
    ])
    py = get_scheduler("refine").schedule(graph, cluster)
    nat = NativeScheduler("refine").schedule(graph, cluster)
    assert_same_schedule(py, nat, f"refine fuzz seed={seed}")


def test_refine_parity_misaligned_node_ids():
    """refine's bottleneck tie-break compares node-id STRINGS, which cross
    the ABI as lexicographic ranks.  Every other fixture uses ids whose
    sorted order equals cluster order, so the rank plumbing degenerates to
    the identity there; this case uses ids sorted differently than their
    indices (n1 < n10 < n2) and a symmetric graph engineered so multiple
    devices tie on finish time — a wrong rank picks a different
    bottleneck and diverges."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph
    from distributed_llm_scheduler_tpu.core.cluster import DeviceState

    graph = TaskGraph([
        Task(
            f"t{i:02d}", 0.1, 0.5,
            params_needed={f"w{i:02d}"}, param_bytes={f"w{i:02d}": 2 << 28},
        )
        for i in range(12)  # identical independent tasks, one param each
    ])
    cluster = Cluster([
        DeviceState("n2", 4.0), DeviceState("n10", 4.0), DeviceState("n1", 4.0)
    ])
    py = get_scheduler("refine").schedule(graph, cluster)
    nat = NativeScheduler("refine").schedule(graph, cluster)
    assert_same_schedule(py, nat, "refine misaligned node ids")


@pytest.mark.parametrize("policy,microbatches,n_tasks", [
    ("pack", 8, 1561), ("refine", 2, 391),
])
def test_parity_where_packs_runs_engage(policy, microbatches, n_tasks):
    """The DAG cells' own graph at tiny widths — 24 equal layer groups; 8
    microbatches, 1,561 tasks, for ``pack``; 2 for ``refine``, whose climb
    from that seed replays the graph 400 times in Python — on four
    devices: LPT deals the layers out in turn and ``make_runs_contiguous``
    hands them back as runs (the tiny graphs above have no class of two
    groups spread over two devices), so the C++ pass is held to the Python
    one where it moves groups."""
    import dataclasses

    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.obs import process_metrics

    graph = build_gpt2_dag(
        dataclasses.replace(GPT2Config.tiny(), n_layer=24),
        batch=microbatches, seq_len=16, microbatches=microbatches,
    ).graph
    assert len(graph.topo_order) == n_tasks
    py = ALL_SCHEDULERS[policy]().schedule(graph, Cluster.uniform(4, 4.0))
    assert process_metrics().snapshot()["gauges"][
        "sched.pack.groups_made_contiguous"]["value"] > 0
    nat = NativeScheduler(policy).schedule(graph, Cluster.uniform(4, 4.0))
    assert not py.failed
    assert_same_schedule(py, nat, f"{policy}/24 equal layers")

"""GroupPackScheduler: non-contiguous balanced group packing.

Generic policy contracts (completion, validation, native parity) come from
the parametrized suites; these tests pin pack's specific claims: balanced
param loads, tied-weight gravity, and its win over contiguity in the
host-link-bound regime it was built for.
"""

import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.sim import LinkModel, SimulatedBackend
from distributed_llm_scheduler_tpu.sched.pack import GroupPackScheduler
from distributed_llm_scheduler_tpu.sched.pipeline import PipelineStageScheduler

from test_pipeline_rebalance import (
    flagship_shaped_graph,
    host_bound_link,
    per_device_load,
)


def test_pack_balances_param_loads():
    graph = flagship_shaped_graph(n_layers=6, n_shards=4, mb=2)
    cluster = Cluster.uniform(4, 100.0)
    s = GroupPackScheduler(link=host_bound_link()).schedule(graph, cluster)
    assert not s.failed
    loads = per_device_load(graph, s)
    # 11.4 GB total over 4 devices; LPT must stay within one small group
    # (0.9) of the 2.85 perfect split
    assert max(loads.values()) <= 2.85 + 0.9 + 1e-6, loads


def test_pack_competitive_in_host_bound_regime():
    """Pack must crush round-robin and stay within a few percent of the
    load-aware pipeline on a graph small enough for contiguity to cost
    nothing (the flagship-scale advantage is measured by bench.py: 21.6 ms
    pack vs 23.3 ms pipeline/greedy under the measured TPU link)."""
    graph = flagship_shaped_graph(n_layers=6, n_shards=4, mb=2)
    link = host_bound_link()
    sim = SimulatedBackend(fidelity="full", link=link)

    def run(sched):
        c = Cluster.uniform(4, 100.0)
        return sim.execute(graph, c, sched.schedule(graph, c)).makespan

    m_pack = run(GroupPackScheduler(link=link))
    m_pipe = run(PipelineStageScheduler(link=link))
    m_rr = run(get_scheduler("roundrobin"))
    # round-robin splits every group's weights across devices (each device
    # re-loads most layer weights); pack loads each group once
    assert m_pack <= m_pipe * 1.05
    assert m_pack < m_rr * 0.75


def test_pack_registered_and_default_constructible():
    s = get_scheduler("pack")
    assert isinstance(s, GroupPackScheduler)


def test_pack_fails_oversized_group_gracefully():
    graph = flagship_shaped_graph(n_layers=2, n_shards=1, mb=1)
    # layer groups are 1.3 GB; caps below that: layer groups cannot place,
    # shard (0.9) can — dependents of failed tasks fail, roots complete
    cluster = Cluster.uniform(2, 1.0)
    s = GroupPackScheduler(link=host_bound_link()).schedule(graph, cluster)
    assert any(t.startswith("mb0_layer") for t in s.failed)
    assert "mb0_shard_0" in s.completed


def test_pack_minimizes_bottleneck_not_total():
    """Union-aware LPT optimizes the per-device MAX load (the host-link
    bottleneck), not total bytes: two groups sharing a big table spread
    across devices (5 GB + 5 GB) rather than co-locating (6 GB + 1 GB),
    because 5 < 6 even though 10 GB total > 7 GB total."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph

    GB = 1024**3
    tasks = [
        Task("a", 0.01, 1e-3, [], {"big", "a_own"},
             param_bytes={"big": 4 * GB, "a_own": GB}, group="ga"),
        Task("b", 0.01, 1e-3, ["a"], {"big", "b_own"},
             param_bytes={"big": 4 * GB, "b_own": GB}, group="gb"),
    ]
    graph = TaskGraph(tasks, name="tied").freeze()
    cluster = Cluster.uniform(2, 100.0)
    s = GroupPackScheduler(link=host_bound_link()).schedule(graph, cluster)
    assert s.placement["a"] != s.placement["b"]
    loads = per_device_load(graph, s)
    assert max(loads.values()) == pytest.approx(5.0)


def test_pack_spills_oversized_group_per_task():
    """Graceful degradation: a group whose param
    union exceeds every device budget no longer zeroes out — its tasks
    spill to singleton placement (min new-param-bytes device that fits),
    so pack degrades toward greedy instead of failing the whole group."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph

    GB = 1024**3
    # one group of 4 tasks, each with its own 0.8 GB param: union 3.2 GB
    # fits on NO 1.0 GB device, but every task fits alone
    tasks = [
        Task(f"t{i}", 0.01, 1e-3, [f"t{i-1}"] if i else [],
             {f"w{i}"}, param_bytes={f"w{i}": int(0.8 * GB)}, group="g0")
        for i in range(4)
    ]
    graph = TaskGraph(tasks, name="spill").freeze()
    cluster = Cluster.uniform(4, 1.0)
    s = GroupPackScheduler(link=host_bound_link()).schedule(graph, cluster)
    assert not s.failed
    assert len({s.placement[f"t{i}"] for i in range(4)}) == 4


def test_refine_completes_under_pressure_cliff():
    """The flagship-winning policy must not zero out at the config-#5
    pressure cliff: refine completion >= roundrobin's on a graph whose
    group unions exceed the per-device budget (train-bench regime)."""
    from distributed_llm_scheduler_tpu.sched.refine import RefinedPackScheduler

    graph = flagship_shaped_graph(n_layers=6, n_shards=2, mb=2)
    total_gb = sum(
        graph.param_size_gb(p)
        for p in {p for t in graph.tasks() for p in t.params_needed}
    )
    # per-device budget ~0.55x of an even split: whole layer groups can't
    # always co-locate, so completion requires the spill path
    cluster = Cluster.uniform(4, max(total_gb / 4 * 0.55, 1.0))
    ref = RefinedPackScheduler(link=host_bound_link()).schedule(graph, cluster)
    rr = get_scheduler("roundrobin").schedule(graph, cluster)
    assert len(ref.completed) >= len(rr.completed)
    assert len(ref.completed) > 0

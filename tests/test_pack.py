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


def _two_chains(extra_dep=None):
    """Two three-task chains on n0, a lone value ``r`` on n1."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph

    deps = {"a0": [], "a1": ["a0"], "a2": ["a1"],
            "b0": [], "b1": ["b0"], "b2": ["b1"], "r": []}
    if extra_dep:
        deps[extra_dep[0]] = deps[extra_dep[0]] + [extra_dep[1]]
    graph = TaskGraph(
        [Task(t, 0.01, 1e-3, d, set()) for t, d in deps.items()],
        name="two_chains",
    ).freeze()
    placement = {t: "n1" if t == "r" else "n0" for t in deps}
    return graph, placement


@pytest.mark.parametrize("extra_dep,want", [
    # lockstep becomes chain after chain
    (None, ["r", "a0", "a1", "a2", "b0", "b1", "b2"]),
    # a1 needs r from the other node and nothing on n0 has read r yet:
    # the chain stops there rather than wait where the order did not
    (("a1", "r"), ["r", "a0", "b0", "b1", "b2", "a1", "a2"]),
    # b0 read r first, so n0 holds it when a1 comes up
    (("b0", "r"), ["r", "a0", "a1", "a2", "b0", "b1", "b2"]),
], ids=["lockstep", "stops_at_unheld_remote", "held_remote"])
def test_run_chains_through(extra_dep, want):
    from distributed_llm_scheduler_tpu.sched.pack import run_chains_through

    graph, placement = _two_chains(extra_dep)
    order = ["r", "a0", "b0", "a1", "b1", "a2", "b2"]
    assert run_chains_through(graph, placement, order) == want


def test_pack_runs_a_microbatch_through_a_layer_before_the_next():
    """Placement is what LPT gave and the order is topological; on every
    device a microbatch's pass through a layer (three tasks in a row) is
    run to its end before the next microbatch's (the event simulation
    alone alternates the microbatches a task at a time)."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph
    from distributed_llm_scheduler_tpu.sched import pack

    GB = 1024**3
    tasks = []
    for m in range(3):
        prev = []
        for i in range(4):
            for part in "abc":
                tid = f"mb{m}_layer_{i}_{part}"
                tasks.append(Task(
                    tid, 0.01, 1e-3, prev, {f"L{i}"},
                    param_bytes={f"L{i}": GB}, group=f"layer_{i}",
                ))
                prev = [tid]
    graph = TaskGraph(tasks, name="layers_of_three").freeze()

    def lockstep_pairs(through):
        old, pack.run_chains_through = pack.run_chains_through, through
        try:
            s = GroupPackScheduler(link=host_bound_link()).schedule(
                graph, Cluster.uniform(2, 100.0))
        finally:
            pack.run_chains_through = old
        assert not s.failed
        seen = set()
        for tid in s.assignment_order:
            assert all(d in seen for d in graph[tid].dependencies), tid
            seen.add(tid)
        return s.placement, sum(
            a[:-1] != b[:-1]
            for tids in s.per_node.values()
            for a, b in zip(tids, tids[1:]) if a[-1] != "c"
        )

    placement, broken = lockstep_pairs(pack.run_chains_through)
    assert broken == 0
    plain_placement, plain_broken = lockstep_pairs(lambda g, p, order: order)
    assert plain_placement == placement and plain_broken > 0

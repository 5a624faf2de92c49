"""GroupPackScheduler: non-contiguous balanced group packing.

Generic policy contracts (completion, validation, native parity) come from
the parametrized suites; these tests pin pack's specific claims: balanced
param loads, the bottleneck and not the total minimised where groups share
a table, its standing in the host-link-bound regime it was built for, and
equal groups handed back as consecutive runs with every device's bytes,
peak and fit as LPT left them.
"""

import functools

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
    nothing (at flagship scale bench.py's cost-model replay under an
    estimated link read 21.6 ms pack vs 23.3 ms pipeline/greedy: a replay,
    not a speed on a chip)."""
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


# -- interchangeable groups as consecutive runs (make_runs_contiguous) -----

@functools.lru_cache(maxsize=None)
def _benchmark_graph():
    """The DAG cells' own graph: GPT-2 medium, bf16, 32 x 512, 8
    microbatches (26 groups: ``embed``, 24 equal ``layer_i``, ``head``)."""
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    return build_gpt2_dag(
        GPT2Config.medium(dtype=jnp.bfloat16), batch=32, seq_len=512,
        microbatches=8,
    ).graph


def _plans(graph, cluster, monkeypatch):
    """``(plain LPT's plan, pack's plan, groups pack says it moved)``."""
    from distributed_llm_scheduler_tpu.obs import process_metrics
    from distributed_llm_scheduler_tpu.sched import pack

    new = GroupPackScheduler().plan(graph, cluster.devices)
    moved = process_metrics().snapshot()["gauges"][
        "sched.pack.groups_made_contiguous"]["value"]
    with monkeypatch.context() as m:
        m.setattr(pack, "make_runs_contiguous", lambda *a: 0)
        lpt = GroupPackScheduler().plan(graph, cluster.devices)
    return lpt, new, moved


def _device_books(graph, plan, n_dev):
    """Per device: parameter-union bytes (the sizes summed in size order,
    so equal multisets give equal floats), activation peak, and how many
    groups of each (bytes, peak) footprint it holds."""
    from collections import Counter

    from distributed_llm_scheduler_tpu.sched.pipeline import _group_stats

    groups, _compute, activ, gparams = _group_stats(graph)
    books = []
    for d in range(n_dev):
        mine = [i for i, g in enumerate(groups) if plan.get(g) == d]
        union = set().union(*(gparams[i] for i in mine)) if mine else set()
        books.append((
            sum(sorted(graph.param_size_gb(p) for p in union)),
            max((activ[i] for i in mine), default=0.0),
            Counter(
                (sum(sorted(graph.param_size_gb(p) for p in gparams[i])),
                 activ[i]) for i in mine
            ),
        ))
    return books


def _cross_edges(graph, plan):
    """Group-to-group edges whose two groups sit on different devices."""
    edges = {
        (graph[d].group or d, t.group or t.task_id)
        for t in graph.tasks() for d in t.dependencies
    }
    return sum(
        a != b and a in plan and b in plan and plan[a] != plan[b]
        for a, b in edges
    )


def _layer_runs(plan, n_dev):
    """Per device the indices of its ``layer_i`` groups, ascending."""
    return [
        sorted(int(g[6:]) for g, d in plan.items()
               if d == dev and g.startswith("layer_"))
        for dev in range(n_dev)
    ]


@pytest.mark.parametrize("n_dev,lpt_edges,edges", [
    (4, 25, 3), (2, 25, 1), (8, 25, 7),
])
def test_equal_layers_come_back_as_consecutive_runs(
        n_dev, lpt_edges, edges, monkeypatch):
    """On the benchmark's own graph LPT deals the 24 equal layer groups
    out in turn; pack hands every device the same NUMBER of them as one
    consecutive run.  Every device's parameter-union bytes, activation
    peak and count of each footprint equal plain LPT's exactly, and a
    microbatch crosses chips where a run ends and nowhere else."""
    from distributed_llm_scheduler_tpu.obs import process_metrics

    graph = _benchmark_graph()
    cluster = Cluster.uniform(n_dev, 15.75)
    lpt, new, moved = _plans(graph, cluster, monkeypatch)
    assert list(new) == list(lpt)  # LPT's placement order (refine's seed)
    assert _device_books(graph, new, n_dev) == _device_books(
        graph, lpt, n_dev)
    assert moved == sum(new[g] != lpt[g] for g in lpt) > 0
    for run in _layer_runs(new, n_dev):
        assert run == list(range(run[0], run[0] + len(run))) if run else True
    assert any(
        run != list(range(run[0], run[0] + len(run)))
        for run in _layer_runs(lpt, n_dev) if run
    )
    assert _cross_edges(graph, lpt) == lpt_edges
    assert _cross_edges(graph, new) == edges
    GroupPackScheduler().plan(graph, cluster.devices)
    assert process_metrics().snapshot()["gauges"][
        "sched.pack.cross_node_group_edges"]["value"] == edges
    if n_dev == 4:
        # the run after `embed` on its chip, the run before `head` on its
        assert new["layer_0"] == new["embed"] == 0
        assert new["layer_23"] == new["head"] == 1
        assert [len(r) for r in _layer_runs(new, 4)] == [4, 4, 8, 8]


def test_unequal_caps_are_honoured_as_lpt_honoured_them(monkeypatch):
    """Devices of 15.75 / 1.0 / 0.2 / 4.0 GB: the head (1.6 GB with its
    logits) fits two of them and the third holds seven layers at most.
    The runs change no device's bytes, peak or counts, every group still
    fits where it is planned, and nothing is spilled or failed that plain
    LPT placed."""
    from distributed_llm_scheduler_tpu import DeviceState
    from distributed_llm_scheduler_tpu.sched import pack

    graph = _benchmark_graph()

    def cluster():
        return Cluster([
            DeviceState(f"n{i}", cap, 1.0)
            for i, cap in enumerate((15.75, 1.0, 0.2, 4.0))
        ])

    lpt, new, moved = _plans(graph, cluster(), monkeypatch)
    assert moved > 0 and len(new) == len(lpt) == 26
    books = _device_books(graph, new, 4)
    assert books == _device_books(graph, lpt, 4)
    assert len({sum(b[2].values()) for b in books}) > 2  # unequal counts
    for (union_gb, peak, _), dev in zip(books, cluster().devices):
        assert union_gb + peak <= dev.total_memory + 1e-9
    assert _cross_edges(graph, new) < _cross_edges(graph, lpt)

    def placed_as_planned(plan, schedule):
        ids = [d.node_id for d in cluster().devices]
        return not schedule.failed and all(
            node == ids[plan[graph[tid].group]]
            for tid, node in schedule.placement.items()
        )

    assert placed_as_planned(
        new, GroupPackScheduler().schedule(graph, cluster()))
    with monkeypatch.context() as m:
        m.setattr(pack, "make_runs_contiguous", lambda *a: 0)
        assert placed_as_planned(
            lpt, GroupPackScheduler().schedule(graph, cluster()))


def _graph_without_a_class(kind):
    from distributed_llm_scheduler_tpu import Task, TaskGraph

    GB = 1024**3
    tasks = []
    for m in range(2):
        prev = []
        for i in range(8):
            if kind == "unequal":
                # every layer its own size: LPT can tell them all apart
                params = {f"L{i}": int((1.0 + i / 16) * GB)}
            else:
                # equal layers, each tied to one table: none owns its
                # parameters alone, so moving one moves the table's bytes
                params = {f"L{i}": GB, "table": GB // 4}
            tid = f"mb{m}_layer_{i}"
            tasks.append(Task(
                tid, 0.01, 1e-3, prev, set(params), param_bytes=params,
                group=f"layer_{i}",
            ))
            prev = [tid]
    return TaskGraph(tasks, name=f"no_class_{kind}").freeze()


@pytest.mark.parametrize("kind", ["unequal", "tied"])
def test_a_graph_without_a_class_gets_lpts_plan_back(kind, monkeypatch):
    graph = _graph_without_a_class(kind)
    lpt, new, moved = _plans(graph, Cluster.uniform(4, 100.0), monkeypatch)
    assert len(set(lpt.values())) == 4
    assert new == lpt and list(new) == list(lpt) and moved == 0


def test_groups_side_by_side_keep_lpts_labels(monkeypatch):
    """With ``vocab_shards=4`` the table's shards are a class too (three
    equal, parameters their own), but they read one another nowhere: runs
    would cross as many group edges as LPT's deal, so the shards stay where
    LPT put them, while the equal layers of the same graph still come back
    as runs with every device's books unchanged."""
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    graph = build_gpt2_dag(
        GPT2Config.medium(dtype=jnp.bfloat16), batch=8, seq_len=64,
        microbatches=2, vocab_shards=4,
    ).graph
    lpt, new, moved = _plans(graph, Cluster.uniform(4, 15.75), monkeypatch)
    shards = [g for g in lpt if g.startswith("vocab_shard_")]
    assert len(shards) == 4 and len({lpt[g] for g in shards}) > 1
    assert all(new[g] == lpt[g] for g in shards)
    assert moved == sum(new[g] != lpt[g] for g in lpt) > 0
    assert _device_books(graph, new, 4) == _device_books(graph, lpt, 4)
    for run in _layer_runs(new, 4):
        assert run == list(range(run[0], run[0] + len(run)))
    assert _cross_edges(graph, new) < _cross_edges(graph, lpt)

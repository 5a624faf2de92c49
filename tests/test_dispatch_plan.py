"""Dispatch-plan tests: determinism, donation safety, coalescing.

The planned fast path (backends/dispatch_plan.py) trades per-task
bookkeeping for a precomputed launch table; these tests pin the
properties that make that trade safe:

* the plan is a pure function of (graph, schedule, ext keys, flags) —
  two builds must be structurally identical;
* donation never deletes a buffer any later launch still reads;
* coalescing may only re-linearize: per-node schedule order and
  topological enqueue order survive, and task outputs stay bit-identical
  to the un-coalesced path (optimization_barrier guarantees this);
* fused launches are what ``execute()`` does by default: one launch a
  same-device span, whether its structure occurs once in the plan or in
  every layer; launches of one structure share ONE executable whatever
  their layer, a ``fn`` with host effects keeps the graph on per-task
  launches, and the transfer accounting is the per-task plan's;
* what ``execute()`` derives from its arguments alone is kept on the
  backend between calls (``PreparedCall``) and is never stale: a call
  whose graph, schedule, flags or weights changed runs what a fresh
  backend would run.
"""

import jax
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.backends.dispatch_plan import (
    GRAPH_INPUT,
    DispatchPlan,
    _cut_runs,
    _relinearize,
    donation_supported,
)
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


@pytest.fixture(scope="module")
def mesh_cluster():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    return Cluster.from_jax_devices(hbm_cap_gb=4.0)


@pytest.fixture(scope="module")
def setup(mesh_cluster):
    # microbatches/vocab_shards > 1 give the DAG real parallelism, so
    # relinearization has same-device runs to build
    dag = build_gpt2_dag(
        GPT2Config.tiny(), batch=2, seq_len=16,
        microbatches=2, vocab_shards=2,
    )
    params = dag.init_params()
    ids = dag.make_inputs()
    backend = DeviceBackend(mesh_cluster)
    schedule = get_scheduler("roundrobin").schedule(dag.graph, mesh_cluster)
    assert not schedule.failed
    dag.graph.freeze()
    return dag, params, ids, backend, schedule


def _build(setup, **kw):
    dag, params, _ids, backend, schedule = setup
    order = backend.dispatch_order(dag.graph, schedule)
    placed, _ = backend.place_params(dag.graph, schedule, params)
    return DispatchPlan.build(
        backend, dag.graph, schedule, order, placed, **kw
    )


@pytest.mark.parametrize("flags", [
    dict(),
    dict(donate=True),
    dict(coalesce=True),
    dict(coalesce=True, donate=True),
])
def test_plan_determinism_across_builds(setup, flags):
    """Two builds over identical inputs produce structurally identical
    plans — signature() carries every slot index, launch grouping, and
    donation decision."""
    p1 = _build(setup, **flags)
    p2 = _build(setup, **flags)
    assert p1.signature() == p2.signature()
    assert p1.n_launches == p2.n_launches


def _deps(graph, tid):
    return graph[tid].arg_tasks or graph[tid].dependencies


@pytest.mark.parametrize("coalesce", [False, True])
def test_donation_never_aliases_later_consumer(setup, coalesce):
    """A donated buffer is deleted by XLA; the plan must prove no later
    launch (or the fence, or the final output read) still needs it."""
    plan = _build(setup, donate=True, coalesce=coalesce)
    assert any(st.donate_argnums for st in plan.steps), (
        "donation produced no donating launches — test is vacuous"
    )
    protected = (
        {plan.final_slot}
        | {s for _n, s in plan.fence_slots}
        | {s for _k, s in plan.ext_slots}
        | {s for _n, _d, s in plan.input_slots}
    )
    for gi, st in enumerate(plan.steps):
        for s in st.donate_slots:
            assert s not in protected, (gi, s)
            # the donating launch itself reads the slot exactly once
            assert st.arg_slots.count(s) == 1, (gi, s)
            for gj in range(gi + 1, len(plan.steps)):
                assert s not in plan.steps[gj].arg_slots, (
                    f"slot {s} donated at launch {gi} but read again "
                    f"at launch {gj}"
                )


def _per_node_sequences(plan):
    seq = {}
    for st in plan.steps:
        seq.setdefault(st.node_id, []).extend(st.tids)
    return seq


def _spans(graph, schedule, backend):
    order = backend.dispatch_order(graph, schedule)
    return _cut_runs(
        graph, schedule.placement,
        _relinearize(graph, schedule, order, set()),
    )


def test_coalesce_preserves_per_node_order_and_topo(setup):
    """Coalescing only re-linearizes: each node executes its tasks in
    exactly the schedule's per-node order, and every task is enqueued
    after all of its upstreams."""
    dag, *_ = setup
    plain = _build(setup)
    coal = _build(setup, coalesce=True)
    assert _per_node_sequences(coal) == _per_node_sequences(plain)

    seen = set()
    for st in coal.steps:
        for tid in st.tids:
            for d in _deps(dag.graph, tid):
                assert d == GRAPH_INPUT or d in seen, (tid, d)
            seen.add(tid)


def test_coalesce_fewer_launches_on_packing_schedule(setup):
    """With a schedule that packs consecutive tasks per device, coalesced
    groups must actually form (the perf claim depends on it)."""
    dag, params, _ids, backend, _sched = setup
    schedule = get_scheduler("greedy").schedule(
        dag.graph, backend.cluster
    )
    assert not schedule.failed
    order = backend.dispatch_order(dag.graph, schedule)
    placed, _ = backend.place_params(dag.graph, schedule, params)
    plain = DispatchPlan.build(
        backend, dag.graph, schedule, order, placed
    )
    coal = DispatchPlan.build(
        backend, dag.graph, schedule, order, placed, coalesce=True
    )
    assert coal.n_launches < plain.n_launches
    assert _per_node_sequences(coal) == _per_node_sequences(plain)


def test_coalesced_outputs_bit_identical(setup):
    """optimization_barrier between coalesced members keeps every task's
    numerics bit-for-bit equal to separate launches."""
    dag, params, ids, backend, schedule = setup
    rp = backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True
    )
    rc = backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True, coalesce=True
    )
    assert rp.planned and rc.planned
    assert set(rp.task_outputs) == set(rc.task_outputs)
    for tid, out in rp.task_outputs.items():
        la = jax.tree_util.tree_leaves(out)
        lb = jax.tree_util.tree_leaves(rc.task_outputs[tid])
        assert len(la) == len(lb), tid
        for a, b in zip(la, lb):
            assert np.array_equal(np.asarray(a), np.asarray(b)), tid


def test_planned_transfer_accounting_matches_legacy(setup):
    """The plan counts transfer edges/bytes statically; the numbers must
    match the legacy loop's per-argument accounting exactly."""
    dag, params, ids, backend, schedule = setup
    rl = backend.execute(
        dag.graph, schedule, params, ids, planned=False
    )
    rp = backend.execute(dag.graph, schedule, params, ids)
    rc = backend.execute(
        dag.graph, schedule, params, ids, coalesce=True
    )
    assert rp.transfer_edges == rl.transfer_edges
    assert rc.transfer_edges == rl.transfer_edges
    assert rp.transfer_bytes == rl.transfer_bytes
    np.testing.assert_allclose(
        np.asarray(rl.output), np.asarray(rp.output), rtol=0, atol=0
    )


def test_summary_reports_dispatch_overhead(setup):
    dag, params, ids, backend, schedule = setup
    rep = backend.execute(dag.graph, schedule, params, ids, reps=2)
    assert rep.planned
    assert rep.dispatch_overhead_s > 0
    s = rep.summary()
    assert "dispatch_overhead_ms" in s
    assert s["planned"] is True
    phases = s["dispatch_phases_ms"]
    for k in ("loop_s", "stage_s", "launch_s"):
        assert k in phases, k
    # staging + launching partition the loop wall
    assert phases["launch_s"] <= phases["loop_s"] + 1e-9


def test_donate_requires_planned_path(setup):
    dag, params, ids, backend, schedule = setup
    with pytest.raises(ValueError):
        backend.execute(
            dag.graph, schedule, params, ids, planned=False, donate=True
        )
    with pytest.raises(ValueError):
        backend.execute(
            dag.graph, schedule, params, ids, donate=True,
            keep_outputs=True,
        )


def test_donation_frees_dying_intermediates(setup):
    """On platforms that honor donation, a planned+donated run completes
    and produces the same output as the undonated plan (donation changes
    buffer lifetimes, never values)."""
    if not donation_supported():
        pytest.skip("platform ignores donate_argnums")
    dag, params, ids, backend, schedule = setup
    rd = backend.execute(
        dag.graph, schedule, params, ids, donate=True
    )
    rn = backend.execute(
        dag.graph, schedule, params, ids, donate=False
    )
    np.testing.assert_allclose(
        np.asarray(rd.output), np.asarray(rn.output), rtol=0, atol=0
    )


# -- fused launches are the default ------------------------------------


def _deep(n_devices, policy, n_layer=4, microbatches=4):
    """A multi-layer, multi-microbatch tiny GPT-2 DAG placed by ``policy``
    over the first ``n_devices`` CPU devices, on a backend of its own."""
    import dataclasses

    dag = build_gpt2_dag(
        dataclasses.replace(GPT2Config.tiny(), n_layer=n_layer),
        batch=microbatches, seq_len=16, microbatches=microbatches,
    )
    cluster = Cluster.from_jax_devices(
        jax.devices()[:n_devices], hbm_cap_gb=4.0
    )
    schedule = get_scheduler(policy).schedule(dag.graph, cluster)
    assert not schedule.failed
    return dag, dag.init_params(), dag.make_inputs(), DeviceBackend(cluster), schedule


DEEP_CASES = [(1, "heft"), (4, "pack"), (4, "roundrobin"), (8, "greedy")]


@pytest.fixture(scope="module", params=DEEP_CASES,
                ids=[f"{p}-{n}dev" for n, p in DEEP_CASES])
def deep(request):
    return _deep(*request.param)


def test_default_execute_fuses_and_is_bit_identical_per_task(deep):
    """``execute()`` with default arguments launches fewer programs than
    tasks wherever the placement leaves same-device runs that repeat, and
    every task's output equals the legacy loop's bit for bit."""
    dag, params, ids, backend, schedule = deep
    ref = backend.execute(
        dag.graph, schedule, params, ids, planned=False, keep_outputs=True
    )
    rep = backend.execute(dag.graph, schedule, params, ids, keep_outputs=True)
    assert rep.planned
    n_tasks = len(dag.graph.topo_order)
    assert ref.n_dispatches == n_tasks
    if schedule.policy != "roundrobin":
        assert rep.n_dispatches < n_tasks
    assert set(rep.task_outputs) == set(ref.task_outputs)
    for tid, out in ref.task_outputs.items():
        assert np.array_equal(
            np.asarray(out), np.asarray(rep.task_outputs[tid])
        ), tid
    assert np.array_equal(np.asarray(ref.output), np.asarray(rep.output))


def test_default_plan_keeps_transfers_and_per_node_order(deep):
    """Fusing changes how many host calls a step makes and nothing else:
    transfer edges and bytes are the per-task plan's, every node runs its
    tasks in the schedule's order, every task is enqueued after its
    upstreams."""
    dag, params, ids, backend, schedule = deep
    plain = backend.execute(dag.graph, schedule, params, ids, coalesce=False)
    fused = backend.execute(dag.graph, schedule, params, ids)
    assert fused.transfer_edges == plain.transfer_edges
    assert fused.transfer_bytes == plain.transfer_bytes
    assert np.array_equal(np.asarray(plain.output), np.asarray(fused.output))

    order = backend.dispatch_order(dag.graph, schedule)
    placed, _ = backend.place_params(dag.graph, schedule, params)
    p_plain = DispatchPlan.build(backend, dag.graph, schedule, order, placed)
    p_fused = DispatchPlan.build(
        backend, dag.graph, schedule, order, placed, coalesce=True
    )
    assert [st.tids for st in p_fused.steps] == [
        st.tids for st in backend._prepared[dag.graph].plan.steps
    ]   # what the default call above ran
    assert p_fused.transfer_edges == p_plain.transfer_edges
    assert _per_node_sequences(p_fused) == _per_node_sequences(p_plain)
    assert _per_node_sequences(p_fused) == {
        n: list(ts) for n, ts in schedule.per_node.items() if ts
    }
    seen = set()
    for st in p_fused.steps:
        for tid in st.tids:
            assert all(d in seen for d in _deps(dag.graph, tid)), tid
            seen.add(tid)


@pytest.mark.parametrize("n_devices,policy", [(1, "heft"), (4, "pack")])
def test_launches_of_one_structure_share_one_executable(n_devices, policy):
    """The fused executable is keyed by what the program depends on —
    member fns, in-run wiring, exports, donation — not by task ids: a
    4-layer x 4-microbatch graph builds a handful, and a graph twice as
    deep builds no more (``jit_cache_misses`` flat in depth)."""
    from distributed_llm_scheduler_tpu.obs import process_metrics

    built = []
    for n_layer in (4, 8):
        dag, params, ids, backend, schedule = _deep(
            n_devices, policy, n_layer=n_layer
        )
        rep = backend.execute(dag.graph, schedule, params, ids)
        plan_steps = rep.n_dispatches
        structures = len(backend._group_cache)
        assert 1 <= structures <= 8
        assert plan_steps < len(dag.graph.topo_order)
        gauge = process_metrics().snapshot()["gauges"]
        assert gauge["compile.group_structures"]["value"] == structures
        misses = backend.jit_cache_misses
        backend.execute(dag.graph, schedule, params, ids, warmup=False)
        assert backend.jit_cache_misses == misses   # nothing built twice
        built.append((structures, misses))
    if n_devices == 1:
        # one chip: the same spans repeat down the layers, so depth adds
        # launches and not programs
        assert built[0] == built[1]
    fused_jits = {
        id(st.fn) for st in _default_plan(
            dag, params, backend, schedule).steps if st.group
    }
    assert len(fused_jits) <= structures


def _default_plan(dag, params, backend, schedule):
    order = backend.dispatch_order(dag.graph, schedule)
    placed, _ = backend.place_params(dag.graph, schedule, params)
    return DispatchPlan.build(
        backend, dag.graph, schedule, order, placed, coalesce=True,
        donate=donation_supported(),
    )


# the third: eight equal layers over four chips, which ``pack`` hands out
# as runs of two — a microbatch's chain on a chip is two layers long
FUSED_CASES = [(1, "heft"), (4, "pack"), (4, "pack", 8)]


@pytest.fixture(scope="module", params=FUSED_CASES,
                ids=["-".join(map(str, c)) for c in FUSED_CASES])
def fused(request):
    """A graph and backend of this group's own, run once with default
    arguments: (dag, params, ids, backend, schedule, first report)."""
    case = _deep(*request.param)
    dag, params, ids, backend, schedule = case
    return (*case, backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True
    ))


def test_default_plan_fuses_every_span_repeated_or_not(fused):
    """The default plan is exactly one launch a span of ``_cut_runs``,
    whether the span's structure occurs once in the plan or in every
    layer: a span met once is ONE step with all its members, never a
    launch a task because nobody shares its structure."""
    from collections import Counter

    dag, _params, _ids, backend, schedule, rep = fused
    plan = backend._prepared[dag.graph].plan
    spans = _spans(dag.graph, schedule, backend)
    assert [st.tids for st in plan.steps] == [tuple(sp) for sp in spans]
    assert rep.n_dispatches == plan.n_launches == len(spans)
    assert len(spans) < len(dag.graph.topo_order) // 4
    assert all(st.group is (len(st.tids) > 1) for st in plan.steps)
    calls = Counter(id(st.fn) for st in plan.steps if st.group)
    met_once = [st for st in plan.steps if calls.get(id(st.fn)) == 1]
    assert met_once, "every structure repeats: the case shows nothing"
    assert max(len(st.tids) for st in met_once) >= 8


def test_default_equals_per_task_plan_bit_for_bit(fused):
    """Against ``coalesce=False`` on a backend that never fused: every
    task's output and the logits bit for bit, the same transfer edges and
    bytes (the per-node order is the test's above, on the same cases)."""
    dag, params, ids, backend, schedule, rep = fused
    other = DeviceBackend(backend.cluster)
    ref = other.execute(
        dag.graph, schedule, params, ids, coalesce=False, keep_outputs=True
    )
    assert ref.n_dispatches == len(dag.graph.topo_order) > rep.n_dispatches
    assert not other._group_cache
    assert set(rep.task_outputs) == set(ref.task_outputs)
    for tid, out in ref.task_outputs.items():
        assert np.array_equal(
            np.asarray(out), np.asarray(rep.task_outputs[tid])
        ), tid
    assert np.array_equal(_logits(ref), _logits(rep))
    assert rep.transfer_edges == ref.transfer_edges
    assert rep.transfer_bytes == ref.transfer_bytes


def test_a_second_call_builds_no_group_executable(fused):
    """A span met once is compiled once a process, not once a call: the
    next ``execute()`` on the backend hits the kept plan and the gauge of
    fused executables stands still."""
    from distributed_llm_scheduler_tpu.obs import process_metrics

    def structures():
        return process_metrics().snapshot()["gauges"][
            "compile.group_structures"]["value"]

    dag, params, ids, backend, schedule, first = fused
    built, gauge = len(backend._group_cache), structures()
    misses, c0 = backend.jit_cache_misses, _prepared_counts()
    again = backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True, warmup=False
    )
    assert _delta(c0) == (1, 0, 0)
    assert structures() == gauge and len(backend._group_cache) == built
    assert backend.jit_cache_misses == misses
    assert again.n_dispatches == first.n_dispatches
    assert np.array_equal(_logits(again), _logits(first))


@pytest.mark.parametrize("n_devices,policy,launches,edges,programs", [
    (1, "heft", 49, 0, 4), (4, "pack", 64, 48, 3),
])
def test_launch_counts_at_the_benchmark_shape(
        n_devices, policy, launches, edges, programs):
    """The DAG cells' own step — GPT-2 medium, batch 32 x 512, 8
    microbatches, 1,561 tasks — planned and never run (no weights): the
    launches (one a span), transfer edges and fused programs PERF.md and
    the ledger's ``launches_step`` quote, under ``heft`` on one device and
    ``pack`` on four (a chip holds a run of 4 or 8 consecutive layers and
    runs a microbatch through them before it takes the next: a span is
    four layers of one microbatch, the same program on every chip),
    donating as the chip does."""
    import jax.numpy as jnp

    dag = build_gpt2_dag(
        GPT2Config.medium(dtype=jnp.bfloat16), batch=32, seq_len=512,
        microbatches=8,
    )
    assert len(dag.graph.topo_order) == 1561
    cluster = Cluster.from_jax_devices(
        jax.devices()[:n_devices], hbm_cap_gb=15.75
    )
    schedule = get_scheduler(policy).schedule(dag.graph, cluster)
    assert not schedule.failed
    backend = DeviceBackend(cluster)
    placed = {
        (glob, node): None
        for tid, node in schedule.placement.items()
        for glob in dag.graph[tid].params_needed
    }
    plan = DispatchPlan.build(
        backend, dag.graph, schedule,
        backend.dispatch_order(dag.graph, schedule), placed,
        coalesce=True, donate=True,
    )
    assert len(_spans(dag.graph, schedule, backend)) == launches
    assert (plan.n_launches, plan.transfer_edges) == (launches, edges)
    assert len(backend._group_cache) == programs
    assert sum(len(st.tids) for st in plan.steps) == 1561
    if n_devices == 4:
        # every span but the last (``output_concat``) is one microbatch's
        for st in plan.steps[:-1]:
            assert len({t.split("_")[0] for t in st.tids}) == 1, st.tids
        assert sum(len(st.xfer_slots) for st in plan.steps) == 24
        sizes = sorted(len(st.tids) for st in plan.steps)
        assert sizes == [1] * 8 + [2] * 7 + [3] + [32] * 48


def _chains_on_a_node(lengths, sink, own_head_fn=False):
    """``len(lengths)`` chains on one node, chain ``c`` a line of
    ``lengths[c]`` tasks whose head reads a value of another node; with
    ``sink`` one last task reads every chain's end, so the run never comes
    clean; ``own_head_fn`` gives every head a ``fn`` of its own."""
    from distributed_llm_scheduler_tpu import Task, TaskGraph

    def f(p, x):
        return x

    tasks = [Task("far", 1e-6, 1e-4, fn=f)]
    order, ends = [], []
    for c, n in enumerate(lengths):
        prev = "far"
        for i in range(n):
            tid = f"c{c}_{i}"
            fn = (lambda p, x: x) if own_head_fn and i == 0 else f
            tasks.append(Task(
                tid, 1e-6, 1e-4, dependencies=[prev], fn=fn,
                arg_tasks=[prev],
            ))
            order.append(tid)
            prev = tid
        ends.append(prev)
    if sink:
        tasks.append(Task(
            "sink", 1e-6, 1e-4, dependencies=ends, fn=f, arg_tasks=ends,
        ))
        order.append("sink")
    graph = TaskGraph(tasks, name="chains_on_a_node").freeze()
    placement = {t: "n0" for t in order}
    placement["far"] = "n1"
    return graph, placement, order


@pytest.mark.parametrize("lengths,sink,own_head_fn,want", [
    # a head where the span is the remainder a cap cut left: cut
    ((34, 34), True, False, [32, 2, 32, 3]),
    # a head under a quarter of the cap is no cut; at 10 members it is
    ((5, 5, 5, 5), True, False, [10, 11]),
    # a head once the span holds a quarter of the cap
    ((9, 9), True, False, [9, 10]),
    # no head ever: the cap alone
    ((70,), False, False, [32, 32, 6]),
    # a head with a fn of its own, run through: launched alone, and the
    # cap's spans after it are the ones every chain shares
    ((33, 33), True, True, [1, 32, 1, 32, 1]),
    # the same heads side by side (the next task is no reader): together
    ((1, 1, 1, 1, 1, 1, 1, 1), False, True, [8]),
], ids=["head_after_cap_remainder", "head_under_a_quarter", "head_at_a_quarter",
        "no_head_cap_only", "own_fn_head_alone", "own_fn_heads_side_by_side"])
def test_cut_runs_closes_a_span_where_a_chain_ends(
        lengths, sink, own_head_fn, want):
    graph, placement, order = _chains_on_a_node(lengths, sink, own_head_fn)
    spans = _cut_runs(graph, placement, order)
    assert [t for sp in spans for t in sp] == order
    assert [len(sp) for sp in spans] == want


def test_tasks_per_launch_lands_in_the_process_registry(deep):
    from distributed_llm_scheduler_tpu.obs import (
        process_metrics,
        reset_ambient,
    )

    dag, params, ids, backend, schedule = deep
    backend.execute(dag.graph, schedule, params, ids)
    reset_ambient()
    rep = backend.execute(dag.graph, schedule, params, ids, warmup=False)
    h = process_metrics().snapshot()["histograms"]["execute.tasks_per_launch"]
    assert h["count"] == 1
    assert h["max"] == pytest.approx(
        len(dag.graph.topo_order) / rep.n_dispatches
    )
    reset_ambient()


def _chain_graph(noisy):
    """Two independent chains of 24 repeated (scale, shift) stages over
    one shared pair of fns — long enough that the capped spans repeat;
    ``noisy`` puts an unordered host callback in one of the fns."""
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu import Task, TaskGraph

    heard = []

    def f_scale(p, x):
        if noisy:
            jax.debug.callback(lambda v: heard.append(float(v)), x.sum())
        return x * p["w"]

    def f_shift(p, x):
        return x + 1.0

    def f_root(p, x):
        return x.astype(jnp.float32)

    tasks, params = [], {}
    for c in range(2):
        prev = f"c{c}_root"
        tasks.append(Task(prev, 1e-6, 1e-4, fn=f_root))
        for i in range(24):
            w = f"w{i % 4}"
            params[w] = jnp.full((4,), 1.0 + (i % 4) / 8, jnp.float32)
            a, b = f"c{c}_s{i}_scale", f"c{c}_s{i}_shift"
            tasks.append(Task(
                a, 1e-6, 1e-4, dependencies=[prev], params_needed={w},
                param_bytes={w: 16}, fn=f_scale, arg_tasks=[prev],
                param_alias={"w": w},
            ))
            tasks.append(Task(
                b, 1e-6, 1e-4, dependencies=[a], fn=f_shift, arg_tasks=[a],
            ))
            prev = b
    return TaskGraph(tasks, name=f"chains_{noisy}").freeze(), params, heard


@pytest.mark.parametrize("noisy", [False, True])
def test_a_fn_with_host_effects_keeps_per_task_launches(noisy):
    """The one thing a fused launch cannot keep is the per-launch
    ordering of an unordered host callback: ``execute()`` reads each
    distinct fn's jaxpr effects once and leaves such a graph on one
    launch a task; the same graph without the callback fuses."""
    import jax.numpy as jnp

    graph, params, heard = _chain_graph(noisy)
    cluster = Cluster.from_jax_devices(jax.devices()[:1], hbm_cap_gb=4.0)
    backend = DeviceBackend(cluster)
    schedule = get_scheduler("heft").schedule(graph, cluster)
    assert not schedule.failed
    x = jnp.arange(4, dtype=jnp.int32)
    assert backend.host_effect_free(graph, params, x) is (not noisy)
    rep = backend.execute(graph, schedule, params, x, keep_outputs=True)
    ref = backend.execute(
        graph, schedule, params, x, planned=False, keep_outputs=True
    )
    n_tasks = len(graph.topo_order)
    if noisy:
        assert rep.n_dispatches == n_tasks
        jax.effects_barrier()
        assert heard  # and the callback did run
    else:
        assert rep.n_dispatches < n_tasks
    for tid, out in ref.task_outputs.items():
        assert np.array_equal(
            np.asarray(out), np.asarray(rep.task_outputs[tid])
        ), tid


@pytest.mark.parametrize("stage,order", [
    (0, "lockstep"), (0, "lagged"), (5, "lockstep"), (5, "lagged"),
])
def test_launch_structure_names_no_task_and_no_interleaving(stage, order):
    """The key an executable is cached under holds fn objects and
    positions only: the same two stages of two chains give one key
    whichever layer they sit in and however the policy interleaved the
    two chains; the plan's tids keep the span's own order."""
    from distributed_llm_scheduler_tpu.backends.dispatch_plan import (
        _program_order,
        launch_structure,
    )

    graph, _params, _heard = _chain_graph(False)

    def span_of(i, how):
        a = [f"c0_s{i}_scale", f"c0_s{i}_shift",
             f"c0_s{i + 1}_scale", f"c0_s{i + 1}_shift"]
        b = [t.replace("c0_", "c1_") for t in a]
        if how == "lockstep":
            return [t for pair in zip(a, b) for t in pair]
        return [a[0], a[1], b[0], a[2], b[1], a[3], b[2], b[3]]

    def key_of(span):
        members = _program_order(graph, span)
        exports = (members[3], members[7])   # each chain's last value
        return launch_structure(graph, members, exports)

    want = key_of(span_of(0, "lockstep"))
    got = key_of(span_of(stage, order))
    assert got.key == want.key
    # one outside read a chain
    assert len(got.ext_list) == len(want.ext_list) == 2
    fns, binds, export_pos = got.key
    assert len(fns) == 8 and export_pos == (3, 7)
    assert not any(isinstance(x, str) for row in binds for _k, x in row)


# -- donation-alias analysis (analysis/donation_pass) -------------------


def test_donation_table_passes_analysis(setup):
    """A builder-produced plan is donation-safe by construction; the
    independent DON00x pass must agree, and must catch a hand-mutated
    table that re-reads a donated slot."""
    from distributed_llm_scheduler_tpu.analysis import analyze_donation

    plan = _build(setup, donate=donation_supported())
    table = plan.donation_table()
    assert table["steps"] and table["final_slot"] is not None
    assert analyze_donation(plan).ok

    donated = [
        (gi, s)
        for gi, st in enumerate(table["steps"])
        for s in st["donate_slots"]
    ]
    if donated:  # mutate: a later launch re-reads a donated slot
        _gi, slot = donated[0]
        bad = dict(table)
        bad["steps"] = table["steps"] + (
            {
                "tids": ("late_reader",),
                "node_id": table["steps"][0]["node_id"],
                "arg_slots": (slot,), "xfer_slots": (),
                "donate_slots": (), "out_slots": (),
            },
        )
        assert analyze_donation(bad).has("DON001")


# -- execute() tiles its own wall time (always on) ----------------------

LEAVES = {
    "order_s", "place_s", "plan_s", "warmup_s", "rtt_s", "stage_s",
    "launch_s", "fence_s", "report_s", "other_s",
}


def test_leaf_phases_tile_the_call_and_land_once_in_the_process_registry(
        setup, monkeypatch):
    """With no tracer and no ``DLS_TRACE``: the leaf phases of one
    ``execute()`` sum to its wall by construction, that wall is the one
    a caller measures around the call (within 2%), and each phase is
    observed once per call in ``obs.process_metrics()``."""
    import time

    from distributed_llm_scheduler_tpu.obs import (
        process_metrics,
        reset_ambient,
    )

    monkeypatch.delenv("DLS_TRACE", raising=False)
    dag, params, ids, backend, schedule = setup
    backend.execute(dag.graph, schedule, params, ids)   # compiled, imported
    reset_ambient()
    around = []
    for _ in range(5):
        a = time.perf_counter()
        rep = backend.execute(dag.graph, schedule, params, ids, warmup=False)
        b = time.perf_counter()
        leaves = rep.leaf_phases()
        assert set(leaves) == LEAVES
        assert all(v >= 0 for k, v in leaves.items() if k != "other_s")
        assert sum(leaves.values()) == pytest.approx(rep.wall_s, rel=1e-9)
        ph = rep.dispatch_phases
        assert ph["stage_s"] + ph["launch_s"] == pytest.approx(ph["loop_s"])
        assert ph["warmup_s"] == 0.0 and ph["plan_s"] >= 0
        assert rep.attribution is None
        around.append(abs((b - a) - rep.wall_s) / (b - a))
    # the best of five: a preempted host thread is not the program's
    assert min(around) < 0.02
    hists = process_metrics().snapshot()["histograms"]
    assert set(hists) == (
        {f"execute.phase.{k}" for k in LEAVES | {"loop_s"}}
        | {"execute.wall_s", "execute.tasks_per_launch",
           "execute.native_layout_exports", "execute.attn_row_form_tasks"}
    )
    assert all(h["count"] == 5 for h in hists.values())
    assert hists["execute.native_layout_exports"]["max"] == 0   # the CPU
    assert hists["execute.attn_row_form_tasks"]["max"] == 0     # XLA here
    assert hists["execute.wall_s"]["max"] >= rep.wall_s
    reset_ambient()
    assert process_metrics().snapshot()["histograms"] == {}


@pytest.mark.parametrize("kw,split", [
    ({"coalesce": False}, True),
    ({"planned": False}, False),
    ({"profile": True}, False),
    ({"stream_params": True}, False),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_every_execution_path_tiles_its_call(setup, kw, split):
    """The per-task loop does not split its loop and reports ``loop_s``
    as the leaf; the plan splits it into staging and launches; every
    path reports the fence wait and sums to the wall."""
    dag, params, ids, backend, schedule = setup
    rep = backend.execute(dag.graph, schedule, params, ids, **kw)
    leaves = rep.leaf_phases()
    assert ("loop_s" in leaves) is not split
    assert ("launch_s" in leaves) is split
    assert leaves["fence_s"] > 0 and leaves["warmup_s"] > 0
    assert sum(leaves.values()) == pytest.approx(rep.wall_s, rel=1e-9)
    assert rep.summary()["wall_ms"] == pytest.approx(rep.wall_s * 1e3)


# -- the prepared call: kept between calls, never stale ------------------


def _prepared_counts():
    from distributed_llm_scheduler_tpu.obs import process_metrics

    counters = process_metrics().snapshot()["counters"]
    return tuple(
        int(counters.get(f"execute.prepared.{k}", {"value": 0})["value"])
        for k in ("hits", "structure_misses", "placement_misses")
    )


def _delta(before):
    return tuple(a - b for a, b in zip(_prepared_counts(), before))


@pytest.fixture()
def small():
    """A graph, weights, backend and schedule of the test's own (nothing
    kept by an earlier test), over four devices."""
    dag, params, ids, backend, schedule = _deep(
        4, "pack", n_layer=2, microbatches=2
    )
    return dag, params, ids, backend, schedule


def _logits(rep):
    return np.asarray(rep.output)


@pytest.mark.parametrize("n_devices,policy", [(1, "heft"), (4, "pack")])
def test_a_repeated_call_hits_and_is_bit_identical(n_devices, policy):
    """The second call with the same graph, schedule and weights builds
    nothing and puts nothing, and its logits are the first call's and a
    fresh backend's bit for bit."""
    dag, params, ids, backend, schedule = _deep(
        n_devices, policy, n_layer=2, microbatches=2
    )
    c0 = _prepared_counts()
    first = backend.execute(dag.graph, schedule, params, ids)
    assert _delta(c0) == (0, 1, 0)
    plan = backend._prepared[dag.graph].plan
    placed = dict(backend._prepared[dag.graph].placed)
    again = backend.execute(dag.graph, schedule, params, ids, warmup=False)
    third = backend.execute(dag.graph, schedule, params, ids, reps=2)
    assert _delta(c0) == (2, 1, 0)
    entry = backend._prepared[dag.graph]
    assert entry.plan is plan
    assert all(entry.placed[k] is v for k, v in placed.items())
    fresh = DeviceBackend(backend.cluster).execute(
        dag.graph, schedule, params, ids
    )
    for rep in (again, third, fresh):
        assert np.array_equal(_logits(first), _logits(rep))
    assert again.n_dispatches == first.n_dispatches
    assert again.transfer_bytes == first.transfer_bytes
    assert again.param_bytes_placed == first.param_bytes_placed


@pytest.mark.parametrize("changed", ["all", "one"])
def test_new_weights_under_the_same_names_are_placed_and_run(small, changed):
    """A training loop: other ``jax.Array``s under the same names give
    the new weights' logits, count a placement miss and no structure
    miss; only the names that changed are put again."""
    dag, params, ids, backend, schedule = small
    backend.execute(dag.graph, schedule, params, ids)
    entry = backend._prepared[dag.graph]
    plan, placed = entry.plan, dict(entry.placed)
    names = list(params) if changed == "all" else [sorted(params)[0]]
    new = dict(params)
    for name in names:
        new[name] = params[name] * 1.5 + 0.01
    c0 = _prepared_counts()
    rep = backend.execute(dag.graph, schedule, new, ids, warmup=False)
    assert _delta(c0) == (0, 0, 1)
    assert backend._prepared[dag.graph] is entry and entry.plan is plan
    for (name, node), was in placed.items():
        assert (entry.placed[(name, node)] is was) is (name not in names)
    want = DeviceBackend(backend.cluster).execute(
        dag.graph, schedule, new, ids
    )
    assert np.array_equal(_logits(rep), _logits(want))
    c0 = _prepared_counts()
    old = backend.execute(dag.graph, schedule, params, ids, warmup=False)
    assert not np.array_equal(_logits(rep), _logits(old))
    assert _delta(c0) == (0, 0, 1)
    # and now the call's weights are the kept ones again: a hit
    backend.execute(dag.graph, schedule, params, ids, warmup=False)
    assert _delta(c0) == (1, 0, 1)


@pytest.mark.parametrize("kind", ["numpy", "pytree", "deleted"])
def test_a_parameter_that_may_have_changed_is_put_again(small, kind):
    """A host array written in place between calls is honoured (it is
    put every call); a pytree parameter is followed leaf by leaf; a
    deleted array is not served from its replica."""
    dag, params, ids, backend, schedule = small
    name = "wte" if "wte" in params else sorted(params)[0]
    mine = dict(params)
    if kind == "numpy":
        mine[name] = np.array(params[name])
    elif kind == "deleted":
        mine[name] = params[name] + 0.0
    if kind == "pytree":
        from distributed_llm_scheduler_tpu.backends.dispatch_plan import (
            PreparedCall,
        )

        entry = PreparedCall.__new__(PreparedCall)
        entry.pairs_of = {"w": (("w", "n0"),)}
        pair = (params[name], params[name] + 1.0)
        entry.sources = {"w": pair}
        assert entry.stale_names({"w": pair}) == []
        assert entry.stale_names({"w": list(pair)}) == []
        assert entry.stale_names({"w": (pair[0], pair[1] + 0.0)}) == ["w"]
        assert entry.stale_names({"w": (pair[0], np.asarray(pair[1]))}) == ["w"]
        assert entry.stale_names({"w": pair[0]}) == ["w"]
        return
    first = backend.execute(dag.graph, schedule, mine, ids)
    c0 = _prepared_counts()
    if kind == "numpy":
        mine[name][...] = mine[name] * 2.0 + 0.5
        rep = backend.execute(dag.graph, schedule, mine, ids, warmup=False)
        assert _delta(c0) == (0, 0, 1)
        want = DeviceBackend(backend.cluster).execute(
            dag.graph, schedule, mine, ids
        )
        assert np.array_equal(_logits(rep), _logits(want))
        assert not np.array_equal(_logits(rep), _logits(first))
        # unwritten, it is still put: a host array is never trusted
        c0 = _prepared_counts()
        backend.execute(dag.graph, schedule, mine, ids, warmup=False)
        assert _delta(c0) == (0, 0, 1)
    else:
        mine[name].delete()
        with pytest.raises(RuntimeError, match="deleted"):
            backend.execute(dag.graph, schedule, mine, ids, warmup=False)
        # half placed, the entry is not kept: the next call builds anew
        assert dag.graph not in backend._prepared
        mine[name] = params[name] + 0.0
        rep = backend.execute(dag.graph, schedule, mine, ids, warmup=False)
        assert _delta(c0) == (0, 1, 0)
        assert np.array_equal(_logits(rep), _logits(first))


def _mutate_in_place(schedule, cluster, graph):
    # swap two independent neighbours of one node's list and of the
    # global order alike: still a valid schedule, another decision
    for tasks in schedule.per_node.values():
        for a, b in zip(tasks, tasks[1:]):
            if a not in graph[b].dependencies and (
                set(graph[a].dependencies) == set(graph[b].dependencies)
            ):
                i, j = tasks.index(a), tasks.index(b)
                tasks[i], tasks[j] = b, a
                o = schedule.assignment_order
                i, j = o.index(a), o.index(b)
                o[i], o[j] = o[j], o[i]
                return schedule
    raise AssertionError("no swappable pair")


STRUCTURE_CHANGES = {
    "schedule_mutated_in_place": lambda s, c, g: (
        _mutate_in_place(s, c, g), {}),
    "another_policy": lambda s, c, g: (
        get_scheduler("roundrobin").schedule(g, c), {}),
    "ext_keys": lambda s, c, g: (s, {"ext_outputs": {"outside": np.ones(3)}}),
    "keep_outputs": lambda s, c, g: (s, {"keep_outputs": True}),
    "donate": lambda s, c, g: (s, {"donate": not donation_supported()}),
    "coalesce": lambda s, c, g: (s, {"coalesce": False}),
    "input_shape": lambda s, c, g: (s, {}),
}


@pytest.mark.parametrize("change", sorted(STRUCTURE_CHANGES))
def test_a_changed_structure_misses_and_runs_what_a_fresh_backend_runs(
        small, change):
    """Whatever the plan is a function of — the schedule's decision (even
    when the same object was written in place), the flags, the ext keys,
    the input's shape — a change builds anew, and gives what a backend
    that never saw the earlier call gives."""
    dag, params, ids, backend, schedule = small
    backend.execute(dag.graph, schedule, params, ids)
    old_plan = backend._prepared[dag.graph].plan
    schedule2, kw = STRUCTURE_CHANGES[change](
        schedule, backend.cluster, dag.graph
    )
    if change == "input_shape":
        ids = jax.numpy.concatenate([ids, ids], axis=0)
    c0 = _prepared_counts()
    rep = backend.execute(dag.graph, schedule2, params, ids, **kw)
    assert _delta(c0) == (0, 1, 0)
    assert backend._prepared[dag.graph].plan is not old_plan
    want = DeviceBackend(backend.cluster).execute(
        dag.graph, schedule2, params, ids, **kw
    )
    assert np.array_equal(_logits(rep), _logits(want))
    assert rep.n_dispatches == want.n_dispatches
    assert rep.transfer_edges == want.transfer_edges
    assert rep.transfer_bytes == want.transfer_bytes
    # the same arguments again: kept
    c0 = _prepared_counts()
    again = backend.execute(
        dag.graph, schedule2, params, ids, warmup=False, **kw
    )
    assert _delta(c0) == (1, 0, 0)
    assert np.array_equal(_logits(again), _logits(want))
    assert again.transfer_bytes == want.transfer_bytes


@pytest.mark.parametrize("when", ["from_the_start", "after_a_pass"])
def test_a_schedule_that_fails_the_gate_raises_on_every_call(small, when):
    """The gate's verdict is kept for a pass only: a failing schedule
    raises on the first and on the second call, and a schedule written
    into failing after it passed raises too."""
    from distributed_llm_scheduler_tpu.analysis import AnalysisError

    dag, params, ids, backend, schedule = small
    if when == "after_a_pass":
        backend.execute(dag.graph, schedule, params, ids)
        assert backend._prepared[dag.graph].gate_passed
    node = next(n for n, ts in schedule.per_node.items() if ts)
    schedule.per_node["ghost"] = [schedule.per_node[node].pop()]
    c0 = _prepared_counts()
    for _ in range(2):
        with pytest.raises(AnalysisError, match="SCH001"):
            backend.execute(dag.graph, schedule, params, ids, warmup=False)
    assert _delta(c0) == (0, 0, 0)


def test_the_gate_runs_again_where_its_pass_was_not_its_own(small):
    """A pass on the caller's ``pre_report`` is not kept, and a backend
    whose gate was off runs it once it is on."""
    from distributed_llm_scheduler_tpu import analysis

    dag, params, ids, backend, schedule = small
    report = analysis.analyze(dag.graph, backend.cluster, schedule)
    backend.execute(dag.graph, schedule, params, ids, pre_report=report)
    assert not backend._prepared[dag.graph].gate_passed
    backend.execute(dag.graph, schedule, params, ids, warmup=False)
    assert backend._prepared[dag.graph].gate_passed
    backend.pre_analysis = False
    c0 = _prepared_counts()
    backend.execute(dag.graph, schedule, params, ids, warmup=False)
    assert _delta(c0) == (0, 1, 0)
    assert not backend._prepared[dag.graph].gate_passed


@pytest.mark.parametrize("kw", [
    {"memprof": True}, {"profile": True}, {"stream_params": True},
    {"planned": False},
], ids=lambda kw: next(iter(kw)))
def test_the_paths_that_bypass_the_prepared_call_leave_it_alone(small, kw):
    """``memprof`` (placement is its subject) and the paths that run no
    plan neither count nor touch the entry."""
    from distributed_llm_scheduler_tpu.obs.memprof import MemoryProfiler

    dag, params, ids, backend, schedule = small
    ref = backend.execute(dag.graph, schedule, params, ids)
    entry = backend._prepared[dag.graph]
    plan, placed = entry.plan, dict(entry.placed)
    if "memprof" in kw:
        kw = {"memprof": MemoryProfiler()}
    c0 = _prepared_counts()
    rep = backend.execute(dag.graph, schedule, params, ids, **kw)
    assert _delta(c0) == (0, 0, 0)
    assert backend._prepared[dag.graph] is entry and entry.plan is plan
    assert all(entry.placed[k] is v for k, v in placed.items())
    np.testing.assert_allclose(
        _logits(rep), _logits(ref), rtol=2e-5, atol=2e-5
    )
    if "memprof" in kw:
        # placement is recorded: the profiler saw every parameter put
        assert any(
            ev["label"].startswith("param:") for ev in kw["memprof"].events
        )


def test_a_dead_graph_releases_its_entry_and_its_replicas():
    import gc
    import weakref

    dag, params, ids, backend, schedule = _deep(
        4, "pack", n_layer=2, microbatches=2
    )
    backend.execute(dag.graph, schedule, params, ids)
    entry = backend._prepared[dag.graph]
    # a replica on a device the caller's array does not live on
    home = next(iter(params.values())).devices()
    replica = next(
        weakref.ref(v) for v in entry.placed.values()
        if v.devices() != home
    )
    plan = weakref.ref(entry.plan)
    assert len(backend._prepared) == 1
    del entry, dag
    gc.collect()
    assert len(backend._prepared) == 0
    assert plan() is None and replica() is None


@pytest.mark.parametrize("kw", [{}, {"coalesce": False}, {"reps": 2}],
                         ids=["fused", "per_task", "fused_two_reps"])
def test_no_kept_replica_is_deleted_by_a_donating_run(small, kw):
    if not donation_supported():
        pytest.skip("platform ignores donate_argnums")
    dag, params, ids, backend, schedule = small
    first = backend.execute(
        dag.graph, schedule, params, ids, donate=True, **kw
    )
    for _ in range(2):
        rep = backend.execute(
            dag.graph, schedule, params, ids, donate=True, warmup=False,
            **kw,
        )
        entry = backend._prepared[dag.graph]
        assert not any(v.is_deleted() for v in entry.placed.values())
        assert not any(v.is_deleted() for v in params.values())
        assert np.array_equal(_logits(rep), _logits(first))


def test_the_fence_round_trip_is_probed_once_a_backend(small, monkeypatch):
    from distributed_llm_scheduler_tpu.utils import costmodel

    dag, params, ids, backend, schedule = small
    calls = []
    real = costmodel._fence_rtt

    def counted(device, samples=5):
        calls.append(device)
        return real(device, samples)

    monkeypatch.setattr(costmodel, "_fence_rtt", counted)
    reps = [
        backend.execute(dag.graph, schedule, params, ids, **kw)
        for kw in ({}, {"warmup": False}, {"planned": False},
                   {"fence_rtt": 0.25})
    ]
    assert len(calls) == 1
    assert all(r.leaf_phases()["rtt_s"] >= 0 for r in reps)
    assert reps[3].leaf_phases()["rtt_s"] == 0.0


# -- the layout of what a launch exports -------------------------------------

NATIVE_CASES = [(1, "heft"), (4, "pack"), (4, "roundrobin")]
NATIVE_IDS = [f"{p}-{n}dev" for n, p in NATIVE_CASES]


def _aval(graph, tid):
    spec = graph[tid].out_shape
    return tuple(spec.shape), spec.dtype


@pytest.mark.parametrize("n_devices,policy", NATIVE_CASES, ids=NATIVE_IDS)
def test_only_a_value_that_stays_on_its_chip_may_keep_its_layout(
        n_devices, policy):
    """The exports a plan leaves to the compiler (``native_slots``) are
    those no launch on another chip reads, the step's output among them —
    less what some launch of the same program may not leave open
    (launches of one program ask alike) and a result that could take a
    dying argument's buffer (it is that buffer); a value that is put, a
    kept output and the graph input never are."""
    dag, params, _ids, backend, schedule = _deep(
        n_devices, policy, n_layer=2
    )
    graph, placement = dag.graph, schedule.placement
    plan = _default_plan(dag, params, backend, schedule)
    readers = {}
    for t in graph.topo_order:
        for d in _deps(graph, t):
            readers.setdefault(d, []).append(t)
    tid_of = {
        s: t for st in plan.steps for t, s in zip(st.out_tids, st.out_slots)
    }
    stays = {
        t for t in tid_of.values()
        if all(placement[r] == placement[t] for r in readers.get(t, ()))
    }
    final = graph.topo_order[-1]
    assert final in stays
    put = {t for st in plan.steps for t in st.xfer_src_tids}
    assert bool(put) is (n_devices > 1)
    assert not put & stays
    inputs = {s for _n, _d, s in plan.input_slots}
    # per program (launches that share one executable): the positions its
    # launches ask about, and those every one of them could
    asked_of, could = {}, {}
    for st in plan.steps:
        assert set(st.native_slots) <= set(st.out_slots) - inputs
        dying = {
            _aval(graph, tid_of[st.arg_slots[a - 1]])
            for a in st.donate_argnums
        }
        pos = {st.out_slots.index(s) for s in st.native_slots}
        ok = {
            i for i, s in enumerate(st.out_slots)
            if tid_of[s] in stays and _aval(graph, tid_of[s]) not in dying
        }
        assert asked_of.setdefault(id(st.fn), pos) == pos
        could[id(st.fn)] = could.get(id(st.fn), ok) & ok
    assert asked_of == could
    asked = {tid_of[s] for st in plan.steps for s in st.native_slots}
    assert final in asked and asked <= stays
    if n_devices == 1 and donation_supported():
        assert asked < stays   # the residual stream is donated on
    order = backend.dispatch_order(graph, schedule)
    placed, _ = backend.place_params(graph, schedule, params)
    kept = DispatchPlan.build(
        backend, graph, schedule, order, placed, coalesce=True,
        keep_outputs=True,
    )
    assert not any(st.native_slots for st in kept.steps)


def _native_observed():
    from distributed_llm_scheduler_tpu.obs import process_metrics

    return process_metrics().snapshot()["histograms"].get(
        "execute.native_layout_exports"
    )


@pytest.mark.parametrize("n_devices,policy", NATIVE_CASES[:2],
                         ids=NATIVE_IDS[:2])
def test_a_layout_the_compiler_picks_is_kept_and_its_readers_follow(
        n_devices, policy, monkeypatch):
    """With the real CPU compiler every export comes back in the default
    layout: the histogram observes 0 and the logits are
    ``execute_dag_locally``'s bit for bit.  With the compiler's answer
    stubbed to another layout, each producer is built with it, the
    readers compile against what they are handed — as many fused programs
    as before — and the histogram observes how many exports kept it."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_llm_scheduler_tpu.backends import dispatch_plan
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import (
        execute_dag_locally,
    )
    from distributed_llm_scheduler_tpu.obs import process_metrics, reset_ambient

    def structures():
        return process_metrics().snapshot()["gauges"][
            "compile.group_structures"]["value"]

    reset_ambient()
    dag, params, ids, backend, schedule = _deep(n_devices, policy, n_layer=2)
    rep = backend.execute(dag.graph, schedule, params, ids)
    want = np.asarray(execute_dag_locally(dag, params, ids))
    assert np.array_equal(np.asarray(rep.output), want)
    seen = _native_observed()
    assert (seen["count"], seen["max"]) == (1, 0)
    plan = backend._prepared[dag.graph].plan
    assert plan.native_layout_exports == 0
    asked = sum(len(st.native_slots) for st in plan.steps)
    programs = len({id(st.fn) for st in plan.steps})
    built, gauge = len(backend._group_cache), structures()

    # the head's chip answers (0, 2, 1) for every value it is asked about
    # (all of rank 3 in this graph); the others answer as they do
    head = backend.cluster[schedule.placement["output_concat"]].jax_device
    picked = Format(Layout((0, 2, 1)), SingleDeviceSharding(head))
    real = dispatch_plan.NativeLaunch.picked

    def answer(self, pd, args):
        formats, avals = real(self, pd, args)
        return jax.tree_util.tree_map(
            lambda fmt: picked if fmt.sharding == picked.sharding else fmt,
            formats,
        ), avals

    monkeypatch.setattr(dispatch_plan.NativeLaunch, "picked", answer)
    reset_ambient()
    other = DeviceBackend(backend.cluster)
    stubbed = other.execute(dag.graph, schedule, params, ids)
    again = other.execute(dag.graph, schedule, params, ids, warmup=False)
    plan2 = other._prepared[dag.graph].plan
    assert plan2.signature() == plan.signature()
    on_head = sum(
        len(st.native_slots) for st in plan2.steps if st.dev == head
    )
    assert 1 <= on_head <= asked
    assert plan2.native_layout_exports == on_head
    seen = _native_observed()
    assert (seen["count"], seen["min"], seen["max"]) == (2, on_head, on_head)
    assert stubbed.output.format.layout.major_to_minor == (0, 2, 1)
    assert np.array_equal(np.asarray(stubbed.output), want)
    assert np.array_equal(np.asarray(again.output), want)
    assert len({id(st.fn) for st in plan2.steps}) == programs
    assert (len(other._group_cache), structures()) == (built, gauge)
    # the programs that keep a layout were compiled in this process, and
    # the persistent cache is on again for everything after them
    assert jax.config.jax_enable_compilation_cache
    reset_ambient()


def test_an_executable_from_the_persistent_cache_forgets_its_result_layout(
        tmp_path):
    """Why a launch that keeps a layout is compiled in this process
    (``_compile_in_process``): with the pinned jaxlib an executable loaded
    from the persistent compilation cache hands out arrays that report the
    runtime's default layout whatever layout it wrote them in, and a
    reader compiled against what they report reads other values.  The day
    the first assertion after ``loaded`` fails the loader keeps layouts:
    ``_compile_in_process`` and the second, named program of
    ``NativeLaunch`` can go, and ``auto`` be the launch's executable."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_llm_scheduler_tpu.backends.dispatch_plan import (
        _compile_in_process,
    )

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    dev = jax.devices()[0]
    written = Format(Layout((0, 2, 1)), SingleDeviceSharding(dev))
    x = jax.device_put(
        jnp.arange(4 * 16 * 32, dtype=jnp.float32).reshape(4, 16, 32), dev
    )

    def producer():   # a new jit object each time: nothing kept in memory
        return jax.jit(lambda a: a * 2 + 1, out_shardings=written)

    reader = jax.jit(lambda a: a.sum(axis=1))
    try:
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0.0)
        jax.config.update(names[2], 0)
        compilation_cache.reset_cache()
        fresh = producer()(x)
        assert fresh.format.layout.major_to_minor == (0, 2, 1)
        assert any("jit__lambda" in f.name for f in tmp_path.iterdir())
        want = np.asarray(x).sum(axis=1) * 2 + 16
        assert np.array_equal(np.asarray(reader(fresh)), want)
        loaded = producer()(x)   # the same program, from the cache
        assert loaded.format.layout.major_to_minor == (0, 1, 2)   # the fault
        assert np.array_equal(np.asarray(loaded), np.asarray(fresh))
        assert not np.array_equal(np.asarray(reader(loaded)), want)
        # compiled in this process, with that entry in the cache, it holds
        own = _compile_in_process(producer(), x)(x)
        assert own.format.layout.major_to_minor == (0, 2, 1)
        assert np.array_equal(np.asarray(reader(own)), want)
        assert jax.config.jax_enable_compilation_cache
    finally:
        for n in names:
            jax.config.update(n, was[n])
        compilation_cache.reset_cache()

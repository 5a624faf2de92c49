"""Dispatch-plan tests: determinism, donation safety, coalescing.

The planned fast path (backends/dispatch_plan.py) trades per-task
bookkeeping for a precomputed launch table; these tests pin the
properties that make that trade safe:

* the plan is a pure function of (graph, schedule, ext keys, flags) —
  two builds must be structurally identical;
* donation never deletes a buffer any later launch still reads;
* coalescing may only re-linearize: per-node schedule order and
  topological enqueue order survive, and task outputs stay bit-identical
  to the un-coalesced path (optimization_barrier guarantees this).
"""

import jax
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.backends.dispatch_plan import (
    GRAPH_INPUT,
    DispatchPlan,
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
        assert ph["warmup_s"] == 0.0 and ph["plan_s"] > 0
        assert rep.attribution is None
        around.append(abs((b - a) - rep.wall_s) / (b - a))
    # the best of five: a preempted host thread is not the program's
    assert min(around) < 0.02
    hists = process_metrics().snapshot()["histograms"]
    assert set(hists) == (
        {f"execute.phase.{k}" for k in LEAVES | {"loop_s"}}
        | {"execute.wall_s"}
    )
    assert all(h["count"] == 5 for h in hists.values())
    assert hists["execute.wall_s"]["max"] >= rep.wall_s
    reset_ambient()
    assert process_metrics().snapshot()["histograms"] == {}


@pytest.mark.parametrize("kw,split", [
    ({"compiled": True}, True),
    ({"segments": True}, False),
    ({"profile": True}, False),
])
def test_every_execution_path_tiles_its_call(setup, kw, split):
    """The paths that do not split their loop report ``loop_s`` as the
    leaf; every path reports the fence wait and sums to the wall."""
    dag, params, ids, backend, schedule = setup
    rep = backend.execute(dag.graph, schedule, params, ids, **kw)
    leaves = rep.leaf_phases()
    assert ("loop_s" in leaves) is not split
    assert ("launch_s" in leaves) is split
    assert leaves["fence_s"] > 0 and leaves["warmup_s"] > 0
    assert sum(leaves.values()) == pytest.approx(rep.wall_s, rel=1e-9)
    assert rep.summary()["wall_ms"] == pytest.approx(rep.wall_s * 1e3)

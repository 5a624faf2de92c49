"""Elastic recovery: node death mid-run -> re-place surviving work."""

import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
from distributed_llm_scheduler_tpu.frontend.generators import generate_llm_dag
from distributed_llm_scheduler_tpu.sched.elastic import (
    remainder_graph,
    reschedule,
    surviving_work,
)


@pytest.fixture()
def run_state():
    """A half-executed run: schedule an LLM DAG on 4 nodes, call the first
    half of the assignment order 'completed', then kill node 2."""
    graph = generate_llm_dag(num_layers=6, num_heads=4, seed=3)
    graph.freeze()
    cluster = Cluster.uniform(4, 16.0)
    schedule = get_scheduler("pack").schedule(graph, cluster)
    assert not schedule.failed
    order = schedule.assignment_order
    completed = set(order[: len(order) // 2])
    dead = cluster.devices[2].node_id
    return graph, schedule, completed, dead, cluster.without(dead)


def test_surviving_work_partition(run_state):
    graph, schedule, completed, dead, _ = run_state
    must_run, available = surviving_work(graph, schedule, completed, {dead})
    all_ids = {t.task_id for t in graph.tasks()}
    assert must_run | available == all_ids
    assert not (must_run & available)
    # everything completed on the dead node re-runs; on survivors it doesn't
    placement = schedule.placement
    for t in completed:
        if placement[t] == dead:
            assert t in must_run
        else:
            assert t in available
    # incomplete tasks always re-run
    assert all(t in must_run for t in all_ids - completed)


def test_remainder_graph_prunes_satisfied_deps(run_state):
    graph, schedule, completed, dead, _ = run_state
    must_run, available = surviving_work(graph, schedule, completed, {dead})
    sub = remainder_graph(graph, must_run)
    assert {t.task_id for t in sub.tasks()} == must_run
    for t in sub.tasks():
        orig = graph[t.task_id]
        kept = set(t.dependencies)
        pruned = set(orig.dependencies) - kept
        assert kept <= must_run          # only unsatisfied deps remain
        assert pruned <= available       # pruned deps have live outputs
        assert t.params_needed == orig.params_needed  # params must reload


def test_reschedule_completes_on_survivors(run_state):
    graph, schedule, completed, dead, survivors = run_state
    new_s, sub, must_run, available = reschedule(
        graph, schedule, completed, {dead}, survivors,
        get_scheduler("pack"),
    )
    assert not new_s.failed
    assert set(new_s.placement) == must_run
    assert dead not in new_s.per_node
    # replay the returned remainder to confirm it actually executes
    rep = SimulatedBackend(fidelity="full").execute(sub, survivors, new_s)
    assert rep.completed_tasks == len(must_run)
    # recovered run's total coverage equals the full task set
    assert available | set(new_s.completed) == {
        t.task_id for t in graph.tasks()
    }


def test_reschedule_rejects_dead_node_in_cluster(run_state):
    graph, schedule, completed, dead, _ = run_state
    bad = Cluster.uniform(4, 16.0)  # node_2 still present
    with pytest.raises(ValueError, match="dead nodes"):
        reschedule(
            graph, schedule, completed, {bad.devices[2].node_id}, bad,
            get_scheduler("pack"),
        )


def test_no_failure_reschedules_only_incomplete(run_state):
    graph, schedule, completed, _, _ = run_state
    must_run, available = surviving_work(graph, schedule, completed, set())
    assert available == completed
    assert must_run == {t.task_id for t in graph.tasks()} - completed


def test_device_recovery_end_to_end():
    """The headline, via the PUBLIC flow: a first run retains outputs
    (keep_outputs=True), a node dies, reschedule() consumes the report's
    task_outputs, and re-execution with ext_outputs reproduces the fused
    forward exactly — no host-side recomputation anywhere."""
    import jax
    import numpy as np

    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16)
    graph = dag.graph
    params, ids = dag.init_params(), dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:4], hbm_cap_gb=8.0)
    schedule = get_scheduler("pack").schedule(graph, cluster)
    first = DeviceBackend(cluster).execute(
        graph, schedule, params, ids, keep_outputs=True
    )
    assert first.task_outputs  # retention is what makes recovery drivable
    # "mid-run" state: the first half of the assignment order finished
    order = schedule.assignment_order
    completed = set(order[: len(order) // 2])
    dead = cluster.devices[2].node_id
    # survivors keep their original node ids, jax bindings, and slice
    # topology (Cluster.without copies identity fields)
    survivors = cluster.without(dead)
    new_s, remainder, must_run, available = reschedule(
        graph, schedule, completed, {dead}, survivors,
        get_scheduler("pack"), have_outputs=first.task_outputs,
    )
    assert not new_s.failed
    # available is exactly what we can feed: completed, on survivors, and
    # actually retained
    ext = {tid: first.task_outputs[tid] for tid in available}
    rep = DeviceBackend(survivors).execute(
        remainder, new_s, params, ids, ext_outputs=ext,
    )
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )
    assert rep.n_dispatches <= len(must_run)


def test_recovery_cost_bounded(run_state):
    """Work re-done after the failure is bounded by what the dead node
    held: the remainder never exceeds incomplete + completed-on-dead."""
    graph, schedule, completed, dead, _ = run_state
    must_run, _ = surviving_work(graph, schedule, completed, {dead})
    on_dead = {t for t in completed if schedule.placement[t] == dead}
    incomplete = {t.task_id for t in graph.tasks()} - completed
    assert must_run == incomplete | on_dead

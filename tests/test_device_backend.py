"""Device backend tests on the CPU-faked 8-device mesh."""

import jax
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, Task, TaskGraph, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.core.schedule import Schedule
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


@pytest.fixture(scope="module")
def mesh_cluster():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    return Cluster.from_jax_devices(hbm_cap_gb=4.0)


@pytest.fixture(scope="module")
def tiny_setup():
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16)
    params = dag.init_params()
    ids = dag.make_inputs()
    return dag, params, ids


def test_cluster_binds_jax_devices(mesh_cluster):
    assert len(mesh_cluster) == 8
    for d in mesh_cluster:
        assert d.jax_device is not None


def test_backend_rejects_unbound_cluster():
    from distributed_llm_scheduler_tpu import DeviceState

    with pytest.raises(ValueError):
        DeviceBackend(Cluster([DeviceState("n0", 4.0)]))


@pytest.mark.parametrize("policy", ["roundrobin", "mru", "critical"])
def test_placed_execution_matches_oracle(mesh_cluster, tiny_setup, policy):
    """The headline capability: scheduled multi-device execution produces
    the same logits as the fused single-program forward."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler(policy).schedule(dag.graph, mesh_cluster)
    assert not schedule.failed
    backend = DeviceBackend(mesh_cluster)
    rep = backend.execute(dag.graph, schedule, params, ids)
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )
    assert rep.makespan_s > 0
    assert rep.n_devices == 8


def test_cross_device_transfers_counted(mesh_cluster, tiny_setup):
    """Round-robin spreads adjacent tasks across cores, so cross-device
    edges must be detected and counted."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler("roundrobin").schedule(dag.graph, mesh_cluster)
    placement = schedule.placement
    expected_edges = sum(
        1
        for t in dag.graph
        for d in t.dependencies
        if placement[d] != placement[t.task_id]
    )
    rep = DeviceBackend(mesh_cluster).execute(dag.graph, schedule, params, ids)
    assert rep.transfer_edges == expected_edges
    assert rep.transfer_bytes > 0


def test_param_replication_follows_placement(mesh_cluster, tiny_setup):
    """Weight tying: wte is needed by embedding and output_projection; if
    they land on different cores the param must be placed on both."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler("roundrobin").schedule(dag.graph, mesh_cluster)
    backend = DeviceBackend(mesh_cluster)
    placed, bytes_per_node = backend.place_params(dag.graph, schedule, params)
    placement = schedule.placement
    wte_nodes = {
        placement[t.task_id] for t in dag.graph if "wte" in t.params_needed
    }
    for node_id in wte_nodes:
        assert ("wte", node_id) in placed
    # placed bytes accounted on every node that got something
    assert sum(bytes_per_node.values()) >= sum(
        v.size * v.dtype.itemsize for k, v in params.items()
    )


def test_profile_mode_yields_per_task_timings(mesh_cluster, tiny_setup):
    dag, params, ids = tiny_setup
    schedule = get_scheduler("greedy").schedule(dag.graph, mesh_cluster)
    rep = DeviceBackend(mesh_cluster).execute(
        dag.graph, schedule, params, ids, profile=True
    )
    assert set(rep.timings) == set(dag.graph.task_ids())
    for t in rep.timings.values():
        assert t.finish >= t.start >= 0
    # profile timings land on the schedule for Gantt rendering
    assert schedule.timings


def test_jit_cache_reused_across_runs(mesh_cluster, tiny_setup):
    """Second execution of the same (schedule, backend) must not recompile:
    warm run should be much faster than the compile pass."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler("mru").schedule(dag.graph, mesh_cluster)
    backend = DeviceBackend(mesh_cluster)
    rep1 = backend.execute(dag.graph, schedule, params, ids, warmup=True)
    # min-of-3: a single warm run can catch an OS scheduling hiccup on a
    # loaded host (observed ~once per full-suite run at a 0.5 s bar)
    warm = min(
        backend.execute(
            dag.graph, schedule, params, ids, warmup=False
        ).makespan_s
        for _ in range(3)
    )
    assert warm < max(rep1.compile_s, 1.0)


def test_reps_amortized_makespan(mesh_cluster, tiny_setup):
    """reps>1 queues the placed run N times with ONE end fence; per-run
    makespan must agree with the single-shot measurement (loose band:
    both include host dispatch, which varies run-to-run) and the output
    must still match the oracle after repeated execution."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler("greedy").schedule(dag.graph, mesh_cluster)
    backend = DeviceBackend(mesh_cluster)
    backend.execute(dag.graph, schedule, params, ids, warmup=True)
    single = min(
        backend.execute(
            dag.graph, schedule, params, ids, warmup=False
        ).makespan_s
        for _ in range(3)
    )
    rep = backend.execute(
        dag.graph, schedule, params, ids, warmup=False, reps=4
    )
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )
    # amortized must be the same order as single-shot: generous bounds
    # because CPU-mesh host dispatch dominates and jitters under load
    assert rep.makespan_s < single * 3 + 0.5
    assert rep.makespan_s > single * 0.1
    # incompatible modes fail loudly
    with pytest.raises(ValueError):
        backend.execute(
            dag.graph, schedule, params, ids, reps=2, profile=True
        )
    with pytest.raises(ValueError):
        backend.execute(
            dag.graph, schedule, params, ids, reps=2, stream_params=True
        )


def test_reps_amortized_fused_launches(mesh_cluster, tiny_setup):
    """Fused launches with reps>1: same oracle, same launch count."""
    dag, params, ids = tiny_setup
    schedule = get_scheduler("greedy").schedule(dag.graph, mesh_cluster)
    backend = DeviceBackend(mesh_cluster)
    once = backend.execute(dag.graph, schedule, params, ids)
    rep = backend.execute(dag.graph, schedule, params, ids, reps=3)
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )
    assert rep.makespan_s > 0
    assert rep.n_dispatches == once.n_dispatches <= len(dag.graph)


def _microbatch_pipeline():
    """2-stage x 2-ops-per-stage x n_mb microbatch chain graph with real
    matmul fns — the shape where dispatch order matters: per-device FIFO
    streams serialize whatever order tasks were enqueued, so Kahn-wave
    order (all microbatches' op k before any op k+1) delays the downstream
    stage by a whole stage-total, while 1F1B order streams microbatches
    through."""
    import functools

    import jax.numpy as jnp

    n_mb, n_ops = 6, 4
    dim = 384

    @functools.partial(jax.jit, static_argnums=())
    def op(pd, x):
        w = pd["w"]
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x

    tasks = []
    for m in range(n_mb):
        for k in range(n_ops):
            deps = [f"mb{m}_op{k-1}"] if k else []
            tasks.append(
                Task(
                    f"mb{m}_op{k}",
                    0.01,
                    0.005,
                    deps,
                    {f"w{k}"},
                    param_bytes={f"w{k}": dim * dim * 4},
                    fn=op,
                    param_alias={"w": f"w{k}"},
                )
            )
    g = TaskGraph(tasks, name="mb_pipeline").freeze()
    key = jax.random.PRNGKey(0)
    params = {
        f"w{k}": jax.random.normal(key, (dim, dim), jnp.float32) * 0.1
        for k in range(n_ops)
    }
    x0 = jnp.ones((64, dim), jnp.float32)
    return g, params, x0, n_mb, n_ops


def _pipeline_schedules(g, n_mb, n_ops, node_ids):
    """(wave, f1b1) Schedule pair: identical placement (ops 0..n/2-1 on
    node 0, rest on node 1), different per-node orders."""
    half = n_ops // 2

    def mk(per_node_orders):
        s = Schedule(policy="manual")
        s.per_node = per_node_orders
        s.assignment_order = [
            t for lst in per_node_orders.values() for t in lst
        ]
        s.completed = set(s.assignment_order)
        return s

    wave = mk({
        node_ids[0]: [
            f"mb{m}_op{k}" for k in range(half) for m in range(n_mb)
        ],
        node_ids[1]: [
            f"mb{m}_op{k}" for k in range(half, n_ops) for m in range(n_mb)
        ],
    })
    f1b1 = mk({
        node_ids[0]: [
            f"mb{m}_op{k}" for m in range(n_mb) for k in range(half)
        ],
        node_ids[1]: [
            f"mb{m}_op{k}" for m in range(n_mb) for k in range(half, n_ops)
        ],
    })
    return wave, f1b1


def test_dispatch_order_honors_per_node_lists(mesh_cluster):
    """The emitted global order must preserve each node's scheduled list
    exactly (per-device FIFO semantics) and dispatch producers first."""
    g, _, _, n_mb, n_ops = _microbatch_pipeline()
    ids = [d.node_id for d in mesh_cluster][:2]
    _, f1b1 = _pipeline_schedules(g, n_mb, n_ops, ids)
    order = DeviceBackend.dispatch_order(g, f1b1)
    assert sorted(order) == sorted(g.task_ids())
    pos = {t: i for i, t in enumerate(order)}
    # per-node subsequences preserved verbatim
    for nid, lst in f1b1.per_node.items():
        assert [t for t in order if t in set(lst)] == lst
    # valid linearization: producers dispatched before consumers
    for t in g:
        for d in t.dependencies:
            assert pos[d] < pos[t.task_id]


def test_dispatch_order_inconsistent_orders_fall_back():
    """A cross-node ordering cycle (no real policy emits one) must not
    deadlock: the remainder falls back to topo order."""
    g = TaskGraph(
        [
            Task("c1", 0.1, 1.0, []),
            Task("q", 0.1, 1.0, ["c1"]),
            Task("c2", 0.1, 1.0, ["q"]),
        ],
        name="cycle",
    ).freeze()
    s = Schedule(policy="manual")
    # n0's head q waits on c1; n1's head c2 waits on q -> both stuck
    s.per_node = {"n0": ["q"], "n1": ["c2", "c1"]}
    s.assignment_order = ["q", "c2", "c1"]
    order = DeviceBackend.dispatch_order(g, s)
    assert sorted(order) == ["c1", "c2", "q"]
    pos = {t: i for i, t in enumerate(order)}
    assert pos["c1"] < pos["q"] < pos["c2"]


def test_schedule_order_materializes_in_real_execution(mesh_cluster):
    """The scheduled order must exist in *real* execution,
    not only in the replay.  Each task's fn records its actual device-side
    execution via a host callback; for both a Kahn-wave and a 1F1B schedule
    over the same placement, each device's recorded execution sequence must
    equal its scheduled per-node list — i.e. the backend's dispatch is
    order-sensitive and the 1F1B interleaving physically happens."""
    import jax.numpy as jnp

    n_mb, n_ops = 4, 4
    record = []

    def make_fn(tag):
        def cb():
            record.append(tag)

        def fn(pd, x):
            jax.debug.callback(cb, ordered=False)
            return jnp.tanh(x @ pd["w"])

        return fn

    dim = 16
    tasks = [
        Task(
            f"mb{m}_op{k}",
            0.001,
            0.001,
            [f"mb{m}_op{k-1}"] if k else [],
            {f"w{k}"},
            param_bytes={f"w{k}": dim * dim * 4},
            fn=make_fn(f"mb{m}_op{k}"),
            param_alias={"w": f"w{k}"},
        )
        for m in range(n_mb)
        for k in range(n_ops)
    ]
    g = TaskGraph(tasks, name="mb_pipeline_cb").freeze()
    params = {
        f"w{k}": jax.random.normal(jax.random.PRNGKey(k), (dim, dim)) * 0.1
        for k in range(n_ops)
    }
    x0 = jnp.ones((4, dim), jnp.float32)

    ids = [d.node_id for d in mesh_cluster][:2]
    sub = Cluster([d for d in mesh_cluster if d.node_id in ids])
    backend = DeviceBackend(sub)
    for sched in _pipeline_schedules(g, n_mb, n_ops, ids):
        backend.execute(g, sched, params, x0)  # warm: compiles, runs once
        jax.effects_barrier()  # fence warm-run callbacks before clearing
        record.clear()
        backend.execute(g, sched, params, x0, warmup=False)
        jax.effects_barrier()  # fence measured-run callbacks
        executed = list(record)
        assert sorted(executed) == sorted(g.task_ids())
        for nid, lst in sched.per_node.items():
            members = set(lst)
            assert [t for t in executed if t in members] == lst, (
                f"device {nid} executed out of scheduled order"
            )


def test_f1b1_order_improves_measured_makespan(mesh_cluster, placed_replay):
    """Measured version of the order-sensitivity check: both orders run
    on the mesh, and with every task's own measured time for its cost
    (the ``placed_replay`` fixture: fenced per-task times of the placed
    run, replayed in the scheduled order) 1F1B order must beat wave
    order.  The callback test above proves the order materializes; this
    one that, at the measured task times, it is worth what the schedule
    says (14 against 19 task times for equal tasks).  The free-running
    wall time is not compared: on cores shared with other test workers
    the two virtual devices do not overlap reliably."""
    from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend

    g, params, x0, n_mb, n_ops = _microbatch_pipeline()
    ids = [d.node_id for d in mesh_cluster][:2]
    sub = Cluster([d for d in mesh_cluster if d.node_id in ids])
    wave, f1b1 = _pipeline_schedules(g, n_mb, n_ops, ids)
    sim = SimulatedBackend(fidelity="full")
    best = {
        name: makespan for name, (makespan, _) in placed_replay(
            g, params, x0, sub, {"wave": wave, "f1b1": f1b1}, sim).items()
    }
    # 14/19 = 0.74 for equal tasks; demand a conservative 10%
    assert best["f1b1"] < best["wave"] * 0.9, best


def test_schedule_only_graph_rejected(mesh_cluster):
    """Synthetic DAGs (no fns) must fail loudly, not mysteriously."""
    from distributed_llm_scheduler_tpu.frontend.generators import generate_llm_dag

    g = generate_llm_dag(num_layers=2)
    schedule = get_scheduler("roundrobin").schedule(g, mesh_cluster)
    with pytest.raises(ValueError, match="no fn"):
        DeviceBackend(mesh_cluster).execute(g, schedule, {}, None)

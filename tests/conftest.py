"""Test configuration: fake an 8-device CPU mesh before JAX initializes.

Mirrors how the reference tests "multi-node" behavior without a cluster
(in-process simulation, SURVEY.md §4): scheduler logic runs on plain Python
objects, and device-backend / sharding tests run against 8 virtual CPU
devices via ``--xla_force_host_platform_device_count`` so no TPU is needed.
"""

import os

# Must be set before jax initializes a backend.  The suite is written for
# the 8-device virtual CPU mesh, so it defaults JAX_PLATFORMS to cpu; the
# chip is exercised by chip_smoke.py, not by pytest.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, Task, TaskGraph


@pytest.fixture
def diamond_graph() -> TaskGraph:
    """The reference's canonical 4-task diamond fixture
    (reference schedulers.py:534-543): t1 -> {t2, t3} -> t4."""
    g = TaskGraph(
        [
            Task("t1", 1.0, 2.0, [], {"p1"}),
            Task("t2", 1.5, 3.0, ["t1"], {"p2"}),
            Task("t3", 0.8, 1.5, ["t1"], {"p1", "p3"}),
            Task("t4", 1.2, 2.5, ["t2", "t3"], {"p2", "p3"}),
        ],
        name="diamond",
    )
    return g.freeze()


@pytest.fixture
def two_nodes() -> Cluster:
    """The reference smoke-test cluster (schedulers.py:545-548)."""
    return Cluster(
        [DeviceState("node_0", 3.0, 1.0), DeviceState("node_1", 2.5, 1.2)]
    )


@pytest.fixture
def placed_replay():
    """``replay(graph, params, graph_input, cluster, schedules, sim) ->
    {name: (makespan_s, work_s)}``: placed runs' makespans as a CPU shared
    with other test workers can measure them.

    Every schedule really runs on the mesh (once free-running to warm,
    then ``profile=True``: every task fenced and timed where it was
    placed), and ``sim`` replays each schedule with its tasks' own
    measured times for their costs.  A task's time is its minimum over
    ``repeats`` rounds, and a round visits every schedule in turn, so a
    burst of load from another worker lands on all of them alike and is
    dropped by the minimum.  What load remains stretches the tasks about
    alike: it moves the scale of a makespan, not which of two placements
    is shorter, nor the makespan's ratio to the work (``work_s``, the sum
    of the task times).  The raw wall time of a free-running eight-device
    run on cores that six workers share says neither."""
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend

    def replay(graph, params, graph_input, cluster, schedules, sim,
               repeats=5):
        backend = DeviceBackend(cluster)
        for schedule in schedules.values():
            backend.execute(graph, schedule, params, graph_input)
        best = {name: {} for name in schedules}
        for _ in range(repeats):
            for name, schedule in schedules.items():
                rep = backend.execute(graph, schedule, params, graph_input,
                                      profile=True, warmup=False)
                for tid, t in rep.timings.items():
                    best[name][tid] = min(
                        best[name].get(tid, t.duration), t.duration)
        modeled = {t.task_id: t.compute_time for t in graph}
        out = {}
        try:
            for name, schedule in schedules.items():
                assert set(best[name]) == set(schedule.placement), name
                for tid, seconds in best[name].items():
                    graph[tid].compute_time = max(seconds, 1e-7)
                out[name] = (sim.execute(graph, cluster, schedule).makespan,
                             sum(best[name].values()))
        finally:
            for t in graph:
                t.compute_time = modeled[t.task_id]
        return out

    return replay


@pytest.fixture
def replayed_rank_check(placed_replay):
    """``check(graph, params, graph_input, policies, cluster) -> dict``:
    the rank check's question — does the placement the simulator predicts
    to win really win on the mesh — asked of :func:`placed_replay`
    makespans, not of free-running wall times.  Set-up as
    ``eval.rankcheck.run_rank_check`` does it (live link and cost
    calibration, link-aware policies, host-synchronous transfers on the
    CPU mesh); returns ``predicted`` and ``measured`` seconds a policy."""
    import os

    from distributed_llm_scheduler_tpu import get_scheduler
    from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
    from distributed_llm_scheduler_tpu.utils.costmodel import calibrate
    from distributed_llm_scheduler_tpu.utils.linkmodel import calibrate_link

    def check(graph, params, graph_input, policies, cluster):
        link = calibrate_link(
            [d.jax_device for d in cluster],
            sizes=(1 << 14, 1 << 18, 1 << 22), repeats=3,
        ).to_link_model()
        cm = calibrate(graph, params, graph_input, repeats=3)
        cm.apply(graph)
        sim = SimulatedBackend(
            fidelity="full", link=link, host_slots=os.cpu_count() or 1,
            dispatch_s=cm.dispatch_s, host_synchronous_transfers=True,
        )
        scheds = {}
        for policy in policies:
            scheds[policy] = get_scheduler(policy, link=link).schedule(
                graph, cluster)
            assert not scheds[policy].failed, policy
        measured = {
            policy: makespan for policy, (makespan, _) in placed_replay(
                graph, params, graph_input, cluster, scheds, sim).items()}
        # the model the predictions are made from: each task's least time
        # over a calibration before and one after the placed runs — a
        # burst of load from another worker that covers one calibration
        # would otherwise inflate every compute time against the link's
        # and turn a predicted separation into a tie
        again = calibrate(graph, params, graph_input, repeats=3)
        for tid, seconds in again.task_seconds.items():
            graph[tid].compute_time = max(
                min(seconds, cm.task_seconds[tid]), 1e-7)
        predicted = {
            policy: sim.execute(graph, cluster, sched).makespan
            for policy, sched in scheds.items()
        }
        return {"predicted": predicted, "measured": measured}

    return check


@pytest.fixture(scope="session")
def session_serve_engine():
    """ONE compiled bench-scenario serving engine for the whole session.

    Building a ``PagedDecodeEngine`` pays DAG construction, scheduling,
    and XLA compilation (~seconds); every engine the serve/soak tests
    need has the same SCENARIO geometry, so they share this instance and
    re-point it at their own clock/flight via
    ``PagedDecodeEngine.rebind_obs`` — warm executables, clean state."""
    from distributed_llm_scheduler_tpu.eval import serve_bench
    from distributed_llm_scheduler_tpu.serve.frontend import VirtualClock

    eng, _pool = serve_bench.build_serve_engine(clock=VirtualClock())
    return eng


@pytest.fixture(scope="session")
def session_slo_engine():
    """ONE compiled tiny-geometry engine for the SLO/flight-recorder
    tests (slots=2, page_size=8, n_pages=32, pages_per_seq=4,
    seg_steps=4 — deliberately different from the bench SCENARIO).

    Tests re-point it at their own clock/tracer/metrics/flight via
    ``PagedDecodeEngine.rebind_obs``, which also swaps in a pristine
    ``PagePool`` of the same geometry — so read page accounting off
    ``eng.pool`` *after* the rebind, not from a captured pool."""
    from distributed_llm_scheduler_tpu import get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2
    from distributed_llm_scheduler_tpu.models.kv_pages import PagePool

    cfg = gpt2.GPT2Config.tiny()
    slots, ps, n_pages, ppseq = 2, 8, 32, 4
    dag = build_paged_decode_dag(
        cfg, slots=slots, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq
    )
    params = dag.init_params()
    weights = {
        k: v for k, v in params.items()
        if not (k.startswith("cache_") or k == "page_table")
    }
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=ps)
    return backend.paged_decode_engine(
        dag.graph, sched, cfg, weights, pool,
        slots=slots, pages_per_seq=ppseq, seg_steps=4,
    )


@pytest.fixture(scope="session")
def session_fleet_engines(session_serve_engine):
    """Three compiled SCENARIO-geometry engines for the fleet tests
    (replica ids ``n0``..``n2``): the shared session serve engine plus
    two more builds — the only extra XLA compilations the fleet tier
    costs the whole suite.  Tests re-register them through
    ``EngineRegistry``, whose factory ``rebind_obs``-es each onto a
    per-replica clock + replica-prefixed metrics (swapping in a
    pristine ``PagePool``), so every test starts clean on warm
    executables."""
    from distributed_llm_scheduler_tpu.eval import serve_bench
    from distributed_llm_scheduler_tpu.serve.frontend import VirtualClock

    engines = {"n0": session_serve_engine}
    for rid in ("n1", "n2"):
        eng, _pool = serve_bench.build_serve_engine(clock=VirtualClock())
        engines[rid] = eng
    return engines


@pytest.fixture(scope="session")
def fleet_engine_factory(session_fleet_engines):
    """``EngineRegistry(factory=...)``-shaped seam over the pooled
    fleet engines: rebinds obs per replica per test, no fresh XLA
    builds.  Replica ids beyond the pool raise KeyError — fleet tests
    stay within N<=3."""

    def factory(rid, *, clock=None, metrics=None):
        eng = session_fleet_engines[rid]
        eng.rebind_obs(clock=clock, metrics=metrics)
        return eng

    return factory


@pytest.fixture(scope="session")
def serve_engine_factory(session_serve_engine):
    """``run_soak(engine_factory=...)``-shaped seam over the session
    engine: rebinds obs per leg; a non-default attention impl changes
    the compiled graph itself, so that (rare) case builds fresh."""

    def factory(*, clock=None, flight=None, attention_impl=None):
        eng = session_serve_engine
        if (attention_impl is not None
                and attention_impl != eng.attention_impl):
            from distributed_llm_scheduler_tpu.eval import serve_bench

            fresh, _pool = serve_bench.build_serve_engine(
                clock=clock, flight=flight, attention_impl=attention_impl
            )
            return fresh
        eng.rebind_obs(clock=clock, flight=flight)
        return eng

    return factory

"""Observability layer tests: span tracer, metrics registry, Perfetto
exporter, schedule-trace extensions, decode TTFT/TPOT, and the
zero-overhead disabled path."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, Task, TaskGraph, get_scheduler
from distributed_llm_scheduler_tpu.obs import (
    ambient_metrics,
    ambient_tracer,
    attribute_run,
    attribute_trace,
    compute_drift,
    reset_ambient,
    trace_enabled,
)
from distributed_llm_scheduler_tpu.obs.export import (
    chrome_events,
    export_perfetto,
    trace_summary,
    validate_trace,
)
from distributed_llm_scheduler_tpu.obs.metrics import (
    _HIST_CAP,
    MetricsRegistry,
    diff_snapshots,
    validate_snapshot,
)
from distributed_llm_scheduler_tpu.obs.trace import HOST_TRACK, Tracer


class FakeClock:
    """Deterministic injectable clock: tests set ``.t`` between calls."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# Tracer


def test_span_nesting_and_ordering_with_fake_clock():
    clk = FakeClock(1.0)
    tr = Tracer(clock=clk)
    outer = tr.begin("outer", cat="schedule", policy="greedy")
    clk.t = 2.0
    inner = tr.begin("inner", track="core_0", cat="launch")
    clk.t = 3.0
    tr.end(inner)
    clk.t = 5.0
    tr.end(outer, makespan_s=4.0)

    assert len(tr) == 2
    # inner closes first, so it lands first in the event list
    first, second = tr.events
    assert (first["name"], first["t0"], first["t1"]) == ("inner", 2.0, 3.0)
    assert (second["name"], second["t0"], second["t1"]) == ("outer", 1.0, 5.0)
    assert second["args"]["policy"] == "greedy"
    assert second["args"]["makespan_s"] == 4.0
    # nesting invariant for Perfetto: parent strictly encloses child
    assert second["t0"] <= first["t0"] and first["t1"] <= second["t1"]
    assert tr.tracks() == [HOST_TRACK, "core_0"]


def test_tracer_span_contextmanager_and_complete():
    clk = FakeClock(10.0)
    tr = Tracer(clock=clk)
    with tr.span("work", track="core_1", cat="task", tid="t1"):
        clk.t = 12.0
    tr.complete("seg0", 20.0, 21.5, track="core_1", cat="launch", tasks=3)
    spans = {e["name"]: e for e in tr.events}
    assert spans["work"]["t0"] == 10.0 and spans["work"]["t1"] == 12.0
    assert spans["seg0"]["t0"] == 20.0 and spans["seg0"]["t1"] == 21.5
    assert spans["seg0"]["args"]["tasks"] == 3


def test_tracer_instant_counter_flow():
    clk = FakeClock(0.5)
    tr = Tracer(clock=clk)
    tr.instant("retire", track="decode", cat="decode", rid="r0")
    tr.counter("decode.queue_depth", 3)
    clk.t = 0.75
    tr.counter("decode.queue_depth", 2, t=0.6)
    tr.flow("transfer", "core_0", 0.5, "core_1", 0.7, bytes=128)

    kinds = [e["type"] for e in tr.events]
    assert kinds == ["instant", "counter", "counter", "flow"]
    inst, c1, c2, fl = tr.events
    assert inst["t"] == 0.5 and inst["args"]["rid"] == "r0"
    assert c1["value"] == 3 and c2["t"] == 0.6
    assert fl["src_track"] == "core_0" and fl["dst_track"] == "core_1"
    assert fl["args"]["bytes"] == 128
    assert tr.counter_names() == ["decode.queue_depth"]
    # flow-only tracks still surface via the exporter's tid map
    evs = chrome_events(tr)
    names = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert {"decode", "core_0", "core_1"} <= names


# ---------------------------------------------------------------------------
# Metrics


def test_metrics_snapshot_schema_and_values():
    reg = MetricsRegistry()
    reg.counter("dispatch.launches").inc(3)
    reg.counter("dispatch.launches").inc(2)
    reg.counter("transfer.bytes", unit="bytes").inc(1024)
    g = reg.gauge("decode.queue_depth")
    g.set(5)
    g.set(2)
    h = reg.histogram("decode.ttft_s", unit="s")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)

    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert snap["schema"] == "dls.metrics/1"
    assert snap["counters"]["dispatch.launches"]["value"] == 5
    assert snap["counters"]["transfer.bytes"]["unit"] == "bytes"
    # gauge keeps last value plus high-water mark
    qd = snap["gauges"]["decode.queue_depth"]
    assert qd["value"] == 2 and qd["max"] == 5
    ttft = snap["histograms"]["decode.ttft_s"]
    assert ttft["count"] == 4
    assert ttft["min"] == 0.1 and ttft["max"] == 0.4
    assert abs(ttft["mean"] - 0.25) < 1e-12
    assert ttft["p50"] in (0.2, 0.3)
    assert ttft["unit"] == "s"
    # snapshot is JSON-serializable as-is (artifact embedding contract)
    json.dumps(snap)


def test_metrics_get_or_create_is_stable():
    reg = MetricsRegistry()
    a = reg.counter("x", unit="bytes")
    b = reg.counter("x")
    assert a is b
    snap = reg.snapshot()
    assert snap["counters"]["x"]["unit"] == "bytes"


def test_validate_snapshot_rejects_malformed():
    assert validate_snapshot(None) != []
    assert validate_snapshot({"schema": "bogus/9"}) != []
    bad = {
        "schema": "dls.metrics/1",
        "counters": {"c": {}},  # missing value
        "gauges": {},
        "histograms": {"h": {"count": 1}},  # missing stats
    }
    errs = validate_snapshot(bad)
    assert errs and any("c" in e for e in errs)
    # p99 is contractual: a histogram row without it is malformed
    no_p99 = {
        "schema": "dls.metrics/1",
        "counters": {},
        "gauges": {},
        "histograms": {"h": {
            "count": 1, "sum": 1.0, "min": 1.0, "max": 1.0,
            "mean": 1.0, "p50": 1.0, "p95": 1.0, "unit": None,
        }},
    }
    assert any("p99" in e for e in validate_snapshot(no_p99))


def test_histogram_reservoir_keeps_sampling_past_cap():
    """The old keep-first reservoir froze percentiles after _HIST_CAP
    observations; Algorithm R must let a regime change that happens
    after the cap move the quantiles."""
    reg = MetricsRegistry()
    h = reg.histogram("decode.tpot_s")
    for _ in range(_HIST_CAP):
        h.observe(1.0)
    snap0 = reg.snapshot()["histograms"]["decode.tpot_s"]
    assert snap0["p50"] == 1.0 and snap0["p99"] == 1.0
    # regime change entirely past the cap: 20x the reservoir size
    for _ in range(20 * _HIST_CAP):
        h.observe(100.0)
    snap1 = reg.snapshot()["histograms"]["decode.tpot_s"]
    assert snap1["count"] == 21 * _HIST_CAP  # exact stats never sampled
    assert snap1["min"] == 1.0 and snap1["max"] == 100.0
    assert snap1["p50"] == 100.0  # keep-first would still say 1.0
    assert snap1["p99"] == 100.0
    assert len(h._samples) == _HIST_CAP  # bounded memory


def test_histogram_reservoir_is_deterministic_per_name():
    """Seeding from the metric name (no global random state) makes two
    registries fed the same stream agree bitwise."""
    rega, regb = MetricsRegistry(), MetricsRegistry()
    ha = rega.histogram("decode.ttft_s")
    hb = regb.histogram("decode.ttft_s")
    for i in range(3 * _HIST_CAP):
        v = float(i % 97)
        ha.observe(v)
        hb.observe(v)
    assert ha._samples == hb._samples
    # a different name seeds a different reservoir
    hc = MetricsRegistry().histogram("decode.tpot_s")
    for i in range(3 * _HIST_CAP):
        hc.observe(float(i % 97))
    assert hc._samples != ha._samples


def test_metrics_prefix_namespaces_every_instrument():
    reg = MetricsRegistry(prefix="n0.", replica="n0")
    reg.counter("decode.tokens_delivered").inc(7)
    reg.gauge("pool.used_pages").set(3)
    reg.histogram("decode.ttft_s").observe(0.1)
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert snap["replica"] == "n0"
    assert set(snap["counters"]) == {"n0.decode.tokens_delivered"}
    assert set(snap["gauges"]) == {"n0.pool.used_pages"}
    assert set(snap["histograms"]) == {"n0.decode.ttft_s"}
    # get-or-create resolves the same instrument through the prefix
    assert reg.counter("decode.tokens_delivered").value == 7
    # an unlabeled registry's snapshot stays byte-identical to pre-fleet
    bare = MetricsRegistry().snapshot()
    assert "replica" not in bare
    # the label is contractual when present: non-empty string only
    assert validate_snapshot(dict(snap, replica="")) != []
    assert validate_snapshot(dict(snap, replica=3)) != []


def test_diff_snapshots_carries_replica_labels():
    a = MetricsRegistry(prefix="n0.", replica="n0")
    b = MetricsRegistry(prefix="n0.", replica="n1")
    a.counter("tok").inc(2)
    b.counter("tok").inc(5)
    d = diff_snapshots(a.snapshot(), b.snapshot())
    assert d["replica_a"] == "n0" and d["replica_b"] == "n1"
    assert d["counters"]["n0.tok"]["value_delta"] == 3
    # unlabeled diffs stay label-free
    bare = diff_snapshots(
        MetricsRegistry().snapshot(), MetricsRegistry().snapshot()
    )
    assert "replica_a" not in bare


def test_diff_snapshots_tracks_p99():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    a = reg.snapshot()
    for v in (50.0, 60.0, 70.0, 80.0):
        h.observe(v)
    b = reg.snapshot()
    d = diff_snapshots(a, b)
    row = d["histograms"]["lat"]
    assert row["p99_a"] == a["histograms"]["lat"]["p99"]
    assert row["p99_b"] == b["histograms"]["lat"]["p99"]
    assert row["p99_delta"] == row["p99_b"] - row["p99_a"]


# ---------------------------------------------------------------------------
# Exporter


def _sample_tracer() -> Tracer:
    clk = FakeClock(100.0)
    tr = Tracer(clock=clk)
    ev = tr.begin("execute", cat="schedule")
    tr.complete("task_a", 100.5, 101.0, track="core_0", cat="task")
    tr.complete("task_b", 101.2, 101.9, track="core_1", cat="task")
    tr.flow("transfer", "core_0", 101.0, "core_1", 101.2, bytes=64)
    tr.instant("fence_done", track=HOST_TRACK, cat="collect", t=102.0)
    tr.counter("decode.queue_depth", 1, t=100.2)
    tr.counter("decode.queue_depth", 0, t=101.8)
    clk.t = 102.5
    tr.end(ev)
    return tr


def test_chrome_events_structure_and_epoch():
    evs = chrome_events(_sample_tracer(), process_name="proc")
    proc = [e for e in evs if e["name"] == "process_name"]
    assert len(proc) == 1 and proc[0]["args"]["name"] == "proc"
    rows = [e for e in evs if e["name"] == "thread_name"]
    row_names = [e["args"]["name"] for e in rows]
    assert row_names[0] == HOST_TRACK  # host row is always tid 1
    assert set(row_names) == {HOST_TRACK, "core_0", "core_1"}

    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    # epoch normalizes to the earliest event: execute began at t=100.0
    assert xs["execute"]["ts"] == 0
    assert xs["task_a"]["ts"] == pytest.approx(0.5e6)
    assert xs["task_a"]["dur"] == pytest.approx(0.5e6)
    host_tid = rows[0]["tid"]
    assert xs["execute"]["tid"] == host_tid

    counters = [e for e in evs if e["ph"] == "C"]
    assert len(counters) == 2
    assert [c["args"]["value"] for c in counters] == [1, 0]

    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["id"] == ends[0]["id"]
    assert ends[0]["bp"] == "e"
    assert starts[0]["tid"] != ends[0]["tid"]

    insts = [e for e in evs if e["ph"] == "i"]
    assert insts and insts[0]["s"] == "t"


def test_export_perfetto_roundtrip_and_validate(tmp_path):
    path = str(tmp_path / "obs" / "trace.json")
    export_perfetto(_sample_tracer(), path)
    assert validate_trace(path) == []
    with open(path) as f:
        obj = json.load(f)
    assert obj["displayTimeUnit"] == "ms"
    summ = trace_summary(path)
    assert summ["spans"] == 3
    assert summ["flows"] == 1
    assert summ["counter_samples"] == 2
    assert summ["counter_tracks"] == ["decode.queue_depth"]
    assert HOST_TRACK in summ["rows"]


def test_validate_trace_flags_corruption():
    errs = validate_trace(
        {
            "traceEvents": [
                {"ph": "Z", "name": "bad", "pid": 1, "tid": 1},
                {"ph": "X", "name": "neg", "pid": 1, "tid": 1,
                 "ts": 1.0, "dur": -2.0},
                {"ph": "C", "name": "c", "pid": 1, "tid": 0,
                 "ts": 0.0, "args": {}},
                {"ph": "s", "name": "transfer", "pid": 1, "tid": 1,
                 "ts": 0.0, "id": 7},  # start without finish
            ]
        }
    )
    assert len(errs) >= 4


# ---------------------------------------------------------------------------
# Schedule exporter extensions (flows + fence), backward compatible


def _timed_schedule():
    from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
    from distributed_llm_scheduler_tpu.frontend.generators import (
        generate_llm_dag,
    )

    graph = generate_llm_dag(num_layers=3, num_heads=2, seed=1)
    cluster = Cluster.uniform(2, 16.0)
    schedule = get_scheduler("roundrobin").schedule(graph, cluster)
    SimulatedBackend().execute(graph, cluster, schedule)
    return graph, schedule


def test_schedule_trace_transfer_flows_and_fence(tmp_path):
    from distributed_llm_scheduler_tpu.utils.profiling import (
        export_chrome_trace,
    )

    graph, schedule = _timed_schedule()
    path = export_chrome_trace(
        schedule, str(tmp_path / "t.json"), graph=graph
    )
    assert validate_trace(path) == []
    with open(path) as f:
        events = json.load(f)["traceEvents"]

    placement = schedule.placement
    cross = sum(
        1
        for t in graph
        for d in t.dependencies
        if placement[d] != placement[t.task_id]
    )
    starts = [e for e in events if e["ph"] == "s"]
    ends = [e for e in events if e["ph"] == "f"]
    assert cross > 0 and len(starts) == cross and len(ends) == cross

    fences = [e for e in events if e["ph"] == "i" and e["name"] == "run_fence"]
    assert len(fences) == 1
    assert fences[0]["tid"] == 0  # no extra thread row for the fence
    threads = [e for e in events if e["name"] == "thread_name"]
    assert len(threads) == len({t.node_id for t in schedule.timings.values()})


def test_schedule_trace_without_graph_has_no_flows(tmp_path):
    from distributed_llm_scheduler_tpu.utils.profiling import (
        export_chrome_trace,
    )

    _, schedule = _timed_schedule()
    path = export_chrome_trace(schedule, str(tmp_path / "t.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert not [e for e in events if e["ph"] in ("s", "f")]
    assert [e for e in events if e["name"] == "run_fence"]


# ---------------------------------------------------------------------------
# Decode engine: TTFT / TPOT on a scripted clock


def test_decode_engine_ttft_tpot_scripted_clock(session_slo_engine):
    """Submit at t=10/12, admit (prefill) at t=20, retire at t=24 after 9
    tokens in total -> TTFT {10, 8} and TPOT (24-20)/8 = 0.5 exactly.

    Rides the session-scoped slo engine (same 2-slot geometry this test
    used to build from scratch): ``rebind_obs`` points the warm
    executables at this test's scripted clock/tracer/metrics."""
    eng = session_slo_engine
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    reg = MetricsRegistry()
    eng.rebind_obs(clock=clk, tracer=tr, metrics=reg)

    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    clk.t = 10.0
    eng.submit("r0", prompt, 9)
    clk.t = 12.0
    eng.submit("r1", prompt, 9)
    clk.t = 20.0
    eng.step_segment()  # admits both, runs first 4-step segment
    clk.t = 24.0
    eng.step_segment()  # final 4 steps -> both retire here

    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    ttft = snap["histograms"]["decode.ttft_s"]
    assert ttft["count"] == 2
    assert ttft["min"] == pytest.approx(8.0)   # r1: 20 - 12
    assert ttft["max"] == pytest.approx(10.0)  # r0: 20 - 10
    tpot = snap["histograms"]["decode.tpot_s"]
    assert tpot["count"] == 2
    assert tpot["min"] == pytest.approx(0.5)
    assert tpot["max"] == pytest.approx(0.5)
    assert snap["counters"]["decode.requests_completed"]["value"] == 2
    assert snap["gauges"]["decode.page_pool_occupancy_pages"]["max"] > 0

    # trace side: admission wave + segments + retire instants all landed
    names = [e["name"] for e in tr.events]
    assert "admission_wave" in names and "prefill" in names
    assert names.count("segment") == 2
    retires = [e for e in tr.events if e["name"] == "retire"]
    assert {e["args"]["rid"] for e in retires} == {"r0", "r1"}
    assert "decode.queue_depth" in tr.counter_names()
    assert "decode.page_pool_occupancy_pages" in tr.counter_names()
    # engine returned every page (leak gauge wired in run(); check the
    # pool AFTER the rebind — rebind_obs swaps in a pristine one)
    assert eng.pool.free_pages == eng.pool.n_pages - 1


# ---------------------------------------------------------------------------
# Ambient wiring + zero-overhead disabled path


def test_ambient_disabled_by_default(monkeypatch):
    monkeypatch.delenv("DLS_TRACE", raising=False)
    reset_ambient()
    try:
        assert not trace_enabled()
        assert ambient_tracer() is None
        assert ambient_metrics() is None
    finally:
        reset_ambient()


def test_ambient_enabled_is_process_wide_singleton(monkeypatch):
    monkeypatch.setenv("DLS_TRACE", "1")
    reset_ambient()
    try:
        assert trace_enabled()
        tr = ambient_tracer()
        assert tr is not None and ambient_tracer() is tr
        mg = ambient_metrics()
        assert mg is not None and ambient_metrics() is mg
        reset_ambient()
        assert ambient_tracer() is not tr
    finally:
        reset_ambient()


def test_execute_traced_output_matches_untraced(monkeypatch):
    """Explicit trace=/metrics= instrumentation must not perturb results,
    and the disabled path must not record anything ambient."""
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    monkeypatch.delenv("DLS_TRACE", raising=False)
    reset_ambient()
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    params = dag.init_params()
    ids = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:4])
    schedule = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    backend = DeviceBackend(cluster)

    plain = backend.execute(dag.graph, schedule, params, ids)

    tr = Tracer()
    reg = MetricsRegistry()
    traced = backend.execute(
        dag.graph, schedule, params, ids, trace=tr, metrics=reg
    )
    np.testing.assert_array_equal(
        np.asarray(plain.output), np.asarray(traced.output)
    )

    names = {e["name"] for e in tr.events}
    assert {"execute", "dispatch_order", "place_params"} <= names
    assert tr.tracks()[0] == HOST_TRACK and len(tr.tracks()) > 1

    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert snap["counters"]["dispatch.launches"]["value"] > 0
    assert snap["histograms"]["execute.makespan_s"]["count"] == 1
    # ambient stayed off: nothing leaked into the process-wide slot
    assert ambient_tracer() is None
    # exported trace from a real run is Perfetto-valid
    evs = chrome_events(tr)
    assert validate_trace({"traceEvents": evs}) == []

    # the traced run self-attributes; the untraced run has nothing to
    assert plain.attribution is None and "attribution" not in plain.summary()
    att = traced.attribution
    assert att is not None and att["critical_path"]
    assert sum(att["fractions"].values()) == pytest.approx(1.0, abs=1e-6)
    assert traced.summary()["attribution"] is att


def test_attribution_is_read_not_computed_by_execute(monkeypatch):
    """The critical-path walk costs several steps' time on a graph of
    1,500 launches, so ``execute`` only notes where its spans are; the
    walk runs once, when ``.attribution`` is first read."""
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.obs import attribution as attr_mod

    calls = []
    real = attr_mod.attribute_run

    def counting(tracer, window=None, **kw):
        calls.append(window)
        return real(tracer, window=window, **kw)

    monkeypatch.setattr(attr_mod, "attribute_run", counting)
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    params, ids = dag.init_params(), dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:2])
    schedule = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    backend = DeviceBackend(cluster)
    tr = Tracer()
    first = backend.execute(dag.graph, schedule, params, ids, trace=tr)
    second = backend.execute(
        dag.graph, schedule, params, ids, trace=tr, warmup=False
    )
    assert calls == []
    att = second.attribution
    assert len(calls) == 1
    assert second.attribution is att and second.summary()["attribution"] is att
    assert len(calls) == 1
    # each report attributes its own call's window of the shared tracer
    ex = [e for e in tr.events if e["name"] == "execute"]
    assert calls[0] == (ex[1]["t0"], ex[1]["t1"])
    assert first.attribution["makespan_s"] != att["makespan_s"]
    assert calls[1] == (ex[0]["t0"], ex[0]["t1"])
    assert sum(att["fractions"].values()) == pytest.approx(1.0, abs=1e-6)


def test_traced_execute_adds_a_constant_number_of_events_and_leaf_spans():
    """Twenty calls on one tracer: every call adds the same events, and
    its host-track leaves tile the ``execute`` span without overlap
    (``execute`` and ``rep0`` enclose, in a category of their own)."""
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.obs.trace import CAT_CALL

    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    params, ids = dag.init_params(), dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:2])
    schedule = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    backend = DeviceBackend(cluster)
    backend.execute(dag.graph, schedule, params, ids)
    tr = Tracer()
    sizes = []
    for _ in range(20):
        rep = backend.execute(
            dag.graph, schedule, params, ids, trace=tr, warmup=False
        )
        sizes.append(len(tr.events))
    added = {b - a for a, b in zip([0] + sizes, sizes)}
    assert len(added) == 1 and added.pop() > rep.n_dispatches

    last = tr.events[sizes[-2]:]
    host = [e for e in last if e["type"] == "span"
            and e["track"] == HOST_TRACK]
    enclosing = {e["name"] for e in host if e["cat"] == CAT_CALL}
    assert enclosing == {"execute", "rep0"}
    leaves = sorted((e for e in host if e["cat"] != CAT_CALL),
                    key=lambda e: e["t0"])
    assert [e["name"] for e in leaves] == [
        "dispatch_order", "place_params", "plan_build", "fence_rtt",
        "stage_input", "dispatch_loop", "fence", "report",
    ]
    assert all(a["t1"] <= b["t0"] for a, b in zip(leaves, leaves[1:]))
    ex = next(e for e in host if e["name"] == "execute")
    assert ex["t0"] <= leaves[0]["t0"] and leaves[-1]["t1"] <= ex["t1"]
    # the spans are the phase clock's own reads
    by = {e["name"]: e["t1"] - e["t0"] for e in leaves}
    ph = rep.dispatch_phases
    assert by["dispatch_order"] == pytest.approx(ph["order_s"], abs=1e-12)
    assert by["fence"] == pytest.approx(ph["fence_s"], abs=1e-12)
    assert by["stage_input"] + by["dispatch_loop"] <= ph["loop_s"]


# ---------------------------------------------------------------------------
# Attribution (run doctor)


def _doctor_tracer():
    """Scripted-clock scenario with a known critical path:

    host    : execute [0, 9]; dispatch_order [0, 0.2]; place_params [0.2, 0.8]
    core_0  : task_a [1, 3], task_b [3, 4.5]
    core_1  : task_c [5, 8]   <- flow from task_b@4.5 releases at 5.0

    Critical path task_a -> task_b -> task_c; makespan 8.0 tiles into
    compute 6.5 + transfer 0.5 + dispatch 0.8 + idle 0.2.
    """
    clk = FakeClock(0.0)
    tr = Tracer(clock=clk)
    ex = tr.begin("execute", cat="schedule", policy="manual")
    tr.complete("dispatch_order", 0.0, 0.2, cat="schedule")
    tr.complete("place_params", 0.2, 0.8, cat="stage")
    tr.complete("task_a", 1.0, 3.0, track="core_0", cat="task", tid="task_a")
    tr.complete("task_b", 3.0, 4.5, track="core_0", cat="task", tid="task_b")
    tr.complete("task_c", 5.0, 8.0, track="core_1", cat="task", tid="task_c")
    tr.flow("transfer", "core_0", 4.5, "core_1", 5.0,
            src="task_b", dst="task_c", bytes=64)
    clk.t = 9.0
    tr.end(ex)
    return tr


def test_attribution_golden_critical_path():
    att = attribute_run(_doctor_tracer())
    assert [s.name for s in att.critical_path] == ["task_a", "task_b", "task_c"]
    assert att.makespan_s == pytest.approx(8.0)
    b = att.breakdown_s
    assert b["compute"] == pytest.approx(6.5)
    assert b["transfer"] == pytest.approx(0.5)
    assert b["dispatch"] == pytest.approx(0.8)
    assert b["idle"] == pytest.approx(0.2)
    # exact tiling invariant: the four buckets sum to the makespan
    assert abs(sum(b.values()) - att.makespan_s) < 1e-9
    assert sum(att.fractions().values()) == pytest.approx(1.0, abs=1e-9)

    step_a, step_b, step_c = att.critical_path
    assert step_a.wait_kind == "wait" and step_a.wait_s == pytest.approx(1.0)
    assert step_b.wait_kind == "" and step_b.wait_s == 0.0
    assert step_c.wait_kind == "transfer"
    assert step_c.wait_s == pytest.approx(0.5)
    # summary is JSON-round-trippable
    assert json.loads(json.dumps(att.summary()))["makespan_s"] == 8.0


def test_attribution_stragglers_bubbles_per_device():
    att = attribute_run(_doctor_tracer())
    assert att.stragglers == ["core_1"]
    # three idle windows overlap the critical path's wait gaps, the
    # biggest being core_1's [0, 5] lead-in (1.5s of path waits inside)
    assert len(att.bubbles) == 3
    top = att.bubbles[0]
    assert top["device"] == "core_1"
    assert top["critical_overlap_s"] == pytest.approx(1.5)
    pd = att.per_device
    assert pd["core_0"]["busy_s"] == pytest.approx(3.5)
    assert pd["core_1"]["busy_s"] == pytest.approx(3.0)
    assert pd["core_1"]["utilization"] == pytest.approx(3.0 / 8.0)
    assert pd["core_1"]["last_finish_s"] == pytest.approx(8.0)


def test_attribution_roundtrip_through_export(tmp_path):
    tr = _doctor_tracer()
    live = attribute_run(tr)
    path = tmp_path / "trace.json"
    export_perfetto(tr, str(path))
    exported = attribute_trace(str(path))
    assert (
        [s.name for s in exported.critical_path]
        == [s.name for s in live.critical_path]
    )
    assert exported.makespan_s == pytest.approx(live.makespan_s, abs=1e-6)
    for k, v in live.breakdown_s.items():
        assert exported.breakdown_s[k] == pytest.approx(v, abs=1e-6)
    assert exported.stragglers == live.stragglers
    # loaded-dict form attributes identically to the path form
    with open(path) as f:
        again = attribute_trace(json.load(f))
    assert again.summary()["critical_path"] == exported.summary()["critical_path"]


def test_attribution_empty_and_windowed():
    # no device spans: empty verdict, no crash, zero fractions
    att = attribute_run(Tracer(clock=FakeClock(0.0)))
    assert att.critical_path == [] and att.makespan_s == 0.0
    assert sum(att.fractions().values()) == 0.0

    # an explicit window clips the walk: only task_c fits in [4, 9], its
    # wait back to the window start binds to the (still-included) flow
    att2 = attribute_run(_doctor_tracer(), window=(4.0, 9.0))
    assert [s.name for s in att2.critical_path] == ["task_c"]
    assert att2.makespan_s == pytest.approx(4.0)
    assert att2.breakdown_s["compute"] == pytest.approx(3.0)
    assert att2.breakdown_s["transfer"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Cost-model drift


def _drift_fixture():
    from distributed_llm_scheduler_tpu.core.schedule import Schedule, TaskTiming

    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 2.0, ["a"], set()),
    ])
    s = Schedule(policy="manual", per_node={"n0": ["a", "b"]},
                 assignment_order=["a", "b"], completed={"a", "b"})
    s.timings = {
        "a": TaskTiming("a", "n0", 0.0, 2.0),  # measured 2.0 vs predicted 1.0
        "b": TaskTiming("b", "n0", 2.0, 3.0),  # measured 1.0 vs predicted 2.0
    }
    return g, s


def test_drift_report_math_exact():
    g, s = _drift_fixture()
    rep = compute_drift(g, s)
    assert rep.source == "compute_time"
    assert {t.task_id: t.ratio for t in rep.tasks} == {"a": 2.0, "b": 0.5}
    # two-sided worst: the 2x underestimate and the 2x overestimate tie
    assert rep.worst_ratio() == pytest.approx(2.0)
    assert rep.exceeds(1.5)
    assert not rep.exceeds(2.5) and not rep.exceeds(None)
    assert rep.measured_makespan_s == pytest.approx(3.0)
    # predicted: the same chain replayed under compute_time = 1 + 2
    assert rep.predicted_makespan_s == pytest.approx(3.0)
    assert rep.makespan_ratio == pytest.approx(1.0)
    assert rep.per_class["a"]["median_ratio"] == pytest.approx(2.0)
    assert rep.per_class["b"]["measured_s"] == pytest.approx(1.0)
    # |log ratio| ranking lists both equally-wrong tasks
    assert {t.task_id for t in rep.worst} == {"a", "b"}
    summ = json.loads(json.dumps(rep.summary()))
    assert summ["n_tasks"] == 2 and summ["worst_ratio"] == pytest.approx(2.0)


def test_drift_uses_cost_model_and_never_mutates_graph():
    from distributed_llm_scheduler_tpu.utils.costmodel import CostModel

    g, s = _drift_fixture()
    cm = CostModel(
        graph_name="fixture", platform="cpu",
        task_seconds={"a": 4.0, "b": 1.0}, method="profile",
    )
    rep = compute_drift(g, s, cm)
    assert rep.source == "profile"
    assert {t.task_id: t.ratio for t in rep.tasks} == {"a": 0.5, "b": 1.0}
    # the predicted-makespan simulation swapped 4.0/1.0 in and back out
    assert rep.predicted_makespan_s == pytest.approx(5.0)
    assert g["a"].compute_time == 1.0 and g["b"].compute_time == 2.0
    # skip rule: non-positive predictions drop the task from the ratios
    cm0 = CostModel(
        graph_name="fixture", platform="cpu",
        task_seconds={"a": 0.0, "b": 1.0}, method="profile",
    )
    rep0 = compute_drift(g, s, cm0)
    assert [t.task_id for t in rep0.tasks] == ["b"]

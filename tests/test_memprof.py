"""Memory doctor tests: MemoryProfiler golden timelines and the
watermark invariant, memory-drift math and gating, traced-vs-untraced
bit-identity on all three execution paths, decode page-pool folding,
Perfetto memory counter tracks, `metrics diff`, cost-pass measured
payloads, and the regress direction/tolerance wiring for the new
memory metrics."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
from distributed_llm_scheduler_tpu.obs.memdrift import (
    DeviceMemDrift,
    MemDriftReport,
    compute_mem_drift,
    predicted_node_peak_bytes,
)
from distributed_llm_scheduler_tpu.obs.memprof import (
    BUCKETS,
    COUNTER_PREFIX,
    MemoryProfiler,
)
from distributed_llm_scheduler_tpu.obs.trace import Tracer


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# MemoryProfiler: golden timeline + the watermark invariant


def test_golden_timeline_and_watermark():
    """Scripted alloc/free sequence -> exact timeline tuples, peak at
    the right instant, bucket sums tiling the peak, verify() clean."""
    clk = FakeClock(1.0)
    mem = MemoryProfiler(clock=clk)
    mem.alloc("core_0", "param:w0", 100, "params")
    clk.t = 2.0
    mem.alloc("core_0", "input", 40, "activations")
    clk.t = 3.0
    mem.alloc("core_0", "out:t1", 60, "activations")
    clk.t = 4.0
    mem.free("core_0", "input")
    clk.t = 5.0
    mem.alloc("core_1", "xfer:t1", 60, "transfers")

    assert mem.devices() == ["core_0", "core_1"]
    assert mem.timeline("core_0") == [
        (1.0, 100), (2.0, 140), (3.0, 200), (4.0, 160),
    ]
    assert mem.timeline("core_1") == [(5.0, 60)]
    assert mem.live_bytes("core_0") == 160
    assert mem.peak("core_0") == (200, 3.0)

    wm = mem.watermark("core_0")
    assert wm["peak_bytes"] == 200 and wm["peak_t"] == 3.0
    assert wm["buckets"] == {
        "params": 100, "activations": 100, "kv_pages": 0, "transfers": 0,
    }
    assert sum(wm["buckets"].values()) == wm["peak_bytes"]
    assert wm["n_live"] == 3
    assert mem.verify() == []
    assert len(mem) == 5


def test_realloc_replaces_and_rep_loop_stays_flat():
    """Re-bearing the same label (the rep loop) must not accumulate:
    the previous buffer is released in the same event."""
    mem = MemoryProfiler(clock=FakeClock())
    for _ in range(5):
        mem.alloc("core_0", "out:t1", 64, "activations")
    assert mem.live_bytes("core_0") == 64
    assert mem.peak("core_0")[0] == 64
    assert mem.events[-1]["replaced"] == 64
    assert "replaced" not in mem.events[0]
    assert mem.verify() == []


def test_free_unknown_label_is_a_noop():
    mem = MemoryProfiler(clock=FakeClock())
    assert mem.free("core_0", "out:never_born") == 0
    assert len(mem) == 0
    mem.alloc("core_0", "out:t1", 10)
    assert mem.free("core_0", "out:t1") == 10
    assert mem.free("core_0", "out:t1") == 0  # double free: no-op
    assert mem.live_bytes("core_0") == 0
    assert mem.verify() == []


def test_verify_replays_independently_and_catches_corruption():
    """verify() recomputes from the raw event log; a tampered total is
    detected even though the incremental bookkeeping never saw it."""
    mem = MemoryProfiler(clock=FakeClock())
    mem.alloc("core_0", "a", 10)
    mem.alloc("core_0", "b", 20)
    assert mem.verify() == []
    mem.events[1]["total"] = 999  # corrupt the recorded timeline
    errs = mem.verify()
    assert errs and "live-set sum 30 != recorded total 999" in errs[0]


def test_task_output_bytes_tracks_last_birth():
    mem = MemoryProfiler(clock=FakeClock())
    mem.alloc("core_0", "out:t1", 100, "activations")
    mem.alloc("core_0", "param:w", 50, "params")  # not an out: label
    mem.alloc("core_0", "out:t1", 120, "activations")  # re-birth wins
    mem.alloc("core_1", "out:t2", 30, "activations")
    assert mem.task_output_bytes() == {"t1": 120, "t2": 30}


def test_reconcile_attaches_platform_peaks():
    mem = MemoryProfiler(clock=FakeClock())
    mem.alloc("core_0", "a", 100)
    mem.alloc("core_1", "b", 100)
    mem.reconcile({"core_0": 150})
    devs = mem.summary()["devices"]
    assert devs["core_0"]["source"] == "platform"
    assert devs["core_0"]["platform_peak_bytes"] == 150
    assert devs["core_0"]["platform_ratio"] == pytest.approx(1.5)
    assert devs["core_1"]["source"] == "model"
    assert "platform_peak_bytes" not in devs["core_1"]
    assert mem.summary()["schema"] == "dls.memprof/1"
    assert mem.summary()["buckets"] == list(BUCKETS)


def test_memprof_emits_per_device_counter_tracks():
    clk = FakeClock(1.0)
    tr = Tracer(clock=clk)
    mem = MemoryProfiler(clock=clk, tracer=tr)
    mem.alloc("core_0", "a", 100)
    mem.alloc("core_1", "b", 50)
    mem.free("core_0", "a")
    names = tr.counter_names()
    assert COUNTER_PREFIX + "core_0" in names
    assert COUNTER_PREFIX + "core_1" in names


# ---------------------------------------------------------------------------
# Memory drift: ratio math, ordering, gate


def _dev(nid, pred, meas):
    return DeviceMemDrift(node_id=nid, predicted_bytes=pred,
                          measured_bytes=meas)


def test_drift_worst_ratio_is_two_sided():
    """A 4x under-prediction and a 4x over-prediction are equally
    wrong: worst_ratio folds both sides through max(r, 1/r)."""
    rep = MemDriftReport(devices=[_dev("a", 100, 25), _dev("b", 100, 300)])
    # a: ratio 0.25 -> two-sided 4.0; b: ratio 3.0 -> two-sided 3.0
    assert rep.worst_ratio() == pytest.approx(4.0)
    assert MemDriftReport().worst_ratio() == 1.0


def test_drift_exceeds_gate_semantics():
    rep = MemDriftReport(devices=[_dev("a", 100, 200)])
    assert not rep.exceeds(None)          # no threshold -> never gates
    assert not rep.exceeds(2.0)           # strict >: landing on it is ok
    assert rep.exceeds(1.999)
    assert not MemDriftReport().exceeds(1.0)  # no devices -> ratio 1.0


def test_compute_mem_drift_on_scheduled_graph():
    """End-to-end drift vs the MEM001 no-evict replay: synthetic
    memprof peaks at 2x the prediction -> every device ratio 2.0,
    worst ordering by |log ratio|, task drift vs memory_required."""
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    cluster = Cluster.from_jax_devices(jax.devices()[:2], hbm_cap_gb=4.0)
    schedule = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    predicted = predicted_node_peak_bytes(dag.graph, cluster, schedule)
    assert set(predicted) == {d.node_id for d in cluster}
    assert all(v > 0 for v in predicted.values())

    mem = MemoryProfiler(clock=FakeClock())
    nids = sorted(predicted)
    mem.alloc(nids[0], "a", 2 * predicted[nids[0]])
    mem.alloc(nids[1], "b", 4 * predicted[nids[1]])
    tid = next(iter(dag.graph.task_ids()))
    want_task = int(round(dag.graph[tid].memory_required * (1024 ** 3)))
    mem.alloc(nids[0], f"out:{tid}", 3 * max(want_task, 1), "activations")

    drift = compute_mem_drift(dag.graph, cluster, schedule, mem)
    ratios = {d.node_id: d.ratio for d in drift.devices}
    # the out: birth also lands on nids[0]'s timeline, so its ratio is
    # >= 2x; nids[1] is exactly 4x
    assert ratios[nids[1]] == pytest.approx(4.0)
    assert drift.worst_devices[0].node_id == nids[1] or (
        abs(math.log(drift.worst_devices[0].ratio)) >= math.log(4.0)
    )
    # worst list is sorted by |log ratio| descending
    logs = [abs(math.log(d.ratio)) for d in drift.worst_devices]
    assert logs == sorted(logs, reverse=True)
    if want_task > 0:
        td = {t.task_id: t for t in drift.tasks}
        assert tid in td
        assert td[tid].ratio == pytest.approx(3.0, rel=1e-6)
    s = drift.summary()
    assert s["n_devices"] == 2
    assert s["worst_ratio"] == pytest.approx(drift.worst_ratio())


def test_drift_headroom_near_oom_warning():
    """A measured peak within 10% of the HBM budget must warn."""
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    cap_gb = 0.001  # ~1 MB budget so a small alloc is near-OOM
    cluster = Cluster.from_jax_devices(jax.devices()[:1], hbm_cap_gb=cap_gb)
    schedule = get_scheduler("greedy").schedule(dag.graph, cluster)
    nid = next(iter(cluster)).node_id
    mem = MemoryProfiler(clock=FakeClock())
    mem.alloc(nid, "a", int(0.95 * cap_gb * (1024 ** 3)))
    drift = compute_mem_drift(dag.graph, cluster, schedule, mem)
    assert drift.warnings and "near OOM" in drift.warnings[0]
    assert drift.headroom[nid]["warn"] is True
    assert drift.headroom[nid]["headroom_frac"] == pytest.approx(
        0.05, abs=1e-6
    )


# ---------------------------------------------------------------------------
# Instrumented execution: bit-identity + recorded timelines


@pytest.fixture(scope="module")
def exec_setup():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=8)
    params = dag.init_params()
    ids = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:4], hbm_cap_gb=4.0)
    schedule = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    return dag, params, ids, cluster, schedule


@pytest.mark.parametrize("mode", ["per_task_loop", "planned", "per_task_plan"])
def test_memprof_run_bit_identical(exec_setup, mode):
    """memprof instrumentation must not perturb results on either
    execution path (nor on the plan's per-task parity reference), and
    must record a verifiable timeline."""
    dag, params, ids, cluster, schedule = exec_setup
    kw = {
        "per_task_loop": {"planned": False},
        "planned": {"planned": True},
        "per_task_plan": {"coalesce": False},
    }[mode]
    backend = DeviceBackend(cluster)
    plain = backend.execute(dag.graph, schedule, params, ids, **kw)
    assert plain.memory is None  # zero-overhead disabled path

    mem = MemoryProfiler()
    traced = backend.execute(
        dag.graph, schedule, params, ids, memprof=mem, **kw
    )
    np.testing.assert_array_equal(
        np.asarray(plain.output), np.asarray(traced.output)
    )
    assert len(mem) > 0
    assert mem.verify() == []
    assert mem.devices()  # at least one per-device timeline
    for dev in mem.devices():
        wm = mem.watermark(dev)
        assert sum(wm["buckets"].values()) == wm["peak_bytes"]
    assert traced.memory is not None
    assert traced.memory["schema"] == "dls.memprof/1"
    # params were staged somewhere: the params bucket is live at some peak
    assert any(
        mem.watermark(d)["buckets"]["params"] > 0 for d in mem.devices()
    )


def test_memprof_perfetto_counter_tracks(exec_setup, tmp_path):
    """A memprof-instrumented traced run exports >=1 memory counter
    track per recorded device, and the trace validates clean."""
    from distributed_llm_scheduler_tpu.obs.export import (
        export_perfetto,
        trace_summary,
        validate_trace,
    )

    dag, params, ids, cluster, schedule = exec_setup
    tr = Tracer()
    mem = MemoryProfiler(tracer=tr)
    DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, trace=tr, memprof=mem,
    )
    path = export_perfetto(tr, str(tmp_path / "mem_trace.json"),
                           memprof=mem)
    assert validate_trace(path) == []
    s = trace_summary(path)
    tracks = set(s["counter_tracks"])
    for dev in mem.devices():
        assert COUNTER_PREFIX + dev in tracks


# ---------------------------------------------------------------------------
# Decode engine: KV page-pool folding


def test_decode_page_pool_folds_into_memprof(session_slo_engine):
    """Page allocations at admission land in the kv_pages bucket in
    whole-page units; retirement frees them back to zero.

    Rides the session-scoped slo engine (same 2-slot geometry this test
    used to build from scratch): ``rebind_obs`` re-points the warm
    executables at this test's scripted clock + profiler."""
    from distributed_llm_scheduler_tpu.models.kv_pages import pages_needed

    cfg = GPT2Config.tiny()
    eng = session_slo_engine
    clk = FakeClock(0.0)
    mem = MemoryProfiler(clock=clk)
    eng.rebind_obs(clock=clk, memprof=mem)
    ps = eng.pool.page_size
    page_bytes = (
        cfg.n_layer * 2 * ps * cfg.n_head * (cfg.n_embd // cfg.n_head)
        * np.dtype(cfg.dtype).itemsize
    )
    assert eng._page_bytes == page_bytes

    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    max_new = 9
    eng.submit("r0", prompt, max_new)
    eng.submit("r1", prompt, max_new)
    clk.t = 1.0
    eng.step_segment()  # admits both
    node = next(iter(mem.devices()))
    need = pages_needed(prompt.shape[1] + max_new, ps)
    assert mem.live_bytes(node) == 2 * need * page_bytes
    wm_live = {
        lbl for ev in mem.events
        if ev["kind"] == "alloc" for lbl in [ev["label"]]
    }
    assert {"kv:r0", "kv:r1"} <= wm_live
    assert all(
        ev["bucket"] == "kv_pages" for ev in mem.events
        if ev["label"].startswith("kv:")
    )
    clk.t = 2.0
    eng.step_segment()
    clk.t = 3.0
    eng.step_segment()  # both retire (9 new tokens over 12 steps)
    assert mem.live_bytes(node) == 0
    frees = [e for e in mem.events if e["kind"] == "free"]
    assert {e["label"] for e in frees} == {"kv:r0", "kv:r1"}
    assert mem.verify() == []
    wm = mem.watermark(node)
    assert wm["buckets"]["kv_pages"] == wm["peak_bytes"]
    assert wm["peak_bytes"] == 2 * need * page_bytes


# ---------------------------------------------------------------------------
# metrics diff


def _snap(counters=(), gauges=(), hists=()):
    from distributed_llm_scheduler_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for name, v in counters:
        reg.counter(name).inc(v)
    for name, v in gauges:
        reg.gauge(name).set(v)
    for name, vals in hists:
        for v in vals:
            reg.histogram(name).observe(v)
    return reg.snapshot()


def test_diff_snapshots_deltas_and_one_sided():
    from distributed_llm_scheduler_tpu.obs.metrics import diff_snapshots

    a = _snap(counters=[("runs", 2), ("only_a", 1)],
              hists=[("lat", [1.0, 2.0])])
    b = _snap(counters=[("runs", 5), ("only_b", 1)],
              hists=[("lat", [2.0, 3.0, 4.0])])
    d = diff_snapshots(a, b)
    assert d["schema"] == "dls.metrics-diff/1"
    assert d["counters"]["runs"]["value_delta"] == 3
    assert d["counters"]["only_a"] == {"only_in": "a"}
    assert d["counters"]["only_b"] == {"only_in": "b"}
    lat = d["histograms"]["lat"]
    assert lat["count_a"] == 2 and lat["count_b"] == 3
    assert lat["count_delta"] == 1
    assert lat["p50_delta"] == pytest.approx(
        b["histograms"]["lat"]["p50"] - a["histograms"]["lat"]["p50"]
    )


def test_diff_snapshots_rejects_schema_mismatch():
    from distributed_llm_scheduler_tpu.obs.metrics import diff_snapshots

    a = _snap(counters=[("runs", 1)])
    bad = dict(_snap(), schema="dls.metrics/2")
    with pytest.raises(ValueError, match="snapshot b invalid"):
        diff_snapshots(a, bad)


def test_metrics_diff_cli(tmp_path, capsys):
    from distributed_llm_scheduler_tpu.__main__ import main

    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(_snap(counters=[("runs", 1)])))
    pb.write_text(json.dumps(_snap(counters=[("runs", 4)])))
    assert main(["metrics", "diff", str(pa), str(pb)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counters"]["runs"]["value_delta"] == 3

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(dict(_snap(), schema="dls.metrics/2")))
    assert main(["metrics", "diff", str(pa), str(bad)]) == 2
    assert main(["metrics", "diff", str(pa), str(tmp_path / "no.json")]) == 2


# ---------------------------------------------------------------------------
# doctor --memory CLI


def test_doctor_memory_cli_exit_codes(capsys):
    from distributed_llm_scheduler_tpu.__main__ import main

    argv = ["doctor", "--memory", "--model", "gpt2-tiny",
            "--num-nodes", "2"]
    assert main(argv) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["memory"]["devices"]
    for entry in rep["memory"]["devices"].values():
        assert entry["n_events"] > 0
        wm = entry["watermark"]["buckets"]
        assert sum(wm.values()) == entry["peak_bytes"]
    assert rep["mem_drift"]["worst_ratio"] is not None

    # an impossible gate: any real drift exceeds a ~1.0 threshold
    assert main(argv + ["--mem-drift-threshold", "1.0000001"]) == 1
    capsys.readouterr()

    # synthetic graphs carry no fns: the memory doctor refuses
    assert main(["doctor", "--memory", "--model", "llm"]) == 2


# ---------------------------------------------------------------------------
# cost pass: measured payloads


def test_cost_pass_attaches_measured_gb():
    from distributed_llm_scheduler_tpu.analysis.cost_pass import analyze_cost
    from distributed_llm_scheduler_tpu.core.graph import GB, Task, TaskGraph

    g = TaskGraph([
        Task("big", memory_required=0.1, compute_time=1.0),
        Task("unchecked", memory_required=0.2, compute_time=1.0),
    ])
    measured = {"big": int(0.35 * GB), "unchecked": int(0.19 * GB)}
    rep = analyze_cost(
        g, {"big": 0.5}, factor=2.0, memory_report=measured,
    )
    by_code = {}
    for d in rep.diagnostics:
        by_code.setdefault(d.code, []).append(d)
    cst1 = by_code["CST001"][0]  # 0.5 compiled > 2 * 0.1 analytic
    assert cst1.data["measured_gb"] == pytest.approx(0.35, rel=1e-6)
    cst3 = by_code["CST003"][0]  # no preflight for "unchecked"
    assert cst3.data["measured_gb"] == pytest.approx(0.19, rel=1e-6)
    # a MemoryProfiler works directly as the report source
    mem = MemoryProfiler(clock=FakeClock())
    mem.alloc("n0", "out:big", int(0.35 * GB), "activations")
    rep2 = analyze_cost(g, {"big": 0.5}, factor=2.0, memory_report=mem)
    d1 = [d for d in rep2.diagnostics if d.code == "CST001"][0]
    assert d1.data["measured_gb"] == pytest.approx(0.35, rel=1e-6)


# ---------------------------------------------------------------------------
# regress: per-device memory metrics


def test_regress_memory_metric_directions_and_tolerances():
    from distributed_llm_scheduler_tpu.eval.regress import (
        _default_tol,
        _direction,
        compare_artifacts,
    )

    assert _direction("peak_hbm_bytes.core_3") == "lower"
    assert _direction("kv_pages_peak") == "lower"
    assert _default_tol("peak_hbm_bytes.core_3", 0.15) == 0.02
    assert _default_tol("kv_pages_peak", 0.15) == 0.0
    assert _default_tol("some_other_metric", 0.15) == 0.15

    base = {"peak_hbm_bytes.core_0": 1000, "kv_pages_peak": 4}
    metrics = ["peak_hbm_bytes.core_0", "kv_pages_peak"]
    ok = compare_artifacts(dict(base), base, metrics=metrics)
    assert ok.ok
    # +3% on a per-device peak breaks the 2% band
    v = compare_artifacts(
        {"peak_hbm_bytes.core_0": 1030, "kv_pages_peak": 4},
        base, metrics=metrics,
    )
    assert not v.ok
    assert v.failures()[0].metric == "peak_hbm_bytes.core_0"
    # kv_pages_peak is exact: any increase regresses
    v2 = compare_artifacts(
        {"peak_hbm_bytes.core_0": 1000, "kv_pages_peak": 5},
        base, metrics=metrics,
    )
    assert [c.metric for c in v2.failures()] == ["kv_pages_peak"]
    # dropping a per-device metric is a missing failure, not a pass
    v3 = compare_artifacts(
        {"kv_pages_peak": 4}, base, metrics=metrics,
    )
    assert [c.status for c in v3.failures()] == ["missing"]


def test_committed_medium_baseline_self_compares_clean():
    """The recaptured r07 baseline must pass against itself with the
    exact CI metric list (the gate's by-construction sanity)."""
    from distributed_llm_scheduler_tpu.eval.regress import compare_artifacts

    base = "BENCH_MEDIUM_r07.json"
    art = json.load(open(base))
    mem_metrics = [k for k in art if k.startswith("peak_hbm_bytes.")]
    assert len(mem_metrics) == 8  # one per core on the 8-core cluster
    assert art["kv_pages_peak"] == 4
    v = compare_artifacts(
        base, base, metrics=mem_metrics + ["kv_pages_peak"],
    )
    assert v.ok and len(v.checks) == 9


def test_modeled_kv_pages_peak_matches_decode_leg_geometry():
    from distributed_llm_scheduler_tpu.eval.benchlib import (
        modeled_kv_pages_peak,
    )
    from distributed_llm_scheduler_tpu.models.kv_pages import pages_needed

    got = modeled_kv_pages_peak(slots=2, prompt_len=8, max_new=6,
                                page_size=8)
    assert got == 2 * pages_needed(14, 8) == 4

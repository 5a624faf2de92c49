"""The per-layer metrics that read the program's own phase timings: the
two readers on hand-made contexts, every entry of ``BENCHMARK.json``
through the run's own loader, and both kinds of cell end to end on the
CPU at a tiny size."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, run  # noqa: E402
from benchmark.metrics.readers import registry_hist, span_share  # noqa: E402

EXEC = ["exec_replan_ms", "exec_stage_ms", "exec_launch_ms",
        "exec_fence_ms", "exec_other_ms"]
SERVE = ["engine_empty_share", "seg_span_share", "seg_fold_ms_p50",
         "prefill_dispatch_ms_p50"]


@pytest.fixture()
def registry():
    """The program's process-wide registry, emptied before and after."""
    from distributed_llm_scheduler_tpu import obs

    obs.reset_ambient()
    yield obs.process_metrics()
    obs.reset_ambient()


def _observe(registry, **phases):
    for name, values in phases.items():
        for v in values:
            registry.histogram(f"execute.phase.{name}").observe(v)


def test_registry_hist_sums_the_medians_of_the_named_histograms(registry):
    _observe(registry, order_s=[0.010, 0.012, 0.900],
             place_s=[0.020, 0.021, 0.022], plan_s=[0.030] * 3)
    params = {"histograms": ["execute.phase.order_s", "execute.phase.place_s",
                             "execute.phase.plan_s"], "scale": 1000.0}
    # the slow first call does not move a median
    assert registry_hist.read({}, params) == pytest.approx(12 + 21 + 30)
    assert registry_hist.read(
        {}, {"histograms": ["execute.phase.place_s"]}) == pytest.approx(0.021)


def test_registry_hist_subtracts_what_is_named_under_minus(registry):
    """The rest of the median call: its wall less the named phases."""
    _observe(registry, launch_s=[0.2, 0.21, 0.22], fence_s=[0.01] * 3)
    for v in (0.25, 0.26, 0.9):
        registry.histogram("execute.wall_s").observe(v)
    params = {"histograms": ["execute.wall_s"], "scale": 1000.0,
              "minus": ["execute.phase.launch_s", "execute.phase.fence_s"]}
    assert registry_hist.read({}, params) == pytest.approx(260 - 210 - 10)
    params["minus"].append("execute.phase.never_observed_s")
    assert registry_hist.read({}, params) is None


def test_registry_hist_finds_nothing_in_an_empty_registry(registry):
    params = {"histograms": ["execute.phase.order_s"], "scale": 1000.0}
    assert registry_hist.read({}, params) is None
    _observe(registry, order_s=[0.010])
    params["histograms"].append("execute.phase.never_observed_s")
    assert registry_hist.read({}, params) is None


def test_registry_hist_finds_nothing_in_a_program_without_the_registry(
        monkeypatch):
    """The parent commit: ``obs`` has no ``process_metrics``."""
    from distributed_llm_scheduler_tpu import obs

    monkeypatch.delattr(obs, "process_metrics")
    assert registry_hist.read(
        {}, {"histograms": ["execute.phase.order_s"]}) is None


def _span(name, t0, t1, **args):
    return {"type": "span", "name": name, "track": "decode", "t0": t0,
            "t1": t1, "args": args}


SPANS = [
    _span("segment", 9.0, 10.5),          # cut by the slice's start
    _span("fold", 10.5, 10.502),
    _span("admit", 10.6, 10.601),
    _span("prefill_chunk", 10.601, 10.6025, tokens=97),
    _span("idle_wait", 10.7, 10.9),
    _span("segment", 11.0, 11.5),
    _span("fold", 11.5, 11.504),
    _span("idle_wait", 11.9, 12.4),       # cut by its end
    _span("segment", 13.0, None),         # never closed
    {"type": "instant", "name": "segment", "t": 11.2},
]
CTX = {"spans": SPANS, "slice": (10.0, 12.0), "t0": 8.0, "seconds": 4.0}


def test_span_share_is_the_time_the_spans_cover_inside_the_slice():
    assert span_share.read(CTX, {"span": "segment"}) == pytest.approx(
        (0.5 + 0.5) / 2.0)
    assert span_share.read(CTX, {"span": "idle_wait"}) == pytest.approx(
        (0.2 + 0.1) / 2.0)


def test_span_share_p50_is_the_median_duration_inside_the_window():
    params = {"span": "fold", "stat": "p50"}
    assert span_share.read(CTX, params) == pytest.approx(3.0)
    # a span that ends after the window is not the window's
    late = dict(CTX, seconds=3.501)
    assert span_share.read(late, params) == pytest.approx(2.0)


def test_span_share_without_a_span_leaves_the_metric_out():
    assert span_share.read(CTX, {"span": "no_such_span"}) is None
    assert span_share.read(
        CTX, {"span": "no_such_span", "stat": "p50"}) is None
    # no traced slice: a share has nothing to be a share of
    assert span_share.read({"spans": SPANS}, {"span": "segment"}) is None
    assert span_share.read({}, {"span": "segment"}) is None


def test_span_share_with_a_witness_reads_no_span_as_zero():
    """A program that draws ``admit`` spans draws ``idle_wait`` whenever
    its engine is empty: none of them is a share of 0, not a metric the
    program cannot give (the parent draws neither)."""
    busy = dict(CTX, spans=[s for s in SPANS if s["name"] != "idle_wait"])
    params = {"span": "idle_wait", "witness": "admit"}
    assert span_share.read(busy, params) == 0.0
    parent = dict(CTX, spans=[s for s in SPANS
                              if s["name"] in ("segment", "prefill_chunk")])
    assert span_share.read(parent, params) is None


@pytest.mark.parametrize("cell,names", [
    ("m-dag-1chip", EXEC), ("m-dag-4chip", EXEC),
    ("xl-chat", SERVE), ("xl-docqa", SERVE),
])
def test_every_new_entry_resolves_through_the_runs_own_loader(
        cell, names, registry):
    """``BENCHMARK.json`` entry -> ``metrics/<name>.json`` -> reader, the
    way a run finds them, in the cells that list them and in no other."""
    loaded = harness.load_cell(cell)
    defs = [m for m in loaded.per_layer if m["name"] in names]
    assert [m["name"] for m in defs] == names
    assert all(cell in m["workloads"] for m in defs)
    others = {m["name"] for m in loaded.per_layer} & set(EXEC + SERVE)
    assert others == set(names)
    _observe(registry, order_s=[0.01], place_s=[0.02], plan_s=[0.03],
             stage_s=[0.004], launch_s=[0.2], fence_s=[0.005],
             rtt_s=[0.001], report_s=[0.002], other_s=[0.003])
    registry.histogram("execute.wall_s").observe(0.3)
    got = harness.read_metrics(defs, CTX)
    assert set(got) == set(names)
    want = {"exec_replan_ms": 60.0, "exec_stage_ms": 4.0,
            "exec_launch_ms": 200.0, "exec_fence_ms": 5.0,
            "exec_other_ms": 31.0, "engine_empty_share": 0.15,
            "seg_span_share": 0.5, "seg_fold_ms_p50": 3.0,
            "prefill_dispatch_ms_p50": 1.5}
    for name, m in got.items():
        assert m["value"] == pytest.approx(want[name]), name
    # a context with nothing to read leaves every one of them out
    from distributed_llm_scheduler_tpu import obs

    obs.reset_ambient()
    assert harness.read_metrics(defs, {"spans": []}) == {}


# -- end to end, on the tiny cells of the end-to-end test --------------------

_spec = importlib.util.spec_from_file_location(
    "_benchmark_end_to_end", Path(__file__).with_name(
        "test_benchmark_end_to_end.py"))
_e2e = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_e2e)
tiny_root = _e2e.tiny_root


@pytest.fixture()
def phase_root(tiny_root):
    """The tiny checkout with this PR's entries appended for its cells."""
    path = tiny_root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    committed = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in committed["per_layer"]:
        if m["name"] in EXEC + SERVE:
            tiny = "tiny-dag" if m["name"] in EXEC else "tiny-chat"
            spec["per_layer"].append(dict(m, workloads=[tiny]))
    path.write_text(json.dumps(spec))
    return tiny_root


def test_traced_dag_cell_splits_its_step_by_phase(phase_root, capsys):
    assert run.main(["--workload", "tiny-dag", "--seed", "21",
                     "--seconds", "2", "--trace", "1"]) == 0
    line = _e2e._last_line(capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(EXEC) <= set(got)
    assert all(got[k] >= 0 for k in EXEC if k != "exec_other_ms")
    assert all(line["metrics"][k]["unit"] == "ms" for k in EXEC)
    # the five tile the median call with nothing left over; the median
    # step the harness times is that call and its own few lines
    assert sum(got[k] for k in EXEC) == pytest.approx(
        got["dag_step_ms_p50"], rel=0.1)
    assert got["exec_stage_ms"] + got["exec_launch_ms"] == pytest.approx(
        got["dispatch_ms"], rel=0.25)


def test_traced_serve_cell_reads_its_tick_spans(phase_root, capsys):
    assert run.main(["--workload", "tiny-chat", "--seed", "22",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _e2e._last_line(capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SERVE) <= set(got)
    assert 0.0 <= got["engine_empty_share"] <= 1.0
    assert 0.0 < got["seg_span_share"] <= 1.0
    assert got["engine_empty_share"] + got["seg_span_share"] <= 1.0 + 1e-9
    assert got["seg_fold_ms_p50"] > 0 and got["prefill_dispatch_ms_p50"] > 0
    # the new spans are handed to the gap attribution with the old ones
    named = {name for name, _s in line["breakdown"]["idle_gaps"]}
    assert named & {"admit", "fold", "idle_wait"}


@pytest.mark.parametrize("cell,keys", [
    ("tiny-dag", {"untraced_before", "traced", "untraced_after",
                  "events_per_step"}),
    ("tiny-chat", {"untraced", "traced"}),
])
def test_tracing_cost_measures_a_cell_with_and_without_the_tracer(
        tiny_root, capsys, cell, keys):
    """What an attached tracer costs is read on the cell's own path; the
    numbers mean something only on the chip, the path is checked here."""
    from benchmark import tracing_cost

    assert tracing_cost.main(["--workload", cell, "--seed", "23",
                              "--seconds", "1"]) == 0
    out = _e2e._last_line(capsys)
    assert keys <= set(out) and out["traced_over_untraced"] > 0
    assert out["workload"] == cell and out["device"]["count"] >= 1
    if cell == "tiny-dag":
        # a constant number of events a step, whatever the tracer holds
        assert out["events_per_step"] == int(out["events_per_step"])
        assert out["traced"]["steps"] >= 3
    else:
        assert out["traced"]["n"] == out["untraced"]["n"] > 0
        assert out["traced"]["failed"] == out["untraced"]["failed"] == 0
        assert out["traced"]["events"] > 0 == out["untraced"]["events"]

"""The reduction from a profiler trace to numbers: on hand-made events,
and on a cut of a trace recorded on the v5e (``benchmark/fixtures/``)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import xplane  # noqa: E402

MS = 1e6


def _trace(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": list(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host)}]},
    ]}


def test_busy_is_the_union_of_intervals():
    evs = [["a", 0, 4 * MS], ["b", 2 * MS, 4 * MS], ["c", 10 * MS, 1 * MS],
           ["inside", 3 * MS, 1 * MS], ["empty", 20 * MS, 0]]
    assert xplane.busy_ns(evs) == pytest.approx(7 * MS)
    assert xplane.busy_ns([]) == 0


def test_self_time_charges_a_parent_only_what_its_children_leave():
    evs = [["while", 0, 10 * MS], ["fusion.1", 1 * MS, 3 * MS],
           ["fusion.2", 5 * MS, 4 * MS], ["copy", 12 * MS, 2 * MS],
           ["fusion.1", 20 * MS, 1 * MS]]
    own = xplane.self_times(evs)
    assert own["while"] == pytest.approx(3 * MS)
    assert own["fusion.1"] == pytest.approx(4 * MS)
    assert own["fusion.2"] == pytest.approx(4 * MS)
    assert own["copy"] == pytest.approx(2 * MS)
    assert sum(own.values()) == pytest.approx(xplane.busy_ns(evs))


def test_matching_sums_duration_and_counts():
    evs = [["jit_seg(1)", 0, 5 * MS], ["jit__fn(2)", 6 * MS, 1 * MS],
           ["jit_seg(1)", 8 * MS, 7 * MS]]
    assert xplane.matching(evs, r"^jit_seg") == (12 * MS, 2)
    assert xplane.matching(evs, r"nothing") == (0.0, 0)


def test_idle_gaps_and_their_attribution_to_host_spans():
    ops = [["a", 0, 2 * MS], ["b", 5 * MS, 1 * MS], ["c", 10 * MS, 2 * MS]]
    gaps = xplane.idle_gaps(ops, (0, 12 * MS))
    assert gaps == [(2 * MS, 5 * MS), (6 * MS, 10 * MS)]
    host = [("segment", 0, 2.5 * MS), ("prefill_chunk", 6 * MS, 9 * MS)]
    by = xplane.attribute_gaps(gaps, host)
    assert by["segment"] == pytest.approx(0.5e-3)
    assert by["prefill_chunk"] == pytest.approx(3e-3)
    assert by["host: between program spans"] == pytest.approx(3.5e-3)
    assert sum(by.values()) == pytest.approx(7e-3)


def test_summarize_maps_host_spans_through_the_sync_marker():
    # the marker was entered at host time 50.0 s and sits at 1 ms on the
    # trace's clock; a host span from 50.004 to 50.008 s then covers the
    # device's gap from 5 to 9 ms
    ops = [["a", 2 * MS, 3 * MS], ["b", 9 * MS, 1 * MS]]
    host = [[xplane.SYNC_MARKER, 1 * MS, 1000]]
    out = xplane.summarize(
        _trace(ops, host=host), window_s=0.010,
        host_spans_s=[("segment", 50.004, 50.008)], t_sync_host_s=50.0)
    assert out["busy_s"] == pytest.approx(4e-3)
    assert out["window_s"] == 0.010
    assert out["device_ops"][0] == ["a", pytest.approx(3e-3)]
    assert out["idle_gaps"] == [["segment", pytest.approx(4e-3)]]
    assert out["longest_gap_ms"] == pytest.approx(4.0)


def test_summarize_averages_busy_over_the_devices_used():
    t = _trace([["a", 0, 4 * MS]])
    t["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["a", 0, 2 * MS]]}]})
    out = xplane.summarize(t, window_s=0.01)
    assert out["busy_s_per_device"] == [pytest.approx(4e-3),
                                        pytest.approx(2e-3)]
    assert out["busy_s"] == pytest.approx(3e-3)
    assert xplane.summarize(t, 0.01, n_devices=1)["busy_s"] == pytest.approx(
        4e-3)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        xplane.summarize({"planes": [{"name": "/host:CPU", "lines": []}]}, 1.0)


# -- a cut of a trace recorded on the v5e -------------------------------------

FIXTURE = ROOT / "benchmark" / "fixtures" / "dag_v5e_cut.json"


@pytest.fixture(scope="module")
def recorded():
    """40 ms of ``m-dag-1chip`` (GPT-2 medium forward, batch 32, placed
    per task) on one TPU v5 lite, PR 23: the device plane's module, op
    and async-op lines as ``xplane.load`` gives them, plus the harness's
    sync marker from the host plane."""
    return xplane.load_json(str(FIXTURE))


def test_recorded_trace_inventory(recorded):
    assert xplane.inventory(recorded) == [
        "/device:TPU:0 / XLA Modules: 189 events",
        "/device:TPU:0 / XLA Ops: 1279 events",
        "/device:TPU:0 / Async XLA Ops: 256 events",
        "/host:CPU / python3: 1 events"]
    assert len(xplane.device_planes(recorded)) == 1


def test_recorded_trace_busy_union_and_self_times(recorded):
    dev = xplane.device_planes(recorded)[0]
    ops = xplane.line_events(dev, xplane.OPS_LINE)
    mods = xplane.line_events(dev, xplane.MODULES_LINE)
    assert xplane.busy_ns(ops) == pytest.approx(12_082_825.0)
    # every op runs inside a module, so the modules cover a little more
    assert xplane.busy_ns(mods) == pytest.approx(12_093_703.0)
    own = xplane.self_times(ops)
    assert sum(own.values()) == pytest.approx(xplane.busy_ns(ops))
    kinds = {}
    for name, ns in own.items():
        kinds[xplane.op_kind(name)] = kinds.get(xplane.op_kind(name), 0) + ns
    assert xplane.top(kinds, 3, 1e-6) == [
        ["convolution_add_fusion", pytest.approx(4.180666)],
        ["fusion", pytest.approx(2.885513)],
        ["_flash_mha", pytest.approx(2.151055)]]


def test_recorded_trace_kernel_and_module_time_by_regex(recorded):
    dev = xplane.device_planes(recorded)[0]
    ops = xplane.line_events(dev, xplane.OPS_LINE)
    mods = xplane.line_events(dev, xplane.MODULES_LINE)
    assert xplane.matching(ops, r"^_flash_mha") == (2_151_055.0, 24)
    assert xplane.matching(mods, r"^jit_f_attn") == (5_392_385.0, 24)


def test_recorded_trace_idle_gaps_go_to_the_host_span_that_covers_them(
        recorded):
    dev = xplane.device_planes(recorded)[0]
    ops = xplane.line_events(dev, xplane.OPS_LINE)
    first = min(e[1] for e in ops)
    last = max(e[1] + e[2] for e in ops)
    gaps = xplane.idle_gaps(ops, (first, last))
    assert len(gaps) == 1036
    idle = sum(b - a for a, b in gaps)
    assert idle == pytest.approx(27_884_348.0)
    assert idle + xplane.busy_ns(ops) == pytest.approx(last - first)
    # the marker sits at 54.684634 ms on the trace's clock; say the host
    # entered it at 1000 s and was inside one execute() call throughout
    out = xplane.summarize(
        recorded, window_s=0.040,
        host_spans_s=[("execute", 1000.0, 1001.0)], t_sync_host_s=1000.0)
    assert out["busy_s"] == pytest.approx(0.012082825)
    assert out["device_span_s"] == pytest.approx(0.039967173)
    # every gap of the cut lies after the marker, inside that one span
    assert out["idle_gaps"] == [["execute", pytest.approx(idle / 1e9)]]
    # a span that ends 20 ms after the marker covers nothing of the cut
    # (its events begin 88 ms after it): the idle time goes unattributed
    out = xplane.summarize(
        recorded, window_s=0.040,
        host_spans_s=[("execute", 1000.0, 1000.020)], t_sync_host_s=1000.0)
    assert out["idle_gaps"] == [["host: between program spans",
                                 pytest.approx(idle / 1e9)]]
    assert out["device_ops"][0][0] == "convolution_add_fusion"

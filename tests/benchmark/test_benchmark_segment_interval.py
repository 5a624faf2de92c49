"""The per-layer metrics that tile a served token's interval: the device-
trace reader on a small hand-made trace (``fixtures/
serve_interval_cut.json``: one device plane's module line, one host
plane's ``dls/*`` annotations, in ms-round numbers), the two registry
readers on hand-made registries, and every new entry of
``BENCHMARK.json`` through the run's own loader."""

from __future__ import annotations

import copy
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, run, xplane  # noqa: E402
from benchmark.metrics.readers import (  # noqa: E402
    registry_counter_ratio,
    registry_stat,
    segment_interval,
)

NEW = ["step_interval_ms", "seg_period_ms_p99", "seg_behind_prefill_share",
       "prefill_stall_ms_step", "seg_idle_ms_step"]
SERVING = ["xl-chat", "xl-docqa", "xing-longctx", "dots3-longctx",
           "glm-reason"]
PARAMS = {"segment": "^jit_seg", "prefill": "^jit__fn",
          "skip": "dls/idle_wait", "per_event": ["engine", "seg_steps"],
          "scale": 1e-06}
MS = 1e6


@pytest.fixture()
def trace():
    return xplane.load_json(
        str(ROOT / "benchmark" / "fixtures" / "serve_interval_cut.json"))


@pytest.fixture()
def registry():
    """The program's process-wide registry, emptied before and after."""
    from distributed_llm_scheduler_tpu import obs

    obs.reset_ambient()
    yield obs.process_metrics()
    obs.reset_ambient()


def _ctx(trace, seg_steps=8):
    return {"trace": trace, "n_devices": 1,
            "config": {"engine": {"seg_steps": seg_steps}}}


def _read(ctx, part):
    return segment_interval.read(ctx, dict(PARAMS, part=part))


# -- the device-trace reader ---------------------------------------------------


def test_the_three_parts_sum_to_the_start_to_start_interval(trace):
    """Five segment programs: a chunk between the first two, nothing
    between the next two, an empty engine before the fourth, a wave's
    prefill ahead of it and two chunks ahead of the fifth."""
    t = segment_interval.tile(trace, 1, "^jit_seg", "^jit__fn",
                              "dls/idle_wait")
    assert (t["pairs"], t["skipped"]) == (3, 1)
    assert t["interval"] == pytest.approx((71 + 41 + 102) * MS)
    assert t["segment"] == pytest.approx(3 * 40 * MS)
    assert t["prefill"] == pytest.approx((30 + 0 + 60) * MS)
    assert t["idle"] == pytest.approx((1 + 1 + 2) * MS)
    assert t["segment"] + t["prefill"] + t["idle"] == pytest.approx(
        t["interval"])
    ctx = _ctx(trace)
    got = {part: _read(ctx, part) for part in segment_interval.PARTS}
    assert got["prefill"] == pytest.approx(90 / 24)
    assert got["idle"] == pytest.approx(4 / 24)
    assert got["segment"] == pytest.approx(5.0)
    assert got["segment"] + got["prefill"] + got["idle"] == pytest.approx(
        got["interval"])


def test_a_pair_beside_an_empty_engine_is_left_out(trace):
    """The third pair's 198 ms hold the front-end's sleep: with the
    annotation it is dropped, without it it would swamp the idle part."""
    kept = segment_interval.tile(trace, 1, "^jit_seg", "^jit__fn", None)
    assert (kept["pairs"], kept["skipped"]) == (4, 0)
    assert kept["idle"] == pytest.approx((4 + 198 - 42 - 10) * MS)
    awake = copy.deepcopy(trace)
    host = awake["planes"][1]["lines"][0]
    host["events"] = [e for e in host["events"] if e[0] != "dls/idle_wait"]
    t = segment_interval.tile(awake, 1, "^jit_seg", "^jit__fn",
                              "dls/idle_wait")
    assert (t["pairs"], t["skipped"]) == (4, 0)


def test_a_pair_whose_later_segment_says_no_slot_continued_is_left_out(trace):
    """One request's last segment, then the next one's whole prefill with
    nobody decoding, and no tick asleep between them: only the program
    knows.  Its ``segment`` spans reach the trace's clock through the
    sync marker (0.1 ms on the trace = the slice's start on the host)."""
    host0 = 100.0

    def span(t0_ms, t1_ms, **args):
        return {"type": "span", "name": "segment", "track": "decode",
                "t0": host0 + (t0_ms - 0.1) / 1e3,
                "t1": host0 + (t1_ms - 0.1) / 1e3, "args": args}

    spans = [span(0.5, 41.2, continuing=0), span(43.2, 112.2, continuing=2),
             span(112.8, 155.2, continuing=2), span(310.6, 351.2, continuing=0),
             span(354.7, 453.2, continuing=0)]   # the fifth: all new slots
    ctx = dict(_ctx(trace), spans=spans, slice=(host0, host0 + 0.5))
    new = segment_interval.all_new(ctx)
    assert len(new) == 3
    assert new[1] == pytest.approx((310.6 * MS, 351.2 * MS))
    t = segment_interval.tile(trace, 1, "^jit_seg", "^jit__fn",
                              "dls/idle_wait", new)
    assert (t["pairs"], t["skipped"]) == (2, 2)
    assert t["prefill"] == pytest.approx(30 * MS)
    assert _read(ctx, "prefill") == pytest.approx(30 / 16)
    # a program that does not say (the parent), or a trace with no
    # marker: the annotation rule alone
    for other in (dict(_ctx(trace), spans=[dict(e, args={}) for e in spans],
                       slice=ctx["slice"]),
                  dict(_ctx(trace), spans=spans, slice=(None, None))):
        assert segment_interval.all_new(other) == []
        assert _read(other, "prefill") == pytest.approx(90 / 24)


def test_idle_goes_to_the_innermost_annotation_that_covers_it(trace):
    """Each idle instant has one name; ``dls/prefill`` nested in the
    engine's ``dls/admit`` is cut out of it."""
    t = segment_interval.tile(trace, 1, "^jit_seg", "^jit__fn",
                              "dls/idle_wait")
    under = t["idle_under"]
    assert sum(under.values()) == pytest.approx(t["idle"])
    # 41.0-41.5: the readback's tail, the fold, the front-end's admit, the
    # chunk's dispatch; 71.5-72.0 and 112-113: waiting in the next readback
    assert under["dls/fold"] == pytest.approx((0.2 + 0.3 + 0.2) * MS)
    assert under["dls/prefill_chunk"] == pytest.approx((0.05 + 0.5) * MS)
    notes = segment_interval._annotations(trace)
    for (_n, _a, end), (_m, start, _b) in zip(notes, notes[1:]):
        assert end <= start
    assert ("dls/prefill", 296 * MS, 310.4 * MS) in notes
    assert [(a, b) for n, a, b in notes if n == "dls/admit" and a >= 295 * MS
            and b <= 311 * MS] == [(295 * MS, 296 * MS),
                                   (310.4 * MS, 310.5 * MS)]


def test_no_pair_of_segment_programs_gives_none(trace):
    one = copy.deepcopy(trace)
    mods = one["planes"][0]["lines"][0]
    mods["events"] = [e for e in mods["events"]
                      if not e[0].startswith("jit_seg")][:1] + [
                          ["jit_seg(11)", 1e6, 4e7]]
    assert _read(_ctx(one), "prefill") is None
    assert _read(_ctx(one), "idle") is None
    assert _read({"trace": None}, "idle") is None
    assert _read({"n_devices": 1}, "idle") is None
    # every pair beside an empty engine: nothing either
    asleep = copy.deepcopy(trace)
    asleep["planes"][1]["lines"][0]["events"].append(
        ["dls/idle_wait", 0.0, 500 * MS])
    assert _read(_ctx(asleep), "prefill") is None


def test_the_tiling_is_computed_once_a_context_and_logged(trace, capsys):
    ctx = _ctx(trace)
    _read(ctx, "prefill")
    _read(ctx, "idle")
    out = capsys.readouterr().out
    assert out.count("benchmark: segment interval:") == 1
    assert "3 pairs (1 with no continuing slot left out)" in out
    assert "idle under dls/segment" in out


# -- the registry readers --------------------------------------------------------


def test_registry_stat_reads_a_tail_of_one_histogram(registry):
    h = registry.histogram("decode.seg_period_ms", unit="ms")
    for v in range(1, 201):
        h.observe(float(v))
    snap = registry.snapshot()["histograms"]["decode.seg_period_ms"]
    params = {"histogram": "decode.seg_period_ms", "stat": "p99"}
    assert registry_stat.read({}, params) == snap["p99"] == 199.0
    assert registry_stat.read({}, dict(params, stat="max", scale=0.5)) == 100.0
    assert registry_stat.read({}, dict(params, histogram="decode.none")) is None
    assert registry_stat.read({}, dict(params, stat="p42")) is None


def test_registry_counter_ratio_is_one_counter_over_another(registry):
    params = {"num": "decode.segments_behind_prefill",
              "den": "decode.segments_continuing"}
    assert registry_counter_ratio.read({}, params) is None
    registry.counter("decode.segments_continuing").inc(8)
    assert registry_counter_ratio.read({}, params) == 0.0
    registry.counter("decode.segments_behind_prefill").inc(6)
    assert registry_counter_ratio.read({}, params) == 0.75


@pytest.mark.parametrize("reader", [registry_stat, registry_counter_ratio])
def test_registry_readers_find_nothing_without_the_registry(
        monkeypatch, reader):
    from distributed_llm_scheduler_tpu import obs

    monkeypatch.delattr(obs, "process_metrics")
    assert reader.read({}, {"histogram": "h", "stat": "p99", "num": "a",
                            "den": "b"}) is None


# -- through the run's own loader --------------------------------------------------


@pytest.mark.parametrize("cell", SERVING)
def test_every_new_entry_resolves_through_the_runs_own_loader(
        cell, trace, registry):
    loaded = harness.load_cell(cell)
    defs = [m for m in loaded.per_layer if m["name"] in NEW]
    assert [m["name"] for m in defs] == NEW
    assert all(m["workloads"] == SERVING and m["moves"] == "tpot_ms_mean"
               for m in defs)
    for v in (7.0, 7.5, 9.0):
        registry.histogram("decode.step_interval_ms").observe(v)
        registry.histogram("decode.seg_period_ms").observe(8 * v)
        registry.counter("decode.segments_continuing").inc()
    registry.counter("decode.segments_behind_prefill").inc(2)
    ctx = dict(_ctx(trace, loaded.config["engine"]["seg_steps"]), spans=[])
    got = {k: v["value"] for k, v in harness.read_metrics(defs, ctx).items()}
    steps = loaded.config["engine"]["seg_steps"]
    assert got == pytest.approx({
        "step_interval_ms": 7.5, "seg_period_ms_p99": 72.0,
        "seg_behind_prefill_share": 2 / 3,
        "prefill_stall_ms_step": 90 / 3 / steps,
        "seg_idle_ms_step": 4 / 3 / steps})
    # a program and a trace with nothing to read: every one is left out
    from distributed_llm_scheduler_tpu import obs

    obs.reset_ambient()
    assert harness.read_metrics(defs, {"spans": [], "trace": None}) == {}


@pytest.mark.parametrize("cell", ["m-dag-1chip", "m-dag-4chip"])
def test_the_dag_cells_list_none_of_them(cell):
    assert not {m["name"] for m in harness.load_cell(cell).per_layer} & set(NEW)


# -- end to end, on the tiny served cell of the end-to-end test --------------------

_spec = importlib.util.spec_from_file_location(
    "_benchmark_end_to_end_for_intervals", Path(__file__).with_name(
        "test_benchmark_end_to_end.py"))
_e2e = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_e2e)
tiny_root = _e2e.tiny_root


def test_traced_serve_cell_prints_what_its_engine_recorded(
        tiny_root, registry, capsys):
    """The tiny checkout with the five entries appended: the engine's
    histograms and counters reach the line of a traced run; on the CPU
    the trace has no module line, and the two device metrics are left
    out without an error."""
    path = tiny_root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    committed = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"] += [dict(m, workloads=["tiny-chat"])
                          for m in committed["per_layer"] if m["name"] in NEW]
    path.write_text(json.dumps(spec))
    assert run.main(["--workload", "tiny-chat", "--seed", "24",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _e2e._last_line(capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW[:3]) <= set(got) and not set(NEW[3:]) & set(got)
    assert 0 < got["step_interval_ms"] <= got["seg_period_ms_p99"]
    assert 0.0 <= got["seg_behind_prefill_share"] <= 1.0
    assert line["metrics"]["seg_behind_prefill_share"]["unit"] == "ratio"

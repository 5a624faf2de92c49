"""What every dispatched segment records about the interval between its
continuing slots' previous delivery and this one: the prefill programs
enqueued ahead of it, the period on the engine's clock, the span
arguments that carry them and the always-on histograms and counters —
on the tiny session engine, on a scripted clock."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from distributed_llm_scheduler_tpu import obs  # noqa: E402
from distributed_llm_scheduler_tpu.obs.trace import Tracer  # noqa: E402
from distributed_llm_scheduler_tpu.serve import VirtualClock  # noqa: E402

HISTS = ("decode.step_interval_ms", "decode.seg_period_ms")


class _TickingClock:
    """Every read moves it on by ``dt``: spans get a length and an order."""

    def __init__(self, dt: float = 1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


@pytest.fixture()
def bound(session_serve_engine):
    """The session engine (4 slots, pages of 8, 4 a request, segments of
    4 steps) in chunked mode on a ticking clock, with or without a
    tracer; the process-wide registry emptied before and after."""
    eng = session_serve_engine
    obs.reset_ambient()

    def bind(traced):
        clk = _TickingClock()
        tr = Tracer(clock=clk) if traced else None
        eng.rebind_obs(clock=clk, tracer=tr)
        eng.chunk_tokens = 8
        return eng, tr, clk

    yield bind
    eng.chunk_tokens = None
    eng.rebind_obs(clock=VirtualClock())
    obs.reset_ambient()


def _prompt(n, seed):
    return jnp.asarray(
        np.random.RandomState(seed).randint(1, 50, size=(1, n)), jnp.int32)


def _chunk_between_segments(eng):
    """``a`` (8 tokens in, 16 out) decodes through five segments; ``long``
    (24 in: three chunks of 8; 8 out) arrives after the first, so one of
    its chunks is enqueued ahead of each of the next three."""
    eng.submit("a", _prompt(8, 1), 16)
    eng.step_segment()
    eng.submit("long", _prompt(24, 2), 8)
    while eng._slot_req.count(None) < eng.slots or eng._queue:
        eng.step_segment()
    assert eng.results["a"].size == 16 and eng.results["long"].size == 8


def _spans(tr, name):
    return [e for e in tr.events if e["type"] == "span"
            and e["track"] == "decode" and e["name"] == name]


def test_a_chunk_between_two_segments_is_on_the_later_segments_span(bound):
    eng, tr, clk = bound(True)
    _chunk_between_segments(eng)
    segs, chunks = _spans(tr, "segment"), _spans(tr, "prefill_chunk")
    assert len(segs) == 5 and len(chunks) == 3
    args = [s["args"] for s in segs]
    assert [a["seq"] for a in args] == [0, 1, 2, 3, 4]
    # the span that pays for a chunk's device time is the segment it ran
    # ahead of: the chunk names it
    assert [c["args"]["seq"] for c in chunks] == [1, 2, 3]
    assert all(c["t1"] <= segs[c["args"]["seq"]]["t0"] for c in chunks)
    # the first segment ran behind a's own whole-prompt wave
    assert [a["prefill_programs_ahead"] for a in args] == [1, 1, 1, 1, 0]
    assert [a["prefill_tokens_ahead"] for a in args] == [8, 8, 8, 8, 0]
    # a continues through segments 1-3, long (first decoded in 3) into 4
    assert [a["continuing"] for a in args] == [0, 1, 1, 1, 1]
    assert [a["steps_ran"] for a in args] == [4, 4, 4, 4, 3]
    assert all(a["steps"] == 4 and a["active"] >= 1 for a in args)
    assert "period_s" not in args[0]
    for prev, seg in zip(segs, segs[1:]):
        assert seg["args"]["period_s"] == pytest.approx(
            seg["t1"] - prev["t1"], abs=1e-12)
    assert all(a["period_s"] > 0 for a in args[1:])


@pytest.mark.parametrize("traced", [False, True])
def test_registries_hold_one_sample_a_continuing_segment(bound, traced):
    eng, tr, _clk = bound(traced)
    assert (eng.tracer is not None) == traced
    _chunk_between_segments(eng)
    for reg in (eng.metrics, obs.process_metrics()):
        snap = reg.snapshot()
        assert [snap["histograms"][h]["count"] for h in HISTS] == [4, 4]
        assert all(snap["histograms"][h]["unit"] == "ms" for h in HISTS)
        assert snap["counters"]["decode.segments_continuing"]["value"] == 4
        assert snap["counters"]["decode.segments_behind_prefill"]["value"] == 3
        period = reg.histogram("decode.seg_period_ms")
        step = reg.histogram("decode.step_interval_ms")
        # four steps ran in three of the four, three in the last
        assert period.sum / 4 < step.sum < period.sum / 3
        assert step.min > 0
    if traced:
        periods = [s["args"]["period_s"] * 1e3
                   for s in _spans(tr, "segment") if "period_s" in s["args"]]
        assert eng.metrics.histogram("decode.seg_period_ms").sum == (
            pytest.approx(sum(periods)))


def test_no_period_across_an_emptied_engine_or_for_new_slots_only(bound):
    eng, tr, clk = bound(True)
    eng.submit("a", _prompt(8, 3), 5)      # 1 + 4: retires in its segment
    eng.step_segment()
    assert eng._slot_req.count(None) == eng.slots
    clk.t += 30.0                          # the engine sits empty
    eng.submit("b", _prompt(8, 4), 9)      # 1 + 4 + 4
    eng.submit("c", _prompt(16, 5), 5)     # chunked: decodes from segment 2
    eng.run()
    args = [s["args"] for s in _spans(tr, "segment")]
    assert [a["continuing"] for a in args] == [0, 0, 1]
    assert ["period_s" in a for a in args] == [False, False, True]
    assert args[2]["period_s"] < 1.0
    assert eng.metrics.histogram("decode.seg_period_ms").count == 1
    # a preempted slot taken by another request is no continuing slot
    eng.reset()
    eng.submit("d", _prompt(8, 6), 12)
    eng.step_segment()
    eng.preempt("d")
    eng.submit("e", _prompt(8, 7), 6)
    eng.run()
    assert eng.metrics.histogram("decode.seg_period_ms").count == 2
    assert [s["args"]["continuing"] for s in _spans(tr, "segment")][3:] == [
        0, 0, 1]


def test_without_a_tracer_no_tracer_method_is_reachable(bound, monkeypatch):
    """The recording that is always on is plain arithmetic and registry
    calls; every ``Tracer`` method stays behind the ``None`` guard."""
    def unreachable(*_a, **_k):
        raise AssertionError("a Tracer method ran with no tracer attached")

    for name in ("begin", "end", "span", "complete", "instant", "counter",
                 "flow"):
        monkeypatch.setattr(Tracer, name, unreachable)
    eng, _tr, _clk = bound(False)
    assert eng.tracer is None and eng.reqtrace is None
    _chunk_between_segments(eng)
    assert eng.metrics.histogram("decode.step_interval_ms").count == 4
    assert obs.process_metrics().counter(
        "decode.segments_behind_prefill").value == 3

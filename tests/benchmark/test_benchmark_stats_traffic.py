"""The arithmetic from rows to numbers, and the traffic generator."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import stats  # noqa: E402
from benchmark.traffic import closed_loop, open_loop  # noqa: E402


@pytest.mark.parametrize("vals,q,want", [
    ([], 90, None), ([5.0], 90, 5.0), ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6), ([10, 0], 25, 2.5),
    (list(range(101)), 90, 90.0),
])
def test_percentile_matches_numpy(vals, q, want):
    got = stats.percentile(vals, q)
    assert got == (None if want is None else pytest.approx(want))
    if vals:
        assert got == pytest.approx(float(np.percentile(vals, q)))


def _rows():
    arrivals = [NS(rid="a", t=0.0, prompt_len=10, max_new_tokens=5),
                NS(rid="b", t=1.0, prompt_len=10, max_new_tokens=3),
                NS(rid="shed", t=2.0, prompt_len=10, max_new_tokens=4),
                NS(rid="short", t=2.5, prompt_len=10, max_new_tokens=4)]
    rows = [
        {"rid": "a", "state": "retired", "t_admit": 100.1, "t_retire": 101.0,
         "n_tokens": 5, "deliveries": [[100.2, 1], [100.6, 2], [101.0, 2]]},
        {"rid": "b", "state": "retired", "t_admit": 101.0, "t_retire": 109.0,
         "n_tokens": 3, "deliveries": [[101.4, 1], [109.0, 2]]},
        {"rid": "shed", "state": "shed", "t_admit": None, "t_retire": None,
         "n_tokens": 0, "deliveries": []},
        {"rid": "short", "state": "retired", "t_admit": 102.6,
         "t_retire": 103.0, "n_tokens": 2,
         "deliveries": [[102.8, 1], [103.0, 1]]},
    ]
    first = {"a": 100.3, "b": 101.5, "short": 102.9}
    return stats.request_records(arrivals, rows, first, 100.0, 110.0)


def test_request_records_ttft_tpot_and_failures():
    a, b, shed, short = _rows()
    # TTFT is anchored at the due time and ends at the harness's stamp
    assert a["ttft_ms"] == pytest.approx(300.0)
    assert b["ttft_ms"] == pytest.approx(500.0)
    # TPOT: retire minus the row's first delivery over the other tokens
    assert a["tpot_ms"] == pytest.approx((101.0 - 100.2) / 4 * 1e3)
    assert b["tpot_ms"] == pytest.approx((109.0 - 101.4) / 2 * 1e3)
    assert a["queue_wait_ms"] == pytest.approx(100.0)
    assert not a["failed"] and not b["failed"]
    # a shed request is ranked with the time it had waited at the end of
    # the run (finite), fails, and has no TPOT or queue wait
    assert shed["failed"] and shed["ttft_ms"] == pytest.approx(8000.0)
    assert shed["tpot_ms"] is None and shed["queue_wait_ms"] is None
    # a request that retired with fewer tokens than it asked for fails
    assert short["failed"] and short["tpot_ms"] is None


def test_shed_request_ranks_last_in_the_percentile():
    recs = _rows()
    vals = [r["ttft_ms"] for r in recs]
    assert stats.percentile(vals, 100) == pytest.approx(8000.0)
    assert sum(r["failed"] for r in recs) == 2


def test_tokens_in_window_uses_the_stamp_for_the_first_token():
    recs = _rows()
    # window [100, 101.45]: a's first (100.3) + 2 + 2, b's row says 101.4
    # but the harness saw its first token at 101.5 — outside
    assert stats.tokens_in_window(recs, 100.0, 101.45) == 5
    assert stats.tokens_in_window(recs, 100.0, 110.0) == 5 + 3 + 2


@pytest.mark.parametrize("t_cut,ttft,tpot", [
    (None, {"a", "b", "shed", "short"}, {"a", "b"}),   # no profiler
    (105.0, {"a", "b", "short"}, {"a"}),   # b retires at 109: after
    (100.3, set(), set()),                 # a's first token is at the cut
])
def test_traced_run_reads_only_what_closed_before_the_profiler(
        t_cut, ttft, tpot):
    recs = _rows()
    cut = stats.closed_before(recs, t_cut)
    assert {r["rid"] for r in cut if r["ttft_ms"] is not None} == ttft
    assert {r["rid"] for r in cut if r["tpot_ms"] is not None} == tpot
    # the records of the run itself are left as they were
    assert [r["ttft_ms"] for r in recs] == [r["ttft_ms"] for r in _rows()]


def test_lateness():
    out = stats.lateness_ms([(1.0, 1.002), (2.0, 2.010), (3.0, 3.004)])
    assert out["n"] == 3
    assert out["p50_ms"] == pytest.approx(4.0)
    assert out["max_ms"] == pytest.approx(10.0)


CHAT = {"prompt_len": {"dist": "log_uniform", "lo": 129, "hi": 384},
        "output_len": {"dist": "log_uniform", "lo": 64, "hi": 512},
        "max_total": 896}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**33 + 1])
def test_traffic_is_a_pure_function_of_the_seed(seed):
    a = open_loop.generate(CHAT, 3.0, 30.0, seed)
    b = open_loop.generate(CHAT, 3.0, 30.0, seed)
    assert a == b
    assert len(a) == 90
    assert all(0.0 <= r.t < 30.0 for r in a)
    assert [r.t for r in a] == sorted(r.t for r in a)
    assert all(129 <= r.prompt_len <= 384 for r in a)
    assert all(64 <= r.max_new_tokens <= 512 for r in a)
    assert all(r.prompt_len + r.max_new_tokens <= 896 for r in a)
    assert len({r.rid for r in a}) == len(a)


def test_every_seed_offers_the_same_work_in_another_order():
    a = open_loop.generate(CHAT, 3.0, 30.0, 1)
    b = open_loop.generate(CHAT, 3.0, 30.0, 2)
    assert a != b
    for f in ("prompt_len", "max_new_tokens"):
        assert sorted(getattr(r, f) for r in a) == sorted(
            getattr(r, f) for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.t for r in rs]), 9))
    assert gaps(a) == gaps(b)


def test_traffic_refuses_a_request_over_the_total():
    with pytest.raises(ValueError, match="max_total"):
        open_loop.generate(dict(CHAT, max_total=500), 3.0, 30.0, 1)


def test_prompt_tokens_are_seeded_and_avoid_padding():
    a = open_loop.prompt_token_ids("r3", 200, 50257, 2**31 + 5)
    assert a.shape == (1, 200) and a.dtype == np.int32
    assert (a == open_loop.prompt_token_ids("r3", 200, 50257, 2**31 + 5)).all()
    assert (a != open_loop.prompt_token_ids("r4", 200, 50257, 2**31 + 5)).any()
    assert (a != open_loop.prompt_token_ids("r3", 200, 50257, 6)).any()
    assert a.min() >= 1 and a.max() < 50257


def test_closed_loop_ids():
    tr = {"batch": 8, "seq_len": 512}
    a = closed_loop.input_ids(tr, 50257, 2**31 + 9)
    assert a.shape == (8, 512)
    assert (a == closed_loop.input_ids(tr, 50257, 2**31 + 9)).all()
    assert (a != closed_loop.input_ids(tr, 50257, 10)).any()

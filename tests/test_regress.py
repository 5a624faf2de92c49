"""Perf-regression gate (eval/regress.py): direction-aware metric
comparison, inclusive tolerance edges, missing-leg failures, artifact
unwrapping, and the `regress` CLI's exit codes against the committed
baseline."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from distributed_llm_scheduler_tpu.eval.regress import (
    DEFAULT_METRICS,
    compare_artifacts,
    load_artifact,
    parse_tolerances,
)

BASE = {
    "metric": "makespan",
    "value": 100.0,
    "vs_baseline": 1.5,
    "dispatch_overhead": 0.2,
    "peak_hbm_gb_modeled": 4.0,
    "kv_pages_peak": 4,
    "mfu_single_chip": 0.30,
    "oracle_ok": True,
    "serve.goodput_tok_s": 200.0,
    "serve.ttft_p99_ms": 130.0,
    "serve.queue_wait_p95_ms": 120.0,
    "serve.attribution.max_residual_s": 0.0,
    "serve.prefix.goodput_tok_s": 165.2,
    "serve.prefix.ttft_p99_ms": 167.6,
    "serve.prefix.goodput_gain": 1.6,
    "serve.prefix.shared_page_hits": 25,
    "serve.prefix.pages_leaked": 0,
    "serve.chunked.tpot_p99_ms": 91.4,
    "serve.chunked.ttft_p99_ms": 3397.6,
    "serve.chunked.goodput_tok_s": 36.3,
    "serve.chunked.tpot_p99_gain": 1.41,
    "serve.chunked.token_parity": True,
    "serve.chunked.pages_leaked": 0,
    "fleet.goodput_tok_s": 335.5,
    "fleet.goodput_gain_vs_rr": 3.52,
    "fleet.drains": 1,
    "fleet.restarts": 1,
    "fleet.pages_leaked": 0,
    "fleet.healthy_drains": 0,
    "fleet.deterministic": True,
    "decode.paged_tokens_exact": True,
    "decode.pages_leaked": 0,
    "decode.kernel_tokens_exact": True,
    "decode.kernel_parity_ok": True,
    "decode.kernel_pages_leaked": 0,
    "search.makespan_ms": 1.6768,
    "search.replay_ms": 1.6768,
    "search.margin_vs_hand_pct": 0.65,
    "search.ici_slow_margin_pct": 0.66,
    "search.ici_fast_margin_pct": 0.64,
    "search.beats_hand": True,
    "search.beats_ici_extreme": True,
    "search.placement_digest": "d0f9c4",
}


def _fresh(**overrides):
    out = dict(BASE)
    out.update(overrides)
    return out


# ---------------------------------------------------------------------------
# compare_artifacts


def test_self_compare_passes_by_construction():
    v = compare_artifacts(BASE, BASE)
    assert v.ok and v.exit_code == 0 and not v.failures()
    assert {c.metric for c in v.checks} == set(DEFAULT_METRICS)
    assert all(c.status == "ok" for c in v.checks)


def test_makespan_regression_fails():
    v = compare_artifacts(_fresh(value=120.0), BASE)  # +20% > 10% tol
    assert not v.ok and v.exit_code == 1
    (bad,) = v.failures()
    assert bad.metric == "value" and bad.status == "regressed"
    assert bad.to_json()["ratio"] == pytest.approx(1.2)


def test_direction_awareness():
    # lower-is-better metric dropping is an improvement...
    v = compare_artifacts(_fresh(value=50.0), BASE)
    assert v.ok
    assert {c.status for c in v.checks if c.metric == "value"} == {"improved"}
    # ...while a higher-is-better metric dropping the same way regresses
    v2 = compare_artifacts(_fresh(mfu_single_chip=0.15), BASE)
    assert not v2.ok
    (bad,) = v2.failures()
    assert bad.metric == "mfu_single_chip"


def test_tolerance_edge_is_inclusive():
    # landing exactly on baseline * (1 + tol) is still ok
    v = compare_artifacts(_fresh(value=110.0), BASE)
    assert {c.status for c in v.checks if c.metric == "value"} == {"ok"}
    v2 = compare_artifacts(_fresh(value=110.0 + 1e-6), BASE)
    assert not v2.ok


def test_per_metric_tolerance_overrides_default():
    fresh = _fresh(value=120.0)
    assert not compare_artifacts(fresh, BASE).ok
    assert compare_artifacts(fresh, BASE, tolerances={"value": 0.25}).ok
    # a global loosening does the same
    assert compare_artifacts(fresh, BASE, default_tolerance=0.25).ok


def test_missing_metric_is_a_failure_not_a_pass():
    fresh = dict(BASE)
    del fresh["peak_hbm_gb_modeled"]
    v = compare_artifacts(fresh, BASE)
    assert not v.ok
    (bad,) = v.failures()
    assert bad.metric == "peak_hbm_gb_modeled" and bad.status == "missing"
    assert bad.fresh is None
    # ... and a None value counts as missing too
    v2 = compare_artifacts(_fresh(dispatch_overhead=None), BASE)
    assert v2.failures()[0].status == "missing"


def test_bool_metric_flip():
    v = compare_artifacts(_fresh(oracle_ok=False), BASE)
    assert not v.ok
    (bad,) = v.failures()
    assert bad.metric == "oracle_ok" and bad.status == "regressed"
    # false -> true reads as improvement
    base = dict(BASE, oracle_ok=False)
    v2 = compare_artifacts(_fresh(oracle_ok=True), base)
    assert v2.ok
    assert {c.status for c in v2.checks if c.metric == "oracle_ok"} \
        == {"improved"}


def test_metrics_narrows_the_comparison():
    v = compare_artifacts(_fresh(value=500.0), BASE,
                          metrics=["mfu_single_chip"])
    assert v.ok and [c.metric for c in v.checks] == ["mfu_single_chip"]
    # metrics absent from the baseline are silently not checked
    v2 = compare_artifacts(BASE, BASE, metrics=["no_such_metric"])
    assert v2.checks == []


def test_verdict_render_and_json():
    v = compare_artifacts(_fresh(value=120.0, mfu_single_chip=0.5), BASE)
    text = v.render()
    assert "regress: FAIL" in text and "[!] value" in text
    assert "[+] mfu_single_chip" in text
    blob = json.loads(json.dumps(v.to_json()))
    assert blob["ok"] is False and blob["n_regressed"] == 1
    ok_text = compare_artifacts(BASE, BASE).render()
    assert "regress: PASS" in ok_text


# ---------------------------------------------------------------------------
# artifact loading + tolerance parsing


def test_load_artifact_unwraps_driver_capture(tmp_path):
    wrapped = {"n": 5, "cmd": "bench", "rc": 0, "parsed": dict(BASE)}
    p = tmp_path / "wrapped.json"
    p.write_text(json.dumps(wrapped))
    assert load_artifact(str(p)) == BASE
    # a flat artifact (has "metric") passes through untouched
    q = tmp_path / "flat.json"
    q.write_text(json.dumps(BASE))
    assert load_artifact(str(q)) == BASE
    with pytest.raises(ValueError):
        load_artifact([1, 2, 3])


def test_parse_tolerances():
    assert parse_tolerances(["value=0.25", " mfu_single_chip =0.5"]) == {
        "value": 0.25, "mfu_single_chip": 0.5,
    }
    with pytest.raises(ValueError):
        parse_tolerances(["value:0.25"])


# ---------------------------------------------------------------------------
# CLI wiring against the committed baseline


def test_regress_cli_baseline_self_compare_and_injected_regression(
    tmp_path, capsys,
):
    from distributed_llm_scheduler_tpu.__main__ import main

    baseline = str(Path(__file__).resolve().parents[1] / "BENCH_MEDIUM_r05.json")
    rc = main(["regress", "--fresh", baseline, "--baseline", baseline])
    assert rc == 0
    assert "regress: PASS" in capsys.readouterr().out

    hurt = load_artifact(baseline)
    hurt["value"] = hurt["value"] * 1.2  # the acceptance-criteria injection
    p = tmp_path / "fresh.json"
    p.write_text(json.dumps(hurt))
    rc = main(["regress", "--fresh", str(p), "--baseline", baseline,
               "--json"])
    assert rc == 1
    blob = json.loads(capsys.readouterr().out)
    assert blob["ok"] is False
    assert any(
        c["metric"] == "value" and c["status"] == "regressed"
        for c in blob["checks"]
    )


def test_regress_cli_bad_inputs_are_usage_errors(tmp_path, capsys):
    from distributed_llm_scheduler_tpu.__main__ import main

    baseline = str(Path(__file__).resolve().parents[1] / "BENCH_MEDIUM_r05.json")
    rc = main(["regress", "--fresh", "no_such.json",
               "--baseline", baseline])
    assert rc == 2
    rc = main(["regress", "--fresh", baseline, "--baseline", baseline,
               "--tolerance", "value:0.5"])
    assert rc == 2
    capsys.readouterr()

"""Wiring tests for eval/capture_artifacts: the one-shot artifact pass
must place correctly-named files at the repo root, stamp platform/round,
and FAIL when a leg fails — no error stub is written that could later be
read as a record."""

import json

import pytest

from distributed_llm_scheduler_tpu.eval import capture_artifacts as ca


def test_capture_writes_stamped_artifacts(tmp_path, monkeypatch):
    monkeypatch.setattr(ca, "REPO_ROOT", str(tmp_path))
    monkeypatch.setitem(
        ca.LEGS, "stream", ("STREAM", lambda: {"slowdown": 2.0})
    )
    rc = ca.main(["7", "stream"])
    assert rc == 0
    path = tmp_path / "STREAM_r07.json"
    data = json.loads(path.read_text())
    assert data["slowdown"] == 2.0
    assert data["round"] == 7
    assert data["platform"]  # stamped from the live jax platform
    assert data["capture_wall_s"] >= 0


def test_capture_failing_leg_fails_the_pass(tmp_path, monkeypatch):
    def boom():
        raise RuntimeError("leg died")

    monkeypatch.setattr(ca, "REPO_ROOT", str(tmp_path))
    monkeypatch.setitem(ca.LEGS, "decode", ("DECODE", boom))
    with pytest.raises(RuntimeError, match="leg died"):
        ca.main(["4", "decode"])
    # nothing on disk stands in for the measurement that did not happen
    assert list(tmp_path.iterdir()) == []


def test_capture_rejects_bad_args(tmp_path, monkeypatch):
    monkeypatch.setattr(ca, "REPO_ROOT", str(tmp_path))
    assert ca.main([]) == 2
    assert ca.main(["x"]) == 2
    assert ca.main(["4", "nosuchleg"]) == 2
    assert list(tmp_path.iterdir()) == []


def test_measure_decode_dag_llama_family():
    """The decode perf probe is family-generic: the llama backbone (GQA
    cache layout, RoPE at the traced position) must satisfy the same
    logits oracle through the scheduler."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import (
        measure_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.llama import LlamaConfig

    r = measure_decode_dag(
        LlamaConfig.tiny(), batch=2, prompt_len=16, new_tokens=3, reps=2
    )
    assert r["family"] == "llama"
    assert r["oracle_ok"]
    assert r["token_agreement"] == 1.0
    assert r["graph_classes_compiled"] == 2

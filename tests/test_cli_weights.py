"""CLI `execute --weights`: pretrained checkpoint -> scheduled execution."""

import json
import os
import subprocess
import sys

import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _donor_file(tmp_path, n_embd=128):
    hf = transformers.GPT2Config(
        vocab_size=512, n_positions=128, n_embd=n_embd,
        n_layer=2, n_head=4,
    )
    model = transformers.GPT2LMHeadModel(hf)
    path = str(tmp_path / "donor.pt")
    torch.save(model.state_dict(), path)
    return path


def _run_execute(weights_path):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "execute",
         "--model", "gpt2-tiny", "--weights", weights_path,
         "--batch", "1", "--seq-len", "16"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )


def test_execute_with_pretrained_weights(tmp_path):
    r = _run_execute(_donor_file(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    # 28 mapped params for 2 layers: wte, wpe, 12 x 2 per-layer, ln_f g+b
    # (the donor's tied lm_head and mask buffers are dropped by the map)
    assert "loaded 28 params" in r.stderr
    report = json.loads(r.stdout[r.stdout.index("{"):])
    assert report["makespan_ms"] > 0
    assert report["n_devices"] == 8


def test_execute_rejects_mismatched_weights(tmp_path):
    """A checkpoint with the wrong width must fail loudly (shape check in
    frontend/pretrained.py), not run with silently-wrong weights."""
    r = _run_execute(_donor_file(tmp_path, n_embd=64))
    assert r.returncode == 2  # clean CLI error, not a traceback
    assert "shape mismatch" in r.stderr
    assert "Traceback" not in r.stderr


def test_execute_missing_weights_file(tmp_path):
    r = _run_execute(str(tmp_path / "nope.pt"))
    assert r.returncode == 2
    assert "Traceback" not in r.stderr

"""CLI `generate`: autoregressive decoding end-to-end, incl. --weights."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout=300):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "generate",
         *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )


def test_generate_greedy_tiny():
    r = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
             "--max-new-tokens", "4")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["prompt_ids"] == [5, 6, 7]
    assert len(out["generated_ids"]) == 4
    assert all(0 <= t < 512 for t in out["generated_ids"])


def test_generate_rejects_bad_prompt():
    r = _run("--model", "gpt2-tiny", "--prompt-ids", "5,notanint")
    assert r.returncode == 2
    r = _run("--model", "gpt2-tiny", "--prompt-ids", "99999")
    assert r.returncode == 2  # out of tiny vocab range


def test_generate_weights_missing_file():
    r = _run("--model", "mixtral-tiny", "--weights", "/nonexistent.pt")
    assert r.returncode == 2  # supported family, missing file
    assert "/nonexistent.pt" in r.stderr


def test_execute_rejects_weights_for_synthetic_model():
    """The execute-side fail-fast gate for families without an HF map."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "execute",
         "--model", "llm", "--weights", "/nonexistent.pt",
         "--batch", "1", "--seq-len", "16"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert r.returncode == 2
    assert "families" in r.stderr


def test_generate_with_llama_weights(tmp_path):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf = transformers.LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, rms_norm_eps=1e-5, max_position_embeddings=128,
        attention_bias=False, tie_word_embeddings=False,
    )
    donor = transformers.LlamaForCausalLM(hf)
    path = str(tmp_path / "llama_donor.pt")
    torch.save(donor.state_dict(), path)
    r = _run("--model", "llama-tiny", "--weights", path,
             "--prompt-ids", "1,2,3", "--max-new-tokens", "3")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["generated_ids"]) == 3


def test_generate_with_pretrained_weights(tmp_path):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf = transformers.GPT2Config(
        vocab_size=512, n_positions=128, n_embd=128, n_layer=2, n_head=4
    )
    model = transformers.GPT2LMHeadModel(hf)
    path = str(tmp_path / "donor.pt")
    torch.save(model.state_dict(), path)
    r = _run("--model", "gpt2-tiny", "--weights", path,
             "--prompt-ids", "1,2,3", "--max-new-tokens", "3")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["generated_ids"]) == 3
    # greedy decoding of the donor's weights is deterministic: re-running
    # must reproduce the same tokens
    r2 = _run("--model", "gpt2-tiny", "--weights", path,
              "--prompt-ids", "1,2,3", "--max-new-tokens", "3")
    assert json.loads(r2.stdout.strip().splitlines()[-1]) == out


def test_execute_inject_failure_recovers():
    """CLI fault injection: kill a node mid-run, recover on survivors."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "execute",
         "--model", "gpt2-tiny", "--num-nodes", "4", "--scheduler", "pack",
         "--batch", "1", "--seq-len", "16",
         "--inject-failure", "1:0.4"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=400,
    )
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    rec = out["recovery"]
    assert rec["output_matches_uninterrupted"] is True
    assert rec["rerun_tasks"] > 0
    assert rec["reused_outputs"] > 0


def test_execute_inject_failure_rejects_unknown_node():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "execute",
         "--model", "gpt2-tiny", "--num-nodes", "4",
         "--batch", "1", "--seq-len", "16",
         "--inject-failure", "nope"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=400,
    )
    assert r.returncode == 2
    assert "unknown node" in r.stderr


def test_execute_inject_failure_full_completion_edge():
    """FRAC=1.0: everything completed before the failure; only the dead
    node's (lost) outputs re-run, and verification uses the retained final
    output when the final task survived."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "execute",
         "--model", "gpt2-tiny", "--num-nodes", "4", "--scheduler", "pack",
         "--batch", "1", "--seq-len", "16",
         "--inject-failure", "1:1.0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=400,
    )
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout)["recovery"]
    assert rec["output_matches_uninterrupted"] is True


def test_generate_task_graph_matches_whole_program():
    """--task-graph routes generation through per-step decode DAGs placed
    by the scheduler; greedy tokens must equal the whole-program path."""
    plain = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "3", timeout=400,
    )
    assert plain.returncode == 0, plain.stderr
    tg = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "3", "--task-graph", "--scheduler", "mru",
        "--num-nodes", "4", timeout=400,
    )
    assert tg.returncode == 0, tg.stderr
    a = json.loads(plain.stdout)
    b = json.loads(tg.stdout)
    assert b["task_graph"] is True
    assert a["generated_ids"] == b["generated_ids"]


def test_generate_task_graph_loop_steps_matches():
    """--loop-steps folds decode windows into one dispatched program per
    window (backends/decode_loop); tokens must equal the whole-program
    path, including a ragged tail window (6 tokens = 1 prefill + windows
    2 + 2 + 1)."""
    plain = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "6", timeout=400,
    )
    assert plain.returncode == 0, plain.stderr
    looped = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "6", "--task-graph", "--scheduler", "heft",
        "--num-nodes", "1", "--loop-steps", "2", timeout=400,
    )
    assert looped.returncode == 0, looped.stderr
    a = json.loads(plain.stdout)
    b = json.loads(looped.stdout)
    assert b["loop_steps"] == 2 and b["task_graph"] is True
    assert a["generated_ids"] == b["generated_ids"]


def test_loop_steps_requires_task_graph():
    r = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
             "--loop-steps", "4")
    assert r.returncode == 2
    assert "--task-graph" in r.stderr


def test_loop_steps_rejects_nonpositive():
    r = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
             "--task-graph", "--loop-steps", "0")
    assert r.returncode == 2
    assert ">= 1" in r.stderr


def test_task_graph_zero_new_tokens():
    """--max-new-tokens 0 returns empty ids on both task-graph paths
    (the loop path must not enter a negative-length window)."""
    for extra in ([], ["--loop-steps", "2"]):
        r = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
                 "--max-new-tokens", "0", "--task-graph", *extra,
                 timeout=400)
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout)["generated_ids"] == []


def test_generate_quantized_weights():
    """--quantize int8 decodes on dequant-shimmed int8 weights; at f32
    tiny scale greedy tokens equal the fp path (no near-ties to flip)."""
    fp = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
              "--max-new-tokens", "4")
    assert fp.returncode == 0, fp.stderr
    q = _run("--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
             "--max-new-tokens", "4", "--quantize", "int8")
    assert q.returncode == 0, q.stderr
    a, b = json.loads(fp.stdout), json.loads(q.stdout)
    assert b["weights"] == "int8"
    assert len(b["generated_ids"]) == 4
    assert a["generated_ids"] == b["generated_ids"]


def test_generate_quantized_task_graph_paths_agree():
    """--quantize int8 composes with --task-graph: the per-token and
    looped dispatch modes run the SAME channel-quantized weights, so
    their tokens must match exactly on the CPU mesh."""
    per_tok = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "4", "--task-graph", "--scheduler", "heft",
        "--num-nodes", "1", "--quantize", "int8", timeout=400,
    )
    assert per_tok.returncode == 0, per_tok.stderr
    looped = _run(
        "--model", "gpt2-tiny", "--prompt-ids", "5,6,7",
        "--max-new-tokens", "4", "--task-graph", "--scheduler", "heft",
        "--num-nodes", "1", "--quantize", "int8", "--loop-steps", "2",
        timeout=400,
    )
    assert looped.returncode == 0, looped.stderr
    a, b = json.loads(per_tok.stdout), json.loads(looped.stdout)
    assert a["weights"] == b["weights"] == "int8"
    assert len(a["generated_ids"]) == 4
    assert a["generated_ids"] == b["generated_ids"]

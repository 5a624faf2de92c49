"""CLI `train` on the MoE family: dp x ep expert parallelism, dense and
routed dispatch, from the command line."""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout=400):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "train",
         *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )


def _losses(stdout):
    return [float(m) for m in re.findall(r"loss (\d+\.\d+)", stdout)]


def test_train_moe_routed_loss_decreases():
    r = _run("--model", "mixtral-tiny", "--steps", "3", "--seq-len", "16",
             "--routed")
    assert r.returncode == 0, r.stderr
    assert "routed" in r.stderr and "ep=" in r.stderr
    losses = _losses(r.stdout)
    assert len(losses) == 3 and losses[-1] < losses[0], r.stdout


def test_train_moe_dense():
    r = _run("--model", "mixtral-tiny", "--steps", "2", "--seq-len", "16")
    assert r.returncode == 0, r.stderr
    assert "dense dispatch" in r.stderr
    assert len(_losses(r.stdout)) == 2


def test_train_moe_rejects_pp():
    r = _run("--model", "mixtral-tiny", "--pp", "2")
    assert r.returncode == 2
    assert "MoE path trains dp x ep" in r.stderr

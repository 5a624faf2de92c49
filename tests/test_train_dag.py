"""Training-step DAG: fwd+bwd+optimizer as tasks (BASELINE.json config #5
at test scale)."""

import jax
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, get_scheduler
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import execute_dag_locally
from distributed_llm_scheduler_tpu.frontend.train_dag import build_gpt2_train_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


@pytest.fixture(scope="module")
def tiny_train():
    return build_gpt2_train_dag(GPT2Config.tiny(), batch=2, seq_len=16, lr=1e-2)


def test_structure(tiny_train):
    g = tiny_train.graph
    L = tiny_train.config.n_layer
    assert len(g) == 3 * L + 7
    # backward edges invert the forward chain
    assert f"layer_{L-1}_fwd" in g["head_bwd"].dependencies
    assert "head_bwd" in g[f"layer_{L-1}_bwd"].dependencies
    assert f"layer_1_bwd" in g["layer_0_bwd"].dependencies
    # remat: bwd needs the layer's params again
    assert g["layer_0_bwd"].params_needed == g["layer_0_fwd"].params_needed
    # fwd activations are consumed by the *distant* bwd task
    assert "layer_0_fwd" in g["layer_1_bwd"].dependencies


def test_one_step_matches_value_and_grad(tiny_train):
    """DAG execution of the step == fused jax.value_and_grad + SGD."""
    params = tiny_train.init_params()
    inputs = tiny_train.make_inputs()
    got = execute_dag_locally(tiny_train, params, inputs)
    want = jax.jit(tiny_train.reference_forward)(params, inputs)
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-5)
    assert set(got["params"]) == set(want["params"]) == set(params)
    for k in want["params"]:
        np.testing.assert_allclose(
            np.asarray(got["params"][k]), np.asarray(want["params"][k]),
            rtol=2e-4, atol=2e-5, err_msg=k,
        )
    # and the step actually moved the weights
    assert not np.allclose(np.asarray(got["params"]["wte"]),
                           np.asarray(params["wte"]))


def test_loss_decreases_over_steps(tiny_train):
    """Two chained DAG steps on the same batch reduce the loss."""
    params = tiny_train.init_params()
    inputs = tiny_train.make_inputs()
    out1 = execute_dag_locally(tiny_train, params, inputs)
    out2 = execute_dag_locally(tiny_train, out1["params"], inputs)
    assert float(out2["loss"]) < float(out1["loss"])


def test_all_policies_schedule_train_dag(tiny_train):
    g = tiny_train.graph
    cluster = Cluster([DeviceState(f"d{i}", 2.0) for i in range(4)])
    for name in ("roundrobin", "dfs", "greedy", "critical", "mru", "heft"):
        s = get_scheduler(name).schedule(g, cluster)
        assert not s.failed, (name, sorted(s.failed)[:3])


def test_activation_memory_pressure_favors_mru(tiny_train):
    """Under tight memory the training DAG's double param use (fwd + remat
    bwd) makes eviction-aware placement the only one that completes."""
    g = tiny_train.graph
    need = g.total_param_gb()
    results = {}
    for name in ("mru", "critical", "roundrobin"):
        cluster = Cluster([DeviceState(f"d{i}", need * 0.42) for i in range(2)])
        s = get_scheduler(name).schedule(g, cluster)
        results[name] = len(s.completed) / len(g)
    assert results["mru"] >= max(results.values()) - 1e-9


def test_train_dag_executes_on_placed_devices(tiny_train):
    """The whole fwd+bwd+opt step runs through DeviceBackend on a
    multi-device mesh with loss and updated params matching local
    execution."""
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend

    params = tiny_train.init_params()
    inputs = tiny_train.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:4], hbm_cap_gb=2.0)
    local = execute_dag_locally(tiny_train, params, inputs)
    for pol in ("mru", "heft"):
        s = get_scheduler(pol).schedule(tiny_train.graph, cluster)
        assert not s.failed, pol
        rep = DeviceBackend(cluster).execute(
            tiny_train.graph, s, params, inputs
        )
        assert rep.transfer_edges > 0  # the step actually spread
        np.testing.assert_allclose(
            float(rep.output["loss"]), float(local["loss"]), rtol=1e-5
        )
        for k in local["params"]:
            np.testing.assert_allclose(
                np.asarray(rep.output["params"][k]),
                np.asarray(local["params"][k]),
                rtol=2e-4, atol=2e-5, err_msg=(pol, k),
            )


def test_train_bench_tiny():
    """eval/train_bench end-to-end at test scale: oracle passes, every
    policy leg reports, winner's peak-HBM is measured."""
    from distributed_llm_scheduler_tpu.eval.train_bench import (
        measure_train_dag,
    )

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        res = measure_train_dag(
            config=GPT2Config.tiny(), batch=2, seq_len=16,
            pressure_frac=0.5, cache_dir=td, log=lambda m: None,
        )
    assert res["oracle_ok"], res
    assert res["executed_step_ms"] > 0
    assert len(res["policies"]) >= 8
    assert res["winner_peak_hbm_gb"] is not None
    assert res["policies"][res["best_policy"]]["completion"] == 1.0
    assert res["baseline_complete"] in (True, False)

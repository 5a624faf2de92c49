"""Mixtral MoE model family + expert-task DAG (BASELINE.json config #4 at
test scale)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import execute_dag_locally
from distributed_llm_scheduler_tpu.frontend.moe_dag import build_moe_dag
from distributed_llm_scheduler_tpu.models import mixtral
from distributed_llm_scheduler_tpu.models.mixtral import MixtralConfig


@pytest.fixture(scope="module")
def tiny():
    return MixtralConfig.tiny()


@pytest.fixture(scope="module")
def tiny_dag(tiny):
    return build_moe_dag(tiny, batch=2, seq_len=16)


def test_mixtral_8x7b_param_counts():
    cfg = MixtralConfig.mixtral_8x7b()
    total = mixtral.num_params(cfg)
    active = mixtral.num_active_params(cfg)
    # well-known numbers: ~46.7B total, ~12.9B active per token
    assert abs(total - 46.7e9) < 0.5e9, total
    assert abs(active - 12.9e9) < 0.5e9, active


def test_router_weights_topk(tiny):
    """Dense gate layout: exactly top_k nonzeros per token, summing to 1."""
    d, E, k = tiny.d_model, tiny.n_experts, tiny.top_k
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, E))
    gates = mixtral.router_weights(x, w, k)
    assert gates.shape == (2, 8, E)
    nz = (np.asarray(gates) > 0).sum(axis=-1)
    assert (nz == k).all()
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)


def test_moe_block_matches_manual_sparse(tiny):
    """Dense-formulation MoE == computing only the selected experts."""
    params = mixtral.init_params(tiny, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, tiny.d_model))
    got = mixtral.moe_block(params, x, 0, tiny)

    gates = np.asarray(
        mixtral.router_weights(x, params["l0_router"], tiny.top_k)
    )
    want = np.zeros_like(np.asarray(got))
    for e in range(tiny.n_experts):
        eo = np.asarray(mixtral.expert_ffn(
            x, params[f"l0_e{e}_w_gate"], params[f"l0_e{e}_w_up"],
            params[f"l0_e{e}_w_down"],
        ))
        # only tokens that routed to e contribute
        want += gates[..., e : e + 1] * eo
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_dag_structure(tiny_dag, tiny):
    g = tiny_dag.graph
    E = tiny.n_experts
    assert len(g) == (7 + E) * tiny.n_layers + 3
    assert g.unique_params() == set(tiny_dag.param_specs)
    # combine joins router + all experts
    comb = g["layer_0_moe_combine"]
    assert len(comb.dependencies) == 1 + E
    # every expert task owns exactly its three matrices
    e0 = g["layer_0_expert_0"]
    assert e0.params_needed == {"l0_e0_w_gate", "l0_e0_w_up", "l0_e0_w_down"}


def test_dag_execution_matches_fused_forward(tiny_dag):
    params = tiny_dag.init_params()
    ids = tiny_dag.make_inputs()
    got = execute_dag_locally(tiny_dag, params, ids)
    want = jax.jit(tiny_dag.reference_forward)(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_forward_finite_and_causal(tiny):
    params = mixtral.init_params(tiny, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, tiny.vocab_size)
    logits = jax.jit(lambda p, i: mixtral.forward(p, i, tiny))(params, ids)
    assert logits.shape == (1, 16, tiny.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    ids2 = ids.at[0, -1].set((ids[0, -1] + 1) % tiny.vocab_size)
    logits2 = mixtral.forward(params, ids2, tiny)
    np.testing.assert_allclose(np.asarray(logits[0, :-1]),
                               np.asarray(logits2[0, :-1]),
                               rtol=1e-4, atol=1e-5)


def test_expert_placement_under_hbm_limits(tiny):
    """The config-#4 scenario: per-core HBM below total params, so experts
    must spread; MRU completes via locality-aware placement + eviction."""
    dag = build_moe_dag(tiny, batch=2, seq_len=16)
    g = dag.graph
    total = g.total_param_gb()
    cluster = Cluster([DeviceState(f"d{i}", total * 0.45) for i in range(4)])
    for name in ("mru", "greedy", "heft"):
        s = get_scheduler(name).schedule(g, cluster)
        assert not s.failed, (name, sorted(s.failed)[:3])
        # experts must not all land on one device
        homes = {
            n for n, tids in s.per_node.items()
            if any("expert" in t for t in tids)
        }
        assert len(homes) >= 2, (name, s.per_node)


def test_expert_locality_across_microbatches(tiny):
    """With microbatches streaming through, a locality-aware policy should
    pin each expert's weights to one home (params cached once), not copy
    them to every device."""
    dag = build_moe_dag(tiny, batch=4, seq_len=16, microbatches=2)
    g = dag.graph
    cluster = Cluster([DeviceState(f"d{i}", g.total_param_gb(), 1.0) for i in range(4)])
    s = get_scheduler("greedy").schedule(g, cluster)
    assert not s.failed
    # each expert weight set should be resident on exactly one device
    homes = {}
    for node, tids in s.per_node.items():
        for t in tids:
            if "expert" in t:
                key = t.split("_", 1)[1] if t.startswith("mb") else t
                homes.setdefault(key, set()).add(node)
    multi = {k: v for k, v in homes.items() if len(v) > 1}
    assert not multi, multi


def test_vocab_sharded_mixtral_matches_fused(tiny):
    """Vocab sharding through the shared decoder backbone works for the MoE
    family too."""
    import numpy as np

    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import (
        execute_dag_locally,
    )
    from distributed_llm_scheduler_tpu.frontend.moe_dag import build_moe_dag

    dag = build_moe_dag(tiny, batch=2, seq_len=16, vocab_shards=2)
    assert "tok_emb" not in dag.graph.unique_params()
    params = dag.init_params()
    ids = dag.make_inputs()
    fused = dag.reference_forward(params, ids)
    via_dag = execute_dag_locally(dag, params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(via_dag), rtol=1e-5, atol=1e-5
    )


# -- routed task-graph dispatch --------------------------

def _routed_dag(tiny, capacity_factor, microbatches=1):
    return build_moe_dag(
        tiny, batch=2, seq_len=16, microbatches=microbatches,
        routed=True, capacity_factor=capacity_factor,
    )


def test_routed_dag_matches_dense_at_full_capacity(tiny):
    """Non-dropping capacity: the routed DAG's placed execution equals the
    dense DAG's output AND the routed whole-program oracle."""
    full = tiny.n_experts / tiny.top_k
    dag = _routed_dag(tiny, full)
    params = dag.init_params()
    ids = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:2], hbm_cap_gb=4.0)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    assert not sched.failed
    rep = DeviceBackend(cluster).execute(dag.graph, sched, params, ids)
    oracle = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(oracle), rtol=2e-5, atol=2e-5
    )
    dense = mixtral.forward(params, ids, tiny)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_routed_dag_matches_routed_oracle_with_drops(tiny):
    """At a squeezing capacity the task-graph dispatch must drop the SAME
    assignments as the whole-program routed forward (mb=1: identical
    arrival order), so outputs match exactly."""
    dag = _routed_dag(tiny, 0.75)
    params = dag.init_params()
    ids = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:1], hbm_cap_gb=8.0)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(dag.graph, sched, params, ids)
    oracle = dag.reference_forward(params, ids)  # routed, same capacity
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(oracle), rtol=2e-5, atol=2e-5
    )
    # and it must NOT equal dense (something actually dropped)
    dense = mixtral.forward(params, ids, tiny)
    assert not np.allclose(
        np.asarray(rep.output), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_routed_expert_flops_below_dense_inflation(tiny):
    """Routed expert tasks must carry (and compute) ~top_k/E of the dense
    per-expert work, not the E/k-inflated dense count."""
    dag_d = build_moe_dag(tiny, batch=2, seq_len=16)
    dag_r = _routed_dag(tiny, 1.0)
    dense_task = dag_d.graph["layer_0_expert_0"]
    routed_task = dag_r.graph["layer_0_expert_0"]
    # dense fn computes every token: its true compute is E/K x its
    # recorded useful flops; routed computes only the capacity buffer
    dense_true_flops = dense_task.flops * tiny.n_experts / tiny.top_k
    assert routed_task.flops < 0.7 * dense_true_flops


def test_routed_dag_microbatched_oracle_with_drops(tiny):
    """mb=2 with a squeezing capacity: the DAG routes per microbatch, so
    the oracle must too (a whole-batch routing oracle drops different
    assignments — the bug this test pins)."""
    dag = _routed_dag(tiny, 0.75, microbatches=2)
    params = dag.init_params()
    ids = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:2], hbm_cap_gb=8.0)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(dag.graph, sched, params, ids)
    oracle = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(oracle), rtol=2e-5, atol=2e-5
    )
    # whole-batch routing at the same capacity factor is NOT the oracle
    whole = mixtral.forward(params, ids, tiny, routed=True,
                            capacity_factor=0.75)
    assert not np.allclose(
        np.asarray(rep.output), np.asarray(whole), rtol=2e-5, atol=2e-5
    )

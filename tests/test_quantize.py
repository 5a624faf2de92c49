"""Int8 weight quantization: smaller bytes, same execution contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
from distributed_llm_scheduler_tpu.core.fusion import fuse_linear_chains
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.frontend.llama_dag import build_llama_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
from distributed_llm_scheduler_tpu.models.llama import LlamaConfig
from distributed_llm_scheduler_tpu.utils.quantize import (
    QParam,
    dequantize,
    quantize_array,
    quantize_dag,
    quantize_like,
    quantize_params,
)


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 0.05
    qp = quantize_array(x)
    assert qp.q.dtype == jnp.int8
    assert qp.scale.shape == (1, 128)
    back = dequantize(qp, jnp.float32)
    # symmetric int8: error <= scale/2 per element
    assert np.all(
        np.abs(np.asarray(back) - np.asarray(x))
        <= np.asarray(qp.scale) / 2 + 1e-9
    )


def test_small_and_1d_params_stay_fp():
    params = {
        "big": jnp.ones((128, 128)),
        "bias": jnp.ones((128,)),
        "tiny": jnp.ones((4, 4)),
    }
    q = quantize_params(params)
    assert isinstance(q["big"], QParam)
    assert not isinstance(q["bias"], QParam)
    assert not isinstance(q["tiny"], QParam)


@pytest.fixture(scope="module")
def qsetup():
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16)
    return dag, quantize_dag(dag)


def test_param_bytes_shrink(qsetup):
    dag, qdag = qsetup
    assert qdag.graph.name.endswith("_int8")
    ratio = qdag.graph.total_param_gb() / dag.graph.total_param_gb()
    assert ratio < 0.30  # f32 -> int8 + scales


def test_quantized_dag_matches_quantized_oracle(qsetup):
    """Placed execution of the quantized graph must match the quantized
    fused forward exactly — same weights, two execution paths."""
    _, qdag = qsetup
    params = qdag.init_params()
    ids = qdag.make_inputs()
    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    schedule = get_scheduler("pack").schedule(qdag.graph, cluster)
    assert not schedule.failed
    rep = DeviceBackend(cluster).execute(qdag.graph, schedule, params, ids)
    fused = qdag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(fused), rtol=2e-4, atol=2e-4
    )


def test_quantized_close_to_full_precision(qsetup):
    dag, qdag = qsetup
    ids = dag.make_inputs()
    full = np.asarray(dag.reference_forward(dag.init_params(), ids))
    quant = np.asarray(qdag.reference_forward(qdag.init_params(), ids))
    rel = np.abs(quant - full).mean() / (np.abs(full).mean() + 1e-9)
    assert rel < 0.05, rel


def test_quantized_fused_graph_fused_launches():
    """Quantization composes with chain fusion and fused launches."""
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16,
                         microbatches=2, vocab_shards=2)
    import dataclasses

    dag = dataclasses.replace(dag, graph=fuse_linear_chains(dag.graph))
    qdag = quantize_dag(dag)
    params, ids = qdag.init_params(), qdag.make_inputs()
    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    schedule = get_scheduler("pipeline").schedule(qdag.graph, cluster)
    rep = DeviceBackend(cluster).execute(qdag.graph, schedule, params, ids)
    fused = qdag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(fused), rtol=2e-4, atol=2e-4
    )
    assert rep.planned and rep.n_dispatches < len(qdag.graph)


def test_quantized_llama_family():
    dag = build_llama_dag(LlamaConfig.tiny(), batch=1, seq_len=16)
    qdag = quantize_dag(dag)
    params, ids = qdag.init_params(), qdag.make_inputs()
    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    schedule = get_scheduler("greedy").schedule(qdag.graph, cluster)
    rep = DeviceBackend(cluster).execute(qdag.graph, schedule, params, ids)
    fused = qdag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(fused), rtol=2e-4, atol=2e-4
    )


def test_replay_load_times_shrink(qsetup):
    """The scheduler-visible effect: quantized loads shorten the replayed
    makespan in a load-dominated regime."""
    dag, qdag = qsetup
    from distributed_llm_scheduler_tpu.backends.sim import LinkModel

    link = LinkModel(param_load_gbps=0.1, interconnect_gbps=50.0)
    cluster = Cluster.uniform(4, 8.0)
    sim = SimulatedBackend(fidelity="full", link=link)
    m_full = sim.execute(
        dag.graph, cluster,
        get_scheduler("pack").schedule(dag.graph, cluster),
    ).makespan
    m_q = sim.execute(
        qdag.graph, cluster,
        get_scheduler("pack").schedule(qdag.graph, cluster),
    ).makespan
    assert m_q < m_full * 0.5


def test_quantize_like_follows_dag_specs(qsetup):
    dag, qdag = qsetup
    fp = dag.init_params()
    q = quantize_like(qdag, fp)
    for k, spec in qdag.param_specs.items():
        assert isinstance(q[k], QParam) == isinstance(spec, QParam), k


def test_cli_rejects_unknown_quantize_mode():
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    with pytest.raises(ValueError, match="quantize"):
        RunConfig(model="gpt2-tiny", quantize="int3").build_graph()


def test_quantize_rejected_for_synthetic_and_train_step():
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    with pytest.raises(ValueError, match="synthetic"):
        RunConfig(model="llm", quantize="int8").build_graph()
    with pytest.raises(ValueError, match="train-step"):
        RunConfig(
            model="gpt2-tiny", quantize="int8", train_step=True
        ).build_graph()


def test_qparam_bytes_matches_actual_layout():
    """Accounted bytes must equal what quantize_array really produces."""
    from distributed_llm_scheduler_tpu.utils.quantize import qparam_bytes

    for shape in [(64, 128), (128, 64), (50, 7, 32)]:
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        qp = quantize_array(x)
        actual = qp.q.size * qp.q.dtype.itemsize + (
            qp.scale.size * qp.scale.dtype.itemsize
        )
        assert qparam_bytes(jax.ShapeDtypeStruct(shape, jnp.float32)) == actual


def test_untouched_tasks_keep_fn_identity(qsetup):
    dag, qdag = qsetup
    for tid in dag.graph.topo_order:
        t, qt = dag.graph[tid], qdag.graph[tid]
        has_quant = any(
            isinstance(qdag.param_specs.get(g), QParam)
            for _, g in t.param_items()
        )
        if not has_quant:
            assert qt.fn is t.fn, tid
        elif t.fn is not None:
            assert qt.fn is not t.fn, tid


def test_shard_group_quantization_is_coherent():
    """Shards follow their BASE table's quantization decision even when
    individually below min_elems — mixing fp shards with a quantized base
    would re-introduce DAG-vs-oracle re-rounding divergence."""
    # V=512, D=128: base wte = 65536 elems; each of 8 shards = 8192... use
    # min_elems high enough that shards alone wouldn't qualify
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=16,
                         vocab_shards=8)
    qdag = quantize_dag(dag, min_elems=16_000)  # shards are 8192 < 16000
    specs = qdag.param_specs
    assert isinstance(specs["wte"], QParam)
    for k in range(8):
        assert isinstance(specs[f"wte_shard_{k}"], QParam), k
    params = qdag.init_params()
    ids = qdag.make_inputs()
    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    schedule = get_scheduler("pack").schedule(qdag.graph, cluster)
    rep = DeviceBackend(cluster).execute(qdag.graph, schedule, params, ids)
    fused = qdag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(rep.output), np.asarray(fused), rtol=2e-4, atol=2e-4
    )


def test_quantize_dag_idempotent(qsetup):
    _, qdag = qsetup
    again = quantize_dag(qdag)
    # re-application is a no-op: same quantized spec set, same byte totals
    for k, spec in qdag.param_specs.items():
        assert isinstance(again.param_specs[k], QParam) == isinstance(
            spec, QParam
        ), k
    assert (
        again.graph.total_param_gb() == qdag.graph.total_param_gb()
    )


def test_grouped_scales_roundtrip_and_layout():
    from distributed_llm_scheduler_tpu.utils.quantize import (
        quantize_array_grouped,
    )

    x = jax.random.normal(jax.random.PRNGKey(3), (256, 96)) * 0.05
    qp = quantize_array_grouped(x, group=64)
    assert qp.q.dtype == jnp.int8 and qp.q.shape == x.shape
    # grouped layout: one scale per (64-row block, channel), ndim + 1
    assert qp.scale.shape == (4, 1, 96)
    back = dequantize(qp, jnp.float32)
    scale_full = np.repeat(np.asarray(qp.scale), 64, axis=1).reshape(256, 96)
    assert np.all(
        np.abs(np.asarray(back) - np.asarray(x)) <= scale_full / 2 + 1e-9
    )


def test_grouped_falls_back_when_axis_indivisible():
    from distributed_llm_scheduler_tpu.utils.quantize import (
        quantize_array_grouped,
    )

    # 8-expert leading axis: 8 % 64 != 0 -> per-channel layout
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 32, 48))
    qp = quantize_array_grouped(x, group=64)
    assert qp.scale.shape == (1, 1, 48)
    np.testing.assert_allclose(
        np.asarray(dequantize(qp, jnp.float32)),
        np.asarray(dequantize(quantize_array(x), jnp.float32)),
    )


def test_rowwise_scales_for_embeddings():
    from distributed_llm_scheduler_tpu.utils.quantize import (
        quantize_array_rowwise,
    )

    # rows with very different magnitudes: row-wise scales keep each
    # row's relative error bounded where column scales can't
    rows = jnp.stack([jnp.ones(128) * 10.0 ** -i for i in range(12)])
    qp = quantize_array_rowwise(rows)
    assert qp.scale.shape == (12, 1)
    back = dequantize(qp, jnp.float32)
    rel = np.abs(np.asarray(back) - np.asarray(rows)) / np.asarray(rows)
    assert rel.max() < 1 / 127  # every row, even the 1e-11 one

    col = quantize_array(rows)
    back_col = np.asarray(dequantize(col, jnp.float32))
    # column scales are dominated by the 10.0 row: small rows vanish
    assert np.all(back_col[8:] == 0)


def test_grouped_scheme_beats_channel_on_logit_error():
    from distributed_llm_scheduler_tpu.models import gpt2 as mod
    from distributed_llm_scheduler_tpu.utils.quantize import (
        ROWWISE_EMBED_KEYS,
    )

    cfg = GPT2Config.tiny()
    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size, dtype=jnp.int32
    )
    ref = mod.forward(params, ids, cfg).astype(jnp.float32)

    def rmse(scheme_kw):
        q = quantize_params(params, min_elems=64, **scheme_kw)
        dense = {k: dequantize(v, cfg.dtype) for k, v in q.items()}
        got = mod.forward(dense, ids, cfg).astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((got - ref) ** 2)))

    e_channel = rmse({})
    e_grouped = rmse({
        "scheme": "grouped",
        "group": 16,
        "rowwise_keys": ROWWISE_EMBED_KEYS["gpt2"],
    })
    assert e_grouped < e_channel


def test_quantize_params_rejects_unknown_scheme():
    with pytest.raises(ValueError, match="scheme"):
        quantize_params({"w": jnp.ones((128, 128))}, scheme="nope")

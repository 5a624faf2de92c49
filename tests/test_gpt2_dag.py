"""GPT-2 model + DAG frontend tests.

The key parity checks: 99 tasks for GPT-2 small (8*12+3, reference
test_gpt2.py:45-168 / paper §6.1), weight tying, residual edges; and the
key *new* capability: DAG execution is numerically equivalent to the fused
whole-model forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu.frontend.gpt2_dag import (
    build_gpt2_dag,
    execute_dag_locally,
)
from distributed_llm_scheduler_tpu.frontend.tracer import trace_to_chain
from distributed_llm_scheduler_tpu.models import gpt2
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


@pytest.fixture(scope="module")
def tiny_dag():
    return build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16)


@pytest.fixture(scope="module")
def small_dag():
    return build_gpt2_dag(GPT2Config.small(), batch=1, seq_len=512)


def test_gpt2_small_task_count(small_dag):
    dag = small_dag
    # 8 tasks x 12 layers + embedding + final_ln + output_projection = 99
    assert len(dag.graph) == 99
    s = dag.graph.summary()
    assert s["max_deps"] == 2
    assert abs(s["avg_deps"] - 1.23) < 0.02  # paper §6.1: avg 1.23 deps/task


def test_weight_tying():
    dag = build_gpt2_dag(GPT2Config.tiny(), seq_len=16)
    emb = dag.graph["embedding"]
    out = dag.graph["output_projection"]
    assert "wte" in emb.params_needed and "wte" in out.params_needed


def test_residual_edges():
    dag = build_gpt2_dag(GPT2Config.tiny(), seq_len=16)
    # attn_residual joins the residual stream and the attention branch
    assert set(dag.graph["layer_0_attn_residual"].dependencies) == {
        "embedding",
        "layer_0_attention",
    }
    assert set(dag.graph["layer_1_attn_residual"].dependencies) == {
        "layer_0_output",
        "layer_1_attention",
    }


def test_real_param_bytes():
    cfg = GPT2Config.tiny()
    dag = build_gpt2_dag(cfg, seq_len=16)
    attn = dag.graph["layer_0_attention"]
    qkv_bytes = attn.param_bytes["h0_attn_qkv_w"]
    assert qkv_bytes == cfg.n_embd * 3 * cfg.n_embd * 4  # float32
    # total graph params must equal the model's true param count
    total_param_bytes = sum(
        dag.graph.param_size_gb(p) for p in dag.graph.unique_params()
    ) * 1024**3
    assert total_param_bytes == pytest.approx(gpt2.num_params(cfg) * 4, rel=1e-6)


def test_num_params_gpt2_small():
    assert gpt2.num_params(GPT2Config.small()) == pytest.approx(124e6, rel=0.02)


def test_dag_execution_matches_fused_forward(tiny_dag):
    """The load-bearing correctness check: task-by-task DAG execution must
    reproduce the fused forward."""
    params = tiny_dag.init_params()
    ids = tiny_dag.make_inputs()
    fused = tiny_dag.reference_forward(params, ids)
    via_dag = execute_dag_locally(tiny_dag, params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(via_dag), rtol=1e-5, atol=1e-5
    )


def test_forward_is_jittable_and_causal(tiny_dag):
    """jit compiles; causality: future tokens don't affect past logits."""
    cfg = tiny_dag.config
    params = tiny_dag.init_params()
    fwd = jax.jit(lambda p, ids: gpt2.forward(p, ids, cfg))
    ids = tiny_dag.make_inputs()
    out1 = fwd(params, ids)
    assert out1.shape == (2, 16, cfg.vocab_size)
    # perturb the last token: logits at earlier positions must not change
    ids2 = ids.at[:, -1].set((ids[:, -1] + 1) % cfg.vocab_size)
    out2 = fwd(params, ids2)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-6, atol=1e-6
    )


def test_loss_fn_finite(tiny_dag):
    params = tiny_dag.init_params()
    ids = tiny_dag.make_inputs()
    targets = jnp.roll(ids, -1, axis=1)
    loss = gpt2.loss_fn(params, ids, targets, tiny_dag.config)
    assert np.isfinite(float(loss))
    # random init: loss should be near ln(vocab)
    assert abs(float(loss) - np.log(tiny_dag.config.vocab_size)) < 1.0


def test_tracer_linear_chain(tiny_dag):
    cfg = tiny_dag.config
    params = tiny_dag.init_params()
    ids = tiny_dag.make_inputs()
    g = trace_to_chain(lambda i: gpt2.forward(params, i, cfg), ids, name="gpt2")
    assert len(g) > cfg.n_layer * 4  # at least the matmul-ish ops survive
    # linear chain: every non-root has exactly the previous task as dep
    order = g.topo_order
    for i, tid in enumerate(order):
        deps = g[tid].dependencies
        assert deps == ([] if i == 0 else [order[i - 1]])
    # closed-over params surface as named params with real sizes
    assert g.total_param_gb() > 0


def test_scheduling_real_gpt2_dag(small_dag):
    """End-to-end parity scenario (reference test_gpt2.py:274-299): schedule
    the GPT-2 small DAG on the 4-laptop fleet with MRU -> 99/99 complete.
    With real byte sizes the DAG is far smaller than the reference's
    0.5GB-per-param fiction, so completion is expected."""
    dag = small_dag
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler

    cluster = Cluster.laptops()
    s = get_scheduler("mru").schedule(dag.graph, cluster)
    assert len(s.completed) == 99
    assert not s.failed


def test_tracer_tracks_params_through_trivial_ops():
    """Regression: a weight consumed only via transpose/cast must still be
    charged to the downstream task."""
    import jax.numpy as jnp

    w = jnp.ones((64, 32), jnp.float32)
    g = trace_to_chain(lambda x: x @ w.T, jnp.ones((8, 32)), name="tw")
    assert g.total_param_gb() > 0
    (task,) = [t for t in g if "dot_general" in t.task_id]
    assert task.params_needed  # the transposed const reaches the matmul


def test_microbatched_dag_matches_fused_forward():
    """Pipelined (4-microbatch) DAG execution == fused full-batch forward."""
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=8, seq_len=16, microbatches=4)
    assert len(dag.graph) == 4 * (8 * 2 + 3) + 1
    params = dag.init_params()
    ids = dag.make_inputs()
    fused = dag.reference_forward(params, ids)
    via_dag = execute_dag_locally(dag, params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(via_dag), rtol=1e-5, atol=1e-5
    )


def test_a_task_fn_is_traced_once_per_argument_shapes(monkeypatch):
    """Building the DAG shape-evaluates a shared ``fn`` once per argument
    shapes, not once per task: every layer's and microbatch's tasks read
    the spec their first twin was traced to."""
    calls = []
    real = jax.eval_shape
    monkeypatch.setattr(
        jax, "eval_shape", lambda fn, *a, **k: calls.append(fn) or real(fn, *a, **k)
    )
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=8, seq_len=16, microbatches=4)
    fns = {dag.graph[t].fn for t in dag.graph.topo_order}
    assert len(calls) < len(dag.graph) // 3
    # the residual adds meet two shapes' worth of arguments at most
    assert len(fns) <= len(calls) <= 2 * len(fns)
    twins = [t for t in dag.graph.topo_order if t.endswith("layer_1_ln1")]
    assert len(twins) == 4
    assert len({id(dag.graph[t].out_shape) for t in twins}) == 1


def test_microbatch_validation():
    with pytest.raises(ValueError, match="divisible"):
        build_gpt2_dag(GPT2Config.tiny(), batch=3, seq_len=16, microbatches=2)


def test_costmodel_roundtrip(tmp_path, tiny_dag):
    """Calibration persists and reloads identically; cache hit skips
    re-measurement."""
    from distributed_llm_scheduler_tpu.utils.costmodel import (
        CostModel,
        calibrate_cached,
    )

    params = tiny_dag.init_params()
    ids = tiny_dag.make_inputs()
    cm1 = calibrate_cached(
        tiny_dag.graph, params, ids, cache_dir=str(tmp_path), repeats=1
    )
    cm2 = calibrate_cached(
        tiny_dag.graph, params, ids, cache_dir=str(tmp_path), repeats=1
    )
    assert cm1.task_seconds == cm2.task_seconds  # second call = cache hit
    assert not cm1.cache_hit and cm2.cache_hit  # provenance of each object
    assert cm1.measured_at and cm2.measured_at == cm1.measured_at
    assert set(cm1.task_seconds) == set(tiny_dag.graph.task_ids())
    assert cm1.apply(tiny_dag.graph) == len(tiny_dag.graph)
    loaded = CostModel.load(
        str(tmp_path / f"{tiny_dag.graph.name}_cpu.json")
    )
    assert loaded.task_seconds == cm1.task_seconds
    # refresh=True bypasses the cache: a NEW measurement (fresh stamp
    # allowed to differ; must not be marked a cache hit)
    cm3 = calibrate_cached(
        tiny_dag.graph, params, ids, cache_dir=str(tmp_path), repeats=1,
        refresh=True,
    )
    assert not cm3.cache_hit
    assert set(cm3.task_seconds) == set(tiny_dag.graph.task_ids())


def test_cache_age_days_handles_naive_and_bad_stamps():
    from distributed_llm_scheduler_tpu.utils.costmodel import cache_age_days

    assert cache_age_days("") is None
    assert cache_age_days("not-a-date") is None
    # timezone-naive stamp (hand-edited artifact): assumed UTC, not a crash
    age = cache_age_days("2026-07-30T00:00:00")
    assert age is not None and age > 0
    aware = cache_age_days("2026-07-30T00:00:00+00:00")
    assert abs(age - aware) < 1e-6


def test_vocab_sharded_dag_matches_fused_forward():
    """Sharded tied embedding/head: partial-lookup sum and logit-slice
    concat must reproduce the fused forward exactly (each token id hits
    exactly one shard; slices partition the vocab axis)."""
    dag = build_gpt2_dag(
        GPT2Config.tiny(), batch=4, seq_len=16, microbatches=2, vocab_shards=3
    )
    graph = dag.graph
    # per mb: 3 embed partials + combine, 3 logit slices + concat replace
    # the monolithic embedding/output_projection tasks
    assert "mb0_embedding_shard_2" in graph
    assert "mb1_output_projection_shard_0" in graph
    # the full table is never referenced: every wte use is via shards
    assert "wte" not in graph.unique_params()
    params = dag.init_params()
    ids = dag.make_inputs()
    fused = dag.reference_forward(params, ids)
    via_dag = execute_dag_locally(dag, params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(via_dag), rtol=1e-5, atol=1e-5
    )


def test_vocab_shard_sizes_cover_vocab():
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16, vocab_shards=5)
    rows = [
        dag.param_specs[f"wte_shard_{k}"].shape[0] for k in range(5)
    ]
    assert sum(rows) == dag.config.vocab_size
    assert all(r > 0 for r in rows)


def test_vocab_shards_validation():
    with pytest.raises(ValueError, match="vocab_shards"):
        build_gpt2_dag(GPT2Config.tiny(), batch=2, seq_len=16, vocab_shards=0)


def test_costmodel_calibrate_times_every_task(tiny_dag):
    """``calibrate`` has one method — serial per-task wall times ending
    in ``block_until_ready``: every task gets a positive entry and the
    model records how it was measured."""
    from distributed_llm_scheduler_tpu.utils import costmodel

    cm = costmodel.calibrate(
        tiny_dag.graph, tiny_dag.init_params(), tiny_dag.make_inputs(),
        repeats=1,
    )
    assert set(cm.task_seconds) == set(tiny_dag.graph.task_ids())
    assert all(t > 0 for t in cm.task_seconds.values())
    assert cm.method == "profile" and cm.dispatch_s == 0.0


def test_readback_fence_forces_completion():
    """The fence returns only after the value is host-visible (smoke: it
    must work on pytrees and scalars alike)."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.utils.costmodel import readback_fence

    readback_fence(jnp.ones((3, 4)) * 2.0)
    readback_fence({"a": jnp.zeros((2,)), "b": jnp.ones(())})
    readback_fence(jax.jit(lambda x: x @ x.T)(jnp.ones((8, 8))))

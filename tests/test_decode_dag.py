"""Decode step as a task DAG (frontend/decode_dag.py): the scheduling
layer sees an inference workload.

Pins: prefill-step DAG logits == models/decode cached forward; decode-step
DAG at pos>0 stays exact over a multi-step loop with functional cache
updates; cache slabs are real placeable params the scheduler accounts;
multi-device placed execution matches; and position is RUNTIME data —
one decode graph serves every step, so an N-token generation compiles
O(1) programs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler, validate_schedule
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.frontend.decode_dag import (
    apply_cache_updates,
    build_decode_dag,
    decode_inputs,
)
from distributed_llm_scheduler_tpu.models import gpt2
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

CFG = GPT2Config.tiny()
B, P, M = 2, 8, 32


def _prompt():
    return jax.random.randint(
        jax.random.PRNGKey(1), (B, P), 0, CFG.vocab_size, dtype=jnp.int32
    )


def test_cache_slabs_are_placeable_params():
    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=M)
    g = dag.graph
    for i in range(CFG.n_layer):
        t = g[f"layer_{i}"]
        assert f"cache_k_{i}" in t.params_needed
        assert f"cache_v_{i}" in t.params_needed
        # real bytes: B x H x M x hd x itemsize
        expect = B * CFG.n_head * M * CFG.head_dim * 4
        assert t.param_bytes[f"cache_k_{i}"] == expect


def test_prefill_dag_matches_cached_forward():
    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=M)
    params = dag.init_params()
    inputs = decode_inputs(_prompt(), 0)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(dag.graph, sched, params, inputs)
    want = dag.reference_forward(params, inputs)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )


def _run_generation(n_new, backend, cluster, model_params, ids, max_len):
    """Prefill DAG + ONE reused decode DAG over n_new greedy tokens."""
    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=max_len)
    params = dag.init_params()
    params.update(model_params)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(
        dag.graph, sched, params, decode_inputs(ids, 0), keep_outputs=True
    )
    params = apply_cache_updates(params, rep.task_outputs, CFG, pos=0)
    tok = jnp.argmax(np.asarray(rep.output)[:, -1, :], axis=-1)
    got = [tok]

    # ONE decode graph + ONE schedule reused for every position
    ddag = build_decode_dag(CFG, batch=B, step_len=1, max_len=max_len)
    dsched = get_scheduler("greedy").schedule(ddag.graph, cluster)
    for s in range(1, n_new):
        pos = P + s - 1
        drep = backend.execute(
            ddag.graph, dsched, params,
            decode_inputs(tok[:, None], pos), keep_outputs=True,
        )
        params = apply_cache_updates(params, drep.task_outputs, CFG, pos=pos)
        tok = jnp.argmax(np.asarray(drep.output)[:, -1, :], axis=-1)
        got.append(tok)
    return jnp.stack(got, axis=1)


def test_multistep_decode_loop_token_exact():
    """Prefill DAG + a reused decode DAG with functional cache updates
    must reproduce models/decode.generate greedy tokens exactly."""
    ids = _prompt()
    model_params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    n_new = 3
    want = gpt2.generate(model_params, ids, CFG, max_new_tokens=n_new)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    got = _run_generation(n_new, backend, cluster, model_params, ids, M)
    np.testing.assert_array_equal(np.asarray(want[:, P:P + n_new]),
                                  np.asarray(got))


def test_long_generation_compiles_constant_graphs():
    """32+ new tokens: position is runtime data, so after the first decode
    step NO new jitted callables appear — the whole generation runs on
    two compiled programs' worth of task fns (prefill + decode classes).
    The bar was <= 4 graphs over >= 32 tokens; the traced-position
    design gives exactly 2."""
    ids = _prompt()
    model_params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    n_new = 32
    max_len = P + n_new
    want = gpt2.generate(model_params, ids, CFG, max_new_tokens=n_new)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)

    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=max_len)
    params = dag.init_params()
    params.update(model_params)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(
        dag.graph, sched, params, decode_inputs(ids, 0), keep_outputs=True
    )
    params = apply_cache_updates(params, rep.task_outputs, CFG, pos=0)
    tok = jnp.argmax(np.asarray(rep.output)[:, -1, :], axis=-1)
    got = [tok]

    ddag = build_decode_dag(CFG, batch=B, step_len=1, max_len=max_len)
    dsched = get_scheduler("greedy").schedule(ddag.graph, cluster)
    jit_cache_sizes = []
    for s in range(1, n_new):
        pos = P + s - 1
        drep = backend.execute(
            ddag.graph, dsched, params,
            decode_inputs(tok[:, None], pos), keep_outputs=True,
            warmup=(s == 1),
        )
        params = apply_cache_updates(params, drep.task_outputs, CFG, pos=pos)
        tok = jnp.argmax(np.asarray(drep.output)[:, -1, :], axis=-1)
        got.append(tok)
        jit_cache_sizes.append(len(backend._jit_cache))
    # token-exact over the whole run
    np.testing.assert_array_equal(
        np.asarray(want[:, P:P + n_new]),
        np.asarray(jnp.stack(got, axis=1)),
    )
    # no new jitted callables after the first decode step: steps 2..31
    # reuse the same compiled fns, position flowing in as data
    assert len(set(jit_cache_sizes)) == 1, jit_cache_sizes


def test_decode_inputs_shapes():
    dag = build_decode_dag(CFG, batch=B, step_len=1, max_len=M)
    inp = dag.make_inputs(pos=5)
    assert inp["ids"].shape == (B, 1)
    assert int(inp["pos"]) == 5


@pytest.mark.parametrize("policy", ["mru", "roundrobin"])
def test_decode_dag_multi_device(policy):
    """Placed decode step on the 8-device mesh: cache slabs distribute,
    validator passes, logits exact."""
    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=M)
    params = dag.init_params()
    inputs = decode_inputs(_prompt(), 0)
    cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
    sched = get_scheduler(policy).schedule(dag.graph, cluster)
    assert not sched.failed
    vrep = validate_schedule(dag.graph, cluster, sched)
    assert vrep.ok
    rep = DeviceBackend(cluster).execute(dag.graph, sched, params, inputs)
    want = dag.reference_forward(params, inputs)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )


def test_position_bounds_checked():
    with pytest.raises(ValueError):
        build_decode_dag(CFG, batch=1, step_len=8, pos=30, max_len=32)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_backbone_decode_dag_multistep_token_exact(family):
    """Llama/Mixtral decode steps through the scheduler reproduce the
    whole-program greedy tokens exactly (GQA cache layout, RoPE at the
    traced step position, per-step MoE routing) — with ONE decode graph
    reused across steps."""
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_decode_dag,
    )

    if family == "llama":
        from distributed_llm_scheduler_tpu.models import llama as mod
        from distributed_llm_scheduler_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()
    else:
        from distributed_llm_scheduler_tpu.models import mixtral as mod
        from distributed_llm_scheduler_tpu.models.mixtral import (
            MixtralConfig,
        )

        cfg = MixtralConfig.tiny()
    b, p_len, m, n_new = 2, 6, 16, 3
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (b, p_len), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    model_params = mod.init_params(cfg, jax.random.PRNGKey(0))
    want = mod.generate(model_params, ids, cfg, max_new_tokens=n_new)

    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    dag = build_decode_dag(cfg, batch=b, step_len=p_len, max_len=m)
    params = dag.init_params()
    params.update(model_params)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(
        dag.graph, sched, params, decode_inputs(ids, 0), keep_outputs=True
    )
    params = apply_cache_updates(params, rep.task_outputs, cfg, pos=0)
    tok = jnp.argmax(np.asarray(rep.output)[:, -1, :], axis=-1)
    got = [tok]
    ddag = build_decode_dag(cfg, batch=b, step_len=1, max_len=m)
    dsched = get_scheduler("greedy").schedule(ddag.graph, cluster)
    for s in range(1, n_new):
        pos = p_len + s - 1
        drep = backend.execute(
            ddag.graph, dsched, params,
            decode_inputs(tok[:, None], pos), keep_outputs=True,
        )
        params = apply_cache_updates(params, drep.task_outputs, cfg, pos=pos)
        tok = jnp.argmax(np.asarray(drep.output)[:, -1, :], axis=-1)
        got.append(tok)
    np.testing.assert_array_equal(
        np.asarray(want[:, p_len:p_len + n_new]),
        np.asarray(jnp.stack(got, axis=1)),
    )


def test_decode_inputs_bounds_check():
    """Runtime position bounds: the build-time guard can't see runtime
    positions, so decode_inputs(max_len=...) must catch the overflow that
    dynamic_update_slice would silently clamp."""
    ids = jnp.zeros((1, 1), jnp.int32)
    decode_inputs(ids, 31, max_len=32)  # fits
    with pytest.raises(ValueError, match="exceeds"):
        decode_inputs(ids, 32, max_len=32)
    with pytest.raises(ValueError, match="exceeds"):
        decode_inputs(jnp.zeros((1, 8), jnp.int32), 25, max_len=32)


def test_measure_decode_dag_bench_leg():
    """The task-graph decode perf probe (eval/decode_bench.measure_decode_dag)
    must produce a structurally complete report on the CPU mesh with the
    greedy-token oracle holding — the shape contract DECODE_r{N}.json relies
    on (timing magnitudes are only meaningful on the TPU)."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import (
        measure_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    r = measure_decode_dag(
        GPT2Config.tiny(), batch=2, prompt_len=16, new_tokens=4, reps=2
    )
    assert r["oracle_ok"], "task-graph logits must match forward_cached"
    # at f32 tiny-vocab scale there are no argmax ties to flip
    assert r["token_agreement"] == 1.0
    assert r["graph_classes_compiled"] == 2  # prefill + one decode class
    assert r["step_ms_per_task"] > 0
    assert r["tok_s_end_to_end"] is not None and r["n_timed_steps"] == 2
    # the K-step on-device loop leg: present, f32-exact vs whole-program
    assert r["looped"] is not None
    assert r["looped"]["token_agreement_vs_whole_program"] == 1.0
    assert r["looped"]["tok_s"] > 0
    # int8-weight window: runs, byte-counted, tokens vs the bf16 window
    q = r["looped"]["int8_weights"]
    assert q["tok_s"] > 0 and q["weight_bytes"] > 0
    assert 0.0 <= q["token_agreement_vs_bf16_loop"] <= 1.0


def test_decode_loop_token_exact_and_chains():
    """The on-device K-step loop (backends/decode_loop.py) must reproduce
    models/decode.generate greedy tokens exactly from a DAG-path prefill,
    and chaining two loop calls (donated caches fed back) must equal one
    longer loop."""
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_decode_loop,
        split_cache_params,
    )

    ids = _prompt()
    model_params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    n_new = 6
    max_len = P + n_new
    want = gpt2.generate(model_params, ids, CFG, max_new_tokens=n_new)

    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    dag = build_decode_dag(CFG, batch=B, step_len=P, max_len=max_len)
    params = dag.init_params()
    params.update(model_params)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(
        dag.graph, sched, params, decode_inputs(ids, 0), keep_outputs=True
    )
    params = apply_cache_updates(params, rep.task_outputs, CFG, pos=0)
    tok0 = jnp.argmax(np.asarray(rep.output)[:, -1, :], axis=-1).astype(
        jnp.int32
    )[:, None]

    ddag = build_decode_dag(CFG, batch=B, step_len=1, max_len=max_len)
    dsched = get_scheduler("greedy").schedule(ddag.graph, cluster)
    weights, caches = split_cache_params(params)

    def fresh_caches():
        # donation consumes the buffers — each loop launch needs its own
        return {k: jnp.array(v) for k, v in caches.items()}

    # one loop over the remaining n_new - 1 tokens
    loop = build_decode_loop(ddag.graph, dsched, CFG, steps=n_new - 1)
    toks, _ = loop(weights, fresh_caches(), tok0, jnp.int32(P))
    got = jnp.concatenate([tok0, toks], axis=1)
    np.testing.assert_array_equal(
        np.asarray(want[:, P:P + n_new]), np.asarray(got)
    )

    # two chained shorter loops == the one long loop
    k1 = 2
    loop_a = build_decode_loop(ddag.graph, dsched, CFG, steps=k1)
    loop_b = build_decode_loop(ddag.graph, dsched, CFG, steps=n_new - 1 - k1)
    t1, c1 = loop_a(weights, fresh_caches(), tok0, jnp.int32(P))
    t2, _ = loop_b(weights, c1, t1[:, -1:], jnp.int32(P + k1))
    chained = jnp.concatenate([tok0, t1, t2], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(chained))


def test_decode_loop_rejects_multi_node_placement():
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        compose_step_fn,
    )
    from distributed_llm_scheduler_tpu.core.cluster import DeviceState

    ddag = build_decode_dag(CFG, batch=B, step_len=1, max_len=M)
    cluster = Cluster([DeviceState(f"n{i}", 64.0) for i in range(2)])
    sched = get_scheduler("roundrobin").schedule(ddag.graph, cluster)
    with pytest.raises(ValueError, match="single-node"):
        compose_step_fn(ddag.graph, sched, CFG)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_decode_loop_token_exact_backbones(family):
    """The K-step on-device loop is family-generic: Llama (GQA + RoPE)
    and Mixtral (per-step MoE routing) loop tokens must equal the
    whole-program greedy stream, same pin as the gpt2 loop test."""
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_decode_loop,
        split_cache_params,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_decode_dag,
    )

    if family == "llama":
        from distributed_llm_scheduler_tpu.models import llama as mod
        from distributed_llm_scheduler_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()
    else:
        from distributed_llm_scheduler_tpu.models import mixtral as mod
        from distributed_llm_scheduler_tpu.models.mixtral import (
            MixtralConfig,
        )

        cfg = MixtralConfig.tiny()
    b, p_len, m, n_new = 2, 6, 16, 4
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (b, p_len), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    model_params = mod.init_params(cfg, jax.random.PRNGKey(0))
    want = mod.generate(model_params, ids, cfg, max_new_tokens=n_new)

    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    backend = DeviceBackend(cluster)
    dag = build_decode_dag(cfg, batch=b, step_len=p_len, max_len=m)
    params = dag.init_params()
    params.update(model_params)
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = backend.execute(
        dag.graph, sched, params, decode_inputs(ids, 0), keep_outputs=True
    )
    params = apply_cache_updates(params, rep.task_outputs, cfg, pos=0)
    tok0 = jnp.argmax(np.asarray(rep.output)[:, -1, :], axis=-1).astype(
        jnp.int32
    )[:, None]

    ddag = build_decode_dag(cfg, batch=b, step_len=1, max_len=m)
    dsched = get_scheduler("greedy").schedule(ddag.graph, cluster)
    weights, caches = split_cache_params(params)
    loop = build_decode_loop(ddag.graph, dsched, cfg, steps=n_new - 1)
    toks, _ = loop(weights, caches, tok0, jnp.int32(p_len))
    got = jnp.concatenate([tok0, toks], axis=1)
    np.testing.assert_array_equal(
        np.asarray(want[:, p_len:p_len + n_new]), np.asarray(got)
    )

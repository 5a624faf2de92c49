"""KV-cache decoding: parity with the fused forward across all families.

The invariant that matters: prefill+decode through the static-shape cache
must produce exactly the tokens the full forward would, for GPT-2, Llama
(GQA+RoPE), and Mixtral (per-token routing).  The reference has no decode
path to mirror (it never executes a model); the oracle here is our own
fused forward, the same one the DAG backends are checked against.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu.models import decode, gpt2, llama, mixtral

FAMILIES = {
    "gpt2": (gpt2, gpt2.GPT2Config.tiny()),
    "llama": (llama, llama.LlamaConfig.tiny()),
    "mixtral": (mixtral, mixtral.MixtralConfig.tiny()),
}


def _setup(name, batch=2, T=8):
    mod, config = FAMILIES[name]
    params = mod.init_params(config, jax.random.PRNGKey(0))
    vocab = config.vocab_size
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (batch, T), 0, vocab, dtype=jnp.int32
    )
    return mod, config, params, ids


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_prefill_matches_fused_forward(family):
    mod, config, params, ids = _setup(family)
    cache = mod.init_cache(config, ids.shape[0], 16)
    logits, cache = mod.forward_cached(params, ids, cache, 0, config)
    ref = mod.forward(params, ids, config)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
    # prompt K/V occupy the first T cache rows of every layer
    assert cache["k"].shape[3] == 16
    assert not np.allclose(np.asarray(cache["k"][:, :, :, : ids.shape[1]]), 0.0)
    assert np.allclose(np.asarray(cache["k"][:, :, :, ids.shape[1] :]), 0.0)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_stepwise_decode_matches_growing_forward(family):
    """Decoding token-by-token through the cache reproduces the last-position
    logits of the fused forward over the growing sequence — the exact
    incremental-vs-recompute equivalence KV caching claims."""
    mod, config, params, ids = _setup(family, batch=1, T=4)
    steps, M = 4, 16
    cache = mod.init_cache(config, 1, M)
    logits, cache = mod.forward_cached(params, ids, cache, 0, config)
    seq = ids
    for pos in range(ids.shape[1], ids.shape[1] + steps):
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        ref = mod.forward(params, seq, config)
        logits, cache = mod.forward_cached(
            params, nxt[:, None], cache, pos, config
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, -1, :]),
            np.asarray(ref[:, -1, :]),
            rtol=5e-4,
            atol=5e-4,
        )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_greedy_generate_matches_naive_loop(family):
    mod, config, params, ids = _setup(family, batch=2, T=4)
    new = 5
    out = mod.generate(params, ids, config, max_new_tokens=new)
    assert out.shape == (2, 4 + new)
    assert np.array_equal(np.asarray(out[:, :4]), np.asarray(ids))
    # naive oracle: rerun the full forward on the growing sequence
    seq = ids
    for _ in range(new):
        logits = mod.forward(params, seq, config)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    assert np.array_equal(np.asarray(out), np.asarray(seq))


def test_generate_single_token():
    mod, config, params, ids = _setup("gpt2", batch=1, T=4)
    out = mod.generate(params, ids, config, max_new_tokens=1)
    assert out.shape == (1, 5)
    logits = mod.forward(params, ids, config)
    assert int(out[0, -1]) == int(jnp.argmax(logits[0, -1]))


def test_temperature_sampling_deterministic_and_in_range():
    mod, config, params, ids = _setup("gpt2", batch=2, T=4)
    k = jax.random.PRNGKey(7)
    a = mod.generate(params, ids, config, max_new_tokens=6, temperature=0.8, key=k)
    b = mod.generate(params, ids, config, max_new_tokens=6, temperature=0.8, key=k)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(a.min()) >= 0 and int(a.max()) < config.vocab_size
    c = mod.generate(
        params, ids, config, max_new_tokens=6, temperature=0.8,
        key=jax.random.PRNGKey(8),
    )
    assert not np.array_equal(np.asarray(a), np.asarray(c))  # key matters


def test_top_k_one_is_greedy():
    mod, config, params, ids = _setup("gpt2", batch=1, T=4)
    greedy = mod.generate(params, ids, config, max_new_tokens=4)
    k1 = mod.generate(
        params, ids, config, max_new_tokens=4, temperature=1.0, top_k=1,
        key=jax.random.PRNGKey(3),
    )
    assert np.array_equal(np.asarray(greedy), np.asarray(k1))


def test_max_len_validation():
    mod, config, params, ids = _setup("gpt2", batch=1, T=4)
    with pytest.raises(ValueError, match="max_len"):
        mod.generate(params, ids, config, max_new_tokens=8, max_len=6)


def test_zero_and_negative_new_tokens():
    mod, config, params, ids = _setup("gpt2", batch=1, T=4)
    out = mod.generate(params, ids, config, max_new_tokens=0)
    assert np.array_equal(np.asarray(out), np.asarray(ids))
    with pytest.raises(ValueError):
        mod.generate(params, ids, config, max_new_tokens=-1)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_position_limit_enforced(family):
    """Decoding past the position table / RoPE horizon must refuse loudly —
    dynamic_slice would otherwise clamp and silently repeat the last
    position's embedding."""
    mod, config, params, ids = _setup(family, batch=1, T=4)
    limit = getattr(config, "n_positions", None) or config.max_seq_len
    with pytest.raises(ValueError, match="position limit"):
        mod.generate(params, ids, config, max_new_tokens=limit)


def test_generate_reuses_compiled_program():
    from distributed_llm_scheduler_tpu.models.decode import _compiled_run

    mod, config, params, ids = _setup("gpt2", batch=1, T=4)
    _compiled_run.cache_clear()
    mod.generate(params, ids, config, max_new_tokens=3)
    mod.generate(params, ids, config, max_new_tokens=3)
    info = _compiled_run.cache_info()
    assert info.misses == 1 and info.hits == 1


def test_sample_token_greedy_no_key():
    logits = jnp.array([[0.1, 2.0, -1.0], [3.0, 0.0, 0.0]])
    toks = decode.sample_token(logits, None, 0.0)
    assert toks.tolist() == [1, 0]


def test_decode_bench_helper_runs():
    """The throughput probe works on any backend (tiny config on CPU)."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import measure_decode
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    res = measure_decode(
        config=GPT2Config.tiny(), batch=2, prompt_len=8, new_tokens=4,
        reps=2,
    )
    assert res["decode_tok_s"] > 0
    assert res["wall_s"] > 0
    assert res["new_tokens"] == 4.0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kv_int8_decode_matches_dense(family):
    """int8 KV cache (per-row absmax scales, dequant fused into the
    attention einsums): lossy by design, but on tiny models the greedy
    tokens should track the dense cache closely — and the cache container
    must actually be int8."""
    mod, config, params, ids = _setup(family, batch=2, T=10)
    dense = mod.generate(params, ids, config, max_new_tokens=6)
    q8 = mod.generate(params, ids, config, max_new_tokens=6, kv_int8=True)
    first = float(jnp.mean(
        (dense[:, 10] == q8[:, 10]).astype(jnp.float32)
    ))
    assert first >= 0.5, (family, dense[:, 10:], q8[:, 10:])
    # container check: quantize_cache halves the value bytes
    cache = mod.init_cache(config, 2, 16)
    qc = decode.quantize_cache(cache)
    assert qc["k"].dtype == jnp.int8 and qc["v"].dtype == jnp.int8
    assert qc["k_scale"].shape == cache["k"].shape[:-1] + (1,)
    q_bytes = sum(v.nbytes for v in qc.values())
    d_bytes = sum(v.nbytes for v in cache.values())
    assert q_bytes < 0.75 * d_bytes


def test_kv_int8_update_and_attention_roundtrip():
    """A written row survives quantize->dequantize within int8's per-row
    resolution, and masked (never-written) rows still contribute nothing."""
    cache = decode.init_cache(1, 1, 2, 8, 4, jnp.float32)
    qc = decode.quantize_cache(cache)
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 3, 4))
    v = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 3, 4))
    qc = decode.update_layer_cache(qc, 0, k, v, 0)
    kc, vc, ks, vs = decode.layer_view(qc, 0)
    k_back = kc.astype(jnp.float32) * ks
    assert jnp.max(jnp.abs(k_back[:, :, :3] - k)) < 0.02
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 3, 4))
    dense_cache = decode.update_layer_cache(cache, 0, k, v, 0)
    want = decode.cached_attention(
        q, dense_cache["k"][0], dense_cache["v"][0], 0, 0.5
    )
    got = decode.cached_attention(
        q, kc, vc, 0, 0.5, k_scale=ks, v_scale=vs
    )
    assert jnp.max(jnp.abs(want - got)) < 0.05


def test_decode_bench_kv_int8_leg():
    from distributed_llm_scheduler_tpu.eval.decode_bench import measure_decode
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    res = measure_decode(
        config=GPT2Config.tiny(), batch=2, prompt_len=8, new_tokens=4,
        reps=2, quantize=True, kv_int8=True,
    )
    assert res["decode_tok_s"] > 0
    assert res["weights"] == "int8" and res["kv_cache"] == "int8"
    assert 0.5 <= res["first_token_agreement"] <= 1.0, res


def test_decode_bench_quantized_leg():
    """int8 decode: same loop on (int8, scale) weights dequantized inside
    the step.  Tokens may legitimately diverge (quantization perturbs
    logits) but on a tiny model most greedy tokens should agree, and the
    timing fields must be populated."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import measure_decode
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    res = measure_decode(
        config=GPT2Config.tiny(), batch=2, prompt_len=8, new_tokens=4,
        reps=2, quantize=True,
    )
    assert res["decode_tok_s"] > 0
    assert res["weights"] == "int8"
    # sequence agreement compounds argmax flips on random-init weights
    # (the r4 TPU capture measured 0.30 on GPT-2 small) — only the
    # non-compounding first-token agreement is stable enough to bound
    assert 0.5 <= res["first_token_agreement"] <= 1.0, res
    assert 0.0 <= res["token_agreement"] <= 1.0


def test_decode_roofline_math():
    """Roofline bound: pure arithmetic on param + KV-cache bytes over the
    device kind's published HBM bandwidth; None on the host platform, an
    error for an accelerator kind with no published peak."""
    import types

    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.eval.benchlib import DEVICE_PEAKS
    from distributed_llm_scheduler_tpu.eval.decode_bench import (
        decode_roofline,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    cfg = GPT2Config.tiny(dtype=jnp.bfloat16)
    roof = decode_roofline(cfg, batch=4, cache_len=32, device=v5e)
    assert roof is not None
    # bytes decompose exactly: params + cache read + cache write
    kv_read = 2 * cfg.n_layer * 4 * cfg.n_head * 32 * cfg.head_dim * 2
    assert roof["kv_cache_bytes"] == float(kv_read)
    assert roof["bytes_per_step"] > roof["param_bytes"] + kv_read - 1
    expect_s = roof["bytes_per_step"] / DEVICE_PEAKS["TPU v5 lite"][
        "hbm_bytes_s"]
    assert roof["step_bound_ms"] == pytest.approx(expect_s * 1e3)
    assert roof["bound_tok_s"] == pytest.approx(4 / expect_s)
    # the host platform has no bound, not a fabricated one ...
    assert decode_roofline(cfg, 4, 32, jax.devices("cpu")[0]) is None
    # ... and an unknown accelerator kind is an error, never a default
    other = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(KeyError, match="no published peaks"):
        decode_roofline(cfg, 4, 32, other)


def test_decode_bench_sharded_helper_runs():
    """tp decode throughput probe on the CPU mesh (functional numbers,
    disclosed via functional_only)."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import (
        measure_decode_sharded,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    res = measure_decode_sharded(
        GPT2Config.tiny(), tp=2, batch=2, prompt_len=8, new_tokens=4,
        reps=2,
    )
    assert res["tok_s_end_to_end"] > 0
    assert res["functional_only"] is True  # CPU mesh
    assert res["tp"] == 2.0


def test_decode_attribution_functional():
    """Per-component decode attribution: every
    component reports a positive time, derived fields are consistent, and
    byte counts are exact.  CPU = structural check; TPU gives the real
    numbers."""
    from distributed_llm_scheduler_tpu.eval.decode_bench import (
        decode_attribution,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny()
    r = decode_attribution(cfg, batch=2, prompt_len=16, new_tokens=8, reps=2)
    for k in ("forward_donated_ms", "forward_undonated_ms",
              "head_ms", "attn_ms", "sample_ms"):
        assert r[k] > 0, (k, r)
    # step_ms is DIFFERENCED (wall(N) - wall(1)) and clamps to ~0 when a
    # loaded host times the longer run no slower than the shorter one —
    # non-negative is the structural guarantee; positivity needs a quiet
    # machine (the TPU artifact asserts it there)
    assert r["step_ms"] >= 0, r
    assert r["cache_copy_ms"] >= 0
    assert r["loop_overhead_ms"] >= 0
    assert r["head_bytes"] == cfg.n_embd * cfg.vocab_size * 4
    assert r["family"] == "gpt2"
    assert r["decode_tok_s"] > 0

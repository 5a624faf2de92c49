"""The Laguna block — grouped-query attention with rotary positions on
the ``kv`` path, query heads that differ by layer kind, ring layers of
the ``kv`` kind, softmax-routed experts of which a chip holds a share —
against the benchmark's plain reference (which imports nothing of the
program), its kernels against their ``jax.numpy`` forms, and a mixed
queue on a pool that cannot hold every slot at full length."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import laguna as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu.backends.device import (  # noqa: E402
    DeviceBackend,
)
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import laguna  # noqa: E402
from distributed_llm_scheduler_tpu.models.kv_pages import PagePool  # noqa: E402
from distributed_llm_scheduler_tpu.ops import gqa_attention as G  # noqa: E402

TYPES = ["full_attention"] + ["sliding_attention"] * 3
#: layer 0 full + dense, then sliding x 3, full; groups of 3 and 5 over 2
#: KV heads; YaRN on half a head beside plain rotary; a window of 6 in a
#: ring of 8 rows; 4 of 8 experts held
HF = {
    "model_type": "laguna", "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": TYPES * 2, "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 10, 10, 10] * 2,
    "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 6,
    "gating": "per-head", "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 10000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 1000,
            "partial_rotary_factor": 1}},
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 4,
    "n_router_outputs": 8, "held_experts": [1, 2, 5, 6],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256, "vocab_size": 256,
    "dtype": "float32", "init": {"std": 0.3},
}
S, PS, PPSEQ = 3, 8, 12


def _config(hf=HF):
    return laguna.LagunaConfig.from_hf(hf, dtype=jnp.float32, ring_rows=8)


def _engine(cfg, params, impl=None, chunk=16, slots=S, n_pages=None):
    n_pages = n_pages or slots * PPSEQ + 1
    ddag = build_paged_decode_dag(
        cfg, slots=slots, page_size=PS, n_pages=n_pages, pages_per_seq=PPSEQ,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("heft").schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=PS)
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, cfg, params, pool, slots=slots,
        pages_per_seq=PPSEQ, seg_steps=4, attention_impl=impl,
        chunk_tokens=chunk)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    # prompts under and over a chunk (16) and the ring (8): whole-prompt
    # waves and chunks; every request wraps its rings several times
    return {"a": (rng.integers(1, 256, (1, 40)), 30),
            "b": (rng.integers(1, 256, (1, 10)), 24),
            "c": (rng.integers(1, 256, (1, 23)), 50),
            "d": (rng.integers(1, 256, (1, 50)), 12)}


@pytest.fixture(scope="module")
def served():
    cfg, params = _config(), R.make_params(HF, 2**31 + 9)
    eng = _engine(cfg, params)
    reqs = _requests()
    for rid, (ids, n) in reqs.items():
        eng.submit(rid, ids, n)
    return cfg, params, reqs, eng.run(), eng


def test_program_shapes_are_the_references():
    cfg = _config()
    want = {f"h{i}_{k}": tuple(s) for i in range(5)
            for k, (s, _) in R.layer_shapes(HF, i).items()}
    got = {k: tuple(s) for k, (s, _) in laguna.param_shapes(cfg).items()
           if k[0] == "h" and k[1].isdigit()}
    assert got == want
    assert sum(int(np.prod(s)) for s, _ in laguna.param_shapes(
        cfg).values()) == R.param_count(HF)
    assert cfg.layer_types == laguna.published_layer_types(5)
    assert cfg.layer_heads == (6, 10, 10, 10, 6)
    whole = laguna.LagunaConfig()
    assert whole.layer_types.count("full") == 12 and whole.n_layers == 48
    assert {whole.layer_heads[i] for i in range(48) if whole.is_full(i)} == {48}
    assert laguna.LagunaConfig.tiny().layer_heads == (6, 10, 10, 10, 6)


def test_rotary_tables_are_the_references():
    """YaRN over half a head with the attention factor, plain over a
    whole one: the program's angles are the reference's tables."""
    cfg = _config()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(40, 3, 8)),
                    jnp.float32)
    for layer, full in ((0, True), (1, False)):
        cos, sin = R.rope_tables(HF, full, 40)
        want = R._rope(x, cos, sin)
        got = laguna.rope(x, jnp.arange(40)[:, None], cfg, layer)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert cos.shape[-1] == 4 and R.rope_tables(HF, True, 2)[0].shape[-1] == 2


def test_served_tokens_are_the_references_across_wraps(served):
    """Chunked prefill and paged decode through the engine against the
    reference's full forward, logits not tokens: contexts from 10 to 74
    rows, so several wraps of the 8-row rings under a window of 6."""
    cfg, params, reqs, out, eng = served
    for rid, (ids, n) in reqs.items():
        seq = np.concatenate([ids[0], out[rid]])
        gaps = R.served_gaps(params, HF, seq, ids.shape[1], n, 80)
        assert gaps.max() < 1e-3, (rid, gaps.max())
    hist = eng.metrics.snapshot()["histograms"]
    share = hist["attn.full_row_share"]
    # two full layers read every row, three window layers at most 6
    assert share["count"] == eng.segments_run and 0.4 < share["p50"] < 1.0
    assert hist["moe.experts_touched_share"]["count"] == eng.segments_run
    assert eng.pool.free_pages == eng.pool.n_pages - 1


def test_program_logits_are_the_references():
    cfg, params = _config(), R.make_params(HF, 77)
    ids = np.random.default_rng(1).integers(1, 256, (1, 64))
    ref = R.logits(params, HF, ids, rows=slice(0, 64))
    with jax.default_matmul_precision("highest"):
        mine = laguna.forward(params, jnp.asarray(ids), cfg, impl="xla")
        kern = laguna.forward(params, jnp.asarray(ids), cfg,
                              impl="pallas_interpret")
    assert float(jnp.abs(ref - mine).max()) < 5e-4
    assert float(jnp.abs(ref - kern).max()) < 5e-4


def test_engine_with_interpreted_kernels_serves_the_same_tokens(served):
    cfg, params, reqs, out, eng0 = served
    eng = _engine(cfg, params, impl="pallas_interpret")
    assert eng.resolved_attention_impl == "pallas_interpret"
    for rid in ("a", "b"):
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    for rid in ("a", "b"):
        np.testing.assert_array_equal(got[rid], out[rid])
    count = lambda e, n: e.metrics.counter("decode." + n).value  # noqa: E731
    # every prefill program's attention traced to the chunk kernel
    assert count(eng, "prefill_attn_kernel_programs") == (
        count(eng, "chunk_waves") + count(eng, "admission_waves")) > 0
    assert count(eng0, "prefill_attn_kernel_programs") == 0


# -- the two kernels against the gather path ---------------------------------------


@pytest.mark.parametrize("lengths", [[0, 3, 5], [7, 8, 9], [40, 100, 31]])
def test_ring_kernel_is_exact_past_any_number_of_wraps(lengths):
    """``_swa_kv_attn`` interpreted against the gather path: under the
    window, at its bound, and after 1 to 12 wraps of a 16-row ring; rows
    the ring still holds from before the window are not seen."""
    S_, Hq, Hkv, hd, ps, rp, window = 3, 10, 2, 8, 8, 2, 12
    rng = np.random.default_rng(sum(lengths))
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    k_pool, v_pool = mk(1 + S_ * rp, ps, Hkv * hd), mk(1 + S_ * rp, ps, Hkv * hd)
    q, k_new, v_new = mk(S_, Hq, hd), mk(S_, Hkv, hd), mk(S_, Hkv, hd)
    L = jnp.asarray(lengths, jnp.int32)
    kw = dict(window=window, sm_scale=hd ** -0.5)
    want = G.kv_window_attention(q, k_pool, v_pool, L, k_new, v_new,
                                 impl="xla", **kw)
    got = G.kv_window_attention(q, k_pool, v_pool, L, k_new, v_new,
                                impl="pallas_interpret", **kw)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the plain form: position p lies in ring row p mod 16
    ring = rp * ps
    for s, n in enumerate(lengths):
        pos = np.arange(max(0, n - window + 1), n)
        keys = np.concatenate([np.asarray(k_pool)[1 + s * rp:1 + (s + 1) * rp]
                               .reshape(ring, Hkv, hd)[pos % ring],
                               np.asarray(k_new)[s][None]])
        vals = np.concatenate([np.asarray(v_pool)[1 + s * rp:1 + (s + 1) * rp]
                               .reshape(ring, Hkv, hd)[pos % ring],
                               np.asarray(v_new)[s][None]])
        for h in range(Hq):
            sc = keys[:, h // (Hq // Hkv)] @ np.asarray(q)[s, h] * hd ** -0.5
            pr = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[s, h], pr / pr.sum() @ vals[:, h // (Hq // Hkv)],
                rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pos0,window,before", [
    (0, None, None), (24, None, None), (0, 6, 6), (16, 6, 6), (40, 20, 20)])
def test_chunk_kernel_is_the_loop(pos0, window, before):
    """``_gqa_chunk_flash`` interpreted against the model file's loop:
    causal over a cache from position 0, and under a window with the
    rows before the chunk read out of a ring; groups of 3 and 5."""
    cfg = _config()
    rng = np.random.default_rng(pos0 + (window or 0))
    T, Hkv, hd = 16, 2, 8
    M = 64 if before is None else before + T
    for H in (6, 10):
        q = jnp.asarray(rng.normal(size=(2, T, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, Hkv, M, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, Hkv, M, hd)), jnp.float32)
        want = laguna.chunk_attention(q, k, v, pos0, cfg, "xla", window,
                                      before)
        got = laguna.chunk_attention(q, k, v, pos0, cfg, "pallas_interpret",
                                     window, before)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # a chunk of 13 rows is padded to the sublane tile and cut back
        got = laguna.chunk_attention(q[:, :13], k, v, pos0, cfg,
                                     "pallas_interpret", window, before)
        np.testing.assert_allclose(got, want[:, :13], rtol=2e-5, atol=2e-5)


# -- a chip's share of the experts -----------------------------------------------


def test_the_four_shares_add_up_to_the_whole_layer():
    """8 routed experts over 4 chips, 2 a chip: the parts the 4 held
    lists give, the shared expert counted once, are the uncut layer — by
    the program against the reference's layer with every expert; and
    attention counted once is the reference's."""
    hf = dict(HF, num_experts=8, held_experts=list(range(8)),
              num_hidden_layers=2)
    whole = R.make_params(hf, 11)
    p = {k[3:]: v for k, v in whole.items() if k.startswith("h1_")}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = R._moe(x, {k: v.astype(jnp.float32) for k, v in p.items()},
                      hf, False)
        total = jnp.zeros_like(x)
        for chip in range(4):
            held = (2 * chip, 2 * chip + 1)
            cfg = _config(dict(hf, num_experts=2, held_experts=held))
            mine = dict(p, exp_gu_w=p["exp_gu_w"][2 * chip:2 * chip + 2],
                        exp_down_w=p["exp_down_w"][2 * chip:2 * chip + 2])
            y, stats = laguna.moe_ffn(mine, x, cfg, held=held,
                                      shared=chip == 0, impl="xla",
                                      route=laguna.moe_route)
            total = total + y
            assert 0.0 <= float(stats[0]) <= 1.0
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    idx, gate = laguna.moe_route(p, x, _config(hf))
    assert idx.shape == (24, 3)
    np.testing.assert_allclose(gate.sum(-1), 2.5, rtol=1e-5)


# -- the cache, and a pool that cannot hold every slot -----------------------------


def test_the_cache_spec_says_heads_and_rings_per_layer():
    cfg = _config()
    spec = laguna.cache_spec(cfg)
    assert spec.kind == "kv" and spec.has_rings and spec.head_dim == 8
    assert [lc.q_heads for lc in spec.layers] == [6, 10, 10, 10, 6]
    assert [lc.window for lc in spec.layers] == [None, 6, 6, 6, None]
    assert spec.layer_kinds(0) == ("k", "v")
    assert spec.layer_kinds(1) == ("wk", "wv")
    with pytest.raises(ValueError, match="layers differ"):
        spec.rows
    pools = spec.init_pools(9, PS, jnp.float32, slots=3)
    assert pools["cache_k_0"].shape == pools["cache_v_4"].shape == (9, 8, 16)
    assert pools["cache_wk_2"].shape == (1 + 3 * 1, 8, 16)   # slots x 1 page
    assert spec.paged_row_elems == 2 * 2 * 16      # two full layers, K and V
    dense = spec.init_dense(2, 24, jnp.float32, page_size=PS)
    assert dense["k"].shape == (2, 2, 2, 24, 8)
    assert dense["wk"].shape == (3, 2, 2, 8, 8)    # the ring's rows, not cap
    # gather / scatter round-trip, a ring through the ring table
    rng = np.random.default_rng(0)
    pools = {k: jnp.asarray(rng.normal(size=v.shape), jnp.float32)
             for k, v in pools.items()}
    pages, ring = jnp.asarray([2, 5, 7]), jnp.asarray(spec.ring_table(3, PS)[1])
    got = spec.gather(spec.init_dense(1, 24, jnp.float32, page_size=PS),
                      pools, pages, 1, 24, ring)
    np.testing.assert_array_equal(
        spec.to_rows(got["wv"][2]).reshape(8, 16), pools["cache_wv_3"][2])
    back = spec.scatter({k: jnp.zeros_like(v) for k, v in pools.items()},
                        got, pages, PS, ring)
    np.testing.assert_array_equal(back["cache_k_4"][5], pools["cache_k_4"][5])
    np.testing.assert_array_equal(back["cache_wk_1"][2], pools["cache_wk_1"][2])
    assert spec.resolve_impl("xla", 3, 9, PS, jnp.float32) == "xla"
    # the analysis sees every layer kind's query heads (DEC005)
    from distributed_llm_scheduler_tpu.analysis.decode_pass import (
        analyze_decode,
    )

    dag = build_paged_decode_dag(cfg, slots=3, page_size=PS, n_pages=9,
                                 pages_per_seq=2)
    assert dag.graph.kv_q_heads == (6, 10)
    assert not analyze_decode(
        dag.graph, param_specs=dag.param_specs).has("DEC005")
    dag.graph.kv_q_heads = (6, 7)      # a count no KV head divides
    dec5 = [d for d in analyze_decode(
        dag.graph, param_specs=dag.param_specs).diagnostics
        if d.code == "DEC005"]
    assert len(dec5) == 1 and "n_q_heads 7" in dec5[0].message


def test_a_long_request_waits_for_pages_and_every_page_returns():
    """Three slots of up to 12 pages over a pool of 16: short and long
    requests share the queue.  Chunks take their pages as they come, but
    only where that leaves every slot mid-prefill an order to finish in
    (``_safe_after``): the second long prompt stalls until the first has
    its pages and goes on when they free; what is served is what a roomy
    pool serves, and every page comes back.  Without the rule the two
    grow chunk by chunk into a pool neither can finish in."""
    cfg, params = _config(), R.make_params(HF, 5)
    rng = np.random.default_rng(3)
    reqs = {f"r{i}": (rng.integers(1, 256, (1, p)), n) for i, (p, n) in
            enumerate([(70, 20), (9, 6), (66, 24), (12, 30), (20, 8)])}
    tight, roomy, blind = (_engine(cfg, params, n_pages=17),
                           _engine(cfg, params),
                           _engine(cfg, params, n_pages=17))
    blind._safe_after = lambda take, s=None, horizon=None: (
        blind.pool.can_alloc(take))
    for eng in (tight, roomy, blind):
        for rid, (ids, n) in reqs.items():
            eng.submit(rid, ids, n)
    got, want = tight.run(), roomy.run()
    for rid in reqs:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert tight.metrics.counter("decode.chunk_stalls").value > 0
    assert roomy.metrics.counter("decode.chunk_stalls").value == 0
    assert tight.pool.free_pages == tight.pool.n_pages - 1
    with pytest.raises(RuntimeError, match="engine stalled"):
        blind.run()
    # a request that may not enter yet asks for more than the pool has
    eng = _engine(cfg, params, n_pages=17)
    assert eng.admission_pages_needed(*reqs["r2"]) == 2     # its first chunk
    eng.submit("r0", *reqs["r0"])
    eng.submit("r2", *reqs["r2"])
    eng.step_segment()      # nobody decodes: chunks back to back
    assert eng.lengths[0] == 70 + 4 and eng.is_prefilling("r2")
    assert len(eng._slot_pages[0]) == 12 and eng.pool.free_pages == 0
    assert eng.admission_pages_needed(*reqs["r4"]) == 1 > eng.pool.free_pages
    assert not eng._safe_after(1, 1) and eng._safe_after(0, 1)

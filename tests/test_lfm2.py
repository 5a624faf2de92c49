"""The LFM2 hybrid block — gated short convolutions whose cache is a state
a slot, three to one beside grouped-query attention with normed heads,
every layer an operator AND a feed-forward, all experts held — against
the benchmark's plain reference (which imports nothing of the program):
the family's forward, chunked prefill and paged decode through the
engine, what a state layer that also routes asks of the engine (padding,
reuse, idle slots, counts), the conv step against the chunk form, and
``xing4.moe_ffn`` with every expert held and no shared one."""

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

from benchmark.reference import lfm2 as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu.analysis.decode_pass import (  # noqa: E402
    analyze_decode,
)
from distributed_llm_scheduler_tpu.backends.device import (  # noqa: E402
    DeviceBackend,
)
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import (  # noqa: E402
    family_of,
    lfm2,
    model_config,
    xing4,
)
from distributed_llm_scheduler_tpu.models.kv_pages import PagePool  # noqa: E402
from distributed_llm_scheduler_tpu.ops import short_conv  # noqa: E402

#: ``Lfm2Config.tiny()`` under the published keys: both dense layers and
#: two whole periods, 8 experts top-2 of a width (20) that is no multiple
#: of 16; a convolution gain that makes the state matter, a query norm
#: gain that peaks the attention
HF = {
    "model_type": "lfm2_moe", "hidden_size": 32, "num_hidden_layers": 10,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 2
    + ["conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "num_dense_layers": 2, "moe_intermediate_size": 20, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "max_position_embeddings": 256, "vocab_size": 256,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "dtype": "float32",
    "init": {"std": 0.3, "conv_gain": 2.0, "q_norm_gain": 2.0},
    "engine": {"chunk_tokens": 16},
}
S, PS, PPSEQ, CHUNK = 3, 8, 12, 16


def _config():
    return lfm2.Lfm2Config.from_hf(HF, dtype=jnp.float32)


def _engine(cfg, params, impl=None, slots=S):
    n_pages = slots * PPSEQ + 1
    ddag = build_paged_decode_dag(
        cfg, slots=slots, page_size=PS, n_pages=n_pages, pages_per_seq=PPSEQ,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("heft").schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=PS)
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, cfg, params, pool, slots=slots,
        pages_per_seq=PPSEQ, seg_steps=4, attention_impl=impl,
        chunk_tokens=CHUNK)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    # three chunks with a padded last one; shorter than a chunk; a single
    # token and two (shorter than the convolution); exactly a chunk; two
    # chunks and a token — six requests through three slots
    return {rid: (rng.integers(1, 256, (1, p)), n) for rid, p, n in (
        ("a", 40, 30), ("b", 10, 24), ("c", 1, 20), ("d", 2, 9),
        ("f", 16, 9), ("g", 33, 12))}


@pytest.fixture(scope="module")
def served():
    cfg, params = _config(), R.make_params(HF, 2**31 + 9)
    eng = _engine(cfg, params)
    reqs = _requests()
    for rid, (ids, n) in reqs.items():
        eng.submit(rid, ids, n)
    return cfg, params, reqs, eng.run(), eng


def test_the_family_is_registered_and_its_shapes_are_the_references():
    cfg = _config()
    assert cfg == lfm2.Lfm2Config.tiny() == model_config("lfm2-tiny")
    assert family_of(cfg) == "lfm2"
    want = {f"h{i}_{k}": tuple(s) for i in range(10)
            for k, (s, _) in R.layer_shapes(HF, i).items()}
    got = {k: tuple(s) for k, (s, _) in lfm2.param_shapes(cfg).items()
           if k[0] == "h" and k[1].isdigit()}
    assert got == want
    assert sum(int(np.prod(s)) for s, _ in lfm2.param_shapes(
        cfg).values()) == R.param_count(HF)
    # four kinds of layer by their local names: conv + dense, conv +
    # experts, attention + experts (attention + dense only where asked)
    kinds = {frozenset(lfm2.layer_param_names(cfg, i)) for i in range(10)}
    assert len(kinds) == 3
    both = lfm2.Lfm2Config.tiny(num_dense_layers=3)
    assert {"q_w", "mlp_gu_w"} <= set(lfm2.layer_param_names(both, 2))
    whole = lfm2.Lfm2Config()
    assert whole.n_layers == 40 and whole.head_dim * whole.n_heads == 2048
    assert whole.layer_types.count("conv") == 30
    spec = lfm2.cache_spec(cfg)
    assert [spec.layer_kinds(i) for i in range(4)] == [
        ("conv",), ("conv",), ("k", "v"), ("conv",)]
    assert spec.has_state and not spec.has_rings
    assert [spec.layer(i).state for i in range(4)] == [True, True, False, True]
    # a slot's state at the published widths: 2 rows of 2,048 in bf16
    assert int(np.prod(short_conv.state_shape(2048, 3))) * 2 == 8192
    assert short_conv.state_shape(2048, 3) == (2, 16, 128)


def test_from_hf_refuses_what_the_family_does_not_compute():
    for key, bad in (("conv_bias", True), ("norm_topk_prob", False),
                     ("use_expert_bias", False),
                     ("layer_types", HF["layer_types"][:9]),
                     ("layer_types", ["sliding_attention"] * 10),
                     ("rope_parameters", {"rope_theta": 1e4,
                                          "rope_type": "yarn"})):
        with pytest.raises(ValueError):
            lfm2.Lfm2Config.from_hf(dict(HF, **{key: bad}))


def test_program_logits_are_the_references():
    cfg, params = _config(), R.make_params(HF, 77)
    ids = np.random.default_rng(1).integers(1, 256, (2, 45))
    ref = R.logits(params, HF, ids)
    with jax.default_matmul_precision("highest"):
        mine = lfm2.forward(params, jnp.asarray(ids), cfg, impl="xla")
        kern = lfm2.forward(params, jnp.asarray(ids), cfg,
                            impl="pallas_interpret")
    assert float(jnp.abs(ref).max()) > 1.0
    # float32 against float32: what is left is the order of the sums
    assert float(jnp.abs(ref - mine).max()) < 5e-4
    assert float(jnp.abs(ref - kern).max()) < 5e-4
    # the state matters at this init: lose it every 16 tokens and the
    # logits behind the boundary leave the reference's
    lost = R.logits(params, HF, ids, since=R.lost_since(45, 45, 16))
    assert float(jnp.abs(ref - lost)[:, :16].max()) < 1e-5
    assert float(jnp.abs(ref - lost)[:, 16:].max()) > 0.05
    # and so do the q / k norms' learned weights: move them and nothing
    # agrees
    bare = dict(params, **{k: jnp.full_like(v, 1e3) for k, v in params.items()
                           if k.endswith(("q_norm_g", "k_norm_g"))})
    assert float(jnp.abs(ref - R.logits(bare, HF, ids)).max()) > 0.05


def test_served_tokens_are_the_references_across_chunks_and_padding(served):
    """Chunked prefill and paged decode through the engine against the
    reference's full forward, logits not tokens: prompts of 1 to 40
    tokens, so chunks that are full, padded, and shorter than the
    convolution; six requests through three slots, so every slot is
    reused with another request's state in its rows.  The tolerance is
    float32's against float32 over 10 layers (the order of the sums):
    the int8 control and a lost state both read a hundred times it."""
    cfg, params, reqs, out, eng = served
    for rid, (ids, n) in reqs.items():
        seq = np.concatenate([ids[0], out[rid]])
        gaps = R.served_gaps(params, HF, seq, ids.shape[1], n, 96)
        assert gaps.max() < 1e-3, (rid, gaps.max())
    ids, n = reqs["a"]
    seq = np.concatenate([ids[0], out["a"]])
    for control in (True, "conv_state_lost"):
        assert R.served_gaps(params, HF, seq, 40, n, 96,
                             control=control).max() > 0.1
    snap = eng.metrics.snapshot()
    count = {k: v["value"] for k, v in snap["counters"].items()}
    # no whole-prompt program: every prompt went through the chunk program
    assert count.get("decode.admission_waves", 0) == 0
    assert count["decode.chunk_waves"] == 3 + 1 + 1 + 1 + 1 + 3
    assert [k for k in eng._prefill_store if k != "cow_copy"] == [
        ("chunk", CHUNK, 1, None)]
    hist = snap["histograms"]
    assert hist["conv.slots_stepped"]["count"] == eng.segments_run
    assert 1.0 <= hist["conv.slots_stepped"]["p50"] <= S
    assert "ssm.slots_stepped" not in hist
    assert hist["moe.experts_touched_share"]["count"] == eng.segments_run
    assert hist["decode.page_pool_used_share"]["count"] == eng.segments_run
    assert eng.pool.free_pages == eng.pool.n_pages - 1


def test_the_segment_span_carries_the_counts_of_both_kinds(served):
    """A state layer that also routes: ``decode_layer``'s named counts
    reach the ``segment`` span — ``conv_slots`` (slot-steps of the
    segment) and ``experts_touched`` (over all 8 expert layers)."""
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params)
    eng.tracer = Tracer()
    eng.submit("b", *reqs["b"])
    got = eng.run()
    np.testing.assert_array_equal(got["b"], out["b"])
    segs = [e for e in eng.tracer.events if e.get("name") == "segment"
            and "conv_slots" in e.get("args", {})]
    assert len(segs) == eng.segments_run
    # one slot decoding: a slot-step a step it ran, 2 picks of 8 experts
    assert sum(e["args"]["conv_slots"] for e in segs) == 24 - 1
    assert all(0 < e["args"]["experts_touched"] <= 2 for e in segs)


def test_engine_with_interpreted_kernels_serves_the_same_tokens(served):
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, impl="pallas_interpret")
    assert eng.resolved_attention_impl == "pallas_interpret"
    for rid in ("a", "c", "g"):
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    for rid in ("a", "c", "g"):
        np.testing.assert_array_equal(got[rid], out[rid])
    # the attention layers' K and V stay in their pages under the kernel
    assert eng._chunk_in_pages()
    assert eng.metrics.counter(
        "decode.prefill_paged_chunk_programs").value == 7


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_conv_step_is_the_chunk_form_row_by_row(impl):
    """``_short_conv_step`` over the slots' pool, a token at a time,
    against ``short_conv_chunk`` over the same rows: outputs and carried
    inputs, live slots alone; the trash row and a slot that is not live
    keep their bytes."""
    rng = np.random.default_rng(3)
    h, K, T, slots = 256, 3, 9, 4
    shape = short_conv.state_shape(h, K)
    w = jnp.asarray(rng.normal(size=(h, K)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(slots, T, h)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(1 + slots, *shape)), jnp.float32)
    live = jnp.asarray([True, False, True, True])
    start = np.asarray(pool)
    outs = []
    for t in range(T):
        v, pool = short_conv.short_conv_step(u[:, t], w, pool, live, impl=impl)
        outs.append(np.asarray(v))
    for s in range(slots):
        want_v, want_c = short_conv.short_conv_chunk(
            u[s], w, jnp.asarray(start[1 + s]), 5, T - 1)
        if live[s]:
            np.testing.assert_allclose(
                np.stack([o[s] for o in outs]), want_v, rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(pool[1 + s]), want_c)
        else:
            np.testing.assert_array_equal(np.asarray(pool[1 + s]),
                                          start[1 + s])
    np.testing.assert_array_equal(np.asarray(pool[0]), start[0])
    # a chunk at position 0 starts from zero whatever it is handed, and a
    # padded chunk carries its last REAL rows
    v0, c0 = short_conv.short_conv_chunk(u[0], w, pool[1], 0, 4)
    vz, _ = short_conv.short_conv_chunk(u[0], w, jnp.zeros(shape), 7, 4)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(vz))
    np.testing.assert_array_equal(np.asarray(c0).reshape(2, h),
                                  np.asarray(u[0, 3:5]))


def test_a_padded_chunk_leaves_the_state_of_its_real_rows():
    """The engine pads a prompt's last chunk with token 0; the conv
    layers' states that come back are the inputs after the last REAL row,
    bit for bit against an unpadded run — and a chunk at position 0
    starts from zero whatever the cache handed in holds."""
    hf = dict(HF, num_hidden_layers=4, layer_types=HF["layer_types"][:4])
    cfg = lfm2.Lfm2Config.from_hf(hf, dtype=jnp.float32)
    params = R.make_params(hf, 5)       # conv, conv, attention, conv
    rng = np.random.default_rng(2)
    real = 11
    ids = np.zeros((1, CHUNK), np.int32)
    ids[0, :real] = rng.integers(1, 256, real)
    dirty = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype) for k, v in
             lfm2.init_cache(cfg, 1, 32).items()}
    last_p, cache_p = lfm2.forward_cached_row(
        params, jnp.asarray(ids), dirty, 0, cfg, real - 1, impl="xla")
    last_u, cache_u = lfm2.forward_cached_row(
        params, jnp.asarray(ids[:, :real]), lfm2.init_cache(cfg, 1, 32),
        0, cfg, real - 1, impl="xla")
    np.testing.assert_array_equal(np.asarray(cache_p["conv"]),
                                  np.asarray(cache_u["conv"]))
    np.testing.assert_allclose(last_p, last_u, rtol=1e-5, atol=1e-5)
    # and a chunk that does NOT begin at 0 starts from what it is handed
    _, cache_c = lfm2.forward_cached_row(
        params, jnp.asarray(ids), dirty, CHUNK, cfg, real - 1, impl="xla")
    assert np.abs(np.asarray(cache_c["conv"]) - np.asarray(
        cache_u["conv"])).max() > 1e-3


def test_slots_that_do_not_decode_keep_their_state_bit_for_bit(served):
    """While one slot decodes through several segments, a slot that holds
    a finished request's state and a slot mid-prefill (between its first
    and its second chunk) see every state pool row of theirs unchanged by
    the segments, the trash row too."""
    cfg, params, reqs, _, _ = served
    eng = _engine(cfg, params)
    eng.submit("b", *reqs["b"])         # slot 0: decodes 24 tokens
    eng.submit("d", reqs["d"][0], 2)    # slot 1: done after one segment
    eng.step_segment()
    eng.step_segment()
    assert eng._slot_req[1] is None and eng.remaining[0] > 0
    state = [k for k in eng.pools if k.split("_")[1] == "conv"]
    assert len(state) == 8
    before = {k: np.asarray(eng.pools[k]) for k in state}
    real_seg, seen = eng._seg, []

    def watched(w, pools, *rest):
        mine = {k: np.asarray(pools[k]) for k in state}
        out = real_seg(w, pools, *rest)
        seen.append((mine, {k: np.asarray(out[1][k]) for k in state}))
        return out

    eng._seg = watched
    eng.submit("a", *reqs["a"])         # slot 1 again: three chunks
    eng.step_segment()
    assert eng.is_prefilling("a") and eng._slot_req[1] == "a"
    eng.step_segment()
    assert len(seen) == 2
    for mine, after in seen:
        for k in state:
            # rows: 0 trash, 1 the decoding slot, 2 mid-prefill, 3 empty
            assert (mine[k][1] != after[k][1]).any(), k
            for row in (0, 2, 3):
                np.testing.assert_array_equal(mine[k][row], after[k][row],
                                              err_msg=f"{k} row {row}")
    for k in state:     # the empty slot's rows never moved at all
        np.testing.assert_array_equal(np.asarray(eng.pools[k])[3],
                                      before[k][3])
    eng._seg = real_seg
    eng.run()


def test_a_reused_slot_serves_what_a_fresh_engine_serves(served):
    """``_retire`` leaves a slot's state rows standing; the next request's
    first chunk starts from zero all the same."""
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, slots=1)
    for rid in ("g", "b", "c"):         # one slot: each inherits the last's
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    assert np.abs(np.asarray(eng.pools["cache_conv_0"])[1]).max() > 0
    for rid in ("g", "b", "c"):
        np.testing.assert_array_equal(got[rid], out[rid])


def test_the_step_graph_wires_state_pools_to_one_task_each():
    cfg = _config()
    ddag = build_paged_decode_dag(cfg, slots=S, page_size=PS, n_pages=37,
                                  pages_per_seq=PPSEQ)
    g = ddag.graph
    assert g.state_kinds == ("conv",)
    need = {t.task_id: set(t.params_needed) for t in g.tasks()}
    for i in range(10):
        conv = cfg.is_conv(i)
        assert ("page_table" in need[f"layer_{i}"]) == (not conv), i
        assert (f"cache_conv_{i}" in need[f"layer_{i}"]) == conv
        assert (f"h{i}_router_w" in need[f"layer_{i}"]) == (i >= 2)
    assert "wte" in need["embed"] and "wte" in need["logits"]   # tied
    assert not analyze_decode(g, param_specs=ddag.param_specs).errors


# -- every expert held, no shared one ---------------------------------------------


def test_moe_ffn_with_every_expert_held_and_no_shared_expert():
    """``xing4.moe_ffn(held=None, shared=False)``: the kernel
    interpreted, the ``ragged_dot`` twin and the reference's masked loop
    over all the experts agree; a token that is not live gets nothing."""
    cfg = _config()
    p = {k[3:]: v for k, v in R.make_params(HF, 4).items()
         if k.startswith("h3_")}
    assert "shared_gu_w" not in p and p["exp_gu_w"].shape[0] == 8
    x = jnp.asarray(np.random.default_rng(4).normal(size=(13, 32)),
                    jnp.float32)
    live = jnp.arange(13) % 3 != 0
    with jax.default_matmul_precision("highest"):
        want = R._moe(x, {k: v.astype(jnp.float32) for k, v in p.items()},
                      HF, False)
        y_x, st_x = xing4.moe_ffn(p, x, cfg, held=None, shared=False,
                                  impl="xla")
        y_k, st_k = xing4.moe_ffn(p, x, cfg, held=None, shared=False,
                                  impl="pallas_interpret")
        y_l, st_l = xing4.moe_ffn(p, x, cfg, held=None, shared=False,
                                  live=live, impl="pallas_interpret")
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(y_x, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_k, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st_x, st_k)
    np.testing.assert_allclose(y_l[live], want[live], rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(y_l[~live]).max()) == 0.0
    # 13 tokens x top-2 over 8 experts: (share touched, max / mean picks)
    assert 0 < float(st_k[0]) <= 1 and float(st_k[1]) >= 1
    assert float(st_l[0]) <= float(st_k[0])

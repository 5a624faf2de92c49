"""The Nemotron-H hybrid stack — Mamba-2 mixers whose cache is a state a
slot, ungated ``relu^2`` experts of which a chip holds a share, attention
with no positions, every layer ONE of the three — against the benchmark's
plain reference (which imports nothing of the program): the family's
forward, chunked prefill and paged decode through the engine, what a
state layer asks of the engine (padding, reuse, preemption, refusals) and
the ungated expert path."""

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

from benchmark.reference import nemotron_h as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu.analysis.decode_pass import (  # noqa: E402
    analyze_decode,
)
from distributed_llm_scheduler_tpu.analysis.page_pass import (  # noqa: E402
    analyze_pages,
)
from distributed_llm_scheduler_tpu.backends.device import (  # noqa: E402
    DeviceBackend,
)
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import (  # noqa: E402
    kv_pages,
    nemotron_h,
    xing4,
)
from distributed_llm_scheduler_tpu.models.kv_pages import PagePool  # noqa: E402

#: all three letters, two mixers apart and one last; heads two to a state
#: row, two groups; 4 of 8 experts held, an expert width (20) that is no
#: multiple of 16; a convolution gain that makes the state matter
HF = {
    "model_type": "nemotron_h", "hidden_size": 32, "num_hidden_layers": 6,
    "hybrid_override_pattern": "ME*MEM", "mamba_num_heads": 4,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 4, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "use_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
    "use_bias": False, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 20,
    "moe_shared_expert_intermediate_size": 24, "n_routed_experts": 4,
    "n_router_outputs": 8, "held_experts": [1, 2, 5, 6],
    "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 256, "vocab_size": 256,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "dtype": "float32", "init": {"std": 0.3, "conv_gain": 2.0},
    "engine": {"chunk_tokens": 16},
}
S, PS, PPSEQ, CHUNK = 3, 8, 12, 16


def _config(hf=HF):
    return nemotron_h.NemotronHConfig.from_hf(hf, dtype=jnp.float32)


def _engine(cfg, params, impl=None, chunk=CHUNK, slots=S, sharing=False):
    n_pages = slots * PPSEQ + 1
    ddag = build_paged_decode_dag(
        cfg, slots=slots, page_size=PS, n_pages=n_pages, pages_per_seq=PPSEQ,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("heft").schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=PS, sharing=sharing)
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, cfg, params, pool, slots=slots,
        pages_per_seq=PPSEQ, seg_steps=4, attention_impl=impl,
        chunk_tokens=chunk)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    # three chunks with a padded last one; shorter than a chunk; shorter
    # than the convolution (1, 2 and 3 tokens); exactly a chunk; two
    # chunks and a token — seven requests through three slots
    return {rid: (rng.integers(1, 256, (1, p)), n) for rid, p, n in (
        ("a", 40, 30), ("b", 10, 24), ("c", 1, 20), ("d", 2, 9),
        ("e", 3, 12), ("f", 16, 9), ("g", 33, 12))}


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
    want = {f"h{i}_{k}": tuple(s) for i in range(6)
            for k, (s, _) in R.layer_shapes(HF, i).items()}
    got = {k: tuple(s) for k, (s, _) in nemotron_h.param_shapes(cfg).items()
           if k[0] == "h" and k[1].isdigit()}
    assert got == want
    assert sum(int(np.prod(s)) for s, _ in nemotron_h.param_shapes(
        cfg).values()) == R.param_count(HF)
    whole = nemotron_h.NemotronHConfig()
    assert whole.n_layers == 52 and whole.d_inner == 4096
    assert {c: whole.pattern.count(c) for c in "ME*"} == {
        "M": 23, "E": 23, "*": 6}
    assert whole.conv_width == 6144
    spec = nemotron_h.cache_spec(cfg)
    assert [spec.layer_kinds(i) for i in range(6)] == [
        ("ssm", "conv"), (), ("k", "v"), ("ssm", "conv"), (), ("ssm", "conv")]
    assert spec.has_state and not spec.has_rings
    assert spec.kind_dtype("ssm", jnp.bfloat16) == jnp.float32
    assert spec.kind_dtype("conv", jnp.bfloat16) == jnp.bfloat16


def test_program_logits_are_the_references():
    cfg, params = _config(), R.make_params(HF, 77)
    ids = np.random.default_rng(1).integers(1, 256, (2, 45))
    ref = R.logits(params, HF, ids)
    with jax.default_matmul_precision("highest"):
        mine = nemotron_h.forward(params, jnp.asarray(ids), cfg, impl="xla")
        kern = nemotron_h.forward(params, jnp.asarray(ids), cfg,
                                  impl="pallas_interpret")
    assert float(jnp.abs(ref).max()) > 1.0
    assert float(jnp.abs(ref - mine).max()) < 5e-4
    assert float(jnp.abs(ref - kern).max()) < 5e-4
    # the state matters at this init: lose it every 16 tokens and the
    # logits behind the boundary leave the reference's
    lost = R.logits(params, HF, ids, reset_every=16)
    assert float(jnp.abs(ref - lost)[:, :16].max()) < 1e-5
    assert float(jnp.abs(ref - lost)[:, 16:].max()) > 0.05


def test_served_tokens_are_the_references_across_chunks_and_padding(served):
    """Chunked prefill and paged decode through the engine against the
    reference's full forward, logits not tokens: prompts of 1 to 40
    tokens, so chunks that are full, padded, and shorter than the
    convolution; seven requests through three slots, so every slot is
    reused with another request's state in its rows."""
    cfg, params, reqs, out, eng = served
    for rid, (ids, n) in reqs.items():
        seq = np.concatenate([ids[0], out[rid]])
        gaps = R.served_gaps(params, HF, seq, ids.shape[1], n, 96)
        assert gaps.max() < 1e-3, (rid, gaps.max())
    snap = eng.metrics.snapshot()
    count = {k: v["value"] for k, v in snap["counters"].items()}
    # no whole-prompt program: every prompt went through the chunk program
    assert count.get("decode.admission_waves", 0) == 0
    assert count["ssm.first_chunks"] == 7
    assert count["ssm.chunks_carried"] == 2 + 2         # a: 3 chunks, g: 3
    assert count["decode.chunk_waves"] == 11
    assert [k for k in eng._prefill_store if k != "cow_copy"] == [
        ("chunk", CHUNK, 1, None)]
    hist = snap["histograms"]
    assert hist["ssm.slots_stepped"]["count"] == eng.segments_run
    assert 1.0 <= hist["ssm.slots_stepped"]["p50"] <= S
    assert hist["moe.experts_touched_share"]["count"] == eng.segments_run
    assert eng.pool.free_pages == eng.pool.n_pages - 1


def test_engine_with_interpreted_kernels_serves_the_same_tokens(served):
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, impl="pallas_interpret")
    assert eng.resolved_attention_impl == "pallas_interpret"
    for rid in ("a", "c", "e", "g"):
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    for rid in ("a", "c", "e", "g"):
        np.testing.assert_array_equal(got[rid], out[rid])
    # the attention layer's K and V stay in their pages under the kernel
    assert eng._chunk_in_pages()
    assert eng.metrics.counter(
        "decode.prefill_paged_chunk_programs").value == eng.metrics.counter(
        "decode.chunk_waves").value == 8


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_padded_chunk_leaves_the_state_of_its_real_rows(impl):
    """The engine pads a prompt's last chunk with token 0; the mixers'
    states that come back are the states after the last REAL row, bit for
    bit against an unpadded run — and a chunk at position 0 starts from
    zero whatever the cache handed in holds."""
    cfg, params = _config(), R.make_params(HF, 5)
    rng = np.random.default_rng(2)
    real = 11
    ids = np.zeros((1, CHUNK), np.int32)
    ids[0, :real] = rng.integers(1, 256, real)
    dirty = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype) for k, v in
             nemotron_h.init_cache(cfg, 1, 32).items()}
    last_p, cache_p = nemotron_h.forward_cached_row(
        params, jnp.asarray(ids), dirty, 0, cfg, real - 1, impl=impl)
    last_u, cache_u = nemotron_h.forward_cached_row(
        params, jnp.asarray(ids[:, :real]), nemotron_h.init_cache(cfg, 1, 32),
        0, cfg, real - 1, impl=impl)
    for kind in ("ssm", "conv"):
        np.testing.assert_array_equal(np.asarray(cache_p[kind]),
                                      np.asarray(cache_u[kind]), err_msg=kind)
    np.testing.assert_allclose(last_p, last_u, rtol=1e-5, atol=1e-5)
    # and a chunk that does NOT begin at 0 starts from what it is handed
    _, cache_c = nemotron_h.forward_cached_row(
        params, jnp.asarray(ids), dirty, CHUNK, cfg, real - 1, impl=impl)
    assert np.abs(np.asarray(cache_c["ssm"]) - np.asarray(
        cache_u["ssm"])).max() > 1e-3


def test_slots_that_do_not_decode_keep_their_state_bit_for_bit(served):
    """While one slot decodes through several segments, a slot that holds
    a finished request's state and a slot mid-prefill (between its first
    and its second chunk) see every state pool row of theirs unchanged by
    the segments; the mid-prefill slot's rows change only by its own
    chunk programs."""
    cfg, params, reqs, _, _ = served
    eng = _engine(cfg, params)
    eng.submit("b", *reqs["b"])         # slot 0: decodes 24 tokens
    eng.submit("d", reqs["d"][0], 2)    # slot 1: done after one segment
    eng.step_segment()
    eng.step_segment()
    assert eng._slot_req[1] is None and eng.remaining[0] > 0
    state = [k for k in eng.pools if k.split("_")[1] in ("ssm", "conv")]
    assert len(state) == 6
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
            for row in (2, 3):
                np.testing.assert_array_equal(mine[k][row], after[k][row],
                                              err_msg=f"{k} row {row}")
    # the empty slot's rows never moved at all
    for k in state:
        np.testing.assert_array_equal(np.asarray(eng.pools[k])[3],
                                      before[k][3])
    eng._seg = real_seg
    eng.run()


def test_a_reused_slot_serves_what_a_fresh_engine_serves(served):
    """``_retire`` leaves a slot's state rows standing; the next request's
    first chunk starts from zero all the same."""
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, slots=1)
    for rid in ("g", "b", "e"):         # one slot: each inherits the last's
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    for rid in ("g", "b", "e"):
        fresh = _engine(cfg, params, slots=1)
        fresh.submit(rid, *reqs[rid])
        np.testing.assert_array_equal(got[rid], fresh.run()[rid])
        np.testing.assert_array_equal(got[rid], out[rid])


def test_preempt_then_resume_serves_the_uninterrupted_tokens(served):
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params)
    ids, n = reqs["a"]
    eng.submit("a", ids, n)
    eng.submit("b", *reqs["b"])
    while len(eng._tokens.get("a", ())) < 9:
        eng.step_segment()
    got = eng.preempt("a")
    assert eng.metrics.counter("decode.state_rebuilds").value == 1
    assert 9 <= len(got["tokens"]) < n and got["remaining"] == n - len(
        got["tokens"])
    # the resume re-prefills prompt + tokens: the state is rebuilt from 0
    eng.submit("a2", np.concatenate([ids, got["tokens"][None]], axis=1),
               got["remaining"])
    rest = eng.run()
    np.testing.assert_array_equal(
        np.concatenate([got["tokens"], rest["a2"]]), out["a"])
    np.testing.assert_array_equal(rest["b"], out["b"])


def test_what_the_engine_refuses_for_state_layers():
    cfg, params = _config(), R.make_params(HF, 1)
    with pytest.raises(ValueError, match="chunk_tokens"):
        _engine(cfg, params, chunk=None)
    with pytest.raises(ValueError, match="state layers"):
        _engine(cfg, params, sharing=True)
    eng = _engine(cfg, params)
    assert all(eng.chunk_eligible(p) for p in (1, 16, 17, 90))
    odd = _engine(cfg, params, chunk=20)
    with pytest.raises(ValueError, match="whole-prompt"):
        # 85 tokens in chunks of 20 reach row 100, past the capacity of 96
        odd.submit("x", np.ones((1, 85), np.int32), 2)
    odd.submit("x", np.ones((1, 80), np.int32), 2)
    log = kv_pages.PageOwnershipLog()
    eng.attach_ownership_log(log)
    assert log.uncovered is None and "slot-owned states" in log.unkeyed
    assert log.snapshot()["unkeyed"] == log.unkeyed
    eng.submit("y", np.ones((1, 5), np.int32), 3)
    eng.run()
    codes = [d.code for d in analyze_pages(log).errors]
    assert codes == ["PGL009"]
    plain = kv_pages.PageOwnershipLog()
    assert plain.unkeyed is None and "unkeyed" not in plain.snapshot()


def test_the_step_graph_wires_state_pools_to_one_task_each():
    cfg = _config()
    ddag = build_paged_decode_dag(cfg, slots=S, page_size=PS, n_pages=37,
                                  pages_per_seq=PPSEQ)
    g = ddag.graph
    assert g.state_kinds == ("conv", "ssm")
    need = {t.task_id: set(t.params_needed) for t in g.tasks()}
    assert "page_table" in need["layer_2"]              # the attention layer
    for tid in ("layer_0", "layer_1", "layer_3", "layer_4", "layer_5"):
        assert "page_table" not in need[tid], tid
    assert {"cache_ssm_3", "cache_conv_3"} <= need["layer_3"]
    assert not any(p.startswith("cache_") for p in need["layer_1"])
    assert not analyze_decode(g, param_specs=ddag.param_specs).errors
    # a second holder of a state pool is refused: its task hands the whole
    # pool back and the loop composer keeps one writer's
    t = g["layer_5"]
    t.params_needed.add("cache_ssm_0")
    t.param_bytes["cache_ssm_0"] = g["layer_0"].param_bytes["cache_ssm_0"]
    bad = analyze_decode(g, param_specs=ddag.param_specs)
    assert [d.code for d in bad.errors] == ["DEC003"]
    assert "cache_ssm_0" in bad.errors[0].message


# -- the ungated experts --------------------------------------------------------


def _expert_layer(seed=0, n=13):
    cfg = _config()
    p = {k: v for k, v in R.make_params(HF, seed).items()
         if k.startswith("h1_")}
    p = {k[3:]: v for k, v in p.items()}
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n, 32)),
                    jnp.float32)
    return cfg, p, x


def test_ungated_experts_kernel_is_its_twin_and_the_reference():
    """I = 20: no multiple of 128 or of 16, so the I tile is I itself;
    the kernel interpreted, the ``ragged_dot`` twin and the reference's
    masked loop over the held experts agree, live mask and all."""
    cfg, p, x = _expert_layer()
    assert xing4.ungated_i_tile(20) == 20
    assert xing4.ungated_i_tile(1856) == 464 and xing4.ungated_i_tile(1024) == 512
    with jax.default_matmul_precision("highest"):
        want = R._moe(x, p, HF, False)
        y_x, st_x = nemotron_h.experts(p, x, cfg, impl="xla")
        y_k, st_k = nemotron_h.experts(p, x, cfg, impl="pallas_interpret")
        live = jnp.arange(13) % 3 != 0
        y_l, _ = nemotron_h.experts(p, x, cfg, live=live,
                                    impl="pallas_interpret")
        shared = jnp.square(jax.nn.relu(x @ p["shared_up_w"])) @ p[
            "shared_down_w"]
    np.testing.assert_allclose(y_x, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_k, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st_x, st_k)
    # a token that is not live keeps the shared expert and loses its picks
    np.testing.assert_allclose(y_l[live], want[live], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_l[~live], shared[~live], rtol=1e-4,
                               atol=1e-4)


def test_the_two_chips_shares_add_up_to_the_whole_layer():
    """Experts 0-3 on one chip and 4-7 on the other, the shared expert
    counted once, = the uncut reference layer over all 8."""
    whole = dict(HF, n_routed_experts=8, held_experts=None)
    params = R.make_params(whole, 4)
    p = {k[3:]: v for k, v in params.items() if k.startswith("h1_")}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(17, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = R._moe(x, p, whole, False)
        shared = jnp.square(jax.nn.relu(x @ p["shared_up_w"])) @ p[
            "shared_down_w"]
        parts = []
        for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
            cfg = nemotron_h.NemotronHConfig.from_hf(
                dict(HF, held_experts=list(held)), dtype=jnp.float32)
            mine = dict(p, exp_up_w=p["exp_up_w"][jnp.asarray(held)],
                        exp_down_w=p["exp_down_w"][jnp.asarray(held)])
            y, _ = nemotron_h.experts(mine, x, cfg, impl="pallas_interpret")
            parts.append(y - shared)
    assert float(jnp.abs(parts[0]).max()) > 1e-2 < float(
        jnp.abs(parts[1]).max())
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               rtol=2e-4, atol=2e-4)

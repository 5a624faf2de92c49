"""The dots3 block — sparse selection inside paged latent attention, a
window layer's ring, a chip's share of the experts — against the
benchmark's plain reference (which imports nothing of the program), its
kernels against their ``jax.numpy`` forms, and what the engine refuses
for a cache with ring layers."""

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

from benchmark.reference import dots3 as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu.backends.device import (  # noqa: E402
    DeviceBackend,
)
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import dots3  # noqa: E402
from distributed_llm_scheduler_tpu.models.kv_pages import (  # noqa: E402
    PageOwnershipLog,
    PagePool,
)
from distributed_llm_scheduler_tpu.ops import attention as A  # noqa: E402

PERIOD = ["full_attention", "full_attention", "sliding_attention",
          "sliding_attention", "sliding_attention"]
#: two periods, a selection of 16 rows, a window of 9 in a ring of 16
HF = {
    "hidden_size": 32, "num_hidden_layers": 9, "first_k_dense_replace": 1,
    "layer_types": PERIOD + PERIOD[1:], "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "rope_theta": 10000, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": 16, "swa_num_attention_heads": 2,
    "swa_q_lora_rank": 16, "swa_kv_lora_rank": 24,
    "swa_qk_nope_head_dim": 12, "swa_qk_rope_head_dim": 4,
    "swa_v_head_dim": 8, "swa_rope_theta": 1000, "sliding_window_size": 9,
    "apply_mla_qkv_lora_rescale": True, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_router_outputs": 8, "held_experts": [1, 2, 5, 6],
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "max_position_embeddings": 256, "vocab_size": 256, "dtype": "float32",
    "init": {"std": 0.3, "q_gain": 1.0},
}
S, PS, PPSEQ = 3, 8, 12


def _config(hf=HF):
    return dots3.Dots3Config.from_hf(hf, dtype=jnp.float32, ring_rows=16)


def _engine(cfg, params, impl=None, chunk=16, sharing=False, slots=S):
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
    # prompts under and over the selection (16) and the ring (16); with
    # their outputs every one wraps its rings at least twice
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
    want = {f"h{i}_{k}": tuple(s) for i in range(9)
            for k, (s, _) in R.layer_shapes(HF, i).items()}
    got = {k: tuple(s) for k, (s, _) in dots3.param_shapes(cfg).items()
           if k[0] == "h" and k[1].isdigit()}
    assert got == want
    assert cfg.layer_types == dots3.published_layer_types(9)
    assert dots3.published_layer_types(46).count("full") == 13


def test_served_tokens_are_the_references_across_wraps_and_selections(served):
    """Chunked prefill and paged decode through the engine against the
    reference's full forward: contexts from 10 to 74 rows, so under and
    over ``index_topk`` and across several wraps of the 16-row rings."""
    cfg, params, reqs, out, eng = served
    for rid, (ids, n) in reqs.items():
        seq = np.concatenate([ids[0], out[rid]])
        gaps = R.served_gaps(params, HF, seq, ids.shape[1], n, 80)
        assert gaps.max() < 1e-3, (rid, gaps.max())
    share = eng.metrics.snapshot()["histograms"]["dsa.selected_share"]
    assert share["count"] == eng.segments_run and 0.2 < share["p50"] < 0.6
    assert eng.pool.free_pages == eng.pool.n_pages - 1


def test_program_logits_are_the_references():
    cfg, params = _config(), R.make_params(HF, 77)
    ids = np.random.default_rng(1).integers(1, 256, (1, 64))
    ref = R.logits(params, HF, ids, rows=slice(0, 64))
    with jax.default_matmul_precision("highest"):
        mine = dots3.forward(params, jnp.asarray(ids), cfg, impl="xla")
    assert float(jnp.abs(ref - mine).max()) < 5e-4


@pytest.mark.parametrize("impl", [None, "pallas_interpret"])
def test_the_decode_steps_read_the_rows_the_reference_selects(served, impl):
    """``stats_probe`` hands out what ``jit_seg``'s full layers read, a
    step and slot at a time: at every decoded position exactly the
    reference's ``min(t + 1, index_topk)`` rows."""
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, impl=impl)
    read = {}

    def probe(stats, rids, lengths, owed):
        idx = stats["dsa_idx"]            # (steps, full layers, slots, k)
        assert set(stats) == {"dsa_idx"} and idx.shape[1:] == (3, S, 16)
        for s, rid in enumerate(rids):
            for j in range(min(int(owed[s]), idx.shape[0])):
                read[rid, int(lengths[s]) + j] = idx[j, :, s]
        for s in np.flatnonzero(owed <= 0):
            assert (idx[:, :, s] == -1).all()

    eng.stats_probe = probe
    rids = ("b", "c") if impl else tuple(reqs)
    for rid in rids:
        eng.submit(rid, *reqs[rid])
    eng.run()
    for rid in rids:
        ids, n = reqs[rid]
        P = ids.shape[1]
        seq = np.concatenate([ids[0], out[rid]])
        _, picked, _ = R.served_gaps(params, HF, seq, P, n, 80,
                                     selections=True)
        for i in range(1, n):
            for layer in range(3):
                rows = read[rid, P - 1 + i][layer]
                assert sorted(rows[rows >= 0]) == list(
                    np.flatnonzero(picked[layer, i])), (rid, i, layer)


@pytest.mark.parametrize("impl", ["pallas_interpret"])
def test_engine_with_interpreted_kernels_serves_the_same_tokens(served, impl):
    cfg, params, reqs, out, _ = served
    eng = _engine(cfg, params, impl=impl)
    for rid in ("b", "c"):
        eng.submit(rid, *reqs[rid])
    got = eng.run()
    for rid in ("b", "c"):
        assert (got[rid] == out[rid]).all()


def test_a_context_under_the_selection_is_plain_mla():
    """``index_topk`` past every context: the decode path selects every
    row, so the indexer's weights change nothing."""
    hf = dict(HF, index_topk=512)
    cfg, params = _config(hf), R.make_params(hf, 3)
    other = {k: (v * -2.5 if "_idx_" in k and k.endswith("_w") else v)
             for k, v in params.items()}
    ids, n = _requests(5)["c"]
    outs = []
    for w in (params, other):
        eng = _engine(cfg, w)
        eng.submit("r", ids, n)
        outs.append(eng.run()["r"])
    assert (outs[0] == outs[1]).all()
    share = eng.metrics.snapshot()["histograms"]["dsa.selected_share"]
    assert share["min"] == 1.0


# -- kernels against their jnp forms (interpret mode) --------------------------


def _pool_and_table(rng, slots, ppseq, ps, width):
    n_pages = slots * ppseq + 1
    pool = jnp.asarray(rng.normal(size=(n_pages, ps, width)), jnp.float32)
    table = (1 + rng.permutation(slots * ppseq).astype(np.int32)).reshape(
        slots, ppseq)
    return pool, jnp.asarray(table)


def test_dsa_index_kernel_scores_the_live_rows():
    rng = np.random.default_rng(0)
    slots, ppseq, ps, Hi, Di = 3, 5, 8, 4, 16
    pool, table = _pool_and_table(rng, slots, ppseq, ps, Di)
    q = jnp.asarray(rng.normal(size=(slots, Hi, Di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(slots, Hi)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(slots, Di)), jnp.float32)
    lengths = jnp.asarray([0, 17, 39], jnp.int32)
    want = A.dsa_index_scores(q, w, pool, table, lengths, new, impl="xla")
    got = A.dsa_index_scores(q, w, pool, table, lengths, new,
                             impl="pallas_interpret")
    assert np.isneginf(np.asarray(got)[1, 18:]).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    keys = jnp.take(pool, table[2], axis=0).reshape(-1, Di)
    one = (jnp.maximum(q[2] @ keys[:39].T, 0) * w[2][:, None]).sum(0)
    np.testing.assert_allclose(got[2, :39], one, rtol=1e-5, atol=1e-5)


def test_selected_row_attention_reads_the_selected_rows_only():
    rng = np.random.default_rng(1)
    slots, ppseq, ps, H, width, rank, k = 2, 6, 8, 4, 40, 32, 16
    pool, table = _pool_and_table(rng, slots, ppseq, ps, width)
    q = jnp.asarray(rng.normal(size=(slots, H, width)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(slots, width)), jnp.float32)
    lengths = jnp.asarray([5, 41], jnp.int32)
    scores = jnp.where(
        jnp.arange(ppseq * ps)[None] <= lengths[:, None],
        jnp.asarray(rng.normal(size=(slots, ppseq * ps)), jnp.float32),
        -jnp.inf)
    idx, n = A.dsa_select(scores, lengths, k)
    assert n.tolist() == [6, 16]
    want = A.dsa_sparse_attention(q, pool, table, idx, n, lengths, new, rank,
                                  impl="xla")
    got = A.dsa_sparse_attention(q, pool, table, idx, n, lengths, new, rank,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # by hand for slot 1: softmax over its 16 picked rows alone
    rows = jnp.take(pool, table[1], axis=0).reshape(-1, width)
    rows = rows.at[41].set(new[1])[idx[1]]
    p = jax.nn.softmax(q[1] @ rows.T, axis=-1)
    np.testing.assert_allclose(got[1], p @ rows[:, :rank], rtol=1e-4,
                               atol=1e-5)
    # rows it did not pick do not matter
    keep = ~np.isin(np.arange(16, 24), np.asarray(idx[1]))
    assert keep.any()
    pages = np.array(pool[table[1, 2]])
    pages[keep] = 99.0
    spoiled = pool.at[table[1, 2]].set(jnp.asarray(pages))
    again = A.dsa_sparse_attention(q, spoiled, table, idx, n, lengths, new,
                                   rank, impl="pallas_interpret")
    np.testing.assert_allclose(again[1], got[1], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lengths", [[0, 3, 8], [15, 16, 17], [40, 100, 31]])
def test_ring_kernel_masks_what_the_window_leaves_behind(lengths):
    """Before, at and far past a wrap of the 16-row ring: the rows older
    than the window that the ring still holds are not seen."""
    rng = np.random.default_rng(2)
    slots, rp, ps, H, width, rank, window = 3, 2, 8, 4, 40, 32, 9
    pool = jnp.asarray(rng.normal(size=(1 + slots * rp, ps, width)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, H, width)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(slots, width)), jnp.float32)
    L = jnp.asarray(lengths, jnp.int32)
    want = A.latent_window_attention(q, pool, L, new, rank, window,
                                     impl="xla")
    got = A.latent_window_attention(q, pool, L, new, rank, window,
                                    impl="pallas_interpret")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    ring = np.asarray(pool[1:]).reshape(slots, rp * ps, width)
    for s, n in enumerate(lengths):     # by hand, position by position
        rows = [np.asarray(new[s]) if p == n else ring[s, p % (rp * ps)]
                for p in range(max(0, n - window + 1), n + 1)]
        rows = jnp.asarray(np.stack(rows))
        p = jax.nn.softmax(q[s] @ rows.T, axis=-1)
        np.testing.assert_allclose(got[s], p @ rows[:, :rank], rtol=1e-4,
                                   atol=1e-5)


def test_kth_largest_mask_is_top_k_ties_to_the_earlier_row():
    rng = np.random.default_rng(3)
    scores = np.round(rng.normal(size=(5, 7, 40)), 1)       # many ties
    scores[0, 0, :] = 0.0
    allowed = rng.random((5, 7, 40)) < 0.7
    allowed[1, 1, 3:] = False                               # fewer than k
    got = np.asarray(A.kth_largest_mask(
        jnp.asarray(scores, jnp.float32), jnp.asarray(allowed), 8))
    _, idx = jax.lax.top_k(
        jnp.where(jnp.asarray(allowed), jnp.asarray(scores, jnp.float32),
                  -jnp.inf), 8)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    assert (got == (want & allowed)).all()
    assert got[1, 1].sum() == allowed[1, 1].sum() <= 3


# -- a chip's share of the experts -----------------------------------------------


def test_the_eight_shares_add_up_to_the_whole_layer():
    """16 routed experts over 8 chips, 2 a chip: the parts the 8 held
    lists give, the shared expert counted once, are the uncut layer — by
    the program against the reference's layer with every expert."""
    hf = dict(HF, n_routed_experts=16, n_router_outputs=16,
              held_experts=list(range(16)), num_hidden_layers=2)
    whole = R.make_params(hf, 11)
    p = {k[3:]: v for k, v in whole.items() if k.startswith("h1_")}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = R._moe(x, {k: v.astype(jnp.float32) for k, v in p.items()},
                      hf, False)
        total = jnp.zeros_like(x)
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            cfg = _config(dict(hf, n_routed_experts=2, held_experts=held))
            mine = dict(p, exp_gu_w=p["exp_gu_w"][2 * chip:2 * chip + 2],
                        exp_down_w=p["exp_down_w"][2 * chip:2 * chip + 2])
            y, stats = dots3.moe_ffn(mine, x, cfg, held=held,
                                     shared=chip == 0, impl="xla")
            total = total + y
            assert 0.0 <= float(stats[0]) <= 1.0
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# -- what a cache with ring layers refuses --------------------------------------


def test_the_cache_spec_is_per_layer_and_a_ring_does_not_grow():
    cfg = _config()
    spec = dots3.cache_spec(cfg)
    assert spec.has_rings and spec.n_layers == 9
    # the layers differ: nothing answers for the whole model
    for ask in (lambda: spec.rows, lambda: spec.kinds, lambda: spec.row_elems,
                lambda: spec.init_slabs(1, 8, jnp.float32)):
        with pytest.raises(ValueError, match="layers differ"):
            ask()
    assert spec.walk == ("i", 16)      # `_dsa_index` walks the keys' blocks
    assert [spec.layer_kinds(i) for i in (0, 1, 2)] == [
        ("c", "i"), ("c", "i"), ("w",)]
    assert spec.layer(2).window == 9 and spec.layer(0).window is None
    small = spec.init_pools(9, 8, jnp.float32, slots=2)
    large = spec.init_pools(999, 8, jnp.float32, slots=2)
    assert small["cache_w_2"].shape == large["cache_w_2"].shape == (5, 8, 128)
    assert large["cache_c_0"].shape == (999, 8, 128)
    assert large["cache_i_0"].shape == (999, 8, 16)
    assert spec.ring_table(2, 8).tolist() == [[1, 2], [3, 4]]
    # the pages a request is charged are the paged layers' alone
    assert spec.paged_row_elems == 3 * (128 + 16)     # the full layers
    with pytest.raises(ValueError, match="slots"):
        spec.init_pools(9, 8, jnp.float32)


def test_prefix_sharing_is_refused_for_ring_layers():
    cfg, params = _config(), R.make_params(HF, 1)
    with pytest.raises(ValueError, match="ring"):
        _engine(cfg, params, sharing=True)
    eng = _engine(cfg, params)
    eng.pool.sharing = True        # the serve bench toggles it live
    with pytest.raises(ValueError, match="prefix sharing is not built"):
        eng.submit("r", np.ones((1, 9), np.int32), 3)
        eng.run()


def test_the_page_prover_refuses_what_it_cannot_see():
    from distributed_llm_scheduler_tpu.analysis.page_pass import analyze_pages

    cfg, params = _config(), R.make_params(HF, 1)
    eng = _engine(cfg, params)
    log = PageOwnershipLog()
    eng.attach_ownership_log(log)
    eng.submit("r", np.ones((1, 20), np.int32), 5)
    eng.run()
    assert log.uncovered and "ring" in log.uncovered
    for source in (log, log.snapshot()):
        rep = analyze_pages(source)
        assert [d.code for d in rep.errors] == ["PGL008"]
    # a cache without rings is proven as before
    plain = PageOwnershipLog()
    assert plain.uncovered is None and "uncovered" not in plain.snapshot()


def test_preemption_resumes_by_prefill_over_fresh_rings():
    """A preempted request re-submitted as prompt + tokens serves the
    continuation an unpreempted run does: its slot's rings are refilled
    by the prefill, whatever slot it lands in."""
    cfg, params = _config(), R.make_params(HF, 2**31 + 9)
    ids, n = _requests()["a"]
    eng = _engine(cfg, params, slots=2)
    eng.submit("whole", ids, n)
    whole = eng.run()["whole"]
    eng = _engine(cfg, params, slots=2)
    eng.submit("filler", np.full((1, 12), 7, np.int32), 40)
    eng.submit("first", ids, n)
    for _ in range(4):
        eng.step_segment()
    got = eng.preempt("first")
    assert 0 < got["remaining"] < n
    eng.submit("again", np.concatenate([ids[0], got["tokens"]])[None],
               got["remaining"])
    rest = eng.run()["again"]
    assert (np.concatenate([got["tokens"], rest]) == whole).all()

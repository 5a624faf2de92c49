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
from distributed_llm_scheduler_tpu.models import kv_pages, laguna  # noqa: E402
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


def _engine(cfg, params, impl=None, chunk=16, slots=S, n_pages=None,
            ppseq=PPSEQ):
    n_pages = n_pages or slots * ppseq + 1
    ddag = build_paged_decode_dag(
        cfg, slots=slots, page_size=PS, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("heft").schedule(ddag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=PS)
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, cfg, params, pool, slots=slots,
        pages_per_seq=ppseq, seg_steps=4, attention_impl=impl,
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
    # and every chunk program (a's 40 tokens: 16, 16, 8) left the full
    # layers in their pages; the gather path has no paged form
    assert eng._chunk_in_pages() and not eng0._chunk_in_pages()
    assert count(eng, "prefill_paged_chunk_programs") == count(
        eng, "chunk_waves") == 3
    assert count(eng0, "prefill_paged_chunk_programs") == 0 < count(
        eng0, "chunk_waves")


def test_a_chunk_writes_its_own_pages_and_the_dense_path_agrees(served):
    """Two engines on the interpreted kernels, one held to the dense round
    trip: a short request decodes in one slot while a prompt of three
    chunks is prefilled in another.  Every chunk program of the paged
    path changes the chunk's own pages of the four paged pools and no
    other page but the trash page — not the decoding slot's, not the
    earlier chunks' — and when the prompt is in, the two engines' pools
    agree on every page a request holds and on every ring."""
    cfg, params, reqs, out, _ = served
    paged, dense = (_engine(cfg, params, impl="pallas_interpret")
                    for _ in range(2))
    dense._chunk_in_pages = lambda: False
    real, seen = paged._chunk_prefill, []

    def watched(ids, pt_row, base, creal, slot=0, nxt_chunk=None):
        before = {k: np.asarray(v) for k, v in paged.pools.items()}
        first = real(ids, pt_row, base, creal, slot, nxt_chunk)
        seen.append((before, {k: np.asarray(v) for k, v in
                              paged.pools.items()}, np.array(pt_row), base))
        return first

    paged._chunk_prefill = watched
    in_pool = lambda name: name.split("_")[1] in ("k", "v")  # noqa: E731
    for eng in (paged, dense):
        eng.submit("b", *reqs["b"])     # 10 tokens: prefilled whole
        eng.submit("a", *reqs["a"])     # 40 tokens: chunks of 16, 16, 8
        eng.step_segment()
        while eng.is_prefilling("a"):
            eng.step_segment()
    assert [base for *_, base in seen] == [0, 16, 32]
    held = set(paged._slot_pages[0]) | set(paged._slot_pages[1])
    assert len(held) > 8 and 0 not in held
    for before, after, row, base in seen:
        mine = set(row[base // PS: base // PS + 16 // PS].tolist()) - {0}
        assert mine and mine <= held
        for name in before:
            moved = {int(i) for i in np.nonzero(
                (before[name] != after[name]).any(axis=(1, 2)))[0]}
            if in_pool(name):       # a full layer's: out of the shared pool
                assert mine <= moved <= mine | {0}, (name, base)
    for name in paged.pools:
        a, b = np.asarray(paged.pools[name]), np.asarray(dense.pools[name])
        pages = sorted(held) if in_pool(name) else slice(1, None)
        np.testing.assert_array_equal(a[pages], b[pages], err_msg=name)
    assert paged.metrics.counter(
        "decode.prefill_paged_chunk_programs").value == 3
    assert dense.metrics.counter(
        "decode.prefill_paged_chunk_programs").value == 0
    for eng in (paged, dense):
        got = eng.run()
        for rid in ("a", "b"):
            np.testing.assert_array_equal(got[rid], out[rid])


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


#: (pages a slot, chunk's first position, its real rows, sequences):
#: position 0; one chunk in, two sequences with a table row each; a last
#: chunk whose live rows end inside a page; the last chunk of a 264-page
#: slot (the cell's 33,792 rows at a sixteenth); a capacity of 10 pages
#: that is no whole key block, the chunk's pad rows past it
PAGED_CHUNKS = [(12, 0, 32, 1), (12, 32, 32, 2), (12, 64, 21, 1),
                (264, 2080, 32, 1), (10, 64, 13, 1)]


@pytest.mark.parametrize("ppseq,base,creal,b", PAGED_CHUNKS)
def test_paged_chunk_kernel_is_the_loop(ppseq, base, creal, b, monkeypatch):
    """``_gqa_chunk_flash_paged`` interpreted against the model file's
    loop over the same rows gathered dense: the chunk's rows written
    through table rows that are NOT in ascending physical order and whose
    tails are the trash page, key blocks of 4 pages and query tiles of 2
    (the cell's 512-row blocks of 128-row pages at a sixteenth); groups
    of 3 and 5.  Only the chunk's own pages change."""
    cfg = _config()
    ps, T, Hkv, hd = PS, 32, 2, 8
    monkeypatch.setattr(G, "_CHUNK_KV_BLOCK", 4 * ps)
    monkeypatch.setattr(G, "_CHUNK_Q_TILE", 2 * ps)
    rng = np.random.default_rng(base + ppseq)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    n_pages = (b + 1) * ppseq + 1
    claimed = -(-(base + creal) // ps)
    rows = np.zeros((b, ppseq), np.int32)
    rows[:, :claimed] = rng.permutation(np.arange(1, n_pages))[
        :b * claimed].reshape(b, claimed)
    assert (np.diff(rows[:, :claimed], axis=1) < 0).any(axis=1).all()
    pages = jnp.asarray(rows)
    k0, v0 = mk(n_pages, ps, Hkv * hd), mk(n_pages, ps, Hkv * hd)
    k_new, v_new = mk(b, T, Hkv * hd), mk(b, T, Hkv * hd)
    k1 = kv_pages.write_chunk_pages(k0, k_new, pages, jnp.int32(base))
    v1 = kv_pages.write_chunk_pages(v0, v_new, pages, jnp.int32(base))
    mine = []
    for s in range(b):      # a page of the chunk past the table: to trash
        own = [int(rows[s, j]) for j in range(base // ps, (base + T) // ps)
               if j < ppseq and rows[s, j]]
        assert own
        np.testing.assert_array_equal(
            np.asarray(k1)[own].reshape(-1, Hkv * hd),
            np.asarray(k_new)[s, :len(own) * ps])
        mine += own
    others = np.setdiff1d(np.arange(1, n_pages), mine)
    np.testing.assert_array_equal(np.asarray(k1)[others],
                                  np.asarray(k0)[others])
    dense = lambda pool: jnp.take(pool, pages, axis=0).reshape(  # noqa: E731
        b, ppseq * ps, Hkv, hd).transpose(0, 2, 1, 3)
    for H in (6, 10):
        q = mk(b, T, H, hd)
        want = laguna.chunk_attention(q, dense(k1), dense(v1), base, cfg,
                                      "xla")
        got = G.gqa_paged_chunk_attention(
            q, k1, v1, pages, jnp.int32(base), scale=cfg.softmax_scale,
            impl="pallas_interpret")
        np.testing.assert_allclose(got[:, :creal], want[:, :creal],
                                   rtol=2e-5, atol=2e-5)
        # and the dense kernel on the same tiles is the same to the bit
        same = laguna.chunk_attention(q, dense(k1), dense(v1), base, cfg,
                                      "pallas_interpret")
        if ppseq % 4 == 0:
            np.testing.assert_array_equal(got[:, :creal], same[:, :creal])


def test_the_paged_chunk_kernel_is_taken_by_shape(monkeypatch):
    """The cell's geometry takes it; a head that is no whole lane tile
    (GPT-2's 64) or a page under the sublane tile does not, and then
    ``auto`` keeps the dense round trip where an explicit request
    raises.  The gather path has no paged form."""
    from distributed_llm_scheduler_tpu.ops import attention as A

    bf16 = jnp.bfloat16
    assert G.gqa_paged_chunk_constraints(128, 128, bf16) == []
    assert "head_dim 64" in G.gqa_paged_chunk_constraints(16, 64, bf16)[0]
    assert "page_size 8" in G.gqa_paged_chunk_constraints(8, 128, bf16)[0]
    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    assert G.gqa_paged_chunk_impl(None, 128, 128, bf16) == "pallas"
    assert G.gqa_paged_chunk_impl("auto", 16, 64, bf16) == "xla"
    assert G.gqa_paged_chunk_impl(
        "pallas_interpret", 16, 64, bf16) == "pallas_interpret"
    with pytest.raises(ValueError, match="does not qualify"):
        G.gqa_paged_chunk_impl("pallas", 16, 64, bf16)
    monkeypatch.setattr(A, "_auto_impl", lambda: "xla")
    assert G.gqa_paged_chunk_impl(None, 128, 128, bf16) == "xla"
    with pytest.raises(ValueError, match="no gather form"):
        G.gqa_paged_chunk_attention(
            jnp.zeros((1, 8, 6, 8)), jnp.zeros((3, 8, 16)),
            jnp.zeros((3, 8, 16)), jnp.zeros((1, 2), jnp.int32), 0,
            scale=1.0, impl="xla")


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def test_the_paged_chunk_program_holds_nothing_of_a_slots_capacity():
    """The chunk program on the paged path, equation by equation: no
    value of ``capacity x Hkv x head_dim`` elements or more but the pools
    themselves (each written where it lies) — where the dense round trip
    holds the slot's whole cache four ways."""
    cfg, params = _config(), R.make_params(HF, 3)
    ppseq, found = 128, {}
    for name in ("paged", "dense"):
        eng = _engine(cfg, params, impl="pallas_interpret", slots=2,
                      ppseq=ppseq)
        if name == "dense":
            eng._chunk_in_pages = lambda: False
        eng.submit("a", np.ones((1, 20), np.int32), 2)
        eng.run()
        (key, fn), = [(k, f) for k, f in eng._prefill_store.items()
                      if k[0] == "chunk"]
        i32 = jnp.int32
        jaxpr = jax.make_jaxpr(fn)(
            eng.weights, jnp.zeros((1, key[1]), i32), eng.pools,
            jnp.zeros((ppseq,), i32), i32(0), i32(1), *eng._ring_args((0,)))
        pool_shapes = {v.shape for v in eng.pools.values()}
        floor = ppseq * PS * cfg.n_kv_heads * cfg.head_dim
        found[name] = sorted(
            (eqn.primitive.name, v.aval.shape) for eqn in _walk(jaxpr.jaxpr)
            for v in eqn.outvars
            if hasattr(v.aval, "shape") and v.aval.shape not in pool_shapes
            and int(np.prod(v.aval.shape)) >= floor)
    assert found["paged"] == [], found["paged"]
    assert len(found["dense"]) >= 8      # K and V of two layers, out and back


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

"""The Ouro family (``models/ouro.py``): a stack whose layers run more
than once a token, served through the path every family takes.

The program against ``benchmark/reference/ouro.py`` (float32, no cache,
no kernel) on seeded random weights at ``ouro-tiny`` — 3 layers x 3
passes, 4 heads of 128: chunked prefill then decoding through the paged
cache on logits, every pass's state and every pass's exit gate; what the
graph names and what the analysis counts; the passes rolled into one
traced loop; a pool three slots oversubscribe; a shared page copied in
every plane."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import ouro as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler, models  # noqa: E402
from distributed_llm_scheduler_tpu.analysis.decode_pass import (  # noqa: E402
    analyze_decode,
)
from distributed_llm_scheduler_tpu.backends.decode_loop import (  # noqa: E402
    compose_paged_step_fn,
)
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend  # noqa: E402
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import ouro  # noqa: E402
from distributed_llm_scheduler_tpu.models.kv_pages import (  # noqa: E402
    CacheSpec,
    LayerCache,
    PagePool,
)

CFG = ouro.OuroConfig.tiny()
SLOTS, PS, N_PAGES, PPSEQ, CHUNK = 3, 8, 16, 12, 16


def hf_of(cfg) -> dict:
    """The reference's view of a config: the published keys."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab_size,
        total_ut_steps=cfg.total_ut_steps, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps, early_exit_threshold=1.0,
        max_position_embeddings=cfg.max_positions, dtype="float32",
        init={"std": 0.3})


def build(cfg=CFG, impl=None, sharing=False, n_pages=N_PAGES, seed=5):
    dag = build_paged_decode_dag(
        cfg, slots=SLOTS, page_size=PS, n_pages=n_pages,
        pages_per_seq=PPSEQ, attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("heft").schedule(dag.graph, cluster)
    weights = R.make_params(hf_of(cfg), seed)
    engine = DeviceBackend(cluster).paged_decode_engine(
        dag.graph, plan, cfg, weights,
        PagePool(n_pages=n_pages, page_size=PS, sharing=sharing),
        slots=SLOTS, pages_per_seq=PPSEQ, seg_steps=4, attention_impl=impl,
        chunk_tokens=CHUNK)
    return dag, plan, weights, engine


def prompts(lengths, seed=0, vocab=CFG.vocab_size):
    rng = np.random.default_rng(seed)
    return {f"r{i}": rng.integers(1, vocab, size=(1, n))
            for i, n in enumerate(lengths)}


# -- the program against the reference --------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunked_prefill_then_paged_decode_agree_with_the_reference(impl):
    """Every decode step's ``h_u`` and ``lam_u`` (read out of the served
    path by the probe) and every token against the reference's full
    forward of prompt + tokens; under ``pallas_interpret`` the chunk
    program leaves the pools in their pages and the step's attention is
    the paged kernel."""
    cfg, hf = CFG, hf_of(CFG)
    _, _, weights, eng = build(impl=impl)
    seen = []
    eng.stats_probe = lambda st, rids, L, owed: seen.append(
        (st, list(rids), L.copy(), owed.copy()))
    new = 9
    asked = prompts([20, 37, 50])
    for rid, p in asked.items():
        eng.submit(rid, p, new)
    out = eng.run()
    assert eng._chunk_in_pages() == (impl == "pallas_interpret")
    ref = {}
    for rid, p in asked.items():
        seq = np.concatenate([p[0], out[rid]])
        logits, hs, lams = R.forward(weights, hf, seq[:-1])
        ref[rid] = (len(p[0]), logits, hs, lams)
        got = np.asarray(jnp.argmax(logits[len(p[0]) - 1:], -1))
        np.testing.assert_array_equal(got, out[rid])
    checked, expected = 0, 0.0
    for st, rids, lengths, owed in seen:
        h = st["loop_h"][:, :, 0]          # (steps, passes, S, h)
        lam = st["loop_lam"][:, :, 0]      # (steps, passes, S)
        assert h.shape[1] == cfg.total_ut_steps
        mine = []       # the gate's expected exit pass, a decoding slot-step
        for s, rid in enumerate(rids):
            for k in range(min(int(owed[s]), h.shape[0])):
                _, _, hs, lams = ref[rid]
                at = int(lengths[s]) + k
                np.testing.assert_allclose(
                    h[k, :, s], np.asarray(hs[:, at]), rtol=2e-3, atol=2e-3)
                np.testing.assert_allclose(
                    lam[k, :, s], np.asarray(lams[:, at]), rtol=2e-3,
                    atol=2e-3)
                mine.append(float((R.exit_distribution(
                    np.asarray(lams[:, at])) * np.arange(1, 4)).sum()))
                checked += 1
        expected += np.mean(mine)
    assert checked == 3 * (new - 1)
    hist = eng.metrics.snapshot()["histograms"]
    assert hist["loop.passes_per_token"]["min"] == 3.0
    assert hist["loop.passes_per_token"]["max"] == 3.0
    # one observation a segment: the mean over its decoding slot-steps
    assert hist["loop.exit_pass_expected"]["sum"] == pytest.approx(
        expected, rel=1e-3)
    assert 1.0 < hist["loop.exit_pass_expected"]["min"] < 3.0
    counters = eng.metrics.snapshot()["counters"]
    assert counters["loop.layer_passes"]["value"] == (
        cfg.n_layers * cfg.total_ut_steps * 3 * (new - 1))
    assert hist["decode.page_pool_used_share"]["count"] == eng.segments_run


def test_the_dense_cached_forward_agrees_on_logits_states_and_gates():
    """The whole-prompt program's path (a dense cache scanned over as
    (passes, layers, ...)) against the reference, and a second chunk at a
    later position against the same rows."""
    weights = R.make_params(hf_of(CFG), 11)
    ids = prompts([48], seed=3)["r0"]
    logits, hs, lams = R.forward(weights, hf_of(CFG), ids[0])
    cache = ouro.init_cache(CFG, 1, 48)
    assert cache["k"].shape == (9, 1, 4, 48, 128)
    a, cache = ouro.forward_cached(
        weights, jnp.asarray(ids[:, :32]), cache, 0, CFG, impl="xla")
    x, after, (h2, lam2) = ouro._prefill(
        weights, jnp.asarray(ids[:, 32:]), cache, 32, CFG, impl="xla")
    b = ouro.head(weights, x, CFG)
    got = np.concatenate([np.asarray(a[0]), np.asarray(b[0])])
    np.testing.assert_allclose(got, np.asarray(logits), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h2[:, 0]), np.asarray(hs[:, 32:]),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lam2[:, 0]),
                               np.asarray(lams[:, 32:]), rtol=2e-3, atol=2e-3)


def test_at_one_pass_the_family_is_a_plain_sandwich_norm_decoder():
    """``total_ut_steps`` 1: one plane, one pass of tasks, the final norm
    still closes it; served tokens are the reference's at one pass and
    not the three-pass model's."""
    one = dataclasses.replace(CFG, total_ut_steps=1)
    dag, _, weights, eng = build(cfg=one)
    assert ouro.cache_spec(one).passes == 1
    assert dag.graph.pass_tasks == (
        ("p0_layer_0", "p0_layer_1", "p0_layer_2", "p0_end"),)
    assert dag.param_specs["cache_k_0"].shape == (N_PAGES, PS, 512)
    asked = prompts([20, 33])
    for rid, p in asked.items():
        eng.submit(rid, p, 6)
    out = eng.run()
    for rid, p in asked.items():
        seq = np.concatenate([p[0], out[rid]])
        logits = R.forward(weights, hf_of(one), seq[:-1])[0]
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[len(p[0]) - 1:], -1)), out[rid])
        three = R.forward(weights, hf_of(CFG), seq[:-1])[0]
        assert np.abs(np.asarray(three) - np.asarray(logits)).max() > 1e-2


# -- what the graph names, what the analysis counts -------------------------------


def test_the_graph_names_every_weight_once_and_a_layers_tasks_share_one_fn():
    dag, plan, _, _ = build()
    g, L, U = dag.graph, CFG.n_layers, CFG.total_ut_steps
    ids = [t.task_id for t in g]
    assert ids == ["embed"] + [
        t for u in range(U)
        for t in [f"p{u}_layer_{i}" for i in range(L)] + [f"p{u}_end"]
    ] + ["logits"]
    assert g.pass_tasks == tuple(
        tuple([f"p{u}_layer_{i}" for i in range(L)] + [f"p{u}_end"])
        for u in range(U))
    shapes = ouro.param_shapes(CFG)
    for i in range(L):
        tasks = [g[f"p{u}_layer_{i}"] for u in range(U)]
        assert all(t.fn is tasks[0].fn for t in tasks)
        assert all(t.param_alias == tasks[0].param_alias for t in tasks)
        weights = {glob for glob in tasks[0].param_alias.values()
                   if glob in shapes}
        assert weights == {f"h{i}_{k}" for k in ouro.layer_param_shapes(CFG)}
        assert tasks[0].param_alias["cache_k"] == f"cache_k_{i}"
        assert tasks[0].param_alias["page_table"] == "page_table"
    # layers all alike: ONE layer fn in the whole graph, one pass-end fn
    assert len({id(g[f"p0_layer_{i}"].fn) for i in range(L)}) == 1
    assert len({id(g[f"p{u}_end"].fn) for u in range(U)}) == 1
    assert set(g["logits"].param_alias.values()) == {"head_w"}
    assert set(g["p1_end"].param_alias.values()) == {
        "norm_f_g", "exit_w", "exit_b"}
    # every weight is needed by the graph, under one name
    needed = set().union(*(t.params_needed for t in g))
    assert needed == set(shapes) | {
        f"cache_{k}_{i}" for k in "kv" for i in range(L)} | {"page_table"}
    # FLOPs every time, bytes once
    flops = ouro.decode_flops(CFG, SLOTS, PPSEQ * PS)[1][0]
    assert sum(t.flops for t in g if t.group == "layer_0") == U * flops
    layer_bytes = sum(
        np.prod(s) * 4 for s, _ in ouro.layer_param_shapes(CFG).values())
    held = {}
    for t in g:
        held.update(t.param_bytes)
    assert sum(v for k, v in held.items() if k.startswith("h0_")) == (
        layer_bytes)


def test_the_analysis_counts_a_layers_bytes_once_and_a_page_id_in_every_plane():
    dag, plan, _, eng = build()
    rep = analyze_decode(dag.graph, schedule=plan,
                         param_specs=dag.param_specs, chunk_tokens=CHUNK,
                         decode_budget=eng.decode_rows_per_segment)
    assert not [d for d in rep.diagnostics if d.code == "DEC003"]
    assert not rep.errors
    info = next(d for d in rep.diagnostics if d.code == "DEC004")
    spec = ouro.cache_spec(CFG)
    per_id = PS * spec.paged_row_elems * 4      # every plane of every layer
    assert spec.paged_row_elems == 3 * 3 * 2 * 512
    assert info.data["kv_bytes"] == N_PAGES * per_id
    assert info.data["n_cache_params"] == 2 * CFG.n_layers
    assert eng._page_bytes == per_id
    assert dag.param_specs["cache_k_0"].shape == (3 * N_PAGES, PS, 512)
    # the residency the placement sees: each name once
    total = sum(dag.graph.param_size_gb(p) for p in set().union(
        *(t.params_needed for t in dag.graph)))
    want = sum(np.prod(v.shape) * v.dtype.itemsize
               for v in dag.param_specs.values())
    assert total * 1e9 == pytest.approx(want, rel=1e-6) or (
        total * 2**30 == pytest.approx(want, rel=1e-6))


def test_a_spec_with_passes_is_for_paged_layers_only():
    row = (("k", (2, 8)), ("v", (2, 8)))
    with pytest.raises(ValueError, match="paged layers only"):
        CacheSpec("kv", (LayerCache(row, window=4),), ring_rows=8, passes=2)
    with pytest.raises(ValueError, match="paged layers only"):
        CacheSpec("kv", (LayerCache((("s", (4,)),), state=True),), passes=2)
    with pytest.raises(ValueError, match="passes must be"):
        CacheSpec("kv", (LayerCache(row),), passes=0)
    with pytest.raises(ValueError, match="leave the loop"):
        ouro.OuroConfig.tiny(early_exit_threshold=0.9)
    spec = CacheSpec("kv", (LayerCache(row),) * 2, passes=3)
    pool = spec.init_pools(5, 4, jnp.float32)["cache_k_1"]
    assert pool.shape == (15, 4, 16)
    table = jnp.asarray([[1, 2, 0]])
    np.testing.assert_array_equal(spec.plane(pool, table, 2), [[11, 12, 10]])
    assert spec.plane(pool, table, 0) is not None
    one = CacheSpec("kv", (LayerCache(row),) * 2)
    assert one.plane(pool, table, 0) is table


# -- the passes are a loop in the program -----------------------------------------


def test_the_composed_step_holds_one_attention_call_site_a_layer():
    """The lowered step has ``n_layers`` ``_paged_flash`` call sites and
    ``n_layers`` layers' worth of matmuls — not one a (pass, layer)."""
    dag, plan, _, _ = build(impl="pallas_interpret")
    step = compose_paged_step_fn(dag.graph, plan, CFG)
    specs = dag.param_specs
    pools = {k: v for k, v in specs.items() if k.startswith("cache_")}
    weights = {k: v for k, v in specs.items()
               if k not in pools and k != "page_table"}
    text = jax.jit(step).lower(
        weights, pools, specs["page_table"],
        jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)).as_text()
    assert text.count("call @_paged_flash") == CFG.n_layers
    # q, k, v, o, gate|up, down a layer and the head, beside the few of
    # the interpreted kernel's one body; three passes unrolled hold 55
    dots = text.count("stablehlo.dot_general")
    assert 6 * CFG.n_layers + 1 <= dots < 2 * 6 * CFG.n_layers
    assert text.count("stablehlo.while") >= 1


def test_a_later_pass_that_is_not_the_first_again_cannot_be_rolled():
    dag, plan, _, _ = build()
    g = dag.graph
    real = g["p1_layer_1"].fn
    g["p1_layer_1"].fn = lambda p, prev: real(p, prev)
    with pytest.raises(ValueError, match="is not 'p0_layer_1' again"):
        compose_paged_step_fn(g, plan, CFG)
    g["p1_layer_1"].fn = real
    alias = g["p2_layer_0"].param_alias
    g["p2_layer_0"].param_alias = dict(alias, q_w="h1_q_w")
    with pytest.raises(ValueError, match="is not 'p0_layer_0' again"):
        compose_paged_step_fn(g, plan, CFG)
    g["p2_layer_0"].param_alias = alias
    compose_paged_step_fn(g, plan, CFG)
    del g.pass_tasks
    with pytest.raises((ValueError, KeyError)):
        compose_paged_step_fn(g, plan, CFG)


# -- the pool, not the slots, is what admission runs out of -----------------------


def test_an_oversubscribed_pool_finishes_every_request_and_leaks_nothing():
    """3 slots x 12 pages over 15 allocatable ids: pages go out by need,
    chunks wait their turn under the banker's rule, every request gets
    exactly its tokens — the same tokens a pool that never runs short
    gives — and every page comes back."""
    asked = prompts([60, 52, 44, 70, 30, 66], seed=4)
    new = 20
    outs = []
    for n_pages in (N_PAGES, 40):
        _, _, _, eng = build(n_pages=n_pages)
        for rid, p in asked.items():
            eng.submit(rid, p, new)
        outs.append(eng.run())
        snap = eng.metrics.snapshot()
        assert snap["gauges"]["decode.pages_leaked"]["value"] == 0
        assert eng.pool.free_pages == n_pages - 1
        assert all(len(outs[-1][r]) == new for r in asked)
        if n_pages == N_PAGES:
            share = snap["histograms"]["decode.page_pool_used_share"]
            assert share["max"] > 0.9      # the pool is what binds
    for rid in asked:
        np.testing.assert_array_equal(outs[0][rid], outs[1][rid])


def test_a_shared_page_is_copied_in_every_plane():
    """Prefix sharing beside planes: a twin aliases the first request's
    prompt pages in every pass's plane, a forced alias on a write page is
    split by a copy of every plane, and the tokens are the unshared
    engine's."""
    p = prompts([40], seed=9)["r0"]
    asked = {"a": p, "b": p.copy()}
    outs = {}
    for sharing in (False, True):
        _, _, _, eng = build(sharing=sharing, n_pages=40)
        eng.chunk_tokens = None        # whole-prompt admission shares
        for rid, ids in asked.items():
            eng.submit(rid, ids, 10)
        outs[sharing] = eng.run()
        if sharing:
            c = eng.metrics.snapshot()["counters"]
            assert c["decode.prefix_shared_pages"]["value"] > 0
    for rid in asked:
        np.testing.assert_array_equal(outs[False][rid], outs[True][rid])
    _, _, _, eng = build(sharing=True, n_pages=40)
    pools = {k: jnp.arange(v.size, dtype=v.dtype).reshape(v.shape)
             for k, v in eng.pools.items()}
    before = {k: np.asarray(v) for k, v in pools.items()}
    new = eng._cow_copy(pools, eng._planes(3), eng._planes(7))
    for k, v in new.items():
        for u in range(CFG.total_ut_steps):
            np.testing.assert_array_equal(
                np.asarray(v[7 + 40 * u]), before[k][3 + 40 * u])
        np.testing.assert_array_equal(np.asarray(v[8]), before[k][8])


def test_models_seam_serves_the_family_like_the_others():
    row = models.families()["ouro"]
    assert models.offers(row, *models.PAGED_FUNCTIONS)
    assert models.offers(row, *models.LOOP_FUNCTIONS)
    assert not models.offers(row, *models.DRAFT_FUNCTIONS)
    assert getattr(ouro, "PREFILL_TAKES_PAGES") and ouro.DECODE_TAKES_LIVE
    assert models.family_of(models.model_config("ouro-tiny")) == "ouro"
    assert models.cache_spec(CFG).passes == 3

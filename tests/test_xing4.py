"""The Xing4.0 block (``models/xing4.py``) against the benchmark's plain
float32 reference, at tiny widths on seeded random weights, and the
engine's per-layer cache description.

Tolerances: everything here runs in float32, so the program and the
reference (float32, "highest") differ by summation order only — 1e-4 on
logits of magnitude ~5 is thirty times the largest difference read
(3e-6 .. 2.3e-5) and a thousand times below what int8 moves them (the
control test: > 0.05).
"""

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

from benchmark.reference import gpt2 as RG  # noqa: E402
from benchmark.reference import xing4 as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu.backends.decode_loop import (  # noqa: E402
    compose_paged_step_fn,
)
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend  # noqa: E402
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import (  # noqa: E402
    cache_spec,
    gpt2,
    xing4,
)
from distributed_llm_scheduler_tpu.models.kv_pages import PagePool  # noqa: E402
from distributed_llm_scheduler_tpu.ops.attention import (  # noqa: E402
    latent_block_pages,
    mla_kernel_constraints,
    mla_paged_decode_attention,
    resolve_mla_paged_impl,
)

TOL = 1e-4
HF = {
    "hidden_size": 32, "hc_mult": 4, "num_attention_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "n_routed_experts": 8,
    "moe_intermediate_size": 16, "n_shared_experts": 1,
    "intermediate_size": 64, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "routed_scaling_factor": 2, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "max_position_embeddings": 256, "n_group": 1, "topk_group": 1,
    "dtype": "float32", "init": {"std": 0.3, "q_gain": 2.0},
}
CFG = xing4.Xing4Config.from_hf(HF, dtype=jnp.float32)
IMPLS = ("xla", "pallas_interpret")


@pytest.fixture(scope="module")
def weights():
    return R.make_params(HF, 2**31 + 123)


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(1, 256, size=(2, 20)).astype(
        np.int32)


def test_reference_weights_have_the_programs_names_and_shapes(weights):
    want = {k: (tuple(s), jnp.dtype(d))
            for k, (s, d) in xing4.param_shapes(CFG).items()}
    assert {k: (v.shape, v.dtype) for k, v in weights.items()} == want


@pytest.mark.parametrize("impl", IMPLS)
def test_whole_prompt_prefill_logits_match_the_reference(weights, ids, impl):
    got = xing4.forward(weights, jnp.asarray(ids), CFG, impl=impl)
    want = R.logits(weights, HF, ids)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("impl", IMPLS)
def test_chunked_prefill_logits_match_the_reference(weights, ids, impl):
    cache = xing4.init_cache(CFG, 2, 24)
    outs, pos = [], 0
    for n in (8, 8, 4):
        lg, cache = xing4.forward_cached(
            weights, jnp.asarray(ids[:, pos:pos + n]), cache, pos, CFG,
            impl=impl)
        outs.append(lg)
        pos += n
    want = R.logits(weights, HF, ids)
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want).max()) < TOL
    # and the one-row variant the engine's chunk program uses
    row, _ = xing4.forward_cached_row(
        weights, jnp.asarray(ids[:, :8]), xing4.init_cache(CFG, 2, 24), 0,
        CFG, jnp.int32(5), impl=impl)
    assert float(jnp.abs(row - want[:, 5]).max()) < TOL


def _engine(cfg, w, impl, chunk, slots=3, ps=8, n_pages=25, ppseq=6,
            pool=None):
    ddag = build_paged_decode_dag(
        cfg, slots=slots, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    sched = get_scheduler("heft").schedule(ddag.graph, cluster)
    eng = DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, sched, cfg, w,
        pool or PagePool(n_pages=n_pages, page_size=ps), slots=slots,
        pages_per_seq=ppseq, seg_steps=4, attention_impl=impl,
        chunk_tokens=chunk)
    return ddag, sched, eng


@pytest.mark.parametrize("impl,chunk", [
    ("xla", None), ("xla", 8), ("pallas_interpret", None),
    ("pallas_interpret", 8)])
def test_served_tokens_are_the_references_greedy_tokens(weights, impl, chunk):
    """Prefill (whole or in chunks of 8), then three decode segments of 4
    steps through the latent pages: every served token is the plain
    reference's own greedy token at its position (gap 0 up to float32
    ties, which seeded weights do not produce)."""
    _ddag, _sched, eng = _engine(CFG, weights, impl, chunk)
    rng = np.random.RandomState(1)
    prompts = {f"r{i}": rng.randint(1, 256, size=(1, n)).astype(np.int32)
               for i, n in enumerate((5, 17, 9, 23))}
    for rid, p in prompts.items():
        eng.submit(rid, jnp.asarray(p), 10)
    out = eng.run()
    assert eng.pool.free_pages == eng.pool.n_pages - 1
    for rid, p in prompts.items():
        seq = np.concatenate([p[0], np.asarray(out[rid])])
        gaps = R.served_gaps(weights, HF, seq, p.shape[1], 10, 48)
        assert gaps.max() < TOL, (rid, gaps)
    snap = eng.metrics.snapshot()["histograms"]
    assert 0.0 < snap["moe.experts_touched_share"]["p50"] <= 1.0
    assert snap["moe.pick_imbalance"]["p50"] >= 1.0


@pytest.mark.parametrize("impl", IMPLS)
def test_paged_decode_step_logits_match_the_reference(weights, ids, impl):
    """One composed decode step over pages that a prefill filled: the
    logits of every slot against the reference at that position, with
    one slot empty."""
    S, ps, n_pages, ppseq = 3, 8, 25, 6
    ddag, sched, _ = _engine(CFG, weights, impl, None)
    spec = cache_spec(CFG)
    step = jax.jit(compose_paged_step_fn(ddag.graph, sched, CFG))
    lens = (13, 19)
    cache = xing4.init_cache(CFG, 2, ppseq * ps)
    pools = spec.init_pools(n_pages, ps, CFG.dtype)
    table = np.zeros((S, ppseq), np.int32)
    table[0], table[1] = np.arange(1, 7), np.arange(7, 13)
    for b, n in enumerate(lens):
        _, c = xing4.forward_cached(
            weights, jnp.asarray(ids[b:b + 1, :n]),
            {"c": cache["c"][:, b:b + 1]}, 0, CFG, impl="xla")
        pools = spec.scatter(pools, c, jnp.asarray(table[b]), ps)
    cur = np.array([[ids[0, 13]], [ids[1, 19]], [0]], np.int32)
    logits, new_pools, stats = step(
        weights, pools, jnp.asarray(table), jnp.asarray(cur),
        jnp.asarray([13, 19, 0], jnp.int32),
        jnp.asarray([True, True, False]))
    want = R.logits(weights, HF, ids)
    assert float(jnp.abs(logits[0, 0] - want[0, 13]).max()) < TOL
    assert float(jnp.abs(logits[1, 0] - want[1, 19]).max()) < TOL
    assert stats.shape == (CFG.n_layers - CFG.n_dense_layers, 2)
    # the step's row landed at position 13 of slot 0's second page
    row = new_pools["cache_c_0"][2, 13 - 8]
    assert float(jnp.abs(row).max()) > 0 and float(
        jnp.abs(pools["cache_c_0"][2, 13 - 8]).max()) == 0


def test_absorbed_mla_equals_expanded_mla(weights):
    """The decode form (W_UK folded into the query, W_UV applied after,
    scores against the cached row) and the prefill form (K and V rebuilt
    from the latents) give the same attention output."""
    p = xing4.layer_params(weights, CFG, 1)
    rng = np.random.RandomState(2)
    T = 11
    x = jnp.asarray(rng.randn(T, CFG.hidden_size), jnp.float32)
    q_nope, q_rope, rows = xing4.mla_project(
        p, x, jnp.arange(T, dtype=jnp.int32), CFG)
    expanded = xing4.mla_expanded_attention(
        p, q_nope[None], q_rope[None],
        jnp.pad(rows, ((0, 16 - T), (0, 0)))[None], 0, CFG)[0] @ p["o_w"]
    pool = jnp.zeros((3, 8, rows.shape[1])).at[1].set(rows[:8]).at[
        2, :T - 8].set(rows[8:])
    o_lat = mla_paged_decode_attention(
        xing4.mla_absorbed_query(p, q_nope[-1:], q_rope[-1:], CFG), pool,
        jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([T - 1], jnp.int32),
        CFG.kv_lora_rank, new_row=rows[-1:], impl="xla")
    absorbed = xing4.mla_absorbed_output(p, o_lat, CFG)
    assert float(jnp.abs(absorbed[0] - expanded[-1]).max()) < 1e-5


@pytest.mark.parametrize("lengths", [
    (0, 0, 0), (7, 8, 9), (15, 16, 17), (31, 0, 24), (47, 3, 40)])
@pytest.mark.parametrize("has_new", [True, False])
def test_mla_paged_kernel_matches_the_gather_path(lengths, has_new):
    """Lengths straddling page and block boundaries (page 8, a block of
    pages by ``latent_block_pages``), empty slots, capacity's last row,
    NaN in every page no slot attends."""
    S, H, W, rank, ps, ppseq = 3, 4, 48, 32, 8, 6
    k = jax.random.split(jax.random.PRNGKey(len(lengths) + sum(lengths)), 3)
    q = jax.random.normal(k[0], (S, H, W))
    pool = jax.random.normal(k[1], (1 + S * ppseq, ps, W))
    new = jax.random.normal(k[2], (S, W)) if has_new else None
    table = np.zeros((S, ppseq), np.int32)
    live = set()
    for s, n in enumerate(lengths):
        pages = min(n, ppseq * ps - 1) // ps + 1
        table[s, :pages] = 1 + s * ppseq + np.arange(pages)
        live.update(table[s, :pages].tolist())
    dead = [i for i in range(pool.shape[0]) if i not in live]
    pool = pool.at[jnp.asarray(dead)].set(jnp.nan)
    args = (q, pool, jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            rank)
    a = mla_paged_decode_attention(*args, new_row=new, impl="xla")
    b = mla_paged_decode_attention(*args, new_row=new,
                                   impl="pallas_interpret")
    assert bool(jnp.isfinite(a).all()) and bool(jnp.isfinite(b).all())
    assert float(jnp.abs(a - b).max()) < 1e-5
    assert latent_block_pages(ps, ppseq, W, jnp.float32) == ppseq


def test_mla_kernel_constraints_and_dispatch():
    assert mla_kernel_constraints(128, 640, 512, jnp.bfloat16) == []
    assert mla_kernel_constraints(8, 640, 512, jnp.bfloat16)
    assert mla_kernel_constraints(128, 48, 32, jnp.bfloat16)
    assert resolve_mla_paged_impl("auto", 8, 48, 32, jnp.float32) == "xla"
    assert resolve_mla_paged_impl(
        "pallas_interpret", 8, 48, 32, jnp.float32) == "pallas_interpret"
    with pytest.raises(ValueError):
        resolve_mla_paged_impl("pallas", 8, 48, 32, jnp.float32)
    # 6 pages of (128, 640) bf16 a grid step at the served geometry
    assert latent_block_pages(128, 136, 640, jnp.bfloat16) == 6
    assert xing4.latent_row_width(xing4.Xing4Config()) == 640


@pytest.mark.parametrize("impl", IMPLS)
def test_h_res_is_doubly_stochastic_and_the_clamp_engages(weights, impl):
    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.randn(9, CFG.hc_mult, CFG.hidden_size), jnp.float32)
    # at the program's own init (Phi N(0, 0.02), B_res = 3 I) twenty
    # sweeps converge: rows and columns sum to 1
    p = xing4.layer_params(
        xing4.init_params(CFG, jax.random.PRNGKey(8)), CFG, 0)
    pre, post, res = xing4.hc_maps(X, p, "hca", CFG, impl)
    assert float(jnp.abs(res.sum(1) - 1).max()) < 1e-4
    assert float(jnp.abs(res.sum(2) - 1).max()) < 1e-4
    assert bool(((pre > 0) & (pre < 1)).all())
    assert bool(((post > 0) & (post < 2)).all())
    # at the test's wide weights the maps are the reference's (rows sum
    # to 1 there too; the columns are then only near it)
    p = xing4.layer_params(weights, CFG, 0)
    pre, post, res = xing4.hc_maps(X, p, "hca", CFG, impl)
    assert float(jnp.abs(res.sum(2) - 1).max()) < 1e-4
    want = R._hc(X, p, "hca", HF)
    for got, ref in zip((pre, post, res), want):
        assert float(jnp.abs(got - ref).max()) < 1e-5
    # biases far outside +-30: without the clamp exp() overflows to inf
    # and the sweeps give NaN; with it the result is the one at +-30
    n = CFG.hc_mult
    far = dict(p, hca_b=p["hca_b"].at[2 * n].set(1e4).at[2 * n + 1].set(-1e4))
    at30 = dict(p, hca_b=p["hca_b"].at[2 * n].set(30.0).at[
        2 * n + 1].set(-30.0), hca_alpha=p["hca_alpha"].at[2].set(0.0))
    far["hca_alpha"] = at30["hca_alpha"]
    r_far = xing4.hc_maps(X, far, "hca", CFG, impl)[2]
    r_30 = xing4.hc_maps(X, at30, "hca", CFG, impl)[2]
    assert bool(jnp.isfinite(r_far).all())
    assert float(jnp.abs(r_far - r_30).max()) == 0.0


@pytest.mark.parametrize("impl", IMPLS)
def test_held_shares_of_experts_add_up_to_the_uncut_layer(impl):
    """24 experts held in three shares of 8, the shared expert counted
    once: the sum is the layer that holds all of them."""
    cfg = xing4.Xing4Config.tiny(n_routed_experts=24, experts_per_tok=4)
    w = xing4.init_params(cfg, jax.random.PRNGKey(4), std=0.3)
    p = xing4.layer_params(w, cfg, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (13, cfg.hidden_size))
    full, stats = xing4.moe_ffn(p, x, cfg, impl=impl)
    parts = jnp.zeros_like(full)
    for j in range(3):
        held = list(range(8 * j, 8 * j + 8))
        part = dict(p, exp_gu_w=p["exp_gu_w"][8 * j:8 * j + 8],
                    exp_down_w=p["exp_down_w"][8 * j:8 * j + 8])
        parts = parts + xing4.moe_ffn(
            part, x, cfg, held=held, shared=(j == 0), impl=impl)[0]
    assert float(jnp.abs(parts - full).max()) < 1e-5
    assert 0 < float(stats[0]) <= 1 and float(stats[1]) >= 1


@pytest.mark.parametrize("impl", IMPLS)
def test_no_token_is_dropped_when_every_token_picks_one_expert(impl):
    """A router that sends all 150 tokens to expert 3 (and, top-2, to
    expert 5): more rows than one m tile of the grouped kernel holds,
    and every one is computed."""
    cfg = xing4.Xing4Config.tiny()
    w = xing4.init_params(cfg, jax.random.PRNGKey(6), std=0.3)
    p = xing4.layer_params(w, cfg, 1)
    bias = jnp.zeros((cfg.n_routed_experts,)).at[3].set(9.0).at[5].set(8.0)
    p = dict(p, router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (150, cfg.hidden_size))
    got, stats = xing4.moe_ffn(p, x, cfg, shared=False, impl=impl)
    idx, gate = xing4.moe_route(p, x, cfg)
    assert set(np.asarray(idx).ravel().tolist()) == {3, 5}
    want = jnp.zeros_like(got)
    for j in range(cfg.experts_per_tok):
        for e in (3, 5):
            y = xing4._swiglu(x, p["exp_gu_w"][e].T, p["exp_down_w"][e])
            want = want + jnp.where(
                (idx[:, j] == e)[:, None], gate[:, j, None] * y, 0.0)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(stats[0]) == 2 / cfg.n_routed_experts
    assert float(stats[1]) == cfg.n_routed_experts / 2
    # tokens that are not live pick nothing
    none = xing4.moe_ffn(p, x, cfg, shared=False, impl=impl,
                         live=jnp.zeros((150,), bool))[0]
    assert float(jnp.abs(none).max()) == 0.0


def test_cache_spec_describes_both_families():
    kv = cache_spec(gpt2.GPT2Config.tiny())
    assert (kv.kind, kv.kinds) == ("kv", ("k", "v"))
    assert kv.row_elems == 2 * 128 and set(kv.init_pools(3, 4, jnp.float32)) == {
        f"cache_{k}_{i}" for k in "kv" for i in range(2)}
    lat = cache_spec(CFG)
    assert (lat.kind, lat.kinds, lat.rows) == ("latent", ("c",), (("c", (128,)),))
    pools = lat.init_pools(5, 4, jnp.float32)
    assert pools["cache_c_2"].shape == (5, 4, 128)
    dense = lat.init_dense(2, 8, jnp.float32)
    assert dense["c"].shape == (3, 2, 8, 128)
    filled = {"c": jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8, 128))}
    pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
    back = lat.gather(dense, lat.scatter(pools, filled, pages, 4), pages, 2, 8)
    assert bool((back["c"] == filled["c"]).all())


# -- GPT-2 through the refactored engine: the parent's tokens, bit for bit --

GOLDEN = {
    "r0": [319, 460, 460, 460, 460, 460, 311, 386, 347, 123],
    "r1": [130, 130, 130, 130, 130, 1, 1, 1, 184, 184],
    "r2": [335, 60, 60, 60, 60, 60, 60, 60, 150, 150],
    "r3": [274, 507, 197, 317, 53, 311, 400, 461, 461, 78],
    "r4": [362, 29, 123, 1, 400, 249, 27, 27, 461, 1],
}


@pytest.mark.parametrize("chunk,sharing", [
    (None, False), (8, False), (None, True)])
def test_gpt2_served_tokens_are_the_parents(chunk, sharing):
    """``GOLDEN`` was served by the commit before the engine's cache went
    through ``CacheSpec`` (whole-prompt, chunked and shared-prefix
    prefill, three slots, five requests): the same weights and prompts
    give the same tokens, bit for bit."""
    cfg = gpt2.GPT2Config.tiny()
    w = RG.make_params({
        "n_embd": cfg.n_embd, "n_layer": cfg.n_layer,
        "vocab_size": cfg.vocab_size, "n_positions": cfg.n_positions,
        "dtype": "float32",
        "init": {"std": 0.02, "qk_gain": 6.0, "attn_proj_gain": 16.0}},
        2**31 + 5)
    pool = (PagePool(n_pages=25, page_size=8, sharing=True) if sharing
            else None)
    _, _, eng = _engine(cfg, w, "xla", chunk, pool=pool)
    # ... out of pools in the stored form: a row's heads on the lanes
    assert {p.shape[2:] for p in eng.pools.values()} == {
        (cfg.n_head * cfg.head_dim,)}
    rng = np.random.RandomState(0)
    base = rng.randint(1, cfg.vocab_size, size=(1, 16)).astype(np.int32)
    prompts = [rng.randint(1, cfg.vocab_size, size=(1, n)).astype(np.int32)
               for n in (5, 17, 9)]
    prompts.append(np.concatenate([base, prompts[0]], 1))
    prompts.append(np.concatenate([base, prompts[2][:, :3]], 1))
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", jnp.asarray(p), 10)
    out = eng.run()
    assert {k: [int(t) for t in v] for k, v in out.items()} == GOLDEN


PROBED = ("mla_paged_flash_ps16_bf16", "moe_experts_bf16", "hc_maps_bf16",
          "mla_chunk_flash_bf16")


@pytest.fixture(scope="module")
def smoke_probes():
    import chip_smoke as cs

    ph = cs.phase_kernels(cs.CompileMeter(), interpret=True, n_head=4,
                          head_dim=16, flash_T=32)
    return {c["name"]: c for c in ph["probe"]}


@pytest.mark.parametrize("name", PROBED)
def test_chip_smoke_probes_the_latent_kernels(smoke_probes, name):
    """``chip_smoke.py``'s kernels phase runs the Xing4.0 block's three
    kernels and the prefill's chunk kernel against their gather / XLA
    paths (interpreted here, compiled on the chip)."""
    assert smoke_probes[name]["ok"], smoke_probes[name]


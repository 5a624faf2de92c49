"""Fused Pallas ragged paged attention kernel (ops/attention.py:
``_paged_flash`` + the shared impl dispatch).

Pins: interpret-mode kernel output is allclose to the XLA gather path
across ragged length mixes, page-size edge cases (empty slot, 1-token
tail, exactly-full page, single-page request), GQA head ratios, and
trash-page masking (pools poisoned at TRASH_PAGE); a full
PagedDecodeEngine run retires BITWISE-identical token ids under
``impl="xla"`` and ``impl="pallas_interpret"`` with zero leaked pages;
the shared ``resolve_attention_impl`` helper's dispatch rules (unknown
impl raises, and so does an explicit pallas request the shape cannot
honour);
and the DEC005 eligibility diagnostic fires exactly on geometries
``paged_kernel_constraints`` rejects.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.models.kv_pages import TRASH_PAGE, PagePool
from distributed_llm_scheduler_tpu.ops.attention import (
    lane_width,
    latent_block_pages,
    paged_block_pages,
    paged_decode_attention,
    paged_kernel_constraints,
    paged_pallas_supported,
    resolve_attention_impl,
)


def _pools(rng, n_pages, ps, Hkv, hd, dtype=jnp.float32):
    """Random K and V pools in the stored form ``(n_pages, page_size,
    Hkv * hd)``: a row's heads side by side on the lanes."""
    return tuple(
        jnp.asarray(rng.randn(n_pages, ps, Hkv * hd), dtype)
        for _ in range(2))


def _paged_state(S, Hkv, hd, ps, ppseq, lengths, seed=0, poison=True,
                 dtype=jnp.float32):
    """Random stored-form pools + a page table covering each slot's
    rows, with the trash page poisoned so parity also proves the
    masking."""
    rng = np.random.RandomState(seed)
    k_pool, v_pool = _pools(rng, S * ppseq + 1, ps, Hkv, hd, dtype)
    if poison:
        k_pool = k_pool.at[TRASH_PAGE].set(1e9)
        v_pool = v_pool.at[TRASH_PAGE].set(1e9)
    pt = np.full((S, ppseq), TRASH_PAGE, np.int32)
    page = 1
    for s, L in enumerate(lengths):
        # pages for the L cached rows plus this step's insert row
        for j in range((min(L + 1, ppseq * ps) + ps - 1) // ps):
            pt[s, j] = page
            page += 1
    return k_pool, v_pool, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)


F32, BF16 = jnp.float32, jnp.bfloat16

# (name, S, Hq, Hkv, hd, ps, ppseq, lengths, with_insert, dtype)
FIXTURES = [
    ("ragged_mix", 3, 4, 2, 8, 16, 4, [0, 5, 49], True, F32),
    ("no_insert", 3, 4, 2, 8, 16, 4, [1, 16, 31], False, F32),
    ("mha_heads", 2, 2, 2, 8, 16, 2, [15, 19], True, F32),
    ("gqa_4to1", 2, 8, 2, 16, 16, 2, [3, 30], True, F32),
    ("single_page_request", 2, 4, 2, 8, 16, 1, [1, 15], True, F32),
    ("one_token_and_empty", 2, 4, 2, 8, 16, 2, [1, 0], True, F32),
    ("exactly_full_pages", 2, 4, 2, 8, 16, 2, [16, 31], True, F32),
    ("capacity_minus_one", 2, 4, 2, 8, 16, 2, [31, 31], True, F32),
    ("small_pages_interpret", 3, 4, 2, 8, 4, 4, [0, 5, 15], True, F32),
]

# The merged-lane row (PR 28): every KV head's values side by side on the
# lanes, heads told apart by the masked query.  GPT-2 XL's own row (25
# heads of 64 = 1,600 values, not a whole number of 128-lane tiles), a
# single KV head under 1 and 8 query heads, a GQA group over a padded
# head count (3 KV heads -> a sublane tile of 8), both dtypes.
LANE_FIXTURES = [
    ("lanes_xl_row_bf16", 2, 25, 25, 64, 16, 3, [0, 37], True, BF16),
    ("lanes_xl_row_f32", 2, 25, 25, 64, 16, 2, [17, 31], True, F32),
    ("lanes_single_kv_head_mha", 2, 1, 1, 64, 16, 2, [5, 20], True, F32),
    ("lanes_single_kv_head_gqa_8to1_bf16", 2, 8, 1, 128, 16, 2, [16, 0],
     True, BF16),
    ("lanes_gqa_2to1_three_kv_heads", 3, 6, 3, 32, 16, 3, [0, 33, 47],
     True, F32),
    ("lanes_gqa_4to1_no_insert_bf16", 2, 16, 4, 64, 16, 2, [1, 31], False,
     BF16),
]
FIXTURES += LANE_FIXTURES

# The block walk (PR 25) at the rows_per_block the stored row gives (PR
# 28).  At page 16, 4 KV heads of 128 (a 512-wide row), float32, a page
# is 32 KiB in VMEM and a block 16 pages = 256 rows; GPT-2 XL's row in
# bf16 is a 52 KiB page and a block 9 pages = 144 rows
# (``test_block_rule`` pins both).  So a 40-page table is 3 blocks of 16,
# 16 and 8 pages and a 20-page XL table 3 of 9, 9 and 2: lengths at
# rows_per_block - 1 / = / + 1, dead slots (L = 0) between live ones,
# L = capacity - 1 with the insert and past it, GQA.
BLOCK_FIXTURES = [
    ("block_straddle_below_at_above", 3, 4, 4, 128, 16, 40,
     [255, 256, 257], True, F32),
    ("block_dead_slots_between_live", 6, 4, 4, 128, 16, 40,
     [0, 255, 0, 256, 0, 600], True, F32),
    ("block_table_not_multiple_capacity_minus_one", 4, 4, 4, 128, 16, 40,
     [639, 0, 512, 511], True, F32),
    ("block_gqa_2to1", 3, 8, 4, 128, 16, 40, [257, 0, 639], True, F32),
    ("block_no_insert", 3, 4, 4, 128, 16, 40, [256, 0, 639], False, F32),
    ("block_past_capacity_clamps", 2, 4, 4, 128, 16, 40, [640, 1], True,
     F32),
    ("block_xl_row_straddle_bf16", 4, 25, 25, 64, 16, 20,
     [143, 144, 145, 0], True, BF16),
    ("block_xl_row_capacity_edges_bf16", 3, 25, 25, 64, 16, 20,
     [319, 0, 320], True, BF16),
    # groups that are no power of two over 8 KV heads of 128 (a 1,024-wide
    # row: a full layer's 48 query heads and a window layer's 72), page
    # 128, bf16: a block is 2 pages = 256 rows
    ("block_gqa_6to1_bf16", 3, 48, 8, 128, 128, 5, [255, 0, 600], True,
     BF16),
    ("block_gqa_9to1_bf16", 2, 72, 8, 128, 128, 3, [383, 256], True, BF16),
]
FIXTURES += BLOCK_FIXTURES


def _tol(dtype):
    """Kernel against gather path: float32 agrees to rounding; in
    bfloat16 the kernel rounds the probabilities and the output to 8
    bits."""
    return dict(atol=1e-5, rtol=1e-5) if dtype == F32 else dict(
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize(
    "name,S,Hq,Hkv,hd,ps,ppseq,lengths,with_insert,dtype",
    FIXTURES, ids=[f[0] for f in FIXTURES],
)
def test_kernel_matches_gather(name, S, Hq, Hkv, hd, ps, ppseq, lengths,
                               with_insert, dtype):
    k_pool, v_pool, pt, L = _paged_state(
        S, Hkv, hd, ps, ppseq, lengths, dtype=dtype)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(S, Hq, 1, hd), dtype)
    kn = vn = None
    if with_insert:
        kn = jnp.asarray(rng.randn(S, Hkv, 1, hd), dtype)
        vn = jnp.asarray(rng.randn(S, Hkv, 1, hd), dtype)
    scale = hd ** -0.5
    # the gather path on the same values in float32 (the CPU backend has
    # no grouped bfloat16 dot)
    f32 = lambda t: None if t is None else t.astype(jnp.float32)
    ref = paged_decode_attention(
        f32(q), f32(k_pool), f32(v_pool), pt, L, scale, k_new=f32(kn),
        v_new=f32(vn), impl="xla"
    )
    got = paged_decode_attention(
        q, k_pool, v_pool, pt, L, scale, k_new=kn, v_new=vn,
        impl="pallas_interpret",
    )
    assert got.shape == q.shape and got.dtype == q.dtype
    assert bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite output"
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype), err_msg=f"{name}: kernel diverged from gather path",
    )


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_head_split_pools_are_a_view_of_the_stored_form(impl):
    """``(n_pages, page_size, Hkv, hd)`` pools — what ``chip_smoke.py``
    and the benchmark's AOT test pass — give bitwise what the stored
    ``(n_pages, page_size, Hkv * hd)`` form gives."""
    S, Hq, Hkv, hd, ps, ppseq = 3, 6, 3, 16, 16, 3
    k_pool, v_pool, pt, L = _paged_state(S, Hkv, hd, ps, ppseq, [0, 20, 47])
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(S, Hq, 1, hd), jnp.float32)
    kn = jnp.asarray(rng.randn(S, Hkv, 1, hd), jnp.float32)
    vn = jnp.asarray(rng.randn(S, Hkv, 1, hd), jnp.float32)
    split = lambda pool: pool.reshape(*pool.shape[:2], Hkv, hd)
    outs = [
        np.asarray(paged_decode_attention(
            q, kp, vp, pt, L, hd ** -0.5, k_new=kn, v_new=vn, impl=impl))
        for kp, vp in ((k_pool, v_pool), (split(k_pool), split(v_pool)))
    ]
    np.testing.assert_array_equal(outs[0], outs[1])


def test_kernel_masks_poisoned_trash_page():
    """Flip the trash-page poison on and off: outputs must be bitwise
    identical — the kernel's masked pages contribute exactly nothing."""
    S, Hq, Hkv, hd, ps, ppseq = 2, 4, 2, 8, 16, 4
    lengths = [3, 20]
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(S, Hq, 1, hd), jnp.float32)
    outs = []
    for poison in (False, True):
        k_pool, v_pool, pt, L = _paged_state(
            S, Hkv, hd, ps, ppseq, lengths, seed=2, poison=poison
        )
        outs.append(paged_decode_attention(
            q, k_pool, v_pool, pt, L, hd ** -0.5, impl="pallas_interpret"
        ))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_block_rule():
    """``paged_block_pages``: as many pages as keep the K and V double
    buffers inside the VMEM budget, a page reckoned at ``page_size`` rows
    of whole 128-lane tiles, never more than the table holds; a short
    table is one block.  ``latent_block_pages`` is the same rule over
    one pool."""
    # the block fixtures above rest on these: 3 blocks of 16, 16, 8 ...
    assert paged_block_pages(16, 40, 4, 128, jnp.float32) == 16
    # ... and GPT-2 XL serving: page 16, a row of 25 x 64 = 1,600 values
    # held as 1,664 lanes, bf16 -> a 52 KiB page, 9 to a block (it was a
    # 128 KiB head-padded page and 4), 8 blocks a 64-page slot
    assert lane_width(1600) == 1664 and lane_width(640) == 640
    assert paged_block_pages(16, 64, 25, 64, jnp.bfloat16) == 9
    assert paged_block_pages(16, 20, 25, 64, jnp.bfloat16) == 9
    # a row narrower than one tile still occupies one
    assert paged_block_pages(16, 200, 2, 8, jnp.float32) == 64
    # the tiny serving geometries: the whole table is one block
    for ppseq in (1, 2, 4, 8):
        assert paged_block_pages(4, ppseq, 4, 16, jnp.float32) == ppseq
        assert paged_block_pages(8, ppseq, 12, 64, jnp.bfloat16) == ppseq
    # a page wider than the budget still makes a block of one
    assert paged_block_pages(512, 4, 64, 256, jnp.float32) == 1
    # one pool, so twice the pages: Xing4.0's 128 x 640 latent page
    assert latent_block_pages(128, 136, 640, jnp.bfloat16) == 6
    assert latent_block_pages(16, 64, 1600, jnp.bfloat16) == 19


# (name, S, Hq, Hkv, hd, ps, ppseq, lengths, dtype)
DEAD_BLOCK_GEOMETRIES = [
    ("wide_row_f32", 4, 4, 4, 128, 16, 40, [0, 260, 255, 639], F32),
    ("xl_row_bf16", 4, 25, 25, 64, 16, 20, [0, 150, 143, 319], BF16),
]


@pytest.mark.parametrize("past", ["last_live_block", "last_live_page"])
@pytest.mark.parametrize(
    "name,S,Hq,Hkv,hd,ps,ppseq,lengths,dtype",
    DEAD_BLOCK_GEOMETRIES, ids=[g[0] for g in DEAD_BLOCK_GEOMETRIES],
)
def test_kernel_never_touches_dead_blocks(name, S, Hq, Hkv, hd, ps, ppseq,
                                          lengths, dtype, past):
    """The dead-block witness: every page of the table is a real page of
    its own (no trash page), and every page that lies wholly past a
    slot's last live block — or, stricter, past its last live page — is
    filled with NaN.  A block that was computed and masked away would
    give 0 * NaN; the output must stay finite and bitwise equal."""
    ppb = paged_block_pages(ps, ppseq, Hkv, hd, dtype)
    assert ppb < ppseq  # several blocks a slot, or nothing is dead
    rng = np.random.RandomState(9)
    n_pages = S * ppseq + 1
    k_pool = rng.randn(n_pages, ps, Hkv * hd).astype(np.float32)
    v_pool = rng.randn(n_pages, ps, Hkv * hd).astype(np.float32)
    pt = 1 + np.arange(S * ppseq, dtype=np.int32).reshape(S, ppseq)
    k_nan, v_nan = k_pool.copy(), v_pool.copy()
    k_nan[TRASH_PAGE] = v_nan[TRASH_PAGE] = np.nan
    n_dead = 0
    for s, L in enumerate(lengths):
        last_page = min(L, ppseq * ps - 1) // ps
        first_dead = (
            (last_page // ppb + 1) * ppb if past == "last_live_block"
            else last_page + 1
        )
        for j in range(first_dead, ppseq):
            k_nan[pt[s, j]] = v_nan[pt[s, j]] = np.nan
            n_dead += 1
    assert n_dead > S  # the witness has something to witness
    q = jnp.asarray(rng.randn(S, Hq, 1, hd), dtype)
    kn = jnp.asarray(rng.randn(S, Hkv, 1, hd), dtype)
    vn = jnp.asarray(rng.randn(S, Hkv, 1, hd), dtype)
    outs = [
        np.asarray(paged_decode_attention(
            q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(pt), jnp.asarray(lengths, jnp.int32), hd ** -0.5,
            k_new=kn, v_new=vn, impl="pallas_interpret",
        ), np.float32)
        for kp, vp in ((k_pool, v_pool), (k_nan, v_nan))
    ]
    assert np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[0], outs[1])


# -- ragged multi-token-q (chunked prefill) ----------------------------------

def _ragged_state(S, Hkv, hd, ps, ppseq, spans, seed=0, poison=True):
    """Random pools + a page table covering each slot's base context AND
    its chunk rows (``spans`` is ``[(base_len, q_len), ...]``) — the
    chunk's K/V are already scattered (write-then-attend at chunk
    granularity), so any pool content exercises both paths equally."""
    rng = np.random.RandomState(seed)
    k_pool, v_pool = _pools(rng, S * ppseq + 1, ps, Hkv, hd)
    if poison:
        k_pool = k_pool.at[TRASH_PAGE].set(1e9)
        v_pool = v_pool.at[TRASH_PAGE].set(1e9)
    pt = np.full((S, ppseq), TRASH_PAGE, np.int32)
    page = 1
    for s, (L, QL) in enumerate(spans):
        for j in range((max(L + QL, 1) + ps - 1) // ps):
            pt[s, j] = page
            page += 1
    ln = jnp.asarray([L for L, _ in spans], jnp.int32)
    ql = jnp.asarray([QL for _, QL in spans], jnp.int32)
    return k_pool, v_pool, jnp.asarray(pt), ln, ql


# (name, S, Hq, Hkv, hd, ps, ppseq, Tn, [(base_len, q_len), ...])
RAGGED_FIXTURES = [
    # chunk rows cross a physical page boundary mid-chunk
    ("chunk_straddles_page", 2, 4, 2, 8, 16, 3, 8, [(13, 8), (21, 8)]),
    # chunk length == page_size: the chunk fills one page exactly
    ("chunk_eq_page", 2, 4, 2, 8, 16, 3, 16, [(0, 16), (16, 16)]),
    # ragged tail: final chunk shorter than the padded Tn grid, plus an
    # idle slot (q_len == 0) whose rows are all padding
    ("final_partial_and_idle", 3, 4, 2, 8, 16, 3, 8,
     [(32, 3), (5, 0), (0, 8)]),
    # GQA: 4 query heads per KV head across chunk rows
    ("gqa_chunk_heads", 2, 8, 2, 16, 16, 2, 8, [(15, 8), (0, 5)]),
    # small pages: one chunk spans three physical pages
    ("small_pages_chunk", 2, 4, 2, 8, 4, 3, 8, [(2, 8), (0, 1)]),
]


@pytest.mark.parametrize(
    "name,S,Hq,Hkv,hd,ps,ppseq,Tn,spans",
    RAGGED_FIXTURES, ids=[f[0] for f in RAGGED_FIXTURES],
)
def test_ragged_kernel_matches_gather(name, S, Hq, Hkv, hd, ps, ppseq,
                                      Tn, spans):
    k_pool, v_pool, pt, L, ql = _ragged_state(S, Hkv, hd, ps, ppseq, spans)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(S, Hq, Tn, hd), jnp.float32)
    scale = hd ** -0.5
    ref = paged_decode_attention(
        q, k_pool, v_pool, pt, L, scale, impl="xla", q_lens=ql
    )
    got = paged_decode_attention(
        q, k_pool, v_pool, pt, L, scale, impl="pallas_interpret", q_lens=ql
    )
    assert bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite output"
    # compare REAL rows only (t < q_lens[s]); padding rows are
    # documented as finite-but-meaningless
    mask = (np.arange(Tn)[None, :] < np.asarray(ql)[:, None])
    m4 = jnp.asarray(mask.astype(np.float32))[:, None, :, None]
    np.testing.assert_allclose(
        np.asarray(got * m4), np.asarray(ref * m4), atol=1e-5, rtol=1e-5,
        err_msg=f"{name}: ragged kernel diverged from gather path",
    )


def test_ragged_kernel_masks_poisoned_trash_page():
    """Poison on/off must not change any real chunk row: pages past a
    slot's base+chunk rows gather the trash page and are masked."""
    S, Hq, Hkv, hd, ps, ppseq, Tn = 2, 4, 2, 8, 16, 3, 8
    spans = [(13, 8), (3, 5)]
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(S, Hq, Tn, hd), jnp.float32)
    outs = []
    for poison in (False, True):
        k_pool, v_pool, pt, L, ql = _ragged_state(
            S, Hkv, hd, ps, ppseq, spans, seed=2, poison=poison
        )
        outs.append(paged_decode_attention(
            q, k_pool, v_pool, pt, L, hd ** -0.5,
            impl="pallas_interpret", q_lens=ql,
        ))
    mask = (np.arange(Tn)[None, :] <
            np.asarray([QL for _, QL in spans])[:, None])
    m4 = np.asarray(mask, np.float32)[:, None, :, None]
    np.testing.assert_array_equal(
        np.asarray(outs[0]) * m4, np.asarray(outs[1]) * m4
    )


def test_ragged_q_requires_q_lens_and_rejects_k_new():
    S, Hq, Hkv, hd, ps, ppseq = 2, 4, 2, 8, 16, 2
    k_pool, v_pool, pt, L, ql = _ragged_state(
        S, Hkv, hd, ps, ppseq, [(0, 8), (3, 8)]
    )
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(S, Hq, 8, hd), jnp.float32)
    with pytest.raises(ValueError, match="requires per-slot q_lens"):
        paged_decode_attention(q, k_pool, v_pool, pt, L, impl="xla")
    kn = jnp.asarray(rng.randn(S, Hkv, 1, hd), jnp.float32)
    with pytest.raises(ValueError, match="no k_new"):
        paged_decode_attention(
            q, k_pool, v_pool, pt, L, impl="xla", q_lens=ql,
            k_new=kn, v_new=kn,
        )


# -- shared impl dispatch ----------------------------------------------------

def test_resolve_attention_impl_rules():
    assert resolve_attention_impl("xla", lambda i: True) == "xla"
    assert resolve_attention_impl(
        "pallas_interpret", lambda i: True
    ) == "pallas_interpret"
    # an explicit kernel request the shape cannot honour raises — it is
    # never quietly served by the gather path
    with pytest.raises(ValueError, match="requested explicitly"):
        resolve_attention_impl("pallas", lambda i: False)
    with pytest.raises(ValueError, match="unknown attention impl"):
        resolve_attention_impl("cuda", lambda i: True)
    # auto on a non-TPU host resolves to the gather path
    if jax.default_backend() != "tpu":
        assert resolve_attention_impl(None, lambda i: True) == "xla"
        assert resolve_attention_impl("auto", lambda i: True) == "xla"
        assert resolve_attention_impl("auto", lambda i: False) == "xla"


def test_paged_kernel_constraints():
    # the default engine geometry (ps=16, hd=8, f32) is eligible
    assert paged_kernel_constraints(16, 8, 2) == []
    # each violated constraint is named
    bad_ps = paged_kernel_constraints(6, 8, 2)
    assert len(bad_ps) == 1 and "page_size 6" in bad_ps[0]
    bad_hd = paged_kernel_constraints(16, 12, 2)
    assert len(bad_hd) == 1 and "head_dim 12" in bad_hd[0]
    bad_gqa = paged_kernel_constraints(16, 8, 4, n_q_heads=6)
    assert any("n_q_heads 6" in c for c in bad_gqa)
    # bf16 pages tile at 16 rows, so ps=8 is ineligible there but f32
    # (8-row sublanes) is fine
    assert paged_kernel_constraints(8, 8, 2) == []
    bad_bf16 = paged_kernel_constraints(8, 8, 2, dtype=jnp.bfloat16)
    assert len(bad_bf16) == 1 and "16-row" in bad_bf16[0]


def test_paged_pallas_supported_shapes():
    q = (4, 4, 1, 8)
    pool_ok = (64, 16, 2, 8)
    assert paged_pallas_supported(q, pool_ok, interpret=True)
    # interpret mode only needs structural validity, not lowering tiles
    assert paged_pallas_supported(q, (64, 6, 2, 8), interpret=True)
    assert not paged_pallas_supported(q, (64, 6, 2, 8), interpret=False)
    # multi-token q is the ragged prefill-chunk path: structurally
    # supported; compiled mode additionally requires the chunk rows to
    # fill the sublane tile (q_tokens constraint)
    assert paged_pallas_supported((4, 4, 2, 8), pool_ok, interpret=True)
    assert paged_pallas_supported((4, 4, 8, 8), pool_ok, interpret=False)
    assert not paged_pallas_supported((4, 4, 7, 8), pool_ok,
                                      interpret=False)
    # head mismatch stays structurally unsupported
    assert not paged_pallas_supported((4, 3, 1, 8), (64, 16, 2, 8),
                                      interpret=True)


# -- engine-level bit-identity ----------------------------------------------

def _build_engine(impl, slots=2, ps=8, n_pages=32, ppseq=4):
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=slots, page_size=ps,
                                 n_pages=n_pages, pages_per_seq=ppseq,
                                 attention_impl=impl)
    params = dag.init_params()
    weights = {k: v for k, v in params.items()
               if not (k.startswith("cache_") or k == "page_table")}
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    pool = PagePool(n_pages=n_pages, page_size=ps)
    eng = DeviceBackend(cluster).paged_decode_engine(
        dag.graph, sched, cfg, weights, pool,
        slots=slots, pages_per_seq=ppseq, seg_steps=4,
        attention_impl=impl,
    )
    return eng, pool, cfg


def test_engine_tokens_bitwise_identical_across_impls():
    """Same churny workload through two engines differing only in
    attention impl: retired token ids must match bitwise, and both
    pools must come back whole."""
    results = {}
    pools = {}
    for impl in ("xla", "pallas_interpret"):
        eng, pool, cfg = _build_engine(impl)
        rng = np.random.RandomState(11)
        for i in range(5):
            P = [8, 16, 8][i % 3]
            gen = [10, 5, 1][i % 3]
            ids = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (1, P)), jnp.int32
            )
            eng.submit(f"r{i}", ids, gen)
        results[impl] = eng.run()
        pools[impl] = pool
        assert eng.summary()["attention_impl"] == impl
    assert set(results["xla"]) == set(results["pallas_interpret"])
    for rid in results["xla"]:
        np.testing.assert_array_equal(
            np.asarray(results["xla"][rid]),
            np.asarray(results["pallas_interpret"][rid]),
            err_msg=f"{rid}: tokens diverge between impls",
        )
    for impl, pool in pools.items():
        assert pool.free_pages == pool.n_pages - 1, f"{impl} leaked pages"


def test_dag_names_distinguish_impls():
    """The impl is part of the graph identity: explicit impls get a
    name suffix, the default stays byte-stable for schedule caches."""
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    base = build_paged_decode_dag(cfg, slots=2)
    forced = build_paged_decode_dag(cfg, slots=2, attention_impl="xla")
    assert base.graph.name != forced.graph.name
    assert forced.graph.name.endswith("_attxla")
    assert base.attention_impl is None
    assert forced.graph.attention_impl == "xla"
    with pytest.raises(ValueError, match="unknown attention impl"):
        build_paged_decode_dag(cfg, slots=2, attention_impl="nope")


# -- DEC005 eligibility diagnostic ------------------------------------------

def test_dec005_fires_on_ineligible_geometry():
    from distributed_llm_scheduler_tpu.analysis import analyze
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=2, page_size=6)
    rep = analyze(dag.graph, params=dag.param_specs)
    dec5 = [d for d in rep.diagnostics if d.code == "DEC005"]
    assert len(dec5) == 1
    assert dec5[0].severity.name == "WARNING"
    assert "page_size 6" in dec5[0].message
    # a warning, never a gate: exit code stays 0
    assert rep.exit_code == 0


def test_dec005_silent_on_default_geometry_and_without_specs():
    from distributed_llm_scheduler_tpu.analysis import analyze
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=2)  # default ps=16, hd=8
    rep = analyze(dag.graph, params=dag.param_specs)
    assert not rep.has("DEC005")
    # no specs -> the pass cannot judge geometry, stays silent
    ineligible = build_paged_decode_dag(cfg, slots=2, page_size=6)
    rep2 = analyze(ineligible.graph)
    assert not rep2.has("DEC005")


# -- DEC006 chunk-size diagnostic --------------------------------------------

def test_dec006_fires_on_degenerate_chunk_size():
    from distributed_llm_scheduler_tpu.analysis import analyze
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=2)  # eligible ps=16, hd=8
    # ragged-kernel ineligible chunk: 7 rows misses the 8-row sublane
    rep = analyze(dag.graph, params=dag.param_specs, chunk_tokens=7)
    dec6 = [d for d in rep.diagnostics if d.code == "DEC006"]
    assert len(dec6) == 1 and dec6[0].severity.name == "WARNING"
    assert "q_tokens 7" in dec6[0].message
    assert rep.exit_code == 0  # a warning, never a gate
    # oversized chunk: exceeds the slots*seg_steps per-segment budget
    rep2 = analyze(dag.graph, params=dag.param_specs,
                   chunk_tokens=48, decode_budget=32)
    dec6 = [d for d in rep2.diagnostics if d.code == "DEC006"]
    assert len(dec6) == 1
    assert "exceeds the per-segment decode-token capacity 32" \
        in dec6[0].message


def test_dec006_silent_on_sane_chunk_and_without_chunking():
    from distributed_llm_scheduler_tpu.analysis import analyze
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=2)
    rep = analyze(dag.graph, params=dag.param_specs,
                  chunk_tokens=16, decode_budget=32)
    assert not rep.has("DEC006")
    # chunking off -> the check never runs
    rep2 = analyze(dag.graph, params=dag.param_specs)
    assert not rep2.has("DEC006")

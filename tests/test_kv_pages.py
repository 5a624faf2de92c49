"""Paged KV cache (models/kv_pages.py + ops.paged_decode_attention +
backends.PagedDecodeEngine).

Pins: the free-list allocator's backpressure contract (exhaustion raises,
double-free raises, budget sizing); scatter/gather round-trips through
the page indirection; ragged paged attention is BITWISE equal to the
dense decode attention at every per-slot length (the parity the decode
benchmark gates on); and the continuous-batching engine emits exactly
the tokens ``generate`` would, per request, under admission/retirement
churn with zero leaked pages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, get_scheduler
from distributed_llm_scheduler_tpu.models.kv_pages import (
    DEFAULT_PAGE_SIZE,
    TRASH_PAGE,
    PageOwnershipLog,
    PagePool,
    gather_kv,
    gather_kv_flat,
    init_paged_kv,
    page_table_array,
    pages_needed,
    pool_bytes_per_layer,
    prefix_chunk_keys,
    write_prompt_kv,
    write_token_kv,
)


# -- allocator --------------------------------------------------------------

def test_pool_reserves_trash_page():
    pool = PagePool(n_pages=8, page_size=4)
    assert pool.free_pages == 7  # page 0 never handed out
    got = pool.alloc(7)
    assert TRASH_PAGE not in got
    assert sorted(got) == list(range(1, 8))


def test_alloc_free_recycles_lifo():
    pool = PagePool(n_pages=8, page_size=4)
    a = pool.alloc(3)
    pool.free(a)
    b = pool.alloc(3)
    assert b == list(reversed(a))  # most-recently-freed first
    assert pool.used_pages == 3 and pool.free_pages == 4


def test_exhaustion_raises_not_clamps():
    pool = PagePool(n_pages=4, page_size=4)
    pool.alloc(3)
    assert not pool.can_alloc(1)
    with pytest.raises(MemoryError, match="exhausted"):
        pool.alloc(1)


def test_double_free_and_trash_free_raise():
    pool = PagePool(n_pages=4, page_size=4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    with pytest.raises(ValueError, match="reserved"):
        pool.free([TRASH_PAGE])


def test_pages_needed_ceil():
    assert pages_needed(0, 16) == 0
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    with pytest.raises(ValueError):
        pages_needed(-1, 16)


def test_from_budget_accounts_all_layers():
    # budget for exactly 10 pages across 4 layers of K+V pools
    per_page = 4 * pool_bytes_per_layer(1, 16, 2, 8, jnp.float32)
    pool = PagePool.from_budget(10 * per_page, 4, 2, 8, jnp.float32,
                                page_size=16)
    assert pool.n_pages == 10 and pool.free_pages == 9
    with pytest.raises(ValueError, match="fits"):
        PagePool.from_budget(per_page, 4, 2, 8, jnp.float32, page_size=16)


def test_device_hbm_bytes_is_positive():
    from distributed_llm_scheduler_tpu.utils.costmodel import device_hbm_bytes

    assert device_hbm_bytes(jax.devices()[0]) > 0
    assert device_hbm_bytes(None) > 0


# -- prefix sharing: intern table, refcounts, chain hashes ------------------

def test_prefix_chunk_keys_chain_over_full_prefix():
    ks = prefix_chunk_keys(list(range(16)), 4)
    assert len(ks) == 4  # only FULL pages get keys
    assert prefix_chunk_keys(list(range(15)), 4) == ks[:3]  # tail dropped
    # chained: same page 0, divergent page 1 -> key 0 equal, key 1 differs
    a = prefix_chunk_keys([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = prefix_chunk_keys([1, 2, 3, 4, 9, 9, 9, 9], 4)
    assert a[0] == b[0] and a[1] != b[1]
    # a page-0 divergence poisons every later key (whole-prefix digest,
    # not per-page: KV rows depend on everything before them)
    c = prefix_chunk_keys([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert c[0] != a[0] and c[1] != a[1]
    # container-agnostic: a (1, P) device row hashes like a plain list
    assert (prefix_chunk_keys(jnp.asarray([[1, 2, 3, 4]], jnp.int32), 4)
            == prefix_chunk_keys([1, 2, 3, 4], 4))
    with pytest.raises(ValueError, match="page_size"):
        prefix_chunk_keys([1], 0)


def test_match_share_release_roundtrip():
    pool = PagePool(n_pages=8, page_size=4, sharing=True)
    keys = prefix_chunk_keys(list(range(8)), 4)
    pages = pool.alloc(2)
    for p, k in zip(pages, keys):
        pool.register(p, k)
    assert pool.match_prefix(keys) == (2, pages)
    # longest-resident-run semantics: an unknown key stops the match
    assert pool.match_prefix(keys + ["nope"]) == (2, pages)
    assert pool.match_prefix(["nope"] + keys) == (0, [])
    pool.share(pages)
    assert pool.refcount(pages[0]) == 2
    assert pool.used_pages == 2 and pool.logical_pages == 4
    assert pool.shared_pages == 2
    with pytest.raises(ValueError, match="shared"):
        pool.free([pages[0]])  # aliased pages must go through release_ref
    pool.release_ref(pages)  # drop the alias: nothing freed physically
    assert pool.used_pages == 2 and pool.refcount(pages[0]) == 1
    assert pool.match_prefix(keys) == (2, pages)  # still interned
    # last reference frees the pages PHYSICALLY but retains the intern
    # entries (LRU): the prefix stays matchable until alloc pressure or
    # an explicit drop evicts it
    pool.release_ref(pages)
    assert pool.free_pages == 7
    assert pool.match_prefix(keys) == (2, pages)
    assert pool.cached_pages == 2
    assert pool.is_cached(pages[0]) and pool.is_cached(pages[1])
    assert pool.drop_cached() == 2
    assert pool.cached_pages == 0
    assert pool.match_prefix(keys) == (0, [])


def test_lru_retention_alloc_prefers_uncached_then_evicts_oldest():
    """Cached-free pages are the allocator's LAST resort, and eviction
    under pressure is oldest-release-first (LRU)."""
    pool = PagePool(n_pages=6, page_size=4, sharing=True)
    a = pool.alloc(2)      # pages for prefix A
    b = pool.alloc(2)      # pages for prefix B
    ka = prefix_chunk_keys(list(range(8)), 4)
    kb = prefix_chunk_keys(list(range(100, 108)), 4)
    for p, k in zip(a, ka):
        pool.register(p, k)
    for p, k in zip(b, kb):
        pool.register(p, k)
    pool.free(a)           # A released first -> oldest cached
    pool.free(b)
    assert pool.free_pages == 5 and pool.cached_pages == 4
    # one uncached free page exists; a 1-page alloc must take IT and
    # leave both prefixes matchable
    c = pool.alloc(1)
    assert pool.cached_pages == 4
    assert pool.match_prefix(ka)[0] == 2
    assert pool.match_prefix(kb)[0] == 2
    # pressure: the next alloc must evict from A (older) before B
    d = pool.alloc(2)
    assert pool.match_prefix(ka)[0] == 0, "oldest prefix must evict first"
    assert pool.match_prefix(kb)[0] == 2
    pool.free(c)
    pool.free(d)


def test_share_revives_cached_free_pages_as_alloc():
    """A match on a cached-free page revives it: ``share`` re-allocates
    it off the free list (an 'alloc' event, not a 'share' — the page had
    no live reference to add to) and the books balance."""
    log = PageOwnershipLog(n_pages=8)
    pool = PagePool(n_pages=8, page_size=4, sharing=True, ownlog=log)
    keys = prefix_chunk_keys(list(range(8)), 4)
    pages = pool.alloc(2)
    for p, k in zip(pages, keys):
        pool.register(p, k)
    pool.free(pages)       # retained: physically free, still matchable
    h, matched = pool.match_prefix(keys)
    assert (h, matched) == (2, pages)
    before = pool.free_pages
    pool.share(matched)    # revival: consumes the free-list entries
    assert pool.free_pages == before - 2
    assert pool.refcount(pages[0]) == 1 and not pool.is_cached(pages[0])
    kinds = [e["kind"] for e in log.snapshot()["events"]]
    assert kinds[-1] == "alloc", "revival must book as an allocation"
    pool.release_ref(pages)
    assert pool.free_pages == before  # and back to retained-free
    assert pool.cached_pages == 2


def test_sharing_disabled_pool_is_inert():
    pool = PagePool(n_pages=8, page_size=4)
    pages = pool.alloc(2)
    keys = prefix_chunk_keys(list(range(8)), 4)
    pool.register(pages[0], keys[0])  # no-op when sharing is off
    assert pool.match_prefix(keys) == (0, [])
    with pytest.raises(ValueError, match="sharing disabled"):
        pool.share(pages)
    pool.release_ref(pages)  # degrades to a plain free
    assert pool.free_pages == 7


def test_sharing_error_paths_and_first_writer_interning():
    pool = PagePool(n_pages=8, page_size=4, sharing=True)
    with pytest.raises(ValueError, match="unallocated"):
        pool.share([3])
    with pytest.raises(ValueError, match="unallocated"):
        pool.register(3, "k")
    with pytest.raises(ValueError, match="unallocated"):
        pool.release_ref([3])
    # first writer wins: a duplicate key keeps the incumbent page so
    # existing aliases of it stay valid
    a, b = pool.alloc(2)
    pool.register(a, "k")
    pool.register(b, "k")
    assert pool.match_prefix(["k"]) == (1, [a])


def test_share_unshare_events_carry_tiling_and_refcounts():
    log = PageOwnershipLog(n_pages=8)
    pool = PagePool(n_pages=8, page_size=4, sharing=True, ownlog=log)
    pages = pool.alloc(2)
    pool.share(pages)
    pool.release_ref(pages)   # unshare (rc 2 -> 1)
    pool.release_ref(pages)   # last ref -> physical free
    kinds = [e["kind"] for e in log.snapshot()["events"]]
    assert kinds == ["alloc", "share", "unshare", "free"]
    share_ev = log.snapshot()["events"][1]
    # share moves no physical pages: tiling counts unchanged from alloc
    assert share_ev["free_pages"] == 5 and share_ev["used_pages"] == 2
    assert share_ev["refcounts"] == [2, 2]
    unshare_ev = log.snapshot()["events"][2]
    assert unshare_ev["refcounts"] == [1, 1]  # post-decrement
    # disabled-sharing streams never carry the key at all
    log2 = PageOwnershipLog(n_pages=8)
    pool2 = PagePool(n_pages=8, page_size=4, ownlog=log2)
    pool2.free(pool2.alloc(1))
    assert all("refcounts" not in e for e in log2.snapshot()["events"])


# -- scatter / gather -------------------------------------------------------

def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_prompt_write_gather_roundtrip():
    ps, hkv, hd = 4, 2, 8
    # the stored form: a row's heads side by side, (pages, ps, hkv * hd)
    pool_arr = init_paged_kv(1, 8, ps, hkv, hd, jnp.float32)["cache_k_0"]
    assert pool_arr.shape == (8, ps, hkv * hd)
    rows = _rand(0, (2 * ps, hkv, hd))
    pt = page_table_array([[3, 5]], pages_per_seq=4)
    pool_arr = write_prompt_kv(pool_arr, rows, jnp.asarray([3, 5]))
    # a stored row is the token's (hkv, hd) values flattened
    np.testing.assert_array_equal(
        np.asarray(pool_arr[3]), np.asarray(rows[:ps].reshape(ps, hkv * hd)))
    view = gather_kv(pool_arr, pt, hd)  # (1, hkv, 16, hd) dense orientation
    dense = rows.transpose(1, 0, 2)[None]
    np.testing.assert_array_equal(np.asarray(view[:, :, : 2 * ps]), dense)
    # tail entries gather the (zero) trash page
    assert not np.any(np.asarray(view[:, :, 2 * ps:]))
    # flat view is the token-major layout of the same data
    flat = gather_kv_flat(pool_arr, pt, hd)
    np.testing.assert_array_equal(
        np.asarray(flat), np.asarray(view.transpose(0, 2, 1, 3))
    )


def test_token_write_lands_in_page_slot_and_trash_for_inactive():
    ps, hkv, hd = 4, 2, 8
    pool_arr = jnp.zeros((8, ps, hkv * hd), jnp.float32)
    pt = page_table_array([[2, 4], [6, 7]], pages_per_seq=2)
    new = _rand(1, (2, hkv, 1, hd))
    # slot 0 at length 5 -> logical page 1 (phys 4), slot offset 1;
    # slot 1 inactive -> its row must NOT land anywhere visible
    out = write_token_kv(
        pool_arr, new, pt,
        jnp.asarray([5, 2], jnp.int32),
        jnp.asarray([True, False]),
    )
    np.testing.assert_array_equal(
        np.asarray(out[4, 1]), np.asarray(new[0, :, 0].reshape(-1)))
    # only the trash page and the target slot changed
    changed = np.flatnonzero(
        np.asarray(jnp.any(out != pool_arr, axis=(1, 2)))
    )
    assert set(changed) <= {TRASH_PAGE, 4}


def test_page_table_array_rejects_overflow():
    with pytest.raises(ValueError, match="pages_per_seq"):
        page_table_array([[1, 2, 3]], pages_per_seq=2)


# -- ragged paged attention: bitwise dense parity ---------------------------

@pytest.mark.parametrize("lengths", [[0, 5, 15], [3, 3, 3], [15, 0, 7]])
def test_paged_attention_bitwise_dense_parity(lengths):
    from distributed_llm_scheduler_tpu.models.decode import (
        _decode_attention_natural,
    )
    from distributed_llm_scheduler_tpu.ops.attention import (
        paged_decode_attention,
    )

    S, Hq, Hkv, hd, ps, ppseq = 3, 4, 2, 8, 4, 4
    M = ps * ppseq
    scale = hd ** -0.5
    rng = np.random.RandomState(0)
    dense_k = jnp.asarray(rng.randn(S, Hkv, M, hd), jnp.float32)
    dense_v = jnp.asarray(rng.randn(S, Hkv, M, hd), jnp.float32)
    q = jnp.asarray(rng.randn(S, Hq, 1, hd), jnp.float32)
    k_new = jnp.asarray(rng.randn(S, Hkv, 1, hd), jnp.float32)
    v_new = jnp.asarray(rng.randn(S, Hkv, 1, hd), jnp.float32)

    # scatter each slot's dense rows into disjoint pages
    pool = PagePool(n_pages=S * ppseq + 1, page_size=ps)
    tables = [pool.alloc(ppseq) for _ in range(S)]
    k_pool = jnp.zeros((pool.n_pages, ps, Hkv * hd), jnp.float32)
    v_pool = jnp.zeros_like(k_pool)
    for s in range(S):
        pages = jnp.asarray(tables[s])
        k_pool = write_prompt_kv(k_pool, dense_k[s].transpose(1, 0, 2), pages)
        v_pool = write_prompt_kv(v_pool, dense_v[s].transpose(1, 0, 2), pages)
    pt = page_table_array(tables, ppseq)
    L = jnp.asarray(lengths, jnp.int32)

    got = paged_decode_attention(
        q, k_pool, v_pool, pt, L, scale, k_new=k_new, v_new=v_new
    )
    # dense oracle: write-then-attend at each slot's own position
    for s in range(S):
        k_s = jax.lax.dynamic_update_slice(
            dense_k[s: s + 1], k_new[s: s + 1], (0, 0, int(lengths[s]), 0)
        )
        v_s = jax.lax.dynamic_update_slice(
            dense_v[s: s + 1], v_new[s: s + 1], (0, 0, int(lengths[s]), 0)
        )
        want = _decode_attention_natural(
            q[s: s + 1], k_s, v_s, jnp.int32(lengths[s]), scale, None, None
        )
        np.testing.assert_array_equal(
            np.asarray(got[s: s + 1]), np.asarray(want),
            err_msg=f"slot {s} length {lengths[s]} not bitwise equal",
        )


def test_paged_attention_impl_dispatch():
    """An explicit ``pallas`` on a geometry the dispatch rules reject
    raises — it is never quietly served by the gather path; ``auto``
    may choose the gather path (DEC005 is the observability for it), and
    an unknown impl is a hard error."""
    from distributed_llm_scheduler_tpu.ops.attention import (
        paged_decode_attention,
    )

    # page_size 4 / head_dim 4 violate the dispatch's tile rules
    z = jnp.ones((1, 2, 1, 4), jnp.float32)
    pool = jnp.zeros((2, 4, 2 * 4), jnp.float32)
    pt = jnp.zeros((1, 2), jnp.int32)
    L = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="requested explicitly"):
        paged_decode_attention(z, pool, pool, pt, L, impl="pallas")
    got = paged_decode_attention(z, pool, pool, pt, L, impl="auto")
    ref = paged_decode_attention(z, pool, pool, pt, L, impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError, match="unknown attention impl"):
        paged_decode_attention(z, pool, pool, pt, L, impl="triton")


# -- continuous batching engine ---------------------------------------------

def test_paged_loop_rejects_multi_node_placement():
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        compose_paged_step_fn,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    dag = build_paged_decode_dag(GPT2Config.tiny(), slots=2, page_size=4,
                                 n_pages=8, pages_per_seq=4)
    cluster = Cluster([DeviceState(f"n{i}", 64.0) for i in range(2)])
    sched = get_scheduler("roundrobin").schedule(dag.graph, cluster)
    with pytest.raises(ValueError, match="single-node"):
        compose_paged_step_fn(dag.graph, sched, GPT2Config.tiny())


def test_continuous_batching_token_exact_under_churn(session_slo_engine):
    """More requests than slots, mixed prompt/gen lengths, so slots
    retire and readmit mid-run: every request's tokens must equal the
    whole-program greedy ``generate`` stream, and every page must come
    back to the pool.  Rides the session-scoped engine (same tiny
    geometry) instead of paying its own DAG build + XLA compile; the
    ``generate`` reference runs off ``eng.weights`` — the exact arrays
    the engine decodes with — so token parity is still end-to-end."""
    from distributed_llm_scheduler_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    eng = session_slo_engine
    eng.rebind_obs()  # pristine pool + run state, warm executables
    pool = eng.pool
    n_pages = pool.n_pages
    cap = eng.page_size * eng.pages_per_seq
    params = eng.weights

    rng = np.random.RandomState(3)
    reqs = []
    for i in range(6):
        P = [8, 16, 8][i % 3]
        gen = [10, 5, 1][i % 3]  # gen=1 retires straight from prefill
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, P)), jnp.int32)
        reqs.append((f"r{i}", ids, gen))
        eng.submit(f"r{i}", ids, gen)
    res = eng.run()

    assert set(res) == {rid for rid, _, _ in reqs}
    for rid, ids, gen in reqs:
        want = gpt2.generate(params, ids, cfg, max_new_tokens=gen,
                             max_len=cap)
        want_new = np.asarray(want)[0, ids.shape[1]:]
        np.testing.assert_array_equal(
            res[rid], want_new, err_msg=f"{rid} diverged from generate"
        )
    assert pool.free_pages == n_pages - 1, "pages leaked"

    # the engine is reusable: reset returns every page and replays clean
    eng.reset()
    eng.submit("again", reqs[0][1], 3)
    res2 = eng.run()
    want = gpt2.generate(params, reqs[0][1], cfg, max_new_tokens=3,
                         max_len=cap)
    np.testing.assert_array_equal(
        res2["again"], np.asarray(want)[0, reqs[0][1].shape[1]:]
    )


def test_engine_rejects_oversized_request():
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny()
    dag = build_paged_decode_dag(cfg, slots=2, page_size=4, n_pages=8,
                                 pages_per_seq=2)  # capacity 8
    params = dag.init_params()
    weights = {k: v for k, v in params.items()
               if not (k.startswith("cache_") or k == "page_table")}
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    sched = get_scheduler("greedy").schedule(dag.graph, cluster)
    eng = DeviceBackend(cluster).paged_decode_engine(
        dag.graph, sched, cfg, weights,
        PagePool(n_pages=8, page_size=4), slots=2, pages_per_seq=2,
    )
    ids = jnp.zeros((1, 6), jnp.int32)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit("big", ids, 3)  # 6 + 3 > 8


def test_shared_prefix_churn_property(session_slo_engine):
    """Seeded random admit/decode/preempt interleavings over a
    shared-prefix request mix: after EVERY action the pool must tile
    physically (free + unique used == allocatable), refcounts must
    cover every slot-held page, the intern table must only point at
    live pages or retained cached-free ones (LRU retention), and the
    ownership stream must replay clean through the page-lifetime
    prover.  At the end: zero physical leaks, a clean
    final prover pass (orphan scan included), and bitwise-identical
    tokens for two concurrently-decoded requests aliasing the same
    prefix pages."""
    from distributed_llm_scheduler_tpu.analysis.page_pass import (
        analyze_pages,
    )

    eng = session_slo_engine
    log = PageOwnershipLog(n_pages=eng.pool.n_pages)
    try:
        eng.pool.sharing = True  # rebind builds a pristine SHARING pool
        eng.rebind_obs(ownlog=log)
        assert eng.sharing

        rng = np.random.RandomState(17)
        system = [int(t) for t in rng.randint(1, 40, size=8)]
        users = [[int(t) for t in rng.randint(1, 40, size=8)]
                 for _ in range(4)]
        prompts = {}

        def prompt_for(i):
            toks = system + users[i % 4]
            if i % 2:  # every other request is a two-turn session
                toks = toks + users[(i + 1) % 4]
            return jnp.asarray([toks], jnp.int32)

        def check():
            occ = eng.page_occupancy()
            assert occ["free_pages"] + occ["used_pages"] == occ["n_pages"]
            pool = eng.pool
            assert pool.logical_pages >= pool.used_pages
            for s in range(eng.slots):
                for p in eng._slot_pages[s]:
                    assert pool.refcount(p) >= 1
            for key, page in pool._intern.items():
                # live, or physically free with its entry retained
                assert page in pool._allocated or pool.is_cached(page)
                assert pool._page_key.get(page) == key
            rep = analyze_pages(log, final=False)  # mid-run: no orphan scan
            assert [d.code for d in rep.diagnostics] == []

        nxt, resumed = 0, 0
        for _ in range(48):
            in_flight = [eng._slot_req[s] for s in range(eng.slots)
                         if eng._slot_req[s] is not None]
            roll = float(rng.rand())
            if (roll < 0.45 and nxt < 10) or (not in_flight
                                              and not eng._queue):
                if nxt >= 10:
                    break  # workload drained and nothing left to submit
                rid = f"c{nxt}"
                prompts[rid] = prompt_for(nxt)
                eng.submit(rid, prompts[rid], int(rng.randint(2, 6)))
                nxt += 1
            elif roll < 0.62 and in_flight:
                victim = in_flight[int(rng.randint(len(in_flight)))]
                ev = eng.preempt(victim)
                if int(ev["remaining"]) > 0:
                    # deterministic resume: prompt + generated prefix
                    # re-queued under a derived rid (greedy decode makes
                    # the continuation exact)
                    rid2 = f"{victim}.r{resumed}"
                    resumed += 1
                    prompts[rid2] = jnp.concatenate(
                        [prompts[victim],
                         jnp.asarray(ev["tokens"], jnp.int32)[None, :]],
                        axis=1,
                    )
                    eng.submit(rid2, prompts[rid2], int(ev["remaining"]))
            else:
                eng.step_segment()
            check()

        eng.run()  # drain whatever churn left behind
        check()
        occ = eng.page_occupancy()
        assert occ["free_pages"] == occ["n_pages"], "pages leaked"

        # epilogue: a second identical prompt arriving one segment later
        # must alias the first's freshly-interned pages and decode to
        # bitwise-identical token streams.  (Same-wave twins also share
        # now: _admit defers duplicate prefixes by one wave so the first
        # copy's pages are interned before the twin scatters.)
        twin = prompt_for(1)  # 24 tokens -> 2 shareable full pages
        n_share = sum(1 for e in log.events if e["kind"] == "share")
        # budget > seg_steps so za is still resident when zb arrives
        eng.submit("za", twin, 8)
        eng.step_segment()  # admit + intern za's pages
        eng.submit("zb", twin, 8)
        res = eng.run()
        np.testing.assert_array_equal(res["za"], res["zb"])
        kinds = [e["kind"] for e in log.snapshot()["events"]]
        assert sum(1 for k in kinds if k == "share") > n_share
        assert "cow" not in kinds
        check()
        assert eng.page_occupancy()["free_pages"] == occ["n_pages"]
        # final pass WITH the orphan scan: every alloc found its free
        assert [d.code for d in analyze_pages(log).diagnostics] == []
    finally:
        eng.pool.sharing = False  # next rebind builds a non-sharing pool
        eng.attach_ownership_log(None)
        eng.reset()


def test_same_wave_twins_share_prefix_pages(session_slo_engine):
    """Two identical prompts submitted into the SAME admission wave
    must still alias prefix pages: ``_admit`` defers the duplicate by
    one wave so the first copy's pages are interned before the twin
    scatters.  Tokens stay bitwise identical to a no-sharing baseline,
    the ownership log shows share events with no CoW, and nothing
    leaks."""
    from distributed_llm_scheduler_tpu.analysis.page_pass import (
        analyze_pages,
    )

    eng = session_slo_engine
    log = PageOwnershipLog(n_pages=eng.pool.n_pages)
    try:
        rng = np.random.RandomState(5)
        prompt = jnp.asarray(
            [[int(t) for t in rng.randint(1, 40, size=16)]], jnp.int32
        )  # 16 tokens -> 2 full shareable pages at page_size=8

        eng.pool.sharing = False
        eng.rebind_obs()
        eng.submit("base", prompt, 4)
        base = np.asarray(eng.run()["base"])

        eng.pool.sharing = True
        eng.rebind_obs(ownlog=log)
        eng.submit("twin_a", prompt, 4)
        eng.submit("twin_b", prompt, 4)  # same wave: no segment between
        res = eng.run()
        np.testing.assert_array_equal(np.asarray(res["twin_a"]), base)
        np.testing.assert_array_equal(np.asarray(res["twin_b"]), base)

        kinds = [e["kind"] for e in log.snapshot()["events"]]
        assert sum(1 for k in kinds if k == "share") >= 1
        assert "cow" not in kinds  # neither twin writes the shared pages
        occ = eng.page_occupancy()
        assert occ["free_pages"] == occ["n_pages"], "pages leaked"
        assert eng.pool.cached_pages >= 2  # prefix retained for revival
        assert [d.code for d in analyze_pages(log).diagnostics] == []
    finally:
        eng.pool.sharing = False
        eng.attach_ownership_log(None)
        eng.reset()

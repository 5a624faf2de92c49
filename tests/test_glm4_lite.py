"""The GLM-4 MoE "lite" block (``models/glm4_lite.py``) and the step that
verifies its own draft: against the benchmark's plain float32 reference
at tiny widths on seeded random weights, and against the same program
stepped one row at a time.

Tolerances: everything here runs in float32, so the program and the
reference (float32, "highest") differ by summation order only — 1e-4 on
logits of magnitude ~5 (as ``tests/test_xing4.py``).  The speculative
output must BE the one-row greedy output; a request may part from it
only at a position where the reference's two candidates lie within 1e-4
of each other, and is compared no further.
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

from benchmark.reference import glm as R  # noqa: E402
from distributed_llm_scheduler_tpu import Cluster, get_scheduler  # noqa: E402
from distributed_llm_scheduler_tpu import models  # noqa: E402
from distributed_llm_scheduler_tpu.analysis.page_pass import (  # noqa: E402
    analyze_pages,
)
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend  # noqa: E402
from distributed_llm_scheduler_tpu.frontend.decode_dag import (  # noqa: E402
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import glm4_lite  # noqa: E402
from distributed_llm_scheduler_tpu.models.kv_pages import (  # noqa: E402
    PageOwnershipLog,
    PagePool,
    write_step_rows,
    write_token_rows,
)
from distributed_llm_scheduler_tpu.ops.attention import (  # noqa: E402
    mla_paged_decode_attention,
)

TOL = 1e-4
#: tiny widths; the ``init`` group is the configuration's construction at
#: this size: read on the CPU it accepts 0.58 of its drafts (emb_gain 6 /
#: eh_identity 2: 0.24; 14 / 3: 0.64)
HF = {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "n_routed_experts": 8, "moe_intermediate_size": 16,
    "n_shared_experts": 1, "intermediate_size": 64,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "partial_rotary_factor": 1,
    "routed_scaling_factor": 1.8, "max_position_embeddings": 256,
    "n_group": 1, "topk_group": 1, "dtype": "float32",
    "init": {"std": 0.1, "q_gain": 4.0, "emb_gain": 10.0,
             "eh_identity": 2.5, "mtp_out_gain": 0.2},
}
CFG = glm4_lite.Glm4LiteConfig.from_hf(HF, dtype=jnp.float32)
IMPLS = ("xla", "pallas_interpret")
S, PS, PPSEQ, SEG = 3, 8, 8, 4
CAP = PS * PPSEQ


@pytest.fixture(scope="module")
def weights():
    return R.make_params(HF, 2**31 + 7)


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(1, 256, size=(2, 20)).astype(
        np.int32)


def _engine(weights, impl="xla", chunk=16, slots=S, ownlog=None):
    n_pages = 1 + slots * PPSEQ
    ddag = build_paged_decode_dag(
        CFG, slots=slots, page_size=PS, n_pages=n_pages,
        pages_per_seq=PPSEQ, attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("greedy").schedule(ddag.graph, cluster)
    eng = DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, CFG, weights,
        PagePool(n_pages=n_pages, page_size=PS), slots=slots,
        pages_per_seq=PPSEQ, seg_steps=SEG, attention_impl=impl,
        chunk_tokens=chunk)
    if ownlog is not None:
        eng.attach_ownership_log(ownlog)
    return eng


@pytest.fixture(scope="module")
def engine(weights):
    return _engine(weights)


@jax.jit
def _one_row(weights, ids, cache, pos):
    logits, cache = glm4_lite.forward_cached(
        weights, ids, cache, pos, CFG, impl="xla")
    return jnp.argmax(logits[0, -1]), cache


def _one_row_greedy(weights, prompt, n_new):
    """``n_new`` greedy tokens by the SAME program stepped one row at a
    time: the family's cached forward, no draft."""
    tok, cache = _one_row(weights, jnp.asarray(prompt),
                          glm4_lite.init_cache(CFG, 1, CAP), 0)
    out, pos = [int(tok)], prompt.shape[1]
    while len(out) < n_new:
        tok, cache = _one_row(
            weights, jnp.asarray([[out[-1]]], jnp.int32), cache, pos)
        out.append(int(tok))
        pos += 1
    return out


def _assert_same_or_tied(weights, prompt, served, want):
    """``served`` is ``want``, or parts from it where the reference holds
    the two candidates within 1e-4 of each other."""
    served = [int(t) for t in served]
    assert len(served) == len(want)
    if served == want:
        return
    i = next(j for j in range(len(want)) if served[j] != want[j])
    seq = np.concatenate([prompt[0], np.asarray(want[:i], np.int32)])
    row = np.asarray(R.logits(weights, HF, seq[None])[0, -1])
    assert abs(row[served[i]] - row[want[i]]) < 1e-4, (i, served, want)


def _counters(eng):
    c = eng.metrics.snapshot()["counters"]
    return {k: c[k]["value"] for k in
            ("mtp.drafts_verified", "mtp.drafts_accepted",
             "mtp.rows_rolled_back")}


# -- the model against the reference ---------------------------------------------


def test_reference_weights_have_the_programs_names_and_shapes(weights):
    want = {k: (tuple(s), jnp.dtype(d))
            for k, (s, d) in glm4_lite.param_shapes(CFG).items()}
    assert {k: (v.shape, v.dtype) for k, v in weights.items()} == want
    assert R.n_parameters(HF) == sum(
        int(np.prod(v.shape)) for v in weights.values())


@pytest.mark.parametrize("impl", IMPLS)
def test_main_and_draft_logits_match_the_reference(weights, ids, impl):
    got = glm4_lite.forward(weights, jnp.asarray(ids), CFG, impl=impl)
    assert float(jnp.abs(got - R.logits(weights, HF, ids)).max()) < TOL
    got = glm4_lite.forward_draft(weights, jnp.asarray(ids), CFG, impl=impl)
    want = R.draft_logits(weights, HF, ids)[:, :-1]
    assert float(jnp.abs(got - want).max()) < TOL


def test_chunked_prefill_with_the_draft_matches_the_reference(weights, ids):
    """Chunks of 8, 8 and 4 through ``forward_cached_draft``, the ids
    shifted by one: each chunk's last row gives the reference's main and
    draft logits, and the last chunk, told nothing of the token after the
    prompt, takes its own argmax for it."""
    cache = glm4_lite.init_cache(CFG, 2, 24)
    T, pos = ids.shape[1], 0
    main = R.logits(weights, HF, ids)
    first = np.asarray(jnp.argmax(main[:, -1], -1))
    draft = R.draft_logits(
        weights, HF, np.concatenate([ids, first[:, None]], 1))
    for n in (8, 8, 4):
        nxt = np.full((2, n), -1, np.int32)
        k = min(n, T - pos - 1)
        nxt[:, :k] = ids[:, pos + 1:pos + 1 + k]
        lg, dl, cache = glm4_lite.forward_cached_draft(
            weights, jnp.asarray(ids[:, pos:pos + n]), jnp.asarray(nxt),
            cache, pos, CFG, n - 1)
        pos += n
        assert float(jnp.abs(lg - main[:, pos - 1]).max()) < TOL
        assert float(jnp.abs(dl - draft[:, pos - 1]).max()) < TOL


@pytest.mark.parametrize("impl", IMPLS)
def test_decode_step_through_the_cache_matches_the_reference(
        weights, ids, impl):
    """Prefill 18 tokens into pages, then ONE verifying step over the
    rows ``[x@18, d@19]``, ``x`` the prefill's own first token: the
    step's main logits are the reference's at positions 18 and 19 of
    the whole sequence; its draft logits of row 0 the reference draft
    module's at 18 given the step's ``y0``, and of row 1 — in the slot
    whose draft IS ``y0`` — the reference's at 19 given ``y1`` (the
    other slot's row 1 follows a rejected draft and is nobody's).  The
    paged DAG's oracle agrees on every row."""
    P = 18
    ids = ids.copy()
    ids[:, P] = np.asarray(jnp.argmax(
        R.logits(weights, HF, ids[:, :P])[:, -1], -1))
    y0 = np.asarray(jnp.argmax(
        R.logits(weights, HF, ids[:, :P + 1])[:, -1], -1))
    ids[0, P + 1] = y0[0]                   # slot 0: the draft is right
    ids[1, P + 1] = (y0[1] + 1) % 255 + 1   # slot 1: it is not
    eng = _engine(weights, impl=impl, slots=2, chunk=None)
    for b in range(2):
        eng.submit(f"p{b}", ids[b:b + 1, :P], 2)
    eng._admit()
    assert eng.cur_tok[:, 0].tolist() == ids[:, P].tolist()
    dag = build_paged_decode_dag(
        CFG, slots=2, page_size=PS, n_pages=1 + 2 * PPSEQ,
        pages_per_seq=PPSEQ, attention_impl=impl)
    params = {**eng.weights, **eng.pools,
              "page_table": jnp.asarray(eng.page_table)}
    inputs = {"ids": jnp.asarray(ids[:, P:P + 2]),
              "lengths": jnp.asarray(eng.lengths),
              "active": jnp.ones((2,), bool)}
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("greedy").schedule(dag.graph, cluster)
    out = DeviceBackend(cluster).execute(
        dag.graph, plan, params, inputs).output
    want = R.logits(weights, HF, ids)[:, P:P + 2]
    assert float(jnp.abs(out["logits"] - want).max()) < TOL
    y = np.asarray(jnp.argmax(want, -1))
    assert y[:, 0].tolist() == y0.tolist()
    for b, r in ((0, 0), (1, 0), (0, 1)):
        seq = np.concatenate([ids[b, :P + 1 + r], y[b, r:r + 1]])
        d = R.draft_logits(weights, HF, seq[None])[0, P + r]
        assert float(jnp.abs(out["draft_logits"][b, r] - d).max()) < TOL
    oracle = dag.reference_forward(params, inputs)
    for k in ("logits", "draft_logits"):
        assert float(jnp.abs(out[k] - oracle[k]).max()) < TOL, k


# -- the verifying step, served ----------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
def test_speculative_output_is_the_one_row_greedy_output(weights, impl):
    """Whole-prompt and chunked admission, both paths of the step taken."""
    eng = _engine(weights, impl=impl)
    rng = np.random.RandomState(1)
    prompts = {f"r{i}": rng.randint(1, 256, (1, n)).astype(np.int32)
               for i, n in enumerate((5, 8, 11, 35, 14, 17))}
    for rid, prompt in prompts.items():
        eng.submit(rid, prompt, 24)
    served = eng.run()
    for rid, prompt in prompts.items():
        _assert_same_or_tied(weights, prompt, served[rid],
                             _one_row_greedy(weights, prompt, 24))
    c = _counters(eng)
    rejected = c["mtp.drafts_verified"] - c["mtp.drafts_accepted"]
    assert c["mtp.drafts_accepted"] > 10 and rejected > 10
    assert c["mtp.rows_rolled_back"] == rejected
    assert eng.pool.free_pages == eng.pool.n_pages - 1
    hist = eng.metrics.snapshot()["histograms"]
    assert 0.3 < hist["mtp.accept_rate"]["mean"] < 0.9
    assert hist["mtp.tokens_per_step"]["count"] == eng.segments_run


@pytest.mark.parametrize("max_new", range(1, 11))
def test_no_request_gets_more_than_it_asked_for(weights, engine, max_new):
    """A slot that owes 1 when a step would yield 2 emits only the one;
    every budget from 1 up, over a prompt whose drafts are mostly
    accepted."""
    engine.rebind_obs()
    rng = np.random.RandomState(7)
    prompts = {f"q{i}": rng.randint(1, 256, (1, 6 + i)).astype(np.int32)
               for i in range(3)}
    for rid, prompt in prompts.items():
        engine.submit(rid, prompt, max_new)
    served = engine.run()
    for rid, prompt in prompts.items():
        assert len(served[rid]) == max_new
        _assert_same_or_tied(weights, prompt, served[rid],
                             _one_row_greedy(weights, prompt, max_new))
    assert engine.pool.free_pages == engine.pool.n_pages - 1


def test_host_state_after_every_fold_is_the_devices(weights, engine):
    """``lengths`` / ``remaining`` / ``cur_tok`` the host folds from the
    counts are what the segment's carry held: replaying the record the
    segment returned (``[y0, y1, count, next draft]`` a step), the host's
    lengths advance by the counts, never past what a slot owed, and the
    draft kept for the next segment is the last step's."""
    engine.rebind_obs()
    seen = []
    seg = engine._seg

    def spy(w, pools, table, lengths, cur, remaining):
        out = seg(w, pools, table, lengths, cur, remaining)
        seen.append((np.array(lengths), np.array(cur), np.array(remaining),
                     np.asarray(out[0])))
        return out

    engine._seg = spy
    try:
        rng = np.random.RandomState(3)
        for i, (n, k) in enumerate(((6, 9), (9, 3), (12, 14), (7, 2))):
            engine.submit(f"h{i}", rng.randint(1, 256, (1, n)), k)
        # retirement in the middle of a segment: budgets 3 and 2 end
        # inside a 4-step segment and the queued request takes the slot
        while engine._queue or any(r is not None for r in engine._slot_req):
            n_before = len(seen)
            rids = list(engine._slot_req)
            engine.step_segment()
            if len(seen) == n_before:
                continue
            L0, cur0, owed, rec = seen[-1]
            counts = rec[..., 2]
            assert counts.min() >= 0 and counts.max() <= 2
            assert (counts.sum(1) <= owed).all()
            for s in range(S):
                # the device stopped exactly when the slot owed nothing
                left = owed[s] - np.cumsum(counts[s])
                assert (counts[s][1:][left[:-1] == 0] == 0).all()
                if rids[s] is not None and engine._slot_req[s] == rids[s]:
                    assert engine.lengths[s] == L0[s] + counts[s].sum()
                    assert engine.remaining[s] == left[-1] > 0
                    assert engine.cur_tok[s, 1] == rec[s, -1, 3]
                    took = rec[s, :, :2][
                        np.arange(2)[None, :] < counts[s][:, None]]
                    assert engine.cur_tok[s, 0] == took[-1]
        assert {k: len(v) for k, v in engine.results.items()} == {
            "h0": 9, "h1": 3, "h2": 14, "h3": 2}
    finally:
        engine._seg = seg


def test_a_rejected_drafts_row_past_the_last_page_edge(weights):
    """Prompt 6 + 3 new on 8-row pages: the last step sits at row 7 and
    its draft row is row 8, the first of a second page that the
    request's ``prompt + max_new`` footprint allocates, that no query
    ever reads and that goes back with the rest: the output is the
    one-row output, no page leaks, the ownership stream proves clean."""
    log = PageOwnershipLog()
    eng = _engine(weights, slots=2, ownlog=log)
    rng = np.random.RandomState(11)
    prompts = {f"e{i}": rng.randint(1, 256, (1, 6)).astype(np.int32)
               for i in range(4)}
    for rid, prompt in prompts.items():
        eng.submit(rid, prompt, 3)
    served = eng.run()
    assigned = [e for e in log.events if e["kind"] == "assign"]
    assert len(assigned) == 4        # row 8 had its page, every time
    assert all(len(e["pages"]) == 2 for e in assigned)
    for rid, prompt in prompts.items():
        _assert_same_or_tied(weights, prompt, served[rid],
                             _one_row_greedy(weights, prompt, 3))
    assert eng.pool.free_pages == eng.pool.n_pages - 1
    assert not analyze_pages(log).errors


def test_what_the_family_refuses(weights):
    with pytest.raises(ValueError, match="num_nextn_predict_layers must be 1"):
        glm4_lite.Glm4LiteConfig.from_hf({**HF, "num_nextn_predict_layers": 0})
    eng = _engine(weights)
    eng.pool.sharing = True
    with pytest.raises(ValueError, match="stepped with its draft module"):
        eng.sharing
    assert models.draft_rows(CFG) == 2
    assert models.draft_rows(models.model_config("xing4-tiny")) == 1


def test_the_probe_sees_the_drafts_the_steps_verified(weights, engine):
    """``stats_probe`` gets, per segment, the counts and the drafts: a
    draft is accepted exactly where it is the token the step emits
    first."""
    engine.rebind_obs()
    got = []
    engine.stats_probe = lambda stats, rids, lengths, owed: got.append(
        (stats["mtp_counts"].copy(), stats["mtp_drafts"].copy(),
         list(rids), lengths, owed))
    try:
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, 256, (1, 9)).astype(np.int32)
        engine.submit("z", prompt, 20)
        toks = [int(t) for t in engine.run()["z"]]
    finally:
        engine.stats_probe = None
    at = {}
    for counts, drafts, rids, lengths, owed in got:
        s = rids.index("z")
        L = int(lengths[s])
        for j in range(SEG):
            if counts[s, j]:
                at[L] = (int(drafts[s, j]), int(counts[s, j]))
                L += int(counts[s, j])
    seq = list(prompt[0]) + toks
    assert min(at) == 9 and sum(n for _, n in at.values()) == 19
    for L, (d, n) in at.items():
        if L + 2 < len(seq):     # the last token owed is never a pair
            assert (d == seq[L + 1]) == (n == 2), (L, d, n)


# -- the kernel at one and two query rows -----------------------------------------


def _dense_mla(q, rows_of, lengths, new_row, rank, R_):
    """Plain per-slot softmax over each query row's own prefix."""
    S_, RH, _ = q.shape
    H = RH // R_
    out = np.zeros((S_, RH, rank), np.float32)
    for s in range(S_):
        rows = np.array(rows_of[s], np.float32)
        for r in range(R_):
            rows[lengths[s] + r] = new_row[s, r]
        for r in range(R_):
            n = lengths[s] + r + 1
            sc = q[s, r * H:(r + 1) * H] @ rows[:n].T
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[s, r * H:(r + 1) * H] = p @ rows[:n, :rank]
    return out


@pytest.mark.parametrize("q_rows", [1, 2])
@pytest.mark.parametrize("lengths", [
    (0, 5, 15), (7, 8, 16), (23, 24, 30), (30, 1, 9)])
def test_mla_paged_attention_at_one_and_two_rows(q_rows, lengths):
    """Interpret mode and the gather path against a dense reference:
    ragged last pages, ``L`` and ``L + 1`` on either side of a page edge
    (7|8, 15|16, 23|24), the walk ending a page later than ``L``'s."""
    slots, ps, ppseq, H, width, rank = 3, 8, 4, 4, 128, 32
    rng = np.random.RandomState(sum(lengths) + q_rows)
    pool = rng.randn(1 + slots * ppseq, ps, width).astype(np.float32)
    table = (1 + rng.permutation(slots * ppseq).astype(np.int32)).reshape(
        slots, ppseq)
    q = (0.3 * rng.randn(slots, q_rows * H, width)).astype(np.float32)
    new = rng.randn(slots, q_rows, width).astype(np.float32)
    L = np.asarray(lengths, np.int32)
    rows_of = [pool[table[s]].reshape(-1, width) for s in range(slots)]
    want = _dense_mla(q, rows_of, L, new, rank, q_rows)
    new_arg = new[:, 0] if q_rows == 1 else new
    for impl in IMPLS:
        got = mla_paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
            jnp.asarray(L), rank, new_row=jnp.asarray(new_arg), impl=impl,
            q_rows=q_rows, name="_mtp_mla_paged_flash")
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5, err_msg=impl)


def test_write_step_rows_is_two_single_row_writes():
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(7, 8, 16).astype(np.float32))
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    rows = jnp.asarray(rng.randn(2, 2, 16).astype(np.float32))
    lengths = jnp.asarray([7, 12], jnp.int32)      # 7|8 crosses a page
    for active in ([True, True], [True, False]):
        active = jnp.asarray(active)
        got = write_step_rows(pool, rows, table, lengths, active)
        want = write_token_rows(pool, rows[:, 0], table, lengths, active)
        want = write_token_rows(want, rows[:, 1], table, lengths + 1, active)
        np.testing.assert_array_equal(
            np.asarray(got)[1:], np.asarray(want)[1:])

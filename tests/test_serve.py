"""Online serving layer tests: loadgen determinism (in-process and
cross-process), trace round-trips, engine duplicate-rid rejection, page
occupancy accounting, preemption invariants (pages return to the pool;
resumed tokens bitwise-equal a fresh run of prompt+prefix), the
fifo-vs-slo goodput comparison on a VirtualClock, and the ``serve`` CLI
exit-code contract (0 ok / 1 breach / 2 malformed)."""

import json
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from distributed_llm_scheduler_tpu.eval import serve_bench  # noqa: E402
from distributed_llm_scheduler_tpu.obs import SLOPolicy  # noqa: E402
from distributed_llm_scheduler_tpu.obs.reqlog import (  # noqa: E402
    validate_request_log,
)
from distributed_llm_scheduler_tpu.serve import (  # noqa: E402
    Arrival,
    ServiceTimeModel,
    ServingFrontend,
    VirtualClock,
    arrivals_to_json,
    load_trace,
    poisson_arrivals,
    prompt_token_ids,
    save_trace,
    schedule_digest,
    session_arrivals,
    session_prompt_token_ids,
    validate_trace_obj,
)

GEN_KW = dict(
    prompt_lens=(8, 16), max_new_tokens=(8, 16), priorities=(0, 1),
    priority_weights=(0.3, 0.7),
)


# -- loadgen ---------------------------------------------------------------
def test_poisson_arrivals_deterministic_in_process():
    a = poisson_arrivals(40.0, 16, seed=7, **GEN_KW)
    b = poisson_arrivals(40.0, 16, seed=7, **GEN_KW)
    assert a == b
    assert schedule_digest(a) == schedule_digest(b)
    assert schedule_digest(a) != schedule_digest(
        poisson_arrivals(40.0, 16, seed=8, **GEN_KW)
    )
    assert all(x.t < y.t for x, y in zip(a, a[1:]))
    assert all(x.prompt_len in (8, 16) for x in a)
    assert all(x.priority in (0, 1) for x in a)


def test_poisson_arrivals_deterministic_cross_process():
    """Same seed -> bitwise-identical schedule in a fresh interpreter
    (legacy RandomState is stability-guaranteed across platforms)."""
    local = schedule_digest(poisson_arrivals(40.0, 16, seed=7, **GEN_KW))
    prog = (
        "from distributed_llm_scheduler_tpu.serve import "
        "poisson_arrivals, schedule_digest; "
        "print(schedule_digest(poisson_arrivals(40.0, 16, seed=7, "
        "prompt_lens=(8, 16), max_new_tokens=(8, 16), "
        "priorities=(0, 1), priority_weights=(0.3, 0.7))))"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        check=True,
    )
    assert out.stdout.strip() == local


def test_poisson_arrivals_rejects_bad_params():
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 4, seed=0)
    with pytest.raises(ValueError):
        poisson_arrivals(1.0, 0, seed=0)
    with pytest.raises(ValueError):
        poisson_arrivals(1.0, 4, seed=0, priorities=(0, 1),
                         priority_weights=(1.0,))


def test_prompt_token_ids_deterministic_and_in_vocab():
    a = prompt_token_ids("r3", 16, 512, seed=0)
    assert a.shape == (1, 16) and a.dtype == np.int32
    assert np.array_equal(a, prompt_token_ids("r3", 16, 512, seed=0))
    assert not np.array_equal(
        a, prompt_token_ids("r4", 16, 512, seed=0)
    )
    assert a.min() >= 1 and a.max() < 512


def test_trace_roundtrip_and_validation(tmp_path):
    arrivals = poisson_arrivals(40.0, 8, seed=3, **GEN_KW)
    path = str(tmp_path / "trace.json")
    save_trace(arrivals, path)
    assert load_trace(path) == arrivals
    assert validate_trace_obj(arrivals_to_json(arrivals)) == []
    # malformed variants -> named errors / ValueError from load_trace
    assert validate_trace_obj([]) != []
    assert validate_trace_obj({"schema": "nope", "arrivals": []}) != []
    obj = arrivals_to_json(arrivals)
    obj["arrivals"][1]["rid"] = obj["arrivals"][0]["rid"]  # duplicate
    assert any("duplicate" in e for e in validate_trace_obj(obj))
    obj = arrivals_to_json(arrivals)
    obj["arrivals"][0]["t"] = -1.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(obj))
    with pytest.raises(ValueError, match="malformed"):
        load_trace(str(bad))


SESSION_KW = dict(
    system_len=8, user_len=8, turns=2, max_new_tokens=(8,),
    priorities=(0, 1), priority_weights=(0.3, 0.7),
)


def test_session_arrivals_shared_prefix_schedule():
    a = session_arrivals(40.0, 8, 7, **SESSION_KW)
    assert a == session_arrivals(40.0, 8, 7, **SESSION_KW)
    assert len(a) == 16                      # n_sessions * turns
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))  # time-sorted
    # rids are derived {prefix}{i}t{k}; turn k's prompt grows by one
    # user chunk on top of the shared system prompt
    for x in a:
        sid, _, turn = x.rid.rpartition("t")
        assert sid and turn.isdigit()
        assert x.prompt_len == 8 + (int(turn) + 1) * 8
    # plain Arrival rows: the dls.arrivals/1 machinery applies unchanged
    assert validate_trace_obj(arrivals_to_json(a)) == []
    assert schedule_digest(a) != schedule_digest(
        session_arrivals(40.0, 8, 8, **SESSION_KW)
    )
    with pytest.raises(ValueError, match="rate_rps"):
        session_arrivals(0.0, 8, 7, **SESSION_KW)
    with pytest.raises(ValueError, match="turns"):
        session_arrivals(40.0, 8, 7, system_len=8, user_len=8, turns=0)
    with pytest.raises(ValueError, match="system_len"):
        session_arrivals(40.0, 8, 7, system_len=0, user_len=8)


def test_session_prompts_extend_bitwise():
    kw = dict(system_len=8, user_len=8)
    t0 = session_prompt_token_ids("s3t0", 16, 512, **kw)
    t1 = session_prompt_token_ids("s3t1", 24, 512, **kw)
    other = session_prompt_token_ids("s9t0", 16, 512, **kw)
    assert t0.shape == (1, 16) and t1.shape == (1, 24)
    # turn k's prompt is bitwise turn k-1's plus one chunk, and every
    # session opens with the identical system tokens — the properties
    # that make the workload prefix-shareable
    np.testing.assert_array_equal(t1[:, :16], t0)
    np.testing.assert_array_equal(other[:, :8], t0[:, :8])
    assert not np.array_equal(other[:, 8:], t0[:, 8:])
    with pytest.raises(ValueError, match="session rid"):
        session_prompt_token_ids("nope", 16, 512, **kw)
    with pytest.raises(ValueError, match="implies prompt_len"):
        session_prompt_token_ids("s3t1", 16, 512, **kw)


# -- engine: duplicate rids, occupancy, preemption -------------------------
@pytest.fixture()
def _engine(session_serve_engine):
    """Each test gets the session engine rebound to a fresh VirtualClock
    and a pristine pool (compiled programs kept) — the same clean-slate
    contract serve_bench leans on.  ``eng.pool`` is re-read after the
    rebind because rebind_obs swaps the pool object."""

    def fresh():
        eng = session_serve_engine
        eng.rebind_obs(clock=VirtualClock())
        return eng, eng.pool

    return fresh


def test_submit_duplicate_rid_rejected(_engine):
    eng, _pool = _engine()
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    eng.submit("a", prompt, 16)
    with pytest.raises(ValueError, match="queued"):
        eng.submit("a", prompt, 16)         # still queued
    eng.step_segment()                      # 4 of 16 tokens: mid-flight
    with pytest.raises(ValueError, match="in flight"):
        eng.submit("a", prompt, 16)         # decoding in a slot
    eng.run()
    assert "a" in eng.results
    with pytest.raises(ValueError, match="retired"):
        eng.submit("a", prompt, 4)          # already retired


def test_page_occupancy_and_summary(_engine):
    eng, pool = _engine()
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    occ0 = eng.page_occupancy()
    assert occ0["used_pages"] == 0
    assert occ0["free_pages"] == occ0["n_pages"] == pool.n_pages - 1
    eng.submit("a", prompt, 16)
    eng.submit("b", prompt, 16)
    eng.step_segment()                      # 4 of 16 tokens: mid-flight
    occ = eng.page_occupancy()
    assert set(occ["per_request"]) == {"a", "b"}
    assert occ["used_pages"] == sum(occ["per_request"].values())
    assert occ["free_pages"] + occ["used_pages"] == occ["n_pages"]
    s = eng.summary()
    assert s["in_flight"] == 2 and s["free_slots"] == eng.slots - 2
    assert s["page_occupancy"] == occ
    eng.run()
    final = eng.page_occupancy()
    assert final["used_pages"] == 0 and final["per_request"] == {}


def test_preemption_returns_pages_and_resumes_bitwise_equal(_engine):
    """The satellite invariants: preempting a request frees all of its
    pages, and re-running with prompt+generated-prefix yields tokens
    bitwise-equal to both a fresh run of that stitched prompt and the
    uninterrupted original run."""
    eng, pool = _engine()
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    free0 = pool.free_pages
    eng.submit("a", prompt, 16)
    eng.submit("b", prompt, 16)
    eng.step_segment()
    res = eng.preempt("a")
    assert res["rid"] == "a"
    assert res["tokens"].size + res["remaining"] == 16
    # a's pages are back; only b's remain held
    occ = eng.page_occupancy()
    assert "a" not in occ["per_request"]
    assert pool.free_pages == free0 - occ["per_request"]["b"]
    # engine record is terminal-preempted and still schema-valid
    snap = eng.reqlog.snapshot()
    rec = {r["rid"]: r for r in snap["requests"]}["a"]
    assert rec["state"] == "preempted"
    assert rec["t_preempt"] is not None and rec["t_retire"] is None
    assert validate_request_log(snap) == []
    # resume under a derived rid with the generated prefix as prompt
    stitched_prompt = np.concatenate(
        [np.asarray(prompt), res["tokens"][None, :]], axis=1
    )
    eng.submit("a#p1", stitched_prompt, res["remaining"])
    out = eng.run()
    stitched = np.concatenate([res["tokens"], out["a#p1"]])
    assert pool.free_pages == free0  # zero leaked pages
    # re-fresh the shared engine for the uninterrupted reference run
    # (run() returns the results dict by reference and reset() rebinds
    # rather than clears it, so `out` and `stitched` survive)
    eng2, _ = _engine()
    eng2.submit("fresh", stitched_prompt, res["remaining"])
    eng2.submit("ref", prompt, 16)
    ref = eng2.run()
    assert np.array_equal(out["a#p1"], ref["fresh"])
    assert np.array_equal(stitched, ref["ref"])


def test_preempt_requires_in_flight(_engine):
    eng, _pool = _engine()
    with pytest.raises(ValueError, match="not in flight"):
        eng.preempt("ghost")
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    eng.submit("q", prompt, 2)
    with pytest.raises(ValueError, match="not in flight"):
        eng.preempt("q")  # queued, never admitted to a slot


# -- frontend + bench: the fifo-vs-slo comparison --------------------------
@pytest.fixture(scope="module")
def serve_artifact(session_serve_engine):
    eng = session_serve_engine
    eng.rebind_obs(clock=VirtualClock())
    return serve_bench.measure_serving(seed=7, engine=eng)


def test_slo_admission_beats_fifo_under_overload(serve_artifact):
    fifo = serve_artifact["legs"]["fifo_admit_all"]
    slo = serve_artifact["legs"]["slo_preempt"]
    assert slo["goodput_tok_s"] > fifo["goodput_tok_s"]
    assert slo["preemptions"] >= 1          # preemption actually fired
    assert slo["shed"] >= 1                 # admission actually shed
    assert fifo["shed"] == 0 and fifo["preemptions"] == 0
    assert fifo["completed"] == fifo["n_requests"]  # admit-all drains
    # every row set is schema-shaped and accounted for
    for leg in (fifo, slo):
        assert leg["pages_leaked"] == 0
        states = {r["state"] for r in leg["requests"]}
        assert states <= {"retired", "shed"}
        assert leg["completed"] + leg["shed"] == leg["n_requests"]


def test_serve_run_deterministic_under_fixed_seed(serve_artifact):
    assert serve_artifact["deterministic"] is True
    assert serve_bench.gate_failures(serve_artifact) == []
    assert serve_bench.validate_serve_artifact(serve_artifact) == []


# -- prefix sharing: the r17 gates ------------------------------------------
def test_prefix_sharing_beats_disabled_with_exact_books(serve_artifact):
    """The tentpole's headline: at equal offered load the sharing leg
    strictly wins BOTH goodput and TTFT p99 over the sharing-disabled
    leg, pages actually alias, the refcount books balance exactly, and
    the ownership stream proves clean."""
    px = serve_artifact["prefix"]
    assert serve_bench.prefix_gate_failures(px) == []
    sh, un = px["legs"]["shared"], px["legs"]["unshared"]
    assert sh["goodput_tok_s"] > un["goodput_tok_s"]
    assert sh["ttft_p99_ms"] < un["ttft_p99_ms"]
    assert px["goodput_gain"] > 1.0
    assert px["deterministic"] is True
    acct = px["accounting"]
    assert acct["shared"]["shared_page_hits"] >= 1
    assert acct["unshared"]["shared_page_hits"] == 0
    for name in ("shared", "unshared"):
        a = acct[name]
        assert a["logical_pages_peak"] >= a["physical_pages_peak"]
        assert a["physical_pages_end"] == a["logical_pages_end"] == 0
        assert px["page_pass"][name] == []
        assert px["legs"][name]["pages_leaked"] == 0
    # the flattened regression metrics mirror the nested blocks exactly
    assert (serve_artifact["serve.prefix.goodput_tok_s"]
            == sh["goodput_tok_s"])
    assert (serve_artifact["serve.prefix.goodput_gain"]
            == px["goodput_gain"])
    assert serve_artifact["serve.prefix.pages_leaked"] == 0


def test_sharing_toggle_changes_no_tokens(_engine):
    """Sharing is a memory-management change ONLY: the same staggered
    two-request workload decodes to bitwise-identical tokens with the
    intern table on and off."""
    eng, _pool = _engine()
    prompt = jnp.asarray([list(range(1, 17))], jnp.int32)

    def leg():
        eng.submit("a", prompt, 8)
        eng.step_segment()   # admit a first so b CAN alias when sharing
        eng.submit("b", prompt, 8)
        out = eng.run()
        return {k: np.asarray(v) for k, v in out.items()}

    off = leg()
    assert eng.summary().get("prefix_sharing") is None
    try:
        eng.pool.sharing = True   # rebind inherits the live pool's mode
        eng.rebind_obs(clock=VirtualClock())
        assert eng.sharing
        on = leg()
        assert eng.metrics.counter("decode.prefix_shared_pages").value >= 1
    finally:
        eng.pool.sharing = False
        eng.rebind_obs(clock=VirtualClock())
    assert off.keys() == on.keys()
    for k in off:
        np.testing.assert_array_equal(off[k], on[k])


def test_forced_alias_triggers_cow_and_keeps_tokens_bitwise(_engine):
    """The COW seam: admission structurally never writes a shared page,
    so FORCE an alias onto a page in the coming write range — the
    engine must alloc-copy-release (recording ``cow`` then ``write``),
    keep the aliased content intact, and still emit the exact token
    stream of an unforced run."""
    from distributed_llm_scheduler_tpu.analysis import analyze_pages
    from distributed_llm_scheduler_tpu.models.kv_pages import (
        PageOwnershipLog,
    )

    eng, _pool = _engine()
    prompt = jnp.asarray([[5, 4, 3, 2, 1, 2, 3, 4]], jnp.int32)
    eng.submit("ref", prompt, 16)
    ref = eng.run()["ref"]

    log = PageOwnershipLog()
    try:
        eng.pool.sharing = True
        eng.rebind_obs(clock=VirtualClock(), ownlog=log)
        eng.submit("vic", prompt, 16)
        eng.step_segment()            # 4 of 16 decoded: length 12
        s = next(i for i in range(eng.slots)
                 if eng._slot_req[i] == "vic")
        li = int(eng.lengths[s]) // eng.page_size  # the page being written
        src = int(eng.page_table[s, li])
        eng.pool.share([src])         # the forced alias
        out = eng.run()["vic"]        # next segment must COW-split first
        np.testing.assert_array_equal(out, np.asarray(ref))
        kinds = [e["kind"] for e in log.events]
        assert "cow" in kinds
        assert eng.metrics.counter("decode.cow_splits").value >= 1
        # the engine moved off src; the forced reference still pins it
        assert eng.pool.refcount(src) == 1
        eng.pool.release_ref([src])
        occ = eng.page_occupancy()
        assert occ["free_pages"] == occ["n_pages"]
        # the full forced stream replays clean: alloc-before-release
        # ordering, ownership transfer, and the final free all prove
        assert [d.code for d in analyze_pages(log).diagnostics] == []
    finally:
        eng.pool.sharing = False
        eng.rebind_obs(clock=VirtualClock())


def test_chunked_prefill_bitwise_across_chunk_sizes(_engine):
    """Chunked prefill is a SCHEDULING change only: the same mixed
    long/short workload decodes to bitwise-identical token streams with
    chunking off, chunk_tokens=8 (the 24-token long splits into three
    chunks), and chunk_tokens=16 (two ragged chunks) — with zero page
    leaks and the chunk counters accounting for every prefill token."""
    from distributed_llm_scheduler_tpu.obs.metrics import MetricsRegistry

    eng, pool = _engine()

    def workload():
        rng = np.random.RandomState(0)
        eng.submit("long", jnp.asarray(
            rng.randint(1, 50, size=(1, 24)), jnp.int32), 4)
        for i in range(5):
            plen = int(rng.choice([3, 5, 8]))
            eng.submit(f"s{i}", jnp.asarray(
                rng.randint(1, 50, size=(1, plen)), jnp.int32), 3)
        out = eng.run()
        leak = (eng.pool.n_pages - 1) - eng.pool.free_pages
        return {k: np.asarray(v) for k, v in out.items()}, leak

    whole, leak_w = workload()
    assert leak_w == 0
    try:
        m = MetricsRegistry()
        eng.rebind_obs(clock=VirtualClock(), metrics=m)
        eng.chunk_tokens = 8
        chunk8, leak_8 = workload()
        assert leak_8 == 0
        assert m.counter("decode.chunk_admitted").value >= 1
        assert m.counter("decode.chunk_waves").value >= 2
        assert m.counter("decode.chunk_prefill_tokens").value == 24

        eng.reset()
        eng.chunk_tokens = 16
        chunk16, leak_16 = workload()
        assert leak_16 == 0
    finally:
        eng.chunk_tokens = None
        eng.rebind_obs(clock=VirtualClock())

    assert whole.keys() == chunk8.keys() == chunk16.keys()
    for k in whole:
        np.testing.assert_array_equal(whole[k], chunk8[k])
        np.testing.assert_array_equal(whole[k], chunk16[k])


class _TickingClock:
    """Scripted wall clock: every read moves it on by ``dt``, so spans
    get a length and a strict order; ``sleep`` is the front-end's idle
    sleep and moves it by what was asked."""

    def __init__(self, dt: float = 1e-4):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += seconds


def test_decode_track_spans_tile_the_tick_without_overlap(_engine):
    """A served run in chunked mode on a scripted wall clock: the
    ``decode``-track spans ``admit`` / ``prefill_chunk`` / ``segment`` /
    ``fold`` / ``idle_wait`` never overlap, a ``fold`` starts where its
    segment's readback ended, and ``idle_wait`` is drawn exactly on the
    ticks that find the engine empty."""
    from distributed_llm_scheduler_tpu.obs.trace import Tracer

    eng, _pool = _engine()
    clk = _TickingClock()
    tr = Tracer(clock=clk)
    eng.rebind_obs(clock=clk, tracer=tr)
    eng.chunk_tokens = 8
    busy_ticks, idle_ticks = [], []
    try:
        fe = ServingFrontend(
            eng,
            [Arrival("a", 0.0, 24, 6), Arrival("b", 0.001, 8, 3),
             Arrival("c", 0.6, 20, 9), Arrival("d", 1.3, 24, 2)],
            SLOPolicy(ttft_s=60.0),
            sleep=lambda s: (idle_ticks.append(fe.ticks), clk.sleep(s)),
        )
        step = eng.step_segment
        eng.step_segment = lambda: (busy_ticks.append(fe.ticks), step())[1]
        rep = fe.run()
    finally:
        del eng.step_segment
        eng.chunk_tokens = None
        eng.rebind_obs(clock=VirtualClock())
    assert rep["completed"] == 4 and rep["pages_leaked"] == 0

    names = ("admit", "prefill_chunk", "segment", "fold", "idle_wait")
    spans = sorted(
        (e for e in tr.events if e["type"] == "span"
         and e["track"] == "decode" and e["name"] in names),
        key=lambda e: (e["t0"], e["t1"]))
    assert {e["name"] for e in spans} == set(names)
    for a, b in zip(spans, spans[1:]):
        assert a["t1"] <= b["t0"], (a, b)
    by = {n: [e for e in spans if e["name"] == n] for n in names}
    # one fold per segment, from the segment's own readback stamp
    assert ([e["t0"] for e in by["fold"]]
            == [e["t1"] for e in by["segment"]])
    # every request's first token comes from its prefill, not a segment
    assert sum(e["args"]["delivered"] for e in by["fold"]) == 20 - 4
    assert sum(e["args"]["retired"] for e in by["fold"]) == 4
    # every tick draws the front-end's admit; a tick that drives the
    # engine draws the engine's too, and never sleeps
    assert idle_ticks and not set(idle_ticks) & set(busy_ticks)
    assert len(idle_ticks) + len(busy_ticks) == fe.ticks
    assert len(by["idle_wait"]) == len(idle_ticks)
    assert len(by["admit"]) == fe.ticks + len(busy_ticks)
    assert sum(e["args"]["admitted"] for e in by["admit"]
               if "backlog" in e["args"]) == 4
    assert all(e["t1"] - e["t0"] >= 0.0005 for e in by["idle_wait"])


def test_first_token_is_stamped_after_the_last_chunks_readback(_engine):
    """Chunked mode: the last chunk's result is still on the device when
    ``_fold_chunked`` gets it, and reading it waits for the chunk.  The
    first delivery may not be stamped before that wait is over — a
    stand-in result whose ``int()`` moves the scripted clock shows it."""
    eng, _pool = _engine()
    clk = _TickingClock()
    eng.rebind_obs(clock=clk)
    eng.chunk_tokens = 8
    read_at = []

    class Scalar:
        def __init__(self, real):
            self.real = real

        def __int__(self):
            clk.sleep(0.075)          # the chunk program finishes
            read_at.append(clk.t)
            return int(self.real)

    class Pending:
        def __init__(self, real):
            self.real = real

        def __getitem__(self, i):
            return Scalar(self.real[i])

    chunk = eng._chunk_prefill
    eng._chunk_prefill = lambda *a: Pending(chunk(*a))
    try:
        rng = np.random.RandomState(3)
        eng.submit("long", jnp.asarray(
            rng.randint(1, 50, size=(1, 24)), jnp.int32), 5)
        out = eng.run()
        rec = eng.reqlog.get("long")
        ttft = eng.metrics.histogram("decode.ttft_s").max
    finally:
        del eng._chunk_prefill
        eng.chunk_tokens = None
        eng.rebind_obs(clock=VirtualClock())
    assert out["long"].size == 5 and len(read_at) == 1
    assert rec.t_first_token >= read_at[0]
    assert rec.deliveries[0] == (rec.t_first_token, 1)
    assert ttft == pytest.approx(rec.t_first_token - rec.t_submit)
    assert ttft > 0.075


def test_frontend_rejects_bad_config(_engine):
    eng, _pool = _engine()
    arrivals = [Arrival("a", 0.0, 8, 4)]
    with pytest.raises(ValueError, match="admission"):
        ServingFrontend(eng, arrivals, admission="lifo")
    with pytest.raises(ValueError, match="ttft"):
        ServingFrontend(eng, arrivals, None, admission="slo")
    with pytest.raises(ValueError, match="duplicate"):
        ServingFrontend(
            eng, arrivals + [Arrival("a", 1.0, 8, 4)],
            SLOPolicy(ttft_s=1.0),
        )
    fe = ServingFrontend(eng, arrivals, SLOPolicy(ttft_s=1.0))
    with pytest.raises(ValueError, match="duplicate"):
        fe.submit(Arrival("a", 2.0, 8, 4))


def test_frontend_fifo_without_policy(_engine):
    """fifo admit-all with no SLO policy: everything completes, goodput
    equals throughput, nothing breaches."""
    eng, pool = _engine()
    arrivals = poisson_arrivals(50.0, 6, seed=11, **GEN_KW)
    fe = ServingFrontend(
        eng, arrivals, None, admission="fifo",
        time_model=ServiceTimeModel(),
    )
    rep = fe.run()
    assert rep["completed"] == 6 and rep["breached"] is False
    assert rep["tokens_good"] == rep["tokens_total"] > 0
    assert rep["pages_leaked"] == 0
    for a in arrivals:
        assert fe.results[a.rid].size == a.max_new_tokens
    # a re-freshed engine reproduces the served tokens exactly (capture
    # first: fe.results holds its own dict, unaffected by the reset)
    first = arrivals[0]
    served = fe.results[first.rid]
    want = prompt_token_ids(first.rid, first.prompt_len,
                            eng.config.vocab_size)
    eng2, _ = _engine()
    eng2.submit("chk", jnp.asarray(want), first.max_new_tokens)
    assert np.array_equal(eng2.run()["chk"], served)


# -- CLI -------------------------------------------------------------------
def test_serve_cli_exit_codes(tmp_path):
    from distributed_llm_scheduler_tpu.__main__ import main

    trace = str(tmp_path / "trace.json")
    out = str(tmp_path / "report.json")
    # 0: generous targets, trace saved for replay
    assert main([
        "serve", "--model", "gpt2-tiny", "--requests", "8", "--seed", "7",
        "--save-trace", trace, "--out", out,
    ]) == 0
    rep = json.load(open(out))
    assert rep["breached"] is False and rep["pages_leaked"] == 0
    assert validate_trace_obj(json.load(open(trace))) == []
    # 1: replaying the saved trace with an impossible TTFT under
    # admit-all breaches; the flight dump validates
    fdir = str(tmp_path / "flight")
    assert main([
        "serve", "--model", "gpt2-tiny", "--trace", trace,
        "--admission", "fifo", "--ttft", "0.000001", "--window", "0.2",
        "--flight-dir", fdir,
    ]) == 1
    dump = json.load(open(tmp_path / "flight" / "flight_requests.json"))
    assert dump["request_log"]["requests"]
    # 2: malformed trace / bad policy / non-gpt2 model
    bad = tmp_path / "bad.json"
    bad.write_text("{\"schema\": \"nope\"}")
    assert main([
        "serve", "--model", "gpt2-tiny", "--trace", str(bad),
    ]) == 2
    assert main([
        "serve", "--model", "gpt2-tiny", "--window", "0",
    ]) == 2
    assert main(["serve", "--model", "llama-tiny"]) == 2
    # 2: arrival exceeding the engine's per-request KV capacity
    big = tmp_path / "big.json"
    big.write_text(json.dumps({
        "schema": "dls.arrivals/1",
        "arrivals": [{"rid": "x", "t": 0.0, "prompt_len": 100,
                      "max_new_tokens": 8, "priority": 0}],
    }))
    assert main([
        "serve", "--model", "gpt2-tiny", "--trace", str(big),
    ]) == 2

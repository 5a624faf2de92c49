"""The Laguna cell's files, its parameter count, its three cost
functions on hand-made contexts, its reference's control, a whole run at
a tiny size on the CPU, and its two programs compiled for the v5e at the
file's own engine — all found by name, with no edit to a benchmark file
that was there.

The command itself refuses anything but a TPU; the platform override
lives here, in the test."""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import costs_laguna, harness, run, xplane  # noqa: E402
from benchmark.reference import laguna as R  # noqa: E402
from benchmark.runners import laguna_serve, xing4_serve  # noqa: E402

CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "laguna-s-2.1-ep4.json").read_text())
GEO = CFG["engine"]
TYPES = ["full_attention"] + ["sliding_attention"] * 3
TINY = {
    "source": "test", "runner": "laguna_serve", "reference": "laguna",
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
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256, "vocab_size": 2048,
    "dtype": "float32", "init": {"std": 0.3},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 10, "n_pages": 25,
          "ring_pages": 1, "seg_steps": 4, "chunk_tokens": 16,
          "admission": "slo", "scheduler": "heft", "attention_impl": "xla"}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("laguna-mixed")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "laguna-s-2.1-ep4", "mixed-len-fixed", 1)
    assert harness.load_runner(cell) is laguna_serve
    assert harness.load_reference(cell.config) is R
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_mean", "setup_s"}
    new = {"gqa_paged_attn_roofline", "swa_kv_attn_roofline",
           "gqa_chunk_flash_roofline", "attn_full_row_share"}
    names = {m["name"] for m in cell.per_layer}
    assert new | {"moe_expert_roofline", "moe_experts_touched_share",
                  "moe_pick_imbalance", "kv_live_block_share",
                  "seg_behind_prefill_share", "seg_period_ms_p99"} <= names
    # their costs read other families' widths: not this cell's
    assert not {"paged_attn_roofline", "mla_paged_attn_roofline",
                "swa_latent_attn_roofline"} & names
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    geo, t = cell.config["engine"], cell.traffic
    assert geo["pages_per_seq"] * geo["page_size"] == t["max_total"] == (
        t["prompt_len"]["hi"] + t["output_len"]["hi"])
    # the padded chunk grid of the longest prompt fits a slot
    assert t["max_total"] % geo["chunk_tokens"] == 0
    # oversubscribed on purpose: the pool cannot hold every slot at full length
    assert geo["n_pages"] - 1 < geo["slots"] * geo["pages_per_seq"]
    assert geo["ring_pages"] * geo["page_size"] >= cell.config["sliding_window"]
    assert t["output_len"]["hi"] <= R.ROW_WINDOW
    # every block of four arrivals carries a prompt of each quartile
    a = laguna_serve.schedule(t, float(cell.params["rate_rps"]), 51.0)
    assert a == xing4_serve.schedule(t, float(cell.params["rate_rps"]), 51.0)
    rank = {p: i for i, p in enumerate(sorted(r.prompt_len for r in a))}
    blocks = -(-len(a) // 4)
    # (the top stratum is short of three blocks: any sixteen in a row hold
    # all four, whatever the order inside a block)
    for i in range(len(a) - 15):
        assert {rank[r.prompt_len] // blocks for r in a[i:i + 16]} == {
            0, 1, 2, 3}
    assert len(a) >= 40
    assert min(rank) < 300 and max(rank) > 30000


def test_the_configuration_is_the_catalogs_but_for_its_three_cuts():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == "laguna-s-2.1-ep4")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 64, 25088)
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert CFG["vocab_size"] * 4 == 100352 and CFG["n_router_outputs"] == 256
    assert CFG["held_experts"] == list(range(64))
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "rms_norm_eps": 1e-06,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "sliding_window": 512,
        "decoder_sparse_step": 1, "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0, "mlp_only_layers": [0],
        "gating": "per-head", "norm_topk_prob": True,
        "tie_word_embeddings": False}
    assert {k: CFG[k] for k in published} == published
    full = CFG["rope_parameters"]["full_attention"]
    assert (full["factor"], full["attention_factor"],
            full["partial_rotary_factor"]) == (128, 1.4852030263919618, 0.5)
    assert len(CFG["layer_types"]) == 48       # the published lists, whole
    assert CFG["layer_types"][:5] == TYPES + TYPES[:1]
    assert CFG["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert {"router_activation", "shared_expert", "gating", "attention",
            "rope", "sliding_window", "cache_rows", "engine", "init",
            "reduced"} <= set(CFG["assumed"])
    assert "4 chips share each layer" in CFG["deployment"]


def test_the_configuration_holds_3002_million_parameters():
    """The issue's count, the reference's and the program's
    ``param_shapes`` agree: 3,002.0 M parameters, 6.00 GB in bf16."""
    from distributed_llm_scheduler_tpu.models import laguna

    n = R.param_count(CFG)
    assert abs(n - 3002.0e6) < 0.05e6
    mcfg = laguna_serve.model_config(CFG)
    shapes = laguna.param_shapes(mcfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == n
    assert 5.99e9 < 2 * n < 6.02e9
    per = {i: sum(int(np.prod(s)) for s, _ in
                  laguna.layer_param_shapes(mcfg, i).values())
           for i in range(5)}
    # layer 0 157.44 M, a sliding expert layer 677.35 M, the full one 658.40 M
    assert [round(per[i] / 1e6, 2) for i in range(5)] == [
        157.44, 677.35, 677.35, 677.35, 658.40]
    assert mcfg.ring_rows == 640 and mcfg.n_held_experts == 64


# -- the three cost functions on hand-made contexts ---------------------------


def _ctx(records, segments=((10.0, 10.1),), spans=()):
    return {"config": CFG, "records": records, "slice_segments": segments,
            "slice": (9.0, 11.0), "spans": list(spans)}


def _rec(prompt, new, first, deliveries, retire=None):
    return {"prompt_len": prompt, "max_new_tokens": new, "t_first": first,
            "t_retire": retire, "deliveries": deliveries}


def test_decode_costs_count_the_rows_a_call_must_read():
    """Two slots decode through one segment of 8 steps: one at 1,000
    rows (all of them in a full layer, 512 in a window layer), one at
    100 (all of them in both); a retired request and one still
    prefilling read nothing."""
    recs = [_rec(990, 100, 5.0, [(6.0, 9)]),       # holds 1,000 at t0
            _rec(96, 50, 9.5, [(9.6, 3)]),         # holds 100
            _rec(500, 20, 1.0, [(2.0, 19)], retire=3.0),
            _rec(7000, 30, None, [])]
    ctx = _ctx(recs)
    full = sum(1000 + s for s in range(8)) + sum(100 + s for s in range(8))
    assert costs_laguna.gqa_paged_attention_bytes(ctx) == 4096 * full / 8
    ring = 8 * 512 + sum(101 + s for s in range(8))
    assert costs_laguna.swa_kv_attention_bytes(ctx) == 4096 * ring / 8
    assert costs_laguna.gqa_paged_attention_bytes(_ctx(recs, ())) == 0.0


def test_chunk_cost_counts_the_pairs_the_mask_admits():
    span = lambda name, t0, **args: {  # noqa: E731
        "type": "span", "name": name, "t0": t0, "t1": t0 + 0.01, "args": args}
    # one chunk of 512 real rows at 8,192, one last chunk of 100 at 1,024,
    # one whole prompt of 300, and a chunk outside the slice
    spans = [span("prefill_chunk", 9.5, base=8192, tokens=512, rid="a"),
             span("prefill_chunk", 10.5, base=1024, tokens=100, rid="b"),
             span("prefill", 10.7, requests=1, prompt_len=300),
             span("prefill_chunk", 12.0, base=0, tokens=512, rid="c"),
             span("prefill", 10.71, tokens=300),     # a request's waterfall
             span("segment", 10.0, steps=8)]
    full = (512 * 8192 + 512 * 513 // 2) + (100 * 1024 + 100 * 101 // 2) + (
        300 * 301 // 2)
    ring = 512 * 512 + 100 * 512 + 300 * 301 // 2
    want = 4.0 * 128 * (2 * 48 * full + 3 * 72 * ring) / 15
    assert costs_laguna.gqa_chunk_flash_flops(_ctx([], spans=spans)) == want
    assert costs_laguna.gqa_chunk_flash_flops(_ctx([])) == 0.0
    assert costs_laguna._pairs(0, 600, 512) == 512 * 513 // 2 + 88 * 512


# -- the reference and its int8 control ------------------------------------------

P, T, PAD = 40, 72, 80


@pytest.fixture(scope="module")
def greedy():
    """Weights and one greedy continuation by the reference itself."""
    import jax.numpy as jnp

    params = R.make_params(TINY, 2**31 + 77)
    seq = list(np.random.RandomState(3).randint(1, 2048, size=P))
    for _ in range(T - P):
        ids = np.zeros((1, PAD), np.int32)
        ids[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(R.logits(
            params, TINY, ids, rows=slice(len(seq) - 1, len(seq)))[0, 0])))
    return params, np.asarray(seq, np.int32)


def test_sound_tokens_have_no_gap_and_the_int8_control_fails_the_limits(
        greedy):
    params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2   # context-sensitive
    assert R.served_gaps(params, TINY, seq, P, T - P, PAD).max() == 0.0
    control = R.served_gaps(params, TINY, seq, P, T - P, PAD, control=True)
    assert control.max() > 10 * 1e-3 and control.mean() > 10 * 1e-4
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0


def test_the_references_blocks_do_not_change_its_numbers(monkeypatch):
    """Query blocks and a sliding layer's band of keys are how 33k tokens
    fit; they are not part of the mathematics."""
    params = R.make_params(TINY, 9)
    ids = np.random.RandomState(2).randint(1, 2048, size=(1, 64))
    whole = np.asarray(R.logits(params, TINY, ids))
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    monkeypatch.setattr(R, "SCORE_ELEMS", 6 * 64 * 8)
    R._layer.clear_cache()
    blocked = np.asarray(R.logits(params, TINY, ids))
    R._layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, rtol=1e-4, atol=1e-4)
    assert laguna_serve.reference_rows(300, 33792) == 2048
    assert laguna_serve.reference_rows(9000, 33792) == 16384
    assert laguna_serve.reference_rows(33000, 33792) == 33792


# -- a whole run, end to end, at a tiny size ---------------------------------


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture()
def tiny_root(tmp_path, monkeypatch):
    import shutil

    import jax

    b = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    _write(b / "configs" / "tiny-laguna.json", dict(TINY, engine=ENGINE))
    # prompts under and over a chunk (16): whole-prompt programs, a length
    # each, beside chunks; the pool holds 24 pages for 4 slots of up to 10
    _write(b / "traffic" / "tiny-mixed.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 80,
        "prompt_len": {"dist": "log_uniform", "lo": 10, "hi": 60},
        "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}})
    _write(b / "workloads" / "tiny-laguna.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    cells = ["tiny-laguna"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-laguna",
                     "file": "benchmark/configs/tiny-laguna.json"}],
        "workloads": [{"name": "tiny-laguna", "config": "tiny-laguna",
                       "traffic": "tiny-mixed", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("moe_experts_touched_share", "ratio"),
                         ("moe_pick_imbalance", "ratio"),
                         ("attn_full_row_share", "ratio"),
                         ("gqa_paged_attn_roofline", "%"),
                         ("swa_kv_attn_roofline", "%"),
                         ("gqa_chunk_flash_roofline", "%"),
                         ("moe_expert_roofline", "%"))],
    })
    monkeypatch.setattr(harness, "HERE", b)
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(laguna_serve, "MIN_REFERENCE_ROWS", 32)
    monkeypatch.setattr(xplane, "DEVICE_PLANE", r"^/host:CPU$")
    monkeypatch.setattr(xplane, "OPS_LINE", r"^tf_XLA")
    return tmp_path


def test_cell_end_to_end(tiny_root, capsys):
    assert run.main(["--workload", "tiny-laguna", "--seed", "3000000017",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    assert "whole-prompt lengths" in out
    assert "compared compilations_in_window = 0" in out


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-laguna", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    m = line["metrics"]
    # contexts of 10-80 rows against a window of 6: well over 0.4
    assert 0.4 < m["attn_full_row_share"]["value"] < 1.0
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"gqa_paged_attn_roofline", "swa_kv_attn_roofline",
                "gqa_chunk_flash_roofline", "moe_expert_roofline"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_a_ring_that_forgets_the_window_is_not_correct(tiny_root, capsys,
                                                      monkeypatch):
    """The timed path broken: the window layers' decode reads every row
    the ring holds, not the last ``sliding_window``; the served tokens
    leave the reference's."""
    from distributed_llm_scheduler_tpu.models import laguna

    real = laguna.kv_window_attention
    monkeypatch.setattr(
        laguna, "kv_window_attention",
        lambda *a, window, **kw: real(*a, window=8, **kw))
    assert run.main(["--workload", "tiny-laguna", "--seed", "11",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "NOT CORRECT" in out


# -- the two programs compiled for the v5e, at the file's engine -----------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_segment_and_chunk_programs_fit_the_chip(one_chip, monkeypatch):
    """The two programs the window runs, whole, at the cell's geometry:
    they compile for the v5e with every kernel inside (groups of 6 in
    ``_paged_flash``, of 9 in ``_swa_kv_attn``, both in
    ``_gqa_chunk_flash``), read each pool where it lies, and weights +
    pools + temporaries leave room in 15.75 GB."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import laguna
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mcfg = laguna_serve.model_config(CFG)
    S, ps, ppseq, n_pages, rp = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages", "ring_pages"))
    ddag = build_paged_decode_dag(
        mcfg, slots=S, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl="auto")
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler(GEO["scheduler"]).schedule(ddag.graph, cluster)
    specs = {k: sds(v.shape, v.dtype) for k, v in ddag.param_specs.items()}
    pools = {k: v for k, v in specs.items() if k.startswith("cache_")}
    weights = {k: v for k, v in specs.items()
               if k not in pools and k != "page_table"}

    def gb(d):
        return sum(np.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in d.values()) / 1e9

    assert 6.0 < gb(weights) < 6.02 and 5.5 < gb(pools) < 5.7
    assert sorted(pools) == sorted(
        [f"cache_{k}_{i}" for i in (0, 4) for k in "kv"]
        + [f"cache_w{k}_{i}" for i in (1, 2, 3) for k in "kv"])
    assert pools["cache_wk_2"].shape == (1 + S * rp, ps, 1024)

    seg = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 1), i32), sds((S,), i32)).compile()
    text = seg.as_text()
    for name in ("_paged_flash", "_swa_kv_attn", "_moe_experts"):
        assert name in text, name
    for shape in (f"{n_pages},{ps},1024", f"{1 + S * rp},{ps},1024"):
        assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text)
        assert not re.search(rf"copy-start\S*\(bf16\[{shape}\]", text)
    assert seg.memory_analysis().temp_size_in_bytes < 0.5e9

    spec, cap = laguna.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal, ring):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, page_size=ps), pools, pages,
            1, cap, ring)
        last, cache = laguna.forward_cached_row(
            w, ids, cache, pos0, mcfg, creal - 1, impl="auto")
        return (jnp.argmax(last, -1).astype(i32),
                spec.scatter(pools, cache, pages, ps, ring))

    done = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, sds((1, GEO["chunk_tokens"]), i32), pools,
        sds((ppseq,), i32), sds((), i32), sds((), i32),
        sds((rp,), i32)).compile()
    assert "_gqa_chunk_flash" in done.as_text()
    temp = done.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9
    # weights + pools + the chunk program's temporaries: under 14 of 15.75
    assert gb(weights) + gb(pools) + temp / 1e9 < 14.0

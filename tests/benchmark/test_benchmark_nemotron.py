"""The Nemotron-H cell's files, its parameter count, its three cost
functions on hand-made contexts, its reference's controls, and whole runs
at a tiny size on the CPU — sound, and with the state lost between two
chunks — all found by name, with no edit to a benchmark file that was
there.  (Its programs compiled for the v5e:
``test_benchmark_nemotron_aot.py``.)

The command itself refuses anything but a TPU; the platform override
lives here, in the test."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import costs_nemotron, harness, run, xplane  # noqa: E402
from benchmark.reference import nemotron_h as R  # noqa: E402
from benchmark.runners import laguna_serve, nemotron_serve  # noqa: E402
from benchmark.runners import xing4_serve  # noqa: E402

CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "nemotron-3-nano-ep2.json").read_text())
GEO = CFG["engine"]
TINY = {
    "source": "test", "runner": "nemotron_serve", "reference": "nemotron_h",
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
    "max_position_embeddings": 256, "vocab_size": 2048,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "dtype": "float32", "init": {"std": 0.3, "conv_gain": 2.0},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 10, "n_pages": 41,
          "seg_steps": 4, "chunk_tokens": 16, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}
TINY = dict(TINY, engine=ENGINE)


def _names(metrics):
    return {m["name"] for m in metrics}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("nemotron-chat")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "nemotron-3-nano-ep2", "chat-rate-fixed", 1)
    assert harness.load_runner(cell) is nemotron_serve
    assert harness.load_reference(cell.config) is R
    assert _names(cell.end_to_end) == {"tpot_ms_mean", "setup_s"}
    names = _names(cell.per_layer)
    new = {"ssm_step_roofline", "ssd_chunk_roofline", "relu2_expert_roofline",
           "ssm_slots_stepped"}
    assert new | {"gqa_paged_attn_roofline", "moe_experts_touched_share",
                  "moe_pick_imbalance", "kv_live_block_share",
                  "decode_step_dev_ms", "prefill_dev_us_tok",
                  "seg_behind_prefill_share", "seg_period_ms_p99"} <= names
    # costs that count three matrices an expert, or other families' rows
    assert not {"moe_expert_roofline", "gqa_chunk_flash_roofline",
                "paged_attn_roofline", "mla_paged_attn_roofline"} & names
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert all(by_name[n]["workloads"] == ["nemotron-chat"] for n in new)
    assert "nemotron-chat" not in by_name["moe_expert_roofline"]["workloads"]
    assert by_name["ssm_step_roofline"]["layer"] == by_name[
        "ssm_slots_stepped"]["layer"] == "state layers"
    assert by_name["relu2_expert_roofline"]["layer"] == by_name[
        "moe_expert_roofline"]["layer"]
    geo, t = cell.config["engine"], cell.traffic
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "log_uniform", "lo": 128, "hi": 2048},
        {"dist": "log_uniform", "lo": 128, "hi": 1024})
    assert t["max_total"] == 3072 == (
        t["prompt_len"]["hi"] + t["output_len"]["hi"])
    cap = geo["pages_per_seq"] * geo["page_size"]
    # a slot holds the longest request, and the longest prompt's chunks
    assert cap >= t["max_total"] and t["prompt_len"]["hi"] % geo[
        "chunk_tokens"] == 0
    # every slot at full length: the state, not the K/V, bounds the batch
    assert geo["n_pages"] - 1 == geo["slots"] * geo["pages_per_seq"]
    assert geo["chunk_tokens"] % cell.config["chunk_size"] == 0
    assert geo["chunk_tokens"] % geo["page_size"] == 0
    assert t["output_len"]["hi"] <= R.ROW_WINDOW
    rate = float(cell.params["rate_rps"])
    a = nemotron_serve.schedule(t, rate, 51.0)
    assert a == xing4_serve.schedule(t, rate, 51.0)    # pinned, one for all
    assert len(a) == round(rate * 51) >= 100
    assert min(r.prompt_len for r in a) < 140 < 1900 < max(
        r.prompt_len for r in a)
    assert int(cell.params["check_requests"]) == 8


def test_the_configuration_is_the_catalogs_row_but_for_its_four_cuts():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"]
                 if c["name"] == "nemotron-3-nano-ep2")
    assert entry["source"] == CFG["source"]
    reduced = {"num_hidden_layers": 13, "hybrid_override_pattern":
               "MEMEM*EMEMEM*", "n_routed_experts": 64, "vocab_size": 65536}
    assert set(entry["reduced"]) == set(reduced)
    assert {k: CFG[k] for k in reduced} == reduced
    published = {
        "hidden_size": 2688, "intermediate_size": 1856, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
        "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "use_conv_bias": True, "mamba_proj_bias": False, "use_bias": False,
        "layer_norm_epsilon": 1e-05, "time_step_min": 0.001,
        "time_step_max": 0.1, "time_step_floor": 0.0001,
        "max_position_embeddings": 262144, "tie_word_embeddings": False,
        "rope_theta": 10000, "partial_rotary_factor": 1}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert CFG["published"]["hybrid_override_pattern"].startswith(
        CFG["hybrid_override_pattern"])
    assert CFG["n_router_outputs"] == 128 == 2 * CFG["n_routed_experts"]
    assert CFG["held_experts"] == list(range(64))
    assert CFG["vocab_size"] * 2 == 131072
    assert {"positions", "router", "d_inner", "gated_norm", "time_step_limit",
            "dtype", "reduced", "engine", "init"} <= set(CFG["assumed"])
    assert "2 chips share each layer" in CFG["deployment"]
    # the catalog's row, where the catalog is at hand: every number of it
    # under the same key, but for the four cuts
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
        assert row["source_url"] == CFG["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in reduced} == {
            k: CFG[k] for k in row["config"] if k not in reduced}


def test_the_configuration_holds_3926_million_parameters():
    """The issue's count, the reference's and the program's
    ``param_shapes`` agree: 3,926 M parameters, 7.85 GB in bf16; a mixer
    38.74 M, an attention layer 23.40 M, an expert layer of 64 held
    658.9 M, embedding + head 352.3 M."""
    from distributed_llm_scheduler_tpu.models import nemotron_h

    n = R.param_count(CFG)
    assert abs(n - 3926e6) < 0.5e6
    mcfg = nemotron_serve.model_config(CFG)
    shapes = nemotron_h.param_shapes(mcfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == n
    assert 7.84e9 < 2 * n < 7.87e9
    per = {c: round(sum(
        int(np.prod(s)) for s, _ in nemotron_h.layer_param_shapes(
            mcfg, mcfg.pattern.index(c)).values()) / 1e6, 2) for c in "M*E"}
    assert per == {"M": 38.74, "*": 23.40, "E": 658.89}
    assert mcfg.pattern == "MEMEM*EMEMEM*" and mcfg.n_held_experts == 64
    assert (mcfg.d_inner, mcfg.conv_width) == (4096, 6144)
    # a slot's state in one mixer: 2,097,152 B + 36,864 B
    assert costs_nemotron._state_bytes(CFG) == 2097152 + 36864


# -- the three cost functions on hand-made contexts ---------------------------


def _span(name, t0, **args):
    return {"type": "span", "name": name, "t0": t0, "t1": t0 + 0.01,
            "args": args}


def _ctx(spans):
    return {"config": CFG, "records": [], "slice": (9.0, 11.0),
            "spans": list(spans)}


def test_the_costs_read_the_programs_own_counts():
    spans = [_span("segment", 9.5, ssm_slots=160.0, experts_touched=40.0),
             _span("segment", 10.5, ssm_slots=80.0, experts_touched=20.0),
             _span("segment", 12.0, ssm_slots=512.0, experts_touched=64.0),
             _span("segment", 10.6, tokens=8),     # a request's waterfall
             _span("prefill_chunk", 9.7, base=0, tokens=512, creal=512,
                   state_carried=False),
             _span("prefill_chunk", 10.2, base=512, tokens=88, creal=88,
                   state_carried=True),
             _span("prefill_chunk", 10.21, base=512, tokens=88)]
    ctx = _ctx(spans)
    # (160 + 80) slot-steps over 2 segments of 8 steps = 15 slots a call
    assert costs_nemotron.ssm_step_bytes(ctx) == 15 * 2 * (2097152 + 36864)
    assert costs_nemotron.moe_expert_bytes(ctx) == 30 * 2 * 2688 * 1856 * 2
    # 300 real tokens a call: x, y 4096 each, B, C 1024 each, dt 64, at 2 B
    assert costs_nemotron.ssd_chunk_bytes(ctx) == (
        300 * (2 * 4096 + 2 * 1024 + 64) * 2 + 2 * 2097152)
    empty = _ctx([])
    assert costs_nemotron.ssm_step_bytes(empty) == 0.0
    assert costs_nemotron.ssd_chunk_bytes(empty) == 0.0
    assert costs_nemotron.moe_expert_bytes(empty) == 0.0
    # the gated cost would read this cell half as high again
    from benchmark import costs_latent
    assert costs_latent.moe_expert_bytes(ctx) == 1.5 * (
        costs_nemotron.moe_expert_bytes(ctx))


# -- the reference and its controls ----------------------------------------------

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


def test_sound_tokens_have_no_gap_and_the_controls_fail_the_limits(greedy):
    params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2   # context-sensitive
    assert R.served_gaps(params, TINY, seq, P, T - P, PAD).max() == 0.0
    int8 = R.served_gaps(params, TINY, seq, P, T - P, PAD, control=True)
    assert int8.max() > 10 * 1e-3 and int8.mean() > 10 * 1e-4
    # the state lost every chunk_tokens (16) tokens: what the engine would
    # serve if a chunk began from zero — far outside the limits
    lost = R.served_gaps(params, TINY, seq, P, T - P, PAD,
                         control="state_reset")
    assert lost.mean() > 10 * 1e-4
    # the state in bfloat16: read and reported, a hair at this size
    bf16 = R.served_gaps(params, TINY, seq, P, T - P, PAD,
                         control="state_bf16")
    assert 0.0 <= bf16.mean() < int8.mean()
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0


def test_balanced_router_biases_equalise_the_experts_load():
    """``init.balance_tokens``: a bias that evens out a skewed router on
    the batch it was set on; the cell's file asks for it, the tiny one
    does not, and weights stay a function of the seed."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    skew = rng.normal(size=(1, 16)) * 1.5      # some experts everyone likes
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(512, 16)) + skew))

    def loads(bias):
        _, idx = jax.lax.top_k(scores + bias, 3)
        return np.bincount(np.asarray(idx).ravel(), minlength=16)

    before = loads(jnp.zeros((16,)))
    after = loads(R._balanced_bias(scores, k=3))
    assert before.max() > 3 * before.mean()
    assert after.max() < 1.15 * after.mean() and after.min() > 0.85 * after.mean()
    assert CFG["init"]["balance_tokens"] == 1024 and "balance_tokens" not in TINY["init"]
    asked = dict(TINY, init=dict(TINY["init"], balance_tokens=256))
    plain, even = R.make_params(TINY, 5), R.make_params(asked, 5)
    assert sorted(plain) == sorted(even)
    moved = sorted(k for k in plain if not np.array_equal(plain[k], even[k]))
    assert moved == ["h1_router_bias", "h4_router_bias"]
    again = R.make_params(asked, 5)
    assert all(np.array_equal(even[k], again[k]) for k in moved)
    # on other tokens than it was set on, the most picked expert of 8
    # still gets under twice its share (6.6 x at random, seed 5)
    ids = np.random.RandomState(1).randint(1, 2048, size=400)
    with jax.default_matmul_precision("highest"):
        x = even["wte"][ids].astype(jnp.float32)
        x = R._layer(x, {k: even[f"h0_{k}"] for k in R.layer_shapes(TINY, 0)},
                     cfg=R._frozen(asked), layer=0, int8=False,
                     state_bits=None, reset_every=0)
        xn = R._rms(x, even["h1_norm_g"], 1e-5)
        for p_, top in ((plain, None), (even, 2.0)):
            s = jax.nn.sigmoid(xn @ p_["h1_router_w"]) + p_["h1_router_bias"]
            picks = np.bincount(np.asarray(jax.lax.top_k(s, 3)[1]).ravel(),
                                minlength=8)
            if top is not None:
                assert picks.max() < top * picks.mean(), picks


def test_the_references_query_blocks_do_not_change_its_numbers(monkeypatch):
    params = R.make_params(TINY, 9)
    ids = np.random.RandomState(2).randint(1, 2048, size=(1, 64))
    whole = np.asarray(R.logits(params, TINY, ids))
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    R._layer.clear_cache()
    blocked = np.asarray(R.logits(params, TINY, ids))
    R._layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, rtol=1e-4, atol=1e-4)


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
    _write(b / "configs" / "tiny-nemotron.json", TINY)
    # prompts under and over a chunk (16): one padded chunk, or several
    _write(b / "traffic" / "tiny-chat.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 80,
        "prompt_len": {"dist": "log_uniform", "lo": 3, "hi": 60},
        "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}})
    _write(b / "workloads" / "tiny-nemotron.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    cells = ["tiny-nemotron"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-nemotron",
                     "file": "benchmark/configs/tiny-nemotron.json"}],
        "workloads": [{"name": "tiny-nemotron", "config": "tiny-nemotron",
                       "traffic": "tiny-chat", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("moe_experts_touched_share", "ratio"),
                         ("moe_pick_imbalance", "ratio"),
                         ("ssm_slots_stepped", "slots"),
                         ("gqa_paged_attn_roofline", "%"),
                         ("ssm_step_roofline", "%"),
                         ("ssd_chunk_roofline", "%"),
                         ("relu2_expert_roofline", "%"))],
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
    assert run.main(["--workload", "tiny-nemotron", "--seed", "3000000017",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    # two program classes: the segment and the chunk
    assert "the segment and [\"('chunk', 16, 1, 'xla')\"]" in out
    assert "compared compilations_in_window = 0" in out
    assert "'ssm.first_chunks': 14" in out      # the window's 12, 2 warm-ups


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-nemotron", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    m = line["metrics"]
    assert 1.0 <= m["ssm_slots_stepped"]["value"] <= ENGINE["slots"]
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"gqa_paged_attn_roofline", "ssm_step_roofline",
                "ssd_chunk_roofline", "relu2_expert_roofline"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_a_state_lost_between_two_chunks_is_not_correct(tiny_root, capsys,
                                                        monkeypatch):
    """The timed path broken: every chunk starts its mixers from zero,
    as if the slot's state were not carried from one chunk program to the
    next; prompts of more than one chunk leave the reference's."""
    from distributed_llm_scheduler_tpu.models import nemotron_h

    real = nemotron_h.mixer_chunk
    monkeypatch.setattr(
        nemotron_h, "mixer_chunk",
        lambda p, u, conv, h, pos0, last, cfg, impl=None: real(
            p, u, conv, h, 0, last, cfg, impl))
    assert run.main(["--workload", "tiny-nemotron", "--seed", "11",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "NOT CORRECT" in out


def test_a_reading_reports_both_controls(tiny_root, capsys):
    """``benchmark.readings --control 1`` on this runner: the program's
    numbers, the int8 forward's and the bfloat16-state forward's."""
    import types

    import jax

    cell = harness.load_cell("tiny-nemotron")
    nemotron_serve.readings(cell, jax.devices()[:1], types.SimpleNamespace(
        seeds=[5], seconds=2.0, control=1))
    rows = [json.loads(line[len("READING "):]) for line in
            capsys.readouterr().out.splitlines() if line.startswith("READING")]
    assert len(rows) == 1 and rows[0]["failed"] == 0
    assert rows[0]["program"]["gap_mean"] < 1e-4
    assert rows[0]["control"]["gap_mean"] > 10 * 1e-4
    assert rows[0]["control_state_bf16"]["gap_mean"] < rows[0]["control"][
        "gap_mean"]

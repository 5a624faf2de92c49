"""The LFM2 cell's files, its parameter count, its cost functions on
hand-made contexts, its reference's controls, and whole runs at a tiny
size on the CPU — sound, with the conv state lost between two chunks, and
with the q / k norm left out — every entry of ``BENCHMARK.json`` looked up
BY NAME, with no edit to a benchmark file that was there.  (Its programs
compiled for the v5e: ``test_benchmark_lfm2_aot.py``.)

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

from benchmark import costs_lfm2, harness, run, xplane  # noqa: E402
from benchmark.reference import lfm2 as R  # noqa: E402
from benchmark.runners import laguna_serve, lfm2_serve  # noqa: E402
from benchmark.runners import xing4_serve  # noqa: E402

CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "lfm2-24b-a2b-serve.json").read_text())
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
GEO = CFG["engine"]
NEW = {"conv_step_dev_us_step", "conv_slots_stepped", "conv_slots_traced",
       "lfm2_step_hbm_roofline"}
TINY = {
    "source": "test", "runner": "lfm2_serve", "reference": "lfm2",
    "model_type": "lfm2_moe", "hidden_size": 32, "num_hidden_layers": 10,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 2
    + ["conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "num_dense_layers": 2, "moe_intermediate_size": 20, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "max_position_embeddings": 256, "vocab_size": 2048,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "dtype": "float32",
    "init": {"std": 0.3, "conv_gain": 2.0, "q_norm_gain": 2.0},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 10, "n_pages": 41,
          "seg_steps": 4, "chunk_tokens": 16, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}
TINY = dict(TINY, engine=ENGINE)


def _by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


def _names(metrics):
    return {m["name"] for m in metrics}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("lfm2-wide")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "lfm2-24b-a2b-serve", "agent-wide-fixed", 1)
    assert harness.load_runner(cell) is lfm2_serve
    assert harness.load_reference(cell.config) is R
    assert _names(cell.end_to_end) == {"tpot_ms_mean", "setup_s"}
    names = _names(cell.per_layer)
    assert NEW | {"gqa_paged_attn_roofline", "moe_expert_roofline",
                  "moe_experts_touched_share", "moe_pick_imbalance",
                  "kv_live_block_share",
                  "decode_step_dev_ms", "prefill_dev_us_tok",
                  "seg_behind_prefill_share", "seg_period_ms_p99"} <= names
    # not joined, and why: ``gqa_chunk_flash_roofline`` (a head of 64 is no
    # whole lane tile, so the chunk program runs no such kernel) and
    # ``pool_pages_used_share`` (an accepted test compares its list of
    # cells whole: a ``benchmark`` issue's to join; the runner prints the
    # histogram's median).  Membership only is asserted here, so that a
    # later PR that joins the cell to either reddens nothing
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    for n in NEW:
        assert "lfm2-wide" in _by_name(SPEC["per_layer"], n)["workloads"]
        assert _by_name(SPEC["per_layer"], n)["moves"] == "tpot_ms_mean"
    for n in ("conv_slots_stepped", "conv_slots_traced"):
        assert _by_name(SPEC["per_layer"], n)["layer"] == _by_name(
            SPEC["per_layer"], "ssm_slots_stepped")["layer"]
    assert _by_name(SPEC["per_layer"], "lfm2_step_hbm_roofline")[
        "layer"] == _by_name(SPEC["per_layer"], "decode_step_dev_ms")["layer"]
    entry = _by_name(SPEC["workloads"], "lfm2-wide")
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "lfm2-wide" in _by_name(SPEC["end_to_end"],
                                   "tpot_ms_mean")["workloads"]
    geo, t = cell.config["engine"], cell.traffic
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (128, 1024)
    assert t["prompt_len"]["dist"] == t["output_len"]["dist"] == "log_uniform"
    assert t["max_total"] == 3072 == (
        t["prompt_len"]["hi"] + t["output_len"]["hi"])
    cap = geo["pages_per_seq"] * geo["page_size"]
    # a slot holds the longest request, and the longest prompt's chunks
    assert cap >= t["max_total"] and t["prompt_len"]["hi"] % geo[
        "chunk_tokens"] == 0
    # every slot at full length: the slots, not the K/V, bound the batch
    assert geo["n_pages"] - 1 == geo["slots"] * geo["pages_per_seq"]
    assert geo["slots"] == 128 and geo["chunk_tokens"] % geo["page_size"] == 0
    rate = float(cell.params["rate_rps"])
    a = lfm2_serve.schedule(t, rate, 51.0)
    assert a == xing4_serve.schedule(t, rate, 51.0)    # pinned, one for all
    assert len(a) == round(rate * 51) >= 100
    lens = sorted(r.prompt_len for r in a)
    assert lens[0] < 140 < 900 < lens[-1]
    # two prompts in three are one padded chunk
    assert 0.55 < sum(p <= geo["chunk_tokens"] for p in lens) / len(lens) < 0.8
    assert int(cell.params["check_requests"]) == 8
    assert cell.params["limits"]["min_tokens_checked"] <= 8 * t[
        "output_len"]["lo"]


def test_the_configuration_is_the_catalogs_row_but_for_its_depth():
    entry = _by_name(SPEC["configs"], "lfm2-24b-a2b-serve")
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b-serve.json"
    reduced = {"num_hidden_layers": 10, "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]}
    assert set(entry["reduced"]) == set(reduced) and len(entry["why"]) <= 200
    assert {k: CFG[k] for k in reduced} == reduced
    published = {
        "hidden_size": 2048, "intermediate_size": 11776, "conv_L_cache": 3,
        "conv_bias": False, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "vocab_size": 65536,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"]["num_hidden_layers"] == 40
    assert CFG["head_dim"] * CFG["num_attention_heads"] == CFG["hidden_size"]
    assert {"head_dim", "tie_word_embeddings", "gate_sum", "expert_bias",
            "rotary", "dtype", "reduced", "engine", "init"} <= set(
        CFG["assumed"])
    assert "stage 1 of 4" in CFG["deployment"]
    assert (CFG["runner"], CFG["reference"]) == ("lfm2_serve", "lfm2")
    # the catalog's row, where the catalog is at hand: every key of it
    # under the same name, but for the depth and its layer_types
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"name": "LFM2-24B-A2B"' in line)
        assert row["source_url"] == CFG["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in reduced} == {
            k: CFG[k] for k in row["config"] if k not in reduced}
        assert row["config"]["layer_types"][:10] == CFG["layer_types"]


def test_the_configuration_holds_5267_million_parameters():
    """The issue's count, the reference's and the program's
    ``param_shapes`` agree: 5,267 M parameters, 10.53 GB in bf16; a dense
    conv layer 89.1 M, an attention + expert layer 614.6 M, a conv +
    expert layer 620.9 M, the embedding (tied) 134.2 M."""
    from distributed_llm_scheduler_tpu.models import lfm2

    n = R.param_count(CFG)
    assert abs(n - 5267e6) < 0.5e6
    mcfg = lfm2_serve.model_config(CFG)
    shapes = lfm2.param_shapes(mcfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == n
    assert 10.52e9 < 2 * n < 10.55e9
    per = [round(sum(int(np.prod(s)) for s, _ in lfm2.layer_param_shapes(
        mcfg, i).values()) / 1e6, 1) for i in (0, 2, 3)]
    assert per == [89.1, 614.6, 620.9]
    assert (mcfg.n_layers, mcfg.head_dim, mcfg.n_routed_experts) == (10, 64, 64)
    # a slot's state in one conv layer, and what a step reads whatever
    # it routes: 0.87 GB beside 8 x 1.208 GB of experts
    assert costs_lfm2.conv_state_bytes(CFG) == 8192
    fixed = costs_lfm2.fixed_weight_bytes(CFG)
    experts = 8 * 64 * 3 * 2048 * 1536 * 2
    # (the routers and their biases are float32: 2 B more a value)
    assert fixed + experts == 2 * n + 8 * (2048 + 1) * 64 * 2
    assert 0.86e9 < fixed < 0.88e9


# -- the cost functions on hand-made contexts ----------------------------------


def _span(name, t0, **args):
    return {"type": "span", "name": name, "t0": t0, "t1": t0 + 0.01,
            "args": args}


def _record(prompt, have, owed, t_first=1.0):
    return {"t_first": t_first, "t_retire": None, "prompt_len": prompt,
            "max_new_tokens": have + owed,
            "deliveries": [(t_first + 0.1, have - 1)]}


def test_the_costs_read_the_programs_own_counts():
    spans = [_span("segment", 9.5, conv_slots=800.0, experts_touched=64.0),
             _span("segment", 10.5, conv_slots=480.0, experts_touched=48.0),
             _span("segment", 12.0, conv_slots=1024.0, experts_touched=64.0),
             _span("segment", 10.6, tokens=8)]     # a request's waterfall
    ctx = {"config": CFG, "slice": (9.0, 11.0), "spans": spans,
           "records": [_record(300, 50, 100), _record(200, 10, 3)],
           "slice_segments": [(9.5, 9.6)]}
    # (800 + 480) slot-steps over 2 segments of 8 steps = 80 slots a step:
    # the traced slice's own width, and what ``step_bytes`` counts states for
    from benchmark.metrics.readers import span_arg_mean
    how = json.loads((harness.HERE / "metrics"
                      / "conv_slots_traced.json").read_text())
    assert how["reader"] == "span_arg_mean"
    assert span_arg_mean.read(ctx, how["params"]) == 80.0
    assert span_arg_mean.read(dict(ctx, spans=[]), how["params"]) is None
    assert span_arg_mean.read(
        {"config": CFG, "spans": spans}, how["params"]) is None   # no slice
    # live rows a step: 8 steps of the first request at 350.., 3 of the second
    rows = (sum(350 + s for s in range(8)) + sum(210 + s for s in range(3))) / 8
    want = (costs_lfm2.fixed_weight_bytes(CFG)
            + 8 * 56 * 3 * 2048 * 1536 * 2 + 2 * 2048 * rows
            + 8 * 2 * 8192 * 80)
    assert costs_lfm2.step_bytes(ctx) == pytest.approx(want, rel=1e-12)
    # all 64 experts of all 8 layers, 100 rows of 1,500 tokens: the whole
    # chip's weights and a little more, 12.9 ms at 819 GB/s
    full = dict(ctx, spans=[_span("segment", 9.5, conv_slots=800.0,
                                  experts_touched=64.0)])
    assert 10.5e9 < costs_lfm2.step_bytes(full) < 10.6e9
    empty = dict(ctx, spans=[])
    assert costs_lfm2.step_bytes(empty) == 0.0
    # the joined metrics' cost functions read THIS configuration's keys
    from benchmark import costs_laguna, costs_latent
    assert costs_laguna.gqa_paged_attention_bytes(ctx) == 2 * 8 * 64 * 2 * rows
    assert costs_latent.moe_expert_bytes(ctx) == 56 * 3 * 2048 * 1536 * 2


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


def test_sound_tokens_have_no_gap_and_the_controls_fail_the_limits(
        greedy, monkeypatch):
    params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2   # context-sensitive
    assert R.served_gaps(params, TINY, seq, P, T - P, PAD).max() == 0.0
    # the head in windows of rows: the same numbers from 3 windows as 1
    monkeypatch.setattr(R, "ROW_WINDOW", 16)
    assert R.served_gaps(params, TINY, seq, P, T - P, PAD).max() == 0.0
    int8 = R.served_gaps(params, TINY, seq, P, T - P, PAD, control=True)
    assert int8.max() > 10 * 1e-3 and int8.mean() > 10 * 1e-4
    # the carried rows lost at the prompt's chunk boundaries (16) and every
    # 64 decoded tokens: what the engine would serve if a chunk or a step
    # began from zero — far outside the limits
    monkeypatch.setattr(R, "LOST_EVERY", 8)
    lost = R.served_gaps(params, TINY, seq, P, T - P, PAD,
                         control="conv_state_lost")
    assert lost.mean() > 10 * 1e-4
    since = R.lost_since(12, 5, 4, every=3)
    assert since.tolist() == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1, 2, 0]
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0
    with pytest.raises(ValueError):
        R.served_gaps(params, TINY, seq, P, T - P, PAD, control="state_bf16")


def test_balanced_expert_biases_are_a_function_of_the_seed():
    assert CFG["init"]["balance_tokens"] == 1024
    assert "balance_tokens" not in TINY["init"]
    asked = dict(TINY, init=dict(TINY["init"], balance_tokens=256))
    plain, even = R.make_params(TINY, 5), R.make_params(asked, 5)
    assert sorted(plain) == sorted(even)
    moved = sorted(k for k in plain if not np.array_equal(plain[k], even[k]))
    assert moved == [f"h{i}_router_bias" for i in range(2, 10)]
    again = R.make_params(asked, 5)
    assert all(np.array_equal(even[k], again[k]) for k in moved)
    # the q norm starts at its gain, the k norm at 1
    assert float(even["h2_q_norm_g"][0]) == 2.0 == 2 * float(
        even["h2_k_norm_g"][0])


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
    _write(b / "configs" / "tiny-lfm2.json", TINY)
    # prompts under and over a chunk (16): one padded chunk, or several
    _write(b / "traffic" / "tiny-wide.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 80,
        "prompt_len": {"dist": "log_uniform", "lo": 3, "hi": 60},
        "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}})
    _write(b / "workloads" / "tiny-lfm2.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    cells = ["tiny-lfm2"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-lfm2",
                     "file": "benchmark/configs/tiny-lfm2.json"}],
        "workloads": [{"name": "tiny-lfm2", "config": "tiny-lfm2",
                       "traffic": "tiny-wide", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("moe_experts_touched_share", "ratio"),
                         ("moe_pick_imbalance", "ratio"),
                         ("pool_pages_used_share", "ratio"),
                         ("conv_slots_stepped", "slots"),
                         ("conv_step_dev_us_step", "us"),
                         ("conv_slots_traced", "slots"),
                         ("lfm2_step_hbm_roofline", "%"),
                         ("gqa_paged_attn_roofline", "%"),
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
    assert run.main(["--workload", "tiny-lfm2", "--seed", "3000000017",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    # two program classes: the segment and the chunk
    assert "the segment and [\"('chunk', 16, 1, 'xla')\"]" in out
    assert "compared compilations_in_window = 0" in out
    assert "compared pages_leaked = 0" in out
    assert "'ssm.first_chunks': 14" in out      # the window's 12, 2 warm-ups


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-lfm2", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    m = line["metrics"]
    assert 1.0 <= m["conv_slots_stepped"]["value"] <= ENGINE["slots"]
    assert 0.0 < m["conv_slots_traced"]["value"] <= ENGINE["slots"]
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    assert 0 < m["pool_pages_used_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"gqa_paged_attn_roofline", "conv_step_dev_us_step",
                "lfm2_step_hbm_roofline", "moe_expert_roofline"} & set(m)
    assert line["device"]["busy_s"] > 0


def _not_correct(capsys, seed):
    assert run.main(["--workload", "tiny-lfm2", "--seed", str(seed),
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "NOT CORRECT" in out


def test_a_conv_state_lost_between_two_chunks_is_not_correct(
        tiny_root, capsys, monkeypatch):
    """The timed path broken: every chunk starts its convolutions from
    zero, as if the slot's carried rows were not handed from one chunk
    program to the next; prompts of more than one chunk leave the
    reference's."""
    from distributed_llm_scheduler_tpu.models import lfm2

    real = lfm2.short_conv_chunk
    monkeypatch.setattr(
        lfm2, "short_conv_chunk",
        lambda u, w, carried, pos0, last: real(u, w, carried, 0, last))
    _not_correct(capsys, 11)


def test_the_q_and_k_norm_left_out_is_not_correct(tiny_root, capsys,
                                                  monkeypatch):
    """The timed path broken: attention without the per-head RMSNorm of
    ``q`` and ``k`` (rotation kept)."""
    from distributed_llm_scheduler_tpu.models import lfm2

    def qkv(p, xn, positions, cfg):
        N, hd = xn.shape[0], cfg.head_dim
        at = positions[:, None]
        return (lfm2.rope((xn @ p["q_w"]).reshape(N, -1, hd), at, cfg),
                lfm2.rope((xn @ p["k_w"]).reshape(N, -1, hd), at, cfg),
                (xn @ p["v_w"]).reshape(N, -1, hd))

    monkeypatch.setattr(lfm2, "qkv", qkv)
    _not_correct(capsys, 13)


def test_a_reading_reports_both_controls(tiny_root, capsys):
    """``benchmark.readings --control 1`` on this runner: the program's
    numbers, the int8 forward's and the lost-state forward's."""
    import types

    import jax

    cell = harness.load_cell("tiny-lfm2")
    lfm2_serve.readings(cell, jax.devices()[:1], types.SimpleNamespace(
        seeds=[5], seconds=2.0, control=1))
    rows = [json.loads(line[len("READING "):]) for line in
            capsys.readouterr().out.splitlines() if line.startswith("READING")]
    assert len(rows) == 1 and rows[0]["failed"] == 0
    lim = cell.params["limits"]
    assert rows[0]["program"]["gap_mean"] < lim["gap_mean"]
    assert rows[0]["program"]["gap_max"] < lim["gap_max"]
    for control in ("control", "control_conv_state_lost"):
        assert rows[0][control]["gap_mean"] > 10 * lim["gap_mean"], control


def test_the_sweep_reads_the_width_of_each_rate_alone(tiny_root, capsys):
    """One engine, two rates and another output range: each row's widths
    are that rate's own (a registry a rate, the window's ``segment``
    spans), not the process's running median."""
    assert lfm2_serve.sweep(["--workload", "tiny-lfm2", "--rates", "2,6",
                             "--seconds", "2", "--seed", "9",
                             "--output-len", "8,24"]) == 0
    rows = [json.loads(line[len("SWEEP "):]) for line in
            capsys.readouterr().out.splitlines() if line.startswith("SWEEP")]
    assert [r["rate_rps"] for r in rows] == [2.0, 6.0]
    for r in rows:
        assert r["failed"] == 0 and r["output_len"] == [8, 24]
        assert r["slots_peak"] >= r["slots_stepped_p50"] >= 1.0
        assert r["slots_window_p50"] > 0 and r["slots_last_mean"] > 0
        assert r["slots_peak"] >= r["slots_window_p50"]
    # three times the arrivals fill no fewer slots
    assert rows[1]["slots_stepped_p50"] >= rows[0]["slots_stepped_p50"]
    assert rows[1]["slots_window_p50"] >= rows[0]["slots_window_p50"]

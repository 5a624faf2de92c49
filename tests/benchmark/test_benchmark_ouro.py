"""The Ouro cell's files, its parameter count, its cost function on a
hand-made context, its reference's controls and a whole run at a tiny
size on the CPU — all found by NAME, never by position or count, with no
edit to a benchmark file that was there.

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

from benchmark import costs_ouro, harness, run, xplane  # noqa: E402
from benchmark.reference import ouro as R  # noqa: E402
from benchmark.runners import ouro_serve  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "ouro-2.6b-serve.json").read_text())
GEO = CFG["engine"]
CELL = "ouro-reason"
NEW_METRICS = ("loop_step_hbm_roofline", "loop_passes_per_token",
               "loop_exit_pass_expected", "pool_pages_used_share")
TINY = {
    "source": "test", "runner": "ouro_serve", "reference": "ouro",
    "model_type": "ouro", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 128,
    "intermediate_size": 96, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
    "vocab_size": 2048, "total_ut_steps": 3, "early_exit_threshold": 1,
    "dtype": "float32", "init": {"std": 0.3},
}
# 3 slots of up to 12 pages over 15 allocatable ids: pages go out by need
ENGINE = {"slots": 3, "page_size": 8, "pages_per_seq": 12, "n_pages": 16,
          "seg_steps": 4, "chunk_tokens": 16, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}


def _by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


# -- the files ---------------------------------------------------------------


def test_the_cells_files_load_by_name():
    cell = harness.load_cell(CELL)
    assert cell.config_name == "ouro-2.6b-serve" and cell.chips == 1
    assert cell.traffic_name == "reason-short-fixed"
    assert cell.config["runner"] == "ouro_serve"
    assert harness.load_runner(cell) is ouro_serve
    assert harness.load_reference(cell.config) is R
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_mean", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"gqa_paged_attn_roofline", "kv_live_block_share",
            "decode_step_dev_ms", "prefill_dev_us_tok", "step_interval_ms",
            "seg_idle_ms_step", "prefill_stall_ms_step"} <= reported
    t = cell.traffic
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (129, 640)
    # ISSUE 47's top of 512 lowered as it provides (PERF.md section 4)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (128, 384)
    assert t["schedule_seed"] == 17320
    # every prompt is longer than a chunk: none is a whole-prompt program
    assert t["prompt_len"]["lo"] > GEO["chunk_tokens"]
    assert t["max_total"] == GEO["pages_per_seq"] * GEO["page_size"] == 1152
    assert t["prompt_len"]["hi"] + t["output_len"]["hi"] <= t["max_total"]
    assert cell.params["check_requests"] == 8
    assert cell.params["slo_ttft_s"] == 6.0


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_names_the_cell_and_a_reader(metric):
    entry = _by_name(SPEC["per_layer"], metric)
    assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_ms_mean"
    how = json.loads((ROOT / "benchmark" / "metrics"
                      / f"{metric}.json").read_text())
    reader = harness._module(f"metrics/readers/{how['reader']}")
    if entry["source"] == "device_trace":
        # an untraced run, or a program without the module: nothing read
        assert reader.read({"trace": None, "config": CFG, "n_devices": 1,
                            "device_kind": "TPU v5 lite"},
                           how["params"]) is None
    else:
        # a program that never observed the histogram: nothing read
        assert harness._module("metrics/readers/" + how["reader"]).read(
            {}, {**how["params"], "histograms": ["no.such.histogram"],
                 "histogram": "no.such.histogram"}) is None


def test_the_configuration_is_the_catalogs_key_for_key():
    """Nothing cut: every key of the catalog row's ``config`` under the
    same key with the same value, ``reduced`` empty."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Ouro-2.6B")
    entry = _by_name(SPEC["configs"], "ouro-2.6b-serve")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value, key
    for item in ("sandwich_norms", "pass_end", "exit_gate", "attention",
                 "rope", "cache_per_pass", "engine", "init", "dtype"):
        assert len(CFG["assumed"][item]) > 40, item


def test_the_configuration_holds_2668_million_parameters():
    import math

    from distributed_llm_scheduler_tpu.models import ouro

    mcfg = ouro_serve.model_config(CFG)
    shapes = ouro.param_shapes(mcfg)
    n = sum(math.prod(s) for s, _ in shapes.values())
    assert n == R.param_count(CFG) == 2_667_974_657
    assert costs_ouro.layer_params(CFG) == 51_388_416
    assert costs_ouro.token_cache_bytes(CFG) == 1_572_864
    # one name a weight, however many passes read it
    assert len(shapes) == 48 * 10 + 5
    spec = ouro.cache_spec(mcfg)
    assert spec.passes == 4 and spec.n_layers == 48
    assert spec.paged_row_elems * 2 == 1_572_864


def test_the_init_group_scales_draws_and_sets_norm_gains():
    """``<name>_gain`` multiplies the draw of ``<name>_w`` or of
    ``<name>``; a norm gain named in the group starts there, not at 1."""
    plain = R.make_params(TINY, 5)
    init = {"std": 0.3, "wte_gain": 2.0, "q_gain": 3.0, "attn_post_g": 0.25}
    gained = R.make_params(dict(TINY, init=init), 5)
    for name, factor in (("wte", 2.0), ("h1_q_w", 3.0), ("h1_k_w", 1.0),
                         ("head_w", 1.0)):
        np.testing.assert_allclose(np.asarray(gained[name]),
                                   factor * np.asarray(plain[name]), rtol=1e-6)
    assert float(gained["h2_attn_post_g"][0]) == 0.25
    assert float(gained["h2_ffn_post_g"][0]) == 1.0 == float(
        plain["h2_attn_post_g"][0])
    assert float(np.abs(np.asarray(gained["exit_b"])).max()) == 0.0


# -- the cost function ---------------------------------------------------------


def test_a_steps_bytes_count_the_weights_once_a_pass_and_every_live_row():
    rec = {"prompt_len": 100, "max_new_tokens": 50, "t_first": 1.0,
           "t_retire": None, "deliveries": [(5.0, 8)]}
    ctx = {"config": CFG, "records": [rec], "slice_segments": [(10.0, 10.1)]}
    # the request holds 100 + 9 rows when the segment starts, one more a step
    rows = np.mean([109 + s for s in range(GEO["seg_steps"])])
    weights = 2 * (4 * (48 * 51_388_416 + 2 * 2048 + 1) + 2048 * 49152)
    assert costs_ouro.loop_step_bytes(ctx) == pytest.approx(
        weights + 1_572_864 * rows)
    assert 19.9e9 < weights < 20.0e9        # 24.3 ms at 819 GB/s
    assert costs_ouro.loop_step_bytes(
        {"config": CFG, "records": [], "slice_segments": []}) == weights


# -- the reference and its controls ----------------------------------------------

P, T, PAD = 40, 72, 128


@pytest.fixture(scope="module")
def greedy():
    """Weights and one greedy continuation by the reference itself."""
    import jax.numpy as jnp

    params = R.make_params(TINY, 2**31 + 77)
    layers = R.stack_layers(params, TINY)
    seq = list(np.random.RandomState(3).randint(1, 2048, size=P))
    for _ in range(T - P):
        ids = np.zeros((PAD,), np.int32)
        ids[:len(seq)] = seq
        seq.append(int(jnp.argmax(R.forward(
            params, TINY, ids, rows=slice(len(seq) - 1, len(seq)),
            layers=layers)[0][0])))
    return params, np.asarray(seq, np.int32)


def test_sound_tokens_have_no_gap_and_both_controls_fail_the_limits(greedy):
    params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2   # context-sensitive
    assert R.served_gaps(params, TINY, seq, P, T - P, PAD).max() == 0.0
    for control in ouro_serve.CONTROLS:
        gaps = R.served_gaps(params, TINY, seq, P, T - P, PAD,
                             control=control)
        assert gaps.max() > 10 * 1e-3 and gaps.mean() > 10 * 1e-4, control
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0


def test_the_references_passes_are_what_the_equations_say(monkeypatch):
    """One pass is a plain sandwich-norm decoder; the query blocks are
    how a long sequence fits, not part of the mathematics; the exit
    distribution sums to one and its last entry is what is left."""
    params = R.make_params(TINY, 9)
    ids = np.random.RandomState(2).randint(1, 2048, size=(64,))
    logits, hs, lams = R.forward(params, TINY, ids)
    assert hs.shape == (3, 64, 64) and lams.shape == (3, 64)
    one, hs1, _ = R.forward(params, TINY, ids, total_ut_steps=1)
    np.testing.assert_allclose(np.asarray(hs1[0]), np.asarray(hs[0]),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(one) - np.asarray(logits)).max() > 1e-2
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    R._one_pass.clear_cache()
    blocked = np.asarray(R.forward(params, TINY, ids)[0])
    R._one_pass.clear_cache()
    # nine layer applications, every one behind a norm: summation order
    np.testing.assert_allclose(blocked, np.asarray(logits), rtol=1e-3,
                               atol=5e-3)
    p = R.exit_distribution(lams)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-12)
    lam = np.asarray(lams, np.float64)
    np.testing.assert_allclose(p[2], (1 - lam[0]) * (1 - lam[1]))
    assert ouro_serve.reference_rows(300, 1152) == 512
    assert ouro_serve.reference_rows(1025, 1152) == 1152
    assert ouro_serve.reference_rows(130, 1152) == 256


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
    _write(b / "configs" / "tiny-ouro.json", dict(TINY, engine=ENGINE))
    # every prompt over a chunk (16), as in the cell
    _write(b / "traffic" / "tiny-reason.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 96,
        "prompt_len": {"dist": "log_uniform", "lo": 17, "hi": 60},
        "output_len": {"dist": "log_uniform", "lo": 6, "hi": 24}})
    _write(b / "workloads" / "tiny-ouro.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    cells = ["tiny-ouro"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-ouro",
                     "file": "benchmark/configs/tiny-ouro.json"}],
        "workloads": [{"name": "tiny-ouro", "config": "tiny-ouro",
                       "traffic": "tiny-reason", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("gqa_paged_attn_roofline", "%"),
                         ("loop_step_hbm_roofline", "%"),
                         ("loop_passes_per_token", "passes"),
                         ("loop_exit_pass_expected", "passes"),
                         ("pool_pages_used_share", "ratio"))],
    })
    monkeypatch.setattr(harness, "HERE", b)
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(ouro_serve, "MIN_REFERENCE_ROWS", 32)
    monkeypatch.setattr(xplane, "DEVICE_PLANE", r"^/host:CPU$")
    monkeypatch.setattr(xplane, "OPS_LINE", r"^tf_XLA")
    return tmp_path


def test_cell_end_to_end(tiny_root, capsys):
    assert run.main(["--workload", "tiny-ouro", "--seed", "3000000017",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    assert "compared compilations_in_window = 0" in out
    assert "compared pages_leaked = 0" in out


def test_cell_traced_prints_every_new_program_metric(tiny_root, capsys):
    assert run.main(["--workload", "tiny-ouro", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    m = line["metrics"]
    assert m["loop_passes_per_token"]["value"] == 3.0
    assert 1.0 <= m["loop_exit_pass_expected"]["value"] <= 3.0
    assert 0 < m["pool_pages_used_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"gqa_paged_attn_roofline", "loop_step_hbm_roofline"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_passes_that_share_plane_0_are_not_correct(tiny_root, capsys,
                                                   monkeypatch):
    """The timed path broken: every pass reads and writes plane 0 of the
    pools (the approximation the family's report describes, and a page
    table that forgot its offset); the served tokens leave the
    reference's."""
    from distributed_llm_scheduler_tpu.models.kv_pages import CacheSpec

    monkeypatch.setattr(CacheSpec, "plane", lambda self, pool, table, u: table)
    assert run.main(["--workload", "tiny-ouro", "--seed", "11",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "NOT CORRECT" in out

"""The GLM cell's files, its reference's control, the drafts it compares
and a whole run at a tiny size on the CPU — all found by name, with no
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

from benchmark import harness, run, xplane  # noqa: E402
from benchmark.reference import glm as R  # noqa: E402
from benchmark.runners import glm_serve, xing4_serve  # noqa: E402

#: tiny widths under the configuration's own construction (tests/
#: test_glm4_lite.py reads 0.58 acceptance from it)
TINY = {
    "source": "test", "runner": "glm_serve", "reference": "glm",
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
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 8, "n_pages": 33,
          "seg_steps": 4, "chunk_tokens": 8, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}
NEW = {"mtp_accept_rate", "mtp_tokens_per_step", "mtp_draft_dev_us_step"}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("glm-reason")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "glm-4.7-flash-serve", "reason-fixed", 1)
    assert harness.load_runner(cell) is glm_serve
    assert harness.load_reference(cell.config) is R
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_mean", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"mla_paged_attn_roofline", "moe_expert_roofline",
                  "decode_step_dev_ms", "window_tok_s"} <= names
    # the one-row K/V kernel, the streams, the selection: not this cell's
    assert not {"paged_attn_roofline", "hc_maps_dev_us_step",
                "dsa_index_roofline"} & names
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    geo, t = cell.config["engine"], cell.traffic
    assert geo["n_pages"] == geo["slots"] * geo["pages_per_seq"] + 1
    # the footprint prompt + max_new holds the last step's draft row
    assert geo["pages_per_seq"] * geo["page_size"] >= t["max_total"] + 1
    assert t["schedule_seed"] == 27182
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (512, 2048)


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = spec["configs"][-1]
    assert entry["name"] == "glm-4.7-flash-serve"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].endswith("zai-org/GLM-4.7-Flash/blob/main/config.json")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 6       # of 47: the one cut
    assert {"mtp_form", "mtp_hidden", "rope", "routing", "cache_row", "step",
            "engine", "init", "reduced"} <= set(cfg["assumed"])
    assert set(cfg["init"]) == {"std", "q_gain", "exp_down_gain", "emb_gain",
                                "eh_identity", "mtp_out_gain"}
    assert spec["workloads"][-1]["name"] == "glm-reason"
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert len(spec["workloads"]) == 7


def test_the_configuration_holds_4539_million_parameters():
    """The issue's count, the reference's and the program's
    ``param_shapes`` agree: 4,539.3 M parameters, 9.08 GB in bf16, the
    draft module 643.71 M of them."""
    from distributed_llm_scheduler_tpu.models import glm4_lite

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "glm-4.7-flash-serve.json").read_text())
    n = R.n_parameters(cfg)
    assert n == 4_539_331_712
    mcfg = glm_serve.model_config(cfg)
    shapes = glm4_lite.param_shapes(mcfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == n
    assert sum(int(np.prod(s)) for k, (s, _) in shapes.items()
               if k.startswith("mtp_")) == 643_706_176
    spec = glm4_lite.cache_spec(mcfg)
    assert (spec.n_layers, spec.draft_layers) == (7, 1)
    assert spec.paged_row_elems * 2 == 8960        # bytes a token


def test_the_schedule_is_the_files_own():
    cell = harness.load_cell("glm-reason")
    rate = float(cell.params["rate_rps"])
    a = glm_serve.schedule(cell.traffic, rate, 51.0)
    assert a == xing4_serve.schedule(cell.traffic, rate, 51.0)
    t = cell.traffic
    assert all(t["prompt_len"]["lo"] <= r.prompt_len <= t["prompt_len"]["hi"]
               and t["output_len"]["lo"] <= r.max_new_tokens
               <= t["output_len"]["hi"]
               and r.prompt_len + r.max_new_tokens <= t["max_total"]
               for r in a)


# -- the reference, its draft module and its int8 control -----------------------

P, T, PAD = 24, 72, 80


@pytest.fixture(scope="module")
def greedy():
    """Weights, one greedy continuation by the reference itself, and the
    drafts its own draft module would have had verified: a step at every
    length (the densest case)."""
    import jax.numpy as jnp

    params = R.make_params(TINY, 2**31 + 77)
    seq = list(np.random.RandomState(3).randint(1, 256, size=P))
    for _ in range(T - P):
        ids = np.zeros((1, PAD), np.int32)
        ids[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(R.logits(
            params, TINY, ids, rows=slice(len(seq) - 1, len(seq)))[0, 0])))
    seq = np.asarray(seq, np.int32)
    d = np.asarray(jnp.argmax(R.draft_logits(params, TINY, seq[None])[0], -1))
    drafts = {L: int(d[L - 1]) for L in range(P, T - 1)}
    return params, seq, drafts


def test_sound_tokens_and_drafts_have_no_gap_and_the_control_fails(greedy):
    params, seq, drafts = greedy
    sound = R.served_check(params, TINY, seq, P, T - P, PAD, drafts=drafts)
    assert sound["gaps"].max() == 0.0 and sound["draft_gaps"].max() == 0.0
    # the reference's own agreement IS the served one here, and is partial
    assert (sound["served_accepts"] == sound["ref_accepts"]).all()
    assert 0.2 < sound["ref_accepts"].mean() < 0.95
    assert (R.served_gaps(params, TINY, seq, P, T - P, PAD)
            == sound["gaps"]).all()
    control = R.served_check(params, TINY, seq, P, T - P, PAD, drafts=drafts,
                             control=True)
    # the embedding carries most of a logit here, so int8 flips few
    # tokens; those it flips lie well past the tiny cell's limits
    # (read: max 7.3e-3, draft mean 4.1e-4 against limits 1e-3 and 1e-4)
    assert control["gaps"].max() > 5 * 1e-3
    assert control["draft_gaps"].mean() > 2 * 1e-4
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 256
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0
    wrong = dict(drafts)
    wrong[P + 3] = (wrong[P + 3] + 1) % 256
    assert R.served_check(params, TINY, seq, P, T - P, PAD,
                          drafts=wrong)["draft_gaps"][3] > 0.0


def test_weights_are_a_pure_function_of_the_seed():
    a, b = R.make_params(TINY, 2**31 + 5), R.make_params(TINY, 2**31 + 5)
    c = R.make_params(TINY, 2**31 + 6)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["mtp_eh_w"] == c["mtp_eh_w"]).all()
    # W_eh's embedding half is a I + noise; the hidden half is noise
    eh = np.asarray(a["mtp_eh_w"])
    assert abs(np.diag(eh[:32]).mean() - 2.5) < 0.1
    assert abs(np.diag(eh[32:]).mean()) < 0.1


def test_a_cycle_is_told_from_a_sequence():
    rng = np.random.RandomState(0)
    assert not glm_serve.cycles(rng.randint(0, 1000, 500))
    assert glm_serve.cycles(np.tile(rng.randint(0, 1000, 17), 40))
    assert not glm_serve.cycles(np.tile(rng.randint(0, 1000, 100), 5))


# -- a whole run at a tiny size ---------------------------------------------------


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
    _write(b / "configs" / "tiny-glm.json", dict(TINY, engine=ENGINE))
    _write(b / "traffic" / "tiny-fixed.json", {
        "generator": "open_loop", "schedule_seed": 27182, "max_total": 63,
        "prompt_len": {"dist": "log_uniform", "lo": 9, "hi": 24},
        "output_len": {"dist": "log_uniform", "lo": 12, "hi": 36}})
    _write(b / "workloads" / "tiny-glm.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 40, "gap_max": 1e-3,
                   "gap_mean": 1e-4, "min_drafts_checked": 20,
                   "draft_gap_mean": 1e-4, "accept_diff_max": 0.02}})
    cells = ["tiny-glm"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-glm",
                     "file": "benchmark/configs/tiny-glm.json"}],
        "workloads": [{"name": "tiny-glm", "config": "tiny-glm",
                       "traffic": "tiny-fixed", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("moe_experts_touched_share", "ratio"),
                         ("mtp_accept_rate", "ratio"),
                         ("mtp_tokens_per_step", "tokens/step"),
                         ("mtp_draft_dev_us_step", "us"),
                         ("mla_paged_attn_roofline", "%"),
                         ("moe_expert_roofline", "%"))],
    })
    monkeypatch.setattr(harness, "HERE", b)
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(xplane, "DEVICE_PLANE", r"^/host:CPU$")
    monkeypatch.setattr(xplane, "OPS_LINE", r"^tf_XLA")
    return tmp_path


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_end_to_end(tiny_root, capsys):
    for seed in ("2147483999", "3000000017"):
        assert run.main(["--workload", "tiny-glm", "--seed", seed,
                         "--seconds", "3", "--trace", "0"]) == 0
        out = capsys.readouterr().out
        line = json.loads(out.strip().splitlines()[-1])
        assert line["correct"] is True and line["failed"] == 0, out
        assert line["attempted"] == 12
        assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
        for name in ("draft_logit_gap_mean", "mtp_accept_rate_diff",
                     "drafts_checked", "served_logit_gap_max"):
            assert f"compared {name} = " in out


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-glm", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    m = line["metrics"]
    assert 0.1 < m["mtp_accept_rate"]["value"] < 1
    assert 1.1 < m["mtp_tokens_per_step"]["value"] < 2
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"mla_paged_attn_roofline", "moe_expert_roofline",
                "mtp_draft_dev_us_step"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_a_step_that_accepts_every_draft_is_not_correct(
        tiny_root, capsys, monkeypatch):
    """The acceptance is ``y0 == d`` on the device.  A loop that takes
    every draft unchecked emits the token that follows a WRONG token and
    leaves the wrong token's row in every cache: the served tokens fall
    below the reference's best and the run is not correct."""
    import jax.numpy as jnp

    real = jnp.logical_and

    def accept_all(a, b):
        # the loop's only logical_and is (y0 == d) & (owes more than one)
        return real(jnp.ones_like(a), b)

    from distributed_llm_scheduler_tpu.backends import decode_loop

    monkeypatch.setattr(decode_loop.jnp, "logical_and", accept_all)
    assert run.main(["--workload", "tiny-glm", "--seed", "21",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "served_logit_gap_max" in out and "NOT CORRECT" in out


def test_drafts_the_engine_did_not_verify_are_not_correct(
        tiny_root, capsys, monkeypatch):
    """The drafts compared are the served path's own: shifted by one
    token id on their way to the check, their gap in the reference draft
    module's logits and the acceptance both fall outside the limits."""
    real = glm_serve.Drafts.__call__

    def shifted(self, stats, rids, lengths, owed):
        stats = dict(stats, mtp_drafts=(stats["mtp_drafts"] + 1) % 256)
        return real(self, stats, rids, lengths, owed)

    monkeypatch.setattr(glm_serve.Drafts, "__call__", shifted)
    assert run.main(["--workload", "tiny-glm", "--seed", "22",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "compared draft_logit_gap_mean" in out
    assert out.count("NOT CORRECT") >= 2


def test_the_new_metrics_reduce_a_trace():
    """``mtp_draft_dev_us_step`` through ``op_in_module``: the ops whose
    names begin ``_mtp_`` inside ``jit_seg`` events, per step; the main
    model's kernels of the same families are not counted."""
    from benchmark.metrics.readers import op_in_module

    how = json.loads((harness.HERE / "metrics"
                      / "mtp_draft_dev_us_step.json").read_text())
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_seg(1)", 0.0, 1000.0], ["jit__fn(2)", 2000.0, 500.0]]},
        {"name": "XLA Ops", "events": [
            ["_mla_paged_flash.1", 10.0, 50.0],
            ["_mtp_mla_paged_flash.1", 100.0, 40.0],
            ["_moe_experts.2", 200.0, 300.0],
            ["_mtp_moe_experts.2", 600.0, 120.0],
            ["_mtp_moe_experts.3", 2100.0, 99.0]]}]}
    ctx = {"trace": {"planes": [plane]}, "n_devices": 1,
           "config": {"engine": {"seg_steps": 8}}}
    assert op_in_module.read(ctx, how["params"]) == pytest.approx(
        (40 + 120) / 8 * 1e-3)

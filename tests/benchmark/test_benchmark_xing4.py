"""The Xing4.0 cell's files, its pinned schedule, its reference's control
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
from benchmark.reference import xing4 as R  # noqa: E402
from benchmark.runners import xing4_serve  # noqa: E402
from benchmark.traffic import open_loop  # noqa: E402

TINY = {
    "source": "test", "runner": "xing4_serve", "reference": "xing4",
    "hidden_size": 64, "hc_mult": 4, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "intermediate_size": 128, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 2048,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "routed_scaling_factor": 2, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "max_position_embeddings": 256, "n_group": 1, "topk_group": 1,
    "dtype": "float32", "init": {"std": 0.1, "q_gain": 3.0},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 8, "n_pages": 33,
          "seg_steps": 4, "chunk_tokens": 8, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("xing-longctx")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "xing4-29b-a4b-serve", "longctx-fixed", 1)
    assert harness.load_runner(cell) is xing4_serve
    assert harness.load_reference(cell.config) is R
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_mean", "setup_s"}
    new = {"mla_paged_attn_roofline", "moe_expert_roofline",
           "moe_experts_touched_share", "moe_pick_imbalance",
           "hc_maps_dev_us_step"}
    names = {m["name"] for m in cell.per_layer}
    assert new <= names and "paged_attn_roofline" not in names
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    geo = cell.config["engine"]
    assert geo["n_pages"] == geo["slots"] * geo["pages_per_seq"] + 1
    assert (geo["pages_per_seq"] * geo["page_size"]
            >= cell.traffic["max_total"])


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"]
                 if c["name"] == "xing4-29b-a4b-serve")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 0)
    published = {
        "hidden_size": 3584, "num_attention_heads": 32, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "vocab_size": 131072, "ep_size": 1,
        "routed_scaling_factor": 2, "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"]["factor"] == 64
    assert {"mhc_maps", "mhc_sinkhorn", "mhc_entry_exit", "rope", "routing",
            "cache_row", "engine", "init"} <= set(cfg["assumed"])
    assert "8-stage pipeline" in cfg["deployment"]


def test_the_schedule_is_the_same_for_every_seed_and_the_tokens_are_not():
    cell = harness.load_cell("xing-longctx")
    rate = float(cell.params["rate_rps"])
    a = xing4_serve.schedule(cell.traffic, rate, 51.0)
    b = xing4_serve.schedule(cell.traffic, rate, 51.0)
    assert a == b and len(a) >= 30
    # nothing of --seed reaches it: the generator called with another
    # seed gives another order, so the pin is the file's schedule_seed
    assert a == open_loop.generate(cell.traffic, rate, 51.0, 12345)
    assert a != open_loop.generate(cell.traffic, rate, 51.0, 12346)
    lo, hi = (cell.traffic["prompt_len"][k] for k in ("lo", "hi"))
    assert all(lo <= r.prompt_len <= hi
               and r.prompt_len + r.max_new_tokens
               <= cell.traffic["max_total"] for r in a)
    v = int(cell.config["vocab_size"])
    t1 = open_loop.prompt_token_ids(a[0].rid, a[0].prompt_len, v, 3000000001)
    t2 = open_loop.prompt_token_ids(a[0].rid, a[0].prompt_len, v, 3000000002)
    assert t1.shape == t2.shape and (t1 != t2).mean() > 0.99


# -- the reference and its int8 control ----------------------------------------

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
    """Limits that sound float32 output meets by orders of magnitude
    (its gaps are exactly 0) and the int8 forward does not: a tenth of
    the control's smallest reading here (0.15 widest, 0.012 mean)."""
    params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2   # context-sensitive
    sound = R.served_gaps(params, TINY, seq, P, T - P, PAD)
    assert sound.max() == 0.0
    control = R.served_gaps(params, TINY, seq, P, T - P, PAD, control=True)
    limits = {"gap_max": 0.015, "gap_mean": 0.0012}
    assert sound.max() <= limits["gap_max"] < control.max()
    assert sound.mean() <= limits["gap_mean"] < control.mean()
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0


def test_weights_are_a_pure_function_of_the_seed():
    cfg = dict(TINY, num_hidden_layers=1)
    a, b = R.make_params(cfg, 2**31 + 5), R.make_params(cfg, 2**31 + 5)
    c = R.make_params(cfg, 2**31 + 6)
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    assert (np.asarray(a["wte"]) != np.asarray(c["wte"])).any()


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
    _write(b / "configs" / "tiny-xing.json", dict(TINY, engine=ENGINE))
    _write(b / "traffic" / "tiny-fixed.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 64,
        "prompt_len": {"dist": "log_uniform", "lo": 9, "hi": 40},
        "output_len": {"dist": "log_uniform", "lo": 4, "hi": 12}})
    _write(b / "workloads" / "tiny-xing.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    cells = ["tiny-xing"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-xing",
                     "file": "benchmark/configs/tiny-xing.json"}],
        "workloads": [{"name": "tiny-xing", "config": "tiny-xing",
                       "traffic": "tiny-fixed", "chips": 1}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": n, "unit": u, "moves": "tpot_ms_mean", "workloads": cells}
            for n, u in (("tpot_ms_p90", "ms"), ("window_tok_s", "tokens/s"),
                         ("kv_live_block_share", "ratio"),
                         ("moe_experts_touched_share", "ratio"),
                         ("moe_pick_imbalance", "ratio"),
                         ("mla_paged_attn_roofline", "%"),
                         ("moe_expert_roofline", "%"),
                         ("hc_maps_dev_us_step", "us"))],
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


def test_cell_end_to_end_and_its_schedule_ignores_the_seed(tiny_root, capsys):
    lines = []
    for seed in ("2147483999", "3000000017"):
        assert run.main(["--workload", "tiny-xing", "--seed", seed,
                         "--seconds", "3", "--trace", "0"]) == 0
        lines.append(_last_line(capsys))
    for line in lines:
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] == 12
        assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-xing", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    m = line["metrics"]
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert m["moe_pick_imbalance"]["value"] >= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"mla_paged_attn_roofline", "moe_expert_roofline",
                "hc_maps_dev_us_step"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_a_served_token_altered_is_not_correct(tiny_root, capsys, monkeypatch):
    from distributed_llm_scheduler_tpu.backends import decode_loop

    real = decode_loop.PagedDecodeEngine._retire

    def retire_with_one_wrong_token(self, s):
        rid = self._slot_req[s]
        if not str(rid).startswith("warm"):
            toks = self._tokens[rid]
            toks[-1] = (toks[-1] + 1) % self.config.vocab_size
        return real(self, s)

    monkeypatch.setattr(decode_loop.PagedDecodeEngine, "_retire",
                        retire_with_one_wrong_token)
    assert run.main(["--workload", "tiny-xing", "--seed", "21",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "served_logit_gap_max" in out and "NOT CORRECT" in out


def test_the_new_readers_reduce_a_trace():
    """``op_in_module`` on hand-made planes: ops of a kernel count only
    inside the module events named, per event and unit, or as a share of
    the roofline from the new cost module."""
    from benchmark.metrics.readers import op_in_module

    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_seg(1)", 0.0, 1000.0], ["jit__fn(2)", 2000.0, 1000.0],
            ["jit_seg(1)", 4000.0, 1000.0]]},
        {"name": "XLA Ops", "events": [
            ["_hc_maps.1", 100.0, 10.0], ["_hc_maps.2", 500.0, 30.0],
            ["_hc_maps.1", 2100.0, 500.0], ["_hc_maps.1", 4100.0, 20.0],
            ["_moe_experts.1", 200.0, 100.0],
            ["_moe_experts.1", 2200.0, 700.0]]}]}]}
    ctx = {"trace": trace, "n_devices": 1, "device_kind": "TPU v5 lite",
           "config": {"engine": {"seg_steps": 2}, "hidden_size": 3584,
                      "moe_intermediate_size": 1024, "dtype": "bfloat16"},
           "slice": (0.0, 10.0), "spans": [
               {"type": "span", "name": "segment", "t0": 1.0, "t1": 2.0,
                "args": {"experts_touched": 2.0}}]}
    per_step = op_in_module.read(ctx, {
        "pattern": "^_hc_maps", "within": "^jit_seg",
        "per_event": ["engine", "seg_steps"], "scale": 0.001})
    assert per_step == pytest.approx((10 + 30 + 20) / 2 / 2 * 1e-3)
    share = op_in_module.read(ctx, {
        "pattern": "^_moe_experts", "within": "^jit_seg",
        "costs": "costs_latent", "cost": "moe_expert_bytes",
        "peak": "hbm_bytes_s"})
    least_s = 2.0 * 3 * 3584 * 1024 * 2 / 819e9
    assert share == pytest.approx(100 * least_s / 100e-9)
    assert op_in_module.read(dict(ctx, trace=None), {}) is None
    assert op_in_module.read(ctx, {"pattern": "^absent", "within": "^jit_seg"}
                             ) is None

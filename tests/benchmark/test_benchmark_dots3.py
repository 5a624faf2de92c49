"""The dots3 cell's files, its reference's control, its selection
overlap and a whole run at a tiny size on the CPU — all found by name,
with no edit to a benchmark file that was there.

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
from benchmark.reference import dots3 as R  # noqa: E402
from benchmark.runners import dots3_serve, xing4_serve  # noqa: E402

PERIOD = ["full_attention", "full_attention", "sliding_attention",
          "sliding_attention", "sliding_attention"]
TINY = {
    "source": "test", "runner": "dots3_serve", "reference": "dots3",
    "hidden_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "layer_types": PERIOD + PERIOD[1:], "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "rope_theta": 10000, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": 16, "swa_num_attention_heads": 2,
    "swa_q_lora_rank": 16, "swa_kv_lora_rank": 24,
    "swa_qk_nope_head_dim": 12, "swa_qk_rope_head_dim": 4,
    "swa_v_head_dim": 8, "swa_rope_theta": 1000, "sliding_window_size": 9,
    "apply_mla_qkv_lora_rescale": True, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_router_outputs": 8, "held_experts": [1, 2, 5, 6],
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "max_position_embeddings": 256, "vocab_size": 2048, "dtype": "float32",
    "init": {"std": 0.3, "q_gain": 1.0},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 8, "n_pages": 33,
          "ring_pages": 2, "seg_steps": 4, "chunk_tokens": 8,
          "admission": "slo", "scheduler": "heft", "attention_impl": "xla"}


def test_the_cells_files_load_by_name():
    cell = harness.load_cell("dots3-longctx")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "dots3-note-prev-ep8", "longctx-reason-fixed", 1)
    assert harness.load_runner(cell) is dots3_serve
    assert harness.load_reference(cell.config) is R
    assert {m["name"] for m in cell.end_to_end} == {"tpot_ms_mean", "setup_s"}
    new = {"dsa_index_roofline", "dsa_sparse_attn_roofline",
           "dsa_select_dev_us_step", "dsa_selected_share",
           "swa_latent_attn_roofline"}
    names = {m["name"] for m in cell.per_layer}
    assert new <= names and "moe_expert_roofline" in names
    # their costs count every live row: not this cell's
    assert not {"mla_paged_attn_roofline", "paged_attn_roofline"} & names
    for name in names:      # each has its data file and its reader
        how = json.loads((harness.HERE / "metrics" / f"{name}.json").read_text())
        harness._module(f"metrics/readers/{how['reader']}")
    geo, t = cell.config["engine"], cell.traffic
    assert geo["n_pages"] == geo["slots"] * geo["pages_per_seq"] + 1
    assert geo["pages_per_seq"] * geo["page_size"] >= t["max_total"]
    assert (geo["ring_pages"] * geo["page_size"]
            >= cell.config["sliding_window_size"])
    # every prompt is at least twice the selection: no request bypasses it
    assert t["prompt_len"]["lo"] >= 2 * cell.config["index_topk"]
    assert t["output_len"]["hi"] <= R.ROW_WINDOW


def test_the_configuration_is_the_catalogs_but_for_its_three_cuts():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"]
                 if c["name"] == "dots3-note-prev-ep8")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 32, 19008)
    assert cfg["vocab_size"] * 8 == 152064 and cfg["n_router_outputs"] == 256
    assert cfg["held_experts"] == list(range(32))
    published = {
        "hidden_size": 5120, "num_attention_heads": 128, "q_lora_rank": 1024,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
        "index_topk": 2048, "swa_num_attention_heads": 64,
        "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_v_head_dim": 128, "sliding_window_size": 513,
        "intermediate_size": 13824, "moe_intermediate_size": 1536,
        "n_shared_experts": 1, "num_experts_per_tok": 8,
        "first_k_dense_replace": 1, "rope_theta": 80000000,
        "swa_rope_theta": 50000, "routed_scaling_factor": 1,
        "max_position_embeddings": 524288}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 46       # the published list, whole
    assert cfg["layer_types"][:5] == PERIOD
    assert {"apply_mla_qkv_lora_rescale", "indexer", "attention_gate_type",
            "sliding_window_size", "rope", "routing", "cache_rows", "engine",
            "init"} <= set(cfg["assumed"])
    assert "8 chips share each layer" in cfg["deployment"]


def test_the_configuration_holds_4087_million_parameters():
    """The issue's count, the reference's and the program's
    ``param_shapes`` agree: 4,087 M parameters, 8.2 GB in bf16."""
    from distributed_llm_scheduler_tpu.models import dots3

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "dots3-note-prev-ep8.json").read_text())
    n = R.param_count(cfg)
    assert abs(n - 4087e6) < 1e6
    shapes = dots3.param_shapes(dots3_serve.model_config(cfg))
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == n
    assert 8.1e9 < 2 * n < 8.3e9


def test_the_schedule_is_the_files_own():
    cell = harness.load_cell("dots3-longctx")
    rate = float(cell.params["rate_rps"])
    a = dots3_serve.schedule(cell.traffic, rate, 51.0)
    assert a == xing4_serve.schedule(cell.traffic, rate, 51.0)
    lo, hi = (cell.traffic["prompt_len"][k] for k in ("lo", "hi"))
    assert all(lo <= r.prompt_len <= hi
               and r.prompt_len + r.max_new_tokens
               <= cell.traffic["max_total"] for r in a)


# -- the reference, its selection and its int8 control -------------------------

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
    sound, picked, judged = R.served_gaps(
        params, TINY, seq, P, T - P, PAD, selections=True)
    assert sound.max() == 0.0 and judged is None
    # every query keeps exactly min(t + 1, index_topk) rows, none after it
    assert picked.shape == (2, T - P, PAD)
    assert (picked.sum(-1) == 16).all()
    assert not picked[:, 0, P:].any()
    control, _, low = R.served_gaps(
        params, TINY, seq, P, T - P, PAD, control=True, selections=True)
    assert control.max() > 10 * 1e-3 and control.mean() > 10 * 1e-4
    assert 0.5 < (picked & low).sum() / picked.sum() < 1.0
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 2048
    assert R.served_gaps(params, TINY, bad, P, T - P, PAD)[5] > 0.0


def test_the_served_selection_is_the_references(greedy):
    """The rows the engine's decode steps read (off its ``stats_probe``)
    against the reference's selection at the same positions."""
    import jax

    params, seq = greedy
    _, picked, _ = R.served_gaps(
        params, TINY, seq, P, T - P, PAD, selections=True)
    eng = dots3_serve.build_engine(
        dict(TINY, engine=dict(ENGINE, pages_per_seq=10, n_pages=41)),
        jax.devices()[0], params)
    eng.stats_probe = sel = dots3_serve.Selections()
    eng.submit("r", seq[None, :P], T - P)
    assert (eng.run()["r"] == seq[P:]).all()
    seen, mine = sel.masks("r", P - 1, T - P, PAD)
    # every position but the first, which is the chunk program's
    assert not seen[0] and seen[1:].all()
    assert mine.shape == picked.shape
    assert (mine.sum(-1)[:, seen] == 16).all()
    assert (mine & picked)[:, seen].sum() / picked[:, seen].sum() > 0.99


def test_a_context_under_the_selection_attends_everything():
    """With ``index_topk`` past the context the selection keeps every
    row and the layer is plain MLA: the indexer's weights do not matter."""
    cfg = dict(TINY, index_topk=4096)
    params = R.make_params(cfg, 5)
    other = dict(params)
    for k in params:
        if "_idx_" in k and k.endswith("_w"):
            other[k] = params[k] * -3.0
    ids = np.random.RandomState(1).randint(1, 2048, size=(1, 48))
    a, b = R.logits(params, cfg, ids), R.logits(other, cfg, ids)
    assert np.abs(np.asarray(a - b)).max() == 0.0
    c = R.logits(other, TINY, ids)        # index_topk 16 < 48: they do
    assert np.abs(np.asarray(a - c)).max() > 1e-3


def test_the_references_blocks_do_not_change_its_numbers(monkeypatch):
    """Query blocks, head groups and a sliding layer's key slice are how
    long sequences fit; they are not part of the mathematics."""
    params = R.make_params(TINY, 9)
    ids = np.random.RandomState(2).randint(1, 2048, size=(1, 64))
    whole = np.asarray(R.logits(params, TINY, ids))
    monkeypatch.setattr(R, "Q_BLOCK", 16)
    monkeypatch.setattr(R, "HEAD_GROUP", 2)
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
    _write(b / "configs" / "tiny-dots.json", dict(TINY, engine=ENGINE))
    _write(b / "traffic" / "tiny-fixed.json", {
        "generator": "open_loop", "schedule_seed": 12345, "max_total": 64,
        "prompt_len": {"dist": "log_uniform", "lo": 20, "hi": 40},
        "output_len": {"dist": "log_uniform", "lo": 6, "hi": 20}})
    _write(b / "workloads" / "tiny-dots.json", {
        "rate_rps": 4.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4, "selection_overlap_min": 0.99}})
    cells = ["tiny-dots"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "tiny-dots",
                     "file": "benchmark/configs/tiny-dots.json"}],
        "workloads": [{"name": "tiny-dots", "config": "tiny-dots",
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
                         ("dsa_selected_share", "ratio"),
                         ("dsa_index_roofline", "%"),
                         ("dsa_sparse_attn_roofline", "%"),
                         ("dsa_select_dev_us_step", "us"),
                         ("swa_latent_attn_roofline", "%"),
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
    assert run.main(["--workload", "tiny-dots", "--seed", "3000000017",
                     "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    assert "compared selection_overlap = 1.0" in out


def test_cell_traced_reads_the_program_counters(tiny_root, capsys):
    assert run.main(["--workload", "tiny-dots", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    m = line["metrics"]
    # contexts of 20-60 rows against a selection of 16: well under 1
    assert 0.2 < m["dsa_selected_share"]["value"] < 0.9
    assert 0 < m["moe_experts_touched_share"]["value"] <= 1
    assert 0 < m["kv_live_block_share"]["value"] <= 1
    # device-trace metrics find no TPU module line on this trace: left out
    assert not {"dsa_index_roofline", "dsa_sparse_attn_roofline",
                "dsa_select_dev_us_step", "swa_latent_attn_roofline",
                "moe_expert_roofline"} & set(m)
    assert line["device"]["busy_s"] > 0


def test_a_broken_selection_is_not_correct(tiny_root, capsys, monkeypatch):
    """The decode step's selection made to keep the WORST rows: the
    served tokens leave the reference's."""
    import jax

    from distributed_llm_scheduler_tpu.models import dots3

    def worst(scores, lengths, top_k):
        k = min(int(top_k), scores.shape[1])
        masked = jax.numpy.where(jax.numpy.isinf(scores), scores, -scores)
        _, idx = jax.lax.top_k(masked, k)
        return idx.astype("int32"), jax.numpy.minimum(lengths + 1, k)

    monkeypatch.setattr(dots3, "dsa_select", worst)
    assert run.main(["--workload", "tiny-dots", "--seed", "21",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "served_logit_gap" in out and "NOT CORRECT" in out
    # and the rows the decode steps read are not the reference's
    overlap = next(ln for ln in out.splitlines()
                   if "compared selection_overlap" in ln)
    assert overlap.endswith("NOT CORRECT")


@pytest.mark.parametrize("how", ["nothing_read_off_the_engine",
                                 "rows_beyond_the_references"])
def test_a_served_selection_that_differs_is_not_correct(
        tiny_root, capsys, monkeypatch, how):
    if how == "nothing_read_off_the_engine":
        monkeypatch.setattr(dots3_serve.Selections, "__call__",
                            lambda self, *a: None)
        low, high = 0.0, 0.0
    else:       # up to twice the rows, every one of the reference's among them
        masks = dots3_serve.Selections.masks

        def wider(self, *a):
            seen, picked = masks(self, *a)
            return seen, picked | np.roll(picked, 1, axis=-1)

        monkeypatch.setattr(dots3_serve.Selections, "masks", wider)
        low, high = 0.5, 0.9
    assert run.main(["--workload", "tiny-dots", "--seed", "21",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    said = next(ln for ln in out.splitlines()
                if "compared selection_overlap" in ln)
    assert low <= float(said.split("= ")[1].split(" ")[0]) <= high
    assert said.endswith("NOT CORRECT")


def test_the_new_readers_and_costs_reduce_a_trace():
    """``op_gap_in_module`` and the cost functions on hand-made planes
    and rows."""
    from benchmark import costs_dots3
    from benchmark.metrics.readers import op_gap_in_module, op_in_module

    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_seg(1)", 0.0, 1000.0], ["jit__fn(2)", 2000.0, 1000.0]]},
        {"name": "XLA Ops", "events": [
            ["_dsa_index.1", 100.0, 10.0], ["sort.3", 120.0, 50.0],
            ["_dsa_sparse_attn.1", 200.0, 20.0],
            ["_dsa_index.2", 500.0, 10.0],
            ["_dsa_sparse_attn.2", 550.0, 20.0],
            ["_dsa_index.1", 2100.0, 10.0],
            ["_dsa_sparse_attn.1", 2900.0, 10.0]]}]}]}
    cfg = {"engine": {"seg_steps": 2}, "index_head_dim": 128,
           "index_topk": 2048, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
           "swa_kv_lora_rank": 1024, "swa_qk_rope_head_dim": 64,
           "sliding_window_size": 513, "dtype": "bfloat16"}
    rows = [{"t_first": 0.5, "t_retire": None, "prompt_len": 5000,
             "max_new_tokens": 100, "deliveries": [(0.8, 8)]},
            {"t_first": 5.0, "t_retire": None, "prompt_len": 9000,
             "max_new_tokens": 100, "deliveries": []}]
    ctx = {"trace": trace, "n_devices": 1, "device_kind": "TPU v5 lite",
           "config": cfg, "slice": (0.0, 10.0), "spans": [],
           "records": rows, "slice_segments": [(1.0, 2.0)]}
    gap = op_gap_in_module.read(ctx, {
        "after": "^_dsa_index", "before": "^_dsa_sparse_attn",
        "within": "^jit_seg", "per_event": ["engine", "seg_steps"],
        "scale": 0.001})
    assert gap == pytest.approx(((200 - 110) + (550 - 510)) / 1 / 2 * 1e-3)
    assert op_gap_in_module.read(dict(ctx, trace=None), {}) is None
    # one request decodes at positions 5009 and 5010; the other not yet
    assert costs_dots3.dsa_index_bytes(ctx) == (5009 + 5010) / 2 * 256
    assert costs_dots3.dsa_sparse_attention_bytes(ctx) == 2048 * 1152
    assert costs_dots3.swa_latent_attention_bytes(ctx) == 513 * 2176
    share = op_in_module.read(ctx, {
        "pattern": "^_dsa_index", "within": "^jit_seg",
        "costs": "costs_dots3", "cost": "dsa_index_bytes",
        "peak": "hbm_bytes_s"})
    assert share == pytest.approx(100 * (5009.5 * 256 / 819e9) / 10e-9)

"""The whole command, end to end, on the CPU at a tiny size — and the
proof that a configuration, a cell, a traffic mix and a metric are added
as files plus appended entries, with no edit to a file that is there.

The command itself refuses anything but a TPU; the platform override
lives here, in the test."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, run, xplane  # noqa: E402

TINY = {
    "source": "test", "reference": "gpt2", "activation_function": "gelu_new",
    "layer_norm_epsilon": 1e-05, "n_embd": 64, "n_head": 4, "n_layer": 2,
    "n_positions": 64, "vocab_size": 512, "dtype": "float32",
    "init": {"std": 0.02, "qk_gain": 6.0, "attn_proj_gain": 16.0},
}
ENGINE = {"slots": 4, "page_size": 8, "pages_per_seq": 8, "n_pages": 33,
          "seg_steps": 4, "chunk_tokens": 8, "admission": "slo",
          "scheduler": "heft", "attention_impl": "xla"}


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture()
def tiny_root(tmp_path, monkeypatch):
    """A throw-away checkout: a copy of ``benchmark/`` as committed, plus
    two configurations, two cells, two mixes and one new metric with a
    reader of its own — every one of them only a new file — and a
    ``BENCHMARK.json`` with entries for them.  The harness finds them the
    way a run finds the committed ones: by name, under its directory."""
    import shutil

    import benchmark.metrics.readers as readers_pkg

    b = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(b): p.read_bytes()
              for p in b.rglob("*") if p.is_file()}
    _write(b / "configs" / "tiny-serve.json",
           dict(TINY, runner="serve", engine=ENGINE))
    _write(b / "configs" / "tiny-dag.json", dict(TINY, runner="dag"))
    _write(b / "traffic" / "tiny-chat.json", {
        "generator": "open_loop", "max_total": 64,
        "prompt_len": {"dist": "log_uniform", "lo": 9, "hi": 24},
        "output_len": {"dist": "log_uniform", "lo": 4, "hi": 12}})
    _write(b / "traffic" / "tiny-fwd.json", {
        "generator": "closed_loop", "batch": 4, "seq_len": 32,
        "microbatches": 2, "policy": "heft"})
    _write(b / "workloads" / "tiny-chat.json", {
        "rate_rps": 6.0, "slo_ttft_s": 60.0, "drain_s": 60.0,
        "trace_seconds": 1.0, "check_requests": 4,
        "limits": {"min_tokens_checked": 8, "gap_max": 1e-3,
                   "gap_mean": 1e-4}})
    _write(b / "workloads" / "tiny-dag.json", {
        "trace_seconds": 1.0,
        "limits": {"max_abs": 1e-3, "rel_fro": 1e-4, "top1_gap_mean": 1e-5}})
    _write(b / "metrics" / "requests_due.json",
           {"reader": "count_records", "params": {}})
    (b / "metrics" / "readers" / "count_records.py").write_text(
        "def read(ctx, params):\n"
        "    return len(ctx['records']) if 'records' in ctx else None\n")
    # added, never edited: every file that was there is byte for byte
    assert all((b / rel).read_bytes() == data
               for rel, data in before.items())
    serve_cells, dag_cells = ["tiny-chat"], ["tiny-dag"]
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [
            {"name": "tiny-serve", "file": "benchmark/configs/tiny-serve.json"},
            {"name": "tiny-dag", "file": "benchmark/configs/tiny-dag.json"}],
        "workloads": [
            {"name": "tiny-chat", "config": "tiny-serve",
             "traffic": "tiny-chat", "chips": 1},
            {"name": "tiny-dag", "config": "tiny-dag", "traffic": "tiny-fwd",
             "chips": 2}],
        "end_to_end": [
            {"name": "tpot_ms_mean", "unit": "ms", "workloads": serve_cells},
            {"name": "dag_step_ms", "unit": "ms", "workloads": dag_cells},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "tpot_ms_p90", "unit": "ms", "moves": "tpot_ms_mean",
             "workloads": serve_cells},
            {"name": "seg_host_gap_ms_p50", "unit": "ms",
             "moves": "tpot_ms_mean", "workloads": serve_cells},
            {"name": "window_tok_s", "unit": "tokens/s",
             "moves": "tpot_ms_mean", "workloads": serve_cells},
            {"name": "requests_due", "unit": "count",
             "moves": "tpot_ms_mean", "workloads": serve_cells},
            {"name": "decode_step_dev_ms", "unit": "ms",
             "moves": "tpot_ms_mean", "workloads": serve_cells},
            {"name": "dag_step_ms_p50", "unit": "ms",
             "moves": "dag_step_ms", "workloads": dag_cells},
            {"name": "dispatch_ms", "unit": "ms", "moves": "dag_step_ms",
             "workloads": dag_cells},
            {"name": "launches_step", "unit": "count",
             "moves": "dag_step_ms", "workloads": dag_cells},
            {"name": "transfer_mb_step", "unit": "MB",
             "moves": "dag_step_ms", "workloads": dag_cells}],
    })
    # the harness looks under its own directory, and the package of
    # readers also where the new reader lies: the loader is the run's own
    monkeypatch.setattr(harness, "HERE", b)
    monkeypatch.setattr(readers_pkg, "__path__",
                        [*readers_pkg.__path__, str(b / "metrics" / "readers")])
    # test-only: the CPU stands in for the chip, the host's executor
    # threads for the device plane, and the cache stays where it is
    import jax

    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    monkeypatch.setattr(xplane, "DEVICE_PLANE", r"^/host:CPU$")
    monkeypatch.setattr(xplane, "OPS_LINE", r"^tf_XLA")
    return tmp_path


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_serve_cell_end_to_end(tiny_root, capsys):
    assert run.main(["--workload", "tiny-chat", "--seed", "2147483999",
                     "--seconds", "3", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 18
    assert set(line["metrics"]) == {"tpot_ms_mean", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def test_serve_cell_traced_reads_added_metric(tiny_root, capsys):
    assert run.main(["--workload", "tiny-chat", "--seed", "7",
                     "--seconds", "3", "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    # the metric that exists only as new files is on the line; the one
    # whose reader finds nothing on this trace (no TPU module line) is
    # left out
    assert line["metrics"]["requests_due"] == {"value": 18.0, "unit": "count"}
    assert "tpot_ms_p90" in line["metrics"]
    # the tokens delivered inside the window follow the seed's order, so
    # they are read per layer and carry no bound
    assert line["metrics"]["window_tok_s"]["value"] > 0
    assert "decode_step_dev_ms" not in line["metrics"]
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_dag_cell_end_to_end_two_devices(tiny_root, capsys):
    assert run.main(["--workload", "tiny-dag", "--seed", "11",
                     "--seconds", "2", "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"dag_step_ms", "setup_s"}
    assert line["device"]["count"] == 2


def test_dag_step_time_is_the_whole_window_over_its_steps(
        tiny_root, capsys, monkeypatch):
    """A stall between two steps is in no single step's time, and has to
    be in ``dag_step_ms`` all the same."""
    import time

    monkeypatch.setattr(harness.TraceSlice, "poll",
                        lambda self, now, end: time.sleep(0.05))
    assert run.main(["--workload", "tiny-dag", "--seed", "13",
                     "--seconds", "1", "--trace", "0"]) == 0
    line = _last_line(capsys)
    step = line["metrics"]["dag_step_ms"]["value"]
    assert step >= 50.0
    # from the window's start to the end of its last step: all of it
    assert step * line["attempted"] >= 1000.0 - 1.0


def test_dag_cell_traced(tiny_root, capsys):
    assert run.main(["--workload", "tiny-dag", "--seed", "12",
                     "--seconds", "2", "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    assert {"dag_step_ms_p50", "dispatch_ms", "launches_step",
            "transfer_mb_step"} <= set(line["metrics"])
    assert line["metrics"]["launches_step"]["value"] >= 1


def test_command_refuses_off_tpu(capsys, monkeypatch):
    """The look for a chip unpatched: on this CPU the command exits
    non-zero and prints no result line."""
    monkeypatch.setattr(harness, "configure_jax", lambda: None)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "xl-chat", "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"correct"' not in out


# -- the timed path broken underneath: ``correct`` has to come out false ------


def test_serve_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, capsys, monkeypatch):
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
    assert run.main(["--workload", "tiny-chat", "--seed", "21",
                     "--seconds", "2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert "served_logit_gap_max" in out and "NOT CORRECT" in out


def test_serve_request_cut_short_is_not_correct(tiny_root, capsys,
                                                monkeypatch):
    from distributed_llm_scheduler_tpu.backends import decode_loop

    real = decode_loop.PagedDecodeEngine._retire

    def retire_dropping_a_token(self, s):
        rid = self._slot_req[s]
        if str(rid) == "r0" and len(self._tokens[rid]) > 1:
            self._tokens[rid].pop()
        return real(self, s)

    monkeypatch.setattr(decode_loop.PagedDecodeEngine, "_retire",
                        retire_dropping_a_token)
    run.main(["--workload", "tiny-chat", "--seed", "22", "--seconds", "2",
              "--trace", "0"])
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "requests_with_wrong_token_count = 1" in out


def test_dag_step_that_leaves_out_part_of_the_batch_is_not_correct(
        tiny_root, capsys, monkeypatch):
    from distributed_llm_scheduler_tpu.backends import device

    real = device.DeviceBackend.execute

    def execute_dropping_a_row(self, *a, **kw):
        rep = real(self, *a, **kw)
        rep.output = rep.output.at[-1].set(0)
        return rep

    monkeypatch.setattr(device.DeviceBackend, "execute",
                        execute_dropping_a_row)
    run.main(["--workload", "tiny-dag", "--seed", "23", "--seconds", "1",
              "--trace", "0"])
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert "logits_rel_frobenius" in out

"""Schedule typechecker (analysis/typecheck_pass) + stream prover
(analysis/stream_pass): one golden repro per code (TYP001-TYP003,
STR001-STR003), the `lint --json` schema, and the `precomputed=` gate
reuse (docs/ANALYSIS.md catalogue)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_scheduler_tpu import (
    Cluster,
    DeviceState,
    Task,
    TaskGraph,
    get_scheduler,
)
from distributed_llm_scheduler_tpu.analysis import (
    JSON_SCHEMA,
    AnalysisError,
    Severity,
    analyze,
    analyze_streaming,
    analyze_typecheck,
    pre_execution_gate,
)
from distributed_llm_scheduler_tpu.core.schedule import Schedule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sched(per_node, order=None):
    if order is None:
        order = [t for tids in per_node.values() for t in tids]
    return Schedule(
        policy="manual",
        per_node=per_node,
        assignment_order=order,
        completed=set(order),
    )


def two_caps(cap0=4.0, cap1=4.0):
    return Cluster([DeviceState("n0", cap0), DeviceState("n1", cap1)])


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


# -- TYP001: aval disagreement ----------------------------------------------

def test_typ001_fn_rejects_input_edge():
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], set(), out_shape=f32(4, 4)),
        Task("b", 0.0, 1.0, ["a"], set(),
             fn=lambda p, x: x @ jnp.ones((5, 5), jnp.float32)),
    ]).freeze()
    rep = analyze_typecheck(g)
    (d,) = rep.by_code("TYP001")
    assert d.severity == Severity.ERROR and d.task == "b"
    assert "a" in d.data["args"]
    assert rep.exit_code == 1


def test_typ001_declared_vs_computed():
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], set(), out_shape=f32(4, 4)),
        Task("b", 0.0, 1.0, ["a"], set(),
             fn=lambda p, x: x, out_shape=f32(2, 2)),
    ]).freeze()
    rep = analyze_typecheck(g)
    (d,) = rep.by_code("TYP001")
    assert d.task == "b"
    assert d.data["declared"] != d.data["computed"]


def test_typ001_unknown_inputs_do_not_cascade():
    # "a" has no fn and no out_shape: its aval is unknown; "b" must not
    # be flagged (tolerant degradation), nor "c" downstream of it
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], set()),
        Task("b", 0.0, 1.0, ["a"], set(), fn=lambda p, x: x),
        Task("c", 0.0, 1.0, ["b"], set(), fn=lambda p, x: x),
    ]).freeze()
    assert analyze_typecheck(g).ok


# -- TYP002: quantized-edge dtype legality ----------------------------------

def _qspec(shape=(8, 8)):
    from distributed_llm_scheduler_tpu.utils.quantize import QParam

    return QParam(
        jax.ShapeDtypeStruct(shape, jnp.int8),
        jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32),
    )


def test_typ002_raw_int8_crosses_edge():
    g = TaskGraph([
        Task("qt", 0.0, 1.0, [], {"w"},
             out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int8)),
        Task("c", 0.0, 1.0, ["qt"], set()),
    ]).freeze()
    rep = analyze_typecheck(g, param_specs={"w": _qspec()})
    (d,) = rep.by_code("TYP002")
    assert d.task == "qt" and d.data["consumers"] == ["c"]
    # same graph without QNT metadata: ordinary int8 edge, no finding
    assert analyze_typecheck(g).ok


def test_typ002_narrowing_float_edge():
    g = TaskGraph([
        Task("src", 0.0, 1.0, [], set(), out_shape=f32(4,)),
        Task("qt", 0.0, 1.0, ["src"], {"w"},
             out_shape=jax.ShapeDtypeStruct((4,), jnp.bfloat16)),
    ]).freeze()
    rep = analyze_typecheck(g, param_specs={"w": _qspec()})
    (d,) = rep.by_code("TYP002")
    assert d.data["src_dtype"] == "float32"
    assert d.data["producer"] == "src"


def test_typ002_integer_edges_exempt():
    # argmax-style int32 edge into a quantized task never fires
    g = TaskGraph([
        Task("ids", 0.0, 1.0, [], set(),
             out_shape=jax.ShapeDtypeStruct((4,), jnp.int32)),
        Task("qt", 0.0, 1.0, ["ids"], {"w"},
             out_shape=jax.ShapeDtypeStruct((4,), jnp.bfloat16)),
    ]).freeze()
    assert not analyze_typecheck(
        g, param_specs={"w": _qspec()}
    ).has("TYP002")


# -- TYP003: transfer-byte divergence ---------------------------------------

def test_typ003_cost_model_drift_on_cross_device_edge():
    g = TaskGraph([
        Task("a", 1.0, 1.0, [], set(), out_shape=f32(4, 4)),  # 64 B aval
        Task("b", 0.0, 1.0, ["a"], set()),
    ]).freeze()
    s = sched({"n0": ["a"], "n1": ["b"]})
    rep = analyze_typecheck(g, two_caps(), s)
    (d,) = rep.by_code("TYP003")
    assert d.severity == Severity.WARNING and d.task == "a"
    assert d.data["basis"] == "memory_required"
    assert d.data["charged_gb"] == pytest.approx(1.0)
    assert d.data["consumer"] == "b"
    assert rep.exit_code == 0  # warning never breaks clean
    # co-located: no transfer, no finding
    assert not analyze_typecheck(
        g, two_caps(), sched({"n0": ["a", "b"]})
    ).has("TYP003")
    # out_bytes matching the aval silences it
    g2 = TaskGraph([
        Task("a", 1.0, 1.0, [], set(), out_shape=f32(4, 4), out_bytes=64),
        Task("b", 0.0, 1.0, ["a"], set()),
    ]).freeze()
    assert not analyze_typecheck(g2, two_caps(), s).has("TYP003")


# -- STR001-STR003: stream-safety prover ------------------------------------

def _stream_fixture(cap_gb, *sizes_gb):
    GB = 1 << 30
    tasks, prev = [], []
    for i, s in enumerate(sizes_gb):
        tasks.append(Task(
            f"t{i}", 0.0, 1.0, list(prev), {f"p{i}"},
            param_bytes={f"p{i}": int(s * GB)},
        ))
        prev = [f"t{i}"]
    g = TaskGraph(tasks).freeze()
    cluster = Cluster([DeviceState("n0", cap_gb)])
    return g, cluster, sched({"n0": [t.task_id for t in tasks]})


def test_str001_union_fits():
    rep = analyze_streaming(*_stream_fixture(1.0, 0.3, 0.3))
    (d,) = rep.by_code("STR001")
    assert d.severity == Severity.INFO
    assert d.data["union_gb"] == pytest.approx(0.6)
    assert not rep.has("STR002") and not rep.has("STR003")


def test_str002_prefix_fits():
    rep = analyze_streaming(*_stream_fixture(1.0, 0.6, 0.6))
    (d,) = rep.by_code("STR002")
    assert d.severity == Severity.WARNING and d.task == "t1"
    assert d.data["prefix_tasks"] == 1
    assert d.data["prefix_gb"] == pytest.approx(0.6)
    assert d.data["spill_task"] == "t1" and not rep.has("STR003")


def test_str003_evicts_from_the_first_task():
    rep = analyze_streaming(*_stream_fixture(1.0, 1.5, 0.2))
    (d,) = rep.by_code("STR003")
    assert d.task == "t0" and d.severity == Severity.WARNING
    assert rep.exit_code == 0  # warnings only: streaming still runs


# -- satellite: lint --json --------------------------------------------------

def test_report_to_json_schema():
    g = TaskGraph([
        Task("a", 1.0, 1.0, [], set(), out_shape=f32(4, 4)),
        Task("b", 0.0, 1.0, ["a"], set()),
    ]).freeze()
    s = sched({"n0": ["a"], "n1": ["b"]})
    rep = analyze(g, two_caps(), s)
    payload = rep.to_json()
    assert payload["schema"] == JSON_SCHEMA == "dls.lint/1"
    assert payload["exit_code"] == rep.exit_code
    assert set(payload["counts"]) == {"error", "warning", "info"}
    for d in payload["diagnostics"]:
        assert set(d) == {
            "code", "severity", "message", "task", "node", "param", "data"
        }
        assert d["severity"] in ("error", "warning", "info")
    json.dumps(payload)  # round-trippable, no numpy leakage


def test_cli_lint_json():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", "lint",
         "--json", "--model", "gpt2-tiny"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    payload = json.loads(r.stdout)
    assert payload["schema"] == "dls.lint/1"
    assert payload["exit_code"] == 0


# -- satellite: precomputed gate reuse ---------------------------------------

def test_gate_reuses_precomputed_report():
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], set()),
        Task("b", 0.0, 1.0, ["a"], set()),
    ]).freeze()
    cluster = two_caps()
    s = sched({"n0": ["a"], "n1": ["b"]})
    rep = analyze(g, cluster, s)
    assert rep.schedule_signature == s.signature()
    gated = pre_execution_gate(g, cluster, s, backend="sim", precomputed=rep)
    assert gated is not None and gated.ok
    # stale report (different schedule): silently falls back to fresh
    s2 = sched({"n0": ["a", "b"]})
    assert pre_execution_gate(
        g, cluster, s2, backend="sim", precomputed=rep
    ).ok


def test_gate_precomputed_still_raises_on_errors():
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], set()),
        Task("b", 0.0, 1.0, ["a"], set()),
    ]).freeze()
    cluster = two_caps()
    bad = sched({"n0": ["b", "a"]})  # SCH009: b before its dependency
    rep = analyze(g, cluster, bad)
    assert rep.has("SCH009")
    with pytest.raises(AnalysisError):
        pre_execution_gate(g, cluster, bad, backend="sim", precomputed=rep)


# -- builders x default scheduler stay TYP/STR-clean -------------------------

def test_builders_typecheck_clean():
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_decode_dag,
    )
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny()
    for dag in (
        build_gpt2_dag(cfg, batch=1, seq_len=16),
        build_decode_dag(cfg, batch=2),
    ):
        cluster = Cluster.from_jax_devices(hbm_cap_gb=4.0)
        schedule = get_scheduler("greedy").schedule(dag.graph, cluster)
        rep = analyze(
            dag.graph, cluster, schedule,
            params=dag.param_specs,
            graph_input=dag.input_spec,
        )
        bad = [
            d for d in rep.diagnostics
            if d.code.startswith(("TYP", "STR"))
            and d.severity == Severity.ERROR
        ]
        assert not bad, bad
        assert not rep.has("TYP003"), rep.by_code("TYP003")

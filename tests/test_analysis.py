"""Static-analysis subsystem (analysis/): one failing fixture per pass,
gate behavior on the backends, and a lint smoke test over every frontend
DAG builder x the default scheduler (docs/ANALYSIS.md catalogue)."""

from __future__ import annotations

import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, Task, TaskGraph
from distributed_llm_scheduler_tpu.analysis import (
    CODES,
    AnalysisError,
    Severity,
    analyze,
    analyze_graph,
    analyze_memory,
    analyze_pipeline,
    analyze_quantization,
    analyze_schedule,
    analyze_sharding,
    pre_execution_gate,
)
from distributed_llm_scheduler_tpu.core.schedule import Schedule


def sched(per_node, completed=None, failed=None, order=None):
    if order is None:
        order = [t for tids in per_node.values() for t in tids]
    return Schedule(
        policy="manual",
        per_node=per_node,
        assignment_order=order,
        completed=set(order) if completed is None else completed,
        failed=failed or set(),
    )


# -- pass 1: graph hygiene --------------------------------------------------

def test_graph_pass_cycle():
    g = TaskGraph([
        Task("a", 1.0, 1.0, ["c"], set()),
        Task("b", 1.0, 1.0, ["a"], set()),
        Task("c", 1.0, 1.0, ["b"], set()),
        Task("waiter", 1.0, 1.0, ["c"], set()),
    ])
    rep = analyze_graph(g)
    assert rep.exit_code == 1
    (d,) = rep.by_code("DAG001")
    assert d.severity == Severity.ERROR
    assert set(d.data["tasks"]) == {"a", "b", "c"}
    # the task waiting on the cycle is flagged as blocked, not cyclic
    assert [x.task for x in rep.by_code("DAG004")] == ["waiter"]


def test_graph_pass_dangling_duplicate_negative():
    g = TaskGraph([
        Task("a", -1.0, 1.0, ["ghost"], set()),
        Task("b", 1.0, 1.0, ["a", "a"], set()),
    ])
    rep = analyze_graph(g)
    assert rep.has("DAG002") and rep.has("DAG005")
    assert rep.by_code("DAG003")[0].severity == Severity.WARNING
    assert rep.exit_code == 1


def test_graph_pass_param_sizes():
    g = TaskGraph([
        Task("a", 1.0, 1.0, [], {"p", "q"}, param_bytes={"p": 100}),
        Task("b", 1.0, 1.0, ["a"], {"p"}, param_bytes={"p": 200}),
    ])
    rep = analyze_graph(g)
    assert rep.has("DAG007")           # p: 100 vs 200 bytes
    assert rep.by_code("DAG006")[0].param == "q"
    clean = analyze_graph(TaskGraph([Task("a", 1.0, 1.0, [], {"p"})]))
    assert clean.ok and not clean.has("DAG006")  # no sizes declared at all


# -- pass 2: schedule consistency + memory feasibility ----------------------

def two_caps(cap0=1.0, cap1=1.0):
    return Cluster([DeviceState("n0", cap0), DeviceState("n1", cap1)])


def test_schedule_pass_catches_corruption():
    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 1.0, ["a"], set()),
    ]).freeze()
    rep = analyze_schedule(
        g, two_caps(), sched({"n0": ["b", "a"]})
    )
    assert rep.has("SCH009")  # b ordered before its dependency a
    rep2 = analyze_schedule(
        g, two_caps(), sched({"n0": ["a", "b"], "n1": ["a"], "bogus": []})
    )
    assert rep2.has("SCH001") and rep2.has("SCH003")


def test_memory_pass_overcommit():
    g = TaskGraph([
        Task("big", 5.0, 1.0, [], {"w"}, param_bytes={"w": 2 << 30}),
    ]).freeze()
    rep = analyze_memory(g, two_caps(), sched({"n0": ["big"]}))
    assert rep.exit_code == 1
    (d,) = rep.by_code("MEM003")
    assert d.task == "big" and d.node == "n0"
    assert d.data["own_gb"] > d.data["cap_gb"]


def test_memory_pass_eviction_warning_and_strict():
    # two 0.6 GB params through one 1.0 GB node: each task fits alone,
    # the no-evict residency does not
    nbytes = int(0.6 * (1 << 30))
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], {"p1"}, param_bytes={"p1": nbytes}),
        Task("b", 0.0, 1.0, ["a"], {"p2"}, param_bytes={"p2": nbytes}),
    ]).freeze()
    s = sched({"n0": ["a", "b"]})
    rep = analyze_memory(g, two_caps(), s)
    assert rep.ok and rep.has("MEM002")
    assert rep.by_code("MEM002")[0].severity == Severity.WARNING
    strict = analyze_memory(g, two_caps(), s, strict=True)
    assert strict.exit_code == 1


def test_memory_pass_oversized_param():
    g = TaskGraph([
        Task("a", 0.0, 1.0, [], {"w"}, param_bytes={"w": 8 << 30}),
    ]).freeze()
    rep = analyze_memory(g, two_caps(), sched({"n0": []}, completed=set()))
    assert rep.by_code("MEM004")[0].param == "w"


# -- pass 3: sharding consistency -------------------------------------------

MESH = {"dp": 2, "tp": 4, "sp": 1}


def test_sharding_pass_rank_mismatch():
    # attn_qkv_w expects P(None, "tp") — a 1-D tensor cannot carry it
    rep = analyze_sharding({"attn_qkv_w": (768,)}, MESH, family="gpt2")
    assert rep.exit_code == 1
    assert rep.by_code("SHD002")[0].param == "attn_qkv_w"


def test_sharding_pass_unknown_axis_and_divisibility():
    rep = analyze_sharding(
        {"attn_qkv_w": (768, 2304)}, {"dp": 2}, family="gpt2"
    )
    assert rep.has("SHD001")  # no "tp" axis in the mesh
    rep2 = analyze_sharding(
        {"attn_qkv_w": (768, 2306)}, MESH, family="gpt2"
    )
    assert rep2.has("SHD003")  # 2306 % 4 != 0
    clean = analyze_sharding(
        {"attn_qkv_w": (768, 2304), "ln_f_g": (768,)}, MESH, family="gpt2"
    )
    assert clean.ok


def test_sharding_pass_conflicting_axis_reuse():
    rep = analyze_sharding(
        {"attn_qkv_w": (768, 2304)},
        MESH,
        family="gpt2",
        batch_spec=("tp", None),  # tp shards params AND the batch
    )
    assert rep.has("SHD005")
    assert rep.exit_code == 1


# -- pass 4: pipeline soundness ---------------------------------------------

def chain4():
    return TaskGraph([
        Task("t1", 0.1, 1.0, [], set()),
        Task("t2", 0.1, 1.0, ["t1"], set()),
        Task("t3", 0.1, 1.0, [], set()),
        Task("t4", 0.1, 1.0, ["t3"], set()),
    ]).freeze()


def test_pipeline_pass_deadlock():
    # n0 runs t4 before t1, n1 runs t2 before t3: circular wait
    # t1 -> t2 (dep), t2 -> t3 (n1 order), t3 -> t4 (dep), t4 -> t1 (n0)
    s = sched({"n0": ["t4", "t1"], "n1": ["t2", "t3"]})
    rep = analyze_pipeline(chain4(), s)
    assert rep.exit_code == 1
    (d,) = rep.by_code("PIP002")
    assert set(d.data["tasks"]) == {"t1", "t2", "t3", "t4"}


def test_pipeline_pass_same_node_inversion():
    s = sched({"n0": ["t2", "t1"], "n1": ["t3", "t4"]})
    rep = analyze_pipeline(chain4(), s)
    assert rep.by_code("PIP001")[0].task == "t2"


def test_pipeline_pass_accepts_wrapped_stages():
    # virtual-stage style wrap (stage s on device s % 2) is NOT a deadlock
    s = sched({"n0": ["t1", "t3"], "n1": ["t2", "t4"]})
    assert analyze_pipeline(chain4(), s).ok


# -- pass 5: quantization dtype flow ----------------------------------------

def qgraph(nbytes):
    return TaskGraph([
        Task("a", 0.1, 1.0, [], {"w"}, param_bytes={"w": nbytes}),
    ]).freeze()


def test_quant_pass_dtypes_and_layout():
    from distributed_llm_scheduler_tpu.utils.quantize import QParam

    bad_dtype = {
        "w": QParam(
            q=np.zeros((128, 64), np.float32),     # should be int8
            scale=np.zeros((1, 64), np.float32),
        )
    }
    rep = analyze_quantization(qgraph(1), bad_dtype)
    assert rep.exit_code == 1 and rep.has("QNT001")

    bad_scale = {
        "w": QParam(
            q=np.zeros((128, 64), np.int8),
            scale=np.zeros((7, 7), np.float32),    # no known layout
        )
    }
    rep2 = analyze_quantization(qgraph(1), bad_scale)
    assert rep2.exit_code == 1 and rep2.has("QNT002")


def test_quant_pass_bytes_and_should_quantize():
    from distributed_llm_scheduler_tpu.utils.quantize import (
        QParam,
        qparam_bytes,
    )

    q = np.zeros((128, 64), np.int8)
    spec = {"w": QParam(q=q, scale=np.zeros((1, 64), np.float32))}
    ok = analyze_quantization(qgraph(qparam_bytes(q)), spec)
    assert ok.ok
    wrong = analyze_quantization(qgraph(128 * 64 * 4), spec)
    assert wrong.has("QNT004")

    tiny = {
        "w": QParam(
            q=np.zeros((4, 4), np.int8), scale=np.zeros((1, 4), np.float32)
        )
    }
    rep = analyze_quantization(qgraph(qparam_bytes(tiny["w"].q)), tiny)
    assert rep.ok and rep.has("QNT003")  # warning only


# -- real quantized DAG stays clean -----------------------------------------

def test_quantize_dag_output_lints_clean():
    from distributed_llm_scheduler_tpu.utils.config import RunConfig
    from distributed_llm_scheduler_tpu.utils.quantize import QParam

    dag = RunConfig(model="gpt2-tiny", quantize="int8").build_graph()
    assert any(isinstance(s, QParam) for s in dag.param_specs.values())
    rep = analyze_quantization(dag.graph, dag.param_specs)
    assert rep.ok, rep.render()


# -- pass 7: decode-loop composability ---------------------------------------

def decode_graph(pool_bytes_1=1024):
    """Two-layer decode-ish graph: each layer aliases its own cache pool
    plus the shared page_table (the paged wiring contract)."""
    pb = {"page_table": 64}
    return TaskGraph([
        Task("embed", 0.1, 1.0, [], set()),
        Task("l0", 0.1, 1.0, ["embed"], {"cache_k_0", "page_table"},
             param_bytes={"cache_k_0": 1024, **pb}),
        Task("l1", 0.1, 1.0, ["l0"], {"cache_k_1", "page_table"},
             param_bytes={"cache_k_1": pool_bytes_1, **pb}),
        Task("logits", 0.1, 1.0, ["l1"], set()),
    ])


def test_decode_pass_noop_without_cache_params():
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    g = TaskGraph([Task("a", 0.1, 1.0, [], {"w"})])
    assert analyze_decode(g, two_caps(), sched({"n0": ["a"]})).diagnostics == []


def test_decode_pass_clean_single_node_and_residency_info():
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    g = decode_graph()
    rep = analyze_decode(
        g, two_caps(), sched({"n0": ["embed", "l0", "l1", "logits"]})
    )
    assert rep.ok and not rep.warnings
    (info,) = rep.by_code("DEC004")
    assert info.data["paged"] and info.data["kv_bytes"] == 2048


def test_decode_pass_dec001_cache_alias_across_nodes():
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    g = TaskGraph([
        Task("l0", 0.1, 1.0, [], {"cache_k_0"},
             param_bytes={"cache_k_0": 1024}),
        Task("l1", 0.1, 1.0, ["l0"], {"cache_k_0"},
             param_bytes={"cache_k_0": 1024}),
    ])
    s = sched({"n0": ["l0"], "n1": ["l1"]})
    rep = analyze_decode(g, two_caps(), s)
    (d,) = rep.by_code("DEC001")
    assert d.param == "cache_k_0" and d.data["nodes"] == ["n0", "n1"]
    with pytest.raises(AnalysisError):  # gated on both backends
        pre_execution_gate(g, two_caps(), s, backend="device")


def test_decode_pass_dec002_multi_node_is_warning_only():
    g = decode_graph()
    s = sched({"n0": ["embed", "l0"], "n1": ["l1", "logits"]})
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    rep = analyze_decode(g, two_caps(), s)
    assert rep.ok and rep.has("DEC002")  # dispatchable, scan-ineligible
    assert pre_execution_gate(g, two_caps(), s, backend="device").ok


def test_decode_pass_dec003_wiring():
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    # pools without the table / table without pools
    g = TaskGraph([
        Task("l0", 0.1, 1.0, [], {"cache_k_0"}),
        Task("l1", 0.1, 1.0, ["l0"], {"page_table"}),
    ])
    rep = analyze_decode(g)
    assert {d.task for d in rep.by_code("DEC003")} == {"l0", "l1"}
    # pool geometry mismatch across layers
    rep2 = analyze_decode(decode_graph(pool_bytes_1=2048))
    assert any("geometry" in d.message for d in rep2.by_code("DEC003"))


def _draft_graph(rows, draft=True, sink=True, own_pool=True):
    g = decode_graph()
    tasks = list(g)
    if draft:
        tasks.append(Task(
            "draft", 0.1, 1.0, ["logits"],
            {"page_table", "cache_k_2" if own_pool else "cache_k_1"},
            group="draft"))
        if not sink:
            tasks.append(Task("after", 0.1, 1.0, ["draft"], set()))
    g = TaskGraph(tasks)
    g.rows_per_step = rows
    return g


@pytest.mark.parametrize("kw, says", [
    (dict(rows=2), None),
    (dict(rows=1, draft=False), None),
    (dict(rows=2, draft=False), "0 draft task"),
    (dict(rows=1), "no draft to verify"),
    (dict(rows=2, sink=False), "must be the sink"),
    (dict(rows=2, own_pool=False), "hold a cache pool no layer task reads"),
])
def test_decode_pass_dec003_rows_a_step_and_the_draft_task(kw, says):
    """More than one row a slot a step means drafts, and drafts mean one
    ``draft`` task — the sink, with a pool of its own — and the reverse."""
    from distributed_llm_scheduler_tpu.analysis import analyze_decode

    found = [d for d in analyze_decode(_draft_graph(**kw)).by_code("DEC003")]
    if says is None:
        assert not found
    else:
        (d,) = found
        assert says in d.message and d.data["rows_per_step"] == kw["rows"]


def test_a_family_stepped_with_its_draft_lints_clean_and_budgets_its_rows():
    """The real builder for ``glm4_lite``: no error on one node, and
    DEC006's per-segment budget counts two rows a slot a step."""
    from distributed_llm_scheduler_tpu.analysis import analyze_decode
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import model_config
    from distributed_llm_scheduler_tpu.sched.policies import get_scheduler

    dag = build_paged_decode_dag(model_config("glm4_lite-tiny"), slots=2,
                                 page_size=8, n_pages=9, pages_per_seq=4)
    assert dag.rows_per_step == 2
    cluster = Cluster([DeviceState("n0", 64.0)])
    s = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = analyze_decode(dag.graph, cluster, s)
    assert rep.ok and not rep.has("DEC003")
    over = analyze_decode(dag.graph, cluster, s, chunk_tokens=12,
                          decode_budget=2 * 4 * 1)
    fits = analyze_decode(dag.graph, cluster, s, chunk_tokens=12,
                          decode_budget=2 * 4 * dag.rows_per_step)

    def exceeds(rep):
        return any("exceeds" in d.message for d in rep.by_code("DEC006"))

    assert exceeds(over) and not exceeds(fits)


def test_paged_dag_lints_clean_on_one_node():
    """The real paged builder + a single-node schedule must produce no
    errors or warnings from the decode pass (the engine's own gate)."""
    from distributed_llm_scheduler_tpu.analysis import analyze_decode
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.sched.policies import get_scheduler

    dag = build_paged_decode_dag(GPT2Config.tiny(), slots=2, page_size=4,
                                 n_pages=8, pages_per_seq=4)
    cluster = Cluster([DeviceState("n0", 64.0)])
    s = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = analyze_decode(dag.graph, cluster, s)
    assert rep.ok and not rep.warnings, rep.render()
    assert rep.by_code("DEC004")[0].data["paged"]


# -- mechanical fixes (lint --fix) -------------------------------------------

def test_fix_duplicate_dependencies_preserves_arity():
    from distributed_llm_scheduler_tpu.analysis import (
        fix_duplicate_dependencies,
    )

    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 1.0, ["a", "a"], set()),
    ])
    assert analyze_graph(g).has("DAG003")
    fixed = fix_duplicate_dependencies(g)
    assert fixed == ["b"]
    t = g["b"]
    assert t.dependencies == ["a"]          # edges deduplicated ...
    assert t.arg_tasks == ["a", "a"]        # ... fn call arity pinned
    assert not analyze_graph(g).has("DAG003")
    assert fix_duplicate_dependencies(g) == []  # idempotent


def test_fix_duplicate_dependencies_rebuilds_frozen_edges():
    from distributed_llm_scheduler_tpu.analysis import (
        fix_duplicate_dependencies,
    )

    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 1.0, ["a", "a"], set()),
        Task("c", 0.1, 1.0, ["b"], set()),
    ]).freeze()
    assert fix_duplicate_dependencies(g) == ["b"]
    assert g.topo_order == ["a", "b", "c"]
    assert g.dependents("a") == ["b"]  # stale duplicate edge rebuilt away


def test_fix_per_node_order_repairs_inversions():
    from distributed_llm_scheduler_tpu.analysis import fix_per_node_order

    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 1.0, ["a"], set()),
        Task("c", 0.1, 1.0, ["b"], set()),
    ]).freeze()
    s = sched({"n0": ["b", "a"], "n1": ["c"]})  # PIP001: b before its dep a
    assert analyze_pipeline(g, s).has("PIP001")
    before_placement = dict(s.placement)
    changed = fix_per_node_order(g, s)
    assert changed == ["n0"]
    assert s.per_node["n0"] == ["a", "b"]
    assert s.assignment_order == ["a", "b", "c"]
    assert s.placement == before_placement      # where is untouched
    assert not analyze_pipeline(g, s).has("PIP001")
    assert not analyze_schedule(g, two_caps(), s).has("SCH005")
    assert fix_per_node_order(g, s) == []       # already legal: no-op


def test_fix_per_node_order_none_on_cycle_and_stays_close():
    from distributed_llm_scheduler_tpu.analysis import fix_per_node_order

    cyc = TaskGraph([
        Task("a", 0.1, 1.0, ["b"], set()),
        Task("b", 0.1, 1.0, ["a"], set()),
    ])
    s = sched({"n0": ["b", "a"]})
    snapshot = [list(s.per_node["n0"]), list(s.assignment_order)]
    assert fix_per_node_order(cyc, s) is None   # no legal order exists
    assert [list(s.per_node["n0"]), list(s.assignment_order)] == snapshot

    # tie-break keeps the repaired order as close to the original as a
    # legal order allows: independent x/y keep their relative order
    g = TaskGraph([
        Task("x", 0.1, 1.0, [], set()),
        Task("y", 0.1, 1.0, [], set()),
        Task("z", 0.1, 1.0, ["y"], set()),
    ])
    s2 = sched({"n0": ["z", "x", "y"]})
    assert fix_per_node_order(g, s2) == ["n0"]
    assert s2.per_node["n0"] == ["x", "y", "z"]


# -- cost pass (CST00x): analytic memory vs XLA preflight --------------------

def test_cost_pass_flags_two_sided_divergence():
    from distributed_llm_scheduler_tpu.analysis import analyze_cost

    g = TaskGraph([
        Task("under", 1.0, 1.0, [], set()),
        Task("over", 8.0, 1.0, ["under"], set()),
        Task("fine", 1.0, 1.0, ["under"], set()),
        Task("unmeasured", 1.0, 1.0, ["over"], set()),
    ])
    compiled = {"under": 3.0, "over": 2.0, "fine": 1.5}
    rep = analyze_cost(g, compiled)
    (u,) = rep.by_code("CST001")
    assert u.task == "under" and u.severity == Severity.WARNING
    assert u.data["compiled_gb"] == 3.0 and u.data["factor"] == 2.0
    (o,) = rep.by_code("CST002")
    assert o.task == "over"
    (m,) = rep.by_code("CST003")
    assert m.task == "unmeasured" and m.severity == Severity.INFO
    # warnings only: cost drift degrades placement, it never gates
    assert rep.exit_code == 0


def test_cost_pass_snapshot_and_floor():
    from distributed_llm_scheduler_tpu.analysis import analyze_cost

    # preflight mutated memory_required up to the compiled value; only
    # the analytic_gb snapshot lets the pass still see under-prediction
    g = TaskGraph([Task("t", 3.0, 1.0, [], set())])  # already raised
    rep = analyze_cost(g, {"t": 3.0}, analytic_gb={"t": 1.0})
    assert rep.has("CST001")
    assert not analyze_cost(g, {"t": 3.0}).has("CST001")
    # sub-floor scalar glue never flags, in either direction
    tiny = TaskGraph([Task("s", 1e-6, 1.0, [], set())])
    assert analyze_cost(tiny, {"s": 5e-4}).ok
    assert not analyze_cost(tiny, {}).has("CST003")
    # custom factor widens the accepted band
    g2 = TaskGraph([Task("t", 1.0, 1.0, [], set())])
    assert analyze_cost(g2, {"t": 2.5}).has("CST001")
    assert analyze_cost(g2, {"t": 2.5}, factor=3.0).ok


def test_analyze_wires_compiled_gb_through():
    g = TaskGraph([Task("t", 1.0, 1.0, [], set())])
    rep = analyze(g, compiled_gb={"t": 5.0}, analytic_gb={"t": 1.0})
    assert rep.has("CST001")
    assert analyze(g).ok  # pass only runs when compiled_gb is given


# -- pre-execution gate ------------------------------------------------------

def corrupted():
    g = TaskGraph([
        Task("a", 0.1, 1.0, [], set()),
        Task("b", 0.1, 1.0, ["a"], set()),
    ]).freeze()
    return g, two_caps(), sched({"n0": ["b", "a"]})


def test_gate_raises_on_corruption_sim():
    g, cl, s = corrupted()
    with pytest.raises(AnalysisError) as e:
        pre_execution_gate(g, cl, s, backend="sim")
    assert e.value.report.has("SCH009")
    assert isinstance(e.value, ValueError)


def test_gate_device_is_lenient_where_dispatch_legalizes():
    # dispatch_order legalizes per-node inversions on the device backend;
    # the device gate only rejects hard corruption (here: none)
    g, cl, s = corrupted()
    assert pre_execution_gate(g, cl, s, backend="device") is not None
    bad = sched({"n0": ["a"], "n1": ["a", "b"]})  # duplicate placement
    with pytest.raises(AnalysisError):
        pre_execution_gate(g, cl, bad, backend="device")


def test_gate_env_opt_out(monkeypatch):
    g, cl, s = corrupted()
    monkeypatch.setenv("DLS_SKIP_ANALYSIS", "1")
    assert pre_execution_gate(g, cl, s, backend="sim") is None


def test_sim_backend_runs_the_gate(monkeypatch):
    from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend

    g, cl, s = corrupted()
    with pytest.raises(AnalysisError):
        SimulatedBackend(fidelity="full").execute(g, cl, s)
    # per-instance opt out restores the old (crash-or-garbage) behavior;
    # the replay itself still raises on the unknown-order placement or
    # produces *a* report — either way no AnalysisError
    rep = SimulatedBackend(fidelity="full", pre_analysis=False).execute(
        g, cl, s
    )
    assert rep.makespan >= 0.0


def test_gate_accepts_every_policy_output():
    from distributed_llm_scheduler_tpu.frontend.generators import (
        generate_llm_dag,
    )
    from distributed_llm_scheduler_tpu.sched.policies import (
        ALL_SCHEDULERS,
        get_scheduler,
    )

    graph = generate_llm_dag(num_layers=4, num_heads=4, seed=3)
    for name in ALL_SCHEDULERS:
        cluster = Cluster.heterogeneous(20.0, 4)
        s = get_scheduler(name).schedule(graph, cluster)
        for backend in ("sim", "device"):
            rep = pre_execution_gate(graph, cluster, s, backend=backend)
            assert rep is not None and rep.ok, (name, backend)


# -- orchestration + CLI -----------------------------------------------------

def test_analyze_runs_applicable_passes():
    g, cl, s = corrupted()
    rep = analyze(g, cl, s, param_shapes={"attn_qkv_w": (768,)},
                  mesh_axes=MESH, family="gpt2")
    assert rep.has("SCH009") and rep.has("SHD002") and rep.has("MEM001")
    assert {d.code for d in rep.diagnostics} <= set(CODES)


@pytest.mark.parametrize(
    "argv",
    [
        ["lint", "--model", "gpt2-tiny"],
        ["lint", "--model", "gpt2-tiny", "--train-step"],
        ["lint", "--model", "gpt2-tiny", "--decode"],
        ["lint", "--model", "gpt2-tiny", "--quantize", "int8"],
        ["lint", "--model", "llama-tiny"],
        ["lint", "--model", "mixtral-tiny"],
        ["lint", "--model", "mixtral-tiny", "--routed"],
        ["lint", "--model", "llm"],
        ["lint", "--model", "random"],
        ["lint", "--model", "pipeline", "--scheduler", "pipeline"],
    ],
    ids=lambda a: " ".join(a[1:]),
)
def test_lint_cli_clean_on_every_builder(argv):
    from distributed_llm_scheduler_tpu.__main__ import main

    assert main(argv) == 0


def test_lint_cli_flags_failed_fit(capsys):
    from distributed_llm_scheduler_tpu.__main__ import main

    # 0.05 GB nodes cannot hold gpt2-tiny tasks: scheduler fails tasks,
    # lint still reports cleanly (graceful degradation is not corruption)
    rc = main([
        "lint", "--model", "llm", "--hbm-gb", "0.05", "--num-nodes", "2"
    ])
    out = capsys.readouterr()
    assert rc == 0
    assert "failed" in out.err


# -- pass: MPMD happens-before (hb_pass) -------------------------------------

from distributed_llm_scheduler_tpu.analysis import (  # noqa: E402
    StageOp,
    analyze_happens_before,
    stage_programs_1f1b,
)


@pytest.mark.parametrize("S,M", [(1, 2), (2, 4), (3, 6), (4, 8)])
def test_hb_1f1b_is_clean(S, M):
    # the golden deadlock-free reference: no errors, and the steady
    # state overlaps (no COL007 serialization warning) whenever there
    # is more than one stage
    rep = analyze_happens_before(stage_programs_1f1b(S, M))
    assert rep.ok, [d.render() for d in rep.diagnostics]
    assert not rep.has("COL007")


def test_hb_bidirectional_exchange_deadlocks():
    # both stages post their recv before their send: the canonical
    # MPMD deadlock — each wait's matching send sits behind the wait
    stages = {
        "stage0": [
            StageOp("recv", "stage1", "b"),
            StageOp("compute", None, "x"),
            StageOp("send", "stage1", "a"),
        ],
        "stage1": [
            StageOp("recv", "stage0", "a"),
            StageOp("compute", None, "y"),
            StageOp("send", "stage0", "b"),
        ],
    }
    rep = analyze_happens_before(stages)
    assert rep.exit_code == 1
    (d,) = rep.by_code("COL005")
    assert d.severity == Severity.ERROR
    assert "deadlock" in d.message
    # the rendered cycle names both stages' ops
    assert "stage0:" in d.message and "stage1:" in d.message


def test_hb_send_first_exchange_is_clean():
    # same channel pattern, send posted first: buffered sends make this
    # legal — the model must NOT treat sends as rendezvous
    stages = {
        "stage0": [("send", "stage1", "a"), ("recv", "stage1", "b")],
        "stage1": [("send", "stage0", "b"), ("recv", "stage0", "a")],
    }
    assert analyze_happens_before(stages).ok


def test_hb_cardinality_and_tag_mismatch():
    rep = analyze_happens_before({
        "stage0": [("send", "stage1", "f0"), ("send", "stage1", "f1")],
        "stage1": [("recv", "stage0", "f0")],
    })
    (d,) = rep.by_code("COL006")
    assert d.data == {"sends": 2, "recvs": 1}
    rep = analyze_happens_before({
        "stage0": [("send", "stage1", "f0")],
        "stage1": [("recv", "stage0", "g0")],
    })
    assert rep.has("COL006")  # matched position, different value tag


def test_hb_collective_order_divergence_cycles():
    # two stages disagreeing on the relative order of two rendezvous
    # collectives: a cycle through the merged nodes
    rep = analyze_happens_before({
        "stage0": [("collective", None, "ar1"), ("collective", None, "ar2")],
        "stage1": [("collective", None, "ar2"), ("collective", None, "ar1")],
    })
    assert rep.has("COL005")


def test_hb_serialized_ping_pong_warns_col007():
    # stage1 cannot start microbatch m before stage0 finishes BOTH of
    # its computes for m, and stage0 waits for the gradient before the
    # next microbatch: zero overlap, one active stage at a time
    s0, s1 = [], []
    for m in range(4):
        s0 += [
            ("compute", None, f"f{m}"), ("send", "stage1", f"f{m}"),
            ("recv", "stage1", f"g{m}"), ("compute", None, f"g{m}"),
        ]
        s1 += [
            ("recv", "stage0", f"f{m}"), ("compute", None, f"f{m}"),
            ("compute", None, f"g{m}"), ("send", "stage0", f"g{m}"),
        ]
    rep = analyze_happens_before({"stage0": s0, "stage1": s1})
    (d,) = rep.by_code("COL007")
    assert d.severity == Severity.WARNING
    assert rep.exit_code == 0  # warning, not an error
    assert "bubbles" in d.message  # cross-reference to obs attribution


def test_hb_gate_wiring():
    g = TaskGraph([Task("a", 0.1, 1.0, [], set())]).freeze()
    dead = {
        "stage0": [("recv", "stage1", "b"), ("send", "stage1", "a")],
        "stage1": [("recv", "stage0", "a"), ("send", "stage0", "b")],
    }
    with pytest.raises(AnalysisError) as ei:
        pre_execution_gate(
            g, two_caps(), sched({"n0": ["a"]}), backend="device",
            stage_programs=dead,
        )
    assert ei.value.report.has("COL005")
    # COL007 is a warning: a serialized-but-acyclic program passes
    ok = pre_execution_gate(
        g, two_caps(), sched({"n0": ["a"]}), backend="device",
        stage_programs=stage_programs_1f1b(2, 4),
    )
    assert ok is not None and ok.ok


# -- pass: donation-alias races (donation_pass) ------------------------------

from distributed_llm_scheduler_tpu.analysis import analyze_donation  # noqa: E402


def _table(steps, **kw):
    base = {
        "steps": tuple(steps), "fence_slots": (), "final_slot": None,
        "keep_list": (), "ext_slots": (), "n_slots": 8,
    }
    base.update(kw)
    return base


def _step(tid, node="d0", arg_slots=(), xfer_slots=(), donate_slots=(),
          out_slots=()):
    return {
        "tids": (tid,), "node_id": node, "arg_slots": tuple(arg_slots),
        "xfer_slots": tuple(xfer_slots), "donate_slots": tuple(donate_slots),
        "out_slots": tuple(out_slots),
    }


def test_donation_read_after_donation():
    rep = analyze_donation(_table([
        _step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,)),
        _step("b", arg_slots=(0, 1), out_slots=(2,)),
    ], final_slot=2))
    (d,) = rep.by_code("DON001")
    assert d.severity == Severity.ERROR
    assert d.data["slot"] == 0 and "freed" in d.message


def test_donation_double_donation():
    rep = analyze_donation(_table([
        _step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,)),
        _step("b", arg_slots=(2,), donate_slots=(0,), out_slots=(3,)),
    ]))
    assert rep.has("DON002")
    rep = analyze_donation(_table([
        _step("a", arg_slots=(0,), donate_slots=(0, 0), out_slots=(1,)),
    ]))
    (d,) = rep.by_code("DON002")
    assert "twice" in d.message


def test_donation_cross_device_transfer_race():
    rep = analyze_donation(_table([
        _step("a", node="d0", arg_slots=(0,), donate_slots=(0,),
              out_slots=(1,)),
        _step("b", node="d1", arg_slots=(0,), xfer_slots=(0,),
              out_slots=(2,)),
    ]))
    (d,) = rep.by_code("DON003")
    assert "across the device boundary" in d.message
    assert not rep.has("DON001")  # classified as the race, not the read


def test_donation_post_run_readers():
    rep = analyze_donation(_table(
        [_step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,))],
        final_slot=0,
    ))
    assert rep.has("DON001")
    rep = analyze_donation(_table(
        [_step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,))],
        fence_slots=(("d1", 0),),
    ))
    assert rep.has("DON001")
    rep = analyze_donation(_table(
        [_step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,))],
        keep_list=(("t0", 0),),
    ))
    assert rep.has("DON001")


def test_donation_last_consumer_is_clean():
    # reading AND donating a slot in the same launch is the normal
    # pattern — no diagnostic
    rep = analyze_donation(_table([
        _step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,)),
        _step("b", arg_slots=(1,), donate_slots=(1,), out_slots=(2,)),
    ], final_slot=2))
    assert rep.ok, [d.render() for d in rep.diagnostics]


def test_donation_takes_a_plan_or_its_table_and_nothing_else():
    assert analyze_donation(_table([])).ok
    for not_a_plan in ({"donated_argnums": (1, 2)}, {}, None, [1]):
        with pytest.raises(TypeError):
            analyze_donation(not_a_plan)


def test_donation_gate_wiring():
    g = TaskGraph([Task("a", 0.1, 1.0, [], set())]).freeze()
    bad = _table([
        _step("a", arg_slots=(0,), donate_slots=(0,), out_slots=(1,)),
        _step("b", arg_slots=(0,), out_slots=(2,)),
    ])
    with pytest.raises(AnalysisError) as ei:
        pre_execution_gate(
            g, two_caps(), sched({"n0": ["a"]}), backend="device", plan=bad,
        )
    assert ei.value.report.has("DON001")
    rep = analyze(g, stage_programs=stage_programs_1f1b(2, 2), plan=bad)
    assert rep.has("DON001")  # analyze() wires both new passes through


# -- collective walk: custom-derivative calls + dedupe -----------------------

def test_collective_walk_sees_through_custom_derivatives():
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.analysis import (
        analyze_collectives_jaxpr,
    )
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    perm = [(0, 1), (1, 0)]

    @jax.custom_jvp
    def rotate(v):
        return jax.lax.ppermute(v, "x", perm)

    @rotate.defjvp
    def _rotate_jvp(primals, tangents):
        return rotate(primals[0]), jax.lax.ppermute(tangents[0], "x", perm)

    @jax.custom_vjp
    def rotate2(v):
        return jax.lax.ppermute(v, "x", [(0, 0), (1, 0)])  # repeated dst

    rotate2.defvjp(
        lambda v: (rotate2(v), None),
        lambda _res, g: (g,),
    )

    def check(body):
        fn = shard_map(
            body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
            check_vma=False,
        )
        return analyze_collectives_jaxpr(
            fn, jax.ShapeDtypeStruct((2,), jnp.float32), where="t"
        )

    # the jvp-wrapped ppermute has a valid perm: walk reaches it, clean
    assert check(rotate).ok
    # the vjp-wrapped ppermute repeats a destination: COL004 — a
    # malformed perm must not hide behind the custom-derivative call
    rep = check(rotate2)
    assert rep.has("COL004")


def test_branch_divergence_col003():
    """A cond whose branches issue different collective sequences is the
    SPMD smuggling route for per-device divergence — the jaxpr walk
    flags it."""
    import jax

    from distributed_llm_scheduler_tpu.analysis import (
        analyze_collectives_jaxpr,
    )

    def good(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.ppermute(v, "dev", [(0, 1)]),
            lambda v: jax.lax.ppermute(v, "dev", [(0, 1)]),
            x,
        )

    def bad(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.ppermute(v, "dev", [(0, 1)]),
            lambda v: v * 2.0,
            x,
        )

    x = np.ones((4,), np.float32)
    jaxpr_good = jax.make_jaxpr(good, axis_env=[("dev", 2)])(x)
    jaxpr_bad = jax.make_jaxpr(bad, axis_env=[("dev", 2)])(x)
    assert analyze_collectives_jaxpr(jaxpr_good).ok
    rep = analyze_collectives_jaxpr(jaxpr_bad)
    assert rep.has("COL003")


def test_report_dedupe_counts_occurrences():
    from distributed_llm_scheduler_tpu.analysis import AnalysisReport

    rep = AnalysisReport()
    for _ in range(3):
        rep.add("COL004", Severity.ERROR, "perm is bad", task="t")
    rep.add("COL004", Severity.ERROR, "perm is bad", task="other")
    rep = rep.dedupe()
    assert len(rep.diagnostics) == 2  # distinct provenance survives
    d = rep.diagnostics[0]
    assert d.data["occurrences"] == 3
    assert "(x3)" in d.render()
    assert "(x" not in rep.diagnostics[1].render()


# -- parallel-strategy sweep + CLI -------------------------------------------

def test_parallel_sweep_covers_registry_and_is_clean():
    from distributed_llm_scheduler_tpu import parallel
    from distributed_llm_scheduler_tpu.analysis import (
        sweep_parallel_collectives,
    )

    assert set(parallel.COLLECTIVE_ENTRY_POINTS) == {
        "ring_attention", "ulysses", "expert", "pipeline_pp", "train",
        "decode",
    }
    rep = sweep_parallel_collectives()
    assert rep.ok, [d.render() for d in rep.diagnostics]


def test_parallel_sweep_flags_broken_probe_col008():
    from distributed_llm_scheduler_tpu.analysis import (
        sweep_parallel_collectives,
    )

    rep = sweep_parallel_collectives(entries=("no_such_module",))
    (d,) = rep.by_code("COL008")
    assert d.severity == Severity.ERROR and d.task == "no_such_module"


def test_lint_cli_parallel():
    from distributed_llm_scheduler_tpu.__main__ import main

    assert main(["lint", "--parallel"]) == 0
    assert main(["lint", "--parallel", "--decode"]) == 2


# -- serving safety: lifecycle (LCY) + determinism (DET) ---------------------

from pathlib import Path  # noqa: E402

from distributed_llm_scheduler_tpu.analysis import (  # noqa: E402
    analyze_determinism,
    analyze_lifecycle,
)
from distributed_llm_scheduler_tpu.obs.reqlog import (  # noqa: E402
    RequestLog,
    validate_request_log,
)

_FIXTURES = Path(__file__).parent / "fixtures" / "determinism"


def _row(**kw):
    """A legal retired engine row; override fields to break it."""
    row = {
        "rid": "r0", "prompt_len": 8, "max_new_tokens": 8,
        "state": "retired", "t_submit": 0.0, "t_admit": 0.1,
        "t_first_token": 0.2, "t_retire": 0.6, "t_preempt": None,
        "n_tokens": 3, "deliveries": [[0.2, 1], [0.4, 1], [0.6, 1]],
        "queue_wait_s": 0.1, "ttft_s": 0.2, "tpot_s": 0.2, "e2e_s": 0.6,
    }
    row.update(kw)
    return row


def _snap(*rows):
    return {"schema": "dls.requests/1", "requests": list(rows),
            "evicted": 0}


def test_lifecycle_clean_rows_and_validator_agreement():
    retired = _row()
    preempted = _row(rid="r1", state="preempted", t_retire=None,
                     t_preempt=0.5, e2e_s=None, tpot_s=None)
    shed = _row(rid="r2", state="shed", t_admit=None, t_first_token=None,
                t_retire=None, n_tokens=0, deliveries=[],
                queue_wait_s=None, ttft_s=None, tpot_s=None, e2e_s=None)
    # ties are legal: the virtual clock stamps coalesced events equally
    tied = _row(rid="r3", t_first_token=0.1, t_retire=0.1,
                deliveries=[[0.1, 1], [0.1, 2]])
    rep = analyze_lifecycle([retired, preempted, shed, tied], final=True)
    assert rep.diagnostics == [], [d.render() for d in rep.diagnostics]
    # the engine-schema validator agrees on its (shed-free) subset
    assert validate_request_log(_snap(retired, preempted, tied)) == []


def test_lifecycle_illegal_transitions_lcy001():
    # first token without admission
    rep = analyze_lifecycle(
        [_row(t_admit=None, queue_wait_s=None)], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY001"}
    # a preempted record must not carry t_retire — and the reqlog
    # validator rejects the same row for the same reason
    bad = _row(state="preempted", t_preempt=0.5)
    rep = analyze_lifecycle([bad], final=True)
    assert any(d.code == "LCY001" for d in rep.diagnostics)
    assert any("t_retire" in e for e in validate_request_log(_snap(bad)))


def test_lifecycle_time_travel_lcy002_matches_validator():
    bad = _row(t_retire=0.05, e2e_s=0.05, deliveries=[[0.2, 3]])
    rep = analyze_lifecycle([bad], final=True)
    msgs = [d.message for d in rep.diagnostics if d.code == "LCY002"]
    assert msgs, [d.render() for d in rep.diagnostics]
    # the message text comes from the SHARED helper, so the validator
    # flags the identical violation wording
    verrs = validate_request_log(_snap(bad))
    assert any(m.split(": ", 1)[-1] in e for m in msgs for e in verrs)


def test_lifecycle_unknown_state_lcy004():
    bad = _row(state="vanished")
    rep = analyze_lifecycle([bad], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY004"}
    assert any("unknown state" in e for e in validate_request_log(_snap(bad)))
    rep = analyze_lifecycle(["not-a-record"], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY004"}


def test_lifecycle_terminal_exhaustiveness_lcy003():
    live = _row(state="decoding", t_retire=None, e2e_s=None, tpot_s=None)
    assert analyze_lifecycle([live], final=False).diagnostics == []
    rep = analyze_lifecycle([live], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY003"}


def test_lifecycle_token_accounting_lcy005():
    bad = _row(n_tokens=7)
    rep = analyze_lifecycle([bad], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY005"}
    assert any("n_tokens" in e for e in validate_request_log(_snap(bad)))
    # tokens counted but no delivery evidence
    rep = analyze_lifecycle([_row(deliveries=None)], final=True)
    assert {d.code for d in rep.diagnostics} == {"LCY005"}


def test_lifecycle_accepts_live_request_log_object():
    log = RequestLog()
    log.submit("a", 8, 4, 0.0)
    log.admit("a", 0.1)
    log.first_token("a", 0.2)
    log.deliver("a", 0.4, 3)
    log.retire("a", 0.4)
    assert analyze_lifecycle(log, final=True).diagnostics == []
    log.submit("b", 8, 4, 0.5)      # still queued: fine live, not final
    assert analyze_lifecycle(log, final=False).diagnostics == []
    rep = analyze_lifecycle(log, final=True, label="live")
    assert [d.code for d in rep.diagnostics] == ["LCY003"]
    assert rep.diagnostics[0].message.startswith("live: ")


@pytest.mark.parametrize(
    "fixture,code,count",
    [
        ("det001_clock.py", "DET001", 3),
        ("serve/det002_rng.py", "DET002", 2),
        ("det003_setiter.py", "DET003", 2),
        ("det004_idkey.py", "DET004", 3),
        ("det005_env.py", "DET005", 3),
    ],
)
def test_determinism_fixture_fires(fixture, code, count):
    rep = analyze_determinism(paths=[_FIXTURES / fixture])
    codes = [d.code for d in rep.diagnostics]
    assert codes == [code] * count, [d.render() for d in rep.diagnostics]
    assert all(d.severity == Severity.ERROR for d in rep.diagnostics)


def test_determinism_markers_suppress():
    rep = analyze_determinism(paths=[_FIXTURES / "markered_clean.py"])
    assert rep.diagnostics == [], [d.render() for d in rep.diagnostics]


def test_determinism_repo_tree_is_clean():
    """The repo-wide gate: every wall-clock/RNG/env/set-order hazard in
    the package is either fixed or carries an inline justification."""
    rep = analyze_determinism()
    assert rep.diagnostics == [], [d.render() for d in rep.diagnostics]


def test_analyze_wires_serving_passes_through():
    g = TaskGraph([Task("t1", 1.0, 2.0, [], set())]).freeze()
    rep = analyze(
        g,
        page_events=[{"seq": 0, "kind": "alloc", "pages": [3],
                      "owner": None, "site": None, "free_pages": 4,
                      "used_pages": 1}],
        request_log=[_row(state="decoding", t_retire=None, e2e_s=None,
                          tpot_s=None)],
        request_log_final=True,
    )
    codes = {d.code for d in rep.diagnostics}
    assert "PGL001" in codes and "LCY003" in codes
    assert codes <= set(CODES)

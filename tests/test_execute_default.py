"""``DeviceBackend.execute`` as it is called with no flags.

There are two ways to run a placed step: the plan (fused same-device
spans by default, ``coalesce=False`` its per-task parity reference) and
the per-task loop (``planned=False``).  These tests hold the default to
both references over the DAG families and the placement policies, and
pin what every path owes its caller whatever it fuses: a task whose
producer never ran is skipped, donation never corrupts a later run, a
schedule whose per-node order inverts a dependency is legalised, and a
keyword of a deleted path is a ``TypeError`` like any unknown one.
"""

import gc
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import (
    Cluster,
    Task,
    TaskGraph,
    get_scheduler,
    quantize_dag,
)
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.core.schedule import Schedule
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.frontend.llama_dag import build_llama_dag
from distributed_llm_scheduler_tpu.frontend.moe_dag import build_moe_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
from distributed_llm_scheduler_tpu.models.llama import LlamaConfig
from distributed_llm_scheduler_tpu.models.mixtral import MixtralConfig

# (policy, devices): one chip, the four multi-chip policies the deleted
# paths were tested under, and pack over 2 and 8 chips
PLACEMENTS = [
    ("heft", 1), ("pipeline", 4), ("roundrobin", 4), ("mru", 4),
    ("pack", 4), ("pack", 2), ("pack", 8),
]

_BUILDERS = {
    "gpt2_mb": lambda: build_gpt2_dag(
        GPT2Config.tiny(), batch=4, seq_len=16, microbatches=2,
        vocab_shards=2,
    ),
    "llama": lambda: build_llama_dag(
        LlamaConfig.tiny(), batch=2, seq_len=16, microbatches=2,
    ),
    "moe": lambda: build_moe_dag(MixtralConfig.tiny(), batch=2, seq_len=16),
    "gpt2_int8": lambda: quantize_dag(build_gpt2_dag(
        GPT2Config.tiny(), batch=2, seq_len=16, microbatches=2,
    )),
}


@pytest.fixture(scope="module")
def backend_for():
    """One backend a device count for the whole module: its jit caches
    are keyed by ``fn`` object and launch structure, so the cases share
    what they compile."""
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    made = {}

    def get(n: int) -> DeviceBackend:
        if n not in made:
            made[n] = DeviceBackend(Cluster.from_jax_devices(
                jax.devices()[:n], hbm_cap_gb=8.0
            ))
        return made[n]

    return get


@pytest.fixture(scope="module")
def family():
    """``family(name) -> (dag, params, ids, reference logits)``, built
    once a module."""
    made = {}

    def get(name: str):
        if name not in made:
            dag = _BUILDERS[name]()
            params, ids = dag.init_params(), dag.make_inputs()
            dag.graph.freeze()
            made[name] = (
                dag, params, ids,
                np.asarray(dag.reference_forward(params, ids)),
            )
        return made[name]

    return get


def _bits(x):
    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(x)]


def _same_bits(a, b):
    la, lb = _bits(a), _bits(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(x, y)


# -- (a) the default against both references ------------------------------


@pytest.mark.parametrize("policy,n_devices", PLACEMENTS,
                         ids=[f"{p}{n}" for p, n in PLACEMENTS])
@pytest.mark.parametrize("name", list(_BUILDERS))
def test_default_equals_per_task_plan_and_reference(
    backend_for, family, name, policy, n_devices,
):
    """``execute()`` with no flags: bit for bit what ``coalesce=False``
    returns, the fused forward within the oracle tolerance, no more
    launches than tasks, and the per-task plan's transfer accounting.

    The int8 graph is held to 1e-5 instead of to the bit: two members of
    one launch that dequantise the same parameter (the tied ``wte``)
    share the product there, where a task alone contracts it into its
    first add — 4.2e-7 on the logits on one chip, on the parent too
    (ROADMAP D17)."""
    dag, params, ids, want = family(name)
    backend = backend_for(n_devices)
    schedule = get_scheduler(policy).schedule(dag.graph, backend.cluster)
    assert not schedule.failed
    rep = backend.execute(dag.graph, schedule, params, ids)
    per_task = backend.execute(
        dag.graph, schedule, params, ids, coalesce=False
    )
    assert rep.planned and per_task.planned
    if name == "gpt2_int8":
        np.testing.assert_allclose(
            np.asarray(rep.output), np.asarray(per_task.output),
            rtol=1e-5, atol=1e-5,
        )
    else:
        _same_bits(rep.output, per_task.output)
    np.testing.assert_allclose(
        np.asarray(rep.output), want, rtol=2e-4, atol=2e-4
    )
    n_tasks = len(schedule.placement)
    assert per_task.n_dispatches == n_tasks
    assert rep.n_dispatches <= n_tasks
    if n_devices == 1:
        assert rep.n_dispatches <= 2
    assert rep.transfer_edges == per_task.transfer_edges
    assert rep.transfer_bytes == per_task.transfer_bytes
    assert (rep.transfer_edges > 0) == (
        len(set(schedule.placement.values())) > 1
    )


@pytest.mark.parametrize("policy", ["roundrobin", "pipeline", "pack"])
def test_default_counts_transfers_as_the_per_task_loop_does(
    backend_for, family, policy,
):
    """The plan and the ``_run`` loop agree on the output's bits and on
    what crossed chips: the loop moves a remote value once per consuming
    task, the plan once per consuming launch, never more."""
    dag, params, ids, _ = family("gpt2_mb")
    backend = backend_for(4)
    schedule = get_scheduler(policy).schedule(dag.graph, backend.cluster)
    rep = backend.execute(dag.graph, schedule, params, ids)
    loop = backend.execute(dag.graph, schedule, params, ids, planned=False)
    assert not loop.planned and loop.n_dispatches == len(schedule.placement)
    _same_bits(rep.output, loop.output)
    assert 0 < rep.transfer_edges <= loop.transfer_edges
    assert 0 < rep.transfer_bytes <= loop.transfer_bytes


@pytest.mark.parametrize("policy", ["roundrobin", "pipeline", "pack"])
def test_default_keeps_every_task_output_when_asked(
    backend_for, family, policy,
):
    """``keep_outputs`` on the fused path exports every member: the same
    keys and the same bits as the per-task plan keeps."""
    dag, params, ids, _ = family("gpt2_mb")
    backend = backend_for(4)
    schedule = get_scheduler(policy).schedule(dag.graph, backend.cluster)
    rep = backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True
    )
    per_task = backend.execute(
        dag.graph, schedule, params, ids, keep_outputs=True, coalesce=False
    )
    assert set(rep.task_outputs) == set(schedule.placement)
    assert set(rep.task_outputs) == set(per_task.task_outputs)
    for tid, out in rep.task_outputs.items():
        _same_bits(out, per_task.task_outputs[tid])


# -- (b) fail-and-continue -------------------------------------------------

PATHS = [{}, {"coalesce": False}, {"planned": False}]
PATH_IDS = ["default", "per_task_plan", "per_task_loop"]


@pytest.mark.parametrize("kw", PATHS, ids=PATH_IDS)
def test_tasks_downstream_of_a_failed_task_are_skipped(kw):
    """A task absent from the placement drops its dependents instead of
    crashing; the report's output is None because the graph's final task
    did not run."""
    g = TaskGraph(name="fail")

    def fn(pd, x):
        return x + 1.0

    for tid, deps in (("root", []), ("dead", ["root"]),
                      ("child_of_dead", ["dead"]), ("alive", ["root"])):
        g.add_task(Task(tid, memory_required=0.0, compute_time=1e-6,
                        dependencies=deps, fn=fn))
    cluster = Cluster.from_jax_devices(jax.devices()[:2], hbm_cap_gb=8.0)
    n0 = cluster.devices[0].node_id
    sched = Schedule(  # "dead" never placed
        policy="manual",
        per_node={n0: ["root", "child_of_dead", "alive"]},
        assignment_order=["root", "child_of_dead", "alive"],
    )
    rep = DeviceBackend(cluster).execute(
        g, sched, {}, jnp.zeros((2,)), keep_outputs=True, **kw
    )
    assert set(rep.task_outputs) == {"root", "alive"}
    np.testing.assert_array_equal(
        np.asarray(rep.task_outputs["alive"]), np.full((2,), 2.0, np.float32)
    )
    assert rep.output is None
    assert rep.n_dispatches == (1 if kw == {} else 2)


# -- (c) donation across runs ----------------------------------------------


@pytest.mark.parametrize("kw", [
    {}, {"donate": True}, {"donate": True, "coalesce": False},
    {"donate": True, "reps": 3},
], ids=["default", "donate", "donate_per_task", "donate_reps3"])
def test_a_donating_run_repeated_returns_the_same_bits(
    backend_for, family, kw,
):
    """Donation frees only buffers no later launch, fence or caller
    reads: three runs on one backend (and three reps in one run) return
    what a run that donates nothing returns."""
    dag, params, ids, _ = family("gpt2_mb")
    backend = backend_for(4)
    schedule = get_scheduler("roundrobin").schedule(
        dag.graph, backend.cluster
    )
    want = backend.execute(
        dag.graph, schedule, params, ids, donate=False
    ).output
    for _ in range(3):
        rep = backend.execute(dag.graph, schedule, params, ids, **kw)
        _same_bits(rep.output, want)


# -- (d) the deleted paths are gone, not ignored ---------------------------


@pytest.mark.parametrize("kw", [
    {"compiled": True}, {"segments": True}, {"rebatch": False},
], ids=lambda kw: next(iter(kw)))
def test_a_removed_keyword_is_a_type_error(backend_for, family, kw):
    dag, params, ids, _ = family("gpt2_mb")
    backend = backend_for(1)
    schedule = get_scheduler("heft").schedule(dag.graph, backend.cluster)
    with pytest.raises(TypeError, match=next(iter(kw))):
        backend.execute(dag.graph, schedule, params, ids, **kw)


def test_execute_signature_names_two_paths_only():
    names = set(inspect.signature(DeviceBackend.execute).parameters)
    assert not names & {"compiled", "segments", "rebatch"}
    assert {"planned", "coalesce", "profile", "stream_params"} <= names
    for fn in (DeviceBackend.warmup, DeviceBackend._run):
        assert not set(inspect.signature(fn).parameters) & {
            "segments", "rebatch", "segments_pre",
        }


def test_cli_execute_has_no_segments_flag(capsys):
    from distributed_llm_scheduler_tpu.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["execute", "--model", "gpt2-tiny", "--segments"])
    assert exc.value.code == 2
    assert "--segments" in capsys.readouterr().err


def test_the_gate_takes_no_lowered_program():
    from distributed_llm_scheduler_tpu.analysis import pre_execution_gate

    assert "program" not in inspect.signature(pre_execution_gate).parameters


def test_every_diagnostic_code_has_an_emitter():
    """``diagnostics.CODES`` lists what some pass can still raise: a code
    whose pass was deleted goes with it."""
    import os

    from distributed_llm_scheduler_tpu import analysis

    root = os.path.dirname(analysis.__file__)
    sources = ""
    for name in sorted(os.listdir(root)):
        if name.endswith(".py") and name != "diagnostics.py":
            with open(os.path.join(root, name)) as f:
                sources += f.read()
    orphans = [c for c in analysis.CODES if f'"{c}"' not in sources]
    assert orphans == []
    assert not {"COL001", "COL002", "TYP004"} & set(analysis.CODES)


# -- (e) a per-node order that inverts a dependency ------------------------


def _inverted_order_case():
    """a1 on A; b1 on B (dep a1); a2 on A (dep b1) — but A's per-node
    order lists a2 FIRST.  ``dispatch_order`` legalises it through its
    topological fallback."""
    g = TaskGraph()
    g.add_task(Task("a1", memory_required=0.001, compute_time=1e-6,
                    fn=lambda p, x: x + 1.0))
    g.add_task(Task("b1", memory_required=0.001, compute_time=1e-6,
                    dependencies=["a1"], fn=lambda p, x: x * 2.0))
    g.add_task(Task("a2", memory_required=0.001, compute_time=1e-6,
                    dependencies=["b1"], fn=lambda p, x: x - 3.0))
    g.freeze()
    cluster = Cluster.from_jax_devices(jax.devices()[:2], hbm_cap_gb=8.0)
    node_a, node_b = [d.node_id for d in cluster]
    sched = Schedule(policy="manual")
    sched.per_node = {node_a: ["a2", "a1"], node_b: ["b1"]}
    sched.assignment_order = ["a1", "b1", "a2"]
    return g, cluster, sched


@pytest.mark.parametrize("kw", PATHS, ids=PATH_IDS)
def test_an_inverted_per_node_order_still_runs(kw):
    g, cluster, sched = _inverted_order_case()
    rep = DeviceBackend(cluster).execute(
        g, sched, {}, np.float32(1.0), **kw
    )
    assert np.array_equal(
        np.asarray(rep.output), np.float32((1.0 + 1.0) * 2.0 - 3.0)
    )
    assert rep.transfer_edges == 2


# -- what the backend keeps a graph alive for: nothing ---------------------


def test_a_dead_graph_releases_its_effect_verdict():
    """The per-graph caches are weak-keyed and hold no value that
    references the graph: dropping the graph drops the entries."""
    one = Cluster.from_jax_devices(jax.devices()[:1], hbm_cap_gb=8.0)
    backend = DeviceBackend(one)
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=16)
    params, ids = dag.init_params(), dag.make_inputs()
    schedule = get_scheduler("greedy").schedule(dag.graph, one)
    backend.execute(dag.graph, schedule, params, ids)
    assert len(backend._effect_free) == 1 and len(backend._prepared) == 1
    del dag, schedule
    gc.collect()
    assert len(backend._effect_free) == 0 and len(backend._prepared) == 0

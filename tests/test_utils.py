"""Serialization, checkpoint, profiling, visu, and CLI tests."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, DeviceState, get_scheduler
from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
from distributed_llm_scheduler_tpu.frontend.generators import generate_llm_dag
from distributed_llm_scheduler_tpu.utils.serialization import (
    load_graph,
    load_schedule,
    save_graph,
    save_schedule,
)


@pytest.fixture()
def llm_graph():
    return generate_llm_dag(num_layers=2, seed=3)


def test_graph_roundtrip(tmp_path, llm_graph):
    path = save_graph(llm_graph, str(tmp_path / "g.json"))
    g2 = load_graph(path)
    assert g2.task_ids() == llm_graph.task_ids()
    for tid in llm_graph.task_ids():
        a, b = llm_graph[tid], g2[tid]
        assert a.dependencies == b.dependencies
        assert a.params_needed == b.params_needed
        assert a.compute_time == b.compute_time
    # a reloaded graph schedules identically
    cluster = Cluster([DeviceState("n0", 8.0), DeviceState("n1", 8.0)])
    s1 = get_scheduler("mru").schedule(llm_graph, cluster)
    s2 = get_scheduler("mru").schedule(g2, cluster)
    assert s1.per_node == s2.per_node


def test_schedule_roundtrip(tmp_path, llm_graph):
    cluster = Cluster([DeviceState("n0", 8.0), DeviceState("n1", 8.0)])
    s = get_scheduler("heft").schedule(llm_graph, cluster)
    SimulatedBackend().execute(llm_graph, cluster, s)  # fills timings
    path = save_schedule(s, str(tmp_path / "s.json"))
    s2 = load_schedule(path)
    assert s2.per_node == s.per_node
    assert s2.assignment_order == s.assignment_order
    assert s2.makespan == pytest.approx(s.makespan)


def test_checkpoint_npz_roundtrip(tmp_path):
    from distributed_llm_scheduler_tpu.models import gpt2
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.utils.checkpoint import (
        load_params,
        save_params,
    )
    import jax

    params = gpt2.init_params(GPT2Config.tiny(), jax.random.PRNGKey(0))
    path = save_params(params, str(tmp_path / "ckpt.npz"))
    restored = load_params(path)
    assert set(restored) == set(params)
    np.testing.assert_array_equal(
        np.asarray(params["wte"]), restored["wte"]
    )


def test_checkpoint_orbax_roundtrip(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from distributed_llm_scheduler_tpu.models import gpt2
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config
    from distributed_llm_scheduler_tpu.utils.checkpoint import (
        load_params,
        save_params,
    )
    import jax

    params = gpt2.init_params(GPT2Config.tiny(), jax.random.PRNGKey(0))
    path = save_params(params, str(tmp_path / "orbax_ckpt"))
    restored = load_params(path)
    np.testing.assert_array_equal(
        np.asarray(params["wte"]), np.asarray(restored["wte"])
    )


def test_visualize_dag_and_gantt(tmp_path, llm_graph):
    from distributed_llm_scheduler_tpu.visu.plots import (
        visualize_dag,
        visualize_schedule,
    )

    p1 = visualize_dag(llm_graph, str(tmp_path / "dag.png"), detailed=True)
    assert os.path.getsize(p1) > 5000
    cluster = Cluster([DeviceState("n0", 8.0), DeviceState("n1", 8.0)])
    s = get_scheduler("heft").schedule(llm_graph, cluster)
    with pytest.raises(ValueError, match="no timings"):
        visualize_schedule(s, str(tmp_path / "gantt.png"))
    SimulatedBackend().execute(llm_graph, cluster, s)
    p2 = visualize_schedule(s, str(tmp_path / "gantt.png"))
    assert os.path.getsize(p2) > 5000


def test_profiling_helpers():
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.utils.profiling import (
        compiled_cost_analysis,
        time_fn,
        wall_timer,
    )
    import jax

    with wall_timer() as t:
        pass
    assert t["seconds"] >= 0

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    assert time_fn(f, x) > 0
    ca = compiled_cost_analysis(lambda x: x @ x, x)
    assert isinstance(ca, dict)  # may be empty on some backends


def _run_cli(*args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu", *args],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=300,
    )


def test_cli_schedule_and_visualize(tmp_path):
    r = _run_cli(
        "schedule", "--model", "llm", "--num-layers", "2",
        "--num-nodes", "2", "--hbm-gb", "8", "--out-dir", str(tmp_path), "--save",
    )
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout[: r.stdout.index("graph ->")])
    assert out["schedule"]["completed"] == 16
    r2 = _run_cli(
        "visualize", "--model", "llm", "--num-layers", "2",
        "--num-nodes", "2", "--hbm-gb", "8", "--out-dir", str(tmp_path),
    )
    assert r2.returncode == 0, r2.stderr
    assert any(f.endswith(".gantt.png") for f in os.listdir(tmp_path))


def test_cli_help():
    r = _run_cli("--help")
    assert r.returncode == 0
    for cmd in ("schedule", "sweep", "execute", "visualize", "train", "bench"):
        assert cmd in r.stdout


def test_export_chrome_trace(tmp_path):
    """Replay timings -> Chrome/Perfetto trace JSON: one thread per
    device, one complete event per task, microsecond timestamps."""
    import json

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.sim import SimulatedBackend
    from distributed_llm_scheduler_tpu.frontend.generators import (
        generate_llm_dag,
    )
    from distributed_llm_scheduler_tpu.utils.profiling import (
        export_chrome_trace,
    )

    graph = generate_llm_dag(num_layers=3, num_heads=2, seed=1)
    cluster = Cluster.uniform(2, 16.0)
    schedule = get_scheduler("critical").schedule(graph, cluster)
    SimulatedBackend().execute(graph, cluster, schedule)
    path = export_chrome_trace(schedule, str(tmp_path / "t.json"))
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    tasks = [e for e in events if e["ph"] == "X"]
    threads = [e for e in events if e["name"] == "thread_name"]
    assert len(tasks) == len(schedule.timings)
    assert len(threads) == len({t.node_id for t in schedule.timings.values()})
    for e in tasks:
        assert e["dur"] >= 0 and e["ts"] >= 0


def test_export_chrome_trace_requires_timings(tmp_path):
    import pytest as _pytest

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.frontend.generators import (
        generate_llm_dag,
    )
    from distributed_llm_scheduler_tpu.utils.profiling import (
        export_chrome_trace,
    )

    graph = generate_llm_dag(num_layers=2, num_heads=2, seed=1)
    schedule = get_scheduler("roundrobin").schedule(
        graph, Cluster.uniform(2, 16.0)
    )
    with _pytest.raises(ValueError, match="no timings"):
        export_chrome_trace(schedule, str(tmp_path / "t.json"))


def test_public_surface_resolves():
    """Every name in __all__ must be importable from the package root."""
    import distributed_llm_scheduler_tpu as dls

    for name in dls.__all__:
        assert getattr(dls, name, None) is not None, name


def test_cli_visualize_menu(tmp_path):
    """--menu drives the stdin loop (reference visu.py:294-339 analog):
    render both DAG styles, a gantt for an explicit policy, print the
    summary, reject an unknown choice, and exit cleanly on q."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llm_scheduler_tpu",
         "visualize", "--model", "llm", "--num-layers", "2",
         "--num-nodes", "2", "--hbm-gb", "8", "--out-dir", str(tmp_path),
         "--menu"],
        input="1\n2\n3 mru\n4\nbogus\nq\n",
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), env=env,
        timeout=300,
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.count("dag ->") == 2
    assert "gantt ->" in r.stdout
    assert "num_tasks" in r.stdout or "tasks" in r.stdout  # summary keys
    assert "unknown choice" in r.stdout
    assert any(".mru.gantt.png" in f or f.endswith(".gantt.png")
               for f in os.listdir(tmp_path))

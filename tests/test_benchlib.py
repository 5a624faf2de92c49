"""Bench decision-logic tests.

Everything decision-shaped in the bench lives in ``eval/benchlib`` as pure
functions; these tests pin it: peaks keyed by ``device_kind`` (unknown
accelerator kind raises, the host platform has none), the link provenance
string, best-policy picking, the JSON payload (device fields and oracle_ok
included) and the parity oracle.
"""

import json
import types

import pytest

from distributed_llm_scheduler_tpu.eval.benchlib import (
    DEVICE_PEAKS,
    BenchResult,
    choose_link,
    compute_mfu,
    device_peaks,
    pick_best,
    task_class,
)

V5E = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
HOST = types.SimpleNamespace(platform="cpu", device_kind="cpu")


def test_task_class_strips_mb_layer_shard():
    assert task_class("mb3_layer_7_attention") == "layer_attention"
    assert task_class("mb0_layer_0_attention") == "layer_attention"
    assert task_class("mb0_embedding_shard_2") == "embedding"
    assert task_class("mb7_output_projection") == "output_projection"
    assert task_class("output_concat") == "output_concat"


# -- peaks -------------------------------------------------------------------


def test_device_peaks_keyed_by_device_kind():
    assert device_peaks(V5E) is DEVICE_PEAKS["TPU v5 lite"]
    # the host platform has no peak by design
    assert device_peaks(HOST) is None
    # an accelerator kind that is not in the table is an error — it never
    # borrows another chip's numbers through its platform name
    other = types.SimpleNamespace(platform="tpu", device_kind="TPU v4")
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks(other)


def test_compute_mfu_against_the_kinds_bf16_peak():
    assert compute_mfu(197e12, 1.0, V5E) == pytest.approx(1.0)
    assert compute_mfu(1e12, 1.0, HOST) is None
    assert compute_mfu(0.0, 1.0, V5E) is None


# -- link --------------------------------------------------------------------


def test_choose_link_measures_this_machine_and_names_it(tmp_path):
    import jax

    link, prov = choose_link(cache_dir=str(tmp_path))
    kind = jax.devices()[0].device_kind
    assert prov.startswith(kind + ":")
    assert "param_load=measured" in prov
    assert link.param_load_gbps > 0
    # the cache it wrote is keyed by device_kind, not by platform
    from distributed_llm_scheduler_tpu.utils.costmodel import kind_slug

    assert (tmp_path / f"link_{kind_slug(jax.devices()[0])}.json").exists()


# -- result shaping ----------------------------------------------------------


def test_pick_best_ignores_incomplete_policies():
    ms = {
        "roundrobin": (10.0, 1.0),
        "fast_but_broken": (1.0, 0.5),
        "heft": (4.0, 1.0),
    }
    name, best, rr = pick_best(ms)
    assert (name, best, rr) == ("heft", 4.0, 10.0)


def test_pick_best_all_incomplete_returns_baseline():
    ms = {"roundrobin": (10.0, 0.9), "heft": (4.0, 0.8)}
    assert pick_best(ms) == ("roundrobin", 10.0, 10.0)


def test_bench_result_payload_names_device_and_oracle():
    r = BenchResult(
        n_policies=7,
        best_policy="pipeline",
        best_makespan_s=0.010,
        baseline_makespan_s=0.025,
        platform="tpu",
        device_kind="TPU v5 lite",
        n_devices=1,
        oracle_ok=False,
        link_provenance="TPU v5 lite:interconnect=estimated",
    )
    payload = r.to_json()
    assert payload["metric"] == "gpt2s_fwd_dag_makespan_best_of_7_policies"
    assert payload["vs_baseline"] == pytest.approx(2.5)
    assert payload["oracle_ok"] is False
    assert (payload["platform"], payload["device_kind"],
            payload["n_devices"]) == ("tpu", "TPU v5 lite", 1)
    # no provenance suffix and no fallback flag: there is one source
    assert "fallback" not in payload
    assert payload["best_policy"] == "pipeline"
    json.dumps(payload)  # must be serializable as-is


# -- ICI sensitivity ---------------------------------------------------------


def test_ici_sensitivity_structure_and_monotonicity():
    """Replaying fixed placements under 4x cheaper/dearer ICI must produce
    a result per scale, and cheaper ICI can only help (or not hurt) the
    best transfer-crossing makespan."""
    from distributed_llm_scheduler_tpu import (
        Cluster,
        DeviceState,
        Task,
        TaskGraph,
        get_scheduler,
    )
    from distributed_llm_scheduler_tpu.backends.sim import LinkModel
    from distributed_llm_scheduler_tpu.eval.benchlib import ici_sensitivity

    # linear chain with large activations: cross-node edges dominate
    tasks = [
        Task(f"t{i}", memory_required=0.5, compute_time=0.01,
             dependencies=[f"t{i-1}"] if i else [], params_needed=set())
        for i in range(8)
    ]
    graph = TaskGraph(tasks, name="chain").freeze()
    cluster = Cluster([DeviceState(f"n{i}", 8.0) for i in range(4)])
    schedules = {
        name: get_scheduler(name).schedule(graph, cluster)
        for name in ("roundrobin", "greedy")
    }
    link = LinkModel(param_load_gbps=10.0, interconnect_gbps=10.0,
                     latency_s=1e-6)
    sens = ici_sensitivity(graph, cluster, schedules, link)
    assert set(sens) == {"x0.25", "x4"}
    for v in sens.values():
        assert v["best_policy"] in schedules
        assert v["best_makespan_s"] > 0
    # roundrobin spreads the chain across nodes -> every edge crosses; 16x
    # bandwidth difference must separate the scaled replays
    assert (
        sens["x4"]["best_makespan_s"] <= sens["x0.25"]["best_makespan_s"]
    )


def test_ici_sensitivity_none_interconnect_is_stable():
    """A link with interconnect_gbps=None (the reference's zero-cost mode)
    must pass through unscaled rather than crash."""
    from distributed_llm_scheduler_tpu import (
        Cluster,
        DeviceState,
        Task,
        TaskGraph,
        get_scheduler,
    )
    from distributed_llm_scheduler_tpu.backends.sim import LinkModel
    from distributed_llm_scheduler_tpu.eval.benchlib import ici_sensitivity

    tasks = [Task("a", 0.1, 0.01, [], set()), Task("b", 0.1, 0.01, ["a"], set())]
    graph = TaskGraph(tasks, name="ab").freeze()
    cluster = Cluster([DeviceState("n0", 4.0), DeviceState("n1", 4.0)])
    schedules = {"roundrobin": get_scheduler("roundrobin").schedule(graph, cluster)}
    link = LinkModel(param_load_gbps=None, interconnect_gbps=None)
    sens = ici_sensitivity(graph, cluster, schedules, link)
    ms = [v["best_makespan_s"] for v in sens.values()]
    assert ms[0] == pytest.approx(ms[1])


# -- robust numerical oracle -------------------------------------------------


def test_oracle_close_f32_strict():
    import numpy as np

    from distributed_llm_scheduler_tpu.eval.benchlib import oracle_close

    a = np.random.RandomState(0).randn(1000).astype(np.float32)
    assert oracle_close(a, a, "float32")
    b = a.copy()
    b[3] += 1e-2  # one element past f32 tolerance -> strict fail
    assert not oracle_close(a, b, "float32")


def test_oracle_close_bf16_tolerates_tail_outliers():
    import numpy as np

    from distributed_llm_scheduler_tpu.eval.benchlib import oracle_close

    a = np.random.RandomState(1).randn(4_000_000).astype(np.float32)
    b = a + np.random.RandomState(2).randn(a.size).astype(np.float32) * 1e-3
    b[123] = a[123] + 0.2  # a lone rounding-tail outlier
    assert oracle_close(a, b, "bfloat16")


def test_oracle_close_bf16_rejects_systematic_error():
    import numpy as np

    from distributed_llm_scheduler_tpu.eval.benchlib import oracle_close

    a = np.random.RandomState(3).randn(100_000).astype(np.float32)
    assert not oracle_close(a, a * 1.1, "bfloat16")  # 10% scale error
    assert not oracle_close(a, np.roll(a, 1), "bfloat16")  # scrambled
    assert not oracle_close(a, a.reshape(-1, 1), "bfloat16")  # shape



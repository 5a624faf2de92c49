"""Parameter streaming: models bigger than the HBM budget still execute.

The reference's founding scenario is weights that don't fit (37.5 GB of
params on 28 GB of laptops, reference ``test_gpt2.py:274-299``) — handled
there by *placement* across nodes.  ``stream_params=True`` adds the
single-node answer: load-on-demand with LRU eviction under the node's
budget, correct output, measured eviction traffic.
"""

import jax
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


@pytest.fixture(scope="module")
def setup():
    dag = build_gpt2_dag(GPT2Config.tiny(), batch=1, seq_len=16)
    return dag, dag.init_params(), dag.make_inputs()


def _tight_cluster(dag, n_devices, fraction):
    """Budget = fraction of total param bytes (plus nothing else)."""
    total_gb = dag.graph.total_param_gb()
    return Cluster.from_jax_devices(
        jax.devices()[:n_devices], hbm_cap_gb=total_gb * fraction
    )


def test_oversubscribed_single_device_executes(setup):
    """Weights ~3x the budget: streaming must evict and still be exact."""
    dag, params, ids = setup
    cluster = _tight_cluster(dag, 1, 0.35)
    # MRU is the eviction-aware policy: it PLACES under the tight budget
    # (bookkeeping eviction), and streaming makes that plan physical
    schedule = get_scheduler("mru").schedule(dag.graph, cluster)
    assert not schedule.failed
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, stream_params=True
    )
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )
    assert rep.param_evictions > 0
    assert rep.param_loads > len(dag.graph.unique_params())  # reloads happened
    budget = int(cluster.devices[0].total_memory * 1024**3)
    peak = max(rep.peak_param_bytes.values())
    # LRU may pin one task's own params past the line; small slack only
    assert peak <= budget * 1.5


def test_fits_in_budget_no_evictions(setup):
    dag, params, ids = setup
    cluster = _tight_cluster(dag, 1, 4.0)
    schedule = get_scheduler("greedy").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, stream_params=True
    )
    assert rep.param_evictions == 0
    # each unique param loads exactly once
    assert rep.param_loads == len(dag.graph.unique_params())
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )


def test_streaming_multi_device(setup):
    dag, params, ids = setup
    cluster = _tight_cluster(dag, 4, 0.2)  # per-node budget tiny
    schedule = get_scheduler("mru").schedule(dag.graph, cluster)
    assert not schedule.failed
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, stream_params=True
    )
    fused = dag.reference_forward(params, ids)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(rep.output), rtol=2e-5, atol=2e-5
    )


def test_streaming_stats_in_summary(setup):
    dag, params, ids = setup
    cluster = _tight_cluster(dag, 1, 0.35)
    schedule = get_scheduler("mru").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, stream_params=True
    )
    s = rep.summary()
    assert s["param_loads"] == rep.param_loads
    assert s["param_evictions"] == rep.param_evictions
    assert s["peak_param_gb"]


def test_batched_loads_and_bytes(setup):
    """A task's missing params go up in one device_put: call count strictly
    below the per-param load count, bytes ledger populated."""
    dag, params, ids = setup
    cluster = _tight_cluster(dag, 1, 0.35)
    schedule = get_scheduler("mru").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, params, ids, stream_params=True
    )
    assert 0 < rep.param_load_calls < rep.param_loads
    assert rep.param_load_bytes > 0
    s = rep.summary()
    assert s["param_load_calls"] == rep.param_load_calls
    assert s["param_load_mb"] > 0


def _mk_streamer(params, budget_gb, seq, lookahead=2):
    """seq: ordered [(tid, (param names,))] for the single node, or None
    for the planless (LRU) mode."""
    from distributed_llm_scheduler_tpu.core.cluster import Cluster

    cluster = Cluster.from_jax_devices(jax.devices()[:1], hbm_cap_gb=budget_gb)
    node = cluster.devices[0].node_id
    plan = {node: seq} if seq is not None else None
    return (
        DeviceBackend._ParamStreamer(
            cluster, params, plan=plan, lookahead=lookahead
        ),
        node,
    )


def test_belady_beats_lru_on_scan_pattern():
    """Cyclic scan over 3 params with room for 2 (lookahead 0, isolating
    the eviction policy): LRU thrashes (every access misses); Belady keeps
    the soonest-needed resident and converts some misses to hits."""
    import numpy as np

    params = {
        k: np.ones((256, 256), np.float32) for k in ("a", "b", "c")
    }
    per = params["a"].nbytes
    budget_gb = (2 * per + per // 2) / 1024**3  # fits exactly 2
    seq = [("t%d" % i, (k,)) for i, k in enumerate("abc" * 4)]

    st, node = _mk_streamer(params, budget_gb, seq, lookahead=0)
    for tid, globs in seq:
        pd = st.get_task(tid, node, [(g, g) for g in globs])
        st.note_task(node, globs, pd[globs[0]] + 1.0)
    belady_loads = st.loads

    st2, node2 = _mk_streamer(params, budget_gb, None, lookahead=0)  # LRU
    for tid, globs in seq:
        pd = st2.get_task(tid, node2, [(g, g) for g in globs])
        st2.note_task(node2, globs, pd[globs[0]] + 1.0)
    assert belady_loads < st2.loads, (belady_loads, st2.loads)
    assert st2.loads == len(seq)  # LRU thrashes every access


def test_prefetch_eliminates_demand_stalls():
    """Same scan with the prefetcher on: total loads may match LRU, but
    every load after warmup was issued ahead of use — the dispatch loop
    never stalls on a missing param."""
    import numpy as np

    params = {
        k: np.ones((256, 256), np.float32) for k in ("a", "b", "c")
    }
    per = params["a"].nbytes
    budget_gb = (2 * per + per // 2) / 1024**3
    seq = [("t%d" % i, (k,)) for i, k in enumerate("abc" * 4)]
    st, node = _mk_streamer(params, budget_gb, seq, lookahead=2)
    for tid, globs in seq:
        pd = st.get_task(tid, node, [(g, g) for g in globs])
        st.note_task(node, globs, pd[globs[0]] + 1.0)
    assert st.demand_misses <= 1  # only the very first access can stall
    assert st.loads >= len(params)


def test_prefetch_loads_ahead_of_use():
    """With budget for everything, the first get_task prefetches the
    lookahead window's params in the same pass."""
    import numpy as np

    params = {k: np.ones((64, 64), np.float32) for k in "abcd"}
    seq = [("t%d" % i, (k,)) for i, k in enumerate("abcd")]
    st, node = _mk_streamer(params, 1.0, seq, lookahead=3)
    st.get_task("t0", node, [("a", "a")])
    # a + the 3 lookahead params are already resident after one call
    assert set(st.resident[node]) == {"a", "b", "c", "d"}
    assert st.loads == 4
    # one batched call for the current param, one per prefetched task
    assert st.load_calls <= 4


def test_streamer_ledger_counts_graveyard():
    """Evicted-but-not-freed buffers still count toward the byte ledger:
    memory is physical until the deferred delete actually runs, so the
    peak can't be under-reported by fast eviction."""
    import numpy as np

    params = {k: np.ones((128, 128), np.float32) for k in "ab"}
    per = params["a"].nbytes
    seq = [("t0", ("a",)), ("t1", ("b",))]
    st, node = _mk_streamer(params, 1.0, seq, lookahead=0)  # roomy budget
    pd = st.get_task("t0", node, [("a", "a")])
    st.note_task(node, ("a",), pd["a"] + 1.0)
    st.get_task("t1", node, [("b", "b")])
    assert st.bytes[node] == 2 * per
    # evict both: ledger must NOT drop until the flush deletes buffers
    assert st._evict_one(node, set(), None) == per
    assert st._evict_one(node, set(), None) == per
    assert st.evictions == 2
    assert st.bytes[node] == 2 * per, "graveyard bytes left the ledger"
    # partial flush frees exactly the oldest entry's bytes
    st._flush(node, 1)
    assert st.bytes[node] == per
    st._flush(node, per)
    assert st.bytes[node] == 0


def test_prefetch_never_overshoots_budget():
    """Prefetch with everything pinned must skip, not load past the cap:
    the over-budget escape exists for a task's own params only."""
    import numpy as np

    params = {k: np.ones((128, 128), np.float32) for k in "ab"}
    per = params["a"].nbytes
    budget_gb = (per + per // 2) / 1024**3  # fits exactly 1
    seq = [("t0", ("a",)), ("t1", ("b",))]
    st, node = _mk_streamer(params, budget_gb, seq, lookahead=1)
    st.get_task("t0", node, [("a", "a")])  # 'a' pinned; prefetch of 'b'
    # must refuse (evicting 'a' is forbidden, overshooting is worse)
    assert set(st.resident[node]) == {"a"}
    assert st.peak[node] <= int(budget_gb * 1024**3)


def test_duplicate_global_loads_once():
    """A fused task can alias two local names to one global param; the
    streamer must load it once and ledger it once (a double load would
    orphan a device buffer and inflate the budget forever)."""
    import numpy as np

    params = {"w": np.ones((64, 64), np.float32)}
    seq = [("t0", ("w", "w"))]
    st, node = _mk_streamer(params, 1.0, seq, lookahead=0)
    pd = st.get_task("t0", node, [("a", "w"), ("b", "w")])
    assert pd["a"] is pd["b"]
    assert st.loads == 1
    assert st.bytes[node] == params["w"].nbytes

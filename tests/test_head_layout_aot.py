"""The placed-DAG cells' head launches compiled for the v5e without a
chip, through the backend's own builders: the logits leave every launch
in the order the head's matmul writes them, so no program re-orders them
(ISSUE 48).  The whole plan is walked — a launch is resolved as its first
call resolves it (``NativeLaunch.resolve``), against the layouts its
producers were compiled to hand it — which takes a quarter of a minute a
cell: ``slow``, not part of tier-1.  The topology is described
only inside the fixture (on-chip-measurement guide, section 2)."""

from __future__ import annotations

import os
import re

import pytest

pytestmark = pytest.mark.slow

LOGITS = re.compile(r"\[\d+,512,50257\]")


@pytest.fixture(scope="module")
def chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_plan(devices, policy):
    """The cell's plan built on described chips and every launch of it
    compiled in order, each against the formats its arguments arrive in:
    ``[(step, compiled, exports left in another layout than the
    default)]``, one compile per distinct (program, argument formats)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends import dispatch_plan as dp
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    dag = build_gpt2_dag(
        GPT2Config.medium(dtype=jnp.bfloat16), batch=32, seq_len=512,
        microbatches=8,
    )
    graph = dag.graph
    cluster = Cluster.from_jax_devices(devices, hbm_cap_gb=15.75)
    schedule = get_scheduler(policy).schedule(graph, cluster)
    backend = DeviceBackend(cluster)
    placed = {
        (glob, node): None
        for tid, node in schedule.placement.items()
        for glob in graph[tid].params_needed
    }
    plan = dp.DispatchPlan.build(
        backend, graph, schedule, backend.dispatch_order(graph, schedule),
        placed, coalesce=True, donate=True,
    )
    tid_of = {
        s: t for st in plan.steps for t, s in zip(st.out_tids, st.out_slots)
    }
    inputs = {s for _n, _d, s in plan.input_slots}
    formats = {}   # slot -> Format of a value left in a non-default layout
    memo = {}
    out = []
    for st in plan.steps:
        here = SingleDeviceSharding(st.dev)

        def sds(spec, fmt=None):
            return jax.ShapeDtypeStruct(spec.shape, spec.dtype,
                                        sharding=fmt or here)

        def params_of(tid):
            return {loc: sds(dag.param_specs[glob])
                    for loc, glob in graph[tid].param_items()}

        pds = (tuple(params_of(t) for t in dp._program_order(graph, st.tids))
               if st.group else params_of(st.tids[0]))
        put = {pos for pos, _ in st.xfer_map}
        args, arrive = [], []
        for pos, s in enumerate(st.arg_slots):
            if s in inputs:
                args.append(sds(dag.input_spec))
                continue
            fmt = None if pos in put else formats.get(s)
            arrive.append(fmt)
            args.append(sds(graph[tid_of[s]].out_shape, fmt))
        key = (id(st.fn), st.dev.id, tuple(arrive))
        if key not in memo:
            # a launch that leaves a result's layout to the compiler asks
            # through ``auto``; where the answer is not the default, what it
            # runs is a program compiled to name the same layouts
            fn, kept = (st.fn, 0) if st.program is None else (
                st.program.resolve(pds, args))
            memo[key] = fn if kept else fn.lower(pds, *args).compile()
        compiled = memo[key]
        outs = compiled.output_formats
        native = []
        for t, s, fmt in zip(st.out_tids, st.out_slots,
                             outs if st.group else (outs,)):
            spec = graph[t].out_shape
            default = Layout.from_pjrt_layout(st.dev.client.get_default_layout(
                spec.dtype, spec.shape, st.dev))
            if s in st.native_slots and fmt.layout != default:
                formats[s] = fmt
                native.append(t)
        out.append((st, compiled, native))
    return out


def _logits_copies(compiled):
    """Entry-computation ops that re-order a ``[., 512, 50257]`` value: a
    ``copy`` or a fusion XLA named for the copy it holds."""
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ", line)
        if m and m.group(1).startswith("copy") and LOGITS.search(m.group(2)):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("n_chips,policy,kept", [(1, "heft", 1),
                                                 (4, "pack", 8)])
def test_no_head_launch_reorders_the_logits(chips, n_chips, policy, kept):
    walked = _compile_plan(chips[:n_chips], policy)
    heads = [(st, c, native) for st, c, native in walked
             if any(t.endswith("output_projection") or t == "output_concat"
                    for t in st.tids)]
    assert len(heads) == (1 if n_chips == 1 else 8)
    assert len({id(st.fn) for st, _c, _n in heads}) == min(n_chips, 2)
    for st, compiled, native in heads:
        assert not _logits_copies(compiled), st.tids[-2:]
        assert native == [st.out_tids[-1]]
        fmt = compiled.output_formats[-1]
        assert fmt.layout.major_to_minor == (0, 2, 1)
    # nothing but the logits is left in another layout than the default
    assert sum(len(native) for _st, _c, native in walked) == kept
    st, last, _native = heads[-1]
    assert st.tids[-1] == "output_concat"
    assert last.memory_analysis().temp_size_in_bytes < 0.6e9   # 1.88e9 before
    assert "dynamic-update-slice" in last.as_text()

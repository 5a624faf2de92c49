"""The LFM2 cell's new kernel and its two programs compiled for the v5e at
the cell's own widths, without a chip, as ``test_benchmark_nemotron_aot.py``
does for the Nemotron-H cell (same fixture: the topology is described only
inside it, and where the TPU's library cannot be loaded the tests skip)."""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "lfm2-24b-a2b-serve.json").read_text())
GEO = CFG["engine"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(one_chip):
    import jax

    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def test_the_conv_step_and_the_wide_expert_step_compile(one_chip):
    """``_short_conv_step`` over 128 slots' states (2 rows of 2,048 bf16
    each: a 1 MB pool, one VMEM block) with the pool aliased in place,
    and the gated grouped experts with all 64 held at a decode step's 128
    rows x top-4 and at a chunk's 512."""
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models import xing4
    from distributed_llm_scheduler_tpu.ops import short_conv

    sds = _sds(one_chip)
    bf, f32 = jnp.bfloat16, jnp.float32
    S, h, K = GEO["slots"], CFG["hidden_size"], CFG["conv_L_cache"]
    pool = (1 + S, *short_conv.state_shape(h, K))
    assert pool == (129, 2, 16, 128)
    assert not short_conv.short_conv_constraints(pool, bf)
    step = short_conv._short_conv_step.lower(
        sds((S, h), bf), sds((h, K), bf), sds(pool, bf),
        sds((S,), jnp.bool_), impl="pallas").compile()
    text = step.as_text()
    assert "_short_conv_step" in text
    assert not re.search(r"bf16\[129,2,16,128\]\S* copy\(", text)
    E, I, k = CFG["num_experts"], CFG["moe_intermediate_size"], CFG[
        "num_experts_per_tok"]
    for n in (S, GEO["chunk_tokens"]):
        moe = xing4._moe_experts.lower(
            sds((n, h), bf), sds((n, k), jnp.int32), sds((n, k), f32),
            sds((E, 2 * I, h), bf), sds((E, I, h), bf),
            impl="pallas").compile()
        assert "_moe_experts" in moe.as_text()


def test_segment_and_chunk_programs_fit_the_chip(one_chip, monkeypatch):
    """The two programs the window runs, whole, at the cell's geometry:
    they compile for the v5e with every kernel inside, keep each pool
    where it lies — the conv pools aliased in place through
    ``_short_conv_step``, no pool-shaped copy — and weights + pools +
    temporaries leave room in 15.75 GB."""
    import jax
    import jax.numpy as jnp

    from benchmark.runners import lfm2_serve
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import lfm2
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    i32 = jnp.int32
    sds = _sds(one_chip)
    mcfg = lfm2_serve.model_config(CFG)
    S, ps, ppseq, n_pages = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages"))
    ddag = build_paged_decode_dag(
        mcfg, slots=S, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl="auto")
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler(GEO["scheduler"]).schedule(ddag.graph, cluster)
    specs = {k: sds(v.shape, v.dtype) for k, v in ddag.param_specs.items()}
    pools = {k: v for k, v in specs.items() if k.startswith("cache_")}
    weights = {k: v for k, v in specs.items()
               if k not in pools and k != "page_table"}

    def gb(d):
        return sum(np.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in d.values()) / 1e9

    convs = [i for i in range(mcfg.n_layers) if mcfg.is_conv(i)]
    assert convs == [0, 1, 3, 4, 5, 7, 8, 9]
    assert sorted(pools) == sorted(
        [f"cache_{k}_{i}" for i in (2, 6) for k in "kv"]
        + [f"cache_conv_{i}" for i in convs])
    assert pools["cache_conv_0"].shape == (1 + S, 2, 16, 128)
    assert pools["cache_conv_0"].dtype == jnp.bfloat16
    assert pools["cache_k_2"].shape == (n_pages, ps, 512)
    # 10.53 GB of weights; K/V 1.61 GB and the slots' states 8 MB
    assert 10.52 < gb(weights) < 10.55 and 1.61 < gb(pools) < 1.63

    seg = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 1), i32), sds((S,), i32)).compile()
    text = seg.as_text()
    for name in ("_paged_flash", "_short_conv_step", "_moe_experts"):
        assert name in text, name
    for shape in (rf"bf16\[{1 + S},2,16,128\]",
                  rf"bf16\[{n_pages},{ps},512\]"):
        assert not re.search(rf"{shape}\S* copy\(", text), shape
        assert not re.search(rf"copy-start\S*\({shape}", text), shape
    mem = seg.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
    # the donated pools come back in their own buffers
    assert mem.alias_size_in_bytes >= 0.99 * gb(pools) * 1e9

    # a head of 64 is no whole lane tile: the chunk program gathers the
    # slot's K and V dense, as the engine would decide
    from distributed_llm_scheduler_tpu.ops.gqa_attention import (
        gqa_paged_chunk_impl,
    )

    assert gqa_paged_chunk_impl("auto", ps, mcfg.head_dim,
                                mcfg.dtype) == "xla"
    spec, cap = lfm2.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal, state):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, ps), pools, pages, 1, cap,
            state=state)
        last, cache = lfm2.forward_cached_row(
            w, ids, cache, pos0, mcfg, creal - 1, impl="auto")
        return (jnp.argmax(last, -1).astype(i32), spec.scatter(
            pools, cache, pages, ps, state=state))

    done = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, sds((1, GEO["chunk_tokens"]), i32), pools,
        sds((ppseq,), i32), sds((), i32), sds((), i32),
        sds((1,), i32)).compile()
    assert "_moe_experts" in done.as_text()
    temp = done.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9
    # weights + pools + the chunk program's temporaries: under 14 of 15.75
    assert gb(weights) + gb(pools) + temp / 1e9 < 14.0

"""The Nemotron-H cell's three new kernels and its two programs compiled
for the v5e at the cell's own widths, without a chip, as
``test_benchmark_xing4_aot.py`` does for the Xing4.0 cell (same fixture:
the topology is described only inside it, and where the TPU's library
cannot be loaded the tests skip)."""

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
                  / "nemotron-3-nano-ep2.json").read_text())
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


def test_the_three_kernels_compile_at_the_published_widths(one_chip):
    """``_ssm_step`` over 64 slots' states (64 heads of 64 x 128 float32
    and 3 x 6,144 bf16 inputs each), ``_ssd_chunk`` over a 512-token
    chunk in 4 blocks of 128, and the ungated grouped experts at I =
    1,856 — 14.5 lane tiles, so the I tile is 464 = 29 sublane tiles."""
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models import xing4
    from distributed_llm_scheduler_tpu.ops import ssm

    sds = _sds(one_chip)
    bf, f32 = jnp.bfloat16, jnp.float32
    S, H, P, N, G, K = GEO["slots"], 64, 64, 128, 8, 4
    W = H * P + 2 * G * N
    assert not ssm.ssm_kernel_constraints(H, P, N, G, CFG["chunk_size"])
    step = ssm._ssm_step.lower(
        sds((S, W), bf), sds((S, H), bf), sds((W, K), bf), sds((W,), bf),
        sds((H,), f32), sds((H,), f32), sds((H,), f32),
        sds((1 + S, K - 1, W // N, N), bf), sds((1 + S, H, P, N), f32),
        sds((S,), jnp.bool_), heads=H, head_dim=P, groups=G,
        impl="pallas").compile()
    assert "_ssm_step" in step.as_text()
    T = GEO["chunk_tokens"]
    chunk = ssm._ssd_chunk.lower(
        sds((T, H, P), f32), sds((T, H), f32), sds((H,), f32),
        sds((T, G, N), f32), sds((T, G, N), f32), sds((H, P, N), f32),
        block=CFG["chunk_size"], impl="pallas").compile()
    assert "_ssd_chunk" in chunk.as_text()
    E, I, h = 64, CFG["moe_intermediate_size"], CFG["hidden_size"]
    assert xing4.ungated_i_tile(I) == 464 and I % 128
    for n in (S, T):        # a decode step's tokens, a chunk's
        moe = xing4._moe_experts_ungated.lower(
            sds((n, h), bf), sds((n, 6), jnp.int32), sds((n, 6), f32),
            sds((E, I, h), bf), sds((E, I, h), bf), act="relu2",
            impl="pallas").compile()
        assert "_moe_experts" in moe.as_text()


def test_segment_and_chunk_programs_fit_the_chip(one_chip, monkeypatch):
    """The two programs the window runs, whole, at the cell's geometry:
    they compile for the v5e with every kernel inside, keep each pool
    where it lies — the state pools aliased in place through
    ``_ssm_step``, no pool-shaped copy — and weights + pools +
    temporaries leave room in 15.75 GB."""
    import jax
    import jax.numpy as jnp

    from benchmark.runners import nemotron_serve
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import nemotron_h
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    i32 = jnp.int32
    sds = _sds(one_chip)
    mcfg = nemotron_serve.model_config(CFG)
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

    mixers = [i for i, c in enumerate(mcfg.pattern) if c == "M"]
    assert sorted(pools) == sorted(
        [f"cache_{k}_{i}" for i in (5, 12) for k in "kv"]
        + [f"cache_{k}_{i}" for i in mixers for k in ("ssm", "conv")])
    assert pools["cache_ssm_0"].shape == (1 + S, 64, 64, 128)
    assert pools["cache_ssm_0"].dtype == jnp.float32
    assert pools["cache_conv_0"].shape == (1 + S, 3, 48, 128)
    assert pools["cache_k_5"].shape == (n_pages, ps, 256)
    # 7.85 GB of weights; K/V 0.42 GB and the slots' states 0.83 GB
    assert 7.84 < gb(weights) < 7.87 and 1.24 < gb(pools) < 1.27

    seg = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 1), i32), sds((S,), i32)).compile()
    text = seg.as_text()
    for name in ("_paged_flash", "_ssm_step", "_moe_experts"):
        assert name in text, name
    for shape in (rf"f32\[{1 + S},64,64,128\]", rf"bf16\[{1 + S},3,48,128\]",
                  rf"bf16\[{n_pages},{ps},256\]"):
        assert not re.search(rf"{shape}\S* copy\(", text), shape
        assert not re.search(rf"copy-start\S*\({shape}", text), shape
    mem = seg.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
    # the donated pools come back in their own buffers
    assert mem.alias_size_in_bytes >= 0.99 * gb(pools) * 1e9

    spec, cap = nemotron_h.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal, state):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, ps, True), pools, pages, 1,
            cap, in_pages=True, state=state)
        last, cache = nemotron_h.forward_cached_row(
            w, ids, cache, pos0, mcfg, creal - 1, impl="auto",
            pages=pages[None])
        return (jnp.argmax(last, -1).astype(i32), spec.scatter(
            pools, cache, pages, ps, in_pages=True, state=state))

    done = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, sds((1, GEO["chunk_tokens"]), i32), pools,
        sds((ppseq,), i32), sds((), i32), sds((), i32),
        sds((1,), i32)).compile()
    text = done.as_text()
    for name in ("_ssd_chunk", "_gqa_chunk_flash_paged", "_moe_experts"):
        assert name in text, name
    temp = done.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9
    # weights + pools + the chunk program's temporaries: under 12 of 15.75
    assert gb(weights) + gb(pools) + temp / 1e9 < 12.0

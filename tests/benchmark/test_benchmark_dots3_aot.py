"""The dots3 cell's new kernels, and its segment and chunk programs
whole, compiled for the v5e at the cell's own sizes without a chip, as
``test_benchmark_xing4_aot.py`` does (same fixture: where the TPU's
library cannot be loaded the tests skip).  The memory the compiler
reports is checked against the chip: 8.2 GB of weights, 1 GB of pools,
temporaries well under what is left of 16 GB."""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CFG = json.loads((ROOT / "benchmark" / "configs"
                  / "dots3-note-prev-ep8.json").read_text())
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


def test_index_and_ring_kernels_compile_at_the_served_widths(one_chip):
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.ops import attention as A

    sds, bf, f32, i32 = _sds(one_chip), jnp.bfloat16, jnp.float32, jnp.int32
    S, ps, ppseq, P = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages"))
    Hi, Di = CFG["index_n_heads"], CFG["index_head_dim"]
    text = jax.jit(lambda q, w, pool, tab, ln: A._dsa_index(
        q, w, pool, tab, ln, interpret=False)).lower(
        sds((S, Hi, Di), f32), sds((S, Hi), f32), sds((P, ps, Di), bf),
        sds((S, ppseq), i32), sds((S,), i32)).compile().as_text()
    assert "tpu_custom_call" in text and "_dsa_index" in text
    pool = rf"bf16\[{P},{ps},{Di}\]"
    assert re.search(pool + r"\{2,1,0", text)
    assert not re.search(pool + r"\S* copy\(", text)

    H, rank = CFG["swa_num_attention_heads"], CFG["swa_kv_lora_rank"]
    W = A.lane_width(rank + CFG["swa_qk_rope_head_dim"])
    ring = 1 + S * GEO["ring_pages"]
    assert W == 1152
    text = jax.jit(lambda q, pool, ln, new: A._swa_latent_attn(
        q, pool, ln, new, rank=rank, window=CFG["sliding_window_size"],
        interpret=False)).lower(
        sds((S, H, W), bf), sds((ring, ps, W), bf), sds((S,), i32),
        sds((S, W), bf)).compile().as_text()
    assert "tpu_custom_call" in text and "_swa_latent_attn" in text
    assert not re.search(rf"bf16\[{ring},{ps},{W}\]\S* copy\(", text)


@pytest.mark.slow
def test_segment_and_chunk_programs_fit_the_chip(one_chip, monkeypatch):
    """The two programs the window runs, whole, at the cell's geometry:
    they compile for the v5e (every kernel inside), read each pool where
    it lies, and weights + pools + temporaries leave room in 16 GB.
    Marked slow (``pytest -m slow``): a minute of all-core compiling
    beside tier-1's workers shifts the replayed task times that
    ``tests/test_stress_rankcheck.py`` compares; run it before a chip
    call that touches either program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners import dots3_serve
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import dots3
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    sds, i32 = _sds(one_chip), jnp.int32
    mcfg = dots3_serve.model_config(CFG)
    S, ps, ppseq, P = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages"))
    ddag = build_paged_decode_dag(
        mcfg, slots=S, page_size=ps, n_pages=P, pages_per_seq=ppseq,
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

    assert 8.1 < gb(weights) < 8.3 and 0.9 < gb(pools) < 1.05
    # a window layer's pool does not grow with the context
    assert pools["cache_w_2"].shape == (1 + S * GEO["ring_pages"], ps, 1152)

    seg = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 1), i32), sds((S,), i32)).compile()
    text = seg.as_text()
    for name in ("_dsa_index", "_dsa_sparse_attn", "_swa_latent_attn",
                 "_moe_experts"):
        assert name in text, name
    for shape in (f"{P},{ps},128", f"{P},{ps},640",
                  f"{1 + S * GEO['ring_pages']},{ps},1152"):
        assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text), shape
        assert not re.search(rf"copy-start\S*\(bf16\[{shape}\]", text), shape
    assert seg.memory_analysis().temp_size_in_bytes < 2e9

    spec, cap = dots3.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal, ring):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, page_size=ps), pools, pages,
            1, cap, ring)
        last, cache = dots3.forward_cached_row(
            w, ids, cache, pos0, mcfg, creal - 1, impl="auto")
        return (jnp.argmax(last, axis=-1).astype(i32),
                spec.scatter(pools, cache, pages, ps, ring))

    done = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, sds((1, GEO["chunk_tokens"]), i32), pools, sds((ppseq,), i32),
        sds((), i32), sds((), i32), sds((GEO["ring_pages"],), i32)).compile()
    assert done.memory_analysis().temp_size_in_bytes < 3e9

"""The Ouro cell's two programs compiled for the v5e at the published
widths and the file's own engine, without a chip, as
``test_benchmark_nemotron_aot.py`` does for its cell (same fixture: the
topology is described only inside it, and where the TPU's library cannot
be loaded the tests skip).  What the compile has to show for a looped
stack: the passes are ONE traced loop (48 attention call sites, not
192), the pools' planes are read and written where they lie, and the
whole thing fits beside the weights with room to spare."""

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
                  / "ouro-2.6b-serve.json").read_text())
GEO = CFG["engine"]
HBM = 15.75e9      # what a v5e chip reports


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


def test_segment_and_chunk_programs_fit_the_chip_with_the_passes_rolled(
        one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark.runners import ouro_serve
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import ouro
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mcfg = ouro_serve.model_config(CFG)
    S, ps, ppseq, n_pages = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages"))
    L, U = mcfg.n_layers, mcfg.total_ut_steps
    ddag = build_paged_decode_dag(
        mcfg, slots=S, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl="auto")
    # embed, (48 layers + the pass's end) x 4, logits
    assert len(ddag.graph.tasks()) == 2 + U * (L + 1) == 198
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler(GEO["scheduler"]).schedule(ddag.graph, cluster)
    specs = {k: sds(v.shape, v.dtype) for k, v in ddag.param_specs.items()}
    pools = {k: v for k, v in specs.items() if k.startswith("cache_")}
    weights = {k: v for k, v in specs.items()
               if k not in pools and k != "page_table"}

    def gb(d):
        return sum(np.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in d.values()) / 1e9

    assert sorted(pools) == sorted(
        f"cache_{k}_{i}" for i in range(L) for k in "kv")
    assert pools["cache_k_0"].shape == (U * n_pages, ps, 2048)
    # 5.34 GB of weights; a page id is 25.2 MB across the 192 entries
    assert 5.33 < gb(weights) < 5.35
    assert gb(pools) * 1e9 == n_pages * ps * 1_572_864

    lowered = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 1), i32), sds((S,), i32))
    # one call site a layer: the passes are a loop in the program
    assert lowered.as_text().count("call @_paged_flash") == L
    seg = lowered.compile()
    text = seg.as_text()
    assert "_paged_flash" in text
    pool = rf"bf16\[{U * n_pages},{ps},2048\]"
    assert not re.search(rf"{pool}\S* copy\(", text)
    assert not re.search(rf"copy-start\S*\({pool}", text)
    mem = seg.memory_analysis()
    assert mem.temp_size_in_bytes < 0.25e9
    # the donated pools come back in their own buffers
    assert mem.alias_size_in_bytes >= 0.99 * gb(pools) * 1e9
    seg_need = mem.argument_size_in_bytes + mem.temp_size_in_bytes

    spec, cap = ouro.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, ps, True), pools, pages, 1,
            cap, in_pages=True)
        last, cache = ouro.forward_cached_row(
            w, ids, cache, pos0, mcfg, creal - 1, impl="auto",
            pages=pages[None])
        return (jnp.argmax(last, -1).astype(i32), spec.scatter(
            pools, cache, pages, ps, in_pages=True))

    low = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, sds((1, GEO["chunk_tokens"]), i32), pools,
        sds((ppseq,), i32), sds((), i32), sds((), i32))
    assert low.as_text().count("call @_gqa_chunk_flash_paged") == L
    done = low.compile()
    text = done.as_text()
    assert "_gqa_chunk_flash_paged" in text
    assert not re.search(rf"{pool}\S* copy\(", text)
    assert not re.search(rf"copy-start\S*\({pool}", text)
    mem = done.memory_analysis()
    assert mem.temp_size_in_bytes < 0.25e9
    assert mem.alias_size_in_bytes >= 0.99 * gb(pools) * 1e9
    # both programs leave the runtime at least 0.5 GB of the chip
    need = max(seg_need, mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert 12e9 < need < HBM - 0.5e9

"""The GLM cell's verifying kernel and its two programs compiled for the
v5e at the cell's own widths, without a chip, as
``test_benchmark_xing4_aot.py`` does for the Xing4.0 cell (same fixture:
the topology is described only inside it, and where the TPU's library
cannot be loaded the tests skip)."""

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
                  / "glm-4.7-flash-serve.json").read_text())
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


def test_two_row_mla_kernel_compiles_and_reads_the_pool_where_it_lies(
        one_chip):
    """``q_rows = 2`` at the served geometry: 40 query rows a slot in one
    walk, under the draft layer's trace name; the pool is taken in the
    layout XLA gives the argument — no transposing copy in front."""
    import jax
    import jax.numpy as jnp

    from benchmark.runners import glm_serve
    from distributed_llm_scheduler_tpu.models.xing4 import latent_row_width
    from distributed_llm_scheduler_tpu.ops import attention as A

    cfg = glm_serve.model_config(CFG)
    W, S, ps = latent_row_width(cfg), GEO["slots"], GEO["page_size"]
    assert (W, cfg.kv_lora_rank, cfg.n_heads) == (640, 512, 20)
    assert not A.mla_kernel_constraints(ps, W, 512, jnp.bfloat16)
    sds, bf = _sds(one_chip), jnp.bfloat16

    def call(q, pool, table, lengths, new):
        return A._mla_paged_flash(
            q, pool, table, lengths, new, rank=512, has_new=True,
            interpret=False, q_rows=2, name="_mtp_mla_paged_flash")

    text = jax.jit(call).lower(
        sds((S, 2 * cfg.n_heads, W), bf), sds((GEO["n_pages"], ps, W), bf),
        sds((S, GEO["pages_per_seq"]), jnp.int32), sds((S,), jnp.int32),
        sds((S, 2, W), bf)).compile().as_text()
    assert "tpu_custom_call" in text and "_mtp_mla_paged_flash" in text
    pool = rf"bf16\[{GEO['n_pages']},{ps},{W}\]"
    assert not re.search(rf"{pool}\S* copy\(", text)


def test_segment_and_chunk_programs_fit_the_chip(one_chip, monkeypatch):
    """The two programs the window runs, whole, at the cell's geometry:
    they compile for the v5e (every kernel inside, the draft layer's
    under their own names), read each pool where it lies, and weights +
    pools + temporaries leave room in 16 GB (~11 s: six layers and a
    scan compile fast, so it runs with the rest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners import glm_serve
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.decode_loop import (
        build_paged_decode_loop,
    )
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import glm4_lite
    from distributed_llm_scheduler_tpu.ops import attention as A

    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    sds, i32 = _sds(one_chip), jnp.int32
    mcfg = glm_serve.model_config(CFG)
    S, ps, ppseq, P = (GEO[k] for k in (
        "slots", "page_size", "pages_per_seq", "n_pages"))
    ddag = build_paged_decode_dag(
        mcfg, slots=S, page_size=ps, n_pages=P, pages_per_seq=ppseq,
        attention_impl="auto")
    assert [t.task_id for t in ddag.graph][-2:] == ["logits", "draft"]
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler(GEO["scheduler"]).schedule(ddag.graph, cluster)
    specs = {k: sds(v.shape, v.dtype) for k, v in ddag.param_specs.items()}
    pools = {k: v for k, v in specs.items() if k.startswith("cache_")}
    weights = {k: v for k, v in specs.items()
               if k not in pools and k != "page_table"}

    def gb(d):
        return sum(np.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in d.values()) / 1e9

    assert 9.0 < gb(weights) < 9.15 and 1.75 < gb(pools) < 1.85
    assert len(pools) == 7

    seg = build_paged_decode_loop(
        ddag.graph, plan, mcfg, GEO["seg_steps"]).lower(
        weights, pools, sds((S, ppseq), i32), sds((S,), i32),
        sds((S, 2), i32), sds((S,), i32)).compile()
    text = seg.as_text()
    for name in ("_mla_paged_flash", "_moe_experts", "_mtp_mla_paged_flash",
                 "_mtp_moe_experts"):
        assert name in text, name
    shape = f"{P},{ps},640"
    assert not re.search(rf"bf16\[{shape}\]\S* copy\(", text)
    assert not re.search(rf"copy-start\S*\(bf16\[{shape}\]", text)
    assert seg.memory_analysis().temp_size_in_bytes < 2.5e9

    spec, cap = glm4_lite.cache_spec(mcfg), ppseq * ps

    def chunk(w, ids, pools, pages, pos0, creal):
        cache = spec.gather(
            spec.init_dense(1, cap, mcfg.dtype, page_size=ps), pools, pages,
            1, cap)
        last, draft, cache = glm4_lite.forward_cached_draft(
            w, ids[0], ids[1], cache, pos0, mcfg, creal - 1, impl="auto")
        return (jnp.stack([jnp.argmax(last, -1), jnp.argmax(draft, -1)],
                          -1).astype(i32),
                spec.scatter(pools, cache, pages, ps))

    ids = sds((1, GEO["chunk_tokens"]), i32)
    done = jax.jit(chunk, donate_argnums=(2,)).lower(
        weights, (ids, ids), pools, sds((ppseq,), i32),
        sds((), i32), sds((), i32)).compile()
    assert done.memory_analysis().temp_size_in_bytes < 3e9

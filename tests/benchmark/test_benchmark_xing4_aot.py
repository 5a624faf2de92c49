"""The Xing4.0 cell's three kernels compiled for the v5e at the cell's
own widths, without a chip, as ``test_benchmark_aot.py`` does for the
GPT-2 cells (same fixture: the topology is described only inside it, and
where the TPU's library cannot be loaded the tests skip)."""

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
                  / "xing4-29b-a4b-serve.json").read_text())


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


def test_mla_paged_kernel_compiles_and_reads_the_pool_where_it_lies(one_chip):
    """At the served geometry the kernel takes the pool in the layout
    XLA gives the argument (row on the lanes): no transposing copy of
    the pool in front of the call, which a 576-wide row would cost."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models.xing4 import (
        Xing4Config,
        latent_row_width,
    )
    from distributed_llm_scheduler_tpu.ops import attention as A

    cfg, geo = Xing4Config.from_hf(CFG), CFG["engine"]
    W, S, ps = latent_row_width(cfg), geo["slots"], geo["page_size"]
    assert (W, cfg.kv_lora_rank) == (640, 512)
    assert not A.mla_kernel_constraints(ps, W, 512, jnp.bfloat16)
    sds, bf = _sds(one_chip), jnp.bfloat16

    def call(q, pool, table, lengths, new):
        return A._mla_paged_flash(q, pool, table, lengths, new, rank=512,
                                  has_new=True, interpret=False)

    text = jax.jit(call).lower(
        sds((S, cfg.n_heads, W), bf), sds((geo["n_pages"], ps, W), bf),
        sds((S, geo["pages_per_seq"]), jnp.int32), sds((S,), jnp.int32),
        sds((S, W), bf)).compile().as_text()
    assert "tpu_custom_call" in text and "_mla_paged_flash" in text
    pool = rf"bf16\[{geo['n_pages']},{ps},{W}\]"
    assert re.search(pool + r"\{2,1,0", text)
    assert not re.search(pool + r"\S* copy\(", text)


@pytest.mark.parametrize("tokens", [16, 512])
def test_moe_and_hc_kernels_compile_at_the_served_widths(one_chip, tokens):
    """A decode step's 16 tokens and a chunk's 512."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models import xing4 as X

    cfg = X.Xing4Config.from_hf(CFG)
    sds, bf, f32 = _sds(one_chip), jnp.bfloat16, jnp.float32
    h, E, I = cfg.hidden_size, cfg.n_routed_experts, cfg.moe_intermediate_size
    k, n = cfg.experts_per_tok, cfg.hc_mult

    def moe(x, idx, gate, gu, dw):
        return X._moe_experts(x, idx, gate, gu, dw, impl="pallas")

    text = jax.jit(moe).lower(
        sds((tokens, h), bf), sds((tokens, k), jnp.int32),
        sds((tokens, k), f32), sds((E, 2 * I, h), bf),
        sds((E, I, h), bf)).compile().as_text()
    assert "tpu_custom_call" in text and "_moe_experts" in text

    def maps(xf, phi, alpha, b):
        return X._hc_maps(
            xf, phi, alpha, b, n=n, iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps, clamp=cfg.hc_clamp, rms_eps=cfg.rms_eps,
            impl="pallas")

    maps_n = 2 * n + n * n
    text = jax.jit(maps).lower(
        sds((tokens, n * h), bf), sds((maps_n, n * h), f32), sds((3,), f32),
        sds((maps_n,), f32)).compile().as_text()
    assert "tpu_custom_call" in text and "_hc_maps" in text

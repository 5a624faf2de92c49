"""The kernels of the benchmark's cells compiled for the v5e at the
cells' own widths, without a chip (about two seconds each).  The
topology is described only inside the fixture; all such compiles live in
this one file (see the on-chip-measurement guide, section 2)."""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _config(name: str) -> dict:
    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        return json.load(f)


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
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_paged_decode_kernel_compiles_at_xl_serving_geometry(one_chip):
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.ops import attention as A

    cfg = _config("gpt2-xl-serve")
    geo = cfg["engine"]
    H, hd = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    assert (H, hd) == (25, 64)
    S, ps = geo["slots"], geo["page_size"]
    dt = jnp.dtype(cfg["dtype"])

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, kp, vp, table, lengths, kn, vn):
        return A._paged_flash(q, kp, vp, table, lengths, kn, vn,
                              sm_scale=hd ** -0.5, has_new=True,
                              interpret=False)

    compiled = jax.jit(call).lower(
        sds((S, H, 1, hd)), sds((geo["n_pages"], ps, H, hd)),
        sds((geo["n_pages"], ps, H, hd)),
        sds((S, geo["pages_per_seq"]), jnp.int32), sds((S,), jnp.int32),
        sds((S, H, 1, hd)), sds((S, H, 1, hd)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_mha_compiles_at_medium_dag_shapes(one_chip):
    import jax
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.ops import attention as A

    cfg = _config("gpt2-medium-dag")
    with open(ROOT / "benchmark" / "traffic" / "fwd-heft.json") as f:
        tr = json.load(f)
    H, hd = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    rows, T = tr["batch"] // tr["microbatches"], tr["seq_len"]
    x = jax.ShapeDtypeStruct((rows, H, T, hd), jnp.dtype(cfg["dtype"]),
                             sharding=one_chip)

    def call(q, k, v):
        return A._flash_mha(q, k, v, causal=True, sm_scale=hd ** -0.5,
                            block=A._pick_block(T), interpret=False)

    compiled = jax.jit(call).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()

"""Pallas kernel numerics: flash attention vs the XLA oracle.

Runs the kernels in interpreter mode (CPU-safe per conftest's faked
8-device CPU mesh) and compares against the plain-XLA reference paths —
the same scheme the reference uses for "multi-node without a cluster"
applied to "TPU kernels without a TPU" (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_scheduler_tpu.ops import (
    gqa_mha,
    mha,
    pallas_supported,
    reference_mha,
)


def _qkv(B=2, H=3, T=64, hd=32, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, H, T, hd), dtype=dtype)
        for i in range(3)
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = _qkv()
    ref = mha(q, k, v, causal=causal, impl="xla")
    pal = mha(q, k, v, causal=causal, impl="pallas_interpret")
    assert jnp.abs(ref - pal).max() < 1e-4


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = mha(q, k, v, impl="xla").astype(jnp.float32)
    pal = mha(q, k, v, impl="pallas_interpret").astype(jnp.float32)
    assert jnp.abs(ref - pal).max() < 3e-2


def test_flash_multiblock_causality():
    # T=64 with block<=32 forces the causal loop across several K/V blocks;
    # row i of the output must ignore positions > i entirely
    q, k, v = _qkv(B=1, H=1, T=64, hd=32)
    out_full = mha(q, k, v, impl="pallas_interpret")
    # perturb the "future" half of k/v: rows < 32 must not change
    k2 = k.at[:, :, 32:].set(99.0)
    v2 = v.at[:, :, 32:].set(-99.0)
    out_perturbed = mha(q, k2, v2, impl="pallas_interpret")
    assert jnp.allclose(out_full[:, :, :32], out_perturbed[:, :, :32], atol=1e-5)
    assert not jnp.allclose(out_full[:, :, 32:], out_perturbed[:, :, 32:], atol=1.0)


def test_flash_gradients():
    """jax.grad through the kernel path must work (training-step DAGs
    differentiate through causal_attention on TPU where pallas is auto)."""
    q, k, v = _qkv(B=1, H=2, T=32, hd=16)

    def loss(impl):
        def f(q, k, v):
            return (mha(q, k, v, impl=impl) ** 2).sum()
        return f

    ref_grads = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    pal_grads = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
    for r, p in zip(ref_grads, pal_grads):
        assert jnp.abs(r - p).max() < 1e-3


def test_gqa_broadcast():
    q, k, v = _qkv(H=4)
    ref = gqa_mha(q, k[:, :2], v[:, :2], impl="xla")
    pal = gqa_mha(q, k[:, :2], v[:, :2], impl="pallas_interpret")
    assert jnp.abs(ref - pal).max() < 1e-4


def test_tiny_shape_falls_back():
    q, k, v = _qkv(T=4, hd=8)
    assert not pallas_supported(q.shape)
    out = mha(q, k, v)  # auto impl must not crash on unsupported shapes
    assert jnp.abs(out - reference_mha(q, k, v)).max() < 1e-5


def test_explicit_kernel_on_ineligible_shape_raises():
    """An explicit kernel request is never quietly served by the XLA
    path: the same tiny shape ``auto`` routes around must raise."""
    q, k, v = _qkv(T=4, hd=8)
    with pytest.raises(ValueError, match="requested explicitly"):
        mha(q, k, v, impl="pallas_interpret")


def test_models_use_dispatcher():
    """GPT-2/Llama tiny forwards still match their DAG-executed oracles
    after the flash-attention integration (covered in depth by
    test_gpt2_dag/test_llama); here just smoke the fused forward."""
    from distributed_llm_scheduler_tpu.models import gpt2

    config = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, config.vocab_size)
    logits = gpt2.forward(params, ids, config)
    assert logits.shape == (1, 32, config.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_output_projection_orientations_agree():
    """The decode-shape MXU-natural head (wte @ x', contraction on lanes
    for both operands) must produce the standard x @ wte.T logits on
    both sides of the 64-row threshold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_scheduler_tpu.models.gpt2 import output_projection

    wte = jax.random.normal(jax.random.PRNGKey(0), (512, 64))
    for b, t in ((2, 1), (8, 8), (4, 32)):  # 2, 64 (boundary), 128 rows
        x = jax.random.normal(jax.random.PRNGKey(1), (b, t, 64))
        got = output_projection(x, wte)
        want = x @ wte.T
        assert got.shape == (b, t, 512)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

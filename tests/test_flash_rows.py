"""The row form of dense attention (``ops/flash_rows.py``): the kernel
interpreted on the CPU against ``reference_mha``, the shape rule, the
fallback to the head-major path, the gradient, and the count that
``build_gpt2_dag`` stamps on the graph."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu.models import gpt2
from distributed_llm_scheduler_tpu.ops import attention as A
from distributed_llm_scheduler_tpu.ops import flash_rows as R

KERNEL = "pallas_interpret"
BLOCK = R._BLOCK


def _qkv(B, T, H, hd, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal((B, T, 3 * H * hd)), dtype)


def _reference(qkv, H, causal, sm_scale=None):
    """``reference_mha`` on the float32 head-split view, merged back."""
    q, k, v = (t.astype(jnp.float32) for t in R._split_heads(qkv, H))
    return R._merge_heads(
        A.reference_mha(q, k, v, causal=causal, sm_scale=sm_scale))


def _old_causal_attention(x, qkv_w, qkv_b, proj_w, proj_b, n_head):
    """``gpt2.causal_attention`` as it stood before the row form: what a
    call the shape rule refuses must still be."""
    B, T, D = x.shape
    hd = D // n_head
    qkv = x @ qkv_w + qkv_b
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    out = A.mha(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
    return out @ proj_w + proj_b


def _attention_args(B, T, D, dtype, seed=1):
    rng = np.random.RandomState(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), dtype)

    return (arr(B, T, D), arr(D, 3 * D, scale=0.1), arr(3 * D, scale=0.1),
            arr(D, D, scale=0.1), arr(D, scale=0.1))


# every T, head geometry, batch, dtype and mask of ISSUE 49's list, each
# value of one beside each value of T and of the geometry, without the
# whole product (the interpreted kernel takes seconds a case)
_GEOMETRIES = [(2, 64), (16, 64), (4, 128)]
CASES = [
    (B, T, H, hd, dtype, causal)
    for ti, T in enumerate([128, 256, 512, 1024])
    for gi, (H, hd) in enumerate(_GEOMETRIES)
    for B, dtype, causal in [
        ((1, 4)[(ti + gi) % 2] if T <= 256 else 1,
         (jnp.bfloat16, jnp.float32)[(ti + gi + 1) % 2], True),
        (1, (jnp.bfloat16, jnp.float32)[(ti + gi) % 2], gi != ti % 3),
    ][:1 if T == 1024 and gi else 2]
]


@pytest.mark.parametrize(
    "B,T,H,hd,dtype,causal", CASES,
    ids=[f"B{B}-T{T}-H{H}x{hd}-{jnp.dtype(d).name}-"
         f"{'causal' if c else 'full'}" for B, T, H, hd, d, c in CASES])
def test_rows_kernel_matches_the_reference(B, T, H, hd, dtype, causal):
    assert R.rows_impl(KERNEL, T, H, hd, dtype) == KERNEL
    qkv = _qkv(B, T, H, hd, dtype, seed=T + H)
    out = R.mha_rows(qkv, n_head=H, causal=causal, impl=KERNEL)
    assert out.shape == (B, T, H * hd) and out.dtype == qkv.dtype
    want = _reference(qkv, H, causal)
    if dtype == jnp.float32:
        tol = dict(rtol=1e-5, atol=1e-5)
    else:  # the output's own rounding, and the probabilities' in the PV
        tol = dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), **tol)


@pytest.mark.parametrize("T", [256, 512])
@pytest.mark.parametrize("H,hd", _GEOMETRIES[::2], ids=["2x64", "4x128"])
def test_block_edges_see_their_own_prefix_and_no_further(T, H, hd):
    """Float32, so that one key too many or too few shows: the last row
    of every query block and the first of the next against the reference
    at 1e-5 — a row that saw one key more differs by ~|v| / row."""
    qkv = _qkv(1, T, H, hd, jnp.float32, seed=3)
    out = np.asarray(R.mha_rows(qkv, n_head=H, impl=KERNEL))
    want = np.asarray(_reference(qkv, H, True))
    edges = sorted({r for b in range(BLOCK, T + 1, BLOCK)
                    for r in (b - 1, b) if r < T} | {0})
    assert len(edges) >= 2 * (T // BLOCK)
    np.testing.assert_allclose(out[:, edges], want[:, edges],
                               rtol=1e-5, atol=1e-5)
    # and the mask is the diagonal's: a spike in the key just past a
    # block's last row reaches the next row and not that one
    D = H * hd
    spiked = qkv.at[0, BLOCK, 2 * D:].set(1e3)        # v of key BLOCK
    got = np.asarray(R.mha_rows(spiked, n_head=H, impl=KERNEL))
    np.testing.assert_allclose(got[0, BLOCK - 1], out[0, BLOCK - 1],
                               rtol=1e-6, atol=1e-6)
    assert np.abs(got[0, BLOCK] - out[0, BLOCK]).max() > 1.0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_views_into_one_projection_equal_three_arrays(dtype, causal):
    B, T, H, hd = 2, 256, 4, 64
    qkv = _qkv(B, T, H, hd, dtype, seed=5)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    packed = R.mha_rows(qkv, n_head=H, causal=causal, impl=KERNEL)
    apart = R.mha_rows(q, k, v, n_head=H, causal=causal, impl=KERNEL)
    np.testing.assert_array_equal(np.asarray(packed, np.float32),
                                  np.asarray(apart, np.float32))


def test_a_scale_that_is_no_power_of_two_is_applied_to_the_scores():
    """Head 128's 1/sqrt(128) must not round q a second time in bf16."""
    qkv = _qkv(1, 128, 2, 128, jnp.bfloat16, seed=7)
    out = R.mha_rows(qkv, n_head=2, impl=KERNEL)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(_reference(qkv, 2, True)),
        rtol=2e-2, atol=2e-2)
    custom = R.mha_rows(qkv, n_head=2, sm_scale=0.3, impl=KERNEL)
    np.testing.assert_allclose(
        np.asarray(custom, np.float32),
        np.asarray(_reference(qkv, 2, True, sm_scale=0.3)),
        rtol=2e-2, atol=2e-2)


# -- the shape rule ---------------------------------------------------------

@pytest.mark.parametrize("T,H,hd,dtype,takes", [
    (512, 12, 64, jnp.bfloat16, True),      # GPT-2 small
    (512, 16, 64, jnp.bfloat16, True),      # medium (the benchmark's)
    (1024, 20, 64, jnp.bfloat16, True),     # large
    (512, 25, 64, jnp.bfloat16, False),     # XL: 12.5 tiles
    (512, 3, 64, jnp.bfloat16, False),      # 1.5 tiles
    (512, 16, 8, jnp.float32, False),       # sixteen heads a tile
    (128, 4, 32, jnp.float32, True),        # four heads a tile
    (512, 8, 32, jnp.bfloat16, True),       # ... whose scores are 1 MiB
    (1024, 8, 32, jnp.bfloat16, False),     # ... and 2 MiB: past VMEM
    (1024, 2, 256, jnp.bfloat16, True),     # a tile's blocks 4 MiB
    (1024, 2, 256, jnp.float32, False),     # ... and 8 MiB: past VMEM
    (1024, 1, 512, jnp.bfloat16, False),
    (128, 1, 256, jnp.float32, True),       # a head of two tiles
    (128, 2, 96, jnp.float32, False),       # a head that splits a tile
    (64, 2, 64, jnp.float32, False),        # T short of a block
    (192, 2, 64, jnp.float32, False),       # T not whole blocks
    (2048, 2, 64, jnp.bfloat16, False),     # past what is unrolled
    (512, 16, 64, jnp.float16, False),      # a dtype the kernel is not for
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_shape_rule(T, H, hd, dtype, takes):
    assert R.rows_supported(T, H, hd, dtype) is takes
    assert (R.rows_impl(KERNEL, T, H, hd, dtype) == KERNEL) is takes
    # XLA, asked for or chosen off the TPU, is never the row form
    assert R.rows_impl("xla", T, H, hd, dtype) is None
    assert R.rows_impl(None, T, H, hd, dtype) is None


@pytest.mark.parametrize("H,hd,T", [(25, 64, 128), (3, 64, 128), (4, 8, 32)],
                         ids=["xl-25x64", "3x64", "tiny-head"])
@pytest.mark.parametrize("impl", [None, "xla", KERNEL],
                         ids=["auto", "xla", "kernel"])
def test_refused_shapes_run_what_they_ran_before(H, hd, T, impl, monkeypatch):
    """The fallback is split -> ``mha`` -> merge: the same values as the
    head-major path gives, bit for bit, and — through
    ``causal_attention`` — the program text it had before."""
    dtype = jnp.float32
    assert R.rows_impl(impl, T, H, hd, dtype) is None
    qkv = _qkv(2, T, H, hd, dtype, seed=11)
    got = R.mha_rows(qkv, n_head=H, impl=impl)
    want = R._merge_heads(A.mha(*R._split_heads(qkv, H), impl=impl))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if impl is not None:
        monkeypatch.setattr(A, "_auto_impl", lambda: impl)
    args = _attention_args(2, T, H * hd, dtype)
    new = jax.make_jaxpr(lambda *a: gpt2.causal_attention(*a, H))(*args)
    old = jax.make_jaxpr(lambda *a: _old_causal_attention(*a, H))(*args)
    assert str(new) == str(old)
    np.testing.assert_array_equal(
        np.asarray(gpt2.causal_attention(*args, H)),
        np.asarray(_old_causal_attention(*args, H)))


@pytest.mark.parametrize("T,H,hd", [(12, 3, 64), (8, 25, 64)],
                         ids=["T12-3x64", "T8-25x64"])
@pytest.mark.parametrize("impl", ["pallas", KERNEL])
def test_an_explicit_kernel_neither_form_takes_raises(T, H, hd, impl):
    qkv = _qkv(1, T, H, hd, jnp.float32)
    assert not A.pallas_supported((1, H, T, hd))
    with pytest.raises(ValueError, match="requested explicitly"):
        R.mha_rows(qkv, n_head=H, impl=impl)
    with pytest.raises(ValueError, match="unknown attention impl"):
        R.mha_rows(qkv, n_head=H, impl="mosaic")


def test_an_explicit_kernel_falls_to_the_head_major_one_where_it_can():
    """(3, 64) at T 128: not the row form's, the head-major kernel's."""
    qkv = _qkv(1, 128, 3, 64, jnp.float32, seed=13)
    assert A.pallas_supported((1, 3, 128, 64))
    got = R.mha_rows(qkv, n_head=3, impl=KERNEL)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference(qkv, 3, True)),
                               rtol=1e-5, atol=1e-5)


# -- through the model's task ------------------------------------------------

@pytest.fixture
def kernel_on(monkeypatch):
    """``auto`` resolves as on a TPU, to the interpreted kernel."""
    monkeypatch.setattr(A, "_auto_impl", lambda: KERNEL)


@pytest.mark.parametrize("H", [2, 1], ids=["2x64", "1x128"])
def test_causal_attention_takes_the_row_form(kernel_on, H):
    args = _attention_args(2, 128, 128, jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: gpt2.causal_attention(*a, H))(*args))
    assert "_flash_mha_rows" in text and "transpose" not in text
    np.testing.assert_allclose(
        np.asarray(gpt2.causal_attention(*args, H)),
        np.asarray(_old_causal_attention(*args, H)), rtol=2e-5, atol=2e-5)


def test_vjp_through_causal_attention_equals_the_references(kernel_on,
                                                            monkeypatch):
    args = _attention_args(2, 128, 128, jnp.float32, seed=17)
    H = 2
    out, vjp = jax.vjp(lambda *a: gpt2.causal_attention(*a, H), *args)
    g = jnp.asarray(
        np.random.RandomState(19).standard_normal(out.shape), out.dtype)
    grads = vjp(g)
    monkeypatch.setattr(A, "_auto_impl", lambda: "xla")
    ref_out, ref_vjp = jax.vjp(
        lambda *a: _old_causal_attention(*a, H), *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(grads, ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_head,impl,expected", [
    (2, KERNEL, 6),     # 3 layers x 2 microbatches
    (2, "xla", 0),      # the CPU's own choice
    (4, KERNEL, 6),     # four heads of 32 a tile
    (1, KERNEL, 6),     # one head of 128
], ids=["2x64-kernel", "2x64-xla", "4x32-kernel", "1x128-kernel"])
def test_the_dag_counts_its_row_form_tasks(monkeypatch, n_head, impl,
                                           expected):
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.gpt2_dag import build_gpt2_dag
    from distributed_llm_scheduler_tpu.obs import (
        process_metrics,
        reset_ambient,
    )

    monkeypatch.setattr(A, "_auto_impl", lambda: impl)
    cfg = gpt2.GPT2Config(vocab_size=256, n_positions=128, n_embd=128,
                          n_layer=3, n_head=n_head)
    dag = build_gpt2_dag(cfg, batch=2, seq_len=128, microbatches=2)
    assert dag.graph.attn_row_form_tasks == expected
    short = build_gpt2_dag(cfg, batch=2, seq_len=64, microbatches=2)
    assert short.graph.attn_row_form_tasks == 0
    if impl != "xla" and n_head != 2:
        return
    # execute() reports the stamp once a call
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    schedule = get_scheduler("heft").schedule(dag.graph, cluster)
    params = dag.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 128), jnp.int32)
    reset_ambient()
    backend = DeviceBackend(cluster)
    rep = backend.execute(dag.graph, schedule, params, ids)
    hist = process_metrics().snapshot()["histograms"][
        "execute.attn_row_form_tasks"]
    assert hist["max"] == hist["p50"] == expected and hist["count"] >= 1
    want = dag.reference_forward(params, ids)
    np.testing.assert_allclose(np.asarray(rep.output), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    reset_ambient()

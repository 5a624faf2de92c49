"""The prefill's expanded-MLA kernel (``ops.attention._mla_chunk_flash``)
interpreted on the CPU at toy widths: against a dense softmax for every
mask source and shape corner, against the XLA loops the three latent
families keep as its fallback, a chunked prompt's rows bit-equal to a
whole-prompt run's, the dispatch by shape, and the engine's count of the
prefill programs whose attention is the kernel."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from distributed_llm_scheduler_tpu.models import (  # noqa: E402
    dots3,
    glm4_lite,
    xing4,
)
from distributed_llm_scheduler_tpu.ops import attention as A  # noqa: E402


def _dense(qn, qr, w_uk, w_uv, rows, pos0, key_pos0, mask, rank, window):
    """Plain softmax over every key at once, float32."""
    T, dr, M = qn.shape[1], qr.shape[-1], rows.shape[1]
    c, k_r = rows[..., :rank], rows[..., rank:rank + dr]
    k = jnp.einsum("bmc,chd->bmhd", c, w_uk)
    v = jnp.einsum("bmc,chd->bmhd", c, w_uv)
    s = (jnp.einsum("bthd,bmhd->bhtm", qn, k)
         + jnp.einsum("bthd,bmd->bhtm", qr, k_r))
    q_pos = pos0 + jnp.arange(T)[:, None]
    k_pos = key_pos0 + jnp.arange(M)[None, :]
    ok = k_pos <= q_pos
    if window is not None:
        ok = ok & (k_pos > q_pos - window) & (k_pos >= 0)
    ok = ok[None] if mask is None else ok[None] & mask
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhtm,bmhd->bthd", p, v)


def _inputs(b, T, H, M, dn=8, dr=4, dv=8, rank=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, T, H, dn)),
            jax.random.normal(ks[1], (b, T, H, dr)),
            jax.random.normal(ks[2], (rank, H, dn)) * 0.3,
            jax.random.normal(ks[3], (rank, H, dv)) * 0.3,
            jax.random.normal(ks[4], (b, M, rank + dr + 4)))


def _flash(qn, qr, w_uk, w_uv, rows, pos0, key_pos0, mask, window, tq, kb):
    return A._mla_chunk_flash(
        qn, qr, w_uk, w_uv, rows, jnp.int32(pos0), jnp.int32(key_pos0), mask,
        rank=w_uk.shape[0], window=window, q_tile=tq, kv_block=kb,
        interpret=True)


# b, T, H, M, pos0, key_pos0, window, mask, q tile, key block
CORNERS = {
    "causal-live-blocks-of-all": (1, 8, 4, 48, 16, 0, None, False, 8, 8),
    "two-sequences-T-not-a-tile-multiple": (2, 12, 2, 48, 10, 0, None,
                                            False, 8, 16),
    "ragged-last-key-block-under-a-selection": (1, 8, 4, 40, 24, 0, None,
                                                True, 8, 16),
    "window-9-ring-rows-before-position-0": (2, 8, 2, 16, 5, -3, 9, False,
                                             8, 8),
    "window-9-later-chunk-two-query-tiles": (2, 8, 2, 16, 40, 32, 9, False,
                                             4, 8),
    "whole-prompt-tiles-skip-later-blocks": (1, 16, 6, 64, 0, 0, None,
                                             False, 8, 16),
    "three-heads-one-a-step": (1, 8, 3, 32, 8, 0, None, True, 8, 8),
}


@pytest.mark.parametrize("name", sorted(CORNERS))
def test_kernel_is_the_dense_softmax_under_the_layers_mask(name):
    b, T, H, M, pos0, kp0, window, masked, tq, kb = CORNERS[name]
    qn, qr, w_uk, w_uv, rows = _inputs(b, T, H, M)
    mask = None
    if masked:
        mask = jax.random.bernoulli(jax.random.PRNGKey(7), 0.5, (b, T, M))
        mask = mask.at[:, jnp.arange(T), pos0 + jnp.arange(T) - kp0].set(True)
        # rows that are allowed nothing in the first key block
        mask = mask.at[:, :3, :kb].set(False)
    got = _flash(qn, qr, w_uk, w_uv, rows, pos0, kp0, mask, window, tq, kb)
    want = _dense(qn, qr, w_uk, w_uv, rows, pos0, kp0, mask,
                  w_uk.shape[0], window)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 9], ids=["causal", "window-9"])
@pytest.mark.parametrize("chunk", [8, 4], ids=["chunks-of-8", "chunks-of-4"])
def test_a_chunked_prompts_rows_are_the_whole_prompt_runs_bit_for_bit(
        window, chunk):
    """Key blocks start at multiples of the tile from key row 0 and a
    query row's arithmetic depends on neither T nor the query tile it
    falls in: 24 rows at once (three query tiles) against the same rows
    a chunk at a time, under one selection."""
    P, M, kb = 24, 32, 8
    qn, qr, w_uk, w_uv, rows = _inputs(2, P, 2, M, seed=3)
    mask = jax.random.bernoulli(jax.random.PRNGKey(11), 0.7, (2, P, M))
    mask = mask.at[:, jnp.arange(P), jnp.arange(P)].set(True)
    whole = _flash(qn, qr, w_uk, w_uv, rows, 0, 0, mask, window, 8, kb)
    for at in range(0, P, chunk):
        part = _flash(qn[:, at:at + chunk], qr[:, at:at + chunk], w_uk, w_uv,
                      rows, at, 0, mask[:, at:at + chunk], window, 8, kb)
        assert np.array_equal(np.asarray(part),
                              np.asarray(whole[:, at:at + chunk])), at


def _xing_case(cfg_cls, mod):
    cfg = cfg_cls.tiny()
    params = mod.init_params(cfg, jax.random.PRNGKey(1), std=0.3)
    return cfg, {k[len("h1_"):]: v for k, v in params.items()
                 if k.startswith("h1_")}


@pytest.mark.parametrize("family", ["xing4", "glm4_lite"])
@pytest.mark.parametrize("pos0", [0, 16], ids=["first-chunk", "at-16"])
def test_causal_layers_kernel_against_their_xla_loop(family, pos0):
    """``xing4.mla_expanded_attention`` (Xing4.0's and GLM's prefill,
    the draft layer's too): the entry point's two ways at the family's
    own toy widths, two sequences, a cache of 40 rows."""
    mod, cls = {"xing4": (xing4, xing4.Xing4Config),
                "glm4_lite": (glm4_lite, glm4_lite.Glm4LiteConfig)}[family]
    cfg, p = _xing_case(cls, mod)
    b, T, cap = 2, 8, 40
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q_nope = jax.random.normal(ks[0], (b, T, cfg.n_heads,
                                       cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(ks[1], (b, T, cfg.n_heads,
                                       cfg.qk_rope_head_dim))
    rows = jax.random.normal(ks[2], (b, cap, xing4.latent_row_width(cfg)))
    out = {impl: xing4.mla_expanded_attention(
        p, q_nope, q_rope, rows, jnp.int32(pos0), cfg, impl)
        for impl in ("xla", "pallas_interpret")}
    assert out["xla"].shape == (b, T, cfg.n_heads * cfg.v_head_dim)
    np.testing.assert_allclose(out["pallas_interpret"], out["xla"],
                               atol=2e-5, rtol=2e-5)


def _dots3_layer(kind):
    cfg = dots3.Dots3Config.tiny()
    layer = next(i for i in range(cfg.n_layers)
                 if cfg.is_full(i) == (kind == "full") and i > 0)
    params = dots3.init_params(cfg, jax.random.PRNGKey(4), std=0.3)
    return cfg, layer, dots3.layer_params(params, cfg, layer)


@pytest.mark.parametrize("pos0", [0, 3, 40], ids=["at-0", "at-3", "at-40"])
def test_window_layer_kernel_against_its_xla_loop_over_a_ring(pos0):
    """A sliding layer over a chunk of 8 at ``pos0``: window 9 over the
    8 rows the ring held before the chunk (at 0 and 3 some of them lie
    before position 0 and are nobody's) and the chunk's own."""
    cfg, layer, p = _dots3_layer("sliding")
    a, b, T = cfg.attn(layer), 2, 8
    xn = jax.random.normal(jax.random.PRNGKey(5), (b * T, cfg.hidden_size))
    ring = jax.random.normal(jax.random.PRNGKey(6),
                             (b, cfg.ring_rows, a.row_width))
    out = {impl: dots3._sliding_prefill_attention(
        p, xn, ring, jnp.int32(pos0), T - 1, b, T, cfg, a, impl)
        for impl in ("xla", "pallas_interpret")}
    np.testing.assert_allclose(out["pallas_interpret"][0], out["xla"][0],
                               atol=2e-5, rtol=2e-5)
    assert np.array_equal(np.asarray(out["pallas_interpret"][1]),
                          np.asarray(out["xla"][1]))


@pytest.mark.parametrize("pos0", [0, 24], ids=["under-the-selection",
                                               "over-the-selection"])
def test_full_layer_kernel_against_its_xla_loop_under_the_selection(pos0):
    """A full layer over a chunk of 8: the indexer's exact selection
    (16 rows; at 24 every query drops rows, whole stretches of a block
    among them) streamed to the kernel as its mask."""
    cfg, layer, p = _dots3_layer("full")
    a, b, T, cap = cfg.attn(layer), 2, 8, 48
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    xn = jax.random.normal(ks[0], (b * T, cfg.hidden_size))
    rows_c = jax.random.normal(ks[1], (b, cap, a.row_width))
    rows_i = jax.random.normal(ks[2], (b, cap, cfg.index_head_dim))
    out = {impl: dots3._full_prefill_attention(
        p, xn, rows_c, rows_i, jnp.int32(pos0), b, T, cfg, a, impl)
        for impl in ("xla", "pallas_interpret")}
    np.testing.assert_allclose(out["pallas_interpret"][0], out["xla"][0],
                               atol=2e-5, rtol=2e-5)


SERVED = {   # T, nope, rope, value, rank, row width: the three cells'
    "dots3-full": (512, 128, 64, 128, 512, 640),
    "dots3-sliding": (512, 192, 64, 128, 1024, 1152),
    "xing4": (512, 128, 64, 128, 512, 640),
    "glm": (512, 192, 64, 256, 512, 640),
}


@pytest.mark.parametrize("name", sorted(SERVED))
def test_the_cells_shapes_take_the_kernel_by_shape_alone(name, monkeypatch):
    shape = SERVED[name]
    assert A.mla_chunk_constraints(*shape, jnp.bfloat16) == []
    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    assert A.resolve_mla_chunk_impl(None, *shape, jnp.bfloat16) == "pallas"
    monkeypatch.setattr(A, "_auto_impl", lambda: "xla")
    assert A.resolve_mla_chunk_impl("auto", *shape, jnp.bfloat16) == "xla"


@pytest.mark.parametrize("shape, what", [
    ((500, 128, 64, 128, 512, 640), "q_tokens 500"),
    ((512, 128, 64, 128, 576, 640), "latent rank 576"),
    ((512, 100, 64, 128, 512, 640), "nope head dim 100"),
    ((512, 128, 64, 96, 512, 640), "value head dim 96"),
], ids=["odd-chunk", "rank-off-the-lanes", "odd-nope", "narrow-value"])
def test_a_shape_off_the_tiling_falls_back_by_shape(shape, what, monkeypatch):
    """``auto`` takes the XLA loop, an explicit ``pallas`` raises, the
    interpreter has no tiling."""
    assert any(what in r for r in A.mla_chunk_constraints(
        *shape, jnp.bfloat16))
    monkeypatch.setattr(A, "_auto_impl", lambda: "pallas")
    assert A.resolve_mla_chunk_impl(None, *shape, jnp.bfloat16) == "xla"
    assert A.resolve_mla_chunk_impl(
        "pallas_interpret", *shape, jnp.bfloat16) == "pallas_interpret"
    with pytest.raises(ValueError, match="does not qualify"):
        A.resolve_mla_chunk_impl("pallas", *shape, jnp.bfloat16)


@pytest.mark.parametrize("model", ["dots3-tiny", "gpt2-tiny"])
def test_the_engine_counts_the_prefill_programs_on_the_kernel(model):
    """``decode.prefill_attn_kernel_programs`` beside ``decode.chunk_waves``
    and ``decode.admission_waves``: every prefill program of a latent
    family served with the kernels, none of a family with another
    attention, none on the XLA loop."""
    from test_serving_lowering import _engine

    counts = {}
    for impl in ("xla", "pallas_interpret"):
        eng = _engine(model, impl)
        eng.submit("a", jnp.ones((1, 8), jnp.int32), 2)
        eng.submit("b", jnp.ones((1, 35), jnp.int32), 2)
        eng.run()
        snap = eng.metrics.snapshot()["counters"]
        counts[impl] = {k.split(".", 1)[1]: v["value"]
                        for k, v in snap.items()}
    on = counts["pallas_interpret"]
    assert (on["chunk_waves"], on["admission_waves"]) == (3, 1)
    latent = model != "gpt2-tiny"
    assert on.get("prefill_attn_kernel_programs", 0) == (4 if latent else 0)
    assert "prefill_attn_kernel_programs" not in counts["xla"]
    # both keep the dense round trip of the chunk program: a latent row
    # needs no turn, GPT-2's head of 64 is no whole lane tile
    assert "prefill_paged_chunk_programs" not in {**on, **counts["xla"]}

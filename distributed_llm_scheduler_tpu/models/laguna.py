"""Laguna family (``model_type`` ``laguna``): the served block.

Grouped-query attention whose keys are rotated BEFORE they are cached,
in two kinds of layer that differ on the ``kv`` path (:func:`cache_spec`
is per layer):

* a **full layer** (``layer_heads`` 48 of 128 over 8 KV heads) rotates the
  first ``full_rotary_dim`` values of each head with YaRN frequencies,
  ``cos`` and ``sin`` times ``full_attention_factor``, and pages its
  rotated K and its V for the whole context in the shared pool
  (``_paged_flash`` over the live blocks, groups of 6);
* a **sliding layer** (72 heads, groups of 9) rotates every value at a
  plain theta and sees positions ``t - sliding_window < s <= t``; its K
  and V rows live in rings the slot owns (:class:`.kv_pages.CacheSpec`,
  ring layers of the ``kv`` kind; ``_swa_kv_attn``), so it costs nothing
  per context token.

Every head's output is gated by ``sigmoid(xn W_g)`` before ``W_o``
(``dots3.head_gate``'s form).  The FFN is SwiGLU in ``dense_layers`` and
elsewhere softmax-routed experts — the ``experts_per_tok`` largest of
``softmax(xn W_r)`` renormalised, times ``routed_scaling_factor`` — plus
an ungated shared expert; a chip may hold a share of the routed ones
(``held_experts``: the router keeps its published width).  The grouped
expert kernel and ``moe_ffn`` are ``xing4``'s, the gate ``dots3``'s.
"""

from __future__ import annotations

import math
import types
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import paged_decode_attention
from ..ops.gqa_attention import (
    gqa_chunk_attention,
    gqa_paged_chunk_attention,
    kv_window_attention,
)
from .dots3 import head_gate
from .kv_pages import write_chunk_pages
from .xing4 import _swiglu, moe_ffn, rms_norm, yarn_inv_freq

FULL, SLIDING = "full", "sliding"


def published_layer_types(n_layers: int) -> Tuple[str, ...]:
    """The published pattern cut to ``n_layers``: full, sliding x 3."""
    return tuple(FULL if i % 4 == 0 else SLIDING for i in range(n_layers))


@dataclass(frozen=True)
class LagunaConfig:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    layer_types: Tuple[str, ...] = published_layer_types(48)
    #: query heads of each layer (``num_attention_heads_per_layer``)
    layer_heads: Tuple[int, ...] = tuple(
        48 if t == FULL else 72 for t in published_layer_types(48))
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512            # positions seen, the query's own in
    #: rows a slot's ring keeps in a sliding layer (>= the window)
    ring_rows: int = 640
    # rope_parameters.full_attention (YaRN over the rotated values)
    full_rope_theta: float = 5e5
    full_rotary_dim: int = 64            # head_dim * partial_rotary_factor
    full_rope_factor: float = 128.0
    full_rope_original_max: int = 8192
    full_rope_beta_fast: float = 32.0
    full_rope_beta_slow: float = 1.0
    full_attention_factor: float = 1.4852030263919618
    # rope_parameters.sliding_attention
    sliding_rope_theta: float = 1e4
    sliding_rotary_dim: int = 128
    intermediate_size: int = 12288
    dense_layers: Tuple[int, ...] = (0,)     # mlp_only_layers
    moe_intermediate_size: int = 1024
    shared_intermediate_size: int = 1024
    n_routed_experts: int = 256          # the router's outputs
    experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    #: the routed experts this chip holds, in the order of its expert
    #: weights' leading axis (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    rms_eps: float = 1e-6
    max_positions: int = 1048576
    dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """The structure at toy widths (CPU tests, the CLI preset): layer
        0 full and dense, then sliding x 3, full; groups of 3 and 5 over 2
        KV heads; partial YaRN rotary beside plain; a window that wraps
        its ring several times in a test's context."""
        types_ = published_layer_types(5)
        base = dict(
            vocab_size=256, hidden_size=32, layer_types=types_,
            layer_heads=tuple(6 if t == FULL else 10 for t in types_),
            n_kv_heads=2, head_dim=8, sliding_window=6, ring_rows=8,
            full_rope_theta=1e4, full_rotary_dim=4, full_rope_factor=8.0,
            full_rope_original_max=16, full_attention_factor=1.2,
            sliding_rope_theta=1e3, sliding_rotary_dim=8,
            intermediate_size=64, moe_intermediate_size=16,
            shared_intermediate_size=16, n_routed_experts=8,
            experts_per_tok=3, max_positions=256, dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "LagunaConfig":
        """From the published ``config.json``'s keys (``model_type``
        ``laguna``).  A chip's share states ``num_experts`` as the experts
        it holds, lists them under ``held_experts`` and the router's width
        under ``n_router_outputs``."""
        n = int(c["num_hidden_layers"])
        rp = c["rope_parameters"]
        full, slide = rp["full_attention"], rp["sliding_attention"]
        if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
            raise ValueError("built: YaRN full layers, plain sliding ones")
        if c.get("gating") != "per-head" or int(c["decoder_sparse_step"]) != 1:
            raise ValueError("built: per-head gates, every layer sparse")
        if c.get("moe_router_logit_softcapping"):
            raise ValueError("router logit softcapping is not built")
        hd = int(c["head_dim"])
        held = c.get("held_experts")
        if held is not None and len(held) != int(c["num_experts"]):
            raise ValueError("held_experts does not list num_experts")
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            layer_types=tuple(
                {"full_attention": FULL, "sliding_attention": SLIDING}[t]
                for t in c["layer_types"][:n]),
            layer_heads=tuple(
                int(h) for h in c["num_attention_heads_per_layer"][:n]),
            n_kv_heads=int(c["num_key_value_heads"]), head_dim=hd,
            sliding_window=int(c["sliding_window"]),
            full_rope_theta=float(full["rope_theta"]),
            full_rotary_dim=int(hd * float(full["partial_rotary_factor"])),
            full_rope_factor=float(full["factor"]),
            full_rope_original_max=int(
                full["original_max_position_embeddings"]),
            full_rope_beta_fast=float(full["beta_fast"]),
            full_rope_beta_slow=float(full["beta_slow"]),
            full_attention_factor=float(full["attention_factor"]),
            sliding_rope_theta=float(slide["rope_theta"]),
            sliding_rotary_dim=int(
                hd * float(slide["partial_rotary_factor"])),
            intermediate_size=int(c["intermediate_size"]),
            dense_layers=tuple(int(i) for i in c["mlp_only_layers"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            shared_intermediate_size=int(
                c["shared_expert_intermediate_size"]),
            n_routed_experts=int(c.get("n_router_outputs", c["num_experts"])),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["moe_routed_scaling_factor"]),
            held_experts=None if held is None else tuple(int(e) for e in held),
            rms_eps=float(c["rms_norm_eps"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_held_experts(self) -> int:
        return (self.n_routed_experts if self.held_experts is None
                else len(self.held_experts))

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def is_dense(self, layer: int) -> bool:
        return layer in self.dense_layers

    def is_full(self, layer: int) -> bool:
        return self.layer_types[layer] == FULL


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: LagunaConfig, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters (expert
    weights ``(held, 2I, h)`` / ``(held, I, h)``, as ``xing4``'s)."""
    h, dt, f32 = cfg.hidden_size, cfg.dtype, jnp.float32
    H, kv = cfg.layer_heads[layer], cfg.n_kv_heads * cfg.head_dim
    out = {
        "attn_norm_g": ((h,), dt),
        "q_w": ((h, H * cfg.head_dim), dt),
        "k_w": ((h, kv), dt),
        "v_w": ((h, kv), dt),
        "gate_w": ((h, H), dt),
        "o_w": ((H * cfg.head_dim, h), dt),
        "ffn_norm_g": ((h,), dt),
    }
    if cfg.is_dense(layer):
        out["mlp_gu_w"] = ((h, 2 * cfg.intermediate_size), dt)
        out["mlp_down_w"] = ((cfg.intermediate_size, h), dt)
    else:
        E, I = cfg.n_held_experts, cfg.moe_intermediate_size
        out["router_w"] = ((h, cfg.n_routed_experts), f32)
        out["exp_gu_w"] = ((E, 2 * I, h), dt)
        out["exp_down_w"] = ((E, I, h), dt)
        out["shared_gu_w"] = ((h, 2 * cfg.shared_intermediate_size), dt)
        out["shared_down_w"] = ((cfg.shared_intermediate_size, h), dt)
    return out


def param_shapes(cfg: LagunaConfig) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: LagunaConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains."""
    shapes = param_shapes(cfg)
    return {
        name: (jnp.ones(shape, dt) if name.endswith("_g") else
               (std * jax.random.normal(k, shape)).astype(dt))
        for k, (name, (shape, dt)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))}


# -- small pieces ---------------------------------------------------------------


def rope_inv_freq(cfg: LagunaConfig, layer: int) -> Tuple[np.ndarray, float]:
    """``(frequencies of the rotated pairs, factor on cos and sin)`` of
    layer ``layer``'s kind: YaRN's blend over a full layer's rotated
    values (``xing4.yarn_inv_freq``), plain for a sliding one."""
    if cfg.is_full(layer):
        return yarn_inv_freq(types.SimpleNamespace(
            qk_rope_head_dim=cfg.full_rotary_dim,
            rope_theta=cfg.full_rope_theta, rope_factor=cfg.full_rope_factor,
            rope_original_max=cfg.full_rope_original_max,
            rope_beta_fast=cfg.full_rope_beta_fast,
            rope_beta_slow=cfg.full_rope_beta_slow,
        )), cfg.full_attention_factor
    dim = cfg.sliding_rotary_dim
    return (1.0 / cfg.sliding_rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32), 1.0


def rope(x, positions, cfg: LagunaConfig, layer: int):
    """Rotate the leading rotary values of the last axis (half-split
    pairing within them) at ``positions`` (broadcastable to ``x``'s
    leading axes), in float32; the rest passes through."""
    inv, factor = rope_inv_freq(cfg, layer)
    rot = 2 * inv.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32[..., :rot], 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x32[..., rot:]], -1
    ).astype(x.dtype)


def qkv(p, xn, positions, cfg: LagunaConfig, layer: int):
    """Of tokens ``xn`` (N, h) at ``positions`` (N,): rotated ``q`` (N,
    H, hd), rotated ``k`` and ``v`` (N, Hkv, hd) — the rows cached."""
    N, hd = xn.shape[0], cfg.head_dim
    at = positions[:, None]
    q = rope((xn @ p["q_w"]).reshape(N, -1, hd), at, cfg, layer)
    k = rope((xn @ p["k_w"]).reshape(N, -1, hd), at, cfg, layer)
    return q, k, (xn @ p["v_w"]).reshape(N, -1, hd)


def gated_output(p, o, xn):
    """Heads ``o`` (N, H, hd) times their gates, through ``W_o``."""
    o = o.astype(jnp.float32) * head_gate(p, xn)[:, :, None]
    return o.astype(xn.dtype).reshape(o.shape[0], -1) @ p["o_w"]


def moe_route(p, x, cfg: LagunaConfig):
    """The ``experts_per_tok`` largest of ``softmax(x W_r)`` (float32);
    gates are the picked probabilities renormalised and scaled.  Returns
    ``(idx (N, k) int32, gate (N, k) float32)``."""
    s = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), p["router_w"],
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    g, idx = jax.lax.top_k(s, cfg.experts_per_tok)
    g = g / (g.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return idx.astype(jnp.int32), g


def ffn(p, x, cfg: LagunaConfig, layer: int, live=None, impl=None):
    """SwiGLU in a dense layer; elsewhere the part of the routed experts
    this chip holds plus the shared expert (``xing4.moe_ffn``)."""
    if cfg.is_dense(layer):
        return _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"]), None
    return moe_ffn(p, x, cfg, held=cfg.held_experts, live=live, impl=impl,
                   route=moe_route)


def chunk_attention(q, k, v, pos0, cfg: LagunaConfig, impl=None,
                    window=None, keys_before=None):
    """A chunk's queries ``q`` (b, T, H, hd) at ``pos0 + t`` over ``k`` /
    ``v`` (b, Hkv, M, hd) from position 0 (or ``keys_before`` rows ahead
    of the chunk), causal, under ``window``: the kernel where the shape
    takes it (:func:`...ops.gqa_attention.gqa_chunk_attention`), else the
    loop below — key blocks up to the last query's, an online-softmax
    carry in float32.  Returns (b, T, H, hd)."""
    b, T, H, hd = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    key0 = 0 if keys_before is None else pos0 - keys_before

    def xla_loop():
        kb = next((c for c in (1024, 512, 256, 128) if M % c == 0), M)
        qg = (q.astype(jnp.float32) * cfg.softmax_scale).astype(
            q.dtype).reshape(b, T, Hkv, H // Hkv, hd)
        q_pos = (pos0 + jnp.arange(T, dtype=jnp.int32))[:, None]
        low = jnp.finfo(jnp.float32).min

        def body(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * kb, kb, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, j * kb, kb, axis=2)
            k_pos = key0 + j * kb + jnp.arange(kb, dtype=jnp.int32)[None, :]
            ok = k_pos <= q_pos
            if window is not None:
                ok = ok & (k_pos > q_pos - window) & (k_pos >= 0)
            s = jnp.where(ok, jnp.einsum(
                "bthgd,bhmd->bhgtm", qg, kj,
                preferred_element_type=jnp.float32), low)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            return (m_new, l * alpha + pr.sum(-1),
                    acc * alpha[..., None] + jnp.einsum(
                        "bhgtm,bhmd->bhgtd", pr.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32))

        shape = (b, Hkv, H // Hkv, T)
        _, l, acc = jax.lax.fori_loop(
            0, jnp.clip((pos0 + T - 1 - key0) // kb + 1, 1, M // kb), body,
            (jnp.full(shape, low, jnp.float32), jnp.zeros(shape, jnp.float32),
             jnp.zeros((*shape, hd), jnp.float32)))
        return (acc / l[..., None]).astype(q.dtype).transpose(
            0, 3, 1, 2, 4).reshape(b, T, H, hd)

    return gqa_chunk_attention(
        q, k, v, pos0, scale=cfg.softmax_scale, xla_loop=xla_loop,
        window=window, keys_before=keys_before, impl=impl)


# -- the block, prefill and decode ------------------------------------------------


def layer_params(params, cfg: LagunaConfig, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def _kinds(cfg: LagunaConfig, layer: int) -> Tuple[str, str]:
    """The layer's two pool kinds: paged ``k`` / ``v``, ring ``wk`` / ``wv``."""
    return ("k", "v") if cfg.is_full(layer) else ("wk", "wv")


def prefill_layer(p, x, cache, pos0, last, cfg: LagunaConfig, layer: int,
                  impl=None, pages=None):
    """One layer over a chunk ``x`` (b, T, h) at positions ``pos0 + t``
    whose last real row is ``last``; ``cache`` the layer's own rows by
    kind, (b, Hkv, cap or ring, hd).  A full layer writes the chunk's
    rotated K and V at ``pos0`` and attends the cache — with ``pages``
    (b, pages_per_seq), the sequences' table rows, ``cache`` holds its
    two pools as they are stored: the chunk's rows (whole pages) go into
    the pages that hold their positions and the attention reads K and V
    through the table, so nothing of the slot's other rows moves.  A
    sliding layer reads the ``sliding_window`` rows before the chunk out
    of its ring (before the chunk overwrites any), attends those and the
    chunk's own under the window, then writes the chunk's real rows (of a
    chunk longer than the ring the last ring's worth) at ``(pos0 + t) mod
    ring``.  Returns ``(x', cache')``."""
    b, T, h = x.shape
    kk, kv = _kinds(cfg, layer)
    xf = x.reshape(b * T, h)
    xn = rms_norm(xf, p["attn_norm_g"], cfg.rms_eps)
    t = jnp.arange(T, dtype=jnp.int32)
    q, k, v = qkv(p, xn, jnp.tile(pos0 + t, b), cfg, layer)
    q = q.reshape(b, T, -1, cfg.head_dim)

    def heads_first(r):     # the dense cache keeps heads ahead of positions
        return r.reshape(b, T, -1, cfg.head_dim).transpose(0, 2, 1, 3).astype(
            cache[kk].dtype)

    if pages is not None and cfg.is_full(layer):
        keys, vals = (
            write_chunk_pages(cache[kind], r.reshape(b, T, -1), pages, pos0)
            for kind, r in ((kk, k), (kv, v)))
        o = gqa_paged_chunk_attention(q, keys, vals, pages, pos0,
                                      scale=cfg.softmax_scale, impl=impl)
        cache = {kk: keys, kv: vals}
    elif cfg.is_full(layer):
        keys = jax.lax.dynamic_update_slice_in_dim(
            cache[kk], heads_first(k), pos0, axis=2)
        vals = jax.lax.dynamic_update_slice_in_dim(
            cache[kv], heads_first(v), pos0, axis=2)
        o = chunk_attention(q, keys, vals, pos0, cfg, impl)
        cache = {kk: keys, kv: vals}
    else:
        new_k, new_v = heads_first(k), heads_first(v)
        R, back = cache[kk].shape[2], cfg.sliding_window
        before = (pos0 - back + jnp.arange(back, dtype=jnp.int32)) % R
        o = chunk_attention(
            q, jnp.concatenate(
                [jnp.take(cache[kk], before, axis=2), new_k], axis=2),
            jnp.concatenate(
                [jnp.take(cache[kv], before, axis=2), new_v], axis=2),
            pos0, cfg, impl, window=cfg.sliding_window, keys_before=back)
        at = jnp.where((t <= last) & (t > last - R), (pos0 + t) % R, R)
        cache = {kk: cache[kk].at[:, :, at].set(new_k, mode="drop"),
                 kv: cache[kv].at[:, :, at].set(new_v, mode="drop")}
    xf = xf + gated_output(p, o.reshape(b * T, -1, cfg.head_dim), xn)
    y, _ = ffn(p, rms_norm(xf, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
               impl=impl)
    return (xf + y).reshape(b, T, h), cache


def decode_layer(p, x, lengths, live, cfg: LagunaConfig, layer: int,
                 impl=None):
    """One layer of one decode step: ``x`` (S, h), one token a slot at
    position ``lengths[s]`` (this step's rotated rows attended before
    they are written: the pool writes are the loop composer's).  A full
    layer attends ``p["cache_k"]`` / ``p["cache_v"]`` through
    ``p["page_table"]``; a sliding layer its rings ``p["cache_wk"]`` /
    ``p["cache_wv"]`` under the window.  Returns ``(x', new rows by pool
    kind, stats)``: ``stats["attn"]`` = (rows the live slots' attention
    read in a full layer, in a window layer) — one of the two is 0 —
    and ``stats["moe"]`` as ``xing4``'s of an expert layer."""
    kk, kv = _kinds(cfg, layer)
    xn = rms_norm(x, p["attn_norm_g"], cfg.rms_eps)
    q, k, v = qkv(p, xn, lengths, cfg, layer)
    held = jnp.where(live, lengths.astype(jnp.int32) + 1, 0)
    if cfg.is_full(layer):
        o = paged_decode_attention(
            q[:, :, None, :], p["cache_k"], p["cache_v"], p["page_table"],
            lengths, cfg.softmax_scale, k_new=k[:, :, None, :],
            v_new=v[:, :, None, :], impl=impl)[:, :, 0, :]
        rows = jnp.stack([held.sum(), jnp.zeros((), held.dtype)])
    else:
        o = kv_window_attention(
            q, p["cache_wk"], p["cache_wv"], lengths, k, v,
            window=cfg.sliding_window, sm_scale=cfg.softmax_scale, impl=impl)
        rows = jnp.stack([jnp.zeros((), held.dtype),
                          jnp.minimum(held, cfg.sliding_window).sum()])
    stats = {"attn": rows.astype(jnp.float32)}
    x = x + gated_output(p, o, xn)
    y, moe = ffn(p, rms_norm(x, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
                 live=live, impl=impl)
    if moe is not None:
        stats["moe"] = moe
    return x + y, {kk: k, kv: v}, stats


def head(params, x, cfg: LagunaConfig):
    """Final RMSNorm and the untied head."""
    return jnp.dot(rms_norm(x, params["norm_f_g"], cfg.rms_eps),
                   params["head_w"], preferred_element_type=jnp.float32)


# -- the rest of what the paged builder and the engine call
# (models/__init__.py) ---------------------------------------------------------

EMBED_PARAMS = ("wte",)
HEAD_PARAMS = ("norm_f_g", "head_w")
#: the step's graph takes ``active`` (the slots that decode) as an input
#: and carries it on every edge as ``live``
DECODE_TAKES_LIVE = True
#: :func:`forward_cached_row` takes ``pages``: where the chunk kernel
#: admits the shape, a chunk program leaves the full layers' K and V in
#: their pages (the engine asks, ``PagedDecodeEngine._chunk_in_pages``)
PREFILL_TAKES_PAGES = True


def layer_param_names(cfg: LagunaConfig, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: LagunaConfig):
    """Per layer: a full layer pages its rotated ``k`` and its ``v`` for
    the whole context; a sliding layer keeps ``wk`` / ``wv`` in rings.
    Each says the query heads that read it; the pool the decode step
    walks live blocks of is the full layers' (``_paged_flash``)."""
    from .kv_pages import CacheSpec, LayerCache

    row = (cfg.n_kv_heads, cfg.head_dim)
    layers = tuple(
        LayerCache((("k", row), ("v", row)), q_heads=cfg.layer_heads[i])
        if cfg.is_full(i) else
        LayerCache((("wk", row), ("wv", row)), window=cfg.sliding_window,
                   q_heads=cfg.layer_heads[i])
        for i in range(cfg.n_layers))
    rings = any(lc.window is not None for lc in layers)
    return CacheSpec("kv", layers, ring_rows=cfg.ring_rows if rings else 0,
                     walk=("k", None))


def decode_embed(p, ids, lengths, cfg: LagunaConfig):
    """Positions are the layers' rotary angles, not the embedding's."""
    return p["wte"][ids[:, 0]]


def decode_head(p, x, cfg: LagunaConfig):
    return head(p, x, cfg)[:, None, :]


def decode_flops(cfg: LagunaConfig, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step: a
    layer's weights streamed once (experts: the picked ones) and the
    attention over the slot's capacity (full) or the window (sliding)."""
    S, h = slots, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    layers = []
    for i in range(cfg.n_layers):
        seen = capacity if cfg.is_full(i) else cfg.sliding_window
        f = 2.0 * 2.0 * S * cfg.layer_heads[i] * seen * cfg.head_dim
        f += sum(2.0 * S * math.prod(shape)
                 * (picked if k.startswith("exp_") else 1.0)
                 for k, (shape, _) in layer_param_shapes(cfg, i).items()
                 if len(shape) >= 2)
        layers.append(f)
    return 2.0 * S * h, layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: LagunaConfig, batch: int, cap: int, dtype=None,
               page_size: Optional[int] = None):
    """The zeroed dense cache of :func:`forward_cached`: ``{"k", "v"}``
    (full layers, batch, Hkv, cap, hd) and ``{"wk", "wv"}`` (sliding
    layers, batch, Hkv, ring, hd)."""
    return cache_spec(cfg).init_dense(
        batch, cap, dtype or cfg.dtype, page_size=page_size)


def _prefill(params, ids, cache, pos_start, last, cfg, impl=None, pages=None):
    """``cache`` by kind: the layers that keep it stacked — or, with
    ``pages``, a full layer's kinds as tuples of their pools
    (:func:`prefill_layer`), which come back as tuples."""
    x = params["wte"][ids]
    seen: Dict[str, int] = {}
    out = {k: [] for k in cache}
    for i in range(cfg.n_layers):
        kinds = _kinds(cfg, i)
        n = seen[kinds[0]] = seen.get(kinds[0], -1) + 1
        x, mine = prefill_layer(
            layer_params(params, cfg, i), x, {k: cache[k][n] for k in kinds},
            pos_start, last, cfg, i, impl, pages)
        for k, v in mine.items():
            out[k].append(v)
    return x, {k: tuple(v) if isinstance(cache[k], tuple) else jnp.stack(v)
               for k, v in out.items()}


def forward_cached(params, ids, cache, pos_start, cfg: LagunaConfig,
                   impl=None):
    """The family's cached forward (the engine's prefill contract):
    ``ids`` (b, T) at positions ``pos_start + t`` over ``cache``
    (:func:`init_cache`); returns ``(logits (b, T, V) float32, cache)``."""
    x, cache = _prefill(
        params, ids, cache, pos_start, ids.shape[1] - 1, cfg, impl)
    return head(params, x, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: LagunaConfig,
                       row, impl=None, pages=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V); ``row`` is the chunk's last real row — the rows after it are
    padding and a sliding layer's ring does not take them.  ``pages``
    (b, pages_per_seq), :data:`PREFILL_TAKES_PAGES`: the full layers'
    kinds of ``cache`` are tuples of their pools, written and read
    through these table rows where they lie (``ids`` whole pages, at a
    page's first position)."""
    x, cache = _prefill(params, ids, cache, pos_start, row, cfg, impl, pages)
    return head(params, jax.lax.dynamic_index_in_dim(
        x, row, 1, keepdims=False), cfg), cache


def forward(params, ids, cfg: LagunaConfig, impl=None):
    """Logits (b, T, V) of whole sequences: a prefill from position 0."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]

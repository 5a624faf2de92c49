"""dots3 family (``model_type`` ``dots3_note``): the served block of the
language model (towers and MTP are not built).

Two kinds of layer in one model, each with a cache of its own
(:func:`cache_spec` is per layer):

* a **full layer** is MLA whose query attends only the ``index_topk``
  rows a learned indexer picks (DeepSeek-V3.2's "DSA" lightning
  indexer): per token it caches the latent row ``[c | k_r]`` *and* an
  ``index_head_dim`` indexer key, both for the whole context, in pages of
  the shared pool.  A decode step scores the slot's cached keys
  (``_dsa_index``), keeps the best ``index_topk`` exactly
  (:func:`...ops.attention.dsa_select`) and runs absorbed MLA over those
  rows alone, gathered through the page table (``_dsa_sparse_attn``): it
  reads ``min(L, index_topk)`` latent rows a slot whatever the context.
  A prefill chunk selects per query by an exact threshold
  (:func:`...ops.attention.kth_largest_mask`) and runs expanded MLA over
  the slot's rows under that mask — the same mathematics at dense cost;
* a **sliding layer** is an MLA of its own sizes over the last
  ``sliding_window`` positions; its latent rows live in a ring the slot
  owns (:class:`.kv_pages.CacheSpec`, ring layers), so it costs nothing
  per context token (``_swa_latent_attn``).

Both gate each head's output by ``sigmoid(x W_g)`` before ``W_o``, and
rescale their two normalised latents (``lora_rescale``).  The FFN is
SwiGLU in the leading dense layer and elsewhere sigmoid-routed experts
plus a shared one, of which a chip may hold a share
(``held_experts``): the router keeps its published width, and the layer
computes the part its own experts give.

What this file shares with ``xing4`` it calls there: the absorbed query
and output, the router, the grouped expert kernel and ``moe_ffn`` — an
optimisation of one is the other's too.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (
    dsa_index_scores,
    dsa_select,
    dsa_sparse_attention,
    index_scores,
    kth_largest_mask,
    lane_width,
    latent_window_attention,
    mla_chunk_attention,
)
from .xing4 import (
    _swiglu,
    mla_absorbed_output,
    mla_absorbed_query,
    moe_ffn,
    rms_norm,
)

FULL, SLIDING = "full", "sliding"


def published_layer_types(n_layers: int) -> Tuple[str, ...]:
    """The published pattern cut to ``n_layers``: layer 0 full, then the
    period full, sliding, sliding, sliding."""
    return tuple(FULL if i == 0 or (i - 1) % 4 == 0 else SLIDING
                 for i in range(n_layers))


@dataclass(frozen=True)
class Dots3Config:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 152064
    hidden_size: int = 5120
    layer_types: Tuple[str, ...] = published_layer_types(46)
    n_dense_layers: int = 1              # first_k_dense_replace
    # full layers
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding layers
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window: int = 513            # itself and 512 before
    #: rows a slot's ring keeps in a sliding layer (>= the window)
    ring_rows: int = 768
    lora_rescale: bool = True            # apply_mla_qkv_lora_rescale
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256          # the router's outputs
    n_shared_experts: int = 1
    experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    #: the routed experts this chip holds, in the order of its expert
    #: weights' leading axis (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    rms_eps: float = 1e-5
    max_positions: int = 524288
    dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw) -> "Dots3Config":
        """Every mechanism at toy widths, two periods deep (CPU tests,
        the CLI preset): a selection well under a test's context, a
        window shorter than the ring."""
        base = dict(
            vocab_size=256, hidden_size=32,
            layer_types=published_layer_types(9), n_dense_layers=1,
            n_heads=4, q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, rope_theta=1e4,
            index_n_heads=2, index_head_dim=16, index_topk=16,
            swa_n_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
            swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
            swa_v_head_dim=8, swa_rope_theta=1e3, sliding_window=9,
            ring_rows=16, intermediate_size=64, moe_intermediate_size=16,
            n_routed_experts=8, experts_per_tok=2, max_positions=256,
            dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "Dots3Config":
        """From the published ``config.json``'s keys (``model_type``
        ``dots3_note``).  A chip's share states ``n_routed_experts`` as
        the experts it holds, lists them under ``held_experts`` and the
        router's width under ``n_router_outputs``."""
        if c.get("rope_scaling") is not None:
            raise ValueError("rope scaling is not built for this family")
        types = tuple(
            {"full_attention": FULL, "sliding_attention": SLIDING}[t]
            for t in c["layer_types"][:int(c["num_hidden_layers"])])
        held = c.get("held_experts")
        if held is not None and len(held) != int(c["n_routed_experts"]):
            raise ValueError("held_experts does not list n_routed_experts")
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            layer_types=types,
            n_dense_layers=int(c["first_k_dense_replace"]),
            n_heads=int(c["num_attention_heads"]),
            q_lora_rank=int(c["q_lora_rank"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_head_dim=int(c["qk_nope_head_dim"]),
            qk_rope_head_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            rope_theta=float(c["rope_theta"]),
            index_n_heads=int(c["index_n_heads"]),
            index_head_dim=int(c["index_head_dim"]),
            index_topk=int(c["index_topk"]),
            swa_n_heads=int(c["swa_num_attention_heads"]),
            swa_q_lora_rank=int(c["swa_q_lora_rank"]),
            swa_kv_lora_rank=int(c["swa_kv_lora_rank"]),
            swa_qk_nope_head_dim=int(c["swa_qk_nope_head_dim"]),
            swa_qk_rope_head_dim=int(c["swa_qk_rope_head_dim"]),
            swa_v_head_dim=int(c["swa_v_head_dim"]),
            swa_rope_theta=float(c["swa_rope_theta"]),
            sliding_window=int(c["sliding_window_size"]),
            lora_rescale=bool(c["apply_mla_qkv_lora_rescale"]),
            intermediate_size=int(c["intermediate_size"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            n_routed_experts=int(
                c.get("n_router_outputs", c["n_routed_experts"])),
            n_shared_experts=int(c["n_shared_experts"]),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
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

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers

    def is_full(self, layer: int) -> bool:
        return self.layer_types[layer] == FULL

    def attn(self, layer: int) -> "AttnDims":
        """The MLA sizes of layer ``layer``'s kind."""
        if self.is_full(layer):
            return AttnDims(
                self.hidden_size, self.n_heads, self.q_lora_rank,
                self.kv_lora_rank, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.rope_theta,
                self.rms_eps, self.lora_rescale)
        return AttnDims(
            self.hidden_size, self.swa_n_heads, self.swa_q_lora_rank,
            self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
            self.swa_qk_rope_head_dim, self.swa_v_head_dim,
            self.swa_rope_theta, self.rms_eps, self.lora_rescale)


@dataclass(frozen=True)
class AttnDims:
    """One kind of layer's MLA sizes, under the names ``xing4``'s
    absorbed-query and absorbed-output functions read."""

    hidden_size: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    lora_rescale: bool

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def row_width(self) -> int:
        """``[c | k_r]`` padded to whole 128-lane tiles (as ``xing4``)."""
        return lane_width(self.kv_lora_rank + self.qk_rope_head_dim)

    def rescale(self, rank: int) -> float:
        """LongCat-Flash's variance alignment of a normalised latent."""
        return math.sqrt(self.hidden_size / rank) if self.lora_rescale else 1.0


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: Dots3Config, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters (expert
    weights ``(held, 2I, h)`` / ``(held, I, h)``, as ``xing4``'s)."""
    h, a, dt, f32 = cfg.hidden_size, cfg.attn(layer), cfg.dtype, jnp.float32
    H = a.n_heads
    out = {
        "attn_norm_g": ((h,), dt),
        "q_a_w": ((h, a.q_lora_rank), dt),
        "q_norm_g": ((a.q_lora_rank,), dt),
        "q_b_w": ((a.q_lora_rank, H * a.qk_head_dim), dt),
        "kv_a_w": ((h, a.kv_lora_rank + a.qk_rope_head_dim), dt),
        "kv_norm_g": ((a.kv_lora_rank,), dt),
        "kv_b_w": ((a.kv_lora_rank,
                    H * (a.qk_nope_head_dim + a.v_head_dim)), dt),
        "gate_w": ((h, H), dt),
        "o_w": ((H * a.v_head_dim, h), dt),
        "ffn_norm_g": ((h,), dt),
    }
    if cfg.is_full(layer):
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        out.update({
            "idx_q_w": ((a.q_lora_rank, Hi * Di), dt),
            "idx_k_w": ((h, Di), dt),
            "idx_k_norm_g": ((Di,), dt),
            "idx_k_norm_b": ((Di,), dt),
            "idx_w_w": ((h, Hi), dt),
        })
    if cfg.is_dense(layer):
        out["mlp_gu_w"] = ((h, 2 * cfg.intermediate_size), dt)
        out["mlp_down_w"] = ((cfg.intermediate_size, h), dt)
    else:
        E, I = cfg.n_held_experts, cfg.moe_intermediate_size
        Is = I * cfg.n_shared_experts
        out["router_w"] = ((h, cfg.n_routed_experts), f32)
        out["router_bias"] = ((cfg.n_routed_experts,), f32)
        out["exp_gu_w"] = ((E, 2 * I, h), dt)
        out["exp_down_w"] = ((E, I, h), dt)
        out["shared_gu_w"] = ((h, 2 * Is), dt)
        out["shared_down_w"] = ((Is, h), dt)
    return out


def param_shapes(cfg: Dots3Config) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: Dots3Config, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains, zero
    norm biases, a small router bias."""
    shapes = param_shapes(cfg)
    out = {}
    for k, (name, (shape, dt)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dt)
        elif name.endswith("_norm_b"):
            out[name] = jnp.zeros(shape, dt)
        elif name.endswith("router_bias"):
            out[name] = (0.01 * jax.random.normal(k, shape)).astype(dt)
        else:
            out[name] = (std * jax.random.normal(k, shape)).astype(dt)
    return out


# -- small pieces ---------------------------------------------------------------


def rope(x, positions, theta: float):
    """Rotate the last axis (half-split pairing, no scaling) at
    ``positions`` (broadcastable to ``x``'s leading axes), in float32."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def _layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def mla_project(p, x, positions, a: AttnDims):
    """Of tokens ``x`` (N, h) at ``positions`` (N,): ``q_nope`` (N, H,
    dn), rotated ``q_rope`` (N, H, dr), the rescaled query latent ``c_q``
    (N, q_lora_rank) the indexer reads too, and the cache row
    ``[RMSNorm(c) * s | RoPE(k_r) | 0]`` (N, ``a.row_width``)."""
    N, H = x.shape[0], a.n_heads
    f32 = jnp.float32
    cq = rms_norm(x @ p["q_a_w"],
                  p["q_norm_g"].astype(f32) * a.rescale(a.q_lora_rank),
                  a.rms_eps)
    q = (cq @ p["q_b_w"]).reshape(N, H, a.qk_head_dim)
    q_nope = q[..., :a.qk_nope_head_dim]
    q_rope = rope(q[..., a.qk_nope_head_dim:], positions[:, None],
                  a.rope_theta)
    ckr = x @ p["kv_a_w"]
    c = rms_norm(ckr[:, :a.kv_lora_rank],
                 p["kv_norm_g"].astype(f32) * a.rescale(a.kv_lora_rank),
                 a.rms_eps)
    k_r = rope(ckr[:, a.kv_lora_rank:], positions, a.rope_theta)
    pad = a.row_width - c.shape[1] - k_r.shape[1]
    row = jnp.concatenate([c, k_r, jnp.zeros((N, pad), c.dtype)], axis=-1)
    return q_nope, q_rope, cq, row


def index_project(p, x, cq, positions, cfg: Dots3Config):
    """The indexer's side of tokens ``x`` (N, h): queries ``q`` (N, Hi,
    Di) float32 from the query latent, the key ``k`` (N, Di) to cache
    (a LayerNorm of ``x W_kI``), both rotated on their first
    ``qk_rope_head_dim`` values, and the head weights ``w`` (N, Hi)
    float32, ``Hi^-1/2 Di^-1/2`` folded in."""
    N, Hi, Di = x.shape[0], cfg.index_n_heads, cfg.index_head_dim
    dr, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    q = jnp.dot(cq, p["idx_q_w"],
                preferred_element_type=jnp.float32).reshape(N, Hi, Di)
    q = jnp.concatenate(
        [rope(q[..., :dr], positions[:, None], theta), q[..., dr:]], -1)
    k = _layer_norm(x @ p["idx_k_w"], p["idx_k_norm_g"], p["idx_k_norm_b"],
                    cfg.rms_eps)
    k = jnp.concatenate([rope(k[:, :dr], positions, theta), k[:, dr:]], -1)
    w = jnp.dot(x, p["idx_w_w"], preferred_element_type=jnp.float32) * (
        Hi ** -0.5 * Di ** -0.5)
    return q, k, w


def head_gate(p, x):
    """``sigmoid(x W_g)`` (N, H) float32: a head's output times its gate
    before ``W_o``."""
    return jax.nn.sigmoid(
        jnp.dot(x, p["gate_w"], preferred_element_type=jnp.float32))


def _kv_block(rows: int) -> int:
    """Rows the expanded attention rebuilds K/V for at a time."""
    return next((kb for kb in (1024, 512, 256, 128) if rows % kb == 0), rows)


def expanded_attention(p, q_nope, q_rope, rows, mask_of, n_blocks, kb: int,
                       a: AttnDims, impl=None, pos0=0, window=None,
                       keys_before=None, mask=None):
    """Expanded MLA of a chunk's queries ``q_*`` (b, T, H, .) at
    positions ``pos0 + t`` over ``rows`` (b, M, width) — from position 0,
    or ``keys_before`` rows ahead of the chunk and the chunk's — under
    the layer's mask: the last ``window`` positions (none: every earlier
    one) and the selection ``mask`` (b, T, M) bool.  Through
    :func:`...ops.attention.mla_chunk_attention`: the kernel where the
    shape takes it, else the loop below (kept here: its lowered text is
    pinned by ``tests/fixtures/serving_lowered_sha256.json``) — K and V
    rebuilt from the latents ``kb`` rows at a time, block ``j`` under
    ``mask_of(j)`` (b or 1, T, kb) bool (the same mask, a block at a
    time), blocks ``[0, n_blocks)`` (the count may be data), an
    online-softmax carry in float32.  Returns (b, T, H, dv)."""
    b, T, H, _ = q_nope.shape
    rank, dr, dv = a.kv_lora_rank, a.qk_rope_head_dim, a.v_head_dim
    w = p["kv_b_w"].reshape(rank, H, a.qk_nope_head_dim + dv)
    w_uk, w_uv = w[..., :a.qk_nope_head_dim], w[..., a.qk_nope_head_dim:]

    def xla_loop():
        qn = (q_nope.astype(jnp.float32) * a.softmax_scale).astype(
            q_nope.dtype)
        qr = (q_rope.astype(jnp.float32) * a.softmax_scale).astype(
            q_rope.dtype)
        low = jnp.finfo(jnp.float32).min

        def body(j, carry):
            m, l, acc = carry
            blk = jax.lax.dynamic_slice_in_dim(rows, j * kb, kb, axis=1)
            c, k_r = blk[..., :rank], blk[..., rank:rank + dr]
            k_nope = jnp.einsum("bmc,chd->bmhd", c, w_uk)
            v = jnp.einsum("bmc,chd->bmhd", c, w_uv)
            s = (jnp.einsum("bthd,bmhd->bhtm", qn, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthd,bmd->bhtm", qr, k_r,
                              preferred_element_type=jnp.float32))
            ok = mask_of(j)[:, None]
            s = jnp.where(ok, s, low)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            l = l * alpha + pr.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhtm,bmhd->bhtd", pr.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((b, H, T), low, jnp.float32),
                jnp.zeros((b, H, T), jnp.float32),
                jnp.zeros((b, H, T, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
        return (acc / l[..., None]).astype(q_nope.dtype).transpose(0, 2, 1, 3)

    return mla_chunk_attention(
        q_nope, q_rope, w_uk, w_uv, rows, pos0, scale=a.softmax_scale,
        rank=rank, xla_loop=xla_loop, window=window,
        keys_before=keys_before, mask=mask, impl=impl)


# -- the two attentions over a chunk (prefill) -----------------------------------


def _full_prefill_attention(p, xn, rows_c, rows_i, pos0, b, T,
                            cfg: Dots3Config, a: AttnDims, impl=None):
    """A full layer over a chunk: the chunk's latent rows and indexer
    keys written at ``pos0``, every query's index scores over the rows
    it may see, the exact ``index_topk`` selection as a mask, expanded
    MLA under it.  Returns the gated heads (b*T, H*dv) and the two
    caches."""
    positions = jnp.tile(pos0 + jnp.arange(T, dtype=jnp.int32), b)
    q_nope, q_rope, cq, row = mla_project(p, xn, positions, a)
    qi, ki, wi = index_project(p, xn, cq, positions, cfg)
    rows_c = jax.lax.dynamic_update_slice_in_dim(
        rows_c, row.reshape(b, T, -1).astype(rows_c.dtype), pos0, axis=1)
    rows_i = jax.lax.dynamic_update_slice_in_dim(
        rows_i, ki.reshape(b, T, -1).astype(rows_i.dtype), pos0, axis=1)
    cap = rows_c.shape[1]
    kb = _kv_block(cap)
    live = jnp.minimum((pos0 + T + kb - 1) // kb, cap // kb)
    qi = qi.reshape(b, T, cfg.index_n_heads, cfg.index_head_dim)
    wi = wi.reshape(b, T, cfg.index_n_heads)

    def score(j, out):
        keys = jax.lax.dynamic_slice_in_dim(rows_i, j * kb, kb, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, index_scores(qi, wi, keys), j * kb, axis=2)

    scores = jax.lax.fori_loop(
        0, live, score, jnp.full((b, T, cap), -jnp.inf, jnp.float32))
    q_pos = pos0 + jnp.arange(T, dtype=jnp.int32)
    causal = jnp.arange(cap, dtype=jnp.int32)[None, :] <= q_pos[:, None]
    picked = kth_largest_mask(scores, causal[None], cfg.index_topk)
    o = expanded_attention(
        p, q_nope.reshape(b, T, a.n_heads, -1),
        q_rope.reshape(b, T, a.n_heads, -1), rows_c,
        lambda j: jax.lax.dynamic_slice_in_dim(picked, j * kb, kb, axis=2),
        live, kb, a, impl, pos0=pos0, mask=picked)
    o = o.reshape(b * T, a.n_heads, -1).astype(jnp.float32) * head_gate(
        p, xn)[:, :, None]
    return o.astype(xn.dtype).reshape(b * T, -1), rows_c, rows_i


def _sliding_prefill_attention(p, xn, ring, pos0, last, b, T,
                               cfg: Dots3Config, a: AttnDims, impl=None):
    """A sliding layer over a chunk: the ``sliding_window - 1`` rows
    before the chunk read out of the ring (before the chunk overwrites
    any), expanded MLA over those and the chunk's own under the window,
    then the chunk's real rows (``t <= last``; of a chunk longer than the
    ring the last ring's worth) written at ``(pos0 + t) mod ring``.
    Returns the gated heads (b*T, H*dv) and the ring."""
    R, back = ring.shape[1], cfg.sliding_window - 1
    t = jnp.arange(T, dtype=jnp.int32)
    positions = jnp.tile(pos0 + t, b)
    q_nope, q_rope, _, row = mla_project(p, xn, positions, a)
    new = row.reshape(b, T, -1).astype(ring.dtype)
    before = pos0 - back + jnp.arange(back, dtype=jnp.int32)
    keys = jnp.concatenate(
        [jnp.take(ring, before % R, axis=1), new], axis=1)
    M = back + T
    kb = M if M <= 2048 else 1024
    if M % kb:
        keys = jnp.pad(keys, ((0, 0), (0, kb - M % kb), (0, 0)))
    q_pos = pos0 + t

    def mask_of(j):
        k_pos = pos0 - back + j * kb + jnp.arange(kb, dtype=jnp.int32)
        return ((k_pos[None, :] <= q_pos[:, None])
                & (k_pos[None, :] > q_pos[:, None] - cfg.sliding_window)
                & (k_pos[None, :] >= 0))[None]

    o = expanded_attention(
        p, q_nope.reshape(b, T, a.n_heads, -1),
        q_rope.reshape(b, T, a.n_heads, -1), keys, mask_of,
        keys.shape[1] // kb, kb, a, impl, pos0=pos0,
        window=cfg.sliding_window, keys_before=back)
    o = o.reshape(b * T, a.n_heads, -1).astype(jnp.float32) * head_gate(
        p, xn)[:, :, None]
    keep = jnp.logical_and(t <= last, t > last - R)
    ring = ring.at[:, jnp.where(keep, (pos0 + t) % R, R)].set(
        new, mode="drop")
    return o.astype(xn.dtype).reshape(b * T, -1), ring


# -- feed-forward ---------------------------------------------------------------


def ffn(p, x, cfg: Dots3Config, layer: int, live=None, impl=None):
    """SwiGLU in a dense layer; elsewhere the part of the routed experts
    this chip holds plus the shared expert (``xing4.moe_ffn``)."""
    if cfg.is_dense(layer):
        return _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"]), None
    return moe_ffn(p, x, cfg, held=cfg.held_experts, live=live, impl=impl)


# -- the block, prefill and decode ------------------------------------------------


def layer_params(params, cfg: Dots3Config, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def prefill_layer(p, x, cache, pos0, last, cfg: Dots3Config, layer: int,
                  impl=None):
    """One layer over a chunk ``x`` (b, T, h) at positions ``pos0 + t``
    whose last real row is ``last``; ``cache`` the layer's own rows,
    ``{"c", "i"}`` (b, cap, .) of a full layer or ``{"w"}`` (b, ring, .)
    of a sliding one.  Returns ``(x', cache')``."""
    b, T, h = x.shape
    a = cfg.attn(layer)
    xf = x.reshape(b * T, h)
    xn = rms_norm(xf, p["attn_norm_g"], cfg.rms_eps)
    if cfg.is_full(layer):
        o, rows_c, rows_i = _full_prefill_attention(
            p, xn, cache["c"], cache["i"], pos0, b, T, cfg, a, impl)
        cache = {"c": rows_c, "i": rows_i}
    else:
        o, ring = _sliding_prefill_attention(
            p, xn, cache["w"], pos0, last, b, T, cfg, a, impl)
        cache = {"w": ring}
    xf = xf + o @ p["o_w"]
    y, _ = ffn(p, rms_norm(xf, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
               impl=impl)
    return (xf + y).reshape(b, T, h), cache


def decode_layer(p, x, lengths, live, cfg: Dots3Config, layer: int,
                 impl=None):
    """One layer of one decode step: ``x`` (S, h), one token a slot at
    position ``lengths[s]`` (this step's rows attended before they are
    written: the pool writes are the loop composer's).  A full layer
    scores the slot's cached indexer keys ``p["cache_i"]``, selects, and
    attends the selected rows of ``p["cache_c"]`` through
    ``p["page_table"]``; a sliding layer attends its ring ``p["cache_w"]``
    under the window.  Returns ``(x', new rows by pool kind, stats)``:
    ``stats["dsa"]`` = (latent rows the live slots read, rows they hold)
    and ``stats["dsa_idx"]`` (S, ``index_topk``) int32, the positions each
    live slot's attention read (-1 where it read none), of a full layer;
    ``stats["moe"]`` as ``xing4``'s of an expert layer."""
    a = cfg.attn(layer)
    xn = rms_norm(x, p["attn_norm_g"], cfg.rms_eps)
    q_nope, q_rope, cq, row = mla_project(p, xn, lengths, a)
    q = mla_absorbed_query(p, q_nope, q_rope, a)
    stats = {}
    if cfg.is_full(layer):
        qi, ki, wi = index_project(p, xn, cq, lengths, cfg)
        scores = dsa_index_scores(
            qi, wi, p["cache_i"], p["page_table"], lengths, ki, impl=impl)
        idx, n = dsa_select(scores, lengths, cfg.index_topk)
        o_lat = dsa_sparse_attention(
            q, p["cache_c"], p["page_table"], idx, n, lengths, row,
            a.kv_lora_rank, impl=impl)
        new = {"c": row, "i": ki}
        held = jnp.where(live, lengths.astype(jnp.int32) + 1, 0)
        stats["dsa"] = jnp.stack([
            jnp.where(live, n, 0).sum(), held.sum()]).astype(jnp.float32)
        read = live[:, None] & (jnp.arange(idx.shape[1])[None, :] < n[:, None])
        stats["dsa_idx"] = jnp.where(read, idx, -1)
    else:
        o_lat = latent_window_attention(
            q, p["cache_w"], lengths, row, a.kv_lora_rank,
            cfg.sliding_window, impl=impl)
        new = {"w": row}
    o_lat = (o_lat.astype(jnp.float32)
             * head_gate(p, xn)[:, :, None]).astype(o_lat.dtype)
    x = x + mla_absorbed_output(p, o_lat, a)
    y, moe = ffn(p, rms_norm(x, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
                 live=live, impl=impl)
    if moe is not None:
        stats["moe"] = moe
    return x + y, new, stats or None


def head(params, x, cfg: Dots3Config):
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


def layer_param_names(cfg: Dots3Config, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: Dots3Config):
    """Per layer: a full layer pages a latent row ``c`` and an indexer
    key ``i`` for the whole context; a sliding layer keeps its latent row
    ``w`` in a ring.  The pool the decode step walks live blocks of is
    the indexer keys' (``_dsa_index``)."""
    from .kv_pages import CacheSpec, LayerCache

    slide = None
    layers = []
    for i in range(cfg.n_layers):
        a = cfg.attn(i)
        if cfg.is_full(i):
            layers.append(LayerCache(
                (("c", (a.row_width,)), ("i", (cfg.index_head_dim,))),
                rank=a.kv_lora_rank))
        else:
            slide = slide or LayerCache(
                (("w", (a.row_width,)),), rank=a.kv_lora_rank,
                window=cfg.sliding_window)
            layers.append(slide)
    return CacheSpec(
        "latent", tuple(layers),
        ring_rows=cfg.ring_rows if slide is not None else 0,
        walk=("i", cfg.index_head_dim))


def decode_embed(p, ids, lengths, cfg: Dots3Config):
    """Positions are the layers' rotary angles, not the embedding's."""
    return p["wte"][ids[:, 0]]


def decode_head(p, x, cfg: Dots3Config):
    return head(p, x, cfg)[:, None, :]


def decode_flops(cfg: Dots3Config, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step: a
    layer's weights streamed once (experts: the picked ones), the index
    scores over the slot's capacity and the attention over the selected
    rows (full) or the window (sliding)."""
    S, h = slots, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    layers = []
    for i in range(cfg.n_layers):
        a = cfg.attn(i)
        seen = (min(cfg.index_topk, capacity) if cfg.is_full(i)
                else cfg.sliding_window)
        f = 2.0 * 2.0 * S * a.n_heads * seen * a.row_width
        if cfg.is_full(i):
            f += 2.0 * S * cfg.index_n_heads * capacity * cfg.index_head_dim
        f += sum(2.0 * S * math.prod(shape)
                 * (picked if k.startswith("exp_") else 1.0)
                 for k, (shape, _) in layer_param_shapes(cfg, i).items()
                 if len(shape) >= 2)
        layers.append(f)
    return 2.0 * S * h, layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: Dots3Config, batch: int, cap: int, dtype=None,
               page_size: Optional[int] = None):
    """The zeroed dense cache of :func:`forward_cached`: ``{"c", "i"}``
    (full layers, batch, cap, .) and ``{"w"}`` (sliding layers, batch,
    ring, .)."""
    return cache_spec(cfg).init_dense(
        batch, cap, dtype or cfg.dtype, page_size=page_size)


def _prefill(params, ids, cache, pos_start, last, cfg, impl=None):
    x = params["wte"][ids]
    seen = {"c": 0, "w": 0}
    out = {k: [] for k in cache}
    for i in range(cfg.n_layers):
        kind = "c" if cfg.is_full(i) else "w"
        n = seen[kind]
        seen[kind] = n + 1
        mine = ({"c": cache["c"][n], "i": cache["i"][n]} if kind == "c"
                else {"w": cache["w"][n]})
        x, mine = prefill_layer(
            layer_params(params, cfg, i), x, mine, pos_start, last, cfg, i,
            impl)
        for k, v in mine.items():
            out[k].append(v)
    return x, {k: jnp.stack(v) for k, v in out.items()}


def forward_cached(params, ids, cache, pos_start, cfg: Dots3Config,
                   impl=None):
    """The family's cached forward (the engine's prefill contract):
    ``ids`` (b, T) at positions ``pos_start + t`` over ``cache``
    (:func:`init_cache`); returns ``(logits (b, T, V) float32, cache)``."""
    x, cache = _prefill(
        params, ids, cache, pos_start, ids.shape[1] - 1, cfg, impl)
    return head(params, x, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: Dots3Config,
                       row, impl=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V); ``row`` is the chunk's last real row — the rows after it are
    padding and a sliding layer's ring does not take them."""
    x, cache = _prefill(params, ids, cache, pos_start, row, cfg, impl)
    return head(params, jax.lax.dynamic_index_in_dim(
        x, row, 1, keepdims=False), cfg), cache


def forward(params, ids, cfg: Dots3Config, impl=None):
    """Logits (b, T, V) of whole sequences: a prefill from position 0."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]

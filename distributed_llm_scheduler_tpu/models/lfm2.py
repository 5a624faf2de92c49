"""LFM2 family (``model_type`` ``lfm2_moe``): the served hybrid block.

Every layer is an OPERATOR and a FEED-FORWARD, each behind its own
RMSNorm on a plain residual stream::

    h = h + Op_l(rms(h; op_norm))        h = h + FFN_l(rms(h; ffn_norm))

``Op`` by ``layer_types`` (published: ``conv conv full_attention conv``
repeated):

* ``conv`` — a **doubly gated short convolution**: ``[B | C | z] = x
  W_in``; ``u = B * z``; ``v_t = sum_k w[:, k] u_{t - (K - 1) + k}``
  (depthwise, causal, ``conv_L_cache`` = K taps, no bias, no activation);
  ``Op = (C * v) W_out``.  What it caches is a state a SLOT — the last ``K
  - 1`` rows of ``u``, 8 KB at the published widths — overwritten by every
  step and every chunk (:class:`.kv_pages.CacheSpec`, state layers;
  :mod:`..ops.short_conv`).
* ``full`` — grouped-query attention; ``q`` and ``k`` each through an
  RMSNorm over a head's values with a learned weight, THEN rotated over
  the whole head at a plain theta; the normed, rotated ``k`` and ``v`` are
  the rows paged (``_paged_flash`` in groups).

``FFN``: SwiGLU in the first ``num_dense_layers``, elsewhere routed
experts alone — the ``experts_per_tok`` largest of ``sigmoid(x W_g) +
expert_bias``, their weights the scores without the bias over their sum
(``norm_topk_prob``; nothing added to the sum), times
``routed_scaling_factor``: ``xing4.moe_route``'s rule to the letter, so
``xing4.moe_ffn`` routes with its own — and EVERY expert of a layer is
held, no shared one.  The head is tied to the embedding.

A state layer here also routes: ``decode_layer`` hands back the conv pool
whole AND counts, as a dict of named arrays (``conv``: the slots it
stepped; ``moe``: ``xing4``'s routing pair), and an attention layer
routes too.  A convolution runs THROUGH padding where attention masks it,
so the chunk form stops its state at the chunk's last real row and starts
from zero at position 0 (:func:`..ops.short_conv.short_conv_chunk`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import paged_decode_attention
from ..ops.gqa_attention import gqa_paged_chunk_attention
from ..ops.short_conv import short_conv_chunk, short_conv_step, state_shape
from .kv_pages import write_chunk_pages
from .laguna import chunk_attention
from .xing4 import _swiglu, moe_ffn, rms_norm

CONV, FULL = "conv", "full"


def published_layer_types(n_layers: int) -> Tuple[str, ...]:
    """The published pattern cut to ``n_layers``: conv, conv, full, conv."""
    return tuple(FULL if i % 4 == 2 else CONV for i in range(n_layers))


@dataclass(frozen=True)
class Lfm2Config:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = published_layer_types(40)
    conv_L_cache: int = 3                # taps of the short convolution
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    intermediate_size: int = 11776
    num_dense_layers: int = 2
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64           # all of them held
    experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-5
    max_positions: int = 128000
    dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw) -> "Lfm2Config":
        """The structure at toy widths (CPU tests, the CLI preset): both
        dense layers and two whole periods, groups of 2 over 2 KV heads,
        8 experts top-2 of a width that is no multiple of 16."""
        base = dict(
            vocab_size=256, hidden_size=32,
            layer_types=published_layer_types(10), n_heads=4, n_kv_heads=2,
            head_dim=8, rope_theta=1e4, intermediate_size=48,
            moe_intermediate_size=20, n_routed_experts=8, experts_per_tok=2,
            max_positions=256, dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "Lfm2Config":
        """From the published ``config.json``'s keys (``model_type``
        ``lfm2_moe``); what this file does not compute is refused."""
        n = int(c["num_hidden_layers"])
        kinds = {"conv": CONV, "full_attention": FULL}
        types_ = c["layer_types"]
        if len(types_) != n or set(types_) - set(kinds):
            raise ValueError(
                f"layer_types is not {n} of conv / full_attention")
        if c.get("conv_bias") or not c["norm_topk_prob"] or not c[
                "use_expert_bias"]:
            raise ValueError("built: no convolution bias, renormalised "
                             "gates, an expert bias")
        rp = c["rope_parameters"]
        if rp.get("rope_type", "default") != "default":
            raise ValueError("built: plain rotary positions")
        heads = int(c["num_attention_heads"])
        hd = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            layer_types=tuple(kinds[t] for t in types_),
            conv_L_cache=int(c["conv_L_cache"]), n_heads=heads,
            n_kv_heads=int(c["num_key_value_heads"]), head_dim=hd,
            rope_theta=float(rp["rope_theta"]),
            intermediate_size=int(c["intermediate_size"]),
            num_dense_layers=int(c["num_dense_layers"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            n_routed_experts=int(c["num_experts"]),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            rms_eps=float(c["norm_eps"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    def is_conv(self, layer: int) -> bool:
        return self.layer_types[layer] == CONV


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: Lfm2Config, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters, by its
    operator and its feed-forward (expert weights ``(E, 2I, h)`` /
    ``(E, I, h)``, as ``xing4``'s)."""
    h, dt, f32 = cfg.hidden_size, cfg.dtype, jnp.float32
    out = {"op_norm_g": ((h,), dt), "ffn_norm_g": ((h,), dt)}
    if cfg.is_conv(layer):
        out.update({"in_w": ((h, 3 * h), dt),                # [B | C | z]
                    "conv_w": ((h, cfg.conv_L_cache), dt),
                    "out_w": ((h, h), dt)})
    else:
        hd = cfg.head_dim
        q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        out.update({"q_w": ((h, q), dt), "k_w": ((h, kv), dt),
                    "v_w": ((h, kv), dt), "o_w": ((q, h), dt),
                    "q_norm_g": ((hd,), dt), "k_norm_g": ((hd,), dt)})
    if cfg.is_dense(layer):
        out.update({"mlp_gu_w": ((h, 2 * cfg.intermediate_size), dt),
                    "mlp_down_w": ((cfg.intermediate_size, h), dt)})
    else:
        E, I = cfg.n_routed_experts, cfg.moe_intermediate_size
        out.update({"router_w": ((h, E), f32), "router_bias": ((E,), f32),
                    "exp_gu_w": ((E, 2 * I, h), dt),
                    "exp_down_w": ((E, I, h), dt)})
    return out


def param_shapes(cfg: Lfm2Config) -> Dict[str, Tuple]:
    out = {"wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
           "norm_f_g": ((cfg.hidden_size,), cfg.dtype)}
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: Lfm2Config, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains, a
    small expert bias."""
    shapes = param_shapes(cfg)
    out = {}
    for k, (name, (shape, dt)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dt)
        elif name.endswith("router_bias"):
            out[name] = (0.01 * jax.random.normal(k, shape)).astype(dt)
        else:
            out[name] = (std * jax.random.normal(k, shape)).astype(dt)
    return out


# -- the two operators and the feed-forward -----------------------------------------


def layer_params(params, cfg: Lfm2Config, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def conv_gates(p, xn):
    """``u = B * z`` and ``C`` of ``xn W_in``, (N, h) each."""
    B, C, z = jnp.split(xn @ p["in_w"], 3, axis=-1)
    return B * z, C


def rope(x, positions, cfg: Lfm2Config):
    """Rotate the whole last axis (half-split pairing) at ``positions``
    (broadcastable to ``x``'s leading axes), in float32."""
    hd = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (
        np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def qkv(p, xn, positions, cfg: Lfm2Config):
    """Of tokens ``xn`` (N, h) at ``positions`` (N,): ``q`` (N, H, hd) and
    ``k`` (N, Hkv, hd), each head normed and THEN rotated, and ``v`` —
    ``k`` and ``v`` are the rows cached."""
    N, hd = xn.shape[0], cfg.head_dim
    at = positions[:, None]
    q = rms_norm((xn @ p["q_w"]).reshape(N, -1, hd), p["q_norm_g"],
                 cfg.rms_eps)
    k = rms_norm((xn @ p["k_w"]).reshape(N, -1, hd), p["k_norm_g"],
                 cfg.rms_eps)
    return (rope(q, at, cfg), rope(k, at, cfg),
            (xn @ p["v_w"]).reshape(N, -1, hd))


def ffn(p, x, cfg: Lfm2Config, layer: int, live=None, impl=None):
    """SwiGLU in a dense layer; elsewhere all the routed experts and
    nothing beside them (``xing4.moe_ffn`` and its ``moe_route``)."""
    if cfg.is_dense(layer):
        return _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"]), None
    return moe_ffn(p, x, cfg, held=None, shared=False, live=live, impl=impl)


# -- prefill and decode ---------------------------------------------------------


def prefill_layer(p, x, cache, pos0, last, cfg: Lfm2Config, layer: int,
                  impl=None, pages=None):
    """One layer over a chunk ``x`` (b, T, h) at positions ``pos0 + t``
    whose last real row is ``last``; ``cache`` the layer's own entries by
    kind: a conv layer's carried inputs ``conv`` (b, K - 1, R, N), an
    attention layer's ``k`` / ``v`` (b, Hkv, cap, hd) — or, with
    ``pages`` (b, pages_per_seq), its two pools as they are stored
    (``laguna.prefill_layer``'s form).  Returns ``(x', cache')``."""
    b, T, h = x.shape
    xf = x.reshape(b * T, h)
    xn = rms_norm(xf, p["op_norm_g"], cfg.rms_eps)
    if cfg.is_conv(layer):
        u, C = conv_gates(p, xn)
        outs = [short_conv_chunk(u.reshape(b, T, h)[s], p["conv_w"],
                                 cache["conv"][s], pos0, last)
                for s in range(b)]
        v, conv = (jnp.stack(t) for t in zip(*outs))
        op = (C.astype(jnp.float32) * v.reshape(b * T, h)).astype(
            x.dtype) @ p["out_w"]
        cache = {"conv": conv}
    else:
        t = jnp.arange(T, dtype=jnp.int32)
        q, k, v = qkv(p, xn, jnp.tile(pos0 + t, b), cfg)
        q = q.reshape(b, T, -1, cfg.head_dim)
        if pages is not None:
            keys, vals = (
                write_chunk_pages(cache[kk], r.reshape(b, T, -1), pages, pos0)
                for kk, r in (("k", k), ("v", v)))
            o = gqa_paged_chunk_attention(q, keys, vals, pages, pos0,
                                          scale=cfg.softmax_scale, impl=impl)
        else:
            keys, vals = (
                jax.lax.dynamic_update_slice_in_dim(
                    cache[kk], r.reshape(b, T, -1, cfg.head_dim).transpose(
                        0, 2, 1, 3).astype(cache[kk].dtype), pos0, axis=2)
                for kk, r in (("k", k), ("v", v)))
            o = chunk_attention(q, keys, vals, pos0, cfg, impl)
        op = o.reshape(b * T, -1) @ p["o_w"]
        cache = {"k": keys, "v": vals}
    xf = xf + op
    y, _ = ffn(p, rms_norm(xf, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
               impl=impl)
    return (xf + y).reshape(b, T, h), cache


def decode_layer(p, x, lengths, live, cfg: Lfm2Config, layer: int,
                 impl=None):
    """One layer of one decode step: ``x`` (S, h), one token a slot at
    position ``lengths[s]``.  Returns ``(x', what the layer caches by pool
    kind, stats)``: a conv layer hands back its state POOL whole (the
    live slots' rows shifted by this step's ``u`` in place) and counts the
    slots it stepped (``stats["conv"]``), an attention layer this step's
    normed, rotated ``k`` and ``v`` rows (attended before they are
    written: the pool writes are the loop composer's); either routes, in
    an expert layer, and ``stats["moe"]`` is ``xing4``'s pair."""
    xn = rms_norm(x, p["op_norm_g"], cfg.rms_eps)
    stats = {}
    if cfg.is_conv(layer):
        u, C = conv_gates(p, xn)
        v, pool = short_conv_step(u, p["conv_w"], p["cache_conv"], live,
                                  impl=impl)
        op = (C.astype(jnp.float32) * v).astype(x.dtype) @ p["out_w"]
        new = {"conv": pool}
        stats["conv"] = live.sum(dtype=jnp.float32)
    else:
        q, k, v = qkv(p, xn, lengths, cfg)
        o = paged_decode_attention(
            q[:, :, None, :], p["cache_k"], p["cache_v"], p["page_table"],
            lengths, cfg.softmax_scale, k_new=k[:, :, None, :],
            v_new=v[:, :, None, :], impl=impl)[:, :, 0, :]
        op = o.reshape(x.shape[0], -1) @ p["o_w"]
        new = {"k": k, "v": v}
    x = x + op
    y, moe = ffn(p, rms_norm(x, p["ffn_norm_g"], cfg.rms_eps), cfg, layer,
                 live=live, impl=impl)
    if moe is not None:
        stats["moe"] = moe
    return x + y, new, stats or None


def head(params, x, cfg: Lfm2Config):
    """Final RMSNorm and the head tied to the embedding."""
    return jax.lax.dot_general(
        rms_norm(x, params["norm_f_g"], cfg.rms_eps), params["wte"],
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# -- the rest of what the paged builder and the engine call
# (models/__init__.py) ---------------------------------------------------------

EMBED_PARAMS = ("wte",)
HEAD_PARAMS = ("norm_f_g", "wte")
#: the step's graph takes ``active`` (the slots that decode) as an input
#: and carries it on every edge as ``live``
DECODE_TAKES_LIVE = True
#: :func:`forward_cached_row` takes ``pages`` (``laguna``'s form): where
#: the paged chunk kernel admits the shape, a chunk program leaves the
#: attention layers' K and V in their pages (a head of 64 does not: the
#: engine asks, ``PagedDecodeEngine._chunk_in_pages``)
PREFILL_TAKES_PAGES = True


def layer_param_names(cfg: Lfm2Config, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: Lfm2Config):
    """Per layer by its operator: a conv layer keeps a STATE a slot (the
    last ``conv_L_cache - 1`` rows of ``u`` in the cache's dtype, its
    channels 128 to a row as ``_short_conv_step`` reads them), an
    attention layer pages its ``k`` and ``v`` for the whole context."""
    from .kv_pages import CacheSpec, LayerCache

    row = (cfg.n_kv_heads, cfg.head_dim)
    kinds = {
        CONV: LayerCache(
            (("conv", state_shape(cfg.hidden_size, cfg.conv_L_cache)),),
            state=True),
        FULL: LayerCache((("k", row), ("v", row)), q_heads=cfg.n_heads),
    }
    return CacheSpec("kv", tuple(kinds[t] for t in cfg.layer_types),
                     walk=("k", None))


def decode_embed(p, ids, lengths, cfg: Lfm2Config):
    """Positions are the attention layers' rotary angles, not the
    embedding's."""
    return p["wte"][ids[:, 0]]


def decode_head(p, x, cfg: Lfm2Config):
    return head(p, x, cfg)[:, None, :]


def decode_flops(cfg: Lfm2Config, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step: a
    layer's weights streamed once (experts: the picked ones), an
    attention layer's scores over the slot's capacity, a conv layer's
    taps."""
    S, h = slots, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    layers = []
    for i in range(cfg.n_layers):
        f = (2.0 * S * h * cfg.conv_L_cache if cfg.is_conv(i) else
             2.0 * 2.0 * S * cfg.n_heads * capacity * cfg.head_dim)
        f += sum(2.0 * S * math.prod(shape)
                 * (picked if k.startswith("exp_") else 1.0)
                 for k, (shape, _) in layer_param_shapes(cfg, i).items()
                 if len(shape) >= 2)
        layers.append(f)
    return 2.0 * S * h, layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: Lfm2Config, batch: int, cap: int, dtype=None,
               page_size: Optional[int] = None):
    """The zeroed dense cache of :func:`forward_cached`: ``{"k", "v"}``
    (attention layers, batch, Hkv, cap, hd) and ``{"conv"}`` (conv
    layers, batch, the state)."""
    return cache_spec(cfg).init_dense(
        batch, cap, dtype or cfg.dtype, page_size=page_size)


def _prefill(params, ids, cache, pos_start, last, cfg, impl=None, pages=None):
    """``cache`` by kind: the layers that keep it stacked — or, with
    ``pages``, an attention layer's kinds as tuples of their pools, which
    come back as tuples."""
    x = params["wte"][ids]
    spec = cache_spec(cfg)
    seen: Dict[str, int] = {}
    out = {k: [] for k in cache}
    for i in range(cfg.n_layers):
        kinds = spec.layer_kinds(i)
        for k in kinds:
            seen[k] = seen.get(k, -1) + 1
        x, mine = prefill_layer(
            layer_params(params, cfg, i), x,
            {k: cache[k][seen[k]] for k in kinds}, pos_start, last, cfg, i,
            impl, pages)
        for k in kinds:
            out[k].append(mine[k])
    return x, {k: tuple(v) if isinstance(cache[k], tuple) else jnp.stack(v)
               for k, v in out.items()}


def forward_cached(params, ids, cache, pos_start, cfg: Lfm2Config,
                   impl=None):
    """The family's cached forward: ``ids`` (b, T) at positions
    ``pos_start + t`` over ``cache`` (:func:`init_cache`); returns
    ``(logits (b, T, V) float32, cache)``, the states after the last
    row."""
    x, cache = _prefill(
        params, ids, cache, pos_start, ids.shape[1] - 1, cfg, impl)
    return head(params, x, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: Lfm2Config,
                       row, impl=None, pages=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V); ``row`` is the chunk's last REAL row — the rows after it are
    padding, and the conv layers' states that come back are the inputs
    after ``row`` (this family's duty: no mask keeps a convolution out of
    padding).  ``pages`` (b, pages_per_seq), :data:`PREFILL_TAKES_PAGES`:
    the attention layers' kinds of ``cache`` are tuples of their pools,
    written and read through these table rows."""
    x, cache = _prefill(params, ids, cache, pos_start, row, cfg, impl, pages)
    return head(params, jax.lax.dynamic_index_in_dim(
        x, row, 1, keepdims=False), cfg), cache


def forward(params, ids, cfg: Lfm2Config, impl=None):
    """Logits (b, T, V) of whole sequences: a prefill from position 0."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]

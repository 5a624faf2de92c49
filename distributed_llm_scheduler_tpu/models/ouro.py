"""Ouro family (``model_type`` ``ouro``): a looped stack, served.

The ``n_layers`` decoder layers run ``total_ut_steps`` times a token on
ONE set of weights: pass ``u`` starts from the final norm of pass ``u -
1`` and attends its OWN keys and values — a cache entry a (pass, layer),
never shared between passes — so a token's cache is ``total_ut_steps``
times what a stack of these widths keeps, while its weights are read
``total_ut_steps`` times a token (:func:`cache_spec`: ``passes``).

A layer is multi-head attention (as many KV heads as query heads; plain
rotary positions on every value of a head, half-split pairing; the
ROTATED keys are what is cached) and a SwiGLU, each between two
RMSNorms — one on the sublayer's input, one on its output before the
residual takes it (the family's "sandwich")::

    x = x + rms(attn(rms(x, g1)) W_o, g2)
    x = x + rms(swiglu(rms(x, g3)), g4)

The final norm closes EVERY pass (``h_u = rms(x, g_f)``, the next pass's
input); an exit gate ``lam_u = sigmoid(h_u . w_e + b_e)`` reads every
``h_u``, and the exit distribution is ``p_0 = lam_0``, ``p_u = lam_u
prod_{j<u} (1 - lam_j)``, the last pass taking what is left.  At
``early_exit_threshold`` 1.0 (published) the cumulative exit mass
reaches the threshold at the last pass only: every token runs every
pass, and the logits are ``h_last W_head``.  A threshold under 1 — a
slot that leaves the loop early — is not built (:class:`OuroConfig`
refuses it; ROADMAP, Queue 2).

The passes are a loop in the program, not four copies of the stack:
the chunk program here scans :func:`_pass` over ``u`` with the pools
carried, and the decode step's composer does the same over the graph's
pass sub-chain (``backends/decode_passes.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import paged_decode_attention
from ..ops.gqa_attention import gqa_paged_chunk_attention
from .kv_pages import write_chunk_pages
from .laguna import chunk_attention
from .xing4 import _swiglu, rms_norm


@dataclass(frozen=True)
class OuroConfig:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    #: passes over the ``n_layers`` layers a token (``total_ut_steps``)
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    max_positions: int = 65536
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                "an early_exit_threshold under 1 lets a slot leave the loop "
                "before its last pass, whose later planes must still be "
                "filled for the tokens after it: not built")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads must be a multiple of KV heads")

    @classmethod
    def tiny(cls, **kw) -> "OuroConfig":
        """The structure at toy widths (CPU tests, the CLI preset): 3
        layers run 3 times, 4 heads of 128 (a whole lane tile, so the
        chunk program may leave its pools in their pages)."""
        base = dict(
            vocab_size=256, hidden_size=64, n_layers=3, n_heads=4,
            n_kv_heads=4, head_dim=128, intermediate_size=96,
            rope_theta=1e4, total_ut_steps=3, max_positions=512,
            dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "OuroConfig":
        """From the published ``config.json``'s keys (``model_type``
        ``ouro``)."""
        if c.get("rope_scaling") or c.get("use_sliding_window"):
            raise ValueError("built: plain rotary, every layer full")
        if c.get("hidden_act", "silu") != "silu" or c.get(
                "tie_word_embeddings"):
            raise ValueError("built: SwiGLU, an untied head")
        heads = int(c["num_attention_heads"])
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            n_layers=int(c["num_hidden_layers"]), n_heads=heads,
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
            intermediate_size=int(c["intermediate_size"]),
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
            total_ut_steps=int(c["total_ut_steps"]),
            early_exit_threshold=float(c["early_exit_threshold"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: OuroConfig, layer: int = 0) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters: the four
    norms' gains in the order they are applied, the projections, and the
    SwiGLU's gate beside its up-projection."""
    h, dt = cfg.hidden_size, cfg.dtype
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "attn_norm_g": ((h,), dt), "q_w": ((h, q), dt), "k_w": ((h, kv), dt),
        "v_w": ((h, kv), dt), "o_w": ((q, h), dt), "attn_post_g": ((h,), dt),
        "ffn_norm_g": ((h,), dt),
        "mlp_gu_w": ((h, 2 * cfg.intermediate_size), dt),
        "mlp_down_w": ((cfg.intermediate_size, h), dt),
        "ffn_post_g": ((h,), dt),
    }


def param_shapes(cfg: OuroConfig) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
        "exit_w": ((cfg.hidden_size,), cfg.dtype),
        "exit_b": ((1,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: OuroConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains."""
    shapes = param_shapes(cfg)
    return {
        name: (jnp.ones(shape, dt) if name.endswith("_g") else
               (std * jax.random.normal(k, shape)).astype(dt))
        for k, (name, (shape, dt)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))}


# -- small pieces ---------------------------------------------------------------


def rope(x, positions, cfg: OuroConfig):
    """Rotate every value of the last axis (half-split pairing) at
    ``positions`` (broadcastable to ``x``'s leading axes), in float32."""
    hd = cfg.head_dim
    inv = (1.0 / cfg.rope_theta ** (
        np.arange(0, hd, 2, dtype=np.float64) / hd)).astype(np.float32)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def qkv(p, xn, positions, cfg: OuroConfig):
    """Of tokens ``xn`` (N, h) at ``positions`` (N,): rotated ``q`` (N,
    H, hd), rotated ``k`` and ``v`` (N, Hkv, hd) — the rows cached."""
    N, hd = xn.shape[0], cfg.head_dim
    at = positions[:, None]
    q = rope((xn @ p["q_w"]).reshape(N, -1, hd), at, cfg)
    k = rope((xn @ p["k_w"]).reshape(N, -1, hd), at, cfg)
    return q, k, (xn @ p["v_w"]).reshape(N, -1, hd)


def after_attention(p, x, o, cfg: OuroConfig):
    """The rest of a layer on tokens ``x`` (N, h) whose heads' outputs
    are ``o`` (N, H * hd): both residuals, each behind its post-norm."""
    x = x + rms_norm(o @ p["o_w"], p["attn_post_g"], cfg.rms_eps)
    m = _swiglu(rms_norm(x, p["ffn_norm_g"], cfg.rms_eps),
                p["mlp_gu_w"], p["mlp_down_w"])
    return x + rms_norm(m, p["ffn_post_g"], cfg.rms_eps)


def pass_end(p, x, cfg: OuroConfig):
    """What closes a pass on tokens ``x`` (..., h): ``(h_u, lam_u)`` — the
    final norm, which the next pass starts from, and the exit gate's
    probability (float32, ``x``'s leading shape)."""
    h_u = rms_norm(x, p["norm_f_g"], cfg.rms_eps)
    z = jnp.sum(h_u.astype(jnp.float32) * p["exit_w"].astype(jnp.float32),
                -1) + p["exit_b"].astype(jnp.float32)[0]
    return h_u, jax.nn.sigmoid(z)


def exit_update(lam, u, survive, expected, cfg: OuroConfig):
    """The exit distribution one pass on: ``p_u = lam_u * survive`` (the
    last pass takes all of ``survive`` = ``prod_{j<u} (1 - lam_j)``);
    returns ``(survive', expected + (u + 1) p_u)``."""
    p_u = jnp.where(u == cfg.total_ut_steps - 1, survive, lam * survive)
    return survive - p_u, expected + (u + 1).astype(jnp.float32) * p_u


# -- the block, prefill and decode ------------------------------------------------


def layer_params(params, cfg: OuroConfig, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def prefill_layer(p, x, cache, pos0, cfg: OuroConfig, impl=None, pages=None):
    """One layer of one pass over a chunk ``x`` (b, T, h) at positions
    ``pos0 + t``; ``cache`` this (pass, layer)'s ``{"k", "v"}``: dense
    rows (b, Hkv, cap, hd), or — with ``pages`` (b, pages_per_seq), the
    sequences' table rows ALREADY shifted into the pass's plane — the
    layer's two pools as they are stored: the chunk's rotated K and V
    (whole pages) go into the pages that hold their positions and the
    attention reads through the table.  Returns ``(x', cache')``."""
    b, T, h = x.shape
    xf = x.reshape(b * T, h)
    xn = rms_norm(xf, p["attn_norm_g"], cfg.rms_eps)
    t = jnp.arange(T, dtype=jnp.int32)
    q, k, v = qkv(p, xn, jnp.tile(pos0 + t, b), cfg)
    q = q.reshape(b, T, -1, cfg.head_dim)
    if pages is not None:
        keys, vals = (
            write_chunk_pages(cache[kind], r.reshape(b, T, -1), pages, pos0)
            for kind, r in (("k", k), ("v", v)))
        o = gqa_paged_chunk_attention(q, keys, vals, pages, pos0,
                                      scale=cfg.softmax_scale, impl=impl)
    else:
        def heads_first(r):
            return r.reshape(b, T, -1, cfg.head_dim).transpose(
                0, 2, 1, 3).astype(cache["k"].dtype)

        keys = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], heads_first(k), pos0, axis=2)
        vals = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], heads_first(v), pos0, axis=2)
        o = chunk_attention(q, keys, vals, pos0, cfg, impl)
    xf = after_attention(p, xf, o.reshape(b * T, -1), cfg)
    return xf.reshape(b, T, h), {"k": keys, "v": vals}


def decode_layer(p, x, lengths, live, cfg: OuroConfig, layer: int,
                 impl=None, u=0):
    """One layer of pass ``u`` (static or traced) of one decode step:
    ``x`` (S, h), one token a slot at position ``lengths[s]``, attending
    the pass's plane of ``p["cache_k"]`` / ``p["cache_v"]`` through
    ``p["page_table"]`` (this step's rotated rows attended before they
    are written: the pool writes are the loop composer's).  Returns
    ``(x', new rows by pool kind, None)``."""
    xn = rms_norm(x, p["attn_norm_g"], cfg.rms_eps)
    q, k, v = qkv(p, xn, lengths, cfg)
    o = paged_decode_attention(
        q[:, :, None, :], p["cache_k"], p["cache_v"],
        cache_spec(cfg).plane(p["cache_k"], p["page_table"], u), lengths,
        cfg.softmax_scale, k_new=k[:, :, None, :], v_new=v[:, :, None, :],
        impl=impl)[:, :, 0, :]
    x = after_attention(p, x, o.reshape(x.shape[0], -1), cfg)
    return x, {"k": k, "v": v}, None


# -- the rest of what the paged builder and the engine call
# (models/__init__.py) ---------------------------------------------------------

EMBED_PARAMS = ("wte",)
HEAD_PARAMS = ("head_w",)
#: what the task that closes a pass aliases (``decode_pass_end``)
PASS_END_PARAMS = ("norm_f_g", "exit_w", "exit_b")
#: the step's graph takes ``active`` (the slots that decode) as an input
#: and carries it on every edge as ``live``
DECODE_TAKES_LIVE = True
#: :func:`forward_cached_row` takes ``pages``: where the chunk kernel
#: admits the shape, a chunk program leaves K and V in their pages
PREFILL_TAKES_PAGES = True


def layer_param_names(cfg: OuroConfig, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: OuroConfig):
    """Every layer pages its rotated ``k`` and its ``v``, once a pass."""
    from .kv_pages import CacheSpec, LayerCache

    row = (cfg.n_kv_heads, cfg.head_dim)
    return CacheSpec(
        "kv", (LayerCache((("k", row), ("v", row))),) * cfg.n_layers,
        q_heads=cfg.n_heads, passes=cfg.total_ut_steps)


def decode_embed(p, ids, lengths, cfg: OuroConfig):
    """Positions are the layers' rotary angles, not the embedding's."""
    return p["wte"][ids[:, 0]]


def decode_pass_end(p, x, live, u, survive, expected, cfg: OuroConfig):
    """The task that closes pass ``u`` of a decode step: ``(h_u, survive',
    expected', stats)`` — the next pass's input, the exit distribution a
    pass on (:func:`exit_update`) and ``stats``: ``loop`` = (slots that
    ran the pass, their ``(u + 1) p_u`` summed), ``loop_lam`` (S,) and
    ``loop_h`` (S, h) for a probe."""
    h_u, lam = pass_end(p, x, cfg)
    s2, e2 = exit_update(lam, u, survive, expected, cfg)
    ran = live.astype(jnp.float32)
    return h_u, s2, e2, {
        "loop": jnp.stack([ran.sum(), ((e2 - expected) * ran).sum()]),
        "loop_lam": lam, "loop_h": h_u}


def decode_head(p, x, cfg: OuroConfig):
    """The untied head on the last pass's ``h_u`` (already normed)."""
    return head(p, x, cfg)[:, None, :]


def decode_flops(cfg: OuroConfig, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of ONE pass of one paged
    step: a layer's weights streamed once and the attention over the
    slot's capacity.  The graph holds ``total_ut_steps`` tasks a layer."""
    S, h = slots, cfg.hidden_size
    f = 2.0 * 2.0 * S * cfg.n_heads * capacity * cfg.head_dim + sum(
        2.0 * S * math.prod(shape)
        for shape, _ in layer_param_shapes(cfg).values() if len(shape) >= 2)
    return 2.0 * S * h, [f] * cfg.n_layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: OuroConfig, batch: int, cap: int, dtype=None):
    """The zeroed dense cache of :func:`forward_cached`: ``{"k", "v"}``
    each (passes * layers, batch, Hkv, cap, hd), entry ``u * layers + l``."""
    return cache_spec(cfg).init_dense(batch, cap, dtype or cfg.dtype)


def _pass(params, x, cache, pos0, cfg, impl, pages):
    """The ``n_layers`` layers and the pass's end over a chunk ``x``;
    ``cache`` this pass's ``{"k", "v"}``: stacked dense rows (layers, b,
    Hkv, cap, hd), or tuples of the layers' pools with ``pages`` shifted
    into the pass's plane.  Returns ``(h_u, lam_u, cache')``."""
    out = {"k": [], "v": []}
    for i in range(cfg.n_layers):
        x, mine = prefill_layer(
            layer_params(params, cfg, i), x,
            {k: cache[k][i] for k in out}, pos0, cfg, impl, pages)
        for k in out:
            out[k].append(mine[k])
    h_u, lam = pass_end(params, x, cfg)
    return h_u, lam, {k: tuple(v) if pages is not None else jnp.stack(v)
                      for k, v in out.items()}


def _prefill(params, ids, cache, pos_start, cfg, impl=None, pages=None):
    """Every pass over a chunk, ONE traced pass under a ``lax.scan`` over
    ``u``: with ``pages`` the pools are the carry (each pass reads and
    writes its plane of them in place); a dense cache is scanned over as
    (passes, layers, ...).  Returns ``(h of the last pass, cache, the
    passes' (h_u, lam_u) stacked)``."""
    spec = cache_spec(cfg)
    x = params["wte"][ids]
    us = jnp.arange(cfg.total_ut_steps, dtype=jnp.int32)
    if pages is not None:
        def body(carry, u):
            x, pools = carry
            h_u, lam, pools = _pass(
                params, x, pools, pos_start, cfg, impl,
                spec.plane(pools["k"][0], pages, u))
            return (h_u, pools), (h_u, lam)

        (x, cache), per_pass = jax.lax.scan(body, (x, dict(cache)), us)
        return x, cache, per_pass

    def dense_body(x, mine):
        h_u, lam, mine = _pass(params, x, mine, pos_start, cfg, impl, None)
        return h_u, (mine, h_u, lam)

    split = {k: v.reshape(cfg.total_ut_steps, cfg.n_layers, *v.shape[1:])
             for k, v in cache.items()}
    x, (split, hs, lams) = jax.lax.scan(dense_body, x, split)
    return x, {k: v.reshape(cache[k].shape) for k, v in split.items()}, (
        hs, lams)


def head(params, x, cfg: OuroConfig):
    return jnp.dot(x, params["head_w"], preferred_element_type=jnp.float32)


def forward_cached(params, ids, cache, pos_start, cfg: OuroConfig,
                   impl=None):
    """The family's cached forward (the engine's prefill contract):
    ``ids`` (b, T) at positions ``pos_start + t`` over ``cache``
    (:func:`init_cache`); returns ``(logits (b, T, V) float32, cache)``."""
    x, cache, _ = _prefill(params, ids, cache, pos_start, cfg, impl)
    return head(params, x, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: OuroConfig,
                       row, impl=None, pages=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V).  ``pages`` (b, pages_per_seq), :data:`PREFILL_TAKES_PAGES`:
    ``cache``'s kinds are tuples of the layers' pools, written and read
    through these table rows where they lie (``ids`` whole pages, at a
    page's first position), pass ``u`` in its plane."""
    x, cache, _ = _prefill(params, ids, cache, pos_start, cfg, impl, pages)
    return head(params, jax.lax.dynamic_index_in_dim(
        x, row, 1, keepdims=False), cfg), cache

"""Nemotron-H family (``model_type`` ``nemotron_h``): the served hybrid
stack.

Every layer is ONE sublayer, ``x = x + f(RMSNorm(x))``, ``f`` by the
letter of ``pattern`` (the published ``hybrid_override_pattern``):

* ``M`` — a **Mamba-2 mixer**: ``[z | xBC | dt] = u W_in``; ``xBC`` through
  a causal depthwise convolution of ``conv_kernel`` taps and ``silu``;
  ``[x | B | C] = xBC`` (``x`` heads of ``mamba_head_dim``, ``B`` / ``C``
  ``n_groups`` groups of ``ssm_state``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = h_t C_t + D x_t``; the gate ``silu(z)`` BEFORE a grouped
  RMSNorm; ``W_out``.  What it caches is not a row a token but a state a
  SLOT — the float32 ``h`` and the convolution's last ``conv_kernel - 1``
  inputs — overwritten by every step and every chunk
  (:class:`.kv_pages.CacheSpec`, state layers; :mod:`..ops.ssm`).
* ``*`` — grouped-query attention with **no position of any kind** (the
  mixers carry order): paged K / V rows through ``_paged_flash``.
* ``E`` — routed experts of two matrices, ``relu(x W_u)^2 W_d``, picked by
  ``xing4.moe_route`` (sigmoid scores, the correction bias, renormalised,
  scaled), plus a shared one; a chip may hold a share (``held_experts``).
  Caches nothing.

A scan runs THROUGH padding where attention masks it, so this family's
prefill stops its states at the chunk's last real row itself
(:func:`forward_cached_row`'s ``row``): rows after it get ``dt = 0`` and
the convolution state is the last real rows.  A chunk at position 0
starts from a zero state whatever the slot's rows hold.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import paged_decode_attention
from ..ops.gqa_attention import gqa_paged_chunk_attention
from ..ops.ssm import ssd_chunk, ssm_step
from .kv_pages import write_chunk_pages
from .laguna import chunk_attention
from .xing4 import moe_ffn_ungated, rms_norm

MIXER, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = PUBLISHED_PATTERN
    # M
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128                # tokens in a block of the scan
    # *
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # E
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    n_routed_experts: int = 128          # the router's outputs
    experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    mlp_act: str = "relu2"
    #: the routed experts this chip holds, in the order of its expert
    #: weights' leading axis (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    rms_eps: float = 1e-5
    max_positions: int = 262144
    dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """The structure at toy widths (CPU tests, the CLI preset): all
        three letters, two mixers apart so a state crosses layers, heads
        two to a state row, two groups, an expert width that is no
        multiple of 128 or 16."""
        base = dict(
            vocab_size=256, hidden_size=32, pattern="ME*MEM",
            mamba_heads=4, mamba_head_dim=8, ssm_state=16, n_groups=2,
            conv_kernel=4, chunk_size=4, n_heads=4, n_kv_heads=2, head_dim=8,
            moe_intermediate_size=20, shared_intermediate_size=24,
            n_routed_experts=8, experts_per_tok=3, max_positions=256,
            dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "NemotronHConfig":
        """From the published ``config.json``'s keys.  A chip's share
        states ``n_routed_experts`` as the experts it holds, lists them
        under ``held_experts`` and the router's width under
        ``n_router_outputs``."""
        n = int(c["num_hidden_layers"])
        pattern = c["hybrid_override_pattern"]
        if len(pattern) != n or set(pattern) - {MIXER, EXPERTS, ATTENTION}:
            raise ValueError(f"pattern {pattern!r} is not {n} of M, E, *")
        if c.get("mlp_hidden_act") != "relu2" or c.get(
                "mamba_hidden_act") != "silu":
            raise ValueError("built: relu2 experts, silu mixers")
        if (int(c["n_group"]), int(c["topk_group"])) != (1, 1) or not c[
                "norm_topk_prob"] or int(c["n_shared_experts"]) != 1:
            raise ValueError("built: one routing group, renormalised "
                             "gates, one shared expert")
        if any(c.get(k) for k in ("attention_bias", "mlp_bias", "use_bias",
                                  "mamba_proj_bias")) or not c[
                                      "use_conv_bias"]:
            raise ValueError("built: no projection bias, a convolution bias")
        held = c.get("held_experts")
        if held is not None and len(held) != int(c["n_routed_experts"]):
            raise ValueError("held_experts does not list n_routed_experts")
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            pattern=pattern, mamba_heads=int(c["mamba_num_heads"]),
            mamba_head_dim=int(c["mamba_head_dim"]),
            ssm_state=int(c["ssm_state_size"]), n_groups=int(c["n_groups"]),
            conv_kernel=int(c["conv_kernel"]), chunk_size=int(c["chunk_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            shared_intermediate_size=int(
                c["moe_shared_expert_intermediate_size"]),
            n_routed_experts=int(
                c.get("n_router_outputs", c["n_routed_experts"])),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            held_experts=None if held is None else tuple(int(e) for e in held),
            rms_eps=float(c["layer_norm_epsilon"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_held_experts(self) -> int:
        return (self.n_routed_experts if self.held_experts is None
                else len(self.held_experts))

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: ``[x | B | C]``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: NemotronHConfig, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters, by its
    letter (expert weights ``(held, I, h)`` both, as the grouped kernel
    reads them)."""
    h, dt, f32 = cfg.hidden_size, cfg.dtype, jnp.float32
    out = {"norm_g": ((h,), dt)}
    kind = cfg.pattern[layer]
    if kind == MIXER:
        H, di, W = cfg.mamba_heads, cfg.d_inner, cfg.conv_width
        out.update({
            "in_w": ((h, di + W + H), dt),          # [z | xBC | dt]
            "conv_w": ((W, cfg.conv_kernel), dt), "conv_b": ((W,), dt),
            "dt_bias": ((H,), f32), "a_log": ((H,), f32),
            "d_skip": ((H,), f32),
            "mnorm_g": ((di,), dt), "out_w": ((di, h), dt)})
    elif kind == ATTENTION:
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        out.update({"q_w": ((h, q), dt), "k_w": ((h, kv), dt),
                    "v_w": ((h, kv), dt), "o_w": ((q, h), dt)})
    else:
        E, I = cfg.n_held_experts, cfg.moe_intermediate_size
        out.update({
            "router_w": ((h, cfg.n_routed_experts), f32),
            "router_bias": ((cfg.n_routed_experts,), f32),
            "exp_up_w": ((E, I, h), dt), "exp_down_w": ((E, I, h), dt),
            "shared_up_w": ((h, cfg.shared_intermediate_size), dt),
            "shared_down_w": ((cfg.shared_intermediate_size, h), dt)})
    return out


def param_shapes(cfg: NemotronHConfig) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: NemotronHConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains, and
    Mamba-2's own draws for the recurrence — ``A`` uniform in [1, 16],
    ``dt_bias`` the inverse softplus of a log-uniform step in [0.001,
    0.1], ``D`` = 1 — without which every head forgets in ten tokens."""
    shapes = param_shapes(cfg)
    out = {}
    for k, (name, (shape, dt)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if name.endswith(("_g", "d_skip")):
            out[name] = jnp.ones(shape, dt)
        elif name.endswith("a_log"):
            out[name] = jnp.log(jax.random.uniform(
                k, shape, minval=1.0, maxval=16.0)).astype(dt)
        elif name.endswith("dt_bias"):
            step = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(0.1)))
            out[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        elif name.endswith("router_bias"):
            out[name] = (0.01 * jax.random.normal(k, shape)).astype(dt)
        else:
            out[name] = (std * jax.random.normal(k, shape)).astype(dt)
    return out


# -- the three sublayers -----------------------------------------------------------


def layer_params(params, cfg: NemotronHConfig, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def _split_in(p, u, cfg: NemotronHConfig):
    """``z`` (N, d_inner), ``xBC`` (N, W), ``dt`` (N, H) of ``u W_in``."""
    zxd = u @ p["in_w"]
    di, W = cfg.d_inner, cfg.conv_width
    return zxd[:, :di], zxd[:, di:di + W], zxd[:, di + W:]


def gated_output(p, y, z, cfg: NemotronHConfig):
    """``RMSNorm_groups(y * silu(z)) * w`` (the gate BEFORE the norm,
    groups of ``d_inner / n_groups``, float32 statistics), through
    ``W_out``: ``y`` (N, d_inner) float32."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    gg = g.reshape(g.shape[0], cfg.n_groups, -1)
    gg = gg * jax.lax.rsqrt(
        jnp.mean(gg * gg, -1, keepdims=True) + cfg.rms_eps)
    out = gg.reshape(g.shape) * p["mnorm_g"].astype(jnp.float32)
    return out.astype(z.dtype) @ p["out_w"]


def mixer_chunk(p, u, conv, h, pos0, last, cfg: NemotronHConfig, impl=None):
    """A Mamba-2 mixer over a chunk of ONE sequence: ``u`` (T, h) the
    normed rows at positions ``pos0 + t`` whose last real row is
    ``last``; ``conv`` (K - 1, W / N, N) and ``h`` (H, P, N) the state
    the sequence holds — taken as zero where ``pos0`` is 0.  Returns ``(out
    (T, h), conv', h')``: the states after row ``last`` (the rows behind
    it are padding: they get ``dt = 0`` and stay out of ``conv'``)."""
    T, K1 = u.shape[0], cfg.conv_kernel - 1
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state)
    first = pos0 == 0
    stored = conv.shape
    conv = jnp.where(first, jnp.zeros_like(conv), conv).reshape(K1, -1)
    h = jnp.where(first, jnp.zeros_like(h), h)
    z, xbc, dt_raw = _split_in(p, u, cfg)
    seq = jnp.concatenate([conv, xbc.astype(conv.dtype)], 0)   # (K1 + T, W)
    w = p["conv_w"].astype(jnp.float32)
    act = jax.nn.silu(sum(
        seq[j:j + T].astype(jnp.float32) * w[:, j] for j in range(K1 + 1))
        + p["conv_b"].astype(jnp.float32))
    x = act[:, :H * P].reshape(T, H, P)
    B = act[:, H * P:H * P + G * N].reshape(T, G, N)
    C = act[:, H * P + G * N:].reshape(T, G, N)
    real = (jnp.arange(T) <= last)[:, None]
    dt = jnp.where(real, jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"]), 0.0)
    y, h = ssd_chunk(x, dt, -jnp.exp(p["a_log"]), B, C, h,
                     block=cfg.chunk_size, impl=impl)
    y = y + p["d_skip"][None, :, None] * x
    return (gated_output(p, y.reshape(T, H * P), z, cfg),
            jax.lax.dynamic_slice_in_dim(seq, last + 1, K1, 0).reshape(
                stored), h)


def mixer_step(p, u, live, cfg: NemotronHConfig, impl=None):
    """A Mamba-2 mixer's decode step: ``u`` (S, h) one normed token a
    slot, the slots' states in the pools ``p["cache_conv"]`` /
    ``p["cache_ssm"]``, updated in place for the ``live`` slots only.
    Returns ``(out (S, h), conv pool', ssm pool')``."""
    z, xbc, dt_raw = _split_in(p, u, cfg)
    y, conv, ssm = ssm_step(
        xbc, dt_raw, p["conv_w"], p["conv_b"], p["dt_bias"], p["a_log"],
        p["d_skip"], p["cache_conv"], p["cache_ssm"], live,
        groups=cfg.n_groups, impl=impl)
    return gated_output(p, y.reshape(y.shape[0], -1), z, cfg), conv, ssm


def qkv(p, xn, cfg: NemotronHConfig):
    """``q`` (N, H, hd), ``k`` and ``v`` (N, Hkv, hd): no rotation."""
    N, hd = xn.shape[0], cfg.head_dim
    return ((xn @ p["q_w"]).reshape(N, -1, hd),
            (xn @ p["k_w"]).reshape(N, -1, hd),
            (xn @ p["v_w"]).reshape(N, -1, hd))


def experts(p, xn, cfg: NemotronHConfig, live=None, impl=None):
    """The part of the routed experts this chip holds plus the shared
    expert (``xing4.moe_ffn_ungated``); ``(y, routing stats)``."""
    return moe_ffn_ungated(p, xn, cfg, cfg.mlp_act, held=cfg.held_experts,
                           live=live, impl=impl)


# -- prefill and decode ---------------------------------------------------------


def prefill_layer(p, x, cache, pos0, last, cfg: NemotronHConfig, layer: int,
                  impl=None, pages=None):
    """One layer over a chunk ``x`` (b, T, h) at positions ``pos0 + t``
    whose last real row is ``last``; ``cache`` the layer's own entries by
    kind: a mixer's states ``ssm`` (b, H, P, N) / ``conv`` (b, K - 1, W /
    N, N),
    an attention layer's ``k`` / ``v`` (b, Hkv, cap, hd) — or, with
    ``pages`` (b, pages_per_seq), its two pools as they are stored, the
    chunk's rows written into the pages that hold their positions and
    read through the table (``laguna.prefill_layer``'s form).  Returns
    ``(x', cache')``."""
    b, T, h = x.shape
    kind = cfg.pattern[layer]
    xn = rms_norm(x.reshape(b * T, h), p["norm_g"], cfg.rms_eps)
    if kind == EXPERTS:
        y, _ = experts(p, xn, cfg, impl=impl)
        return x + y.reshape(b, T, h), cache
    if kind == MIXER:
        outs = [mixer_chunk(p, xn.reshape(b, T, h)[s], cache["conv"][s],
                            cache["ssm"][s], pos0, last, cfg, impl)
                for s in range(b)]
        y, conv, ssm = (jnp.stack(v) for v in zip(*outs))
        return x + y, {"conv": conv, "ssm": ssm}
    q, k, v = qkv(p, xn, cfg)
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
    return (x + (o.reshape(b * T, -1) @ p["o_w"]).reshape(b, T, h),
            {"k": keys, "v": vals})


def decode_layer(p, x, lengths, live, cfg: NemotronHConfig, layer: int,
                 impl=None):
    """One layer of one decode step: ``x`` (S, h), one token a slot.
    Returns ``(x', what the layer caches by pool kind, stats)``: a mixer
    hands back its two state POOLS whole (the live slots' rows updated in
    place, ``stats["ssm"]`` the slots it stepped), an attention layer
    this step's ``k`` / ``v`` rows (attended before they are written: the
    pool writes are the loop composer's), an expert layer nothing and
    ``stats["moe"]`` as ``xing4``'s."""
    kind = cfg.pattern[layer]
    xn = rms_norm(x, p["norm_g"], cfg.rms_eps)
    if kind == EXPERTS:
        y, moe = experts(p, xn, cfg, live=live, impl=impl)
        return x + y, {}, {"moe": moe}
    if kind == MIXER:
        y, conv, ssm = mixer_step(p, xn, live, cfg, impl)
        return x + y, {"ssm": ssm, "conv": conv}, {
            "ssm": live.sum(dtype=jnp.float32)}
    q, k, v = qkv(p, xn, cfg)
    o = paged_decode_attention(
        q[:, :, None, :], p["cache_k"], p["cache_v"], p["page_table"],
        lengths, cfg.softmax_scale, k_new=k[:, :, None, :],
        v_new=v[:, :, None, :], impl=impl)[:, :, 0, :]
    return x + o.reshape(x.shape[0], -1) @ p["o_w"], {"k": k, "v": v}, None


def head(params, x, cfg: NemotronHConfig):
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
#: :func:`forward_cached_row` takes ``pages`` (``laguna``'s form): a chunk
#: program leaves the attention layers' K and V in their pages
PREFILL_TAKES_PAGES = True


def layer_param_names(cfg: NemotronHConfig, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: NemotronHConfig):
    """Per layer by its letter: a mixer keeps a STATE a slot (``ssm``
    float32; ``conv`` in the cache's dtype, its channels ``ssm_state`` to
    a row as ``_ssm_step`` reads them), an attention layer pages its
    ``k`` and ``v`` for the whole context, an expert layer caches
    nothing."""
    from .kv_pages import CacheSpec, LayerCache

    row = (cfg.n_kv_heads, cfg.head_dim)
    kinds = {
        MIXER: LayerCache(
            (("ssm", (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)),
             ("conv", (cfg.conv_kernel - 1, cfg.conv_width // cfg.ssm_state,
                       cfg.ssm_state))), state=True),
        ATTENTION: LayerCache((("k", row), ("v", row)), q_heads=cfg.n_heads),
        EXPERTS: LayerCache(()),
    }
    return CacheSpec("kv", tuple(kinds[c] for c in cfg.pattern),
                     walk=("k", None), dtypes=(("ssm", jnp.float32),))


def decode_embed(p, ids, lengths, cfg: NemotronHConfig):
    """No positions anywhere: the embedding alone."""
    return p["wte"][ids[:, 0]]


def decode_head(p, x, cfg: NemotronHConfig):
    return head(p, x, cfg)[:, None, :]


def decode_flops(cfg: NemotronHConfig, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step: a
    layer's weights streamed once (experts: the picked ones), an
    attention layer's scores over the slot's capacity, a mixer's state
    update."""
    S, h = slots, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    extra = {
        MIXER: 6.0 * S * cfg.d_inner * cfg.ssm_state,
        ATTENTION: 2.0 * 2.0 * S * cfg.n_heads * capacity * cfg.head_dim,
        EXPERTS: 0.0,
    }
    layers = [
        extra[cfg.pattern[i]] + sum(
            2.0 * S * math.prod(shape)
            * (picked if k.startswith("exp_") else 1.0)
            for k, (shape, _) in layer_param_shapes(cfg, i).items()
            if len(shape) >= 2)
        for i in range(cfg.n_layers)]
    return 2.0 * S * h, layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: NemotronHConfig, batch: int, cap: int, dtype=None,
               page_size: Optional[int] = None):
    """The zeroed dense cache of :func:`forward_cached`: ``{"k", "v"}``
    (attention layers, batch, Hkv, cap, hd) and ``{"ssm", "conv"}``
    (mixers, batch, the state)."""
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


def forward_cached(params, ids, cache, pos_start, cfg: NemotronHConfig,
                   impl=None):
    """The family's cached forward: ``ids`` (b, T) at positions
    ``pos_start + t`` over ``cache`` (:func:`init_cache`); returns
    ``(logits (b, T, V) float32, cache)``, the states after the last
    row."""
    x, cache = _prefill(
        params, ids, cache, pos_start, ids.shape[1] - 1, cfg, impl)
    return head(params, x, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: NemotronHConfig,
                       row, impl=None, pages=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V); ``row`` is the chunk's last REAL row — the rows after it are
    padding, and the mixers' states that come back are the states after
    ``row`` (this family's duty: no mask keeps a scan out of padding).
    ``pages`` (b, pages_per_seq), :data:`PREFILL_TAKES_PAGES`: the
    attention layers' kinds of ``cache`` are tuples of their pools,
    written and read through these table rows."""
    x, cache = _prefill(params, ids, cache, pos_start, row, cfg, impl, pages)
    return head(params, jax.lax.dynamic_index_in_dim(
        x, row, 1, keepdims=False), cfg), cache


def forward(params, ids, cfg: NemotronHConfig, impl=None):
    """Logits (b, T, V) of whole sequences: a prefill from position 0."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]

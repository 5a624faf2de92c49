"""GLM-4 MoE "lite" family (``model_type`` ``glm4_moe_lite``): the served
block WITH its multi-token-prediction module as the self-draft.

The main model is ``xing4``'s block without the hyper-connection
streams: pre-norm residual layers of multi-head latent attention (no
rotary scaling) and a SwiGLU that is dense in the leading layers and
sigmoid-routed experts plus a shared one elsewhere.  What this file
shares with ``xing4`` it calls there — the MLA projection, the expanded
(prefill) and absorbed (decode) attention, the router, the grouped
expert kernel — so an optimisation of one is the other's too.

What no other family has is the **draft module** (DeepSeek-V3's MTP
form, ``num_nextn_predict_layers`` = 1): for position ``i`` with the
main model's output ``h_i`` (after its final RMSNorm) and the NEXT token
``t_{i+1}``,

    z_i   = W_eh [ RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i) ]
    z'_i  = Layer_mtp(z_i)        one full expert layer, own weights,
                                  own latent cache over positions <= i
    draft logits_i = Head(RMSNorm_sh(z'_i))        predicts t_{i+2}

with ``Emb`` and ``Head`` the main model's.  A decode step
(:data:`DECODE_ROWS` = 2 rows a slot) feeds the main model the current
token and the draft made for the position after it, both causal, and the
draft module the two rows that come out (``draft_decode``): the engine
accepts the draft where the main model's own argmax is that token and
then takes the second row's outputs, so a step yields one or two tokens
of exactly the greedy sequence (``backends/decode_loop.py``).  A prefill
program runs the draft layer over every chunk with the ids shifted by
one (:func:`forward_cached_draft`), so the draft cache is whole when the
first step comes.  The draft layer's kernels run under their own names
in a device trace (``_mtp_mla_paged_flash``, ``_mtp_moe_experts``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import mla_paged_decode_attention
from .xing4 import (
    _swiglu,
    latent_row_width,
    mla_absorbed_output,
    mla_absorbed_query,
    mla_expanded_attention,
    mla_project,
    moe_ffn,
    rms_norm,
)

#: rows a slot feeds a decode step: the current token and one draft
DECODE_ROWS = 2
#: the draft layer's kernels in a device trace
DRAFT_ATTN_NAME = "_mtp_mla_paged_flash"
DRAFT_MOE_NAME = "_mtp_moe_experts"


@dataclass(frozen=True)
class Glm4LiteConfig:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    n_layers: int = 47
    n_dense_layers: int = 1              # first_k_dense_replace
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    rms_eps: float = 1e-5
    rope_theta: float = 1e6
    max_positions: int = 202752
    dtype: Any = jnp.bfloat16

    # what ``xing4``'s rotary embedding reads: at factor 1 its YaRN blend
    # is the plain frequencies and both attention scales are 1
    rope_factor = 1.0
    rope_beta_fast = 32.0
    rope_beta_slow = 1.0
    rope_mscale = 1.0
    rope_mscale_all_dim = 1.0

    @classmethod
    def tiny(cls, **kw) -> "Glm4LiteConfig":
        """Every mechanism at toy widths (CPU tests, the CLI preset)."""
        base = dict(
            vocab_size=256, hidden_size=32, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=8,
            experts_per_tok=2, rope_theta=1e4, max_positions=256,
            dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "Glm4LiteConfig":
        """From the published ``config.json``'s keys (``model_type``
        ``glm4_moe_lite``): one routing group, no rotary scaling, the
        whole rotary part rotated, and the one MTP module this family is
        stepped with."""
        if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing is not built")
        if c.get("rope_scaling") is not None:
            raise ValueError("rope scaling is not built for this family")
        if float(c.get("partial_rotary_factor", 1)) != 1:
            raise ValueError("a partly rotated rope part is not built")
        if int(c["num_nextn_predict_layers"]) != 1:
            raise ValueError(
                "this family is served with its one MTP module as the "
                "draft: num_nextn_predict_layers must be 1")
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            n_layers=int(c["num_hidden_layers"]),
            n_dense_layers=int(c["first_k_dense_replace"]),
            n_heads=int(c["num_attention_heads"]),
            q_lora_rank=int(c["q_lora_rank"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_head_dim=int(c["qk_nope_head_dim"]),
            qk_rope_head_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            intermediate_size=int(c["intermediate_size"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            n_routed_experts=int(c["n_routed_experts"]),
            n_shared_experts=int(c["n_shared_experts"]),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            rms_eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def rope_original_max(self) -> int:
        return self.max_positions

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: Glm4LiteConfig, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters (expert
    weights ``(E, 2I, h)`` / ``(E, I, h)``, as ``xing4``'s); ``layer``
    ``>= n_layers`` is the draft module's layer, an expert layer."""
    h, H, dt, f32 = cfg.hidden_size, cfg.n_heads, cfg.dtype, jnp.float32
    out = {
        "attn_norm_g": ((h,), dt),
        "q_a_w": ((h, cfg.q_lora_rank), dt),
        "q_norm_g": ((cfg.q_lora_rank,), dt),
        "q_b_w": ((cfg.q_lora_rank, H * cfg.qk_head_dim), dt),
        "kv_a_w": ((h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt),
        "kv_norm_g": ((cfg.kv_lora_rank,), dt),
        "kv_b_w": ((cfg.kv_lora_rank,
                    H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt),
        "o_w": ((H * cfg.v_head_dim, h), dt),
        "ffn_norm_g": ((h,), dt),
    }
    if cfg.is_dense(layer):
        out["mlp_gu_w"] = ((h, 2 * cfg.intermediate_size), dt)
        out["mlp_down_w"] = ((cfg.intermediate_size, h), dt)
    else:
        E, I = cfg.n_routed_experts, cfg.moe_intermediate_size
        Is = I * cfg.n_shared_experts
        out["router_w"] = ((h, E), f32)
        out["router_bias"] = ((E,), f32)
        out["exp_gu_w"] = ((E, 2 * I, h), dt)
        out["exp_down_w"] = ((E, I, h), dt)
        out["shared_gu_w"] = ((h, 2 * Is), dt)
        out["shared_down_w"] = ((Is, h), dt)
    return out


def draft_param_shapes(cfg: Glm4LiteConfig) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of the draft module's own parameters:
    its layer's, the two input norms, ``W_eh`` (embedding half first) and
    the norm before the shared head."""
    h, dt = cfg.hidden_size, cfg.dtype
    out = dict(layer_param_shapes(cfg, cfg.n_layers))
    out.update({"enorm_g": ((h,), dt), "hnorm_g": ((h,), dt),
                "eh_w": ((2 * h, h), dt), "norm_g": ((h,), dt)})
    return out


def param_shapes(cfg: Glm4LiteConfig) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    for k, v in draft_param_shapes(cfg).items():
        out[f"mtp_{k}"] = v
    return out


def init_params(cfg: Glm4LiteConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains, a
    small router bias."""
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


def layer_params(params, cfg: Glm4LiteConfig, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def draft_params(params, cfg: Glm4LiteConfig):
    """The draft module's view of the weights: its own under their local
    names, and the embedding, final norm and head it shares."""
    return {k: params[g] for k, g in draft_param_names(cfg).items()}


# -- one layer, prefill and decode ---------------------------------------------


def _ffn(p, x, cfg, dense: bool, live=None, impl=None, moe_name=None):
    if dense:
        return _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"]), None
    return moe_ffn(p, x, cfg, live=live, impl=impl, name=moe_name)


def _prefill_block(p, x, rows, pos0, cfg: Glm4LiteConfig, dense: bool,
                   impl=None):
    """One pre-norm layer over a chunk: ``x`` (b, T, h) at positions
    ``pos0 + t``; ``rows`` (b, cap, width) the sequences' cached rows.
    Returns the new residual and ``rows`` with the chunk's written."""
    b, T, h = x.shape
    positions = jnp.tile(pos0 + jnp.arange(T, dtype=jnp.int32), b)
    xf = x.reshape(b * T, h)
    q_nope, q_rope, row = mla_project(
        p, rms_norm(xf, p["attn_norm_g"], cfg.rms_eps), positions, cfg)
    new_rows = jax.lax.dynamic_update_slice_in_dim(
        rows, row.reshape(b, T, -1).astype(rows.dtype), pos0, axis=1)
    o = mla_expanded_attention(
        p, q_nope.reshape(b, T, cfg.n_heads, -1),
        q_rope.reshape(b, T, cfg.n_heads, -1), new_rows, pos0, cfg, impl)
    xf = xf + o.reshape(b * T, -1) @ p["o_w"]
    y, _ = _ffn(p, rms_norm(xf, p["ffn_norm_g"], cfg.rms_eps), cfg, dense,
                impl=impl)
    return (xf + y).reshape(b, T, h), new_rows


def _decode_block(p, x, lengths, live, cfg: Glm4LiteConfig, dense: bool,
                  impl=None, attn_name=None, moe_name=None):
    """One pre-norm layer of one decode step: ``x`` (S, R, h), ``R``
    consecutive rows a slot at positions ``lengths[s] + r``; absorbed MLA
    over the latent pool ``p["cache_c"]`` through ``p["page_table"]``,
    the heads of row ``r`` seeing positions ``<= lengths[s] + r`` (the
    step's rows attended before they are written: the pool write is the
    loop composer's).  ``live`` (S,) takes the slots that decode nothing
    out of the routing.  Returns ``(x', rows (S, R, width), moe stats or
    None)``."""
    S, R, h = x.shape
    H = cfg.n_heads
    positions = (lengths[:, None] + jnp.arange(R, dtype=lengths.dtype)
                 ).reshape(-1)
    xf = x.reshape(S * R, h)
    q_nope, q_rope, row = mla_project(
        p, rms_norm(xf, p["attn_norm_g"], cfg.rms_eps), positions, cfg)
    q = mla_absorbed_query(p, q_nope, q_rope, cfg)
    o_lat = mla_paged_decode_attention(
        q.reshape(S, R * H, -1), p["cache_c"], p["page_table"], lengths,
        cfg.kv_lora_rank, new_row=row.reshape(S, R, -1), impl=impl,
        q_rows=R, name=attn_name)
    xf = xf + mla_absorbed_output(p, o_lat.reshape(S * R, H, -1), cfg)
    live_rows = None if live is None else jnp.repeat(live, R)
    y, stats = _ffn(p, rms_norm(xf, p["ffn_norm_g"], cfg.rms_eps), cfg,
                    dense, live=live_rows, impl=impl, moe_name=moe_name)
    return (xf + y).reshape(S, R, h), row.reshape(S, R, -1), stats


def _norm_head(x, g, head_w, cfg):
    return jnp.dot(rms_norm(x, g, cfg.rms_eps), head_w,
                   preferred_element_type=jnp.float32)


def head(params, x, cfg: Glm4LiteConfig):
    """Final RMSNorm and the untied head: float32 logits."""
    return _norm_head(x, params["norm_f_g"], params["head_w"], cfg)


def _draft_input(p, h_main, nxt, cfg: Glm4LiteConfig):
    """``z = W_eh [RMSNorm_e(Emb(nxt)) ; RMSNorm_h(h_main)]`` — ``h_main``
    the main model's output AFTER its final RMSNorm, ``nxt`` the token
    after each position."""
    e = rms_norm(p["wte"][nxt], p["enorm_g"], cfg.rms_eps)
    hn = rms_norm(h_main, p["hnorm_g"], cfg.rms_eps)
    return jnp.concatenate([e, hn], axis=-1) @ p["eh_w"]


# -- what the paged builder and the engine call (models/__init__.py) -----------

EMBED_PARAMS = ("wte",)
HEAD_PARAMS = ("norm_f_g", "head_w")
#: the step's graph takes ``active`` (the slots that decode) as an input
#: and carries it on every edge as ``live``
DECODE_TAKES_LIVE = True


def layer_param_names(cfg: Glm4LiteConfig, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def draft_param_names(cfg: Glm4LiteConfig) -> Dict[str, str]:
    """Local -> global names of what the step's ``draft`` task reads: the
    draft module's own parameters and the three it shares."""
    out = {k: f"mtp_{k}" for k in draft_param_shapes(cfg)}
    out.update({k: k for k in EMBED_PARAMS + HEAD_PARAMS})
    return out


def cache_spec(cfg: Glm4LiteConfig):
    """One latent pool a main layer and one more, the last, for the draft
    module's layer: the same row, positions, pages and table."""
    from .kv_pages import CacheSpec

    spec = CacheSpec.uniform(
        "latent", cfg.n_layers + 1, (("c", (latent_row_width(cfg),)),),
        rank=cfg.kv_lora_rank)
    return dataclasses.replace(spec, draft_layers=1)


def decode_embed(p, ids, lengths, cfg: Glm4LiteConfig):
    """``ids`` (S, R): positions are the layers' rotary angles."""
    return p["wte"][ids]


def decode_layer(p, x, lengths, live, cfg: Glm4LiteConfig, layer: int,
                 impl=None):
    x, rows, stats = _decode_block(
        p, x, lengths, live, cfg, cfg.is_dense(layer), impl)
    return x, {"c": rows}, stats


def decode_head(p, x, cfg: Glm4LiteConfig):
    return head(p, x, cfg)


def draft_decode(p, x, logits, lengths, live, cfg: Glm4LiteConfig,
                 impl=None):
    """The draft module's half of a decode step: ``x`` (S, R, h) the main
    model's residual before its final norm, ``logits`` (S, R, V) its
    logits.  Row ``r`` takes ``(h_{L+r}, argmax logits_r)``, both rows
    are cached, and row ``r``'s output predicts the token two past
    position ``L + r``.  Returns ``{"draft_logits": (S, R, V) float32,
    "c_new": (S, R, width)}``."""
    y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = _draft_input(p, rms_norm(x, p["norm_f_g"], cfg.rms_eps), y, cfg)
    z, rows, _ = _decode_block(
        p, z, lengths, live, cfg, False, impl,
        attn_name=DRAFT_ATTN_NAME, moe_name=DRAFT_MOE_NAME)
    return {"draft_logits": _norm_head(z, p["norm_g"], p["head_w"], cfg),
            "c_new": rows}


def _weight_flops(shapes, picked: float, rows: int) -> float:
    return sum(2.0 * rows * math.prod(shape)
               * (picked if k.startswith("exp_") else 1.0)
               for k, (shape, _) in shapes.items() if len(shape) >= 2)


def _attention_flops(cfg, rows: int, capacity: int) -> float:
    return 2.0 * 2.0 * rows * cfg.n_heads * capacity * latent_row_width(cfg)


def decode_flops(cfg: Glm4LiteConfig, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step of
    :data:`DECODE_ROWS` rows a slot (a layer's weights streamed once;
    experts: the picked ones)."""
    N, h = slots * DECODE_ROWS, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    layers = [_weight_flops(layer_param_shapes(cfg, i), picked, N)
              + _attention_flops(cfg, N, capacity)
              for i in range(cfg.n_layers)]
    return 2.0 * N * h, layers, 2.0 * N * h * cfg.vocab_size


def draft_flops(cfg: Glm4LiteConfig, slots: int, capacity: int) -> float:
    """FLOPs of the step's ``draft`` task: its layer, ``W_eh`` and the
    shared head once more."""
    N = slots * DECODE_ROWS
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    return (_weight_flops(draft_param_shapes(cfg), picked, N)
            + _attention_flops(cfg, N, capacity)
            + 2.0 * N * cfg.hidden_size * cfg.vocab_size)


def init_cache(cfg: Glm4LiteConfig, batch: int, cap: int, dtype=None):
    """The dense cache the prefill contract takes: the main layers' rows
    and, last, the draft layer's."""
    return {"c": jnp.zeros(
        (cfg.n_layers + 1, batch, cap, latent_row_width(cfg)),
        dtype or cfg.dtype)}


def _prefill(params, ids, cache, pos_start, cfg, impl=None):
    """The main model over a chunk: its residual (b, T, h) and the main
    layers' rows."""
    x = params["wte"][ids]
    rows_out = []
    for i in range(cfg.n_layers):
        x, rows = _prefill_block(
            layer_params(params, cfg, i), x, cache["c"][i], pos_start, cfg,
            cfg.is_dense(i), impl)
        rows_out.append(rows)
    return x, rows_out


def forward_cached(params, ids, cache, pos_start, cfg: Glm4LiteConfig,
                   impl=None):
    """The MAIN model's cached forward: ``ids`` (b, T) at positions
    ``pos_start + t`` over ``cache`` ``{"c": (L + 1, b, cap, width)}``;
    returns ``(logits (b, T, V) float32, cache)`` — the draft layer's
    rows untouched."""
    x, rows = _prefill(params, ids, cache, pos_start, cfg, impl)
    return head(params, x, cfg), {"c": jnp.stack(rows + [cache["c"][-1]])}


def forward_cached_row(params, ids, cache, pos_start, cfg: Glm4LiteConfig,
                       row, impl=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V)."""
    x, rows = _prefill(params, ids, cache, pos_start, cfg, impl)
    last = jax.lax.dynamic_index_in_dim(x, row, 1, keepdims=False)
    return head(params, last, cfg), {
        "c": jnp.stack(rows + [cache["c"][-1]])}


def forward_cached_draft(params, ids, nxt, cache, pos_start,
                         cfg: Glm4LiteConfig, row, impl=None):
    """The prefill contract of a family stepped with its draft: the main
    model over the chunk ``ids`` (b, T) and the draft module over the
    same positions, position ``t`` taking ``(h_t, nxt[t])`` — ``nxt``
    (b, T) the ids shifted by one, **negative** where the token after a
    position is the one this call itself decides (the main model's
    argmax at ``row``).  Every layer's rows are written, the draft
    layer's among them.  Returns ``(logits (b, V), draft logits (b, V),
    cache)`` of chunk row ``row``: the next token's, and those of the
    token after it."""
    x, rows = _prefill(params, ids, cache, pos_start, cfg, impl)
    h_main = rms_norm(x, params["norm_f_g"], cfg.rms_eps)
    last = jax.lax.dynamic_index_in_dim(h_main, row, 1, keepdims=False)
    logits = jnp.dot(last, params["head_w"],
                     preferred_element_type=jnp.float32)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    nxt = jnp.where(nxt < 0, first[:, None], nxt)
    p = draft_params(params, cfg)
    z, drows = _prefill_block(
        p, _draft_input(p, h_main, nxt, cfg), cache["c"][cfg.n_layers],
        pos_start, cfg, False, impl)
    dlast = jax.lax.dynamic_index_in_dim(z, row, 1, keepdims=False)
    return (logits, _norm_head(dlast, p["norm_g"], p["head_w"], cfg),
            {"c": jnp.stack(rows + [drows])})


def forward(params, ids, cfg: Glm4LiteConfig, impl=None):
    """Main-model logits (b, T, V) of whole sequences."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]


def forward_draft(params, ids, cfg: Glm4LiteConfig, impl=None):
    """Draft logits (b, T - 1, V) of whole sequences, teacher-forced:
    position ``t`` takes ``(h_t, ids[t + 1])`` and predicts ``ids[t +
    2]`` (what the tests hold against the reference's draft module)."""
    b, T = ids.shape
    cache = init_cache(cfg, b, T)
    x, _ = _prefill(params, ids, cache, 0, cfg, impl)
    p = draft_params(params, cfg)
    h_main = rms_norm(x, params["norm_f_g"], cfg.rms_eps)
    z = _draft_input(p, h_main[:, :-1], ids[:, 1:], cfg)
    z, _ = _prefill_block(p, z, cache["c"][-1][:, :T - 1], 0, cfg, False,
                          impl)
    return _norm_head(z, p["norm_g"], p["head_w"], cfg)

"""Mixtral-style sparse MoE in pure JAX: third model family
(BASELINE.json config #4: "Mixtral-8x7B MoE DAG, expert nodes as tasks").

Architecture = Llama backbone (RMSNorm, RoPE, GQA — reused from
:mod:`.llama`) with the SwiGLU FFN replaced by a router + N experts with
top-k gating.  The reference never models MoE (its extractor is GPT-2-only,
reference ``test_gpt2.py:45-168``); this family exists because expert
placement is exactly the param-cache-locality problem the reference's MRU
scheduler targets: each expert is a large, independently placeable set of
weights used by a data-dependent subset of tokens.

TPU/XLA note on routing — two static-shape formulations, both first-class:

* **Dense dispatch** (task DAGs, EP sharding, the default oracle): every
  expert processes every token; its output is scaled by the (possibly
  zero) top-k gate weight.  Simple, exact, placement-friendly (each
  expert is one task) — but computes ``n_experts/top_k``x the useful
  FLOPs.  The FLOP *estimates* on expert tasks are scaled by
  ``top_k/n_experts`` (the useful work) while the dense cost appears in
  measured calibration — the gap is visible, not hidden.
* **Routed dispatch** (:func:`moe_routed`, ``forward(..., routed=True)``):
  capacity-factor token routing with static capacity buffers — each
  expert computes only its top-k-assigned tokens up to capacity
  ``C = ceil(top_k * tokens / n_experts * capacity_factor)``; tokens
  beyond an expert's capacity are DROPPED (their gate contribution is
  zero), the standard static-shape sparse-MoE trade (Switch/GShard
  semantics).  At ``capacity_factor = n_experts/top_k`` nothing can drop
  and routed output equals dense output exactly (the oracle test).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import llama as _llama

# the Llama backbone ops are the same module-level functions
rms_norm = _llama.rms_norm
embedding = _llama.embedding
gqa_attention = _llama.gqa_attention
residual_add = _llama.residual_add
lm_head = _llama.lm_head


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32_000
    max_seq_len: int = 8192
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14_336
    n_experts: int = 8
    top_k: int = 2
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MixtralConfig":
        """Mixtral-8x7B (46.7B total / ~12.9B active params)."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "MixtralConfig":
        """Test-sized: 2 layers, 4 experts, top-2 — CPU-fast, same topology."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("d_model", 64)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("ffn_hidden", 128)
        kw.setdefault("n_experts", 4)
        kw.setdefault("top_k", 2)
        kw.setdefault("rope_theta", 10_000.0)
        return cls(**kw)


# -- parameter init ---------------------------------------------------------

def init_params(config: MixtralConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Flat naming scheme shared with the DAG frontend: the Llama names plus
    ``l{i}_router`` and per-expert ``l{i}_e{e}_w_gate/w_up/w_down``."""
    std = 0.02
    d, dtype = config.d_model, config.dtype
    hd, nh, nkv = config.head_dim, config.n_heads, config.n_kv_heads
    f, E = config.ffn_hidden, config.n_experts
    params: Dict[str, jax.Array] = {}

    def normal(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(dtype)

    keys = iter(jax.random.split(key, 2 + config.n_layers * (5 + 3 * E)))
    params["tok_emb"] = normal(next(keys), (config.vocab_size, d))
    out_scale = std / math.sqrt(2 * config.n_layers)
    for i in range(config.n_layers):
        p = f"l{i}_"
        params[p + "attn_norm_g"] = jnp.ones((d,), dtype)
        params[p + "wq"] = normal(next(keys), (d, nh * hd))
        params[p + "wk"] = normal(next(keys), (d, nkv * hd))
        params[p + "wv"] = normal(next(keys), (d, nkv * hd))
        params[p + "wo"] = normal(next(keys), (nh * hd, d), out_scale)
        params[p + "ffn_norm_g"] = jnp.ones((d,), dtype)
        params[p + "router"] = normal(next(keys), (d, E))
        for e in range(E):
            q = f"{p}e{e}_"
            params[q + "w_gate"] = normal(next(keys), (d, f))
            params[q + "w_up"] = normal(next(keys), (d, f))
            params[q + "w_down"] = normal(next(keys), (f, d), out_scale)
    params["final_norm_g"] = jnp.ones((d,), dtype)
    params["lm_head"] = normal(next(keys), (d, config.vocab_size))
    return params


def param_shapes(config: MixtralConfig) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    shaped = jax.eval_shape(
        lambda k: init_params(config, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    return {k: (v.shape, v.dtype) for k, v in shaped.items()}


def num_params(config: MixtralConfig) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(config).values())


def num_active_params(config: MixtralConfig) -> int:
    """Params touched per token: everything except the (E - top_k)
    non-selected experts per layer."""
    per_expert = 3 * config.d_model * config.ffn_hidden
    inactive = (config.n_experts - config.top_k) * per_expert * config.n_layers
    return num_params(config) - inactive


# -- MoE ops (DAG task granularity) -----------------------------------------

def router_weights(x: jax.Array, w_router: jax.Array, top_k: int) -> jax.Array:
    """Top-k gate weights, dense layout: (B, T, E) with zeros off the top-k.

    Softmax is taken over the selected logits only (Mixtral semantics:
    renormalized top-k), in float32.  Static shapes: lax.top_k + one-hot
    scatter-free reconstruction.
    """
    logits = (x @ w_router).astype(jnp.float32)  # (B, T, E)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)  # (B, T, k)
    top_w = jax.nn.softmax(top_vals, axis=-1)  # (B, T, k)
    E = logits.shape[-1]
    onehot = jax.nn.one_hot(top_idx, E, dtype=top_w.dtype)  # (B, T, k, E)
    dense = jnp.einsum("btk,btke->bte", top_w, onehot)
    return dense.astype(x.dtype)


def expert_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    """One expert's SwiGLU over ALL tokens (dense static-shape MoE)."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_combine(weights: jax.Array, *expert_outs: jax.Array) -> jax.Array:
    """Sum of expert outputs scaled by their dense gate column."""
    out = jnp.zeros_like(expert_outs[0])
    for e, eo in enumerate(expert_outs):
        out = out + weights[..., e : e + 1] * eo
    return out


def _moe(block_params: Dict[str, jax.Array], x: jax.Array,
         config: MixtralConfig) -> jax.Array:
    """Router + dense experts + combine over UNPREFIXED param names — the
    single implementation of the MoE layer math; :func:`moe_block` and
    :func:`transformer_block` both delegate here so the DAG path and the
    remat oracle cannot drift."""
    w = router_weights(x, block_params["router"], config.top_k)
    outs = [
        expert_ffn(
            x,
            block_params[f"e{e}_w_gate"],
            block_params[f"e{e}_w_up"],
            block_params[f"e{e}_w_down"],
        )
        for e in range(config.n_experts)
    ]
    return moe_combine(w, *outs)


# -- routed dispatch primitives ----------------------------------------------
# The ONE implementation of the capacity-buffer routing math, shared by the
# whole-program path (moe_routed), the EP-sharded path
# (parallel/expert.moe_routed_stacked), and the task-graph frontend
# (frontend/moe_dag routed tasks) — three consumers, one source of truth,
# so a change to capacity/position/tie-breaking semantics cannot silently
# break the oracle equivalences the tests pin.


def moe_capacity(N: int, E: int, k: int, capacity_factor: float) -> int:
    """Static per-expert capacity: ``ceil(k*N/E * cf)`` clamped to [1, N]."""
    return min(N, max(1, math.ceil(k * N / E * capacity_factor)))


def route_topk(
    xf: jax.Array, w_router: jax.Array, k: int, C: int, out_dtype
) -> Dict[str, jax.Array]:
    """Static-shape top-k routing metadata over flat tokens ``xf (N, D)``.

    Returns ``{top_w (N, k), flat_e (N*k,), pos (N*k,), keep (N*k,)}``:
    renormalized gate weights, expert id per (token, slot) assignment,
    position within the expert's arrival order (clamped to C-1 when
    dropped), and the under-capacity mask.
    """
    E = w_router.shape[-1]
    logits = (xf @ w_router).astype(jnp.float32)  # (N, E)
    top_vals, top_idx = jax.lax.top_k(logits, k)  # (N, k)
    top_w = jax.nn.softmax(top_vals, axis=-1).astype(out_dtype)

    flat_e = top_idx.reshape(-1)  # (N*k,) expert per assignment
    # position of each assignment within its expert's arrival order
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (N*k, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    mypos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = mypos < C
    return {
        "top_w": top_w,
        "flat_e": flat_e,
        "pos": jnp.where(keep, mypos, C - 1),
        "keep": keep,
    }


def routed_dispatch(
    xf: jax.Array, route: Dict[str, jax.Array], E: int, C: int
) -> jax.Array:
    """Scatter kept assignments into the global ``(E, C, D)`` buffer."""
    N, D = xf.shape
    k = route["top_w"].shape[-1]
    tok_idx = jnp.repeat(jnp.arange(N), k)
    contrib = jnp.where(route["keep"][:, None], xf[tok_idx], 0)
    return jnp.zeros((E, C, D), xf.dtype).at[
        route["flat_e"], route["pos"]
    ].add(contrib)


def routed_expert_buffer(
    xf: jax.Array, route: Dict[str, jax.Array], expert: int, C: int
) -> jax.Array:
    """ONE expert's ``(C, D)`` capacity buffer — the task-graph form,
    where each expert task dispatches only its own tokens."""
    N, D = xf.shape
    k = route["top_w"].shape[-1]
    tok_idx = jnp.repeat(jnp.arange(N), k)
    mine = route["keep"] & (route["flat_e"] == expert)
    contrib = jnp.where(mine[:, None], xf[tok_idx], 0)
    return jnp.zeros((C, D), xf.dtype).at[route["pos"]].add(contrib)


def routed_collect(
    out_buf: jax.Array, route: Dict[str, jax.Array], N: int
) -> jax.Array:
    """Gather expert outputs ``(E, C, D)`` back to tokens ``(N, D)``,
    weighted by the renormalized gates; dropped assignments contribute 0."""
    D = out_buf.shape[-1]
    k = route["top_w"].shape[-1]
    gathered = out_buf[route["flat_e"], route["pos"]]  # (N*k, D)
    gathered = jnp.where(route["keep"][:, None], gathered, 0)
    tok_idx = jnp.repeat(jnp.arange(N), k)
    w_flat = route["top_w"].reshape(-1, 1)
    return jnp.zeros((N, D), out_buf.dtype).at[tok_idx].add(
        gathered * w_flat
    )


def route_stats(route: Dict[str, jax.Array], C: int) -> Dict[str, Any]:
    return {
        "capacity": C,
        "dropped_slots": jnp.sum(~route["keep"]),
        "total_slots": route["flat_e"].shape[0],
    }


def moe_routed(
    block_params: Dict[str, jax.Array],
    x: jax.Array,
    config: MixtralConfig,
    capacity_factor: float = 2.0,
    with_stats: bool = False,
):
    """Sparse top-k dispatch with static-shape capacity buffers.

    Every shape is static (XLA-compilable): per-expert position comes
    from a cumulative sum over the flattened (token, slot) assignment
    order, tokens land in an ``(E, C, D)`` buffer via scatter-add (each
    kept assignment owns a unique (expert, position) cell), experts run
    as ONE batched einsum over stacked weights, and outputs gather back
    weighted by the renormalized top-k gates.  Assignments whose expert
    is over capacity are dropped — their contribution is zero, exactly
    the Switch/GShard trade disclosed in the module docstring.  FLOPs
    scale with ``top_k/n_experts`` (+capacity slack) instead of running
    every expert on every token.

    Returns ``out`` or ``(out, stats)`` with ``stats = {capacity,
    dropped_slots, total_slots}`` when ``with_stats``.
    """
    B, T, D = x.shape
    E, k = config.n_experts, config.top_k
    N = B * T
    C = moe_capacity(N, E, k, capacity_factor)
    xf = x.reshape(N, D)

    route = route_topk(xf, block_params["router"], k, C, x.dtype)
    buf = routed_dispatch(xf, route, E, C)

    wg = jnp.stack([block_params[f"e{e}_w_gate"] for e in range(E)])
    wu = jnp.stack([block_params[f"e{e}_w_up"] for e in range(E)])
    wd = jnp.stack([block_params[f"e{e}_w_down"] for e in range(E)])
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)  # (E, C, D)

    out = routed_collect(out_buf, route, N).reshape(B, T, D)
    if with_stats:
        return out, route_stats(route, C)
    return out


def routed_transformer_block(
    block_params: Dict[str, jax.Array],
    x: jax.Array,
    config: MixtralConfig,
    capacity_factor: float = 2.0,
) -> jax.Array:
    """:func:`transformer_block` with the routed (capacity-buffer) MoE in
    place of dense dispatch — identical attention path (shared via
    :func:`_block_with_moe`), same param layout."""
    return _block_with_moe(
        block_params, x, config,
        lambda bp, h: moe_routed(bp, h, config, capacity_factor),
    )


def moe_block(params: Dict[str, jax.Array], x: jax.Array, layer: int,
              config: MixtralConfig) -> jax.Array:
    """Router + dense experts + combine, as the fused oracle composes it
    (layer-prefixed params; delegates to :func:`_moe`)."""
    p = f"l{layer}_"
    moe_keys = ["router"] + [
        f"e{e}_{s}"
        for e in range(config.n_experts)
        for s in ("w_gate", "w_up", "w_down")
    ]
    return _moe({k: params[p + k] for k in moe_keys}, x, config)


# -- whole-model forward (fused baseline + correctness oracle) --------------

def _layer_keys(config: MixtralConfig) -> Tuple[str, ...]:
    """Unprefixed per-layer param names (the remat block's vocabulary)."""
    keys = ["attn_norm_g", "wq", "wk", "wv", "wo", "ffn_norm_g", "router"]
    for e in range(config.n_experts):
        keys += [f"e{e}_w_gate", f"e{e}_w_up", f"e{e}_w_down"]
    return tuple(keys)


def _block_with_moe(
    block_params: Dict[str, jax.Array],
    x: jax.Array,
    config: MixtralConfig,
    moe_fn,
) -> jax.Array:
    """The one attention+residual block body, parameterized by the MoE
    dispatch (dense :func:`_moe` or :func:`moe_routed`) so the two block
    variants cannot drift apart on the attention path."""
    h = rms_norm(x, block_params["attn_norm_g"], config.rms_eps)
    h = gqa_attention(
        h, block_params["wq"], block_params["wk"], block_params["wv"],
        block_params["wo"], config.n_heads, config.n_kv_heads,
        config.rope_theta,
    )
    x = residual_add(x, h)
    h = rms_norm(x, block_params["ffn_norm_g"], config.rms_eps)
    return residual_add(x, moe_fn(block_params, h))


def transformer_block(
    block_params: Dict[str, jax.Array], x: jax.Array, config: MixtralConfig
) -> jax.Array:
    """One layer (RMSNorm + GQA + router/experts/combine with residuals),
    params keyed unprefixed — the rematerialization unit.  Same math as
    the prefixed :func:`moe_block` path."""
    return _block_with_moe(
        block_params, x, config, lambda bp, h: _moe(bp, h, config)
    )


def forward_with_block(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: MixtralConfig,
    block_fn: Any,
    layer_keys: Tuple[str, ...],
    remat: bool = False,
    scan: bool = False,
) -> jax.Array:
    """Mixtral's forward skeleton IS the Llama backbone's
    (:func:`..llama.backbone_forward`): embed -> n_layers x block -> final
    norm -> LM head, parameterized by the layer block so the per-expert
    path (:func:`forward`), the stacked EP path
    (``parallel/expert.forward_ep``), and the scanned variants all share
    one implementation."""
    return _llama.backbone_forward(
        params, input_ids, config, block_fn, layer_keys,
        remat=remat, scan=scan,
    )


def nll_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Next-token cross-entropy in float32 (shared by both MoE paths)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def forward(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: MixtralConfig,
    remat: bool = False,
    routed: bool = False,
    capacity_factor: float = 2.0,
) -> jax.Array:
    """``remat=True`` checkpoints each block — especially valuable for MoE,
    whose dense-dispatch expert activations are ``n_experts`` times the
    dense model's.  ``routed=True`` switches every layer's MoE to the
    capacity-buffer sparse dispatch (:func:`moe_routed`) — top_k/n_experts
    the FLOPs, with the disclosed capacity-drop semantics."""
    if routed:
        import functools

        # keyword-frozen capacity keeps the (params, x, config) contract
        block = functools.partial(
            routed_transformer_block, capacity_factor=capacity_factor
        )
    else:
        block = transformer_block
    return forward_with_block(
        params, input_ids, config, block, _layer_keys(config),
        remat=remat,
    )


def stack_layer_params(
    params: Dict[str, jax.Array], config: MixtralConfig
) -> Dict[str, jax.Array]:
    """Scanned-forward layout via the shared :func:`..llama.stack_layers`;
    per-expert tensors stack to (n_layers, d, f) per expert key."""
    return _llama.stack_layers(params, config.n_layers, _layer_keys(config))


def forward_scan(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: MixtralConfig,
    remat: bool = False,
) -> jax.Array:
    """Forward over stacked layer params via ``lax.scan`` — one compiled
    block regardless of depth.  Matches :func:`forward` numerically."""
    return forward_with_block(
        params, input_ids, config, transformer_block, _layer_keys(config),
        remat=remat, scan=True,
    )


# -- KV-cache decoding (models/decode.py drives this) ------------------------

def init_cache(config: MixtralConfig, batch: int, max_len: int):
    from . import decode

    return decode.init_cache(
        config.n_layers, batch, config.n_kv_heads, max_len,
        config.head_dim, config.dtype,
    )


def forward_cached(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    cache,
    pos_start,
    config: MixtralConfig,
) -> Tuple[jax.Array, Any]:
    """Cached forward over positions [pos_start, pos_start + T).  Attention
    is the shared Llama-backbone cached path; the FFN is the same
    router/experts/combine math as :func:`transformer_block` — routing is
    per-token, so decode steps route each new token independently, exactly
    as the fused forward would."""
    pos_start = jnp.asarray(pos_start, jnp.int32)
    keys = _layer_keys(config)
    x = _llama.embedding(input_ids, params["tok_emb"])
    for i in range(config.n_layers):
        p = f"l{i}_"
        bp = {k: params[p + k] for k in keys}
        h = rms_norm(x, bp["attn_norm_g"], config.rms_eps)
        h, cache = _llama.attention_cached(h, bp, cache, i, pos_start, config)
        x = residual_add(x, h)
        h = rms_norm(x, bp["ffn_norm_g"], config.rms_eps)
        x = residual_add(x, _moe(bp, h, config))
    x = rms_norm(x, params["final_norm_g"], config.rms_eps)
    return _llama.lm_head(x, params["lm_head"]), cache


# -- what the decode-step DAG builder calls (models/__init__.py): the
# Llama backbone's, with the experts for an FFN --------------------------------

PARAM_RULES = _llama.PARAM_RULES
EMBED_PARAMS = _llama.EMBED_PARAMS
HEAD_PARAMS = _llama.HEAD_PARAMS
cache_spec = _llama.cache_spec
embed = _llama.embed
head = _llama.head
cached_embed = _llama.cached_embed


def layer_param_names(config: MixtralConfig, layer: int) -> Dict[str, str]:
    return {k: f"l{layer}_{k}" for k in _layer_keys(config)}


def cached_layer(p, x, pos, config: MixtralConfig, layer: int):
    """Router + dense experts per step: routing is per token, exactly
    as the fused cached forward does."""
    return _llama.cached_layer(
        p, x, pos, config, layer, ffn=lambda h: _moe(p, h, config))


def cached_flops(config: MixtralConfig, batch: int, step_len: int,
                 max_len: int):
    # router + DENSE per-step expert sweep (every expert runs every
    # token — the disclosed dense-dispatch cost)
    tokens_d = 2.0 * batch * step_len * config.d_model
    return _llama.cached_flops(
        config, batch, step_len, max_len,
        ffn_flops=(tokens_d * config.n_experts
                   + config.n_experts * 3 * tokens_d * config.ffn_hidden))


def generate(
    params: Dict[str, jax.Array],
    prompt_ids: jax.Array,
    config: MixtralConfig,
    max_new_tokens: int,
    **kw,
) -> jax.Array:
    from . import decode

    return decode.generate(
        forward_cached, init_cache, params, prompt_ids, config,
        max_new_tokens, **kw,
    )


def loss_fn(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    targets: jax.Array,
    config: MixtralConfig,
    remat: bool = False,
    scan: bool = False,
    routed: bool = False,
) -> jax.Array:
    if routed:
        if scan:
            raise ValueError(
                "routed MoE is per-layer (stacked-expert einsums inside "
                "the block); use scan=False"
            )
        return nll_loss(
            forward(params, input_ids, config, remat=remat, routed=True),
            targets,
        )
    fwd = forward_scan if scan else forward
    return nll_loss(fwd(params, input_ids, config, remat=remat), targets)

"""Llama-3 in pure JAX: second model family (BASELINE.json config #3).

Same design as :mod:`.gpt2` — functional, flat ``Dict[str, jax.Array]``
params whose names are shared with the DAG frontend's ``params_needed``
vocabulary — but the Llama architecture: RMSNorm (no biases), rotary
position embeddings (no learned position table), grouped-query attention
(n_kv_heads < n_heads), SwiGLU FFN, untied LM head.

The reference never models Llama (its extractor is GPT-2-only, reference
``test_gpt2.py:45-168``); this family exists because the rebuild's baseline
configs call for "Llama-3 8B layer-wise DAG, pipeline-stage scheduling
across v5e-16".  Per-op functions are individually jittable so the DAG
frontend (``frontend/llama_dag.py``) wraps them as task fns; ``forward``
is the fused oracle.

TPU notes: all matmuls run in the model dtype (bfloat16 on TPU) for the
MXU; RMSNorm and softmax accumulate in float32.  RoPE tables are computed
inside the jitted fn from static shapes — XLA constant-folds them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import gqa_mha as _fused_gqa

# Megatron split of the Llama backbone (llama + mixtral; parallel/
# sharding.py reads it): GQA attention and the SwiGLU / expert FFNs.  KV
# projections are column-sharded over tp, so tp must divide n_kv_heads for
# an even head split (LlamaConfig defaults: 8 kv heads).  The expert
# suffixes (``e{j}_w_gate`` etc.) match the same FFN rules — dense-dispatch
# experts tensor-parallelize exactly like the dense FFN.  ``lm_head``
# (d, vocab) column-shards when tp divides the vocab (128256 = 8 x 16032);
# ``tok_emb`` stays replicated (row-sharded gathers cost an all-gather per
# lookup for ~1 GB saved — the wrong trade at decode time).
PARAM_RULES = [
    (r"tok_emb$", P()),
    (r"(wq|wk|wv)$", P(None, "tp")),     # column: heads split over tp
    (r"wo$", P("tp", None)),             # row: output partial-summed
    (r"(w_gate|w_up)$", P(None, "tp")),
    (r"w_down$", P("tp", None)),
    (r"router$", P()),
    (r"lm_head$", P(None, "tp")),
    (r".*_g$", P()),                     # RMSNorm gains replicated
    (r".*", P()),
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    max_seq_len: int = 8192
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14_336
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        """Llama-3 8B (8.03B params): the config #3 target."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized: 2 layers, 128 wide, GQA 4:2 — CPU-fast, same topology."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("d_model", 128)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("ffn_hidden", 256)
        kw.setdefault("rope_theta", 10_000.0)
        return cls(**kw)


# -- parameter init ---------------------------------------------------------

def init_params(config: LlamaConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Flat naming scheme shared with the DAG frontend:
    ``tok_emb, l{i}_attn_norm_g, l{i}_wq/wk/wv/wo, l{i}_ffn_norm_g,
    l{i}_w_gate/w_up/w_down, final_norm_g, lm_head``."""
    std = 0.02
    d, dtype = config.d_model, config.dtype
    hd, nh, nkv = config.head_dim, config.n_heads, config.n_kv_heads
    f = config.ffn_hidden
    params: Dict[str, jax.Array] = {}

    def normal(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(dtype)

    keys = iter(jax.random.split(key, 2 + config.n_layers * 7))
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
        params[p + "w_gate"] = normal(next(keys), (d, f))
        params[p + "w_up"] = normal(next(keys), (d, f))
        params[p + "w_down"] = normal(next(keys), (f, d), out_scale)
    params["final_norm_g"] = jnp.ones((d,), dtype)
    params["lm_head"] = normal(next(keys), (d, config.vocab_size))
    return params


def param_shapes(config: LlamaConfig) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    shaped = jax.eval_shape(
        lambda k: init_params(config, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    return {k: (v.shape, v.dtype) for k, v in shaped.items()}


def num_params(config: LlamaConfig) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(config).values())


# -- per-op functions (DAG task granularity) --------------------------------

def rms_norm(x: jax.Array, g: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (xf * scale * g.astype(jnp.float32)).astype(x.dtype)


def embedding(input_ids: jax.Array, tok_emb: jax.Array) -> jax.Array:
    return tok_emb[input_ids]


def rope_tables(T: int, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) of shape (T, head_dim//2), float32.  Static-shape; XLA
    constant-folds these when they appear inside a jitted task fn."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponents)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, H, T, hd) with interleaved (even, odd) rotation pairs."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf1 * sin + xf2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def gqa_attention(
    x: jax.Array,
    wq: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    wo: jax.Array,
    n_heads: int,
    n_kv_heads: int,
    rope_theta: float,
) -> jax.Array:
    """Causal grouped-query attention with RoPE, incl. output projection —
    one task, matching the per-layer "attention" granularity of the GPT-2
    DAG (reference test_gpt2.py:75-90 puts qkv+proj on a single task)."""
    B, T, D = x.shape
    hd = wq.shape[-1] // n_heads

    q = (x @ wq).reshape(B, T, n_heads, hd).transpose(0, 2, 1, 3)
    k = (x @ wk).reshape(B, T, n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (x @ wv).reshape(B, T, n_kv_heads, hd).transpose(0, 2, 1, 3)

    cos, sin = rope_tables(T, hd, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    # fused flash-attention kernel on TPU (KV heads broadcast across their
    # query group inside gqa_mha), plain-XLA path elsewhere (ops/)
    out = _fused_gqa(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
    return out @ wo


def ffn_gate(x: jax.Array, w_gate: jax.Array) -> jax.Array:
    return x @ w_gate


def ffn_up(x: jax.Array, w_up: jax.Array) -> jax.Array:
    return x @ w_up


def ffn_glu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def ffn_down(x: jax.Array, w_down: jax.Array) -> jax.Array:
    return x @ w_down


def residual_add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def lm_head(x: jax.Array, w: jax.Array) -> jax.Array:
    return x @ w


# -- whole-model forward (fused baseline + correctness oracle) --------------

def transformer_block(
    block_params: Dict[str, jax.Array], x: jax.Array, config: LlamaConfig
) -> jax.Array:
    """One layer (RMSNorm + GQA + SwiGLU with residuals), params keyed by
    the unprefixed names — the rematerialization unit."""
    h = rms_norm(x, block_params["attn_norm_g"], config.rms_eps)
    h = gqa_attention(
        h, block_params["wq"], block_params["wk"], block_params["wv"],
        block_params["wo"], config.n_heads, config.n_kv_heads,
        config.rope_theta,
    )
    x = residual_add(x, h)
    h = rms_norm(x, block_params["ffn_norm_g"], config.rms_eps)
    g = ffn_gate(h, block_params["w_gate"])
    u = ffn_up(h, block_params["w_up"])
    h = ffn_down(ffn_glu(g, u), block_params["w_down"])
    return residual_add(x, h)


_BLOCK_KEYS = (
    "attn_norm_g", "wq", "wk", "wv", "wo", "ffn_norm_g",
    "w_gate", "w_up", "w_down",
)


def forward(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: LlamaConfig,
    remat: bool = False,
) -> jax.Array:
    """``remat=True`` checkpoints each block (HBM for FLOPs), as in
    :func:`..gpt2.forward`."""
    return backbone_forward(
        params, input_ids, config, transformer_block, _BLOCK_KEYS,
        remat=remat,
    )


_LAYER_PREFIX_RE = None  # compiled lazily (module import stays light)


def stack_layers(
    params: Dict[str, jax.Array], n_layers: int, keys: Tuple[str, ...]
) -> Dict[str, jax.Array]:
    """Per-layer ``l{i}_*`` tensors -> stacked ``layers_*`` with a leading
    layer dim (non-layer params unchanged) — the scanned-forward layout.
    Shared by the Llama-backbone families (Mixtral reuses it)."""
    import re

    global _LAYER_PREFIX_RE
    if _LAYER_PREFIX_RE is None:
        _LAYER_PREFIX_RE = re.compile(r"^l\d+_")
    out = {k: v for k, v in params.items() if not _LAYER_PREFIX_RE.match(k)}
    for key in keys:
        out["layers_" + key] = jnp.stack(
            [params[f"l{i}_{key}"] for i in range(n_layers)]
        )
    return out


def backbone_forward(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: Any,
    block_fn: Any,
    layer_keys: Tuple[str, ...],
    remat: bool = False,
    scan: bool = False,
) -> jax.Array:
    """The one Llama-backbone forward skeleton: embed -> n_layers x block
    -> final RMSNorm -> LM head.  Parameterized by the layer block so
    Llama, Mixtral (per-expert AND stacked-EP layouts), and their scanned
    variants all share it instead of drifting.  ``scan=True`` expects
    stacked ``layers_*`` params (:func:`stack_layers`) and runs the block
    under ``lax.scan`` — traced/compiled once regardless of depth;
    ``remat=True`` checkpoints the block either way.
    """
    block = (
        jax.checkpoint(block_fn, static_argnums=(2,)) if remat else block_fn
    )
    x = embedding(input_ids, params["tok_emb"])
    if scan:
        stacked = {k: params["layers_" + k] for k in layer_keys}

        def step(h, layer_params):
            return block(layer_params, h, config), None

        x, _ = jax.lax.scan(step, x, stacked)
    else:
        for i in range(config.n_layers):
            p = f"l{i}_"
            x = block({k: params[p + k] for k in layer_keys}, x, config)
    x = rms_norm(x, params["final_norm_g"], config.rms_eps)
    return lm_head(x, params["lm_head"])


def stack_layer_params(
    params: Dict[str, jax.Array], config: LlamaConfig
) -> Dict[str, jax.Array]:
    return stack_layers(params, config.n_layers, _BLOCK_KEYS)


def forward_scan(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: LlamaConfig,
    remat: bool = False,
) -> jax.Array:
    """Forward over stacked layer params (cf. :func:`..gpt2.forward_scan`);
    matches :func:`forward` numerically."""
    return backbone_forward(
        params, input_ids, config, transformer_block, _BLOCK_KEYS,
        remat=remat, scan=True,
    )


# -- KV-cache decoding (models/decode.py drives this) ------------------------

def init_cache(config: LlamaConfig, batch: int, max_len: int):
    from . import decode

    return decode.init_cache(
        config.n_layers, batch, config.n_kv_heads, max_len,
        config.head_dim, config.dtype,
    )


def attention_cached(
    x: jax.Array,
    block_params: Dict[str, jax.Array],
    cache,
    layer: int,
    pos_start,
    config: Any,
):
    """GQA with RoPE at absolute positions [pos_start, pos_start+T), reading
    and writing the stacked-layer KV cache.  Shared with Mixtral (same
    Llama-backbone attention, reference-free — the reference has no
    attention math at all)."""
    from . import decode

    B, T, D = x.shape
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim

    q = (x @ block_params["wq"]).reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
    k = (x @ block_params["wk"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
    v = (x @ block_params["wv"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)

    # RoPE at absolute positions: tables for the full cache length (static),
    # sliced at the (possibly traced) write cursor
    M = cache["k"].shape[3]
    cos_all, sin_all = rope_tables(M, hd, config.rope_theta)
    cos = jax.lax.dynamic_slice_in_dim(cos_all, pos_start, T, axis=0)
    sin = jax.lax.dynamic_slice_in_dim(sin_all, pos_start, T, axis=0)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    cache = decode.update_layer_cache(cache, layer, k, v, pos_start)
    kc, vc, ks, vs = decode.layer_view(cache, layer)
    out = decode.cached_attention(
        q, kc, vc, pos_start, 1.0 / math.sqrt(hd),
        k_scale=ks, v_scale=vs,
    )
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
    return out @ block_params["wo"], cache


def forward_cached(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    cache,
    pos_start,
    config: LlamaConfig,
) -> Tuple[jax.Array, Any]:
    """Cached forward over positions [pos_start, pos_start + T); one code
    path for prefill and decode (cf. :func:`..gpt2.forward_cached`)."""
    pos_start = jnp.asarray(pos_start, jnp.int32)
    x = embedding(input_ids, params["tok_emb"])
    for i in range(config.n_layers):
        p = f"l{i}_"
        bp = {k: params[p + k] for k in _BLOCK_KEYS}
        h = rms_norm(x, bp["attn_norm_g"], config.rms_eps)
        h, cache = attention_cached(h, bp, cache, i, pos_start, config)
        x = residual_add(x, h)
        h = rms_norm(x, bp["ffn_norm_g"], config.rms_eps)
        g = ffn_gate(h, bp["w_gate"])
        u = ffn_up(h, bp["w_up"])
        h = ffn_down(ffn_glu(g, u), bp["w_down"])
        x = residual_add(x, h)
    x = rms_norm(x, params["final_norm_g"], config.rms_eps)
    return lm_head(x, params["lm_head"]), cache


# -- what the decode-step DAG builder calls (models/__init__.py); Mixtral
# shares all of it but the FFN ----------------------------------------------

EMBED_PARAMS = ("tok_emb",)
HEAD_PARAMS = ("final_norm_g", "lm_head")


def layer_param_names(config: LlamaConfig, layer: int) -> Dict[str, str]:
    return {k: f"l{layer}_{k}" for k in _BLOCK_KEYS}


def cache_spec(config: Any):
    from .kv_pages import CacheSpec

    row = (config.n_kv_heads, config.head_dim)
    return CacheSpec.uniform("kv", config.n_layers, (("k", row), ("v", row)),
                             q_heads=config.n_heads)


def embed(p, ids, config: Any):
    return embedding(ids, p["tok_emb"])


def head(p, x, config: Any):
    return lm_head(rms_norm(x, p["final_norm_g"], config.rms_eps),
                   p["lm_head"])


def cached_embed(p, ids, pos, config: Any):
    return embed(p, ids, config)


def cached_layer(p, x, pos, config: Any, layer: int, ffn=None):
    """One layer of a cached step over the dense GQA slabs ``cache_k`` /
    ``cache_v`` (b, n_kv_heads, max_len, hd): RoPE dynamic-sliced at the
    traced ``pos``, attention over [0, pos + T).  ``ffn(h)`` replaces the
    SwiGLU FFN (Mixtral's experts).  Returns ``(x, {"k": ..., "v": ...})``."""
    from . import decode

    B, T, _ = x.shape
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    h = rms_norm(x, p["attn_norm_g"], config.rms_eps)
    q = (h @ p["wq"]).reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
    k = (h @ p["wk"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
    v = (h @ p["wv"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
    cos_all, sin_all = rope_tables(
        p["cache_k"].shape[2], hd, config.rope_theta)
    cos = jax.lax.dynamic_slice(cos_all, (pos, 0), (T, hd // 2))
    sin = jax.lax.dynamic_slice(sin_all, (pos, 0), (T, hd // 2))
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice(
        p["cache_k"], k.astype(p["cache_k"].dtype),
        (jnp.int32(0), jnp.int32(0), pos, jnp.int32(0)))
    v_cache = jax.lax.dynamic_update_slice(
        p["cache_v"], v.astype(p["cache_v"].dtype),
        (jnp.int32(0), jnp.int32(0), pos, jnp.int32(0)))
    att = decode.cached_attention(
        q, k_cache, v_cache, pos, 1.0 / math.sqrt(hd))
    x = x + att.transpose(0, 2, 1, 3).reshape(B, T, nh * hd) @ p["wo"]
    h2 = rms_norm(x, p["ffn_norm_g"], config.rms_eps)
    if ffn is None:
        out = ffn_down(
            ffn_glu(ffn_gate(h2, p["w_gate"]), ffn_up(h2, p["w_up"])),
            p["w_down"])
    else:
        out = ffn(h2)
    return x + out, {"k": k, "v": v}


def cached_flops(config: Any, batch: int, step_len: int, max_len: int,
                 ffn_flops=None):
    """``(embed, [a layer's ...], head)`` FLOPs of one cached step;
    attention scans the full masked cache, O(max_len) at any position."""
    B, T, M, D = batch, step_len, max_len, config.d_model
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    if ffn_flops is None:  # gate, up, down matmuls
        ffn_flops = 3 * 2.0 * B * T * D * config.ffn_hidden
    layer = (
        2.0 * B * T * D * (nh + 2 * nkv) * hd
        + 2.0 * 2.0 * B * nh * T * M * hd
        + 2.0 * B * T * nh * hd * D
        + ffn_flops
    )
    return (2.0 * B * T * D, [layer] * config.n_layers,
            2.0 * B * T * D * config.vocab_size)


def generate(
    params: Dict[str, jax.Array],
    prompt_ids: jax.Array,
    config: LlamaConfig,
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
    config: LlamaConfig,
    remat: bool = False,
    scan: bool = False,
) -> jax.Array:
    fwd = forward_scan if scan else forward
    logits = fwd(params, input_ids, config, remat=remat)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()

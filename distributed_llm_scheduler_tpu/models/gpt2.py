"""GPT-2 in pure JAX: the flagship model family.

A from-scratch functional implementation (no flax/haiku): params are a flat
``Dict[str, jax.Array]`` keyed by the same names the DAG frontend uses for
its tasks' ``params_needed`` sets, so scheduler placement and real execution
share one vocabulary.  The reference extracts model *structure* from
HuggingFace GPT2Model with random weights (reference ``test_gpt2.py:45-48``);
here the model is ours, so structure, weights, and per-op functions all come
from the same place.

Every per-op function (`layer_norm`, `attention`, `ffn_*`, …) is
individually jittable — the DAG frontend wraps them as task fns — and
`forward` composes them into the whole-model forward used as the fused
single-program baseline and the correctness oracle for DAG execution.

TPU notes: matmul-heavy ops run in the model dtype (bfloat16 by default on
TPU) to hit the MXU; layer norms accumulate in float32 for stability.
Static shapes everywhere; causal masking via `jnp.where` on an affine
index grid (no dynamic slicing), so XLA tiles cleanly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import paged_decode_attention
from ..ops.flash_rows import mha_rows as _mha_rows

# Megatron split (parallel/sharding.py reads it): parameter-name pattern
# -> PartitionSpec, checked in order
PARAM_RULES = [
    # embedding table replicated: GPT-2's vocab (50257) is not divisible by
    # any tp, and NamedSharding requires even splits.  Memory-sharding the
    # table needs vocab padding to a tp multiple first — future work.
    (r"wte$", P()),
    (r"wpe$", P()),                      # positions replicated
    (r"attn_qkv_w$", P(None, "tp")),
    (r"attn_qkv_b$", P("tp")),
    (r"attn_proj_w$", P("tp", None)),
    (r"attn_proj_b$", P()),
    (r"mlp_fc_w$", P(None, "tp")),
    (r"mlp_fc_b$", P("tp")),
    (r"mlp_proj_w$", P("tp", None)),
    (r"mlp_proj_b$", P()),
    (r"ln.*_[gb]$", P()),
    (r".*", P()),                        # anything else: replicated
]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dtype: Any = jnp.float32
    ln_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        """124M — the reference's extraction target (test_gpt2.py:47)."""
        return cls(**kw)

    @classmethod
    def medium(cls, **kw) -> "GPT2Config":
        """355M (BASELINE.json config #2)."""
        return cls(n_embd=1024, n_layer=24, n_head=16, **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        """Test-sized: 2 layers, 128 wide — CPU-fast, same topology."""
        return cls(
            vocab_size=512, n_positions=128, n_embd=128, n_layer=2, n_head=4, **kw
        )


# -- parameter init --------------------------------------------------------

def init_params(config: GPT2Config, key: jax.Array) -> Dict[str, jax.Array]:
    """GPT-2 initialization: N(0, 0.02) weights, zero biases, unit LN gains.

    Flat naming scheme shared with the DAG frontend:
    ``wte, wpe, ln_f_g, ln_f_b, h{i}_ln1_g, h{i}_attn_qkv_w, ...``
    """
    std = 0.02
    d, dtype = config.n_embd, config.dtype
    params: Dict[str, jax.Array] = {}

    def normal(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(dtype)

    n_keys = 2 + config.n_layer * 4
    keys = iter(jax.random.split(key, n_keys))

    params["wte"] = normal(next(keys), (config.vocab_size, d))
    params["wpe"] = normal(next(keys), (config.n_positions, d))
    for i in range(config.n_layer):
        p = f"h{i}_"
        params[p + "ln1_g"] = jnp.ones((d,), dtype)
        params[p + "ln1_b"] = jnp.zeros((d,), dtype)
        params[p + "attn_qkv_w"] = normal(next(keys), (d, 3 * d))
        params[p + "attn_qkv_b"] = jnp.zeros((3 * d,), dtype)
        # residual-branch projections scaled down by sqrt(2*n_layer), as GPT-2
        params[p + "attn_proj_w"] = normal(
            next(keys), (d, d), std / math.sqrt(2 * config.n_layer)
        )
        params[p + "attn_proj_b"] = jnp.zeros((d,), dtype)
        params[p + "ln2_g"] = jnp.ones((d,), dtype)
        params[p + "ln2_b"] = jnp.zeros((d,), dtype)
        params[p + "mlp_fc_w"] = normal(next(keys), (d, 4 * d))
        params[p + "mlp_fc_b"] = jnp.zeros((4 * d,), dtype)
        params[p + "mlp_proj_w"] = normal(
            next(keys), (4 * d, d), std / math.sqrt(2 * config.n_layer)
        )
        params[p + "mlp_proj_b"] = jnp.zeros((d,), dtype)
    params["ln_f_g"] = jnp.ones((d,), dtype)
    params["ln_f_b"] = jnp.zeros((d,), dtype)
    return params


def param_shapes(config: GPT2Config) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """(shape, dtype) per param without materializing arrays (eval_shape)."""
    shaped = jax.eval_shape(
        lambda k: init_params(config, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    return {k: (v.shape, v.dtype) for k, v in shaped.items()}


# -- per-op functions (task granularity of the reference DAG) ---------------

def layer_norm(
    x: jax.Array, g: jax.Array, b: jax.Array, eps: float = 1e-5
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def embedding(input_ids: jax.Array, wte: jax.Array, wpe: jax.Array) -> jax.Array:
    T = input_ids.shape[-1]
    return wte[input_ids] + wpe[:T]


def causal_attention(
    x: jax.Array,
    qkv_w: jax.Array,
    qkv_b: jax.Array,
    proj_w: jax.Array,
    proj_b: jax.Array,
    n_head: int,
) -> jax.Array:
    """Multi-head causal self-attention incl. output projection — one task,
    matching the reference's per-layer "attention" granularity
    (reference test_gpt2.py:75-90: qkv + proj params on a single task)."""
    # q, k, v stay where the projection wrote them: the row-form kernel
    # reads the three thirds of ``qkv`` through its index maps and writes
    # (B, T, D) for the output projection, when the shapes allow it
    # (ops/flash_rows.rows_supported: whole 128-lane tiles of heads, T in
    # whole blocks — GPT-2 small / medium / large); any other call (XL's
    # 25 heads, the tiny configurations, XLA off the TPU) is split into
    # (B, n_head, T, hd), run through ops/attention.mha and merged, as
    # before.  The choice is the shapes' alone.  This function keeps its
    # line count: the lines below it are in the serving kernels' trace
    # stacks (ROADMAP D16).
    qkv = x @ qkv_w + qkv_b
    out = _mha_rows(qkv, n_head=n_head, causal=True)
    return out @ proj_w + proj_b


def ffn_expand(x: jax.Array, fc_w: jax.Array, fc_b: jax.Array) -> jax.Array:
    return x @ fc_w + fc_b


def ffn_activation(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


def ffn_contract(x: jax.Array, proj_w: jax.Array, proj_b: jax.Array) -> jax.Array:
    return x @ proj_w + proj_b


def residual_add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def output_projection(x: jax.Array, wte: jax.Array) -> jax.Array:
    """Logits via weight tying with the embedding table
    (reference test_gpt2.py:160-166).

    At decode shapes (few rows against the full table) ``x @ wte.T``
    makes XLA stream the (V, D) table against its storage order — the
    same transposed-operand stall the decode attention fix measured at
    ~1/5 of HBM rate (models/decode._decode_attention_natural).  For
    small row counts the scores compute as ``wte · x`` instead — both
    operands contract their LAST axis (lanes), no transpose
    materialized — and only the tiny (V, rows) result transposes.  Row
    threshold 64: past that the matmul is MXU-compute-bound and the big
    output transpose would cost more than it saves.

    The fast path only handles the canonical (B, T, D) activations;
    pre-flattened (rows, D) inputs take the plain tied matmul."""
    if x.ndim == 3:
        B, T, D = x.shape
    else:
        B = 0  # disable the reshape fast path below
    if x.ndim == 3 and B * T <= 64:
        flat = x.reshape(B * T, D)
        scores = jax.lax.dot_general(
            wte, flat, (((1,), (1,)), ((), ()))
        )  # (V, B*T): wte rows on sublanes, contraction on lanes
        return scores.T.reshape(B, T, wte.shape[0])
    return x @ wte.T


# -- whole-model forward (fused baseline + correctness oracle) --------------

_BLOCK_KEYS = (
    "ln1_g", "ln1_b", "attn_qkv_w", "attn_qkv_b", "attn_proj_w",
    "attn_proj_b", "ln2_g", "ln2_b", "mlp_fc_w", "mlp_fc_b",
    "mlp_proj_w", "mlp_proj_b",
)


def transformer_block(
    block_params: Dict[str, jax.Array], x: jax.Array, config: GPT2Config
) -> jax.Array:
    """One layer (pre-LN attention + MLP with residuals), params keyed by
    the unprefixed ``_BLOCK_KEYS`` names.  The unit of rematerialization
    and of the scanned forward."""
    ln1 = layer_norm(x, block_params["ln1_g"], block_params["ln1_b"], config.ln_eps)
    attn = causal_attention(
        ln1,
        block_params["attn_qkv_w"],
        block_params["attn_qkv_b"],
        block_params["attn_proj_w"],
        block_params["attn_proj_b"],
        config.n_head,
    )
    x = residual_add(x, attn)
    ln2 = layer_norm(x, block_params["ln2_g"], block_params["ln2_b"], config.ln_eps)
    h = ffn_expand(ln2, block_params["mlp_fc_w"], block_params["mlp_fc_b"])
    h = ffn_activation(h)
    h = ffn_contract(h, block_params["mlp_proj_w"], block_params["mlp_proj_b"])
    return residual_add(x, h)


def _select_block(remat: bool):
    """The layer function both forwards iterate: checkpointed or plain."""
    if remat:
        return jax.checkpoint(transformer_block, static_argnums=(2,))
    return transformer_block


def _head(
    x: jax.Array, params: Dict[str, jax.Array], config: GPT2Config
) -> jax.Array:
    """Shared epilogue: final LN + weight-tied output projection."""
    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], config.ln_eps)
    return output_projection(x, params["wte"])


def forward(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: GPT2Config,
    remat: bool = False,
) -> jax.Array:
    """Full forward pass composing exactly the per-op functions above.

    ``remat=True`` wraps each layer in ``jax.checkpoint`` so the backward
    pass recomputes block activations instead of storing them — the
    standard TPU HBM-for-FLOPs trade for training deep models.
    """
    block = _select_block(remat)
    x = embedding(input_ids, params["wte"], params["wpe"])
    for i in range(config.n_layer):
        p = f"h{i}_"
        x = block({k: params[p + k] for k in _BLOCK_KEYS}, x, config)
    return _head(x, params, config)


# -- scanned forward (stacked layers, one compiled block) --------------------

def stack_layer_params(
    params: Dict[str, jax.Array], config: GPT2Config
) -> Dict[str, jax.Array]:
    """Per-layer ``h{i}_*`` tensors -> stacked ``layers_*`` with a leading
    layer dim (plus the non-layer params unchanged).  The scanned-forward
    layout; numbers are identical to the flat layout."""
    out = {
        k: v for k, v in params.items() if not k.startswith("h")
    }
    for key in _BLOCK_KEYS:
        out["layers_" + key] = jnp.stack(
            [params[f"h{i}_{key}"] for i in range(config.n_layer)]
        )
    return out


def forward_scan(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: GPT2Config,
    remat: bool = False,
) -> jax.Array:
    """Forward over stacked layer params via ``lax.scan``.

    XLA traces and compiles the transformer block ONCE instead of
    ``n_layer`` times — the idiomatic TPU formulation for deep models
    (compile time and program size stay O(1) in depth).  Combine with
    ``remat=True`` for the standard scan-over-remat-blocks training setup.
    Matches :func:`forward` numerically (same block math, same order).
    """
    block = _select_block(remat)
    stacked = {k: params["layers_" + k] for k in _BLOCK_KEYS}

    def step(x, layer_params):
        return block(layer_params, x, config), None

    x = embedding(input_ids, params["wte"], params["wpe"])
    x, _ = jax.lax.scan(step, x, stacked)
    return _head(x, params, config)


def loss_fn(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    targets: jax.Array,
    config: GPT2Config,
    remat: bool = False,
    scan: bool = False,
) -> jax.Array:
    """Next-token cross-entropy (training-step DAGs and the parallel layer).

    ``scan=True`` expects stacked-layer params (:func:`stack_layer_params`)
    and runs the scanned forward."""
    fwd = forward_scan if scan else forward
    logits = fwd(params, input_ids, config, remat=remat)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def num_params(config: GPT2Config) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(config).values())


# -- KV-cache decoding (models/decode.py drives this) ------------------------

def init_cache(config: GPT2Config, batch: int, max_len: int):
    from . import decode

    return decode.init_cache(
        config.n_layer, batch, config.n_head, max_len,
        config.head_dim, config.dtype,
    )


def forward_cached(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    cache,
    pos_start,
    config: GPT2Config,
) -> Tuple[jax.Array, Any]:
    """Forward over ``input_ids`` occupying absolute positions
    [pos_start, pos_start + T), reading and writing the KV cache.

    One code path serves prefill (T = prompt length, pos_start = 0) and
    decode (T = 1); ``pos_start`` may be a traced int32 scalar.  Matches
    :func:`forward` exactly when the cache holds the full history
    (``tests/test_decode.py`` pins logits parity and greedy-token parity).
    """
    from . import decode

    B, T = input_ids.shape
    pos_start = jnp.asarray(pos_start, jnp.int32)
    nh, hd = config.n_head, config.head_dim
    scale = 1.0 / math.sqrt(hd)

    wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], pos_start, T, axis=0)
    x = params["wte"][input_ids] + wpe
    for i in range(config.n_layer):
        p = f"h{i}_"
        ln1 = layer_norm(x, params[p + "ln1_g"], params[p + "ln1_b"], config.ln_eps)
        qkv = ln1 @ params[p + "attn_qkv_w"] + params[p + "attn_qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        cache = decode.update_layer_cache(cache, i, k, v, pos_start)
        kc, vc, ks, vs = decode.layer_view(cache, i)
        att = decode.cached_attention(
            q, kc, vc, pos_start, scale, k_scale=ks, v_scale=vs
        )
        att = att.transpose(0, 2, 1, 3).reshape(B, T, config.n_embd)
        x = x + (att @ params[p + "attn_proj_w"] + params[p + "attn_proj_b"])
        ln2 = layer_norm(x, params[p + "ln2_g"], params[p + "ln2_b"], config.ln_eps)
        h = ffn_contract(
            ffn_activation(
                ffn_expand(ln2, params[p + "mlp_fc_w"], params[p + "mlp_fc_b"])
            ),
            params[p + "mlp_proj_w"],
            params[p + "mlp_proj_b"],
        )
        x = x + h
    return _head(x, params, config), cache


def forward_cached_row(
    params, input_ids, cache, pos_start, config: GPT2Config, row,
    impl: Optional[str] = None,
):
    """:func:`forward_cached` and the logits of chunk row ``row`` (static
    or traced) only, (b, V): all a prefill program needs of them.
    ``impl`` is the served families' common argument; the dense cached
    attention here has one implementation."""
    del impl
    logits, cache = forward_cached(params, input_ids, cache, pos_start, config)
    return jax.lax.dynamic_index_in_dim(
        logits, row, 1, keepdims=False), cache


# -- what the decode-step DAG builders call (models/__init__.py) -------------
# ``p`` is a task's params under LOCAL names: a layer's weights as
# :func:`layer_param_names` spells them, plus its ``cache_k`` / ``cache_v``
# (dense slabs or page pools) and, paged, the ``page_table``.

EMBED_PARAMS = ("wte", "wpe")
HEAD_PARAMS = ("ln_f_g", "ln_f_b", "wte")

_LAYER_NAMES = {
    "ln1_g": "ln1_g", "ln1_b": "ln1_b",
    "qkv_w": "attn_qkv_w", "qkv_b": "attn_qkv_b",
    "attn_proj_w": "attn_proj_w", "attn_proj_b": "attn_proj_b",
    "ln2_g": "ln2_g", "ln2_b": "ln2_b",
    "fc_w": "mlp_fc_w", "fc_b": "mlp_fc_b",
    "mlp_proj_w": "mlp_proj_w", "mlp_proj_b": "mlp_proj_b",
}


def layer_param_names(config: GPT2Config, layer: int) -> Dict[str, str]:
    return {loc: f"h{layer}_{glob}" for loc, glob in _LAYER_NAMES.items()}


def cache_spec(config: GPT2Config):
    from .kv_pages import CacheSpec

    row = (config.n_head, config.head_dim)
    return CacheSpec.uniform("kv", config.n_layer, (("k", row), ("v", row)))


def embed(p, ids, config: GPT2Config):
    return embedding(ids, p["wte"], p["wpe"])


def head(p, x, config: GPT2Config):
    return _head(x, p, config)


def _cached_block(p, x, config: GPT2Config, attend):
    """One layer over ``x`` (B, T, D): LN, QKV split into heads,
    ``attend(q, k, v)`` over (B, H, T, hd), projection, MLP.  Returns the
    residual stream and this step's ``k`` / ``v`` heads."""
    B, T, D = x.shape
    H, hd = config.n_head, config.head_dim
    ln1 = layer_norm(x, p["ln1_g"], p["ln1_b"], config.ln_eps)
    qkv = ln1 @ p["qkv_w"] + p["qkv_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    att = attend(q, k, v).transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + (att @ p["attn_proj_w"] + p["attn_proj_b"])
    ln2 = layer_norm(x, p["ln2_g"], p["ln2_b"], config.ln_eps)
    h = ffn_contract(
        ffn_activation(ffn_expand(ln2, p["fc_w"], p["fc_b"])),
        p["mlp_proj_w"], p["mlp_proj_b"],
    )
    return x + h, k, v


def cached_embed(p, ids, pos, config: GPT2Config):
    """Token embedding + position rows [pos, pos + T) at a traced ``pos``."""
    wpe_rows = jax.lax.dynamic_slice(
        p["wpe"], (pos, jnp.int32(0)), (ids.shape[-1], config.n_embd))
    return p["wte"][ids] + wpe_rows


def cached_layer(p, x, pos, config: GPT2Config, layer: int):
    """One layer of a cached step over the dense slabs: attention over
    [0, pos + T) of the cache, this step's keys / values included.
    Returns ``(x, {"k": k_new, "v": v_new})``."""
    from . import decode

    def attend(q, k, v):
        k_cache = jax.lax.dynamic_update_slice(
            p["cache_k"], k.astype(p["cache_k"].dtype),
            (jnp.int32(0), jnp.int32(0), pos, jnp.int32(0)))
        v_cache = jax.lax.dynamic_update_slice(
            p["cache_v"], v.astype(p["cache_v"].dtype),
            (jnp.int32(0), jnp.int32(0), pos, jnp.int32(0)))
        return decode.cached_attention(
            q, k_cache, v_cache, pos, 1.0 / math.sqrt(config.head_dim))

    x, k, v = _cached_block(p, x, config, attend)
    return x, {"k": k, "v": v}


def cached_flops(config: GPT2Config, batch: int, step_len: int, max_len: int):
    """``(embed, [a layer's ...], head)`` FLOPs of one cached step:
    projections on T tokens + attention over the FULL masked cache
    (compute is O(max_len) at any position: static shapes)."""
    B, T, M, D = batch, step_len, max_len, config.n_embd
    layer = (
        2.0 * B * T * D * 3 * D
        + 2.0 * 2.0 * B * config.n_head * T * M * config.head_dim
        + 2.0 * B * T * D * D
        + 2.0 * B * T * D * 4 * D * 2
    )
    return (2.0 * B * T * D, [layer] * config.n_layer,
            2.0 * B * T * D * config.vocab_size)


def decode_embed(p, ids, lengths, config: GPT2Config):
    """Paged step: slot ``s`` sits at its own ``lengths[s]``."""
    wpe_rows = jnp.take(p["wpe"], lengths, axis=0)[:, None, :]
    return p["wte"][ids] + wpe_rows


def decode_layer(p, x, lengths, live, config: GPT2Config, layer: int,
                 impl: Optional[str] = None):
    """One layer of the paged step, ``x`` (S, 1, D): ragged paged
    attention over the shared pools (this step's k / v inserted into the
    gathered view: the pool write itself is the loop composer's fold),
    then the MLP.  Returns ``(x, {"k": k_new, "v": v_new}, None)``."""
    del live

    def attend(q, k, v):
        return paged_decode_attention(
            q, p["cache_k"], p["cache_v"], p["page_table"], lengths,
            1.0 / math.sqrt(config.head_dim), k_new=k, v_new=v, impl=impl)

    x, k, v = _cached_block(p, x, config, attend)
    return x, {"k": k, "v": v}, None


decode_head = head


def decode_flops(config: GPT2Config, slots: int, capacity: int):
    """The paged step's: attention gathers the slot's full paged
    capacity every step."""
    return cached_flops(config, slots, 1, capacity)


def generate(
    params: Dict[str, jax.Array],
    prompt_ids: jax.Array,
    config: GPT2Config,
    max_new_tokens: int,
    **kw,
) -> jax.Array:
    """Autoregressive generation (greedy by default; see
    :func:`.decode.generate` for temperature/top-k)."""
    from . import decode

    return decode.generate(
        forward_cached, init_cache, params, prompt_ids, config,
        max_new_tokens, **kw,
    )

"""Autoregressive KV-cache decoding shared by all model families.

The reference never *runs* a model (its forward pass is a simulated DAG
replay, reference ``simulation.py:216-278``), so it has no inference story
beyond "the DAG was scheduled".  The rebuild executes for real, and real
inference means token-by-token decoding — this module supplies the shared
machinery: a static-shape KV cache, masked cached attention, and a
``lax.scan`` generation loop with greedy/temperature sampling.

TPU notes (why the design looks like this):

- **Static shapes only.** The cache is allocated at ``max_len`` up front and
  every decode step attends over the full ``(B, H, 1, max_len)`` score
  matrix with a position mask — no growing tensors, so XLA compiles the
  step exactly once and `lax.scan` drives the whole generation as ONE
  compiled program (no per-token dispatch from Python).
- **Traced positions.** ``pos`` is an int32 scalar carried through the scan;
  cache writes use ``lax.dynamic_update_slice`` and RoPE/wpe lookups use
  ``lax.dynamic_slice``, both of which accept traced starts — nothing
  recompiles as generation advances.
- **Decode is bandwidth-bound, not MXU-bound** (one token's GEMVs), so the
  cached-attention path uses plain XLA einsums; the Pallas flash kernel
  (``ops/attention.py``) stays on the prefill/training path where the
  O(T^2) score matrix actually matters.

Each family module (``gpt2``, ``llama``, ``mixtral``) provides
``init_cache(config, batch, max_len)`` and
``forward_cached(params, ids, cache, pos_start, config)``; this module's
:func:`generate` drives any of them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

KVCache = Dict[str, jax.Array]  # {"k": (L, B, Hkv, M, hd), "v": same}


def init_cache(
    n_layers: int,
    batch: int,
    n_kv_heads: int,
    max_len: int,
    head_dim: int,
    dtype: Any,
) -> KVCache:
    """Zeroed stacked-layer cache; positions >= the write cursor are masked
    out by :func:`cached_attention`, so zeros never leak into outputs."""
    shape = (n_layers, batch, n_kv_heads, max_len, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def quantize_cache(cache: KVCache) -> KVCache:
    """An int8 container with the same (L, B, Hkv, M, hd) geometry: values
    as int8 plus one float32 absmax scale per cached row (L, B, Hkv, M, 1).

    Decode is bandwidth-bound and the cache buffer is re-read whole every
    step (module docstring), so halving its bytes is the same structural
    lever int8 weights are — at the cost of per-row quantization error
    (lossy: opt in via ``generate(kv_int8=True)``).  Init scales are 1.0
    but never read: every row is either written (getting a real scale)
    or masked out by :func:`cached_attention`."""
    L, B, H, M, _ = cache["k"].shape
    s = jnp.ones((L, B, H, M, 1), jnp.float32)
    return {
        "k": jnp.zeros(cache["k"].shape, jnp.int8), "k_scale": s,
        "v": jnp.zeros(cache["v"].shape, jnp.int8), "v_scale": s,
    }


def layer_view(cache: KVCache, layer: int):
    """(k, v, k_scale, v_scale) of one layer — scales are None for a
    dense cache, so family attention code handles both layouts with one
    call (gpt2 ``forward_cached``, llama ``attention_cached``)."""
    ks, vs = cache.get("k_scale"), cache.get("v_scale")
    return (
        cache["k"][layer],
        cache["v"][layer],
        None if ks is None else ks[layer],
        None if vs is None else vs[layer],
    )


def _quantize_rows(new: jax.Array):
    """(B, Hkv, T, hd) -> int8 values + per-row float32 absmax scales."""
    s = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.where(s == 0, 1.0, s / 127.0)
    q = jnp.round(new.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s


def update_layer_cache(
    cache: KVCache, layer: int, k_new: jax.Array, v_new: jax.Array,
    pos_start: jax.Array
) -> KVCache:
    """Write (B, Hkv, T_new, hd) keys/values at [pos_start, pos_start+T_new)
    of layer ``layer``.  ``pos_start`` may be traced.  An int8 cache
    (:func:`quantize_cache` layout) quantizes the incoming rows on write."""
    def put(buf, new):
        return jax.lax.dynamic_update_slice(
            buf, new[None].astype(buf.dtype), (layer, 0, 0, pos_start, 0)
        )

    if "k_scale" in cache:
        kq, ks = _quantize_rows(k_new)
        vq, vs = _quantize_rows(v_new)
        return {
            "k": put(cache["k"], kq), "k_scale": put(cache["k_scale"], ks),
            "v": put(cache["v"], vq), "v_scale": put(cache["v_scale"], vs),
        }
    return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}


def cached_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos_start: jax.Array,
    sm_scale: float,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Causal attention of ``q`` (B, Hq, T_new, hd) over a full-length cache
    (B, Hkv, M, hd) whose rows beyond ``pos_start + T_new`` are invalid.

    Query row ``r`` (absolute position ``pos_start + r``) may attend cache
    columns ``c <= pos_start + r`` — this single mask covers both the
    "stale tail" of the cache and causality among the new tokens, so the
    same code path serves prefill (T_new = prompt) and decode (T_new = 1).
    KV heads broadcast across their query group (GQA).

    ``k_scale``/``v_scale`` (B, Hkv, M, 1) mark an int8 cache
    (:func:`quantize_cache`).  The cache stays int8 through the dots —
    the int8->compute-dtype convert fuses into the einsum's read — and
    the per-row scales fold into the score columns / softmax weights
    AFTER the contractions (algebraically exact: the scale is constant
    along the contracted head_dim axis).  Scaling the cache *before*
    the dot instead would materialize a full dequantized copy per step,
    which costs more HBM traffic than the int8 layout saves (measured:
    6.1k tok/s materialized vs 7.1k bf16 baseline on the v5e).
    """
    B, Hq, Tn, hd = q.shape
    Hkv, M = k_cache.shape[1], k_cache.shape[2]
    if Tn == 1:
        return _decode_attention_natural(
            q, k_cache, v_cache, pos_start, sm_scale, k_scale, v_scale
        )
    if Hq != Hkv:
        group = Hq // Hkv
        k_cache = jnp.repeat(k_cache, group, axis=1)
        v_cache = jnp.repeat(v_cache, group, axis=1)
        if k_scale is not None:
            k_scale = jnp.repeat(k_scale, group, axis=1)
        if v_scale is not None:
            v_scale = jnp.repeat(v_scale, group, axis=1)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_cache.astype(q.dtype)
    ) * sm_scale
    if k_scale is not None:
        # (B, H, M, 1) -> one multiplier per score column
        scores = scores * k_scale[..., 0][:, :, None, :].astype(
            scores.dtype
        )
    rows = pos_start + jax.lax.broadcasted_iota(jnp.int32, (Tn, M), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Tn, M), 1)
    scores = jnp.where(cols <= rows, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if v_scale is not None:
        probs = probs * v_scale[..., 0][:, :, None, :]
    out_dtype = q.dtype
    return jnp.einsum(
        "bhqk,bhkd->bhqd",
        probs.astype(out_dtype), v_cache.astype(out_dtype),
    )


def _decode_attention_natural(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    sm_scale: float,
    k_scale: Optional[jax.Array],
    v_scale: Optional[jax.Array],
) -> jax.Array:
    """Single-token cached attention in MXU-natural orientation.

    The prefill-orientation einsum (``bhqd,bhkd->bhqk``) at T_new = 1
    makes XLA transpose the K cache every step.  Computing scores as
    ``K @ q`` instead ((B, Hkv, M, G) with M on sublanes, exactly the
    cache's storage layout) runs the identical math with both operands
    read in storage order.  The rewrite's on-chip gain has no surviving
    record: not measured (ROADMAP S4 re-measures decode attention on the
    v5e).

    GQA comes free: the query group joins the G axis (``bhgd`` below),
    so K/V stream ONCE per KV head — the prefill path's ``jnp.repeat``
    reads them ``group`` times.  int8 scale folding is unchanged in
    algebra, just applied along the natural axes.
    """
    B, Hq, _, hd = q.shape
    Hkv, M = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = (q * sm_scale).reshape(B, Hkv, G, hd)
    # scores (B, Hkv, M, G): contract hd (lanes), batch (B, Hkv) — both
    # operands read in storage order, no transpose materialized
    s = jax.lax.dot_general(
        k_cache.astype(qg.dtype), qg,
        (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        s = s * k_scale.astype(s.dtype)  # (B, Hkv, M, 1) broadcasts over G
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(rows <= pos, s, jnp.finfo(s.dtype).min)
    m = s.max(axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=2, keepdims=True)
    if v_scale is not None:
        p = p * v_scale.astype(p.dtype)
    out_dtype = q.dtype
    # out (B, Hkv, G, hd): contract M (sublanes of both), batch (B, Hkv)
    o = jax.lax.dot_general(
        p.astype(out_dtype), v_cache.astype(out_dtype),
        (((2,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    return (o / l.reshape(B, Hkv, G, 1)).astype(out_dtype).reshape(
        B, Hq, 1, hd
    )


def sample_token(
    logits: jax.Array,
    key: Optional[jax.Array],
    temperature: float,
    top_k: int = 0,
) -> jax.Array:
    """(B, V) logits -> (B,) int32 token ids.

    ``temperature == 0`` is greedy argmax (no key needed).  ``top_k > 0``
    restricts sampling to the k most likely tokens (static k, so the
    lax.top_k shape is fixed under jit).
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert key is not None, "temperature sampling needs a PRNG key"
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _position_limit(config: Any) -> Optional[int]:
    """The family's maximum absolute position: GPT-2's learned table length
    or the Llama-backbone's trained RoPE horizon."""
    return getattr(config, "n_positions", None) or getattr(
        config, "max_seq_len", None
    )


@functools.lru_cache(maxsize=64)
def _compiled_run(
    forward_cached: Callable[..., Tuple[jax.Array, KVCache]],
    init_cache_fn: Callable[[Any, int, int], KVCache],
    config: Any,
    B: int,
    T: int,
    M: int,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    kv_int8: bool = False,
):
    """One compiled generation program per static configuration — repeated
    generate() calls with the same shapes reuse it instead of re-tracing
    (config is a frozen dataclass, so it hashes by value)."""

    @jax.jit
    def run(params, prompt_ids, key):
        cache = init_cache_fn(config, B, M)
        if kv_int8:
            cache = quantize_cache(cache)
        logits, cache = forward_cached(params, prompt_ids, cache, 0, config)
        key, sub = jax.random.split(key)
        first = sample_token(logits[:, -1, :], sub, temperature, top_k)

        def step(carry, _):
            cache, tok, pos, key = carry
            logits, cache = forward_cached(
                params, tok[:, None], cache, pos, config
            )
            key, sub = jax.random.split(key)
            nxt = sample_token(logits[:, -1, :], sub, temperature, top_k)
            return (cache, nxt, pos + 1, key), tok

        (_, last, _, _), toks = jax.lax.scan(
            step,
            (cache, first, jnp.int32(T), key),
            None,
            length=max_new_tokens - 1,
        ) if max_new_tokens > 1 else ((cache, first, None, key), None)
        new = (
            jnp.concatenate([toks.T, last[:, None]], axis=1)
            if toks is not None
            else last[:, None]
        )
        return jnp.concatenate([prompt_ids, new], axis=1)

    return run


def generate(
    forward_cached: Callable[..., Tuple[jax.Array, KVCache]],
    init_cache_fn: Callable[[Any, int, int], KVCache],
    params: Dict[str, jax.Array],
    prompt_ids: jax.Array,
    config: Any,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    kv_int8: bool = False,
) -> jax.Array:
    """Prefill the prompt, then scan ``max_new_tokens`` decode steps.

    ``kv_int8=True`` stores the KV cache as int8 with per-row scales
    (:func:`quantize_cache` — lossy, so opt-in): the cache buffer is the
    second-largest byte term a decode step re-reads.

    Returns (B, prompt_len + max_new_tokens) int32: prompt + generated.
    The whole loop is one jitted program — prefill compiles once for the
    prompt shape, the decode step compiles once and is iterated by
    ``lax.scan`` on device — and the compiled program is cached per static
    configuration, so repeated calls don't re-trace.
    """
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        return prompt_ids
    B, T = prompt_ids.shape
    M = max_len if max_len is not None else T + max_new_tokens
    if M < T + max_new_tokens:
        # an undersized cache would CLAMP dynamic_update_slice writes and
        # silently corrupt generation — refuse loudly (not an assert: this
        # must survive python -O)
        raise ValueError(f"max_len {M} < prompt {T} + new {max_new_tokens}")
    limit = _position_limit(config)
    if limit is not None and T + max_new_tokens > limit:
        # past the position table/RoPE horizon, dynamic_slice would CLAMP
        # its start and silently repeat the last position's embedding
        raise ValueError(
            f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model's position limit {limit}"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    run = _compiled_run(
        forward_cached, init_cache_fn, config, B, T, M, max_new_tokens,
        float(temperature), int(top_k), bool(kv_int8),
    )
    return run(params, prompt_ids, key)

"""Ring attention: causal attention over sequence chunks on a mesh axis.

Long-context capability (new vs the reference, which never executes
attention at all — SURVEY.md §5.7): the sequence dimension is sharded over
the ``sp`` mesh axis; each device holds one Q/K/V chunk.  K/V chunks rotate
around the ring with ``jax.lax.ppermute`` (ICI neighbor hops on a TPU
slice) while each device accumulates its queries' attention over every K/V
block using a numerically-stable online softmax (flash-attention style
running max/denominator).  Causality is enforced blockwise: a Q chunk
attends to a K/V chunk fully when the source block index is lower, with a
triangular mask when equal, not at all when higher.

Compute/communication overlap is XLA's job (the ppermute for step i+1 is
independent of step i's math); the implementation only has to keep the loop
body fusion-friendly: static shapes, `lax.fori_loop`, no data-dependent
Python control flow.
"""

from __future__ import annotations

import math
from functools import partial
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _block_scores(q, k, q_blk, kv_blk, blk_len):
    """Masked scores of one Q chunk against one K/V chunk.

    q: (B, H, Tq, hd); k: (B, H, Tk, hd).  Causal blockwise via global
    positions: full when kv_blk < q_blk, triangular when equal, fully
    masked when kv_blk > q_blk.
    """
    hd = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    i = jax.lax.broadcasted_iota(jnp.int32, scores.shape[-2:], 0)
    j = jax.lax.broadcasted_iota(jnp.int32, scores.shape[-2:], 1)
    qpos = q_blk * blk_len + i
    kpos = kv_blk * blk_len + j
    return jnp.where(kpos <= qpos, scores, jnp.finfo(scores.dtype).min)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
) -> jax.Array:
    """Causal ring attention over the ``axis_name`` mesh axis.

    Call inside ``shard_map`` with q/k/v already sequence-sharded:
    per-device shapes (B, H, T_local, hd).  Returns the local output chunk
    (B, H, T_local, hd).
    """
    n_blocks = axis_size(axis_name)
    my_blk = jax.lax.axis_index(axis_name)
    B, H, T, hd = q.shape
    fmax = jnp.finfo(jnp.float32)

    def attend(k_cur, v_cur, kv_blk, numer, denom, m):
        scores = _block_scores(q, k_cur, my_blk, kv_blk, T).astype(jnp.float32)
        m_new = jnp.maximum(m, scores.max(-1))
        # guard fully-masked rows: max stays at -inf -> exp underflows to 0
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        scale = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        p = jnp.exp(scores - m_safe[..., None])
        numer = numer * scale[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), v_cur
        ).astype(jnp.float32)
        denom = denom * scale + p.sum(-1)
        return numer, denom, m_new

    def body(step, carry):
        # rotate at loop entry (K/V blocks travel backwards around the
        # ring), so the final iteration doesn't pay a permute whose result
        # would be discarded
        k_cur, v_cur, numer, denom, m = carry
        perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_blk = (my_blk - step) % n_blocks
        numer, denom, m = attend(k_cur, v_cur, kv_blk, numer, denom, m)
        return k_cur, v_cur, numer, denom, m

    numer0 = jnp.zeros((B, H, T, hd), jnp.float32)
    denom0 = jnp.zeros((B, H, T), jnp.float32)
    m0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    numer, denom, m = attend(k, v, my_blk, numer0, denom0, m0)  # own block
    _, _, numer, denom, _ = jax.lax.fori_loop(
        1, n_blocks, body, (k, v, numer, denom, m)
    )
    out = numer / jnp.maximum(denom, fmax.tiny)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
) -> jax.Array:
    """Convenience wrapper: shard (B, H, T, hd) tensors over ``axis_name``
    on their sequence dim and run ring attention via shard_map."""
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sh = NamedSharding(mesh, spec)
    return fn(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh)
    )


def reference_causal_attention(q, k, v):
    """Unsharded oracle for tests: plain causal attention on (B,H,T,hd)."""
    hd = q.shape[-1]
    T = q.shape[-2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    i = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    scores = jnp.where(j <= i, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def collective_probe(devices=None):
    """``(fn, example_avals)`` for the analysis sweep (lint --parallel).

    Traces the shard_map'd ring body abstractly — zero FLOPs — so
    ``analysis.parallel_sweep`` can check the ppermute ring schedule
    (COL003/COL004) on every lint run.
    """
    devs = list(devices if devices is not None else jax.devices())[:4]
    import numpy as np

    mesh = Mesh(np.array(devs), ("sp",))
    spec = P(None, None, "sp", None)
    fn = shard_map(
        partial(ring_attention, axis_name="sp"),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((1, 2, 4 * len(devs), 8), jnp.float32)
    return fn, (x, x, x)

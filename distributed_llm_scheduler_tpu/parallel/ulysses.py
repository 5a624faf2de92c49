"""Ulysses-style sequence parallelism: all-to-all head redistribution.

The second of the two standard long-context strategies (ring attention,
:mod:`.ring_attention`, is the other; the reference executes no attention
at all — SURVEY.md §5.7).  Where ring attention keeps queries home and
rotates K/V around the ring in ``sp`` steps, Ulysses redistributes ONCE:

1. inputs arrive sequence-sharded — each of the ``sp`` devices holds
   (B, H, T/sp, hd) for ALL heads;
2. an all-to-all over ``sp`` re-shards from sequence to heads — each
   device now holds (B, H/sp, T, hd): its head group over the FULL
   sequence, so plain (flash) attention runs locally with exact causality
   and no online-softmax machinery;
3. a second all-to-all restores sequence sharding for the surrounding
   sequence-parallel layers.

Trade-offs vs ring: two all-to-alls of the whole activation instead of
``sp`` neighbor hops of K/V (cheaper on all-to-all-rich ICI when
``sp <= n_heads``), but head count must be divisible by ``sp``, while
ring has no such constraint.  Both are exposed so callers pick per
topology/model — the classic DeepSpeed-Ulysses vs ring-attention choice.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import mha as _fused_mha


def _seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """(B, H, T_local, hd) seq-sharded -> (B, H_local, T, hd) head-sharded.

    ``all_to_all`` scatters the head dim across the axis and gathers the
    sequence dim: one fused ICI collective, the Ulysses primitive.
    """
    return jax.lax.all_to_all(
        x, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def _heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """Inverse of :func:`_seq_to_heads`."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Causal attention under Ulysses sequence parallelism.

    Call inside ``shard_map`` with q/k/v sequence-sharded: per-device
    shapes (B, H, T_local, hd), H divisible by the axis size.  Returns the
    local sequence chunk (B, H, T_local, hd).
    """
    sp = axis_size(axis_name)
    H = q.shape[1]
    if H % sp != 0:
        raise ValueError(
            f"ulysses needs heads ({H}) divisible by the {axis_name!r} "
            f"axis size ({sp}); use ring attention otherwise"
        )
    q, k, v = (_seq_to_heads(t, axis_name) for t in (q, k, v))
    # full sequence, local head group: exact attention, no online softmax
    out = _fused_mha(q, k, v, causal=causal)
    return _heads_to_seq(out, axis_name)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Convenience wrapper: shard (B, H, T, hd) tensors over ``axis_name``
    on their sequence dim and run Ulysses attention via shard_map."""
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ulysses_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sh = NamedSharding(mesh, spec)
    return fn(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh)
    )


def collective_probe(devices=None):
    """``(fn, example_avals)`` for the analysis sweep (lint --parallel):
    the shard_map'd Ulysses body with heads divisible by the sp axis, so
    both all_to_all redistributions land in the traced jaxpr."""
    devs = list(devices if devices is not None else jax.devices())[:4]
    import jax.numpy as jnp
    import numpy as np

    mesh = Mesh(np.array(devs), ("sp",))
    sp = len(devs)
    spec = P(None, None, "sp", None)
    fn = shard_map(
        partial(ulysses_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((1, 2 * sp, 4 * sp, 8), jnp.float32)
    return fn, (x, x, x)

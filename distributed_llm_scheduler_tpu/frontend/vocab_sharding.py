"""Shared vocab-sharding pieces for the model-family DAG builders.

Task-graph tensor parallelism for the vocab-sized tables (embedding,
LM head): balanced row/column shards, partial-lookup tasks whose sum equals
the full lookup exactly, and logit-slice concatenation.  GPT-2
(:mod:`.gpt2_dag`, tied table: row shards serve both ends) and the
llama-architecture backbone (:mod:`.backbone`, separate ``tok_emb`` /
``lm_head``) both build their shard tasks from these helpers so the split
arithmetic and the masked-lookup semantics cannot drift between families.
"""

from __future__ import annotations

from typing import Callable, List

import jax.numpy as jnp


def shard_bounds(vocab_size: int, shards: int, align: int = 128) -> List[int]:
    """Near-balanced split boundaries: ``shards + 1`` cumulative offsets,
    every shard non-empty for any ``1 <= shards <= vocab_size``.

    Interior boundaries snap to multiples of ``align`` (the TPU lane
    width) when the vocab is large enough: a 50257/8 balanced split puts
    every logit-shard matmul and concat slice at a 6283-column offset —
    off the 128-lane grid, so each shard pads/relayouts.  Aligned
    boundaries keep all but the last shard exactly on the grid.  Any
    split is semantically exact (each id hits exactly one shard); tiny
    vocabs where alignment would empty a shard fall back to the balanced
    split."""
    if not 1 <= shards <= vocab_size:
        raise ValueError(
            f"vocab_shards {shards} out of range [1, {vocab_size}]"
        )
    base, extra = divmod(vocab_size, shards)
    lo = [0]
    for k in range(shards):
        lo.append(lo[-1] + base + (1 if k < extra else 0))
    if align > 1 and vocab_size >= shards * align:
        aligned = [0]
        for k in range(1, shards):
            b = round(lo[k] / align) * align
            # monotone and room for the remaining shards
            b = max(b, aligned[-1] + align)
            b = min(b, vocab_size - (shards - k) * align)
            aligned.append(b)
        aligned.append(vocab_size)
        lo = aligned
    return lo


def make_embed_partial_fn(
    lo_b: int, hi_b: int, lo_v: int, rows: int
) -> Callable:
    """Partial lookup over one row shard (``p["shard"]``): token ids outside
    ``[lo_v, lo_v + rows)`` contribute 0, so the shard-sum equals the full
    lookup exactly (each id hits exactly one shard).  ``[lo_b, hi_b)`` slices
    the microbatch from the full input batch."""

    def f_embed_partial(p, input_ids):
        local = input_ids[lo_b:hi_b] - lo_v
        mask = (local >= 0) & (local < rows)
        emb = p["shard"][jnp.clip(local, 0, rows - 1)]
        return emb * mask[..., None].astype(emb.dtype)

    return f_embed_partial


def logit_concat_fn(p, *slices):
    """Concatenate per-shard logit slices along the vocab axis."""
    return jnp.concatenate(slices, axis=-1)

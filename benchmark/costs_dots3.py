"""Bytes of the kernels the dots3 cells bring, from shapes and live
lengths — numerators of their roofline shares, kept with the benchmark
like ``costs.py`` and ``costs_latent.py``."""

from __future__ import annotations

from typing import Any, Callable, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _mean_rows(ctx: Dict[str, Any], rows_of: Callable[[int], int]) -> float:
    """Mean over the calls of the traced slice's decode segments (one a
    layer a step, all slots) of the rows a call must read: ``rows_of(L)``
    for every slot that decodes a token at position ``L`` in that step,
    nothing for a slot that does not.  Positions as
    ``costs_latent.mla_paged_attention_bytes`` counts them: a request
    decoding when a ``segment`` span began holds its prompt plus the
    tokens delivered so far, and one more row with every step of the
    segment that it still owes."""
    steps = int(ctx["config"]["engine"]["seg_steps"])
    rows, calls = 0.0, 0
    for t0, _t1 in ctx.get("slice_segments") or []:
        for r in ctx["records"]:
            if r["t_first"] is None or r["t_first"] > t0:
                continue
            if r["t_retire"] is not None and r["t_retire"] <= t0:
                continue
            have = 1 + sum(k for t, k in r["deliveries"] if t <= t0)
            owed = r["max_new_tokens"] - have
            for s in range(min(steps, max(owed, 0))):
                rows += rows_of(r["prompt_len"] + have + s)
        calls += steps
    return rows / calls if calls else 0.0


def dsa_index_bytes(ctx: Dict[str, Any]) -> float:
    """One ``_dsa_index`` call: the ``index_head_dim`` values of every
    cached key of every decoding slot."""
    cfg = ctx["config"]
    return (int(cfg["index_head_dim"]) * _ITEMSIZE[cfg["dtype"]]
            * _mean_rows(ctx, lambda L: L))


def dsa_sparse_attention_bytes(ctx: Dict[str, Any]) -> float:
    """One ``_dsa_sparse_attn`` call: the ``kv_lora_rank +
    qk_rope_head_dim`` values of the ``min(L + 1, index_topk)`` rows a
    decoding slot at position ``L`` selected (the pad to whole lane
    tiles is the layout's, not the algorithm's)."""
    cfg = ctx["config"]
    k = int(cfg["index_topk"])
    row = (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
           ) * _ITEMSIZE[cfg["dtype"]]
    return row * _mean_rows(ctx, lambda L: min(L + 1, k))


def swa_latent_attention_bytes(ctx: Dict[str, Any]) -> float:
    """One ``_swa_latent_attn`` call: the ``swa_kv_lora_rank +
    swa_qk_rope_head_dim`` values of the ``min(L + 1,
    sliding_window_size)`` rows in a decoding slot's window."""
    cfg = ctx["config"]
    w = int(cfg["sliding_window_size"])
    row = (int(cfg["swa_kv_lora_rank"]) + int(cfg["swa_qk_rope_head_dim"])
           ) * _ITEMSIZE[cfg["dtype"]]
    return row * _mean_rows(ctx, lambda L: min(L + 1, w))

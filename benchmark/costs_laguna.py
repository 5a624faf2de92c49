"""Bytes and operations of the kernels the Laguna cells bring, from
shapes, live lengths and the program's own spans — numerators of their
roofline shares, kept with the benchmark like ``costs.py``,
``costs_latent.py`` and ``costs_dots3.py``."""

from __future__ import annotations

from typing import Any, Dict

from .costs_dots3 import _ITEMSIZE, _mean_rows


def _row_bytes(cfg: Dict[str, Any]) -> int:
    """A token's rotated K row and its V row in one layer."""
    return (2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
            * _ITEMSIZE[cfg["dtype"]])


def gqa_paged_attention_bytes(ctx: Dict[str, Any]) -> float:
    """One ``_paged_flash`` call of a full layer: every live K and V row
    (8 heads of 128 each) of every decoding slot — live rows as
    ``costs_dots3._mean_rows`` counts them (a request decoding when a
    ``segment`` span of the traced slice began holds its prompt plus the
    tokens delivered so far, and one more row with every step it owes)."""
    return _row_bytes(ctx["config"]) * _mean_rows(ctx, lambda L: L)


def swa_kv_attention_bytes(ctx: Dict[str, Any]) -> float:
    """One ``_swa_kv_attn`` call of a window layer: the K and V rows of
    the ``min(L + 1, sliding_window)`` positions in a decoding slot's
    window."""
    cfg = ctx["config"]
    w = int(cfg["sliding_window"])
    return _row_bytes(cfg) * _mean_rows(ctx, lambda L: min(L + 1, w))


def _pairs(base: int, tokens: int, window: int = 0) -> int:
    """(query, key) pairs the mask admits for ``tokens`` real queries at
    positions ``base ..``: every earlier position and the query's own, or
    the last ``window`` of them."""
    if window:
        return sum(min(base + t + 1, window) for t in range(tokens))
    return tokens * base + tokens * (tokens + 1) // 2


def gqa_chunk_flash_flops(ctx: Dict[str, Any]) -> float:
    """Mean FLOPs one ``_gqa_chunk_flash`` call needs, over the prefill
    programs the traced slice dispatched (span ``prefill_chunk``: one
    slot's ``tokens`` real rows at ``base``; span ``prefill``: ``requests``
    whole prompts of ``prompt_len`` from position 0), one call a layer:
    4 x the layer's query heads x head_dim for every (real query, key)
    pair its mask admits — what the mathematics needs whatever computes
    it (the kernel computes whole 512 x 512 tiles)."""
    cfg = ctx["config"]
    lo, hi = ctx.get("slice", (None, None))
    if lo is None:
        return 0.0
    n = int(cfg["num_hidden_layers"])
    heads = [int(h) for h in cfg["num_attention_heads_per_layer"][:n]]
    full = [t == "full_attention" for t in cfg["layer_types"][:n]]
    w, hd = int(cfg["sliding_window"]), int(cfg["head_dim"])
    flops, calls = 0.0, 0
    for e in ctx.get("spans", ()):
        if e.get("type") != "span" or not lo <= e["t0"] <= hi:
            continue
        a = e.get("args", {})
        if e.get("name") == "prefill_chunk" and "base" in a:
            base, tokens, many = int(a["base"]), int(a["tokens"]), 1
        elif e.get("name") == "prefill" and "prompt_len" in a:
            base, tokens, many = 0, int(a["prompt_len"]), int(a["requests"])
        else:       # a request's own waterfall spans carry the names too
            continue
        for H, is_full in zip(heads, full):
            flops += 4.0 * H * hd * many * _pairs(
                base, tokens, 0 if is_full else w)
        calls += n
    return flops / calls if calls else 0.0

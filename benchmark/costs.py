"""Operations and bytes a kernel's call needs, from shapes and live
lengths — the numerators of the roofline shares.  Kept with the
benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations

from typing import Any, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def flash_mha_flops(ctx: Dict[str, Any]) -> float:
    """FLOPs of one causal multi-head attention call of the forward DAG:
    QK^T and PV are 2*T*T*hd multiply-adds each per head and row, halved
    by the causal mask."""
    cfg, tr = ctx["config"], ctx["traffic"]
    rows = int(tr["batch"]) // int(tr["microbatches"])
    T, H = int(tr["seq_len"]), int(cfg["n_head"])
    hd = int(cfg["n_embd"]) // H
    return rows * H * (4.0 * T * T * hd) / 2.0


def paged_decode_attention_bytes(ctx: Dict[str, Any]) -> float:
    """Mean bytes one paged decode-attention call (one layer, one step,
    all slots) must read: every live key and value row of every slot
    that decodes.  Live lengths come from the request records: a request
    decoding when a ``segment`` span of the traced slice began holds its
    prompt plus the tokens delivered to it so far, and one more row with
    every step of the segment that it still owes."""
    cfg = ctx["config"]
    hd_all = int(cfg["n_embd"])              # n_head * head_dim
    row_bytes = 2 * hd_all * _ITEMSIZE[cfg["dtype"]]   # K and V
    steps = int(cfg["engine"]["seg_steps"])
    segs = ctx["slice_segments"]             # [(t0, t1)] on the host clock
    if not segs:
        return 0.0
    total_rows, calls = 0.0, 0
    for t0, _t1 in segs:
        for r in ctx["records"]:
            if r["t_first"] is None or r["t_first"] > t0:
                continue
            if r["t_retire"] is not None and r["t_retire"] <= t0:
                continue
            have = 1 + sum(k for t, k in r["deliveries"] if t <= t0)
            owed = r["max_new_tokens"] - have
            for s in range(min(steps, max(owed, 0))):
                total_rows += r["prompt_len"] + have + s
        calls += steps
    return row_bytes * total_rows / calls


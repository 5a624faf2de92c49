"""Operations and bytes of the kernels the Xing4.0 cells bring, from
shapes, live lengths and the program's own counts — numerators of their
roofline shares, kept with the benchmark like ``costs.py``."""

from __future__ import annotations

from typing import Any, Dict

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def mla_paged_attention_bytes(ctx: Dict[str, Any]) -> float:
    """Mean bytes one absorbed-MLA decode call (one layer, one step, all
    slots) must read: the ``kv_lora_rank + qk_rope_head_dim`` values of
    every live row of every slot that decodes (the pad to whole lane
    tiles is the layout's, not the algorithm's).  Live rows as
    ``costs.paged_decode_attention_bytes`` counts them: a request
    decoding when a ``segment`` span of the traced slice began holds its
    prompt plus the tokens delivered so far, and one more row with every
    step of the segment that it still owes.  FLOPs (rows x heads x (row +
    rank) x 2 over 197 TFLOP/s) are a quarter of the bytes' time: bytes
    are the roof."""
    cfg = ctx["config"]
    row_bytes = (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
                 ) * _ITEMSIZE[cfg["dtype"]]
    steps = int(cfg["engine"]["seg_steps"])
    segs = ctx.get("slice_segments") or []
    rows, calls = 0.0, 0
    for t0, _t1 in segs:
        for r in ctx["records"]:
            if r["t_first"] is None or r["t_first"] > t0:
                continue
            if r["t_retire"] is not None and r["t_retire"] <= t0:
                continue
            have = 1 + sum(k for t, k in r["deliveries"] if t <= t0)
            owed = r["max_new_tokens"] - have
            for s in range(min(steps, max(owed, 0))):
                rows += r["prompt_len"] + have + s
        calls += steps
    return row_bytes * rows / calls if calls else 0.0


def moe_expert_bytes(ctx: Dict[str, Any]) -> float:
    """Mean bytes one ``_moe_experts`` call of a decode step must read:
    the three matrices of every DISTINCT expert its tokens picked
    (3 x hidden x moe_intermediate values each).  The count is the
    program's, made on the device and carried by the ``segment`` spans of
    the traced slice (``experts_touched``: mean over all the segment's
    layer-steps, those in which no slot decodes any more counting 0, as
    the kernel is called in them too)."""
    cfg = ctx["config"]
    lo, hi = ctx.get("slice", (None, None))
    counts = [e["args"]["experts_touched"] for e in ctx.get("spans", ())
              if e.get("type") == "span" and e.get("name") == "segment"
              and "experts_touched" in e.get("args", {})
              and lo is not None and lo <= e["t0"] <= hi]
    if not counts:
        return 0.0
    per_expert = (3 * int(cfg["hidden_size"])
                  * int(cfg["moe_intermediate_size"])
                  * _ITEMSIZE[cfg["dtype"]])
    return per_expert * sum(counts) / len(counts)

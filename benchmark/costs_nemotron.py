"""Bytes of the kernels the Nemotron-H cells bring, from shapes and the
program's own spans — numerators of their roofline shares, kept with
the benchmark like ``costs.py``, ``costs_latent.py``, ``costs_dots3.py``
and ``costs_laguna.py``."""

from __future__ import annotations

from typing import Any, Dict, List

from .costs_latent import _ITEMSIZE


def _span_args(ctx: Dict[str, Any], name: str, key: str) -> List[float]:
    """``args[key]`` of the program's ``name`` spans that began inside
    the traced slice (a request's own waterfall spans carry the names
    too, without the key)."""
    lo, hi = ctx.get("slice", (None, None))
    if lo is None:
        return []
    return [float(e["args"][key]) for e in ctx.get("spans", ())
            if e.get("type") == "span" and e.get("name") == name
            and key in e.get("args", {}) and lo <= e["t0"] <= hi]


def _state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's mixer state in one layer: the float32 SSM state and the
    convolution's last ``conv_kernel - 1`` inputs in the served dtype."""
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return (H * P * N * 4 + (int(cfg["conv_kernel"]) - 1)
            * (H * P + 2 * G * N) * _ITEMSIZE[cfg["dtype"]])


def ssm_step_bytes(ctx: Dict[str, Any]) -> float:
    """Mean bytes one ``_ssm_step`` call (one mixer layer, one step) must
    move: the state of every slot that decodes, read and written.  The
    count is the program's, made on the device and carried by the
    ``segment`` spans (``ssm_slots``: decoding slot-steps summed over the
    segment's steps; a step in which no slot decodes any more counts 0,
    and the kernel is called in it too)."""
    slots = _span_args(ctx, "segment", "ssm_slots")
    if not slots:
        return 0.0
    steps = int(ctx["config"]["engine"]["seg_steps"])
    return 2.0 * _state_bytes(ctx["config"]) * sum(slots) / (
        len(slots) * steps)


def ssd_chunk_bytes(ctx: Dict[str, Any]) -> float:
    """Mean bytes one ``_ssd_chunk`` call (one mixer layer of one chunk
    program) must move whatever computes it: for every REAL token its
    ``x``, ``B``, ``C`` and ``dt`` in and its ``y`` out at the served
    width, and the slot's SSM state in and out.  Real tokens from the
    ``prefill_chunk`` spans (``creal``)."""
    cfg = ctx["config"]
    real = _span_args(ctx, "prefill_chunk", "creal")
    if not real:
        return 0.0
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    per_token = (2 * H * P + 2 * G * N + H) * _ITEMSIZE[cfg["dtype"]]
    return per_token * sum(real) / len(real) + 2.0 * H * P * N * 4


def moe_expert_bytes(ctx: Dict[str, Any]) -> float:
    """``costs_latent.moe_expert_bytes`` for experts of TWO matrices:
    mean bytes one ``_moe_experts`` call of a decode step must read, 2 x
    hidden x moe_intermediate values of every DISTINCT expert its tokens
    picked (``experts_touched`` on the ``segment`` spans)."""
    cfg = ctx["config"]
    counts = _span_args(ctx, "segment", "experts_touched")
    if not counts:
        return 0.0
    return (2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])
            * _ITEMSIZE[cfg["dtype"]] * sum(counts) / len(counts))

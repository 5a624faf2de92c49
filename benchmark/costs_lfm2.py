"""Bytes of what the LFM2 cells bring, from shapes, live lengths and the
program's own spans — numerators of their roofline shares, kept with the
benchmark like ``costs.py``, ``costs_latent.py``, ``costs_dots3.py``,
``costs_laguna.py``, ``costs_nemotron.py`` and ``costs_ouro.py``."""

from __future__ import annotations

from typing import Any, Dict

from .costs_dots3 import _ITEMSIZE, _mean_rows
from .costs_nemotron import _span_args


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    n = int(cfg["num_hidden_layers"])
    types_ = cfg["layer_types"][:n]
    return dict(
        h=h, hd=int(cfg.get("head_dim") or h // heads), heads=heads,
        kv=int(cfg["num_key_value_heads"]), K=int(cfg["conv_L_cache"]),
        conv=sum(t == "conv" for t in types_), attn=sum(
            t == "full_attention" for t in types_),
        dense=int(cfg["num_dense_layers"]), n=n,
        size=_ITEMSIZE[cfg["dtype"]])


def conv_state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's state in one conv layer: the last ``conv_L_cache - 1``
    rows of ``u`` in the served dtype (8,192 B at the published sizes)."""
    d = _dims(cfg)
    return (d["K"] - 1) * d["h"] * d["size"]


def _slots_a_step(ctx: Dict[str, Any]) -> float:
    """Mean slots whose conv state a step updated, over the traced
    slice's segments: the program's count, made on the device
    (``conv_slots`` on the ``segment`` span: slot-steps of the segment; a
    step in which no slot decodes any more counts 0, and runs too)."""
    slots = _span_args(ctx, "segment", "conv_slots")
    if not slots:
        return 0.0
    return sum(slots) / (len(slots) * int(ctx["config"]["engine"]["seg_steps"]))


def fixed_weight_bytes(cfg: Dict[str, Any]) -> int:
    """Every weight a decode step reads whatever it routes: the conv and
    attention operators, the dense feed-forwards, the routers (float32),
    the norms, and the embedding matrix once — as the tied head; the
    embedding's own gather is a row a slot and left out."""
    d = _dims(cfg)
    h, size = d["h"], d["size"]
    q, kv = d["heads"] * d["hd"], d["kv"] * d["hd"]
    conv = h * 3 * h + h * d["K"] + h * h
    attn = 2 * h * q + 2 * h * kv + 2 * d["hd"]
    dense = 3 * h * int(cfg["intermediate_size"])
    router = (h + 1) * int(cfg["num_experts"]) * 4
    return ((d["conv"] * conv + d["attn"] * attn + d["dense"] * dense
             + 2 * d["n"] * h + h + int(cfg["vocab_size"]) * h) * size
            + (d["n"] - d["dense"]) * router)


def step_bytes(ctx: Dict[str, Any]) -> float:
    """One decode step, whatever computes it: :func:`fixed_weight_bytes`;
    the three matrices of every DISTINCT expert the step's tokens picked
    in each expert layer (the program's count: ``experts_touched`` on the
    ``segment`` spans is the mean a layer-step); every live K and V row
    of every decoding slot in each attention layer (live rows as
    ``costs_dots3._mean_rows`` counts them); the decoding slots' conv
    states in and out in each conv layer.  Activations, the rows written
    and the logits are left out (under 0.5%)."""
    cfg = ctx["config"]
    touched = _span_args(ctx, "segment", "experts_touched")
    if not touched:
        return 0.0
    d = _dims(cfg)
    expert = 3 * d["h"] * int(cfg["moe_intermediate_size"]) * d["size"]
    return (fixed_weight_bytes(cfg)
            + (d["n"] - d["dense"]) * expert * sum(touched) / len(touched)
            + d["attn"] * 2 * d["kv"] * d["hd"] * d["size"] * _mean_rows(
                ctx, lambda L: L)
            + d["conv"] * 2 * conv_state_bytes(cfg) * _slots_a_step(ctx))

"""Bytes a decode step of the Ouro cells must move, from shapes, live
lengths and the program's own spans — the numerator of the looped
step's share of the memory roof, kept with the benchmark like
``costs.py``, ``costs_latent.py``, ``costs_dots3.py`` and
``costs_laguna.py``."""

from __future__ import annotations

from typing import Any, Dict

from .costs_dots3 import _ITEMSIZE, _mean_rows


def layer_params(cfg: Dict[str, Any]) -> int:
    """One layer's parameters: the four projections, the SwiGLU's three
    matrices and the four norms' gains."""
    h, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    q, kv = int(cfg["num_attention_heads"]) * hd, int(
        cfg["num_key_value_heads"]) * hd
    return (2 * h * q + 2 * h * kv + 3 * h * int(cfg["intermediate_size"])
            + 4 * h)


def token_cache_bytes(cfg: Dict[str, Any]) -> int:
    """A token's cache: its rotated K row and its V row in every layer of
    every pass (1,572,864 B at the published sizes)."""
    return (int(cfg["total_ut_steps"]) * int(cfg["num_hidden_layers"]) * 2
            * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
            * _ITEMSIZE[cfg["dtype"]])


def loop_step_bytes(ctx: Dict[str, Any]) -> float:
    """One decode step, whatever computes it: the layers' parameters once
    a PASS (pass ``u + 1`` of the first layer needs pass ``u`` of the
    last, so the mathematics reads them ``total_ut_steps`` times), the
    final norm and the exit gate once a pass, the head matrix once, and
    every live row of every decoding slot in every (pass, layer) — live
    rows as ``costs_dots3._mean_rows`` counts them.  The embedding's 16
    rows and the rows written are left out (under 0.01%)."""
    cfg = ctx["config"]
    h, U = int(cfg["hidden_size"]), int(cfg["total_ut_steps"])
    weights = (U * (int(cfg["num_hidden_layers"]) * layer_params(cfg)
                    + 2 * h + 1) + h * int(cfg["vocab_size"]))
    return (weights * _ITEMSIZE[cfg["dtype"]]
            + token_cache_bytes(cfg) * _mean_rows(ctx, lambda L: L))

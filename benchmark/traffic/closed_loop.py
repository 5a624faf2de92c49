"""Closed-loop traffic for step cells: one client, one step in flight.

The data file gives the batch shape; the token ids are a pure function
of ``--seed``."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from .open_loop import _rng


def input_ids(traffic: Dict[str, Any], vocab_size: int,
              seed: int) -> np.ndarray:
    shape = (int(traffic["batch"]), int(traffic["seq_len"]))
    return _rng(seed, 4).randint(0, vocab_size, size=shape).astype(np.int32)

"""Pallas TPU kernels for the hot ops, with XLA reference paths.

Dense (prefill-shaped) attention has two entry points, told apart by the
layout the caller holds:

* ``mha`` / ``gqa_mha`` (``ops/attention.py``) take ``(B, H, T, hd)``,
  heads major: ``models/llama.py`` (Mixtral shares it) and
  ``parallel/ulysses.py`` hold that layout.
* ``mha_rows`` (``ops/flash_rows.py``) takes the ``(B, T, 3·D)`` result
  of the q/k/v projection as it lies (or three ``(B, T, D)``), tokens
  major, and writes ``(B, T, D)``: ``models/gpt2.causal_attention`` — the
  attention task of the forward and train-step DAGs — holds that one.

Both dispatch per platform when the caller leaves ``impl`` on auto: the
hand-written Pallas kernel on TPU, the plain-XLA path elsewhere or when
the shape does not qualify; an explicit ``impl`` is a request and raises
when it cannot be honoured.  Which form a ``mha_rows`` call runs is ONE
shape rule, ``rows_supported`` (the row whole 128-lane tiles of heads, T
whole 128-row blocks, bf16 / float32: GPT-2 small / medium / large;
GPT-2 XL's 25 heads are 12.5 tiles): what it refuses is split into
heads, handed to ``mha`` and merged, as before.  Differentiation works
via a custom_vjp (rematerializing backward).  Tests pin
``impl="pallas_interpret"`` vs ``impl="xla"`` to check kernel numerics
on CPU.
"""

from .attention import gqa_mha, mha, pallas_supported, reference_mha
from .flash_rows import mha_rows, rows_supported

__all__ = [
    "mha",
    "gqa_mha",
    "mha_rows",
    "reference_mha",
    "pallas_supported",
    "rows_supported",
]

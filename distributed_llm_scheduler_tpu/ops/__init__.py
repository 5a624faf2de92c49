"""Pallas TPU kernels for the hot ops, with XLA reference paths.

``mha``/``gqa_mha`` (fused flash attention) dispatch per platform when the
caller leaves ``impl`` on auto: the hand-written Pallas kernel on TPU, the
plain-XLA path elsewhere or when the shape does not qualify.  An explicit
``impl`` is a request and raises when it cannot be honoured.  The model
families' attention routes through these unconditionally
(``models/gpt2.py``, ``models/llama.py`` — Mixtral shares Llama's);
differentiation works via a custom_vjp (rematerializing backward).  Tests
pin ``impl="pallas_interpret"`` vs ``impl="xla"`` to check kernel numerics
on CPU.
"""

from .attention import gqa_mha, mha, pallas_supported, reference_mha

__all__ = [
    "mha",
    "gqa_mha",
    "reference_mha",
    "pallas_supported",
]

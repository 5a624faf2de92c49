"""Parameter and activation sharding rules for the model families.

Megatron-style tensor parallelism expressed as GSPMD sharding annotations —
no hand-written collectives.  The forward is written as a *global* program
(models/gpt2.py); `NamedSharding` placement of params + inputs makes XLA
partition the matmuls and insert the per-layer all-reduces.  The rules are
each family's own (``PARAM_RULES`` in its module under models/):

* qkv / mlp-expand weights: column-sharded over ``tp`` (output features);
* attn-proj / mlp-contract weights: row-sharded over ``tp`` (input
  features) — their matmul results are partial sums XLA all-reduces;
* biases follow their weight's output sharding; LN/scalars replicated;
* embedding table row-(vocab-)sharded over ``tp`` for memory, positions
  replicated; activations batch-sharded over ``dp`` (and sequence over
  ``sp`` when ring attention is active).
"""

from __future__ import annotations

import re
from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import family_module

def param_spec(name: str, family: str = "gpt2") -> P:
    # stacked-layer params (models/gpt2.stack_layer_params): the leading
    # layer dim is never sharded; the per-layer spec shifts right by one
    if name.startswith("layers_"):
        return P(None, *param_spec(name[len("layers_"):], family))
    # the family's own Megatron rules (pattern -> spec, checked in order);
    # a family without any is replicated
    for pattern, spec in getattr(family_module(family), "PARAM_RULES", ()):
        if re.search(pattern, name):
            return spec
    return P()


def param_shardings(
    mesh: Mesh, params: Dict[str, Any], family: str = "gpt2"
) -> Dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, param_spec(k, family)) for k in params}


def shard_params(
    mesh: Mesh, params: Dict[str, Any], family: str = "gpt2"
) -> Dict[str, Any]:
    """device_put the whole param dict according to the rules."""
    shardings = param_shardings(mesh, params, family)
    return {k: jax.device_put(v, shardings[k]) for k, v in params.items()}


def batch_sharding(mesh: Mesh, seq_parallel: bool = False) -> NamedSharding:
    """(B, T) token batches: batch over dp, optionally sequence over sp."""
    return NamedSharding(mesh, P("dp", "sp" if seq_parallel else None))


def activation_sharding(mesh: Mesh, seq_parallel: bool = False) -> NamedSharding:
    """(B, T, D) activations: batch over dp, optionally sequence over sp."""
    return NamedSharding(mesh, P("dp", "sp" if seq_parallel else None, None))

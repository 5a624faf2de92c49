"""Tensor-parallel autoregressive decoding over a device mesh.

Single-chip decoding (:mod:`..models.decode`) cannot serve a model whose
weights exceed one chip's HBM (Llama-3 8B bf16 is ~16 GB against a v5e's
~14 usable) — the model must be sharded to be *runnable at all*, the same
reason the reference schedules models across memory-constrained nodes at
all (its founding premise, reference paper §1).  This module makes the
KV-cache generation loop mesh-parallel the GSPMD way:

* params ``device_put`` with the family's Megatron rules
  (:mod:`.sharding` — qkv/gate/up column-sharded over ``tp``, proj/down
  row-sharded, so tp must divide ``n_kv_heads``);
* the UNCHANGED family ``generate`` program is jitted against those
  shardings — XLA partitions every matmul and inserts the per-layer
  all-reduces, and the KV cache inherits the head sharding through
  propagation (k = x @ wk keeps the tp split through the reshape to
  heads).  No collective is hand-written, no decode-path fork exists:
  sharded and single-chip generation are the same traced program under
  different placements, so they cannot drift.

Works identically on a real TPU slice and the CPU-faked mesh (tests pin
token-exactness against single-device generation).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import cache_spec, family_of, module_of
from .sharding import param_spec, shard_params


def shard_decode_params(
    mesh: Mesh, params: Dict[str, Any], config: Any
) -> Dict[str, Any]:
    """Place a family's params onto ``mesh`` under its Megatron rules.

    Validates the head-divisibility precondition up front (an uneven
    NamedSharding split fails deep inside device_put otherwise).
    """
    family = family_of(config)
    tp = mesh.shape.get("tp", 1)
    if tp > 1:
        # every dimension the family's rules split over tp must divide
        # evenly: the qkv / kv column split means the (kv-)head count, an
        # untied (d, vocab) head the vocabulary
        kv_heads = cache_spec(config).rows[0][1][0]
        if kv_heads % tp != 0:
            raise ValueError(
                f"tp={tp} must divide the (kv-)head count {kv_heads} for "
                "the attention column split (pick a smaller tp)"
            )
        for name, value in params.items():
            for axis, dim in zip(param_spec(name, family), value.shape):
                if axis == "tp" and dim % tp != 0:
                    raise ValueError(
                        f"tp={tp} must divide dimension {dim} of {name} "
                        f"{tuple(value.shape)} for its column / row split "
                        "(pick a smaller tp)"
                    )
    return shard_params(mesh, params, family)


def generate_sharded(
    params: Dict[str, Any],
    prompt_ids: jax.Array,
    config: Any,
    mesh: Mesh,
    max_new_tokens: int,
    key: Optional[jax.Array] = None,
    **kw,
) -> jax.Array:
    """Mesh-parallel generation: shard params, replicate the (small) token
    prompt, and run the family's unchanged ``generate``.

    The data-parallel axis shards the batch when it divides evenly
    (replicated otherwise — a batch of 1 prompt is the common decode
    case and dp>1 would idle anyway).
    """
    params = shard_decode_params(mesh, params, config)
    dp = mesh.shape.get("dp", 1)
    B = prompt_ids.shape[0]
    spec = P("dp", None) if (dp > 1 and B % dp == 0) else P()
    prompt_ids = jax.device_put(prompt_ids, NamedSharding(mesh, spec))
    return module_of(config).generate(
        params, prompt_ids, config, max_new_tokens, key=key, **kw
    )


def collective_probe(devices=None):
    """``(fn, example_avals)`` for the analysis sweep (lint --parallel):
    tensor-parallel greedy decode of 2 tokens on tiny GPT-2, abstract
    params via ``eval_shape``.  Megatron collectives are GSPMD-derived,
    so the sweep mostly proves the sharded decode still traces."""
    import jax.numpy as jnp
    import numpy as np

    from ..models import gpt2

    devs = list(devices if devices is not None else jax.devices())
    tp = 2 if len(devs) >= 2 else 1  # tiny() has n_head=4: tp=2 divides
    mesh = Mesh(np.array(devs[:tp]).reshape(1, 1, tp), ("dp", "sp", "tp"))
    config = gpt2.GPT2Config.tiny()
    params = jax.eval_shape(
        lambda key: gpt2.init_params(config, key), jax.random.PRNGKey(0)
    )
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)

    def fn(params, ids):
        return generate_sharded(params, ids, config, mesh, 2)

    return fn, (params, ids)

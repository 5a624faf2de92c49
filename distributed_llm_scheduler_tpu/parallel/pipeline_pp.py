"""Whole-program pipeline parallelism: one compiled GPipe scan over a
``pp`` mesh axis.

The task-graph path already pipelines microbatches ACROSS compiled tasks
(``sched/pipeline.py`` places contiguous stages, ``sched/eventsim.py``
orders them 1F1B, the device backend dispatches that order).  This module
is the same idea expressed the whole-program way: the entire pipeline —
every stage, every microbatch, every inter-stage hop — is ONE jitted
program in which stages are mesh shards and activations travel by
``lax.ppermute`` over ICI, with zero host involvement per hop.

The classic single-scan formulation (cf. the public scaling-book recipe):
with S stages and M microbatches, step ``t`` of an ``M + S - 1``-step
``lax.scan`` has stage ``s`` processing microbatch ``t - s`` (when that
index is live).  Each step every device ppermutes its previous output to
its successor, selects its input (stage 0: the next embedded microbatch;
others: the received activation), and runs its block slice.  The fill/
drain bubbles compute on zero activations — wasted FLOPs by design, the
textbook pipeline bubble ``(S-1)/(M+S-1)``, masked out of the result.

Layer blocks within a stage run under ``lax.scan`` over stacked params
(the same scanned-block formulation as ``models/gpt2.forward_scan``), so
program size is O(1) in depth.  Embedding/head params are replicated
(only the edge stages read them — the standard GPipe embedding placement
trade, noted rather than hidden).  The LM head runs once, after the
scan, on the collected stage-(S-1) activations.

The pipeline DIFFERENTIATES: reverse-mode AD through the ppermute scan is
the backward pipeline (ppermute transposes to the reverse hop; the scan
transposes to the reverse schedule), so :func:`pp_loss_fn` +
``jax.value_and_grad`` is pipeline-parallel training with no extra code —
gradients match the plain forward's to float precision
(``tests/test_pipeline_pp.py``).  :func:`make_pp_train_step` packages it
with an optimizer the same way ``parallel/train.py`` does for dp/tp.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import cache_spec, mixtral, module_of


def _stack_stage_params(
    mod: Any, params: Dict[str, jax.Array], config: Any, n_stages: int,
    n_layers: int,
) -> Dict[str, jax.Array]:
    """Per-layer tensors -> ``(S, L/S, ...)`` stage stacks: the family's
    public scanned layout (``stack_layer_params``) with its layer axis
    folded into (stage, layer-in-stage)."""
    stacked = mod.stack_layer_params(params, config)
    per = n_layers // n_stages
    return {
        k[len("layers_"):]: v.reshape(n_stages, per, *v.shape[1:])
        for k, v in stacked.items()
        if k.startswith("layers_")
    }


def pipeline_forward(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    config: Any,
    mesh: Mesh,
    microbatches: int,
    remat: bool = False,
) -> jax.Array:
    """Any family's forward as a pp-sharded pipeline; (B, T) -> (B, T, V).

    Requires ``n_layers % pp == 0`` and ``B % microbatches == 0``.
    Matches the family's plain ``forward`` exactly (same block math, same
    order) — the pipeline changes WHERE layers run, not what they compute.
    ``remat=True`` checkpoints each layer block, so the backward pipeline
    recomputes block activations instead of storing every step's — the
    same HBM-for-FLOPs trade as the dp/tp path's ``remat``.
    """
    # the only family-specific pieces — its module's block, embedding and
    # head; the pipeline scan itself is identical for every family
    mod, L = module_of(config), cache_spec(config).n_layers
    S = mesh.shape["pp"]
    B, M = input_ids.shape[0], microbatches
    if L % S != 0:
        raise ValueError(f"n_layers {L} not divisible by pp={S}")
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mb = B // M
    T = input_ids.shape[1]

    stage_params = _stack_stage_params(mod, params, config, S, L)
    shared = {k: params[k] for k in (*mod.EMBED_PARAMS, *mod.HEAD_PARAMS)}
    ids_mb = input_ids.reshape(M, mb, T)
    D = jax.eval_shape(
        lambda sp, ids: mod.embed(sp, ids, config), shared, ids_mb[0]
    ).shape[-1]

    stage_specs = {k: P("pp") for k in stage_params}

    def shard_fn(stage_p, shared_p, ids_mb):
        s = lax.axis_index("pp")
        # (1, L/S, ...) local slice -> (L/S, ...)
        my_layers = {k: v[0] for k, v in stage_p.items()}

        block_fn = (
            jax.checkpoint(mod.transformer_block, static_argnums=(2,))
            if remat else mod.transformer_block
        )

        def run_stage(x):
            def block_step(h, layer_params):
                return block_fn(layer_params, h, config), None

            y, _ = lax.scan(block_step, x, my_layers)
            return y

        perm = [(i, i + 1) for i in range(S - 1)]

        def step(carry, t):
            prev_out, out_buf = carry
            # successor hop: device s receives s-1's previous output
            # (device 0 receives zeros — it sources from the embedding)
            recv = lax.ppermute(prev_out, "pp", perm) if S > 1 else prev_out
            x0 = mod.embed(shared_p, ids_mb[jnp.clip(t, 0, M - 1)], config)
            x = jnp.where(s == 0, x0, recv)
            y = run_stage(x)
            widx = t - (S - 1)
            valid = (widx >= 0) & (widx < M)
            upd = lax.dynamic_update_index_in_dim(
                out_buf, y, jnp.clip(widx, 0, M - 1), axis=0
            )
            out_buf = jnp.where(valid, upd, out_buf)
            return (y, out_buf), None

        init = (
            jnp.zeros((mb, T, D), jnp.float32).astype(config.dtype),
            jnp.zeros((M, mb, T, D), jnp.float32).astype(config.dtype),
        )
        (_, out_buf), _ = lax.scan(
            step, init, jnp.arange(M + S - 1), length=M + S - 1
        )
        # replicate only the (M, mb, T, D) activations — psumming logits
        # here would move V/D (~65x for real GPT-2) more bytes, and the
        # head runs ONCE, outside the shard_map, on the gathered result
        return lax.psum(jnp.where(s == S - 1, out_buf, 0), "pp")

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stage_specs, {k: P() for k in shared}, P()),
        out_specs=P(),
        check_vma=False,
    )
    acts = fn(
        {
            k: jax.device_put(v, NamedSharding(mesh, P("pp")))
            for k, v in stage_params.items()
        },
        shared,
        ids_mb,
    )
    return mod.head(params, acts.reshape(B, T, -1), config)


def pp_loss_fn(
    params: Dict[str, jax.Array],
    input_ids: jax.Array,
    targets: jax.Array,
    config: Any,
    mesh: Mesh,
    microbatches: int,
    remat: bool = False,
) -> jax.Array:
    """Next-token cross-entropy through the pipelined forward.

    Differentiable end-to-end: ``jax.grad`` of this IS pipeline-parallel
    backprop (the scan/ppermute transpose is the backward pipeline).
    """
    logits = pipeline_forward(
        params, input_ids, config, mesh, microbatches, remat=remat
    )
    # the one shared next-token cross-entropy (models/mixtral.nll_loss —
    # also used by the EP path), not a fifth copy of the same math
    return mixtral.nll_loss(logits, targets)


def make_pp_train_step(
    config: Any,
    mesh: Mesh,
    microbatches: int,
    optimizer: Any = None,
    remat: bool = False,
):
    """``(train_step, init_state)`` for pipeline-parallel training, the
    same contract as :func:`.train.make_train_step` (jitted step with
    donated state; params flat — the pipeline stacks them per call, so
    checkpoints stay in the shared flat layout)."""
    from .train import make_step_from_loss

    mod = module_of(config)

    def loss(params, input_ids, targets):
        return pp_loss_fn(
            params, input_ids, targets, config, mesh, microbatches,
            remat=remat,
        )

    return make_step_from_loss(
        loss, lambda key: mod.init_params(config, key), optimizer
    )


def collective_probe(devices=None):
    """``(fn, example_avals)`` for the analysis sweep (lint --parallel):
    the whole-program GPipe scan on a 2-stage pp mesh (1 stage on a
    single device), tiny GPT-2, abstract params via ``eval_shape`` — the
    successor-hop ppermute and the final psum land in the traced jaxpr
    for the COL003/COL004 checks."""
    import numpy as np

    from ..models import gpt2

    devs = list(devices if devices is not None else jax.devices())
    S = 2 if len(devs) >= 2 else 1
    mesh = Mesh(np.array(devs[:S]), ("pp",))
    config = gpt2.GPT2Config.tiny()
    params = jax.eval_shape(
        lambda key: gpt2.init_params(config, key), jax.random.PRNGKey(0)
    )
    ids = jax.ShapeDtypeStruct((4, 8), jnp.int32)

    def fn(params, ids):
        return pipeline_forward(params, ids, config, mesh, microbatches=2)

    return fn, (params, ids)

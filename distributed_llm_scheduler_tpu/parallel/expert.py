"""Expert parallelism: Mixtral experts sharded over an ``ep`` mesh axis.

The task-graph frontend places experts as independently cacheable tasks
(``frontend/moe_dag.py``); this module is the *execution-strategy* form of
the same capability: true expert parallelism inside one
jitted train/forward step, the capability the reference cannot express at
all (its only distribution axis is task placement, reference
``schedulers.py:31-135``).

TPU-idiomatic formulation — no per-expert Python loop, no NCCL-style
all-to-all calls:

* per-expert weights are **stacked** on a leading expert dim:
  ``l{i}_moe_gate/up/down`` with shapes ``(E, d, f)`` / ``(E, f, d)``;
* the stacked dim is sharded ``P("ep")`` — each device holds and computes
  only ``E / ep`` experts;
* the MoE block is three einsums over the expert dim (dense dispatch: every
  expert sees every token, selection via the dense top-k gate from
  :func:`..models.mixtral.router_weights`).  The final combine contracts
  the expert dim, which XLA turns into the psum over ``ep`` — the
  collective is *derived*, not hand-written;
* tokens stay sharded over ``dp`` throughout, so the device holding expert
  e computes it for its own batch shard only (the classic dense-MoE
  dp x ep decomposition).

Dense dispatch is the static-shape trade the model family already makes
(see ``models/mixtral.py`` module doc): capacity-based token dropping or
ragged all-to-alls would break XLA's static shapes for no fidelity gain at
task-DAG scale.  The FLOP overcount vs top-k routing is disclosed there.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import mixtral
from ..models.mixtral import MixtralConfig

_EXPERT_SUFFIXES = ("w_gate", "w_up", "w_down")


def stack_expert_params(
    params: Dict[str, Any], config: MixtralConfig
) -> Dict[str, Any]:
    """Per-expert ``l{i}_e{e}_w_*`` arrays -> stacked ``l{i}_moe_*``.

    The flat per-expert layout is the task-graph vocabulary (one cacheable
    param set per expert task); the stacked layout is the EP-execution
    vocabulary.  Both carry identical numbers; this is a pure re-index.
    """
    out = {
        k: v
        for k, v in params.items()
        if "_e" not in k or not any(k.endswith(s) for s in _EXPERT_SUFFIXES)
    }
    for i in range(config.n_layers):
        for suffix in _EXPERT_SUFFIXES:
            out[f"l{i}_moe_{suffix[2:]}"] = jnp.stack(
                [
                    params[f"l{i}_e{e}_{suffix}"]
                    for e in range(config.n_experts)
                ]
            )
    return out


def unstack_expert_params(
    params: Dict[str, Any], config: MixtralConfig
) -> Dict[str, Any]:
    """Inverse of :func:`stack_expert_params` (checkpoint interchange)."""
    out = {k: v for k, v in params.items() if "_moe_" not in k}
    for i in range(config.n_layers):
        for suffix in _EXPERT_SUFFIXES:
            stacked = params[f"l{i}_moe_{suffix[2:]}"]
            for e in range(config.n_experts):
                out[f"l{i}_e{e}_{suffix}"] = stacked[e]
    return out


def _moe_stacked(
    block_params: Dict[str, Any], x: jax.Array, config: MixtralConfig
) -> jax.Array:
    """Router + stacked-expert SwiGLU + combine over UNPREFIXED names —
    the single implementation of the stacked MoE math (cf.
    ``models.mixtral._moe`` for the per-expert layout).  Under a mesh the
    ``e`` dims partition over ``ep`` and the final contraction becomes
    the cross-expert psum."""
    w = mixtral.router_weights(x, block_params["router"], config.top_k)
    gate, up, down = (
        block_params["moe_gate"], block_params["moe_up"],
        block_params["moe_down"],
    )
    g = jax.nn.silu(jnp.einsum("btd,edf->ebtf", x, gate))
    u = jnp.einsum("btd,edf->ebtf", x, up)
    y = jnp.einsum("ebtf,efd->ebtd", g * u, down)
    return jnp.einsum("bte,ebtd->btd", w, y).astype(x.dtype)


def moe_block_stacked(
    params: Dict[str, Any], x: jax.Array, layer: int, config: MixtralConfig
) -> jax.Array:
    """Layer-prefixed wrapper over :func:`_moe_stacked` (matches
    :func:`..models.mixtral.moe_block` numerically — same math,
    reassociated)."""
    p = f"l{layer}_"
    keys = ("router", "moe_gate", "moe_up", "moe_down")
    return _moe_stacked({k: params[p + k] for k in keys}, x, config)


def moe_routed_stacked(
    block_params: Dict[str, Any],
    x: jax.Array,
    config: MixtralConfig,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
    with_stats: bool = False,
):
    """Routed (capacity-buffer) MoE over STACKED expert weights, sharded
    over the ``ep`` axis — composing
    :func:`..models.mixtral.moe_routed`'s sparse dispatch with expert
    parallelism, so the top_k/E FLOP saving survives exactly where expert
    placement matters.

    TPU-idiomatic formulation: the computation is written in the GLOBAL
    view — tokens scatter-add into an ``(E, C, D)`` capacity buffer,
    experts run as one batched einsum, outputs gather back — and
    ``with_sharding_constraint`` pins the buffer's expert dim to ``ep``
    and the token dims to ``dp``.  The token exchange between dp-sharded
    activations and ep-sharded buffers IS the all-to-all; XLA derives the
    collective from the constraint pair rather than us hand-writing it
    (the scaling-book recipe: annotate, let GSPMD insert collectives).
    ``mesh=None`` skips constraints (single-device tests).

    Routing math is :mod:`..models.mixtral`'s shared primitives
    (``route_topk`` / ``routed_dispatch`` / ``routed_collect``) — one
    source of truth across the whole-program, EP, and task-graph paths.
    """
    B, T, D = x.shape
    E, k = config.n_experts, config.top_k
    N = B * T
    C = mixtral.moe_capacity(N, E, k, capacity_factor)
    xf = x.reshape(N, D)

    route = mixtral.route_topk(xf, block_params["router"], k, C, x.dtype)
    buf = mixtral.routed_dispatch(xf, route, E, C)
    if mesh is not None:
        buf = jax.lax.with_sharding_constraint(
            buf, NamedSharding(mesh, P("ep", None, None))
        )

    gate, up, down = (
        block_params["moe_gate"], block_params["moe_up"],
        block_params["moe_down"],
    )
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, up
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, down)  # (E, C, D)
    if mesh is not None:
        out_buf = jax.lax.with_sharding_constraint(
            out_buf, NamedSharding(mesh, P("ep", None, None))
        )

    out = mixtral.routed_collect(out_buf, route, N).reshape(B, T, D)
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P("dp", None, None))
        )
    if with_stats:
        return out, mixtral.route_stats(route, C)
    return out


_EP_BLOCK_KEYS = (
    "attn_norm_g", "wq", "wk", "wv", "wo", "ffn_norm_g", "router",
    "moe_gate", "moe_up", "moe_down",
)


def _make_ep_block(
    config: MixtralConfig,
    routed: bool = False,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
    stats_sink: Optional[list] = None,
) -> Callable[[Dict[str, Any], jax.Array], jax.Array]:
    """One EP layer over unprefixed params — the rematerialization unit.
    ``routed=True`` swaps dense dispatch for the capacity-buffer sparse
    dispatch (:func:`moe_routed_stacked`).  ``stats_sink`` (routed only):
    a list the block appends each layer's drop stats to at trace time —
    the ONE block body serves both the plain and the stats-collecting
    forward, so they cannot drift."""

    def block(block_params: Dict[str, Any], x: jax.Array) -> jax.Array:
        h = mixtral.rms_norm(x, block_params["attn_norm_g"], config.rms_eps)
        h = mixtral.gqa_attention(
            h, block_params["wq"], block_params["wk"], block_params["wv"],
            block_params["wo"], config.n_heads, config.n_kv_heads,
            config.rope_theta,
        )
        x2 = mixtral.residual_add(x, h)
        h = mixtral.rms_norm(x2, block_params["ffn_norm_g"], config.rms_eps)
        if routed:
            if stats_sink is not None:
                moe, st = moe_routed_stacked(
                    block_params, h, config, capacity_factor, mesh=mesh,
                    with_stats=True,
                )
                stats_sink.append(st)
            else:
                moe = moe_routed_stacked(
                    block_params, h, config, capacity_factor, mesh=mesh
                )
        else:
            moe = _moe_stacked(block_params, h, config)
        return mixtral.residual_add(x2, moe)

    return block


def _ep_block(
    block_params: Dict[str, Any], x: jax.Array, config: MixtralConfig
) -> jax.Array:
    """Dense EP layer (kept as the named entry point for existing callers)."""
    return _make_ep_block(config)(block_params, x)


def forward_ep(
    params: Dict[str, Any],
    input_ids: jax.Array,
    config: MixtralConfig,
    remat: bool = False,
    routed: bool = False,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
    _stats_sink: Optional[list] = None,
) -> jax.Array:
    """Mixtral forward over stacked expert params (the EP train/eval path).

    Shares :func:`..models.mixtral.forward_with_block`'s skeleton; only
    the layer block differs in layout.  ``remat=True`` checkpoints each
    layer — especially valuable under EP, where the dense-dispatch expert
    activations ``(E, B, T, ffn)`` dominate HBM.  ``routed=True`` uses
    capacity-buffer sparse dispatch (top_k/E of the dense FLOPs, plus
    capacity slack; see :func:`moe_routed_stacked`).
    """
    if _stats_sink is not None and remat:
        # jax.checkpoint replays the block; trace-time appends would double
        raise ValueError("stats collection is incompatible with remat")
    block = _make_ep_block(config, routed, capacity_factor, mesh, _stats_sink)
    return mixtral.forward_with_block(
        params, input_ids, config,
        lambda bp, x, cfg: block(bp, x), _EP_BLOCK_KEYS, remat=remat,
    )


def loss_fn_ep(params, input_ids, targets, config: MixtralConfig,
               remat: bool = False, routed: bool = False,
               capacity_factor: float = 2.0, mesh: Optional[Mesh] = None):
    return mixtral.nll_loss(
        forward_ep(params, input_ids, config, remat=remat, routed=routed,
                   capacity_factor=capacity_factor, mesh=mesh), targets
    )


def forward_ep_stats(
    params: Dict[str, Any],
    input_ids: jax.Array,
    config: MixtralConfig,
    capacity_factor: float = 2.0,
    mesh: Optional[Mesh] = None,
):
    """Routed-EP forward that also aggregates per-layer drop statistics
    (total dropped vs total (token, slot) assignments across layers) —
    the observability the routed trade needs to be honest about.
    Returns ``(logits, stats)``.  Same block body as :func:`forward_ep`
    (stats flow out through the block's sink, so the two paths cannot
    drift)."""
    sink: list = []
    logits = forward_ep(
        params, input_ids, config, routed=True,
        capacity_factor=capacity_factor, mesh=mesh, _stats_sink=sink,
    )
    dropped = sum(
        (st["dropped_slots"].astype(jnp.int32) for st in sink),
        jnp.zeros((), jnp.int32),
    )
    return logits, {
        "dropped_slots": dropped,
        "total_slots": sum(st["total_slots"] for st in sink),
        "capacity": sink[-1]["capacity"] if sink else None,
    }


# -- sharding rules ----------------------------------------------------------

def ep_param_spec(name: str) -> P:
    """Stacked expert tensors shard their expert dim over ``ep``; everything
    else (attention, norms, router, embeddings) is replicated — combine
    with tp rules when a tp axis exists (not needed at task-DAG scale)."""
    if "_moe_" in name:
        return P("ep")
    return P()


def ep_param_shardings(
    mesh: Mesh, params: Dict[str, Any]
) -> Dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, ep_param_spec(k)) for k in params}


def shard_ep_params(mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
    sh = ep_param_shardings(mesh, params)
    return {k: jax.device_put(v, sh[k]) for k, v in params.items()}


# -- train step --------------------------------------------------------------

def make_moe_train_step(
    config: MixtralConfig,
    mesh: Mesh,
    optimizer: Optional[Any] = None,
    learning_rate: float = 3e-4,
    remat: bool = False,
    routed: bool = False,
    capacity_factor: float = 2.0,
) -> Tuple[Callable[..., Any], Callable[..., Any]]:
    """dp x ep sharded Mixtral training step; returns ``(step, init)``.

    Mirrors :func:`.train.make_train_step`'s contract: ``init(key)`` builds
    sharded stacked params + optimizer state on the mesh; ``step(state,
    ids, targets) -> (state, loss)`` is one jitted program with donated
    state.  The mesh must define ``dp`` and ``ep`` axes (``ep`` must divide
    ``n_experts``).  ``remat=True`` checkpoints each layer.
    ``routed=True`` trains through the capacity-buffer sparse dispatch
    (:func:`moe_routed_stacked`) — dropped assignments get zero gradient,
    the Switch/GShard trade.
    """
    import optax

    from .train import TrainState

    if config.n_experts % mesh.shape["ep"] != 0:
        raise ValueError(
            f"ep={mesh.shape['ep']} must divide n_experts={config.n_experts}"
        )
    optimizer = optimizer or optax.adamw(learning_rate, weight_decay=0.01)
    data_sh = NamedSharding(mesh, P("dp", None))

    def init_state(key: Optional[jax.Array] = None) -> TrainState:
        key = key if key is not None else jax.random.PRNGKey(0)
        params = shard_ep_params(
            mesh, stack_expert_params(mixtral.init_params(config, key), config)
        )
        return TrainState(
            params=params,
            opt_state=optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
        )

    def step_fn(state: TrainState, input_ids, targets):
        loss, grads = jax.value_and_grad(loss_fn_ep)(
            state.params, input_ids, targets, config, remat,
            routed, capacity_factor, mesh,
        )
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    jitted = jax.jit(
        step_fn, in_shardings=(None, data_sh, data_sh), donate_argnums=(0,)
    )

    def train_step(state: TrainState, input_ids, targets):
        input_ids = jax.device_put(input_ids, data_sh)
        targets = jax.device_put(targets, data_sh)
        return jitted(state, input_ids, targets)

    return train_step, init_state


def collective_probe(devices=None):
    """``(fn, example_avals)`` for the analysis sweep (lint --parallel):
    the routed capacity-buffer MoE on a dp x ep mesh.  The all-to-all
    here is GSPMD-derived from the sharding-constraint pair, so the
    traced jaxpr mostly validates that the strategy still traces; any
    hand-written collective that creeps in gets the COL003/COL004
    checks."""
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    ep = 2 if len(devs) >= 2 else 1
    dp = 2 if len(devs) >= 4 else 1
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep), ("dp", "ep"))
    config = MixtralConfig.tiny()
    D, E, F = config.d_model, config.n_experts, config.ffn_hidden
    bp = {
        "router": jax.ShapeDtypeStruct((D, E), config.dtype),
        "moe_gate": jax.ShapeDtypeStruct((E, D, F), config.dtype),
        "moe_up": jax.ShapeDtypeStruct((E, D, F), config.dtype),
        "moe_down": jax.ShapeDtypeStruct((E, F, D), config.dtype),
    }
    x = jax.ShapeDtypeStruct((2, 8, D), config.dtype)

    def fn(bp, x):
        return moe_routed_stacked(bp, x, config, mesh=mesh)

    return fn, (bp, x)

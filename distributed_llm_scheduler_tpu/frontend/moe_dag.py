"""Mixtral MoE forward DAG builder: expert nodes as tasks
(BASELINE.json config #4).

Per layer the tasks are {attn_norm, attention, attn_residual, ffn_norm,
router, expert_0..E-1, moe_combine, layer_output} — ``7 + E`` tasks/layer —
plus embedding, final_norm, lm_head: ``(7 + n_experts) * n_layers + 3``
(483 for Mixtral-8x7B).  Each expert task owns that expert's three FFN
matrices (~176 MB each for 8x7B), so placement of experts under per-core
HBM limits is exactly the param-cache-locality problem the reference's MRU
policy targets (SURVEY.md §7 stage 8: "expert-placement = param-cache
locality, MRU's sweet spot").  The reference itself has no MoE.

The backbone assembly lives in :mod:`.backbone`, shared with the Llama
frontend; only the router/experts/combine section is defined here.

Two dispatch modes:

* ``routed=False`` (default): experts compute densely (see
  :mod:`..models.mixtral` for why XLA historically wants that);
  expert-task FLOPs are recorded as the *useful* top_k/E fraction so
  cost-model comparisons against measured dense timings expose the
  overhead.
* ``routed=True``: each expert task computes ONLY its capacity buffer —
  the router task emits static-shape routing metadata (top-k weights,
  expert ids, in-expert positions, keep mask), each expert task
  scatter-selects its own ``(C, D)`` buffer from the activations and
  runs SwiGLU on that, and the combine gathers outputs back by the
  metadata.  Measured calibration then times the top_k/E-scaled compute
  the FLOPs field claims — the disclosed E/k inflation is gone exactly
  where expert placement matters.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax.numpy as jnp

from ..models import mixtral
from ..models.mixtral import MixtralConfig
from .backbone import build_decoder_dag
from .gpt2_dag import DEFAULT_EFFECTIVE_FLOPS, ModelDAG, graph_name_tags


def build_moe_dag(
    config: Optional[MixtralConfig] = None,
    batch: int = 1,
    seq_len: int = 512,
    microbatches: int = 1,
    vocab_shards: int = 1,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
    routed: bool = False,
    capacity_factor: float = 2.0,
) -> ModelDAG:
    """Build the per-op forward DAG for a Mixtral config, one task per
    expert."""
    config = config or MixtralConfig.mixtral_8x7b()
    if microbatches < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    D, F = config.d_model, config.ffn_hidden
    E, K = config.n_experts, config.top_k
    Bm = batch // microbatches
    T = seq_len

    def f_router(p, x):
        return mixtral.router_weights(x, p["w"], config.top_k)

    def f_expert(p, x):
        return mixtral.expert_ffn(x, p["w_gate"], p["w_up"], p["w_down"])

    def f_combine(p, weights, *outs):
        return mixtral.moe_combine(weights, *outs)

    # routed mode: static capacity per microbatch; all dispatch math comes
    # from models.mixtral's shared primitives (route_topk /
    # routed_expert_buffer / routed_collect) — one source of truth with
    # the whole-program and EP paths
    N = Bm * T
    C = mixtral.moe_capacity(N, E, K, capacity_factor)

    def f_router_routed(p, x):
        """Top-k routing metadata with static shapes (the task-graph form
        of moe_routed's dispatch prologue)."""
        return mixtral.route_topk(x.reshape(N, D), p["w"], K, C, x.dtype)

    def f_expert_routed(p, x, route, *, expert):
        """Scatter-select THIS expert's capacity buffer, then SwiGLU on
        (C, D) — top_k/E of the dense compute, matching the FLOPs field."""
        buf = mixtral.routed_expert_buffer(x.reshape(N, D), route, expert, C)
        return mixtral.expert_ffn(buf, p["w_gate"], p["w_up"], p["w_down"])

    def f_combine_routed(p, route, *bufs):
        out = mixtral.routed_collect(jnp.stack(bufs), route, N)
        return out.reshape(Bm, T, D)

    # one fn object per expert index, shared across layers AND
    # microbatches (partial binds the static index; param_alias feeds each
    # task its own expert's weights) — E compiles total, not E x layers
    routed_expert_fns = [
        partial(f_expert_routed, expert=e) for e in range(E)
    ]

    def ffn_section(add, mb, i, fnorm, grp):
        """Router + E expert tasks fanning out from the FFN norm, joined
        by the gate-weighted combine.  Dense mode: every expert sees every
        token; routed mode: every expert sees only its capacity buffer."""
        pre = f"l{i}_"
        router = f"{mb}layer_{i}_router"
        add(router,
            f_router_routed if routed else f_router,
            [fnorm], {"w": pre + "router"},
            2.0 * Bm * T * D * E, grp)

        expert_ids = []
        # useful-work fraction: each token activates top_k of E experts.
        # Dense mode computes E/K times this (disclosed); routed mode
        # actually computes it (capacity slack included via C)
        expert_flops = (
            (6.0 * C * D * F) + N * K * D  # FFN on the buffer + dispatch
            if routed
            else (6.0 * Bm * T * D * F) * (K / E)
        )
        for e in range(E):
            ex = f"{mb}layer_{i}_expert_{e}"
            add(ex,
                routed_expert_fns[e] if routed else f_expert,
                [fnorm, router] if routed else [fnorm],
                {"w_gate": f"{pre}e{e}_w_gate",
                 "w_up": f"{pre}e{e}_w_up",
                 "w_down": f"{pre}e{e}_w_down"},
                expert_flops, grp)
            expert_ids.append(ex)

        comb = f"{mb}layer_{i}_moe_combine"
        add(comb,
            f_combine_routed if routed else f_combine,
            [router] + expert_ids, {},
            2.0 * Bm * T * D * E, grp)
        return comb

    name = (
        f"mixtral_{config.n_layers}l_d{D}_e{E}_b{batch}_t{T}"
        + ("_routed" if routed else "")
        + graph_name_tags(microbatches, vocab_shards, config.dtype)
    )
    dag = build_decoder_dag(
        config, mixtral,
        batch=batch, seq_len=seq_len, microbatches=microbatches,
        effective_flops=effective_flops, ffn_section=ffn_section, name=name,
        vocab_shards=vocab_shards,
    )
    if routed:
        # the oracle for a routed DAG is the routed whole-program forward
        # applied PER MICROBATCH: the DAG routes each microbatch
        # independently (its own capacity + arrival order), so a
        # whole-batch routing oracle would drop different assignments
        # whenever microbatches > 1 and capacity bites
        def routed_reference(p, ids):
            outs = [
                mixtral.forward(
                    p, ids[m * Bm:(m + 1) * Bm], config,
                    routed=True, capacity_factor=capacity_factor,
                )
                for m in range(microbatches)
            ]
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs, 0)

        dag.reference_forward = routed_reference
    return dag

"""The paged decode step of a stack whose layers run more than once a
token: the passes as ONE traced loop.

``build_paged_decode_dag`` says what such a model is — embed -> [one task
a layer, the task that closes the pass] x ``passes`` -> logits, the tasks
of a layer all on the same weights (``frontend/decode_dag._looped_chain``)
— and a program that followed the graph task by task would hold the
layers' bodies ``passes`` times: the trace, the compile and the set-up of
a stack ``passes`` times as deep.  The composer here checks that the
later passes' tasks ARE the first pass's (the same ``fn`` objects, the
same aliases, the same order, each behind the task before it) and runs
that sub-chain under one ``lax.scan`` over the pass, the pools carried
and the weights closed over: the program holds one body a layer, as a
plain stack of the same depth does.

A layer's pool is ``passes`` planes of ``n_pages`` pages
(:class:`...models.kv_pages.CacheSpec`): the task reads the plane its
input edge's ``pass`` names, and its rows are written here, right after
it ran, through the same plane of the page table — plane ``u`` of layer
``l`` is read by pass ``u`` of layer ``l`` alone, before this write.
"""

from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp

from ..core.graph import TaskGraph


def _checked_passes(graph: TaskGraph, order: List[str]):
    """``(embed id, first pass's ids, head id)`` of a looped step graph
    whose placed ``order`` is the chain its builder made; raises where a
    later pass is not the first pass again."""
    passes = getattr(graph, "pass_tasks", None)
    if not passes:
        raise ValueError(
            "the graph names no passes (graph.pass_tasks): a looped step "
            "is built by build_paged_decode_dag")
    flat = [tid for mine in passes for tid in mine]
    if order != [order[0], *flat, order[-1]]:
        raise ValueError(
            "a looped step is embed -> the passes' tasks in order -> "
            f"logits; the placed order has {order[:3]} ... {order[-2:]}")
    prev = order[0]
    for tid in order[1:]:
        if list(graph[tid].dependencies) != [prev]:
            raise ValueError(
                f"task {tid!r} of a looped step must follow {prev!r} alone, "
                f"it depends on {list(graph[tid].dependencies)}")
        prev = tid
    first = passes[0]
    for u, mine in enumerate(passes[1:], start=1):
        if len(mine) != len(first):
            raise ValueError(
                f"pass {u} has {len(mine)} tasks, pass 0 {len(first)}")
        for a, b in zip(first, mine):
            if graph[b].fn is not graph[a].fn or (
                    graph[b].param_alias != graph[a].param_alias):
                raise ValueError(
                    f"task {b!r} is not {a!r} again (another fn object or "
                    "other aliases): the passes of a looped step share "
                    "their tasks' functions, weights and pools, or the "
                    "step cannot be rolled")
    return order[0], list(first), order[-1]


def compose_looped_step_fn(graph: TaskGraph, order: List[str], spec: Any,
                           ) -> Callable[..., Any]:
    """``compose_paged_step_fn`` for a graph that names its passes
    (``graph.pass_tasks``, ``spec.passes`` of them; same contract, same
    return): ``step(weights, pools, page_table,
    ids, lengths, active) -> (logits, new_pools, stats)``.  ``stats`` is
    a dict: each name a pass's tasks emit (a dict under ``stats``),
    stacked over the tasks that emit it inside a pass and then over the
    passes, ``(passes, emitters, ...)``."""
    from ..models.kv_pages import write_token_rows

    embed, first, head = _checked_passes(graph, order)

    def bind(task, weights, pools, page_table):
        return {loc: (page_table if glob == "page_table" else
                      pools[glob] if glob in pools else weights[glob])
                for loc, glob in (task.param_alias or {}).items()}

    def step(weights, pools, page_table, ids, lengths, active):
        inputs = {"ids": ids, "lengths": lengths, "active": active}
        start = graph[embed].fn(
            bind(graph[embed], weights, pools, page_table), inputs)

        def one_pass(carry, _):
            prev, pools = carry
            u, pools, named = prev["pass"], dict(pools), {}
            for tid in first:
                task = graph[tid]
                out = task.fn(bind(task, weights, pools, page_table), prev)
                for loc, glob in task.param_alias.items():
                    if glob in pools:
                        pools[glob] = write_token_rows(
                            pools[glob], out.pop(loc[len("cache_"):] + "_new"),
                            spec.plane(pools[glob], page_table, u), lengths,
                            active)
                for k, v in (out.pop("stats", None) or {}).items():
                    named.setdefault(k, []).append(v)
                prev = out
            return (prev, pools), {k: jnp.stack(v) for k, v in named.items()}

        (last, new_pools), stats = jax.lax.scan(
            one_pass, (start, pools), None, length=spec.passes)
        logits = graph[head].fn(
            bind(graph[head], weights, new_pools, page_table), last)
        return logits, new_pools, stats or None

    return step


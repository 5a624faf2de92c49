"""Pass 7 — decode-loop composability (DEC0xx).

The scan-loop composers in ``backends/decode_loop.py`` have contracts the
generic passes cannot see: every ``cache_*`` param is a *mutable* buffer
donated through the scan carry, so it must live on exactly one node; the
whole decode step must sit on one node to be scan-eligible at all; and a
paged graph (one with a ``page_table`` param) must wire the indirection
consistently — every layer that reads a pool must read the table, pools
must share one geometry.  Violations surface here as structured
diagnostics instead of mid-``compose_step_fn`` exceptions.

The pass self-detects decode graphs: a graph with no ``cache_*`` params
gets an empty report, so it is safe to run unconditionally.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Set

from ..core.cluster import Cluster
from ..core.graph import TaskGraph
from ..core.schedule import Schedule
from .diagnostics import AnalysisReport, Severity


def _is_cache_param(name: str) -> bool:
    return name.startswith("cache_")


def analyze_decode(
    graph: TaskGraph,
    cluster: Optional[Cluster] = None,
    schedule: Optional[Schedule] = None,
    param_specs: Optional[Dict[str, Any]] = None,
    chunk_tokens: Optional[int] = None,
    decode_budget: Optional[int] = None,
) -> AnalysisReport:
    """Decode-loop composability checks (no-op on non-decode graphs).

    * ``DEC001`` (error, needs ``schedule``): a ``cache_*`` param is
      needed by tasks placed on more than one node — the loop composers
      donate ONE buffer per cache param, so a multi-node alias means two
      devices would own the same mutable state.  (``page_table`` is a
      read-only broadcast input; sharing it across nodes is legal.)
    * ``DEC002`` (warning, needs ``schedule``): the decode step spans
      multiple nodes at all — legal for plain dispatch, but
      ``build_decode_loop`` / ``build_paged_decode_loop`` will reject it
      (scan-loop ineligible).
    * ``DEC003`` (error): inconsistent paged wiring — a task reads pools
      without the page table (or vice versa; a STATE pool,
      ``graph.state_kinds``, is a slot's and needs no table), or the
      per-layer pools disagree on geometry, or a state pool is held by
      more than one task (its task hands the whole pool back: the loop
      composer takes ONE writer's), or the rows a slot feeds a step
      (``graph.rows_per_step``, stamped by the paged builder: 1, or a
      family's ``DECODE_ROWS`` where it is stepped with its draft module)
      and the ``draft`` task disagree: more than one row without a
      ``draft`` sink that holds a pool of its own, or a ``draft`` task on
      a one-row step.
    * ``DEC004`` (info): per-step KV residency payload
      (``data={"kv_bytes": ..., "paged": ...}``).
    * ``DEC005`` (warning, needs ``param_specs``): the paged pool
      geometry (page_size / head_dim / kv-head layout read off the
      ``cache_*`` pool specs) makes the fused Pallas kernel ineligible,
      so every ``impl="auto"`` dispatch takes the XLA gather path (an
      explicit ``"pallas"`` raises).  The message names each violated
      tiling rule.  A warning, never a gate: the gather path is correct.
    * ``DEC006`` (warning, needs ``chunk_tokens``): the configured
      chunked-prefill chunk size is degenerate — either it violates the
      ragged multi-token-q kernel's tiling constraints
      (``paged_kernel_constraints(..., q_tokens=chunk_tokens)``), so
      every chunk wave silently runs the XLA gather path, or it exceeds
      ``decode_budget`` (the engine's per-segment decode capacity in
      model-forward rows, ``slots * seg_steps * rows a slot a step``),
      so a single chunk monopolizes the
      segment's prefill budget and chunking degenerates to one chunk
      per segment regardless of load.  Like DEC005, a warning and never
      a gate: the engine's output is bitwise-correct either way.
    """
    rep = AnalysisReport()
    tasks = graph.tasks()
    cache_users = [
        t for t in tasks if any(_is_cache_param(p) for p in t.params_needed)
    ]
    if not cache_users:
        return rep
    paged = any("page_table" in t.params_needed for t in tasks)

    # DEC001 / DEC002: placement of the mutable decode state ------------
    if schedule is not None:
        placement: Dict[str, str] = {
            tid: node
            for node, tids in schedule.per_node.items()
            for tid in tids
        }
        param_nodes: Dict[str, Set[str]] = {}
        step_nodes: Set[str] = set()
        for t in tasks:
            node = placement.get(t.task_id)
            if node is None:
                continue
            step_nodes.add(node)
            for p in t.params_needed:
                if _is_cache_param(p):
                    param_nodes.setdefault(p, set()).add(node)
        for p, nodes in sorted(param_nodes.items()):
            if len(nodes) > 1:
                rep.add(
                    "DEC001",
                    Severity.ERROR,
                    f"mutable decode param {p!r} is aliased by tasks on "
                    f"{len(nodes)} nodes ({sorted(nodes)[:4]}): the scan "
                    "carry donates one buffer per cache param",
                    param=p,
                    data={"nodes": sorted(nodes)},
                )
        if len(step_nodes) > 1 and not rep.has("DEC001"):
            rep.add(
                "DEC002",
                Severity.WARNING,
                f"decode step is placed across {len(step_nodes)} nodes "
                f"({sorted(step_nodes)[:4]}): dispatchable, but scan-loop "
                "composition requires single-node placement",
                data={"nodes": sorted(step_nodes)},
            )

    # DEC003: paged wiring consistency ----------------------------------
    if paged:
        state_pools = {f"cache_{k}" for k in getattr(graph, "state_kinds", ())}

        def is_state(p: str) -> bool:
            return p.rsplit("_", 1)[0] in state_pools

        writers: Dict[str, list] = {}
        for t in tasks:
            for p in t.params_needed:
                if is_state(p):
                    writers.setdefault(p, []).append(t.task_id)
        for p, tids in sorted(writers.items()):
            if len(tids) > 1:
                rep.add(
                    "DEC003",
                    Severity.ERROR,
                    f"state pool {p!r} is held by {len(tids)} tasks "
                    f"({tids[:4]}): a state layer's task hands the whole "
                    "pool back, and the loop composer keeps one writer's",
                    param=p,
                    data={"tasks": tids},
                )
        for t in tasks:
            has_pool = any(_is_cache_param(p) and not is_state(p)
                           for p in t.params_needed)
            has_table = "page_table" in t.params_needed
            if has_pool != has_table:
                what = (
                    "reads KV pools without the page_table indirection"
                    if has_pool
                    else "reads page_table without any KV pool"
                )
                rep.add(
                    "DEC003",
                    Severity.ERROR,
                    f"task {t.task_id!r} {what}",
                    task=t.task_id,
                )
        pool_bytes: Dict[str, int] = {}
        for t in tasks:
            for p, nbytes in t.param_bytes.items():
                if _is_cache_param(p):
                    pool_bytes[p] = nbytes
        # one pool shape per KIND of pool (``cache_{kind}_{layer}``): a
        # per-layer cache keeps pools of several kinds and widths side
        # by side, but two layers' pools of one kind are one geometry
        by_kind: Dict[str, Dict[str, int]] = {}
        for p, nbytes in pool_bytes.items():
            by_kind.setdefault(p.rsplit("_", 1)[0], {})[p] = nbytes
        for same in by_kind.values():
            if len(set(same.values())) <= 1:
                continue
            lo = min(same, key=same.get)
            hi = max(same, key=same.get)
            rep.add(
                "DEC003",
                Severity.ERROR,
                "KV page pools disagree on geometry: "
                f"{lo!r} is {same[lo]} bytes but {hi!r} is "
                f"{same[hi]} bytes (one pool shape per graph and kind)",
                param=hi,
                data={"pool_bytes": dict(sorted(pool_bytes.items()))},
            )

        # rows a step and the draft task go together
        rows = int(getattr(graph, "rows_per_step", 1))
        drafts = [t for t in tasks if t.group == "draft"]
        layer_pools = {p for t in tasks if t.group != "draft"
                       for p in t.params_needed if _is_cache_param(p)}
        problem = None
        if rows > 1 and len(drafts) != 1:
            problem = (f"the step feeds {rows} rows a slot but has "
                       f"{len(drafts)} draft task(s): the rows past the "
                       "first are drafts, and one task verifies them")
        elif rows == 1 and drafts:
            problem = (f"task {drafts[0].task_id!r} is a draft task on a "
                       "step of one row a slot: there is no draft to verify")
        elif drafts and (graph.dependents(drafts[0].task_id) or not any(
                _is_cache_param(p) and p not in layer_pools
                for p in drafts[0].params_needed)):
            problem = (f"draft task {drafts[0].task_id!r} must be the sink "
                       "and hold a cache pool no layer task reads (the "
                       "draft module's rows roll back with the model's)")
        if problem:
            rep.add("DEC003", Severity.ERROR, problem,
                    data={"rows_per_step": rows,
                          "draft_tasks": [t.task_id for t in drafts]})

    # DEC005: fused-kernel eligibility of the pool geometry --------------
    # a kv pool is stored (n_pages, page_size, n_kv_heads * head_dim)
    # (``models/kv_pages.CacheSpec``); the paged builder stamps the
    # head_dim that splits its row, and the query-head counts that read
    # it, on the graph.  A latent pool has no
    # heads and goes by its own rules (``mla_kernel_constraints``).
    pool_spec = None
    hd = getattr(graph, "kv_head_dim", None)
    if paged and param_specs and hd:
        pool_spec = next(
            (param_specs[p] for p in sorted(param_specs)
             if p.startswith("cache_k_")),
            None,
        )
        if pool_spec is not None:
            from ..ops.attention import paged_kernel_constraints

            _n_pages, page_size, width = pool_spec.shape
            n_kv = width // hd   # DEC006 below reads these too
            # every query-head count that reads the rows (a family whose
            # layers differ in them stamps each): the group mapping's rule
            violated = list(dict.fromkeys(
                v for hq in getattr(graph, "kv_q_heads", None) or (None,)
                for v in paged_kernel_constraints(
                    page_size, hd, n_kv, n_q_heads=hq, dtype=pool_spec.dtype)
            ))
            if violated:
                rep.add(
                    "DEC005",
                    Severity.WARNING,
                    "paged pool geometry is ineligible for the fused "
                    "Pallas attention kernel (impl='auto' takes the XLA "
                    "gather path; an explicit 'pallas' raises): "
                    + "; ".join(violated),
                    data={
                        "page_size": int(page_size),
                        "head_dim": int(hd),
                        "n_kv_heads": int(n_kv),
                        "dtype": str(pool_spec.dtype),
                        "constraints": list(violated),
                    },
                )

    # DEC006: chunked-prefill chunk-size degeneracy ----------------------
    if paged and chunk_tokens is not None:
        problems = []
        data: Dict[str, Any] = {"chunk_tokens": int(chunk_tokens)}
        if pool_spec is not None:
            from ..ops.attention import paged_kernel_constraints

            ragged_violated = paged_kernel_constraints(
                page_size, hd, n_kv, dtype=pool_spec.dtype,
                q_tokens=int(chunk_tokens),
            )
            if ragged_violated:
                problems.append(
                    "the ragged multi-token-q kernel is ineligible at "
                    f"this chunk size (every chunk wave silently runs "
                    "the XLA gather path): " + "; ".join(ragged_violated)
                )
                data["constraints"] = list(ragged_violated)
        if decode_budget is not None and chunk_tokens > decode_budget:
            problems.append(
                f"chunk_tokens {chunk_tokens} exceeds the per-segment "
                f"decode-token capacity {decode_budget} (slots * "
                "seg_steps * rows a step): one chunk monopolizes each "
                "segment's "
                "prefill budget, so chunked admission degenerates to "
                "one chunk per segment regardless of load"
            )
            data["decode_budget"] = int(decode_budget)
        if problems:
            rep.add(
                "DEC006",
                Severity.WARNING,
                "chunked-prefill chunk size is degenerate: "
                + " AND ".join(problems),
                data=data,
            )

    # DEC004: per-step KV residency payload ------------------------------
    kv_bytes: Dict[str, int] = {}
    for t in tasks:
        for p, nbytes in t.param_bytes.items():
            if _is_cache_param(p):
                kv_bytes[p] = nbytes
    total = sum(kv_bytes.values())
    rep.add(
        "DEC004",
        Severity.INFO,
        f"decode step holds {total / (1 << 20):.1f} MiB of KV cache "
        f"across {len(kv_bytes)} params"
        + (" (paged pools)" if paged else " (dense slabs)"),
        data={
            "kv_bytes": total,
            "n_cache_params": len(kv_bytes),
            "paged": paged,
        },
    )
    return rep

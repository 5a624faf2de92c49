"""On-device multi-step decode over a scheduled decode-step DAG.

The task-graph decode path's end-to-end rate is owned by the host: one
dispatch + one token readback per step costs a host round-trip per
token.  This module folds K decode steps into ONE dispatched XLA
program: the step DAG's tasks are composed
in the schedule's assignment order into a single traced step function
(the same composition the segment-fused dispatch mode runs — the
placement still comes from the scheduler), each layer's ``k_new``/
``v_new`` is folded into its cache slab in-graph, and ``lax.scan``
iterates the step with the cache buffers donated.  The host pays one
round-trip per K tokens instead of per token.

Single-node placements only: a multi-node placement needs per-step
host-mediated transfers, which is exactly the per-task dispatch path
(``DeviceBackend.execute``); this loop exists to amortize the host out
of the single-device steady state.

Reference anchor: the scheduler-owns-inference story is this repo's own
(``frontend/decode_dag.py``); the reference has no execution path at all
(reference ``simulation.py:216-278`` replays schedules against constants).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import TaskGraph
from ..core.schedule import Schedule
from ..models import cache_spec, draft_rows, module_of
from ..obs import process_metrics
from ..obs.trace import annotate
from ..ops.attention import chunk_attention_log


def compose_step_fn(
    graph: TaskGraph,
    schedule: Schedule,
    config: Any,
) -> Callable[[Dict[str, Any], Dict[str, Any], jax.Array, jax.Array],
              Tuple[jax.Array, Dict[str, Any]]]:
    """Compose the placed decode-step DAG into one traced step function.

    Tasks run in the schedule's assignment order (dependency-valid by
    construction), params resolve through each task's alias table, and
    the per-layer cache updates are folded with ``dynamic_update_slice``
    at the traced position — the functional step advance that
    ``apply_cache_updates`` performs on the host, moved in-graph.

    Returns ``step(weights, caches, ids, pos) -> (logits, new_caches)``.
    """
    placement = schedule.placement
    nodes = {placement[tid] for tid in placement}
    if len(nodes) > 1:
        raise ValueError(
            f"decode loop requires a single-node placement, got {len(nodes)} "
            "nodes — multi-node decode steps go through per-task dispatch "
            "(DeviceBackend.execute)"
        )
    # assignment order re-linearized topologically: validate_schedule only
    # guarantees a permutation, not producer-before-consumer (the device
    # backend re-linearizes through dispatch_order for the same reason)
    topo_pos = {tid: i for i, tid in enumerate(graph.topo_order)}
    order = sorted(
        (tid for tid in schedule.assignment_order if tid in placement),
        key=topo_pos.__getitem__,
    )
    missing = set(graph.task_ids()) - set(order)
    if missing:
        raise ValueError(f"placement does not cover tasks {sorted(missing)}")
    sinks = [tid for tid in order if not graph.dependents(tid)]
    if len(sinks) != 1:
        raise ValueError(f"expected one sink (logits) task, got {sinks}")
    sink = sinks[0]
    spec = cache_spec(config)

    def step(weights, caches, ids, pos):
        inputs = {"ids": ids, "pos": pos}
        outs: Dict[str, Any] = {}
        for tid in order:
            task = graph[tid]
            alias = task.param_alias or {}
            p = {
                loc: (caches[glob] if glob in caches else weights[glob])
                for loc, glob in alias.items()
            }
            if task.dependencies:
                args = [outs[d] for d in (task.arg_tasks or task.dependencies)]
            else:
                args = [inputs]
            outs[tid] = task.fn(p, *args)
        logits = outs[sink]
        new_caches = dict(caches)
        for i in range(spec.n_layers):
            o = outs[f"layer_{i}"]
            for kind in spec.kinds:
                buf = new_caches[f"cache_{kind}_{i}"]
                new_caches[f"cache_{kind}_{i}"] = jax.lax.dynamic_update_slice(
                    buf, o[f"{kind}_new"].astype(buf.dtype),
                    (jnp.int32(0), jnp.int32(0), pos, jnp.int32(0)),
                )
        return logits, new_caches

    return step


def build_decode_loop(
    graph: TaskGraph,
    schedule: Schedule,
    config: Any,
    steps: int,
) -> Callable[[Dict[str, Any], Dict[str, Any], jax.Array, jax.Array],
              Tuple[jax.Array, Dict[str, Any]]]:
    """Jit one program that greedily decodes ``steps`` tokens through the
    scheduled step DAG, cache buffers donated.

    ``run(weights, caches, ids, pos) -> (tokens, new_caches)`` where
    ``ids`` is the (B, 1) current token, ``pos`` the current cache
    position, and ``tokens`` the (B, steps) greedy continuation.  The
    caller chains calls by feeding the returned caches (and
    ``tokens[:, -1:]`` / ``pos + steps``) back in; donation makes the
    chain allocation-free on device.
    """
    step = compose_step_fn(graph, schedule, config)

    def run(weights, caches, ids, pos):
        def body(carry, _):
            ids, pos, caches = carry
            logits, caches = step(weights, caches, ids, pos)
            # same argmax the whole-program loop runs (models/decode.py
            # sample_token at temperature 0: bf16 logits, no f32 cast)
            nxt = jnp.argmax(
                logits[:, -1, :], axis=-1
            ).astype(jnp.int32)[:, None]
            return (nxt, pos + 1, caches), nxt[:, 0]

        (_, _, caches2), toks = jax.lax.scan(
            body, (ids, pos, caches), None, length=steps
        )
        return toks.T, caches2  # (B, steps)

    return jax.jit(run, donate_argnums=(1,))


def split_cache_params(
    params: Dict[str, Any],
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(weights, caches) views of a decode-DAG param dict."""
    weights = {k: v for k, v in params.items() if not k.startswith("cache_")}
    caches = {k: v for k, v in params.items() if k.startswith("cache_")}
    return weights, caches


def _placed_order(graph: TaskGraph, schedule: Schedule) -> list:
    """Schedule assignment order, single-node-validated and re-linearized
    topologically (shared by the dense and paged step composers)."""
    placement = schedule.placement
    nodes = {placement[tid] for tid in placement}
    if len(nodes) > 1:
        raise ValueError(
            f"decode loop requires a single-node placement, got {len(nodes)} "
            "nodes — multi-node decode steps go through per-task dispatch "
            "(DeviceBackend.execute)"
        )
    topo_pos = {tid: i for i, tid in enumerate(graph.topo_order)}
    order = sorted(
        (tid for tid in schedule.assignment_order if tid in placement),
        key=topo_pos.__getitem__,
    )
    missing = set(graph.task_ids()) - set(order)
    if missing:
        raise ValueError(f"placement does not cover tasks {sorted(missing)}")
    sinks = [tid for tid in order if not graph.dependents(tid)]
    if len(sinks) != 1:
        raise ValueError(f"expected one sink (logits) task, got {sinks}")
    return order


def compose_paged_step_fn(
    graph: TaskGraph,
    schedule: Schedule,
    config: Any,
) -> Callable[..., Tuple[jax.Array, Dict[str, Any]]]:
    """Compose the placed PAGED decode-step DAG (``build_paged_decode_dag``)
    into one traced step function.

    Same contract as :func:`compose_step_fn` — tasks run in the
    schedule's order, placement stays scheduler-owned — but the cache
    params are shared page pools, positions are the per-slot ``lengths``
    vector, and the per-layer fold is a page-table-directed scatter
    (:func:`...models.kv_pages.write_token_kv`) gated by the ``active``
    mask: inactive slots (retired or not yet admitted) write the trash
    page, so one compiled step serves every admission/retirement state.

    The cache is whatever :func:`...models.cache_spec` says the family
    keeps, layer by layer (K and V pools, one latent pool, or pools that
    differ between layers): layer ``i``'s task emits ``{kind}_new`` for
    each of its pool kinds.  A ring layer's row goes through the static
    ring table at ``lengths mod ring`` instead of the page table
    (:class:`...models.kv_pages.CacheSpec`); a state layer's ``{kind}_new``
    IS its pool, the decoding slots' states already updated in place by
    the task.  Layer tasks that emit ``stats`` have them stacked,
    layer-major — per name where ``stats`` is a dict of named counts.

    A family stepped with its draft module (``models.draft_rows`` > 1):
    ``ids`` is ``(S, R)``, every task's ``{kind}_new`` is ``(S, R, ...)``
    and lands at ``lengths .. lengths + R - 1``, the draft layers' rows
    come from the ``draft`` task, the sink: its output dict is ``logits``.
    A graph that names passes: ``decode_passes.compose_looped_step_fn``.

    Returns ``step(weights, pools, page_table, ids, lengths, active)
    -> (logits, new_pools, stats or None)``.
    """
    from ..models.kv_pages import write_step_rows, write_token_rows

    order = _placed_order(graph, schedule)
    sink = [tid for tid in order if not graph.dependents(tid)][0]
    spec = cache_spec(config)
    if spec.passes > 1 or getattr(graph, "pass_tasks", None):
        return _looped(graph, order, spec)
    n_main = spec.n_layers - spec.draft_layers
    rows_per_step = draft_rows(config)
    if rows_per_step > 1 and spec.has_rings:
        raise ValueError(
            "a step that verifies drafts is not built for a cache with "
            "ring (window) layers: a rejected row would overwrite the "
            "ring row a later query still reads")

    def write(pool, row, page_table, lengths, active, window):
        if rows_per_step > 1:
            return write_step_rows(pool, row, page_table, lengths, active)
        if window is None:
            return write_token_rows(pool, row, page_table, lengths, active)
        slots, ps = page_table.shape[0], pool.shape[1]
        ring = jnp.asarray(spec.ring_table(slots, ps))
        return write_token_rows(
            pool, row, ring, lengths % (ring.shape[1] * ps), active)

    def step(weights, pools, page_table, ids, lengths, active):
        inputs = {"ids": ids, "lengths": lengths, "active": active}
        outs: Dict[str, Any] = {}
        for tid in order:
            task = graph[tid]
            alias = task.param_alias or {}
            p = {}
            for loc, glob in alias.items():
                if glob == "page_table":
                    p[loc] = page_table
                elif glob in pools:
                    p[loc] = pools[glob]
                else:
                    p[loc] = weights[glob]
            if task.dependencies:
                args = [outs[d] for d in (task.arg_tasks or task.dependencies)]
            else:
                args = [inputs]
            outs[tid] = task.fn(p, *args)
        logits = outs[sink]
        new_pools = dict(pools)
        stats, named = [], {}
        for i in range(spec.n_layers):
            o = outs[f"layer_{i}" if i < n_main else "draft"]
            window = spec.layer(i).window
            for kind in spec.layer_kinds(i):
                new_pools[f"cache_{kind}_{i}"] = (
                    o[f"{kind}_new"] if spec.layer(i).state else write(
                        new_pools[f"cache_{kind}_{i}"], o[f"{kind}_new"],
                        page_table, lengths, active, window))
            if isinstance(o.get("stats"), dict):
                for k, v in o["stats"].items():
                    named.setdefault(k, []).append(v)
            elif "stats" in o:
                stats.append(o["stats"])
        if named:
            return logits, new_pools, {
                k: jnp.stack(v) for k, v in named.items()}
        return logits, new_pools, (jnp.stack(stats) if stats else None)

    return step


def kv_live_block_share(lengths, rows_per_block: int, capacity: int) -> float:
    """Share of the page table's blocks that the single-token paged
    kernel walks for these per-slot ``lengths`` (a host numpy array):
    slot ``s`` costs ``cdiv(min(L_s, capacity - 1) + 1, rows_per_block)``
    of its ``cdiv(capacity, rows_per_block)`` blocks, a slot at length 0
    — one the engine is not decoding — one.  ``rows_per_block`` is the page
    size times :func:`...ops.attention.paged_block_pages`."""
    live = lengths.clip(max=capacity - 1) // rows_per_block + 1
    return float(live.sum()) / (
        lengths.size * -(-capacity // rows_per_block))


def build_paged_decode_loop(
    graph: TaskGraph,
    schedule: Schedule,
    config: Any,
    steps: int,
) -> Callable[..., Tuple[jax.Array, Dict[str, Any]]]:
    """Jit one K-step greedy segment over the scheduled paged step DAG,
    page pools donated.

    ``seg(weights, pools, page_table, lengths, cur_tok, remaining) ->
    (tokens, new_pools[, stats])`` where ``cur_tok`` is each slot's (S, 1)
    current token, ``remaining`` the (S,) int32 decode steps each slot
    still owes, and ``tokens`` the (S, steps) greedy continuation (rows
    past a slot's ``remaining`` are garbage — the caller truncates).
    Slots stay active exactly while ``remaining > 0``: lengths stop
    advancing and pool writes divert to the trash page the step after a
    slot finishes, so admission and retirement between segments never
    recompile — the shapes are the static ``slots`` geometry, only array
    contents change.

    ``weights`` is an ARGUMENT, like the dense loop's: the executable
    holds no copy of the model (a closed-over weight dict becomes
    constants of the compiled program — one private copy of the model in
    HBM and in the compile cache per executable), so every serving
    program shares the one device-resident weight dict and is
    independent of weight values.

    A family stepped with its draft module (``models.DRAFT_FUNCTIONS``)
    gets the same segment with a step that VERIFIES: ``cur_tok`` is
    ``(S, 2)`` — the current token ``x`` (position ``L``, not cached
    yet) and the draft ``d`` for ``L + 1`` — and one step

    1. runs the main model over the rows ``[x@L, d@L+1]``, causal, both
       written: ``y0``, ``y1`` the argmax of each row's logits;
    2. accepts the draft where ``y0 == d`` — computed here, on the
       device, from nothing else — and the slot owes more than one token;
    3. runs the draft module over ``[(h_L, y0)@L, (h_L+1, y1)@L+1]``,
       both written; the next draft is its row 1's argmax where the
       draft was accepted, else row 0's;
    4. emits ``y0``, and ``y1`` where accepted; ``lengths`` advances and
       ``remaining`` (TOKENS owed) falls by the count.  The row at ``L +
       1`` of a rejected draft is overwritten by the next step before
       any query may see it (a query row ``r`` sees positions ``<= L +
       r``), in the draft module's pool as in the main layers'.

    ``tokens`` is then ONE int32 array ``(S, steps, 4)`` — per step
    ``[y0, y1, tokens emitted (0, 1 or 2), the draft the NEXT step
    verifies]`` — which is all the host folds from: the tokens of a
    slot are each step's first ``count`` entries, its lengths the sum
    of the counts, the drafts verified the column shifted by one.  The
    sequence emitted is exactly the one-row greedy sequence.
    """
    step = compose_paged_step_fn(graph, schedule, config)
    rows_per_step = draft_rows(config)
    if rows_per_step not in (1, 2):
        raise ValueError(
            f"a step verifies one draft a slot (2 rows), the family asks "
            f"for {rows_per_step}")

    def seg_drafts(weights, pools, page_table, lengths, cur_tok, remaining):
        def body(carry, _):
            pools, lengths, cur, remaining = carry
            active = remaining > 0
            out, pools, stats = step(
                weights, pools, page_table, cur, lengths, active)
            y = jnp.argmax(out["logits"], axis=-1).astype(jnp.int32)
            nd = jnp.argmax(out["draft_logits"], axis=-1).astype(jnp.int32)
            accept = jnp.logical_and(y[:, 0] == cur[:, 1], remaining > 1)
            n = jnp.where(active, 1 + accept.astype(jnp.int32), 0)
            nxt = jnp.where(accept[:, None], jnp.stack(
                [y[:, 1], nd[:, 1]], axis=1), jnp.stack(
                [y[:, 0], nd[:, 0]], axis=1))
            cur = jnp.where(active[:, None], nxt, cur)
            rec = jnp.concatenate([y, n[:, None], cur[:, 1:]], axis=1)
            return (pools, lengths + n, cur, remaining - n), (rec, stats)

        (pools2, _, _, _), (recs, stats) = jax.lax.scan(
            body, (pools, lengths, cur_tok, remaining), None, length=steps
        )
        recs = recs.transpose(1, 0, 2)      # (S, steps, 4)
        if stats is None:
            return recs, pools2
        return recs, pools2, stats

    if rows_per_step > 1:
        return jax.jit(seg_drafts, donate_argnums=(1,))

    def seg(weights, pools, page_table, lengths, cur_tok, remaining):
        def body(carry, _):
            pools, lengths, cur_tok, remaining = carry
            active = remaining > 0
            logits, pools, stats = step(
                weights, pools, page_table, cur_tok, lengths, active
            )
            nxt = jnp.argmax(
                logits[:, -1, :], axis=-1
            ).astype(jnp.int32)[:, None]
            cur_tok = jnp.where(active[:, None], nxt, cur_tok)
            lengths = lengths + active.astype(jnp.int32)
            remaining = jnp.maximum(remaining - 1, 0)
            return (pools, lengths, cur_tok, remaining), (nxt[:, 0], stats)

        (pools2, _, _, _), (toks, stats) = jax.lax.scan(
            body, (pools, lengths, cur_tok, remaining), None, length=steps
        )
        # slot state is NOT returned: the host reconstructs lengths /
        # cur_tok / remaining from ``toks`` exactly (they're deterministic
        # functions of the emitted tokens), saving per-segment readbacks.
        # A family whose layers count something on the device (expert
        # routing) gets the (steps, layers, ...) counts as a third output,
        # read back with the tokens.
        if stats is None:
            return toks.T, pools2
        return toks.T, pools2, stats

    return jax.jit(seg, donate_argnums=(1,))


class PagedDecodeEngine:
    """Continuous-batching paged decode: admit and retire variable-length
    requests between scanned K-step segments.

    The serving loop the dense path cannot run: ``slots`` static batch
    lanes share one paged KV pool; a host-side :class:`...models.kv_pages.
    PagePool` free-list hands each admitted request exactly the pages its
    ``prompt + max_new`` horizon needs (exhaustion leaves requests queued
    — backpressure, not corruption); retirement returns them.  Between
    segments the host folds results, frees, and admits; the segment
    itself is ONE dispatched XLA program (``build_paged_decode_loop``,
    pools donated), so steady-state decode pays one host round-trip per
    ``seg_steps`` tokens across ALL active requests — and because slot
    state is data, not shape, admission never recompiles.

    Placement stays scheduler-owned: the engine composes the placed
    paged decode-step DAG, exactly like the dense loop.  Construct via
    ``DeviceBackend.paged_decode_engine`` to run the pre-execution
    analysis gate first.
    """

    def __init__(
        self,
        graph: TaskGraph,
        schedule: Schedule,
        config: Any,
        weights: Dict[str, Any],
        pool: Any,
        slots: int,
        pages_per_seq: int,
        seg_steps: int = 8,
        tracer: Any = None,
        metrics: Any = None,
        clock: Any = None,
        memprof: Any = None,
        flight: Any = None,
        attention_impl: Optional[str] = None,
        chunk_tokens: Optional[int] = None,
    ):
        import numpy as np

        from ..models.kv_pages import TRASH_PAGE
        from ..obs import (
            MetricsRegistry,
            RequestLog,
            RequestTraceRecorder,
            TeeTracer,
            ambient_flight,
            ambient_metrics,
            ambient_tracer,
            resolve_clock,
        )

        self.config = config
        # ONE device-resident copy, passed to every serving executable as
        # an argument (never closed over: see build_paged_decode_loop)
        self.weights = jax.device_put(weights)
        self.pool = pool
        self.slots = slots
        self.pages_per_seq = pages_per_seq
        # the impl is baked into the graph's layer tasks at DAG build
        # time; the engine records it so (a) the prefill compile-class
        # key can never alias programs traced from differently-dispatched
        # graphs and (b) summary()/benches can report which path ran
        self.attention_impl = (
            attention_impl if attention_impl is not None
            else getattr(graph, "attention_impl", None)
        )
        # what a layer caches for a token: every pool this engine
        # allocates, gathers, scatters, copies and resets goes through it
        self.cache = cache_spec(config)
        n_layers = self.cache.n_layers
        # rows a slot feeds a decode step: 1, or 2 where the family is
        # stepped with its own draft module (``models.DRAFT_FUNCTIONS``) —
        # then ``cur_tok`` holds the current token AND the draft for the
        # position after it, and a step yields one token or two
        self.rows_per_step = draft_rows(config)
        self.page_size = pool.page_size
        self.capacity = pages_per_seq * pool.page_size
        # what the decode step's paged attention actually runs at this
        # geometry on this backend (the request may be None/"auto"); an
        # explicit kernel request the geometry cannot honour raises here,
        # before anything compiles.  ``kv_block_rows``: rows in one block
        # of the paged kernel's walk at this geometry, what
        # ``decode.kv_live_block_share`` counts live blocks in
        self.resolved_attention_impl = self.cache.resolve_impl(
            self.attention_impl, slots, pool.n_pages, pool.page_size,
            config.dtype)
        block_pages = self.cache.block_pages(
            pool.page_size, pages_per_seq, config.dtype)
        self.kv_block_rows = pool.page_size * block_pages
        self.seg_steps = seg_steps
        # chunked prefill: prompts longer than this admit in fixed-token
        # chunks co-scheduled with decode segments instead of one whole-
        # prompt wave.  None (the default) keeps whole-prompt admission
        # — every pre-chunking workload is bit-identical.  Mutable: the
        # serve bench toggles it between legs like ``pool.sharing``.
        if chunk_tokens is not None and chunk_tokens < 1:
            raise ValueError(
                f"chunk_tokens must be >= 1, got {chunk_tokens}"
            )
        self.chunk_tokens = chunk_tokens
        # per-slot in-progress prefill state: slot -> {rid, ids (np
        # (1, P)), P, max_new, next} where ``next`` is the count of
        # prompt tokens already prefilled+scattered.  The slot is
        # occupied (``_slot_req`` set) but decodes nothing
        # (``remaining == 0`` diverts its segment writes to the trash
        # page) until the last chunk folds.
        self._chunk_state: Dict[int, Dict[str, Any]] = {}
        self._chunk_rr = 0
        # extra args of the next ``segment`` span (:meth:`_observe_moe`)
        self._seg_span_args: Dict[str, Any] = {}
        # what stands between two deliveries to a decoding slot
        # (:meth:`_observe_interval`): prefill programs enqueued since the
        # last segment's dispatch and their real tokens, that segment's
        # readback stamp, and the slots it left still decoding
        self._prefill_ahead = [0, 0]
        self._seg_prev_t1 = 0.0
        self._seg_carry = np.zeros((slots,), bool)
        # drain seam (fleet failover): while set, submit() hard-rejects
        # new work — already-queued and in-flight requests keep running
        # to completion, which is what lets a sick replica empty itself
        # before a restart.  Cleared by reset()/rebind_obs().
        self._draining = False
        # virtual-time seam: when set, called with the REAL token count
        # right before every prefill dispatch (whole wave, stitched
        # tail, or chunk) so a VirtualClock frontend can charge prefill
        # compute time proportional to tokens.  None costs nothing.
        self.prefill_time_charge: Optional[Callable[[int], None]] = None
        # probe seam: when set, called once per segment with the named
        # per-step arrays the layers emitted that the engine does not
        # read itself (a sparse-selection family's ``dsa_idx``), as
        # numpy, and the request id, start length and owed steps of
        # every slot.  None costs nothing: the arrays stay on the device.
        self.stats_probe: Optional[Callable[..., None]] = None
        self._np = np
        self.n_layers = n_layers
        self._seg = build_paged_decode_loop(
            graph, schedule, config, seg_steps
        )
        # device state: ONLY the pools live on device (donated through
        # every call); slot bookkeeping stays host-side numpy — lengths /
        # cur_tok / remaining are deterministic functions of the emitted
        # tokens, so keeping them on host avoids a flurry of tiny .at[]
        # dispatches per admission and per-segment readbacks (at serving
        # granularity that overhead was the whole paged-vs-dense margin)
        self.pools = self.cache.init_pools(
            pool.n_pages, pool.page_size, config.dtype, slots=slots
        )
        # ring layers (a window layer's slot-owned pages): the static
        # table the prefill programs gather and scatter a slot's ring
        # through; None where the spec has none, and then no program
        # takes the argument
        self._rings = (
            self.cache.ring_table(slots, pool.page_size)
            if self.cache.has_rings else None
        )
        self.sharing    # a cache with ring or state layers refuses sharing
        if self.cache.has_state and chunk_tokens is None:
            raise ValueError(
                "a cache with state layers is prefilled through the chunk "
                "program only (a scan is stopped at the chunk's last real "
                "row, which is data there): build the engine with "
                "chunk_tokens")
        self.page_table = np.full(
            (slots, pages_per_seq), TRASH_PAGE, np.int32
        )
        self.lengths = np.zeros((slots,), np.int32)
        self.cur_tok = np.zeros((slots, self.rows_per_step), np.int32)
        self.remaining = np.zeros((slots,), np.int32)
        # host state
        self._queue: list = []
        self._slot_req: list = [None] * slots   # request id per busy slot
        self._slot_pages: list = [[] for _ in range(slots)]
        self._tokens: Dict[Any, list] = {}
        self.results: Dict[Any, Any] = {}
        # compile-class bookkeeping is split in two: `_prefill_cache` is
        # the PER-RUN seen-set (cleared by reset(), so the soak
        # sampler's ``jit.prefill_entries`` series of a reused engine is
        # identical to a fresh build's — the soak determinism gate) and
        # `_prefill_store` holds the compiled executables themselves,
        # which survive reset() so warm reruns never pay XLA again
        self._prefill_cache: Dict[Any, Any] = {}
        self._prefill_store: Dict[Any, Any] = {}
        # the compile classes of it whose expanded-MLA attention traced
        # to the chunk kernel (:meth:`_first_tokens_logged`)
        self._prefill_attn_kernel: set = set()
        self.segments_run = 0
        # obs: the tracer is optional (ambient under DLS_TRACE, else off);
        # the registry always exists so benches can snapshot per-engine
        # TTFT/TPOT/occupancy unconditionally — recording happens only at
        # segment boundaries (host side), never inside the scanned program
        self.tracer = tracer if tracer is not None else ambient_tracer()
        self.metrics = (
            metrics if metrics is not None
            else (ambient_metrics() or MetricsRegistry())
        )
        # injectable clock (tests script TTFT/TPOT deterministically);
        # reads happen between dispatches, so the shared obs default
        # keeps the engine on the host tracer's timebase
        self._clock = resolve_clock(clock)
        self._submit_t: Dict[Any, float] = {}     # rid -> submit() time
        self._first_tok_t: Dict[Any, float] = {}  # rid -> first-token time
        # flight recorder (explicit, or ambient under DLS_FLIGHT): its
        # ring tracer joins the span stream — alone when no tracer was
        # wired, teed alongside an explicit/ambient one otherwise
        self.flight = flight if flight is not None else ambient_flight()
        if self.flight is not None:
            if self.tracer is None:
                self.tracer = self.flight.tracer
            else:
                self.tracer = TeeTracer(self.tracer, self.flight.tracer)
        # per-request waterfall recorder: rides the tracer, inheriting
        # its None-guard contract — no tracer, no recorder, no work
        self.reqtrace = (
            RequestTraceRecorder(self.tracer)
            if self.tracer is not None else None
        )
        # request lifecycle log: always on, like the registry — recording
        # is a dict write per lifecycle seam, host side, outside the
        # scanned program.  Timestamps are the SAME clock reads the
        # ttft/tpot histograms observe (bitwise-match contract).
        self.reqlog = RequestLog(clock=self._clock)
        self._reqlogs = self._req_sinks()
        # memory doctor: per-request KV page occupancy folds onto the
        # profiler's timeline as kv_pages-bucket allocations (born at
        # admission, freed at retirement) sized by the physical page —
        # page_size rows x (Hkv, hd) x k+v x n_layers.  Explicit only;
        # None costs nothing (every record below is None-guarded).
        self.memprof = memprof
        # page-ownership event seam (analysis/page_pass): None by default
        # — every record site below is None-guarded, so the bare engine
        # is bit-identical to an instrumented one.  Wire it with
        # attach_ownership_log() or rebind_obs(ownlog=...).
        self.ownlog = None
        self._page_bytes = (
            pool.page_size * self.cache.paged_row_elems
            * np.dtype(config.dtype).itemsize
        )
        # the pools are one placed slab: attribute kv pages to the node
        # the schedule put the decode step on
        self._mem_node = next(iter(schedule.placement.values()), "node0")

    def _req_sinks(self):
        """The engine's full log plus (when wired) the flight ring."""
        if self.flight is not None:
            return (self.reqlog, self.flight.reqlog)
        return (self.reqlog,)

    def attach_ownership_log(self, log: Any) -> None:
        """Wire (or, with ``None``, unwire) the append-only
        page-ownership event seam (:class:`...models.kv_pages.
        PageOwnershipLog`).

        The engine records the owner-attributed ``assign``/``release``
        events at its lifecycle edges; the pool itself records the
        low-level ``alloc``/``free`` events with the tiling counts —
        fault injectors wrap the pool in a delegating proxy, so the
        recorder is planted on the INNER pool (the proxy's withheld
        pages then surface as allocs that never see a free, which is
        exactly what the prover flags)."""
        self.ownlog = log
        pool = self.pool
        inner = getattr(pool, "_inner", None)
        if inner is not None:
            pool = inner
        pool.ownlog = log
        if log is not None and getattr(log, "n_pages", None) is None:
            log.n_pages = pool.n_pages
        if log is not None and self._rings is not None:
            log.uncovered = (
                f"{self._rings.size} slot-owned ring pages in each window "
                "layer's pool are written by their slots and never pass "
                "through the allocator")
        if log is not None and self.cache.has_state:
            log.unkeyed = (
                f"{self.slots} slot-owned states in each state layer's pool "
                "are overwritten by every step and chunk; no page, and no "
                "hash of a page's tokens, stands for one")

    def reset(self) -> None:
        """Fresh pool/table/queue state, compiled programs kept.

        The segment, prefill, and scatter executables are keyed to this
        instance (``_prefill_store``), so benchmarks warm up once, reset,
        and re-time the exact workload without paying compilation again.
        The per-run seen-set ``_prefill_cache`` IS cleared: the soak
        sampler's ``jit.prefill_entries`` series (its length) counts
        compile classes seen *this run*, and a reused engine must give
        the same series a fresh build would."""
        from ..models.kv_pages import TRASH_PAGE

        self._prefill_cache = {}
        np = self._np
        for s, pages in enumerate(self._slot_pages):
            if pages:
                self._release_pages(pages, str(self._slot_req[s]), "reset")
                if self.memprof is not None:
                    self.memprof.free(
                        self._mem_node, f"kv:{self._slot_req[s]}"
                    )
        # the KV arrays below are REBUILT (the old ones go first: pools
        # that fill the chip do not fit twice), so retained prefix intern
        # entries would point at zeroed pages.  Fault-injector wrappers
        # may not expose the method; pristine pools always do.
        drop = getattr(self.pool, "drop_cached", None)
        if drop is not None:
            drop()
        self.pools = None
        self.pools = self.cache.init_pools(
            self.pool.n_pages, self.pool.page_size, self.config.dtype,
            slots=self.slots,
        )
        self.page_table = np.full(
            (self.slots, self.pages_per_seq), TRASH_PAGE, np.int32
        )
        self.lengths = np.zeros((self.slots,), np.int32)
        self.cur_tok = np.zeros(
            (self.slots, self.rows_per_step), np.int32)
        self.remaining = np.zeros((self.slots,), np.int32)
        self._queue = []
        self._slot_req = [None] * self.slots
        self._slot_pages = [[] for _ in range(self.slots)]
        self._tokens = {}
        self.results = {}
        self.segments_run = 0
        self._submit_t = {}
        self._first_tok_t = {}
        self._chunk_state = {}
        self._chunk_rr = 0
        self._draining = False
        self._prefill_ahead = [0, 0]
        self._seg_carry = np.zeros((self.slots,), bool)
        # fresh request log per run (benches reset between reps); the
        # flight ring deliberately survives — it is the always-on
        # last-N record across runs
        from ..obs import RequestLog

        self.reqlog = RequestLog(clock=self._clock)
        self._reqlogs = self._req_sinks()
        if self.reqtrace is not None:
            self.reqtrace.reset()

    def rebind_obs(
        self,
        *,
        clock: Any = None,
        tracer: Any = None,
        metrics: Any = None,
        flight: Any = None,
        memprof: Any = None,
        ownlog: Any = None,
    ) -> None:
        """Re-wire the observability surfaces and wipe run state, keeping
        the compiled executables.

        This is the seam that lets one engine serve many independent legs
        (benches, soaks, test sessions) without re-paying XLA: each leg
        hands in its own clock/tracer/metrics/flight exactly as it would
        to ``__init__``, and gets an engine indistinguishable from a
        fresh build except for the warm ``_prefill_store`` and segment
        executables.  Fault injectors are explicitly undone: a leaky
        pool wrapper is replaced by a pristine :class:`...models.
        kv_pages.PagePool` of the same geometry, and an instance-level
        ``step_segment`` override (jit-churn injection) is popped so the
        class method is reachable again."""
        from ..models.kv_pages import PagePool
        from ..obs import (
            MetricsRegistry,
            RequestLog,
            RequestTraceRecorder,
            TeeTracer,
            ambient_flight,
            ambient_metrics,
            ambient_tracer,
            resolve_clock,
        )

        # same wiring as __init__, in the same order
        self.tracer = tracer if tracer is not None else ambient_tracer()
        self.metrics = (
            metrics if metrics is not None
            else (ambient_metrics() or MetricsRegistry())
        )
        self._clock = resolve_clock(clock)
        self.flight = flight if flight is not None else ambient_flight()
        if self.flight is not None:
            if self.tracer is None:
                self.tracer = self.flight.tracer
            else:
                self.tracer = TeeTracer(self.tracer, self.flight.tracer)
        self.reqtrace = (
            RequestTraceRecorder(self.tracer)
            if self.tracer is not None else None
        )
        self.memprof = memprof
        # undo fault injectors before reset(): a wrapped pool must not
        # receive the stale pages reset() frees, so drop the slot->page
        # bookkeeping and swap in a pristine pool of the same geometry
        self._slot_pages = [[] for _ in range(self.slots)]
        self.pool = PagePool(
            n_pages=self.pool.n_pages, page_size=self.pool.page_size,
            sharing=bool(getattr(self.pool, "sharing", False)),
        )
        self.attach_ownership_log(ownlog)
        # the hook belongs to the leg that set it (a frontend with a
        # virtual clock); a re-bound engine starts uncharged
        self.prefill_time_charge = None
        self.__dict__.pop("step_segment", None)
        # reset() rebuilds pools/tables/reqlog against the just-bound
        # clock and flight sinks
        self.reset()

    # -- prefix sharing ----------------------------------------------------
    @property
    def sharing(self) -> bool:
        """Whether the pool interns prefix chunks (read live off the
        pool, so ``rebind_obs``'s pristine replacement keeps the mode).
        Refused for a cache with ring layers: a shared page carries the
        paged layers' rows of a prefix and nothing of the window layers'
        state, so a request that aliased one would decode over rings it
        never filled; nor of a state layer's.  Refused for a family
        stepped with its draft
        module too: the draft layer's row of a position is made from
        the token AFTER it, which a page's key (the tokens of the page)
        does not cover for its last row."""
        on = bool(getattr(self.pool, "sharing", False))
        if on and self._rings is not None:
            raise ValueError(
                "prefix sharing is not built for a cache with ring "
                "(window) layers: a shared page does not carry their "
                "state; use PagePool(sharing=False)")
        if on and self.cache.has_state:
            raise ValueError(
                "prefix sharing is not built for a cache with state "
                "layers: a page's hash identifies its rows, not the state "
                "a slot holds after them; use PagePool(sharing=False)")
        if on and self.rows_per_step > 1:
            raise ValueError(
                "prefix sharing is not built for a family stepped with "
                "its draft module: the draft layer's last row of a "
                "shared page depends on the token after the page; use "
                "PagePool(sharing=False)")
        return on

    def _release_pages(self, pages, owner: str, site: str) -> None:
        """The ONE page-release path for retire/preempt/reset: records
        the owner-attributed ``release`` (with live refcounts when
        sharing), then drops the reference — last release frees
        physically, earlier ones only decrement.  With sharing off this
        is byte-for-byte the pre-sharing record+free sequence."""
        if self.sharing:
            if self.ownlog is not None:
                self.ownlog.record(
                    "release", pages, owner=owner, site=site,
                    refcounts=[self.pool.refcount(p) for p in pages],
                )
            self.pool.release_ref(pages)
        else:
            if self.ownlog is not None:
                self.ownlog.record(
                    "release", pages, owner=owner, site=site,
                )
            self.pool.free(pages)

    def fresh_pages_needed(self, prompt_ids: Any, max_new_tokens: int) -> int:
        """Pages a request would newly allocate if admitted NOW: its
        ``prompt + max_new`` footprint minus currently-resident shared
        prefix chunks.  The serving frontend's admission check calls
        this so backlog ordering sees the same headroom admission will.
        With sharing off it is exactly ``pages_needed``."""
        from ..models.kv_pages import pages_needed, prefix_chunk_keys

        P = int(prompt_ids.shape[1])
        need = pages_needed(P + max_new_tokens, self.page_size)
        if not self.sharing:
            return need
        h_max = (P - 1) // self.page_size
        keys = prefix_chunk_keys(
            prompt_ids, self.page_size
        )[:h_max]
        h, spages = self.pool.match_prefix(keys)
        # a matched page that is CACHED-FREE (LRU-retained intern entry)
        # still satisfies the prefix, but reviving it consumes one
        # free-list page — count it as physical demand or the headroom
        # check would over-admit and MemoryError mid-wave
        is_cached = getattr(self.pool, "is_cached", None)
        revive = (
            sum(1 for p in spages if is_cached(p))
            if is_cached is not None else 0
        )
        return need - h + revive

    def chunk_eligible(self, prompt_len: int) -> bool:
        """Whether a prompt admits CHUNKED: chunking is on, the prompt
        is longer than one chunk, and the padded chunk grid fits the
        per-slot capacity (the final chunk is padded to ``chunk_tokens``
        rows, so ``ceil(P/chunk) * chunk`` dense-cache rows must exist —
        otherwise the request falls back to whole-prompt admission).  A
        cache with state layers has no whole-prompt program: every prompt
        admits chunked, a short one as one padded chunk (``submit``
        refuses what the grid cannot hold)."""
        ct = self.chunk_tokens
        if self.cache.has_state:
            return True
        if ct is None or prompt_len <= ct:
            return False
        return -(-prompt_len // ct) * ct <= self.capacity

    def admission_pages_needed(
        self, prompt_ids: Any, max_new_tokens: int
    ) -> int:
        """Free-list pages admission must find for this request NOW:
        the first chunk only when it admits chunked (later chunks alloc
        lazily per segment; more than the pool has free where taking
        them would leave the slots mid-prefill no order to finish in,
        :meth:`_safe_after`), the fresh-tail footprint otherwise.  The
        serving frontend's backlog check calls this so its headroom
        arithmetic matches the engine allocator's."""
        from ..models.kv_pages import pages_needed

        P = int(prompt_ids.shape[1])
        if self.chunk_eligible(P):
            return self._chunked_need(P, max_new_tokens)
        return self.fresh_pages_needed(prompt_ids, max_new_tokens)

    def is_prefilling(self, rid: Any) -> bool:
        """Whether ``rid`` holds a slot mid-chunked-prefill.  Such a
        request is NOT preemptible — it has produced no resumable
        prefix yet (no first token), so eviction would only waste the
        chunks already scattered."""
        return any(st["rid"] == rid for st in self._chunk_state.values())

    def _ensure_exclusive(self) -> None:
        """Copy-on-write guard before a segment: any page the coming
        writes would land in while other requests still alias it is
        split — a fresh page is allocated, the content copied on device,
        and the shared reference released (alloc-before-release, the
        ordering PGL007 proves).  Structurally unreachable under the
        admission rule (generation always lands in exclusive tail
        pages), but the seam is real: tests force an alias onto a write
        page and the split must keep every request's tokens bitwise."""
        if not self.sharing:
            return
        np = self._np
        for s in range(self.slots):
            if self._slot_req[s] is None or self.remaining[s] <= 0:
                continue
            lo = int(self.lengths[s])
            hi = lo + min(int(self.remaining[s]), self.seg_steps)
            for li in range(lo // self.page_size,
                            (hi - 1) // self.page_size + 1):
                src = int(self.page_table[s, li])
                if self.pool.refcount(src) <= 1:
                    continue
                t_c0 = (self._clock()
                        if self.reqtrace is not None else None)
                dst = self.pool.alloc(1)[0]
                rid = str(self._slot_req[s])
                if self.ownlog is not None:
                    self.ownlog.record(
                        "cow", [src, dst], owner=rid, site="cow",
                        refcounts=[self.pool.refcount(src),
                                   self.pool.refcount(dst)],
                    )
                self.pools = self._cow_copy(
                    self.pools, self._planes(src), self._planes(dst)
                )
                self.page_table[s, li] = dst
                pages = self._slot_pages[s]
                pages[pages.index(src)] = dst
                self.pool.release_ref([src])
                if self.ownlog is not None:
                    self.ownlog.record(
                        "write", [dst], owner=rid, site="cow",
                        refcounts=[self.pool.refcount(dst)],
                    )
                self.metrics.counter("decode.cow_splits").inc()
                if self.reqtrace is not None:
                    self.reqtrace.cow(rid, t_c0, self._clock(),
                                      src=src, dst=dst)

    @property
    def _cow_copy(self):
        fn = self._prefill_store.get("cow_copy")
        if fn is None:
            def _fn(pools, src, dst):
                new = dict(pools)
                for k in new:
                    new[k] = new[k].at[dst].set(new[k][src])
                return new

            fn = jax.jit(_fn, donate_argnums=(0,))
            self._prefill_store["cow_copy"] = fn
        return fn

    # -- request intake ----------------------------------------------------
    def _emit_queue_depth(self) -> None:
        """The ONE place queue depth reaches both surfaces: the metrics
        gauge and (when tracing) the tracer counter track sample the
        same value at the same event, so they cannot disagree."""
        depth = len(self._queue)
        self.metrics.gauge("decode.queue_depth").set(depth)
        if self.tracer is not None:
            self.tracer.counter("decode.queue_depth", depth)

    # -- drain (fleet failover) --------------------------------------------
    @property
    def draining(self) -> bool:
        """True while the engine rejects new submissions (fleet drain)."""
        return self._draining

    def begin_drain(self) -> None:
        """Stop accepting new work: ``submit()`` raises until the drain
        ends.  Queued and in-flight requests are commitments — they keep
        admitting and decoding to completion, so a draining engine
        empties itself instead of wedging its queue.  Idempotent."""
        self._draining = True

    def end_drain(self) -> None:
        """Re-open submission without a restart (``reset()`` and
        ``rebind_obs()`` also clear the drain flag)."""
        self._draining = False

    # -- pool headroom (ONE surface) ---------------------------------------
    @property
    def free_slots(self) -> int:
        """Batch lanes currently unoccupied."""
        return sum(1 for r in self._slot_req if r is None)

    def page_occupancy(self) -> Dict[str, Any]:
        """Pool headroom as a first-class surface: free/used totals plus
        per-request page counts.  The serving frontend's admission check,
        the engine summary, and the ``decode.page_pool`` metric/trace
        tracks all read THIS dict, so they cannot disagree."""
        per_request = {
            str(self._slot_req[s]): len(self._slot_pages[s])
            for s in range(self.slots)
            if self._slot_req[s] is not None
        }
        occ = {
            "n_pages": self.pool.n_pages - 1,  # page 0 is the trash page
            "free_pages": self.pool.free_pages,
            "used_pages": self.pool.used_pages,
            "per_request": per_request,
        }
        if self.sharing:
            # logical-vs-physical accounting exists only in sharing mode:
            # the disabled engine's occupancy dict stays bitwise-identical
            # to the pre-sharing one
            occ["logical_pages"] = self.pool.logical_pages
            occ["shared_pages"] = self.pool.shared_pages
            occ["per_request_exclusive"] = {
                str(self._slot_req[s]): sum(
                    1 for p in self._slot_pages[s]
                    if self.pool.refcount(p) == 1
                )
                for s in range(self.slots)
                if self._slot_req[s] is not None
            }
        return occ

    def _emit_pool_occupancy(self) -> None:
        """Sample :meth:`page_occupancy` into the ``decode.page_pool``
        gauge and (when tracing) counter track."""
        used = self.page_occupancy()["used_pages"]
        self.metrics.gauge(
            "decode.page_pool_occupancy_pages", unit="pages"
        ).set(used)
        if self.tracer is not None:
            self.tracer.counter("decode.page_pool_occupancy_pages", used)

    def summary(self) -> Dict[str, Any]:
        """Engine-state snapshot: slot/queue/pool headroom at this
        segment boundary (what admission policies key off)."""
        out = {
            "slots": self.slots,
            "free_slots": self.free_slots,
            "queued": len(self._queue),
            "in_flight": self.slots - self.free_slots,
            "completed": len(self.results),
            "segments_run": self.segments_run,
            "attention_impl": self.attention_impl or "auto",
            "attention_impl_resolved": self.resolved_attention_impl,
            "page_occupancy": self.page_occupancy(),
        }
        if self.sharing:
            out["prefix_sharing"] = True
        if self.chunk_tokens is not None:
            out["chunk_tokens"] = self.chunk_tokens
            out["prefilling"] = len(self._chunk_state)
        if self._draining:
            out["draining"] = True
        return out

    def submit(self, rid: Any, prompt_ids: Any, max_new_tokens: int) -> None:
        """Queue a request; admitted into a free slot (and its pages
        allocated) at the next segment boundary.

        Request ids must be unique for the life of the engine state: a
        duplicate would silently clobber ``_submit_t``/``results`` and
        collide lifecycle-log rows, so it is a hard error.  A PREEMPTED
        rid is also spent — the serving layer re-queues the generated
        prefix under a derived rid (``reset()`` clears everything)."""
        if self._draining:
            raise RuntimeError(
                f"engine is draining: rejecting submit of rid {rid!r}"
            )
        if rid in self.results:
            raise ValueError(f"duplicate rid {rid!r}: already retired")
        if rid in self._tokens:
            raise ValueError(f"duplicate rid {rid!r}: already in flight")
        if any(q[0] == rid for q in self._queue):
            raise ValueError(f"duplicate rid {rid!r}: already queued")
        if self.reqlog.get(rid) is not None:
            raise ValueError(
                f"duplicate rid {rid!r}: already has a lifecycle record"
            )
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        if prompt_ids.ndim != 2 or prompt_ids.shape[0] != 1:
            raise ValueError("prompt_ids must be (1, prompt_len)")
        total = prompt_ids.shape[1] + max_new_tokens
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if total > self.capacity:
            raise ValueError(
                f"request needs {total} rows > per-slot capacity "
                f"{self.capacity} ({self.pages_per_seq} pages x "
                f"{self.page_size})"
            )
        if self.cache.has_state and -(-prompt_ids.shape[1] // (
                self.chunk_tokens)) * self.chunk_tokens > self.capacity:
            raise ValueError(
                f"a prompt of {prompt_ids.shape[1]} tokens in chunks of "
                f"{self.chunk_tokens} passes the per-slot capacity "
                f"{self.capacity}, and a cache with state layers has no "
                "whole-prompt program")
        self._queue.append((rid, prompt_ids, max_new_tokens))
        t_sub = self._clock()
        self._submit_t[rid] = t_sub
        for rl in self._reqlogs:
            rl.submit(rid, int(prompt_ids.shape[1]), max_new_tokens, t_sub)
        if self.reqtrace is not None:
            # idempotent: a serving frontend may have registered this
            # rid already at its ARRIVAL anchor; a derived resume rid
            # re-joins the first pass's track
            self.reqtrace.submit(
                rid, t_sub, prompt_len=int(prompt_ids.shape[1]),
                max_new_tokens=max_new_tokens,
            )
        self.metrics.counter("decode.requests_submitted").inc()
        self._emit_queue_depth()

    def _first_tokens(self, w, ids, cache, pos0, row, **pages):
        """The family's cached forward over ``ids`` (b, T) at ``pos0``
        and the greedy token of chunk row ``row`` (static or traced),
        (b,) int32 — all any prefill program needs of the logits: the
        family's ``forward_cached_row``.  For a family stepped with its
        draft module ``ids`` is the pair ``(ids, nxt)`` of
        :meth:`_with_next`, the draft layer's rows are filled too
        (``forward_cached_draft``) and what comes back is (b, 2): the
        first token and the first draft."""
        fam = module_of(self.config)
        if self.rows_per_step == 1:
            last, cache = fam.forward_cached_row(
                w, ids, cache, pos0, self.config, row,
                impl=self.attention_impl, **pages)
            return jnp.argmax(last, axis=-1).astype(jnp.int32), cache
        ids, nxt = ids
        last, draft, cache = fam.forward_cached_draft(
            w, ids, nxt, cache, pos0, self.config, row,
            impl=self.attention_impl)
        return jnp.stack(
            [jnp.argmax(last, axis=-1), jnp.argmax(draft, axis=-1)],
            axis=-1).astype(jnp.int32), cache

    def _prefill_dispatched(self, key) -> None:
        """Right after every prefill dispatch of compile class ``key``
        (traced by then): one whose attention is the chunk kernel counts
        into ``decode.prefill_attn_kernel_programs``, to be read beside
        ``decode.chunk_waves`` and ``decode.admission_waves``."""
        if key in self._prefill_attn_kernel:
            self.metrics.counter("decode.prefill_attn_kernel_programs").inc()

    def _first_tokens_logged(self, key):
        """:meth:`_first_tokens` for the prefill program of compile
        class ``key``, noting WHILE THE PROGRAM IS TRACED what its chunk
        attention resolved to (:func:`...ops.attention.
        chunk_attention_log`; the choice is the shape's, made at trace
        time): the class is in ``_prefill_attn_kernel`` iff every
        expanded-MLA attention in it is the kernel."""
        def fwd(*args, **pages):
            with chunk_attention_log() as impls:
                out = self._first_tokens(*args, **pages)
            if impls and "xla" not in impls:
                self._prefill_attn_kernel.add(key)
            return out

        return fwd

    def _with_next(self, ids, nxt):
        """What a prefill program takes as its ids: ``ids`` itself, or —
        for a family stepped with its draft module — the pair with
        ``nxt``, the token after each position (-1: the one the program
        itself decides at its ``row``)."""
        if self.rows_per_step == 1:
            return ids
        return ids, jnp.asarray(nxt, jnp.int32)

    def _prefill_enqueued(self, tokens: int) -> None:
        """Right before every prefill dispatch (whole wave, stitched
        tail or chunk), with its REAL token count: the program stands
        between the decoding slots' last delivery and their next, so it
        counts toward the next segment's ``prefill_programs_ahead`` /
        ``prefill_tokens_ahead``; and the virtual-time seam is charged."""
        self._prefill_ahead[0] += 1
        self._prefill_ahead[1] += tokens
        if self.prefill_time_charge is not None:
            self.prefill_time_charge(tokens)

    # -- prefill + page scatter (ONE call per admission ROUND; one
    # compiled class per (prompt length, batch size)) ----------------------
    def _ring_args(self, slots) -> tuple:
        """What a prefill program takes beside the page rows of what the
        ``slots`` own outright (``CacheSpec.owned`` names them for
        ``gather`` / ``scatter``): their ring pages, flat, where the
        cache has ring layers, then their rows of the state layers'
        pools where it has those."""
        ring = (() if self._rings is None else
                (jnp.asarray(self._rings[list(slots)].reshape(-1)),))
        if not self.cache.has_state:
            return ring
        return ring + (jnp.asarray(self.cache.state_rows(list(slots))),)

    def _prefill_scatter(self, prompt_ids: jax.Array, pt_rows, slots=()):
        """Prefill ``b`` same-length prompts and scatter all their cache
        rows into their pages in ONE jitted, pool-donating call.

        ``prompt_ids`` (b, P); ``pt_rows`` (b, pages_per_seq) physical
        page rows (trash-padded tails); ``slots`` the slots they go to
        (read where the cache has ring layers).  Returns the (b,) first
        greedy tokens.  Weights are an argument (see the segment fn)."""
        b, P = prompt_ids.shape
        key = (P, b, self.attention_impl)
        fn = self._prefill_store.get(key)
        if fn is None:
            spec, fwd = self.cache, self._first_tokens_logged(key)
            cap, cfg = self.capacity, self.config
            ppseq, ps = self.pages_per_seq, self.page_size

            def _fn(w, ids, pools, pages, *ring):
                cache = spec.init_dense(b, cap, cfg.dtype, page_size=ps)
                first, cache = fwd(w, ids, cache, 0, P - 1)
                return first, spec.scatter(
                    pools, cache, pages.reshape(b * ppseq), ps,
                    **spec.owned(*ring))

            fn = jax.jit(_fn, donate_argnums=(2,))
            self._prefill_store[key] = fn
        # seen-set entry even on store hits: a reused engine's first
        # encounter of a compile class this run counts, warm or not
        if key not in self._prefill_cache:
            self._prefill_cache[key] = fn
        self._prefill_enqueued(b * P)
        nxt = None
        if self.rows_per_step > 1:   # the ids shifted by one, then the
            nxt = self._np.full((b, P), -1, self._np.int32)   # program's own
            nxt[:, :-1] = self._np.asarray(prompt_ids)[:, 1:]
        first, self.pools = fn(
            self.weights, self._with_next(prompt_ids, nxt), self.pools,
            jnp.asarray(pt_rows), *self._ring_args(slots)
        )
        self._prefill_dispatched(key)
        return first

    def _prefill_scatter_shared(
        self, prompt_ids: jax.Array, h: int, shared_rows, wt_rows
    ):
        """Stitched prefill for a wave whose first ``h`` prefix pages are
        already resident: gather the shared pages into the dense cache,
        run the transformer over ONLY the tail ``[h*ps, P)`` at
        ``pos_start = h*ps``, and scatter through the write table (shared
        entries diverted to the trash page, so aliased content is never
        re-written).

        Bitwise contract: ``cached_attention`` masks cache columns
        beyond the write cursor AFTER computing scores, so masked
        operand values never reach the output — the same property the
        preemption-resume path proves cross-shape.  Resident rows are
        bitwise what a full prefill would have produced (KV at position
        j depends only on tokens[0..j]), the tail runs the identical
        ``forward_cached`` at a later ``pos_start``, and rows past P
        stay zero exactly as in the unshared path — so first token,
        scattered pages, and every subsequent decode step match the
        unshared run bit for bit.

        ``prompt_ids`` (b, P) FULL prompts (the resident portion is
        sliced off here, keeping the caller symmetric with
        :meth:`_prefill_scatter`); ``shared_rows`` (b, h) physical ids
        of the resident prefix pages; ``wt_rows`` (b, pages_per_seq)
        the write table.  One compile class per ``(P, h, b, impl)``.
        """
        b, P = prompt_ids.shape
        h = int(h)
        key = ("shared", P, h, b, self.attention_impl)
        fn = self._prefill_store.get(key)
        if fn is None:
            spec, fwd = self.cache, self._first_tokens_logged(key)
            cap, cfg = self.capacity, self.config
            ppseq, ps = self.pages_per_seq, self.page_size
            pre = h * ps

            def _fn(w, ids_tail, pools, spages, wpages):
                cache = spec.gather(
                    spec.init_dense(b, cap, cfg.dtype), pools,
                    spages.reshape(b * h), b, pre)
                first, cache = fwd(w, ids_tail, cache, pre, P - pre - 1)
                return first, spec.scatter(
                    pools, cache, wpages.reshape(b * ppseq), ps)

            fn = jax.jit(_fn, donate_argnums=(2,))
            self._prefill_store[key] = fn
        if key not in self._prefill_cache:
            self._prefill_cache[key] = fn
        tail = prompt_ids[:, h * self.page_size:]
        self._prefill_enqueued(b * (P - h * self.page_size))
        first, self.pools = fn(
            self.weights, tail, self.pools,
            jnp.asarray(shared_rows), jnp.asarray(wt_rows),
        )
        self._prefill_dispatched(key)
        return first

    # -- chunked prefill (co-scheduled with decode segments) ---------------
    def _chunk_prefill(self, ids_chunk, pt_row, base: int, creal: int,
                       slot: int = 0, nxt_chunk=None):
        """Run ONE prefill chunk for one slot: gather the slot's pages
        into a dense per-slot cache, run the transformer over the chunk
        at traced ``pos_start = base``, and scatter every page back
        through the slot's table row — or, where :meth:`_chunk_in_pages`
        says so, leave the paged layers in their pages: the family writes
        the chunk's rows where they lie and attends through the table row
        (counted: ``decode.prefill_paged_chunk_programs``).

        ONE compile class per ``("chunk", chunk_tokens, 1, impl)`` —
        prompt length, chunk index and the final chunk's real length
        ``creal`` are DATA (the final chunk is padded with token 0; in an
        attention layer causal masking keeps pad rows out of every real
        row's scores, and their K/V rows land at positions ``>= P`` that
        stay masked until decode overwrites them; a layer that SCANS the
        chunk has no such mask, and its family must stop its state at
        row ``creal - 1`` itself — ``forward_cached_row``'s ``row`` —
        and start it from zero where ``pos_start`` is 0, whatever the
        slot's rows hold), and so is the page count: the
        gather covers ALL ``pages_per_seq`` table entries (a trash entry
        gathers masked garbage and takes it back).  ``nxt_chunk``: the
        token after each of the chunk's positions, for a family stepped
        with its draft module (:meth:`_with_next`).

        Bitwise contract (:meth:`_prefill_scatter_shared`'s): positions
        ``[0, base)`` hold the bytes the earlier chunks wrote and columns
        past the write cursor are masked AFTER the scores, so the chunk's
        rows and every later step match a whole-prompt run bit for bit."""
        key = ("chunk", self.chunk_tokens, 1, self.attention_impl)
        fn, in_pages = self._prefill_store.get(key), self._chunk_in_pages()
        if fn is None:
            spec, fwd = self.cache, self._first_tokens_logged(key)
            cap, cfg, ps = self.capacity, self.config, self.page_size

            def _fn(w, ids, pools, pages, pos0, creal, *ring):
                kw = {"pages": pages[None]} if in_pages else {}
                own = spec.owned(*ring)
                cache = spec.gather(
                    spec.init_dense(1, cap, cfg.dtype, ps, in_pages), pools,
                    pages, 1, cap, in_pages=in_pages, **own)
                first, cache = fwd(w, ids, cache, pos0, creal - 1, **kw)
                return first, spec.scatter(
                    pools, cache, pages, ps, in_pages=in_pages, **own)

            fn = self._prefill_store[key] = jax.jit(_fn, donate_argnums=(2,))
        if key not in self._prefill_cache:
            self._prefill_cache[key] = fn
        self._prefill_enqueued(int(creal))
        first, self.pools = fn(
            self.weights, self._with_next(ids_chunk, nxt_chunk), self.pools,
            jnp.asarray(pt_row, jnp.int32),
            jnp.int32(base), jnp.int32(creal), *self._ring_args((slot,)),
        )
        self._prefill_dispatched(key)
        if in_pages:
            self.metrics.counter("decode.prefill_paged_chunk_programs").inc()
        return first

    def _admit_chunked(self, s: int) -> None:
        """Admit the queue head into slot ``s`` in CHUNK mode: the slot
        and the FIRST chunk's pages are claimed now; prefill itself
        happens one chunk per segment in :meth:`_advance_chunks`.  The
        slot decodes nothing (``remaining == 0``) until the last chunk
        folds, and first-token delivery fires there."""
        from ..models.kv_pages import TRASH_PAGE, pages_needed

        rid, ids, max_new = self._queue.pop(0)
        P = int(ids.shape[1])
        need = pages_needed(min(self.chunk_tokens, P), self.page_size)
        pages = self.pool.alloc(need)
        t0 = self._clock()
        self._slot_req[s] = rid
        self._slot_pages[s] = list(pages)
        # the WHOLE table row is rewritten: stale entries from the
        # slot's previous occupant would make the chunk prefill's
        # scatter-back land in pages other requests now own
        for i in range(self.pages_per_seq):
            self.page_table[s, i] = (
                pages[i] if i < len(pages) else TRASH_PAGE
            )
        self.lengths[s] = 0
        self.cur_tok[s] = 0
        self.remaining[s] = 0
        self._chunk_state[s] = {
            "rid": rid, "ids": self._np.asarray(ids), "P": P,
            "max_new": max_new, "next": 0,
        }
        if self.memprof is not None:
            # full-horizon footprint, like whole-prompt admission: the
            # profiler tracks the request's eventual residency, not the
            # lazy alloc schedule
            self.memprof.alloc(
                self._mem_node, f"kv:{rid}",
                pages_needed(P + max_new, self.page_size)
                * self._page_bytes,
                "kv_pages",
            )
        if self.ownlog is not None:
            if self.sharing:
                self.ownlog.record(
                    "assign", pages, owner=str(rid), site="admit",
                    refcounts=[self.pool.refcount(p) for p in pages],
                )
            else:
                self.ownlog.record(
                    "assign", pages, owner=str(rid), site="admit"
                )
        for rl in self._reqlogs:
            rl.admit(rid, t0)
        self.metrics.counter("decode.chunk_admitted").inc()
        if self.tracer is not None:
            self.tracer.instant(
                "admit_chunked", track="decode", cat="decode", t=t0,
                rid=str(rid), prompt_len=P,
            )
        if self.reqtrace is not None:
            self.reqtrace.admitted(rid, t0, chunked=True)
        self._emit_pool_occupancy()
        self._emit_queue_depth()

    def _advance_chunks(self, budget: Optional[int] = None) -> int:
        """Advance pending prefills by up to ``budget`` prompt tokens
        this segment — the per-segment prefill token budget that keeps
        a long prompt from starving in-flight decode.  The default
        budget is the segment's own decode-token capacity
        ``slots * seg_steps`` (floored at one chunk so progress is
        always possible): prefill may consume at most as many
        model-forward tokens per segment as the decode work it rides
        alongside.  Round-robin across prefilling slots; a slot whose
        next chunk cannot get its pages stalls (``decode.chunk_stalls``)
        and retries next segment without blocking the others.  Returns
        tokens prefilled."""
        if not self._chunk_state:
            return 0
        from ..models.kv_pages import pages_needed

        ct = self.chunk_tokens
        if budget is None:
            budget = max(ct, self.decode_rows_per_segment)
        advanced = 0
        spent_by: list = []   # rids whose chunks consumed budget here
        order = sorted(self._chunk_state)
        n = len(order)
        rr = self._chunk_rr % n
        for k in range(n):
            if budget <= 0:
                self._trace_budget_stalls(spent_by)
                break
            s = order[(rr + k) % n]
            st = self._chunk_state[s]
            P, base = st["P"], st["next"]
            C = min(ct, P - base)
            if C > budget:
                self._trace_budget_stalls(spent_by)
                break
            final = base + C >= P
            target_rows = P + st["max_new"] if final else base + C
            need = pages_needed(target_rows, self.page_size) - len(
                self._slot_pages[s]
            )
            if need > 0:
                if not self._safe_after(need, s):
                    self.metrics.counter("decode.chunk_stalls").inc()
                    if self.tracer is not None:
                        # the counter TOTAL rides the ring so the
                        # flight recorder's chunk_stall trigger can see
                        # sustained growth post hoc
                        self.tracer.counter(
                            "decode.chunk_stalls",
                            self.metrics.counter(
                                "decode.chunk_stalls"
                            ).value,
                        )
                    if self.reqtrace is not None:
                        self.reqtrace.wait(
                            st["rid"], self._clock(), "page_pool",
                            by=[
                                str(r) for r in self._slot_req
                                if r is not None and r != st["rid"]
                            ],
                        )
                    continue
                fresh = self.pool.alloc(need)
                k0 = len(self._slot_pages[s])
                self._slot_pages[s].extend(fresh)
                for i, p in enumerate(fresh):
                    self.page_table[s, k0 + i] = p
                if self.ownlog is not None:
                    if self.sharing:
                        self.ownlog.record(
                            "assign", fresh, owner=str(st["rid"]),
                            site="admit",
                            refcounts=[
                                self.pool.refcount(p) for p in fresh
                            ],
                        )
                    else:
                        self.ownlog.record(
                            "assign", fresh, owner=str(st["rid"]),
                            site="admit",
                        )
            chunk = self._np.zeros((1, ct), self._np.int32)
            chunk[0, :C] = st["ids"][0, base:base + C]
            nxt = None
            if self.rows_per_step > 1:
                # the ids shifted by one; the prompt's last position
                # takes the program's own first token (-1)
                nxt = self._np.zeros((1, ct), self._np.int32)
                nxt[0, :C] = self._np.append(
                    st["ids"][0, base + 1:base + C + 1], -1)[:C]
            # a DISPATCH span: it ends when the chunk program is enqueued
            # (no sync is added to close it "when ready"); the chunk's
            # device time is the device trace's (prefill_dev_us_tok), and
            # the host pays it inside the NEXT ``segment`` span, whose
            # readback waits behind it (or, for a prompt's last chunk,
            # in ``_fold_chunked``): ``seq`` is that segment's ordinal,
            # and the segment counts the chunk in ``prefill_programs_ahead``
            ev = None
            # a state layer's chunk begins from the state the slot holds
            # (``base > 0``) or from zero, and stops it at ``creal``
            carried = ({"state_carried": base > 0, "creal": C}
                       if self.cache.has_state else self._loop_span_args())
            if self.cache.has_state:
                self.metrics.counter(
                    "ssm.chunks_carried" if base else "ssm.first_chunks"
                ).inc()
            if self.tracer is not None:
                ev = self.tracer.begin(
                    "prefill_chunk", track="decode", cat="decode",
                    rid=str(st["rid"]), base=base, tokens=C,
                    seq=self.segments_run, **carried,
                )
            with annotate("prefill_chunk"):
                first = self._chunk_prefill(
                    jnp.asarray(chunk), self.page_table[s], base, C, s, nxt
                )
            if ev is not None:
                self.tracer.end(ev)
                if self.reqtrace is not None:
                    # same timestamps as the decode-track span: the
                    # waterfall and the engine timeline cannot disagree
                    self.reqtrace.chunk(
                        st["rid"], ev["t0"], ev["t1"], base=base,
                        tokens=C,
                    )
            spent_by.append(str(st["rid"]))
            st["next"] = base + C
            advanced += C
            budget -= C
            self.metrics.counter("decode.chunk_prefill_tokens").inc(C)
            self.metrics.counter("decode.chunk_waves").inc()
            if st["next"] >= P:
                self._fold_chunked(s, st, first)
        self._chunk_rr = (rr + 1) % n
        if advanced:
            self._emit_pool_occupancy()
        return advanced

    def _fold_chunked(self, s: int, st: Dict[str, Any], first) -> None:
        """The LAST chunk folded: its final-row logits are the first
        token, the slot flips from prefilling to decoding, and TTFT
        anchors here — mirroring the whole-prompt admission fold: the
        clock is read after the readback, when the token is on the host
        (the chunk programs still in flight, 75 ms each on the v5e, end
        before it)."""
        rid = st["rid"]
        # the readback: waits for the last chunk.  (1,) the first token,
        # or (1, 2) with the first draft behind it
        first = ([int(first[0])] if self.rows_per_step == 1
                 else self._np.asarray(first).reshape(-1))
        tok = int(first[0])
        t_done = self._clock()
        self.lengths[s] = st["P"]
        self.cur_tok[s] = first
        self.remaining[s] = st["max_new"] - 1
        self._tokens[rid] = [tok]
        self._first_tok_t[rid] = t_done
        del self._chunk_state[s]
        for rl in self._reqlogs:
            rl.first_token(rid, t_done)
        if self.reqtrace is not None:
            self.reqtrace.first_token(rid, t_done)
        sub_t = self._submit_t.pop(rid, None)
        if sub_t is not None:
            self.metrics.histogram("decode.ttft_s", unit="s").observe(
                t_done - sub_t
            )
        if st["max_new"] == 1:  # the fold produced the only token
            self._retire(s)

    def _trace_budget_stalls(self, spent_by: list) -> None:
        """The per-segment prefill token budget ran out: every chunk
        slot still mid-prefill waits on ``chunk_budget``, charged to
        the requests whose chunks consumed the budget this segment and
        the co-resident decoders the budget is sized around."""
        rt = self.reqtrace
        if rt is None:
            return
        t = self._clock()
        decoders = [
            str(self._slot_req[s]) for s in range(self.slots)
            if self._slot_req[s] is not None and self.remaining[s] > 0
        ]
        by = list(dict.fromkeys(list(spent_by) + decoders))
        for st in self._chunk_state.values():
            rid = str(st["rid"])
            if rid in spent_by or st["next"] >= st["P"]:
                continue
            rt.wait(rid, t, "chunk_budget", by=by)

    def _trace_queue_block(self, cause: str) -> None:
        """Stamp WHY admission stopped onto every queued request's
        waterfall: the head waits on the named resource (aggressors =
        the current residents holding it), everyone behind it waits on
        the head — FIFO head-of-line blocking made visible."""
        rt = self.reqtrace
        if rt is None or not self._queue:
            return
        t = self._clock()
        holders = [str(r) for r in self._slot_req if r is not None]
        head = str(self._queue[0][0])
        rt.wait(head, t, cause, by=holders)
        for entry in self._queue[1:]:
            rt.wait(str(entry[0]), t, "head_of_line", by=[head])

    # -- admission / retirement (between segments) -------------------------
    def _admit(self) -> int:
        """FIFO admission, batched: the longest same-prompt-length prefix
        of the queue that fits the free slots and the page pool is
        prefilled in one call.  Head-of-line blocking is deliberate —
        admission order stays strict FIFO (no starvation of big
        requests), batching only coalesces what FIFO would have admitted
        anyway.

        With prefix sharing the batch key tightens to ``(P, h)``: every
        request in a wave matches the same NUMBER of resident prefix
        chunks (the matched page ids are data, not shape), its page need
        drops to the fresh tail only, and the wave runs the stitched
        prefill that skips the resident portion entirely."""
        from ..models.kv_pages import (
            TRASH_PAGE,
            pages_needed,
            prefix_chunk_keys,
        )

        admitted = 0
        sharing = self.sharing
        while self._queue:
            free_slots = [
                s for s in range(self.slots) if self._slot_req[s] is None
            ]
            if not free_slots:
                self._trace_queue_block("slots_full")
                break
            P = self._queue[0][1].shape[1]
            if self.chunk_eligible(int(P)):
                # long prompt: claim a slot + first-chunk pages only and
                # prefill one chunk per segment (no whole-prompt wave)
                if self._chunked_need(
                    int(P), self._queue[0][2]
                ) > self.pool.free_pages:
                    self._trace_queue_block("page_pool")
                    break  # backpressure: head waits for frees
                self._admit_chunked(free_slots[0])
                admitted += 1
                continue
            h0 = 0
            if sharing:
                h_max = (P - 1) // self.page_size
                keys0 = prefix_chunk_keys(self._queue[0][1], self.page_size)
                h0, _ = self.pool.match_prefix(keys0[:h_max])
            batch, hits, budget = [], [], self.pool.free_pages
            seen_keys: set = set()
            for rid, ids, max_new in self._queue:
                if ids.shape[1] != P or len(batch) >= len(free_slots):
                    break
                if self.chunk_eligible(int(ids.shape[1])):
                    break  # chunk-eligible twin of a short head: next wave
                if sharing:
                    keys = prefix_chunk_keys(ids, self.page_size)
                    kt = tuple(keys[:h_max])
                    if kt and kt in seen_keys:
                        # same-wave twin: defer it ONE wave so it aliases
                        # the pages this wave is about to intern instead
                        # of prefilling its own copies
                        break
                    h, spages = self.pool.match_prefix(keys[:h_max])
                    if h != h0:
                        break
                    # fresh tail pages, plus one free-list page per
                    # matched page that is cached-free (revival draws
                    # from the free list even though the page is matched)
                    revive = sum(
                        1 for p in spages if self.pool.is_cached(p)
                    )
                    need = pages_needed(
                        ids.shape[1] + max_new, self.page_size
                    ) - h
                    if need + revive > budget:
                        break
                    budget -= revive
                else:
                    need = pages_needed(ids.shape[1] + max_new,
                                        self.page_size)
                if need > budget:
                    break
                budget -= need
                batch.append((rid, ids, max_new, need))
                if sharing:
                    if kt:
                        seen_keys.add(kt)
                    hits.append((spages, keys))
            if not batch:
                self._trace_queue_block("page_pool")
                break  # backpressure: head waits for frees
            del self._queue[:len(batch)]
            ev_wave = None
            if self.tracer is not None:
                ev_wave = self.tracer.begin(
                    "admission_wave", track="decode", cat="decode",
                    requests=len(batch), prompt_len=P,
                )
            pt_rows = self._np.full(
                (len(batch), self.pages_per_seq), TRASH_PAGE, self._np.int32
            )
            wt_rows = sh_rows = None
            if sharing and h0 > 0:
                # write table: shared prefix pages divert the prefill
                # scatter to the trash page (overwriting it is harmless
                # by design); gather table: the resident sources
                wt_rows = pt_rows.copy()
                sh_rows = self._np.zeros(
                    (len(batch), h0), self._np.int32
                )
            page_lists = []
            for j, (rid, _, _, need) in enumerate(batch):
                if sharing:
                    spages, _keys = hits[j]
                    if spages:
                        # share BEFORE alloc: a matched cached-free page
                        # must be revived before alloc pressure can
                        # evict its intern entry out from under us
                        self.pool.share(spages)
                    fresh = self.pool.alloc(need)
                    pages = list(spages) + fresh
                    # intern every FULL prompt page NOW — before the
                    # wave's prefill — so the NEXT wave of this _admit
                    # call (a same-wave twin deferred by the seen_keys
                    # break) aliases these pages instead of re-prefilling
                    # (first writer wins; the prefill that writes the
                    # content runs before any aliasing wave's stitched
                    # gather reads it)
                    for i in range(P // self.page_size):
                        self.pool.register(int(pages[i]), _keys[i])
                    if h0 > 0:
                        wt_rows[j, :len(pages)] = (
                            [TRASH_PAGE] * h0 + fresh
                        )
                        sh_rows[j] = spages
                else:
                    pages = self.pool.alloc(need)
                page_lists.append(pages)
                pt_rows[j, :len(pages)] = pages
                if self.memprof is not None:
                    self.memprof.alloc(
                        self._mem_node, f"kv:{rid}",
                        need * self._page_bytes, "kv_pages",
                    )
                if self.ownlog is not None:
                    if sharing:
                        self.ownlog.record(
                            "assign", pages, owner=str(rid), site="admit",
                            refcounts=[
                                self.pool.refcount(p) for p in pages
                            ],
                        )
                    else:
                        self.ownlog.record(
                            "assign", pages, owner=str(rid), site="admit"
                        )
            # unconditional read: t_pf0 is each batched request's
            # admission timestamp in the lifecycle log
            t_pf0 = self._clock()
            with annotate("prefill"):
                all_ids = jnp.concatenate(
                    [ids for _, ids, _, _ in batch], axis=0
                )
                if sharing and h0 > 0:
                    first = self._prefill_scatter_shared(
                        all_ids, h0, sh_rows, wt_rows
                    )
                else:
                    first = self._prefill_scatter(
                        all_ids, pt_rows, free_slots[:len(batch)])
                # (b,) first tokens, or (b, 2) with the first drafts
                first = self._np.asarray(first).reshape(len(batch), -1)
            # first token exists NOW (the prefill's readback): the
            # admission timestamp is each request's TTFT anchor
            t_adm = self._clock()
            if self.tracer is not None:
                self.tracer.complete(
                    "prefill", t_pf0, t_adm, track="decode", cat="decode",
                    requests=len(batch), prompt_len=P,
                )
            ttft_h = self.metrics.histogram("decode.ttft_s", unit="s")
            for j, (rid, ids, max_new, _) in enumerate(batch):
                s = free_slots[j]
                self.page_table[s] = pt_rows[j]
                self.lengths[s] = P
                self.cur_tok[s] = first[j]
                self.remaining[s] = max_new - 1
                self._slot_req[s] = rid
                self._slot_pages[s] = page_lists[j]
                self._tokens[rid] = [int(first[j, 0])]
                self._first_tok_t[rid] = t_adm
                if sharing:
                    # intern happened pre-prefill (same-wave aliasing);
                    # the prefill physically wrote the fresh pages,
                    # which the write witness records here
                    if self.ownlog is not None:
                        freshp = page_lists[j][h0:]
                        self.ownlog.record(
                            "write", freshp, owner=str(rid), site="admit",
                            refcounts=[
                                self.pool.refcount(p) for p in freshp
                            ],
                        )
                # t_pf0/t_adm are the same floats the histograms see:
                # record-derived TTFT == histogram sample, bitwise
                for rl in self._reqlogs:
                    rl.admit(rid, t_pf0)
                    rl.first_token(rid, t_adm)
                if self.reqtrace is not None:
                    self.reqtrace.admitted(
                        rid, t_pf0, wave=[b[0] for b in batch],
                    )
                    self.reqtrace.prefill(
                        rid, t_pf0, t_adm, tokens=int(P),
                        wave_size=len(batch), shared_pages=h0,
                    )
                    self.reqtrace.first_token(rid, t_adm)
                sub_t = self._submit_t.pop(rid, None)
                if sub_t is not None:
                    ttft_h.observe(t_adm - sub_t)
                if max_new == 1:  # prefill produced the only token
                    self._retire(s)
            admitted += len(batch)
            self.metrics.counter("decode.admission_waves").inc()
            if sharing:
                self.metrics.counter("decode.prefix_shared_pages").inc(
                    h0 * len(batch)
                )
            if ev_wave is not None:
                self.tracer.end(ev_wave)
            self._emit_pool_occupancy()
            self._emit_queue_depth()
        return admitted

    def _retire(self, s: int) -> None:
        rid = self._slot_req[s]
        self._release_pages(self._slot_pages[s], str(rid), "retire")
        if self.memprof is not None:
            self.memprof.free(self._mem_node, f"kv:{rid}")
        self.results[rid] = self._np.asarray(
            self._tokens.pop(rid), dtype=self._np.int32
        )
        self._slot_req[s] = None
        self._slot_pages[s] = []
        self.metrics.counter("decode.requests_completed").inc()
        # TPOT = steady-state inter-token gap: last token's arrival (this
        # retire happens at the segment fold that produced it) minus the
        # first token's, over n-1 gaps; single-token requests have none
        n = len(self.results[rid])
        t_first = self._first_tok_t.pop(rid, None)
        # ONE clock read feeds the histogram, the lifecycle log, and the
        # trace marker — record-derived TPOT == histogram sample, bitwise
        t_ret = self._clock()
        if t_first is not None and n > 1:
            self.metrics.histogram("decode.tpot_s", unit="s").observe(
                (t_ret - t_first) / (n - 1)
            )
        for rl in self._reqlogs:
            rl.retire(rid, t_ret)
        if self.tracer is not None:
            self.tracer.instant(
                "retire", track="decode", cat="decode", t=t_ret,
                rid=str(rid), tokens=n,
            )
        if self.reqtrace is not None:
            self.reqtrace.retire(rid, t_ret, tokens=n)

    def preempt(
        self, rid: Any, *, cause: Optional[str] = None, by: Any = None,
    ) -> Dict[str, Any]:
        """Evict an in-flight request: free its pages back to the pool
        and hand the generated prefix to the caller for re-queueing.
        ``cause`` stamps the lifecycle record's terminal cause code
        (e.g. ``preempt_tier0_victim``); ``by`` names the request the
        eviction made room for (the waterfall's interference arrow).

        Preemption is the capacity lever priority scheduling needs: a
        high-tier arrival that cannot be admitted (no free slot, no free
        pages) reclaims a low-tier slot NOW instead of waiting out its
        decode.  No progress is lost — greedy decode is deterministic,
        so re-submitting ``prompt + tokens`` (under a new rid) with the
        returned ``remaining`` budget reproduces the exact continuation
        an unpreempted run of that prompt would generate (asserted by
        ``tests/test_serve.py``).

        Only valid between segments, for a rid currently occupying a
        slot (queued/retired rids raise — nothing to evict).  Returns
        ``{"rid", "tokens", "remaining"}``: ``tokens`` the (k,) int32
        generated prefix (prefill token included), ``remaining`` the
        decode steps still owed.  The lifecycle record ends in the
        terminal ``preempted`` state.
        """
        from ..models.kv_pages import TRASH_PAGE

        slot = next(
            (s for s in range(self.slots) if self._slot_req[s] == rid),
            None,
        )
        if slot is None:
            raise ValueError(f"rid {rid!r} is not in flight")
        if slot in self._chunk_state:
            raise ValueError(
                f"rid {rid!r} is mid-chunked-prefill and not preemptible "
                "(no first token yet — there is no resumable prefix)"
            )
        tokens = self._np.asarray(
            self._tokens.pop(rid), dtype=self._np.int32
        )
        remaining = int(self.remaining[slot])
        self._release_pages(self._slot_pages[slot], str(rid), "preempt")
        if self.cache.has_state:
            # no snapshot is kept: the resume re-prefills prompt + tokens
            # and rebuilds the state chunk by chunk from zero
            self.metrics.counter("decode.state_rebuilds").inc()
        if self.memprof is not None:
            self.memprof.free(self._mem_node, f"kv:{rid}")
        self.page_table[slot] = TRASH_PAGE
        self.lengths[slot] = 0
        self.cur_tok[slot] = 0
        self.remaining[slot] = 0
        self._seg_carry[slot] = False
        self._slot_req[slot] = None
        self._slot_pages[slot] = []
        self._first_tok_t.pop(rid, None)
        t_pre = self._clock()
        for rl in self._reqlogs:
            rl.preempt(rid, t_pre, cause)
        if self.tracer is not None:
            self.tracer.instant(
                "preempt", track="decode", cat="decode", t=t_pre,
                rid=str(rid), tokens=int(tokens.shape[0]),
                remaining=remaining,
            )
        if self.reqtrace is not None:
            self.reqtrace.preempt(rid, t_pre, by=by, cause=cause)
        self._emit_pool_occupancy()
        return {"rid": rid, "tokens": tokens, "remaining": remaining}

    # -- the serving loop --------------------------------------------------
    @property
    def decode_rows_per_segment(self) -> int:
        """Model-forward rows one segment's decode steps run: what the
        default per-segment prefill budget is sized by (DEC006's
        ``decode_budget``)."""
        return self.slots * self.seg_steps * self.rows_per_step

    def step_segment(self) -> int:
        """Admit, advance pending prefill chunks (one chunk-token budget
        per segment), run ONE K-step segment, fold tokens, retire
        finished slots.  Returns the number of tokens delivered to
        requests."""
        # in-flight prefills advance BEFORE new admission so chunk
        # slots claim their next pages first (admission would otherwise
        # starve a mid-prefill long of pages every segment); a freshly
        # chunk-admitted request then spends whatever prefill budget is
        # left, so its first chunk still lands this segment
        ct = self.chunk_tokens
        full = (max(ct, self.decode_rows_per_segment)
                if ct is not None else 0)
        spent = self._advance_chunks() if self._chunk_state else 0
        with annotate("admit"):
            t_a0 = self._clock() if self.tracer is not None else 0.0
            admitted = self._admit()
            if self.tracer is not None:
                # the engine's half of admission (the front-end's is its
                # own ``admit`` span): no chunk program is dispatched
                # inside; whole-prompt waves nest in it
                self.tracer.complete(
                    "admit", t_a0, self._clock(), track="decode",
                    cat="decode", admitted=admitted,
                    queue_depth=len(self._queue),
                )
        if (ct is not None and spent < full and any(
                st["next"] == 0 for st in self._chunk_state.values())):
            self._advance_chunks(full - spent)
        owed = self.remaining.copy()
        if not owed.any():
            # nothing to decode: the per-segment prefill throttle
            # protects nobody, so drain pending chunks back-to-back
            # until one folds into decodable work (or all stall on
            # pages) — a lone long prompt prefills at full speed
            while self._chunk_state and not self.remaining.any():
                if not self._advance_chunks():
                    break
            owed = self.remaining.copy()
            if not owed.any():
                return 0
        self._ensure_exclusive()
        # how much of the page table the segment's attention will walk,
        # from the host's own lengths (slots not decoding sit at 0): once
        # per dispatched segment, into the engine's registry and the
        # process-wide always-on one
        # (a step of R rows walks to its last row's block)
        share = kv_live_block_share(
            self.lengths + (self.rows_per_step - 1) * (owed > 0),
            self.kv_block_rows, self.capacity
        )
        for reg in (self.metrics, process_metrics()):
            reg.histogram(
                "decode.kv_live_block_share", unit="ratio"
            ).observe(share)
        with annotate("segment"):
            t_sg0 = self._clock()
            ahead, self._prefill_ahead = self._prefill_ahead, [0, 0]
            toks, self.pools, *stats = self._seg(
                self.weights, self.pools, self.page_table, self.lengths,
                self.cur_tok, self.remaining,
            )
            toks = self._np.asarray(toks)  # the one readback per segment
            emitted, steps_ran, probe, span_args = self._emitted(toks, owed)
            # counted on the device, same program: no new sync
            self._observe_stats(
                stats[0] if stats else {}, owed, steps_ran, probe, span_args)
            # the fold timestamp: every token this segment delivered
            # became host-visible at this readback (lifecycle-log
            # delivery events)
            t_sg1 = self._clock()
        self._observe_interval(owed, steps_ran, ahead, t_sg1)
        with annotate("fold"):
            return self._fold_segment(emitted, owed, t_sg0, t_sg1)

    def _observe_interval(self, owed, steps_ran: int, ahead,
                          t_sg1: float) -> None:
        """What stood between the previous delivery to this segment's
        *continuing* slots and this one, onto the ``segment`` span and —
        tracer or not — into the engine's registry and the process-wide
        one.  A continuing slot decoded in the previous dispatched
        segment too and still holds the same request, which received
        nothing in between: the two readback stamps' difference is the
        gap between two deliveries as its user saw it.  ``ahead``:
        prefill programs (and their real tokens) enqueued since the
        previous segment's dispatch; the device runs them first, so this
        segment's readback waited for them.  A segment after an empty
        engine, or whose decoding slots are all new, has no period."""
        programs, tokens = ahead
        continuing = int((self._seg_carry & (owed > 0)).sum())
        args = {"seq": self.segments_run, "steps_ran": steps_ran,
                "continuing": continuing,
                "prefill_programs_ahead": programs,
                "prefill_tokens_ahead": tokens}
        if continuing:
            args["period_s"] = t_sg1 - self._seg_prev_t1
            period_ms = args["period_s"] * 1e3
            for reg in (self.metrics, process_metrics()):
                reg.histogram("decode.step_interval_ms", unit="ms").observe(
                    period_ms / max(steps_ran, 1))
                reg.histogram("decode.seg_period_ms", unit="ms").observe(
                    period_ms)
                reg.counter("decode.segments_continuing").inc()
                if programs:
                    reg.counter("decode.segments_behind_prefill").inc()
        self._seg_prev_t1 = t_sg1
        self._seg_span_args.update(args)
        # pages in use of the pool's allocatable ones, once a dispatched
        # segment (the gauge beside it keeps the last value only)
        used = self.pool.used_pages / max(self.pool.n_pages - 1, 1)
        for reg in (self.metrics, process_metrics()):
            reg.histogram(
                "decode.page_pool_used_share", unit="ratio").observe(used)

    def _emitted(self, toks, owed):
        """What a segment's readback gave each slot: ``(tokens of slot s
        in order, steps in which a slot still decoded, arrays for the
        ``stats_probe``, arguments for the ``segment`` span)``.  One row a
        step: slot ``s`` ran ``min(owed,
        seg_steps)`` steps, a token each.  A verifying step (``toks``
        (S, steps, 4) = ``[y0, y1, count, next draft]``): the first
        ``count`` of each step's two; the draft the NEXT segment verifies
        goes to ``cur_tok`` here, the counts into the ``mtp.*`` metrics."""
        np = self._np
        if self.rows_per_step == 1:
            ran = np.minimum(owed, self.seg_steps)
            return ([toks[s, :ran[s]] for s in range(self.slots)],
                    min(int(owed.max()), self.seg_steps), {}, {})
        counts = toks[..., 2]
        took = np.arange(2)[None, None, :] < counts[..., None]
        # the drafts the steps verified: each step's is the one the step
        # before left (the first step's came with the segment)
        drafts = np.concatenate(
            [self.cur_tok[:, 1:], toks[:, :-1, 3]], axis=1)
        self.cur_tok[:, 1] = toks[:, -1, 3]
        verified = int((counts > 0).sum())
        accepted = int((counts == 2).sum())
        for reg in (self.metrics, process_metrics()):
            reg.histogram("mtp.accept_rate", unit="ratio").observe(
                accepted / max(verified, 1))
            reg.histogram("mtp.tokens_per_step", unit="tokens").observe(
                int(counts.sum()) / max(verified, 1))
            reg.counter("mtp.drafts_verified").inc(verified)
            reg.counter("mtp.drafts_accepted").inc(accepted)
            # a rejected draft's row, in every pool, is written over
            reg.counter("mtp.rows_rolled_back").inc(verified - accepted)
        return ([toks[s, :, :2][took[s]] for s in range(self.slots)],
                int((counts > 0).any(axis=0).sum()),
                {"mtp_counts": counts, "mtp_drafts": drafts},
                {"drafts": verified, "accepted": accepted,
                 "tokens": int(counts.sum())})

    def _observe_stats(self, stats, owed, steps_ran: int, probe,
                       span_args) -> None:
        """What the segment's layers counted on the device, onto the
        next ``segment`` span and into the registries: an expert
        family's routing counts (an array, or ``stats["moe"]``) and a
        sparse-selection family's rows (``stats["dsa"]``, (steps, full
        layers, 2) = (latent rows the decoding slots' attention read,
        rows those slots hold)) or a window-layer family's
        (``stats["attn"]``, (steps, layers, 2) = (rows the decoding
        slots' attention read in the full layers, in the window
        layers)) or a state-layer family's (``stats["ssm"]`` / ``["conv"]``,
        (steps, state layers) = the slots each layer stepped, every layer
        the same).  Any other named array, and what :meth:`_emitted` read
        for it (``probe``), is the ``stats_probe``'s, if one is set."""
        np = self._np
        if not isinstance(stats, dict):
            stats = {"moe": stats}
        args = dict(span_args)
        if "moe" in stats:
            args.update(self._observe_moe(
                np.asarray(stats["moe"]), steps_ran))
        if "dsa" in stats:
            read, held = np.asarray(stats["dsa"]).reshape(-1, 2).sum(axis=0)
            args.update(rows_selected=float(read), rows_live=float(held))
            if held:
                for reg in (self.metrics, process_metrics()):
                    reg.histogram(
                        "dsa.selected_share", unit="ratio"
                    ).observe(float(read / held))
        if "attn" in stats:
            full, ring = np.asarray(stats["attn"]).reshape(-1, 2).sum(axis=0)
            args.update(rows_full=float(full), rows_window=float(ring))
            if full + ring:
                for reg in (self.metrics, process_metrics()):
                    reg.histogram(
                        "attn.full_row_share", unit="ratio"
                    ).observe(float(full / (full + ring)))
        if "loop" in stats:
            args.update(self._observe_loop(np.asarray(stats["loop"])))
        for kind in ("ssm", "conv"):
            if kind in stats:
                args[f"{kind}_slots"] = self._observe_slots_stepped(
                    kind, np.asarray(stats[kind]), steps_ran)
        self._seg_span_args = args
        if self.stats_probe is not None and (stats or probe):
            self.stats_probe(
                {**{k: np.asarray(v) for k, v in stats.items()
                    if k not in ("moe", "dsa", "attn", "ssm", "conv", "loop")},
                 **probe},
                list(self._slot_req), self.lengths.copy(), owed)

    def _observe_slots_stepped(self, kind: str, stepped,
                               steps_ran: int) -> float:
        """A state-layer family's count of one segment, ``stepped``
        (steps, state layers of ``kind``) = the slots whose state each
        layer updated, every layer the same: histogram ``{kind}.
        slots_stepped`` (mean over the steps in which a slot still
        decoded) in the engine's registry and the process-wide one.
        Returns the slot-steps of the whole segment, for its span."""
        stepped = stepped[:, 0]
        for reg in (self.metrics, process_metrics()):
            reg.histogram(f"{kind}.slots_stepped", unit="slots").observe(
                float(stepped[:max(steps_ran, 1)].mean()))
        return float(stepped.sum())

    def _observe_loop(self, loop) -> Dict[str, float]:
        """A looped stack's counts of one segment, ``loop`` (steps,
        passes, 1, 2) = (slots that ran the pass, their ``(u + 1) p_u``
        of the exit gate summed), into the registries: the layer-stack
        passes a decoded token cost, what the gate's distribution would
        have cost it, the layer applications in all."""
        passes_ran = float(loop[..., 0].sum())
        slot_steps = float(loop[:, 0, ..., 0].sum())
        args = {"passes": int(loop.shape[1]),
                "rows_live": float((self.lengths * (self.remaining > 0)).sum())}
        if slot_steps:
            for reg in (self.metrics, process_metrics()):
                reg.histogram("loop.passes_per_token", unit="passes").observe(
                    passes_ran / slot_steps)
                reg.histogram(
                    "loop.exit_pass_expected", unit="passes").observe(
                        float(loop[..., 1].sum()) / slot_steps)
                reg.counter("loop.layer_passes").inc(
                    int(round(passes_ran)) * self.n_layers)
        return args

    def _observe_moe(self, stats, steps_ran: int) -> Dict[str, float]:
        """An expert family's routing counts of one segment, ``stats``
        (steps, expert layers, 2) = (share of the experts picked, largest
        expert's picks over the mean), into the engine's registry and the
        process-wide one: the median over the layer-steps in which a
        slot still decoded (the first ``steps_ran``)."""
        np = self._np
        ran = stats[:steps_ran].reshape(-1, 2)
        touched, imbalance = (float(v) for v in np.median(ran, axis=0))
        for reg in (self.metrics, process_metrics()):
            reg.histogram(
                "moe.experts_touched_share", unit="ratio").observe(touched)
            reg.histogram(
                "moe.pick_imbalance", unit="ratio").observe(imbalance)
        # for the segment's span: the mean distinct experts a layer-step
        # read, over ALL the segment's steps (a step in which no slot
        # decodes any more reads none, and its expert kernel is called
        # all the same): what the kernel's mean bytes follow
        return {"experts_touched": float(
            stats[..., 0].mean() * getattr(
                self.config, "n_held_experts", self.config.n_routed_experts))}

    def _fold_segment(self, emitted, owed, t_sg0: float,
                      t_sg1: float) -> int:
        """What follows a segment's readback at ``t_sg1``: the tokens
        ``emitted[s]`` go to their requests (a slot's count is what its
        steps yielded — one a step, or one or two where drafts are
        verified — never more than it owed), finished slots retire, the
        gauges are sampled.  Returns the tokens delivered."""
        ran = self._np.asarray([len(t) for t in emitted], self._np.int32)
        if self.tracer is not None:
            self.tracer.complete(
                "segment", t_sg0, t_sg1, track="decode",
                cat="decode", steps=self.seg_steps,
                active=int((owed > 0).sum()), **self._seg_span_args,
            )
        if self.reqtrace is not None:
            # per-request decode spans reuse the segment's two hoisted
            # timestamps: the waterfall cannot disagree with the engine
            # timeline, and the bare run reads the clock no extra time
            residents = [
                str(self._slot_req[s]) for s in range(self.slots)
                if self._slot_req[s] is not None and owed[s] > 0
            ]
            for s in range(self.slots):
                rid = self._slot_req[s]
                if rid is None or owed[s] <= 0:
                    continue
                self.reqtrace.segment(
                    rid, t_sg0, t_sg1, tokens=int(ran[s]),
                    co_resident=residents,
                )
        # slot state advances host-side exactly as the device's carry
        # did: a cached row and one token less owed for every token
        # emitted, the current token the last one emitted
        self.lengths = self.lengths + ran
        self.remaining = owed - ran
        self._seg_carry = self.remaining > 0
        delivered = retired = 0
        for s in range(self.slots):
            rid = self._slot_req[s]
            if rid is None:
                continue
            n = int(ran[s])
            if n:
                self._tokens[rid].extend(emitted[s].tolist())
                self.cur_tok[s, 0] = emitted[s][-1]
                delivered += n
                for rl in self._reqlogs:
                    rl.deliver(rid, t_sg1, n)
            # owed == 0 means the slot is mid-chunk-prefill (occupied,
            # decoding nothing yet) — it retires only after its fold
            if owed[s] > 0 and self.remaining[s] == 0:
                self._retire(s)
                retired += 1
        self.segments_run += 1
        self.metrics.counter("decode.segments_run").inc()
        self.metrics.counter("decode.tokens_delivered").inc(delivered)
        self._emit_pool_occupancy()
        self._emit_queue_depth()
        if self.tracer is not None:
            self.tracer.complete(
                "fold", t_sg1, self._clock(), track="decode", cat="decode",
                delivered=delivered, retired=retired,
            )
        return delivered

    def run(self) -> Dict[Any, Any]:
        """Drain the queue and all active slots; returns {rid: np.int32
        tokens} (prompt excluded; exactly ``max_new_tokens`` each)."""
        def _sig():
            # progress signature: any admission, decode step, chunk
            # advance, or retirement changes it.  Two identical
            # consecutive signatures mean NOTHING can ever move again
            # (the engine is deterministic between segments).
            return (
                len(self.results), len(self._queue),
                int(self.lengths.sum()), int(self.remaining.sum()),
                tuple(sorted(
                    (s, st["next"])
                    for s, st in self._chunk_state.items()
                )),
            )

        while self._queue or any(r is not None for r in self._slot_req):
            before = _sig()
            self.step_segment()
            if _sig() == before:
                raise RuntimeError(
                    "engine stalled: queued requests cannot be admitted "
                    f"({self.pool.free_pages} free pages)"
                )
        # every retire returned its pages, so this is 0 on a clean drain —
        # a nonzero value in a snapshot IS the leak check failing
        self.metrics.gauge("decode.pages_leaked", unit="pages").set(
            (self.pool.n_pages - 1) - self.pool.free_pages
        )
        return self.results

    def _safe_after(self, take: int, s: Optional[int] = None,
                    horizon: Optional[int] = None) -> bool:
        """Whether ``take`` more pages may go to slot ``s`` mid-prefill —
        or to a new chunked request of ``horizon`` pages in all — and
        leave every slot mid-prefill an order to finish in (the banker's
        rule).  Chunks allocate lazily, so on a pool smaller than ``slots
        x pages_per_seq`` two long prompts can grow into pages neither
        can finish in and wait on each other for ever.  A decoding slot
        holds its whole horizon and returns it when it retires, so what
        the slots mid-prefill can count on is the free pages and the
        decoding slots'; least still owed first, each must fit what is
        there and then returns what it held."""
        from ..models.kv_pages import pages_needed

        there = self.pool.free_pages - take
        if there < 0:
            return False
        owed = [] if horizon is None else [(horizon - take, take)]
        for t in range(self.slots):
            held = len(self._slot_pages[t]) + (take if t == s else 0)
            st = self._chunk_state.get(t)
            if st is None:
                there += held
            else:
                owed.append((pages_needed(
                    st["P"] + st["max_new"], self.page_size) - held, held))
        for need, held in sorted(owed):
            if need > there:
                return False
            there += held
        return True

    def _chunked_need(self, prompt_len: int, max_new: int) -> int:
        """What a chunk-eligible request needs ``pool.free_pages`` to be
        to enter: its first chunk's pages, or more than there are where
        it may not enter yet (:meth:`_safe_after`)."""
        from ..models.kv_pages import pages_needed

        first = pages_needed(
            min(self.chunk_tokens, prompt_len), self.page_size)
        if self._safe_after(first, horizon=pages_needed(
                prompt_len + max_new, self.page_size)):
            return first
        return self.pool.free_pages + 1

    def _chunk_in_pages(self) -> bool:
        """Whether a chunk program leaves the paged layers in their pages
        instead of gathering the slot's whole capacity dense, turning it
        heads-first for the family and scattering every page back.
        Decided by what can be observed, no knob: a ``kv`` cache of one
        row a step whose family's prefill takes the table row
        (``PREFILL_TAKES_PAGES``), chunks of whole pages, and a shape the
        paged chunk kernel admits under this engine's attention impl.
        Everything else keeps the round trip: a latent row needs no turn
        and pays little for it, GPT-2's head of 64 is no whole lane tile,
        and the gather path (``xla``) has no paged form."""
        from ..ops.gqa_attention import gqa_paged_chunk_impl

        return (
            self.cache.kind == "kv" and self.rows_per_step == 1
            and getattr(module_of(self.config), "PREFILL_TAKES_PAGES", False)
            and self.chunk_tokens % self.page_size == 0
            and gqa_paged_chunk_impl(
                self.attention_impl, self.page_size, self.cache.head_dim,
                self.config.dtype) != "xla")

    def _planes(self, page: int):
        """A page id as :attr:`_cow_copy` takes it: in every plane of a
        cache whose layers run more than once, else the id itself."""
        if self.cache.passes == 1:
            return jnp.int32(page)
        return jnp.asarray([page + u * self.pool.n_pages
                            for u in range(self.cache.passes)], jnp.int32)

    def _loop_span_args(self) -> Dict[str, Any]:
        """What a ``prefill_chunk`` span says of a looped stack."""
        return {"passes": self.cache.passes} if self.cache.passes > 1 else {}


# below everything: no line above it moves (ROADMAP D16)
from .decode_passes import compose_looped_step_fn as _looped  # noqa: E402

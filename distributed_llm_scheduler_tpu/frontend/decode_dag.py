"""KV-cache decode step as a task DAG: inference through the scheduler.

The task-graph path (the repo's thesis) and the whole-program decode loop
(:mod:`..models.decode`) are deliberately twinned everywhere else; these
builders close the last gap: the scheduling layer never saw an inference
workload.  One cached forward step becomes a per-layer task DAG (embed
task -> one task a layer -> logits task) where the **cache is placeable
parameters**, so *cache residency IS the placement problem* — the same
param-cache-locality story the reference's MRU policy targets, with the
model's largest decode-time tensors.

Two builders, both family-blind: which model this is, and everything it
offers, is asked of :mod:`..models` (the family registry) and answered by
the family module's own functions — ``docs/ARCHITECTURE.md``, "Adding a
model family".

* :func:`build_decode_dag` — the dense step: prefill (``pos = 0``,
  ``step_len`` = prompt length) or a decode step (``step_len = 1``) over
  per-layer ``cache_k_{i}`` / ``cache_v_{i}`` slabs (``B x Hkv x max_len
  x hd``).  Each layer task outputs ``{"x", "k_new", "v_new", "pos"}`` —
  the functional cache-update slices the caller applies to its cache copy
  (retained via ``execute(keep_outputs=True).task_outputs``), so
  execution stays pure.  The step position is a TRACED runtime input
  (``{"ids", "pos"}``), threaded through each task's output dict:
  attention masks against it, RoPE / wpe rows are dynamic-sliced at it,
  cache updates land at it.  ONE graph therefore serves every position
  of a given ``(step_len, max_len)`` class — an N-token generation
  compiles exactly two programs (prefill + decode step), not N.  Compute
  per step is O(max_len) regardless of position (the cache is scanned
  fully, masked), which is also what the FLOPs fields record.
* :func:`build_paged_decode_dag` — the single-token step over shared
  page pools, the one that reaches
  :class:`...backends.decode_loop.PagedDecodeEngine`.

Oracle of both: the family's cached forward on the same cache (logits
exact, multi-step greedy tokens exact — ``tests/test_decode_dag.py``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.graph import Task, TaskGraph
from ..models import (
    cache_spec,
    draft_rows,
    family_of,
    family_module,
    model_config,
)
from .gpt2_dag import DEFAULT_EFFECTIVE_FLOPS, ModelDAG, make_task_adder


class DecodeDAG(ModelDAG):
    """ModelDAG whose graph input is ``{"ids": (B, T) int32, "pos": ()
    int32}`` — position is runtime data, so one graph serves every step
    of its ``(step_len, max_len)`` class.  ``default_pos`` seeds
    ``make_inputs`` (callers stepping a generation pass their own)."""

    default_pos: int = 0

    def make_inputs(self, key: Optional[jax.Array] = None,
                    pos: Optional[int] = None) -> Dict[str, jax.Array]:
        key = key if key is not None else jax.random.PRNGKey(1)
        shape = self.input_spec["ids"].shape
        return {
            "ids": jax.random.randint(
                key, shape, 0, self.config.vocab_size, dtype=jnp.int32
            ),
            "pos": jnp.asarray(
                self.default_pos if pos is None else pos, jnp.int32
            ),
        }


def decode_inputs(
    ids: jax.Array, pos, max_len: Optional[int] = None
) -> Dict[str, jax.Array]:
    """The decode graphs' input pytree for a concrete step.

    Pass ``max_len`` to get the bounds check the build-time guard can no
    longer provide (position is runtime data): an out-of-range position
    would otherwise CLAMP the cache write (``dynamic_update_slice``
    semantics) and silently corrupt the last cache row.
    """
    ids = jnp.asarray(ids, jnp.int32)
    if max_len is not None and not isinstance(pos, jax.core.Tracer):
        if int(pos) + ids.shape[-1] > max_len:
            raise ValueError(
                f"pos {int(pos)} + step_len {ids.shape[-1]} exceeds "
                f"max_len {max_len}"
            )
    return {"ids": ids, "pos": jnp.asarray(pos, jnp.int32)}


def _chain(fam, config, spec, add, flops, f_embed, layer_fn, f_head,
           shared_alias):
    """The skeleton both builders share: embed task -> one task a layer
    -> logits task, through ``add`` (:func:`.gpt2_dag.make_task_adder`).
    ``layer_fn(i)`` makes layer ``i``'s task fn; layers with the same
    local param names and pool kinds share ONE fn object (they must then
    compute the same function), so per-task dispatch compiles each once,
    not once a layer.  ``shared_alias`` is what every layer that caches
    rows aliases beside its weights and its ``cache_{kind}`` (a state
    layer, or one that caches nothing, reads no table).  The spec's draft
    layers are not the chain's: the paged builder hangs the ``draft``
    task behind the logits task."""
    embed_flops, layer_flops, head_flops = flops
    add("embed", f_embed, [], {k: k for k in fam.EMBED_PARAMS}, embed_flops,
        "embed")
    prev, fns = "embed", {}
    for i in range(spec.n_layers - spec.draft_layers):
        alias = dict(fam.layer_param_names(config, i))
        fn = fns.get(key := (*alias, *spec.layer_kinds(i)))
        if fn is None:
            fn = fns[key] = layer_fn(i)
        alias.update(
            {f"cache_{k}": f"cache_{k}_{i}" for k in spec.layer_kinds(i)})
        if spec.layer_kinds(i) and not spec.layer(i).state:
            alias.update(shared_alias)
        tid = f"layer_{i}"
        add(tid, fn, [prev], alias, layer_flops[i], tid)
        prev = tid
    add("logits", f_head, [prev], {k: k for k in fam.HEAD_PARAMS},
        head_flops, "head")


def _name(family: str, what: str, spec, out_specs, geometry: str,
          config: Any) -> str:
    """``{family}{what}_{L}l_d{residual width}_{geometry}[_{dtype}]``;
    ``L`` counts the model's own layers (not a draft module's), the
    width is the last dimension on the embed edge."""
    width = out_specs["embed"]["x"].shape[-1]
    return (f"{family}{what}_{spec.n_layers - spec.draft_layers}l_d{width}"
            f"_{geometry}"
            + ("" if config.dtype == jnp.float32
               else f"_{jnp.dtype(config.dtype).name}"))


def build_decode_dag(
    config: Any = None,
    batch: int = 1,
    step_len: int = 1,
    pos: int = 0,
    max_len: int = 128,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
) -> ModelDAG:
    """Task DAG for one cached forward step of any family that offers the
    dense-cache functions (``models.CACHED_FUNCTIONS``: GPT-2, Llama,
    Mixtral); position is a runtime input.

    ``step_len > 1`` is the prefill class; ``step_len = 1`` the decode
    class — one graph per class covers every position (``pos`` here only
    seeds ``make_inputs``' default and validates against ``max_len``).
    Params are the model weights PLUS per-layer ``cache_k_{i}`` /
    ``cache_v_{i}`` slabs (zeros from ``init_params``; load real cache
    state by overwriting those entries).  The graph's sink is the logits
    task; each layer's cache-update dict is retained via
    ``execute(keep_outputs=True).task_outputs`` — apply updates with
    :func:`apply_cache_updates`.
    """
    config = config or model_config("gpt2-tiny")
    if pos + step_len > max_len:
        raise ValueError(
            f"pos {pos} + step_len {step_len} exceeds max_len {max_len}"
        )
    name = family_of(config)
    fam = family_module(name)
    B, T, M = batch, step_len, max_len
    spec = fam.cache_spec(config)

    specs = {
        k: jax.ShapeDtypeStruct(shape, dtype)
        for k, (shape, dtype) in fam.param_shapes(config).items()
    }
    specs.update(jax.eval_shape(
        lambda: spec.init_slabs(B, M, config.dtype)))
    input_spec = {
        "ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }

    def f_embed(p, inputs):
        pos_t = inputs["pos"]
        return {"x": fam.cached_embed(p, inputs["ids"], pos_t, config),
                "pos": pos_t}

    def layer_fn(i):
        def f_layer(p, prev):
            """One cached layer: the residual stream, this step's
            cache-update slices, and the threaded position."""
            x, new = fam.cached_layer(p, prev["x"], prev["pos"], config, i)
            return {"x": x, "pos": prev["pos"],
                    **{f"{k}_new": v for k, v in new.items()}}
        return f_layer

    def f_head(p, prev):
        return fam.head(p, prev["x"], config)

    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    _chain(
        fam, config, spec,
        make_task_adder(tasks, out_specs, specs, input_spec, effective_flops),
        fam.cached_flops(config, B, T, M), f_embed, layer_fn, f_head, {})

    def init_fn(key):
        params = fam.init_params(config, key)
        params.update(spec.init_slabs(B, M, config.dtype))
        return params

    def reference_forward(params, inputs):
        """Whole-program oracle over the same cache params: stacked-layer
        cache assembled from the per-layer slabs, models/decode math."""
        cache = {
            kind: jnp.stack([params[f"cache_{kind}_{i}"]
                             for i in range(spec.n_layers)])
            for kind in spec.kinds
        }
        model_params = {
            k: v for k, v in params.items() if not k.startswith("cache_")
        }
        logits, _ = fam.forward_cached(
            model_params, inputs["ids"], cache, inputs["pos"], config
        )
        return logits

    graph = TaskGraph(tasks, name=_name(
        name, "dec", spec, out_specs, f"b{B}_t{T}_m{M}", config)).freeze()
    dag = DecodeDAG(
        graph=graph,
        config=config,
        input_spec=input_spec,
        param_specs=specs,
        reference_forward=reference_forward,
        init_fn=init_fn,
    )
    dag.default_pos = pos
    return dag


class PagedDecodeDAG(ModelDAG):
    """ModelDAG for the paged decode step: inputs are ``{"ids": (S, R)
    int32, "lengths": (S,) int32}`` — per-slot ragged positions instead
    of one shared scalar, ``R`` = ``rows_per_step`` rows a slot (1, or
    the family's ``DECODE_ROWS`` where it is stepped with its draft
    module) — and the cache params are shared page pools plus the
    ``page_table`` param (:mod:`..models.kv_pages`)."""

    slots: int = 1
    page_size: int = 0
    pages_per_seq: int = 0
    rows_per_step: int = 1

    @property
    def attention_impl(self) -> Optional[str]:
        """The attention impl baked into the layer tasks (None = op-level
        auto): stamped once, on the graph — the engine receives the bare
        TaskGraph and keys its prefill compile-class cache on it."""
        return self.graph.attention_impl

    def make_inputs(self, key: Optional[jax.Array] = None,
                    lengths: Optional[Any] = None) -> Dict[str, jax.Array]:
        key = key if key is not None else jax.random.PRNGKey(1)
        shape = self.input_spec["ids"].shape
        S = shape[0]
        out = {
            "ids": jax.random.randint(
                key, shape, 0, self.config.vocab_size, dtype=jnp.int32
            ),
            "lengths": (
                jnp.zeros((S,), jnp.int32) if lengths is None
                else jnp.asarray(lengths, jnp.int32)
            ),
        }
        if "active" in self.input_spec:
            out["active"] = jnp.ones((S,), bool)
        return out


def build_paged_decode_dag(
    config: Any = None,
    slots: int = 4,
    page_size: int = 16,
    n_pages: int = 64,
    pages_per_seq: int = 8,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
    attention_impl: Optional[str] = None,
) -> PagedDecodeDAG:
    """Paged single-token decode step as a task DAG, for any family that
    offers the paged functions (``models.PAGED_FUNCTIONS``: GPT-2 over
    K / V pools, the Xing4.0 block over a latent pool, the dots3 block over
    per-layer pools, some of them rings) — the one builder
    that reaches :class:`...backends.decode_loop.PagedDecodeEngine`.

    The dense decode DAG's per-layer slabs become shared page POOLS
    ``(n_pages, page_size, row_width)`` (the stored form of the family's
    :class:`...models.kv_pages.CacheSpec`) and every layer task
    additionally aliases the ``page_table`` param ``(slots,
    pages_per_seq) int32`` — so placement and the analysis passes see the
    paged cache's real residency: the pool bytes are the per-layer page
    residency, and the table is the tiny shared indirection every layer
    reads (the DEC003 wiring contract).  Attention is the family's ragged
    paged op (:func:`...ops.attention.paged_decode_attention`, or the
    absorbed-MLA one): gathered by page table, masked per-slot at the
    runtime ``lengths`` input, bit-identical to a dense cache of capacity
    ``pages_per_seq * page_size``.

    What goes on an edge is ``x`` (whatever the family's residual is),
    ``lengths``, this step's ``{kind}_new`` row for each pool kind, a
    layer's ``stats`` where it counts something (expert routing), and —
    for a family that declares ``DECODE_TAKES_LIVE`` — ``live``, the
    graph's ``active`` input.

    A family that offers ``models.DRAFT_FUNCTIONS`` is stepped with its
    own draft module and no other way: ``ids`` is ``(S, R)`` (``R`` its
    ``DECODE_ROWS``: the current token and the drafts after it, at
    positions ``lengths .. lengths + R - 1``, causal), every layer emits
    ``R`` rows a slot, the logits task passes the residual on beside its
    ``(S, R, V)`` logits, and one more task, ``draft`` — the sink —
    runs the family's ``draft_decode`` over the spec's draft layers'
    pools: its output is ``{"logits", "draft_logits", "{kind}_new"}``.
    Which of the rows count is the loop's to decide
    (``build_paged_decode_loop``).

    The step is scheduler-placed exactly like the dense decode DAG; the
    continuous-batching loop (``backends/decode_loop.py``) composes it
    into scanned K-step segments.

    ``attention_impl`` selects the paged attention implementation baked
    into every layer task (``"xla"`` gather, ``"pallas"`` fused kernel,
    ``"pallas_interpret"``, ``"auto"``); ``None`` leaves the op on its
    own auto dispatch (kernel on TPU when the geometry qualifies, gather
    otherwise).  The choice is part of the graph's identity — the graph
    name carries it, so schedules/compile caches keyed on the graph
    never alias two impls.
    """
    from ..models.kv_pages import TRASH_PAGE
    from ..ops.attention import resolve_attention_impl

    if attention_impl is not None:
        # fail at build time on a typo, not at first trace inside a task
        resolve_attention_impl(attention_impl, lambda _i: True)
    config = config or model_config("gpt2-tiny")
    if n_pages < 2:
        raise ValueError(f"n_pages must be >= 2 (page 0 is reserved), "
                         f"got {n_pages}")
    name = family_of(config)
    fam = family_module(name)
    S, ps = slots, page_size
    M = pages_per_seq * ps  # per-slot gathered capacity
    spec = fam.cache_spec(config)
    takes_live = getattr(fam, "DECODE_TAKES_LIVE", False)
    R = draft_rows(config)
    drafts = R > 1

    specs = {
        k: jax.ShapeDtypeStruct(shape, dtype)
        for k, (shape, dtype) in fam.param_shapes(config).items()
    }
    specs.update(jax.eval_shape(
        lambda: spec.init_pools(n_pages, ps, config.dtype, slots=S)))
    specs["page_table"] = jax.ShapeDtypeStruct((S, pages_per_seq), jnp.int32)
    input_spec = {
        "ids": jax.ShapeDtypeStruct((S, R), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((S,), jnp.int32),
    }
    if takes_live:
        input_spec["active"] = jax.ShapeDtypeStruct((S,), jnp.bool_)

    def f_embed(p, inputs):
        out = {"x": fam.decode_embed(
            p, inputs["ids"], inputs["lengths"], config),
            "lengths": inputs["lengths"]}
        if takes_live:
            out["live"] = inputs["active"]
        return out

    def layer_fn(i):
        def f_layer(p, prev):
            x, new, stats = fam.decode_layer(
                p, prev["x"], prev["lengths"], prev.get("live"), config, i,
                impl=attention_impl)
            out = {"x": x, "lengths": prev["lengths"],
                   **{f"{k}_new": v for k, v in new.items()}}
            if takes_live:
                out["live"] = prev["live"]
            if stats is not None:
                out["stats"] = stats
            return out
        return f_layer

    def f_head(p, prev):
        logits = fam.decode_head(p, prev["x"], config)
        if not drafts:
            return logits
        out = {"x": prev["x"], "lengths": prev["lengths"], "logits": logits}
        if takes_live:
            out["live"] = prev["live"]
        return out

    def f_draft(p, prev):
        out = fam.draft_decode(
            p, prev["x"], prev["logits"], prev["lengths"], prev.get("live"),
            config, impl=attention_impl)
        return {"logits": prev["logits"], **out}

    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec,
                          effective_flops)
    from ..models import LOOP_FUNCTIONS

    pass_tasks = None
    if all(hasattr(fam, n) for n in LOOP_FUNCTIONS):
        pass_tasks = _looped_chain(
            fam, config, spec, add, tasks, out_specs,
            fam.decode_flops(config, S, M), attention_impl)
    else:
        _chain(fam, config, spec, add, fam.decode_flops(config, S, M),
               f_embed, layer_fn, f_head, {"page_table": "page_table"})
    if drafts:
        alias = dict(fam.draft_param_names(config))
        for i in range(spec.n_layers - spec.draft_layers, spec.n_layers):
            alias.update({f"cache_{k}": f"cache_{k}_{i}"
                          for k in spec.layer_kinds(i)})
        alias["page_table"] = "page_table"
        add("draft", f_draft, ["logits"], alias,
            fam.draft_flops(config, S, M), "draft")

    def init_fn(key):
        params = fam.init_params(config, key)
        params.update(spec.init_pools(n_pages, ps, config.dtype, slots=S))
        params["page_table"] = jnp.full(
            (S, pages_per_seq), TRASH_PAGE, jnp.int32)
        return params

    def reference_forward(params, inputs):
        """Independent oracle: per slot, the pages gathered into a dense
        cache and the family's own cached forward (``impl="xla"``: no
        kernel) at the slot's position.  Slow (python loop over slots)
        but shares no code with the paged op."""
        weights = {k: v for k, v in params.items()
                   if not k.startswith("cache_") and k != "page_table"}
        outs = []
        rings = spec.ring_table(S, ps) if spec.has_rings else None
        for s in range(S):
            owned = {} if rings is None else {"ring": jnp.asarray(rings[s])}
            if spec.has_state:
                owned["state"] = jnp.asarray(spec.state_rows([s]))
            cache = spec.gather(
                spec.init_dense(1, M, config.dtype, page_size=ps), params,
                params["page_table"][s], 1, M, **owned)
            if drafts:
                # row by row: row r's draft input is the main model's own
                # argmax at row r, which the call decides where nxt < 0
                ids, nxt, both = inputs["ids"][s:s + 1], [], []
                for r in range(R):
                    lg, dl, _ = fam.forward_cached_draft(
                        weights, ids, jnp.asarray(
                            [nxt + [-1] * (R - r)], jnp.int32), cache,
                        inputs["lengths"][s], config, r, impl="xla")
                    nxt.append(int(jnp.argmax(lg[0])))
                    both.append((lg, dl))
                outs.append({
                    "logits": jnp.stack([lg for lg, _ in both], 1),
                    "draft_logits": jnp.stack([dl for _, dl in both], 1)})
                continue
            logits, _ = fam.forward_cached_row(
                weights, inputs["ids"][s:s + 1], cache,
                inputs["lengths"][s], config, 0, impl="xla")
            outs.append(logits[:, None, :])
        if drafts:
            return {k: jnp.concatenate([o[k] for o in outs], axis=0)
                    for k in outs[0]}
        return jnp.concatenate(outs, axis=0)

    graph = TaskGraph(tasks, name=_name(
        name, "paged", spec, out_specs, f"s{S}_ps{ps}_p{n_pages}", config)
        + ("" if attention_impl is None else f"_att{attention_impl}")
    ).freeze()
    graph.attention_impl = attention_impl
    if pass_tasks is not None:
        # the task ids of each pass, in order: what the loop composer
        # rolls into one traced pass (``backends/decode_passes.py``)
        graph.pass_tasks = pass_tasks
    if spec.head_dim:
        # what splits a stored K/V row into heads: the DEC005 / DEC006
        # eligibility checks see the graph and its param specs only
        graph.kv_head_dim = spec.head_dim
        # and the query-head counts that read the rows, where the layers
        # (or the model) say them: the kernel maps them onto the KV heads
        graph.kv_q_heads = tuple(sorted(
            {lc.q_heads or spec.q_heads for lc in spec.layers} - {None}))
    # the pool kinds that are a state a slot: one task each, no table (DEC003)
    graph.state_kinds = tuple(sorted({k for _, k, _, _ in spec._states()}))
    dag = PagedDecodeDAG(
        graph=graph,
        config=config,
        input_spec=input_spec,
        param_specs=specs,
        reference_forward=reference_forward,
        init_fn=init_fn,
    )
    dag.slots = S
    dag.page_size = ps
    dag.pages_per_seq = pages_per_seq
    dag.rows_per_step = graph.rows_per_step = R
    return dag


def apply_cache_updates(
    params: Dict[str, Any],
    task_outputs: Dict[str, Any],
    config: Any,
    pos: int,
) -> Dict[str, Any]:
    """Fold a run's per-layer ``k_new``/``v_new`` outputs back into the
    cache params — the functional step advance for the NEXT step's graph.

    ``task_outputs``: ``DeviceReport.task_outputs`` from
    ``execute(keep_outputs=True)`` — per-task dispatch retains every
    executed task's output, which includes each layer's update dict.
    Works for every family (:func:`...models.cache_spec`).
    """
    spec = cache_spec(config)
    out = dict(params)
    for i in range(spec.n_layers):
        o = task_outputs.get(f"layer_{i}")
        if o is None:
            raise KeyError(f"layer_{i} output missing from task_outputs")
        for kind in spec.kinds:
            buf = out[f"cache_{kind}_{i}"]
            new = o[f"{kind}_new"].astype(buf.dtype)
            out[f"cache_{kind}_{i}"] = jax.lax.dynamic_update_slice(
                buf, new, (0, 0, pos, 0)
            )
    return out


def _looped_chain(fam, config, spec, add, tasks, out_specs, flops,
                  attention_impl):
    """:func:`_chain` for a family whose layers run ``spec.passes`` times
    a token (it offers ``models.LOOP_FUNCTIONS``; the seam decides, so a
    config of ONE pass is built this way too and its pass closes like
    any other): embed -> [one task a layer, the task that closes the
    pass] x passes -> logits.

    The tasks of layer ``l`` carry the SAME ``fn`` object, the same
    weight aliases (a weight is named once in the graph and read by
    ``passes`` tasks — placement, residency and the analysis passes
    count its bytes once and its FLOPs every time) and the same pool
    aliases: a layer's pool holds a plane a pass, and which plane a task
    reads and writes is told by ``pass`` on its input edge (0 from the
    embed task, one more from every ``p{u}_end``).  The edge also carries
    the exit gate's running ``survive`` / ``expected``
    (``fam.decode_pass_end``), so every pass's input has one structure
    and the loop composer can carry it.  Pass 0's tasks are built through
    ``add``; a later pass's are the same tasks under its own ids, each
    behind the task before it.  Returns the task ids of each pass."""
    import dataclasses

    embed_flops, layer_flops, head_flops = flops
    carried = ("lengths", "live", "pass", "survive", "expected")

    def f_embed(p, inputs):
        S = inputs["lengths"].shape[0]
        return {"x": fam.decode_embed(
            p, inputs["ids"], inputs["lengths"], config),
            "lengths": inputs["lengths"], "live": inputs["active"],
            "pass": jnp.zeros((), jnp.int32),
            "survive": jnp.ones((S,), jnp.float32),
            "expected": jnp.zeros((S,), jnp.float32)}

    def layer_fn(i):
        def f_layer(p, prev):
            x, new, stats = fam.decode_layer(
                p, prev["x"], prev["lengths"], prev["live"], config, i,
                impl=attention_impl, u=prev["pass"])
            out = {"x": x, **{k: prev[k] for k in carried},
                   **{f"{k}_new": v for k, v in new.items()}}
            if stats is not None:
                out["stats"] = stats
            return out
        return f_layer

    def f_end(p, prev):
        x, survive, expected, stats = fam.decode_pass_end(
            p, prev["x"], prev["live"], prev["pass"], prev["survive"],
            prev["expected"], config)
        return {"x": x, "lengths": prev["lengths"], "live": prev["live"],
                "pass": prev["pass"] + 1, "survive": survive,
                "expected": expected, "stats": stats}

    def f_head(p, prev):
        return fam.decode_head(p, prev["x"], config)

    add("embed", f_embed, [], {k: k for k in fam.EMBED_PARAMS}, embed_flops,
        "embed")
    prev, fns, first = "embed", {}, []
    for i in range(spec.n_layers):
        alias = dict(fam.layer_param_names(config, i))
        fn = fns.get(key := (*alias, *spec.layer_kinds(i)))
        if fn is None:
            fn = fns[key] = layer_fn(i)
        alias.update(
            {f"cache_{k}": f"cache_{k}_{i}" for k in spec.layer_kinds(i)})
        alias["page_table"] = "page_table"
        add(tid := f"p0_layer_{i}", fn, [prev], alias, layer_flops[i],
            f"layer_{i}")
        first.append(tid)
        prev = tid
    # the final norm and the gate: a few FLOPs a value of the residual
    add("p0_end", f_end, [prev], {k: k for k in fam.PASS_END_PARAMS},
        4.0 * out_specs["embed"]["x"].size, "pass_end")
    first.append(prev := "p0_end")
    by_id = {t.task_id: t for t in tasks}
    passes = [tuple(first)]
    for u in range(1, spec.passes):
        mine = []
        for tid in first:
            new_id = f"p{u}_" + tid.split("_", 1)[1]
            tasks.append(dataclasses.replace(
                by_id[tid], task_id=new_id, dependencies=[prev],
                arg_tasks=[prev]))
            out_specs[new_id] = out_specs[tid]
            mine.append(prev := new_id)
        passes.append(tuple(mine))
    add("logits", f_head, [prev], {k: k for k in fam.HEAD_PARAMS},
        head_flops, "head")
    return tuple(passes)

"""KV-cache decode step as a task DAG: inference through the scheduler.

The task-graph path (the repo's thesis) and the whole-program decode loop
(:mod:`..models.decode`) are deliberately twinned everywhere else; this
builder closes the last gap: the scheduling layer
never saw an inference workload.  One cached forward step — prefill
(``pos = 0``, ``step_len`` = prompt length) or a decode step
(``step_len = 1``) — becomes a per-layer task DAG where the **KV cache
slabs are placeable parameters**:

* layer ``i``'s task needs ``cache_k_i`` / ``cache_v_i`` (real bytes:
  ``B x Hkv x max_len x hd``), so *cache residency IS the placement
  problem* — the same param-cache-locality story the reference's MRU
  policy targets, with the model's largest decode-time tensors;
* each layer task outputs ``{"x", "k_new", "v_new", "pos"}`` — the
  functional cache-update slices the caller applies to its cache copy
  (retained via ``execute(keep_outputs=True).task_outputs``), so
  execution stays pure;
* the step position is a TRACED runtime input (``{"ids", "pos"}``),
  threaded through each task's output dict: attention masks against it,
  RoPE/wpe rows are dynamic-sliced at it, cache updates land at it.  ONE
  graph therefore serves every position of a given ``(step_len,
  max_len)`` class — an N-token generation compiles exactly two programs
  (prefill + decode step), not N.  Compute per step
  is O(max_len) regardless of position (the cache is scanned fully,
  masked), which is also what the FLOPs fields record.

All three families: :func:`build_decode_dag` (GPT-2),
:func:`build_backbone_decode_dag` (Llama / Mixtral — GQA cache layout,
RoPE dynamic-sliced at the traced position, MoE routing per step), and
the dispatching :func:`build_decode_dag_any`.  Oracle: the family's
``forward_cached`` on the same cache (logits exact, multi-step greedy
tokens exact — ``tests/test_decode_dag.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.graph import Task, TaskGraph
from ..models import decode as _decode
from ..models import gpt2
from ..models.gpt2 import GPT2Config
from .gpt2_dag import DEFAULT_EFFECTIVE_FLOPS, ModelDAG, make_task_adder


def cache_dims(config: Any) -> tuple:
    """``(n_layers, n_kv_heads, head_dim)`` for any family's config — the
    one place that knows gpt2 spells these ``n_layer``/``n_head`` while
    the llama backbone spells them ``n_layers``/``n_kv_heads``.  Callers
    allocating cache slabs must use this, not re-derive the attributes."""
    from ..parallel.decode import _family_of

    if _family_of(config) == "gpt2":
        return config.n_layer, config.n_head, config.head_dim
    return config.n_layers, config.n_kv_heads, config.head_dim


def cache_spec(config: Any):
    """The per-layer cache description of any family's config
    (:class:`...models.kv_pages.CacheSpec`): kind ``kv`` with rows
    ``(n_kv_heads, head_dim)`` for the attention families
    (:func:`cache_dims`), kind ``latent`` with one ``[c | k_r]`` row for
    MLA.  The paged builder, the engine's prefill / chunk / copy-on-write
    / reset code and the step composer all read the cache through it."""
    from ..models.kv_pages import CacheSpec
    from ..parallel.decode import _family_of

    if _family_of(config) == "xing4":
        from ..models.xing4 import latent_row_width

        return CacheSpec("latent", config.n_layers,
                         (("c", (latent_row_width(config),)),))
    n_layers, n_kv, hd = cache_dims(config)
    return CacheSpec("kv", n_layers, (("k", (n_kv, hd)), ("v", (n_kv, hd))))


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


def build_decode_dag(
    config: Optional[GPT2Config] = None,
    batch: int = 1,
    step_len: int = 1,
    pos: int = 0,
    max_len: int = 128,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
) -> ModelDAG:
    """Task DAG for one cached forward step; position is a runtime input.

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
    config = config or GPT2Config.tiny()
    if pos + step_len > max_len:
        raise ValueError(
            f"pos {pos} + step_len {step_len} exceeds max_len {max_len}"
        )
    B, T, D, H = batch, step_len, config.n_embd, config.n_head
    hd, M = config.head_dim, max_len
    eps = config.ln_eps
    scale = 1.0 / math.sqrt(hd)

    specs = {
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype) in gpt2.param_shapes(config).items()
    }
    for i in range(config.n_layer):
        specs[f"cache_k_{i}"] = jax.ShapeDtypeStruct(
            (B, H, M, hd), config.dtype
        )
        specs[f"cache_v_{i}"] = jax.ShapeDtypeStruct(
            (B, H, M, hd), config.dtype
        )
    input_spec = {
        "ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }

    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec, effective_flops)

    def f_embed(p, inputs):
        # token embedding + position rows [pos, pos+T) — traced pos
        pos_t = inputs["pos"]
        wpe_rows = jax.lax.dynamic_slice(
            p["wpe"], (pos_t, jnp.int32(0)), (T, D)
        )
        return {"x": p["wte"][inputs["ids"]] + wpe_rows, "pos": pos_t}

    def f_layer(p, prev):
        """One cached transformer layer: attention over [0, pos+T) of the
        cache (this step's keys/values included), then the MLP.  Returns
        the residual stream, this step's cache-update slices, and the
        threaded position."""
        x, pos_t = prev["x"], prev["pos"]
        ln1 = gpt2.layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = ln1 @ p["qkv_w"] + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        k_cache = jax.lax.dynamic_update_slice(
            p["cache_k"], k.astype(p["cache_k"].dtype),
            (jnp.int32(0), jnp.int32(0), pos_t, jnp.int32(0)),
        )
        v_cache = jax.lax.dynamic_update_slice(
            p["cache_v"], v.astype(p["cache_v"].dtype),
            (jnp.int32(0), jnp.int32(0), pos_t, jnp.int32(0)),
        )
        att = _decode.cached_attention(q, k_cache, v_cache, pos_t, scale)
        att = att.transpose(0, 2, 1, 3).reshape(B, T, D)
        x = x + (att @ p["attn_proj_w"] + p["attn_proj_b"])
        ln2 = gpt2.layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        h = gpt2.ffn_contract(
            gpt2.ffn_activation(
                gpt2.ffn_expand(ln2, p["fc_w"], p["fc_b"])
            ),
            p["mlp_proj_w"], p["mlp_proj_b"],
        )
        return {"x": x + h, "k_new": k, "v_new": v, "pos": pos_t}

    def f_head(p, prev):
        x = gpt2.layer_norm(prev["x"], p["ln_f_g"], p["ln_f_b"], eps)
        return gpt2.output_projection(x, p["wte"])

    add("embed", f_embed, [], {"wte": "wte", "wpe": "wpe"},
        2.0 * B * T * D, "embed")
    prev = "embed"
    for i in range(config.n_layer):
        pre = f"h{i}_"
        alias = {
            "ln1_g": pre + "ln1_g", "ln1_b": pre + "ln1_b",
            "qkv_w": pre + "attn_qkv_w", "qkv_b": pre + "attn_qkv_b",
            "attn_proj_w": pre + "attn_proj_w",
            "attn_proj_b": pre + "attn_proj_b",
            "ln2_g": pre + "ln2_g", "ln2_b": pre + "ln2_b",
            "fc_w": pre + "mlp_fc_w", "fc_b": pre + "mlp_fc_b",
            "mlp_proj_w": pre + "mlp_proj_w",
            "mlp_proj_b": pre + "mlp_proj_b",
            "cache_k": f"cache_k_{i}", "cache_v": f"cache_v_{i}",
        }
        # FLOPs: projections on T tokens + attention over the FULL masked
        # cache (compute is O(M) at any position — static shapes)
        flops = (
            2.0 * B * T * D * 3 * D
            + 2.0 * 2.0 * B * H * T * M * hd
            + 2.0 * B * T * D * D
            + 2.0 * B * T * D * 4 * D * 2
        )
        tid = f"layer_{i}"
        add(tid, f_layer, [prev], alias, flops, f"layer_{i}")
        prev = tid
    add("logits", f_head, [prev], {
        "ln_f_g": "ln_f_g", "ln_f_b": "ln_f_b", "wte": "wte",
    }, 2.0 * B * T * D * config.vocab_size, "head")

    name = (
        f"gpt2dec_{config.n_layer}l_d{D}_b{B}_t{T}_m{M}"
        + ("" if config.dtype == jnp.float32
           else f"_{jnp.dtype(config.dtype).name}")
    )

    def init_fn(key):
        params = gpt2.init_params(config, key)
        for i in range(config.n_layer):
            params[f"cache_k_{i}"] = jnp.zeros((B, H, M, hd), config.dtype)
            params[f"cache_v_{i}"] = jnp.zeros((B, H, M, hd), config.dtype)
        return params

    def reference_forward(params, inputs):
        """Whole-program oracle over the same cache params: stacked-layer
        cache assembled from the per-layer slabs, models/decode math."""
        cache = {
            "k": jnp.stack(
                [params[f"cache_k_{i}"] for i in range(config.n_layer)]
            ),
            "v": jnp.stack(
                [params[f"cache_v_{i}"] for i in range(config.n_layer)]
            ),
        }
        model_params = {
            k: v for k, v in params.items() if not k.startswith("cache_")
        }
        logits, _ = gpt2.forward_cached(
            model_params, inputs["ids"], cache, inputs["pos"], config
        )
        return logits

    graph = TaskGraph(tasks, name=name).freeze()
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


def build_backbone_decode_dag(
    config: Any,
    batch: int = 1,
    step_len: int = 1,
    pos: int = 0,
    max_len: int = 128,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
) -> ModelDAG:
    """Llama-backbone decode-step DAG (Llama and Mixtral configs).

    Same contract as :func:`build_decode_dag`: per-layer tasks own
    ``cache_k_{i}`` / ``cache_v_{i}`` slabs (GQA layout:
    ``B x n_kv_heads x max_len x hd``), RoPE dynamic-sliced at the traced
    step position, Mixtral layers run their router + dense experts per
    step (routing is per-token, exactly as the fused cached forward
    does).  Oracle: the family's ``forward_cached`` over the stacked
    cache.
    """
    from ..models import llama as _llama
    from ..models import mixtral as _mixtral
    from ..parallel.decode import _family_of

    family = _family_of(config)
    if family not in ("llama", "mixtral"):
        raise ValueError(f"backbone decode DAG needs llama/mixtral, got {family}")
    mod = _llama if family == "llama" else _mixtral
    is_moe = family == "mixtral"
    if pos + step_len > max_len:
        raise ValueError(
            f"pos {pos} + step_len {step_len} exceeds max_len {max_len}"
        )
    B, T, D = batch, step_len, config.d_model
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    M, eps = max_len, config.rms_eps
    n_layers = config.n_layers
    scale = 1.0 / math.sqrt(hd)

    specs = {
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype) in mod.param_shapes(config).items()
    }
    for i in range(n_layers):
        for kind in ("k", "v"):
            specs[f"cache_{kind}_{i}"] = jax.ShapeDtypeStruct(
                (B, nkv, M, hd), config.dtype
            )
    input_spec = {
        "ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }

    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec, effective_flops)

    def f_embed(p, inputs):
        return {
            "x": _llama.embedding(inputs["ids"], p["tok_emb"]),
            "pos": inputs["pos"],
        }

    def f_layer(p, prev):
        x, pos_t = prev["x"], prev["pos"]
        h = _llama.rms_norm(x, p["attn_norm_g"], eps)
        q = (h @ p["wq"]).reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
        k = (h @ p["wk"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
        v = (h @ p["wv"]).reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
        cos_all, sin_all = _llama.rope_tables(M, hd, config.rope_theta)
        cos = jax.lax.dynamic_slice(cos_all, (pos_t, 0), (T, hd // 2))
        sin = jax.lax.dynamic_slice(sin_all, (pos_t, 0), (T, hd // 2))
        q, k = _llama.apply_rope(q, cos, sin), _llama.apply_rope(k, cos, sin)
        k_cache = jax.lax.dynamic_update_slice(
            p["cache_k"], k.astype(p["cache_k"].dtype),
            (jnp.int32(0), jnp.int32(0), pos_t, jnp.int32(0)),
        )
        v_cache = jax.lax.dynamic_update_slice(
            p["cache_v"], v.astype(p["cache_v"].dtype),
            (jnp.int32(0), jnp.int32(0), pos_t, jnp.int32(0)),
        )
        att = _decode.cached_attention(q, k_cache, v_cache, pos_t, scale)
        att = att.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
        x = x + att @ p["wo"]
        h2 = _llama.rms_norm(x, p["ffn_norm_g"], eps)
        if is_moe:
            ffn = _mixtral._moe(p, h2, config)
        else:
            ffn = _llama.ffn_down(
                _llama.ffn_glu(
                    _llama.ffn_gate(h2, p["w_gate"]),
                    _llama.ffn_up(h2, p["w_up"]),
                ),
                p["w_down"],
            )
        return {"x": x + ffn, "k_new": k, "v_new": v, "pos": pos_t}

    def f_head(p, prev):
        x = _llama.rms_norm(prev["x"], p["final_norm_g"], eps)
        return _llama.lm_head(x, p["lm_head"])

    add("embed", f_embed, [], {"tok_emb": "tok_emb"}, 2.0 * B * T * D, "embed")
    prev = "embed"
    for i in range(n_layers):
        pre = f"l{i}_"
        alias = {
            "attn_norm_g": pre + "attn_norm_g",
            "wq": pre + "wq", "wk": pre + "wk", "wv": pre + "wv",
            "wo": pre + "wo",
            "ffn_norm_g": pre + "ffn_norm_g",
            "cache_k": f"cache_k_{i}", "cache_v": f"cache_v_{i}",
        }
        if is_moe:
            alias["router"] = pre + "router"
            for e in range(config.n_experts):
                for s in ("w_gate", "w_up", "w_down"):
                    alias[f"e{e}_{s}"] = f"{pre}e{e}_{s}"
        else:
            for s in ("w_gate", "w_up", "w_down"):
                alias[s] = pre + s
        F = config.ffn_hidden
        if is_moe:
            # router + DENSE per-step expert sweep (every expert runs
            # every token — the disclosed dense-dispatch cost)
            ffn_flops = (
                2.0 * B * T * D * config.n_experts
                + config.n_experts * 3 * 2.0 * B * T * D * F
            )
        else:
            ffn_flops = 3 * 2.0 * B * T * D * F  # gate, up, down matmuls
        flops = (
            2.0 * B * T * D * (nh + 2 * nkv) * hd
            + 2.0 * 2.0 * B * nh * T * M * hd  # full masked cache, O(M)
            + 2.0 * B * T * nh * hd * D
            + ffn_flops
        )
        tid = f"layer_{i}"
        add(tid, f_layer, [prev], alias, flops, f"layer_{i}")
        prev = tid
    add("logits", f_head, [prev], {
        "final_norm_g": "final_norm_g", "lm_head": "lm_head",
    }, 2.0 * B * T * D * config.vocab_size, "head")

    name = (
        f"{family}dec_{n_layers}l_d{D}_b{B}_t{T}_m{M}"
        + ("" if config.dtype == jnp.float32
           else f"_{jnp.dtype(config.dtype).name}")
    )

    def init_fn(key):
        params = mod.init_params(config, key)
        for i in range(n_layers):
            params[f"cache_k_{i}"] = jnp.zeros((B, nkv, M, hd), config.dtype)
            params[f"cache_v_{i}"] = jnp.zeros((B, nkv, M, hd), config.dtype)
        return params

    def reference_forward(params, inputs):
        cache = {
            "k": jnp.stack(
                [params[f"cache_k_{i}"] for i in range(n_layers)]
            ),
            "v": jnp.stack(
                [params[f"cache_v_{i}"] for i in range(n_layers)]
            ),
        }
        model_params = {
            k: v for k, v in params.items() if not k.startswith("cache_")
        }
        logits, _ = mod.forward_cached(
            model_params, inputs["ids"], cache, inputs["pos"], config
        )
        return logits

    graph = TaskGraph(tasks, name=name).freeze()
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
    """ModelDAG for the paged decode step: inputs are ``{"ids": (S, 1)
    int32, "lengths": (S,) int32}`` — per-slot ragged positions instead
    of one shared scalar — and the KV cache params are shared page pools
    plus the ``page_table`` param (:mod:`..models.kv_pages`)."""

    slots: int = 1
    page_size: int = 0
    pages_per_seq: int = 0
    #: attention impl baked into the layer tasks (None = op-level auto)
    attention_impl: Optional[str] = None

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


def _finish_paged_dag(tasks, name, config, input_spec, specs,
                      reference_forward, init_fn, slots, page_size,
                      pages_per_seq, attention_impl) -> PagedDecodeDAG:
    """What every family's paged builder ends with."""
    graph = TaskGraph(tasks, name=name).freeze()
    # stamped on the graph too: the engine receives the bare TaskGraph
    # and keys its prefill compile-class cache on the impl
    graph.attention_impl = attention_impl
    dag = PagedDecodeDAG(
        graph=graph,
        config=config,
        input_spec=input_spec,
        param_specs=specs,
        reference_forward=reference_forward,
        init_fn=init_fn,
    )
    dag.slots = slots
    dag.page_size = page_size
    dag.pages_per_seq = pages_per_seq
    dag.attention_impl = attention_impl
    return dag


def _build_xing4_paged_decode_dag(
    config, slots, page_size, n_pages, pages_per_seq, effective_flops,
    attention_impl,
) -> PagedDecodeDAG:
    """The paged decode step of the Xing4.0 block
    (:mod:`..models.xing4`): the residual on every edge between layer
    tasks is the ``(slots, hc_mult, hidden)`` streams, the cache one
    latent pool a layer (:func:`cache_spec`), positions the rotary
    angles of ``lengths``.  Expert layers put their routing counts on the
    edge as ``stats``; the ``active`` input takes the slots that decode
    nothing out of the routing."""
    from ..models import xing4
    from ..models.kv_pages import TRASH_PAGE

    S, ps, h = slots, page_size, config.hidden_size
    spec = cache_spec(config)
    specs = {
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype) in xing4.param_shapes(config).items()
    }
    specs.update(jax.eval_shape(
        lambda: spec.init_pools(n_pages, ps, config.dtype)))
    specs["page_table"] = jax.ShapeDtypeStruct((S, pages_per_seq), jnp.int32)
    input_spec = {
        "ids": jax.ShapeDtypeStruct((S, 1), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((S,), jnp.int32),
        "active": jax.ShapeDtypeStruct((S,), jnp.bool_),
    }
    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec, effective_flops)

    def f_embed(p, inputs):
        return {"x": xing4.embed(p, inputs["ids"][:, 0], config),
                "lengths": inputs["lengths"], "live": inputs["active"]}

    def layer_fn(i):
        def f_layer(p, prev):
            x, row, stats = xing4.decode_layer(
                p, prev["x"], prev["lengths"], prev["live"], p["cache_c"],
                p["page_table"], config, i, impl=attention_impl)
            out = {"x": x, "c_new": row, "lengths": prev["lengths"],
                   "live": prev["live"]}
            if stats is not None:
                out["stats"] = stats
            return out
        return f_layer

    def f_head(p, prev):
        return xing4.head(p, prev["x"], config)[:, None, :]

    add("embed", f_embed, [], {"wte": "wte"}, 2.0 * S * h, "embed")
    prev = "embed"
    M = pages_per_seq * ps
    for i in range(config.n_layers):
        shapes = xing4.layer_param_shapes(config, i)
        alias = {k: f"h{i}_{k}" for k in shapes}
        alias.update(cache_c=f"cache_c_{i}", page_table="page_table")
        # weights streamed once a step (experts: the picked ones), plus
        # the absorbed attention over the slot's capacity
        act = sum(
            2.0 * S * math.prod(shape) * (
                config.experts_per_tok / config.n_routed_experts
                if k.startswith("exp_") else 1.0)
            for k, (shape, _) in shapes.items() if len(shape) >= 2)
        flops = act + 2.0 * 2.0 * S * config.n_heads * M * spec.row_elems
        tid = f"layer_{i}"
        add(tid, layer_fn(i), [prev], alias, flops, tid)
        prev = tid
    add("logits", f_head, [prev],
        {"norm_f_g": "norm_f_g", "head_w": "head_w"},
        2.0 * S * h * config.vocab_size, "head")

    name = (
        f"xing4paged_{config.n_layers}l_d{h}_s{S}_ps{ps}_p{n_pages}"
        + ("" if config.dtype == jnp.float32
           else f"_{jnp.dtype(config.dtype).name}")
        + ("" if attention_impl is None else f"_att{attention_impl}")
    )

    def init_fn(key):
        params = xing4.init_params(config, key)
        params.update(spec.init_pools(n_pages, ps, config.dtype))
        params["page_table"] = jnp.full(
            (S, pages_per_seq), TRASH_PAGE, jnp.int32)
        return params

    def reference_forward(params, inputs):
        """Independent oracle: per slot, the pages gathered into a dense
        latent cache and the family's EXPANDED ``forward_cached`` at the
        slot's position — no absorbed form, no paged op."""
        weights = {k: v for k, v in params.items()
                   if not k.startswith("cache_") and k != "page_table"}
        outs = []
        for s in range(S):
            cache = spec.gather(
                spec.init_dense(1, M, config.dtype), params,
                params["page_table"][s], 1, M)
            logits, _ = xing4.forward_cached(
                weights, inputs["ids"][s:s + 1], cache,
                inputs["lengths"][s], config, impl="xla")
            outs.append(logits)
        return jnp.concatenate(outs, axis=0)

    return _finish_paged_dag(
        tasks, name, config, input_spec, specs, reference_forward, init_fn,
        S, ps, pages_per_seq, attention_impl)


def build_paged_decode_dag(
    config: Any = None,
    slots: int = 4,
    page_size: int = 16,
    n_pages: int = 64,
    pages_per_seq: int = 8,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
    attention_impl: Optional[str] = None,
) -> PagedDecodeDAG:
    """Paged single-token decode step as a task DAG: the GPT-2 family
    (below) or, for a :class:`...models.xing4.Xing4Config`, the Xing4.0
    block over a latent pool (:func:`_build_xing4_paged_decode_dag`) —
    the one builder that reaches :class:`...backends.decode_loop.
    PagedDecodeEngine`.

    The dense decode DAG's per-layer ``cache_k_{i}``/``cache_v_{i}``
    slabs become shared page POOLS ``(n_pages, page_size, H * hd)`` (the
    stored form of :class:`...models.kv_pages.CacheSpec`) and
    every layer task additionally aliases the ``page_table`` param
    ``(slots, pages_per_seq) int32`` — so placement and the analysis
    passes see the paged cache's real residency: the pool bytes are the
    per-layer page residency, and the table is the tiny shared indirection
    every layer reads (the DEC003 wiring contract).  Attention is the
    ragged paged op (:func:`...ops.attention.paged_decode_attention`):
    gathered by page table, masked per-slot at the runtime ``lengths``
    input, bit-identical to a dense cache of capacity ``pages_per_seq *
    page_size``.

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
    from ..ops.attention import paged_decode_attention, resolve_attention_impl

    if attention_impl is not None:
        # fail at build time on a typo, not at first trace inside a task
        resolve_attention_impl(attention_impl, lambda _i: True)
    config = config or GPT2Config.tiny()
    if n_pages < 2:
        raise ValueError(f"n_pages must be >= 2 (page 0 is reserved), "
                         f"got {n_pages}")
    from ..parallel.decode import _family_of

    if _family_of(config) == "xing4":
        return _build_xing4_paged_decode_dag(
            config, slots, page_size, n_pages, pages_per_seq,
            effective_flops, attention_impl)
    S, D, H = slots, config.n_embd, config.n_head
    hd, ps = config.head_dim, page_size
    M = pages_per_seq * page_size  # per-slot gathered capacity
    eps = config.ln_eps
    scale = 1.0 / math.sqrt(hd)

    spec = cache_spec(config)
    specs = {
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype) in gpt2.param_shapes(config).items()
    }
    specs.update(jax.eval_shape(
        lambda: spec.init_pools(n_pages, ps, config.dtype)))
    specs["page_table"] = jax.ShapeDtypeStruct((S, pages_per_seq), jnp.int32)
    input_spec = {
        "ids": jax.ShapeDtypeStruct((S, 1), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((S,), jnp.int32),
    }

    tasks: List[Task] = []
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec, effective_flops)

    def f_embed(p, inputs):
        # per-slot position rows: slot s sits at its own lengths[s]
        lengths = inputs["lengths"]
        wpe_rows = jnp.take(p["wpe"], lengths, axis=0)[:, None, :]
        return {
            "x": p["wte"][inputs["ids"]] + wpe_rows,
            "lengths": lengths,
        }

    def f_layer(p, prev):
        """One paged cached layer: ragged paged attention over the shared
        pools (this step's k/v inserted into the gathered view — the
        pool write itself is the loop composer's fold), then the MLP."""
        x, lengths = prev["x"], prev["lengths"]
        ln1 = gpt2.layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = ln1 @ p["qkv_w"] + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(S, 1, H, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        att = paged_decode_attention(
            q, p["cache_k"], p["cache_v"], p["page_table"], lengths,
            scale, k_new=k, v_new=v, impl=attention_impl,
        )
        att = att.transpose(0, 2, 1, 3).reshape(S, 1, D)
        x = x + (att @ p["attn_proj_w"] + p["attn_proj_b"])
        ln2 = gpt2.layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        h = gpt2.ffn_contract(
            gpt2.ffn_activation(
                gpt2.ffn_expand(ln2, p["fc_w"], p["fc_b"])
            ),
            p["mlp_proj_w"], p["mlp_proj_b"],
        )
        return {"x": x + h, "k_new": k, "v_new": v, "lengths": lengths}

    def f_head(p, prev):
        x = gpt2.layer_norm(prev["x"], p["ln_f_g"], p["ln_f_b"], eps)
        return gpt2.output_projection(x, p["wte"])

    add("embed", f_embed, [], {"wte": "wte", "wpe": "wpe"},
        2.0 * S * D, "embed")
    prev = "embed"
    for i in range(config.n_layer):
        pre = f"h{i}_"
        alias = {
            "ln1_g": pre + "ln1_g", "ln1_b": pre + "ln1_b",
            "qkv_w": pre + "attn_qkv_w", "qkv_b": pre + "attn_qkv_b",
            "attn_proj_w": pre + "attn_proj_w",
            "attn_proj_b": pre + "attn_proj_b",
            "ln2_g": pre + "ln2_g", "ln2_b": pre + "ln2_b",
            "fc_w": pre + "mlp_fc_w", "fc_b": pre + "mlp_fc_b",
            "mlp_proj_w": pre + "mlp_proj_w",
            "mlp_proj_b": pre + "mlp_proj_b",
            "cache_k": f"cache_k_{i}", "cache_v": f"cache_v_{i}",
            "page_table": "page_table",
        }
        # attention gathers the slot's full paged capacity every step
        flops = (
            2.0 * S * D * 3 * D
            + 2.0 * 2.0 * S * H * M * hd
            + 2.0 * S * D * D
            + 2.0 * S * D * 4 * D * 2
        )
        tid = f"layer_{i}"
        add(tid, f_layer, [prev], alias, flops, f"layer_{i}")
        prev = tid
    add("logits", f_head, [prev], {
        "ln_f_g": "ln_f_g", "ln_f_b": "ln_f_b", "wte": "wte",
    }, 2.0 * S * D * config.vocab_size, "head")

    name = (
        f"gpt2paged_{config.n_layer}l_d{D}_s{S}_ps{ps}_p{n_pages}"
        + ("" if config.dtype == jnp.float32
           else f"_{jnp.dtype(config.dtype).name}")
        + ("" if attention_impl is None else f"_att{attention_impl}")
    )

    def init_fn(key):
        params = gpt2.init_params(config, key)
        params.update(spec.init_pools(n_pages, ps, config.dtype))
        params["page_table"] = jnp.full(
            (S, pages_per_seq), TRASH_PAGE, jnp.int32
        )
        return params

    def reference_forward(params, inputs):
        """Independent oracle: per-slot DENSE cached forward — gather
        each slot's pages into a dense (1, H, M, hd) cache and run the
        family's ``forward_cached`` at that slot's position.  Slow
        (python loop over slots) but shares no code with the paged op."""
        from ..models.kv_pages import gather_kv

        model_params = {
            k: v for k, v in params.items()
            if not k.startswith("cache_") and k != "page_table"
        }
        pt = params["page_table"]
        outs = []
        for s in range(S):
            cache = {
                "k": jnp.stack([
                    gather_kv(params[f"cache_k_{i}"], pt[s:s + 1], hd)
                    for i in range(config.n_layer)
                ]),
                "v": jnp.stack([
                    gather_kv(params[f"cache_v_{i}"], pt[s:s + 1], hd)
                    for i in range(config.n_layer)
                ]),
            }
            logits, _ = gpt2.forward_cached(
                model_params, inputs["ids"][s:s + 1], cache,
                inputs["lengths"][s], config,
            )
            outs.append(logits)
        return jnp.concatenate(outs, axis=0)

    dag = _finish_paged_dag(
        tasks, name, config, input_spec, specs, reference_forward, init_fn,
        S, ps, pages_per_seq, attention_impl)
    # what splits a stored K/V row into heads: the DEC005 / DEC006
    # eligibility checks see the graph and its param specs only
    dag.graph.kv_head_dim = hd
    return dag


def build_decode_dag_any(config: Any, **kw) -> ModelDAG:
    """Family-dispatching decode-step DAG builder: GPT-2 configs go to
    :func:`build_decode_dag`, Llama/Mixtral to
    :func:`build_backbone_decode_dag`."""
    from ..parallel.decode import _family_of

    if _family_of(config) == "gpt2":
        return build_decode_dag(config, **kw)
    return build_backbone_decode_dag(config, **kw)


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
    Works for every family (:func:`cache_dims`).
    """
    n_layers, _, _ = cache_dims(config)
    out = dict(params)
    for i in range(n_layers):
        o = task_outputs.get(f"layer_{i}")
        if o is None:
            raise KeyError(f"layer_{i} output missing from task_outputs")
        for kind in ("k", "v"):
            buf = out[f"cache_{kind}_{i}"]
            new = o[f"{kind}_new"].astype(buf.dtype)
            out[f"cache_{kind}_{i}"] = jax.lax.dynamic_update_slice(
                buf, new, (0, 0, pos, 0)
            )
    return out

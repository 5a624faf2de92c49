"""GPT-2 forward-pass DAG builder: the TPU-native LLMDAGExtractor.

Replaces the reference's torch/transformers extractor (reference
``test_gpt2.py:45-168``) with a JAX-native builder over our own model: the
same 8-tasks-per-layer structure (ln1, attention, attn_residual, ln2,
ffn_expand, ffn_activation, ffn_contract, layer_output) plus embedding,
final_ln, and a weight-tied output_projection — ``8*n_layer + 3`` tasks; 99
for GPT-2 small, matching the reference/paper count — but where the
reference stores only heuristic estimates, every task here carries:

* a **jittable fn** ``fn(params: Dict[str, Array], *dep_outputs)`` the
  device backend compiles and dispatches;
* **real param byte sizes** from the model's shapes (vs the reference's
  0.5 GB-per-param fiction, ``schedulers.py:70``);
* **real activation byte sizes** for its output via ``jax.eval_shape``
  (vs the reference's crude weight-shape product, ``test_gpt2.py:18-31``);
* an **analytic FLOP count**, turned into a seed ``compute_time`` estimate
  that the measured cost model later replaces (reference analog: the
  class-based constants in ``test_gpt2.py:33-43``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.graph import Task, TaskGraph
from ..models import gpt2
from ..models.gpt2 import GPT2Config
from ..ops.flash_rows import rows_impl
from .vocab_sharding import logit_concat_fn, make_embed_partial_fn, shard_bounds

# Seed estimate for compute_time: effective sustained FLOP/s of one core on
# these op sizes.  Deliberately rough — the calibrated cost model
# (utils/costmodel) overwrites compute_time with measured timings.
DEFAULT_EFFECTIVE_FLOPS = 2.0e12


@dataclasses.dataclass
class ModelDAG:
    """A task graph plus everything needed to actually run it.

    Shared by every model-family frontend (GPT-2 here, Llama in
    ``llama_dag.py``, Mixtral in ``moe_dag.py``); ``config`` is the family's
    own config dataclass and ``init_fn`` its param initializer (defaults to
    GPT-2's for backward compatibility).
    """

    graph: TaskGraph
    config: Any
    input_spec: jax.ShapeDtypeStruct
    # param name -> ShapeDtypeStruct; materialize with init_params()
    param_specs: Dict[str, Any]
    # the fused single-program oracle: forward(params, input_ids)
    reference_forward: Callable[..., Any]
    # key -> flat params dict for this family's config
    init_fn: Callable[[Any], Dict[str, Any]] = None  # type: ignore[assignment]

    def init_params(self, key: Optional[jax.Array] = None) -> Dict[str, Any]:
        key = key if key is not None else jax.random.PRNGKey(0)
        if self.init_fn is None:
            raise ValueError(
                "ModelDAG has no init_fn; the family's builder must supply one"
            )
        return self.init_fn(key)

    def make_inputs(self, key: Optional[jax.Array] = None) -> jax.Array:
        key = key if key is not None else jax.random.PRNGKey(1)
        return jax.random.randint(
            key, self.input_spec.shape, 0, self.config.vocab_size, dtype=jnp.int32
        )


def _bytes_of(spec: Any) -> int:
    """Total bytes of a spec pytree (single ShapeDtypeStruct or any nest —
    train-DAG tasks output dicts of arrays)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(spec):
        size = 1
        for s in leaf.shape:
            size *= s
        total += size * jnp.dtype(leaf.dtype).itemsize
    return total


_GB = 1024**3


def graph_name_tags(microbatches: int, vocab_shards: int, dtype: Any) -> str:
    """Cache-key-critical name suffix shared by every family builder.

    Graph names key the measured cost-model cache (utils/costmodel), so any
    build option that changes task structure or timings MUST appear here —
    one place, or families drift and stale timings get re-applied.
    """
    return (
        (f"_mb{microbatches}" if microbatches > 1 else "")
        # the 'a' marks lane-ALIGNED shard boundaries (vocab_sharding
        # .shard_bounds align=128): per-shard shapes differ from the old
        # balanced split, and the calibration cache validates by task-id
        # set only — the tag keeps stale pre-alignment caches from
        # replaying wrong per-task seconds
        + (f"_vs{vocab_shards}a" if vocab_shards > 1 else "")
        + ("" if dtype == jnp.float32 else f"_{jnp.dtype(dtype).name}")
    )


def make_task_adder(
    tasks: List["Task"],
    out_specs: Dict[str, Any],
    specs: Dict[str, Any],
    input_spec: Any,
    effective_flops: float,
    traced: Optional[Dict[Any, Any]] = None,
) -> Callable[..., None]:
    """The one task-construction closure every frontend builder shares.

    Returns ``add(tid, fn, deps, alias, flops, group)``: infers the task's
    output spec with ``jax.eval_shape`` chained through ``out_specs``,
    computes real activation/param byte sizes, and appends a fully-wired
    :class:`Task`.  ``alias`` maps fn-local param names -> global param
    names; structurally identical tasks (every layer's ln1, ...) share ONE
    fn object so jit compiles each op shape once, not once per layer.

    ``traced``, a dict the builder hands over empty, remembers
    (fn, argument tree and avals) -> output spec, so a shared ``fn`` is
    traced once per argument shapes and not once per task: the
    microbatched forward DAG has twins by the hundred (1,561 traces of 16
    fns were 4 s of the placed-DAG cells' set-up).  Without it every task
    is traced, as the decode, training and backbone builders have it.
    """

    def add(
        tid: str,
        fn: Callable[..., Any],
        deps: List[str],
        alias: Dict[str, str],
        flops: float,
        group: str,
    ) -> None:
        dep_specs = [out_specs[d] for d in deps] if deps else [input_spec]
        pspec = {loc: specs[glob] for loc, glob in alias.items()}
        if traced is None:
            out = jax.eval_shape(lambda pd, *a: fn(pd, *a), pspec, *dep_specs)
        else:
            leaves, tree = jax.tree_util.tree_flatten((pspec, dep_specs))
            key = (fn, tree, tuple((tuple(x.shape), x.dtype) for x in leaves))
            out = traced.get(key)
            if out is None:
                out = traced[key] = jax.eval_shape(fn, pspec, *dep_specs)
        out_specs[tid] = out
        globals_ = list(alias.values())
        tasks.append(
            Task(
                tid,
                memory_required=_bytes_of(out) / _GB,
                compute_time=max(flops / effective_flops, 1e-7),
                dependencies=list(deps),
                params_needed=set(globals_),
                param_bytes={g: _bytes_of(specs[g]) for g in globals_},
                fn=fn,
                arg_tasks=list(deps),
                param_alias=dict(alias),
                out_shape=out,
                flops=flops,
                group=group,
            )
        )

    return add


def build_gpt2_dag(
    config: Optional[GPT2Config] = None,
    batch: int = 1,
    seq_len: int = 512,
    microbatches: int = 1,
    vocab_shards: int = 1,
    effective_flops: float = DEFAULT_EFFECTIVE_FLOPS,
) -> ModelDAG:
    """Build the per-op forward DAG for a GPT-2 config.

    Sequence length defaults to 512 like the reference's shape hint
    (test_gpt2.py:53).  Shapes are static; every task fn is traceable.

    ``microbatches > 1`` splits the batch into independent per-microbatch
    task chains sharing the layer weights, joined by a final concat — the
    DAG shape of pipeline parallelism.  Good placement keeps each layer's
    weights resident on one core while microbatches stream through
    (1F1B-style overlap emerges from list scheduling); naive placement
    reloads/transfers weights per microbatch.  With ``microbatches=1`` the
    graph is the reference's 99-task shape exactly.

    ``vocab_shards > 1`` splits the tied table into vocab-range row shards
    (``wte_shard_k``) and shards BOTH of its uses — task-graph tensor
    parallelism for the one parameter that dominates host-link load time:
    the embedding lookup becomes per-shard partial tasks summed by a combine
    task, and the weight-tied output projection becomes per-shard logit
    slices concatenated along the vocab axis.  Each logit-slice task shares
    its shard's group with the matching embedding partial, so placement
    naturally reuses the resident shard (tying preserved per shard) and the
    full ``wte`` table exists nowhere: its load spreads over as many device
    queues as the scheduler parks shards on, instead of gating the whole
    pipeline behind one sequential load.
    """
    config = config or GPT2Config.small()
    if seq_len > config.n_positions:
        raise ValueError(
            f"seq_len {seq_len} exceeds n_positions {config.n_positions}"
        )
    if batch % microbatches != 0:
        raise ValueError(f"batch {batch} not divisible by microbatches {microbatches}")
    B, T, D, H, V = batch, seq_len, config.n_embd, config.n_head, config.vocab_size
    Bm = B // microbatches
    S = vocab_shards
    eps = config.ln_eps

    specs = {
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype) in gpt2.param_shapes(config).items()
    }
    shard_lo = shard_bounds(V, S)
    if S > 1:
        for k in range(S):
            specs[f"wte_shard_{k}"] = jax.ShapeDtypeStruct(
                (shard_lo[k + 1] - shard_lo[k], D), specs["wte"].dtype
            )
    input_spec = jax.ShapeDtypeStruct((B, T), jnp.int32)

    tasks: List[Task] = []
    # running map of task_id -> output spec, for eval_shape chaining
    out_specs: Dict[str, Any] = {}
    add = make_task_adder(tasks, out_specs, specs, input_spec, effective_flops,
                          traced={})

    # ---- task fns: fn(params_dict, *dep_outputs), local param names ------
    def make_f_embedding(lo, hi):
        def f_embedding(p, input_ids):
            return gpt2.embedding(input_ids[lo:hi], p["wte"], p["wpe"])

        return f_embedding

    def f_embed_combine(p, *partials):
        T_ = partials[0].shape[-2]
        out = partials[0]
        for part in partials[1:]:
            out = out + part
        return out + p["wpe"][:T_]

    def f_concat(p, *chunks):
        return jnp.concatenate(chunks, axis=0)

    def f_ln(p, x):
        return gpt2.layer_norm(x, p["g"], p["b"], eps)

    def f_attn(p, x):
        return gpt2.causal_attention(
            x, p["qkv_w"], p["qkv_b"], p["proj_w"], p["proj_b"], config.n_head
        )

    def f_residual(p, a, b):
        return gpt2.residual_add(a, b)

    def f_ffn_expand(p, x):
        return gpt2.ffn_expand(x, p["fc_w"], p["fc_b"])

    def f_ffn_act(p, x):
        return gpt2.ffn_activation(x)

    def f_ffn_contract(p, x):
        return gpt2.ffn_contract(x, p["proj_w"], p["proj_b"])

    def f_output_projection(p, x):
        return gpt2.output_projection(x, p["wte"])

    def f_logit_shard(p, x):
        """Logit slice via the tied table's row shard: x @ shard.T — runs
        wherever the embedding parked that shard, so the tied table is
        never loaded twice (nor anywhere in full)."""
        return x @ p["shard"].T

    # ---- graph assembly (8 tasks/layer + 3 per microbatch chain,
    # reference test_gpt2.py:54-166; mb prefix only when pipelining) -------
    hd = D // H
    mb_outputs: List[str] = []
    row_form_tasks = 0
    for m in range(microbatches):
        mb = f"mb{m}_" if microbatches > 1 else ""
        emb = f"{mb}embedding"
        if S > 1:
            part_ids = []
            for k in range(S):
                rows = specs[f"wte_shard_{k}"].shape[0]
                pid = f"{mb}embedding_shard_{k}"
                add(pid,
                    make_embed_partial_fn(m * Bm, (m + 1) * Bm, shard_lo[k], rows),
                    [], {"shard": f"wte_shard_{k}"},
                    3.0 * Bm * T * D, f"vocab_shard_{k}")
                part_ids.append(pid)
            add(emb, f_embed_combine, part_ids, {"wpe": "wpe"},
                (S + 1.0) * Bm * T * D, "embed")
        else:
            add(emb, make_f_embedding(m * Bm, (m + 1) * Bm), [],
                {"wte": "wte", "wpe": "wpe"}, 2.0 * Bm * T * D, "embed")

        prev = emb  # residual-stream carrier entering each layer
        for i in range(config.n_layer):
            pre, grp = f"h{i}_", f"layer_{i}"
            ln1 = f"{mb}layer_{i}_ln1"
            add(ln1, f_ln, [prev],
                {"g": pre + "ln1_g", "b": pre + "ln1_b"}, 5.0 * Bm * T * D, grp)

            attn = f"{mb}layer_{i}_attention"
            attn_flops = (
                2.0 * Bm * T * D * 3 * D          # qkv projection
                + 2.0 * 2.0 * Bm * H * T * T * hd  # scores + probs@v
                + 2.0 * Bm * T * D * D             # output projection
            )
            add(attn, f_attn, [ln1],
                {"qkv_w": pre + "attn_qkv_w", "qkv_b": pre + "attn_qkv_b",
                 "proj_w": pre + "attn_proj_w", "proj_b": pre + "attn_proj_b"},
                attn_flops, grp)
            # does this task run the row-form kernel?  The predicate that
            # dispatches inside ``gpt2.causal_attention``, on what the
            # task is handed
            x = out_specs[ln1]
            row_form_tasks += rows_impl(
                None, x.shape[1], H, hd, x.dtype) is not None

            attn_res = f"{mb}layer_{i}_attn_residual"
            add(attn_res, f_residual, [prev, attn], {}, 1.0 * Bm * T * D, grp)

            ln2 = f"{mb}layer_{i}_ln2"
            add(ln2, f_ln, [attn_res],
                {"g": pre + "ln2_g", "b": pre + "ln2_b"}, 5.0 * Bm * T * D, grp)

            expand = f"{mb}layer_{i}_ffn_expand"
            add(expand, f_ffn_expand, [ln2],
                {"fc_w": pre + "mlp_fc_w", "fc_b": pre + "mlp_fc_b"},
                2.0 * Bm * T * D * 4 * D, grp)

            act = f"{mb}layer_{i}_ffn_activation"
            add(act, f_ffn_act, [expand], {}, 8.0 * Bm * T * 4 * D, grp)

            contract = f"{mb}layer_{i}_ffn_contract"
            add(contract, f_ffn_contract, [act],
                {"proj_w": pre + "mlp_proj_w", "proj_b": pre + "mlp_proj_b"},
                2.0 * Bm * T * 4 * D * D, grp)

            layer_out = f"{mb}layer_{i}_output"
            add(layer_out, f_residual, [attn_res, contract], {},
                1.0 * Bm * T * D, grp)
            prev = layer_out

        fln = f"{mb}final_ln"
        add(fln, f_ln, [prev], {"g": "ln_f_g", "b": "ln_f_b"},
            5.0 * Bm * T * D, "head")
        # weight tying: reuses the embedding table (test_gpt2.py:160-166);
        # sharded builds tie per-shard, so the full table exists nowhere
        proj = f"{mb}output_projection"
        if S > 1:
            slice_ids = []
            for k in range(S):
                rows = specs[f"wte_shard_{k}"].shape[0]
                sid = f"{mb}output_projection_shard_{k}"
                add(sid, f_logit_shard, [fln], {"shard": f"wte_shard_{k}"},
                    2.0 * Bm * T * D * rows, f"vocab_shard_{k}")
                slice_ids.append(sid)
            add(proj, logit_concat_fn, slice_ids, {}, 1.0 * Bm * T * V, "head")
        else:
            add(proj, f_output_projection, [fln], {"wte": "wte"},
                2.0 * Bm * T * D * V, "head")
        mb_outputs.append(proj)

    if microbatches > 1:
        add("output_concat", f_concat, mb_outputs, {}, 1.0 * B * T * V, "head")

    name = f"gpt2_{config.n_layer}l_d{D}_b{B}_t{T}" + graph_name_tags(
        microbatches, S, config.dtype
    )

    def init_fn(key):
        params = gpt2.init_params(config, key)
        for k in range(S if S > 1 else 0):
            params[f"wte_shard_{k}"] = params["wte"][shard_lo[k]:shard_lo[k + 1]]
        return params

    graph = TaskGraph(tasks, name=name).freeze()
    # ``execute()`` reports it a call (``execute.attn_row_form_tasks``)
    graph.attn_row_form_tasks = row_form_tasks
    return ModelDAG(
        graph=graph,
        config=config,
        input_spec=input_spec,
        param_specs=specs,
        reference_forward=partial(gpt2.forward, config=config),
        init_fn=init_fn,
    )


def execute_dag_locally(
    dag: ModelDAG, params: Dict[str, Any], input_ids: Any
) -> Any:
    """Run the DAG task-by-task in topo order on the default device.

    The single-device correctness oracle: must produce bit-identical output
    to ``dag.reference_forward`` modulo fusion-order float differences.
    Backends replace this with placed, timed execution.
    """
    outputs: Dict[str, Any] = {}
    jitted: Dict[Any, Any] = {}
    for tid in dag.graph.topo_order:
        task = dag.graph[tid]
        pd = {loc: params[glob] for loc, glob in task.param_items()}
        args = (
            [outputs[d] for d in (task.arg_tasks or task.dependencies)]
            if task.dependencies
            else [input_ids]
        )
        if task.fn not in jitted:
            jitted[task.fn] = jax.jit(task.fn)
        outputs[tid] = jitted[task.fn](pd, *args)
    return outputs[dag.graph.topo_order[-1]]

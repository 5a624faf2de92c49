"""Int8 weight quantization for memory-constrained scheduling.

The reference's founding premise is fitting models into too-little memory
(its paper schedules a 37.5 GB-param GPT-2 across 28 GB of laptops);
quantization attacks the same constraint at the representation level:
symmetric per-channel int8 weights halve (vs bf16) or quarter (vs f32)
every number the scheduler optimizes — per-param bytes in ``can_fit``,
host-link load times in the replay, HBM residency on chips.

Design (TPU-first):

* a quantized param is a :class:`QParam` pytree ``(q: int8, scale: f32)``
  with per-last-axis-channel absmax scales — it flows through
  ``jax.device_put`` / pytree utilities like any array pair;
* task fns never change: :func:`quantize_dag` wraps each distinct fn ONCE
  (preserving the shared-fn jit-cache economy) with a shim that
  dequantizes ``QParam`` entries back to the param's original dtype before
  calling through.  Dequantization happens ON DEVICE inside the jitted
  task — XLA fuses the ``int8 -> float`` convert+scale into the consuming
  matmul, so HBM traffic and transfers stay int8 and only VMEM sees
  floats;
* scheduling sees the truth: ``Task.param_bytes`` shrink to the int8+scale
  sizes, and the graph name gains an ``_int8`` tag so measured cost-model
  caches can't cross-contaminate precision regimes.

Only float params with >= ``min_elems`` elements and >= 2 dims quantize —
norms gains/biases (tiny, precision-critical) stay in their original
dtype.  The embedding table quantizes per row-channel like any matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..core.graph import TaskGraph, TaskStatus


class QParam(NamedTuple):
    """Symmetric int8 weight: ``deq = q * scale`` in one of three scale
    layouts, distinguished by shape:

    * **channel** (:func:`quantize_array`): ``(1, ..., 1, last)`` — one
      scale per last-axis channel.  The ONLY layout the DAG/shard path
      accepts (:func:`rederive_shard_quants`, :func:`qparam_bytes`
      byte accounting).
    * **rowwise** (:func:`quantize_array_rowwise`): ``(..., n, 1)`` —
      one scale per row; embedding tables on the decode-bench path.
    * **grouped** (:func:`quantize_array_grouped`):
      ``(n0/group, 1, *rest)`` — ``q.ndim + 1``; :func:`dequantize`
      keys the grouped reshape on that rank difference.
    """

    q: jax.Array      # int8, original shape
    scale: jax.Array  # float32, see layout table above


def should_quantize(spec: Any, min_elems: int = 4096) -> bool:
    """Quantize float tensors with >= 2 dims and >= min_elems elements."""
    if isinstance(spec, QParam):
        return False
    shape = tuple(spec.shape)
    if len(shape) < 2:
        return False
    size = 1
    for s in shape:
        size *= s
    return size >= min_elems and jnp.issubdtype(
        jnp.dtype(spec.dtype), jnp.floating
    )


def quantize_array(x: jax.Array) -> QParam:
    """Symmetric absmax int8 over every axis but the last (per-channel)."""
    xf = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=tuple(range(xf.ndim - 1)), keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return QParam(q=q, scale=scale)


def quantize_array_rowwise(x: jax.Array) -> QParam:
    """Symmetric absmax int8 over the LAST axis (one scale per row).

    The right orientation for embedding tables: a ``(V, D)`` table read
    by gather (each row is one token's vector) and, when tied as the LM
    head, contracted over ``D`` — row scales are then per-LOGIT scales,
    so every vocab candidate's logit error is proportional to its own
    row magnitude instead of the column-absmax outlier's.  Measured on
    the gpt2-small decode config this cuts the prefill argmax flip rate
    from 7.6% to 6.7% on its own (fidelity sweep; artifact pending
    recapture)."""
    xf = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return QParam(q=q, scale=scale)


def quantize_array_grouped(x: jax.Array, group: int = 64) -> QParam:
    """Per-channel scales refined along the leading (contraction) axis.

    Splits axis 0 into ``group``-sized blocks, one scale per (block,
    channel): scale shape ``(n0/group, 1, *rest)`` — ndim + 1, which is
    how :func:`dequantize` recognizes the grouped layout.  Falls back to
    :func:`quantize_array` when axis 0 doesn't divide evenly (e.g. the
    8-expert leading axis of MoE weight stacks).  Byte cost: 4·n/group
    extra scale bytes per int8 value block — 6.25% at group=64.
    """
    xf = jnp.asarray(x, jnp.float32)
    n0 = xf.shape[0]
    if xf.ndim < 2 or n0 % group or n0 == group:
        return quantize_array(x)
    xg = xf.reshape((n0 // group, group) + xf.shape[1:])
    absmax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xg / scale), -127, 127).astype(jnp.int8)
    return QParam(q=q.reshape(xf.shape), scale=scale)


#: Embedding-table param names per model family — the tables whose
#: consumers read ROWS (gather; tied-head contraction over the last
#: axis), so ``scheme="grouped"`` quantizes them row-wise.  Llama and
#: Mixtral's untied ``lm_head`` is (d, vocab): its per-channel scales
#: are already per-logit, so it takes the grouped path instead.
ROWWISE_EMBED_KEYS: Dict[str, tuple] = {
    "gpt2": ("wte", "wpe"),
    "llama": ("tok_emb",),
    "mixtral": ("tok_emb",),
}


def dequantize(v: Any, dtype: Any) -> Any:
    """QParam -> dense array in ``dtype``; anything else passes through.

    Handles both scale layouts: broadcastable same-ndim scales
    (per-channel / row-wise) and the grouped ``ndim + 1`` layout of
    :func:`quantize_array_grouped`."""
    if isinstance(v, QParam):
        q, scale = v.q, v.scale
        if scale.ndim == q.ndim + 1:
            g0 = scale.shape[0]
            qg = q.reshape((g0, q.shape[0] // g0) + q.shape[1:])
            return (
                (qg.astype(jnp.float32) * scale)
                .reshape(q.shape)
                .astype(dtype)
            )
        return (q.astype(jnp.float32) * scale).astype(dtype)
    return v


def qparam_bytes(spec: Any) -> int:
    """On-the-wire bytes of the quantized form of ``spec``: int8 values
    plus one float32 scale per last-axis channel (quantize_array's
    layout)."""
    shape = tuple(spec.shape)
    n = 1
    for s in shape:
        n *= s
    return n * 1 + shape[-1] * 4


def quantize_params(
    params: Dict[str, Any],
    min_elems: int = 4096,
    scheme: str = "channel",
    group: int = 64,
    rowwise_keys: tuple = (),
) -> Dict[str, Any]:
    """Quantize every qualifying entry of a flat param dict.

    ``scheme="channel"`` (default) is the per-channel layout every
    byte-accounting consumer (:func:`qparam_bytes`, the DAG/streaming
    paths) assumes.  ``scheme="grouped"`` is the higher-fidelity decode
    variant: ``rowwise_keys`` entries (embedding tables — see
    :data:`ROWWISE_EMBED_KEYS`) get per-row scales, everything else gets
    ``group``-blocked contraction-axis scales.  Fidelity/byte trade-off
    on gpt2-small (B=8, T=512 full-prompt forward, r6 recapture): argmax
    flip rate 6.8% → 5.2% per-channel → grouped, logit RMSE −18%, for
    +6.25% scale bytes on grouped matrices at group=64 (+4.2pp measured
    over all params; ``DECODE_r06.json``'s quantized leg carries the
    shipped scheme's fidelity at its own capture scale: 5.7% flips,
    logit RMSE 0.0135)."""
    if scheme == "channel":
        return {
            k: quantize_array(v) if should_quantize(v, min_elems) else v
            for k, v in params.items()
        }
    if scheme != "grouped":
        raise ValueError(f"unknown quantization scheme {scheme!r}")
    out: Dict[str, Any] = {}
    for k, v in params.items():
        if not should_quantize(v, min_elems):
            out[k] = v
        elif k in rowwise_keys:
            out[k] = quantize_array_rowwise(v)
        else:
            out[k] = quantize_array_grouped(v, group)
    return out


def _shard_groups(names) -> Dict[str, list]:
    """``{base: [(k, shard_name), ...]}`` for ``{base}_shard_{k}`` keys."""
    import re

    groups: Dict[str, list] = {}
    for name in names:
        m = re.fullmatch(r"(.+)_shard_(\d+)", name)
        if m:
            groups.setdefault(m.group(1), []).append((int(m.group(2)), name))
    for entries in groups.values():
        entries.sort()
    return groups


def rederive_shard_quants(params: Dict[str, Any]) -> Dict[str, Any]:
    """Make vocab-shard quantization coherent with the base table's.

    ``{base}_shard_{k}`` entries (vocab sharding: tok_emb/wte row slices,
    lm_head column slices) must carry slices of the BASE table's quantized
    values, not an independent quantization — otherwise the shard-consuming
    DAG path and the full-table fused oracle disagree by re-rounding noise.
    Row slices reuse the base's per-column scales verbatim; column slices
    take the matching scale columns.
    """
    out = dict(params)
    for base, entries in _shard_groups(params).items():
        bq = out.get(base)
        if not isinstance(bq, QParam):
            continue
        if bq.scale.ndim != bq.q.ndim or any(
            s != 1 for s in bq.scale.shape[:-1]
        ):
            # rowwise/grouped layouts: the slice arithmetic below (scale
            # reused verbatim for row slices, column-sliced for column
            # slices) is only correct for channel scales — failing loud
            # beats silently dequantizing shards against the wrong scales
            raise ValueError(
                f"shard group {base!r}: rederive_shard_quants supports "
                f"only channel-layout scales, got scale shape "
                f"{tuple(bq.scale.shape)} for q {tuple(bq.q.shape)}"
            )
        base_shape = bq.q.shape

        def _shape_of(v):
            return tuple((v.q if isinstance(v, QParam) else v).shape)

        present = [name for _, name in entries if name in out]
        shapes = [_shape_of(out[name]) for name in present]
        if not shapes:
            continue
        # Infer the slicing axis ONCE per group from all shard shapes —
        # per-shard shape matching with rows-tried-first silently
        # misreads a square table (or any layout satisfying both tests)
        # as row slices with the wrong scale columns (ADVICE r2).
        rows_ok = all(s[1:] == base_shape[1:] for s in shapes)
        cols_ok = all(s[:-1] == base_shape[:-1] for s in shapes)
        if rows_ok and cols_ok:
            # ambiguous (square base): the shard extents must tile
            # exactly one of the axes; a single whole-table "shard" is
            # identical under either reading
            if shapes == [base_shape]:
                cols_ok = False
            else:
                rsum = sum(s[0] for s in shapes)
                csum = sum(s[-1] for s in shapes)
                rows_ok = rsum == base_shape[0] and csum != base_shape[-1]
                cols_ok = (not rows_ok) and csum == base_shape[-1]
        if rows_ok == cols_ok:
            raise ValueError(
                f"shard group {base!r}: cannot disambiguate row vs column "
                f"slicing (base {base_shape}, shards {shapes})"
            )
        off = 0
        for name, shape in zip(present, shapes):
            if rows_ok:  # row slice (tok_emb/wte)
                if isinstance(out[name], QParam):
                    out[name] = QParam(
                        q=bq.q[off:off + shape[0]], scale=bq.scale
                    )
                # advance even for fp shards: offsets are positional,
                # not conditional on quantization
                off += shape[0]
            else:  # column slice (lm_head)
                if isinstance(out[name], QParam):
                    out[name] = QParam(
                        q=bq.q[..., off:off + shape[-1]],
                        scale=bq.scale[..., off:off + shape[-1]],
                    )
                off += shape[-1]
    return out


def quantize_like(dag: Any, params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize exactly the params a quantized DAG's specs mark quantized —
    the ingestion path (``--weights`` + ``--quantize``): external fp
    checkpoints are fitted first, then converted to the DAG's layout."""
    out = {}
    for k, v in params.items():
        spec = dag.param_specs.get(k)
        if isinstance(spec, QParam) and not isinstance(v, QParam):
            out[k] = quantize_array(v)
        else:
            out[k] = v
    return rederive_shard_quants(out)


def quantize_dag(
    dag: Any, min_elems: int = 4096, exclude_prefixes: tuple = ()
) -> Any:
    """A ModelDAG whose qualifying weights are int8 end-to-end.

    Returns a new dag (the input is untouched): fns wrapped with on-device
    dequantization, ``param_bytes`` shrunk to int8+scale sizes, specs
    swapped to QParam pytrees, ``init_params``/``reference_forward``
    quantization-aware, and the graph renamed with an ``_int8`` tag (cost
    model caches key on the name).

    ``exclude_prefixes``: param names starting with any of these stay in
    their original dtype — decode DAGs quantize weights but must keep
    ``cache_*`` slabs fp (the per-step cache write path updates them in
    place; re-rounding a cache every step would compound error).
    """
    quantized = {
        name for name, spec in dag.param_specs.items()
        if should_quantize(spec, min_elems)
        and not any(name.startswith(px) for px in exclude_prefixes)
    }
    # quantization is decided per SHARD GROUP, not per tensor: vocab
    # shards must follow their base table (they carry slices of its
    # quantized values — mixing fp shards with a quantized base would
    # re-introduce the DAG-vs-oracle re-rounding divergence)
    for base, entries in _shard_groups(dag.param_specs).items():
        if base not in dag.param_specs:
            continue
        names = [n for _, n in entries]
        if base in quantized:
            quantized.update(names)
        else:
            quantized.difference_update(names)
    # QParam specs are already quantized (re-application is a no-op for
    # them); only float specs carry a dtype for the dequant shim
    spec_dtype = {
        name: jnp.dtype(spec.dtype)
        for name, spec in dag.param_specs.items()
        if not isinstance(spec, QParam)
    }

    # wrap each distinct fn object once so structurally identical tasks
    # keep sharing one jitted callable after the transform
    wrapped: Dict[Any, Callable[..., Any]] = {}

    def wrap(fn, local_dtypes):
        dt = tuple(sorted(local_dtypes.items()))
        key = (fn, dt)
        w = wrapped.get(key)
        if w is None:

            def w(pd, *args, _fn=fn, _dt=dict(local_dtypes)):
                deq = {
                    loc: dequantize(v, _dt.get(loc, jnp.float32))
                    for loc, v in pd.items()
                }
                return _fn(deq, *args)

            wrapped[key] = w
        return w

    new_graph = TaskGraph(name=f"{dag.graph.name}_int8")
    for tid in dag.graph.topo_order:
        t = dag.graph[tid]
        pb = dict(t.param_bytes)
        local_dtypes = {}
        for loc, glob in t.param_items():
            if glob in quantized:
                pb[glob] = qparam_bytes(dag.param_specs[glob])
                local_dtypes[loc] = spec_dtype[glob]
        nt = dataclasses.replace(
            t,
            # only tasks that actually touch quantized params get the
            # dequant shim; others keep their fn identity (and jit cache)
            fn=(
                wrap(t.fn, local_dtypes)
                if t.fn is not None and local_dtypes
                else t.fn
            ),
            param_bytes=pb,
            dependencies=list(t.dependencies),
            params_needed=set(t.params_needed),
            arg_tasks=list(t.arg_tasks) if t.arg_tasks is not None else None,
            status=TaskStatus.PENDING,
            assigned_node=None,
        )
        new_graph.add_task(nt)
    new_graph.freeze()

    new_specs = {
        name: (
            QParam(
                q=jax.ShapeDtypeStruct(spec.shape, jnp.int8),
                scale=jax.ShapeDtypeStruct(
                    (1,) * (len(spec.shape) - 1) + (spec.shape[-1],),
                    jnp.float32,
                ),
            )
            if name in quantized
            else spec
        )
        for name, spec in dag.param_specs.items()
    }

    base_init = dag.init_fn
    base_forward = dag.reference_forward

    def init_fn(key):
        return rederive_shard_quants({
            k: quantize_array(v) if k in quantized else v
            for k, v in base_init(key).items()
        })

    def reference_forward(params, input_ids):
        deq = {
            k: dequantize(v, spec_dtype.get(k, jnp.float32))
            for k, v in params.items()
        }
        return base_forward(deq, input_ids)

    return dataclasses.replace(
        dag,
        graph=new_graph,
        param_specs=new_specs,
        init_fn=init_fn,
        reference_forward=reference_forward,
    )

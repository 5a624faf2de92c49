"""Dense attention over token-major rows (Pallas TPU): the kernel that
reads q / k / v as the projection writes them.

:func:`..attention.mha` wants ``(B, H, T, hd)``, so a caller that holds
the ``(B, T, 3·D)`` result of ``x @ qkv_w`` pays three head-split
re-orderings in HBM in front of the kernel and one behind it, and a head
of 64 values fills half of every 128-lane tile it touches.
:func:`mha_rows` takes the projection result as it lies and writes ``(B,
T, D)``, which ``out @ proj_w`` reads as it is.

Layout: grid ``(batch row, lane block)``.  A TILE of heads is 128 lanes
of the row — two heads of 64, one of 128 — or one wider head; a lane
block is up to four tiles (:func:`_step_width`), walked one after the
other inside the grid step.  Its q, k, v and output blocks are ``(T,
width)`` views of the arguments, picked by the ``BlockSpec`` index maps
(k and v are the projection's second and third thirds: nothing is split
beforehand).  Heads inside a tile are told apart on the MXU as
:func:`..attention._paged_kernel` tells them apart: the query block is
stacked once a head, each copy zeroed outside its head's lanes, so ``q_h
· K^T`` over the whole tile is head ``h``'s scores (the other lanes add
exact zeros), ``p_h · V`` gives every row the tile's values and the row
keeps its own head's lanes.  A contraction over 128 lanes costs the MXU
what one over 64 does.

Causality is walked, not masked away: query block ``i`` (``block`` rows)
multiplies key rows ``0 .. (i + 1) * block`` only, and only its diagonal
block is masked.  The query blocks are unrolled, so every prefix is a
static slice and a block's softmax is taken in ONE pass over its prefix
— no running maximum to carry, no accumulator to rescale.  Scores, the
maximum, the sum and the accumulator are float32; q, k, v enter the MXU
in their own dtype (bfloat16 products are exact in the float32
accumulator: the same products the head-major kernel's upcast gives) and
the probabilities enter the second matmul in v's dtype, as
:func:`..attention.reference_mha` and the paged kernels have them.

Which form a call takes is read off its shapes, here and nowhere else
(:func:`rows_supported`); whatever it refuses runs split → ``mha`` →
merge exactly as before.  The module stands alone because the persistent
compile cache keys a Pallas kernel by its source position (ROADMAP D16):
nothing here moves a line of another kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NEG_INF, mha, reference_mha, resolve_attention_impl

# Query rows a block: the prefix a block multiplies ends at its own last
# row, so smaller blocks skip more of the square (10 of 16 block pairs at
# T = 512) and larger ones stream more rows past every weight tile the
# MXU loads.  On the v5e at the medium-DAG shape, four tiles a step: 64
# rows read 81.1 us a call, 128 42.6, 256 43.7, 512 (the whole square)
# 52.2 (PERF.md section 6, PR 49).
_BLOCK = 128
# A grid step takes several tiles of heads where the row has them: the
# q / k / v rows it fetches are that much longer (a 128-lane tile of a
# bf16 row is 256 bytes) and there are that many fewer steps.  On the v5e
# at the medium-DAG shape one tile a step reads 48.3 us a call, two 43.7,
# four 42.5, eight 44.8 (PERF.md section 6, PR 49).
_STEP_TILES = 4
_STEP_BLOCK_BYTES = 4 << 20
# The query blocks are unrolled: that bounds T.
_MAX_ROWS = 1024
# A block's scores are one (heads * block, prefix) float32 tile in VMEM,
# with its exponentials and their cast beside it: two heads of 64 at T =
# 1,024, four of 32 at 512.  (Four of 32 at 1,024, 2 MiB, do not compile
# for the v5e in bfloat16.)
_SCORE_TILE_BYTES = 1 << 20


def rows_supported(T: int, n_head: int, head_dim: int, dtype: Any) -> bool:
    """THE shape rule of the row form: the row is whole 128-lane tiles,
    a head divides a tile or is whole tiles itself, T divides into the
    query blocks and stays within what is unrolled, the dtype is one the
    kernel's matmuls take, and one tile of heads fits the kernel's VMEM
    — its q, k, v and output blocks inside ``_STEP_BLOCK_BYTES`` (the
    bound :func:`_step_width` widens a grid step under) and a query
    block's scores inside ``_SCORE_TILE_BYTES``.  GPT-2 small / medium /
    large qualify; XL's 25 x 64 = 1,600 (12.5 tiles) does not.  Every
    geometry class the rule admits is compiled for the v5e in
    ``tests/test_chip_contract.py``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    lanes = max(128, head_dim)  # of one tile of heads
    return (
        lanes % 128 == 0
        and lanes % head_dim == 0
        and (n_head * head_dim) % lanes == 0
        and T % _BLOCK == 0
        and T <= _MAX_ROWS
        and _tile_block_bytes(T, lanes, dtype.itemsize) <= _STEP_BLOCK_BYTES
        and (lanes // head_dim) * _BLOCK * T * 4 <= _SCORE_TILE_BYTES
    )


def _tile_block_bytes(T: int, lanes: int, itemsize: int) -> int:
    """VMEM one tile of heads takes as q, k, v and output blocks, each
    double-buffered by the pipeline."""
    return 8 * T * lanes * itemsize


def rows_impl(
    impl: Optional[str], T: int, n_head: int, head_dim: int, dtype: Any
) -> Optional[str]:
    """The kernel impl the row form runs this call under, or ``None``
    when the call is not the row form's: the shape rule refuses it, or
    the dispatch shared with :func:`..attention.mha`
    (:func:`..attention.resolve_attention_impl`) resolves to XLA.  What
    dispatches in :func:`mha_rows` and what ``build_gpt2_dag`` counts."""
    if not rows_supported(T, n_head, head_dim, dtype):
        return None
    impl = resolve_attention_impl(impl, lambda _i: True)
    return None if impl == "xla" else impl


def _rows_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, head_dim, causal):
    """One (batch row, lane block) grid step: every ref is ``(1, T,
    width)``, ``width`` lanes of the row — whole 128-lane tiles, each
    holding its heads side by side (or one head several tiles wide)."""
    T, width = q_ref.shape[1:]
    block = _BLOCK
    lanes = max(128, head_dim)  # of one tile of heads
    heads = max(1, lanes // head_dim)
    # a power-of-two scale (1/8 at head 64) folds into q exactly in any
    # dtype; any other is applied to the float32 scores
    fold = q_ref.dtype == jnp.float32 or math.frexp(sm_scale)[0] == 0.5
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, lanes), 1)
    own = [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
           for h in range(heads)]
    if causal:
        # row r of the stacked block is query row r % block of its head
        # (a block is a power of two)
        shape = (heads * block, block)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) & (block - 1)
        below = jax.lax.broadcasted_iota(jnp.int32, shape, 1) <= row

    for c in range(0, width, lanes):
        at = slice(c, c + lanes)
        for start in range(0, T, block):
            q = q_ref[0, start:start + block, at]
            if fold:
                q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
            if heads > 1:
                q = jnp.concatenate(
                    [jnp.where(o, q, jnp.zeros_like(q)) for o in own], axis=0)
            n = start + block if causal else T
            s = jax.lax.dot_general(
                q, k_ref[0, :n, at], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (heads * block, n)
            if not fold:
                s = s * sm_scale
            if causal:
                # the diagonal block is the prefix's last; it holds every
                # row's own position, so no row is ever fully masked
                diag = jnp.where(below, s[:, start:], _NEG_INF)
                s = jnp.concatenate([s[:, :start], diag], axis=1) if start \
                    else diag
            p = jnp.exp(s - s.max(axis=-1, keepdims=True))
            den = p.sum(axis=-1, keepdims=True)
            num = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, :n, at],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )  # (heads * block, lanes): every head's rows over all lanes
            top = slice(0, block)
            num_own = num[top]
            den_own = jnp.broadcast_to(den[top], (block, lanes))
            for h in range(1, heads):
                rows = slice(h * block, (h + 1) * block)
                num_own = jnp.where(own[h], num[rows], num_own)
                den_own = jnp.where(own[h], den[rows], den_own)
            o_ref[0, start:start + block, at] = (
                num_own / den_own).astype(o_ref.dtype)


def _step_width(D: int, lanes: int, T: int, itemsize: int) -> int:
    """Lanes of the row one grid step takes: up to ``_STEP_TILES`` tiles
    of heads, a divisor of the row's tiles, whose q, k, v and output
    blocks (double-buffered by the pipeline) stay inside
    ``_STEP_BLOCK_BYTES`` of VMEM (one tile does: :func:`rows_supported`)."""
    tiles = D // lanes
    fit = _STEP_BLOCK_BYTES // _tile_block_bytes(T, lanes, itemsize)
    return lanes * max(
        n for n in range(1, min(_STEP_TILES, fit) + 1) if tiles % n == 0)


@functools.partial(
    jax.jit,
    static_argnames=("n_head", "packed", "causal", "sm_scale", "interpret"),
)
def _flash_mha_rows(q, k, v, *, n_head, packed, causal, sm_scale, interpret):
    """``q``, ``k``, ``v`` are ``(B, T, D)`` — or, ``packed``, one ``(B,
    T, 3·D)`` projection result passed three times, of which the index
    maps pick the thirds.  ONE op a call, named ``_flash_mha_rows`` in a
    device trace (the benchmark's ``flash_mha_roofline`` reads
    ``^_flash_mha``)."""
    B, T, D = q.shape[0], q.shape[1], q.shape[2] // (3 if packed else 1)
    head_dim = D // n_head
    width = _step_width(D, max(128, head_dim), T, q.dtype.itemsize)
    third = D // width if packed else 0

    def spec(offset):
        return pl.BlockSpec((1, T, width), lambda b, j: (b, 0, offset + j))

    return pl.pallas_call(
        functools.partial(
            _rows_kernel, sm_scale=sm_scale, head_dim=head_dim,
            causal=causal,
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, D), q.dtype),
        grid=(B, D // width),
        in_specs=[spec(0), spec(third), spec(2 * third)],
        out_specs=spec(0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="_flash_mha_rows",
    )(q, k, v)


def _split_heads(qkv, n_head):
    """``(B, T, 3·D)`` or three ``(B, T, D)`` -> three ``(B, H, T, hd)``."""
    q, k, v = jnp.split(qkv, 3, axis=-1) if not isinstance(qkv, tuple) else qkv
    B, T, D = q.shape

    def heads(t):
        return t.reshape(B, T, n_head, D // n_head).transpose(0, 2, 1, 3)

    return heads(q), heads(k), heads(v)


def _merge_heads(out):
    B, H, T, hd = out.shape
    return out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)


@functools.lru_cache(maxsize=None)
def _rows_with_vjp(n_head: int, causal: bool, sm_scale: float,
                   interpret: bool, packed: bool):
    """Differentiable row form, as :func:`..attention._flash_with_vjp`:
    the backward recomputes through the XLA reference on the head-split
    view (residuals are the arguments alone)."""
    kernel = functools.partial(
        _flash_mha_rows, n_head=n_head, packed=packed, causal=causal,
        sm_scale=sm_scale, interpret=interpret,
    )

    def run(*args):
        return kernel(*(args * 3 if packed else args))

    def reference(*args):
        q, k, v = _split_heads(args[0] if packed else args, n_head)
        return _merge_heads(
            reference_mha(q, k, v, causal=causal, sm_scale=sm_scale))

    f = jax.custom_vjp(run)
    f.defvjp(lambda *args: (run(*args), args),
             lambda args, g: jax.vjp(reference, *args)[1](g))
    return f


def mha_rows(
    q: jax.Array,
    k: Optional[jax.Array] = None,
    v: Optional[jax.Array] = None,
    *,
    n_head: int,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Multi-head attention on token-major rows: ``q`` alone is the
    packed ``(B, T, 3·D)`` projection result (q, k, v side by side, each
    ``n_head`` heads wide), or ``q``, ``k``, ``v`` are ``(B, T, D)``
    each; returns ``(B, T, D)``.

    ``impl`` means what it means to :func:`..attention.mha`.  A call the
    row form takes (:func:`rows_impl`) runs one kernel over the rows as
    they lie.  Any other is split into heads, handed to ``mha`` with the
    caller's ``impl`` — which picks the head-major kernel or XLA, or
    raises on an explicit kernel request neither form can honour — and
    merged: the program the callers ran before this entry existed.
    """
    packed = k is None
    T, D = q.shape[1], q.shape[2] // (3 if packed else 1)
    kernel = rows_impl(impl, T, n_head, D // n_head, q.dtype)
    if kernel is None:
        heads = _split_heads(q if packed else (q, k, v), n_head)
        return _merge_heads(
            mha(*heads, causal=causal, sm_scale=sm_scale, impl=impl))
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D // n_head)
    f = _rows_with_vjp(n_head, causal, float(scale),
                       kernel == "pallas_interpret", packed)
    return f(q) if packed else f(q, k, v)

"""Fused attention kernels (Pallas TPU): causal flash prefill + ragged paged decode.

The hot op of every model family (SURVEY.md §7 "hot parts"): materializing
the (T, T) score matrix costs O(T^2) HBM traffic, which at long context is
the bandwidth bottleneck.  The flash kernel streams K/V blocks through VMEM
with an online-softmax accumulator (running max / denominator), so scores
never leave VMEM and HBM traffic is O(T · d).  The same math drives the ring
attention loop in :mod:`..parallel.ring_attention` — there blocks rotate
across chips over ICI; here they stream within one chip's HBM→VMEM.

Layout: grid (batch·heads, Q blocks); per grid step one Q block lives in
VMEM while the kernel walks K/V blocks with ``lax.fori_loop``.  Causality
prunes the loop: Q block ``i`` only visits K/V blocks ``0..i`` (the trip
count is a traced value — Pallas lowers it to a hardware loop, no
recompilation per block).  Scores/accumulators are float32 for stability;
inputs/outputs stay in the model dtype (bfloat16 on TPU hits the MXU).

The decode-side sibling is the ragged paged kernel (``_paged_kernel``):
its grid is the list of LIVE (slot, page block) pairs, as long as the
slots' lengths make it, and each K/V page ref of a step is selected by the
request's page table through a scalar-prefetch index map — only live
physical pages DMA HBM→VMEM, the gathered (S, M, Hkv, hd) view is never
materialized, and the same online-softmax carry runs across a slot's
blocks (the ragged tail masked to −inf).  Both paged impls sit behind
:func:`paged_decode_attention`'s ``impl`` switch with the same dispatch
rules as :func:`mha` (:func:`resolve_attention_impl`).

``mha`` is the public entry: ``impl=None``/``"auto"`` picks the kernel on
TPU when the shape qualifies and the plain-XLA path otherwise, so models
can call it unconditionally; an EXPLICIT ``impl`` is a request, and one
the shape cannot honour raises (:func:`resolve_attention_impl`).

The reference never executes attention (its "attention" is a DAG node with
a cost constant, reference ``test_gpt2.py:75-90``); this file exists
because the rebuild executes for real.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, block, causal):
    """One (batch·head, q-block) grid step.

    q_ref/o_ref: (1, block, hd) VMEM; k_ref/v_ref: (1, T, hd) VMEM.
    """
    q_blk = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # (block, hd)
    hd = q.shape[-1]
    T = k_ref.shape[1]
    n_blocks = T // block

    q_start = q_blk * block
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0) + q_start

    def body(kv_i, carry):
        acc, m, l = carry
        kv_start = kv_i * block
        k = k_ref[0, pl.ds(kv_start, block), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kv_start, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block, block)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1) + kv_start
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, m_new, l

    acc0 = jnp.zeros((block, hd), jnp.float32)
    m0 = jnp.full((block, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block, 1), jnp.float32)
    # causal: Q block i needs K/V blocks 0..i only (diagonal always has the
    # self-position, so no row is ever fully masked and l stays positive)
    trip = jnp.where(causal, q_blk + 1, n_blocks) if causal else n_blocks
    acc, _, l = jax.lax.fori_loop(0, trip, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _pick_block(T: int) -> int:
    """Largest power-of-two divisor of T capped at 512 (MXU-friendly)."""
    block = 1
    while block < 512 and T % (block * 2) == 0:
        block *= 2
    return block


@functools.lru_cache(maxsize=None)
def _flash_with_vjp(causal: bool, sm_scale: float, block: int, interpret: bool):
    """Differentiable flash forward: pallas_call has no autodiff rule, so
    training-step DAGs (``frontend/train_dag.py``) would crash under
    ``jax.vjp`` exactly on TPU where the kernel is selected.  The backward
    recomputes attention through the XLA reference path (flash-style
    rematerialization: residuals are just q/k/v, no O(T^2) tensor is saved
    between fwd and bwd).  Cached per static config so jit sees one stable
    function object per shape family (no retrace churn)."""

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_mha(
            q, k, v, causal=causal, sm_scale=sm_scale, block=block,
            interpret=interpret,
        )

    def f_fwd(q, k, v):
        out = _flash_mha(
            q, k, v, causal=causal, sm_scale=sm_scale, block=block,
            interpret=interpret,
        )
        return out, (q, k, v)

    def f_bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: reference_mha(
                q_, k_, v_, causal=causal, sm_scale=sm_scale
            ),
            q, k, v,
        )
        return vjp(g)

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block", "interpret")
)
def _flash_mha(q, k, v, *, causal, sm_scale, block, interpret):
    B, H, T, hd = q.shape
    flat = lambda t: t.reshape(B * H, T, hd)
    grid = (B * H, T // block)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, sm_scale=sm_scale, block=block, causal=causal
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, hd), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(flat(q), flat(k), flat(v))
    return out.reshape(B, H, T, hd)


def reference_mha(q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """Plain-XLA oracle: same contract as :func:`mha`, O(T^2) memory."""
    hd = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = q.shape[-2]
        i = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        scores = jnp.where(j <= i, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _auto_impl() -> str:
    """What ``auto`` prefers on this process's default backend: the
    compiled kernel on a TPU, the XLA path elsewhere.  A backend that
    fails to initialize raises here — it is never read as "cpu"."""
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"


def pallas_supported(q_shape, block_min: int = 8) -> bool:
    """Kernel preconditions: T divisible by a tile-worthy block."""
    T = q_shape[-2]
    return T >= 2 * block_min and _pick_block(T) >= block_min


def resolve_attention_impl(impl: Optional[str], supported) -> str:
    """The ONE dispatch rule shared by the dense (:func:`mha`) and paged
    (:func:`paged_decode_attention`) entry points, so the two paths cannot
    drift on platform/eligibility behavior.

    ``None`` / ``"auto"`` let the code choose: :func:`_auto_impl`'s
    preference (pallas on TPU, xla elsewhere), downgraded to ``"xla"``
    when the shape does not qualify for the kernel.  An EXPLICIT impl is
    a request: ``"pallas"`` / ``"pallas_interpret"`` on a shape the kernel
    cannot take raises ``ValueError`` instead of running something else
    (engines and the CLI report the resolved name, so what ran is never
    a guess).  ``supported`` is a callable taking the impl name, so
    callers can keep compiled-mode tiling constraints out of the
    interpret path.  Anything outside the known impls raises too.
    """
    if impl is None or impl == "auto":
        impl = _auto_impl()
        return impl if impl == "xla" or supported(impl) else "xla"
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl != "xla" and not supported(impl):
        raise ValueError(
            f"attention impl {impl!r} was requested explicitly but this "
            "call's shape/dtype does not qualify for the kernel; pass "
            "impl='auto' to let the dispatch choose"
        )
    return impl


def _sublane_rows(dtype: Any) -> int:
    """Rows of the dtype's sublane tile: 8 at 32 bits, 16 at 16, 32 at 8."""
    return {2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def paged_kernel_constraints(
    page_size: int,
    head_dim: int,
    n_kv_heads: int,
    n_q_heads: Optional[int] = None,
    dtype: Any = jnp.float32,
    q_tokens: Optional[int] = None,
) -> list:
    """Tile-alignment rules the dispatch applies before choosing the
    COMPILED ragged paged kernel — empty list means the geometry is
    kernel-eligible.

    One source of truth for three consumers: the dispatch (``"auto"``
    takes the gather path when non-empty, an explicit ``"pallas"``
    raises), the DEC005 analysis warning (which quotes these strings
    verbatim), and the docs.  The kernels move K/V one ``(page_size,
    n_kv_heads * head_dim)`` page at a time — the single-token kernel
    several such page refs a grid step, a block of
    :func:`paged_block_pages` pages, the block rule and its VMEM
    reckoning being stated once over :func:`_paged_kernel`; the rules
    here keep ``page_size`` a multiple of the dtype's sublane tile and
    ``head_dim`` a multiple of 8, i.e. natively aligned tiles (interpret
    mode has no tiling and skips this check entirely).  No rule bounds
    the table: ``pages_per_seq`` short of a block is one block, and one
    that is not a multiple of the block has a shorter last block.

    They are a conservative PREFERENCE, not a lowering requirement: on
    the v5e (libtpu 0.0.34) the kernels compile and match the gather path
    at geometries these rules reject — f32 page 4, bf16 page 8, head_dim
    12, a 7-row query chunk (``chip_smoke.py`` kernels probe, PR 21 and,
    for the block walk, PR 25; PERF.md).  Whether misaligned tiles are
    slower is not measured.
    """
    sublane = _sublane_rows(dtype)
    out = []
    if page_size % sublane:
        out.append(
            f"page_size {page_size} is not a multiple of the {sublane}-row "
            f"sublane tile for {jnp.dtype(dtype).name} K/V page blocks"
        )
    if head_dim % 8:
        out.append(
            f"head_dim {head_dim} is not a multiple of the 8-lane sublane "
            "tile of the per-page score/accumulator blocks"
        )
    if n_kv_heads < 1:
        out.append(f"n_kv_heads {n_kv_heads} must be >= 1")
    if n_q_heads is not None and n_q_heads % max(n_kv_heads, 1):
        out.append(
            f"n_q_heads {n_q_heads} is not a multiple of n_kv_heads "
            f"{n_kv_heads} (GQA group mapping)"
        )
    if q_tokens is not None:
        if q_tokens < 1:
            out.append(f"q_tokens {q_tokens} must be >= 1")
        elif q_tokens > 1 and q_tokens % sublane:
            out.append(
                f"q_tokens {q_tokens} is not a multiple of the "
                f"{sublane}-row sublane tile of the ragged multi-token "
                "query block"
            )
    return out


def paged_pallas_supported(
    q_shape, pool_shape, interpret: bool = False,
    dtype: Any = jnp.float32,
) -> bool:
    """Eligibility of the ragged paged kernel for this call.

    ``pool_shape`` is the stored ``(n_pages, page_size, row_width)`` or
    the head-split ``(n_pages, page_size, n_kv_heads, head_dim)`` form
    (:func:`_stored_rows`).  Structural preconditions (every mode): the
    row a whole number of ``head_dim`` heads, query heads an exact
    multiple of them, at least one query token (Tn == 1 is the decode
    step; Tn > 1 is a ragged prefill chunk with per-slot ``q_lens``).
    Compiled mode additionally requires the
    :func:`paged_kernel_constraints` tiling rules at the POOL's ``dtype``
    (the sublane tile depends on it); interpret mode (CPU parity tests)
    has no tiling constraints.
    """
    S, Hq, Tn, hd = q_shape
    page_size, width = pool_shape[1], math.prod(pool_shape[2:])
    Hkv = width // hd
    if Tn < 1 or Hkv < 1 or width % hd or Hq % Hkv or (
            len(pool_shape) == 4 and pool_shape[3] != hd):
        return False
    if interpret:
        return True
    return not paged_kernel_constraints(
        page_size, hd, Hkv, n_q_heads=Hq, dtype=dtype,
        q_tokens=Tn if Tn > 1 else None,
    )


def resolve_paged_impl(
    impl: Optional[str], q_shape, pool_shape, dtype: Any
) -> str:
    """The impl :func:`paged_decode_attention` runs for this geometry —
    the same rule the op applies at trace time, callable from the host so
    engines and reports can NAME what ran (``"auto"`` is a request, never
    an answer)."""
    return resolve_attention_impl(
        impl,
        lambda i: paged_pallas_supported(
            q_shape, pool_shape, interpret=(i == "pallas_interpret"),
            dtype=dtype,
        ),
    )


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Multi-head attention on (B, H, T, hd) tensors.

    impl: "pallas" (TPU kernel), "pallas_interpret" (CPU-debuggable kernel),
    "xla" (reference einsum path), or None/"auto" = auto (pallas on TPU
    when the shape qualifies, xla otherwise).  An explicit kernel impl on
    a shape :func:`pallas_supported` rejects raises.
    """
    impl = resolve_attention_impl(
        impl, lambda _i: pallas_supported(q.shape)
    )
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if impl == "pallas" or impl == "pallas_interpret":
        return _flash_with_vjp(
            causal,
            float(scale),
            _pick_block(q.shape[-2]),
            impl == "pallas_interpret",
        )(q, k, v)
    return reference_mha(q, k, v, causal=causal, sm_scale=scale)


# -- single-token paged attention: the block walk ---------------------------
#
# One grid step covers a BLOCK of ``pages_per_block`` consecutive logical
# pages of one slot.  The rule (stated once, here; the engine's
# ``decode.kv_live_block_share`` counter and the tests read it through
# :func:`paged_block_pages` / :func:`latent_block_pages`): a block is as
# many pages as keep the pipeline's page buffers (two a page and pool)
# inside ``_PAGED_BUFFER_BYTES`` of VMEM at the pool's dtype, a page
# counted as the bytes it occupies there — ``page_size`` rows of
# :func:`lane_width` values — and never more than the table holds: a
# table shorter than one block IS one block.  At GPT-2 XL's serving
# geometry (page 16, a row of 25 x 64 = 1,600 values, bf16) a K or V page
# is 52 KiB, so a block is 9 pages = 144 rows; a latent page of 128 rows
# of 640 is 160 KiB and a block 6 pages.  The K/V kernel computes a block
# at a time — its pages stacked into one (rows_per_block, row_width)
# operand, so the MXU makes two passes over 144 rows where nine pages took
# eighteen — and the latent kernel a page at a time; either way the
# working set is well inside the 16 MiB default VMEM scope.  The budget is
# the v5e's measured optimum (PERF.md section 6, PR 25 and PR 28): every
# slot costs at least one step, and a step pays ~0.04 us for each page ref
# it carries whether the page is live or not, so a smaller block is
# cheaper where most slots hold nothing and a larger one where most are
# full.
_PAGED_BUFFER_BYTES = 2 << 20


def lane_width(values: int) -> int:
    """Lanes the device holds for a row of ``values``: whole 128-lane
    tiles.  The one reckoning of a stored row's width: a page's bytes
    (:func:`_block_pages`) and the latent row a model pads itself to
    (:func:`...models.xing4.latent_row_width`) both come from it."""
    return -(-values // 128) * 128


def _block_pages(
    page_size: int, pages_per_seq: int, row_width: int, dtype: Any,
    pools: int,
) -> int:
    page_bytes = page_size * lane_width(row_width) * jnp.dtype(dtype).itemsize
    return max(1, min(
        pages_per_seq, _PAGED_BUFFER_BYTES // (2 * pools * page_bytes)))


def paged_block_pages(
    page_size: int, pages_per_seq: int, n_kv_heads: int, head_dim: int,
    dtype: Any,
) -> int:
    """Pages in one block of the single-token paged kernel's walk over
    the K and V pools, from what the call can observe (the rule is in
    the comment above; a row is ``n_kv_heads * head_dim`` values).
    ``page_size *`` this is ``rows_per_block``: slot ``s`` costs
    ``cdiv(min(L_s, capacity - 1) + 1, rows_per_block)`` live blocks."""
    return _block_pages(
        page_size, pages_per_seq, n_kv_heads * head_dim, dtype, pools=2)


def latent_block_pages(
    page_size: int, pages_per_seq: int, row_width: int, dtype: Any,
) -> int:
    """:func:`paged_block_pages` for the one latent pool of
    :func:`_mla_paged_flash`: the same VMEM budget, the same rule."""
    return _block_pages(page_size, pages_per_seq, row_width, dtype, pools=1)


def _live_block_tables(page_table, lengths, page_size: int, ppb: int):
    """The dynamic grid both single-token paged kernels walk: the live
    (slot, page block) pairs, slot-major.  Returns ``(slot_of, block_of,
    fetch, lengths, n_live)``: step ``t < n_live`` is block
    ``block_of[t]`` of slot ``slot_of[t]`` and its ``i``-th page ref
    holds physical page ``fetch[t * ppb + i]`` (page 0 past the slot's
    last row: a block index that repeats from one step to the next is
    not fetched again, and the kernel never reads it).  The tables are
    as long as the table's capacity in blocks; the grid only as long as
    the list."""
    S, ppseq = page_table.shape
    lengths = lengths.astype(jnp.int32)
    last_page = jnp.minimum(lengths, ppseq * page_size - 1) // page_size
    blocks = last_page // ppb + 1                       # live, per slot
    ends = jnp.cumsum(blocks)
    steps = jnp.arange(S * -(-ppseq // ppb), dtype=jnp.int32)
    slot_of = jnp.minimum(
        (steps[:, None] >= ends[None, :]).sum(axis=1, dtype=jnp.int32),
        S - 1)
    block_of = steps - (ends - blocks)[slot_of]
    page = block_of[:, None] * ppb + jnp.arange(ppb, dtype=jnp.int32)
    fetch = jnp.where(
        page <= last_page[slot_of][:, None],
        page_table.astype(jnp.int32)[
            slot_of[:, None], jnp.minimum(page, ppseq - 1)],
        0,
    ).reshape(-1)
    return slot_of, block_of, fetch, lengths, ends[-1]


def _stored_rows(pool):
    """A K or V pool in the stored form ``(n_pages, page_size,
    row_width)``, the row its ``n_kv_heads * head_dim`` values as one
    vector on the lanes (:class:`...models.kv_pages.CacheSpec`).  The
    head-split ``(n_pages, page_size, n_kv_heads, head_dim)`` form is
    taken as a view of it."""
    return pool.reshape(*pool.shape[:2], -1)


def _head_rows(pool, head_dim: int):
    """The head-split view ``(n_pages, page_size, n_kv_heads, head_dim)``
    of a pool in either form: what the paths off the serving path read
    (:func:`_paged_flash_ragged`, the gather paths)."""
    return pool.reshape(*pool.shape[:2], -1, head_dim)


def _paged_kernel(
    slot_ref, block_ref, fetch_ref, len_ref, q_ref, kn_ref, vn_ref, *refs,
    page_size, pages_per_seq, pages_per_block, head_dim, has_new,
):
    """One LIVE (slot, page block) of the ragged paged kernel.

    The grid is the list of live blocks, slot-major, and as long as that
    list (:func:`_paged_flash` works it out from the lengths): step ``t``
    is block ``block_ref[t]`` of slot ``slot_ref[t]``.  ``refs`` holds
    ``pages_per_block`` K page refs, as many V page refs, the output and
    the scratch.  Each page ref is ONE physical page where it lies in
    the pool, ``(page_size, row_width)`` with every KV head's values side
    by side on the lanes: its BlockSpec index map reads ``fetch_ref``, so
    the DMA engine fetches exactly the slot's live pages and the gathered
    view never exists in HBM.  Slot ``s`` attends rows ``0 .. last`` with
    ``last = min(lengths[s], capacity - 1)``; its live pages are ``0 ..
    last // page_size`` and its live blocks the ``cdiv`` of that.  A
    block past it is not in the list; inside the last live block a page
    past ``last`` is not fetched, and what its ref holds is masked.

    Heads are told apart on the MXU, not by splitting the lanes.
    ``q_ref`` is ``(groups, row_width)``: group ``g``'s scaled query
    heads side by side as the pools hold their KV heads.  A slot's first
    block spreads it into the MASKED query ``qm`` (scratch), one row a
    query head, holding that head's query in its KV head's lanes and
    zeros in all others (row ``g * heads_p + h`` is head ``h`` of group
    ``g``, a group's heads padded to a sublane tile).  So ``qm . K^T``
    over the whole row is each head's own scores — the other heads'
    lanes add products with an exact zero — and ``p . V`` gives every
    row all heads' values, of which the finalize keeps the row's own
    lanes.  The output is ``(groups, row_width)`` like the query.

    A block is computed at once: its pages stacked into one
    ``(rows_per_block, row_width)`` K and one V, one score matmul, one
    online-softmax update (``acc``/``m``/``l`` VMEM scratch, persistent
    across grid steps, initialized at a slot's first block and folded
    into the output at its last live one) and one value matmul.  Only the
    block holding row ``last`` is ragged: there rows past ``last`` get a
    −inf score and a zeroed V row by selection (whatever they hold, NaN
    included, reaches nothing), and ``has_new`` statically compiles in
    the write-then-attend insert — this step's K/V row substituted at
    ``last`` before the scores (clamped to the capacity's last row like
    the gather path's ``dynamic_update_slice``).  Blocks before it are
    wholly live and take the plain path.  With the insert, a slot at
    length 0 — every slot the engine is not decoding — attends its own
    row alone: the softmax of one score is 1 and the output that row's
    values, so the step reads no page and multiplies nothing.
    """
    del fetch_ref  # read by the page BlockSpecs' index maps only
    ppb = pages_per_block
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, qm_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    groups = q_ref.shape[1]
    heads_p = qm_ref.shape[0] // groups
    t = pl.program_id(0)
    s_idx = slot_ref[t]
    j = block_ref[t]
    L = len_ref[s_idx]
    last = jnp.minimum(L, pages_per_seq * page_size - 1)
    last_page = last // page_size

    if has_new:
        @pl.when(L == 0)
        def _new_row_only():
            o_ref[0] = jnp.broadcast_to(
                vn_ref[0], o_ref.shape[1:]).astype(o_ref.dtype)

        when = lambda cond: pl.when(jnp.logical_and(cond, L > 0))
    else:
        when = pl.when

    def own_lanes():
        """(heads_p, row_width): lane belongs to the row's KV head."""
        shape = (heads_p, qm_ref.shape[1])
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * head_dim
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return (lane >= head) & (lane < head + head_dim)

    @when(j == 0)
    def _init():
        own = own_lanes()
        for g in range(groups):
            qm_ref[g * heads_p:(g + 1) * heads_p] = jnp.where(
                own, q_ref[0, g:g + 1].astype(jnp.float32), 0.0
            ).astype(qm_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(ragged):
        # the block's pages stacked: (pages_per_block * page_size, row_width)
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
        first_row = j * (ppb * page_size)
        if ragged:
            row = jax.lax.broadcasted_iota(
                jnp.int32, (k.shape[0], 1), 0) + first_row
            if has_new:
                k = jnp.where(row == last, kn_ref[0], k)
                v = jnp.where(row == last, vn_ref[0], v)
            v = jnp.where(row <= last, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            qm_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (groups * heads_p, rows of a block)
        if ragged:
            pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + first_row
            s = jnp.where(pos <= last, s, _NEG_INF)
        # position 0 is unmasked for every slot, so after block 0 the
        # running max is a real (finite) score and the exp() arguments
        # stay finite
        m_prev = m_ref[...]                       # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (rows, row_width)
        m_ref[...] = m_new

    when(j < last_page // ppb)(functools.partial(attend, False))
    when(j == last_page // ppb)(functools.partial(attend, True))

    @when(j == last_page // ppb)
    def _finalize():
        own = own_lanes()
        for g in range(groups):
            rows = slice(g * heads_p, (g + 1) * heads_p)
            num = jnp.where(own, acc_ref[rows], 0.0).sum(
                axis=0, keepdims=True)
            den = jnp.where(own, l_ref[rows], 0.0).sum(
                axis=0, keepdims=True)
            o_ref[0, g:g + 1] = (num / den).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "has_new", "interpret")
)
def _paged_flash(
    q, k_pool, v_pool, page_table, lengths, k_new, v_new, *,
    sm_scale, has_new, interpret,
):
    """Fused ragged paged attention whose work follows the live pages,
    reading every page where the pool holds it.

    ``k_pool`` / ``v_pool`` are in the stored form ``(n_pages,
    page_size, row_width)`` (:func:`_stored_rows`); a page ref is a
    ``(1, page_size, row_width)`` block of the argument itself, so no
    copy of a pool is made in front of the call — and none by XLA's
    prefetching either: the pools are pinned to HBM, or the compiler
    moves a whole pool into its fast memory ahead of a call that reads a
    few pages of it.

    The grid is DYNAMIC: one step per live page block, ``sum_s
    cdiv(min(L_s, capacity - 1) + 1, rows_per_block)`` of them
    (:func:`paged_block_pages`), listed slot-major in three small tables
    that ride as scalar-prefetch operands beside the lengths — the slot
    and the block of step ``t``, and ``fetch[t, i]``, the physical page
    the block's ``i``-th page ref holds.  Each pool is passed once per
    page of a block, every pass a one-page BlockSpec whose index map
    reads ``fetch``: the table's page while the logical page is live,
    physical page 0 past the slot's last row (any fixed page would do —
    a block index that repeats from one grid step to the next is not
    fetched again, and the kernel never reads it).  HBM traffic, compute
    and grid steps are proportional to the rows the slots hold, not to
    the table's capacity; the dense gather's (S, M, Hkv, hd)
    intermediate never exists; the step count is data like the lengths,
    so no length, admission or retirement recompiles.
    """
    S, Hq, _, hd = q.shape
    k_pool, v_pool = _stored_rows(k_pool), _stored_rows(v_pool)
    if not interpret:
        k_pool = pltpu.with_memory_space_constraint(k_pool, pltpu.HBM)
        v_pool = pltpu.with_memory_space_constraint(v_pool, pltpu.HBM)
    dtype = k_pool.dtype
    _, page_size, W = k_pool.shape
    Hkv = W // hd
    G = Hq // Hkv
    ppseq = page_table.shape[1]
    ppb = paged_block_pages(page_size, ppseq, Hkv, hd, dtype)
    # query head h * G + g -> group g, lanes of KV head h: (S, G, W)
    qg = (q.astype(jnp.float32) * sm_scale).astype(dtype).reshape(
        S, Hkv, G, hd).transpose(0, 2, 1, 3).reshape(S, G, W)
    if has_new:
        kn = k_new.reshape(S, 1, W).astype(dtype)
        vn = v_new.reshape(S, 1, W).astype(dtype)
    else:  # zero placeholders keep the arity static; kernel never reads
        kn = vn = jnp.zeros((S, 1, W), dtype)

    slot_of, block_of, fetch, lengths, n_live = _live_block_tables(
        page_table, lengths, page_size, ppb)

    def page_spec(i):
        return pl.BlockSpec(
            (1, page_size, W),
            lambda t, slot, blk, fetch, ln: (fetch[t * ppb + i], 0, 0),
        )

    def slot_spec(rows):
        return pl.BlockSpec(
            (1, rows, W), lambda t, slot, blk, fetch, ln: (slot[t], 0, 0))

    pages = [page_spec(i) for i in range(ppb)]
    rows = G * -(-Hkv // 8) * 8   # a group's heads padded to a sublane tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live,),
        in_specs=[slot_spec(G), slot_spec(1), slot_spec(1)] + pages + pages,
        out_specs=slot_spec(G),
        scratch_shapes=[
            pltpu.VMEM((rows, W), dtype),
            pltpu.VMEM((rows, W), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, pages_per_seq=ppseq,
            pages_per_block=ppb, head_dim=hd, has_new=has_new,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, W), q.dtype),
        interpret=interpret,
    )(
        slot_of, block_of, fetch, lengths,
        qg, kn, vn, *([k_pool] * ppb), *([v_pool] * ppb),
    )
    return out.reshape(S, G, Hkv, hd).transpose(0, 2, 1, 3).reshape(
        S, Hq, 1, hd)


def _paged_ragged_kernel(
    pt_ref, len_ref, ql_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref, *, sm_scale, page_size, groups, q_tokens,
):
    """One (slot, logical page) grid step of the ragged MULTI-token-q
    paged kernel — the prefill-chunk shape of :func:`_paged_kernel`.

    The query block carries ``q_tokens`` rows per slot; per-slot
    ``q_lens`` rides scalar prefetch next to the page table and lengths.
    Query row ``t`` of slot ``s`` sits at absolute position
    ``lengths[s] + t`` and attends KV positions ``<= lengths[s] + t``
    (causal within the chunk, full history before it) — write-then-
    attend: the chunk's own K/V rows are already scattered into the
    pool.  Rows at or past ``q_lens[s]`` are padding; their mask is
    clamped to the last real row so every output row stays finite and
    trash-page-invariant (the caller discards them).  The online-softmax
    carry is the single-token kernel's with the (groups) axis widened to
    (groups * q_tokens).
    """
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    L = len_ref[s_idx]
    QL = ql_ref[s_idx]
    hd = q_ref.shape[-1]
    Hkv = k_ref.shape[2]
    # (Hq, Tn, hd) -> (Hkv, G*Tn, hd): adjacent-axis merge, column
    # c = g*q_tokens + t, so t recovers as c % q_tokens
    q = (q_ref[0].astype(jnp.float32) * sm_scale).reshape(
        Hkv, groups * q_tokens, hd
    )
    k = k_ref[0].astype(jnp.float32)  # (page_size, Hkv, hd)
    v = v_ref[0].astype(jnp.float32)
    # scores (Hkv, page_size, G*Tn): K @ q, the gather path's orientation
    s = jax.lax.dot_general(
        k, q, (((2,), (2,)), ((1,), (0,))),
        preferred_element_type=jnp.float32,
    )
    pos = (
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page_size
    )
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) % q_tokens
    t_eff = jnp.clip(t, 0, jnp.maximum(QL - 1, 0))
    s = jnp.where(pos <= L + t_eff, s, _NEG_INF)
    # position 0 is unmasked for every row (L + t_eff >= 0), so the
    # running max turns finite at page 0 and the exp() args stay finite
    m_prev = m_ref[...]                       # (Hkv, G*Tn)
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None, :])        # (Hkv, page_size, G*Tn)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, :, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )  # (Hkv, G*Tn, hd)
    m_ref[...] = m_new

    @pl.when(j == n_j - 1)
    def _finalize():
        out = acc_ref[...] / l_ref[...][:, :, None]
        o_ref[0] = out.reshape(
            Hkv * groups, q_tokens, hd
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_flash_ragged(
    q, k_pool, v_pool, page_table, lengths, q_lens, *,
    sm_scale, interpret,
):
    """Fused ragged multi-token-q paged attention (prefill chunks).

    A static (slots, pages_per_seq) grid, one table-directed page load
    a step whatever the lengths (the walk :func:`_paged_flash` had before
    it followed the live blocks; no serving path runs this kernel, and it
    reads the pools through their head-split view, :func:`_head_rows`),
    with
    a (1, Hq, Tn, hd) query block per slot and per-slot ``q_lens`` as a
    third scalar-prefetch operand.  No
    in-kernel insert: chunk K/V rows are scattered into the pool before
    the call (write-then-attend at chunk granularity).
    """
    S, Hq, Tn, hd = q.shape
    k_pool, v_pool = _head_rows(k_pool, hd), _head_rows(v_pool, hd)
    _, page_size, Hkv, _ = k_pool.shape
    G = Hq // Hkv
    ppseq = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, ppseq),
        in_specs=[
            pl.BlockSpec(
                (1, Hq, Tn, hd), lambda s, j, pt, ln, ql: (s, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, page_size, Hkv, hd),
                lambda s, j, pt, ln, ql: (pt[s, j], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page_size, Hkv, hd),
                lambda s, j, pt, ln, ql: (pt[s, j], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Hq, Tn, hd), lambda s, j, pt, ln, ql: (s, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G * Tn, hd), jnp.float32),
            pltpu.VMEM((Hkv, G * Tn), jnp.float32),
            pltpu.VMEM((Hkv, G * Tn), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_ragged_kernel, sm_scale=sm_scale,
            page_size=page_size, groups=G, q_tokens=Tn,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hq, Tn, hd), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lengths.astype(jnp.int32),
        q_lens.astype(jnp.int32), q, k_pool, v_pool,
    )


def _gather_chunk_attention(
    q, k_pool, v_pool, page_table, lengths, q_lens, scale
):
    """XLA gather path for ragged multi-token q — the op-level parity
    reference for :func:`_paged_flash_ragged`.

    Identical orientation and masking to the single-token gather path
    with the (G) column axis widened to (G*Tn) and the length mask
    shifted per query row: row ``t`` attends positions ``<=
    lengths[s] + t`` (padding rows clamp to the last real row, matching
    the kernel).  Chunk rows must already be resident in the pools.
    """
    from ..models.kv_pages import gather_kv_flat  # lazy: models imports ops

    S, Hq, Tn, hd = q.shape
    k_view = gather_kv_flat(k_pool, page_table, hd)  # (S, M, Hkv, hd)
    v_view = gather_kv_flat(v_pool, page_table, hd)
    Hkv = k_view.shape[2]
    G = Hq // Hkv
    qg = (q * scale).reshape(S, Hkv, G * Tn, hd)
    s = jax.lax.dot_general(
        k_view.astype(qg.dtype), qg,
        (((3,), (3,)), ((0, 2), (0, 1))),
        preferred_element_type=jnp.float32,
    )  # (S, Hkv, M, G*Tn)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3) % Tn
    ql = q_lens.reshape(S, 1, 1, 1).astype(jnp.int32)
    t_eff = jnp.clip(t, 0, jnp.maximum(ql - 1, 0))
    valid = rows <= lengths.reshape(S, 1, 1, 1) + t_eff
    s = jnp.where(valid, s, jnp.finfo(s.dtype).min)
    m = s.max(axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=2, keepdims=True)
    out_dtype = q.dtype
    o = jax.lax.dot_general(
        p.astype(out_dtype), v_view.astype(out_dtype),
        (((2,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32,
    )  # (S, Hkv, G*Tn, hd)
    return (o / l.reshape(S, Hkv, G * Tn, 1)).astype(out_dtype).reshape(
        S, Hq, Tn, hd
    )


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    sm_scale: Optional[float] = None,
    k_new: Optional[jax.Array] = None,
    v_new: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    q_lens: Optional[jax.Array] = None,
) -> jax.Array:
    """Ragged paged single-token attention: gather-by-page-table,
    per-sequence length-masked, static shapes throughout.

    ``q`` (S, Hq, 1, hd) — one new token per batch slot; ``k_pool`` /
    ``v_pool`` (P, page_size, Hkv * hd) — the shared page pools in their
    stored form, a row's heads side by side on the lanes
    (:class:`..models.kv_pages.CacheSpec`; the head-split (P, page_size,
    Hkv, hd) form is taken as a view of it); ``page_table`` (S, pages_per_seq) int32
    — slot ``s``'s logical page ``j`` lives in physical page
    ``page_table[s, j]``; ``lengths`` (S,) int32 — tokens already cached
    per slot.  ``k_new``/``v_new`` (S, Hkv, 1, hd), when given, are this
    step's rows, inserted into the gathered view at ``lengths[s]``
    BEFORE the scores — the write-then-attend order of the dense path
    (:func:`...models.decode.cached_attention`), so outputs are
    bit-identical to a dense cache of the same per-sequence capacity.
    Slot ``s`` attends positions ``m <= lengths[s]``; rows past a
    sequence's last allocated page gather the trash page and are masked
    by the same comparison.

    The math after the gather is the dense decode path's MXU-natural
    orientation (``_decode_attention_natural``: K @ q, scores
    (S, Hkv, M, G), softmax over M) — deliberately, for two reasons:
    scores are elementwise identical to the dense cache's (the parity
    the mixed-length benchmark gates on), and the (pages, page_size)
    leading axes of the pools are exactly the block structure the Pallas
    ragged-paged-attention kernel (:func:`_paged_flash`) consumes.

    ``impl`` mirrors :func:`mha`: ``"xla"`` is the gather path above,
    ``"pallas"`` the fused kernel (page-table-directed VMEM block loads,
    online softmax — no gathered intermediate), ``"pallas_interpret"``
    the same kernel through the Pallas interpreter (CPU parity tests),
    and ``None``/``"auto"`` picks the kernel on TPU when the geometry
    passes :func:`paged_kernel_constraints`, the gather path otherwise
    (the choice DEC005 warns about; :func:`resolve_paged_impl` names it).
    An explicit kernel impl on an ineligible geometry raises.  Kernel outputs are
    allclose — not bitwise — to the gather path (page-blocked online
    softmax associates its reductions differently), which keeps greedy
    argmax tokens identical at engine scale (pinned by the parity gate).

    ``q`` with Tn > 1 is a ragged prefill chunk: per-slot ``q_lens``
    (S,) int32 gives the number of REAL query rows (rows past it are
    padding, returned finite but meaningless), query row ``t`` of slot
    ``s`` sits at absolute position ``lengths[s] + t`` and attends
    causally, and the chunk's K/V rows must already be scattered into
    the pools (``k_new`` is not accepted — write-then-attend is at
    chunk granularity, not per-row).
    """
    S, Hq, Tn, hd = q.shape
    if Tn != 1:
        if q_lens is None:
            raise ValueError(
                f"multi-token q (Tn={Tn}) requires per-slot q_lens"
            )
        if k_new is not None:
            raise ValueError(
                "multi-token q takes no k_new/v_new: scatter the chunk "
                "into the pools first (write-then-attend at chunk "
                "granularity)"
            )
        impl = resolve_paged_impl(impl, q.shape, k_pool.shape, k_pool.dtype)
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
        if impl in ("pallas", "pallas_interpret"):
            return _paged_flash_ragged(
                q, k_pool, v_pool, page_table, lengths, q_lens,
                sm_scale=float(scale),
                interpret=impl == "pallas_interpret",
            )
        return _gather_chunk_attention(
            q, k_pool, v_pool, page_table, lengths, q_lens, scale
        )
    impl = resolve_paged_impl(impl, q.shape, k_pool.shape, k_pool.dtype)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    if impl in ("pallas", "pallas_interpret"):
        return _paged_flash(
            q, k_pool, v_pool, page_table, lengths, k_new, v_new,
            sm_scale=float(scale), has_new=k_new is not None,
            interpret=impl == "pallas_interpret",
        )
    from ..models.kv_pages import gather_kv_flat  # lazy: models imports ops

    # flat (S, M, Hkv, hd) gather: a free reshape of the page gather's
    # output, where the dense (S, Hkv, M, hd) orientation would pay a
    # materializing transpose of the whole working set every step.  The
    # dot_general batch dims below are permuted to match — contraction
    # and softmax reductions see the SAME operands in the SAME logical
    # order, so outputs stay bit-identical to the dense-orientation math
    # (pinned by the parity tests).
    k_view = gather_kv_flat(k_pool, page_table, hd)  # (S, M, Hkv, hd)
    v_view = gather_kv_flat(v_pool, page_table, hd)
    M, Hkv = k_view.shape[1], k_view.shape[2]
    G = Hq // Hkv

    if k_new is not None:
        insert = jax.vmap(
            lambda buf, row, at: jax.lax.dynamic_update_slice(
                buf, row.transpose(1, 0, 2).astype(buf.dtype),
                (at, jnp.int32(0), jnp.int32(0)),
            )
        )
        # (S, Hkv, 1, hd) rows land at per-sequence position lengths[s]
        k_view = insert(k_view, k_new, lengths)
        v_view = insert(v_view, v_new, lengths)

    qg = (q * scale).reshape(S, Hkv, G, hd)
    s = jax.lax.dot_general(
        k_view.astype(qg.dtype), qg,
        (((3,), (3,)), ((0, 2), (0, 1))),
        preferred_element_type=jnp.float32,
    )  # (S, Hkv, M, G)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    valid = rows <= lengths.reshape(S, 1, 1, 1)
    s = jnp.where(valid, s, jnp.finfo(s.dtype).min)
    m = s.max(axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=2, keepdims=True)
    out_dtype = q.dtype
    o = jax.lax.dot_general(
        p.astype(out_dtype), v_view.astype(out_dtype),
        (((2,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32,
    )
    return (o / l.reshape(S, Hkv, G, 1)).astype(out_dtype).reshape(
        S, Hq, 1, hd
    )


# -- absorbed MLA over a latent page pool ------------------------------------
#
# One pool a layer, row = [c | k_r]: ``rank`` normalised latent values and
# the rotated shared key.  With W_UK absorbed into the query and W_UV
# applied after, every head scores against the SAME row (all ``width``
# values) and takes its values from the row's first ``rank``, so a page
# is read once and used by all heads.  The grid is the live-block list of
# :func:`_live_block_tables`, shared with :func:`_paged_flash`.


def mla_kernel_constraints(
    page_size: int, row_width: int, rank: int, dtype: Any = jnp.float32,
) -> list:
    """Tiling rules for the COMPILED latent kernel, in the manner of
    :func:`paged_kernel_constraints` (empty = eligible; ``auto`` takes
    the gather path otherwise, an explicit ``"pallas"`` raises): the
    page a whole number of the dtype's sublane tiles, the value part a
    whole number of 128-lane tiles so that its slice of the row is
    aligned."""
    sublane = _sublane_rows(dtype)
    out = []
    if page_size % sublane:
        out.append(
            f"page_size {page_size} is not a multiple of the {sublane}-row "
            f"sublane tile for {jnp.dtype(dtype).name} latent pages"
        )
    if rank % 128 or not 0 < rank <= row_width:
        out.append(
            f"latent rank {rank} is not a positive multiple of the "
            f"128-lane tile inside the {row_width}-wide row"
        )
    return out


def resolve_mla_paged_impl(
    impl: Optional[str], page_size: int, row_width: int, rank: int,
    dtype: Any,
) -> str:
    """What :func:`mla_paged_decode_attention` runs at this geometry
    (:func:`resolve_attention_impl`'s rule; interpret mode has no
    tiling)."""
    return resolve_attention_impl(
        impl,
        lambda i: i == "pallas_interpret" or not mla_kernel_constraints(
            page_size, row_width, rank, dtype),
    )


def _mla_paged_kernel(
    slot_ref, block_ref, fetch_ref, len_ref, q_ref, new_ref, *refs,
    page_size, pages_per_seq, pages_per_block, rank, has_new, q_rows=1,
):
    """One live (slot, page block) of the absorbed-MLA kernel: the walk,
    the online-softmax carry and the ragged last page are
    :func:`_paged_kernel`'s; a page is ``(page_size, width)`` rows that
    all heads share, scores are float32 over the whole row and the
    float32 accumulator takes the row's first ``rank`` values.

    ``q_rows = R > 1``: the slot's ``R`` consecutive query rows (heads
    of row ``r`` at ``q_ref[0, r * H:(r + 1) * H]``) in the one walk.
    ``len_ref`` then holds the position of the LAST row; row ``r`` sits
    at ``last - (R - 1 - r)``, its ``new_ref[0, r]`` is spliced there and
    its heads see nothing past it."""
    del fetch_ref
    ppb = pages_per_block
    R = q_rows
    c_refs = refs[:ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[ppb:]
    t = pl.program_id(0)
    s_idx = slot_ref[t]
    j = block_ref[t]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    L = len_ref[s_idx]
    last = jnp.minimum(L, pages_per_seq * page_size - 1)
    last_page = last // page_size

    def attend(page, c_ref, ragged):
        q = q_ref[0]                      # (R * H, width), already scaled
        rows = c_ref[0]                   # (page_size, width)
        if ragged:
            row = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0) + page * page_size
            if has_new and R == 1:
                rows = jnp.where(row == last, new_ref[0], rows)
            elif has_new:
                for r in range(R):
                    rows = jnp.where(row == last - (R - 1 - r),
                                     new_ref[0, r:r + 1, :], rows)
            rows = jnp.where(row <= L, rows, jnp.zeros_like(rows))
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (R * H, page_size)
        if ragged:
            pos = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) + page * page_size
            if R == 1:
                s = jnp.where(pos <= L, s, _NEG_INF)
            else:
                H = s.shape[0] // R
                q_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // H
                s = jnp.where(pos <= L - (R - 1) + q_row, s, _NEG_INF)
        m_prev = m_ref[...]                       # (R * H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (R * H, rank)
        m_ref[...] = m_new

    # pages before the first query row's are whole for every row; the
    # pages from its own to the last row's take the masks
    first_page = (last_page if R == 1
                  else jnp.maximum(last - (R - 1), 0) // page_size)
    for i in range(ppb):
        page = j * ppb + i
        if R == 1:
            pl.when(page < last_page)(
                functools.partial(attend, page, c_refs[i], False))
            pl.when(page == last_page)(
                functools.partial(attend, page, c_refs[i], True))
        else:
            pl.when(page < first_page)(
                functools.partial(attend, page, c_refs[i], False))
            pl.when(jnp.logical_and(page >= first_page, page <= last_page))(
                functools.partial(attend, page, c_refs[i], True))

    @pl.when(j == last_page // ppb)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("rank", "has_new", "interpret", "name", "q_rows"),
)
def _mla_paged_flash(
    q, pool, page_table, lengths, new_row, *, rank, has_new, interpret,
    name="_mla_paged_flash", q_rows=1,
):
    """Absorbed MLA over the latent pool through the page table.

    ``q`` (S, H, width) — per head ``[q_nope W_UK^T | q_rope]`` with the
    softmax scale folded in; ``pool`` (P, page_size, width); ``new_row``
    (S, width) this step's ``[c | k_r]``, inserted write-then-attend at
    ``lengths[s]``.  Returns (S, H, rank): ``softmax(q . row) . c`` per
    head, W_UV still to apply.  Work follows the live pages exactly as
    :func:`_paged_flash`'s does.  ``name`` is the kernel's name in a
    device trace (the selected-row attention runs it under its own).

    ``q_rows = R > 1`` (a step that verifies drafts): ``q`` (S, R * H,
    width), row-major, ``new_row`` (S, R, width) spliced at ``lengths[s]
    .. lengths[s] + R - 1``; the heads of row ``r`` see positions ``<=
    lengths[s] + r``; one walk of the slot's live blocks serves every
    row.  Returns (S, R * H, rank)."""
    S, RH, width = q.shape
    _, page_size, _ = pool.shape
    ppseq = page_table.shape[1]
    ppb = latent_block_pages(page_size, ppseq, width, pool.dtype)
    q = q.astype(pool.dtype)
    new = (new_row.astype(pool.dtype) if has_new
           else jnp.zeros((S, q_rows * width), pool.dtype)
           ).reshape(S, q_rows, width)
    # the walk ends at the last query row's page
    slot_of, block_of, fetch, lengths, n_live = _live_block_tables(
        page_table, lengths if q_rows == 1 else lengths + (q_rows - 1),
        page_size, ppb)

    def page_spec(i):
        return pl.BlockSpec(
            (1, page_size, width),
            lambda t, slot, blk, fetch, ln: (fetch[t * ppb + i], 0, 0),
        )

    def slot_spec(rows, cols):
        return pl.BlockSpec(
            (1, rows, cols), lambda t, slot, blk, fetch, ln: (slot[t], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live,),
        in_specs=[slot_spec(RH, width), slot_spec(q_rows, width)]
        + [page_spec(i) for i in range(ppb)],
        out_specs=slot_spec(RH, rank),
        scratch_shapes=[
            pltpu.VMEM((RH, rank), jnp.float32),
            pltpu.VMEM((RH, 1), jnp.float32),
            pltpu.VMEM((RH, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_paged_kernel, page_size=page_size, pages_per_seq=ppseq,
        pages_per_block=ppb, rank=rank, has_new=has_new,
        **({} if q_rows == 1 else {"q_rows": q_rows}))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, RH, rank), pool.dtype),
        interpret=interpret,
        name=name,
    )(slot_of, block_of, fetch, lengths, q, new, *([pool] * ppb))


def _mla_gather_attention(q, pool, page_table, lengths, new_row, rank,
                          q_rows=1):
    """The gather path of :func:`mla_paged_decode_attention`: the slot's
    rows gathered dense through the table, masked past ``lengths`` (the
    heads of query row ``r`` of ``q_rows``: past ``lengths + r``)."""
    S, RH, width = q.shape
    rows = jnp.take(pool, page_table, axis=0).reshape(S, -1, width)
    M = rows.shape[1]
    if new_row is not None:
        for r in range(q_rows):
            at = jnp.minimum(lengths + r if r else lengths, M - 1)
            rows = rows.at[jnp.arange(S), at].set(
                (new_row if q_rows == 1 else new_row[:, r]
                 ).astype(rows.dtype))
    last = lengths if q_rows == 1 else lengths + (q_rows - 1)
    valid = jnp.arange(M)[None, :] <= last[:, None]
    rows = jnp.where(valid[:, :, None], rows, jnp.zeros_like(rows))
    s = jnp.einsum("shw,smw->shm", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32)
    if q_rows == 1:
        seen = valid[:, None, :]
    else:
        q_pos = lengths[:, None] + jnp.repeat(
            jnp.arange(q_rows), RH // q_rows)[None, :]      # (S, R * H)
        seen = jnp.arange(M)[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(seen, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shm,smc->shc", p.astype(rows.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return o.astype(pool.dtype)


def mla_paged_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    rank: int,
    new_row: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    q_rows: int = 1,
    name: Optional[str] = None,
) -> jax.Array:
    """Single-token absorbed MLA over a latent page pool: slot ``s``
    attends rows ``m <= lengths[s]`` of its pages (``new_row`` first
    written at ``lengths[s]``).  Shapes as :func:`_mla_paged_flash`;
    ``impl`` as :func:`paged_decode_attention` — the kernel, the kernel
    interpreted, or the gather path for a geometry
    :func:`mla_kernel_constraints` refuses.  ``q_rows`` consecutive
    query rows a slot (a step that verifies drafts) and the kernel's
    ``name`` in a device trace as :func:`_mla_paged_flash` takes them."""
    impl = resolve_mla_paged_impl(
        impl, pool.shape[1], pool.shape[2], rank, pool.dtype)
    more = {} if q_rows == 1 else {"q_rows": q_rows}
    if name is not None:
        more["name"] = name
    if impl in ("pallas", "pallas_interpret"):
        return _mla_paged_flash(
            q, pool, page_table, lengths, new_row, rank=rank,
            has_new=new_row is not None,
            interpret=impl == "pallas_interpret", **more,
        )
    return _mla_gather_attention(
        q, pool, page_table, lengths, new_row, rank, q_rows)


# -- sparse selection over a latent page pool, and a window over a ring ------
#
# DeepSeek-V3.2's "DSA" as the dots3 family runs it in a decode step: a
# light indexer scores every cached row of a slot, the step keeps the
# ``top_k`` best rows exactly, and absorbed MLA reads those rows alone
# through the page table.  Three device ops with stable names:
# ``_dsa_index`` (a kernel on :func:`_live_block_tables`' grid over the
# indexer-key pool), the exact selection (``lax.top_k`` and the gather of
# the picked rows, XLA), and ``_dsa_sparse_attn`` (:func:`_mla_paged_flash`
# over the gathered rows).  ``_swa_latent_attn`` is absorbed MLA over a
# slot-owned RING of pages with a lower bound on the rows it may see.


def _dsa_index_kernel(
    slot_ref, block_ref, fetch_ref, len_ref, q_ref, w_ref, *refs,
    page_size, pages_per_seq, pages_per_block,
):
    """One live (slot, page block): ``I[r] = sum_j w[j] relu(q[j] . k[r])``
    for the block's rows, float32 throughout (a rounding flip at the
    selection's boundary swaps a whole row of attention)."""
    del fetch_ref
    ppb = pages_per_block
    k_refs, o_ref = refs[:ppb], refs[ppb]
    t = pl.program_id(0)
    L = len_ref[slot_ref[t]]
    last_page = jnp.minimum(L, pages_per_seq * page_size - 1) // page_size
    j = block_ref[t]

    def score(i):
        keys = k_refs[i][0].astype(jnp.float32)           # (ps, Di)
        s = jax.lax.dot_general(
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)          # (Hi, ps)
        o_ref[0, :, i * page_size:(i + 1) * page_size] = (
            jnp.maximum(s, 0.0) * w_ref[0]).sum(axis=0, keepdims=True)

    for i in range(ppb):
        pl.when(j * ppb + i <= last_page)(functools.partial(score, i))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index(q, w, pool, page_table, lengths, *, interpret):
    """Index scores of every live row of every slot: ``q`` (S, Hi, Di)
    float32, ``w`` (S, Hi) float32, ``pool`` (P, page_size, Di) the cached
    indexer keys.  Returns (S, capacity) float32; rows past a slot's last
    live page hold whatever was there (the caller masks by position)."""
    S, Hi, Di = q.shape
    _, ps, _ = pool.shape
    ppseq = page_table.shape[1]
    ppb = latent_block_pages(ps, ppseq, Di, pool.dtype)
    nblk = -(-ppseq // ppb)
    slot_of, block_of, fetch, lengths, n_live = _live_block_tables(
        page_table, lengths, ps, ppb)

    def page_spec(i):
        return pl.BlockSpec(
            (1, ps, Di),
            lambda t, slot, blk, fetch, ln: (fetch[t * ppb + i], 0, 0))

    def slot_spec(rows, cols):
        return pl.BlockSpec(
            (1, rows, cols), lambda t, slot, blk, fetch, ln: (slot[t], 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _dsa_index_kernel, page_size=ps, pages_per_seq=ppseq,
            pages_per_block=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_live,),
            in_specs=[slot_spec(Hi, Di), slot_spec(Hi, 1)]
            + [page_spec(i) for i in range(ppb)],
            out_specs=pl.BlockSpec(
                (1, 1, ppb * ps),
                lambda t, slot, blk, fetch, ln: (slot[t], 0, blk[t])),
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, nblk * ppb * ps), jnp.float32),
        interpret=interpret,
        name="_dsa_index",
    )(slot_of, block_of, fetch, lengths, q.astype(jnp.float32),
      w.astype(jnp.float32)[:, :, None], *([pool] * ppb))
    return out[:, 0, :ppseq * ps]


def index_scores(q, w, keys):
    """``I[b, t, m] = sum_j w[b, t, j] relu(q[b, t, j] . keys[b, m])`` in
    float32: the indexer's score, the plain form (``q`` (B, T, Hi, Di),
    ``w`` (B, T, Hi), ``keys`` (B, M, Di))."""
    s = jnp.einsum("bthd,bmd->bthm", q.astype(jnp.float32),
                   keys.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return (jnp.maximum(s, 0.0)
            * w.astype(jnp.float32)[..., None]).sum(axis=2)


def dsa_index_scores(q, w, pool, page_table, lengths, new_key, impl=None):
    """The indexer's scores of one decode step, (S, capacity) float32:
    slot ``s`` scores the cached keys of its pages and, at position
    ``lengths[s]``, ``new_key`` (S, Di) — this step's, not yet written;
    every later position reads ``-inf``."""
    S, _, Di = q.shape
    ps = pool.shape[1]
    cap = page_table.shape[1] * ps
    impl = resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret" or (
            ps % _sublane_rows(pool.dtype) == 0 and Di % 128 == 0))
    if impl == "xla":
        keys = jnp.take(pool, page_table, axis=0).reshape(S, cap, Di)
        scores = index_scores(q[:, None], w[:, None], keys)[:, 0]
    else:
        scores = _dsa_index(q, w, pool, page_table, lengths,
                            interpret=impl == "pallas_interpret")
    mine = index_scores(q[:, None], w[:, None], new_key[:, None])[:, 0]
    pos = jnp.arange(cap, dtype=jnp.int32)[None, :]
    at = jnp.minimum(lengths, cap - 1)[:, None]
    return jnp.where(pos < at, scores,
                     jnp.where(pos == at, mine, -jnp.inf))


def dsa_select(scores, lengths, top_k: int):
    """The exact selection: the ``min(L + 1, top_k)`` positions of each
    slot with the largest score (``scores`` holds ``-inf`` past ``L``).
    Returns ``(idx (S, k) int32, n (S,) int32)``: the first ``n[s]``
    entries of ``idx[s]`` are the picked positions, best first."""
    k = min(int(top_k), scores.shape[1])
    _, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), jnp.minimum(lengths.astype(jnp.int32) + 1, k)


def dsa_sparse_attention(q, pool, page_table, idx, n, lengths, new_row, rank,
                         impl=None):
    """Absorbed MLA over the selected rows alone: slot ``s`` attends rows
    ``idx[s, :n[s]]`` of its pages (position ``lengths[s]`` is
    ``new_row[s]``, this step's).  The picked rows are gathered through
    the page table — ``k`` rows a slot, whatever the context — and
    :func:`_mla_paged_flash` runs over them as ``_dsa_sparse_attn``.
    ``q`` (S, H, width) as :func:`mla_paged_decode_attention`'s; returns
    (S, H, rank)."""
    S, k = idx.shape
    P, ps, width = pool.shape
    page = jnp.take_along_axis(page_table, idx // ps, axis=1)
    rows = jnp.take(pool.reshape(P * ps, width), page * ps + idx % ps, axis=0)
    rows = jnp.where((idx == lengths[:, None])[:, :, None],
                     new_row.astype(pool.dtype)[:, None, :], rows)
    impl = resolve_attention_impl(
        impl, lambda i: k % ps == 0 and (
            i == "pallas_interpret" or not mla_kernel_constraints(
                ps, width, rank, pool.dtype)))
    if impl == "xla":
        valid = jnp.arange(k)[None, :] < n[:, None]
        s = jnp.einsum("shw,smw->shm", q.astype(rows.dtype), rows,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(valid[:, None, :], s, _NEG_INF), axis=-1)
        return jnp.einsum(
            "shm,smc->shc", p.astype(rows.dtype), rows[..., :rank],
            preferred_element_type=jnp.float32).astype(pool.dtype)
    table = jnp.arange(S * (k // ps), dtype=jnp.int32).reshape(S, k // ps)
    return _mla_paged_flash(
        q, rows.reshape(S * (k // ps), ps, width), table, n - 1, None,
        rank=rank, has_new=False, interpret=impl == "pallas_interpret",
        name="_dsa_sparse_attn")


def _ring_valid(r, L, ring: int, window: int):
    """Which ring rows ``r`` a query at position ``L`` may see: the row
    of position ``p`` is ``p mod ring``, the query's own is ``L mod
    ring``, and ``d`` rows back lies position ``L - d``."""
    d = L % ring - r
    d = jnp.where(d < 0, d + ring, d)
    return jnp.logical_and(d < window, d <= L), d == 0


def _swa_kernel(len_ref, q_ref, new_ref, c_ref, o_ref, acc_ref, m_ref, l_ref,
                *, page_size, ring_pages, window, rank):
    """One (slot, ring page): the online-softmax carry of
    :func:`_mla_paged_kernel` over the slot's own ring, a row masked
    unless its position lies in the window (rows the ring still holds
    from before it, and across a wrap, are not seen)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    L = len_ref[pl.program_id(0)]
    ring = ring_pages * page_size
    col = jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0) + j * page_size
    ok_col, own_col = _ring_valid(col, L, ring, window)
    rows = jnp.where(own_col, new_ref[0], c_ref[0])
    rows = jnp.where(ok_col, rows, jnp.zeros_like(rows))
    q = q_ref[0]
    s = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (H, page_size)
    r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page_size
    ok, _ = _ring_valid(r, L, ring, window)
    s = jnp.where(ok, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)   # a page may hold no row
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == ring_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "window", "interpret"))
def _swa_latent_attn(q, pool, lengths, new_row, *, rank, window, interpret):
    S, H, width = q.shape
    n, ps, _ = pool.shape
    rp = (n - 1) // S
    return pl.pallas_call(
        functools.partial(_swa_kernel, page_size=ps, ring_pages=rp,
                          window=window, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, rp),
            in_specs=[
                pl.BlockSpec((1, H, width), lambda s, j, ln: (s, 0, 0)),
                pl.BlockSpec((1, 1, width), lambda s, j, ln: (s, 0, 0)),
                pl.BlockSpec((1, ps, width),
                             lambda s, j, ln: (1 + s * rp + j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, j, ln: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), pool.dtype),
        interpret=interpret,
        name="_swa_latent_attn",
    )(lengths.astype(jnp.int32), q.astype(pool.dtype),
      new_row.astype(pool.dtype).reshape(S, 1, width), pool)


def latent_window_attention(q, pool, lengths, new_row, rank, window,
                            impl=None):
    """Single-token absorbed MLA over a ring pool (:class:`...models.
    kv_pages.CacheSpec`, ring layers): ``pool`` (1 + S * ring_pages,
    page_size, width), slot ``s`` owning pages ``1 + s * ring_pages + j``
    and position ``p`` lying in ring row ``p mod (ring_pages *
    page_size)``.  The query at position ``lengths[s]`` attends positions
    ``lengths[s] - window < p <= lengths[s]``, its own row ``new_row[s]``
    (not yet written).  ``q`` and the result as
    :func:`mla_paged_decode_attention`'s."""
    S, H, width = q.shape
    n, ps, _ = pool.shape
    ring = (n - 1) // S * ps
    impl = resolve_mla_paged_impl(impl, ps, width, rank, pool.dtype)
    if impl != "xla":
        return _swa_latent_attn(
            q, pool, lengths, new_row, rank=rank, window=window,
            interpret=impl == "pallas_interpret")
    rows = pool[1:].reshape(S, ring, width)
    ok, own = _ring_valid(jnp.arange(ring, dtype=jnp.int32)[None, :],
                          lengths.astype(jnp.int32)[:, None], ring, window)
    rows = jnp.where(own[:, :, None],
                     new_row.astype(rows.dtype)[:, None, :], rows)
    rows = jnp.where(ok[:, :, None], rows, jnp.zeros_like(rows))
    s = jnp.einsum("shw,smw->shm", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("shm,smc->shc", p.astype(rows.dtype), rows[..., :rank],
                      preferred_element_type=jnp.float32).astype(pool.dtype)


def kth_largest_mask(scores, allowed, k: int):
    """``allowed`` and among the ``k`` largest allowed ``scores`` of each
    row (last axis): the exact selection as a mask, for many queries at
    once.  The ``k``-th largest value is found by bisection on the bits
    of an order-preserving integer key, 32 counting passes and no sort;
    a row with no more than ``k`` allowed entries keeps them all.  Of
    entries tied at the ``k``-th value the earliest are kept, as
    ``lax.top_k`` keeps them (the relu makes exact zeros)."""
    bits = jax.lax.bitcast_convert_type(
        scores.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)
    key = jnp.where(allowed, key, jnp.uint32(0))

    def body(b, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32)))
        enough = (key >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    thr = jax.lax.fori_loop(
        0, 32, body, jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = jnp.logical_and(allowed, key > thr[..., None])
    tied = jnp.logical_and(allowed, key == thr[..., None])
    room = k - above.sum(-1, dtype=jnp.int32)
    return jnp.logical_or(above, jnp.logical_and(
        tied, jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room[..., None]))


# -- expanded MLA over a prefill chunk ---------------------------------------
#
# A chunk's queries (b, T, H, .) over the latent rows ``[c | k_r | 0]`` of
# their sequence: K and V of a key block are rebuilt from the block's
# latents with W_UK / W_UV, scored, masked, and folded into an
# online-softmax carry.  Written in plain XLA (the loops the model files
# keep: ``xing4.mla_expanded_attention``, ``dots3.expanded_attention``)
# every (heads, T, block) score tile crosses HBM several times an
# iteration; the kernel keeps it in VMEM.  One algorithm, sized by what
# the call observes (heads 20-128, nope 128 / 192, value 128 / 256, rank
# 512 / 1,024), with the mask the layer type supplies: position bounds
# computed in the kernel, and optionally an explicit selection streamed as
# int8 tiles beside the keys.

#: rows of a query tile and of a key block at most; the key block is the
#: kernel's own tile, from row 0 of the keys whatever the chunk, so a
#: chunked prompt's rows are a whole-prompt run's
_CHUNK_Q_TILE = 512
_CHUNK_KV_BLOCK = 512

_chunk_impl_log: Optional[list] = None


class chunk_attention_log:
    """``with chunk_attention_log() as impls``: what every
    :func:`mla_chunk_attention` call traced inside resolved to
    (``"xla"`` / ``"pallas"`` / ``"pallas_interpret"``), in call order:
    how an engine learns, while one of its prefill programs is traced,
    whether that program's attention is the kernel.  (A class, not
    ``contextlib``: an import line at the top of this file would move
    every kernel above by a line, and Mosaic's payload carries source
    lines — every program holding one would miss the compile cache.)"""

    def __enter__(self) -> list:
        global _chunk_impl_log
        self._outer, _chunk_impl_log = _chunk_impl_log, []
        return _chunk_impl_log

    def __exit__(self, *exc) -> None:
        global _chunk_impl_log
        _chunk_impl_log = self._outer


def mla_chunk_constraints(
    q_tokens: int, nope_dim: int, rope_dim: int, v_dim: int, rank: int,
    row_width: int, dtype: Any = jnp.float32,
) -> list:
    """Tiling rules for the COMPILED chunk kernel, in the manner of
    :func:`mla_kernel_constraints` (empty = eligible; ``auto`` takes the
    XLA loop otherwise, an explicit ``"pallas"`` raises).  The number of
    keys, heads and sequences is free: a last key block may be ragged."""
    sublane = _sublane_rows(dtype)
    out = []
    if q_tokens % sublane:
        out.append(
            f"q_tokens {q_tokens} is not a multiple of the {sublane}-row "
            f"sublane tile of a {jnp.dtype(dtype).name} query tile")
    if rank % 128 or not 0 < rank + rope_dim <= row_width:
        out.append(
            f"latent rank {rank} is not a positive multiple of the "
            f"128-lane tile with its {rope_dim} rotary values inside the "
            f"{row_width}-wide row")
    for name, dim in (("nope", nope_dim), ("rope", rope_dim)):
        if dim % 64:
            out.append(
                f"{name} head dim {dim} is not a multiple of half a "
                "128-lane tile (a contraction the MXU pads)")
    if v_dim % 128:
        out.append(
            f"value head dim {v_dim} is not a multiple of the 128-lane "
            "tile of the accumulator and the output")
    return out


def resolve_mla_chunk_impl(
    impl: Optional[str], q_tokens: int, nope_dim: int, rope_dim: int,
    v_dim: int, rank: int, row_width: int, dtype: Any,
) -> str:
    """What :func:`mla_chunk_attention` runs at this shape
    (:func:`resolve_attention_impl`'s rule; interpret mode has no
    tiling)."""
    return resolve_attention_impl(
        impl,
        lambda i: i == "pallas_interpret" or not mla_chunk_constraints(
            q_tokens, nope_dim, rope_dim, v_dim, rank, row_width, dtype),
    )


def _chunk_heads(n_heads: int) -> int:
    """Heads a grid step computes: enough that a step's matmuls hide its
    fixed cost and the key block's DMA, few enough that the per-head
    operands and carries stay a few MB of VMEM."""
    return next(g for g in (4, 2, 1) if n_heads % g == 0)


def _mla_chunk_kernel(
    pos_ref, qn_ref, qr_ref, wk_ref, wv_ref, rows_ref, *refs,
    rank, rope_dim, window, n_keys, has_mask,
):
    """One (sequence, head group, query tile, key block).

    ``pos_ref`` = [position of query row 0, position of key row 0];
    ``qn_ref`` / ``qr_ref`` (1, G, tq, dn / dr), scaled; ``wk_ref`` /
    ``wv_ref`` (G, rank, dn / dv); ``rows_ref`` (1, kb, width); with
    ``has_mask`` an int8 ``mask_ref`` (1, tq, kb) next; then ``o_ref``
    (1, G, tq, dv) and the float32 carry ``acc`` (G, tq, dv), ``m``,
    ``l`` (G, tq, 1).  A query at ``q_pos`` sees the key at ``k_pos``
    iff ``k_pos <= q_pos`` (and, with ``window``, ``k_pos > q_pos -
    window`` and ``k_pos >= 0``: a ring's rows from before position 0
    are nobody's) and the selection allows it.  bf16 (the rows' dtype)
    into the MXU, float32 out of it; scores, mask, max, sum and the
    probabilities never leave VMEM."""
    if has_mask:
        mask_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    G, tq = qn_ref.shape[1], qn_ref.shape[2]
    kb = rows_ref.shape[1]
    i, j = pl.program_id(2), pl.program_id(3)
    q0 = pos_ref[0] + i * tq
    k0 = pos_ref[1] + j * kb

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a block wholly after the tile's last query, or wholly before the
    # window of its first, leaves the carry as it is: not computed
    seen = k0 <= q0 + (tq - 1)
    if window is not None:
        seen = jnp.logical_and(seen, k0 + (kb - 1) > q0 - window)

    @pl.when(seen)
    def _attend():
        blk = rows_ref[0]                                  # (kb, width)
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1)
        k_pos = k0 + col
        ok = k_pos <= q_pos
        if window is not None:
            ok = jnp.logical_and(ok, jnp.logical_and(
                k_pos > q_pos - window, k_pos >= 0))
        if n_keys % kb:       # the last block is ragged: no row past it
            ok = jnp.logical_and(ok, j * kb + col < n_keys)
            row = j * kb + jax.lax.broadcasted_iota(jnp.int32, (kb, 1), 0)
            blk = jnp.where(row < n_keys, blk, jnp.zeros_like(blk))
        if has_mask:
            ok = jnp.logical_and(ok, mask_ref[0].astype(jnp.int32) != 0)
        c, k_r = blk[:, :rank], blk[:, rank:rank + rope_dim]
        nt = (((1,), (1,)), ((), ()))
        for h in range(G):
            k_n = jnp.dot(c, wk_ref[h], preferred_element_type=jnp.float32
                          ).astype(blk.dtype)              # (kb, dn)
            v = jnp.dot(c, wv_ref[h], preferred_element_type=jnp.float32
                        ).astype(blk.dtype)                # (kb, dv)
            s = (jax.lax.dot_general(
                    qn_ref[0, h], k_n, nt,
                    preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(
                    qr_ref[0, h], k_r, nt,
                    preferred_element_type=jnp.float32))   # (tq, kb)
            s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_ref[h]                              # (tq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row that may see nothing here keeps its carry
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("rank", "window", "q_tile", "kv_block", "interpret"))
def _mla_chunk_flash(qn, qr, w_uk, w_uv, rows, pos0, key_pos0, mask, *,
                     rank, window, q_tile, kv_block, interpret):
    """Expanded MLA of scaled queries ``qn`` (b, T, H, dn) / ``qr`` (b,
    T, H, dr) at positions ``pos0 + t`` over ``rows`` (b, M, width) at
    positions ``key_pos0 + m``; ``w_uk`` (rank, H, dn), ``w_uv`` (rank,
    H, dv); ``mask`` None or (b, T, M) bool.  Only the key blocks up to
    the last query's are walked (the grid's last axis is data), and of
    those a query tile computes the ones its rows can see.  Returns (b,
    T, H, dv) in the rows' dtype."""
    b, T, H, dn = qn.shape
    dr, dv = qr.shape[-1], w_uv.shape[-1]
    M, width = rows.shape[1], rows.shape[2]
    dt = rows.dtype
    tq, kb, G = min(q_tile, T), min(kv_block, M), _chunk_heads(H)
    nq, nk = -(-T // tq), -(-M // kb)
    pos = jnp.stack([jnp.asarray(pos0, jnp.int32),
                     jnp.asarray(key_pos0, jnp.int32)])
    live = jnp.clip((pos[0] + (T - 1) - pos[1]) // kb + 1, 1, nk)

    def block_of(i, j, pos):
        """The key block step ``(i, j)`` holds: ``j`` held inside what
        tile ``i`` computes, so a step that computes nothing fetches
        nothing new."""
        first = pos[0] + i * tq - pos[1]       # tile's first query, as a row
        hi = (first + (tq - 1)) // kb
        lo = 0 if window is None else jnp.maximum(first - window + 1, 0) // kb
        return jnp.clip(j, lo, jnp.minimum(hi, nk - 1))

    heads = lambda d: pl.BlockSpec(
        (1, G, tq, d), lambda s, g, i, j, pos: (s, g, i, 0))
    weight = lambda d: pl.BlockSpec(
        (G, rank, d), lambda s, g, i, j, pos: (g, 0, 0))
    in_specs = [
        heads(dn), heads(dr), weight(dn), weight(dv),
        pl.BlockSpec((1, kb, width),
                     lambda s, g, i, j, pos: (s, block_of(i, j, pos), 0)),
    ]
    args = [qn.astype(dt).transpose(0, 2, 1, 3),
            qr.astype(dt).transpose(0, 2, 1, 3),
            w_uk.astype(dt).transpose(1, 0, 2),
            w_uv.astype(dt).transpose(1, 0, 2), rows]
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, tq, kb),
            lambda s, g, i, j, pos: (s, i, block_of(i, j, pos))))
        args.append(jnp.broadcast_to(mask, (b, T, M)).astype(jnp.int8))
    out = pl.pallas_call(
        functools.partial(
            _mla_chunk_kernel, rank=rank, rope_dim=dr, window=window,
            n_keys=M, has_mask=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H // G, nq, live),
            in_specs=in_specs,
            out_specs=heads(dv),
            scratch_shapes=[
                pltpu.VMEM((G, tq, dv), jnp.float32),
                pltpu.VMEM((G, tq, 1), jnp.float32),
                pltpu.VMEM((G, tq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, T, dv), dt),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_mla_chunk_flash",
    )(pos, *args)
    return out.transpose(0, 2, 1, 3)


def mla_chunk_attention(
    q_nope, q_rope, w_uk, w_uv, rows, pos0, *, scale: float, rank: int,
    xla_loop, window: Optional[int] = None,
    keys_before: Optional[int] = None, mask=None,
    impl: Optional[str] = None,
):
    """Expanded MLA of a prefill chunk: the one entry every latent
    family's prefill goes through.

    ``q_nope`` (b, T, H, dn) and rotated ``q_rope`` (b, T, H, dr) sit at
    positions ``pos0 + t`` (``pos0`` may be traced); ``rows`` (b, M,
    width) hold ``[c | k_r | 0]``, the chunk's own among them: a cache
    from position 0, or with ``keys_before`` that many rows ahead of the
    chunk and then the chunk's (what a ring held, read out in order);
    ``w_uk`` (rank, H, dn) and ``w_uv`` (rank, H, dv) rebuild K and V.
    The mask is the layer's: causal, or with ``window`` the last
    ``window`` positions and nothing before position 0, and under
    ``mask`` (b or 1, T, M) bool only what it allows.
    ``impl`` as :func:`mla_paged_decode_attention`: the kernel, the
    kernel interpreted, or — off the TPU, or for a shape
    :func:`mla_chunk_constraints` refuses — ``xla_loop()``, the caller's
    own plain-XLA loop of the same arithmetic (kept in the model files,
    whose lowered text is pinned).  Returns (b, T, H, dv)."""
    T, dn = q_nope.shape[1], q_nope.shape[3]
    impl = resolve_mla_chunk_impl(
        impl, T, dn, q_rope.shape[3], w_uv.shape[2], rank, rows.shape[2],
        rows.dtype)
    if _chunk_impl_log is not None:
        _chunk_impl_log.append(impl)
    if impl == "xla":
        return xla_loop()
    qn = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
    qr = (q_rope.astype(jnp.float32) * scale).astype(q_rope.dtype)
    key_pos0 = 0 if keys_before is None else pos0 - keys_before
    return _mla_chunk_flash(
        qn, qr, w_uk, w_uv, rows, pos0, key_pos0, mask, rank=rank,
        window=window, q_tile=_CHUNK_Q_TILE, kv_block=_CHUNK_KV_BLOCK,
        interpret=impl == "pallas_interpret").astype(q_nope.dtype)


def gqa_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Grouped-query attention: q (B, Hq, T, hd), k/v (B, Hkv, T, hd) with
    Hq a multiple of Hkv.  KV heads are broadcast across their query group
    (an O(T·d) repeat — negligible next to the O(T^2) attention savings)."""
    Hq, Hkv = q.shape[1], k.shape[1]
    if Hq != Hkv:
        group = Hq // Hkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    return mha(q, k, v, causal=causal, sm_scale=sm_scale, impl=impl)

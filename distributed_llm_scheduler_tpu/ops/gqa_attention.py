"""Grouped-query attention over cached K / V rows where the paged decode
kernel (:func:`.attention.paged_decode_attention`) does not reach: a
single token over a **ring** a slot owns (window layers of the ``kv``
kind), and a prefill **chunk** over the rows of its sequence.

Kept apart from :mod:`.attention` on purpose: Mosaic's payload carries
source lines, so a line added above a kernel there recompiles every
program that holds one (PERF.md section 6, PR 39).  What is shared is
imported: the ring's validity rule, the chunk tiles' sizes, the impl
dispatch and the log an engine reads while it traces a prefill program.

The kernels tell query heads apart as :func:`.attention._paged_flash`
does where K / V rows lie head beside head on the lanes (the ring), and
by a grid axis over the KV heads for a chunk: a block of the family's
dense cache, which keeps heads ahead of positions — or, for a layer
whose rows stay in their pages, a KV head's lane tile of each page,
through the page table (the last section).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _att
from .attention import (
    _CHUNK_KV_BLOCK,
    _CHUNK_Q_TILE,
    _NEG_INF,
    _ring_valid,
    _sublane_rows,
    resolve_attention_impl,
)

# -- one token over a ring of K / V rows -------------------------------------


def kv_window_constraints(page_size: int, head_dim: int, n_kv_heads: int,
                          dtype: Any = jnp.float32) -> list:
    """Tiling rules for the COMPILED ring kernel (empty = eligible), in
    the manner of :func:`.attention.paged_kernel_constraints`."""
    sublane = _sublane_rows(dtype)
    out = []
    if page_size % sublane:
        out.append(f"page_size {page_size} is not a multiple of the "
                   f"{sublane}-row sublane tile for {jnp.dtype(dtype).name}")
    if (n_kv_heads * head_dim) % 128:
        out.append(f"a row of {n_kv_heads} x {head_dim} values is not whole "
                   "128-lane tiles")
    return out


def _swa_kv_kernel(len_ref, q_ref, kn_ref, vn_ref, *refs, page_size,
                   ring_pages, window, head_dim):
    """One slot: its whole ring at once.  ``refs`` = ``ring_pages`` K page
    refs, as many V page refs (each one physical page where it lies, a
    row's KV heads side by side on the lanes) and the output.  The query
    is spread into one masked row a head as :func:`.attention.
    _paged_kernel` spreads it (``q_ref`` (1, groups, row_width)), the
    step's own row substituted at ``L mod ring``, and a row seen iff its
    position lies in ``(L - window, L]`` (:func:`.attention._ring_valid`:
    exact past any number of wraps).  A slot at length 0 sees its own
    row alone; its page refs hold the trash page and are never read."""
    k_refs, v_refs, o_ref = (refs[:ring_pages], refs[ring_pages:-1],
                             refs[-1])
    L = len_ref[pl.program_id(0)]
    ring = ring_pages * page_size
    groups, width = q_ref.shape[1], q_ref.shape[2]
    heads_p = -(-(width // head_dim) // 8) * 8
    own = ((jax.lax.broadcasted_iota(jnp.int32, (heads_p, width), 1)
            // head_dim)
           == jax.lax.broadcasted_iota(jnp.int32, (heads_p, width), 0))
    k = jnp.concatenate([r[0] for r in k_refs], axis=0)      # (ring, width)
    v = jnp.concatenate([r[0] for r in v_refs], axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, (ring, 1), 0)
    ok_row, own_row = _ring_valid(row, L, ring, window)
    k = jnp.where(own_row, kn_ref[0], k)
    v = jnp.where(ok_row, jnp.where(own_row, vn_ref[0], v),
                  jnp.zeros_like(v))
    qm = jnp.concatenate([
        jnp.where(own, q_ref[0, g:g + 1].astype(jnp.float32), 0.0)
        for g in range(groups)], axis=0).astype(k.dtype)
    s = jax.lax.dot_general(
        qm, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (groups * heads_p, ring)
    ok, _ = _ring_valid(
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), L, ring, window)
    s = jnp.where(ok, s, _NEG_INF)
    p = jnp.where(ok, jnp.exp(s - s.max(axis=1, keepdims=True)), 0.0)
    acc = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / p.sum(axis=1, keepdims=True)
    for g in range(groups):
        o_ref[0, g:g + 1] = jnp.where(
            own, acc[g * heads_p:(g + 1) * heads_p], 0.0
        ).sum(axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "window", "interpret"))
def _swa_kv_attn(q, k_pool, v_pool, lengths, k_new, v_new, *, sm_scale,
                 window, interpret):
    S, Hq, hd = q.shape
    n, ps, W = k_pool.shape
    rp = (n - 1) // S
    Hkv = W // hd
    G = Hq // Hkv
    dtype = k_pool.dtype
    if not interpret:
        k_pool = pltpu.with_memory_space_constraint(k_pool, pltpu.HBM)
        v_pool = pltpu.with_memory_space_constraint(v_pool, pltpu.HBM)
    # query head h * G + g -> group g, lanes of KV head h: (S, G, W)
    qg = (q.astype(jnp.float32) * sm_scale).astype(dtype).reshape(
        S, Hkv, G, hd).transpose(0, 2, 1, 3).reshape(S, G, W)

    def page(i):   # a slot that decodes nothing fetches the trash page
        return pl.BlockSpec(
            (1, ps, W),
            lambda s, ln: (jnp.where(ln[s] > 0, 1 + s * rp + i, 0), 0, 0))

    slot = lambda rows: pl.BlockSpec((1, rows, W), lambda s, ln: (s, 0, 0))
    pages = [page(i) for i in range(rp)]
    out = pl.pallas_call(
        functools.partial(_swa_kv_kernel, page_size=ps, ring_pages=rp,
                          window=window, head_dim=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[slot(G), slot(1), slot(1)] + pages + pages,
            out_specs=slot(G)),
        out_shape=jax.ShapeDtypeStruct((S, G, W), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_swa_kv_attn",
    )(lengths.astype(jnp.int32), qg, k_new.reshape(S, 1, W).astype(dtype),
      v_new.reshape(S, 1, W).astype(dtype), *([k_pool] * rp),
      *([v_pool] * rp))
    return out.reshape(S, G, Hkv, hd).transpose(0, 2, 1, 3).reshape(S, Hq, hd)


def kv_window_attention(q, k_pool, v_pool, lengths, k_new, v_new, *,
                        window: int, sm_scale: float,
                        impl: Optional[str] = None):
    """Single-token grouped-query attention over RING pools
    (:class:`...models.kv_pages.CacheSpec`, ring layers of the ``kv``
    kind): ``k_pool`` / ``v_pool`` (1 + S * ring_pages, page_size, Hkv *
    hd), slot ``s`` owning pages ``1 + s * ring_pages + j`` and position
    ``p`` lying in ring row ``p mod (ring_pages * page_size)``.  ``q``
    (S, Hq, hd) at position ``lengths[s]`` attends positions ``lengths[s]
    - window < p <= lengths[s]``, its own rows ``k_new`` / ``v_new`` (S,
    Hkv, hd) — not yet written — among them; query head ``h`` reads KV
    head ``h // (Hq / Hkv)``.  Returns (S, Hq, hd).  ``impl`` as
    :func:`.attention.paged_decode_attention`'s."""
    S, Hq, hd = q.shape
    n, ps, W = k_pool.shape
    Hkv = W // hd
    impl = resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret" or not kv_window_constraints(
            ps, hd, Hkv, k_pool.dtype))
    if impl != "xla":
        return _swa_kv_attn(
            q, k_pool, v_pool, lengths, k_new, v_new,
            sm_scale=float(sm_scale), window=window,
            interpret=impl == "pallas_interpret")
    ring = (n - 1) // S * ps
    ok, own = _ring_valid(jnp.arange(ring, dtype=jnp.int32)[None, :],
                          lengths.astype(jnp.int32)[:, None], ring, window)

    def rows(pool, new):
        r = pool[1:].reshape(S, ring, Hkv, hd)
        r = jnp.where(own[:, :, None, None],
                      new.astype(r.dtype)[:, None], r)
        return jnp.where(ok[:, :, None, None], r, jnp.zeros_like(r))

    k, v = rows(k_pool, k_new), rows(v_pool, v_new)
    qg = (q.astype(jnp.float32) * sm_scale).astype(k.dtype).reshape(
        S, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("shgd,smhd->shgm", qg, k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("shgm,smhd->shgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(q.dtype).reshape(S, Hq, hd)


# -- a prefill chunk over its sequence's K / V rows ---------------------------


def gqa_chunk_constraints(head_dim: int) -> list:
    """Tiling rules for the COMPILED chunk kernel (empty = eligible).
    Keys, heads, sequences and query rows are free: the entry pads the
    query rows to the sublane tile itself."""
    if head_dim % 128:
        return [f"head_dim {head_dim} is not a multiple of the 128-lane "
                "tile of the accumulator and the output"]
    return []


def _gqa_chunk_kernel(pos_ref, *refs, window, n_keys, block_pages=0):
    """One (sequence, KV head, query tile, key block).  ``refs`` = the
    query ``q_ref`` (1, 1, G, tq, hd), the group's query heads, scaled;
    the block's K and its V; the output and the scratch.  K and V are one
    ref each, (1, 1, kb, hd) of a dense cache — or, with ``block_pages``,
    that many page refs each behind the page table (a scalar-prefetch
    operand only the index maps read): one head of one physical page
    where it lies in the pool, (1, page_size, hd), stacked into the
    block here.  ``pos_ref`` = [position of query row 0, of key row 0].
    The mask is :func:`.attention._mla_chunk_kernel`'s: causal, and with
    ``window`` the last ``window`` positions and nothing before position
    0; a block no row of the tile can see is not computed.  Scores, mask
    and probabilities never leave VMEM."""
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]
    if block_pages:
        q_ref, pages = refs[1], refs[2:-4]
        k_refs, v_refs = pages[:block_pages], pages[block_pages:]
        kb = block_pages * k_refs[0].shape[1]
        rows = lambda rs: jnp.concatenate([r[0] for r in rs], axis=0)
    else:
        q_ref, k_refs, v_refs = refs[:3]
        kb = k_refs.shape[2]
        rows = lambda r: r[0, 0]
    G, tq = q_ref.shape[2], q_ref.shape[3]
    i, j = pl.program_id(2), pl.program_id(3)
    q0 = pos_ref[0] + i * tq
    k0 = pos_ref[1] + j * kb

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seen = k0 <= q0 + (tq - 1)
    if window is not None:
        seen = jnp.logical_and(seen, k0 + (kb - 1) > q0 - window)

    @pl.when(seen)
    def _attend():
        k, v = rows(k_refs), rows(v_refs)
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
            v = jnp.where(row < n_keys, v, jnp.zeros_like(v))
        for h in range(G):
            s = jax.lax.dot_general(
                q_ref[0, 0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (tq, kb)
            s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "q_tile", "kv_block", "interpret"))
def _gqa_chunk_flash(q, k, v, pos0, key_pos0, *, window, q_tile, kv_block,
                     interpret):
    """Scaled queries ``q`` (b, Hkv, G, T, hd) at positions ``pos0 + t``
    over ``k`` / ``v`` (b, Hkv, M, hd) at positions ``key_pos0 + m``.
    :func:`.attention._mla_chunk_flash`'s walk: only the key blocks up to
    the last query's (the grid's last axis is data), and of those a query
    tile fetches the ones its rows can see.  Returns (b, Hkv, G, T, hd)."""
    b, Hkv, G, T, hd = q.shape
    M = k.shape[2]
    tq, kb = min(q_tile, T), min(kv_block, M)
    nq, nk = -(-T // tq), -(-M // kb)
    pos = jnp.stack([jnp.asarray(pos0, jnp.int32),
                     jnp.asarray(key_pos0, jnp.int32)])
    live = jnp.clip((pos[0] + (T - 1) - pos[1]) // kb + 1, 1, nk)

    def block_of(i, j, pos):
        first = pos[0] + i * tq - pos[1]       # tile's first query, as a row
        hi = (first + (tq - 1)) // kb
        lo = 0 if window is None else jnp.maximum(first - window + 1, 0) // kb
        return jnp.clip(j, lo, jnp.minimum(hi, nk - 1))

    heads = pl.BlockSpec((1, 1, G, tq, hd),
                         lambda s, h, i, j, pos: (s, h, 0, i, 0))
    keys = pl.BlockSpec((1, 1, kb, hd),
                        lambda s, h, i, j, pos: (s, h, block_of(i, j, pos), 0))
    return pl.pallas_call(
        functools.partial(_gqa_chunk_kernel, window=window, n_keys=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, Hkv, nq, live),
            in_specs=[heads, keys, keys], out_specs=heads,
            scratch_shapes=[pltpu.VMEM((G, tq, hd), jnp.float32),
                            pltpu.VMEM((G, tq, 1), jnp.float32),
                            pltpu.VMEM((G, tq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, k.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_gqa_chunk_flash",
    )(pos, q.astype(k.dtype), k, v)


def gqa_chunk_attention(q, k, v, pos0, *, scale: float, xla_loop,
                        window: Optional[int] = None,
                        keys_before: Optional[int] = None,
                        impl: Optional[str] = None):
    """Grouped-query attention of a prefill chunk: ``q`` (b, T, Hq, hd)
    at positions ``pos0 + t`` (``pos0`` may be traced) over ``k`` / ``v``
    (b, Hkv, M, hd) — the family's dense cache from position 0, the
    chunk's own rows in it, or with ``keys_before`` that many rows ahead
    of the chunk and then the chunk's (what a ring held, in order).
    Causal; with ``window`` the last ``window`` positions and nothing
    before position 0.  ``impl`` as :func:`.attention.
    mla_chunk_attention`'s — the kernel, interpreted, or ``xla_loop()``,
    the caller's plain-XLA loop — and logged to :class:`.attention.
    chunk_attention_log` like it.  Returns (b, T, Hq, hd)."""
    impl = resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret" or not gqa_chunk_constraints(
            q.shape[3]))
    if _att._chunk_impl_log is not None:
        _att._chunk_impl_log.append(impl)
    if impl == "xla":
        return xla_loop()
    out = _gqa_chunk_flash(
        _grouped(q, scale, k.shape[1], k.dtype), k, v, pos0,
        0 if keys_before is None else pos0 - keys_before,
        window=window, q_tile=_CHUNK_Q_TILE, kv_block=_CHUNK_KV_BLOCK,
        interpret=impl == "pallas_interpret")
    return _ungrouped(out, q)


def _grouped(q, scale: float, n_kv_heads: int, dtype: Any):
    """Queries (b, T, Hq, hd) scaled and laid out for the chunk kernels,
    (b, Hkv, G, T', hd): ``T`` padded to ``dtype``'s sublane tile — rows
    past the chunk's are computed, then dropped (:func:`_ungrouped`)."""
    b, T, Hq, hd = q.shape
    pad = -T % _sublane_rows(dtype)
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(
        b, T, n_kv_heads, Hq // n_kv_heads, hd).transpose(0, 2, 3, 1, 4)
    return jnp.pad(qg, ((0, 0),) * 3 + ((0, pad), (0, 0))) if pad else qg


def _ungrouped(out, q):
    """A chunk kernel's (b, Hkv, G, T', hd) as ``q``'s (b, T, Hq, hd)."""
    return out[:, :, :, :q.shape[1]].transpose(0, 3, 1, 2, 4).reshape(
        q.shape).astype(q.dtype)


# -- a prefill chunk over K / V rows that stay in their pages ------------------


def gqa_paged_chunk_constraints(page_size: int, head_dim: int,
                                dtype: Any = jnp.float32) -> list:
    """Tiling rules for the COMPILED paged chunk kernel (empty =
    eligible): a page ref is one KV head of one page, ``(page_size,
    head_dim)`` cut out of the pool's row on a lane-tile boundary."""
    out = gqa_chunk_constraints(head_dim)
    sublane = _sublane_rows(dtype)
    if page_size % sublane:
        out.append(f"page_size {page_size} is not a multiple of the "
                   f"{sublane}-row sublane tile for {jnp.dtype(dtype).name}")
    return out


def gqa_paged_chunk_impl(impl: Optional[str], page_size: int, head_dim: int,
                         dtype: Any) -> str:
    """What :func:`gqa_paged_chunk_attention` runs at this geometry on
    this backend, asked from the host (:func:`.attention.
    resolve_attention_impl`'s rule).  ``"xla"``: it does not — there is
    no gather form of it, the caller keeps its dense cache."""
    return resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret"
        or not gqa_paged_chunk_constraints(page_size, head_dim, dtype))


@functools.partial(
    jax.jit, static_argnames=("q_tile", "block_pages", "interpret"))
def _gqa_chunk_flash_paged(q, k_pool, v_pool, pages, pos0, *, q_tile,
                           block_pages, interpret):
    """:func:`_gqa_chunk_flash` over pools in their stored form
    ``(n_pages, page_size, Hkv * hd)``: key block ``j`` of sequence ``s``
    is the ``block_pages`` pages ``pages[s, j * block_pages + i]``, each
    fetched where it lies — KV head ``h`` is lanes ``[h * hd, (h + 1) *
    hd)`` of the row, a whole lane tile, so a page ref is a block of the
    argument itself and nothing is gathered or turned in HBM.  Positions
    from 0, causal, no window.  A page past the table's end (a capacity
    that is no whole block) repeats the last one; its rows lie past every
    real query and are masked."""
    b, Hkv, G, T, hd = q.shape
    ps, ppseq = k_pool.shape[1], pages.shape[1]
    bp = block_pages
    tq, kb = min(q_tile, T), bp * ps
    nq, nk = -(-T // tq), -(-ppseq // bp)
    pos = jnp.stack([jnp.asarray(pos0, jnp.int32), jnp.zeros((), jnp.int32)])
    live = jnp.clip((pos[0] + (T - 1)) // kb + 1, 1, nk)
    if not interpret:
        k_pool = pltpu.with_memory_space_constraint(k_pool, pltpu.HBM)
        v_pool = pltpu.with_memory_space_constraint(v_pool, pltpu.HBM)

    def page(i):
        def at(s, h, t, j, pos, table):
            block = jnp.minimum(j, (pos[0] + t * tq + (tq - 1)) // kb)
            return (table[s * ppseq + jnp.minimum(block * bp + i, ppseq - 1)],
                    0, h)

        return pl.BlockSpec((1, ps, hd), at)

    heads = pl.BlockSpec((1, 1, G, tq, hd),
                         lambda s, h, t, j, pos, table: (s, h, 0, t, 0))
    page_refs = [page(i) for i in range(bp)]
    return pl.pallas_call(
        functools.partial(_gqa_chunk_kernel, window=None, n_keys=ppseq * ps,
                          block_pages=bp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, Hkv, nq, live),
            in_specs=[heads] + page_refs + page_refs, out_specs=heads,
            scratch_shapes=[pltpu.VMEM((G, tq, hd), jnp.float32),
                            pltpu.VMEM((G, tq, 1), jnp.float32),
                            pltpu.VMEM((G, tq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, k_pool.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_gqa_chunk_flash_paged",
    )(pos, pages.astype(jnp.int32).reshape(-1), q.astype(k_pool.dtype),
      *([k_pool] * bp), *([v_pool] * bp))


def gqa_paged_chunk_attention(q, k_pool, v_pool, pages, pos0, *,
                              scale: float, impl: Optional[str] = None):
    """:func:`gqa_chunk_attention` for a layer whose K and V stay in
    their pages: ``q`` (b, T, Hq, hd) at positions ``pos0 + t`` over the
    rows ``k_pool`` / ``v_pool`` (n_pages, page_size, Hkv * hd) hold for
    each sequence behind its table row ``pages`` (b, pages_per_seq) —
    position ``p`` in row ``p mod page_size`` of page ``pages[s, p //
    page_size]``, the chunk's own rows already written
    (:func:`...models.kv_pages.write_chunk_pages`).  Causal, every live
    key, the dense kernel's tiles and float32 carry: a key block is the
    pages that make ``_CHUNK_KV_BLOCK`` rows.  ``impl`` must resolve to
    the kernel (:func:`gqa_paged_chunk_impl`: the caller asked first);
    logged to :class:`.attention.chunk_attention_log`.  Returns (b, T,
    Hq, hd)."""
    ps, hd = k_pool.shape[1], q.shape[3]
    impl = gqa_paged_chunk_impl(impl, ps, hd, k_pool.dtype)
    if impl == "xla":
        raise ValueError("the paged chunk attention has no gather form: "
                         "ask gqa_paged_chunk_impl before leaving a cache "
                         "in its pages")
    if _att._chunk_impl_log is not None:
        _att._chunk_impl_log.append(impl)
    out = _gqa_chunk_flash_paged(
        _grouped(q, scale, k_pool.shape[2] // hd, k_pool.dtype), k_pool,
        v_pool, pages, pos0, q_tile=_CHUNK_Q_TILE,
        block_pages=max(1, min(_CHUNK_KV_BLOCK // ps, pages.shape[1])),
        interpret=impl == "pallas_interpret")
    return _ungrouped(out, q)

"""A Mamba-2 mixer's recurrence where the serving path meets it: one
token a slot over the state the slot holds (decode), and a prefill chunk
from a state in to a state out.

Per head (``P`` values, a state of ``P x N``), with ``dt > 0`` and ``A <
0``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t

``B`` / ``C`` are shared by the ``H / G`` heads of a group.  Everything
here is float32: the state is multiplied into itself for thousands of
steps.  The skip ``D x``, the gate, the grouped norm and the projections
are the model file's.

* :func:`ssm_step` / ``_ssm_step`` — a decode step.  The slots' states
  live in pools ``(1 + slots, ...)`` (row 0 the trash row, slot ``s`` row
  ``1 + s``: :class:`...models.kv_pages.CacheSpec`, state layers).  The
  kernel's grid runs over the slots that DECODE this step only — a
  scalar-prefetched list of their pool rows, the grid's length data — and
  both pools are aliased in and out, so a slot that decodes nothing, is
  mid-prefill or is empty costs no byte and keeps its bytes.  One grid
  step does a slot's depthwise convolution (shift the last ``K - 1``
  inputs, add the new one), ``silu``, ``dt``, the decay, the update and
  ``y`` over its whole state.  The state's tiles stay ``(P sublanes, N
  lanes)`` as the pool stores them, so ``dt x`` has to cross the lanes
  once — a square transpose a slot, one lane broadcast a tile — and
  nothing else does: ``y = h . C`` sums over the lanes, eight reductions
  a head on the cross-lane unit, so it is the MXU's, which has nothing
  else to do here — a group's rows against ``C`` in a float32 matmul
  that leaves ``y`` along the lanes, where the output wants it.  The
  slot's arithmetic then fits under its DMA (PERF.md section 5).
* :func:`ssd_chunk` / ``_ssd_chunk`` — a chunk's scan for one sequence in
  the chunked (state-space dual) form: blocks of ``block`` tokens, inside
  a block a masked ``(C B^T) . decay`` product, between blocks the carried
  state; exact up to rounding.  A row with ``dt = 0`` leaves the state
  as it is: that is how the caller freezes padding.

Each has its ``impl="xla"`` twin (the same mathematics in ``jax.numpy``)
and runs interpreted under ``"pallas_interpret"``.  New file, so that no
line above an existing kernel moves (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import resolve_attention_impl

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def ssm_kernel_constraints(heads: int, head_dim: int, state: int, groups: int,
                           block: Optional[int] = None) -> list:
    """Tiling rules for the COMPILED kernels (empty = eligible), in the
    manner of :func:`.attention.paged_kernel_constraints`; ``block`` (the
    chunk scan's alone) may be left out for the decode step."""
    out = []
    if state % 128:
        out.append(f"a state row of {state} values is not whole 128-lane "
                   "tiles")
    if head_dim % 8 or state % head_dim:
        out.append(f"head_dim {head_dim} is not whole sublane tiles that "
                   f"divide a state row of {state}")
    if heads % groups or (heads // groups * head_dim) % 128:
        out.append(f"a group's {heads // max(groups, 1)} heads of {head_dim} "
                   "are not whole 128-lane tiles")
    if heads * head_dim > state * state:
        out.append(f"{heads} heads of {head_dim} do not fit one {state} x "
                   f"{state} transposed tile")
    if block is not None and block % 128:
        out.append(f"a scan block of {block} tokens is not whole 128-lane "
                   "tiles")
    return out


def resolve_ssm_impl(impl: Optional[str], heads: int, head_dim: int,
                     state: int, groups: int,
                     block: Optional[int] = None) -> str:
    """``xla`` / ``pallas`` / ``pallas_interpret`` for this file's two
    kernels, by :func:`.attention.resolve_attention_impl`'s rule."""
    return resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret" or not ssm_kernel_constraints(
            heads, head_dim, state, groups, block))


# -- a decode step over the slots' states ------------------------------------


def _step_math(conv, new, w, b, dt_raw, dt_bias, a_log, d_skip, h, *, heads,
               head_dim, groups):
    """One slot, plain: ``conv`` (K-1, W) the last inputs, ``new`` (W,),
    ``h`` (H, P, N).  Returns ``(y (H, P), conv', h')``."""
    H, P, G = heads, head_dim, groups
    N = h.shape[-1]
    win = jnp.concatenate([conv, new[None]], 0).astype(jnp.float32)
    xbc = jax.nn.silu((win * w).sum(0) + b)
    x = xbc[:H * P].reshape(H, P)
    B = jnp.repeat(xbc[H * P:H * P + G * N].reshape(G, N), H // G, 0)
    C = jnp.repeat(xbc[H * P + G * N:].reshape(G, N), H // G, 0)
    dt = jax.nn.softplus(dt_raw + dt_bias)
    dA = jnp.exp(dt * -jnp.exp(a_log))
    h = h * dA[:, None, None] + (dt[:, None] * x)[:, :, None] * B[:, None, :]
    y = (h * C[:, None, :]).sum(-1) + d_skip[:, None] * x
    return y, win[1:].astype(conv.dtype), h


def _ssm_step_kernel(rows_ref, new_ref, dt_ref, w_ref, b_ref, hp_ref,
                     conv_ref, h_ref, y_ref, conv_out, h_out, *, heads,
                     head_dim, groups):
    """One decoding slot.  Channels lie ``N`` to a row: ``R`` rows hold
    the convolution's ``W = R N`` channels — first the heads' ``x``
    (``N / P`` heads a row), then a row of ``B`` a group, then of ``C``.
    ``dt_ref`` (1, N / P, H P / N) and ``hp_ref`` (3, N / P, H P / N) —
    ``dt_bias``, ``A_log``, ``D`` — hold head ``r N / P + e`` at ``[e,
    r]``, where its ``x`` lies once transposed.

    On the lanes: the channels, through the convolution and ``silu``;
    after ONE square transpose the ``x`` rows ``r`` (``p`` on the
    sublanes, as in the state's tiles), where ``dt x`` and ``D x`` are
    plain products with a head's ``dt`` / ``D`` spread down its ``P``
    sublanes; ``N`` in the update ``h dA + (dt x) (x) B`` — float32 on
    the VPU in that order, ``B`` a row, ``dt x`` one lane broadcast a
    tile and ``dA`` one a head: all the cross-lane work a head asks for;
    and ``(head, p)`` in ``y``, because the MXU sums ``h . C`` over ``N``
    — a group's ``H / G`` heads of new rows against the ``G`` rows of
    ``C``, row ``g`` kept — and ``D x`` is transposed back beside it."""
    H, P, G = heads, head_dim, groups
    N = h_ref.shape[-1]
    K1 = conv_ref.shape[1]
    per_row, x_rows, hpg = N // P, H * P // N, H // G
    new = new_ref[0]
    acc = b_ref[...] + w_ref[K1] * new.astype(jnp.float32)
    for j in range(K1):
        acc = acc + w_ref[j] * conv_ref[0, j].astype(jnp.float32)
    for j in range(K1 - 1):         # read before written: in and out alias
        conv_out[0, j] = conv_ref[0, j + 1]
    conv_out[0, K1 - 1] = new
    xbc = jax.nn.silu(acc)                                   # (R, N)
    dt = jax.nn.softplus(dt_ref[0] + hp_ref[0])              # (N / P, x_rows)
    dA = jnp.exp(dt * -jnp.exp(hp_ref[1]))

    def turned(v):                  # padded to a square tile, transposed
        return jnp.pad(v, [(0, N - n) for n in v.shape]).T

    def down(v):                    # a head's value down its P sublanes
        return jnp.concatenate([jnp.broadcast_to(v[e:e + 1], (P, x_rows))
                                for e in range(per_row)], 0)

    # the heads' x with P on the sublanes: xt[e P + p, r] = x[r N / P + e, p]
    xt = turned(xbc[:x_rows])[:, :x_rows]
    dtx, dAd = xt * down(dt), down(dA)
    skip = turned(xt * down(hp_ref[2]))[:x_rows]             # D x, lane-dense
    C = xbc[x_rows + G:x_rows + 2 * G]                       # (G, N)
    g_rows = hpg * P // N
    for g in range(G):
        Bg = xbc[x_rows + g:x_rows + g + 1]                  # (1, N)
        for hd in range(g * hpg, (g + 1) * hpg):
            r, e = hd // per_row, hd % per_row
            at = (slice(e * P, (e + 1) * P), slice(r, r + 1))
            h_out[0, hd] = h_ref[0, hd] * dAd[at] + dtx[at] * Bg
        # the group's (head, p) rows against every group's C, contracted
        # over N: row g has this group's y along the lanes
        yg = jax.lax.dot_general(
            C, h_out[0, g * hpg:(g + 1) * hpg].reshape(hpg * P, N), _NT,
            precision=_HIGHEST, preferred_element_type=jnp.float32)
        for j in range(g_rows):
            row = g * g_rows + j
            y_ref[0, row:row + 1] = (yg[g:g + 1, j * N:(j + 1) * N]
                                     + skip[row:row + 1])


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "impl"))
def _ssm_step(new, dt_raw, conv_w, conv_b, dt_bias, a_log, d_skip, conv_pool,
              ssm_pool, live, *, heads, head_dim, groups, impl):
    S, W = new.shape
    H, P, G = heads, head_dim, groups
    N, K1 = ssm_pool.shape[-1], conv_pool.shape[1]
    R = W // N
    rows = 1 + jnp.arange(S, dtype=jnp.int32)
    f32 = jnp.float32
    if impl == "xla":
        y, conv, h = jax.vmap(functools.partial(
            _step_math, heads=H, head_dim=P, groups=G),
            in_axes=(0, 0, None, None, 0, None, None, None, 0))(
            conv_pool[rows].reshape(S, K1, W), new, conv_w.T.astype(f32),
            conv_b.astype(f32), dt_raw.astype(f32), dt_bias, a_log, d_skip,
            ssm_pool[rows])
        at = jnp.where(live, rows, 0)       # the others' go to the trash row
        return (jnp.where(live[:, None, None], y, 0.0),
                conv_pool.at[at].set(conv.reshape(S, K1, R, N)),
                ssm_pool.at[at].set(h))
    n_live = live.sum(dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    # the decoding slots' pool rows first; the rest is the trash row
    live_rows = jnp.where(jnp.arange(S) < n_live, 1 + order, 0)

    def slot(i, lr):
        return (jnp.maximum(lr[i] - 1, 0), 0, 0)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, lr: (0,) * len(shape))

    lay = (N // P, H * P // N)

    def by_row(v):      # (n, H): head r N / P + e to [n, e, r]
        return v.reshape(-1, lay[1], lay[0]).swapaxes(1, 2)

    y, conv_pool, ssm_pool = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=H, head_dim=P, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.maximum(n_live, 1),),
            in_specs=[
                pl.BlockSpec((1, R, N), slot),
                pl.BlockSpec((1, *lay), slot),
                whole(K1 + 1, R, N), whole(R, N), whole(3, *lay),
                pl.BlockSpec((1, K1, R, N), lambda i, lr: (lr[i], 0, 0, 0)),
                pl.BlockSpec((1, H, P, N), lambda i, lr: (lr[i], 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, H * P // N, N), slot),
                pl.BlockSpec((1, K1, R, N), lambda i, lr: (lr[i], 0, 0, 0)),
                pl.BlockSpec((1, H, P, N), lambda i, lr: (lr[i], 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, H * P // N, N), f32),
            jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype),
            jax.ShapeDtypeStruct(ssm_pool.shape, ssm_pool.dtype),
        ],
        # operands count the prefetched list: the two pools, in place
        input_output_aliases={6: 1, 7: 2},
        interpret=impl == "pallas_interpret",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_ssm_step",
    )(live_rows, new.reshape(S, R, N), by_row(dt_raw.astype(f32)),
      conv_w.T.astype(f32).reshape(K1 + 1, R, N),
      conv_b.astype(f32).reshape(R, N),
      by_row(jnp.stack([dt_bias, a_log, d_skip]).astype(f32)), conv_pool,
      ssm_pool)
    # a slot the grid did not visit has no y: nobody reads it, but it
    # must not be whatever the buffer held
    return (jnp.where(live[:, None, None], y.reshape(S, H, P), 0.0),
            conv_pool, ssm_pool)


def ssm_step(new, dt_raw, conv_w, conv_b, dt_bias, a_log, d_skip, conv_pool,
             ssm_pool, live, *, groups: int, impl: Optional[str] = None):
    """One decode step of every LIVE slot's mixer state, in place.

    ``new`` (S, W) the step's convolution inputs ``[x | B | C]`` (``W = H
    P + 2 G N``), ``dt_raw`` (S, H); ``conv_w`` (W, K), ``conv_b`` (W,),
    ``dt_bias`` / ``a_log`` / ``d_skip`` (H,) float32; ``conv_pool`` (1 +
    S, K - 1, W / N, N) — the channels ``N`` to a row, the form the kernel
    reads a slot's block in — and ``ssm_pool`` (1 + S, H, P, N) the slots'
    states;
    ``live`` (S,) bool.  Returns ``(y (S, H, P) float32 with the skip ``D
    x`` in it — zero for a slot that is not live —, conv_pool', ssm_pool')``:
    the rows of the slots that are not live are not written."""
    _, H, P, N = ssm_pool.shape
    return _ssm_step(
        new, dt_raw, conv_w, conv_b, dt_bias, a_log, d_skip, conv_pool,
        ssm_pool, live, heads=H, head_dim=P, groups=groups,
        impl=resolve_ssm_impl(impl, H, P, N, groups))


# -- a prefill chunk's scan, state in and state out ----------------------------


def _ssd_chunk_kernel(x_ref, xt_ref, b_ref, c_ref, ac_ref, dc_ref, ar_ref,
                      dr_ref, h0_ref, y_ref, h_ref, h_scr, *, head_dim):
    """One (group, block of ``L`` tokens); the group's heads one after
    another.  ``ac`` / ``dc`` (1, L, heads): ``dt A`` and ``dt`` with time
    on the sublanes, ``ar`` / ``dr`` (1, heads, L) the same with time on
    the lanes; ``xt`` the block's ``x`` with time on the lanes."""
    P = head_dim
    c, n_c = pl.program_id(1), pl.num_programs(1)
    L = x_ref.shape[0]
    hpg = ac_ref.shape[2]

    @pl.when(c == 0)
    def _load():
        h_scr[...] = h0_ref[...]

    tri = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    ones = tri.astype(jnp.float32)
    # inclusive running sums of dt A, both ways round
    cs_c = jnp.dot(ones, ac_ref[0], precision=_HIGHEST,
                   preferred_element_type=jnp.float32)          # (L, hpg)
    cs_r = jax.lax.dot_general(ar_ref[0], ones, _NT, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    Bc, Cc = b_ref[...], c_ref[...]
    cb = jax.lax.dot_general(Cc, Bc, _NT, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (L, L)
    for j in range(hpg):
        cc, cr = cs_c[:, j:j + 1], cs_r[j:j + 1, :]
        decay = jnp.exp(jnp.where(tri, cc - cr, -jnp.inf))
        xj = x_ref[:, j * P:(j + 1) * P] * dc_ref[0][:, j:j + 1]
        hj = h_scr[j]
        y_ref[:, j * P:(j + 1) * P] = (
            jnp.dot(cb * decay, xj, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
            + jnp.exp(cc) * jax.lax.dot_general(
                Cc, hj, _NT, precision=_HIGHEST,
                preferred_element_type=jnp.float32))
        # the block's whole sum, at lane 0 (a (1, 1) taken off lane L - 1
        # cannot be broadcast both ways)
        tot = ar_ref[0][j:j + 1, :].sum(axis=1, keepdims=True)
        wr = jnp.exp(tot - cr) * dr_ref[0][j:j + 1, :]
        h_scr[j] = jnp.exp(tot) * hj + jnp.dot(
            xt_ref[j * P:(j + 1) * P, :] * wr, Bc, precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(c == n_c - 1)
    def _store():
        h_ref[...] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block", "impl"))
def _ssd_chunk(x, dt, A, B, C, h0, *, block, impl):
    T, H, P = x.shape
    G, N = B.shape[1:]
    L, hpg = block, H // G
    a = dt * A[None, :]
    if impl == "xla":
        def blk(h, xs):
            xb, ab, db, Bb, Cb = xs          # (L, H, P) (L, H) (L, G, N)
            cs = jnp.cumsum(ab, 0)
            Bh, Ch = jnp.repeat(Bb, hpg, 1), jnp.repeat(Cb, hpg, 1)
            seg = cs[:, None, :] - cs[None, :, :]               # (t, s, H)
            ok = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[..., None]
            m = jnp.einsum("thn,shn->tsh", Ch, Bh, precision=_HIGHEST
                           ) * jnp.exp(jnp.where(ok, seg, -jnp.inf))
            xd = xb * db[:, :, None]
            y = (jnp.einsum("tsh,shp->thp", m, xd, precision=_HIGHEST)
                 + jnp.exp(cs)[:, :, None] * jnp.einsum(
                     "thn,hpn->thp", Ch, h, precision=_HIGHEST))
            w = jnp.exp(cs[-1][None] - cs)
            h = jnp.exp(cs[-1])[:, None, None] * h + jnp.einsum(
                "shp,shn->hpn", xd * w[:, :, None], Bh, precision=_HIGHEST)
            return h, y

        def blocks(v):
            return v.reshape(T // L, L, *v.shape[1:])

        h, y = jax.lax.scan(blk, h0, tuple(map(blocks, (x, a, dt, B, C))))
        return y.reshape(T, H, P), h

    def cols(v):        # (T, H) -> (G, T, hpg): time on the sublanes
        return v.reshape(T, G, hpg).transpose(1, 0, 2)

    x2, a_c, dt_c = x.reshape(T, H * P), cols(a), cols(dt)
    y, h = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, head_dim=P),
        grid=(G, T // L),
        in_specs=[
            pl.BlockSpec((L, hpg * P), lambda g, c: (c, g)),
            pl.BlockSpec((hpg * P, L), lambda g, c: (g, c)),
            pl.BlockSpec((L, N), lambda g, c: (c, g)),
            pl.BlockSpec((L, N), lambda g, c: (c, g)),
            pl.BlockSpec((1, L, hpg), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, L, hpg), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, hpg, L), lambda g, c: (g, 0, c)),
            pl.BlockSpec((1, hpg, L), lambda g, c: (g, 0, c)),
            pl.BlockSpec((hpg, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, hpg * P), lambda g, c: (c, g)),
            pl.BlockSpec((hpg, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hpg, P, N), jnp.float32)],
        interpret=impl == "pallas_interpret",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="_ssd_chunk",
    )(x2, x2.T, B.reshape(T, G * N), C.reshape(T, G * N), a_c, dt_c,
      a_c.transpose(0, 2, 1), dt_c.transpose(0, 2, 1), h0)
    return y.reshape(T, H, P), h


def ssd_chunk(x, dt, A, B, C, h0, *, block: int, impl: Optional[str] = None):
    """A chunk's scan for one sequence: ``x`` (T, H, P), ``dt`` (T, H) —
    after its softplus, 0 on a row that must leave the state alone —,
    ``A`` (H,) negative, ``B`` / ``C`` (T, G, N), ``h0`` (H, P, N), all
    float32; ``T`` any length (padded here to whole blocks with ``dt =
    0``).  Returns ``(y (T, H, P) without the skip, h (H, P, N))``."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    pad = -T % block
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, B, C))
    y, h = _ssd_chunk(x, dt, A, B, C, h0, block=block,
                      impl=resolve_ssm_impl(impl, H, P, N, G, block))
    return y[:T], h

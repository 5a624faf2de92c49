"""A gated short convolution's memory where the serving path meets it:
one token a slot over the ``K - 1`` inputs the slot holds (decode), and a
prefill chunk from the carried inputs in to the carried inputs out.

Depthwise, causal, ``K`` taps, no bias and no activation::

    v_t = sum_{k < K} w[:, k] * u_{t - (K - 1) + k}

The gates around it (``u = B * z`` before, ``C * v`` after) and the
projections are the model file's.  Sums are float32; ``u`` is kept in the
state's dtype, the current one too, so a step and a chunk row see the same
rounded inputs.

* :func:`short_conv_step` / ``_short_conv_step`` — a decode step.  The
  slots' inputs live in a pool ``(1 + slots, K - 1, R, N)`` (row 0 the
  trash row, slot ``s`` row ``1 + s``: :class:`...models.kv_pages.
  CacheSpec`, state layers; the ``R N`` channels ``N`` to a row, whole
  lane tiles).  The state is 8 KB a slot where a recurrent mixer's is
  megabytes, so the kernel is ONE grid step over the whole pool, aliased
  in and out: a loop over the pool's rows, each a few tiles — a row that
  is not live, and the trash row, is written back as it was read, bit for
  bit.  Why a kernel for 1 MB: the ``jax.numpy`` form below compiles, for
  a v5e, to a segment program that copies every layer's pool into
  another layout in front of its step loop and back behind it (16
  pool-shaped copies a segment) and rewrites the whole pool a step —
  ~110 us a decode step on the chip where this kernel's 8 calls take 29
  (PERF.md, PR 50); the kernel pins the layout, leaves the pool where it
  lies, and gives the device trace a name to time
  (``conv_step_dev_us_step``).
* :func:`short_conv_chunk` — a chunk of ONE sequence in plain
  ``jax.numpy`` (``K`` shifted products, XLA's to fuse): the carried
  inputs in front of the chunk's, the inputs carried on are the last
  ``K - 1`` REAL ones (never padding), and a chunk at position 0 starts
  from zero whatever it is handed.

``impl="xla"`` is the step's twin in ``jax.numpy``; ``"pallas_interpret"``
runs the kernel interpreted.  New file, so that no line above an existing
kernel moves (ROADMAP D16).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import resolve_attention_impl

#: the whole pool is one VMEM block, twice (in and out)
_POOL_BYTES_MAX = 4 << 20


def state_shape(channels: int, taps: int):
    """The shape a slot's ``taps - 1`` carried inputs are stored in: the
    channels 128 to a row where they are whole lane tiles."""
    n = 128 if channels % 128 == 0 else channels
    return (taps - 1, channels // n, n)


def short_conv_constraints(pool_shape, dtype) -> list:
    """Rules for the COMPILED step kernel (empty = eligible)."""
    out = []
    if pool_shape[-1] % 128:
        out.append(f"{pool_shape[-1]} channels a row are no whole lane tile")
    size = jnp.dtype(dtype).itemsize * math.prod(pool_shape)
    if size > _POOL_BYTES_MAX:
        out.append(f"a pool of {size} B is no single VMEM block")
    return out


def _short_conv_step_kernel(live_ref, u_ref, w_ref, pool_ref, y_ref, pool_out):
    """Every row of the pool: ``u_ref`` (rows, R, N) the step's inputs at
    the pool's rows, ``w_ref`` (K, R, N) float32, ``live_ref`` (rows,) in
    SMEM.  Read before written: a row's old inputs are loaded first."""
    K1 = pool_ref.shape[1]

    def row(i, carry):
        old = [pool_ref[i, j] for j in range(K1)]
        new = u_ref[i]
        acc = w_ref[K1] * new.astype(jnp.float32)
        for j in range(K1):
            acc = acc + w_ref[j] * old[j].astype(jnp.float32)
        y_ref[i] = acc
        live = live_ref[i] != 0

        @pl.when(live)
        def _shift():
            for j in range(K1):
                pool_out[i, j] = old[j + 1] if j + 1 < K1 else new

        @pl.when(jnp.logical_not(live))
        def _keep():
            for j in range(K1):
                pool_out[i, j] = old[j]

        return carry

    jax.lax.fori_loop(0, pool_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("impl",))
def _short_conv_step(u, conv_w, pool, live, *, impl):
    S, h = u.shape
    rows, K1, R, N = pool.shape
    w = conv_w.T.astype(jnp.float32)                        # (K, h)
    u = u.astype(pool.dtype)
    if impl == "xla":
        st = pool[1:].reshape(S, K1, h)
        y = w[K1] * u.astype(jnp.float32) + sum(
            w[j] * st[:, j].astype(jnp.float32) for j in range(K1))
        new = jnp.concatenate([st[:, 1:], u[:, None]], 1)
        new = jnp.where(live[:, None, None], new, st)
        return y, pool.at[1:].set(new.reshape(S, K1, R, N))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, lv: (0,) * len(shape))

    y, pool = pl.pallas_call(
        _short_conv_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[whole(rows, R, N), whole(K1 + 1, R, N),
                      whole(rows, K1, R, N)],
            out_specs=[whole(rows, R, N), whole(rows, K1, R, N)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, R, N), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the prefetched list: the pool, in place
        input_output_aliases={3: 1},
        interpret=impl == "pallas_interpret",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="_short_conv_step",
    )(jnp.pad(live.astype(jnp.int32), (1, 0)),
      jnp.pad(u, ((1, 0), (0, 0))).reshape(rows, R, N),
      w.reshape(K1 + 1, R, N), pool)
    return y[1:].reshape(S, h), pool


def short_conv_step(u, conv_w, pool, live, impl: Optional[str] = None):
    """One decode step of every LIVE slot's convolution, in place.

    ``u`` (S, h) the step's gated inputs, ``conv_w`` (h, K), ``pool`` (1
    + S, K - 1, R, N) the slots' carried inputs (:func:`state_shape`),
    ``live`` (S,) bool.  Returns ``(v (S, h) float32, pool')``: slot
    ``s``'s row ``1 + s`` shifted by one input where it is live, every
    other row (the trash row too) as it was."""
    impl = resolve_attention_impl(
        impl, lambda i: i == "pallas_interpret" or not short_conv_constraints(
            pool.shape, pool.dtype))
    return _short_conv_step(u, conv_w, pool, live, impl=impl)


@jax.jit
def short_conv_chunk(u, conv_w, carried, pos0, last):
    """A chunk of ONE sequence: ``u`` (T, h) the gated inputs at positions
    ``pos0 + t`` whose last real row is ``last``, ``carried`` (K - 1, R,
    N) what the sequence holds — taken as zero where ``pos0`` is 0.
    Returns ``(v (T, h) float32, carried')``: the inputs after row
    ``last`` (the rows behind it are padding and stay out)."""
    T, h = u.shape
    K1 = carried.shape[0]
    carried = jnp.where(pos0 == 0, jnp.zeros_like(carried), carried)
    seq = jnp.concatenate(
        [carried.reshape(K1, h), u.astype(carried.dtype)], 0)  # (K1 + T, h)
    w = conv_w.astype(jnp.float32)
    v = sum(seq[j:j + T].astype(jnp.float32) * w[:, j] for j in range(K1 + 1))
    return v, jax.lax.dynamic_slice_in_dim(seq, last + 1, K1, 0).reshape(
        carried.shape)

"""Xing4.0 family (``model_type`` ``xing4_0``): the served block.

Three mechanisms no other model file has, each behind one named jitted
function the profiler's trace can find:

* **multi-head latent attention** (MLA, DeepSeek-V2/V3's): the cache row
  of a token is ``[c | k_r]`` — the RMS-normalised ``kv_lora_rank``
  latent and one rotated key shared by the heads — padded with zeros to
  whole 128-lane tiles (:func:`latent_row_width`: 576 -> 640 at the
  published widths, so that XLA's default device layout keeps the row on
  the lanes and the paged kernel reads the pool without a transposing
  copy).  Prefill runs the *expanded* form over a chunk (``k_nope``, ``v``
  rebuilt from the cached latents, a block of rows at a time); decode
  runs the *absorbed* form (``W_UK`` folded into the query, ``W_UV``
  applied after) through :func:`...ops.attention.
  mla_paged_decode_attention` — the same numbers up to rounding.  YaRN
  rotary frequencies as DeepSeek-V3's rotary embedding computes them.
* **sigmoid-routed experts** (``noaux_tc`` with one group: the top-k of
  ``sigmoid(x W_r) + b_corr``, gates renormalised and scaled) plus a
  shared expert; :func:`_moe_experts` is dropless — rows sorted by
  expert, one grouped matmul over the experts picked, no expert computed
  for a token that did not pick it — and a layer computes the part of
  the experts it *holds* (``held``), the shared expert counted once.
* **manifold-constrained hyper-connections** (mHC, arXiv:2512.24880):
  the residual is ``hc_mult`` streams; around every sublayer
  :func:`_hc_maps` yields ``H_pre`` (mix the streams into the sublayer's
  input), ``H_post`` (spread its output) and ``H_res`` (a doubly
  stochastic stream mixer from ``hc_sinkhorn_iters`` Sinkhorn sweeps),
  all in float32.

Parameters are flat (``wte``, ``h{i}_q_a_w`` ...), made by
:func:`init_params` or by the benchmark's reference; weights are bfloat16
when served, router, norms' statistics, softmax and the hyper-connection
maps float32.  ``num_nextn_predict_layers`` (the MTP module) is not
built here: a step yields one token (``glm4_lite`` builds one, on this
file's attention and experts).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.attention import (
    lane_width,
    mla_chunk_attention,
    mla_paged_decode_attention,
    resolve_attention_impl,
)

#: cache rows the expanded prefill attention rebuilds K/V for at a time
PREFILL_KV_BLOCK = 1024


@dataclass(frozen=True)
class Xing4Config:
    """Hyperparameters under the published config's meanings."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    n_layers: int = 40
    n_dense_layers: int = 2              # first_k_dense_replace
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_positions: int = 262144
    dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls, **kw) -> "Xing4Config":
        """Every mechanism at toy widths (CPU tests, the CLI preset)."""
        base = dict(
            vocab_size=256, hidden_size=32, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=8,
            experts_per_tok=2, rope_original_max=16, rope_factor=4.0,
            max_positions=256, dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_hf(cls, c: Dict[str, Any], **kw) -> "Xing4Config":
        """From the published ``config.json``'s keys (``model_type``
        ``xing4_0``); ``n_group`` / ``topk_group`` must be 1."""
        if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing is not built")
        rs = c["rope_scaling"]
        return cls(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            n_layers=int(c["num_hidden_layers"]),
            n_dense_layers=int(c["first_k_dense_replace"]),
            n_heads=int(c["num_attention_heads"]),
            q_lora_rank=int(c["q_lora_rank"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_head_dim=int(c["qk_nope_head_dim"]),
            qk_rope_head_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            intermediate_size=int(c["intermediate_size"]),
            moe_intermediate_size=int(c["moe_intermediate_size"]),
            n_routed_experts=int(c["n_routed_experts"]),
            n_shared_experts=int(c["n_shared_experts"]),
            experts_per_tok=int(c["num_experts_per_tok"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            hc_mult=int(c["hc_mult"]),
            hc_sinkhorn_iters=int(c["hc_sinkhorn_iters"]),
            hc_eps=float(c["hc_eps"]),
            hc_clamp=float(c["mhc_h_res_clamp_max"]),
            rms_eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_beta_fast=float(rs["beta_fast"]),
            rope_beta_slow=float(rs["beta_slow"]),
            rope_original_max=int(rs["original_max_position_embeddings"]),
            rope_mscale=float(rs["mscale"]),
            rope_mscale_all_dim=float(rs["mscale_all_dim"]),
            max_positions=int(c["max_position_embeddings"]), **kw)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-1/2 * m^2``, ``m`` YaRN's attention scale at
        ``mscale_all_dim`` (DeepSeek-V3's ``softmax_scale``)."""
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers


def latent_row_width(cfg: Xing4Config) -> int:
    """Values in one cached row: ``[c | k_r]`` padded to whole 128-lane
    tiles (what the device holds for it in any case; why it matters is
    in :class:`.kv_pages.CacheSpec`)."""
    return lane_width(cfg.kv_lora_rank + cfg.qk_rope_head_dim)


# -- parameters -----------------------------------------------------------------


def layer_param_shapes(cfg: Xing4Config, layer: int) -> Dict[str, Tuple]:
    """Local name -> (shape, dtype) of one layer's parameters.  Expert
    weights are stored ``(E, 2I, h)`` / ``(E, I, h)``: every tile the
    grouped kernel streams is a slab of whole rows."""
    h, n, H = cfg.hidden_size, cfg.hc_mult, cfg.n_heads
    dt, f32 = cfg.dtype, jnp.float32
    maps = 2 * n + n * n
    out = {}
    for hc in ("hca", "hcf"):
        out[f"{hc}_phi"] = ((maps, n * h), f32)
        out[f"{hc}_alpha"] = ((3,), f32)
        out[f"{hc}_b"] = ((maps,), f32)
    out.update({
        "attn_norm_g": ((h,), dt),
        "q_a_w": ((h, cfg.q_lora_rank), dt),
        "q_norm_g": ((cfg.q_lora_rank,), dt),
        "q_b_w": ((cfg.q_lora_rank, H * cfg.qk_head_dim), dt),
        "kv_a_w": ((h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt),
        "kv_norm_g": ((cfg.kv_lora_rank,), dt),
        "kv_b_w": ((cfg.kv_lora_rank,
                    H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt),
        "o_w": ((H * cfg.v_head_dim, h), dt),
        "ffn_norm_g": ((h,), dt),
    })
    if cfg.is_dense(layer):
        out["mlp_gu_w"] = ((h, 2 * cfg.intermediate_size), dt)
        out["mlp_down_w"] = ((cfg.intermediate_size, h), dt)
    else:
        E, I = cfg.n_routed_experts, cfg.moe_intermediate_size
        Is = I * cfg.n_shared_experts
        out["router_w"] = ((h, E), f32)
        out["router_bias"] = ((E,), f32)
        out["exp_gu_w"] = ((E, 2 * I, h), dt)
        out["exp_down_w"] = ((E, I, h), dt)
        out["shared_gu_w"] = ((h, 2 * Is), dt)
        out["shared_down_w"] = ((Is, h), dt)
    return out


def param_shapes(cfg: Xing4Config) -> Dict[str, Tuple]:
    out = {
        "wte": ((cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "head_w": ((cfg.hidden_size, cfg.vocab_size), cfg.dtype),
        "norm_f_g": ((cfg.hidden_size,), cfg.dtype),
    }
    for i in range(cfg.n_layers):
        for k, v in layer_param_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = v
    return out


def init_params(cfg: Xing4Config, key: jax.Array,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights: N(0, std) matrices, unit norm gains, the
    hyper-connection biases at the identity mixing (``B_res`` a scaled
    identity, so ``H_res`` starts near the identity permutation)."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    n = cfg.hc_mult
    out = {}
    for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dt)
        elif name.endswith("_alpha"):
            out[name] = jnp.full(shape, 0.5, dt)
        elif name.endswith(("hca_b", "hcf_b")):
            out[name] = jnp.concatenate([
                jnp.zeros((2 * n,), dt), 3.0 * jnp.eye(n, dtype=dt).ravel()])
        elif name.endswith("router_bias"):
            out[name] = (0.01 * jax.random.normal(k, shape)).astype(dt)
        else:
            out[name] = (std * jax.random.normal(k, shape)).astype(dt)
    return out


# -- small pieces -----------------------------------------------------------------


def rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: Xing4Config) -> np.ndarray:
    """Rotary frequencies with YaRN's per-dimension blend between the
    plain and the interpolated ones (DeepSeek-V3 ``YarnRotaryEmbedding``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / base ** exps, 1.0 / (cfg.rope_factor * base ** exps)

    def corr(rot):
        return dim * math.log(
            cfg.rope_original_max / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def rope(x, positions, cfg: Xing4Config):
    """Rotate the last axis (``qk_rope_head_dim``, half-split pairing) at
    ``positions`` (broadcastable to ``x``'s leading axes), in float32."""
    scale = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    ang = (positions.astype(jnp.float32)[..., None]
           * jnp.asarray(yarn_inv_freq(cfg)))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def _kernel_impl(impl: Optional[str]) -> str:
    """``xla`` / ``pallas`` / ``pallas_interpret`` for this file's own
    kernels, by :func:`resolve_attention_impl`'s rule."""
    return resolve_attention_impl(impl, lambda _i: True)


# -- hyper-connections ----------------------------------------------------------


def _sinkhorn_rows(rows, iters: int, eps: float):
    """``rows[i]`` is row ``i`` of M, shaped (n, tokens): each sweep
    divides every column by (its sum + eps), then every row."""
    for _ in range(iters):
        col = sum(rows) + eps
        rows = [r / col for r in rows]
        rows = [r / (r.sum(axis=0, keepdims=True) + eps) for r in rows]
    return rows


def _maps_of(hmap, n: int, iters: int, eps: float, clamp: float):
    """``H_pre`` (n, tokens), ``H_post`` and the rows of ``H_res`` out of
    the stacked ``H~`` (2n + n^2, tokens)."""
    rows = [jnp.exp(jnp.clip(hmap[2 * n + i * n:2 * n + (i + 1) * n],
                             -clamp, clamp)) for i in range(n)]
    return (jax.nn.sigmoid(hmap[:n]), 2.0 * jax.nn.sigmoid(hmap[n:2 * n]),
            _sinkhorn_rows(rows, iters, eps))


def _hc_kernel(x_ref, phi_ref, ab_ref, o_ref, *, n, iters, eps, clamp,
               rms_eps, kchunk):
    """One block of tokens: ``z = (x Phi) * rsqrt(mean x^2 + eps)`` (the
    flat RMSNorm has no gain, so its scale commutes with the projection),
    then the three maps, tokens on the lanes."""
    tb, nh = x_ref.shape
    maps = phi_ref.shape[0]
    z = jnp.zeros((maps, tb), jnp.float32)
    ss = jnp.zeros((8, tb), jnp.float32)
    ones = jnp.ones((8, kchunk), jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    for k0 in range(0, nh, kchunk):
        xk = x_ref[:, k0:k0 + kchunk].astype(jnp.float32)
        z = z + jax.lax.dot_general(
            phi_ref[:, k0:k0 + kchunk], xk, nt,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        ss = ss + jax.lax.dot_general(
            ones, xk * xk, nt, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    z = z * jax.lax.rsqrt(ss[0:1] / nh + rms_eps)
    hmap = ab_ref[:, 0:1] * z + ab_ref[:, 1:2]
    pre, post, rows = _maps_of(hmap, n, iters, eps, clamp)
    for i, block in enumerate([pre, post] + rows):
        o_ref[i * n:(i + 1) * n, :] = block


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "rms_eps", "impl"))
def _hc_maps(xf, phi, alpha, b, *, n, iters, eps, clamp, rms_eps, impl):
    """The three mappings of one sublayer for ``xf`` (N, n*h), the flat
    streams: returns ``(H_pre (N, n), H_post (N, n), H_res (N, n, n))``
    in float32.  ``phi`` (2n + n^2, n*h) holds Phi_pre, Phi_post, Phi_res
    transposed; ``alpha`` (3,), ``b`` (2n + n^2,)."""
    N, nh = xf.shape
    maps = 2 * n + n * n
    a_rows = jnp.repeat(alpha, np.array([n, n, n * n]),
                        total_repeat_length=maps)
    if impl == "xla":
        x32 = xf.astype(jnp.float32)
        xbar = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + rms_eps)
        z = jnp.einsum("nk,mk->mn", xbar, phi,
                       precision=jax.lax.Precision.HIGHEST)
        pre, post, rows = _maps_of(
            a_rows[:, None] * z + b[:, None], n, iters, eps, clamp)
        out = jnp.concatenate([pre, post] + rows)
    else:
        tb = N if N <= 128 else 128
        pad = -N % tb
        xp = jnp.pad(xf, ((0, pad), (0, 0))) if pad else xf
        kchunk = next(c for c in (2048, 1024, 512, 256, 128, nh)
                      if nh % c == 0)
        out = pl.pallas_call(
            functools.partial(
                _hc_kernel, n=n, iters=iters, eps=eps, clamp=clamp,
                rms_eps=rms_eps, kchunk=kchunk),
            grid=((N + pad) // tb,),
            in_specs=[pl.BlockSpec((tb, nh), lambda i: (i, 0)),
                      pl.BlockSpec((maps, nh), lambda i: (0, 0)),
                      pl.BlockSpec((maps, 2), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((maps, tb), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((maps, N + pad), jnp.float32),
            interpret=impl == "pallas_interpret",
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 << 20),
            name="_hc_maps",
        )(xp, phi, jnp.stack([a_rows, b], axis=1))[:, :N]
    out = out.T
    return out[:, :n], out[:, n:2 * n], out[:, 2 * n:].reshape(N, n, n)


def hc_maps(X, p, hc: str, cfg: Xing4Config, impl: Optional[str] = None):
    """:func:`_hc_maps` for the streams ``X`` (N, n, h) and the
    parameters of hyper-connection ``hc`` (``"hca"`` or ``"hcf"``)."""
    return _hc_maps(
        X.reshape(X.shape[0], -1), p[f"{hc}_phi"], p[f"{hc}_alpha"],
        p[f"{hc}_b"], n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters,
        eps=cfg.hc_eps, clamp=cfg.hc_clamp, rms_eps=cfg.rms_eps,
        impl=_kernel_impl(impl))


def hc_sublayer(X, p, hc: str, norm_g, fn, cfg, impl=None):
    """``X' = H_res X + H_post^T F(RMSNorm(H_pre X))`` for streams ``X``
    (N, n, h); ``fn`` maps (N, h) to ``(y (N, h), aux)``."""
    pre, post, res = hc_maps(X, p, hc, cfg, impl)
    X32 = X.astype(jnp.float32)
    u = jnp.einsum("tn,tnh->th", pre, X32).astype(X.dtype)
    y, aux = fn(rms_norm(u, norm_g, cfg.rms_eps))
    out = (jnp.einsum("tij,tjh->tih", res, X32)
           + post[:, :, None] * y.astype(jnp.float32)[:, None, :])
    return out.astype(X.dtype), aux


# -- attention ------------------------------------------------------------------


def mla_project(p, x, positions, cfg: Xing4Config):
    """Queries and the cache row of tokens ``x`` (N, h) at ``positions``
    (N,): ``q_nope`` (N, H, dn), rotated ``q_rope`` (N, H, dr) and the
    row ``[RMSNorm(c) | RoPE(k_r) | 0]`` (N, :func:`latent_row_width`)."""
    N, H = x.shape[0], cfg.n_heads
    cq = rms_norm(x @ p["q_a_w"], p["q_norm_g"], cfg.rms_eps)
    q = (cq @ p["q_b_w"]).reshape(N, H, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], positions[:, None], cfg)
    ckr = x @ p["kv_a_w"]
    c = rms_norm(ckr[:, :cfg.kv_lora_rank], p["kv_norm_g"], cfg.rms_eps)
    k_r = rope(ckr[:, cfg.kv_lora_rank:], positions, cfg)
    pad = latent_row_width(cfg) - c.shape[1] - k_r.shape[1]
    row = jnp.concatenate(
        [c, k_r, jnp.zeros((N, pad), c.dtype)], axis=-1)
    return q_nope, q_rope, row


def _kv_b_split(p, cfg: Xing4Config):
    """``W_UK`` (rank, H, dn) and ``W_UV`` (rank, H, dv) out of kv_b."""
    w = p["kv_b_w"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_expanded_attention(p, q_nope, q_rope, rows, pos0, cfg: Xing4Config,
                           impl: Optional[str] = None):
    """Expanded MLA of a chunk over its sequence's cached rows.

    ``q_*`` (b, T, H, .) sit at positions ``pos0 + t``; ``rows`` (b, cap,
    width) already hold the chunk's own rows.  Through
    :func:`...ops.attention.mla_chunk_attention`: the kernel where the
    shape takes it, else the loop below — K and V rebuilt from the
    latents :data:`PREFILL_KV_BLOCK` rows at a time and only for the
    blocks a query can see (the trip count is data), with an
    online-softmax carry in float32.  Returns (b, T, H * dv)."""
    b, T, H, dn = q_nope.shape
    cap, rank, dr = rows.shape[1], cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dv = cfg.v_head_dim
    kb = PREFILL_KV_BLOCK if cap % PREFILL_KV_BLOCK == 0 else cap
    w_uk, w_uv = _kv_b_split(p, cfg)
    scale = cfg.softmax_scale

    def xla_loop():
        qn = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
        qr = (q_rope.astype(jnp.float32) * scale).astype(q_rope.dtype)
        q_pos = pos0 + jnp.arange(T, dtype=jnp.int32)

        def body(j, carry):
            m, l, acc = carry
            blk = jax.lax.dynamic_slice_in_dim(rows, j * kb, kb, axis=1)
            c, k_r = blk[..., :rank], blk[..., rank:rank + dr]
            k_nope = jnp.einsum("bmc,chd->bmhd", c, w_uk)
            v = jnp.einsum("bmc,chd->bmhd", c, w_uv)
            s = (jnp.einsum("bthd,bmhd->bhtm", qn, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthd,bmd->bhtm", qr, k_r,
                              preferred_element_type=jnp.float32))
            k_pos = j * kb + jnp.arange(kb, dtype=jnp.int32)
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s,
                          jnp.finfo(jnp.float32).min)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new[..., None])
            l = l * alpha + pr.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhtm,bmhd->bhtd", pr.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((b, H, T), jnp.finfo(jnp.float32).min, jnp.float32),
                jnp.zeros((b, H, T), jnp.float32),
                jnp.zeros((b, H, T, dv), jnp.float32))
        live = jnp.minimum((pos0 + T + kb - 1) // kb, cap // kb)
        _, l, acc = jax.lax.fori_loop(0, live, body, init)
        return (acc / l[..., None]).astype(q_nope.dtype).transpose(0, 2, 1, 3)

    out = mla_chunk_attention(
        q_nope, q_rope, w_uk, w_uv, rows, pos0, scale=scale, rank=rank,
        xla_loop=xla_loop, impl=impl)
    return out.reshape(b, T, H * dv)


def mla_absorbed_query(p, q_nope, q_rope, cfg: Xing4Config):
    """Per head ``[q_nope W_UK^T | q_rope | 0] * softmax_scale``: what
    scores against a cached row."""
    w_uk, _ = _kv_b_split(p, cfg)
    qt = jnp.einsum("shd,chd->shc", q_nope, w_uk,
                    preferred_element_type=jnp.float32)
    q = jnp.concatenate([qt, q_rope.astype(jnp.float32)], -1)
    q = (q * cfg.softmax_scale).astype(q_nope.dtype)
    pad = latent_row_width(cfg) - q.shape[-1]
    return jnp.pad(q, ((0, 0), (0, 0), (0, pad)))


def mla_absorbed_output(p, o_lat, cfg: Xing4Config):
    """``(softmax . c) W_UV`` per head, then ``W_o``."""
    _, w_uv = _kv_b_split(p, cfg)
    o = jnp.einsum("shc,chd->shd", o_lat, w_uv)
    return o.reshape(o.shape[0], -1) @ p["o_w"]


# -- feed-forward ---------------------------------------------------------------


def _swiglu(x, gu_w, down_w):
    gu = x @ gu_w
    g, u = jnp.split(gu, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ down_w


def moe_route(p, x, cfg: Xing4Config):
    """``noaux_tc`` with one group: the ``experts_per_tok`` largest of
    ``sigmoid(x W_r) + b_corr``; gates are the picked scores (without
    the correction), renormalised and scaled.  Float32.  Returns
    ``(idx (N, k) int32, gate (N, k) float32)``."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), p["router_w"],
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.experts_per_tok)
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (g.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return idx.astype(jnp.int32), g


def _moe_kernel(exp_ref, tile_ref, lo_ref, hi_ref, x_ref, g_ref, u_ref,
                d_ref, o_ref, *, n_it):
    """One (work item, I tile): rows ``[lo, hi)`` of an m tile belong to
    the work item's expert; the output tile stays resident while
    consecutive items share it and accumulates over experts and I tiles."""
    t = pl.program_id(0)
    w, it = t // n_it, t % n_it
    first = jnp.logical_or(w == 0, tile_ref[w] != tile_ref[
        jnp.maximum(w - 1, 0)])

    @pl.when(jnp.logical_and(first, it == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    nt = (((1,), (1,)), ((), ()))
    g = jax.lax.dot_general(x, g_ref[0], nt,
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, u_ref[0], nt,
                            preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    mine = jnp.logical_and(row >= lo_ref[w], row < hi_ref[w])
    a = jnp.where(mine, jax.nn.silu(g) * u, 0.0).astype(x.dtype)
    o_ref[...] += jnp.dot(a, d_ref[0], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("impl", "name"))
def _moe_experts(x, idx, gate, gu_w, down_w, *, impl, name="_moe_experts"):
    """The routed experts' part of a layer, dropless.

    ``x`` (N, h); ``idx`` (N, k) int32 — each pick's index into the ``E``
    experts HELD (``gu_w`` (E, 2I, h), ``down_w`` (E, I, h)), ``E`` for a
    pick this layer does not hold or a token that is not live; ``gate``
    (N, k) float32.  The picks are sorted by expert and each expert's
    run of rows meets only that expert's weights, so an expert nobody
    picked is not read and no pick is dropped.  Returns ``(y (N, h)
    float32, sizes (E,) int32)`` — the picks each held expert got.
    ``name`` is the kernel's name in a device trace (a draft module's
    experts run it under their own)."""
    N, k = idx.shape
    E, I2, h = gu_w.shape
    I = I2 // 2
    M0 = N * k
    tm = min(128, -(-M0 // 16) * 16)
    M = -(-M0 // tm) * tm
    flat = jnp.pad(idx.reshape(-1), (0, M - M0), constant_values=E)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    xs = x[jnp.minimum(order // k, N - 1)]
    sizes = (flat[:, None] == jnp.arange(E)[None, :]).sum(0, dtype=jnp.int32)
    if impl == "xla":
        sz = jnp.concatenate([sizes, (M - sizes.sum())[None]])
        gu = jax.lax.ragged_dot(
            xs, jnp.pad(gu_w, ((0, 1), (0, 0), (0, 0))).transpose(0, 2, 1),
            sz, preferred_element_type=jnp.float32)
        a = (jax.nn.silu(gu[:, :I]) * gu[:, I:]).astype(x.dtype)
        ys = jax.lax.ragged_dot(
            a, jnp.pad(down_w, ((0, 1), (0, 0), (0, 0))), sz,
            preferred_element_type=jnp.float32)
    else:
        ti = next(c for c in (256, 128, I) if I % c == 0)
        n_it = I // ti
        # work items: (expert, m tile) pairs, expert-major, as many as
        # the picked experts' row runs cover tiles
        off = jnp.cumsum(sizes) - sizes
        t0, t1 = off // tm, (off + sizes - 1) // tm
        n_tiles = jnp.where(sizes > 0, t1 - t0 + 1, 0)
        ends = jnp.cumsum(n_tiles)
        w = jnp.arange(E + M // tm, dtype=jnp.int32)
        exp_of = jnp.minimum(
            (w[:, None] >= ends[None, :]).sum(1, dtype=jnp.int32), E - 1)
        tile_of = jnp.clip(
            t0[exp_of] + w - (ends - n_tiles)[exp_of], 0, M // tm - 1)
        lo = jnp.maximum(off[exp_of], tile_of * tm) - tile_of * tm
        hi = jnp.minimum(
            (off + sizes)[exp_of], (tile_of + 1) * tm) - tile_of * tm

        def wspec(shift):
            return pl.BlockSpec(
                (1, ti, h), lambda t, e, tl, lo, hi: (
                    e[t // n_it], t % n_it + shift, 0))

        ys = pl.pallas_call(
            functools.partial(_moe_kernel, n_it=n_it),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(jnp.maximum(ends[-1], 1) * n_it,),
                in_specs=[
                    pl.BlockSpec((tm, h), lambda t, e, tl, lo, hi: (
                        tl[t // n_it], 0)),
                    wspec(0), wspec(n_it), wspec(0)],
                out_specs=pl.BlockSpec((tm, h), lambda t, e, tl, lo, hi: (
                    tl[t // n_it], 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((M, h), jnp.float32),
            interpret=impl == "pallas_interpret",
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 << 20),
            name=name,
        )(exp_of, tile_of, lo, hi, xs, gu_w, gu_w, down_w)
    wts = jnp.pad(gate.reshape(-1), (0, M - M0))[order]
    ys = jnp.where((sorted_e < E)[:, None], ys * wts[:, None], 0.0)
    back = jnp.argsort(order)[:M0]
    return ys[back].reshape(N, k, h).sum(1), sizes


def moe_ffn(p, x, cfg: Xing4Config, held: Optional[Sequence[int]] = None,
            shared: bool = True, live=None, impl: Optional[str] = None,
            name: Optional[str] = None, route=None):
    """An expert layer's FFN for tokens ``x`` (N, h): the part of the
    experts in ``held`` (``p['exp_*_w']`` holds exactly those, in that
    order; ``None`` = all) plus, when ``shared``, the shared expert.
    ``live`` (N,) bool takes tokens out of the routing (empty slots).
    ``name``: :func:`_moe_experts`' name in a device trace, where it is
    not its own; ``route``: a family's own :func:`moe_route`.  Returns
    ``(y, stats)``: the float32 pair (share of the held experts picked,
    largest expert's picks over the mean)."""
    idx, gate = (route or moe_route)(p, x, cfg)
    E = p["exp_gu_w"].shape[0]
    if held is not None:
        local = np.full((cfg.n_routed_experts,), E, np.int32)
        local[np.asarray(held)] = np.arange(E)
        idx = jnp.asarray(local)[idx]
    if live is not None:
        idx = jnp.where(live[:, None], idx, E)
    y, sizes = _moe_experts(x, idx, gate, p["exp_gu_w"], p["exp_down_w"],
                            impl=_kernel_impl(impl),
                            **({} if name is None else {"name": name}))
    y = y.astype(x.dtype)
    if shared:
        y = y + _swiglu(x, p["shared_gu_w"], p["shared_down_w"])
    picks = sizes.astype(jnp.float32)
    stats = jnp.stack([
        (sizes > 0).mean(dtype=jnp.float32),
        picks.max() / jnp.maximum(picks.mean(), 1e-9)])
    return y, stats


def ffn(p, x, cfg: Xing4Config, layer: int, live=None, impl=None,
        name: Optional[str] = None):
    if cfg.is_dense(layer):
        return _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"]), None
    return moe_ffn(p, x, cfg, live=live, impl=impl, name=name)


# -- the block, prefill and decode --------------------------------------------


def layer_params(params, cfg: Xing4Config, layer: int):
    return {k: params[f"h{layer}_{k}"]
            for k in layer_param_shapes(cfg, layer)}


def prefill_layer(p, X, rows, pos0, cfg: Xing4Config, layer: int, impl=None):
    """One layer over a chunk: ``X`` (b, T, n, h) at positions ``pos0 +
    t``; ``rows`` (b, cap, width) the sequences' cached rows.  Returns
    the new streams and ``rows`` with the chunk's written."""
    b, T, n, h = X.shape
    positions = jnp.tile(pos0 + jnp.arange(T, dtype=jnp.int32), b)

    def attn(xn):
        q_nope, q_rope, row = mla_project(p, xn, positions, cfg)
        new_rows = jax.lax.dynamic_update_slice_in_dim(
            rows, row.reshape(b, T, -1).astype(rows.dtype), pos0, axis=1)
        o = mla_expanded_attention(
            p, q_nope.reshape(b, T, cfg.n_heads, -1),
            q_rope.reshape(b, T, cfg.n_heads, -1), new_rows, pos0, cfg,
            impl)
        return o.reshape(b * T, -1) @ p["o_w"], new_rows

    Xf = X.reshape(b * T, n, h)
    Xf, new_rows = hc_sublayer(
        Xf, p, "hca", p["attn_norm_g"], attn, cfg, impl)
    Xf, _ = hc_sublayer(
        Xf, p, "hcf", p["ffn_norm_g"],
        lambda xn: ffn(p, xn, cfg, layer, impl=impl), cfg, impl)
    return Xf.reshape(b, T, n, h), new_rows


def decode_layer(p, X, lengths, live, cfg: Xing4Config, layer: int,
                 impl=None):
    """One layer of one decode step: ``X`` (S, n, h), one token a slot at
    position ``lengths[s]``; absorbed MLA over the latent pool
    ``p["cache_c"]`` through ``p["page_table"]`` (this step's row
    attended before it is written: the pool write is the loop
    composer's).  ``live`` takes the slots that decode nothing out of the
    routing.  Returns ``(X', {"c": row (S, width)}, moe stats or None)``."""

    def attn(xn):
        q_nope, q_rope, row = mla_project(p, xn, lengths, cfg)
        o_lat = mla_paged_decode_attention(
            mla_absorbed_query(p, q_nope, q_rope, cfg), p["cache_c"],
            p["page_table"], lengths, cfg.kv_lora_rank, new_row=row,
            impl=impl)
        return mla_absorbed_output(p, o_lat, cfg), row

    X, row = hc_sublayer(X, p, "hca", p["attn_norm_g"], attn, cfg, impl)
    X, stats = hc_sublayer(
        X, p, "hcf", p["ffn_norm_g"],
        lambda xn: ffn(p, xn, cfg, layer, live=live, impl=impl), cfg, impl)
    return X, {"c": row}, stats


def embed(params, ids, cfg: Xing4Config):
    """Entry: the embedding copied to the ``hc_mult`` streams."""
    x = params["wte"][ids]
    return jnp.broadcast_to(
        x[..., None, :], (*x.shape[:-1], cfg.hc_mult, x.shape[-1]))


def head(params, X, cfg: Xing4Config):
    """Exit: the streams summed, final RMSNorm, the untied head."""
    x = X.astype(jnp.float32).sum(-2).astype(X.dtype)
    return jnp.dot(rms_norm(x, params["norm_f_g"], cfg.rms_eps),
                   params["head_w"], preferred_element_type=jnp.float32)


# -- the rest of what the paged builder and the engine call
# (models/__init__.py) ---------------------------------------------------------

EMBED_PARAMS = ("wte",)
HEAD_PARAMS = ("norm_f_g", "head_w")
#: the step's graph takes ``active`` (the slots that decode) as an input
#: and carries it on every edge as ``live``
DECODE_TAKES_LIVE = True


def layer_param_names(cfg: Xing4Config, layer: int) -> Dict[str, str]:
    return {k: f"h{layer}_{k}" for k in layer_param_shapes(cfg, layer)}


def cache_spec(cfg: Xing4Config):
    from .kv_pages import CacheSpec

    return CacheSpec.uniform(
        "latent", cfg.n_layers, (("c", (latent_row_width(cfg),)),),
        rank=cfg.kv_lora_rank)


def decode_embed(p, ids, lengths, cfg: Xing4Config):
    """Positions are the layers' rotary angles, not the embedding's."""
    return embed(p, ids[:, 0], cfg)


def decode_head(p, X, cfg: Xing4Config):
    return head(p, X, cfg)[:, None, :]


def decode_flops(cfg: Xing4Config, slots: int, capacity: int):
    """``(embed, [layer i's ...], head)`` FLOPs of one paged step: a
    layer's weights streamed once (experts: the picked ones), plus the
    absorbed attention over the slot's capacity."""
    S, h = slots, cfg.hidden_size
    picked = cfg.experts_per_tok / cfg.n_routed_experts
    attention = (2.0 * 2.0 * S * cfg.n_heads * capacity
                 * latent_row_width(cfg))
    layers = [
        sum(2.0 * S * math.prod(shape)
            * (picked if k.startswith("exp_") else 1.0)
            for k, (shape, _) in layer_param_shapes(cfg, i).items()
            if len(shape) >= 2) + attention
        for i in range(cfg.n_layers)]
    return 2.0 * S * h, layers, 2.0 * S * h * cfg.vocab_size


def init_cache(cfg: Xing4Config, batch: int, cap: int, dtype=None):
    return {"c": jnp.zeros(
        (cfg.n_layers, batch, cap, latent_row_width(cfg)),
        dtype or cfg.dtype)}


def _prefill(params, ids, cache, pos_start, cfg, impl=None):
    X = embed(params, ids, cfg)
    rows_out = []
    for i in range(cfg.n_layers):
        X, rows = prefill_layer(
            layer_params(params, cfg, i), X, cache["c"][i], pos_start, cfg,
            i, impl)
        rows_out.append(rows)
    return X, {"c": jnp.stack(rows_out)}


def forward_cached(params, ids, cache, pos_start, cfg: Xing4Config,
                   impl=None):
    """The family's cached forward (the engine's prefill contract):
    ``ids`` (b, T) at positions ``pos_start + t`` over ``cache`` ``{"c":
    (L, b, cap, width)}``; returns ``(logits (b, T, V) float32, cache)``."""
    X, cache = _prefill(params, ids, cache, pos_start, cfg, impl)
    return head(params, X, cfg), cache


def forward_cached_row(params, ids, cache, pos_start, cfg: Xing4Config,
                       row, impl=None):
    """:func:`forward_cached` with the logits of chunk row ``row`` only,
    (b, V): a 131,072-wide head over every row of a chunk is work the
    engine throws away."""
    X, cache = _prefill(params, ids, cache, pos_start, cfg, impl)
    last = jax.lax.dynamic_index_in_dim(X, row, 1, keepdims=False)
    return head(params, last, cfg), cache


def forward(params, ids, cfg: Xing4Config, impl=None):
    """Logits (b, T, V) of whole sequences: a prefill from position 0."""
    b, T = ids.shape
    return forward_cached(
        params, ids, init_cache(cfg, b, T), 0, cfg, impl)[0]


# -- ungated experts: two matrices an expert -------------------------------------
# Below everything else on purpose: Mosaic's payload carries source
# lines, so a line added above ``_moe_kernel`` (or above a frame that
# calls it) recompiles every program of the gated families (PERF.md
# section 6, PR 39).  That is also why ``_moe_experts`` keeps its own copy
# of the schedule below instead of calling :func:`_expert_schedule`.

_UNGATED_ACTS = {"relu2": lambda v: jnp.square(jax.nn.relu(v))}


def _expert_schedule(idx, E: int, tm: int):
    """``_moe_experts``' grouped schedule for picks ``idx`` (N, k) over
    ``E`` held experts (``E`` = not held / not live) in m tiles of ``tm``
    rows: ``(order, sorted_e, sizes, M, (exp_of, tile_of, lo, hi),
    n_items)`` — the picks sorted by expert, each held expert's count,
    the padded row count, and the work items (expert, m tile, the rows
    ``[lo, hi)`` of the tile that are the expert's), expert-major."""
    N, k = idx.shape
    M0 = N * k
    M = -(-M0 // tm) * tm
    flat = jnp.pad(idx.reshape(-1), (0, M - M0), constant_values=E)
    order = jnp.argsort(flat, stable=True)
    sizes = (flat[:, None] == jnp.arange(E)[None, :]).sum(0, dtype=jnp.int32)
    off = jnp.cumsum(sizes) - sizes
    t0, t1 = off // tm, (off + sizes - 1) // tm
    n_tiles = jnp.where(sizes > 0, t1 - t0 + 1, 0)
    ends = jnp.cumsum(n_tiles)
    w = jnp.arange(E + M // tm, dtype=jnp.int32)
    exp_of = jnp.minimum(
        (w[:, None] >= ends[None, :]).sum(1, dtype=jnp.int32), E - 1)
    tile_of = jnp.clip(
        t0[exp_of] + w - (ends - n_tiles)[exp_of], 0, M // tm - 1)
    lo = jnp.maximum(off[exp_of], tile_of * tm) - tile_of * tm
    hi = jnp.minimum(
        (off + sizes)[exp_of], (tile_of + 1) * tm) - tile_of * tm
    return (order, flat[order], sizes, M, (exp_of, tile_of, lo, hi),
            jnp.maximum(ends[-1], 1))


def _moe_kernel_ungated(exp_ref, tile_ref, lo_ref, hi_ref, x_ref, u_ref,
                        d_ref, o_ref, *, n_it, act):
    """:func:`_moe_kernel` with ``act(x W_u)`` for ``silu(x W_g) * (x
    W_u)``: one (work item, I tile)."""
    t = pl.program_id(0)
    w, it = t // n_it, t % n_it
    first = jnp.logical_or(w == 0, tile_ref[w] != tile_ref[
        jnp.maximum(w - 1, 0)])

    @pl.when(jnp.logical_and(first, it == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    u = jax.lax.dot_general(x, u_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    mine = jnp.logical_and(row >= lo_ref[w], row < hi_ref[w])
    a = jnp.where(mine, _UNGATED_ACTS[act](u), 0.0).astype(x.dtype)
    o_ref[...] += jnp.dot(a, d_ref[0], preferred_element_type=jnp.float32)


def ungated_i_tile(I: int) -> int:
    """The I tile of the ungated kernel: the largest divisor of ``I``
    that is whole 16-row sublane tiles and keeps an (up, down) pair of
    tiles, double-buffered, well inside VMEM — at most 512 rows; ``I``
    itself where it has none (toy widths)."""
    return next((c for c in range(min(I, 512), 15, -1)
                 if I % c == 0 and c % 16 == 0), I)


@functools.partial(jax.jit, static_argnames=("act", "impl", "name"))
def _moe_experts_ungated(x, idx, gate, up_w, down_w, *, act, impl,
                         name="_moe_experts"):
    """:func:`_moe_experts` for experts of TWO matrices, ``act(x W_u)
    W_d``: ``up_w`` / ``down_w`` (E, I, h).  The same sort, work items and
    scalar prefetch; the kernel body and the ``ragged_dot`` twin differ
    in the activation only, and the device trace knows it by the same
    name."""
    N, k = idx.shape
    E, I, h = up_w.shape
    tm = min(128, -(-N * k // 16) * 16)
    order, sorted_e, sizes, M, items, n_items = _expert_schedule(idx, E, tm)
    xs = x[jnp.minimum(order // k, N - 1)]
    if impl == "xla":
        sz = jnp.concatenate([sizes, (M - sizes.sum())[None]])
        u = jax.lax.ragged_dot(
            xs, jnp.pad(up_w, ((0, 1), (0, 0), (0, 0))).transpose(0, 2, 1),
            sz, preferred_element_type=jnp.float32)
        ys = jax.lax.ragged_dot(
            _UNGATED_ACTS[act](u).astype(x.dtype),
            jnp.pad(down_w, ((0, 1), (0, 0), (0, 0))), sz,
            preferred_element_type=jnp.float32)
    else:
        ti = ungated_i_tile(I)
        n_it = I // ti

        def tiles(t, e, tl, lo, hi):
            return e[t // n_it], t % n_it, 0

        def rows(t, e, tl, lo, hi):
            return tl[t // n_it], 0

        ys = pl.pallas_call(
            functools.partial(_moe_kernel_ungated, n_it=n_it, act=act),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n_items * n_it,),
                in_specs=[pl.BlockSpec((tm, h), rows),
                          pl.BlockSpec((1, ti, h), tiles),
                          pl.BlockSpec((1, ti, h), tiles)],
                out_specs=pl.BlockSpec((tm, h), rows),
            ),
            out_shape=jax.ShapeDtypeStruct((M, h), jnp.float32),
            interpret=impl == "pallas_interpret",
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 << 20),
            name=name,
        )(*items, xs, up_w, down_w)
    wts = jnp.pad(gate.reshape(-1), (0, M - N * k))[order]
    ys = jnp.where((sorted_e < E)[:, None], ys * wts[:, None], 0.0)
    back = jnp.argsort(order)[:N * k]
    return ys[back].reshape(N, k, h).sum(1), sizes


def moe_ffn_ungated(p, x, cfg, act: str, held: Optional[Sequence[int]] = None,
                    live=None, impl: Optional[str] = None, route=None):
    """:func:`moe_ffn` for a layer whose experts are ungated —
    ``p['exp_up_w']`` / ``p['exp_down_w']`` (E, I, h), the shared expert
    ``p['shared_up_w']`` (h, Is) / ``p['shared_down_w']`` (Is, h) — under
    activation ``act`` (a key of ``_UNGATED_ACTS``).  Routing, ``held``,
    ``live`` and the stats are :func:`moe_ffn`'s."""
    idx, gate = (route or moe_route)(p, x, cfg)
    E = p["exp_up_w"].shape[0]
    if held is not None:
        local = np.full((cfg.n_routed_experts,), E, np.int32)
        local[np.asarray(held)] = np.arange(E)
        idx = jnp.asarray(local)[idx]
    if live is not None:
        idx = jnp.where(live[:, None], idx, E)
    y, sizes = _moe_experts_ungated(
        x, idx, gate, p["exp_up_w"], p["exp_down_w"], act=act,
        impl=_kernel_impl(impl))
    y = y.astype(x.dtype) + _UNGATED_ACTS[act](
        x @ p["shared_up_w"]) @ p["shared_down_w"]
    picks = sizes.astype(jnp.float32)
    return y, jnp.stack([
        (sizes > 0).mean(dtype=jnp.float32),
        picks.max() / jnp.maximum(picks.mean(), 1e-9)])

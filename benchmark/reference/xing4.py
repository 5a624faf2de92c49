"""Xing4.0-29B-A4B's block: the benchmark's weights, its plain float32
reference, and the lower-precision control.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/xing4-29b-a4b-serve.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``: hyper-connection streams
around every sublayer (mHC, arXiv:2512.24880), multi-head latent
attention in its EXPANDED form only (keys and values rebuilt from the
latents for every position, no absorbed weights, no cache), YaRN rotary
angles as DeepSeek-V3 computes them, and an expert layer that applies
every expert to every token and keeps, by a mask, the gates of the ones
the router picked.  Long sequences are computed a block of query rows
(attention) or of tokens (experts, maps) at a time so that they fit
beside the weights; no kernel, no paging, no batching trick.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names
(``h{i}_q_a_w`` ...) and shapes because that is the interface the
program takes: expert matrices ``(E, 2I, h)`` gate over up and ``(E, I,
h)``, the three hyper-connection projections stacked and transposed
``(2n + n^2, n h)``.  The init is N(0, std) with one stated departure
(the config's ``init`` group): the query up-projection carries
``q_gain`` so that attention over thousands of positions is peaked —
with near-uniform attention every long context gives the same output and
a check on served tokens would pass a broken cache — and the routed
experts' down-projection ``exp_down_gain`` < 1, because random routers
leave the 4th and 5th of 64 scores nearly tied: rounding flips such a
pick, and at full gain one flipped expert moves the token's stream by an
eighth, which no precision could be told from another through.

``int8=True`` is the same forward in int8, the control: both operands of
every matmul rounded to 8 bits, symmetric absmax — weights per output
column, activations per tensor; queries, keys, values and attention
probabilities per head.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024       # query rows attended at a time
ROW_WINDOW = 1024    # rows of logits one served_gaps call computes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        h=int(c["hidden_size"]), n=int(c["hc_mult"]),
        H=int(c["num_attention_heads"]), rq=int(c["q_lora_rank"]),
        rk=int(c["kv_lora_rank"]), dn=int(c["qk_nope_head_dim"]),
        dr=int(c["qk_rope_head_dim"]), dv=int(c["v_head_dim"]),
        E=int(c["n_routed_experts"]), I=int(c["moe_intermediate_size"]),
        Is=int(c["moe_intermediate_size"]) * int(c["n_shared_experts"]),
        F=int(c["intermediate_size"]), k=int(c["num_experts_per_tok"]),
        L=int(c["num_hidden_layers"]), Ld=int(c["first_k_dense_replace"]),
        V=int(c["vocab_size"]),
    )


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32"): ``w`` is the served dtype."""
    d = _dims(config)
    h, n, H = d["h"], d["n"], d["H"]
    maps = 2 * n + n * n
    out = {}
    for hc in ("hca", "hcf"):
        out[f"{hc}_phi"] = ((maps, n * h), "f32")
        out[f"{hc}_alpha"] = ((3,), "f32")
        out[f"{hc}_b"] = ((maps,), "f32")
    out.update({
        "attn_norm_g": ((h,), "w"), "q_a_w": ((h, d["rq"]), "w"),
        "q_norm_g": ((d["rq"],), "w"),
        "q_b_w": ((d["rq"], H * (d["dn"] + d["dr"])), "w"),
        "kv_a_w": ((h, d["rk"] + d["dr"]), "w"),
        "kv_norm_g": ((d["rk"],), "w"),
        "kv_b_w": ((d["rk"], H * (d["dn"] + d["dv"])), "w"),
        "o_w": ((H * d["dv"], h), "w"), "ffn_norm_g": ((h,), "w"),
    })
    if layer < d["Ld"]:
        out["mlp_gu_w"] = ((h, 2 * d["F"]), "w")
        out["mlp_down_w"] = ((d["F"], h), "w")
    else:
        out["router_w"] = ((h, d["E"]), "f32")
        out["router_bias"] = ((d["E"],), "f32")
        out["exp_gu_w"] = ((d["E"], 2 * d["I"], h), "w")
        out["exp_down_w"] = ((d["E"], d["I"], h), "w")
        out["shared_gu_w"] = ((h, 2 * d["Is"]), "w")
        out["shared_down_w"] = ((d["Is"], h), "w")
    return out


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device from the seed, a layer to a jitted call
    (one call for 9.6 GB would hold every tensor's float32 draw at once)."""
    d = _dims(config)
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    gains = {"q_b_w": float(init.get("q_gain", 1.0)),
             "exp_down_w": float(init.get("exp_down_gain", 1.0))}
    n = d["n"]

    def draw(key, shapes):
        out = {}
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, dt)
            elif name.endswith("_alpha"):
                out[name] = jnp.full(shape, float(init.get("hc_alpha", 0.5)), dt)
            elif name.endswith(("hca_b", "hcf_b")):
                out[name] = jnp.concatenate([
                    jnp.zeros((2 * n,), dt),
                    float(init.get("hc_res_bias", 3.0))
                    * jnp.eye(n, dtype=dt).ravel()])
            elif name == "router_bias":
                out[name] = 0.01 * jax.random.normal(k, shape, dt)
            else:
                scale = std * gains.get(name, 1.0)
                out[name] = (scale * jax.random.normal(
                    k, shape, jnp.float32)).astype(dt)
        return out

    key = seed_key(seed)
    top = {"wte": ((d["V"], d["h"]), "w"), "head_w": ((d["h"], d["V"]), "w"),
           "norm_f_g": ((d["h"],), "w")}
    params = jax.jit(partial(draw, shapes=top))(jax.random.fold_in(key, 0))
    for i in range(d["L"]):
        layer = jax.jit(partial(draw, shapes=layer_shapes(config, i)))(
            jax.random.fold_in(key, i + 1))
        params.update({f"h{i}_{k}": v for k, v in layer.items()})
    return params


# -- the plain forward --------------------------------------------------------


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _q8(x, axis):
    """Symmetric absmax rounding to int8 along ``axis`` (dequantized)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, int8):
    if int8:
        x, w = _q8(x, None), _q8(w, 0)
    return x @ w


def _swiglu(x, gu_w, down_w, int8):
    g, u = jnp.split(_mm(x, gu_w, int8), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, down_w, int8)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(config: Dict[str, Any], T: int):
    """cos, sin (T, dr / 2) at positions 0 .. T-1: YaRN as DeepSeek-V3's
    ``YarnRotaryEmbedding`` (blend of plain and interpolated frequencies
    by a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow``; cos and sin times mscale / mscale_all_dim)."""
    rs = config["rope_scaling"]
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    factor, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / base ** exps, 1.0 / (factor * base ** exps)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = inter * ramp + extra * (1 - ramp)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    m = (_yarn_mscale(factor, float(rs["mscale"]))
         / _yarn_mscale(factor, float(rs["mscale_all_dim"])))
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def _rope(x, cos, sin):
    """Half-split pairing (``assumed``): dims (i, i + dr/2) rotate."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _hc(X, p, hc, config):
    """The three maps of one sublayer for streams ``X`` (T, n, h)."""
    n = int(config["hc_mult"])
    T = X.shape[0]
    xbar = _rms(X.reshape(T, -1), 1.0, float(config["rms_norm_eps"]))
    z = xbar @ p[f"{hc}_phi"].T                       # (T, 2n + n^2)
    a, b = p[f"{hc}_alpha"], p[f"{hc}_b"]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    lo = float(config["mhc_h_res_clamp_min"])
    hi = float(config["mhc_h_res_clamp_max"])
    M = jnp.exp(jnp.clip(res, lo, hi))
    eps = float(config["hc_eps"])
    for _ in range(int(config["hc_sinkhorn_iters"])):
        M = M / (M.sum(axis=1, keepdims=True) + eps)   # every column
        M = M / (M.sum(axis=2, keepdims=True) + eps)   # then every row
    return pre, post, M


def _attention(x, p, cos, sin, config, int8):
    """Expanded MLA over the whole sequence ``x`` (T, h), causal."""
    d = _dims(config)
    T, H, dn, dr, dv = x.shape[0], d["H"], d["dn"], d["dr"], d["dv"]
    eps = float(config["rms_norm_eps"])
    cq = _rms(_mm(x, p["q_a_w"], int8), p["q_norm_g"], eps)
    q = _mm(cq, p["q_b_w"], int8).reshape(T, H, dn + dr)
    ckr = _mm(x, p["kv_a_w"], int8)
    c = _rms(ckr[:, :d["rk"]], p["kv_norm_g"], eps)
    k_r = _rope(ckr[:, d["rk"]:], cos, sin)
    kv = _mm(c, p["kv_b_w"], int8).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (T, H, dr))], -1)
    v = kv[..., dn:]
    q = jnp.concatenate(
        [q[..., :dn], _rope(q[..., dn:], cos[:, None], sin[:, None])], -1)
    rs = config["rope_scaling"]
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    scale = (dn + dr) ** -0.5 * m * m
    if int8:
        q, k, v = (_q8(t, (0, 2)) for t in (q, k, v))
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    pos = jnp.arange(T)

    def block(q0):
        qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        s = jnp.where(pos[None, None, :] <= (q0 + jnp.arange(qb))[None, :, None],
                      s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if int8:
            pr = _q8(pr, (1, 2))
        return jnp.einsum("hqk,khd->qhd", pr, v)

    starts = jnp.arange(0, T, qb)
    o = jax.lax.map(block, starts).reshape(-1, H * dv)[:T]
    return _mm(o, p["o_w"], int8)


def _moe(x, p, config, int8):
    """Every expert applied to every token; the gate is zero where the
    router did not pick it."""
    d = _dims(config)
    s = jax.nn.sigmoid(x @ p["router_w"])
    _, idx = jax.lax.top_k(s + p["router_bias"], d["k"])
    picked = jnp.take_along_axis(s, idx, -1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) * float(
        config["routed_scaling_factor"])
    gates = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(g)

    def one(y, e):
        gu = p["exp_gu_w"][e].astype(jnp.float32).T     # (h, 2I)
        dw = p["exp_down_w"][e].astype(jnp.float32)     # (I, h)
        return y + gates[:, e, None] * _swiglu(x, gu, dw, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d["E"]))
    return y + _swiglu(x, p["shared_gu_w"].astype(jnp.float32),
                       p["shared_down_w"].astype(jnp.float32), int8)


@partial(jax.jit, static_argnames=("cfg", "dense", "int8"))
def _layer(X, p, cos, sin, *, cfg, dense, int8):
    """One layer on streams ``X`` (T, n, h) in float32."""
    config = dict(cfg)
    config["rope_scaling"] = dict(config["rope_scaling"])
    experts = {k: p[k] for k in ("exp_gu_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)        # upcast an expert at a time, inside the scan
    eps = float(config["rms_norm_eps"])

    def around(X, hc, g, fn):
        pre, post, res = _hc(X, p, hc, config)
        u = jnp.einsum("tn,tnh->th", pre, X)
        y = fn(_rms(u, g, eps))
        return (jnp.einsum("tij,tjh->tih", res, X)
                + post[:, :, None] * y[:, None, :])

    X = around(X, "hca", p["attn_norm_g"],
               lambda x: _attention(x, p, cos, sin, config, int8))
    if dense:
        f = lambda x: _swiglu(x, p["mlp_gu_w"], p["mlp_down_w"], int8)  # noqa: E731
    else:
        f = lambda x: _moe(x, p, config, int8)  # noqa: E731
    return around(X, "hcf", p["ffn_norm_g"], f)


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    keep = {k: v for k, v in config.items()
            if not isinstance(v, (dict, list))}
    keep["rope_scaling"] = tuple(sorted(config["rope_scaling"].items()))
    return tuple(sorted(keep.items()))


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(X, g, head_w, *, eps, int8):
    x = _rms(X.sum(-2), g.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), int8)


def hidden(params, config, ids, int8: bool = False):
    """Streams (T, n, h) after the last layer for ``ids`` (T,)."""
    d = _dims(config)
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rope_tables(config, ids.shape[0])
    cfg = _frozen(config)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], d["n"], d["h"]))
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            X = _layer(X, layer, cos, sin, cfg=cfg, dense=i < d["Ld"],
                       int8=int8)
    return X


def logits(params, config, ids, int8: bool = False, rows=None):
    """(B, T, V) float32 logits of ``ids`` (B, T), a sequence at a time;
    with ``rows`` (a slice) only those positions' logits."""
    out = []
    for seq in np.asarray(ids):
        X = hidden(params, config, seq, int8)
        if rows is not None:
            X = X[rows]
        with jax.default_matmul_precision("highest"):
            out.append(_head(X, params["norm_f_g"], params["head_w"],
                             eps=float(config["rms_norm_eps"]), int8=int8))
    return jnp.stack(out)


# -- what the checks compare ---------------------------------------------------


@jax.jit
def _gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the row's best."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_gaps(params, config, seq, prompt_len: int, n_served: int,
                pad_to: int, control: bool = False):
    """Teacher-force ``seq`` (prompt + served tokens, 1-D) through the
    reference and return, for each served token, the gap by which its
    reference logit lies below that position's best (0 = the reference's
    own greedy token).  With ``control=True`` the tokens judged are the
    ones the int8 forward puts first at the same positions.  ``pad_to``
    fixes the compiled length (causal masking keeps the padding out of
    every real row); the head runs over one fixed window of rows that
    holds the served positions."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    win = min(ROW_WINDOW, pad_to)
    if n_served > win:
        raise ValueError(f"{n_served} served tokens exceed the {win}-row window")
    w0 = min(prompt_len - 1, pad_to - win)
    rows = slice(w0, w0 + win)
    mine = slice(prompt_len - 1 - w0, prompt_len - 1 - w0 + n_served)
    ref = logits(params, config, ids[None], rows=rows)[0][mine]
    if control:
        low = logits(params, config, ids[None], int8=True, rows=rows)[0][mine]
        toks = jnp.argmax(low, axis=-1).astype(jnp.int32)
    else:
        toks = jnp.asarray(seq[prompt_len:prompt_len + n_served], jnp.int32)
    return np.asarray(_gaps(ref, toks), np.float64)

"""Laguna-S-2.1's block: the benchmark's weights, its plain float32
reference, and the lower-precision control.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/laguna-s-2.1-ep4.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``: grouped-query attention
with rotary positions on queries and keys — a full layer rotates the
first half of each head with YaRN frequencies and multiplies ``cos`` and
``sin`` by ``attention_factor``, a sliding layer rotates every value at
a plain theta and sees the last ``sliding_window`` positions — a
per-head sigmoid gate before the output projection, and an expert layer
that takes the ten largest of ``softmax(x W_r)``, renormalises them,
scales them, applies every HELD expert to every token and keeps, by a
mask, the gates of the ones the router picked (a pick on an expert this
chip does not hold adds nothing, as in the program), plus an ungated
shared expert.  No kernel, no cache, no paging, no batching: long
sequences are computed a block of query rows at a time (a full layer's
against every key, a sliding layer's against the band of keys its
window reaches) so that 33k tokens fit beside the weights.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names (``h{i}_q_w``
...) and shapes because that is the interface the program takes: expert
matrices ``(E, 2I, h)`` gate over up and ``(E, I, h)``.  The init is
N(0, std) with the departures the config's ``init`` group states: every
``<name>_gain`` multiplies the draw of ``<name>_w``.

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

Q_BLOCK = 1024       # query rows a sliding layer attends at a time
SCORE_ELEMS = 1 << 28   # float32 scores a full layer holds at a time
ROW_WINDOW = 1024    # rows of logits one served_gaps call computes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
        h=int(c["hidden_size"]), Hkv=int(c["num_key_value_heads"]),
        hd=int(c["head_dim"]), E=int(c["num_experts"]),
        R=int(c.get("n_router_outputs", c["num_experts"])),
        I=int(c["moe_intermediate_size"]),
        Is=int(c["shared_expert_intermediate_size"]),
        F=int(c["intermediate_size"]), k=int(c["num_experts_per_tok"]),
        L=int(c["num_hidden_layers"]), V=int(c["vocab_size"]),
        W=int(c["sliding_window"]),
    )


def _heads(config: Dict[str, Any], layer: int) -> int:
    return int(config["num_attention_heads_per_layer"][layer])


def _is_full(config: Dict[str, Any], layer: int) -> bool:
    return config["layer_types"][layer] == "full_attention"


def _is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer in config["mlp_only_layers"]


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32"): ``w`` is the served dtype."""
    d = _dims(config)
    h, H, kv = d["h"], _heads(config, layer), d["Hkv"] * d["hd"]
    out = {
        "attn_norm_g": ((h,), "w"), "q_w": ((h, H * d["hd"]), "w"),
        "k_w": ((h, kv), "w"), "v_w": ((h, kv), "w"),
        "gate_w": ((h, H), "w"), "o_w": ((H * d["hd"], h), "w"),
        "ffn_norm_g": ((h,), "w"),
    }
    if _is_dense(config, layer):
        out["mlp_gu_w"] = ((h, 2 * d["F"]), "w")
        out["mlp_down_w"] = ((d["F"], h), "w")
    else:
        out["router_w"] = ((h, d["R"]), "f32")
        out["exp_gu_w"] = ((d["E"], 2 * d["I"], h), "w")
        out["exp_down_w"] = ((d["E"], d["I"], h), "w")
        out["shared_gu_w"] = ((h, 2 * d["Is"]), "w")
        out["shared_down_w"] = ((d["Is"], h), "w")
    return out


def param_count(config: Dict[str, Any]) -> int:
    d = _dims(config)
    return (2 * d["V"] * d["h"] + d["h"] + sum(
        math.prod(shape) for i in range(d["L"])
        for shape, _ in layer_shapes(config, i).values()))


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device from the seed, a layer to a jitted call
    (one call would hold every tensor's float32 draw at once)."""
    d = _dims(config)
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    gains = {k[:-len("_gain")] + "_w": float(v) for k, v in init.items()
             if k.endswith("_gain")}

    def draw(key, shapes):
        out = {}
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, dt)
            else:
                out[name] = (std * gains.get(name, 1.0) * jax.random.normal(
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


def rope_tables(config: Dict[str, Any], full: bool, T: int):
    """cos, sin (T, rotated / 2) at positions 0 .. T-1 of a layer kind.
    Full: YaRN over the ``head_dim * partial_rotary_factor`` rotated
    values (blend of plain and interpolated frequencies by a linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``,
    bounds floored and ceiled), cos and sin times ``attention_factor``.
    Sliding: plain rotary at its theta over its rotated values."""
    rp = config["rope_parameters"][
        "full_attention" if full else "sliding_attention"]
    dim = int(int(config["head_dim"]) * float(rp["partial_rotary_factor"]))
    base = float(rp["rope_theta"])
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq, m = 1.0 / base ** exps, 1.0
    if rp["rope_type"] == "yarn":
        factor = float(rp["factor"])
        orig = int(rp["original_max_position_embeddings"])

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(corr(float(rp["beta_fast"]))), 0)
        high = min(math.ceil(corr(float(rp["beta_slow"]))), dim - 1)
        ramp = np.clip(
            (np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
        m = float(rp["attention_factor"])
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def _rope(x, cos, sin):
    """``x`` (T, heads, hd): the leading ``2 * cos.shape[-1]`` values of
    each head rotate, half-split pairing (``assumed``); the rest pass."""
    rot = 2 * cos.shape[-1]
    a, b = jnp.split(x[..., :rot], 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)


def _attention(x, p, cos, sin, config, H, full, int8):
    """Grouped-query attention over the whole sequence ``x`` (T, h),
    causal; a sliding layer under its window."""
    d = _dims(config)
    T, Hkv, hd, W = x.shape[0], d["Hkv"], d["hd"], d["W"]
    G = H // Hkv
    q = _rope(_mm(x, p["q_w"], int8).reshape(T, H, hd), cos, sin)
    k = _rope(_mm(x, p["k_w"], int8).reshape(T, Hkv, hd), cos, sin)
    v = _mm(x, p["v_w"], int8).reshape(T, Hkv, hd)
    if int8:
        q, k, v = (_q8(t, (0, 2)) for t in (q, k, v))
    q = q.reshape(T, Hkv, G, hd)
    scale = hd ** -0.5
    if full:
        qb = max(1, min(Q_BLOCK, SCORE_ELEMS // (H * T)))
        qb = 1 << (qb.bit_length() - 1)
        back = 0
    else:
        qb, back = Q_BLOCK, W
        k = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))
    if T % qb:
        qb = T
    n_keys = T if full else qb + back

    def block(q0):
        qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
        # a sliding block's keys: positions q0 - W .. q0 + qb - 1
        ks = k if full else jax.lax.dynamic_slice_in_dim(k, q0, n_keys, 0)
        vs = v if full else jax.lax.dynamic_slice_in_dim(v, q0, n_keys, 0)
        k_pos = (jnp.arange(n_keys) + (0 if full else q0 - back))[None, :]
        q_pos = (q0 + jnp.arange(qb))[:, None]
        ok = k_pos <= q_pos
        if not full:
            ok = ok & (k_pos > q_pos - W) & (k_pos >= 0)
        s = jnp.einsum("qhgd,khd->hgqk", qs, ks) * scale
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        if int8:
            pr = _q8(pr, (2, 3))
        return jnp.einsum("hgqk,khd->qhgd", pr, vs)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H, hd)
    gate = jax.nn.sigmoid(_mm(x, p["gate_w"], int8))       # (T, H)
    return _mm((o * gate[:, :, None]).reshape(T, H * hd), p["o_w"], int8)


def _moe(x, p, config, int8):
    """Every held expert applied to every token; the gate is zero where
    the router did not pick it."""
    d = _dims(config)
    pr = jax.nn.softmax(x @ p["router_w"], axis=-1)
    top, idx = jax.lax.top_k(pr, d["k"])
    g = top / (top.sum(-1, keepdims=True) + 1e-20) * float(
        config["moe_routed_scaling_factor"])
    gates = jnp.zeros_like(pr).at[jnp.arange(x.shape[0])[:, None], idx].set(g)
    held = jnp.asarray(config.get("held_experts") or range(d["E"]), jnp.int32)
    gates = gates[:, held]                                  # (T, E)

    def one(y, e):
        gu = p["exp_gu_w"][e].astype(jnp.float32).T     # (h, 2I)
        dw = p["exp_down_w"][e].astype(jnp.float32)     # (I, h)
        return y + gates[:, e, None] * _swiglu(x, gu, dw, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d["E"]))
    return y + _swiglu(x, p["shared_gu_w"].astype(jnp.float32),
                       p["shared_down_w"].astype(jnp.float32), int8)


@partial(jax.jit, static_argnames=("cfg", "layer", "int8"))
def _layer(x, p, cos, sin, *, cfg, layer, int8):
    """One layer on the residual stream ``x`` (T, h) in float32."""
    config = _thawed(cfg)
    experts = {k: p[k] for k in ("exp_gu_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)        # upcast an expert at a time, inside the scan
    eps = float(config["rms_norm_eps"])
    x = x + _attention(
        _rms(x, p["attn_norm_g"], eps), p, cos, sin, config,
        _heads(config, layer), _is_full(config, layer), int8)
    xn = _rms(x, p["ffn_norm_g"], eps)
    if _is_dense(config, layer):
        return x + _swiglu(xn, p["mlp_gu_w"], p["mlp_down_w"], int8)
    return x + _moe(xn, p, config, int8)


_NESTED = ("rope_parameters", "layer_types", "mlp_only_layers",
           "num_attention_heads_per_layer", "held_experts")


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    import json

    keep = {k: v for k, v in config.items()
            if not isinstance(v, (dict, list))}
    for k in _NESTED:
        if config.get(k) is not None:
            keep[k] = json.dumps(config[k], sort_keys=True)
    return tuple(sorted(keep.items()))


def _thawed(cfg) -> Dict[str, Any]:
    import json

    return {k: json.loads(v) if k in _NESTED else v for k, v in cfg}


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, head_w, *, eps, int8):
    return _mm(_rms(x, g.astype(jnp.float32), eps),
               head_w.astype(jnp.float32), int8)


def hidden(params, config, ids, int8: bool = False):
    """The residual stream (T, h) after the last layer for ``ids`` (T,)."""
    d = _dims(config)
    ids = jnp.asarray(ids, jnp.int32)
    tables = {full: rope_tables(config, full, ids.shape[0])
              for full in (True, False)}
    cfg = _frozen(config)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x = _layer(x, layer, *tables[_is_full(config, i)], cfg=cfg,
                       layer=i, int8=int8)
    return x


def logits(params, config, ids, int8: bool = False, rows=None):
    """(B, T, V) float32 logits of ``ids`` (B, T), a sequence at a time;
    with ``rows`` (a slice) only those positions' logits."""
    out = []
    for seq in np.asarray(ids):
        x = hidden(params, config, seq, int8)
        if rows is not None:
            x = x[rows]
        with jax.default_matmul_precision("highest"):
            out.append(_head(x, params["norm_f_g"], params["head_w"],
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

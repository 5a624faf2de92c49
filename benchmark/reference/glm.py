"""GLM-4.7-Flash's block and its multi-token-prediction module: the
benchmark's weights, its plain float32 reference, and the lower-precision
control.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/glm-4.7-flash-serve.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``: pre-norm residual layers,
multi-head latent attention in its EXPANDED form only (keys and values
rebuilt from the latents for every position, no absorbed weights, no
cache), plain rotary angles, an expert layer that applies every expert
to every token and keeps, by a mask, the gates of the ones the router
picked — and **no speculation**: the main model's full forward, and the
draft module's full forward given the main model's output ``h`` and the
tokens shifted by one (DeepSeek-V3's MTP form: ``z_i = W_eh [RMSNorm_e(
Emb(t_{i+1})) ; RMSNorm_h(h_i)]``, one expert layer of its own, the
shared head behind a norm of its own; its logits at ``i`` predict
``t_{i+2}``).  Long sequences are computed a block of query rows
(attention) at a time; no kernel, no paging, no batching trick.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names (``h{i}_q_a_w``
..., the draft module's ``mtp_*``) and shapes because that is the
interface the program takes.  The init is N(0, std) with the departures
of the config's ``init`` group, each with its reason there: ``q_gain``
and ``exp_down_gain`` as ``xing4``'s, and three that give the one-layer
draft module something to agree with a six-layer model about —
``emb_gain`` on the embedding, so that the ``Emb -> Head`` path carries
most of a logit's variance and the context breaks the tie;
``eh_identity``, W_eh's embedding half ``a I + N(0, std)``; and
``mtp_out_gain`` on the draft layer's output projections.

``int8=True`` is the same forward in int8, the control: both operands of
every matmul rounded to 8 bits, symmetric absmax — weights per output
column, activations per tensor; queries, keys, values and attention
probabilities per head.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024       # query rows attended at a time
ROW_WINDOW = 1024    # rows of logits the head computes at a time

#: the draft module's output projections, scaled by ``mtp_out_gain``
_MTP_OUT = ("o_w", "exp_down_w", "shared_down_w")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        h=int(c["hidden_size"]), H=int(c["num_attention_heads"]),
        rq=int(c["q_lora_rank"]), rk=int(c["kv_lora_rank"]),
        dn=int(c["qk_nope_head_dim"]), dr=int(c["qk_rope_head_dim"]),
        dv=int(c["v_head_dim"]), E=int(c["n_routed_experts"]),
        I=int(c["moe_intermediate_size"]),
        Is=int(c["moe_intermediate_size"]) * int(c["n_shared_experts"]),
        F=int(c["intermediate_size"]), k=int(c["num_experts_per_tok"]),
        L=int(c["num_hidden_layers"]), Ld=int(c["first_k_dense_replace"]),
        V=int(c["vocab_size"]),
    )


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32") of main layer ``layer`` (``w``
    is the served dtype); ``layer >= num_hidden_layers`` is the draft
    module's layer, an expert layer."""
    d = _dims(config)
    h, H = d["h"], d["H"]
    out = {
        "attn_norm_g": ((h,), "w"), "q_a_w": ((h, d["rq"]), "w"),
        "q_norm_g": ((d["rq"],), "w"),
        "q_b_w": ((d["rq"], H * (d["dn"] + d["dr"])), "w"),
        "kv_a_w": ((h, d["rk"] + d["dr"]), "w"),
        "kv_norm_g": ((d["rk"],), "w"),
        "kv_b_w": ((d["rk"], H * (d["dn"] + d["dv"])), "w"),
        "o_w": ((H * d["dv"], h), "w"), "ffn_norm_g": ((h,), "w"),
    }
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


def draft_shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The draft module's own parameters: its layer's, the two input
    norms, ``W_eh`` (4096 -> 2048 at the published widths, embedding
    half first) and the norm before the shared head."""
    h = int(config["hidden_size"])
    out = dict(layer_shapes(config, int(config["num_hidden_layers"])))
    out.update({"enorm_g": ((h,), "w"), "hnorm_g": ((h,), "w"),
                "eh_w": ((2 * h, h), "w"), "norm_g": ((h,), "w")})
    return out


def n_parameters(config: Dict[str, Any]) -> int:
    """Parameters of the configuration as served: embedding, head, final
    norm, every main layer and the draft module."""
    d = _dims(config)
    shapes = [((d["V"], d["h"]), ""), ((d["h"], d["V"]), ""), ((d["h"],), "")]
    for i in range(d["L"]):
        shapes += layer_shapes(config, i).values()
    shapes += draft_shapes(config).values()
    return sum(int(np.prod(s)) for s, _ in shapes)


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device from the seed, a layer to a jitted call
    (one call for 9 GB would hold every tensor's float32 draw at once)."""
    d = _dims(config)
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    main_gains = {"q_b_w": float(init.get("q_gain", 1.0)),
                  "exp_down_w": float(init.get("exp_down_gain", 1.0)),
                  "wte": float(init.get("emb_gain", 1.0))}
    out_gain = float(init.get("mtp_out_gain", 1.0))
    mtp_gains = dict(main_gains)
    for k in _MTP_OUT:
        mtp_gains[k] = mtp_gains.get(k, 1.0) * out_gain
    eh_identity = float(init.get("eh_identity", 0.0))

    def draw(key, shapes, gains):
        out = {}
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, dt)
            elif name == "router_bias":
                out[name] = 0.01 * jax.random.normal(k, shape, dt)
            else:
                w = std * gains.get(name, 1.0) * jax.random.normal(
                    k, shape, jnp.float32)
                if name == "eh_w":       # the embedding half: a I + noise
                    w = w.at[:shape[1]].add(eh_identity * jnp.eye(shape[1]))
                out[name] = w.astype(dt)
        return out

    key = seed_key(seed)
    top = {"wte": ((d["V"], d["h"]), "w"), "head_w": ((d["h"], d["V"]), "w"),
           "norm_f_g": ((d["h"],), "w")}
    params = jax.jit(partial(draw, shapes=top, gains=main_gains))(
        jax.random.fold_in(key, 0))
    for i in range(d["L"]):
        layer = jax.jit(partial(
            draw, shapes=layer_shapes(config, i), gains=main_gains))(
            jax.random.fold_in(key, i + 1))
        params.update({f"h{i}_{k}": v for k, v in layer.items()})
    mtp = jax.jit(partial(
        draw, shapes=draft_shapes(config), gains=mtp_gains))(
        jax.random.fold_in(key, d["L"] + 1))
    params.update({f"mtp_{k}": v for k, v in mtp.items()})
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


def rope_tables(config: Dict[str, Any], T: int):
    """cos, sin (T, dr / 2) at positions 0 .. T-1: plain rotary angles
    (``rope_scaling`` null, the whole 64 rotated)."""
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    inv_freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """Half-split pairing (``assumed``): dims (i, i + dr/2) rotate."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


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
    scale = (dn + dr) ** -0.5
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

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(-1, H * dv)[:T]
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
def _layer(x, p, cos, sin, *, cfg, dense, int8):
    """One pre-norm layer on ``x`` (T, h) in float32."""
    config = dict(cfg)
    experts = {k: p[k] for k in ("exp_gu_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)        # upcast an expert at a time, inside the scan
    eps = float(config["rms_norm_eps"])
    x = x + _attention(_rms(x, p["attn_norm_g"], eps), p, cos, sin, config,
                       int8)
    xn = _rms(x, p["ffn_norm_g"], eps)
    if dense:
        return x + _swiglu(xn, p["mlp_gu_w"], p["mlp_down_w"], int8)
    return x + _moe(xn, p, config, int8)


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if not isinstance(v, (dict, list))))


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, head_w, *, eps, int8):
    return _mm(_rms(x, g.astype(jnp.float32), eps),
               head_w.astype(jnp.float32), int8)


@partial(jax.jit, static_argnames=("eps", "int8"))
def _draft_input(h_main, e, enorm_g, hnorm_g, eh_w, *, eps, int8):
    f32 = jnp.float32
    both = jnp.concatenate([_rms(e.astype(f32), enorm_g.astype(f32), eps),
                            _rms(h_main, hnorm_g.astype(f32), eps)], -1)
    return _mm(both, eh_w.astype(f32), int8)


def hidden(params, config, ids, int8: bool = False):
    """The main model's residual (T, h) after the last layer, before the
    final norm, for ``ids`` (T,)."""
    d = _dims(config)
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rope_tables(config, ids.shape[0])
    cfg = _frozen(config)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x = _layer(x, layer, cos, sin, cfg=cfg, dense=i < d["Ld"],
                       int8=int8)
    return x


def draft_hidden(params, config, ids, x_main, int8: bool = False):
    """The draft module's output (T, h) before its head norm: position
    ``i`` takes ``(h_i, ids[i + 1])`` — ``h_i`` the main model's
    ``x_main[i]`` AFTER the final RMSNorm (``assumed``) — and the last
    position a token 0 nobody reads."""
    d = _dims(config)
    eps = float(config["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
    cos, sin = rope_tables(config, ids.shape[0])
    with jax.default_matmul_precision("highest"):
        h_main = _rms(x_main, params["norm_f_g"].astype(jnp.float32), eps)
        z = _draft_input(
            h_main, params["wte"][nxt], params["mtp_enorm_g"],
            params["mtp_hnorm_g"], params["mtp_eh_w"], eps=eps, int8=int8)
        layer = {k: params[f"mtp_{k}"] for k in layer_shapes(config, d["L"])}
        return _layer(z, layer, cos, sin, cfg=_frozen(config), dense=False,
                      int8=int8)


def _rows_logits(x, g, head_w, config, rows, int8):
    """Logits of the rows ``rows`` (a slice) of ``x`` (T, h), the head a
    window of :data:`ROW_WINDOW` rows at a time."""
    eps = float(config["rms_norm_eps"])
    x = x[rows]
    out = []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, x.shape[0], ROW_WINDOW):
            out.append(_head(x[r0:r0 + ROW_WINDOW], g, head_w, eps=eps,
                             int8=int8))
    return jnp.concatenate(out)


def logits(params, config, ids, int8: bool = False, rows=None):
    """(B, T, V) float32 main-model logits of ``ids`` (B, T), a sequence
    at a time; with ``rows`` (a slice) only those positions' logits."""
    rows = slice(None) if rows is None else rows
    return jnp.stack([
        _rows_logits(hidden(params, config, seq, int8), params["norm_f_g"],
                     params["head_w"], config, rows, int8)
        for seq in np.asarray(ids)])


def draft_logits(params, config, ids, int8: bool = False, rows=None):
    """(B, T, V) float32 draft logits of ``ids`` (B, T): row ``i``
    predicts ``ids[i + 2]`` from ``(h_i, ids[i + 1])`` (the last row's is
    nobody's)."""
    rows = slice(None) if rows is None else rows
    out = []
    for seq in np.asarray(ids):
        z = draft_hidden(params, config, seq,
                         hidden(params, config, seq, int8), int8)
        out.append(_rows_logits(z, params["mtp_norm_g"], params["head_w"],
                                config, rows, int8))
    return jnp.stack(out)


# -- what the checks compare ---------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "int8"))
def _window_stats(x, g, head_w, picks, *, eps, int8):
    """Of a window of rows' logits: the best, its index, and the logit of
    ``picks`` — all the checks need of (rows, V) float32."""
    lg = _head(x, g, head_w, eps=eps, int8=int8)
    return (lg.max(-1), jnp.argmax(lg, -1).astype(jnp.int32),
            jnp.take_along_axis(lg, picks[:, None], -1)[:, 0])


def _rows_stats(x, g, head_w, config, rows, picks, int8):
    """``(best, argmax, logit of picks[i])`` of the rows ``rows`` of ``x``
    (T, h), reduced a window of :data:`ROW_WINDOW` rows at a time: a long
    request's logits (4,096 x 154,880 float32 = 2.5 GB) never exist
    whole beside the weights."""
    eps = float(config["rms_norm_eps"])
    x, picks = x[rows], jnp.asarray(picks, jnp.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, x.shape[0], ROW_WINDOW):
            out.append(_window_stats(
                x[r0:r0 + ROW_WINDOW], g, head_w, picks[r0:r0 + ROW_WINDOW],
                eps=eps, int8=int8))
    return tuple(np.concatenate([np.asarray(o[k]) for o in out])
                 for k in range(3))


def served_check(params, config, seq, prompt_len: int, n_served: int,
                 pad_to: int, drafts: Optional[Dict[int, int]] = None,
                 control: bool = False) -> Dict[str, Any]:
    """Teacher-force ``seq`` (prompt + served tokens, 1-D) through the
    reference main model and draft module.

    ``gaps``: for each served token, the gap by which its reference
    logit lies below that position's best (0 = the reference's own
    greedy token).  ``drafts`` maps a decode step's start length ``L``
    (the position of its current token) to the draft the served step
    verified there — a prediction of ``seq[L + 1]`` made by the draft
    module at position ``L - 1``.  For those steps: ``draft_gaps``, the
    gap of the draft in the reference DRAFT module's logits at ``L - 1``;
    ``served_accepts``, whether the draft is the token served at ``L +
    1``; ``ref_accepts``, whether the reference's own draft argmax at
    ``L - 1`` is its own main argmax at ``L``.  With ``control=True``
    the tokens and drafts judged are the ones the int8 forward puts
    first at the same positions, and ``served_accepts`` is the int8
    forward's own agreement.  ``pad_to`` fixes the compiled length
    (causal masking keeps the padding out of every real row)."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    rows = slice(prompt_len - 1, prompt_len - 1 + n_served)
    g, gd, head_w = params["norm_f_g"], params["mtp_norm_g"], params["head_w"]
    x = hidden(params, config, ids)
    toks = np.asarray(seq[prompt_len:prompt_len + n_served], np.int32)
    if control:
        x8 = hidden(params, config, ids, True)
        _, toks, _ = main8 = _rows_stats(x8, g, head_w, config, rows, toks,
                                         True)
    best, main_arg, got = _rows_stats(x, g, head_w, config, rows, toks, False)
    out = {"gaps": (best - got).astype(np.float64)}
    if not drafts:
        return out
    # a step at length L reads the draft module's row L - 1 and the main
    # model's row L, both inside ``rows`` shifted by one
    at = np.asarray(sorted(drafts), np.int64)
    i_draft, i_main = at - 1 - rows.start, at - rows.start
    given = np.zeros((n_served,), np.int32)
    given[i_draft] = [drafts[int(L)] for L in at]
    accepts = given[i_draft] == np.asarray(seq)[at + 1]
    if control:
        z8 = draft_hidden(params, config, ids, x8, True)
        _, d8, _ = _rows_stats(z8, gd, head_w, config, rows, given, True)
        given[i_draft] = d8[i_draft]
        accepts = d8[i_draft] == main8[1][i_main]
    z = draft_hidden(params, config, ids, x)
    d_best, d_arg, d_got = _rows_stats(z, gd, head_w, config, rows, given,
                                       False)
    out.update(
        draft_gaps=(d_best - d_got)[i_draft].astype(np.float64),
        served_accepts=np.asarray(accepts),
        ref_accepts=d_arg[i_draft] == main_arg[i_main])
    return out


def served_gaps(params, config, seq, prompt_len: int, n_served: int,
                pad_to: int, control: bool = False):
    """:func:`served_check`'s ``gaps`` alone (the interface the serving
    runners' shared token check calls)."""
    return served_check(params, config, seq, prompt_len, n_served, pad_to,
                        control=control)["gaps"]

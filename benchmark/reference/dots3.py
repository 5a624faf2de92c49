"""dots3-note-prev's block: the benchmark's weights, its plain float32
reference, and the lower-precision control.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/dots3-note-prev-ep8.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``: a pre-norm residual block;
multi-head latent attention in its EXPANDED form only (keys and values
rebuilt from the latents for every position, no absorbed weights, no
cache, no ring); in a full layer the indexer's scores for every pair of
positions, an exact top-k per query (``lax.top_k``) turned into a mask,
and softmax over the picked positions alone; in a sliding layer the
window as a mask; a headwise sigmoid gate; and an expert layer that
applies every HELD expert to every token and keeps, by a mask, the
gates of the ones the router picked (the router scores all
``n_router_outputs`` experts; picks that fall on experts this chip does
not hold add nothing).  Long sequences are computed a block of query
rows and a group of heads at a time so that they fit beside the weights.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names
(``h{i}_q_a_w`` ...) and shapes because that is the interface the
program takes.  ``int8=True`` is the same forward in int8, the control:
both operands of every matmul rounded to 8 bits, symmetric absmax —
weights per output column, activations per tensor; queries, keys,
values and attention probabilities per head.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256        # query rows attended at a time
HEAD_GROUP = 16      # heads whose queries, keys and values are built at a time
ROW_WINDOW = 1536    # rows of logits one served_gaps call computes
FULL = "full_attention"


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _attn_dims(c: Dict[str, Any], full: bool) -> Dict[str, Any]:
    pre = "" if full else "swa_"
    return dict(
        H=int(c["num_attention_heads" if full else "swa_num_attention_heads"]),
        rq=int(c[pre + "q_lora_rank"]), rk=int(c[pre + "kv_lora_rank"]),
        dn=int(c[pre + "qk_nope_head_dim"]),
        dr=int(c[pre + "qk_rope_head_dim"]), dv=int(c[pre + "v_head_dim"]),
        theta=float(c[pre + "rope_theta"]))


def _is_full(config: Dict[str, Any], layer: int) -> bool:
    return config["layer_types"][layer] == FULL


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32"): ``w`` is the served dtype."""
    c = config
    full = _is_full(c, layer)
    a, h = _attn_dims(c, full), int(c["hidden_size"])
    H = a["H"]
    out = {
        "attn_norm_g": ((h,), "w"), "q_a_w": ((h, a["rq"]), "w"),
        "q_norm_g": ((a["rq"],), "w"),
        "q_b_w": ((a["rq"], H * (a["dn"] + a["dr"])), "w"),
        "kv_a_w": ((h, a["rk"] + a["dr"]), "w"),
        "kv_norm_g": ((a["rk"],), "w"),
        "kv_b_w": ((a["rk"], H * (a["dn"] + a["dv"])), "w"),
        "gate_w": ((h, H), "w"), "o_w": ((H * a["dv"], h), "w"),
        "ffn_norm_g": ((h,), "w"),
    }
    if full:
        Hi, Di = int(c["index_n_heads"]), int(c["index_head_dim"])
        out.update({
            "idx_q_w": ((a["rq"], Hi * Di), "w"), "idx_k_w": ((h, Di), "w"),
            "idx_k_norm_g": ((Di,), "w"), "idx_k_norm_b": ((Di,), "w"),
            "idx_w_w": ((h, Hi), "w"),
        })
    if layer < int(c["first_k_dense_replace"]):
        F = int(c["intermediate_size"])
        out["mlp_gu_w"] = ((h, 2 * F), "w")
        out["mlp_down_w"] = ((F, h), "w")
    else:
        E, I = int(c["n_routed_experts"]), int(c["moe_intermediate_size"])
        R = int(c.get("n_router_outputs", E))
        Is = I * int(c["n_shared_experts"])
        out["router_w"] = ((h, R), "f32")
        out["router_bias"] = ((R,), "f32")
        out["exp_gu_w"] = ((E, 2 * I, h), "w")
        out["exp_down_w"] = ((E, I, h), "w")
        out["shared_gu_w"] = ((h, 2 * Is), "w")
        out["shared_down_w"] = ((Is, h), "w")
    return out


def param_count(config: Dict[str, Any]) -> int:
    """Parameters this configuration holds on the chip."""
    h, V = int(config["hidden_size"]), int(config["vocab_size"])
    n = 2 * V * h + h
    for i in range(int(config["num_hidden_layers"])):
        n += sum(int(np.prod(s)) for s, _ in layer_shapes(config, i).values())
    return n


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device from the seed, a layer to a jitted call
    (one call for 8 GB would hold every tensor's float32 draw at once)."""
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    gains = {"q_b_w": float(init.get("q_gain", 1.0)),
             "exp_down_w": float(init.get("exp_down_gain", 1.0))}

    def draw(key, shapes):
        out = {}
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, dt)
            elif name.endswith("_norm_b"):
                out[name] = jnp.zeros(shape, dt)
            elif name == "router_bias":
                out[name] = 0.01 * jax.random.normal(k, shape, dt)
            else:
                scale = std * gains.get(name, 1.0)
                out[name] = (scale * jax.random.normal(
                    k, shape, jnp.float32)).astype(dt)
        return out

    key = seed_key(seed)
    h, V = int(config["hidden_size"]), int(config["vocab_size"])
    top = {"wte": ((V, h), "w"), "head_w": ((h, V), "w"),
           "norm_f_g": ((h,), "w")}
    params = jax.jit(partial(draw, shapes=top))(jax.random.fold_in(key, 0))
    for i in range(int(config["num_hidden_layers"])):
        layer = jax.jit(partial(draw, shapes=layer_shapes(config, i)))(
            jax.random.fold_in(key, i + 1))
        params.update({f"h{i}_{k}": v for k, v in layer.items()})
    return params


# -- the plain forward --------------------------------------------------------


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


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


def rope_tables(theta: float, dim: int, T: int):
    """cos, sin (T, dim / 2) at positions 0 .. T-1, plain frequencies."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """Half-split pairing (``assumed``): dims (i, i + d/2) rotate."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _selection(x, cq, p, cos, sin, config, int8):
    """The full layers' indexer: for every query the positions it
    attends, (T, T) bool — its ``min(t + 1, index_topk)`` best-scoring
    positions ``s <= t`` by an exact top-k."""
    T = x.shape[0]
    Hi, Di = int(config["index_n_heads"]), int(config["index_head_dim"])
    dr, k = int(config["qk_rope_head_dim"]), min(int(config["index_topk"]), T)
    qi = _mm(cq, p["idx_q_w"], int8).reshape(T, Hi, Di)
    qi = jnp.concatenate(
        [_rope(qi[..., :dr], cos[:, None], sin[:, None]), qi[..., dr:]], -1)
    ki = _layer_norm(_mm(x, p["idx_k_w"], int8), p["idx_k_norm_g"],
                     p["idx_k_norm_b"], float(config["rms_norm_eps"]))
    ki = jnp.concatenate([_rope(ki[:, :dr], cos, sin), ki[:, dr:]], -1)
    w = _mm(x, p["idx_w_w"], int8) * (Hi ** -0.5 * Di ** -0.5)
    if int8:
        qi, ki = _q8(qi, (0, 2)), _q8(ki, None)
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    pos = jnp.arange(T)

    def block(q0):
        qs = jax.lax.dynamic_slice_in_dim(qi, q0, qb, 0)
        ws = jax.lax.dynamic_slice_in_dim(w, q0, qb, 0)

        def head(acc, j):
            s = qs[:, j] @ ki.T                              # (qb, T)
            return acc + ws[:, j, None] * jax.nn.relu(s), None

        scores, _ = jax.lax.scan(
            head, jnp.zeros((qb, T), jnp.float32), jnp.arange(Hi))
        seen = pos[None, :] <= (q0 + jnp.arange(qb))[:, None]
        _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
        picked = jnp.zeros((qb, T), bool).at[
            jnp.arange(qb)[:, None], idx].set(True)
        return picked & seen

    return jax.lax.map(block, jnp.arange(0, T, qb)).reshape(-1, T)[:T]


def _attention(x, p, tables, config, full, int8):
    """Expanded MLA over the whole sequence ``x`` (T, h): under the
    indexer's selection in a full layer, under the window in a sliding
    one.  Returns the layer's attention output (T, h) and the selection
    ((T, T) bool, or None)."""
    a = _attn_dims(config, full)
    T, H, dn, dr, dv = x.shape[0], a["H"], a["dn"], a["dr"], a["dv"]
    h, eps = int(config["hidden_size"]), float(config["rms_norm_eps"])
    cos, sin = tables
    rescale = bool(config["apply_mla_qkv_lora_rescale"])
    sq = (h / a["rq"]) ** 0.5 if rescale else 1.0
    sk = (h / a["rk"]) ** 0.5 if rescale else 1.0
    cq = _rms(_mm(x, p["q_a_w"], int8), p["q_norm_g"], eps) * sq
    w_q = p["q_b_w"].reshape(a["rq"], H, dn + dr)
    ckr = _mm(x, p["kv_a_w"], int8)
    c = _rms(ckr[:, :a["rk"]], p["kv_norm_g"], eps) * sk
    k_r = _rope(ckr[:, a["rk"]:], cos, sin)
    w_kv = p["kv_b_w"].reshape(a["rk"], H, dn + dv)
    scale = (dn + dr) ** -0.5
    window = int(config["sliding_window_size"])
    allowed = _selection(x, cq, p, cos, sin, config, int8) if full else None
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    hg = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    # a sliding layer's query block meets only the keys its window can
    # reach: a slice of qb + window - 1 positions, the window still a mask
    span = T if full else min(T, qb + window - 1)

    def group(h0):
        wg = jax.lax.dynamic_slice_in_dim(w_kv, h0, hg, 1)
        kv = _mm(c, wg.reshape(a["rk"], -1), int8).reshape(T, hg, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (T, hg, dr))], -1)
        v = kv[..., dn:]
        wq = jax.lax.dynamic_slice_in_dim(w_q, h0, hg, 1)
        qg = _mm(cq, wq.reshape(a["rq"], -1), int8).reshape(T, hg, dn + dr)
        qg = jnp.concatenate(
            [qg[..., :dn],
             _rope(qg[..., dn:], cos[:, None], sin[:, None])], -1)
        if int8:
            qg, k, v = (_q8(t, (0, 2)) for t in (qg, k, v))

        def block(q0):
            qs = jax.lax.dynamic_slice_in_dim(qg, q0, qb, 0)
            k0 = jnp.clip(q0 + qb - span, 0, T - span)
            ks = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
            vs = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
            if full:
                ok = jax.lax.dynamic_slice_in_dim(allowed, q0, qb, 0)
            else:
                at = (q0 + jnp.arange(qb))[:, None]
                to = (k0 + jnp.arange(span))[None, :]
                ok = (to <= at) & (to > at - window)
            s = jnp.einsum("qhd,khd->hqk", qs, ks) * scale
            pr = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            if int8:
                pr = _q8(pr, (1, 2))
            return jnp.einsum("hqk,khd->qhd", pr, vs)

        return jax.lax.map(block, jnp.arange(0, T, qb)).reshape(-1, hg, dv)[:T]

    o = jax.lax.map(group, jnp.arange(0, H, hg))            # (H/hg, T, hg, dv)
    o = o.transpose(1, 0, 2, 3).reshape(T, H, dv)
    o = o * jax.nn.sigmoid(_mm(x, p["gate_w"], int8))[:, :, None]
    return _mm(o.reshape(T, H * dv), p["o_w"], int8), allowed


def _moe(x, p, config, int8):
    """Every held expert applied to every token; the gate is zero where
    the router did not pick it.  The router scores every expert of the
    deployment; this chip's part of the result is what is computed."""
    k = int(config["num_experts_per_tok"])
    E = int(config["n_routed_experts"])
    held = jnp.asarray(config.get("held_experts") or list(range(E)))
    s = jax.nn.sigmoid(x @ p["router_w"])
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    picked = jnp.take_along_axis(s, idx, -1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) * float(
        config["routed_scaling_factor"])
    gates = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(g)

    def one(y, j):
        gu = p["exp_gu_w"][j].astype(jnp.float32).T         # (h, 2I)
        dw = p["exp_down_w"][j].astype(jnp.float32)         # (I, h)
        return y + gates[:, held[j], None] * _swiglu(x, gu, dw, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return y + _swiglu(x, p["shared_gu_w"].astype(jnp.float32),
                       p["shared_down_w"].astype(jnp.float32), int8)


@partial(jax.jit, static_argnames=("cfg", "full", "dense", "int8"))
def _layer(x, p, cos, sin, *, cfg, full, dense, int8):
    """One layer on ``x`` (T, h) in float32; also the selection."""
    config = _thawed(cfg)
    experts = {k: p[k] for k in ("exp_gu_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)        # upcast an expert at a time, inside the scan
    eps = float(config["rms_norm_eps"])
    y, picked = _attention(_rms(x, p["attn_norm_g"], eps), p, (cos, sin),
                           config, full, int8)
    x = x + y
    xn = _rms(x, p["ffn_norm_g"], eps)
    if dense:
        return x + _swiglu(xn, p["mlp_gu_w"], p["mlp_down_w"], int8), picked
    return x + _moe(xn, p, config, int8), picked


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    keep = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in config.items() if not isinstance(v, dict)}
    return tuple(sorted(keep.items()))


def _thawed(cfg) -> Dict[str, Any]:
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg}


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, head_w, *, eps, int8):
    return _mm(_rms(x, g.astype(jnp.float32), eps),
               head_w.astype(jnp.float32), int8)


def hidden(params, config, ids, int8: bool = False, rows=None):
    """``(x (T, h) after the last layer, selections)`` for ``ids`` (T,);
    with ``rows`` (a slice) the full layers' selections of those query
    rows, (full layers, rows, T) bool, else None."""
    ids = jnp.asarray(ids, jnp.int32)
    T, cfg = ids.shape[0], _frozen(config)
    tables = {full: rope_tables(a["theta"], a["dr"], T) for full, a in
              ((f, _attn_dims(config, f)) for f in (True, False))}
    picked = []
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            full = _is_full(config, i)
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x, sel = _layer(
                x, layer, *tables[full], cfg=cfg, full=full,
                dense=i < int(config["first_k_dense_replace"]), int8=int8)
            if full and rows is not None:
                picked.append(sel[rows])
    return x, (jnp.stack(picked) if picked else None)


def logits(params, config, ids, int8: bool = False, rows=None,
           selections: bool = False):
    """(B, T, V) float32 logits of ``ids`` (B, T), a sequence at a time;
    with ``rows`` (a slice) only those positions' logits.  With
    ``selections`` also the full layers' selections of those rows, (B,
    full layers, rows, T) bool."""
    out, picked = [], []
    for seq in np.asarray(ids):
        x, sel = hidden(params, config, seq, int8,
                        rows if selections else None)
        if rows is not None:
            x = x[rows]
        with jax.default_matmul_precision("highest"):
            out.append(_head(x, params["norm_f_g"], params["head_w"],
                             eps=float(config["rms_norm_eps"]), int8=int8))
        picked.append(sel)
    if selections:
        return jnp.stack(out), jnp.stack(picked)
    return jnp.stack(out)


# -- what the checks compare ---------------------------------------------------


@jax.jit
def _gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the row's best."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_window(prompt_len: int, n_served: int, pad_to: int):
    """The fixed window of rows that holds a request's served positions
    and where they lie in it: ``(rows, mine)``, two slices."""
    win = min(ROW_WINDOW, pad_to)
    if n_served > win:
        raise ValueError(f"{n_served} served tokens exceed the {win}-row window")
    w0 = min(prompt_len - 1, pad_to - win)
    return (slice(w0, w0 + win),
            slice(prompt_len - 1 - w0, prompt_len - 1 - w0 + n_served))


def served_gaps(params, config, seq, prompt_len: int, n_served: int,
                pad_to: int, control: bool = False, selections: bool = False):
    """Teacher-force ``seq`` (prompt + served tokens, 1-D) through the
    reference and return, for each served token, the gap by which its
    reference logit lies below that position's best (0 = the reference's
    own greedy token).  With ``control=True`` the tokens judged are the
    ones the int8 forward puts first at the same positions.  ``pad_to``
    fixes the compiled length (causal masking keeps the padding out of
    every real row); the head runs over one fixed window of rows that
    holds the served positions.  With ``selections`` returns ``(gaps,
    picked, judged)``: the rows the full layers' queries at the served
    positions attend, (full layers, n_served, pad_to) bool — the float32
    forward's and, with ``control``, the int8 forward's (else None)."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    rows, mine = served_window(prompt_len, n_served, pad_to)

    def forward(int8):
        out = logits(params, config, ids[None], int8=int8, rows=rows,
                     selections=selections)
        if not selections:
            return out[0][mine], None
        return out[0][0][mine], np.asarray(out[1][0][:, mine])

    ref, picked = forward(False)
    judged = None
    if control:
        low, judged = forward(True)
        toks = jnp.argmax(low, axis=-1).astype(jnp.int32)
    else:
        toks = jnp.asarray(seq[prompt_len:prompt_len + n_served], jnp.int32)
    gaps = np.asarray(_gaps(ref, toks), np.float64)
    return (gaps, picked, judged) if selections else gaps

"""Ouro-2.6B's looped stack: the benchmark's weights, its plain float32
reference, and the controls.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/ouro-2.6b-serve.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``::

    x = E[ids]
    for u in 0 .. total_ut_steps - 1:        # the SAME layers every pass
        for l in 0 .. L - 1:                 # a lax.scan over stacked layers
            n = rms(x, g1_l); q, k, v = n Wq_l, n Wk_l, n Wv_l   (rope: q, k)
            a = softmax(q k^T / sqrt(hd)) v  # this pass's own keys, values
            x = x + rms(a Wo_l, g2_l)        # a norm before AND after
            m = (silu(n' Wg_l) * (n' Wu_l)) Wd_l, n' = rms(x, g3_l)
            x = x + rms(m, g4_l)
        h_u = rms(x, g_f); x = h_u           # the final norm closes a pass
        lam_u = sigmoid(h_u . w_e + b_e)     # the exit gate
    logits = h_last W_head

No kernel, no cache, no paging, no batching: full causal attention, a
block of query rows at a time.  The layers' weights are stacked once, in
the dtype they are served in, and upcast a layer at a time inside the
scan, so the float32 forward fits beside the served weights and nothing
else.  ``total_ut_steps`` is an argument: the forward with a pass left
out is one of the controls.

Weights are made here from ``--seed``, a layer to a jitted call, under
the program's flat names (``h{i}_q_w`` ...) and shapes because that is
the interface the program takes: the SwiGLU's gate and up-projection side
by side ``(h, 2 I)``.  The init is N(0, std), norm gains 1, the gate's
bias 0, with the departures the config's ``init`` group states: every
``<name>_gain`` multiplies the draw of ``<name>_w`` (or of ``<name>``:
``wte_gain``), and a norm gain named there (``attn_post_g``) starts at
the value given instead of 1.

Controls (``served_gaps(control=...)``): ``"int8"`` — the same forward
with both operands of every matmul rounded to 8 bits, symmetric absmax
(weights per output column, activations per tensor; queries, keys,
values and attention probabilities per head); ``"passes_3"`` — the
float32 forward at ``total_ut_steps - 1`` passes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512        # query rows attended at a time
LAYER_NAMES = ("attn_norm_g", "q_w", "k_w", "v_w", "o_w", "attn_post_g",
               "ffn_norm_g", "mlp_gu_w", "mlp_down_w", "ffn_post_g")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        h=int(c["hidden_size"]), H=int(c["num_attention_heads"]),
        Hkv=int(c["num_key_value_heads"]), hd=int(c["head_dim"]),
        F=int(c["intermediate_size"]), L=int(c["num_hidden_layers"]),
        V=int(c["vocab_size"]), U=int(c["total_ut_steps"]))


def layer_shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    d = _dims(config)
    h, q, kv = d["h"], d["H"] * d["hd"], d["Hkv"] * d["hd"]
    return {
        "attn_norm_g": (h,), "q_w": (h, q), "k_w": (h, kv), "v_w": (h, kv),
        "o_w": (q, h), "attn_post_g": (h,), "ffn_norm_g": (h,),
        "mlp_gu_w": (h, 2 * d["F"]), "mlp_down_w": (d["F"], h),
        "ffn_post_g": (h,),
    }


def top_shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    d = _dims(config)
    return {"wte": (d["V"], d["h"]), "head_w": (d["h"], d["V"]),
            "norm_f_g": (d["h"],), "exit_w": (d["h"],), "exit_b": (1,)}


def param_count(config: Dict[str, Any]) -> int:
    return (sum(math.prod(s) for s in top_shapes(config).values())
            + _dims(config)["L"] * sum(
                math.prod(s) for s in layer_shapes(config).values()))


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device from the seed, a layer to a jitted call
    (one call would hold every tensor's float32 draw at once)."""
    d = _dims(config)
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    gains = {k[:-len("_gain")]: float(v) for k, v in init.items()
             if k.endswith("_gain")}

    def draw(key, shapes):
        out = {}
        for k, (name, shape) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            if name.endswith("_g"):
                out[name] = jnp.full(shape, float(init.get(name, 1.0)), dtype)
            elif name.endswith("_b"):
                out[name] = jnp.zeros(shape, dtype)
            else:
                gain = gains.get(name.removesuffix("_w"), 1.0)
                out[name] = (std * gain * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
        return out

    key = seed_key(seed)
    params = jax.jit(partial(draw, shapes=top_shapes(config)))(
        jax.random.fold_in(key, 0))
    one = jax.jit(partial(draw, shapes=layer_shapes(config)))
    for i in range(d["L"]):
        layer = one(jax.random.fold_in(key, i + 1))
        params.update({f"h{i}_{k}": v for k, v in layer.items()})
    return params


def stack_layers(params: Dict[str, Any],
                 config: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The layers' weights stacked for the scan, ``{name: (L, ...)}``, in
    the dtype they are served in (one more copy of them: the engine's
    pools have gone by the time this is made)."""
    L = _dims(config)["L"]
    return {k: jnp.stack([params[f"h{i}_{k}"] for i in range(L)])
            for k in LAYER_NAMES}


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


def rope_tables(config: Dict[str, Any], T: int):
    """cos, sin (T, hd / 2) at positions 0 .. T-1: plain rotary at
    ``rope_theta`` over every value of a head."""
    hd = int(config["head_dim"])
    inv = 1.0 / float(config["rope_theta"]) ** (
        np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """``x`` (T, heads, hd): half-split pairing (``assumed``)."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(x, p, cos, sin, d, int8):
    """Causal attention over the whole sequence ``x`` (T, h), a block of
    query rows against every key at a time."""
    T, H, Hkv, hd = x.shape[0], d["H"], d["Hkv"], d["hd"]
    q = _rope(_mm(x, p["q_w"], int8).reshape(T, H, hd), cos, sin)
    k = _rope(_mm(x, p["k_w"], int8).reshape(T, Hkv, hd), cos, sin)
    v = _mm(x, p["v_w"], int8).reshape(T, Hkv, hd)
    if int8:
        q, k, v = (_q8(t, (0, 2)) for t in (q, k, v))
    q = q.reshape(T, Hkv, H // Hkv, hd)
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    k_pos = jnp.arange(T)[None, :]

    def block(q0):
        qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
        ok = k_pos <= (q0 + jnp.arange(qb))[:, None]
        s = jnp.einsum("qhgd,khd->hgqk", qs, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        if int8:
            pr = _q8(pr, (2, 3))
        return jnp.einsum("hgqk,khd->qhgd", pr, v)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H * hd)
    return _mm(o, p["o_w"], int8)


def _layer(x, p, cos, sin, d, eps, int8):
    """One layer on the residual stream ``x`` (T, h) in float32: each
    sublayer between two norms."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    a = _attention(_rms(x, p["attn_norm_g"], eps), p, cos, sin, d, int8)
    x = x + _rms(a, p["attn_post_g"], eps)
    g, u = jnp.split(_mm(_rms(x, p["ffn_norm_g"], eps), p["mlp_gu_w"], int8),
                     2, axis=-1)
    m = _mm(jax.nn.silu(g) * u, p["mlp_down_w"], int8)
    return x + _rms(m, p["ffn_post_g"], eps)


@partial(jax.jit, static_argnames=("dims", "eps", "int8"))
def _one_pass(x, layers, norm_f_g, exit_w, exit_b, cos, sin, *, dims, eps,
              int8):
    """The stacked layers scanned over ``x`` (T, h), then the pass's end:
    ``(h_u, lam_u)``."""
    d = dict(dims)

    def body(x, p):
        return _layer(x, p, cos, sin, d, eps, int8), None

    x, _ = jax.lax.scan(body, x, layers)
    h_u = _rms(x, norm_f_g.astype(jnp.float32), eps)
    lam = jax.nn.sigmoid(
        h_u @ exit_w.astype(jnp.float32) + exit_b.astype(jnp.float32)[0])
    return h_u, lam


def forward_passes(params, config, ids, total_ut_steps: Optional[int] = None,
                   int8: bool = False, layers=None):
    """Of one sequence ``ids`` (T,): ``(h (U, T, h), lam (U, T))`` — every
    pass's final-normed state and exit-gate probability, float32.
    ``layers``: :func:`stack_layers` of ``params``, where the caller
    keeps it across calls."""
    d = _dims(config)
    U = d["U"] if total_ut_steps is None else int(total_ut_steps)
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rope_tables(config, ids.shape[0])
    if layers is None:
        layers = stack_layers(params, config)
    hs, lams = [], []
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for _ in range(U):
            x, lam = _one_pass(
                x, layers, params["norm_f_g"], params["exit_w"],
                params["exit_b"], cos, sin, dims=tuple(sorted(d.items())),
                eps=float(config["rms_norm_eps"]), int8=int8)
            hs.append(x)
            lams.append(lam)
    return jnp.stack(hs), jnp.stack(lams)


@partial(jax.jit, static_argnames=("int8",))
def _head(x, head_w, *, int8):
    return _mm(x, head_w.astype(jnp.float32), int8)


def forward(params, config, ids, total_ut_steps: Optional[int] = None,
            int8: bool = False, rows=None, layers=None):
    """Of one sequence ``ids`` (T,): ``(logits (T, V) float32, h (U, T,
    h), lam (U, T))``; with ``rows`` (a slice) only those positions'
    logits."""
    hs, lams = forward_passes(params, config, ids, total_ut_steps, int8,
                              layers)
    x = hs[-1] if rows is None else hs[-1][rows]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["head_w"], int8=int8), hs, lams


def exit_distribution(lam):
    """``p (U, ...)`` from ``lam (U, ...)``: ``p_0 = lam_0``, ``p_u =
    lam_u prod_{j<u} (1 - lam_j)``, the last pass taking what is left."""
    lam = np.asarray(lam, np.float64)
    survive = np.cumprod(1.0 - lam, axis=0)
    before = np.concatenate([np.ones_like(lam[:1]), survive[:-1]])
    p = lam * before
    p[-1] = before[-1]
    return p


# -- what the checks compare ---------------------------------------------------


@jax.jit
def _gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the row's best."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_gaps(params, config, seq, prompt_len: int, n_served: int,
                pad_to: int, control: Any = False, layers=None):
    """Teacher-force ``seq`` (prompt + served tokens, 1-D) through the
    reference and return, for each served token, the gap by which its
    reference logit lies below that position's best (0 = the reference's
    own greedy token).  With ``control`` the tokens judged are the ones a
    lesser forward puts first at the same positions: ``"int8"`` (or
    ``True``) the int8 forward's, ``"passes_3"`` the float32 forward's
    with its last pass left out.  ``pad_to`` fixes the compiled length
    (causal masking keeps the padding out of every real row)."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    rows = slice(prompt_len - 1, prompt_len - 1 + n_served)
    ref = forward(params, config, ids, rows=rows, layers=layers)[0]
    if control:
        kw = ({"total_ut_steps": int(config["total_ut_steps"]) - 1}
              if control == "passes_3" else {"int8": True})
        low = forward(params, config, ids, rows=rows, layers=layers, **kw)[0]
        toks = jnp.argmax(low, axis=-1).astype(jnp.int32)
    else:
        toks = jnp.asarray(seq[prompt_len:prompt_len + n_served], jnp.int32)
    return np.asarray(_gaps(ref, toks), np.float64)

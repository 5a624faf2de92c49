"""LFM2-24B-A2B's hybrid block: the benchmark's weights, its plain
float32 reference, and the controls.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/lfm2-24b-a2b-serve.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.  Every layer is an operator
and a feed-forward on a plain residual stream, ``h = h + Op(rms(h))``,
``h = h + FFN(rms(h))``, ``rms`` the plain ``x rsqrt(mean x^2 + eps) w``:

* a ``conv`` operator: ``[B | C | z] = x W_in``; ``u = B * z``; a causal
  depthwise convolution of ``conv_L_cache`` taps without bias, as that
  many SHIFTED PRODUCTS over the whole sequence — rows before the
  sequence are zero, no state in or out; ``(C * v) W_out``.  No
  activation anywhere in it.
* a ``full_attention`` operator: grouped-query attention, ``q`` and ``k``
  each through an ``rms`` over a head's values with a learned weight,
  then rotary over the whole head (``rope_theta``, default type,
  half-split pairing), causal, scale ``head_dim ** -0.5``, a block of
  query rows at a time.
* the feed-forward: ``(silu(x W_1) * x W_3) W_2`` in the first
  ``num_dense_layers``; elsewhere ``s = sigmoid(x W_g)``, the
  ``num_experts_per_tok`` largest of ``s + expert_bias``, their weights
  ``s`` (without the bias) over the sum of the picked (nothing added to
  the sum: ``assumed``), times ``routed_scaling_factor``; EVERY expert
  applied to every token by a loop and kept, by a mask, where the router
  picked it.  No shared expert.  The head is tied to the embedding.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names and shapes
because that is the interface the program takes (the engine is handed
THESE arrays: the chip never holds a second copy).  The init is N(0, std)
with the departures the config's ``init`` group states — every
``<name>_gain`` multiplies the draw of ``<name>_w``, or IS the gain
``<name>_g`` starts at — and, with ``init.balance_tokens``, expert biases
BALANCED (:func:`balance_routers`): the bias exists to equalise the
experts' load and a trained model's does; left at random it cannot.

Controls (``served_gaps(control=...)``): ``True`` / ``"int8"`` — both
operands of every matmul rounded to 8 bits, symmetric absmax, weights per
output column, activations per tensor, attention operands per head;
``"conv_state_lost"`` — the float32 forward with every conv operator's
carried inputs zeroed at every chunk boundary of the prompt and every
``LOST_EVERY`` decoded tokens: what a program that loses a slot's state
computes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024          # query rows attention computes at a time
ROW_WINDOW = 1024       # rows of logits the head computes at a time
LOST_EVERY = 64         # decoded tokens between two losses of the control


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, Any]:
    h, Hq = int(c["hidden_size"]), int(c["num_attention_heads"])
    return dict(
        h=h, L=int(c["num_hidden_layers"]), V=int(c["vocab_size"]),
        K=int(c["conv_L_cache"]), Hq=Hq, Hkv=int(c["num_key_value_heads"]),
        hd=int(c.get("head_dim") or h // Hq), E=int(c["num_experts"]),
        I=int(c["moe_intermediate_size"]), F=int(c["intermediate_size"]),
        k=int(c["num_experts_per_tok"]), nd=int(c["num_dense_layers"]),
    )


def _is_conv(config: Dict[str, Any], layer: int) -> bool:
    kind = config["layer_types"][layer]
    if kind not in ("conv", "full_attention"):
        raise ValueError(f"layer {layer} is {kind!r}")
    return kind == "conv"


def _is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < int(config["num_dense_layers"])


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32"): ``w`` is the served dtype."""
    d = _dims(config)
    h = d["h"]
    out = {"op_norm_g": ((h,), "w"), "ffn_norm_g": ((h,), "w")}
    if _is_conv(config, layer):
        out.update({"in_w": ((h, 3 * h), "w"), "conv_w": ((h, d["K"]), "w"),
                    "out_w": ((h, h), "w")})
    else:
        q, kv = d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
        out.update({"q_w": ((h, q), "w"), "k_w": ((h, kv), "w"),
                    "v_w": ((h, kv), "w"), "o_w": ((q, h), "w"),
                    "q_norm_g": ((d["hd"],), "w"),
                    "k_norm_g": ((d["hd"],), "w")})
    if _is_dense(config, layer):
        out.update({"mlp_gu_w": ((h, 2 * d["F"]), "w"),
                    "mlp_down_w": ((d["F"], h), "w")})
    else:
        out.update({"router_w": ((h, d["E"]), "f32"),
                    "router_bias": ((d["E"],), "f32"),
                    "exp_gu_w": ((d["E"], 2 * d["I"], h), "w"),
                    "exp_down_w": ((d["E"], d["I"], h), "w")})
    return out


def param_count(config: Dict[str, Any]) -> int:
    d = _dims(config)
    return (d["V"] * d["h"] + d["h"] + sum(
        math.prod(shape) for i in range(d["L"])
        for shape, _ in layer_shapes(config, i).values()))


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
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g"):
                out[name] = jnp.full(shape, gains.get(name[:-2], 1.0), dt)
            elif name == "router_bias":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            else:
                out[name] = (std * gains.get(name[:-2], 1.0)
                             * jax.random.normal(k, shape, jnp.float32)
                             ).astype(dt)
        return out

    key = seed_key(seed)
    top = {"wte": ((d["V"], d["h"]), "w"), "norm_f_g": ((d["h"],), "w")}
    params = jax.jit(partial(draw, shapes=top))(jax.random.fold_in(key, 0))
    for i in range(d["L"]):
        layer = jax.jit(partial(draw, shapes=layer_shapes(config, i)))(
            jax.random.fold_in(key, i + 1))
        params.update({f"h{i}_{k}": v for k, v in layer.items()})
    return balance_routers(params, config, seed)


@partial(jax.jit, static_argnames=("k", "steps"))
def _balanced_bias(scores, *, k, steps=300):
    """The expert bias that the aux-loss-free update — ``b_e`` down where
    expert ``e`` got more than its share of the picks, up where less —
    leaves after ``steps`` steps of a step size falling from 0.05 to
    0.0005, for the router scores ``scores`` (T, E) of one batch."""
    T, E = scores.shape

    def step(t, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b - 0.05 * 0.01 ** (t / steps) * jnp.sign(load - T * k / E)

    return jax.lax.fori_loop(0, steps, step, jnp.zeros((E,), jnp.float32))


def balance_routers(params, config, seed):
    """``params`` with every expert layer's ``router_bias`` balanced on
    ``init.balance_tokens`` tokens drawn from the seed (0 or absent:
    left as drawn): the float32 forward of that one sequence, layer by
    layer, each router's bias set from the scores its own input gives
    before the layer's feed-forward is applied with it."""
    n = int(config.get("init", {}).get("balance_tokens", 0))
    if not n:
        return params
    d, cfg = _dims(config), _frozen(config)
    ids = jax.random.randint(jax.random.fold_in(seed_key(seed), 1 << 20),
                             (n,), 1, d["V"])
    cos, sin = rope_tables(config, n)
    since = jnp.arange(n)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x = _operator(x, layer, cos, sin, since, cfg=cfg, layer=i,
                          int8=False)
            if "router_w" in layer:
                xn = _rms(x, layer["ffn_norm_g"].astype(jnp.float32),
                          float(config["norm_eps"]))
                layer["router_bias"] = params[f"h{i}_router_bias"] = (
                    _balanced_bias(jax.nn.sigmoid(xn @ layer["router_w"]),
                                   k=d["k"]))
            x = _feed_forward(x, layer, cfg=cfg, layer=i, int8=False)
    return params


# -- the plain forward --------------------------------------------------------


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


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
    """cos, sin (T, head_dim / 2) at positions 0 .. T-1: plain rotary."""
    rp = config["rope_parameters"]
    if rp.get("rope_type", "default") != "default":
        raise ValueError("the reference computes plain rotary positions")
    hd = _dims(config)["hd"]
    inv = 1.0 / float(rp["rope_theta"]) ** (
        np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """``x`` (T, heads, hd): the whole head rotates, half-split pairing."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _conv_op(x, p, since, config, int8):
    """The gated short convolution over the whole sequence ``x`` (T, h):
    ``conv_L_cache`` shifted products.  ``since`` (T,): tokens since the
    carried inputs were last lost — the position itself in the sound
    forward (rows before the sequence are zero); an input ``d`` back is
    seen where ``since >= d``."""
    K, T = _dims(config)["K"], x.shape[0]
    B, C, z = jnp.split(_mm(x, p["in_w"], int8), 3, axis=-1)
    u = B * z
    pad = jnp.pad(u, ((K - 1, 0), (0, 0)))
    v = jnp.zeros_like(u)
    for j in range(K):          # tap j meets the input K - 1 - j tokens back
        tap = pad[j:j + T] * p["conv_w"][:, j]
        v = v + jnp.where((since >= K - 1 - j)[:, None], tap, 0.0)
    return _mm(C * v, p["out_w"], int8)


def _attention(x, p, cos, sin, config, int8):
    """Grouped-query attention over the whole sequence ``x`` (T, h),
    causal, a block of query rows at a time; q and k normed a head, then
    rotated."""
    d = _dims(config)
    T, H, Hkv, hd = x.shape[0], d["Hq"], d["Hkv"], d["hd"]
    eps = float(config["norm_eps"])
    q = _rope(_rms(_mm(x, p["q_w"], int8).reshape(T, H, hd),
                   p["q_norm_g"], eps), cos, sin)
    k = _rope(_rms(_mm(x, p["k_w"], int8).reshape(T, Hkv, hd),
                   p["k_norm_g"], eps), cos, sin)
    v = _mm(x, p["v_w"], int8).reshape(T, Hkv, hd)
    if int8:
        q, k, v = (_q8(t, (0, 2)) for t in (q, k, v))
    q = q.reshape(T, Hkv, H // Hkv, hd)
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(q0):
        qs = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
        ok = jnp.arange(T)[None, :] <= (q0 + jnp.arange(qb))[:, None]
        s = jnp.einsum("qhgd,khd->hgqk", qs, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        if int8:
            pr = _q8(pr, (2, 3))
        return jnp.einsum("hgqk,khd->qhgd", pr, v)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, H * hd)
    return _mm(o, p["o_w"], int8)


def _moe(x, p, config, int8):
    """Every expert applied to every token; the gate is zero where the
    router did not pick it."""
    d = _dims(config)
    s = jax.nn.sigmoid(x @ p["router_w"])
    _, idx = jax.lax.top_k(s + p["router_bias"], d["k"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / g.sum(-1, keepdims=True) * float(config["routed_scaling_factor"])
    gates = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(g)

    def one(y, e):
        gu = p["exp_gu_w"][e].astype(jnp.float32).T     # (h, 2I)
        dw = p["exp_down_w"][e].astype(jnp.float32)     # (I, h)
        return y + gates[:, e, None] * _swiglu(x, gu, dw, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d["E"]))
    return y


def _f32(p):
    """A layer's weights in float32, its experts as they are served: an
    expert at a time is upcast inside the loop over them."""
    experts = {k: p[k] for k in ("exp_gu_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)
    return p


@partial(jax.jit, static_argnames=("cfg", "layer", "int8"))
def _operator(x, p, cos, sin, since, *, cfg, layer, int8):
    """``x + Op(rms(x))`` on the residual stream ``x`` (T, h) in float32."""
    config, p = _thawed(cfg), _f32(p)
    xn = _rms(x, p["op_norm_g"], float(config["norm_eps"]))
    if _is_conv(config, layer):
        return x + _conv_op(xn, p, since, config, int8)
    return x + _attention(xn, p, cos, sin, config, int8)


@partial(jax.jit, static_argnames=("cfg", "layer", "int8"))
def _feed_forward(x, p, *, cfg, layer, int8):
    """``x + FFN(rms(x))``."""
    config, p = _thawed(cfg), _f32(p)
    xn = _rms(x, p["ffn_norm_g"], float(config["norm_eps"]))
    if _is_dense(config, layer):
        return x + _swiglu(xn, p["mlp_gu_w"], p["mlp_down_w"], int8)
    return x + _moe(xn, p, config, int8)


_NESTED = ("rope_parameters", "layer_types")


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    import json

    keep = {k: v for k, v in config.items()
            if not isinstance(v, (dict, list))}
    for k in _NESTED:
        keep[k] = json.dumps(config[k], sort_keys=True)
    return tuple(sorted(keep.items()))


def _thawed(cfg) -> Dict[str, Any]:
    import json

    return {k: json.loads(v) if k in _NESTED else v for k, v in cfg}


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, wte, *, eps, int8):
    return _mm(_rms(x, g.astype(jnp.float32), eps),
               wte.astype(jnp.float32).T, int8)


def lost_since(T: int, prompt_len: int, chunk: int,
               every: int = LOST_EVERY) -> np.ndarray:
    """``since`` (T,) of the ``conv_state_lost`` control: the carried
    inputs are lost where a chunk of the prompt begins and before every
    ``every``-th decoded token."""
    t = np.arange(T)
    lost = np.where(t < prompt_len, t % chunk == 0,
                    (t - prompt_len) % every == 0) & (t != prompt_len)
    lost[0] = True
    return t - np.maximum.accumulate(np.where(lost, t, 0))


def hidden(params, config, ids, int8: bool = False, since=None):
    """The residual stream (T, h) after the last layer for ``ids`` (T,).
    ``since`` (T,): tokens since the conv operators' carried inputs were
    last lost (None: never — the position itself)."""
    d = _dims(config)
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rope_tables(config, ids.shape[0])
    since = jnp.asarray(
        np.arange(ids.shape[0]) if since is None else since, jnp.int32)
    cfg = _frozen(config)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x = _operator(x, layer, cos, sin, since, cfg=cfg, layer=i,
                          int8=int8)
            x = _feed_forward(x, layer, cfg=cfg, layer=i, int8=int8)
    return x


def head_logits(params, config, x, int8: bool = False):
    """(rows, V) float32 logits of residual rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["norm_f_g"], params["wte"],
                     eps=float(config["norm_eps"]), int8=int8)


def logits(params, config, ids, int8: bool = False, rows=None, since=None):
    """(B, T, V) float32 logits of ``ids`` (B, T), a sequence at a time;
    with ``rows`` (a slice) only those positions' logits."""
    out = []
    for seq in np.asarray(ids):
        x = hidden(params, config, seq, int8, since)
        out.append(head_logits(params, config,
                               x if rows is None else x[rows], int8))
    return jnp.stack(out)


# -- what the checks compare ---------------------------------------------------


def control_how(control: Any, config, T: int, prompt_len: int):
    """What :func:`hidden` is given for a control."""
    if control in (True, "int8"):
        return dict(int8=True)
    if control == "conv_state_lost":
        return dict(since=lost_since(
            T, prompt_len, int(config["engine"]["chunk_tokens"])))
    raise ValueError(f"no control {control!r}")


@jax.jit
def _gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the row's best."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_gaps(params, config, seq, prompt_len: int, n_served: int,
                pad_to: int, control: Any = False):
    """Teacher-force ``seq`` (prompt + served tokens, 1-D) through the
    reference and return, for each served token, the gap by which its
    reference logit lies below that position's best (0 = the reference's
    own greedy token).  With a ``control`` the tokens judged are the ones
    that forward puts first at the same positions.  ``pad_to`` fixes the
    compiled length (a causal convolution and a causal mask both keep the
    padding out of every real row); the head runs over windows of
    ``ROW_WINDOW`` rows that cover the served positions."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    x = hidden(params, config, ids)
    low = None
    if control:
        how = control_how(control, config, pad_to, prompt_len)
        low = hidden(params, config, ids, **how)
        int8 = bool(how.get("int8"))
    win = min(ROW_WINDOW, pad_to)
    first, out = prompt_len - 1, []
    for lo in range(first, first + n_served, win):
        w0 = min(lo, pad_to - win)          # one compiled window shape
        mine = slice(lo - w0, min(lo + win, first + n_served) - w0)
        ref = head_logits(params, config, x[w0:w0 + win])[mine]
        if low is None:
            toks = jnp.asarray(
                seq[lo + 1:lo + 1 + (mine.stop - mine.start)], jnp.int32)
        else:
            toks = jnp.argmax(head_logits(
                params, config, low[w0:w0 + win], int8)[mine],
                axis=-1).astype(jnp.int32)
        out.append(np.asarray(_gaps(ref, toks), np.float64))
    return np.concatenate(out)

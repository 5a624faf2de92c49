"""NVIDIA-Nemotron-3-Nano's hybrid stack: the benchmark's weights, its
plain float32 reference, and the lower-precision controls.

Nothing here imports the program.  The forward pass is the architecture
as ``configs/nemotron-3-nano-ep2.json`` states it (the published
``config.json`` plus the choices listed under ``assumed``), in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.  Every layer is ONE
sublayer, ``x = x + f(RMSNorm(x))``, ``f`` by the letter of
``hybrid_override_pattern``:

* ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; a causal depthwise
  convolution of ``conv_kernel`` taps with bias over ``xBC`` and
  ``silu``; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  (x) B_t``, ``y_t = h_t C_t + D x_t`` as a plain ``lax.scan`` over the
  tokens — no chunked form, no state handed in or out; ``silu(z)`` gates
  ``y`` BEFORE an RMSNorm over groups of ``d_inner / n_groups``; ``W_out``.
* ``*``, grouped-query attention, causal, full, with NO position
  embedding of any kind.
* ``E``: sigmoid router scores, the ``num_experts_per_tok`` largest of
  ``scores + bias``, the picked scores renormalised and scaled; every
  HELD expert ``relu(x W_u)^2 W_d`` applied to every token and kept, by a
  mask, where the router picked it (a pick on an expert this chip does
  not hold adds nothing, as in the program); plus the shared expert.

Departures from the published implementation, beyond ``assumed``: none
in the mathematics; ``d_inner`` is ``mamba_num_heads x mamba_head_dim``;
``time_step_limit`` is (0, inf), so ``dt`` is not clamped.

Weights are made here from ``--seed``, a layer to a jitted call, in the
dtype they are served in, under the program's flat names and shapes
because that is the interface the program takes (expert matrices ``(E, I,
h)`` both).  The init is N(0, std) with the departures the config's
``init`` group states — every ``<name>_gain`` multiplies the draw of
``<name>_w`` — and Mamba-2's own draws for the recurrence: ``A`` uniform
in [1, 16], ``dt_bias`` the inverse softplus of a log-uniform step in
[``time_step_min``, ``time_step_max``] floored at ``time_step_floor``,
``D`` = 1.  With ``init.balance_tokens`` the routers' correction biases
are then BALANCED (:func:`balance_routers`): ``noaux_tc``'s bias exists to
equalise the experts' load and a trained model's does; left at random it
cannot, and a ``relu^2`` stack's residual has a direction common to every
token that piles the picks on a few experts.

Controls (``served_gaps(control=...)``): ``True`` / ``"int8"`` — both
operands of every matmul rounded to 8 bits, symmetric absmax, weights per
output column, activations per tensor, attention operands per head;
``"state_bf16"`` — the float32 forward with every mixer's state rounded to
bfloat16's 8 exponent and 7 mantissa bits after every token
(``lax.reduce_precision``: a convert to bfloat16 and back is what XLA's
excess-precision rule may elide on the TPU); ``"state_reset"`` — with
every mixer's state (and its convolution's memory) zeroed every
``reset_every`` tokens: what a program that loses the state between two
chunks computes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024          # query rows attention computes at a time
ROW_WINDOW = 1024       # rows of logits one served_gaps call computes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dims(c: Dict[str, Any]) -> Dict[str, Any]:
    H, P = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    G, N = int(c["n_groups"]), int(c["ssm_state_size"])
    return dict(
        h=int(c["hidden_size"]), L=int(c["num_hidden_layers"]),
        V=int(c["vocab_size"]), H=H, P=P, G=G, N=N, di=H * P,
        W=H * P + 2 * G * N, K=int(c["conv_kernel"]),
        Hq=int(c["num_attention_heads"]), Hkv=int(c["num_key_value_heads"]),
        hd=int(c["head_dim"]), E=int(c["n_routed_experts"]),
        R=int(c.get("n_router_outputs", c["n_routed_experts"])),
        I=int(c["moe_intermediate_size"]),
        Is=int(c["moe_shared_expert_intermediate_size"]),
        k=int(c["num_experts_per_tok"]),
    )


def layer_shapes(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Local name -> (shape, "w" | "f32"): ``w`` is the served dtype."""
    d = _dims(config)
    h = d["h"]
    out = {"norm_g": ((h,), "w")}
    kind = config["hybrid_override_pattern"][layer]
    if kind == "M":
        out.update({
            "in_w": ((h, d["di"] + d["W"] + d["H"]), "w"),
            "conv_w": ((d["W"], d["K"]), "w"), "conv_b": ((d["W"],), "w"),
            "dt_bias": ((d["H"],), "f32"), "a_log": ((d["H"],), "f32"),
            "d_skip": ((d["H"],), "f32"),
            "mnorm_g": ((d["di"],), "w"), "out_w": ((d["di"], h), "w")})
    elif kind == "*":
        q, kv = d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
        out.update({"q_w": ((h, q), "w"), "k_w": ((h, kv), "w"),
                    "v_w": ((h, kv), "w"), "o_w": ((q, h), "w")})
    elif kind == "E":
        out.update({
            "router_w": ((h, d["R"]), "f32"), "router_bias": ((d["R"],), "f32"),
            "exp_up_w": ((d["E"], d["I"], h), "w"),
            "exp_down_w": ((d["E"], d["I"], h), "w"),
            "shared_up_w": ((h, d["Is"]), "w"),
            "shared_down_w": ((d["Is"], h), "w")})
    else:
        raise ValueError(f"layer {layer} is {kind!r}, not one of M, E, *")
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
    a_lo, a_hi = init.get("a_range", (1.0, 16.0))
    t_lo, t_hi = float(config["time_step_min"]), float(config["time_step_max"])
    t_floor = float(config["time_step_floor"])

    def draw(key, shapes):
        out = {}
        for k, (name, (shape, kind)) in zip(
                jax.random.split(key, len(shapes)), sorted(shapes.items())):
            dt = dtype if kind == "w" else jnp.float32
            if name.endswith("_g") or name == "d_skip":
                out[name] = jnp.ones(shape, dt)
            elif name == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, float(a_lo), float(a_hi)))
            elif name == "dt_bias":
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(t_lo), math.log(t_hi))),
                    t_floor)
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif name == "router_bias":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
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
    return balance_routers(params, config, seed)


@partial(jax.jit, static_argnames=("k", "steps"))
def _balanced_bias(scores, *, k, steps=300):
    """The correction bias that the aux-loss-free update — ``b_e`` down
    where expert ``e`` got more than its share of the picks, up where
    less — leaves after ``steps`` steps of a step size falling from 0.05
    to 0.0005, for the router scores ``scores`` (T, R) of one batch."""
    T, R = scores.shape

    def step(t, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((R,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b - 0.05 * 0.01 ** (t / steps) * jnp.sign(load - T * k / R)

    return jax.lax.fori_loop(0, steps, step, jnp.zeros((R,), jnp.float32))


def balance_routers(params, config, seed):
    """``params`` with every expert layer's ``router_bias`` balanced on
    ``init.balance_tokens`` tokens drawn from the seed (0 or absent:
    left as drawn): the float32 forward of that one sequence, layer by
    layer, each router's bias set from the scores its own input gives
    before the layer is applied with it."""
    n = int(config.get("init", {}).get("balance_tokens", 0))
    if not n:
        return params
    d, cfg = _dims(config), _frozen(config)
    ids = jax.random.randint(jax.random.fold_in(seed_key(seed), 1 << 20),
                             (n,), 1, d["V"])
    eps = float(config["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            if "router_w" in layer:
                xn = _rms(x, layer["norm_g"].astype(jnp.float32), eps)
                layer["router_bias"] = params[f"h{i}_router_bias"] = (
                    _balanced_bias(jax.nn.sigmoid(xn @ layer["router_w"]),
                                   k=d["k"]))
            x = _layer(x, layer, cfg=cfg, layer=i, int8=False,
                       state_bits=None, reset_every=0)
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


def _relu2(x, up_w, down_w, int8):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up_w, int8))), down_w, int8)


def _mixer(u, p, config, int8, state_bits, reset_every):
    """A Mamba-2 mixer over the whole sequence ``u`` (T, h)."""
    d = _dims(config)
    T, H, P, G, N, K = u.shape[0], d["H"], d["P"], d["G"], d["N"], d["K"]
    zxd = _mm(u, p["in_w"], int8)
    z, xbc, dt = (zxd[:, :d["di"]], zxd[:, d["di"]:d["di"] + d["W"]],
                  zxd[:, d["di"] + d["W"]:])
    t = jnp.arange(T)
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = p["conv_b"]
    for j in range(K):          # tap j meets the input K - 1 - j tokens back
        tap = pad[j:j + T] * p["conv_w"][:, j]
        if reset_every:         # the memory lost with the state
            tap = jnp.where((t % reset_every >= K - 1 - j)[:, None], tap, 0.0)
        conv = conv + tap
    xbc = jax.nn.silu(conv)
    x = xbc[:, :H * P].reshape(T, H, P)
    B = jnp.repeat(xbc[:, H * P:H * P + G * N].reshape(T, G, N), H // G, 1)
    C = jnp.repeat(xbc[:, H * P + G * N:].reshape(T, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (T, H)
    decay = jnp.exp(dt * -jnp.exp(p["a_log"]))
    if reset_every:
        decay = jnp.where((t % reset_every == 0)[:, None], 0.0, decay)

    def step(h, xs):
        x_t, B_t, C_t, dt_t, dec_t = xs
        h = (dec_t[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if state_bits is not None:
            h = jax.lax.reduce_precision(h, *state_bits)
        return h, (h * C_t[:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, B, C, dt, decay))
    y = (y + p["d_skip"][:, None] * x).reshape(T, H * P)
    g = (y * jax.nn.silu(z)).reshape(T, G, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True)
                     + float(config["layer_norm_epsilon"]))
    return _mm(g.reshape(T, H * P) * p["mnorm_g"], p["out_w"], int8)


def _attention(x, p, config, int8):
    """Grouped-query attention over the whole sequence ``x`` (T, h),
    causal, a block of query rows at a time; no positions."""
    d = _dims(config)
    T, H, Hkv, hd = x.shape[0], d["Hq"], d["Hkv"], d["hd"]
    q = _mm(x, p["q_w"], int8).reshape(T, H, hd)
    k = _mm(x, p["k_w"], int8).reshape(T, Hkv, hd)
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
    """Every held expert applied to every token; the gate is zero where
    the router did not pick it."""
    d = _dims(config)
    s = jax.nn.sigmoid(x @ p["router_w"])
    _, idx = jax.lax.top_k(s + p["router_bias"], d["k"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (g.sum(-1, keepdims=True) + 1e-20) * float(
        config["routed_scaling_factor"])
    gates = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(g)
    held = jnp.asarray(config.get("held_experts") or range(d["E"]), jnp.int32)
    gates = gates[:, held]                                  # (T, E)

    def one(y, e):
        up = p["exp_up_w"][e].astype(jnp.float32).T      # (h, I)
        dw = p["exp_down_w"][e].astype(jnp.float32)      # (I, h)
        return y + gates[:, e, None] * _relu2(x, up, dw, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d["E"]))
    return y + _relu2(x, p["shared_up_w"].astype(jnp.float32),
                      p["shared_down_w"].astype(jnp.float32), int8)


@partial(jax.jit, static_argnames=("cfg", "layer", "int8", "state_bits",
                                   "reset_every"))
def _layer(x, p, *, cfg, layer, int8, state_bits, reset_every):
    """One layer on the residual stream ``x`` (T, h) in float32."""
    config = dict(cfg)
    experts = {k: p[k] for k in ("exp_up_w", "exp_down_w") if k in p}
    p = {k: v.astype(jnp.float32) for k, v in p.items() if k not in experts}
    p.update(experts)        # upcast an expert at a time, inside the scan
    xn = _rms(x, p["norm_g"], float(config["layer_norm_epsilon"]))
    kind = config["hybrid_override_pattern"][layer]
    if kind == "M":
        return x + _mixer(xn, p, config, int8, state_bits, reset_every)
    if kind == "*":
        return x + _attention(xn, p, config, int8)
    return x + _moe(xn, p, config, int8)


def _frozen(config: Dict[str, Any]):
    """The architecture's keys as a hashable static argument."""
    keep = {k: v for k, v in config.items()
            if not isinstance(v, (dict, list))}
    if config.get("held_experts") is not None:
        keep["held_experts"] = tuple(config["held_experts"])
    return tuple(sorted(keep.items()))


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, head_w, *, eps, int8):
    return _mm(_rms(x, g.astype(jnp.float32), eps),
               head_w.astype(jnp.float32), int8)


def hidden(params, config, ids, int8: bool = False, state_bits=None,
           reset_every: int = 0):
    """The residual stream (T, h) after the last layer for ``ids`` (T,).
    ``state_bits``: (exponent, mantissa) bits every mixer's state is
    rounded to after every token; ``reset_every``: tokens after which
    every mixer's state and convolution memory are lost."""
    d = _dims(config)
    ids = jnp.asarray(ids, jnp.int32)
    cfg = _frozen(config)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids].astype(jnp.float32)
        for i in range(d["L"]):
            layer = {k: params[f"h{i}_{k}"] for k in layer_shapes(config, i)}
            x = _layer(x, layer, cfg=cfg, layer=i, int8=int8,
                       state_bits=state_bits, reset_every=reset_every)
    return x


def logits(params, config, ids, int8: bool = False, rows=None, **how):
    """(B, T, V) float32 logits of ``ids`` (B, T), a sequence at a time;
    with ``rows`` (a slice) only those positions' logits.  ``how``:
    ``state_bits`` / ``reset_every`` of :func:`hidden`."""
    out = []
    for seq in np.asarray(ids):
        x = hidden(params, config, seq, int8, **how)
        if rows is not None:
            x = x[rows]
        with jax.default_matmul_precision("highest"):
            out.append(_head(x, params["norm_f_g"], params["head_w"],
                             eps=float(config["layer_norm_epsilon"]),
                             int8=int8))
    return jnp.stack(out)


# -- what the checks compare ---------------------------------------------------

#: control -> what :func:`logits` is given, from the configuration
CONTROLS = {
    True: lambda c: dict(int8=True), "int8": lambda c: dict(int8=True),
    "state_bf16": lambda c: dict(state_bits=(8, 7)),
    "state_reset": lambda c: dict(
        reset_every=int(c["engine"]["chunk_tokens"])),
}


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
    own greedy token).  With a ``control`` (a key of :data:`CONTROLS`)
    the tokens judged are the ones that forward puts first at the same
    positions.  ``pad_to`` fixes the compiled length (a causal scan and a
    causal mask both keep the padding out of every real row); the head
    runs over one fixed window of rows that holds the served positions."""
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
        low = logits(params, config, ids[None], rows=rows,
                     **CONTROLS[control](config))[0][mine]
        toks = jnp.argmax(low, axis=-1).astype(jnp.int32)
    else:
        toks = jnp.asarray(seq[prompt_len:prompt_len + n_served], jnp.int32)
    return np.asarray(_gaps(ref, toks), np.float64)

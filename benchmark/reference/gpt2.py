"""GPT-2 family: the benchmark's weights, its plain float32 reference,
and the lower-precision control.

Nothing here imports the program.  The forward pass is the published
architecture (Radford et al. 2019; ``huggingface.co/openai-community``
``modeling_gpt2``): learned token and position embeddings, pre-LN blocks
of causal multi-head attention and a 4x tanh-GELU MLP with residuals, a
final LN and the output head tied to the token embedding — in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``, one layer at a time, no
kernel, no cache, no batching tricks.

Weights are made here from ``--seed`` in one jitted call, in the dtype
they are served in, under the program's flat names (``wte``, ``wpe``,
``h{i}_attn_qkv_w`` ...) because that is the interface the program
takes.  The init is GPT-2's N(0, std) with the residual projections
scaled by 1/sqrt(2 n_layer), with two stated departures (the config
file's ``init`` group): the query/key columns carry ``qk_gain`` and the
attention output projection ``attn_proj_gain``, so that attention is
peaked and carries most of the residual stream — with plain random tied
weights the greedy token is all but blind to the KV cache (PERF.md,
PR 21), and a check on served tokens would pass a broken cache.  Biases
are N(0, std), not zero, so a dropped bias shows.

``int8=True`` is the same forward computed in int8, the control: both
operands of every matmul are rounded to 8 bits, symmetric absmax —
weights per output column, activations per tensor (the textbook static
W8A8), queries, keys, values and attention probabilities per head.  It
is what a later PR might be tempted to serve; the limits in the workload
files are set so that it fails.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

_BLOCK = ("ln1_g", "ln1_b", "attn_qkv_w", "attn_qkv_b", "attn_proj_w",
          "attn_proj_b", "ln2_g", "ln2_b", "mlp_fc_w", "mlp_fc_b",
          "mlp_proj_w", "mlp_proj_b")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights on the device, one jitted call, from the seed."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    V, T = int(config["vocab_size"]), int(config["n_positions"])
    dtype = jnp.dtype(config["dtype"])
    init = config.get("init", {})
    std = float(init.get("std", 0.02))
    qk = float(init.get("qk_gain", 1.0))
    ap = float(init.get("attn_proj_gain", 1.0))
    mp = float(init.get("mlp_proj_gain", 1.0))
    bias = float(init.get("bias_std", 0.0))
    res = std / math.sqrt(2 * L)

    def build(key):
        def normal(k, shape, scale):
            return (scale * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        keys = iter(jax.random.split(key, 2 + 8 * L))
        p = {"wte": normal(next(keys), (V, d), std),
             "wpe": normal(next(keys), (T, d), std)}
        col_gain = jnp.concatenate(
            [jnp.full((2 * d,), qk, jnp.float32), jnp.ones((d,), jnp.float32)])
        for i in range(L):
            h = f"h{i}_"
            p[h + "ln1_g"] = jnp.ones((d,), dtype)
            p[h + "ln1_b"] = jnp.zeros((d,), dtype)
            w = std * jax.random.normal(next(keys), (d, 3 * d), jnp.float32)
            p[h + "attn_qkv_w"] = (w * col_gain).astype(dtype)
            p[h + "attn_qkv_b"] = normal(next(keys), (3 * d,), bias)
            p[h + "attn_proj_w"] = normal(next(keys), (d, d), res * ap)
            p[h + "attn_proj_b"] = normal(next(keys), (d,), bias)
            p[h + "ln2_g"] = jnp.ones((d,), dtype)
            p[h + "ln2_b"] = jnp.zeros((d,), dtype)
            p[h + "mlp_fc_w"] = normal(next(keys), (d, 4 * d), std)
            p[h + "mlp_fc_b"] = normal(next(keys), (4 * d,), bias)
            p[h + "mlp_proj_w"] = normal(next(keys), (4 * d, d), res * mp)
            p[h + "mlp_proj_b"] = normal(next(keys), (d,), bias)
        p["ln_f_g"] = jnp.ones((d,), dtype)
        p["ln_f_b"] = jnp.zeros((d,), dtype)
        return p

    return jax.jit(build)(seed_key(seed))


# -- the plain forward --------------------------------------------------------


def _ln(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _q8(x, axis):
    """Symmetric absmax rounding to int8 along ``axis`` (dequantized)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, int8):
    if int8:
        x, w = _q8(x, None), _q8(w, 0)
    return x @ w


@partial(jax.jit, static_argnames=("n_head", "eps", "int8"))
def _block(x, p, *, n_head, eps, int8):
    """One pre-LN block on ``x`` (B, T, D) in float32."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    B, T, D = x.shape
    hd = D // n_head
    h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = _mm(h, p["attn_qkv_w"], int8) + p["attn_qkv_b"]
    q, k, v = (t.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    if int8:
        q, k, v = (_q8(t, (-2, -1)) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if int8:
        pr = _q8(pr, (-2, -1))
    a = jnp.einsum("bhqk,bhkd->bhqd", pr, v)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + _mm(a, p["attn_proj_w"], int8) + p["attn_proj_b"]
    h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
    h = _gelu(_mm(h, p["mlp_fc_w"], int8) + p["mlp_fc_b"])
    return x + _mm(h, p["mlp_proj_w"], int8) + p["mlp_proj_b"]


@partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, g, b, wte, *, eps, int8):
    h = _ln(x, g.astype(jnp.float32), b.astype(jnp.float32), eps)
    return _mm(h, wte.astype(jnp.float32).T, int8)


@jax.jit
def _embed(ids, wte, wpe):
    T = ids.shape[-1]
    return wte[ids].astype(jnp.float32) + wpe[:T].astype(jnp.float32)


def logits(params: Dict[str, Any], config: Dict[str, Any], ids,
           int8: bool = False) -> jax.Array:
    """(B, T, V) float32 logits of ``ids`` (B, T), layer by layer."""
    n_head, eps = int(config["n_head"]), float(config["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(ids, jnp.int32), params["wte"], params["wpe"])
        for i in range(int(config["n_layer"])):
            layer = {k: params[f"h{i}_{k}"] for k in _BLOCK}
            x = _block(x, layer, n_head=n_head, eps=eps, int8=int8)
        return _head(x, params["ln_f_g"], params["ln_f_b"], params["wte"],
                     eps=eps, int8=int8)


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
    ones the int8 forward puts first at the same positions, not the
    served ones.  ``pad_to`` fixes the compiled length; causal masking
    keeps the padding out of every real row."""
    import numpy as np

    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq) - 1] = np.asarray(seq[:-1], np.int32)
    ref = logits(params, config, ids)[0]
    rows = slice(prompt_len - 1, prompt_len - 1 + n_served)
    if control:
        low = logits(params, config, ids, int8=True)[0]
        toks = jnp.argmax(low[rows], axis=-1).astype(jnp.int32)
    else:
        toks = jnp.asarray(seq[prompt_len:prompt_len + n_served], jnp.int32)
    return np.asarray(_gaps(ref[rows], toks), np.float64)


@jax.jit
def _diff_stats(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    d = got - want
    top = jnp.argmax(got, axis=-1).astype(jnp.int32)
    return (jnp.max(jnp.abs(d)), jnp.sum(d * d), jnp.sum(want * want),
            jnp.all(jnp.isfinite(got)), jnp.sum(_gaps(want, top)))


def forward_distance(params, config, ids, got, rows_per_block: int,
                     control: bool = False) -> Dict[str, Any]:
    """Distance of ``got`` (B, T, V), the program's logits for ``ids``
    (B, T), from the reference, computed ``rows_per_block`` rows at a
    time so that the float32 logits fit beside the program's state:
    the largest absolute difference, the relative Frobenius norm of the
    difference, whether all is finite, and the mean gap by which the
    reference logit of the program's first token lies below the
    reference's best at each position (quadratic in the error, so it
    separates neighbouring precisions where the norms do not).  With
    ``control=True`` the int8 forward stands in for ``got``."""
    import numpy as np

    ids = np.asarray(ids, np.int32)
    worst, num, den, finite, gap = 0.0, 0.0, 0.0, True, 0.0
    for b0 in range(0, ids.shape[0], rows_per_block):
        blk = ids[b0:b0 + rows_per_block]
        want = logits(params, config, blk)
        have = (logits(params, config, blk, int8=True) if control
                else got[b0:b0 + rows_per_block])
        m, n2, d2, ok, g = _diff_stats(have, want)
        worst = max(worst, float(m))
        num, den, gap = num + float(n2), den + float(d2), gap + float(g)
        finite = finite and bool(ok)
    return {"max_abs": worst, "rel_fro": math.sqrt(num / max(den, 1e-30)),
            "finite": finite, "top1_gap_mean": gap / ids.size}

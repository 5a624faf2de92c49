"""``ops/ssm.py``: the decode step over the slots' state pools and the
prefill chunk's scan, each against the token-by-token recurrence written
out here, under the interpreted kernel and the ``xla`` twin."""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from distributed_llm_scheduler_tpu.ops import ssm  # noqa: E402

IMPLS = ("xla", "pallas_interpret")
H, P, G, N, K = 4, 8, 2, 16, 4
W = H * P + 2 * G * N


def _recurrence(x, dt, A, B, C, h):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t``,
    a token at a time in numpy float64."""
    x, dt, A, B, C, h = (np.asarray(v, np.float64) for v in (x, dt, A, B, C, h))
    ys = []
    for t in range(x.shape[0]):
        Bh, Ch = np.repeat(B[t], H // G, 0), np.repeat(C[t], H // G, 0)
        h = (np.exp(dt[t] * A)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
        ys.append((h * Ch[:, None, :]).sum(-1))
    return np.stack(ys), h


def _chunk_inputs(T, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (T, G, N)),
            jax.random.normal(k[4], (T, G, N)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("T", [1, 3, 8, 13])
def test_chunk_scan_is_the_recurrence(impl, T):
    """Lengths under, at and over a block of 4, from a state that is not
    zero; the length that is no whole block is padded inside."""
    x, dt, A, B, C, h0 = _chunk_inputs(T, seed=T)
    y, h = ssm.ssd_chunk(x, dt, A, B, C, h0, block=4, impl=impl)
    want_y, want_h = _recurrence(x, dt, A, B, C, h0)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_a_chunk_hands_its_state_to_the_next_and_dt_zero_freezes_it(impl):
    """Two chunks of 8 = one of 16; rows with ``dt = 0`` behind the real
    ones leave the state where the last real row left it, bit for bit."""
    x, dt, A, B, C, h0 = _chunk_inputs(16, seed=5)
    y, h = ssm.ssd_chunk(x, dt, A, B, C, h0, block=4, impl=impl)
    y1, h1 = ssm.ssd_chunk(x[:8], dt[:8], A, B[:8], C[:8], h0, block=4,
                           impl=impl)
    y2, h2 = ssm.ssd_chunk(x[8:], dt[8:], A, B[8:], C[8:], h1, block=4,
                           impl=impl)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(h2, h, rtol=2e-5, atol=2e-5)
    real = 11                                   # a padded last chunk
    frozen = dt.at[real:].set(0.0)
    _, hp = ssm.ssd_chunk(x, frozen, A, B, C, h0, block=4, impl=impl)
    _, hu = ssm.ssd_chunk(x[:real], dt[:real], A, B[:real], C[:real], h0,
                          block=4, impl=impl)
    np.testing.assert_array_equal(np.asarray(hp), np.asarray(hu))


def _step_inputs(S, seed=0, geometry=(H, P, G, N, K), served=jnp.float32):
    """A step's arguments; ``served`` the dtype of what the model hands
    over and of the convolution pool (the cell's: bfloat16)."""
    H, P, G, N, K = geometry
    W = H * P + 2 * G * N
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    return dict(
        new=jax.random.normal(k[0], (S, W)).astype(served),
        dt_raw=jax.random.normal(k[1], (S, H)).astype(served),
        conv_w=(0.5 * jax.random.normal(k[2], (W, K))).astype(served),
        conv_b=(0.1 * jax.random.normal(k[3], (W,))).astype(served),
        dt_bias=jax.random.normal(k[4], (H,)) - 1.0,
        a_log=jax.random.normal(k[5], (H,)),
        d_skip=jnp.ones((H,)) + 0.1 * jax.random.normal(k[6], (H,)),
        conv_pool=jax.random.normal(
            k[7], (1 + S, K - 1, W // N, N)).astype(served),
        ssm_pool=jax.random.normal(k[8], (1 + S, H, P, N)))


def _step_plain(a, s):
    """Slot ``s``'s step in numpy: the convolution over its last K - 1
    inputs and the new one, silu, one step of the recurrence, the skip."""
    win = np.concatenate([np.asarray(a["conv_pool"])[1 + s].reshape(K - 1, W),
                          np.asarray(a["new"])[s][None]]).astype(np.float64)
    z = (win * np.asarray(a["conv_w"], np.float64).T).sum(0) + np.asarray(
        a["conv_b"], np.float64)
    xbc = z / (1 + np.exp(-z))
    x = xbc[:H * P].reshape(1, H, P)
    B = xbc[H * P:H * P + G * N].reshape(1, G, N)
    C = xbc[H * P + G * N:].reshape(1, G, N)
    dt = np.log1p(np.exp(np.asarray(a["dt_raw"], np.float64)[s]
                         + np.asarray(a["dt_bias"], np.float64)))[None]
    y, h = _recurrence(x, dt, -np.exp(np.asarray(a["a_log"], np.float64)),
                       B, C, np.asarray(a["ssm_pool"])[1 + s])
    return (y[0] + np.asarray(a["d_skip"], np.float64)[:, None] * x[0],
            win[1:].reshape(K - 1, W // N, N), h)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5, [True] * 5])
def test_decode_step_updates_the_live_slots_and_no_other(impl, live):
    """Some, none and all of five slots decode: a live slot's ``y`` and
    states are the plain step's; a slot that is not live (decoding
    nothing, mid-prefill or empty) keeps its rows bit for bit and reads
    ``y`` = 0."""
    a = _step_inputs(5, seed=sum(live))
    mask = jnp.asarray(live)
    y, conv, h = ssm.ssm_step(
        a["new"], a["dt_raw"], a["conv_w"], a["conv_b"], a["dt_bias"],
        a["a_log"], a["d_skip"], a["conv_pool"], a["ssm_pool"], mask,
        groups=G, impl=impl)
    for s, on in enumerate(live):
        if on:
            want_y, want_conv, want_h = _step_plain(a, s)
            np.testing.assert_allclose(y[s], want_y, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(h[1 + s], want_h, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(conv[1 + s], want_conv, rtol=1e-6)
        else:
            assert not np.asarray(y[s]).any()
            np.testing.assert_array_equal(np.asarray(h[1 + s]),
                                          np.asarray(a["ssm_pool"][1 + s]))
            np.testing.assert_array_equal(np.asarray(conv[1 + s]),
                                          np.asarray(a["conv_pool"][1 + s]))


@pytest.mark.parametrize("steps", [3, 8])
def test_the_two_impls_of_a_step_agree_and_steps_compose_into_a_chunk(steps):
    """Decode steps of one slot from a zero state = a chunk of as many
    tokens from zero (the state the chunk hands on is the state decode
    holds): three, and eight — two blocks of the scan."""
    a = _step_inputs(1, seed=3)
    zero = dict(conv_pool=jnp.zeros_like(a["conv_pool"]),
                ssm_pool=jnp.zeros_like(a["ssm_pool"]))
    news = jax.random.normal(jax.random.PRNGKey(9), (steps, 1, W))
    dts = jax.random.normal(jax.random.PRNGKey(10), (steps, 1, H))
    out = {}
    for impl in IMPLS:
        conv, h, ys = zero["conv_pool"], zero["ssm_pool"], []
        for t in range(steps):
            y, conv, h = ssm.ssm_step(
                news[t], dts[t], a["conv_w"], a["conv_b"], a["dt_bias"],
                a["a_log"], a["d_skip"], conv, h, jnp.asarray([True]),
                groups=G, impl=impl)
            ys.append(y[0])
        out[impl] = (jnp.stack(ys), h[1])
    np.testing.assert_allclose(out["xla"][0], out["pallas_interpret"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out["xla"][1], out["pallas_interpret"][1],
                               rtol=1e-5, atol=1e-5)
    # the chunk's way: the convolution over the inputs, then the scan
    seq = jnp.concatenate([jnp.zeros((K - 1, W)), news[:, 0]])
    act = jax.nn.silu(sum(seq[j:j + steps] * a["conv_w"][:, j]
                          for j in range(K)) + a["conv_b"])
    x = act[:, :H * P].reshape(steps, H, P)
    dt = jax.nn.softplus(dts[:, 0] + a["dt_bias"])
    for impl in IMPLS:
        y, h = ssm.ssd_chunk(
            x, dt, -jnp.exp(a["a_log"]),
            act[:, H * P:H * P + G * N].reshape(steps, G, N),
            act[:, H * P + G * N:].reshape(steps, G, N),
            jnp.zeros((H, P, N)), block=4, impl=impl)
        np.testing.assert_allclose(y + a["d_skip"][None, :, None] * x,
                                   out[impl][0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(h, out[impl][1], rtol=2e-5, atol=2e-5)


# -- the decode step's kernel at the published geometry ------------------------

PUB = dict(H=64, P=64, G=8, N=128, K=4, S=5)
LIVE = {"some": (True, False, True, True, False), "none": (False,) * 5,
        "all": (True,) * 5}


@functools.lru_cache(maxsize=None)
def _published_step(live):
    """One interpreted step over five slots' pools at Nemotron-H's own
    sizes, in the cell's dtypes, the trash row of both pools NaN; beside
    it what the step is held to: every live slot's new state by the
    parent commit's formula ``h * dA + (dt x) (x) B`` in float32 — its
    one multiply-add jitted, as the interpreted kernel is (XLA's CPU
    backend carries ``h * dA`` exactly into the sum in both; the chip
    rounds it first, in the parent's kernel and in this one alike) — and
    ``_step_math``'s ``y``."""
    H, P, G, N, K, S = (PUB[k] for k in "HPGNKS")
    W, f32 = H * P + 2 * G * N, jnp.float32
    a = _step_inputs(S, sum(live), (H, P, G, N, K), jnp.bfloat16)
    a.update({k: a[k].at[0].set(jnp.nan) for k in ("conv_pool", "ssm_pool")})
    y, conv, h = ssm.ssm_step(
        a["new"], a["dt_raw"], a["conv_w"], a["conv_b"], a["dt_bias"],
        a["a_log"], a["d_skip"], a["conv_pool"], a["ssm_pool"],
        jnp.asarray(live), groups=G, impl="pallas_interpret")
    w = a["conv_w"].T.astype(f32)

    @jax.jit
    def acts(win, new):     # bf16 x bf16 is exact in float32: one rounding
        acc = a["conv_b"].astype(f32) + w[K - 1] * new.astype(f32)
        for j in range(K - 1):
            acc = acc + w[j] * win[j].reshape(W).astype(f32)
        return jax.nn.silu(acc)

    update = jax.jit(lambda h, dA, dtx, B: (
        h * dA[:, None, None] + dtx[:, :, None] * B[:, None, :]))
    want = {}
    for s in np.flatnonzero(live):
        xbc = acts(a["conv_pool"][1 + s], a["new"][s])
        x = xbc[:H * P].reshape(H, P)
        B = jnp.repeat(xbc[H * P:H * P + G * N].reshape(G, N), H // G, 0)
        C = jnp.repeat(xbc[H * P + G * N:].reshape(G, N), H // G, 0)
        dt = jax.nn.softplus(a["dt_raw"][s].astype(f32) + a["dt_bias"])
        dA = jnp.exp(dt * -jnp.exp(a["a_log"]))
        state = update(a["ssm_pool"][1 + s], dA, x * dt[:, None], B)
        total = (jnp.abs(state * C[:, None, :]).sum(-1)
                 + jnp.abs(a["d_skip"][:, None] * x))
        y_math, _, _ = ssm._step_math(
            a["conv_pool"][1 + s].reshape(K - 1, W), a["new"][s], w,
            a["conv_b"].astype(f32),
            a["dt_raw"][s].astype(f32), a["dt_bias"], a["a_log"],
            a["d_skip"], a["ssm_pool"][1 + s], heads=H, head_dim=P, groups=G)
        want[int(s)] = (np.asarray(state), np.asarray(y_math),
                        np.asarray(total))
    return a, np.asarray(y), np.asarray(conv.astype(f32)), np.asarray(h), want


@pytest.mark.parametrize("held", ["state", "y", "others", "trash"])
@pytest.mark.parametrize("live", list(LIVE))
def test_the_published_step_is_the_parents_update_and_rounds_only_y(live, held):
    """H 64, P 64, N 128, G 8, K 4 under the interpreter.  ``state``: a
    live slot's new state is the formula's bit for bit (the update is
    float32 VPU arithmetic in that order whatever computes ``y``);
    ``y``: within 8 ulp of the row's ``sum |h C| + |D x|`` of
    ``_step_math``'s — the kernel sums ``h . C`` on the MXU, in another
    order; here the two read at most 4 apart, each 1.4-4.2 from the sum
    in float64, and one bfloat16 pass would read thousands; ``others``:
    a slot that is not live keeps both its rows bit for bit and reads
    ``y`` = 0; ``trash``: row 0 of both pools, NaN here, reaches no
    slot's rows or ``y`` — also when no slot is live and the grid's one
    step runs on it."""
    mask = LIVE[live]
    a, y, conv, h, want = _published_step(mask)
    K, S = PUB["K"], PUB["S"]
    if held == "state":
        for s, (state, _, _) in want.items():
            np.testing.assert_array_equal(h[1 + s], state)
    elif held == "y":
        for s, (_, y_math, total) in want.items():
            assert (np.abs(y[s] - y_math) <= 8 * np.spacing(total)).all()
    elif held == "others":
        for s in range(S):
            if not mask[s]:
                assert not y[s].any()
                np.testing.assert_array_equal(h[1 + s],
                                              np.asarray(a["ssm_pool"][1 + s]))
                np.testing.assert_array_equal(
                    conv[1 + s],
                    np.asarray(a["conv_pool"][1 + s].astype(jnp.float32)))
    else:
        assert np.isfinite(y).all()
        assert np.isfinite(h[1:]).all() and np.isfinite(conv[1:]).all()
        for s in want:      # the live slots' inputs moved up by one
            np.testing.assert_array_equal(
                conv[1 + s, :K - 2],
                np.asarray(a["conv_pool"][1 + s, 1:].astype(jnp.float32)))


def test_the_compiled_kernels_rules_name_what_a_shape_breaks():
    assert ssm.ssm_kernel_constraints(64, 64, 128, 8, 128) == []
    assert ssm.resolve_ssm_impl("xla", H, P, N, G, 4) == "xla"
    assert ssm.resolve_ssm_impl("pallas_interpret", H, P, N, G, 4) == (
        "pallas_interpret")
    broken = ssm.ssm_kernel_constraints(H, P, N, G, 4)
    assert any("128-lane" in b for b in broken) and len(broken) >= 2
    with pytest.raises(ValueError, match="does not qualify"):
        ssm.resolve_ssm_impl("pallas", H, P, N, G, 4)

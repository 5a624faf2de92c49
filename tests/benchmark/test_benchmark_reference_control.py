"""The plain reference and its lower-precision control, at a size a test
run can hold: the control — the same forward computed in int8 — has to
come out as not correct under limits that sound output meets."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import gpt2 as R  # noqa: E402

CFG = {"n_embd": 256, "n_layer": 8, "n_head": 4, "vocab_size": 5000,
       "n_positions": 64, "dtype": "float32", "layer_norm_epsilon": 1e-05,
       "init": {"std": 0.02, "qk_gain": 6.0, "attn_proj_gain": 16.0}}
P, T, PAD = 24, 56, 64


@pytest.fixture(scope="module")
def greedy():
    """Weights and one greedy continuation by the reference itself: what
    a sound program would serve."""
    import jax.numpy as jnp

    params = R.make_params(CFG, 2**31 + 77)
    seq = list(np.random.RandomState(3).randint(1, 5000, size=P))
    for _ in range(T - P):
        ids = np.zeros((1, PAD), np.int32)
        ids[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(R.logits(params, CFG, ids)[0, len(seq) - 1])))
    return params, np.asarray(seq, np.int32)


def test_weights_are_a_pure_function_of_the_seed():
    a = R.make_params(dict(CFG, n_layer=1), 2**31 + 5)
    b = R.make_params(dict(CFG, n_layer=1), 2**31 + 5)
    c = R.make_params(dict(CFG, n_layer=1), 2**31 + 6)
    assert set(a) == {"wte", "wpe", "ln_f_g", "ln_f_b"} | {
        f"h0_{k}" for k in R._BLOCK}
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    assert (np.asarray(a["wte"]) != np.asarray(c["wte"])).any()


def test_greedy_tokens_depend_on_the_context(greedy):
    """The stated init makes served tokens sensitive to what attention
    reads: the continuation does not settle on one token."""
    _params, seq = greedy
    assert len(set(seq[P:].tolist())) > (T - P) // 2


def test_sound_tokens_have_no_gap_and_the_int8_control_has(greedy):
    params, seq = greedy
    sound = R.served_gaps(params, CFG, seq, P, T - P, PAD)
    assert sound.max() == 0.0
    control = R.served_gaps(params, CFG, seq, P, T - P, PAD, control=True)
    assert control.max() > 0.02 and control.mean() > 1e-3
    assert (control > 0).sum() >= 3


def test_an_altered_token_shows_as_a_gap(greedy):
    params, seq = greedy
    bad = seq.copy()
    bad[P + 5] = (bad[P + 5] + 1) % 5000
    gaps = R.served_gaps(params, CFG, bad, P, T - P, PAD)
    assert gaps[5] > 0.0


def test_forward_distance_separates_the_control():
    cfg = dict(CFG, n_layer=4, init={"std": 0.02, "bias_std": 0.02})
    params = R.make_params(cfg, 11)
    ids = np.random.RandomState(1).randint(0, 5000, size=(4, 32))
    got = R.logits(params, cfg, ids)
    sound = R.forward_distance(params, cfg, ids, got, 2)
    assert sound == {"max_abs": 0.0, "rel_fro": 0.0, "finite": True,
                     "top1_gap_mean": 0.0}
    low = R.forward_distance(params, cfg, ids, got, 2, control=True)
    assert low["finite"] and low["rel_fro"] > 3e-3 and low["max_abs"] > 1e-3
    assert low["top1_gap_mean"] >= 0.0
    # a step that leaves out a row of the batch is far outside any limit
    broken = got.at[-1].set(0)
    assert R.forward_distance(params, cfg, ids, broken, 2)["rel_fro"] > 0.3

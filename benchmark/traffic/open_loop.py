"""The one general open-loop traffic generator.

A traffic mix is a data file ``benchmark/traffic/<name>.json``; this
module turns its parameters, a rate, a window length and ``--seed`` into
an arrival schedule.  Every seed gets the **same multiset** of prompt
lengths, output lengths and inter-arrival gaps, in another order: the
lengths are the stratified quantiles of a log-uniform (or uniform)
distribution over ``[lo, hi]``, the gaps the stratified quantiles of the
exponential distribution with mean ``1 / rate_rps``, rescaled so that
they fill the window.  So two seeds offer the same work and differ only
in its order, and the spread between runs is the system's, not the
draw's.  **This is not a Poisson process**: independent users send
bursts and clumps of long requests that this schedule cannot, so the
latency tails it gives are damped against theirs.  The order is shuffled in
blocks (``_blocked``): every ``BLOCK`` consecutive arrivals carry one
value from each quarter of the distribution, so no seed puts all the
long answers at the end of a window that holds only a few dozen
requests (PERF.md, PR 23: with a free shuffle the tokens delivered
inside the window swung by a quarter from seed to seed).

Prompt *content* is derived from ``(seed, rid)`` exactly as the
program's ``serve.loadgen.prompt_token_ids`` does it (copied, with the
seed folded to 32 bits, so the program cannot move it): any holder of the
schedule rebuilds the tokens.  Token 0 is avoided (the engine pads with
it).
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

SEED_MASK = 0xFFFFFFFF


@dataclass(frozen=True)
class Request:
    """One open-loop arrival; ``t`` is the due offset in seconds."""

    rid: str
    t: float
    prompt_len: int
    max_new_tokens: int
    priority: int = 0


def _rng(seed: int, stream: int) -> np.random.RandomState:
    # seeds run past 2**31: fold into the 32 bits RandomState takes
    folded = (int(seed) ^ (int(seed) >> 32) ^ (stream * 0x9E3779B1)) & SEED_MASK
    return np.random.RandomState(folded)


BLOCK = 4


def _blocked(sorted_values: np.ndarray, seed: int, stream: int) -> np.ndarray:
    """``sorted_values`` in a seeded order in which every run of ``BLOCK``
    consecutive entries holds one value from each of ``BLOCK`` contiguous
    strata (the last block may be short)."""
    n = len(sorted_values)
    rng = _rng(seed, stream)
    n_blocks = -(-n // BLOCK)
    blocks: List[List[Any]] = [[] for _ in range(n_blocks)]
    for s0 in range(0, n, n_blocks):
        stratum = sorted_values[s0:s0 + n_blocks]
        for j, v in zip(rng.permutation(n_blocks)[:len(stratum)], stratum):
            blocks[j].append(v)
    out: List[Any] = []
    for b in blocks:
        out.extend(b[i] for i in rng.permutation(len(b)))
    return np.asarray(out)


def _lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range {spec}")
    u = (np.arange(n) + 0.5) / n
    dist = spec.get("dist", "log_uniform")
    if dist == "log_uniform":
        x = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
    elif dist == "uniform":
        x = lo + (hi - lo) * u
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x).astype(np.int64), lo, hi)


def generate(traffic: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, rid_prefix: str = "r") -> List[Request]:
    """Arrivals due inside ``[0, seconds)`` at mean rate ``rate_rps``."""
    if rate_rps <= 0 or seconds <= 0:
        raise ValueError("rate_rps and seconds must be > 0")
    n = max(1, int(round(rate_rps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)                       # unit-mean exponential
    gaps = _blocked(gaps, seed, 1)
    t = np.cumsum(gaps)
    # the n gaps fill the window: the last arrival lands half a mean gap
    # before its end, whatever the order
    t = t * (seconds * (n - 0.5) / n / t[-1])
    prompts = _blocked(_lengths(traffic["prompt_len"], n), seed, 2)
    outs = _blocked(_lengths(traffic["output_len"], n), seed, 3)
    cap = int(traffic.get("max_total", 0))
    reqs = []
    for i in range(n):
        p, o = int(prompts[i]), int(outs[i])
        if cap and p + o > cap:
            raise ValueError(
                f"request {i}: {p}+{o} tokens exceed max_total {cap}")
        reqs.append(Request(f"{rid_prefix}{i}", float(t[i]), p, o))
    return reqs


def prompt_token_ids(rid: Any, prompt_len: int, vocab_size: int,
                     seed: int = 0) -> np.ndarray:
    """(1, prompt_len) int32 tokens, a pure function of ``(seed, rid)``."""
    key = zlib.crc32(str(rid).encode("utf-8")) & SEED_MASK
    folded = (int(seed) ^ (int(seed) >> 32)) & SEED_MASK
    rng = np.random.RandomState([folded, key])
    return rng.randint(1, max(2, vocab_size),
                       size=(1, prompt_len)).astype(np.int32)

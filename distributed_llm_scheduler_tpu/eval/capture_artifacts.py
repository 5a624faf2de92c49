"""One-shot capture of the round's measured perf artifacts.

The driver records ``BENCH_r{N}.json`` itself (bench.py); everything else
measured — streaming-under-eviction, decode roofline + attribution +
task-graph decode, the training-step DAG — is captured here in ONE
sequential pass, in one process, so every artifact carries the same
device provenance.  A leg that fails fails the pass: the exception
propagates and no artifact is written for it — there is no error stub
that could later be read as a record.

Run on the chip (on CPU the legs shrink to a functional rehearsal, and
the artifact's ``platform`` / model fields say so)::

    python -m distributed_llm_scheduler_tpu.eval.capture_artifacts 4
    python -m distributed_llm_scheduler_tpu.eval.capture_artifacts 4 stream decode

Writes ``STREAM_r{N:02d}.json`` / ``DECODE_r{N:02d}.json`` at the repo
root (next to the earlier rounds' artifacts the judge diffs against).
"""

from __future__ import annotations
# dls-lint: allow-file(DET001) capture harness: legs are stamped with wall time

import json
import os
import sys
import time
from typing import Any, Callable, Dict

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# legs that consult calibration caches use the checkout's .costmodel
# regardless of invocation cwd (same anchoring as bench.py)
CACHE_DIR = os.path.join(REPO_ROOT, ".costmodel")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _timed(name: str, fn: Callable[[], Dict[str, Any]]) -> Dict[str, Any]:
    """Run one capture leg and stamp its wall seconds.  Failures
    propagate: a leg that did not measure has no artifact."""
    t0 = time.time()
    out = fn()
    out["capture_wall_s"] = round(time.time() - t0, 1)
    log(f"capture[{name}]: {out['capture_wall_s']}s")
    return out


def capture_stream(budget_frac: float = 0.3) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from .stream_bench import measure_streaming

    if jax.devices()[0].platform == "tpu":
        return measure_streaming(budget_frac=budget_frac, log=log)
    # CPU rehearsal scale (capture_train's pattern): the medium-class
    # bf16 forward takes hours through a host core.  The artifact's
    # model field and platform stamp disclose the scale, and the claims
    # the schema pins (budget_respected, oracle_ok, floor provenance)
    # are scale-independent.
    from ..models.gpt2 import GPT2Config

    # at small scale the 0.3x budget (70 MB) sits BELOW the tied
    # embedding matrix (77 MB), making the cap unsatisfiable by
    # construction — the budget must exceed the largest single param
    # while staying well under total params so streaming still evicts
    return measure_streaming(
        config=GPT2Config.small(dtype=jnp.bfloat16), batch=4, seq_len=128,
        budget_frac=max(budget_frac, 0.4), log=log,
    )


def capture_decode() -> Dict[str, Any]:
    """The decode artifact: whole-program roofline numbers, per-component
    attribution of the gap to the HBM bound, and the task-graph decode
    path's own perf."""
    import jax

    from .decode_bench import (
        _round4 as _rounded,
        decode_attribution,
        measure_decode,
        measure_decode_dag,
        measure_decode_sharded,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    # CPU rehearsal scale for the gpt2 legs (capture_train's pattern: the
    # full-size legs take hours through a host core).  The artifact's
    # batch / prompt_len / new_tokens fields plus the platform stamp
    # disclose it, and every relative claim a leg makes (int8 vs bf16,
    # paged vs dense) is measured at equal config WITHIN that leg.
    gpt2_kw: Dict[str, Any] = (
        {} if on_tpu else {"batch": 4, "prompt_len": 128, "new_tokens": 16}
    )
    out = _timed(
        "decode.whole_program",
        lambda: _rounded(measure_decode(**gpt2_kw)),
    )
    # the whole_program dict becomes the artifact's top level, where
    # main()'s outer stamp would overwrite its wall time — keep it under
    # its own name like the sibling sub-legs keep theirs
    out["whole_program_wall_s"] = out.pop("capture_wall_s", None)
    out["attribution"] = _timed(
        "decode.attribution", lambda: decode_attribution(**gpt2_kw)
    )
    # int8 weights: decode is bandwidth-bound, so halving the weight
    # bytes is the structural lever (the roofline in this leg reflects
    # the quantized bytes)
    out["quantized"] = _timed(
        "decode.quantized",
        lambda: _rounded(measure_decode(quantize=True, **gpt2_kw)),
    )
    # weights AND KV cache int8: both dominant byte terms halved
    out["quantized_kv"] = _timed(
        "decode.quantized_kv",
        lambda: _rounded(
            measure_decode(quantize=True, kv_int8=True, **gpt2_kw)
        ),
    )
    # family breadth (the gpt2 numbers above are the roofline story;
    # these pin the OTHER decode paths' measured rates): a GPT-2-small-
    # class Llama (GQA 12:4 + RoPE + SwiGLU) and Mixtral (per-token
    # top-2 routing in the decode step).  A CPU run takes the tiny
    # configs — a functional rehearsal, disclosed by the model field.
    import jax.numpy as jnp

    from ..models.llama import LlamaConfig
    from ..models.mixtral import MixtralConfig

    lcfg = (
        LlamaConfig(
            vocab_size=32_000, max_seq_len=1024, d_model=768,
            n_layers=12, n_heads=12, n_kv_heads=4, ffn_hidden=2048,
            dtype=jnp.bfloat16,
        )
        if on_tpu else LlamaConfig.tiny(dtype=jnp.bfloat16)
    )
    mcfg = (
        MixtralConfig(
            vocab_size=32_000, max_seq_len=1024, d_model=512,
            n_layers=8, n_heads=8, n_kv_heads=4, ffn_hidden=1408,
            n_experts=8, top_k=2, dtype=jnp.bfloat16,
        )
        if on_tpu else MixtralConfig.tiny(dtype=jnp.bfloat16)
    )
    # tiny configs cap max_seq_len at 128 — the CPU rehearsal must shrink
    # the sequence budget with them (capture_train's CPU-scale pattern)
    # or decode.generate's position-limit guard rejects every call
    size_kw = {} if on_tpu else {"prompt_len": 64, "new_tokens": 16}
    for name, cfg in (("llama", lcfg), ("mixtral", mcfg)):
        out[name] = _timed(
            f"decode.{name}",
            lambda cfg=cfg: _rounded(measure_decode(config=cfg, **size_kw)),
        )
        out[name]["model"] = (
            f"{name}_{cfg.n_layers}l_d{cfg.d_model}_"
            f"{jnp.dtype(cfg.dtype).name}"
        )
    dag_kw: Dict[str, Any] = (
        {} if on_tpu
        else {"batch": 4, "prompt_len": 128, "new_tokens": 8, "reps": 4}
    )
    out["task_graph"] = _timed(
        "decode.task_graph", lambda: measure_decode_dag(**dag_kw)
    )
    # paged KV cache + continuous batching (r6): mixed-length multi-
    # request traffic, paged engine vs dense static batching at equal
    # token budgets — tokens must match bit-exactly, throughput >= dense
    from .decode_bench import measure_paged_decode

    out["paged"] = _timed(
        "decode.paged", lambda: _rounded(measure_paged_decode())
    )
    # fused Pallas kernel leg (r14): the same serving workload through
    # two engines differing only in attention impl — gather vs fused
    # kernel ("pallas" on TPU, interpret-mode on CPU where the numbers
    # are parity-only and the artifact discloses it)
    from .decode_bench import measure_paged_kernel

    out["paged_kernel"] = _timed(
        "decode.paged_kernel", lambda: measure_paged_kernel()
    )
    # flat decode.* keys at the artifact top level (the serve artifact's
    # flat-key pattern) — what the regress families gate on
    paged, kern = out["paged"], out["paged_kernel"]
    out["decode.paged_tok_s"] = paged["paged_tok_s"]
    out["decode.paged_speedup"] = paged["speedup"]
    out["decode.paged_tokens_exact"] = paged["tokens_exact"]
    out["decode.pages_leaked"] = paged["pages_leaked"]
    out["decode.kernel_tokens_exact"] = kern["tokens_exact"]
    out["decode.kernel_parity_ok"] = kern["parity_ok"]
    out["decode.kernel_pages_leaked"] = (
        kern["pages_leaked_gather"] + kern["pages_leaked_kernel"]
    )
    if "kernel_vs_gather_speedup" in kern:
        # present only when measured on TPU (the CPU interpret wall
        # is the evaluator's, not the lowered kernel's)
        out["decode.kernel_vs_gather_speedup"] = (
            kern["kernel_vs_gather_speedup"]
        )
    if len(jax.devices()) >= 2:
        out["tp_sharded"] = _timed(
            "decode.tp", lambda: measure_decode_sharded(tp=2)
        )
    else:
        # a single chip cannot run tp=2; the CPU-virtual number is
        # functional-only noise — skip, and say so
        out["tp_sharded"] = {
            "skipped": f"{len(jax.devices())} device(s); tp decode is "
            "dryrun/CPU-mesh-tested only (tests/test_sharded_decode.py)"
        }
    return out


def capture_train() -> Dict[str, Any]:
    import jax

    from .train_bench import measure_train_dag

    if jax.devices()[0].platform == "tpu":
        return measure_train_dag(cache_dir=CACHE_DIR)
    # CPU rehearsal scale, disclosed via the artifact's model tag: the
    # full config-#5 step takes minutes per execution on a host, and the
    # completion-cliff story (eviction-aware policies place 100% under
    # the 0.55x pressure budget where critical/dfs drop tasks) is what
    # the artifact exists to show
    return measure_train_dag(batch=4, seq_len=128, cache_dir=CACHE_DIR)


LEGS = {
    "stream": ("STREAM", capture_stream),
    "decode": ("DECODE", capture_decode),
    "train": ("TRAIN", capture_train),
}


def main(argv) -> int:
    if not argv or not argv[0].isdigit():
        print(__doc__, file=sys.stderr)
        return 2
    round_n = int(argv[0])
    wanted = argv[1:] or list(LEGS)
    unknown = [w for w in wanted if w not in LEGS]
    if unknown:
        print(f"unknown legs {unknown}; have {sorted(LEGS)}",
              file=sys.stderr)
        return 2

    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    log(f"capture: round {round_n}, platform={platform} "
        f"({dev.device_kind} x{len(jax.devices())}), legs={wanted}")
    from distributed_llm_scheduler_tpu.obs import (
        ambient_metrics,
        ambient_tracer,
        reset_ambient,
    )

    for w in wanted:
        prefix, fn = LEGS[w]
        t0 = time.time()
        reset_ambient()  # each leg's ambient snapshot starts clean
        out = _timed(w, fn)
        out.setdefault("platform", platform)
        out["device_kind"] = dev.device_kind
        out["round"] = round_n
        # DLS_TRACE=1: attach the leg's ambient metrics snapshot (obs) —
        # transfer bytes per edge, jit-cache hits, overhead histograms
        amb = ambient_metrics()
        if amb is not None:
            out["obs_metrics"] = amb.snapshot()
        atr = ambient_tracer()
        if atr is not None:
            # run-doctor attribution of the leg's last traced execute
            from distributed_llm_scheduler_tpu.obs import attribute_run

            att = attribute_run(atr)
            if att.critical_path:
                out["obs_attribution"] = att.summary()
        path = os.path.join(REPO_ROOT, f"{prefix}_r{round_n:02d}.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        log(f"capture[{w}]: wrote {path} ({time.time()-t0:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

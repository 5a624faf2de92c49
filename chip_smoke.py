#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two halves of the main path once, through the entry points a
user would call, at the full width of GPT-2 small (d 768, 12 layers,
vocab 50257, f32; random weights from a fixed seed):

* **serve** — ``serve --model gpt2 --requests 12 --seed 7`` through the
  paged continuous-batching engine, with the attention the dispatch picks
  (the Pallas paged kernel on a TPU) and again with ``--attention-impl
  xla`` (the gather path), whole-prompt and with ``--chunk-tokens 8``;
* **execute** — ``execute --model gpt2 --batch 8 --seq-len 512
  --microbatches 8 --num-nodes N`` through the placed-DAG executor
  (the planned path, same-device spans fused);
* **kernels** — the three Pallas attention kernels, compiled, against
  their XLA references at the geometry the other phases used;
* **state** — what ran: resolved attention impl, device memory after the
  serve phase, compile-cache directory and size.

Every phase checks that what came out is right (see each ``phase_*``
docstring for the gate and its tolerance), times its wall seconds (each
ends in a host readback or ``block_until_ready``) and reports compile
seconds separately.  Any failed phase fails the run.

One process owns the chip: the CLI is called in-process
(``distributed_llm_scheduler_tpu.__main__.main``) and nothing that needs
JAX is ever started as a child.  Exits non-zero, printing no result, when
JAX gives anything but a TPU.  Needs no network.  The last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py              # one chip: every phase above
    python chip_smoke.py --chips 4    # a four-chip host: ONLY the placed
                                      # execute over four devices and the
                                      # measured chip-to-chip link
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

#: the only platform a result is printed for
REQUIRED_PLATFORM = "tpu"

#: the serve CLI's fixed cache geometry (``__main__.cmd_serve``)
SERVE_GEOMETRY = dict(slots=4, page_size=8, n_pages=13, pages_per_seq=4)

#: a served token passes when its logit under the float32 reference
#: (``jax.default_matmul_precision("highest")``, teacher-forced on the
#: run's own tokens) is within this of that position's best logit.  The
#: engine runs f32 at the backend's DEFAULT matmul precision — bf16
#: passes on a TPU — so a greedy argmax may legally land on a near-tie;
#: it may not land further away than this.
TOKEN_LOGIT_TOL = 0.05
#: max |logit difference| between one decode step of the placed paged DAG
#: (kernel, then gather) and its dense per-slot reference, same precision
STEP_LOGIT_TOL = 0.05
#: max |difference| between a compiled kernel and the XLA reference
#: evaluated at "highest" precision, on unit-normal inputs
KERNEL_ATOL = 3e-2


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Sums what jax reports about compilation (``jax.monitoring``) so a
    phase can say how much of its wall time was compiling, and whether
    the persistent cache served it."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _TRACE = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self) -> None:
        from jax import monitoring

        self.t = {"compile_s": 0.0, "trace_lower_s": 0.0}
        self.n = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._BACKEND:
            self.t["compile_s"] += secs
            self.n["compiles"] += 1
        elif event in self._TRACE:
            self.t["trace_lower_s"] += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            self.n["cache_hits"] += 1
        elif event.endswith("/cache_misses"):
            self.n["cache_misses"] += 1

    def snapshot(self) -> Dict[str, float]:
        return {**self.t, **self.n}

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {
            k: (round(now[k] - snap[k], 3) if isinstance(now[k], float)
                else now[k] - snap[k])
            for k in now
        }


@contextlib.contextmanager
def timed(meter: CompileMeter, out: Dict[str, Any]):
    """Stamp ``wall_s`` and the compile breakdown of a block into ``out``.
    The block must end in a host readback / ``block_until_ready``."""
    snap, t0 = meter.snapshot(), time.perf_counter()
    try:
        yield out
    finally:
        out["wall_s"] = round(time.perf_counter() - t0, 3)
        out.update(meter.since(snap))


def run_cli(argv: List[str]) -> Dict[str, Any]:
    """One CLI command, in this process; returns its exit code and the
    JSON it printed on stdout."""
    from distributed_llm_scheduler_tpu.__main__ import main as cli_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(argv)
    text = buf.getvalue()
    try:
        printed = json.loads(text[text.index("{"):])
    except ValueError:
        printed = None
    return {"rc": rc, "printed": printed}


def memory_stats() -> Optional[Dict[str, int]]:
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def cache_state() -> Dict[str, Any]:
    import jax

    path = jax.config.jax_compilation_cache_dir
    size = n = 0
    if path and os.path.isdir(path):
        for root, _dirs, files in os.walk(path):
            for f in files:
                size += os.path.getsize(os.path.join(root, f))
                n += 1
    return {"dir": path, "entries": n, "bytes": size,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}


def _page_table(rows_per_slot, page_size: int, pages_per_seq: int):
    """(slots, pages_per_seq) int32 table giving each slot, in order, the
    physical pages its ``rows_per_slot[s]`` rows need (page 0 is the trash
    page and pads the tails)."""
    import numpy as np

    table = np.zeros((len(rows_per_slot), pages_per_seq), np.int32)
    nxt = 1
    for s, rows in enumerate(rows_per_slot):
        n = -(-int(rows) // page_size)
        table[s, :n] = range(nxt, nxt + n)
        nxt += n
    return table


# -- serve -------------------------------------------------------------------


def _serve_leg(model: str, impl: Optional[str], chunk: Optional[int],
               meter: CompileMeter) -> Dict[str, Any]:
    """One ``serve`` CLI run; returns its summary, tokens and rows."""
    leg: Dict[str, Any] = {"impl_requested": impl or "auto",
                           "chunk_tokens": chunk}
    with tempfile.TemporaryDirectory() as td:
        out_path = os.path.join(td, "serve.json")
        argv = ["serve", "--model", model, "--requests", "12", "--seed",
                "7", "--out", out_path]
        if impl is not None:
            argv += ["--attention-impl", impl]
        if chunk is not None:
            argv += ["--chunk-tokens", str(chunk)]
        with timed(meter, leg):
            res = run_cli(argv)
        leg["rc"] = res["rc"]
        with open(out_path) as f:
            report = json.load(f)
    for k in ("n_requests", "completed", "shed", "preemptions",
              "pages_leaked", "breached", "attention_impl", "digest",
              "device", "tokens_total"):
        leg[k] = report[k]
    leg["tokens"] = report["tokens"]
    leg["rows"] = [
        {k: r[k] for k in ("rid", "prompt_len", "max_new_tokens")}
        for r in report["requests"]
    ]
    leg["ok"] = bool(
        res["rc"] == 0
        and report["completed"] == report["n_requests"] == 12
        and report["pages_leaked"] == 0
        and all(len(report["tokens"][r["rid"]]) == r["max_new_tokens"]
                for r in leg["rows"])
    )
    gc.collect()  # the leg's engine (and its weights) must not outlive it
    return leg


def _token_reference(model: str):
    """The float32 dense reference for the serve legs, built once: returns
    ``check(leg)``, which teacher-forces it on each request's prompt +
    served tokens and measures, per served token, how far its reference
    logit sits below that position's best one (0 for an exact argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_scheduler_tpu.models import decode, gpt2
    from distributed_llm_scheduler_tpu.serve.loadgen import prompt_token_ids
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    cfg = RunConfig(model=model).model_config()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    cap = SERVE_GEOMETRY["page_size"] * SERVE_GEOMETRY["pages_per_seq"]

    @jax.jit
    def ref_logits(p, x):
        cache = decode.init_cache(
            cfg.n_layer, x.shape[0], cfg.n_head, cap, cfg.head_dim, cfg.dtype
        )
        return gpt2.forward_cached(p, x, cache, 0, cfg)[0]

    def check(leg: Dict[str, Any]) -> Dict[str, Any]:
        rows = leg["rows"]
        ids = np.zeros((len(rows), cap), np.int32)
        for j, r in enumerate(rows):
            prompt = np.asarray(prompt_token_ids(
                r["rid"], r["prompt_len"], cfg.vocab_size, 0
            ))[0]
            seq = np.concatenate(
                [prompt, np.asarray(leg["tokens"][r["rid"]])])
            ids[j, :len(seq) - 1] = seq[:-1]  # causal: the padding is inert
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(ref_logits(params, jnp.asarray(ids)),
                                np.float32)
        worst, n_tok, n_exact = 0.0, 0, 0
        for j, r in enumerate(rows):
            P = r["prompt_len"]
            for i, tok in enumerate(leg["tokens"][r["rid"]]):
                row = logits[j, P - 1 + i]
                short = float(row.max() - row[tok])
                worst = max(worst, short)
                n_tok += 1
                n_exact += int(short == 0.0)
        finite = bool(np.isfinite(logits).all())
        return {"tokens_checked": n_tok, "reference_argmax": n_exact,
                "max_logit_shortfall": round(worst, 6), "finite": finite,
                "ok": finite and worst <= TOKEN_LOGIT_TOL}

    return check


def _step_logit_parity(model: str, kernel_impl: Optional[str],
                       ) -> Dict[str, Any]:
    """One decode step of the placed paged DAG at the serve geometry, on
    pools holding random K/V at ragged lengths: the logits with the
    kernel and with the gather path against the DAG's own dense per-slot
    reference (``PagedDecodeDAG.reference_forward``).  Random weights make
    greedy tokens insensitive to attention, so parity is also gated
    here, where a wrong kernel moves every logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    cfg = RunConfig(model=model).model_config()
    geo = SERVE_GEOMETRY
    rng = np.random.RandomState(11)
    lengths = np.asarray([0, 7, 8, 23], np.int32)[: geo["slots"]]
    table = _page_table(lengths + 1, geo["page_size"], geo["pages_per_seq"])
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    out: Dict[str, Any] = {}
    logits: Dict[str, Any] = {}
    pools: Dict[str, Any] = {}
    for name, impl in (("kernel", kernel_impl), ("gather", "xla")):
        dag = build_paged_decode_dag(cfg, attention_impl=impl, **geo)
        params = dag.init_params()
        for k in sorted(params):
            if k.startswith("cache_"):
                if k not in pools:  # the same random K/V for both impls
                    pools[k] = jnp.asarray(
                        rng.standard_normal(params[k].shape),
                        params[k].dtype,
                    )
                params[k] = pools[k]
        params["page_table"] = jnp.asarray(table)
        inputs = dag.make_inputs(lengths=lengths)
        sched = get_scheduler("greedy").schedule(dag.graph, cluster)
        rep = DeviceBackend(cluster).execute(
            dag.graph, sched, params, inputs
        )
        logits[name] = np.asarray(rep.output, np.float32)
        if name == "kernel":
            logits["dense"] = np.asarray(
                jax.jit(dag.reference_forward)(params, inputs), np.float32
            )
    for name in ("kernel", "gather"):
        out[f"{name}_vs_dense"] = round(
            float(np.abs(logits[name] - logits["dense"]).max()), 6)
    out["kernel_vs_gather"] = round(
        float(np.abs(logits["kernel"] - logits["gather"]).max()), 6)
    out["logit_scale"] = round(float(np.abs(logits["dense"]).max()), 4)
    out["ok"] = bool(
        all(np.isfinite(v).all() for v in logits.values())
        and max(out["kernel_vs_dense"], out["gather_vs_dense"])
        <= STEP_LOGIT_TOL
    )
    return out


def phase_serve(meter: CompileMeter, model: str = "gpt2",
                kernel_impl: Optional[str] = None) -> Dict[str, Any]:
    """The paged serving engine through ``serve``.

    Gates: every leg answers 12/12 requests with exactly the tokens owed
    and leaks no page; the kernel legs' tokens equal the gather legs'
    request by request — or, where they do not, every served token of
    both is a near-argmax of the float32 reference within
    ``TOKEN_LOGIT_TOL`` (the output says which held); every leg passes
    that reference check regardless; whole-prompt and chunked prefill
    serve the same tokens; one decode step's logits match the dense
    reference within ``STEP_LOGIT_TOL`` under both impls.

    ``kernel_impl=None`` is the CLI default (auto); tests pass
    ``"pallas_interpret"`` to drive the kernels on CPU."""
    ph: Dict[str, Any] = {"model": model, "legs": {}}
    with timed(meter, ph):
        reference = None
        for chunk in (None, 8):
            for impl in (kernel_impl, "xla"):
                name = (("kernel" if impl != "xla" else "gather")
                        + ("_chunked" if chunk else ""))
                leg = _serve_leg(model, impl, chunk, meter)
                if reference is None:
                    # measured BEFORE the reference brings its own copy
                    # of the weights onto the device
                    ph["memory_after_first_leg"] = memory_stats()
                    ph["cache_after_first_leg"] = cache_state()
                    reference = _token_reference(model)
                leg["reference"] = reference(leg)
                ph["legs"][name] = leg
                log(f"serve[{name}]: rc={leg['rc']} completed="
                    f"{leg['completed']}/{leg['n_requests']} leaked="
                    f"{leg['pages_leaked']} impl={leg['attention_impl']} "
                    f"wall={leg['wall_s']}s compile={leg['compile_s']}s "
                    f"shortfall={leg['reference']['max_logit_shortfall']}")
        del reference
        ph["step_logits"] = _step_logit_parity(model, kernel_impl)
        log(f"serve[step logits]: {ph['step_logits']}")
    legs = ph["legs"]

    def same(a: str, b: str) -> bool:
        return legs[a]["tokens"] == legs[b]["tokens"]

    ph["token_parity"] = {
        "kernel_vs_gather": same("kernel", "gather"),
        "kernel_vs_gather_chunked": same("kernel_chunked",
                                         "gather_chunked"),
        "whole_vs_chunked": same("gather", "gather_chunked"),
    }
    reference_ok = all(leg["reference"]["ok"] for leg in legs.values())
    exact = all(ph["token_parity"].values())
    ph["parity_gate"] = (
        "tokens exact" if exact else
        f"reference logits within {TOKEN_LOGIT_TOL} (tokens differ)"
    )
    ph["attention_impl"] = legs["kernel"]["attention_impl"]
    ph["ok"] = bool(
        all(leg["ok"] for leg in legs.values())
        and reference_ok
        and ph["step_logits"]["ok"]
    )
    for leg in legs.values():  # keep the report readable
        leg.pop("tokens"), leg.pop("rows")
    return ph


# -- execute -----------------------------------------------------------------


def phase_execute(meter: CompileMeter, model: str = "gpt2", batch: int = 8,
                  seq_len: int = 512, microbatches: int = 8,
                  num_nodes: int = 1,
                  schedulers: tuple = ("heft",)) -> Dict[str, Any]:
    """The placed-DAG executor through ``execute``: the planned path (fused
    same-device launches: ``n_dispatches`` beside ``n_tasks`` shows how
    many programs a step took), one leg per scheduler.

    Gates: the CLI exits 0 on ``num_nodes`` devices; with more than one
    node, every device reports a non-zero HBM peak and at least one edge
    crossed devices; and the output of the same graph + schedule re-driven
    through ``DeviceBackend.execute`` matches ``jit(reference_forward)``
    under ``benchlib.oracle_close`` (the CLI prints only a summary)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.eval.benchlib import oracle_close
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    ph: Dict[str, Any] = {"model": model, "num_nodes": num_nodes,
                          "legs": {}}
    with timed(meter, ph):
        for sched_name in schedulers:
            cfg = RunConfig(
                model=model, batch=batch, seq_len=seq_len,
                microbatches=microbatches, num_nodes=num_nodes,
                scheduler=sched_name,
            )
            dag = cfg.build_graph()
            cluster = cfg.build_cluster_with_devices()
            schedule = cfg.build_scheduler().schedule(dag.graph, cluster)
            params, ids = dag.init_params(), dag.make_inputs()
            want = jax.jit(dag.reference_forward)(params, ids)
            dtype_name = jnp.dtype(dag.config.dtype).name
            backend = DeviceBackend(cluster)
            leg: Dict[str, Any] = {}
            argv = ["execute", "--model", model, "--batch", str(batch),
                    "--seq-len", str(seq_len), "--microbatches",
                    str(microbatches), "--num-nodes", str(num_nodes),
                    "--scheduler", sched_name]
            with timed(meter, leg):
                res = run_cli(argv)
            s = res["printed"] or {}
            leg.update(rc=res["rc"], **{
                k: s.get(k) for k in (
                    "n_devices", "makespan_ms", "n_dispatches",
                    "transfer_edges", "peak_hbm_gb", "planned",
                    "device", "attention_impl")
            })
            with timed(meter, leg.setdefault("oracle", {})):
                rep = backend.execute(dag.graph, schedule, params, ids)
                got = np.asarray(rep.output, np.float32)
            ref = np.asarray(want, np.float32)
            leg["n_tasks"] = len(dag.graph.topo_order)
            leg["oracle"].update(
                n_dispatches=rep.n_dispatches,
                bitwise=bool(np.array_equal(got, ref)),
                max_abs_diff=round(float(np.abs(got - ref).max()), 6),
                rel_fro=float(np.linalg.norm((got - ref).ravel())
                              / max(np.linalg.norm(ref.ravel()), 1e-12)),
                finite=bool(np.isfinite(got).all()),
                close=bool(oracle_close(want, rep.output, dtype_name)),
            )
            peaks = leg.get("peak_hbm_gb") or {}
            # per-device peaks exist where the platform reports
            # memory_stats (a TPU does; the CPU test mesh does not)
            peaks_ok = memory_stats() is None or (
                len(peaks) == num_nodes
                and all(v > 0 for v in peaks.values())
            )
            leg["ok"] = bool(
                res["rc"] == 0
                and leg["n_devices"] == num_nodes
                and leg["oracle"]["finite"] and leg["oracle"]["close"]
                and peaks_ok
                and (num_nodes == 1
                     or (leg["transfer_edges"] or 0) > 0)
            )
            ph["legs"][sched_name] = leg
            log(f"execute[{sched_name}]: rc={leg['rc']} devices="
                f"{leg['n_devices']} launches={leg['n_dispatches']} "
                f"(oracle run {rep.n_dispatches}) of "
                f"{leg['n_tasks']} tasks makespan="
                f"{leg['makespan_ms']}ms "
                f"edges={leg['transfer_edges']} oracle="
                f"{leg['oracle']['close']} (bitwise="
                f"{leg['oracle']['bitwise']} max|d|="
                f"{leg['oracle']['max_abs_diff']}) wall={leg['wall_s']}s "
                f"compile={leg['compile_s']}s")
            del want, params, backend
            gc.collect()
    ph["ok"] = all(leg["ok"] for leg in ph["legs"].values())
    return ph


# -- kernels -----------------------------------------------------------------


def _paged_inputs(rng, S, Hq, Hkv, hd, ps, ppseq, n_pages, lengths, dtype,
                  q_tokens=1):
    import jax.numpy as jnp
    import numpy as np

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    table = _page_table(np.asarray(lengths) + q_tokens, ps, ppseq)
    return dict(
        q=arr(S, Hq, q_tokens, hd), k_pool=arr(n_pages, ps, Hkv, hd),
        v_pool=arr(n_pages, ps, Hkv, hd), page_table=jnp.asarray(table),
        lengths=jnp.asarray(lengths, jnp.int32),
    )


def _kernel_case(name: str, run_kernel, run_ref) -> Dict[str, Any]:
    """Compile + run one kernel, compare with its XLA reference evaluated
    at "highest" matmul precision."""
    import jax
    import numpy as np

    case: Dict[str, Any] = {"name": name}
    try:
        got = np.asarray(jax.block_until_ready(run_kernel()), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(run_ref(), np.float32)
        case["max_abs_diff"] = round(float(np.abs(got - want).max()), 6)
        case["ok"] = bool(np.isfinite(got).all()
                          and case["max_abs_diff"] <= KERNEL_ATOL)
    except Exception as e:  # a kernel that does not lower is a finding
        case["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        case["ok"] = False
    return case


def phase_kernels(meter: CompileMeter, interpret: bool = False,
                  n_head: int = 12, head_dim: int = 64,
                  flash_T: int = 512) -> Dict[str, Any]:
    """``_flash_mha``, ``_flash_mha_rows``, ``_paged_flash`` and
    ``_paged_flash_ragged`` compiled (``interpret`` is for CPU tests only) against their XLA
    references within ``KERNEL_ATOL``, at the serve geometry (page 8, 4
    slots, 4 pages per slot) and the execute phase's sequence length.

    ``probe`` runs the paged kernels at geometries
    ``paged_kernel_constraints`` rejects today (they compile for the v5e
    ahead of time): informational, never part of the gate — it says
    whether those rules describe the chip."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_scheduler_tpu.ops import attention as A

    geo = SERVE_GEOMETRY
    S, ps, ppseq, npg = (geo["slots"], geo["page_size"],
                         geo["pages_per_seq"], geo["n_pages"])
    scale = 1.0 / float(np.sqrt(head_dim))
    ph: Dict[str, Any] = {"interpret": interpret, "cases": [], "probe": []}

    def paged_case(name, dtype=jnp.float32, ps=ps, hd=head_dim, Hq=n_head,
                   Hkv=n_head, lengths=(0, 7, 8, 23), ppseq=ppseq,
                   stored=False):
        rng = np.random.RandomState(5)
        x = _paged_inputs(rng, S, Hq, Hkv, hd, ps, ppseq,
                          S * ppseq + 1, lengths, dtype)
        if stored:  # the engine's form: a row's heads side by side
            for pool in ("k_pool", "v_pool"):
                x[pool] = x[pool].reshape(*x[pool].shape[:2], Hkv * hd)
        kn = jnp.asarray(rng.standard_normal((S, Hkv, 1, hd)), dtype)
        vn = jnp.asarray(rng.standard_normal((S, Hkv, 1, hd)), dtype)
        sc = 1.0 / float(np.sqrt(hd))
        return _kernel_case(
            name,
            lambda: A._paged_flash(
                x["q"], x["k_pool"], x["v_pool"], x["page_table"],
                x["lengths"], kn, vn, sm_scale=sc, has_new=True,
                interpret=interpret),
            lambda: A.paged_decode_attention(
                x["q"], x["k_pool"], x["v_pool"], x["page_table"],
                x["lengths"], sc, k_new=kn, v_new=vn, impl="xla"),
        )

    def ragged_case(name, dtype=jnp.float32, q_tokens=8, ps=ps,
                    lengths=(0, 8, 16, 3), q_lens=(8, 8, 5, 1)):
        rng = np.random.RandomState(6)
        x = _paged_inputs(rng, S, n_head, n_head, head_dim, ps, ppseq,
                          S * ppseq + 1, lengths, dtype, q_tokens=q_tokens)
        ql = jnp.asarray(q_lens, jnp.int32)
        return _kernel_case(
            name,
            lambda: A._paged_flash_ragged(
                x["q"], x["k_pool"], x["v_pool"], x["page_table"],
                x["lengths"], ql, sm_scale=scale, interpret=interpret),
            lambda: A.paged_decode_attention(
                x["q"], x["k_pool"], x["v_pool"], x["page_table"],
                x["lengths"], scale, impl="xla", q_lens=ql),
        )

    def flash_case(name, T):
        rng = np.random.RandomState(7)
        q, k, v = (jnp.asarray(rng.standard_normal((1, n_head, T, head_dim)),
                               jnp.float32) for _ in range(3))
        return _kernel_case(
            name,
            lambda: A._flash_mha(q, k, v, causal=True, sm_scale=scale,
                                 block=A._pick_block(T), interpret=interpret),
            lambda: A.reference_mha(q, k, v, causal=True, sm_scale=scale),
        )

    def rows_case(name, T=512, H=16, hd=64):
        """The row form at the medium-DAG attention task's shape: bf16 q,
        k, v read as thirds of one ``(B, T, 3 * H * hd)`` projection
        result, against the reference on the float32 head-split view."""
        from distributed_llm_scheduler_tpu.ops import flash_rows as R

        rng = np.random.RandomState(9)
        qkv = jnp.asarray(rng.standard_normal((2, T, 3 * H * hd)),
                          jnp.bfloat16)
        return _kernel_case(
            name,
            lambda: R._flash_mha_rows(
                qkv, qkv, qkv, n_head=H, packed=True, causal=True,
                sm_scale=hd ** -0.5, interpret=interpret),
            lambda: R._merge_heads(A.reference_mha(
                *(t.astype(jnp.float32) for t in R._split_heads(qkv, H)),
                causal=True, sm_scale=hd ** -0.5)),
        )

    def latent_cases():
        """The Xing4.0 block's three kernels (``models/xing4.py``,
        ``ops/attention._mla_paged_flash``) and the prefill's
        ``_mla_chunk_flash`` compiled at a tiny aligned geometry against
        their gather / XLA paths: whether they start."""
        from distributed_llm_scheduler_tpu.models import xing4 as X

        rng = np.random.RandomState(8)
        dt = jnp.bfloat16

        def arr(*shape, scale=1.0, dtype=dt):
            return jnp.asarray(scale * rng.standard_normal(shape), dtype)

        H, W, rank, lps, lpp = 4, 256, 128, 16, 4
        lengths = (0, 15, 16, 41)
        q, pool = arr(S, H, W, scale=0.1), arr(S * lpp + 1, lps, W)
        new = arr(S, W)
        table = jnp.asarray(_page_table(np.asarray(lengths) + 1, lps, lpp))
        lens = jnp.asarray(lengths, jnp.int32)
        h, I, E, k, N = 256, 512, 8, 2, 12
        x = arr(N, h)
        idx = jnp.asarray(rng.randint(0, E, size=(N, k)), jnp.int32)
        gate = arr(N, k, dtype=jnp.float32)
        gu, dw = arr(E, 2 * I, h, scale=0.05), arr(E, I, h, scale=0.05)
        kern = "pallas_interpret" if interpret else "pallas"
        cfg = X.Xing4Config.tiny(hidden_size=h, dtype=dt)
        p = {"hca_phi": arr(24, 4 * h, scale=0.02, dtype=jnp.float32),
             "hca_alpha": jnp.full((3,), 0.5, jnp.float32),
             "hca_b": arr(24, dtype=jnp.float32)}
        streams = arr(N, 4, h)
        # the prefill's expanded-MLA kernel through the family's own
        # entry: 32 queries at 560 over a cache of 640 rows, two key
        # blocks, the last one ragged
        ccfg = X.Xing4Config.tiny(
            n_heads=H, kv_lora_rank=rank, qk_nope_head_dim=64,
            qk_rope_head_dim=64, v_head_dim=128, dtype=dt)
        cq = (arr(1, 32, H, 64, scale=0.3), arr(1, 32, H, 64, scale=0.3))
        crows = arr(1, 640, W)
        cp = {"kv_b_w": arr(rank, H * (64 + 128), scale=0.1)}

        def chunk_attn(impl):
            return X.mla_expanded_attention(
                cp, *cq, crows, jnp.int32(560), ccfg, impl)

        return [
            _kernel_case("mla_chunk_flash_bf16", lambda: chunk_attn(kern),
                         lambda: chunk_attn("xla")),
            _kernel_case(
                "mla_paged_flash_ps16_bf16",
                lambda: A._mla_paged_flash(
                    q, pool, table, lens, new, rank=rank, has_new=True,
                    interpret=interpret),
                lambda: A.mla_paged_decode_attention(
                    q, pool, table, lens, rank, new_row=new, impl="xla")),
            _kernel_case(
                "moe_experts_bf16",
                lambda: X._moe_experts(x, idx, gate, gu, dw, impl=kern)[0],
                # float32 operands: at "highest" the v5e compiler refuses
                # ragged_dot's own kernel on bf16 ("Bad lhs type", PR 27)
                lambda: X._moe_experts(
                    x.astype(jnp.float32), idx, gate, gu.astype(jnp.float32),
                    dw.astype(jnp.float32), impl="xla")[0]),
            _kernel_case(
                "hc_maps_bf16",
                lambda: jnp.concatenate([m.reshape(N, -1) for m in X.hc_maps(
                    streams, p, "hca", cfg, kern)], -1),
                lambda: jnp.concatenate([m.reshape(N, -1) for m in X.hc_maps(
                    streams, p, "hca", cfg, "xla")], -1)),
        ]

    with timed(meter, ph):
        ph["cases"] = [
            flash_case(f"flash_mha_T{flash_T}", flash_T),
            rows_case("flash_mha_rows_T512_h16"),
            paged_case(f"paged_flash_ps{ps}_f32"),
            ragged_case(f"paged_flash_ragged_ps{ps}_q8_f32"),
        ]
        ph["probe"] = [
            paged_case("paged_flash_ps4_f32", ps=4, lengths=(0, 3, 4, 11)),
            paged_case("paged_flash_ps8_bf16", dtype=jnp.bfloat16),
            paged_case("paged_flash_ps8_hd12_f32", hd=12, Hq=4, Hkv=2),
            # GPT-2 XL's stored row (25 heads of 64 = 1,600 values, not a
            # whole number of 128-lane tiles), three blocks of 9 pages
            paged_case("paged_flash_ps16_w1600_bf16", dtype=jnp.bfloat16,
                       ps=16, hd=64, Hq=25, Hkv=25, ppseq=20,
                       lengths=(0, 143, 144, 300), stored=True),
            ragged_case("paged_flash_ragged_ps8_q7_f32", q_tokens=7,
                        q_lens=(7, 7, 5, 1)),
        ] + latent_cases()
    for c in ph["cases"] + ph["probe"]:
        log(f"kernel[{c['name']}]: ok={c['ok']} "
            f"max|d|={c.get('max_abs_diff')} {c.get('error', '')}")
    ph["ok"] = all(c["ok"] for c in ph["cases"])
    return ph


# -- link (four chips) -------------------------------------------------------


def phase_link(meter: CompileMeter) -> Dict[str, Any]:
    """``utils.linkmodel.calibrate_link`` over the live devices: with a
    sibling chip the device-to-device leg is MEASURED (on one chip it can
    only be the documented estimate).  Gate: both legs measured, finite
    and positive."""
    import math

    import jax

    from distributed_llm_scheduler_tpu.utils.linkmodel import calibrate_link

    ph: Dict[str, Any] = {}
    with timed(meter, ph):
        cal = calibrate_link(jax.devices(), repeats=3)
    ph.update(
        host_gbps=round(cal.param_load_gbps, 3),
        interconnect_gbps=round(cal.interconnect_gbps, 3),
        latency_us=round(cal.latency_s * 1e6, 2),
        provenance=dict(cal.provenance),
    )
    ph["ok"] = bool(
        cal.provenance.get("param_load") == "measured"
        and cal.provenance.get("interconnect") == "measured"
        and all(math.isfinite(v) and v > 0 for v in
                (cal.param_load_gbps, cal.interconnect_gbps))
    )
    log(f"link: host {ph['host_gbps']} GB/s, chip-to-chip "
        f"{ph['interconnect_gbps']} GB/s, latency {ph['latency_us']} us")
    return ph


# -- state -------------------------------------------------------------------


def phase_state(serve: Dict[str, Any], cache_start: Dict[str, Any],
                model: str = "gpt2") -> Dict[str, Any]:
    """What ran and what it left behind.  Gates: on the required platform
    ``auto`` resolved to the compiled kernel; the first serve leg's peak
    device memory is about ONE copy of the weights (under two — a copy
    per executable would be several); the serve path added under 100 MB
    to the compile cache, and to the directory the cache rule names."""
    import jax
    import numpy as np

    from distributed_llm_scheduler_tpu.models import gpt2
    from distributed_llm_scheduler_tpu.utils.config import RunConfig

    cfg = RunConfig(model=model).model_config()
    weight_bytes = sum(
        int(np.prod(shape)) * np.dtype(dtype).itemsize
        for shape, dtype in gpt2.param_shapes(cfg).values()
    )
    mem = serve.get("memory_after_first_leg")
    after_serve = serve.get("cache_after_first_leg") or {}
    st: Dict[str, Any] = {
        "attention_impl": serve.get("attention_impl"),
        "weight_bytes": weight_bytes,
        "memory_after_first_serve_leg": mem,
        "memory_now": memory_stats(),
        "compile_cache_start": cache_start,
        "compile_cache_after_first_serve_leg": after_serve,
        "compile_cache_now": cache_state(),
    }
    on_chip = jax.devices()[0].platform == REQUIRED_PLATFORM
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    want_dir = env_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
    checks = {
        "impl_is_kernel": (not on_chip) or st["attention_impl"] == "pallas",
        "one_copy_of_weights": (
            mem is None or mem["peak_bytes_in_use"] < 2 * weight_bytes
        ),
        "memory_reported": (not on_chip) or mem is not None,
        "cache_dir": os.path.realpath(str(cache_start["dir"]))
        == os.path.realpath(want_dir),
        "serve_cache_under_100mb": (
            after_serve.get("bytes", 0) - cache_start["bytes"] < 100e6
        ),
    }
    st["checks"] = checks
    st["ok"] = all(checks.values())
    log(f"state: impl={st['attention_impl']} weights={weight_bytes} B "
        f"memory after first serve leg={mem} cache now="
        f"{st['compile_cache_now']} checks={checks}")
    return st


# -- entry -------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: on a four-chip host, run only the placed "
                         "execute over four devices (pack and roundrobin) "
                         "and the measured chip-to-chip link")
    args = ap.parse_args(argv)

    import jax

    # importing the package places the compile cache, before any backend
    from distributed_llm_scheduler_tpu.__main__ import device_info

    dev = device_info()
    print(f"chip_smoke: platform={dev['platform']} device_kind="
          f"{dev['kind']} count={dev['count']}", flush=True)
    if dev["platform"] != REQUIRED_PLATFORM:
        log(f"needs platform {REQUIRED_PLATFORM!r}, jax.devices() gives "
            f"{dev['platform']!r} ({dev['kind']}); no result")
        return 3
    if dev["count"] < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found "
            f"{dev['count']}; no result")
        return 3

    t0 = time.perf_counter()
    meter = CompileMeter()
    cache_start = cache_state()
    log(f"compile cache: {cache_start}")
    phases: Dict[str, Any] = {}

    def run(name: str, fn, *a, **kw) -> None:
        try:
            phases[name] = fn(*a, **kw)
        except Exception:
            import traceback

            phases[name] = {"ok": False,
                            "error": traceback.format_exc(limit=12)}
            log(f"{name}: FAILED\n{phases[name]['error']}")

    if args.chips == 4:
        run("execute_4chip", phase_execute, meter, num_nodes=4,
            schedulers=("pack", "roundrobin"))
        run("link", phase_link, meter)
    else:
        run("serve", phase_serve, meter)
        run("state", lambda: phase_state(phases["serve"], cache_start))
        run("execute", phase_execute, meter)
        run("kernels", phase_kernels, meter)

    ok = all(p.get("ok") for p in phases.values())
    report = {"ok": ok, "device": dev,
              "wall_s": round(time.perf_counter() - t0, 1),
              "jax": jax.__version__, "phases": phases}
    try:
        os.makedirs("chiprun_out", exist_ok=True)
        name = "chip_smoke.json" if args.chips == 1 else "chip_smoke_4.json"
        with open(os.path.join("chiprun_out", name), "w") as f:
            json.dump(report, f, indent=1, sort_keys=True, default=str)
    except OSError as e:
        log(f"could not write chiprun_out/: {e}")
    for name, p in phases.items():
        print(f"chip_smoke: phase {name}: ok={p.get('ok')} wall_s="
              f"{p.get('wall_s')} compile_s={p.get('compile_s')} "
              f"compiles={p.get('compiles')} cache_hits="
              f"{p.get('cache_hits')}", flush=True)
    print(f"chip_smoke: wall_s={report['wall_s']} jax={jax.__version__}",
          flush=True)
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

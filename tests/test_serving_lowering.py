"""The serving programs of the families that are stepped one row a slot
(GPT-2, Xing4.0, dots3) lower to the text they lowered to before the
step learnt to verify drafts: ``tests/fixtures/serving_lowered_sha256.json``
holds the digests taken on the commit before (PR 32's tree, this file
copied there and run under pytest, so under the same ``conftest.py``,
with ``DLS_WRITE_LOWERING=<path>`` set: the cases then write their
digests to the path instead of comparing).

A PR that means to change one of these programs regenerates the fixture
on its own tree and says so; one that does not has moved a program it
did not mean to.  The text is ``jit(...).lower(...).as_text()`` — no
source locations, so it does not follow line numbers — of the decode
segment, the whole-prompt prefill and the chunk program of each family's
tiny variant, under the gather path and under the interpreted kernels.

PR 39 regenerated four of the eighteen on its own tree: the whole-prompt
and chunk programs of ``xing4-tiny`` and ``dots3-tiny`` under
``pallas_interpret``, whose expanded MLA became ``_mla_chunk_flash``.
Every segment, every ``gpt2-tiny`` program and every ``xla`` program is
the digest taken on PR 32's tree.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

FIXTURE = ROOT / "tests" / "fixtures" / "serving_lowered_sha256.json"
MODELS = ("gpt2-tiny", "xing4-tiny", "dots3-tiny")
IMPLS = ("xla", "pallas_interpret")
S, PS, PPSEQ, SEG, CHUNK = 3, 8, 6, 4, 16


def _engine(model: str, impl: str):
    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.decode_dag import (
        build_paged_decode_dag,
    )
    from distributed_llm_scheduler_tpu.models import model_config, module_of
    from distributed_llm_scheduler_tpu.models.kv_pages import PagePool

    cfg = model_config(model)
    n_pages = 1 + S * PPSEQ
    ddag = build_paged_decode_dag(
        cfg, slots=S, page_size=PS, n_pages=n_pages, pages_per_seq=PPSEQ,
        attention_impl=impl)
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    plan = get_scheduler("greedy").schedule(ddag.graph, cluster)
    weights = module_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return DeviceBackend(cluster).paged_decode_engine(
        ddag.graph, plan, cfg, weights,
        PagePool(n_pages=n_pages, page_size=PS), slots=S,
        pages_per_seq=PPSEQ, seg_steps=SEG, attention_impl=impl,
        chunk_tokens=CHUNK)


def lowered(model: str, impl: str) -> dict:
    """Name -> lowered text of the engine's serving programs."""
    eng = _engine(model, impl)
    i32 = jnp.int32
    # build the whole-prompt class (P = 8) and the chunk class without
    # running them: the private builders are keyed stores
    eng.submit("a", jnp.ones((1, 8), i32), 2)
    eng.submit("b", jnp.ones((1, 2 * CHUNK + 3), i32), 2)
    eng.run()
    texts = {"segment": eng._seg.lower(
        eng.weights, eng.pools, eng.page_table, eng.lengths, eng.cur_tok,
        eng.remaining).as_text()}
    ring1 = eng._ring_args((0,))
    for key, fn in eng._prefill_store.items():
        if key[0] == "chunk":
            args = (jnp.zeros((1, key[1]), i32), eng.pools,
                    jnp.zeros((PPSEQ,), i32), i32(0), i32(1), *ring1)
        else:
            P, b, _impl = key
            args = (jnp.zeros((b, P), i32), eng.pools,
                    jnp.zeros((b, PPSEQ), i32), *ring1)
        texts[str(key[0] if key[0] == "chunk" else "prefill")] = fn.lower(
            eng.weights, *args).as_text()
    return texts


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("impl", IMPLS)
def test_one_row_families_lower_to_the_text_they_lowered_to(model, impl):
    got = {f"{model}/{impl}/{name}": hashlib.sha256(text.encode()).hexdigest()
           for name, text in lowered(model, impl).items()}
    out = os.environ.get("DLS_WRITE_LOWERING")
    if out:
        have = json.loads(Path(out).read_text()) if Path(out).exists() else {}
        Path(out).write_text(json.dumps({**have, **got}, indent=1,
                                        sort_keys=True) + "\n")
        return
    want = json.loads(FIXTURE.read_text())
    assert set(got) == {k for k in want if k.startswith(f"{model}/{impl}/")}
    moved = sorted(k for k in got if got[k] != want[k])
    assert not moved, f"serving programs whose lowering changed: {moved}"

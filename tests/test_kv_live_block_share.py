"""``decode.kv_live_block_share`` (PR 25): the host-side counter that
says how much of the page table the paged decode kernel walks.

Pins: the share for fixed ``lengths`` vectors; that the engine observes
it once per ``step_segment`` that dispatches a segment — into its own
registry and into ``obs.process_metrics()`` — and not on a tick that
dispatches none; and that the benchmark's ``kv_live_block_share`` entry
reaches the histogram through the run's own loader and reader.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import obs
from distributed_llm_scheduler_tpu.backends.decode_loop import (
    kv_live_block_share,
)
from distributed_llm_scheduler_tpu.ops.attention import paged_block_pages

from test_paged_kernel import _build_engine

NAME = "decode.kv_live_block_share"


@pytest.mark.parametrize("lengths,rows,capacity,want", [
    # GPT-2 XL's table: 32 slots x 16 blocks of 64 rows; 7 slots decode
    ([130, 900, 350, 512, 260, 700, 445] + [0] * 25, 64, 1024,
     (3 + 15 + 6 + 9 + 5 + 11 + 7 + 25) / 512),
    # every slot empty: one block each, the floor
    ([0] * 32, 64, 1024, 32 / 512),
    # straddling a block boundary: rows 0..L are attended
    ([62, 63, 64, 127, 128], 64, 1024, (1 + 1 + 2 + 2 + 3) / 80),
    # a full slot, and one past capacity (clamped like the insert)
    ([1023, 5000], 64, 1024, 1.0),
    # a table that is not a whole number of blocks: 320 rows = 3 blocks
    ([319, 0, 256, 255], 128, 320, (3 + 1 + 3 + 2) / 12),
    # the tiny serving geometry: the whole table is one block
    ([0, 5, 31, 17], 32, 32, 1.0),
    # GPT-2 XL's table since the pools hold the row on the lanes (PR 28):
    # a block is 9 pages = 144 rows, 8 blocks a slot, the last of 16 rows
    ([130, 900, 350, 512, 260, 700, 445] + [0] * 25, 144, 1024,
     (1 + 7 + 3 + 4 + 2 + 5 + 4 + 25) / 256),
    ([0] * 32, 144, 1024, 32 / 256),
    ([143, 144, 1007, 1008, 1023], 144, 1024, (1 + 2 + 7 + 8 + 8) / 40),
])
def test_share_of_fixed_lengths(lengths, rows, capacity, want):
    got = kv_live_block_share(
        np.asarray(lengths, np.int32), rows, capacity)
    assert got == pytest.approx(want)


def test_engine_observes_once_per_dispatched_segment():
    obs.reset_ambient()
    eng, _pool, cfg = _build_engine("xla", slots=2, ps=8, n_pages=32,
                                    ppseq=4)
    assert eng.kv_block_rows == 8 * paged_block_pages(
        8, 4, cfg.n_head, cfg.head_dim, cfg.dtype)

    def count(reg):
        return reg.snapshot()["histograms"].get(NAME, {}).get("count", 0)

    # a tick with nothing to decode dispatches no segment: no observation
    assert eng.step_segment() == 0
    assert count(eng.metrics) == 0 and count(obs.process_metrics()) == 0
    rng = np.random.RandomState(3)
    eng.submit("a", jnp.asarray(
        rng.randint(0, cfg.vocab_size, (1, 8)), jnp.int32), 6)
    seen = 0
    while eng._queue or any(r is not None for r in eng._slot_req):
        before = eng.segments_run
        eng.step_segment()
        seen += eng.segments_run - before
        assert count(eng.metrics) == seen
        assert count(obs.process_metrics()) == seen
    assert seen >= 2
    snap = eng.metrics.snapshot()["histograms"][NAME]
    assert snap["unit"] == "ratio"
    # one block a slot at this geometry, so every slot is wholly live
    assert snap["min"] == snap["max"] == 1.0
    obs.reset_ambient()


def test_benchmark_entry_reads_the_histogram():
    from benchmark import harness

    obs.reset_ambient()
    for cell in ("xl-chat", "xl-docqa"):
        defs = [m for m in harness.load_cell(cell).per_layer
                if m["name"] == "kv_live_block_share"]
        assert len(defs) == 1 and cell in defs[0]["workloads"]
        assert defs[0]["layer"] == "attention kernels"
        assert defs[0]["moves"] == "tpot_ms_mean"
    for cell in ("m-dag-1chip", "m-dag-4chip"):
        assert "kv_live_block_share" not in {
            m["name"] for m in harness.load_cell(cell).per_layer}
    # nothing observed (the parent, a DAG cell): the metric is left out
    assert harness.read_metrics(defs, {}) == {}
    h = obs.process_metrics().histogram(NAME, unit="ratio")
    for v in (0.10, 0.20, 0.15):
        h.observe(v)
    got = harness.read_metrics(defs, {})
    assert got["kv_live_block_share"]["value"] == pytest.approx(0.15)
    assert got["kv_live_block_share"]["unit"] == "ratio"
    obs.reset_ambient()

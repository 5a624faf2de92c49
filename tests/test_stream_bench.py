"""Oversubscription probe (eval/stream_bench.py) functional check on CPU."""

import jax.numpy as jnp

from distributed_llm_scheduler_tpu.eval.stream_bench import measure_streaming
from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config


def test_measure_streaming_tiny():
    res = measure_streaming(
        config=GPT2Config.tiny(), batch=2, seq_len=32, budget_frac=0.3,
        log=lambda m: None,
    )
    assert res["oracle_ok"], res
    assert res["param_loads"] > 0
    assert res["param_evictions"] > 0
    assert res["budget_respected"], res
    assert res["capped_makespan_ms"] > 0
    assert res["total_param_gb"] > res["budget_gb"]
    # bound reporting: the artifact must show its
    # distance to its own floor
    assert res["param_load_calls"] <= res["param_loads"]
    assert res["param_load_gb"] > 0
    assert res["host_link_gbps"] > 0
    assert res["sustained_gbps"] > 0
    assert 0 < res["bound_utilization"] <= 1.5  # small slack for noise
    # sustained end-to-end throughput; must be consistent with the bytes
    # and makespan the same artifact reports
    expect = res["param_load_gb"] / (res["capped_makespan_ms"] / 1e3)
    assert abs(res["achieved_gbps"] - expect) < 0.01 * max(expect, 1.0)
    # int8 leg: same budget, roughly half the streamed bytes, parity
    # against its own quantized fused oracle — and the budget claim is
    # checked, not assumed
    assert res["quantized_oracle_ok"], res
    assert res["quantized_param_load_gb"] < 0.6 * res["param_load_gb"]
    assert res["quantized_capped_makespan_ms"] > 0
    assert res["quantized_budget_respected"], res
    assert res["quantized_peak_resident_gb"] <= res["budget_gb"] * 1.03

"""Pre-flight XLA memory analysis tests."""

import jax.numpy as jnp
import pytest

from distributed_llm_scheduler_tpu import Task, TaskGraph
from distributed_llm_scheduler_tpu.core.graph import GB
from distributed_llm_scheduler_tpu.utils.hbm import preflight_task_memory


def _mm(pd, x):
    return jnp.tanh(x @ pd["w"])


@pytest.fixture
def chain():
    dim = 256
    tasks = [
        Task(
            "t0", 1e-9, 0.001, [], {"w0"},
            param_bytes={"w0": dim * dim * 4}, fn=_mm,
            param_alias={"w": "w0"},
        ),
        Task(
            "t1", 5.0, 0.001, ["t0"], {"w1"},
            param_bytes={"w1": dim * dim * 4}, fn=_mm,
            param_alias={"w": "w1"},
        ),
    ]
    g = TaskGraph(tasks, name="pf").freeze()
    params = {
        "w0": jnp.ones((dim, dim), jnp.float32),
        "w1": jnp.ones((dim, dim), jnp.float32),
    }
    x = jnp.ones((64, dim), jnp.float32)
    return g, params, x


def test_preflight_raises_optimistic_estimates(chain):
    g, params, x = chain
    compiled = preflight_task_memory(g, params, x)
    # t0's analytic 1e-9 GB was optimistic: output alone is 64*256*4 bytes
    assert g["t0"].memory_required >= (64 * 256 * 4) / GB
    assert g["t0"].memory_required == pytest.approx(compiled["t0"])


def test_preflight_never_lowers_estimates(chain):
    g, params, x = chain
    preflight_task_memory(g, params, x)
    # t1's analytic 5 GB is pessimistic vs the compiled footprint; keep it
    assert g["t1"].memory_required == 5.0


def test_preflight_shares_compiles_across_aliased_tasks(chain):
    g, params, x = chain
    compiled = preflight_task_memory(g, params, x)
    # same fn object + same shapes -> same cached compiled footprint
    assert compiled["t0"] == compiled["t1"]


def test_preflight_skips_schedule_only_graphs():
    g = TaskGraph([Task("a", 0.5, 1.0, [])], name="sched_only").freeze()
    assert preflight_task_memory(g, {}, None) == {}
    assert g["a"].memory_required == 0.5


def test_preflight_records_true_output_bytes(chain):
    g, params, x = chain
    preflight_task_memory(g, params, x)
    # output of t0 is the (64, 256) f32 activation — transfers must be
    # charged by this, not by the temp-inflated footprint
    assert g["t0"].out_bytes == 64 * 256 * 4
    assert g.output_gb("t0") == pytest.approx((64 * 256 * 4) / GB)


def test_output_gb_falls_back_to_memory_required():
    g = TaskGraph([Task("a", 0.5, 1.0, [])], name="fallback").freeze()
    assert g.output_gb("a") == 0.5

"""Expert parallelism: stacked-expert layout + dp x ep sharded train step.

Runs on the 8-virtual-device CPU mesh (conftest).  The correctness anchor
is always :mod:`distributed_llm_scheduler_tpu.models.mixtral`'s per-expert
oracle: stacking, sharding, and the derived psum must not change the math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from distributed_llm_scheduler_tpu.models import mixtral
from distributed_llm_scheduler_tpu.parallel.expert import (
    forward_ep,
    loss_fn_ep,
    make_moe_train_step,
    stack_expert_params,
    unstack_expert_params,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size, dtype=jnp.int32
    )
    targets = jax.random.randint(
        jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size, dtype=jnp.int32
    )
    return cfg, params, ids, targets


def test_stacked_forward_matches_oracle(tiny):
    cfg, params, ids, _ = tiny
    ref = mixtral.forward(params, ids, cfg)
    got = forward_ep(stack_expert_params(params, cfg), ids, cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_stack_unstack_round_trip(tiny):
    cfg, params, _, _ = tiny
    rt = unstack_expert_params(stack_expert_params(params, cfg), cfg)
    assert set(rt) == set(params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(rt[k]), np.asarray(params[k]))


def test_stacked_shapes(tiny):
    cfg, params, _, _ = tiny
    stacked = stack_expert_params(params, cfg)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.ffn_hidden
    assert stacked["l0_moe_gate"].shape == (E, d, f)
    assert stacked["l0_moe_up"].shape == (E, d, f)
    assert stacked["l0_moe_down"].shape == (E, f, d)
    assert not any("_e0_" in k for k in stacked)


def test_ep_loss_matches_single_device(tiny):
    cfg, params, ids, targets = tiny
    l_single = float(mixtral.loss_fn(params, ids, targets, cfg))
    l_ep = float(loss_fn_ep(stack_expert_params(params, cfg), ids, targets, cfg))
    assert abs(l_single - l_ep) < 1e-4


def test_moe_train_step_on_dp_ep_mesh(tiny):
    cfg, _, ids, targets = tiny
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "ep"))
    step, init = make_moe_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))

    # expert tensors are genuinely sharded over ep; a (4, d, f) tensor on
    # ep=4 holds one expert per device
    spec = state.params["l0_moe_gate"].sharding.spec
    assert tuple(spec) == ("ep",)
    shard_shapes = {
        s.data.shape for s in state.params["l0_moe_gate"].addressable_shards
    }
    assert shard_shapes == {(cfg.n_experts // 4, cfg.d_model, cfg.ffn_hidden)}
    # non-expert params replicated
    assert tuple(state.params["l0_wq"].sharding.spec) == ()

    losses = []
    for _ in range(3):
        state, loss = step(state, ids, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert int(state.step) == 3


def test_moe_train_step_rejects_indivisible_ep(tiny):
    cfg, _, _, _ = tiny  # tiny has 4 experts
    devs = np.array(jax.devices()[:8]).reshape(1, 8)
    mesh = Mesh(devs, ("dp", "ep"))
    with pytest.raises(ValueError, match="must divide n_experts"):
        make_moe_train_step(cfg, mesh)


def test_ep_train_loss_matches_unsharded_step(tiny):
    """First-step loss on the dp x ep mesh equals the plain single-device
    loss for the same init key — sharding must not change the program."""
    cfg, _, ids, targets = tiny
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "ep"))
    step, init = make_moe_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(7))
    params = mixtral.init_params(cfg, jax.random.PRNGKey(7))
    expect = float(mixtral.loss_fn(params, ids, targets, cfg))
    _, loss = step(state, ids, targets)
    assert abs(float(loss) - expect) < 1e-4


def test_ep_remat_matches(tiny):
    cfg, params, ids, targets = tiny
    stacked = stack_expert_params(params, cfg)
    plain = forward_ep(stacked, ids, cfg)
    remat = forward_ep(stacked, ids, cfg, remat=True)
    np.testing.assert_allclose(np.asarray(remat), np.asarray(plain),
                               rtol=1e-6, atol=1e-6)
    g_plain = jax.grad(loss_fn_ep)(stacked, ids, targets, cfg)
    g_remat = jax.grad(loss_fn_ep)(stacked, ids, targets, cfg, remat=True)
    for k in g_plain:
        np.testing.assert_allclose(
            np.asarray(g_remat[k]), np.asarray(g_plain[k]),
            rtol=2e-5, atol=2e-5, err_msg=k,
        )


def test_ep_remat_train_step_on_mesh(tiny):
    cfg, _, ids, targets = tiny
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "ep"))
    step_p, init_p = make_moe_train_step(cfg, mesh)
    step_r, init_r = make_moe_train_step(cfg, mesh, remat=True)
    _, loss_p = step_p(init_p(jax.random.PRNGKey(9)), ids, targets)
    _, loss_r = step_r(init_r(jax.random.PRNGKey(9)), ids, targets)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5


# -- routed dispatch under EP ---------------------------

def _full_capacity(cfg):
    """Capacity factor at which nothing can drop (C == N)."""
    return cfg.n_experts / cfg.top_k


def test_routed_ep_matches_dense_at_full_capacity(tiny):
    """Non-dropping capacity: routed-EP forward == dense stacked forward
    == the per-expert oracle (same math, sparse dispatch)."""
    cfg, params, ids, _ = tiny
    stacked = stack_expert_params(params, cfg)
    dense = forward_ep(stacked, ids, cfg)
    routed = forward_ep(
        stacked, ids, cfg, routed=True, capacity_factor=_full_capacity(cfg)
    )
    np.testing.assert_allclose(
        np.asarray(routed), np.asarray(dense), rtol=2e-5, atol=2e-5
    )
    ref = mixtral.forward(params, ids, cfg)
    np.testing.assert_allclose(
        np.asarray(routed), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_routed_ep_on_mesh_matches_single_device(tiny):
    """The sharded (dp x ep) routed forward must equal the unsharded one:
    the with_sharding_constraint pair changes layout, never math."""
    from distributed_llm_scheduler_tpu.parallel.expert import shard_ep_params

    cfg, params, ids, _ = tiny
    stacked = stack_expert_params(params, cfg)
    single = forward_ep(
        stacked, ids, cfg, routed=True, capacity_factor=2.0
    )
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("dp", "ep"))
    sharded = shard_ep_params(mesh, stacked)
    fn = jax.jit(
        lambda p, i: forward_ep(
            p, i, cfg, routed=True, capacity_factor=2.0, mesh=mesh
        )
    )
    got = fn(sharded, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(single), rtol=2e-5, atol=2e-5
    )


def test_routed_ep_train_step_decreases_loss(tiny):
    cfg, _, ids, targets = tiny
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("dp", "ep"))
    step, init = make_moe_train_step(
        cfg, mesh, learning_rate=1e-2, routed=True, capacity_factor=2.0
    )
    state = init(jax.random.PRNGKey(0))
    losses = []
    for _ in range(4):
        state, loss = step(state, ids, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_routed_ep_stats_surface_drops(tiny):
    """forward_ep_stats reports drop fractions: zero at full capacity,
    positive at a squeezing one."""
    from distributed_llm_scheduler_tpu.parallel.expert import forward_ep_stats

    cfg, params, ids, _ = tiny
    stacked = stack_expert_params(params, cfg)
    logits, st = forward_ep_stats(
        stacked, ids, cfg, capacity_factor=_full_capacity(cfg)
    )
    assert int(st["dropped_slots"]) == 0
    assert st["total_slots"] == cfg.n_layers * ids.size * cfg.top_k
    # squeeze: capacity well below the average load must drop something
    _, st2 = forward_ep_stats(stacked, ids, cfg, capacity_factor=0.5)
    assert int(st2["dropped_slots"]) > 0
    # and the full-capacity logits equal the dense path (sanity anchor)
    dense = forward_ep(stacked, ids, cfg)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense), rtol=2e-5, atol=2e-5
    )

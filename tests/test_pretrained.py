"""Pretrained-weight ingestion: HF/torch GPT-2 state dict -> flat params.

The strong form of the round-1 ask ("load real weights through
build_gpt2_dag + fused-forward logit check"): a *torch* GPT2LMHeadModel is
the weight donor AND the independent numerical oracle — its logits must
match our fused forward and our scheduled DAG execution on the same
weights.  (The donor is randomly initialized because this environment has
no network egress; the mapping exercised is byte-identical to what a real
`gpt2` checkpoint feeds through, reference ``test_gpt2.py:47-48``.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu.frontend.gpt2_dag import (
    build_gpt2_dag,
    execute_dag_locally,
)
from distributed_llm_scheduler_tpu.frontend.pretrained import (
    config_from_hf,
    fit_params_to_dag,
    gpt2_params_from_state_dict,
)
from distributed_llm_scheduler_tpu.models import gpt2

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def donor():
    """A tiny torch GPT-2 with random (but real, torch-initialized) weights."""
    hf_config = transformers.GPT2Config(
        vocab_size=512,
        n_positions=128,
        n_embd=128,
        n_layer=2,
        n_head=4,
        attn_pdrop=0.0,
        embd_pdrop=0.0,
        resid_pdrop=0.0,
    )
    model = transformers.GPT2LMHeadModel(hf_config)
    model.eval()
    return model


@pytest.fixture(scope="module")
def ingested(donor):
    config = config_from_hf(donor.config)
    params = gpt2_params_from_state_dict(donor.state_dict(), config)
    return config, params


def torch_logits(donor, ids: np.ndarray) -> np.ndarray:
    with torch.no_grad():
        return donor(torch.from_numpy(ids).long()).logits.numpy()


def test_state_dict_maps_completely(donor, ingested):
    config, params = ingested
    assert set(params) == set(gpt2.param_shapes(config))
    # spot-check layout: Conv1D stores (in, out), so qkv is (d, 3d) as-is
    assert params["h0_attn_qkv_w"].shape == (128, 3 * 128)
    np.testing.assert_array_equal(
        np.asarray(params["wte"]),
        donor.state_dict()["transformer.wte.weight"].numpy(),
    )


def test_fused_forward_matches_torch_logits(donor, ingested):
    config, params = ingested
    ids = np.array([[1, 5, 9, 2, 300, 44, 7, 0]], dtype=np.int32)
    ours = np.asarray(gpt2.forward(params, jnp.asarray(ids), config))
    theirs = torch_logits(donor, ids)
    np.testing.assert_allclose(ours, theirs, rtol=1e-3, atol=2e-3)


def test_dag_execution_matches_torch_logits(donor, ingested):
    """Ingested weights through build_gpt2_dag: the scheduled-execution
    path (vocab-sharded build; shards derived by fit_params_to_dag) agrees
    with the donor model."""
    config, params = ingested
    dag = build_gpt2_dag(config, batch=2, seq_len=8, vocab_shards=2)
    full = fit_params_to_dag(dag, params)
    assert "wte_shard_0" in full and "wte_shard_1" in full
    ids = np.array(
        [[1, 5, 9, 2, 300, 44, 7, 0], [3, 3, 100, 62, 8, 10, 511, 9]],
        dtype=np.int32,
    )
    ours = np.asarray(execute_dag_locally(dag, full, jnp.asarray(ids)))
    theirs = torch_logits(donor, ids)
    np.testing.assert_allclose(ours, theirs, rtol=1e-3, atol=2e-3)


def test_missing_param_raises(donor, ingested):
    config, _ = ingested
    sd = dict(donor.state_dict())
    sd.pop("transformer.h.1.mlp.c_proj.weight")
    with pytest.raises(ValueError, match="missing.*h1_mlp_proj_w"):
        gpt2_params_from_state_dict(sd, config)


def test_unknown_entry_raises(donor, ingested):
    config, _ = ingested
    sd = dict(donor.state_dict())
    sd["transformer.h.0.attn.rotary.inv_freq"] = torch.zeros(4)
    with pytest.raises(ValueError, match="unrecognized"):
        gpt2_params_from_state_dict(sd, config)


def test_shape_mismatch_raises(donor, ingested):
    config, _ = ingested
    narrow = config.__class__(
        vocab_size=config.vocab_size,
        n_positions=config.n_positions,
        n_embd=64,  # wrong width
        n_layer=config.n_layer,
        n_head=config.n_head,
    )
    with pytest.raises(ValueError, match="shape mismatch"):
        gpt2_params_from_state_dict(donor.state_dict(), narrow)


def test_buffers_and_tied_head_are_skipped(donor, ingested):
    config, params = ingested
    # HF state dict carries attn causal-mask buffers + lm_head; none of
    # them may leak into the flat dict
    assert not any("bias_buffer" in k or "lm_head" in k for k in params)
    assert set(params) == set(gpt2.param_shapes(config))


# -- Llama family ------------------------------------------------------------


@pytest.fixture(scope="module")
def llama_donor():
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(1)
    hf = transformers.LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, rms_norm_eps=1e-5, max_position_embeddings=128,
        attention_bias=False, tie_word_embeddings=False,
    )
    return transformers.LlamaForCausalLM(hf).eval()


@pytest.fixture(scope="module")
def llama_ingested(llama_donor):
    from distributed_llm_scheduler_tpu.frontend.pretrained import (
        llama_config_from_hf,
        llama_params_from_state_dict,
    )

    config = llama_config_from_hf(llama_donor.config)
    params = llama_params_from_state_dict(llama_donor.state_dict(), config)
    return config, params


def test_llama_forward_matches_torch_logits(llama_donor, llama_ingested):
    """The RoPE-convention permutation (rotate-half -> interleaved) must
    make our forward reproduce the donor's logits exactly."""
    from distributed_llm_scheduler_tpu.models import llama

    config, params = llama_ingested
    rng = np.random.default_rng(5)
    ids = rng.integers(0, config.vocab_size, (2, 12)).astype(np.int32)
    with torch.no_grad():
        theirs = llama_donor(torch.from_numpy(ids).long()).logits.numpy()
    ours = np.asarray(llama.forward(params, ids, config))
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_llama_generate_runs_on_ingested_weights(llama_ingested):
    from distributed_llm_scheduler_tpu.models import llama

    config, params = llama_ingested
    import jax.numpy as jnp

    ids = jnp.asarray([[7, 8, 9]], dtype=jnp.int32)
    out = llama.generate(params, ids, config, max_new_tokens=4)
    assert out.shape == (1, 7)


def test_llama_tied_embeddings_fall_back(llama_donor, llama_ingested):
    from distributed_llm_scheduler_tpu.frontend.pretrained import (
        llama_params_from_state_dict,
    )

    config, _ = llama_ingested
    sd = {k: v for k, v in llama_donor.state_dict().items()
          if k != "lm_head.weight"}
    params = llama_params_from_state_dict(sd, config)
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]), np.asarray(params["tok_emb"]).T
    )


def test_llama_dag_execution_matches_torch_logits(llama_donor, llama_ingested):
    """Ingested weights flow through the scheduled task-graph path too,
    vocab shards included (fit_params_to_dag slices tok_emb/lm_head)."""
    import jax

    from distributed_llm_scheduler_tpu import Cluster, get_scheduler
    from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
    from distributed_llm_scheduler_tpu.frontend.llama_dag import build_llama_dag
    from distributed_llm_scheduler_tpu.frontend.pretrained import (
        fit_params_to_dag,
    )

    config, params = llama_ingested
    dag = build_llama_dag(config, batch=1, seq_len=12, vocab_shards=2)
    fitted = fit_params_to_dag(dag, params)
    cluster = Cluster.from_jax_devices(jax.devices()[:4], hbm_cap_gb=8.0)
    schedule = get_scheduler("pack").schedule(dag.graph, cluster)
    rep = DeviceBackend(cluster).execute(
        dag.graph, schedule, fitted, dag.make_inputs()
    )
    rng = np.random.default_rng(5)
    with torch.no_grad():
        theirs = llama_donor(
            torch.from_numpy(np.asarray(dag.make_inputs())).long()
        ).logits.numpy()
    np.testing.assert_allclose(
        np.asarray(rep.output), theirs, rtol=3e-4, atol=3e-4
    )


# -- Mixtral family ----------------------------------------------------------


@pytest.fixture(scope="module")
def mixtral_donor():
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(2)
    hf = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2, rope_theta=10000.0,
        rms_norm_eps=1e-5, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    return transformers.MixtralForCausalLM(hf).eval()


@pytest.fixture(scope="module")
def mixtral_ingested(mixtral_donor):
    from distributed_llm_scheduler_tpu.frontend.pretrained import (
        mixtral_config_from_hf,
        mixtral_params_from_state_dict,
    )

    config = mixtral_config_from_hf(mixtral_donor.config)
    params = mixtral_params_from_state_dict(
        mixtral_donor.state_dict(), config
    )
    return config, params


def test_mixtral_forward_matches_torch_logits(mixtral_donor, mixtral_ingested):
    """Attention maps like Llama; the MoE block's w1/w3/w2 -> gate/up/down
    and HF's softmax-then-topk-then-renormalize routing must equal our
    renormalized-top-k router exactly."""
    from distributed_llm_scheduler_tpu.models import mixtral

    config, params = mixtral_ingested
    rng = np.random.default_rng(6)
    ids = rng.integers(0, config.vocab_size, (2, 12)).astype(np.int32)
    with torch.no_grad():
        theirs = mixtral_donor(torch.from_numpy(ids).long()).logits.numpy()
    ours = np.asarray(mixtral.forward(params, ids, config))
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


def test_mixtral_generate_runs_on_ingested_weights(mixtral_ingested):
    import jax.numpy as jnp

    from distributed_llm_scheduler_tpu.models import mixtral

    config, params = mixtral_ingested
    out = mixtral.generate(
        params, jnp.asarray([[5, 6]], dtype=jnp.int32), config,
        max_new_tokens=3,
    )
    assert out.shape == (1, 5)

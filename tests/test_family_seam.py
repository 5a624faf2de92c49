"""The model-family seam (``distributed_llm_scheduler_tpu/models/__init__.py``).

Two halves:

* the CONTRACT every registered family is held to — the fixed-name
  functions exist, the cache the family describes is the cache the
  builders allocate, and a config belongs to a family by its type, not by
  what its class happens to be called;
* a TOY family that lives in this file alone — registered below, built by
  ``build_paged_decode_dag``, scheduled, and served through
  ``PagedDecodeEngine`` with no edit to ``frontend/``, ``backends/``,
  ``utils/config.py`` or ``__main__.py``.  What it takes to add a model
  whose block needs no new kernel is this much and a registry row.
"""

import dataclasses
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_scheduler_tpu import Cluster, get_scheduler, models
from distributed_llm_scheduler_tpu.backends.device import DeviceBackend
from distributed_llm_scheduler_tpu.frontend.decode_dag import (
    build_decode_dag,
    build_paged_decode_dag,
)
from distributed_llm_scheduler_tpu.models import decode as _decode
from distributed_llm_scheduler_tpu.models.kv_pages import CacheSpec, PagePool
from distributed_llm_scheduler_tpu.ops import attention as A

# -- the toy family: token + position embedding, L layers of multi-head
# attention over a K/V cache with a residual, an untied head ------------------


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 64
    width: int = 16
    depth: int = 2
    heads: int = 2
    max_positions: int = 64
    dtype: object = jnp.float32

    @classmethod
    def tiny(cls):
        return cls()

    @property
    def head_dim(self):
        return self.width // self.heads


EMBED_PARAMS = ("emb", "pos")
HEAD_PARAMS = ("out",)


def param_shapes(cfg):
    D = cfg.width
    shapes = {"emb": ((cfg.vocab_size, D), cfg.dtype),
              "pos": ((cfg.max_positions, D), cfg.dtype),
              "out": ((D, cfg.vocab_size), cfg.dtype)}
    for i in range(cfg.depth):
        shapes[f"b{i}_qkv"] = ((D, 3 * D), cfg.dtype)
        shapes[f"b{i}_o"] = ((D, D), cfg.dtype)
    return shapes


def init_params(cfg, key):
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    return {name: (0.5 * jax.random.normal(k, shape)).astype(dt)
            for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items()))}


def layer_param_names(cfg, layer):
    return {"qkv": f"b{layer}_qkv", "o": f"b{layer}_o"}


def cache_spec(cfg):
    row = (cfg.heads, cfg.head_dim)
    return CacheSpec.uniform("kv", cfg.depth, (("k", row), ("v", row)))


def _qkv(p, x, cfg):
    B, T, _ = x.shape
    return [t.reshape(B, T, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)
            for t in jnp.split(x @ p["qkv"], 3, axis=-1)]


def _merge(att, p):
    B, _, T, _ = att.shape
    return att.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["o"]


def decode_embed(p, ids, lengths, cfg):
    return p["emb"][ids] + jnp.take(p["pos"], lengths, axis=0)[:, None, :]


def decode_layer(p, x, lengths, live, cfg, layer, impl=None):
    q, k, v = _qkv(p, x, cfg)
    att = A.paged_decode_attention(
        q, p["cache_k"], p["cache_v"], p["page_table"], lengths,
        1.0 / math.sqrt(cfg.head_dim), k_new=k, v_new=v, impl=impl)
    return x + _merge(att, p), {"k": k, "v": v}, None


def decode_head(p, x, cfg):
    return x @ p["out"]


def decode_flops(cfg, slots, capacity):
    D = cfg.width
    layer = 2.0 * slots * D * 4 * D + 4.0 * slots * capacity * D
    return (2.0 * slots * D, [layer] * cfg.depth,
            2.0 * slots * D * cfg.vocab_size)


def forward_cached(params, ids, cache, pos_start, cfg):
    """The family's own dense cached forward: ``cache`` ``{"k", "v"}``
    each (L, b, heads, cap, hd); positions ``pos_start + t``."""
    B, T = ids.shape
    pos_start = jnp.asarray(pos_start, jnp.int32)
    x = params["emb"][ids] + jax.lax.dynamic_slice_in_dim(
        params["pos"], pos_start, T, axis=0)
    cache = dict(cache)
    for i in range(cfg.depth):
        p = {loc: params[g] for loc, g in layer_param_names(cfg, i).items()}
        q, k, v = _qkv(p, x, cfg)
        for kind, new in (("k", k), ("v", v)):
            cache[kind] = jax.lax.dynamic_update_slice(
                cache[kind], new[None].astype(cache[kind].dtype),
                (i, 0, 0, pos_start, 0))
        att = _decode.cached_attention(
            q, cache["k"][i], cache["v"][i], pos_start,
            1.0 / math.sqrt(cfg.head_dim))
        x = x + _merge(att, p)
    return x @ params["out"], cache


def forward_cached_row(params, ids, cache, pos_start, cfg, row, impl=None):
    logits, cache = forward_cached(params, ids, cache, pos_start, cfg)
    return jax.lax.dynamic_index_in_dim(logits, row, 1, keepdims=False), cache


TOY = models.register_family(models.Family(
    "toy", __name__, "ToyConfig", {"toy-tiny": "tiny"}, "depth",
    "max_positions"))
# the registry imports a family's module by name: this file is one already
assert sys.modules[__name__].ToyConfig is ToyConfig

FAMILIES = sorted(models.families())


def _tiny(family: str):
    variant = next(v for v in models.families()[family].variants
                   if v.endswith("-tiny"))
    return models.model_config(variant)


# -- the contract -----------------------------------------------------------------


def test_the_toy_is_registered_beside_the_ten():
    assert FAMILIES == ["dots3", "glm4_lite", "gpt2", "laguna", "lfm2",
                        "llama", "mixtral", "nemotron_h", "ouro", "toy",
                        "xing4"]


@pytest.mark.parametrize("family", FAMILIES)
def test_family_contract(family):
    """The fixed names exist; the config's type decides the family; the
    cache the family describes is the cache the builders allocate."""
    cfg = _tiny(family)
    mod = models.family_module(family)
    assert models.family_of(cfg) == family
    assert models.module_of(cfg) is mod
    for name in ("param_shapes", "init_params", "cache_spec",
                 "layer_param_names", "EMBED_PARAMS", "HEAD_PARAMS"):
        assert hasattr(mod, name), (family, name)
    row = models.families()[family]
    paged = models.offers(row, *models.PAGED_FUNCTIONS)
    dense = models.offers(row, *models.CACHED_FUNCTIONS)
    assert paged or dense, f"{family} reaches no decode builder"

    spec = mod.cache_spec(cfg)
    assert models.cache_spec(cfg) == spec
    n_main = spec.n_layers - spec.draft_layers
    assert getattr(cfg, row.layers_field) == n_main
    shapes = mod.param_shapes(cfg)
    for names in (mod.EMBED_PARAMS, mod.HEAD_PARAMS,
                  *(mod.layer_param_names(cfg, i).values()
                    for i in range(n_main))):
        assert set(names) <= set(shapes), (family, names)
    # stepped with its own draft module: the seam decides, all or nothing
    drafts = models.offers(row, *models.DRAFT_FUNCTIONS)
    assert drafts == (spec.draft_layers > 0) == (models.draft_rows(cfg) > 1)
    assert drafts or not any(
        hasattr(mod, n) for n in models.DRAFT_FUNCTIONS)
    if drafts:
        assert paged and set(
            mod.draft_param_names(cfg).values()) <= set(shapes)

    def cache_of(dag):
        return {k: (v.shape, v.dtype) for k, v in dag.init_params().items()
                if k.startswith("cache_")}

    def shapes_of(arrays):
        return {k: (v.shape, v.dtype) for k, v in arrays.items()}

    if paged:
        dag = build_paged_decode_dag(
            cfg, slots=2, page_size=8, n_pages=5, pages_per_seq=2)
        assert cache_of(dag) == shapes_of(
            spec.init_pools(5, 8, cfg.dtype, slots=2))
        assert dag.graph.name.startswith(f"{family}paged_{n_main}l_")
        assert ("active" in dag.input_spec) == getattr(
            mod, "DECODE_TAKES_LIVE", False)
        R = models.draft_rows(cfg)
        assert dag.input_spec["ids"].shape == (2, R) and (
            dag.rows_per_step == dag.graph.rows_per_step == R)
        assert [t.task_id for t in dag.graph][-1] == (
            "draft" if drafts else "logits")
    if dense:
        dag = build_decode_dag(cfg, batch=2, step_len=1, max_len=16)
        assert cache_of(dag) == shapes_of(spec.init_slabs(2, 16, cfg.dtype))
        assert dag.graph.name.startswith(f"{family}dec_{n_main}l_")


def test_family_is_decided_by_type_not_by_class_name():
    """A foreign class called ``...GPT2Config...`` is nobody's (the
    substring match this registry replaced took it for GPT-2); a subclass
    of a registered config is its parent's."""
    from distributed_llm_scheduler_tpu.models.gpt2 import GPT2Config

    class NotGPT2Config:
        pass

    @dataclasses.dataclass(frozen=True)
    class WiderGPT2(GPT2Config):
        pass

    with pytest.raises(ValueError, match="unknown model family"):
        models.family_of(NotGPT2Config())
    with pytest.raises(ValueError, match="unknown model family"):
        models.family_of(object())
    assert models.family_of(WiderGPT2.tiny()) == "gpt2"


@pytest.mark.parametrize("model,family", [
    ("gpt2", "gpt2"), ("gpt2-medium", "gpt2"), ("gpt2-tiny", "gpt2"),
    ("llama", "llama"), ("llama-8b", "llama"), ("llama-tiny", "llama"),
    ("mixtral-8x7b", "mixtral"), ("mixtral-tiny", "mixtral"),
    ("xing4-tiny", "xing4"), ("dots3-tiny", "dots3"),
    ("glm4_lite-tiny", "glm4_lite"), ("laguna-tiny", "laguna"),
    ("ouro-tiny", "ouro"), ("toy-tiny", "toy")])
def test_variant_names_make_their_familys_config(model, family):
    assert models.family_of_model(model).name == family
    assert models.family_of(models.model_config(model)) == family


@pytest.mark.parametrize("model", ["llm", "random", "pipeline", "gpt2x",
                                   "mistral-7b"])
def test_other_names_are_no_familys(model):
    assert models.family_of_model(model) is None
    assert models.model_config(model) is None
    assert not models.offers(None, "forward")


def test_unknown_variant_of_a_family_names_the_variants():
    with pytest.raises(ValueError, match="gpt2 / gpt2-medium / gpt2-tiny"):
        models.model_config("gpt2-huge")


def test_a_family_registers_once():
    with pytest.raises(ValueError, match="collides"):
        models.register_family(TOY)
    with pytest.raises(ValueError, match="collides"):
        models.register_family(dataclasses.replace(TOY, name="toy2"))


def test_what_each_family_offers():
    """Who is served, who steps through the dense DAG, who has neither
    changes only with a family's own module."""
    rows = models.families()
    served = {f for f in rows
              if models.offers(rows[f], *models.PAGED_FUNCTIONS)}
    dense = {f for f in rows
             if models.offers(rows[f], *models.CACHED_FUNCTIONS)}
    assert served == {"gpt2", "xing4", "dots3", "glm4_lite", "laguna",
                      "nemotron_h", "ouro", "lfm2", "toy"}
    assert dense == {"gpt2", "llama", "mixtral"}
    assert {f for f in rows if models.offers(
        rows[f], *models.DRAFT_FUNCTIONS)} == {"glm4_lite"}
    assert {f for f in rows if models.offers(
        rows[f], *models.LOOP_FUNCTIONS)} == {"ouro"}


@pytest.mark.parametrize("family", ["gpt2", "llama", "xing4"])
def test_cache_spec_answers_the_engines_two_questions(family):
    """``resolve_impl`` and ``block_pages`` are the op's own rules for the
    spec's kind, asked with what the spec's rows say."""
    cfg = _tiny(family)
    spec = models.cache_spec(cfg)
    slots, n_pages, ps, ppseq = 4, 13, 8, 4
    row = spec.rows[0][1]
    for impl in (None, "xla", "pallas_interpret"):
        if spec.kind == "latent":
            want = A.resolve_mla_paged_impl(
                impl, ps, row[0], cfg.kv_lora_rank, cfg.dtype)
        else:
            want = A.resolve_paged_impl(
                impl, (slots, getattr(cfg, "n_heads", row[0]), 1, row[1]),
                (n_pages, ps, row[0] * row[1]), cfg.dtype)
        assert spec.resolve_impl(impl, slots, n_pages, ps, cfg.dtype) == want
    if spec.kind == "latent":
        assert spec.head_dim is None
        assert spec.block_pages(ps, ppseq, cfg.dtype) == A.latent_block_pages(
            ps, ppseq, row[0], cfg.dtype)
    else:
        assert spec.head_dim == row[1]
        assert spec.block_pages(ps, ppseq, cfg.dtype) == A.paged_block_pages(
            ps, ppseq, *row, cfg.dtype)


# -- the toy, served ------------------------------------------------------------


def _greedy(cfg, weights, prompt, n_new, cap):
    """``n_new`` greedy tokens by the toy's own ``forward_cached``."""
    cache = cache_spec(cfg).init_dense(1, cap, cfg.dtype)
    logits, cache = forward_cached(weights, prompt, cache, 0, cfg)
    out, pos = [int(jnp.argmax(logits[0, -1]))], prompt.shape[1]
    while len(out) < n_new:
        logits, cache = forward_cached(
            weights, jnp.asarray([[out[-1]]], jnp.int32), cache, pos, cfg)
        out.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return out


@pytest.mark.parametrize("chunk", [None, 8])
def test_toy_family_is_built_scheduled_and_served(chunk):
    cfg = ToyConfig.tiny()
    slots, ps, n_pages, ppseq = 2, 8, 9, 4
    dag = build_paged_decode_dag(
        cfg, slots=slots, page_size=ps, n_pages=n_pages, pages_per_seq=ppseq,
        attention_impl="xla")
    assert dag.graph.name == "toypaged_2l_d16_s2_ps8_p9_attxla"
    assert [t.task_id for t in dag.graph] == [
        "embed", "layer_0", "layer_1", "logits"]
    # structurally identical layers share one task fn
    assert dag.graph["layer_0"].fn is dag.graph["layer_1"].fn
    assert dag.graph["layer_1"].param_alias == {
        "qkv": "b1_qkv", "o": "b1_o", "cache_k": "cache_k_1",
        "cache_v": "cache_v_1", "page_table": "page_table"}

    params = dag.init_params()
    weights = {k: v for k, v in params.items()
               if not (k.startswith("cache_") or k == "page_table")}
    # the DAG's own oracle is the toy's forward over gathered pages
    inputs = dag.make_inputs()
    cluster = Cluster.from_jax_devices(jax.devices()[:1])
    schedule = get_scheduler("heft").schedule(dag.graph, cluster)
    backend = DeviceBackend(cluster)
    stepped = backend.execute(dag.graph, schedule, params, inputs).output
    np.testing.assert_allclose(
        stepped, dag.reference_forward(params, inputs), rtol=1e-5, atol=1e-5)

    eng = backend.paged_decode_engine(
        dag.graph, schedule, cfg, weights,
        PagePool(n_pages=n_pages, page_size=ps), slots=slots,
        pages_per_seq=ppseq, seg_steps=4, chunk_tokens=chunk)
    assert eng.resolved_attention_impl == "xla"
    rng = np.random.RandomState(3)
    prompts = {rid: jnp.asarray(
        rng.randint(1, cfg.vocab_size, size=(1, n)), jnp.int32)
        for rid, n in (("a", 5), ("b", 13))}
    for rid, prompt in prompts.items():
        eng.submit(rid, prompt, 9)
    served = eng.run()
    assert eng.pool.free_pages == n_pages - 1
    for rid, prompt in prompts.items():
        assert np.asarray(served[rid]).tolist() == _greedy(
            cfg, weights, prompt, 9, ppseq * ps), rid

"""The model families, and the one place that says which is which.

Layers point one way: ``ops`` <- ``models`` <- ``frontend`` <-
``backends`` <- ``serve`` / the CLI.  Nothing outside this package asks
for a family's *name* to choose a code path: it asks this registry for
the family of a config (:func:`family_of`, decided by the config's type)
or of a CLI model name (:func:`family_of_model`), and then calls the
family module's functions directly (:func:`family_module`).

What a family offers is a set of module-level functions and constants
with fixed names — no base class, no wrapper.  ``docs/ARCHITECTURE.md``
("Adding a model family") lists them; ``tests/test_family_seam.py``
holds every registered family to them and serves a toy family that
lives in the test file alone.

A row's strings are names, resolved on first use (:func:`resolve`): a
family's module is imported when someone asks for it, not when this
package is (set-up time is an end-to-end metric).  ``forward_dag`` /
``train_dag`` / ``weights_mapper`` / ``trainer`` name what the layers
ABOVE offer for the family; this package never imports them.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Mapping, Optional

_PKG = __name__.rsplit(".", 1)[0]


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    #: importable module that holds the family's functions
    module: str
    #: the family's config class, an attribute of that module
    config: str
    #: CLI model name -> classmethod of the config class that makes it
    variants: Mapping[str, str]
    #: config fields: depth (``--num-layers``) and longest sequence
    layers_field: str
    positions_field: str
    #: ``"module:function"`` names of what the upper layers hold for the
    #: family (None: nothing): the forward-DAG builder and the train-step
    #: DAG builder (``frontend/``), the HF state-dict mapper
    #: (``frontend/pretrained.py``), the mesh train-step factory
    #: (``parallel/``)
    forward_dag: Optional[str] = None
    train_dag: Optional[str] = None
    weights_mapper: Optional[str] = None
    trainer: Optional[str] = None


_FAMILIES: Dict[str, Family] = {}


def register_family(family: Family) -> Family:
    """Add a family (its name and its config class each at most once)."""
    for other in _FAMILIES.values():
        if other.name == family.name or (
                other.module, other.config) == (family.module, family.config):
            raise ValueError(f"family {family.name!r} collides with {other}")
    _FAMILIES[family.name] = family
    return family


def families() -> Dict[str, Family]:
    return dict(_FAMILIES)


def family_of(config: Any) -> str:
    """The family a config instance belongs to, by its TYPE: the class
    (or a base of it) is one a family registered.  A class that merely
    has a family's name in its own is nobody's."""
    for cls in type(config).__mro__:
        for fam in _FAMILIES.values():
            if (cls.__module__, cls.__qualname__) == (fam.module, fam.config):
                return fam.name
    raise ValueError(f"unknown model family for config {type(config)!r}")


def family_of_model(model: str) -> Optional[Family]:
    """The family of a CLI model name (``"gpt2-medium"``), or None for a
    synthetic workload.  A name that starts with a family's name and a
    dash but is no variant belongs to that family still, so that the
    caller can say which variants there are (:func:`model_config`)."""
    for fam in _FAMILIES.values():
        if model in fam.variants or model.startswith(fam.name + "-"):
            return fam
    return None


def model_config(model: str) -> Any:
    """A config instance for a variant name; None for a synthetic
    workload, ValueError for an unknown variant of a known family."""
    fam = family_of_model(model)
    if fam is None:
        return None
    maker = fam.variants.get(model)
    if maker is None:
        raise ValueError(
            f"unknown model {model!r}; variants are "
            f"{' / '.join(sorted(fam.variants))}"
        )
    return getattr(getattr(family_module(fam.name), fam.config), maker)()


def family_module(name: str):
    """The module that holds a family's functions, imported on demand."""
    return importlib.import_module(_FAMILIES[name].module)


def module_of(config: Any):
    """:func:`family_module` of :func:`family_of`."""
    return family_module(family_of(config))


def cache_spec(config: Any):
    """What each layer of this config caches for a token
    (:class:`.kv_pages.CacheSpec`): its family's ``cache_spec``."""
    return module_of(config).cache_spec(config)


def offers(family: Optional[Family], *names: str) -> bool:
    """Whether a family's module has all of these names — how a caller
    asks "can this model be served / stepped / generated from here"
    (``offers(family_of_model(name), *PAGED_FUNCTIONS)``).  None (a
    synthetic workload) offers nothing."""
    return family is not None and all(
        hasattr(family_module(family.name), n) for n in names)


#: the functions the paged serving path calls (``build_paged_decode_dag``
#: and ``PagedDecodeEngine``); a family that has them all is served
PAGED_FUNCTIONS = (
    "cache_spec", "layer_param_names", "decode_embed", "decode_layer",
    "decode_head", "decode_flops", "forward_cached_row",
)
#: what a family offers BESIDE :data:`PAGED_FUNCTIONS` to be stepped with
#: its own draft module — a decode step of ``DECODE_ROWS`` rows a slot
#: that verifies the drafts and yields one token or more (``build_paged_
#: decode_dag`` adds the ``draft`` task, ``PagedDecodeEngine`` accepts and
#: folds).  The seam decides, not a flag: a family that has them all is
#: stepped that way and no other
DRAFT_FUNCTIONS = (
    "DECODE_ROWS", "draft_param_names", "draft_decode", "draft_flops",
    "forward_cached_draft",
)
#: what a family whose layers run more than once a token (its
#: ``cache_spec`` says ``passes`` > 1) offers BESIDE :data:`PAGED_FUNCTIONS`:
#: the parameters and the function of the task that closes a pass, and a
#: ``decode_layer`` that takes the pass ``u``.  ``build_paged_decode_dag``
#: then emits the layers' tasks once a pass, all on the same weights, and
#: ``compose_paged_step_fn`` rolls the passes into one traced loop
LOOP_FUNCTIONS = ("PASS_END_PARAMS", "decode_pass_end")
#: the functions the dense decode-step DAG calls (``build_decode_dag``)
CACHED_FUNCTIONS = (
    "cache_spec", "layer_param_names", "cached_embed", "cached_layer",
    "head", "cached_flops", "forward_cached",
)


def draft_rows(config: Any) -> int:
    """Rows a slot feeds one paged decode step of this config's family:
    its ``DECODE_ROWS`` where it offers :data:`DRAFT_FUNCTIONS`, else 1."""
    mod = module_of(config)
    if all(hasattr(mod, n) for n in DRAFT_FUNCTIONS):
        return int(mod.DECODE_ROWS)
    return 1


def resolve(name: str) -> Any:
    """The object a row's ``"module:function"`` string names."""
    module, _, attr = name.partition(":")
    return getattr(importlib.import_module(module), attr)


for _f in (
    Family(
        "gpt2", f"{__name__}.gpt2", "GPT2Config",
        {"gpt2": "small", "gpt2-medium": "medium", "gpt2-tiny": "tiny"},
        "n_layer", "n_positions",
        forward_dag=f"{_PKG}.frontend.gpt2_dag:build_gpt2_dag",
        train_dag=f"{_PKG}.frontend.train_dag:build_gpt2_train_dag",
        weights_mapper=(
            f"{_PKG}.frontend.pretrained:gpt2_params_from_state_dict"),
        trainer=f"{_PKG}.parallel.train:make_train_step",
    ),
    Family(
        "llama", f"{__name__}.llama", "LlamaConfig",
        {"llama": "llama3_8b", "llama-8b": "llama3_8b", "llama-tiny": "tiny"},
        "n_layers", "max_seq_len",
        forward_dag=f"{_PKG}.frontend.llama_dag:build_llama_dag",
        weights_mapper=(
            f"{_PKG}.frontend.pretrained:llama_params_from_state_dict"),
    ),
    Family(
        "mixtral", f"{__name__}.mixtral", "MixtralConfig",
        {"mixtral": "mixtral_8x7b", "mixtral-8x7b": "mixtral_8x7b",
         "mixtral-tiny": "tiny"},
        "n_layers", "max_seq_len",
        forward_dag=f"{_PKG}.frontend.moe_dag:build_moe_dag",
        weights_mapper=(
            f"{_PKG}.frontend.pretrained:mixtral_params_from_state_dict"),
        trainer=f"{_PKG}.parallel.expert:make_moe_train_step",
    ),
    # served only (the paged decode DAG); no forward-DAG builder
    Family(
        "xing4", f"{__name__}.xing4", "Xing4Config", {"xing4-tiny": "tiny"},
        "n_layers", "max_positions",
    ),
    Family(
        "dots3", f"{__name__}.dots3", "Dots3Config", {"dots3-tiny": "tiny"},
        "n_layers", "max_positions",
    ),
    # served with its MTP module as the self-draft (DRAFT_FUNCTIONS)
    Family(
        "glm4_lite", f"{__name__}.glm4_lite", "Glm4LiteConfig",
        {"glm4_lite-tiny": "tiny"}, "n_layers", "max_positions",
    ),
    # a kv cache with rotary keys, per-layer query heads and ring layers
    Family(
        "laguna", f"{__name__}.laguna", "LagunaConfig",
        {"laguna-tiny": "tiny"}, "n_layers", "max_positions",
    ),
    # every layer one sublayer; mixers that keep a state a slot, not rows
    Family(
        "nemotron_h", f"{__name__}.nemotron_h", "NemotronHConfig",
        {"nemotron-h-tiny": "tiny"}, "n_layers", "max_positions",
    ),
    # a looped stack: the layers run several times a token on one set of
    # weights, a K/V plane a pass (LOOP_FUNCTIONS)
    Family(
        "ouro", f"{__name__}.ouro", "OuroConfig", {"ouro-tiny": "tiny"},
        "n_layers", "max_positions",
    ),
    # every layer an operator AND a feed-forward: gated short convolutions
    # (a state a slot) beside attention layers, all experts held
    Family(
        "lfm2", f"{__name__}.lfm2", "Lfm2Config", {"lfm2-tiny": "tiny"},
        "n_layers", "max_positions",
    ),
):
    register_family(_f)
del _f

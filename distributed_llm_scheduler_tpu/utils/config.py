"""Run configuration.

The reference hardcodes every constant (SURVEY.md §5.6: param size, memory
regimes, node profiles, model name — zero argparse anywhere).  Here a
dataclass carries the whole experiment description and maps 1:1 onto the
CLI flags in ``__main__``; everything has a default so ``python -m
distributed_llm_scheduler_tpu <cmd>`` just works.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
from typing import Optional, Tuple

# -- environment seam ------------------------------------------------------
# The ONE module allowed to consult os.environ (determinism lint DET005):
# every env-tunable in the tree reads through these helpers, so the full
# set of environment inputs is greppable from one place and the
# reproducibility battery knows exactly which ambient state can matter.

_TRUTHY = ("1", "true", "yes", "on")


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw environment read (the DET005 seam)."""
    return os.environ.get(name, default)


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean environment read: unset -> ``default``; set -> truthy iff
    the value is one of ``1/true/yes/on`` (case-insensitive)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() in _TRUTHY


@dataclasses.dataclass
class RunConfig:
    # workload
    # gpt2[-medium|-tiny] | llama[-8b|-tiny] | mixtral[-8x7b|-tiny]
    # | llm | random | pipeline
    model: str = "gpt2"
    batch: int = 1
    seq_len: int = 512
    microbatches: int = 1
    vocab_shards: int = 1          # shard the embedding/LM-head tables
    fuse: bool = False             # fuse linear task chains (core/fusion.py)
    quantize: str = "none"         # none | int8 (utils/quantize.py)
    num_layers: Optional[int] = None  # synthetic workloads / overrides
    train_step: bool = False       # schedule one fwd+bwd+opt step (gpt2*)
    routed: bool = False           # mixtral*: capacity-buffer sparse MoE
    capacity_factor: float = 2.0   # routed capacity slack (x k*N/E)

    # cluster
    num_nodes: int = 8
    hbm_gb: float = 14.0
    memory_regime: float = 1.0
    use_jax_devices: bool = False  # bind live devices (device backend)
    slices: int = 1                # >1: multi-slice topology (DCN between)

    # scheduling
    scheduler: str = "heft"
    # search-tier knobs (``--scheduler search``): eval budget and RNG
    # seed for the annealed placement search.  None keeps the policy's
    # own defaults; other policies ignore them (get_scheduler forwards
    # kwargs only to constructors that declare them)
    search_budget: Optional[int] = None
    search_seed: Optional[int] = None

    # backend
    backend: str = "sim"           # sim | sim-reference | device
    prefetch_params: bool = True

    # evaluation sweep
    num_runs: int = 3
    node_counts: Tuple[int, ...] = (2, 4, 8)
    memory_regimes: Tuple[float, ...] = (1.0, 0.9, 0.8)

    # io
    out_dir: str = "evaluation_results"
    seed: int = 0
    # optional pretrained weights for `execute`: a torch state-dict file
    # (gpt2 / llama / mixtral families; frontend/pretrained.py name-maps
    # it) — random init when unset
    weights: Optional[str] = None

    def model_config(self):
        """Model config instance for a real-family variant name.

        The ONE variant-name lookup (CLI generate and build_graph share
        it; the table is the family registry's, :mod:`..models`): returns
        None for synthetic workloads, raises ValueError for an unknown
        variant of a known family."""
        from ..models import model_config

        return model_config(self.model)

    def build_graph(self):
        from ..frontend import generators

        from ..models import family_of_model, resolve

        family = family_of_model(self.model)
        if self.train_step and (family is None or family.train_dag is None):
            raise ValueError(
                "--train-step currently supports gpt2* models only"
            )
        if self.train_step and self.microbatches != 1:
            raise ValueError(
                "--train-step does not support --microbatches yet"
            )
        if self.train_step and self.vocab_shards != 1:
            raise ValueError(
                "--train-step does not support --vocab-shards yet"
            )
        if self.train_step and self.fuse:
            raise ValueError("--train-step does not support --fuse yet")
        if self.quantize not in ("none", "int8"):
            raise ValueError(
                f"unknown quantize mode {self.quantize!r}; choose none | int8"
            )
        builder = (resolve(family.forward_dag)
                   if family is not None and family.forward_dag else None)
        if self.routed and (builder is None or "routed" not in
                            inspect.signature(builder).parameters):
            # same contract as --quantize below: silently ignoring the
            # flag would report dense numbers as routed ones
            raise ValueError(
                "--routed applies to mixtral* models only (sparse expert "
                "dispatch); other workloads have no experts"
            )
        if self.quantize != "none" and self.train_step:
            raise ValueError(
                "--train-step does not support --quantize (int8 weights "
                "are an inference-path representation)"
            )
        if self.quantize != "none" and family is None:
            # silently ignoring the flag would report full-precision
            # numbers as quantized ones
            raise ValueError(
                "--quantize needs a real model family (gpt2*/llama*/"
                "mixtral*); synthetic graphs carry no weights to quantize"
            )

        if family is not None:
            if builder is None:
                raise ValueError(
                    f"model {self.model!r} has no forward DAG: it is served "
                    "only (the `serve` command's paged decode DAG)"
                )
            cfg = self.model_config()
            if self.num_layers:
                cfg = dataclasses.replace(
                    cfg, **{family.layers_field: self.num_layers})
            seq = min(self.seq_len, getattr(cfg, family.positions_field))
            if self.train_step:
                return resolve(family.train_dag)(
                    cfg, batch=self.batch, seq_len=seq)
            extra = (
                {"routed": True, "capacity_factor": self.capacity_factor}
                if self.routed
                else {}
            )
            dag = builder(
                cfg, batch=self.batch, seq_len=seq,
                microbatches=self.microbatches,
                vocab_shards=self.vocab_shards,
                **extra,
            )
            if self.fuse:
                from ..core.fusion import fuse_linear_chains

                dag = dataclasses.replace(
                    dag, graph=fuse_linear_chains(dag.graph)
                )
            if self.quantize == "int8":
                from .quantize import quantize_dag

                dag = quantize_dag(dag)
            return dag
        makers = {
            "llm": lambda: generators.generate_llm_dag(
                num_layers=self.num_layers or 4, seed=self.seed
            ),
            "random": lambda: generators.generate_random_dag(
                num_tasks=(self.num_layers or 4) * 8, seed=self.seed
            ),
            "pipeline": lambda: generators.generate_pipeline_dag(
                num_stages=self.num_layers or 4, seed=self.seed
            ),
        }
        if self.model not in makers:
            raise ValueError(
                f"unknown model {self.model!r}; choose gpt2[-medium|-tiny] / "
                "llama[-8b|-tiny] / mixtral[-8x7b|-tiny] / llm / random / "
                "pipeline"
            )
        graph = makers[self.model]()
        if self.fuse:
            from ..core.fusion import fuse_linear_chains

            graph = fuse_linear_chains(graph)
        return graph

    def build_cluster(self):
        from ..core.cluster import Cluster

        if self.use_jax_devices:
            return Cluster.from_jax_devices(hbm_cap_gb=self.hbm_gb)
        if self.slices > 1:
            if self.num_nodes % self.slices != 0:
                raise ValueError(
                    f"--slices {self.slices} must divide "
                    f"--num-nodes {self.num_nodes}"
                )
            return Cluster.multislice(
                self.slices,
                self.num_nodes // self.slices,
                self.hbm_gb * self.memory_regime,
            )
        return Cluster.uniform(self.num_nodes, self.hbm_gb * self.memory_regime)

    def build_link(self):
        """The replay's link model: tiered (ICI/DCN) for multi-slice
        topologies, flat defaults otherwise."""
        if self.slices > 1:
            from ..backends.sim import TieredLinkModel

            return TieredLinkModel()
        return None  # SimulatedBackend's flat defaults

    def build_scheduler(self):
        """The configured policy; link-aware policies receive the same
        link model the replay charges (``get_scheduler`` detects the
        ``link=`` keyword), so multi-slice runs optimize DCN-aware costs."""
        from ..sched.policies import get_scheduler

        return get_scheduler(
            self.scheduler, link=self.build_link(),
            budget=self.search_budget, seed=self.search_seed,
        )

    def build_backend(self):
        from ..backends.sim import SimulatedBackend

        if self.backend == "sim":
            return SimulatedBackend(
                fidelity="full", prefetch_params=self.prefetch_params,
                link=self.build_link(),
            )
        if self.backend == "sim-reference":
            return SimulatedBackend(fidelity="reference")
        if self.backend == "device":
            from ..backends.device import DeviceBackend

            return DeviceBackend(self.build_cluster_with_devices())
        raise ValueError(f"unknown backend {self.backend!r}")

    def build_cluster_with_devices(self):
        import jax

        from ..core.cluster import Cluster

        # honor num_nodes by taking a prefix of the live devices — the
        # flag was silently dead for live clusters (all devices always
        # bound), which made `--num-nodes 4` a lie on an 8-device host
        devs = jax.devices()
        if self.num_nodes and self.num_nodes < len(devs):
            devs = devs[: self.num_nodes]
        elif self.num_nodes and self.num_nodes > len(devs):
            # live clusters cannot invent devices; disclose the clamp
            # instead of silently reporting an un-honored request
            import sys

            print(
                f"note: {self.num_nodes} nodes requested but only "
                f"{len(devs)} live device(s) exist; binding {len(devs)}",
                file=sys.stderr,
            )
        return Cluster.from_jax_devices(devs, hbm_cap_gb=self.hbm_gb)

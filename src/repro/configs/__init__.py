"""Architecture registry: ``--arch <id>`` resolution.

The ten assigned architectures, deepseek-v2-lite (the benchmark's
latent-attention MoE configuration), plus the paper's own workload (the
KubeAdaptor paper has no model of its own — its workloads are workflow
DAGs, registered in ``configs/workflows.py``).
"""
from repro.configs.base import ArchConfig, ShapeConfig, SHAPES, shape_applicable

from repro.configs import (
    mamba2_2p7b,
    zamba2_1p2b,
    llama4_scout_17b_a16e,
    qwen2_moe_a2p7b,
    qwen2_1p5b,
    gemma_7b,
    deepseek_67b,
    qwen2_0p5b,
    musicgen_medium,
    llama32_vision_11b,
    deepseek_v2_lite,
)

_MODULES = (
    mamba2_2p7b,
    zamba2_1p2b,
    llama4_scout_17b_a16e,
    qwen2_moe_a2p7b,
    qwen2_1p5b,
    gemma_7b,
    deepseek_67b,
    qwen2_0p5b,
    musicgen_medium,
    llama32_vision_11b,
    deepseek_v2_lite,
)

REGISTRY = {m.CONFIG.name: m.CONFIG for m in _MODULES}


def get_config(name: str) -> ArchConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[name]


def list_configs():
    return sorted(REGISTRY)


__all__ = [
    "ArchConfig", "ShapeConfig", "SHAPES", "shape_applicable",
    "REGISTRY", "get_config", "list_configs",
]

"""Architecture config registry: `get_config("<arch-id>")` / `--arch <id>`."""

from __future__ import annotations

from importlib import import_module

from .base import ModelConfig, reduced  # noqa: F401
from .shapes import SHAPES, ShapeSpec, shape_by_name  # noqa: F401

# the 10 assigned architectures, the paper's own models, then the models
# added to the benchmark
ARCH_MODULES = {
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "zamba2-2.7b": "zamba2_2p7b",
    "musicgen-medium": "musicgen_medium",
    "gemma2-2b": "gemma2_2b",
    "minicpm-2b": "minicpm_2b",
    "gemma3-1b": "gemma3_1b",
    "stablelm-1.6b": "stablelm_1p6b",
    "pixtral-12b": "pixtral_12b",
    "rwkv6-3b": "rwkv6_3b",
    "bitnet-3b": "bitnet_3b",
    "bitnet-1.3b": "bitnet_1p3b",
    "gla-1.3b": "gla_1p3b",
    "mellum2-12b-a2.5b": "mellum2_12b_a2p5b",
}
ASSIGNED = tuple(list(ARCH_MODULES)[:10])
PAPER_OWN = tuple(list(ARCH_MODULES)[10:13])


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; have {sorted(ARCH_MODULES)}")
    return import_module(f"repro.configs.{ARCH_MODULES[arch]}").CONFIG


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_MODULES}

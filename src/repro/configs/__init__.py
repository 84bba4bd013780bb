"""Assigned-architecture configs (one module per arch) + lookup helpers."""
from __future__ import annotations

from importlib import import_module

from .base import (  # noqa: F401
    DQConfig,
    EncDecConfig,
    InputShape,
    ModelConfig,
    MoEConfig,
    RGLRUConfig,
    SHAPES,
    SSMConfig,
    TRAIN_4K,
    PREFILL_32K,
    DECODE_32K,
    LONG_500K,
)

# module name -> arch id (assigned pool + paper's own + beyond-paper variants)
_ARCH_MODULES = {
    "recurrentgemma-2b": "recurrentgemma_2b",
    "gemma-2b": "gemma_2b",
    "yi-34b": "yi_34b",
    "mamba2-1.3b": "mamba2_1_3b",
    "chameleon-34b": "chameleon_34b",
    "command-r-plus-104b": "command_r_plus_104b",
    "whisper-tiny": "whisper_tiny",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "arctic-480b": "arctic_480b",
    "starcoder2-7b": "starcoder2_7b",
    # beyond-paper variant: gemma-2b with a sliding window so long_500k runs
    "gemma-2b-swa": "gemma_2b_swa",
    # the paper's own experimental architecture (DCGAN-backbone GAN)
    "dcgan32": "dcgan32",
    # the class-conditional GAN trained data-parallel on accelerators
    "biggan128": "biggan128",
}

ASSIGNED = tuple(k for k in _ARCH_MODULES
                 if k not in ("gemma-2b-swa", "dcgan32", "biggan128"))


def get(name: str) -> ModelConfig:
    try:
        mod = import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_ARCH_MODULES)}")
    return mod.CONFIG


def registry() -> dict:
    return {name: get(name) for name in _ARCH_MODULES}

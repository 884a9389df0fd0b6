"""Architecture registry: ``get_config(arch_id)`` / ``--arch <id>``."""
from repro.configs.base import (FLConfig, INPUT_SHAPES, ModelConfig,
                                ShapeConfig)

_ARCH_MODULES = {
    "zamba2-7b": "zamba2_7b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "codeqwen1.5-7b": "codeqwen1_5_7b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "kanana-2-30b-a3b": "kanana_2_30b_a3b",
    "qwen2.5-14b": "qwen2_5_14b",
    "whisper-tiny": "whisper_tiny",
    "xlstm-1.3b": "xlstm_1_3b",
    "internvl2-1b": "internvl2_1b",
    "granite-20b": "granite_20b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    try:
        modname = _ARCH_MODULES[arch_id]
    except KeyError:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    import importlib
    return importlib.import_module(f"repro.configs.{modname}").CONFIG


def get_shape(shape_id: str) -> ShapeConfig:
    return INPUT_SHAPES[shape_id]


__all__ = ["ARCH_IDS", "FLConfig", "INPUT_SHAPES", "ModelConfig",
           "ShapeConfig", "get_config", "get_shape"]

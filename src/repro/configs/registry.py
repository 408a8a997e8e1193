"""LM architecture registry: --arch <id> -> config or smoke config."""
from __future__ import annotations

import importlib

from repro.models.configs import ModelConfig

ARCH_IDS = (
    "llama4-scout-17b-a16e",
    "olmoe-1b-7b",
    "whisper-large-v3",
    "internlm2-20b",
    "phi3-medium-14b",
    "qwen3-14b",
    "command-r-35b",
    "qwen2-vl-72b",
    "mamba2-130m",
    "hymba-1.5b",
)

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "p")
            for a in ARCH_IDS}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(_MODULES[arch])
    return mod.SMOKE if smoke else mod.CONFIG

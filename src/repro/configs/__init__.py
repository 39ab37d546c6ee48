"""Architecture registry: the registered archs as selectable configs.

``get_config(name)`` returns the FULL paper-table config (exercised only via
the AOT dry-run); ``get_smoke(name)`` returns the reduced same-family config
used by per-arch smoke tests and CPU examples.
"""
from __future__ import annotations

from typing import Dict, List

from ..models.config import ModelConfig
from . import (gemma_7b, glm4_9b, internlm2_20b, kimi_k2_1t_a32b,
               llama_3_2_vision_90b, mamba2_2_7b, mixtral_8x22b,
               moonlight_16b_a3b, musicgen_medium, recurrentgemma_2b,
               smollm_135m)
from .shapes import (LONG_CONTEXT_OK, SHAPES, ShapeSpec, cache_len_for,
                     input_specs, shape_applicable)

_MODULES = {
    "mamba2-2.7b": mamba2_2_7b,
    "llama-3.2-vision-90b": llama_3_2_vision_90b,
    "gemma-7b": gemma_7b,
    "glm4-9b": glm4_9b,
    "internlm2-20b": internlm2_20b,
    "smollm-135m": smollm_135m,
    "recurrentgemma-2b": recurrentgemma_2b,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "mixtral-8x22b": mixtral_8x22b,
    "musicgen-medium": musicgen_medium,
}

ARCHS: List[str] = list(_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {ARCHS}")
    return _MODULES[name].CONFIG


def get_smoke(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {ARCHS}")
    return _MODULES[name].SMOKE


def all_cells() -> List[tuple]:
    """Every applicable (arch, shape) dry-run cell."""
    out = []
    for a in ARCHS:
        cfg = get_config(a)
        for s in SHAPES:
            if shape_applicable(cfg, s):
                out.append((a, s))
    return out


__all__ = ["ARCHS", "SHAPES", "ShapeSpec", "all_cells", "cache_len_for",
           "get_config", "get_smoke", "input_specs", "shape_applicable",
           "LONG_CONTEXT_OK"]

"""Architecture registry: the registered archs as selectable configs.

``get_config(name)`` returns the published config; ``get_smoke(name)``
returns the reduced config of the same blocks used by the CPU tests and
examples.
"""
from __future__ import annotations

from typing import List

from ..models.config import ModelConfig
from . import moonlight_16b_a3b, smollm_135m

_MODULES = {
    "smollm-135m": smollm_135m,
    "moonlight-16b-a3b": moonlight_16b_a3b,
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


__all__ = ["ARCHS", "get_config", "get_smoke"]

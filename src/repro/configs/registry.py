"""Registry of assigned architectures (``--arch <id>``)."""
from __future__ import annotations

from typing import Dict

from repro.configs.base import ArchConfig

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    assert cfg.name not in _REGISTRY, cfg.name
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    _load_all()
    return sorted(_REGISTRY)


_LOADED = False


def _load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    # importing each module registers its config
    from repro.configs import (  # noqa: F401
        jamba_v0_1_52b, whisper_base, yi_6b, qwen1_5_4b, qwen2_1_5b,
        qwen2_0_5b, qwen2_vl_2b, deepseek_v2_236b, deepseek_v2_lite,
        arctic_480b, rwkv6_3b, paper_lenet)
    _LOADED = True

"""Config registry: the 10 assigned architectures + the paper's own.

``get(name)`` returns the full config; ``get_tiny(name)`` the reduced
same-family config the smoke tests instantiate on CPU.
"""
from __future__ import annotations

from .base import ArchConfig, ShapeCell, SHAPES, SUBQUADRATIC, cell_skips, shape_cells
from . import (chatglm3_6b, granite_34b, granite_moe_3b, internlm2_20b,
               llama31_8b, llama32_vision_90b, minitron_4b, mixtral_8x7b,
               rwkv6_1b6, seamless_m4t_medium, zamba2_7b)

_MODULES = [
    chatglm3_6b, granite_34b, minitron_4b, internlm2_20b, mixtral_8x7b,
    granite_moe_3b, rwkv6_1b6, llama32_vision_90b, seamless_m4t_medium,
    zamba2_7b, llama31_8b,
]

ARCHS: dict[str, ArchConfig] = {m.CONFIG.name: m.CONFIG for m in _MODULES}
TINY: dict[str, ArchConfig] = {m.CONFIG.name: m.TINY for m in _MODULES}

# the 10 assigned (llama31-8b is the paper's own, listed separately)
ASSIGNED = [
    "chatglm3-6b", "granite-34b", "minitron-4b", "internlm2-20b",
    "mixtral-8x7b", "granite-moe-3b-a800m", "rwkv6-1.6b",
    "llama-3.2-vision-90b", "seamless-m4t-medium", "zamba2-7b",
]


def get(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def get_tiny(name: str) -> ArchConfig:
    return TINY[get(name).name]


def resolve(name: str, *, tiny: bool = False,
            n_layers: int | None = None) -> ArchConfig:
    """The published (or tiny) config with its depth cut to ``n_layers``
    when given; every other field keeps its value."""
    cfg = get_tiny(name) if tiny else get(name)
    return cfg if n_layers is None else cfg.replace(n_layers=n_layers)


def describe(cfg: ArchConfig) -> str:
    """One line naming the depth a run uses next to the published one."""
    return (f"config {cfg.name}: n_layers={cfg.n_layers} (published "
            f"{get(cfg.name).n_layers}) d_model={cfg.d_model} "
            f"d_ff={cfg.d_ff} heads={cfg.n_heads}/{cfg.n_kv_heads} "
            f"vocab={cfg.vocab_size} dtype={cfg.dtype}")


__all__ = [
    "ARCHS", "ASSIGNED", "TINY", "ArchConfig", "SHAPES", "ShapeCell",
    "SUBQUADRATIC", "cell_skips", "describe", "get", "get_tiny",
    "resolve", "shape_cells",
]

"""Architecture / shape / cell registry.

``get_config("llama3.2-1b")`` returns the exact assigned config;
``CELLS`` enumerates the 40 (arch x shape) dry-run cells.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Tuple

from repro.configs.base import (
    MeshConfig, ModelConfig, MoEConfig, PagedKVConfig, SSMConfig, ShapeConfig,
    TrainConfig, SINGLE_POD, MULTI_POD, model_active_params, model_params,
    reduce_for_smoke, with_blocks,
)

# arch id -> module name
_ARCH_MODULES: Dict[str, str] = {
    "llama3.2-1b": "repro.configs.llama3_2_1b",
    "gemma2-2b": "repro.configs.gemma2_2b",
    "llama3.2-3b": "repro.configs.llama3_2_3b",
    "qwen2-7b": "repro.configs.qwen2_7b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "llama-3.2-vision-90b": "repro.configs.llama3_2_vision_90b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large_398b",
}

ARCH_IDS: Tuple[str, ...] = tuple(_ARCH_MODULES)

SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": ShapeConfig("prefill_32k", seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": ShapeConfig("decode_32k", seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": ShapeConfig("long_500k", seq_len=524288, global_batch=1, kind="decode"),
}


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).config()


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def cell_skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    """None if the (arch, shape) cell is runnable, else the documented reason."""
    if shape.name == "long_500k" and not cfg.has_subquadratic_path:
        return ("long_500k requires a sub-quadratic attention path; "
                f"{cfg.name} is pure full-attention (see DESIGN.md §7)")
    return None


def all_cells(include_skipped: bool = True) -> List[Tuple[str, str, Optional[str]]]:
    """All 40 (arch, shape, skip_reason) cells in registry order."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            cells.append((arch, sname, cell_skip_reason(cfg, shape)))
    if not include_skipped:
        cells = [c for c in cells if c[2] is None]
    return cells


__all__ = [
    "ARCH_IDS", "SHAPES", "MeshConfig", "ModelConfig", "MoEConfig",
    "PagedKVConfig", "SSMConfig", "ShapeConfig", "TrainConfig", "SINGLE_POD",
    "MULTI_POD", "all_cells", "cell_skip_reason", "get_config", "get_shape",
    "model_active_params", "model_params", "reduce_for_smoke", "with_blocks",
]

"""Architecture registry: --arch <id> → configs + model API.

Every entry exposes the same functional API (init/forward/lm_logits/
prefill/init_cache/decode_step) regardless of family; whisper dispatches to
the enc-dec composition, everything else to the generic stack.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp

from repro.configs import (gemma2_9b, granite_3_8b, granite_4_h_small,
                           granite_moe_1b, llama32_vision_90b, llama4_maverick,
                           mamba2_780m, qwen2_72b, recurrentgemma_9b,
                           starcoder2_7b, whisper_base)
from repro.configs.common import ModelConfig, SHAPES, ShapeSpec
from repro.models import encdec, transformer


@dataclasses.dataclass(frozen=True)
class ArchEntry:
    name: str
    full: ModelConfig
    smoke: ModelConfig
    module: object                      # transformer | encdec

    def config(self, preset: str = "full") -> ModelConfig:
        return self.full if preset == "full" else self.smoke

    # frontend stubs -------------------------------------------------------
    def frontend_shape(self, cfg: ModelConfig, batch: int) -> Optional[dict]:
        if cfg.family == "audio":
            return {"frames": (batch, cfg.n_frontend_tokens, cfg.frontend_dim)}
        if cfg.family == "vlm":
            return {"cross_kv": (batch, cfg.n_frontend_tokens,
                                 cfg.frontend_dim)}
        return None


_CONF = {
    "whisper-base": (whisper_base, encdec),
    "gemma2-9b": (gemma2_9b, transformer),
    "qwen2-72b": (qwen2_72b, transformer),
    "starcoder2-7b": (starcoder2_7b, transformer),
    "granite-3-8b": (granite_3_8b, transformer),
    "llama-3.2-vision-90b": (llama32_vision_90b, transformer),
    "mamba2-780m": (mamba2_780m, transformer),
    "recurrentgemma-9b": (recurrentgemma_9b, transformer),
    "granite-moe-1b-a400m": (granite_moe_1b, transformer),
    "llama4-maverick-400b-a17b": (llama4_maverick, transformer),
    "granite-4.0-h-small": (granite_4_h_small, transformer),
}

ARCHS: dict[str, ArchEntry] = {
    name: ArchEntry(name=name, full=mod.FULL, smoke=mod.SMOKE, module=api)
    for name, (mod, api) in _CONF.items()
}


def get(name: str) -> ArchEntry:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def cells(include_skips: bool = True):
    """All (arch × shape) cells with skip annotations."""
    out = []
    for name, entry in ARCHS.items():
        for shape in SHAPES.values():
            skip = None
            if shape.name == "long_500k" and not entry.full.supports_long_context:
                skip = "quadratic attention cannot serve 500k context"
            if skip is None or include_skips:
                out.append((name, shape, skip))
    return out

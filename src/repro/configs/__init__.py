"""Config registry: 10 assigned architectures + the paper's own models."""
from __future__ import annotations

from .base import (ArchConfig, BlockSpec, InputShape, Stage, YaRN,
                   INPUT_SHAPES)

from .h2o_danube_3_4b import CONFIG as h2o_danube_3_4b
from .deepseek_v3_671b import CONFIG as deepseek_v3_671b
from .mamba2_1_3b import CONFIG as mamba2_1_3b
from .whisper_large_v3 import CONFIG as whisper_large_v3
from .jamba_1_5_large_398b import CONFIG as jamba_1_5_large_398b
from .granite_moe_3b_a800m import CONFIG as granite_moe_3b_a800m
from .phi_3_vision_4_2b import CONFIG as phi_3_vision_4_2b
from .gemma2_9b import CONFIG as gemma2_9b
from .yi_34b import CONFIG as yi_34b
from .chatglm3_6b import CONFIG as chatglm3_6b

ARCHS: dict[str, ArchConfig] = {
    c.name: c for c in [
        h2o_danube_3_4b, deepseek_v3_671b, mamba2_1_3b, whisper_large_v3,
        jamba_1_5_large_398b, granite_moe_3b_a800m, phi_3_vision_4_2b,
        gemma2_9b, yi_34b, chatglm3_6b,
    ]
}


def get_config(name: str) -> ArchConfig:
    try:
        return ARCHS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")


__all__ = ["ArchConfig", "BlockSpec", "InputShape", "Stage", "YaRN",
           "INPUT_SHAPES", "ARCHS", "get_config"]

"""Config registry: ``--arch <id>`` resolution for launch/dryrun."""
from repro.configs.base import (ALL_SHAPES, DECODE_32K, LONG_500K,
                                PREFILL_32K, TRAIN_4K, ArchConfig, MLAConfig,
                                MoEConfig, ShapeSpec, SSMConfig, shapes_for)

from repro.configs.qwen2_0_5b import CONFIG as QWEN2_0_5B
from repro.configs.codeqwen1_5_7b import CONFIG as CODEQWEN1_5_7B
from repro.configs.qwen1_5_4b import CONFIG as QWEN1_5_4B
from repro.configs.gemma3_12b import CONFIG as GEMMA3_12B
from repro.configs.musicgen_medium import CONFIG as MUSICGEN_MEDIUM
from repro.configs.dbrx_132b import CONFIG as DBRX_132B
from repro.configs.deepseek_v2_lite_16b import CONFIG as DEEPSEEK_V2_LITE
from repro.configs.mamba2_370m import CONFIG as MAMBA2_370M
from repro.configs.pixtral_12b import CONFIG as PIXTRAL_12B
from repro.configs.zamba2_1_2b import CONFIG as ZAMBA2_1_2B

ARCHS: dict[str, ArchConfig] = {
    c.name: c for c in (
        QWEN2_0_5B, CODEQWEN1_5_7B, QWEN1_5_4B, GEMMA3_12B, MUSICGEN_MEDIUM,
        DBRX_132B, DEEPSEEK_V2_LITE, MAMBA2_370M, PIXTRAL_12B, ZAMBA2_1_2B,
    )
}

SHAPES: dict[str, ShapeSpec] = {s.name: s for s in ALL_SHAPES}


def get_arch(name: str) -> ArchConfig:
    try:
        return ARCHS[name]
    except KeyError as e:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}") from e


def get_shape(name: str) -> ShapeSpec:
    try:
        return SHAPES[name]
    except KeyError as e:
        raise KeyError(f"unknown shape {name!r}; have {sorted(SHAPES)}") from e


def all_cells() -> list[tuple[ArchConfig, ShapeSpec]]:
    """Every (architecture x applicable shape) dry-run cell."""
    return [(cfg, shp) for cfg in ARCHS.values() for shp in shapes_for(cfg)]

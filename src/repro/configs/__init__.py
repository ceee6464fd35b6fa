from repro.configs.base import (ModelConfig, MoESpec, SSMSpec, MLASpec,
                                YaRNSpec, EncoderSpec, get_config, list_archs,
                                reduced_config)
from repro.configs.shapes import SHAPES, ShapeSpec, input_specs, shape_applicable

__all__ = [
    "ModelConfig", "MoESpec", "SSMSpec", "MLASpec", "YaRNSpec", "EncoderSpec",
    "get_config", "list_archs", "reduced_config",
    "SHAPES", "ShapeSpec", "input_specs", "shape_applicable",
]

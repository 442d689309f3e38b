from radixmesh_tpu.models.llama import (
    ModelConfig,
    init_params,
    prefill_forward,
    decode_step,
    param_logical_axes,
    convert_hf_state_dict,
)
from radixmesh_tpu.models import (
    bailing_hybrid, glm4_moe_lite, glm_moe_dsa, jamba, laguna, lfm2_moe, mellum2,
    nemotron_h, phi4flash, qwen2,
)

_PRESETS = {
    "llama3-8b": ModelConfig.llama3_8b,
    "llama3-70b": ModelConfig.llama3_70b,
    "llama3.1-8b": ModelConfig.llama31_8b,
    "llama3.1-70b": ModelConfig.llama31_70b,
    "llama3.2-1b": ModelConfig.llama32_1b,
    "llama3.2-3b": ModelConfig.llama32_3b,
    "llama3-tiny": ModelConfig.tiny,
    "qwen2-72b": qwen2.qwen2_72b,
    "qwen2-7b": qwen2.qwen2_7b,
    "qwen2.5-14b": qwen2.qwen25_14b,
    "qwen2.5-32b": qwen2.qwen25_32b,
    "qwen2-tiny": qwen2.qwen2_tiny,
    "mellum2-12b-a2.5b": mellum2.mellum2_12b_a2_5b,
    "mellum2-12b-a2.5b-l12": mellum2.mellum2_12b_a2_5b_l12,
    "mellum2-tiny": mellum2.mellum2_tiny,
    "glm-4.7-flash": glm4_moe_lite.glm47_flash,
    "glm-4.7-flash-l8": glm4_moe_lite.glm47_flash_l8,
    "glm-4.7-flash-tiny": glm4_moe_lite.glm47_flash_tiny,
    "glm-5.2": glm_moe_dsa.glm52,
    "glm-5.2-ep16": glm_moe_dsa.glm52_ep16,
    "glm-5.2-tiny": glm_moe_dsa.glm52_tiny,
    "lfm2-24b-a2b": lfm2_moe.lfm2_24b_a2b,
    "lfm2-24b-a2b-ep8": lfm2_moe.lfm2_24b_a2b_ep8,
    "lfm2-tiny": lfm2_moe.lfm2_tiny,
    "phi-4-mini-flash-reasoning": phi4flash.phi4_mini_flash_reasoning,
    "phi4flash-tiny": phi4flash.phi4flash_tiny,
    "laguna-xs.2": laguna.laguna_xs2,
    "laguna-xs.2-l5": laguna.laguna_xs2_l5,
    "laguna-tiny": laguna.laguna_tiny,
    "ling-3.0-flash": bailing_hybrid.ling3_flash,
    "ling-3.0-flash-ep8": bailing_hybrid.ling3_flash_ep8,
    "ling-tiny": bailing_hybrid.ling_tiny,
    "nemotron-3-nano-30b-a3b": nemotron_h.nemotron3_nano,
    "nemotron-3-nano-ep8": nemotron_h.nemotron3_nano_ep8,
    "nemotron-tiny": nemotron_h.nemotron_tiny,
    "jamba2-3b": jamba.jamba2_3b,
    "jamba-tiny": jamba.jamba_tiny,
}


def get_config(name: str, **overrides) -> ModelConfig:
    """Model registry: named presets for the BASELINE.json target configs."""
    try:
        cfg = _PRESETS[name]()
    except KeyError:
        raise ValueError(f"unknown model {name!r}; known: {sorted(_PRESETS)}")
    return cfg.replace(**overrides) if overrides else cfg


__all__ = [
    "ModelConfig",
    "init_params",
    "prefill_forward",
    "decode_step",
    "param_logical_axes",
    "convert_hf_state_dict",
    "get_config",
]

"""Architecture-name → model-class registry (the model-zoo dispatch the
reference delegates to the vllm package's registry, SURVEY.md §2.3)."""

from __future__ import annotations

from typing import Any

_REGISTRY: dict[str, Any] = {}


def register_model(cls) -> Any:
    for arch in cls.architectures:
        _REGISTRY[arch] = cls
    return cls


def _populate() -> None:
    if _REGISTRY:
        return
    from vllm_distributed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    from vllm_distributed_tpu.models.falcon_h1 import FalconH1ForCausalLM
    from vllm_distributed_tpu.models.lfm2_moe import Lfm2MoeForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaForCausalLM
    from vllm_distributed_tpu.models.longcat_flash import (
        LongcatFlashForCausalLM,
    )
    from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
    from vllm_distributed_tpu.models.opt import OPTForCausalLM

    register_model(LlamaForCausalLM)
    register_model(OPTForCausalLM)
    register_model(MixtralForCausalLM)
    register_model(LongcatFlashForCausalLM)
    register_model(Lfm2MoeForCausalLM)
    register_model(DeepseekV3ForCausalLM)
    register_model(FalconH1ForCausalLM)


def get_model_class(architecture: str):
    _populate()
    try:
        return _REGISTRY[architecture]
    except KeyError:
        raise ValueError(
            f"unsupported architecture {architecture!r}; known: "
            f"{sorted(_REGISTRY)}"
        ) from None


def list_architectures() -> list[str]:
    _populate()
    return sorted(_REGISTRY)

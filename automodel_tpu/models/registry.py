"""HF architecture → native model-family registry.

Parity: _transformers/registry.py:33 maps HF ``architectures[0]`` to in-tree
ModelClass. Families register a builder returning (model, adapter) from an HF
config. Out-of-tree registration mirrors the reference's decorator.
"""

from __future__ import annotations

from typing import Any, Callable

from automodel_tpu.models.common.config import BackendConfig, TransformerConfig

_REGISTRY: dict[str, Callable] = {}


def register_architecture(*names: str):
    def deco(builder: Callable):
        for n in names:
            _REGISTRY[n] = builder
        return builder

    return deco


def resolve_architecture(hf_config: Any) -> Callable:
    archs = (
        hf_config.get("architectures")
        if isinstance(hf_config, dict)
        else getattr(hf_config, "architectures", None)
    ) or []
    # a family may also register its `model_type` (a config.json without
    # `architectures` then still finds it)
    model_type = (
        hf_config.get("model_type")
        if isinstance(hf_config, dict)
        else getattr(hf_config, "model_type", None)
    )
    for a in (*archs, model_type):
        if a in _REGISTRY:
            return _REGISTRY[a]
    # generic llama-style fallback (SURVEY.md §7 hard part 6): any dense
    # architecture matching the llama layout trains via the generic family.
    from automodel_tpu.models.registry import _llama_builder

    return _llama_builder


def available_architectures() -> list[str]:
    return sorted(_REGISTRY)


@register_architecture(
    "LlamaForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
    "MistralForCausalLM",
    # fused qkv/gate_up checkpoints load through the conversion mapping
    # (checkpoint/conversion_mapping.py FUSED_QKV / FUSED_GATE_UP)
    "Phi3ForCausalLM",
)
def _llama_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.llama import LlamaForCausalLM, LlamaStateDictAdapter

    cfg = TransformerConfig.from_hf(hf_config)
    return LlamaForCausalLM(cfg, backend), LlamaStateDictAdapter(cfg)


@register_architecture("GPT2LMHeadModel")
def _gpt2_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.gpt2 import GPT2Config, GPT2ForCausalLM, GPT2StateDictAdapter

    cfg = GPT2Config.from_hf(hf_config)
    return GPT2ForCausalLM(cfg, backend), GPT2StateDictAdapter(cfg)


@register_architecture("Gemma2ForCausalLM", "Gemma3ForCausalLM")
def _gemma_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.gemma import (
        GemmaConfig,
        GemmaForCausalLM,
        GemmaStateDictAdapter,
    )

    cfg = GemmaConfig.from_hf(hf_config)
    return GemmaForCausalLM(cfg, backend), GemmaStateDictAdapter(cfg)


@register_architecture("Gemma3ForConditionalGeneration")
def _gemma3_vl_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.gemma3_vl import (
        Gemma3VLConfig,
        Gemma3VLForConditionalGeneration,
        Gemma3VLStateDictAdapter,
    )

    cfg = Gemma3VLConfig.from_hf(hf_config)
    return Gemma3VLForConditionalGeneration(cfg, backend), Gemma3VLStateDictAdapter(cfg)


@register_architecture("DeepseekV3ForCausalLM")
def _deepseek_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        DeepseekV3ForCausalLM,
        DeepseekV3StateDictAdapter,
    )

    cfg = DeepseekV3Config.from_hf(hf_config)
    return DeepseekV3ForCausalLM(cfg, backend), DeepseekV3StateDictAdapter(cfg)


@register_architecture("KimiLinearForCausalLM")
def _kimi_linear_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.kimi_linear import (
        KimiLinearConfig,
        KimiLinearForCausalLM,
        KimiLinearStateDictAdapter,
    )

    cfg = KimiLinearConfig.from_hf(hf_config)
    return KimiLinearForCausalLM(cfg, backend), KimiLinearStateDictAdapter(cfg)


@register_architecture("Xing4_0ForCausalLM", "xing4_0")
def _xing4_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.xing4 import (
        Xing4Config,
        Xing4ForCausalLM,
        Xing4StateDictAdapter,
    )

    cfg = Xing4Config.from_hf(hf_config)
    return Xing4ForCausalLM(cfg, backend), Xing4StateDictAdapter(cfg)


@register_architecture("GptOssForCausalLM")
def _gpt_oss_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.gpt_oss import (
        GptOssConfig,
        GptOssForCausalLM,
        GptOssStateDictAdapter,
    )

    cfg = GptOssConfig.from_hf(hf_config)
    return GptOssForCausalLM(cfg, backend), GptOssStateDictAdapter(cfg)


@register_architecture("Qwen3NextForCausalLM")
def _qwen3_next_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.qwen3_next import (
        Qwen3NextConfig,
        Qwen3NextForCausalLM,
        Qwen3NextStateDictAdapter,
    )

    cfg = Qwen3NextConfig.from_hf(hf_config)
    return Qwen3NextForCausalLM(cfg, backend), Qwen3NextStateDictAdapter(cfg)


@register_architecture(
    "Qwen3MoeForCausalLM",
    "Glm4MoeForCausalLM",
    # mixtral / qwen2-moe checkpoints present canonical keys through the
    # conversion mapping (block_sparse_moe w1/w3/w2, shared_expert renames)
    "MixtralForCausalLM",
    "Qwen2MoeForCausalLM",
)
def _moe_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.qwen3_moe import (
        MoEForCausalLM,
        MoEStateDictAdapter,
        MoETransformerConfig,
    )

    cfg = MoETransformerConfig.from_hf(hf_config)
    get = lambda k, d=None: (
        hf_config.get(k, d) if isinstance(hf_config, dict) else getattr(hf_config, k, d)
    )
    model_type = get("model_type", "")
    style = model_type if model_type in ("mixtral", "qwen2_moe") else None
    return MoEForCausalLM(cfg, backend), MoEStateDictAdapter(cfg, hf_key_style=style)


@register_architecture("Qwen3VLMoeForConditionalGeneration")
def _qwen3_vl_moe_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.qwen3_vl_moe import (
        Qwen3VLMoeConfig,
        Qwen3VLMoeForConditionalGeneration,
        Qwen3VLMoeStateDictAdapter,
    )

    cfg = Qwen3VLMoeConfig.from_hf(hf_config)
    return (
        Qwen3VLMoeForConditionalGeneration(cfg, backend),
        Qwen3VLMoeStateDictAdapter(cfg),
    )


@register_architecture("Step3p5ForCausalLM", "Step3P5ForCausalLM")
def _step3p5_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.step3p5 import (
        Step3p5Config,
        Step3p5ForCausalLM,
        Step3p5StateDictAdapter,
    )

    cfg = Step3p5Config.from_hf(hf_config)
    return Step3p5ForCausalLM(cfg, backend), Step3p5StateDictAdapter(cfg)


@register_architecture(
    "NemotronV3ForCausalLM", "NemotronHForCausalLM"
)
def _nemotron_v3_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.nemotron_v3 import (
        NemotronV3Config,
        NemotronV3ForCausalLM,
        NemotronV3StateDictAdapter,
    )

    cfg = NemotronV3Config.from_hf(hf_config)
    return NemotronV3ForCausalLM(cfg, backend), NemotronV3StateDictAdapter(cfg)


@register_architecture("NemotronParseForConditionalGeneration")
def _nemotron_parse_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.nemotron_parse import (
        NemotronParseConfig,
        NemotronParseForConditionalGeneration,
        NemotronParseStateDictAdapter,
    )

    cfg = NemotronParseConfig.from_hf(hf_config)
    return (
        NemotronParseForConditionalGeneration(cfg, backend),
        NemotronParseStateDictAdapter(cfg),
    )


@register_architecture(
    "Qwen3OmniMoeForConditionalGeneration",
    "Qwen3OmniMoeThinkerForConditionalGeneration",
)
def _qwen3_omni_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.qwen3_omni_moe import (
        Qwen3OmniMoeStateDictAdapter,
        Qwen3OmniMoeThinkerConfig,
        Qwen3OmniMoeThinkerForCausalLM,
    )

    cfg = Qwen3OmniMoeThinkerConfig.from_hf(hf_config)
    return (
        Qwen3OmniMoeThinkerForCausalLM(cfg, backend),
        Qwen3OmniMoeStateDictAdapter(cfg),
    )


@register_architecture("KimiVLForConditionalGeneration")
def _kimi_vl_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.kimi_vl import (
        KimiVLConfig,
        KimiVLForConditionalGeneration,
        KimiVLStateDictAdapter,
    )

    cfg = KimiVLConfig.from_hf(hf_config)
    return KimiVLForConditionalGeneration(cfg, backend), KimiVLStateDictAdapter(cfg)


@register_architecture("KimiK25VLForConditionalGeneration", "KimiVLForConditionalGeneration_K25")
def _kimi_k25_vl_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.kimi_k25_vl import (
        KimiK25VLConfig,
        KimiK25VLForConditionalGeneration,
        KimiK25VLStateDictAdapter,
    )

    cfg = KimiK25VLConfig.from_hf(hf_config)
    return (
        KimiK25VLForConditionalGeneration(cfg, backend),
        KimiK25VLStateDictAdapter(cfg),
    )


@register_architecture("SarvamMlaForCausalLM", "sarvam_mla")
def _sarvam_mla_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.sarvam_mla import (
        SarvamMlaConfig,
        SarvamMlaForCausalLM,
        SarvamMlaStateDictAdapter,
    )

    cfg = SarvamMlaConfig.from_hf(hf_config)
    return SarvamMlaForCausalLM(cfg, backend), SarvamMlaStateDictAdapter(cfg)


@register_architecture("Lfm2MoeForCausalLM")
def _lfm2_moe_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.lfm2_moe import (
        Lfm2MoeConfig,
        Lfm2MoeForCausalLM,
        Lfm2MoeStateDictAdapter,
    )

    cfg = Lfm2MoeConfig.from_hf(hf_config)
    return Lfm2MoeForCausalLM(cfg, backend), Lfm2MoeStateDictAdapter(cfg)


@register_architecture("MiniMaxM2ForCausalLM")
def _minimax_m2_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.minimax_m2 import MiniMaxM2Config, MiniMaxM2ForCausalLM
    from automodel_tpu.models.qwen3_moe import MoEStateDictAdapter

    cfg = MiniMaxM2Config.from_hf(hf_config)
    # MiniMax-M2 keeps the mixtral block_sparse_moe w1/w3/w2 key dialect
    # (reference minimax_m2/state_dict_adapter.py expert regex) — load-side
    # renames ride the conversion mapping, save-side the mixtral key style
    return MiniMaxM2ForCausalLM(cfg, backend), MoEStateDictAdapter(
        cfg, hf_key_style="mixtral"
    )


@register_architecture(
    "Qwen3_5MoeForConditionalGeneration", "Qwen3_5MoeForCausalLM"
)
def _qwen3_5_moe_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.qwen3_5_moe import (
        Qwen3_5MoeConfig,
        Qwen3_5MoeForConditionalGeneration,
        Qwen3_5MoeStateDictAdapter,
    )

    cfg = Qwen3_5MoeConfig.from_hf(hf_config)
    return (
        Qwen3_5MoeForConditionalGeneration(cfg, backend),
        Qwen3_5MoeStateDictAdapter(cfg),
    )


@register_architecture("Mistral3ForConditionalGeneration")
def _mistral3_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.mistral3 import (
        Mistral3Config,
        Mistral3ForConditionalGeneration,
        Mistral3StateDictAdapter,
    )

    cfg = Mistral3Config.from_hf(hf_config)
    return Mistral3ForConditionalGeneration(cfg, backend), Mistral3StateDictAdapter(cfg)


@register_architecture("DeepseekV32ForCausalLM")
def _deepseek_v32_builder(hf_config: Any, backend: BackendConfig):
    from automodel_tpu.models.deepseek_v32 import (
        DeepseekV32Config,
        DeepseekV32ForCausalLM,
        DeepseekV32StateDictAdapter,
    )

    cfg = DeepseekV32Config.from_hf(hf_config)
    return DeepseekV32ForCausalLM(cfg, backend), DeepseekV32StateDictAdapter(cfg)

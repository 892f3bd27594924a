"""Model zoo — the role PaddleNLP's ``llm/`` + ``paddlenlp/transformers``
plays for the reference (SURVEY.md §0: the baseline workloads are PaddleNLP
scripts driving the framework). TPU-first implementations built on
paddle_tpu's nn + parallel layers + Pallas kernels.

Served through ``inference.ContinuousBatchingEngine``: Llama, Qwen2 and
GPT-2 (one paged K/V pair a layer), Nemotron-H (per-slot recurrent state
beside paged K/V, ``cache_spec.SlotState``) and EXAONE-MoE (window layers
over per-slot rings beside global layers over pages,
``cache_spec.WindowKV``; gated held-share experts). DeepSeek-V2 (MLA) and
ERNIE run dense only."""

from .gpt2 import GPT2Config, GPT2Model, GPT2ForCausalLM
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,
                    LlamaForCausalLMPipe, LlamaPretrainingCriterion)
from .qwen2 import (Qwen2Config, Qwen2MoeConfig, Qwen2ForCausalLM,
                    Qwen2MoeForCausalLM, Qwen2MoeForCausalLMPipe,
                    Qwen2MoePretrainingCriterion)
from .ernie import (ErnieConfig, ErnieModel, ErnieForPretraining,
                    ErnieForMaskedLM, ErnieForSequenceClassification)
from .deepseek import DeepseekV2Config, DeepseekV2ForCausalLM
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM
from .exaone_moe import ExaoneMoeConfig, ExaoneMoeForCausalLM

__all__ = ["GPT2Config", "GPT2Model", "GPT2ForCausalLM", "LlamaConfig",
           "LlamaModel", "LlamaForCausalLM", "LlamaForCausalLMPipe",
           "LlamaPretrainingCriterion", "Qwen2Config",
           "Qwen2MoeConfig", "Qwen2ForCausalLM", "Qwen2MoeForCausalLM",
           "Qwen2MoeForCausalLMPipe", "Qwen2MoePretrainingCriterion",
           "ErnieConfig", "ErnieModel", "ErnieForPretraining",
           "ErnieForMaskedLM", "ErnieForSequenceClassification", "DeepseekV2Config",
           "DeepseekV2ForCausalLM", "NemotronHConfig",
           "NemotronHForCausalLM", "ExaoneMoeConfig",
           "ExaoneMoeForCausalLM"]

"""Model zoo — the role PaddleNLP's ``llm/`` + ``paddlenlp/transformers``
plays for the reference (SURVEY.md §0: the baseline workloads are PaddleNLP
scripts driving the framework). TPU-first implementations built on
paddle_tpu's nn + parallel layers + Pallas kernels.

Served through ``inference.ContinuousBatchingEngine``: Llama, Qwen2 and
GPT-2 (one paged K/V pair a layer), Nemotron-H (per-slot recurrent state
beside paged K/V, ``cache_spec.SlotState``), EXAONE-MoE (window layers
over per-slot rings beside global layers over pages,
``cache_spec.WindowKV``; gated held-share experts) and Qwen3-Next (gated
delta-rule layers over per-slot float32 state beside gated full attention
over pages; softmax-routed held-share experts beside a gated shared one).
Of Qwen3-Next ONE benchmark cell is measured, on one chip: its share of an
8-way expert-parallel deployment, 12 of 48 layers, serving
(``PERF.md``); nothing of it trains. DeepSeek-V2 (MLA) and ERNIE run dense
only."""

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
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM

__all__ = ["GPT2Config", "GPT2Model", "GPT2ForCausalLM", "LlamaConfig",
           "LlamaModel", "LlamaForCausalLM", "LlamaForCausalLMPipe",
           "LlamaPretrainingCriterion", "Qwen2Config",
           "Qwen2MoeConfig", "Qwen2ForCausalLM", "Qwen2MoeForCausalLM",
           "Qwen2MoeForCausalLMPipe", "Qwen2MoePretrainingCriterion",
           "ErnieConfig", "ErnieModel", "ErnieForPretraining",
           "ErnieForMaskedLM", "ErnieForSequenceClassification", "DeepseekV2Config",
           "DeepseekV2ForCausalLM", "NemotronHConfig",
           "NemotronHForCausalLM", "ExaoneMoeConfig",
           "ExaoneMoeForCausalLM", "Qwen3NextConfig",
           "Qwen3NextForCausalLM"]

"""Flagship model families (TPU-native, hybrid-parallel-ready).

The reference ships its large-model definitions in test/benchmark harnesses
(auto_parallel_gpt_model.py, hybrid_parallel_pp_transformer.py) and fused
transformer ops (operators/fused/).  Here they are first-class: every model
is built from the parallel layers in distributed.fleet.meta_parallel, so
the same definition runs single-chip or on any hybrid mesh.
"""
from .gpt import (
    GPTConfig, GPTModel, GPTForCausalLM, GPTForCausalLMPipe,
    GPTPretrainingCriterion, GPT_CONFIGS, gpt_tiny, gpt2_345m, gpt3_13b,
)
from .llama import (
    LlamaConfig, LlamaModel, LlamaForCausalLM, LlamaForCausalLMPipe,
    LlamaPretrainingCriterion, LLAMA_CONFIGS, llama_tiny, llama2_7b,
    llama2_13b, llama2_70b,
)
from .deepseek_v3 import (
    DeepseekV3Config, DeepseekV3ForCausalLM, deepseek_v3_tiny,
)
from .keye_vl2 import (
    KeyeVL2Config, KeyeVL2ForCausalLM, keye_vl2_tiny,
)
from .evabyte import (
    EvaByteConfig, EvaByteForCausalLM, evabyte_tiny,
)
from .mellum import (
    MellumConfig, MellumForCausalLM, mellum_tiny,
)
from .lfm2 import (
    Lfm2Config, Lfm2ForCausalLM, lfm2_tiny,
)
from .kimi_linear import (
    KimiLinearConfig, KimiLinearForCausalLM, kimi_linear_tiny,
)

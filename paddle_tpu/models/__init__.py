"""Flagship model families (GPT for hybrid-parallel training; the
reference trains these through Fleet — SURVEY.md §3.3)."""
from .gpt import (GPTConfig, GPTForCausalLM, GPTForCausalLMPipe, GPTModel,
                  GPTPretrainingCriterion, ernie_moe_base, gpt_125m,
                  gpt_13b, gpt_1p3b, gpt_350m, gpt_moe_tiny, gpt_tiny)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaPretrainingCriterion, llama_13b, llama_7b,
                    llama_tiny, llama_tiny_draft)

from .mla_moe import MLAMoEConfig, MLAMoEForCausalLM, mla_moe_tiny
from .hybrid_moe import (HybridMoEConfig, HybridMoEForCausalLM, afmoe_tiny,
                         hybrid_moe_tiny)
from .ssm_moe import SSMMoEConfig, SSMMoEForCausalLM, ssm_moe_tiny

__all__ = ["MLAMoEConfig", "MLAMoEForCausalLM", "mla_moe_tiny",
           "SSMMoEConfig", "SSMMoEForCausalLM", "ssm_moe_tiny",
           "HybridMoEConfig", "HybridMoEForCausalLM", "hybrid_moe_tiny",
           "afmoe_tiny",
           "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTForCausalLMPipe",
           "GPTPretrainingCriterion", "gpt_tiny", "gpt_125m", "gpt_350m",
           "gpt_1p3b", "gpt_13b", "gpt_moe_tiny", "ernie_moe_base",
           "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "llama_tiny", "llama_tiny_draft",
           "llama_7b", "llama_13b"]

"""Flagship model families for the benchmark configs (BASELINE.json).

The reference ships transformers in python/paddle/nn/layer/transformer.py and fused
variants in incubate; full LM architectures (GPT/BERT/ERNIE) live in PaddleNLP built on
those layers. Here they are first-class since they are the benchmark configs: GPT
(decoder LM, the north-star config) and BERT (encoder, the to_static config).
"""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, gpt3_1p3b,  # noqa: F401
                  gpt_tiny, shard_gpt_tp)
from .bert import BertConfig, BertModel, BertForPreTraining, bert_base, bert_tiny  # noqa: F401
from .ernie import (ErnieConfig, ErnieModel,  # noqa: F401
                    ErnieForSequenceClassification, ErnieForMaskedLM,
                    ernie_tiny)
from .t5 import (T5Config, T5Model,  # noqa: F401
                 T5ForConditionalGeneration, t5_tiny)
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM, llama_tiny,  # noqa: F401
                    llama_7b, shard_llama_tp)
from .qwen3_next import (Qwen3NextConfig, Qwen3NextModel,  # noqa: F401
                         Qwen3NextForCausalLM, qwen3_next_tiny)
from .falcon_h1 import (FalconH1Config, FalconH1Model,  # noqa: F401
                        FalconH1ForCausalLM, falcon_h1_tiny)
from .longcat_flash import (LongCatFlashConfig, LongCatFlashModel,  # noqa: F401
                            LongCatFlashForCausalLM, longcat_flash_tiny)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3Model,  # noqa: F401
                          DeepseekV3ForCausalLM, deepseek_v3_tiny)

"""Model family: tensor-parallel transformer building blocks + GPT-2 / BERT.

The reference delegates models to Megatron-LM / BingBert examples; on TPU the
framework owns a sharded model zoo (SURVEY.md §7.1 "mpu protocol" row).
"""

from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              init_block_params,
                                              block_partition_specs,
                                              block_apply, stack_apply,
                                              token_batch_specs)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2_SIZES
from deepspeed_tpu.models.pipeline_gpt2 import GPT2Pipelined
from deepspeed_tpu.models.gpt2_moe import GPT2MoE, GPT2MoEPipelined
from deepspeed_tpu.models.moe import MoEConfig
from deepspeed_tpu.models.bert import (BertForPreTraining,
                                       BertForQuestionAnswering, BERT_SIZES)
from deepspeed_tpu.models.looped import LoopedConfig, LoopedLM, LOOPED_SIZES
from deepspeed_tpu.models.hybrid import HybridConfig, HybridLM, HYBRID_SIZES
from deepspeed_tpu.models.latent_moe import (LatentMoEConfig, LatentMoELM,
                                             LATENT_MOE_SIZES)
from deepspeed_tpu.models.delta_moe import (DeltaMoEConfig, DeltaMoELM,
                                            DELTA_MOE_SIZES)
from deepspeed_tpu.models.window_moe import (WindowMoEConfig, WindowMoELM,
                                             WINDOW_MOE_SIZES)
from deepspeed_tpu.models.ssd_hybrid import (Mamba2HybridConfig,
                                             Mamba2HybridLM,
                                             MAMBA2_HYBRID_SIZES)

__all__ = [
    "TransformerConfig", "init_block_params", "block_partition_specs",
    "block_apply", "stack_apply", "token_batch_specs",
    "GPT2", "GPT2_SIZES",
    "GPT2Pipelined", "GPT2MoE", "GPT2MoEPipelined", "MoEConfig",
    "BertForPreTraining", "BertForQuestionAnswering", "BERT_SIZES",
    "LoopedConfig", "LoopedLM", "LOOPED_SIZES",
    "HybridConfig", "HybridLM", "HYBRID_SIZES",
    "LatentMoEConfig", "LatentMoELM", "LATENT_MOE_SIZES",
    "DeltaMoEConfig", "DeltaMoELM", "DELTA_MOE_SIZES",
    "WindowMoEConfig", "WindowMoELM", "WINDOW_MOE_SIZES",
    "Mamba2HybridConfig", "Mamba2HybridLM", "MAMBA2_HYBRID_SIZES",
]

"""GPT-2 with Switch-style Mixture-of-Experts FFNs (expert parallelism).

Beyond-reference model family (see models/moe.py for the routing/expert
parallelism design): every block's FFN is a capacity-routed top-1 MoE, the
expert dim shards over the ``model`` axis, and the Switch load-balancing
aux loss joins the LM loss with ``aux_weight``.  A thin ``GPT2`` subclass:
only the block-stack hooks differ (init/specs/forward); embeddings, the
vocab-parallel head, and the engine protocol are inherited.
"""

from __future__ import annotations

import dataclasses

from deepspeed_tpu.models import moe as M
from deepspeed_tpu.models.gpt2 import GPT2, GPT2_SIZES
from deepspeed_tpu.models.pipeline_gpt2 import GPT2Pipelined


@dataclasses.dataclass
class GPT2MoE(GPT2):
    """Callable model object satisfying the engine protocol."""
    config: M.MoEConfig

    @classmethod
    def from_size(cls, size: str, num_experts: int = 8,
                  capacity_factor: float = 1.25, aux_weight: float = 0.01,
                  router_top_k: int = 1, **overrides) -> "GPT2MoE":
        kw = dict(GPT2_SIZES[size])
        kw.update(overrides)
        kw.setdefault("pre_ln", True)
        kw.setdefault("causal", True)
        return cls(M.MoEConfig(num_experts=num_experts,
                               capacity_factor=capacity_factor,
                               aux_weight=aux_weight,
                               router_top_k=router_top_k, **kw))

    def _init_blocks(self, rng):
        return M.init_moe_block_params(self.config, rng)

    def _block_specs(self):
        return M.moe_block_partition_specs()

    def _stack(self, x, blocks, z3_dims=None):
        x, aux = M.moe_stack_apply(x, blocks, self.config, z3_dims=z3_dims)
        return x, self.config.aux_weight * aux


@dataclasses.dataclass
class GPT2MoEPipelined(GPT2Pipelined):
    """MoE x pipeline parallelism: expert-stacked blocks shard their layer
    dim over ``pipe`` AND their expert dim over ``model`` (expert
    parallelism), micro-batches stream through the GPipe schedule, and
    each stage's Switch aux loss (masked to its real micro-batch ticks)
    psums over the pipe ring into the LM loss.

    Composes with ZeRO (per-(stage, expert-shard) [S, local] flat
    masters), DP, checkpointing, and both pipeline schedules (the 1F1B
    path carries the aux channel through its custom_vjp).
    """
    config: M.MoEConfig = None

    @classmethod
    def from_size(cls, size: str, num_experts: int = 8,
                  capacity_factor: float = 1.25, aux_weight: float = 0.01,
                  router_top_k: int = 1, num_micro_batches: int = 2,
                  schedule: str = "gpipe",
                  **overrides) -> "GPT2MoEPipelined":
        base = GPT2MoE.from_size(size, num_experts=num_experts,
                                 capacity_factor=capacity_factor,
                                 aux_weight=aux_weight,
                                 router_top_k=router_top_k, **overrides)
        return cls(config=base.config,
                   num_micro_batches=num_micro_batches, schedule=schedule)

    _init_blocks = GPT2MoE._init_blocks
    _block_specs = GPT2MoE._block_specs

    _pipe_stack = GPT2MoE._stack

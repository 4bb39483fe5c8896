"""Looped (weight-shared depth) causal LM with exits and an exit gate.

One stack of identical sandwich blocks (RMSNorm / rotary / SwiGLU,
``transformer.sandwich_block_apply``) is applied ``loop_passes`` times with
the SAME weights.  After every pass a final RMSNorm is applied — its output
is both what the next pass takes in and what that pass's exit reads: an
untied vocabulary head gives the exit's logits, and a learned gate
``lambda_t = sigmoid(h^t . w_gate + b_gate)`` per position turns the passes
into an exit distribution ``p_1 = lambda_1``, ``p_t = lambda_t
prod_{j<t}(1 - lambda_j)``, ``p_last = prod_{j<last}(1 - lambda_j)``.  The
training loss per labelled position is the expected cross-entropy under
that distribution less ``exit_entropy_weight`` times its entropy.

A spec over the shared block functions, not a subclass of ``GPT2``.  The
pass loop is a ``lax.scan`` over passes whose body is the layer scan
(``transformer.scan_layers``), the stacked weights closed over: one block
body is compiled whatever the depth and the pass count.  What that means
for the backward pass:

* a shared weight's gradient is the sum of its four uses.  The layer scan
  hands out one pass's gradient stacked per layer; the pass scan's backward
  loop carries the running sum as a loop carry of the WEIGHTS' dtype — under
  the engine's bf16 policy a bf16 accumulator, rounded after every pass,
  before the engine casts the gradient tree to fp32 (under fp32 an fp32
  one).  tests/test_looped_model.py pins this.
* saved activations are ``loop_passes`` times those of a plain stack of the
  same parameters; the recomputation policy (``remat_policy``, set by the
  engine from ``activation_checkpointing.policy``) applies per layer
  application.
* each exit's head and cross-entropy run under ``jax.checkpoint`` inside
  the pass scan, so one exit's logits are live at a time, forward and
  backward; the pass scan keeps the exit's input (one hidden state a pass).

Engine protocol: ``init_params``, ``partition_specs``, ``batch_specs``,
``zero3_min_dims``, ``validate``, ``apply`` (runs inside ``shard_map`` on
local shards, like ``GPT2``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scalars as obs_scalars
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.parallel.topology import MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48          # layers of the stack one pass runs
    num_heads: int = 16
    head_dim: int = 128
    ffn_size: int = 5632
    loop_passes: int = 4          # = exits
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    #: beta of the training loss: expected CE - beta * H(exit distribution)
    exit_entropy_weight: float = 0.1
    init_std: float = 0.02
    remat: bool = True            # per layer application
    # "full": save each block's input and the residuals of a Pallas kernel
    # (the streaming attention kernel's output and log-sum-exp, where
    # attention_plan chose that kernel); replay everything XLA computes.
    # The other policies: transformer.remat_wrap.
    remat_policy: str = "full"
    sp_impl: str = "ring"         # the only one this block wires
    #: also return, WITH the loss, each exit's mean cross-entropy and mean
    #: exit probability (gradient stopped) as the step scalars
    #: ``loop/exit_ce`` / ``loop/exit_prob`` (observability/scalars.py):
    #: ``train_batch`` returns the loss alone, ``read_step_scalars()`` them
    report_exits: bool = False

    def validate(self, mp_size: int = 1):
        if self.loop_passes < 1:
            raise ValueError(f"loop_passes {self.loop_passes} must be >= 1")
        if self.head_dim % 2:
            raise ValueError(f"rotary needs an even head_dim, got "
                             f"{self.head_dim}")
        for what, size in (("heads", self.num_heads),
                           ("vocab", self.vocab_size),
                           ("FFN width", self.ffn_size)):
            if size % mp_size:
                raise ValueError(
                    f"{what} {size} not divisible by mp {mp_size}")
        if self.sp_impl != "ring":
            raise ValueError(
                f"sequence_parallel_impl {self.sp_impl!r}: the rotary "
                f"block runs ring attention only")


LOOPED_SIZES = {
    "tiny": dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                 head_dim=32, ffn_size=352),
    # the published Ouro widths (48 layers, 4 passes)
    "ouro-2.6b": dict(),
}


def exit_distribution(gate_logits):
    """``[passes, ...]`` gate logits -> ``(p, log p)`` of the exit
    distribution over the leading axis, in log space (no 0 * log 0):
    ``log p_t = log lambda_t + sum_{j<t} log(1 - lambda_j)``, the last exit
    taking all that is left."""
    log_stop = jax.nn.log_sigmoid(gate_logits)
    log_go = jax.nn.log_sigmoid(-gate_logits)
    survived = jnp.cumsum(log_go, axis=0) - log_go    # sum over j < t
    log_p = jnp.concatenate(
        [survived[:-1] + log_stop[:-1], survived[-1:]], axis=0)
    return jnp.exp(log_p), log_p


@dataclasses.dataclass
class LoopedLM:
    """Callable model object satisfying the engine protocol."""
    config: LoopedConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3): the block
    #: subtree is gathered per layer inside the scan — once per PASS — the
    #: rest at apply entry (transformer.zero3_enter)
    zero3_dims: object = None
    @classmethod
    def from_size(cls, size: str, **overrides) -> "LoopedLM":
        return cls(LoopedConfig(**{**LOOPED_SIZES[size], **overrides}))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        """Engine hook: shape checks against the actual degrees (built for
        every sp / pp degree, so only ``mp_size`` is read)."""
        self.config.validate(mp_size)

    def step_counts(self) -> dict:
        """What one forward/backward of this model is made of, for the
        ``model`` telemetry group (per micro-step; the engine multiplies
        layer applications by its accumulation steps)."""
        cfg = self.config
        return {"loop_passes": cfg.loop_passes, "exits": cfg.loop_passes,
                "layer_applications": cfg.loop_passes * cfg.num_layers}

    def step_scalars(self) -> dict:
        """The step scalars ``apply`` returns beside its loss, ``{name:
        size}`` (observability/scalars.py): one value per exit of each, and
        only with ``report_exits``."""
        if not self.config.report_exits:
            return {}
        n = self.config.loop_passes
        return {"loop/exit_ce": n, "loop/exit_prob": n}

    # ------------------------------------------------------------------ init
    def init_params(self, rng):
        cfg = self.config
        cfg.validate()
        k_wte, k_head, k_gate, k_blocks = jax.random.split(rng, 4)
        h = cfg.hidden_size
        normal = lambda k, shape: (
            jax.random.normal(k, shape, jnp.float32) * cfg.init_std)
        return {
            "wte": normal(k_wte, (cfg.vocab_size, h)),
            "blocks": T.init_sandwich_block_params(cfg, k_blocks),
            "normf_s": jnp.ones((h,), jnp.float32),
            # untied output head, held [vocab, hidden] like ``wte`` (the
            # vocabulary-parallel logits helper takes that layout)
            "head": normal(k_head, (cfg.vocab_size, h)),
            "gate_w": normal(k_gate, (h,)),
            "gate_b": jnp.zeros((), jnp.float32),
        }

    def partition_specs(self, params=None):
        return {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel
            "blocks": T.sandwich_block_partition_specs(),
            "normf_s": P(),
            "head": P(MODEL_AXIS, None),  # vocab-parallel
            "gate_w": P(), "gate_b": P(),
        }

    def batch_specs(self, batch):
        return T.token_batch_specs(batch)

    def zero3_min_dims(self, params):
        md = jax.tree_util.tree_map(lambda _: 0, params)
        md["blocks"] = jax.tree_util.tree_map(lambda _: 1, md["blocks"])
        return md

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, labels):
        """tokens, labels: int32 [B, T]; labels < 0 are ignored.  Returns
        the mean training loss over the labelled positions (fp32 scalar,
        local to the DP shard); with ``report_exits`` that loss WITH the
        step scalars ``loop/exit_ce`` and ``loop/exit_prob``, the mean
        cross-entropy and the mean probability of exit 1..n
        (``observability.scalars.WithScalars``; no gradient sees them)."""
        cfg = self.config
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        blocks, z3_dims = params["blocks"], z3_deferred.get("blocks")
        with S.scope("embed"):
            x = L.vocab_parallel_embedding(tokens, params["wte"])
        rope = L.rotary_tables(tokens.shape[1], cfg.head_dim, cfg.rope_theta)

        def layer(carry, lp):
            return T.sandwich_block_apply(carry, lp, cfg, rope), None

        @jax.checkpoint
        def exit_ce(h):
            # replayed in the backward: the logits of one exit at a time
            with S.scope("head"):
                logits = L.vocab_parallel_logits(h, params["head"])
                return L.vocab_parallel_cross_entropy(logits, labels)

        def one_pass(h, _):
            h, _ = T.scan_layers(layer, h, blocks, cfg, z3_dims=z3_dims)
            h = L.rms_norm(h, params["normf_s"], cfg.norm_eps)
            with S.scope("exit"):
                gate = (jnp.einsum("bth,h->bt", h.astype(jnp.float32),
                                   params["gate_w"].astype(jnp.float32))
                        + params["gate_b"].astype(jnp.float32))
            return h, (exit_ce(h), gate)

        with S.scope("loop"):
            _, (ce, gate) = jax.lax.scan(one_pass, x, None,
                                         length=cfg.loop_passes)
        with S.scope("exit"):
            p, log_p = exit_distribution(gate)          # [passes, B, T]
            per_position = jnp.sum(
                p * (ce + cfg.exit_entropy_weight * log_p), axis=0)
            mask = labels >= 0
            loss = L.masked_mean_loss(per_position, mask)
            if not cfg.report_exits:
                return loss
            mean = lambda v: jax.lax.stop_gradient(
                jnp.stack([L.masked_mean_loss(x, mask) for x in v]))
            return obs_scalars.WithScalars(
                loss, {"loop/exit_ce": mean(ce), "loop/exit_prob": mean(p)})

    __call__ = apply

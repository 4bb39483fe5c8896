"""Causal LM with latent attention and dropless expert layers (the
DeepSeek-V3 layer equations, arXiv:2412.19437 section 2.1).

Pre-norm residual layers, ``x += MLA(RMSNorm(x)); x += F(RMSNorm(x))``,
RMSNorm without bias, no bias anywhere, rotary positions on a part of each
query / key head, a final RMSNorm and an UNTIED head.  Every layer's mixer is
``layers.latent_attention``; a layer is of one of two kinds, named by ``F``:

* ``dense``  a SwiGLU MLP of width ``dense_ffn_size``
  (``transformer._gated_mlp``);
* ``moe``    ``moe.dropless_moe_ffn``: ``experts_per_token`` of
  ``num_experts`` routed SwiGLU experts of width ``expert_ffn_size`` (sigmoid
  scores, a selection-only correction bias, gates normalised over the chosen
  and scaled by ``route_scale``) plus ``shared_experts`` shared ones, held as
  one SwiGLU ``shared_experts * expert_ffn_size`` wide.

``experts_held = (first, count)`` is this program's SHARE of every expert
layer: it holds the routed experts ``[first, first + count)`` and computes
their part of the result; the router, the correction bias, the top-k, the
gates and the balance loss are over all ``num_experts``.  ``(0,
num_experts)`` is the whole layer.  Nothing stands in for the experts that
are not held, nor for their exchange.

The stack is a list of *segments* ``(kinds, repeats)`` like ``HybridLM``'s,
each ONE ``transformer.scan_segment``; as published: ``(("dense",), 1),
(("moe",), 26)``.  The loss is the mean next-token cross-entropy plus the
sequence-wise balance loss (``moe.balance_loss``) summed over the expert
layers held.  The correction bias ``router_b`` gets a zero gradient by
construction; the step-boundary update that moves it (DeepSeek-V3 section
2.1.2) is NOT run: the engine has no state a step updates from forward
statistics.

A spec over the shared layer functions, not a subclass of ``GPT2``.  The
head and its cross-entropy run in blocks of ``HEAD_BLOCK_ROWS`` positions
under ``jax.checkpoint`` (``transformer.blocked_cross_entropy``).  Engine
protocol: ``init_params``, ``partition_specs``, ``batch_specs``,
``zero3_min_dims``, ``validate``, ``apply`` (inside ``shard_map`` on local
shards), ``step_counts``, ``step_scalars`` (what the expert layers count on
the device: ``apply`` returns the loss WITH them,
``observability.scalars.WithScalars``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import moe as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scalars as obs_scalars
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.parallel.topology import MODEL_AXIS

KINDS = ("dense", "moe")
#: positions per row per block of the head and its cross-entropy: the fp32
#: logits of one block are live at a time
HEAD_BLOCK_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_heads: int = 16
    latent_rank: int = 512        # the compressed key/value latent
    nope_dim: int = 128           # per head: the part of q / k not rotated
    rope_dim: int = 64            # the rotated part; the key's is shared
    v_dim: int = 128              # the value head
    dense_ffn_size: int = 11264
    expert_ffn_size: int = 1408
    num_experts: int = 64         # routed experts, as published
    experts_per_token: int = 6
    shared_experts: int = 2
    #: (first, count): the routed experts this program holds of each layer
    experts_held: tuple = (0, 64)
    route_scale: float = 2.446
    balance_alpha: float = 0.001
    segments: tuple = ((("dense",), 1), (("moe",), 26))
    rope_theta: float = 800000.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    remat: bool = True            # per layer
    # "full": save each layer's input and the residuals of a Pallas kernel;
    # the other policies: transformer.remat_wrap.
    remat_policy: str = "full"

    @property
    def qk_head_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def shared_ffn_size(self) -> int:
        return self.shared_experts * self.expert_ffn_size

    @property
    def kinds(self) -> tuple:
        """The kind of every layer, in order."""
        return tuple(k for kinds, repeats in self.segments
                     for _ in range(repeats) for k in kinds)

    def validate(self, mp_size: int = 1):
        for kinds, repeats in self.segments:
            if repeats < 1 or not kinds or set(kinds) - set(KINDS):
                raise ValueError(f"segment {(kinds, repeats)!r}: a period "
                                 f"of {KINDS} repeated >= 1 times")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held!r}: (first, count) of the "
                f"{self.num_experts} routed experts")
        if not 1 <= self.experts_per_token <= self.num_experts:
            raise ValueError(
                f"experts_per_token {self.experts_per_token} must be in "
                f"[1, num_experts={self.num_experts}]")
        if self.rope_dim % 2:
            raise ValueError(f"rotary needs an even rope_dim, got "
                             f"{self.rope_dim}")
        for what, size in (("heads", self.num_heads),
                           ("vocab", self.vocab_size),
                           ("dense FFN width", self.dense_ffn_size),
                           ("shared experts' width", self.shared_ffn_size),
                           ("experts held", count)):
            if size % mp_size:
                raise ValueError(
                    f"{what} {size} not divisible by mp {mp_size}")


LATENT_MOE_SIZES = {
    "tiny": dict(vocab_size=512, hidden_size=64, num_heads=2,
                 latent_rank=32, nope_dim=24, rope_dim=8, v_dim=16,
                 dense_ffn_size=160, expert_ffn_size=48, num_experts=16,
                 experts_per_token=3, experts_held=(0, 16),
                 segments=((("dense",), 1), (("moe",), 2))),
}


def init_layer_params(cfg: LatentMoEConfig, kind: str, repeats: int, rng):
    """Stacked ``[repeats, ...]`` parameters of one layer of ``kind``:
    normal ``init_std`` for every matrix (the router's included), norm
    scales at 1, the router's correction bias at 0."""
    h, n = cfg.hidden_size, cfg.num_heads
    keys = iter(jax.random.split(rng, 12))
    normal = lambda *shape: (
        jax.random.normal(next(keys), (repeats, *shape), jnp.float32)
        * cfg.init_std)
    ones = lambda *shape: jnp.ones((repeats, *shape), jnp.float32)
    p = {"norm1_s": ones(h), "norm2_s": ones(h),
         "q_w": normal(h, n * cfg.qk_head_dim),
         "kv_a_w": normal(h, cfg.latent_rank + cfg.rope_dim),
         "kv_norm_s": ones(cfg.latent_rank),
         "kv_b_w": normal(cfg.latent_rank, n * (cfg.nope_dim + cfg.v_dim)),
         "o_w": normal(n * cfg.v_dim, h)}
    ff = cfg.dense_ffn_size if kind == "dense" else cfg.shared_ffn_size
    p.update(gate_w=normal(h, ff), up_w=normal(h, ff), down_w=normal(ff, h))
    if kind == "moe":
        e, f = cfg.experts_held[1], cfg.expert_ffn_size
        p.update(router_w=normal(h, cfg.num_experts),
                 router_b=jnp.zeros((repeats, cfg.num_experts), jnp.float32),
                 exp_gate_w=normal(e, h, f), exp_up_w=normal(e, h, f),
                 exp_down_w=normal(e, f, h))
    return p


def layer_partition_specs(kind: str) -> dict:
    """Megatron sharding of one stacked layer: projections into heads or
    into an FFN column-parallel, out of them row-parallel, the experts held
    split over ``model`` by expert; the latent's down projection, its norm
    and the router replicated.  Leading axis = the segment's repeats."""
    col, row = P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)
    p = {"norm1_s": P(), "norm2_s": P(), "q_w": col, "kv_a_w": P(),
         "kv_norm_s": P(), "kv_b_w": col, "o_w": row,
         "gate_w": col, "up_w": col, "down_w": row}
    if kind == "moe":
        by_expert = P(None, MODEL_AXIS, None, None)
        p.update(router_w=P(), router_b=P(), exp_gate_w=by_expert,
                 exp_up_w=by_expert, exp_down_w=by_expert)
    return p


def layer_apply(kind: str, cfg: LatentMoEConfig, x, p, depth, shared):
    """One layer of ``kind`` on local shards (``transformer.scan_segment``'s
    layer signature; ``depth`` is not read, ``shared`` holds the rotary
    tables).  Returns ``(x, (balance loss, step scalars))``: 0 and none
    from a dense layer, ``moe.held_experts``' counts from an expert layer."""
    eps = cfg.norm_eps
    x = x + L.latent_attention(
        L.rms_norm(x, p["norm1_s"], eps), p, rope=shared["rope"],
        nope_dim=cfg.nope_dim, rope_dim=cfg.rope_dim, v_dim=cfg.v_dim,
        latent=cfg.latent_rank, eps=eps)
    u = L.rms_norm(x, p["norm2_s"], eps)
    if kind == "dense":
        return x + T._gated_mlp(u, p), (jnp.zeros((), jnp.float32), {})
    y, aux, counts = M.dropless_moe_ffn(
        u, p, num_experts=cfg.num_experts, top_k=cfg.experts_per_token,
        held=cfg.experts_held, route_scale=cfg.route_scale,
        balance_alpha=cfg.balance_alpha)
    return x + y, (aux, counts)


def _balance(outs):
    """One period's balance loss of its layers' ``(balance loss, step
    scalars)``."""
    return sum(aux for aux, _ in outs)


def _tally(running, outs):
    """The step scalars so far with one period's layers' in them."""
    return obs_scalars.combine([running] + [counts for _, counts in outs])


@dataclasses.dataclass
class LatentMoELM:
    """Callable model object satisfying the engine protocol."""
    config: LatentMoEConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3): a segment's
    #: scan gathers one period of layers at a time, the rest at apply entry
    zero3_dims: object = None
    #: (prefix, all): the sorted (token, choice) rows an expert layer's
    #: routed part works on when the pairs held fit the prefix, and when
    #: they do not (``moe.prefix_rows``; per shard and micro-batch), of the
    #: program ``apply`` last traced; zeros before any
    routed_rows: tuple = (0, 0)

    @classmethod
    def from_size(cls, size: str, **overrides) -> "LatentMoELM":
        return cls(LatentMoEConfig(**{**LATENT_MOE_SIZES[size],
                                      **overrides}))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        self.config.validate(mp_size)
        if sp_size > 1:
            raise ValueError(
                "LatentMoELM is not built for sequence / context "
                "parallelism: the attention core has a key head wider than "
                "its value head, which neither the ring nor the all-to-all "
                "path takes, and the balance loss is per whole sequence")
        if pp_size > 1:
            raise ValueError(
                "LatentMoELM is not built for pipeline stages: the balance "
                "loss of every expert layer joins the last stage's loss, "
                "and the stages' costs differ by kind of layer")

    def kv_cache_dims(self, mp_size: int = 1):
        raise NotImplementedError(
            "LatentMoELM is not built for serving: its cache needs a latent "
            "kind of page (one compressed key/value latent and one rotary "
            "key per token, shared by all heads), the absorbed decode path "
            "that attends in the latent space, and an expert layer in the "
            "inference engine")

    def step_counts(self) -> dict:
        """What one forward/backward of this model is made of, for the
        ``model`` telemetry group (per micro-step)."""
        cfg, kinds = self.config, self.config.kinds
        return {
            **{f"layers_{k}": kinds.count(k) for k in KINDS},
            "layer_applications": len(kinds),
            "experts_total": cfg.num_experts,
            "experts_held": cfg.experts_held[1],
            "experts_per_token": cfg.experts_per_token,
            "latent_rank": cfg.latent_rank,
            "qk_head_dim": cfg.qk_head_dim,
            "v_head_dim": cfg.v_dim,
            "routed_rows_prefix": self.routed_rows[0],
            "routed_rows_all": self.routed_rows[1],
        }

    def step_scalars(self) -> dict:
        """The step scalars ``apply`` returns beside its loss, ``{name:
        size}`` (observability/scalars.py): the expert layers' counts;
        nothing from a stack without an expert layer."""
        if "moe" not in self.config.kinds:
            return {}
        return {"moe/overflow_passes": 1, "moe/held_pairs": 1,
                "moe/max_expert_rows": 1}

    # ------------------------------------------------------------------ init
    def init_params(self, rng):
        cfg = self.config
        cfg.validate()
        k_wte, k_head, *k_segments = jax.random.split(
            rng, 2 + len(cfg.segments))
        blocks = []
        for (kinds, repeats), key in zip(cfg.segments, k_segments):
            keys = jax.random.split(key, len(kinds))
            blocks.append({f"l{j}": init_layer_params(cfg, kind, repeats, k)
                           for j, (kind, k) in enumerate(zip(kinds, keys))})
        h = cfg.hidden_size
        normal = lambda k: (jax.random.normal(k, (cfg.vocab_size, h),
                                              jnp.float32) * cfg.init_std)
        return {"wte": normal(k_wte), "blocks": blocks,
                "normf_s": jnp.ones((h,), jnp.float32),
                # untied output head, held [vocab, hidden] like ``wte``
                "head": normal(k_head)}

    def partition_specs(self, params=None):
        return {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel
            "blocks": [{f"l{j}": layer_partition_specs(kind)
                        for j, kind in enumerate(kinds)}
                       for kinds, _ in self.config.segments],
            "normf_s": P(),
            "head": P(MODEL_AXIS, None),  # vocab-parallel
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
        the mean per-token LM loss plus the balance loss of every expert
        layer held (fp32 scalar, local to the DP shard) and, from a stack
        with an expert layer, the layers' step scalars with it
        (``WithScalars``: ``moe/overflow_passes`` and ``moe/held_pairs``
        summed, ``moe/max_expert_rows`` the largest, over the layers)."""
        cfg = self.config
        pairs = tokens.size * cfg.experts_per_token
        self.routed_rows = (M.prefix_rows(
            pairs, cfg.experts_held[1] // L.axis_size_or_1(MODEL_AXIS),
            cfg.num_experts), pairs)
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        z3_blocks = z3_deferred.get("blocks") or [None] * len(cfg.segments)
        with S.scope("embed"):
            x = L.vocab_parallel_embedding(tokens, params["wte"])
        shared = {"rope": L.rotary_tables(tokens.shape[1], cfg.rope_dim,
                                          cfg.rope_theta)}
        # the layers' step scalars ride the scans' carry beside x and the
        # depth (int32, like the layers' own: no gradient reads them)
        counts = {name: jnp.zeros((), jnp.int32)
                  for name in self.step_scalars()}
        carry, balance = (x, jnp.zeros((), jnp.int32), counts), 0.0
        for (kinds, _), stacked, z3 in zip(cfg.segments, params["blocks"],
                                           z3_blocks):
            carry, aux = T.scan_segment(
                [functools.partial(layer_apply, kind, cfg) for kind in kinds],
                carry, stacked, cfg, shared=shared, collect=_balance,
                tally=_tally, z3_dims=z3)
            balance = balance + jnp.sum(aux)
        with S.scope("head"):
            x = L.rms_norm(carry[0], params["normf_s"], cfg.norm_eps)
            ce = T.blocked_cross_entropy(x, params["head"], labels,
                                         HEAD_BLOCK_ROWS)
            loss = L.masked_mean_loss(ce, labels >= 0) + balance
        counts = carry[2]
        return obs_scalars.WithScalars(loss, counts) if counts else loss

    __call__ = apply

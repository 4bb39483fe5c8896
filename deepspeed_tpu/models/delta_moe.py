"""Causal LM of Gated DeltaNet and gated-attention layers over dropless
expert layers (the Qwen3-Next layer equations: Gated DeltaNet,
arXiv:2412.06464; ``transformers``' ``Qwen3NextGatedDeltaNet`` /
``Qwen3NextAttention`` / ``Qwen3NextSparseMoeBlock``).

Pre-norm residual layers, ``x += Mixer(N(x)); x += MoE(N(x))``, ``N`` the
ZERO-CENTRED RMSNorm ``x rsqrt(mean x^2 + eps) (1 + w)`` (``w`` starts at
zero), no bias anywhere, a final ``N`` and an UNTIED head.  A layer is of
one of two kinds, named by its mixer:

* ``gdn``   ``layers.gated_delta_net``: a linear-attention mixer whose state
  is a ``key_dim x value_dim`` matrix per value head, written by the gated
  delta rule (``ops/delta_rule.py``, chunked, fp32);
* ``full``  ``layers.gated_attention``: causal softmax attention over shared
  key/value heads with per-head zero-centred norms on q and k, rotary on the
  first ``rotary_dim`` dims of a head and a sigmoid gate on the context.

EVERY layer's second half is ``moe.dropless_moe_ffn``: ``experts_per_token``
of ``num_experts`` routed SwiGLU experts (softmax scores, gates renormalised
over the chosen, the Switch form of the balance loss) plus one shared expert
behind a sigmoid gate of its own.  ``experts_held = (first, count)`` is this
program's SHARE of every expert layer, as ``LatentMoELM``'s: the router, the
top-k, the gates and the balance loss are over all ``num_experts``; nothing
stands in for the experts that are not held, nor for their exchange.

The stack is a list of *segments* ``(kinds, repeats)`` like ``HybridLM``'s,
each ONE ``transformer.scan_segment``; as published: ``(("gdn", "gdn",
"gdn", "full"), 12)``.  The loss is the mean next-token cross-entropy plus
the balance loss summed over the layers held.

A spec over the shared layer functions.  Engine protocol: ``init_params``,
``partition_specs``, ``batch_specs``, ``zero3_min_dims``, ``validate``,
``apply`` (inside ``shard_map`` on local shards), ``step_counts``,
``step_scalars`` (the expert layers' counts: ``apply`` returns the loss WITH
them, ``observability.scalars.WithScalars``).
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
from deepspeed_tpu.ops import delta_rule
from deepspeed_tpu.parallel.topology import MODEL_AXIS

KINDS = ("gdn", "full")
#: positions per row per block of the head and its cross-entropy: the fp32
#: logits of one block are live at a time
HEAD_BLOCK_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class DeltaMoEConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    # the gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64          # the first dims of a head that are rotated
    rope_theta: float = 1e7
    # the Gated DeltaNet mixer
    key_heads: int = 16
    value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    # the expert layer
    expert_ffn_size: int = 512
    shared_ffn_size: int = 512
    num_experts: int = 512        # routed experts, as published
    experts_per_token: int = 10
    #: (first, count): the routed experts this program holds of each layer
    experts_held: tuple = (0, 512)
    balance_alpha: float = 0.001
    segments: tuple = ((("gdn", "gdn", "gdn", "full"), 12),)
    norm_eps: float = 1e-6
    init_std: float = 0.02
    remat: bool = True            # per layer
    # "full": save each layer's input and the residuals of a Pallas kernel;
    # the other policies: transformer.remat_wrap.
    remat_policy: str = "full"

    @property
    def kinds(self) -> tuple:
        """The kind of every layer, in order."""
        return tuple(k for kinds, repeats in self.segments
                     for _ in range(repeats) for k in kinds)

    @property
    def qkv_columns(self) -> int:
        """Columns of a DeltaNet mixer's convolved projection: q and k of
        every key head and v of every value head."""
        return (2 * self.key_heads * self.key_dim
                + self.value_heads * self.value_dim)

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
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim}: an even part "
                             f"of the {self.head_dim}-wide head")
        if (self.num_heads % self.num_kv_heads
                or self.value_heads % self.key_heads):
            raise ValueError(
                f"{self.num_heads} query heads on {self.num_kv_heads} "
                f"key/value heads, {self.value_heads} value heads on "
                f"{self.key_heads} key heads: whole groups")
        for what, size in (("key/value heads", self.num_kv_heads),
                           ("DeltaNet key heads", self.key_heads),
                           ("vocab", self.vocab_size),
                           ("shared expert's width", self.shared_ffn_size),
                           ("experts held", count)):
            if size % mp_size:
                raise ValueError(
                    f"{what} {size} not divisible by mp {mp_size}")


DELTA_MOE_SIZES = {
    "tiny": dict(vocab_size=512, hidden_size=64, num_heads=4,
                 num_kv_heads=2, head_dim=16, rotary_dim=4, key_heads=2,
                 value_heads=4, key_dim=8, value_dim=8, expert_ffn_size=32,
                 shared_ffn_size=32, num_experts=16, experts_per_token=3,
                 experts_held=(0, 16),
                 segments=((("gdn", "gdn", "gdn", "full"), 1),)),
}


def init_layer_params(cfg: DeltaMoEConfig, kind: str, repeats: int, rng):
    """Stacked ``[repeats, ...]`` parameters of one layer of ``kind``, as
    the family's public module sets them: normal ``init_std`` for every
    matrix (the router's and the shared expert's gate included), the
    zero-centred norms' offsets at 0, the DeltaNet output norm's scale at 1,
    ``A_log = log(U(0, 16))``, ``dt_bias`` 1, the convolution uniform in
    +-1/sqrt(kernel) (a depthwise ``Conv1d``'s default)."""
    h = cfg.hidden_size
    keys = iter(jax.random.split(rng, 16))
    normal = lambda *shape: (
        jax.random.normal(next(keys), (repeats, *shape), jnp.float32)
        * cfg.init_std)
    zeros = lambda *shape: jnp.zeros((repeats, *shape), jnp.float32)
    p = {"norm1_s": zeros(h), "norm2_s": zeros(h)}
    if kind == "full":
        n, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        p.update(q_w=normal(h, n * 2 * d), k_w=normal(h, kv * d),
                 v_w=normal(h, kv * d), q_norm_s=zeros(d),
                 k_norm_s=zeros(d), o_w=normal(n * d, h))
    else:
        hv, dv, K = cfg.value_heads, cfg.value_dim, cfg.conv_kernel
        bound = K ** -0.5
        p.update(
            in_qkv_w=normal(h, cfg.qkv_columns), in_z_w=normal(h, hv * dv),
            in_b_w=normal(h, hv), in_a_w=normal(h, hv),
            conv_w=jax.random.uniform(
                next(keys), (repeats, K, cfg.qkv_columns), jnp.float32,
                -bound, bound),
            A_log=jnp.log(jax.random.uniform(
                next(keys), (repeats, hv), jnp.float32, 1e-4, 16.0)),
            dt_bias=jnp.ones((repeats, hv), jnp.float32),
            norm_s=jnp.ones((repeats, dv), jnp.float32),
            out_w=normal(hv * dv, h))
    e, f, fs = cfg.experts_held[1], cfg.expert_ffn_size, cfg.shared_ffn_size
    p.update(router_w=normal(h, cfg.num_experts),
             exp_gate_w=normal(e, h, f), exp_up_w=normal(e, h, f),
             exp_down_w=normal(e, f, h),
             gate_w=normal(h, fs), up_w=normal(h, fs), down_w=normal(fs, h),
             shared_gate_w=normal(h))
    return p


def layer_partition_specs(kind: str) -> dict:
    """Megatron sharding of one stacked layer: projections into heads or
    into an FFN column-parallel, out of them row-parallel, per-head vectors
    with their heads, the experts held split over ``model`` by expert; the
    norms, the router and the shared expert's gate replicated.  Leading axis
    = the segment's repeats."""
    col, row = P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)
    by_expert = P(None, MODEL_AXIS, None, None)
    p = {"norm1_s": P(), "norm2_s": P(), "router_w": P(),
         "exp_gate_w": by_expert, "exp_up_w": by_expert,
         "exp_down_w": by_expert, "gate_w": col, "up_w": col,
         "down_w": row, "shared_gate_w": P()}
    if kind == "full":
        p.update(q_w=col, k_w=col, v_w=col, q_norm_s=P(), k_norm_s=P(),
                 o_w=row)
    else:
        p.update(in_qkv_w=col, in_z_w=col, in_b_w=col, in_a_w=col,
                 conv_w=col, A_log=P(None, MODEL_AXIS),
                 dt_bias=P(None, MODEL_AXIS), norm_s=P(), out_w=row)
    return p


def layer_apply(kind: str, cfg: DeltaMoEConfig, x, p, depth, shared):
    """One layer of ``kind`` on local shards (``transformer.scan_segment``'s
    layer signature; ``depth`` is not read, ``shared`` holds the rotary
    tables).  Returns ``(x, (balance loss, step scalars))``."""
    eps = cfg.norm_eps
    u = L.rms_norm(x, p["norm1_s"], eps, zero_centred=True)
    if kind == "full":
        x = x + L.gated_attention(u, p, rope=shared["rope"],
                                  head_dim=cfg.head_dim, eps=eps)
    else:
        x = x + L.gated_delta_net(u, p, key_dim=cfg.key_dim,
                                  value_dim=cfg.value_dim, eps=eps)
    y, aux, counts = M.dropless_moe_ffn(
        L.rms_norm(x, p["norm2_s"], eps, zero_centred=True), p,
        num_experts=cfg.num_experts, top_k=cfg.experts_per_token,
        held=cfg.experts_held, route_scale=1.0,
        balance_alpha=cfg.balance_alpha, scoring="softmax",
        balance="switch")
    return x + y, (aux, counts)


def _balance(outs):
    """One period's balance loss of its layers' ``(balance loss, step
    scalars)``."""
    return sum(aux for aux, _ in outs)


def _tally(running, outs):
    """The step scalars so far with one period's layers' in them."""
    return obs_scalars.combine([running] + [counts for _, counts in outs])


@dataclasses.dataclass
class DeltaMoELM:
    """Callable model object satisfying the engine protocol."""
    config: DeltaMoEConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3): a segment's
    #: scan gathers one period of layers at a time, the rest at apply entry
    zero3_dims: object = None
    #: (prefix, all): the sorted (token, choice) rows an expert layer's
    #: routed part works on (``moe.prefix_rows``; per shard and
    #: micro-batch), of the program ``apply`` last traced; zeros before any
    routed_rows: tuple = (0, 0)
    #: (steps a chunk, chunks a sequence) of the delta rule in that program
    delta_chunks: tuple = (0, 0)
    #: whether that program's rule walks its chunks in the Pallas kernels
    #: (``delta_rule.kernel_walks``: a TPU and shapes the kernels take)
    delta_kernel: bool = False

    @classmethod
    def from_size(cls, size: str, **overrides) -> "DeltaMoELM":
        return cls(DeltaMoEConfig(**{**DELTA_MOE_SIZES[size], **overrides}))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        self.config.validate(mp_size)
        if sp_size > 1:
            raise ValueError(
                "DeltaMoELM is not built for sequence / context "
                "parallelism: the delta rule's matrix state and the "
                "convolution's last steps would have to pass from one "
                "sequence shard to the next, and nothing hands them over")
        if pp_size > 1:
            raise ValueError(
                "DeltaMoELM is not built for pipeline stages: the balance "
                "loss of every expert layer joins the last stage's loss, "
                "and the stages' costs differ by kind of layer")

    def kv_cache_dims(self, mp_size: int = 1):
        raise NotImplementedError(
            "DeltaMoELM is not built for serving: three layers in four "
            "keep a matrix state and the convolution's last steps in "
            "place of keys and values, a state kind of page (with "
            "snapshots for prefix reuse) the inference engine's cache "
            "manager does not have, beside an expert layer it lacks too")

    def step_counts(self) -> dict:
        """What one forward/backward of this model is made of, for the
        ``model`` telemetry group (per micro-step)."""
        cfg, kinds = self.config, self.config.kinds
        chunk, chunks = self.delta_chunks
        return {
            **{f"layers_{k}": kinds.count(k) for k in KINDS},
            "layers_moe": len(kinds),
            "layer_applications": len(kinds),
            "experts_total": cfg.num_experts,
            "experts_held": cfg.experts_held[1],
            "experts_per_token": cfg.experts_per_token,
            "routed_rows_prefix": self.routed_rows[0],
            "routed_rows_all": self.routed_rows[1],
            "delta_chunk": chunk,
            "delta_chunks_per_sequence": chunks,
            "delta_kernel": int(self.delta_kernel),
            # the fp32 boundary states one sequence's backward keeps a layer
            "delta_state_bytes_per_layer": (
                4 * chunks * cfg.value_heads * cfg.key_dim * cfg.value_dim),
        }

    def step_scalars(self) -> dict:
        """The step scalars ``apply`` returns beside its loss, ``{name:
        size}`` (observability/scalars.py): the expert layers' counts."""
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
                "normf_s": jnp.zeros((h,), jnp.float32),
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
        the mean per-token LM loss plus the balance loss of every layer
        held (fp32 scalar, local to the DP shard) with the expert layers'
        step scalars (``WithScalars``: ``moe/overflow_passes`` and
        ``moe/held_pairs`` summed, ``moe/max_expert_rows`` the largest, over
        the layers)."""
        cfg = self.config
        pairs = tokens.size * cfg.experts_per_token
        self.routed_rows = (M.prefix_rows(
            pairs, cfg.experts_held[1] // L.axis_size_or_1(MODEL_AXIS),
            cfg.num_experts), pairs)
        self.delta_chunks = delta_rule.chunk_layout(tokens.shape[1])
        self.delta_kernel = delta_rule.kernel_walks(
            cfg.key_dim, cfg.value_dim, tokens.shape[1],
            params["wte"].dtype)
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        z3_blocks = z3_deferred.get("blocks") or [None] * len(cfg.segments)
        with S.scope("embed"):
            x = L.vocab_parallel_embedding(tokens, params["wte"])
        shared = {"rope": L.rotary_tables(tokens.shape[1], cfg.rotary_dim,
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
            x = L.rms_norm(carry[0], params["normf_s"], cfg.norm_eps,
                           zero_centred=True)
            ce = T.blocked_cross_entropy(x, params["head"], labels,
                                         HEAD_BLOCK_ROWS)
            loss = L.masked_mean_loss(ce, labels >= 0) + balance
        return obs_scalars.WithScalars(loss, carry[2])

    __call__ = apply

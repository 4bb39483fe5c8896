"""Causal LM of Mamba-2 and position-free attention layers (the Granite-4.0-H
layer equations: ``transformers``' ``GraniteMoeHybridForCausalLM`` with no
experts; Mamba-2, arXiv:2405.21060).

Pre-norm residual layers with SCALED branches,

    x <- x + r Mixer(N1(x))          x <- x + r MLP(N2(x))

``N`` RMSNorm (scales start at 1), ``r`` the ``residual_multiplier``, no
bias anywhere, the MLP a SwiGLU ``(silu(x W_g) * (x W_u)) W_d``.  A layer is
of one of two kinds, named by its mixer:

* ``mamba2``     ``layers.mamba2_mixer``: a selective state-space mixer
  whose state is an ``N x P`` matrix per head with a scalar decay a step,
  ``B`` and ``C`` shared by the heads of a group (``ops/ssd.py``, chunked,
  fp32), a gated RMSNorm after it;
* ``attention``  ``layers.rotary_multihead_attention(rope=None)``: causal
  softmax attention over shared key/value heads with NO positions, its
  scores scaled by ``attention_multiplier`` in place of ``1 / sqrt(d)``.

The table is tied: ``x = embedding_multiplier E[t]`` on the way in, logits
``N_f(x) E^T / logits_scaling`` on the way out (the division is taken on
``N_f(x)``: by a power of two, as published, it is exact in any float
dtype).  The loss is the mean next-token cross-entropy over the rows of the
table held.

The stack is a list of *segments* ``(kinds, repeats)`` like ``HybridLM``'s,
each ONE ``transformer.scan_segment``; as published: ``(("mamba2",) * 5 +
("attention",) + ("mamba2",) * 4, 4)``.  The head and its cross-entropy run
in blocks of ``HEAD_BLOCK_ROWS`` positions.

A spec over the shared layer functions.  Engine protocol: ``init_params``,
``partition_specs``, ``batch_specs``, ``zero3_min_dims``, ``validate``,
``apply`` (inside ``shard_map`` on local shards), ``step_counts``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.ops import ssd
from deepspeed_tpu.parallel.topology import MODEL_AXIS

KINDS = ("mamba2", "attention")
PUBLISHED_SEGMENTS = ((("mamba2",) * 5 + ("attention",) + ("mamba2",) * 4,
                       4),)
#: positions per row per block of the head and its cross-entropy: the fp32
#: logits of one block are live at a time
HEAD_BLOCK_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class Mamba2HybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    ffn_size: int = 8192
    # the attention
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    # the Mamba-2 mixer
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    # the scaled paths
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    segments: tuple = PUBLISHED_SEGMENTS
    norm_eps: float = 1e-5
    init_std: float = 0.02
    remat: bool = True            # per layer
    # "full": save each layer's input and the residuals of a Pallas kernel;
    # the other policies: transformer.remat_wrap.
    remat_policy: str = "full"

    @property
    def ssm_channels(self) -> int:
        """``E``: the channels of x (and of z, y and the gated norm)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """The convolved channels: x, B and C."""
        return self.ssm_channels + 2 * self.ssm_groups * self.ssm_state

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
        if (self.num_heads % self.num_kv_heads
                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                f"{self.num_heads} query heads on {self.num_kv_heads} "
                f"key/value heads, {self.ssm_heads} state-space heads in "
                f"{self.ssm_groups} groups: whole groups")
        if mp_size > 1:
            raise ValueError(
                "Mamba2HybridLM is not built for tensor parallelism: a "
                "group's B and C are read by all of its heads and its gated "
                "norm runs over all of its channels, so a split by heads "
                "needs B and C whole on every shard and the norm's "
                "statistic summed over them")


MAMBA2_HYBRID_SIZES = {
    "tiny": dict(vocab_size=512, hidden_size=64, ffn_size=128, num_heads=4,
                 num_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=16,
                 ssm_state=8, ssm_groups=2,
                 segments=((("mamba2", "mamba2", "attention"), 1),)),
}


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def init_layer_params(cfg: Mamba2HybridConfig, kind: str, repeats: int, rng):
    """Stacked ``[repeats, ...]`` parameters of one layer of ``kind``:
    normal ``init_std`` for every matrix, the norms' scales at 1; the
    Mamba-2 mixer's ``A_log = log(1..H)``, ``D = 1``, ``dt_bias`` the
    inverse softplus of a log-uniform draw in [1e-3, 1e-1] (floored at
    1e-4), the convolution's taps and bias uniform in +-1/sqrt(kernel) (a
    depthwise ``Conv1d``'s default)."""
    h, ff = cfg.hidden_size, cfg.ffn_size
    keys = iter(jax.random.split(rng, 16))
    normal = lambda *shape: (
        jax.random.normal(next(keys), (repeats, *shape), jnp.float32)
        * cfg.init_std)
    ones = lambda *shape: jnp.ones((repeats, *shape), jnp.float32)
    p = {"norm1_s": ones(h), "norm2_s": ones(h), "gate_w": normal(h, ff),
         "up_w": normal(h, ff), "down_w": normal(ff, h)}
    if kind == "attention":
        nd = cfg.num_heads * cfg.head_dim
        kvd = cfg.num_kv_heads * cfg.head_dim
        p.update(q_w=normal(h, nd), k_w=normal(h, kvd), v_w=normal(h, kvd),
                 o_w=normal(nd, h))
    else:
        H, E, K = cfg.ssm_heads, cfg.ssm_channels, cfg.ssm_conv
        uniform = lambda *shape: jax.random.uniform(
            next(keys), (repeats, *shape), jnp.float32, -1.0,
            1.0) / math.sqrt(K)
        dt = jnp.exp(jax.random.uniform(next(keys), (repeats, H))
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        p.update(
            in_w=normal(h, E + cfg.conv_channels + H),
            conv_w=uniform(K, cfg.conv_channels),
            conv_b=uniform(cfg.conv_channels),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                (repeats, H)),
            D=ones(H), dt_bias=_inverse_softplus(jnp.maximum(dt, 1e-4)),
            norm_s=ones(E), out_w=normal(E, h))
    return p


def layer_apply(kind: str, cfg: Mamba2HybridConfig, x, p, depth, shared):
    """One layer of ``kind`` on local shards (``transformer.scan_segment``'s
    layer signature; ``depth`` and ``shared`` are not read).  Returns ``(x,
    None)``."""
    eps, r = cfg.norm_eps, cfg.residual_multiplier
    u = L.rms_norm(x, p["norm1_s"], eps)
    if kind == "attention":
        a = L.rotary_multihead_attention(
            u, p["q_w"], p["k_w"], p["v_w"], p["o_w"], None,
            head_dim=cfg.head_dim, causal=True,
            scale=cfg.attention_multiplier)
    else:
        a = L.mamba2_mixer(u, p, heads=cfg.ssm_heads,
                           head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                           groups=cfg.ssm_groups, eps=eps)
    x = x + r * a
    return x + r * T._gated_mlp(L.rms_norm(x, p["norm2_s"], eps), p), None


@dataclasses.dataclass
class Mamba2HybridLM:
    """Callable model object satisfying the engine protocol."""
    config: Mamba2HybridConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3): a segment's
    #: scan gathers one period of layers at a time, the rest at apply entry
    zero3_dims: object = None
    #: (steps a chunk, chunks a sequence) of the state-space duality in the
    #: program ``apply`` last traced; zeros before any
    ssd_chunks: tuple = (0, 0)

    @classmethod
    def from_size(cls, size: str, **overrides) -> "Mamba2HybridLM":
        return cls(Mamba2HybridConfig(**{**MAMBA2_HYBRID_SIZES[size],
                                         **overrides}))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        self.config.validate(mp_size)
        if sp_size > 1:
            raise ValueError(
                "Mamba2HybridLM is not built for sequence / context "
                "parallelism: the state-space duality's matrix state and "
                "the convolution's last steps would have to pass from one "
                "sequence shard to the next, and nothing hands them over")
        if pp_size > 1:
            raise ValueError(
                "Mamba2HybridLM is not built for pipeline stages: its "
                "segments run inside one program, and the stages' costs "
                "differ by kind of layer")

    def kv_cache_dims(self, mp_size: int = 1):
        raise NotImplementedError(
            "Mamba2HybridLM is not built for serving: nine layers in ten "
            "keep a matrix state and the convolution's last steps in place "
            "of keys and values, a state kind of page the inference "
            "engine's cache manager does not have, and a decode step of "
            "the state-space duality")

    def step_counts(self) -> dict:
        """What one forward/backward of this model is made of, for the
        ``model`` telemetry group (per micro-step)."""
        cfg, kinds = self.config, self.config.kinds
        chunk, chunks = self.ssd_chunks
        return {
            **{f"layers_{k}": kinds.count(k) for k in KINDS},
            "layer_applications": len(kinds),
            "kv_group": cfg.num_heads // cfg.num_kv_heads,
            "ssd_chunk": chunk,
            "ssd_chunks_per_row": chunks,
        }

    # ------------------------------------------------------------------ init
    def init_params(self, rng):
        cfg = self.config
        cfg.validate()
        k_wte, *k_segments = jax.random.split(rng, 1 + len(cfg.segments))
        blocks = []
        for (kinds, repeats), key in zip(cfg.segments, k_segments):
            keys = jax.random.split(key, len(kinds))
            blocks.append({f"l{j}": init_layer_params(cfg, kind, repeats, k)
                           for j, (kind, k) in enumerate(zip(kinds, keys))})
        h = cfg.hidden_size
        return {"wte": jax.random.normal(k_wte, (cfg.vocab_size, h),
                                         jnp.float32) * cfg.init_std,
                "blocks": blocks,
                "normf_s": jnp.ones((h,), jnp.float32)}

    def partition_specs(self, params=None):
        # every layer whole on every shard (``validate`` refuses mp > 1)
        whole = lambda kind: jax.tree_util.tree_map(
            lambda _: P(), jax.eval_shape(functools.partial(
                init_layer_params, self.config, kind, 1),
                jax.random.PRNGKey(0)))
        return {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel, tied head
            "blocks": [{f"l{j}": whole(kind) for j, kind in enumerate(kinds)}
                       for kinds, _ in self.config.segments],
            "normf_s": P(),
        }

    def batch_specs(self, batch):
        return T.token_batch_specs(batch)

    def zero3_min_dims(self, params):
        return T.blocks_zero3_min_dims(params)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, labels):
        """tokens, labels: int32 [B, T]; labels < 0 are ignored.  Returns
        the mean per-token LM loss (fp32 scalar, local to the DP shard)."""
        cfg = self.config
        self.ssd_chunks = ssd.chunk_layout(tokens.shape[1])
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        z3_blocks = z3_deferred.get("blocks") or [None] * len(cfg.segments)
        with S.scope("embed"):
            x = (L.vocab_parallel_embedding(tokens, params["wte"])
                 * cfg.embedding_multiplier)
        carry = (x, jnp.asarray(0, jnp.int32))
        for (kinds, _), stacked, z3 in zip(cfg.segments, params["blocks"],
                                           z3_blocks):
            carry, _ = T.scan_segment(
                [functools.partial(layer_apply, kind, cfg) for kind in kinds],
                carry, stacked, cfg, z3_dims=z3)
        with S.scope("head"):
            x = (L.rms_norm(carry[0], params["normf_s"], cfg.norm_eps)
                 / cfg.logits_scaling)
            ce = T.blocked_cross_entropy(x, params["wte"], labels,
                                         HEAD_BLOCK_ROWS)
            return L.masked_mean_loss(ce, labels >= 0)

    __call__ = apply

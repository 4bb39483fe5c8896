"""Decoder-hybrid-decoder causal LM: Mamba, sliding-window, full and
cross-decoder attention on shared keys and values, Gated Memory Units.

Pre-norm residual layers, ``x += mixer(LN(x)); x += MLP(LN(x))``, LayerNorm
with bias, a bias-free SwiGLU MLP, a token embedding and NO positional
encoding of any kind, a final LayerNorm and a head tied to the embedding.
A layer is of one of five kinds, named by its mixer (``models/layers.py``):

* ``mamba``  ``mamba_mixer``: the Mamba-1 selective state-space mixer;
* ``swa``    ``differential_attention`` under a sliding window;
* ``full``   ``differential_attention`` over the whole sequence;
* ``gmu``    ``gated_memory_unit``: a gate on the *memory* ``m``, the scan
  output (before its gate) of the last ``mamba`` layer of the first half;
* ``cross``  ``shared_kv_attention``: queries of its own on the keys and
  values of the ``full`` layer.

The stack is a list of *segments* ``(kinds, repeats)``: ``repeats`` stacked
copies of a whole period of layers, run by ONE ``transformer.scan_layers``
whose body applies the period's layers in order, each under the configured
recomputation policy (``transformer.remat_wrap``).  As published (32
layers): ``(mamba, swa) x 8``, ``(mamba, full) x 1``, ``(gmu, cross) x 7``.
The segment before the first one that reads (``gmu`` / ``cross``) is the
*source*: it runs once, and its scan hands out ``m`` and the full layer's K
and V, which every later segment's body closes over as loop invariants —
two tensors handed from the first half of the stack to every layer of the
second.  The gradient of K and V is the sum over the layers that read them
plus the full layer's own; ``m``'s is the sum over the GMUs plus what
flows on through the Mamba layer's own gate.

Attention is differential (``layers._differential_context``): a layer's
``lambda_init`` is ``0.8 - 0.6 exp(-0.3 i)`` of its PUBLISHED depth ``i``,
which the scan carries beside the hidden state (``first_layer`` is the
depth of the first layer held, for a cut that keeps layers from the
middle of the stack).

A spec over the shared layer functions, not a subclass of ``GPT2``.  The
head and its cross-entropy run in blocks of ``HEAD_BLOCK_ROWS`` positions
under ``jax.checkpoint`` (fp32 logits of one block live at a time, forward
and backward).  Engine protocol: ``init_params``, ``partition_specs``,
``batch_specs``, ``zero3_min_dims``, ``validate``, ``apply`` (inside
``shard_map`` on local shards), ``step_counts``.
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
from deepspeed_tpu.parallel.topology import MODEL_AXIS

KINDS = ("mamba", "swa", "full", "gmu", "cross")
#: kinds that read what the source segment hands out
READERS = ("gmu", "cross")
PUBLISHED_SEGMENTS = ((("mamba", "swa"), 8), (("mamba", "full"), 1),
                      (("gmu", "cross"), 7))
#: positions per block of the head and its cross-entropy: fp32 logits of one
#: block are live at a time (205 MB at 25,008 rows of vocabulary)
HEAD_BLOCK_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_heads: int = 40           # query heads: 20 differential pairs
    num_kv_heads: int = 20        # key heads; 10 value heads twice as wide
    head_dim: int = 64
    ffn_size: int = 10240
    window: int = 512
    ssm_state: int = 16           # N
    ssm_conv: int = 4
    ssm_expand: int = 2           # E = ssm_expand * hidden_size
    segments: tuple = PUBLISHED_SEGMENTS
    #: published depth of the first layer held (``lambda_init`` reads it)
    first_layer: int = 0
    ln_eps: float = 1e-5
    init_std: float = 0.02
    remat: bool = True            # per layer
    # "full": save each layer's input and the residuals of a Pallas kernel
    # (the streaming attention kernel's output and log-sum-exp); replay
    # everything XLA computes, the state-space scan included.  The other
    # policies: transformer.remat_wrap.
    remat_policy: str = "full"

    @property
    def ssm_channels(self) -> int:
        return self.ssm_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        """Rank of the step-size projection: Mamba's ``ceil(h / 16)``."""
        return -(-self.hidden_size // 16)

    @property
    def kinds(self) -> tuple:
        """The kind of every layer, in order."""
        return tuple(k for kinds, repeats in self.segments
                     for _ in range(repeats) for k in kinds)

    @property
    def source_segment(self):
        """Index of the segment that hands out ``m``, K and V (None where
        no layer reads them)."""
        first = next((i for i, (kinds, _) in enumerate(self.segments)
                      if set(kinds) & set(READERS)), None)
        return None if first is None else first - 1

    def validate(self, mp_size: int = 1):
        for kinds, repeats in self.segments:
            if repeats < 1 or not kinds or set(kinds) - set(KINDS):
                raise ValueError(f"segment {(kinds, repeats)!r}: a period "
                                 f"of {KINDS} repeated >= 1 times")
        src = self.source_segment
        if src is not None:
            reads = {k for kinds, _ in self.segments for k in kinds
                     if k in READERS}
            need = {"gmu": "mamba", "cross": "full"}
            made = self.segments[src][0] if src >= 0 else ()
            missing = [need[k] for k in sorted(reads) if need[k] not in made]
            if src < 0 or self.segments[src][1] != 1 or missing:
                raise ValueError(
                    f"segments {self.segments!r}: the segment before the "
                    f"first gmu / cross layer runs once and holds the "
                    f"layers they read (a mamba layer for a gmu, a full "
                    f"layer for a cross layer)")
            if any(set(kinds) & set(READERS)
                   for kinds, _ in self.segments[:src + 1]):
                raise ValueError("gmu / cross layers come after the "
                                 "layers they read")
        if self.num_heads != 2 * self.num_kv_heads or self.num_heads % 4:
            raise ValueError(
                f"differential attention pairs the heads: {self.num_heads} "
                f"query heads need {self.num_heads // 2} key heads, in "
                f"groups of four query heads")
        for what, size in (("groups of four query heads",
                            self.num_heads // 4),
                           ("vocab", self.vocab_size),
                           ("FFN width", self.ffn_size),
                           ("state-space channels", self.ssm_channels)):
            if size % mp_size:
                raise ValueError(
                    f"{what} {size} not divisible by mp {mp_size}")


HYBRID_SIZES = {
    "tiny": dict(vocab_size=512, hidden_size=64, num_heads=8, num_kv_heads=4,
                 head_dim=16, ffn_size=128, window=8, ssm_state=4,
                 segments=((("mamba", "swa"), 2), (("mamba", "full"), 1),
                           (("gmu", "cross"), 2))),
    # the published Phi-4-mini-flash-reasoning widths and layout (32 layers)
    "phi4-mini-flash": dict(),
}


def lambda_init(depth):
    """``0.8 - 0.6 exp(-0.3 i)`` at published depth ``i`` (fp32 scalar)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth.astype(jnp.float32))


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def init_layer_params(cfg: HybridConfig, kind: str, repeats: int, rng):
    """Stacked ``[repeats, ...]`` parameters of one layer of ``kind``:
    normal ``init_std`` for every matrix, norms at 1 / 0, and the mixer's
    own: Mamba's ``A_log = log(1..N)``, ``D = 1``, the step-size bias the
    inverse softplus of log-uniform [1e-3, 1e-1], convolution taps uniform
    within ``1/sqrt(K)``; attention's ``lambda`` vectors normal 0.1."""
    h, ff, d = cfg.hidden_size, cfg.ffn_size, cfg.head_dim
    E, N, R, K = cfg.ssm_channels, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    keys = iter(jax.random.split(rng, 16))
    normal = lambda shape, std=cfg.init_std: (
        jax.random.normal(next(keys), (repeats, *shape), jnp.float32) * std)
    ones = lambda *shape: jnp.ones((repeats, *shape), jnp.float32)
    zeros = lambda *shape: jnp.zeros((repeats, *shape), jnp.float32)
    p = {"ln1_s": ones(h), "ln1_b": zeros(h),
         "ln2_s": ones(h), "ln2_b": zeros(h),
         "gate_w": normal((h, ff)), "up_w": normal((h, ff)),
         "down_w": normal((ff, h))}
    if kind == "mamba":
        dt = jnp.exp(jax.random.uniform(next(keys), (repeats, E))
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        p.update(
            in_u_w=normal((h, E)), in_z_w=normal((h, E)),
            conv_w=jax.random.uniform(next(keys), (repeats, K, E),
                                      jnp.float32, -1.0, 1.0) / math.sqrt(K),
            conv_b=zeros(E), x_w=normal((E, R + 2 * N)),
            dt_w=normal((R, E)), dt_b=_inverse_softplus(dt),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (repeats, E, N)),
            D=ones(E), out_w=normal((E, h)))
    elif kind == "gmu":
        p.update(w1=normal((h, E)), w2=normal((E, h)))
    else:
        nq, nk = cfg.num_heads * d, cfg.num_kv_heads * d
        p.update(q_w=normal((h, nq)), o_w=normal((nq, h)),
                 subln_s=ones(2 * d),
                 **{name: normal((d,), 0.1) for name in
                    ("lam_q1", "lam_k1", "lam_q2", "lam_k2")})
        if kind != "cross":
            p.update(k_w=normal((h, nk)), v_w=normal((h, nk)))
    return p


def layer_partition_specs(kind: str) -> dict:
    """Megatron sharding of one stacked layer: projections into heads or
    state-space channels column-parallel, projections out of them
    row-parallel, per-channel vectors with their channels, the rest
    replicated.  Leading axis = the segment's repeats."""
    col, row = P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)
    vec = P(None, MODEL_AXIS)
    p = {"ln1_s": P(), "ln1_b": P(), "ln2_s": P(), "ln2_b": P(),
         "gate_w": col, "up_w": col, "down_w": row}
    if kind == "mamba":
        p.update(in_u_w=col, in_z_w=col, conv_w=col, conv_b=vec, x_w=row,
                 dt_w=col, dt_b=vec, A_log=row, D=vec, out_w=row)
    elif kind == "gmu":
        p.update(w1=col, w2=row)
    else:
        p.update(q_w=col, o_w=row, subln_s=P(), lam_q1=P(), lam_k1=P(),
                 lam_q2=P(), lam_k2=P())
        if kind != "cross":
            p.update(k_w=col, v_w=col)
    return p


def layer_apply(kind: str, cfg: HybridConfig, x, p, depth, shared):
    """One layer of ``kind`` on local shards: ``x`` [B, T, h], ``p`` its
    parameters (no leading axis), ``depth`` its published depth, ``shared``
    ``{"m", "k", "v"}`` for the kinds that read them.  Returns ``(x,
    made)``, ``made`` the tensors this layer could hand on (a Mamba
    layer's ``m``, an attention layer's ``k`` and ``v``)."""
    u = L.layer_norm(x, p["ln1_s"], p["ln1_b"], cfg.ln_eps)
    attn = dict(head_dim=cfg.head_dim, lam_init=lambda_init(depth),
                eps=cfg.ln_eps)
    made = {}
    if kind == "mamba":
        a, made["m"] = L.mamba_mixer(u, p, state=cfg.ssm_state,
                                     dt_rank=cfg.dt_rank)
    elif kind == "gmu":
        a = L.gated_memory_unit(u, shared["m"], p)
    elif kind == "cross":
        a = L.shared_kv_attention(u, p, shared["k"], shared["v"], **attn)
    else:
        a, made["k"], made["v"] = L.differential_attention(
            u, p, window=cfg.window if kind == "swa" else None, **attn)
    x = x + a
    x = x + T._gated_mlp(L.layer_norm(x, p["ln2_s"], p["ln2_b"], cfg.ln_eps),
                         p)
    return x, made


@dataclasses.dataclass
class HybridLM:
    """Callable model object satisfying the engine protocol."""
    config: HybridConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3): a segment's
    #: scan gathers one period of layers at a time, the rest at apply entry
    zero3_dims: object = None

    @classmethod
    def from_size(cls, size: str, **overrides) -> "HybridLM":
        return cls(HybridConfig(**{**HYBRID_SIZES[size], **overrides}))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        self.config.validate(mp_size)
        if sp_size > 1:
            raise ValueError(
                "HybridLM is not built for sequence / context parallelism: "
                "a state-space scan across sequence shards needs its state "
                "passed from shard to shard, a window and the shared keys "
                "and values need their neighbours'")
        if pp_size > 1:
            raise ValueError(
                "HybridLM is not built for pipeline stages: the memory and "
                "the shared keys and values would cross every stage "
                "boundary after their source, and the stages' costs differ "
                "by kind of layer")

    def kv_cache_dims(self, mp_size: int = 1):
        raise NotImplementedError(
            "HybridLM is not built for serving: its cache needs a window "
            "kind and a state kind of page, one K/V copy shared by the "
            "cross-decoder layers, and decode steps for the state-space "
            "scan and the Gated Memory Unit")

    def step_counts(self) -> dict:
        """What one forward/backward of this model is made of, for the
        ``model`` telemetry group (per micro-step)."""
        cfg, kinds = self.config, self.config.kinds
        return {
            **{f"layers_{k}": kinds.count(k) for k in KINDS},
            "layer_applications": len(kinds),
            "attention_window": cfg.window,
            "kv_group": cfg.num_heads // cfg.num_kv_heads,
            # the fp32 recurrent state one row carries through a Mamba layer
            "ssm_state_bytes_per_row": 4 * cfg.ssm_channels * cfg.ssm_state,
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
        return {
            "wte": jax.random.normal(k_wte, (cfg.vocab_size, h), jnp.float32)
            * cfg.init_std,
            "blocks": blocks,
            "lnf_s": jnp.ones((h,), jnp.float32),
            "lnf_b": jnp.zeros((h,), jnp.float32),
        }

    def partition_specs(self, params=None):
        return {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel, tied head
            "blocks": [{f"l{j}": layer_partition_specs(kind)
                        for j, kind in enumerate(kinds)}
                       for kinds, _ in self.config.segments],
            "lnf_s": P(), "lnf_b": P(),
        }

    def batch_specs(self, batch):
        return T.token_batch_specs(batch)

    def zero3_min_dims(self, params):
        md = jax.tree_util.tree_map(lambda _: 0, params)
        md["blocks"] = jax.tree_util.tree_map(lambda _: 1, md["blocks"])
        return md

    # --------------------------------------------------------------- forward
    def _segment(self, index, carry, stacked, shared, z3_dims):
        """One segment (``transformer.scan_segment``).  Returns ``(carry,
        made)``; ``made`` is what the source segment hands out (None from
        any other)."""
        cfg = self.config
        kinds, _ = cfg.segments[index]
        # the source segment hands out what its layers made: the LAST layer
        # of a kind wins
        merge = lambda outs: {name: t for out in outs
                              for name, t in out.items()}
        return T.scan_segment(
            [functools.partial(layer_apply, kind, cfg) for kind in kinds],
            carry, stacked, cfg, shared=shared, z3_dims=z3_dims,
            collect=merge if index == cfg.source_segment else None)

    def apply(self, params, tokens, labels):
        """tokens, labels: int32 [B, T]; labels < 0 are ignored.  Returns
        the mean per-token LM loss (fp32 scalar, local to the DP shard)."""
        cfg = self.config
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        z3_blocks = z3_deferred.get("blocks") or [None] * len(cfg.segments)
        with S.scope("embed"):
            x = L.vocab_parallel_embedding(tokens, params["wte"])
        carry = (x, jnp.asarray(cfg.first_layer, jnp.int32))
        shared = None
        for i, stacked in enumerate(params["blocks"]):
            carry, made = self._segment(i, carry, stacked, shared,
                                        z3_blocks[i])
            if made is not None:
                # the source segment runs once: its scan stacked one copy
                # of each tensor (a slice of an axis of length one: the
                # hand-over costs no instruction)
                shared = {name: t[0] for name, t in made.items()}
        with S.scope("head"):
            x = L.layer_norm(carry[0], params["lnf_s"], params["lnf_b"],
                             cfg.ln_eps)
            loss = T.blocked_cross_entropy(x, params["wte"], labels,
                                           HEAD_BLOCK_ROWS)
            return L.masked_mean_loss(loss, labels >= 0)

    __call__ = apply

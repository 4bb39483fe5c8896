"""Shared tensor-parallel transformer stack (GPT-2 and BERT build on this).

The reference proves its engine against Megatron-LM GPT-2 and BingBert
(/root/reference/tests/model/Megatron_GPT2/ds_gpt2_test.sh,
tests/model/BingBertSquad/) but outsources the model code.  On TPU we own the
model: blocks are written against the local-shard view used inside
``shard_map`` (see models/layers.py), layers are STACKED on a leading axis and
iterated with ``lax.scan`` so XLA compiles one block body regardless of depth,
and per-block rematerialisation (``jax.checkpoint``) stands in for Megatron's
``--checkpoint-activations``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import zero3 as Z
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.ops.remat_names import (
    FFN1, FULL_SAVES, POST_LN_SUM, SELECTIVE_SAVES)
from deepspeed_tpu.parallel.topology import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def token_batch_specs(batch):
    """Batch shardings for the standard token-aligned LM batch: every >=2-D
    leaf is ``[B, T, ...]`` with dim 1 the sequence (tokens, labels,
    attention masks) and shards ``P('data', 'seq')``; 1-D leaves are
    per-example and shard ``P('data')``.  The engine REQUIRES models to
    declare batch shardings under context parallelism (it will not guess
    which dims are sequences); this is the declaration every [B, T] LM in
    the built-in family uses.  All mesh axes always exist (topology
    make_mesh), so the specs are valid at any parallel degree."""
    import numpy as _np

    def spec(leaf):
        nd = getattr(leaf, "ndim", None)
        if nd is None:
            nd = _np.asarray(leaf).ndim
        if nd >= 2:
            return P(DATA_AXIS, SEQ_AXIS)
        return P(DATA_AXIS) if nd >= 1 else P()

    return jax.tree_util.tree_map(spec, batch)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304
    max_seq_len: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    #: FFN width where it is no whole multiple of the hidden size; None:
    #: ``mlp_ratio * hidden_size`` (see ``ffn_width``)
    ffn_size: Optional[int] = None
    pre_ln: bool = True           # GPT-2 pre-LN; BERT uses post-LN
    causal: bool = True
    remat: bool = True            # per-block activation checkpointing
    # sequence-parallel attention strategy under context_parallel_size>1:
    # "ring" (K/V rotation) or "ulysses" (head<->seq all-to-all); the
    # engine's sequence_parallel_impl JSON key overrides this field
    sp_impl: str = "ring"
    # "full": save each block's input and the residuals of a Pallas kernel
    # (the streaming attention kernel's output and log-sum-exp, where
    # attention_plan chose that kernel); replay everything XLA computes
    # (max memory savings, ~33% extra FLOPs).
    # "dots": save matmul outputs, recompute only cheap
    # elementwise/softmax/LN — the usual TPU sweet spot when HBM allows.
    # "selective": save the named residuals of ops/remat_names.py (what
    # costs a matmul or a kernel call to replay), a fraction of "dots"' bytes.
    remat_policy: str = "full"
    init_std: float = 0.02
    ln_eps: float = 1e-5

    @property
    def ffn_width(self) -> int:
        return (self.ffn_size if self.ffn_size is not None
                else self.mlp_ratio * self.hidden_size)

    def validate(self, mp_size: int = 1):
        h, n = self.hidden_size, self.num_heads
        if h % n:
            raise ValueError(f"hidden {h} not divisible by heads {n}")
        if n % mp_size:
            raise ValueError(f"heads {n} not divisible by mp {mp_size}")
        if self.vocab_size % mp_size:
            raise ValueError(
                f"vocab {self.vocab_size} not divisible by mp {mp_size}")
        if self.ffn_width % mp_size:
            raise ValueError(
                f"FFN width {self.ffn_width} not divisible by mp {mp_size}")


def init_block_params(cfg: TransformerConfig, rng) -> dict:
    """Stacked [L, ...] block parameters, GPT-2 style init (normal 0.02;
    residual projections scaled by 1/sqrt(2L))."""
    Lyr, h = cfg.num_layers, cfg.hidden_size
    ff = cfg.ffn_width
    ks = jax.random.split(rng, 4)
    std = cfg.init_std
    resid_std = std / jnp.sqrt(2.0 * Lyr)
    norm = lambda k, shape, s: (jax.random.normal(k, shape, jnp.float32) * s)
    return {
        "ln1_s": jnp.ones((Lyr, h), jnp.float32),
        "ln1_b": jnp.zeros((Lyr, h), jnp.float32),
        # packed head-major (n, 3, d) on the out dim — see layers.py
        "qkv_w": norm(ks[0], (Lyr, h, 3 * h), std),
        "qkv_b": jnp.zeros((Lyr, 3 * h), jnp.float32),
        "proj_w": norm(ks[1], (Lyr, h, h), resid_std),
        "proj_b": jnp.zeros((Lyr, h), jnp.float32),
        "ln2_s": jnp.ones((Lyr, h), jnp.float32),
        "ln2_b": jnp.zeros((Lyr, h), jnp.float32),
        "fc_w": norm(ks[2], (Lyr, h, ff), std),
        "fc_b": jnp.zeros((Lyr, ff), jnp.float32),
        "fc2_w": norm(ks[3], (Lyr, ff, h), resid_std),
        "fc2_b": jnp.zeros((Lyr, h), jnp.float32),
    }


def block_partition_specs() -> dict:
    """Megatron sharding: QKV + MLP-in column-parallel (out dim over
    ``model``), attention-out + MLP-out row-parallel (in dim over ``model``);
    LayerNorms and row-parallel biases replicated.  Leading axis = layer
    stack."""
    return {
        "ln1_s": P(), "ln1_b": P(),
        "qkv_w": P(None, None, MODEL_AXIS), "qkv_b": P(None, MODEL_AXIS),
        "proj_w": P(None, MODEL_AXIS, None), "proj_b": P(),
        "ln2_s": P(), "ln2_b": P(),
        "fc_w": P(None, None, MODEL_AXIS), "fc_b": P(None, MODEL_AXIS),
        "fc2_w": P(None, MODEL_AXIS, None), "fc2_b": P(),
    }


@S.scoped("ffn")
def _mlp(x, p):
    y = L.column_parallel_linear(x, p["fc_w"], p["fc_b"])
    # named for the "selective" remat policy: saving the pre-GELU ffn lets
    # backward recompute the elementwise GELU without the fc matmul
    y = checkpoint_name(y, FFN1)
    y = L.gelu(y)
    return L.row_parallel_linear(y, p["fc2_w"], p["fc2_b"])


def block_with_ffn(x, p, cfg: TransformerConfig, attn_mask=None, ffn=None):
    """One transformer block on local shards with a pluggable FFN.

    ``ffn(u, p) -> (delta, aux)`` replaces the dense MLP (MoE plugs in
    here, models/moe.py); default is the dense MLP with aux 0.  p leaves
    have NO leading layer axis (scan slices it off).  Returns (x, aux)."""
    # a plug-in FFN (MoE) runs under the same device scope as the dense MLP
    f = (S.scoped("ffn")(ffn) if ffn is not None
         else (lambda u, pp: (_mlp(u, pp), 0.0)))
    attn = lambda u: L.multihead_attention(
        u, p["qkv_w"], p["qkv_b"], p["proj_w"], p["proj_b"],
        n_heads_global=cfg.num_heads, causal=cfg.causal,
        attn_mask=attn_mask, sp_impl=cfg.sp_impl)
    ln1 = lambda u: L.layer_norm(u, p["ln1_s"], p["ln1_b"], cfg.ln_eps)
    ln2 = lambda u: L.layer_norm(u, p["ln2_s"], p["ln2_b"], cfg.ln_eps)
    if cfg.pre_ln:
        x = x + attn(ln1(x))
        delta, aux = f(ln2(x), p)
        x = x + delta
    else:  # post-LN (BERT)
        # a LayerNorm's backward needs its input, here the output of the
        # proj / fc2 matmul plus the residual: named, so the "selective"
        # replay stops at the sum and re-derives only the statistics.  In
        # the pre-LN branch the same sums are the carried state, which the
        # layer scan keeps anyway.
        x = ln1(checkpoint_name(x + attn(x), POST_LN_SUM))
        delta, aux = f(x, p)
        x = ln2(checkpoint_name(x + delta, POST_LN_SUM))
    return x, aux


def block_apply(x, p, cfg: TransformerConfig, attn_mask=None):
    """One dense transformer block on local shards."""
    x, _ = block_with_ffn(x, p, cfg, attn_mask)
    return x


# ------------------------------------------------------ sandwich block
# RMSNorm / rotary / SwiGLU block with a norm before AND after each
# sub-layer, inside the residual (models/looped.py runs it).  ``cfg`` is any
# config with ``hidden_size``, ``num_layers``, ``num_heads``, ``head_dim``,
# ``ffn_size``, ``norm_eps``, ``init_std`` and the ``remat`` fields
# ``remat_wrap`` reads.

def init_sandwich_block_params(cfg, rng) -> dict:
    """Stacked [L, ...] parameters of the sandwich block: four norm scales,
    separate bias-free q/k/v/o projections (heads contiguous), and the gated
    FFN's gate, up and down matrices.  Normal ``init_std`` for every matrix:
    the norm after each sub-layer rescales its output, so the 1/sqrt(2L) of
    ``init_block_params`` on the residual projections would shrink nothing
    but the weights themselves — and make them the ones an optimizer's
    first steps swamp (PERF.md, PR 26)."""
    Lyr, h, ff = cfg.num_layers, cfg.hidden_size, cfg.ffn_size
    nd = cfg.num_heads * cfg.head_dim
    ks = jax.random.split(rng, 7)
    norm = lambda k, shape: (
        jax.random.normal(k, shape, jnp.float32) * cfg.init_std)
    ones = lambda: jnp.ones((Lyr, h), jnp.float32)
    return {
        "norm1_s": ones(), "norm2_s": ones(),
        "norm3_s": ones(), "norm4_s": ones(),
        "q_w": norm(ks[0], (Lyr, h, nd)),
        "k_w": norm(ks[1], (Lyr, h, nd)),
        "v_w": norm(ks[2], (Lyr, h, nd)),
        "o_w": norm(ks[3], (Lyr, nd, h)),
        "gate_w": norm(ks[4], (Lyr, h, ff)),
        "up_w": norm(ks[5], (Lyr, h, ff)),
        "down_w": norm(ks[6], (Lyr, ff, h)),
    }


def sandwich_block_partition_specs() -> dict:
    """Megatron sharding of the sandwich block: q/k/v/gate/up
    column-parallel, o/down row-parallel, norm scales replicated."""
    col, row = P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)
    return {
        "norm1_s": P(), "norm2_s": P(), "norm3_s": P(), "norm4_s": P(),
        "q_w": col, "k_w": col, "v_w": col, "o_w": row,
        "gate_w": col, "up_w": col, "down_w": row,
    }


@S.scoped("ffn")
def _gated_mlp(x, p):
    """SwiGLU: ``(silu(x Wg) * (x Wu)) Wd``, no biases."""
    # named like ``_mlp``'s pre-activation for the "selective" policy
    g = checkpoint_name(L.column_parallel_linear(x, p["gate_w"]), FFN1)
    u = checkpoint_name(L.column_parallel_linear(x, p["up_w"]), FFN1)
    return L.row_parallel_linear(L.silu(g) * u, p["down_w"])


def sandwich_block_apply(x, p, cfg, rope, attn_mask=None):
    """``a = x + norm2(attn(norm1(x)))``, ``a + norm4(ffn(norm3(a)))`` on
    local shards; ``rope`` = ``layers.rotary_tables`` of this shard."""
    eps = cfg.norm_eps
    a = L.rotary_multihead_attention(
        L.rms_norm(x, p["norm1_s"], eps), p["q_w"], p["k_w"], p["v_w"],
        p["o_w"], rope, head_dim=cfg.head_dim, causal=True,
        attn_mask=attn_mask)
    x = x + L.rms_norm(a, p["norm2_s"], eps)
    f = _gated_mlp(L.rms_norm(x, p["norm3_s"], eps), p)
    return x + L.rms_norm(f, p["norm4_s"], eps)


def remat_wrap(body, cfg):
    """Apply the configured per-block rematerialisation policy to a scan
    body (shared by the dense and MoE stacks)."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    if cfg.remat_policy == "selective":
        # keep what costs a matmul or a kernel call to replay and is no
        # larger than the FFN's hidden state (ops/remat_names.py lists the
        # names and their bytes; layers / _mlp / moe_ffn / block_with_ffn /
        # the streaming kernel tag them).  Backward replays elementwise ops,
        # norms, layout copies and, on the XLA attention path, the score
        # einsums and softmax; in a pre-LN block also the proj matmul.
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SELECTIVE_SAVES))
    if cfg.remat_policy == "full":
        # save each block's input and the residuals of a Pallas kernel (the
        # streaming attention kernel's output and log-sum-exp, where
        # attention_plan chose that kernel); replay everything XLA computes.
        # A program on the XLA attention plan has neither name, and saves
        # the input alone.
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                *FULL_SAVES))
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r} "
        "(expected 'full', 'dots' or 'selective')")


def zero3_enter(params, dims, deferred=("blocks",)):
    """ZeRO-3 entry gather (runs inside shard_map, zero3.py design).

    Gathers every partitioned NON-deferred leaf to its model-local shape
    now; ``deferred`` subtrees (the block stacks) stay partitioned — their
    scan body gathers one layer at a time, which is the whole point: peak
    weight memory is one layer, not the model.  Returns ``(params,
    deferred_dims)`` where ``deferred_dims[key]`` indexes the STACKED
    leaves (callers shift by -1 inside the scan).  No-op when ``dims`` is
    None (stage < 3)."""
    if dims is None:
        return params, {}
    masked = {}
    deferred_dims = {}
    for key, sub in dims.items():
        if key in deferred:
            deferred_dims[key] = sub
            masked[key] = jax.tree_util.tree_map(
                lambda _: Z.REPLICATED, sub)
        else:
            masked[key] = sub
    return Z.gather_tree(params, masked), deferred_dims


def zero3_wrap_body(body, z3_dims):
    """Wrap a scan body so each layer's partitioned weights are gathered
    right before use (``z3_dims`` indexes the STACKED leaves; the layer
    axis is already sliced off, hence the -1 shift).  Under remat the
    gather replays in the backward; its autodiff transpose delivers the
    grads reduce-scattered."""
    if z3_dims is None or not Z.partitioned_any(z3_dims):
        return body
    body_dims = Z.shift_dims(z3_dims, -1)

    def wrapped(carry, lp):
        return body(carry, Z.gather_tree(lp, body_dims))

    return wrapped


@S.scoped("block")
def scan_layers(body, carry, stacked_params, cfg, z3_dims=None):
    """``lax.scan`` of ``body(carry, layer_params) -> (carry, y)`` over the
    stacked [L, ...] layers, under ``cfg``'s recomputation policy; where
    ``z3_dims`` marks partitioned leaves (ZeRO-3) the body first gathers its
    layer (``zero3_wrap_body``).  Shared by the dense, MoE and looped stacks.
    The whole scan runs under the ``dstpu/block`` device scope, so the
    per-layer slicing of the stacked parameters and saved activations,
    forward and backward, counts with the blocks it feeds."""
    return jax.lax.scan(remat_wrap(zero3_wrap_body(body, z3_dims), cfg),
                        carry, stacked_params)


def scan_segment(layers, carry, stacked, cfg, *, shared=None, collect=None,
                 tally=None, z3_dims=None):
    """One segment ``(kinds, repeats)`` of a stack made of segments:
    ``repeats`` stacked copies of a whole period of layers run by ONE
    ``scan_layers`` whose body applies the period's layers in order, each
    under ``cfg``'s recomputation policy on its own (no second wrap round
    the period, which would replay every layer twice).

    ``layers[j](x, p, depth, shared) -> (x, out)`` is the period's ``j``-th
    layer; ``stacked[f"l{j}"]`` holds its parameters ``[repeats, ...]``;
    ``carry`` = ``(x, depth)``, ``depth`` the published depth of the next
    layer (an int32 scalar: a layer whose equations read its depth gets it
    here); ``shared``: what every layer may read beside its own parameters,
    closed over as loop invariants.  Returns ``(carry, collected)``:
    ``collect(outs)`` of each period's list of second results, stacked
    ``[repeats, ...]``; None without ``collect``.  With ``tally``, ``carry``
    holds a third element, a running value no gradient reads (a model's
    step scalars), and each period leaves ``tally(running, outs)`` there:
    carried, not stacked — a stacked output costs the loop a write and two
    small copies an iteration on a TPU, a carried scalar an add.  Shared by
    the hybrid and the latent-attention / expert stacks."""
    wrapped = [remat_wrap(layer, cfg) for layer in layers]

    def period(carry, lp):
        x, depth, *running = carry
        outs = []
        for j, layer in enumerate(wrapped):
            x, out = layer(x, lp[f"l{j}"], depth + j, shared)
            outs.append(out)
        if tally:
            running = [tally(running[0], outs)]
        return ((x, depth + len(wrapped), *running),
                (collect(outs) if collect else None))

    return scan_layers(period, carry, stacked,
                       dataclasses.replace(cfg, remat=False),
                       z3_dims=z3_dims)


def blocked_cross_entropy(x, table, labels, block_rows):
    """Per-position cross-entropy ``[B, T]`` of the vocabulary projection
    ``x @ table.T`` (``table`` ``[vocab / mp, h]``, vocabulary-parallel: a
    tied embedding or a head of its own), in blocks of ``block_rows``
    positions under ``jax.checkpoint``: the fp32 logits of one block are
    live at a time, forward and backward.  One block where ``T`` is no
    whole multiple of ``block_rows``."""
    B, T_len, h = x.shape

    @jax.checkpoint
    def block(xb, lb):
        return L.vocab_parallel_cross_entropy(
            L.vocab_parallel_logits(xb, table), lb)

    if T_len <= block_rows or T_len % block_rows:
        return block(x, labels)
    n = T_len // block_rows
    _, ce = jax.lax.scan(
        lambda _, b: (None, block(*b)), None,
        (jnp.moveaxis(x.reshape(B, n, block_rows, h), 1, 0),
         jnp.moveaxis(labels.reshape(B, n, block_rows), 1, 0)))
    return jnp.moveaxis(ce, 0, 1).reshape(B, T_len)


# ------------------------------------------------------------- serving
# KV-cached prefill/decode blocks (deepspeed_tpu/inference/).  The block
# math is the training block's (same LayerNorm/GELU/projection helpers,
# same ``core_attention`` in prefill) so incremental decode stays within
# dtype tolerance of a full-context re-forward — the exactness oracle in
# tests/test_inference.py depends on this sharing, not on luck.

def block_decode(x, p, cfg: TransformerConfig, k_pool, v_pool, pos,
                 rows, write_rows, ring: bool = False):
    """One dense block on a single-token slice x [B, 1, h] against this
    layer's KV page pool ([R, n_local, d] flat rows, read through the
    ``rows`` page-table map); returns ``(x, k_pool', v_pool')``."""
    attn = lambda u: L.decode_multihead_attention(
        u, p["qkv_w"], p["qkv_b"], p["proj_w"], p["proj_b"],
        k_pool, v_pool, pos, rows, write_rows,
        n_heads_global=cfg.num_heads, ring=ring)
    ln1 = lambda u: L.layer_norm(u, p["ln1_s"], p["ln1_b"], cfg.ln_eps)
    ln2 = lambda u: L.layer_norm(u, p["ln2_s"], p["ln2_b"], cfg.ln_eps)
    if cfg.pre_ln:
        a, kc, vc = attn(ln1(x))
        x = x + a
        x = x + _mlp(ln2(x), p)
    else:
        a, kc, vc = attn(x)
        x = ln1(x + a)
        x = ln2(x + _mlp(x, p))
    return x, kc, vc


def block_extend(x, p, cfg: TransformerConfig, k_pool, v_pool, rows,
                 start, n_new):
    """One dense block on a BLOCK of new tokens x [B, E, h] against this
    layer's KV page pool — the prefill / tail-prefill / verify body
    (layers.extend_multihead_attention)."""
    attn = lambda u: L.extend_multihead_attention(
        u, p["qkv_w"], p["qkv_b"], p["proj_w"], p["proj_b"],
        k_pool, v_pool, rows, start, n_new,
        n_heads_global=cfg.num_heads)
    ln1 = lambda u: L.layer_norm(u, p["ln1_s"], p["ln1_b"], cfg.ln_eps)
    ln2 = lambda u: L.layer_norm(u, p["ln2_s"], p["ln2_b"], cfg.ln_eps)
    if cfg.pre_ln:
        a, kc, vc = attn(ln1(x))
        x = x + a
        x = x + _mlp(ln2(x), p)
    else:
        a, kc, vc = attn(x)
        x = ln1(x + a)
        x = ln2(x + _mlp(x, p))
    return x, kc, vc


def stack_decode(x, stacked_params, cfg: TransformerConfig, k, v, pos,
                 rows, write_rows, ring: bool = False):
    """One decode step over the stacked layers: the scan consumes each
    layer's pool slice and stacks the updated slices back — the caller
    donates the pool buffers so XLA updates them in place."""
    def body(carry, xs):
        lp, kc, vc = xs
        x, kc, vc = block_decode(carry, lp, cfg, kc, vc, pos, rows,
                                 write_rows, ring=ring)
        return x, (kc, vc)

    x, (k2, v2) = jax.lax.scan(body, x, (stacked_params, k, v))
    return x, k2, v2


def stack_extend(x, stacked_params, cfg: TransformerConfig, k, v, rows,
                 start, n_new):
    """A block of new tokens over the stacked layers (prefill / tail
    prefill / speculative verify): each layer scatters its new K/V rows
    into its pool slice and attends through the page-table view.  No
    remat: there is no backward to replay for."""
    def body(carry, xs):
        lp, kc, vc = xs
        x, kc, vc = block_extend(carry, lp, cfg, kc, vc, rows, start,
                                 n_new)
        return x, (kc, vc)

    x, (k2, v2) = jax.lax.scan(body, x, (stacked_params, k, v))
    return x, k2, v2


def stack_apply(x, stacked_params, cfg: TransformerConfig, attn_mask=None,
                z3_dims=None):
    """Run all layers via lax.scan over the stacked [L, ...] params.
    ``z3_dims``: ZeRO-3 partition dims of the stacked leaves (gather per
    layer inside the body) — see ``scan_layers``."""
    def body(carry, lp):
        return block_apply(carry, lp, cfg, attn_mask), None
    x, _ = scan_layers(body, x, stacked_params, cfg, z3_dims=z3_dims)
    return x

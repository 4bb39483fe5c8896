"""Mixture-of-Experts transformer with expert parallelism (Switch-style).

Beyond-reference component: the reference v0.1.0 has no MoE (DeepSpeed made
it a headline feature later); SURVEY.md §2 row 22 lists expert parallelism
as absent on both sides.  TPU-native shape:

* **Routing** is the GShard/Switch dense dispatch-combine formulation
  (one-hot slot tensors contracted with einsums) — static shapes,
  MXU-friendly, no scatter/dynamic control flow.  ``router_top_k=1`` gives
  Switch (gate = raw router prob); ``router_top_k=2`` gives GShard-style
  top-2 with gates normalized over the selected pair and sequential slot
  assignment (second choices queue behind first choices).
* **Expert parallelism rides the ``model`` axis**: expert-stacked FFN
  weights shard their expert dim over ``model`` (``E % mp == 0``), exactly
  like Megatron's column/row-parallel splits shard features.  Activations
  are model-replicated (the repo's TP invariant), so each shard computes the
  full router, processes only ITS experts' capacity slots, and the combine
  einsum's partial outputs ``psum`` over ``model`` — the same collective
  pattern as ``vocab_parallel_embedding``/``row_parallel_linear``.  No
  bespoke all-to-all layout: every existing subsystem (ZeRO x MP flat
  masters, per-MP-rank checkpoint files, norm dedup, overflow agreement)
  sees ordinary model-sharded leaves and composes unchanged.
* **Load balancing**: the Switch aux loss ``E * Σ_e f_e · P_e`` (token
  fraction x mean router probability), returned per block, summed by the
  scan, and added to the LM loss with ``aux_weight``.

Capacity: each expert processes ``C = ceil(S * router_top_k *
capacity_factor / E)`` slots per shard (each token occupies one slot per
selected expert); overflow tokens fall through with a zero FFN delta for
that choice (the residual connection carries them — standard Switch
behavior).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.remat_names import FFN1
from deepspeed_tpu.parallel.topology import MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class MoEConfig(T.TransformerConfig):
    num_experts: int = 8
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    # 1 = Switch (top-1); 2 = GShard-style top-2 with normalized gates
    router_top_k: int = 1

    def validate(self, mp_size: int = 1):
        super().validate(mp_size)
        if self.num_experts % mp_size:
            raise ValueError(
                f"num_experts {self.num_experts} not divisible by the "
                f"model/expert-parallel degree {mp_size}")
        if not 1 <= self.router_top_k <= self.num_experts:
            raise ValueError(
                f"router_top_k {self.router_top_k} must be in "
                f"[1, num_experts={self.num_experts}]")


def init_moe_block_params(cfg: MoEConfig, rng) -> dict:
    """Stacked [L, ...] block params: the dense stack's attention/LN leaves
    plus router + expert-stacked FFN weights (replacing fc_w/fc2_w)."""
    base = T.init_block_params(cfg, rng)
    for k in ("fc_w", "fc_b", "fc2_w", "fc2_b"):
        del base[k]
    Lyr, h, E = cfg.num_layers, cfg.hidden_size, cfg.num_experts
    ff = cfg.ffn_width
    ks = jax.random.split(jax.random.fold_in(rng, 17), 3)
    std = cfg.init_std
    resid_std = std / jnp.sqrt(2.0 * Lyr)
    norm = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s
    base.update({
        "router_w": norm(ks[0], (Lyr, h, E), std),
        "exp1_w": norm(ks[1], (Lyr, E, h, ff), std),
        "exp1_b": jnp.zeros((Lyr, E, ff), jnp.float32),
        "exp2_w": norm(ks[2], (Lyr, E, ff, h), resid_std),
        "exp2_b": jnp.zeros((Lyr, E, h), jnp.float32),
    })
    return base


def moe_block_partition_specs() -> dict:
    """Expert dim over ``model`` (expert parallelism); router replicated."""
    specs = T.block_partition_specs()
    for k in ("fc_w", "fc_b", "fc2_w", "fc2_b"):
        del specs[k]
    specs.update({
        "router_w": P(),
        "exp1_w": P(None, MODEL_AXIS, None, None),
        "exp1_b": P(None, MODEL_AXIS, None),
        "exp2_w": P(None, MODEL_AXIS, None, None),
        "exp2_b": P(None, MODEL_AXIS, None),
    })
    return specs


def moe_ffn(x, p, cfg: MoEConfig, axis=MODEL_AXIS, valid=None):
    """Switch FFN on local shards.  x: [B, Tk, h] model-replicated; p leaves
    are this shard's slices (expert dim = E/ep local experts).  ``valid`` is
    an optional [B, Tq] mask (1=real token, 0=padding; Tq may be the global
    sequence length under sequence parallelism — it is sliced to this
    shard's Tk).  Padding tokens are excluded from the load-balancing
    statistics AND from dispatch, so they neither bias the router's
    balance signal nor consume expert capacity.  Returns
    (y [B, Tk, h], aux scalar)."""
    B, Tk, h = x.shape
    E = cfg.num_experts
    S = B * Tk
    ep = L.axis_size_or_1(axis)
    e_local = p["exp1_w"].shape[0]
    # each token occupies router_top_k slots, so capacity scales with k
    cap = int(-(-S * cfg.router_top_k * cfg.capacity_factor // E))  # ceil
    xf = x.reshape(S, h)
    v = None
    if valid is not None:
        if L.axis_size_or_1(L.SEQ_AXIS) > 1 and valid.shape[1] != Tk:
            # sp>1: slice the global [B, T] mask down to this shard's Tk
            start = jax.lax.axis_index(L.SEQ_AXIS) * Tk
            valid = jax.lax.dynamic_slice_in_dim(valid, start, Tk, axis=1)
        v = valid.reshape(S).astype(jnp.float32)

    # -- router (replicated compute: every shard sees every token)
    logits = (xf @ p["router_w"].astype(xf.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [S, E]
    k = cfg.router_top_k
    topv, topi = jax.lax.top_k(probs, k)                       # [S, k]
    gate_norm = jnp.sum(topv, axis=-1, keepdims=True)          # [S, 1]

    # aux loss on the FIRST choice (Switch rule; GShard's top-2 aux also
    # counts only the primary assignment): E * Σ_e fraction_e · mean-prob_e,
    # with fractions/means taken over VALID positions only
    oh0 = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    if v is None:
        frac, pmean = jnp.mean(oh0, axis=0), jnp.mean(probs, axis=0)
    else:
        n = jnp.maximum(jnp.sum(v), 1.0)
        frac = jnp.sum(oh0 * v[:, None], axis=0) / n
        pmean = jnp.sum(probs * v[:, None], axis=0) / n
    aux = E * jnp.sum(frac * pmean)

    # -- this shard's experts only: slice each choice's expert one-hot
    # BEFORE the outer products, so dispatch/combine stay [S, e_local, C]
    # (never materialize [S, E, C])
    shard = jax.lax.axis_index(axis) if ep > 1 else 0
    lo = shard * e_local
    disp_local = jnp.zeros((S, e_local, cap), jnp.float32)
    comb_local = jnp.zeros((S, e_local, cap), jnp.float32)
    counts = jnp.zeros((E,), jnp.float32)   # slots taken by earlier choices
    for j in range(k):
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.float32)  # [S, E]
        if v is not None:
            oh = oh * v[:, None]   # padding takes no capacity slot
        # slot of each token within its expert's queue: tokens of EARLIER
        # choices occupy the head of the queue (GShard's sequential
        # assignment); mask before the row-sum so the -1 and the offset
        # apply once per token
        pos = jnp.sum((jnp.cumsum(oh, axis=0) + counts[None, :] - 1.0)
                      * oh, axis=-1)
        keep = (pos < cap) & (pos >= 0)
        onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                  dtype=jnp.float32) * keep[:, None]
        oh_local = jax.lax.dynamic_slice_in_dim(oh, lo, e_local, axis=1)
        disp_j = oh_local[:, :, None] * onehot_c[:, None, :]   # [S, e, C]
        disp_local = disp_local + disp_j
        if k == 1:
            gate_j = topv[:, 0]       # Switch: scale by the raw router prob
        else:
            # GShard: gates normalized over the k selected experts
            gate_j = topv[:, j] / jnp.maximum(gate_norm[:, 0], 1e-9)
        comb_local = comb_local + disp_j * gate_j[:, None, None]
        counts = counts + jnp.sum(oh, axis=0)

    # gather capacity slots, run the expert FFN batched over local experts
    ein = jnp.einsum("sec,sh->ech", disp_local, xf.astype(jnp.float32))
    ein = ein.astype(x.dtype)                                  # [e, C, h]
    y = jnp.einsum("ech,ehf->ecf", ein, p["exp1_w"].astype(x.dtype))
    y = y + p["exp1_b"].astype(y.dtype)[:, None, :]
    y = checkpoint_name(y, FFN1)
    y = L.gelu(y)
    y = jnp.einsum("ecf,efh->ech", y, p["exp2_w"].astype(y.dtype))
    y = y + p["exp2_b"].astype(y.dtype)[:, None, :]

    # combine back to token order; partial over experts → psum completes it
    out = jnp.einsum("sec,ech->sh", comb_local, y.astype(jnp.float32))
    if ep > 1:
        out = jax.lax.psum(out, axis)
    return out.astype(x.dtype).reshape(B, Tk, h), aux


def moe_block_apply(x, p, cfg: MoEConfig, attn_mask=None):
    """Transformer block with the FFN replaced by the Switch MoE.  The
    attention mask doubles as the router's validity mask (1=real, 0=pad).
    Returns (x, aux)."""
    return T.block_with_ffn(x, p, cfg, attn_mask,
                            ffn=lambda u, pp: moe_ffn(u, pp, cfg,
                                                      valid=attn_mask))


def moe_stack_apply(x, stacked_params, cfg: MoEConfig, attn_mask=None,
                    z3_dims=None):
    """lax.scan over the stacked [L, ...] MoE blocks; returns (x, aux_sum).
    ``z3_dims``: ZeRO-3 partition dims of the stacked leaves (per-layer
    gather, transformer.scan_layers)."""
    def body(carry, lp):
        return moe_block_apply(carry, lp, cfg, attn_mask)

    x, auxes = T.scan_layers(body, x, stacked_params, cfg, z3_dims=z3_dims)
    return x, jnp.sum(auxes)

"""Mixture-of-Experts layers.  TWO expert layers live here, for two
different jobs; do not take one for the other.

1. ``moe_ffn`` (with ``MoEConfig``, ``moe_block_apply``,
   ``moe_stack_apply``; ``models/gpt2_moe.py`` runs it): the capacity-based
   Switch / GShard layer on the ``model`` axis.  Softmax router, top-1 or
   top-2, GELU experts with biases, a capacity ``C = ceil(S * k * cf / E)``
   per expert that DROPS what overflows, and a dense one-hot ``[S, e_local,
   C]`` dispatch / combine contracted with einsums.  Static shapes and no
   gather, right for few experts and short rows; at 16k tokens and 8 experts
   held the one-hot tensors are a gigabyte each and their contractions ten
   times the experts' own work.
2. ``dropless_moe_ffn``: the dropless layer TOLD WHICH EXPERTS IT HOLDS
   (DeepSeek-V3's layer; ``models/latent_moe.py`` and
   ``models/delta_moe.py`` run it).  Scores over all the published experts
   by one of two rules — sigmoid with a selection-only correction bias, or
   a softmax — top-k, gates normalised over the chosen and scaled,
   bias-free SwiGLU experts, shared experts every token passes through
   (behind a sigmoid gate of their own where the model has one), a balance
   loss in one of two forms; the (token, choice) pairs
   that landed on the experts ``[first, first + count)`` held here are
   sorted by expert, run through grouped matmuls over ragged groups
   (``ops/grouped_matmul.py``: Pallas kernels on a TPU) and gathered back
   weighted.  No capacity, no drop: the group sizes are what the routing
   gives, and the rows worked on are a static prefix of the sorted pairs
   sized from the share, or all of them when more landed here (a branch on
   the device, both exact).  What the absent experts would add is left out — the partial
   result is this chip's part of an expert-parallel layer, and nothing
   stands in for the other chips or their exchange.

The first, in detail (beyond-reference component: the reference v0.1.0 has
no MoE; SURVEY.md section 2 row 22 lists expert parallelism as absent on
both sides):

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
* **Load balancing**: the Switch aux loss ``E * sum_e f_e * P_e`` (token
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
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.ops.grouped_matmul import (TILE_ROWS, _tile_rows,
                                              grouped_matmul)
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
    ep = L.axis_size_or_1(MODEL_AXIS)
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


# ------------------------------------------------- dropless, with a share
# The second expert layer of the module docstring.  Shapes below: ``S``
# tokens of the micro-batch, ``k`` experts per token, ``R = S * k`` (token,
# choice) pairs, pair ``r = t * k + j``; ``E`` published experts, ``e``
# held by this shard.

SCORING = ("sigmoid", "softmax")
BALANCE = ("sequence", "switch")


def route_tokens(x, router_w, router_b, *, top_k, scale, scoring="sigmoid"):
    """Scores, choices and gates of ``x`` [S, h] over ALL the published
    experts, in fp32 at the highest matmul precision, by one of two rules.

    ``sigmoid`` (DeepSeek-V3): ``s = sigmoid(x W_g)`` [S, E]; the chosen set
    is the top-``top_k`` of ``s + b`` (``router_b`` is the correction bias:
    it moves the choice and nothing else — the gates read ``s``, so its
    gradient is identically zero); ``g_e = scale * s_e / (sum of the chosen
    s + 1e-20)``.

    ``softmax`` (Qwen's expert layers): ``s = softmax(x W_g)`` over the
    ``E`` experts; the top-``top_k`` of ``s`` (``router_b`` is None: no
    bias); ``g_e = scale * s_e / sum of the chosen s`` — the gates
    renormalised to sum to ``scale``.

    Returns ``(scores [S, E], chosen [S, k] int32, gates [S, k])``."""
    f32 = jnp.float32
    logits = jnp.matmul(x.astype(f32), router_w.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + router_b.astype(f32), top_k)
        floor = 1e-20
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, top_k)
        floor = 0.0
    else:
        raise ValueError(f"scoring {scoring!r}: one of {SCORING}")
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                              + floor)
    return scores, chosen.astype(jnp.int32), gates


def balance_loss(scores, chosen, alpha, form="sequence"):
    """The balance loss of ``scores`` [B, T, E] and ``chosen`` [B, T, k].

    ``sequence``: DeepSeek-V3's sequence-wise loss (eqs. 17-20), per
    sequence, then the mean over the sequences: ``f_e = E / (k T) sum_t 1[e
    in K_t]``, ``P_e = 1 / T sum_t s_te / sum_j s_tj``, ``alpha sum_e f_e
    P_e``.

    ``switch``: the Switch form over ALL the tokens of the micro-batch:
    ``alpha E sum_e F_e P_e`` with ``F_e`` = (pairs on ``e``) / tokens and
    ``P_e`` the mean of ``s_te`` (``scores`` as they are: a softmax sums to
    1).  No gradient through ``f`` / ``F`` in either."""
    B, T_len, E = scores.shape
    k = chosen.shape[-1]
    on = jax.nn.one_hot(chosen, E, dtype=jnp.float32)
    if form == "sequence":
        f = jnp.sum(on, axis=(1, 2)) * (E / (k * T_len))
        P_ = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                      axis=1)
        return alpha * jnp.mean(
            jnp.sum(jax.lax.stop_gradient(f) * P_, axis=-1))
    if form == "switch":
        F = jnp.sum(on, axis=(0, 1, 2)) / (B * T_len)
        return alpha * E * jnp.sum(jax.lax.stop_gradient(F)
                                   * jnp.mean(scores, axis=(0, 1)))
    raise ValueError(f"balance loss form {form!r}: one of {BALANCE}")


def sort_share(chosen, first, count):
    """The (token, choice) pairs in the order the grouped matmuls read
    them: the pairs on expert ``first`` first, then ``first + 1``, ... up to
    ``first + count - 1``, then every pair that landed on an expert not
    held here (stable: a group keeps the tokens' order).  ``chosen`` [S, k]
    -> ``(order [R]: the pair at each sorted row, pos [S, k]: the sorted
    row of each pair, sizes [count]: pairs per held expert)``, all int32."""
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(chosen.shape)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, pos, sizes


def _held_rows(rows, pos, n_held):
    """``rows[pos]`` [S, k, ...] for the pairs an expert here took (``pos <
    n_held``), zeros for the others: what lies past ``n_held`` in a grouped
    matmul's output was never written and is not read."""
    return jnp.take(rows, jnp.where(pos < n_held, pos, rows.shape[0]),
                    axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def dispatch(x, order, pos, n_held):
    """``x`` [S, h] -> the rows of the sorted pairs ``order`` [rows] (the
    first ``rows`` of ``sort_share``'s order: all ``R``, or a prefix that
    holds every pair held; ``pos`` [S, k] is over all pairs; a token
    appears once per choice).  The transpose of a gather is a scatter-add,
    serial on a TPU; a pair's sorted row is known (``pos``), so the
    backward is a gather too: ``dx_t = sum_j d_rows[pos[t, j]]`` over the
    pairs held.  It also leaves out what a transpose would add: the rows
    past ``n_held`` of a grouped matmul's gradient were never written.

    Measured on a v5e at 2 x 8,192 tokens, 8 of 64 experts.  Over all ``R``
    = 98,304 rows, against ``jnp.take`` and a weighted sum left to autodiff
    (with the mask on the rows that form then needs): routing 173 ms a step
    here, 192 there, the experts' products 63 against 74, 22,053 against
    21,094 tokens/s (PERF.md, PR 33, 2026-10).  Over the 24,576-row prefix
    (PERF.md, PR 34, 2026-10; one layer's forward and backward, 32.30 ms
    with every form a gather): a pair-keyed gather out of the short table
    costs 2.1 ms (1.47 of it the copy into ``[S, k, h]``), the fp32
    scatter-add over the prefix rows that could replace it 2.45 — here
    32.49 ms, in ``combine``'s forward 32.64: both stay gathers."""
    return jnp.take(x, order // pos.shape[1], axis=0)


def _dispatch_fwd(x, order, pos, n_held):
    return dispatch(x, order, pos, n_held), (pos, n_held)


def _dispatch_bwd(res, g):
    pos, n_held = res
    dx = jnp.sum(_held_rows(g, pos, n_held).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, gates, order, pos, n_held):
    """``y_t = sum_j gates[t, j] * rows[pos[t, j]]`` over the pairs held
    (fp32 sum, ``rows``' dtype): the experts' outputs [rows, h], in the
    order ``order`` [rows] of ``dispatch``, back in token order [S, h],
    weighted.  Backward by gathers, as ``dispatch`` — but for the gates'
    gradient on a prefix, ``d_gates[t, j] = rows[pos[t, j]] . g[t]``: the
    rows' gradient has already gathered ``g`` by sorted row, so the dot is
    taken per sorted row and ONE NUMBER a held pair is put back in pair
    order, where the gather form fetches the pair's whole row again (one
    layer's forward and backward on a v5e at the 24,576-row prefix: 29.82
    ms against 32.30, PERF.md, PR 34; over all ``R`` rows the form the
    worst case always had is kept, so that branch is that program)."""
    picked = _held_rows(rows, pos, n_held).astype(jnp.float32)
    return jnp.sum(picked * gates[..., None], axis=1).astype(rows.dtype)


def _combine_fwd(rows, gates, order, pos, n_held):
    return (combine(rows, gates, order, pos, n_held),
            (rows, gates, order, pos, n_held))


def _combine_bwd(res, g):
    rows, gates, order, pos, n_held = res
    gf = g.astype(jnp.float32)
    on_prefix = rows.shape[0] < pos.size
    if not on_prefix:
        picked = _held_rows(rows, pos, n_held).astype(jnp.float32)
        d_gates = jnp.sum(picked * gf[:, None, :], axis=-1)
    held = jnp.arange(rows.shape[0]) < n_held
    weight = jnp.where(held, jnp.take(gates.reshape(-1), order),
                       0.0)[:, None]
    by_row = jnp.take(gf, order // pos.shape[1], axis=0)
    if on_prefix:
        per_row = jnp.sum(rows.astype(jnp.float32) * by_row, axis=-1)
        d_gates = jnp.zeros((pos.size,), jnp.float32).at[
            jnp.where(held, order, pos.size)].set(
                per_row, mode="drop", unique_indices=True).reshape(pos.shape)
    d_rows = weight * by_row
    return d_rows.astype(rows.dtype), d_gates, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


@S.scoped("experts")
def grouped_swiglu(rows, p, sizes, n_held):
    """``SwiGLU_e`` of each group of sorted ``rows`` [rows, h] through its
    own expert: three grouped matmuls over the ragged groups ``sizes`` [e]
    (``p``: ``exp_gate_w``, ``exp_up_w`` [e, h, f], ``exp_down_w`` [e, f,
    h]).  The rows past ``n_held`` belong to no group: a grouped matmul
    neither reads nor writes them, so the two pre-activations are zeroed
    there (nothing unwritten reaches the next product or a gradient) and
    the last output is left for ``combine`` to skip."""
    held = (jnp.arange(rows.shape[0]) < n_held)[:, None]
    # named like a dense MLP's pre-activations for the "selective" policy
    gate = checkpoint_name(jnp.where(
        held, grouped_matmul(rows, p["exp_gate_w"], sizes), 0), FFN1)
    up = checkpoint_name(jnp.where(
        held, grouped_matmul(rows, p["exp_up_w"], sizes), 0), FFN1)
    return grouped_matmul(L.silu(gate) * up, p["exp_down_w"], sizes)


#: The held-row prefix is this many times the rows that land on the held
#: experts when every expert draws the same load.  The routing's own noise
#: is small beside it (uniform choices at the cell's 98,304 pairs: 12,288 +-
#: about 100), so what the room is for is a router that favours the experts
#: held: at 2 a share whose experts are ALL twice as popular as the mean
#: still runs in the prefix, which a balance-loss-trained router does not
#: reach over a whole share (single experts do).  More room costs in
#: proportion (every gather, mask and activation of the prefix runs over
#: its rows, held or not); less room sends steps of an unbalanced router to
#: the overflow branch, at the full worst-case price.  A constant, not a
#: setting: the overflow branch makes any value exact.
HEADROOM = 2


def prefix_rows(pairs, count, num_experts):
    """Rows of the static prefix of the sorted pairs that the routed part
    works on when the ``count`` experts held of ``num_experts`` took no
    more: ``HEADROOM`` times their even share of all ``pairs``, rounded up
    to the row tile the grouped matmuls walk ``pairs`` rows in (128 where
    they have none), at most ``pairs`` — the whole layer, and shapes too
    small to round under it, have no prefix."""
    tile = _tile_rows(pairs) or TILE_ROWS[-1]
    even = HEADROOM * pairs * count
    return min(pairs, -(-even // (num_experts * tile)) * tile)


def routed_part(rows, flat, p, gates, order, pos, sizes, n_held):
    """The held experts' part of the layer on the first ``rows`` (static)
    of the sorted pairs, which must hold every pair held (``n_held <=
    rows``): ``dispatch`` of ``order[:rows]``, ``grouped_swiglu`` on
    ``[rows, h]``, ``combine`` back to ``[S, h]``.  ``rows = R`` is the
    worst case, every pair of every token."""
    order = order[:rows]
    with S.scope("route"):
        sorted_rows = dispatch(flat, order, pos, n_held)
    out = grouped_swiglu(sorted_rows, p, sizes, n_held)
    with S.scope("route"):
        return combine(out, gates, order, pos, n_held)


def held_experts(flat, p, chosen, gates, first, num_experts):
    """``sum_j gates[t, j] SwiGLU_e(flat[t])`` [S, h] over the choices
    ``e = chosen[t, j]`` that fall on the experts held, ``[first, first +
    e_local)`` of ``num_experts`` (``p``'s ``exp_*_w``): the pairs sorted
    (``sort_share``), then ``routed_part`` on the prefix if the ``n_held``
    pairs that landed here fit it, else on all of them — chosen on the
    device, both exact (``dropless_moe_ffn``).  Returns it with the
    layer's step scalars (observability/scalars.py), int32, all of them
    values the choice already needs: ``moe/held_pairs`` = ``n_held``,
    ``moe/max_expert_rows`` = the largest of ``sizes``,
    ``moe/overflow_passes`` = 1 where the worst case ran (0 always from a
    share with no prefix)."""
    e_local = p["exp_gate_w"].shape[0]
    with S.scope("route"):
        order, pos, sizes = sort_share(chosen, first, e_local)
        n_held = jnp.sum(sizes)
        counts = {"moe/held_pairs": n_held,
                  "moe/max_expert_rows": jnp.max(sizes),
                  "moe/overflow_passes": jnp.zeros((), jnp.int32)}
    # the branches' operands: of ``p`` only what they read
    experts = {name: p[name]
               for name in ("exp_gate_w", "exp_up_w", "exp_down_w")}
    operands = (flat, experts, gates, order, pos, sizes, n_held)
    pairs = chosen.size
    prefix = prefix_rows(pairs, e_local, num_experts)
    if prefix == pairs:
        return routed_part(pairs, *operands), counts
    # Each branch under ``jax.checkpoint``: what it hands its backward is
    # its operands alone, which the branches share.  Autodiff of a bare
    # ``cond`` makes every intermediate either backward reads an output of
    # the forward ``cond`` — the worst case's fp32 activation pieces among
    # them, zero-filled whenever the prefix runs — and the cell's step then
    # needs 16.57 GB of a v5e's 15.75 (PERF.md, PR 34).  An outer policy
    # still finds the names inside (``selective`` keeps ``ffn1``).
    part = jax.checkpoint(routed_part, static_argnums=0)
    fits = n_held <= prefix
    counts["moe/overflow_passes"] = 1 - fits.astype(jnp.int32)
    return jax.lax.cond(fits, functools.partial(part, prefix),
                        functools.partial(part, pairs), *operands), counts


@S.scoped("moe")
def dropless_moe_ffn(x, p, *, num_experts, top_k, held, route_scale,
                     balance_alpha, scoring="sigmoid", balance="sequence"):
    """The dropless expert layer on local shards, for the experts ``held =
    (first, count)`` of ``num_experts``.  x [B, T, h] model-replicated.
    ``p``: ``router_w`` [h, E] and ``router_b`` [E] (the router is whole
    everywhere), the held experts' ``exp_gate_w`` / ``exp_up_w`` [e, h, f]
    and ``exp_down_w`` [e, f, h], and the shared experts as ONE SwiGLU
    ``gate_w`` / ``up_w`` [h, fs / mp], ``down_w`` [fs / mp, h].  Returns
    ``(y [B, T, h], balance loss, step scalars)`` — the third is
    ``held_experts``' counts of this pass on this shard, for the caller to
    return beside its loss (``observability.scalars.WithScalars``):

        ``y_t = sum_{e in K_t, first <= e < first + count} g_e SwiGLU_e(x_t)
        + SwiGLU_shared(x_t)``

    with ``K_t`` and ``g`` of ``route_tokens`` over all ``num_experts`` by
    the rule ``scoring`` (``softmax`` reads no ``router_b``), the balance
    loss in the form ``balance`` (``balance_loss``), and — where ``p`` holds
    ``shared_gate_w`` [h] — the shared experts' output times ``sigmoid(x .
    shared_gate_w)``, a gate of their own (under ``dstpu/ffn`` with them).

    The rows worked on.  The pairs held come first in ``sort_share``'s
    order, so the routed part (``routed_part``) runs on a static prefix of
    ``prefix_rows`` rows (a quarter of all ``R`` pairs for an eighth of the
    experts) whenever the ``n_held`` pairs that landed here fit it, and on
    all ``R`` rows — the worst case, the one program there was before the
    prefix — when they do not: a ``lax.cond`` on the device, both branches
    exact.  NO PAIR IS DROPPED AND THERE IS NO CAPACITY: an overflowing
    step costs the worst case's time and gives its numbers.  A share with
    no prefix under ``R`` (the whole layer) has no ``cond``.

    Under expert parallelism over the ``model`` axis the held experts are
    split evenly over the shards (``e = count / size``, expert dim sharded
    like ``moe_ffn``'s), each shard computes its own part — on its own
    ``n_held``, so in its own branch — and a ``psum`` outside the branch
    adds them; on one chip there is no exchange and none is emulated.
    Scopes: ``dstpu/route`` (scores, top-k, gates, sort, the two gathers,
    balance loss), ``dstpu/experts`` (the grouped matmuls), ``dstpu/ffn``
    (the shared experts), all inside ``dstpu/moe``, in either branch."""
    B, T_len, h = x.shape
    first, count = held
    ep = L.axis_size_or_1(MODEL_AXIS)
    e_local = p["exp_gate_w"].shape[0]
    if e_local * ep != count:
        raise ValueError(f"{count} experts held over {ep} shards, "
                         f"{e_local} in this shard's weights")
    if ep > 1:
        first = first + jax.lax.axis_index(MODEL_AXIS) * e_local
    flat = x.reshape(B * T_len, h)
    with S.scope("route"):
        scores, chosen, gates = route_tokens(
            flat, p["router_w"], p.get("router_b"), top_k=top_k,
            scale=route_scale, scoring=scoring)
        aux = balance_loss(scores.reshape(B, T_len, num_experts),
                           chosen.reshape(B, T_len, top_k), balance_alpha,
                           form=balance)
    routed, counts = held_experts(flat, p, chosen, gates, first,
                                  num_experts)
    if ep > 1:
        with S.scope("route"):
            routed = jax.lax.psum(routed, MODEL_AXIS)
    routed = routed.reshape(B, T_len, h)
    shared = T._gated_mlp(x, p)
    if "shared_gate_w" in p:
        with S.scope("ffn"):
            gate = jax.nn.sigmoid(jnp.sum(
                x.astype(jnp.float32)
                * p["shared_gate_w"].astype(jnp.float32), axis=-1))
            shared = shared * gate[..., None].astype(shared.dtype)
    return routed + shared, aux, counts

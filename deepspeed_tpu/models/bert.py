"""BERT pretraining model (MLM + optional NSP) with tensor parallelism.

Counterpart of the reference's BingBert pretraining + BingBertSquad fine-tune
suites (/root/reference/tests/model/BingBertSquad/,
docs/_tutorials/bert-pretraining.md — the 14h/64-GPU headline workload).
Post-LN encoder per the original BERT; vocab-parallel MLM head tied to the
embedding.  The SQuAD-style span head is provided for fine-tuning parity.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.parallel.topology import (DATA_AXIS, MODEL_AXIS,
                                             SEQ_AXIS)


BERT_SIZES = {
    "tiny":  dict(num_layers=2,  hidden_size=128, num_heads=4,
                  max_seq_len=128, vocab_size=512),
    "base":  dict(num_layers=12, hidden_size=768, num_heads=12,
                  vocab_size=30528, max_seq_len=512),
    "large": dict(num_layers=24, hidden_size=1024, num_heads=16,
                  vocab_size=30528, max_seq_len=512),
}


def _init_backbone_params(cfg: T.TransformerConfig, rng) -> dict:
    """Embeddings (word/position/token-type) + encoder stack."""
    cfg.validate()
    h = cfg.hidden_size
    ks = jax.random.split(rng, 4)
    std = cfg.init_std
    return {
        "wte": jax.random.normal(ks[0], (cfg.vocab_size, h),
                                 jnp.float32) * std,
        "wpe": jax.random.normal(ks[1], (cfg.max_seq_len, h),
                                 jnp.float32) * std,
        "wtt": jax.random.normal(ks[2], (2, h), jnp.float32) * std,
        "ln_emb_s": jnp.ones((h,), jnp.float32),
        "ln_emb_b": jnp.zeros((h,), jnp.float32),
        "blocks": T.init_block_params(cfg, ks[3]),
    }


def _backbone_partition_specs() -> dict:
    return {
        "wte": P(MODEL_AXIS, None),
        "wpe": P(), "wtt": P(),
        "ln_emb_s": P(), "ln_emb_b": P(),
        "blocks": T.block_partition_specs(),
    }


def _encode(cfg, params, input_ids, attention_mask, token_type_ids,
            z3_block_dims=None):
    """Embed + encoder stack (runs inside shard_map on local shards).
    Callers must already have run ``T.zero3_enter`` on ``params`` under
    ZeRO-3 (``z3_block_dims`` = its deferred block dims)."""
    T_len = input_ids.shape[1]
    with S.scope("embed"):
        x = L.vocab_parallel_embedding(input_ids, params["wte"])
        x = x + L.seq_shard_positions(params["wpe"], T_len).astype(
            x.dtype)[None]
        x = x + jnp.take(params["wtt"].astype(x.dtype), token_type_ids,
                         axis=0)
        x = L.layer_norm(x, params["ln_emb_s"], params["ln_emb_b"],
                         cfg.ln_eps)
    return T.stack_apply(x, params["blocks"], cfg, attn_mask=attention_mask,
                         z3_dims=z3_block_dims)


def _zero3_min_dims(params):
    """Stage-3 hook body shared by both BERT heads (see GPT2)."""
    md = jax.tree_util.tree_map(lambda _: 0, params)
    md["blocks"] = jax.tree_util.tree_map(lambda _: 1, md["blocks"])
    return md


@dataclasses.dataclass
class BertForPreTraining:
    """MLM (+NSP when ``use_nsp``) pretraining loss.

    apply(params, input_ids, attention_mask, token_type_ids, mlm_labels
          [, nsp_labels]) → scalar loss.  mlm_labels < 0 are ignored.
    """
    config: T.TransformerConfig
    use_nsp: bool = False
    #: dense-labels MLM only: when set, gather up to this many masked
    #: positions per sequence BEFORE the vocab projection (the sparse head
    #: the masked-positions format gets for free), instead of the
    #: [B, T, vocab] dense logits.  EXACTNESS CONTRACT: per-sequence masked
    #: counts must not exceed the budget — overflow positions are silently
    #: dropped from the loss (standard BERT data caps masking at
    #: max_predictions_per_seq, so the pipeline's cap is the right value).
    #: Clamped to the sequence length (budget >= T is always exact).  The
    #: dense path remains the fallback: budget None, or sequence
    #: parallelism > 1 (the gather indexes global positions).
    mlm_gather_budget: object = None
    #: ZeRO-3 partition dims (set by the engine at stage 3; zero3.py)
    zero3_dims: object = None

    @classmethod
    def from_size(cls, size: str, use_nsp: bool = False,
                  mlm_gather_budget=None, **overrides):
        kw = dict(BERT_SIZES[size])
        kw.update(overrides)
        kw.setdefault("pre_ln", False)   # BERT is post-LN
        kw.setdefault("causal", False)
        return cls(T.TransformerConfig(**kw), use_nsp=use_nsp,
                   mlm_gather_budget=mlm_gather_budget)

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        """Engine hook: shape checks against the actual degrees (built for
        every sp / pp degree, so only ``mp_size`` is read)."""
        self.config.validate(mp_size)

    def init_params(self, rng):
        cfg = self.config
        h = cfg.hidden_size
        k_bb, k4, k5 = jax.random.split(rng, 3)
        std = cfg.init_std
        params = _init_backbone_params(cfg, k_bb)
        params.update({
            # MLM head: dense + LN + tied decoder with its own output bias
            "mlm_dense_w": jax.random.normal(k4, (h, h), jnp.float32) * std,
            "mlm_dense_b": jnp.zeros((h,), jnp.float32),
            "mlm_ln_s": jnp.ones((h,), jnp.float32),
            "mlm_ln_b": jnp.zeros((h,), jnp.float32),
            "mlm_bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
        })
        if self.use_nsp:
            params["pool_w"] = jax.random.normal(k5, (h, h),
                                                 jnp.float32) * std
            params["pool_b"] = jnp.zeros((h,), jnp.float32)
            params["nsp_w"] = jnp.zeros((h, 2), jnp.float32)
            params["nsp_b"] = jnp.zeros((2,), jnp.float32)
        return params

    def partition_specs(self, params=None):
        specs = _backbone_partition_specs()
        specs.update({
            "mlm_dense_w": P(), "mlm_dense_b": P(),
            "mlm_ln_s": P(), "mlm_ln_b": P(),
            "mlm_bias": P(MODEL_AXIS),     # rides with the vocab shard
        })
        if self.use_nsp:
            specs.update({"pool_w": P(), "pool_b": P(),
                          "nsp_w": P(), "nsp_b": P()})
        return specs

    def batch_specs(self, batch):
        """Engine hook, format-aware (mirrors ``apply``): the first three
        leaves and dense ``mlm_labels`` are [B, T] sequence-aligned; the
        masked-positions leaves are [B, P] (P = max predictions, NOT the
        sequence) and shard over ``data`` only; nsp_labels is [B]."""
        batch = tuple(batch)
        rest = len(batch) - 3
        seq = P(DATA_AXIS, SEQ_AXIS)
        specs = [seq, seq, seq]
        if rest in (1, 2):
            specs.append(seq)                      # dense mlm_labels [B, T]
        elif rest in (3, 4):
            specs += [P(DATA_AXIS, None)] * 3      # positions/ids/weights
        else:
            raise TypeError(
                f"BertForPreTraining batch: expected 4-7 leaves, "
                f"got {len(batch)}")
        if rest in (2, 4):
            specs.append(P(DATA_AXIS))             # nsp_labels [B]
        return tuple(specs)

    def zero3_min_dims(self, params):
        """Engine hook (stage 3): block leaves pin dim >= 1 (layer stack)."""
        return _zero3_min_dims(params)

    def _mlm_head(self, params, h):
        """Dense + LN + tied vocab decoder on [.., H] hidden states."""
        cfg = self.config
        g = L.gelu(h @ params["mlm_dense_w"].astype(h.dtype)
                   + params["mlm_dense_b"].astype(h.dtype))
        g = L.layer_norm(g, params["mlm_ln_s"], params["mlm_ln_b"], cfg.ln_eps)
        logits = L.vocab_parallel_logits(g, params["wte"])
        return logits + params["mlm_bias"].astype(logits.dtype)

    def apply(self, params, input_ids, attention_mask, token_type_ids, *rest):
        """Two MLM input formats (both are scalar-loss):

        * dense labels — ``apply(.., mlm_labels[, nsp_labels])`` with
          ``mlm_labels`` int [B, T], positions < 0 ignored.  Simple, but
          materialises [B, T, vocab] logits.
        * masked positions — ``apply(.., mlm_positions, mlm_ids,
          mlm_weights[, nsp_labels])`` with [B, P] leaves (P = static
          max_predictions_per_seq): the standard BERT pretraining data
          format (the reference's BingBert recipe trains this way,
          docs/_tutorials/bert-pretraining.md).  Gathers the P masked
          positions BEFORE the vocab projection, so the head costs
          P/T of the dense variant in both FLOPs and memory.
        """
        cfg = self.config
        if len(rest) in (1, 2):
            mlm_labels, nsp_labels = rest[0], (rest[1] if len(rest) == 2
                                               else None)
            mlm_positions = None
        elif len(rest) in (3, 4):
            mlm_positions, mlm_ids, mlm_weights = rest[:3]
            nsp_labels = rest[3] if len(rest) == 4 else None
            if L.axis_size_or_1(SEQ_AXIS) > 1:
                raise NotImplementedError(
                    "masked-positions MLM gathers global sequence positions "
                    "— use dense mlm_labels under context_parallel_size > 1")
        else:
            raise TypeError(
                f"BertForPreTraining.apply: expected mlm_labels[, nsp] or "
                f"mlm_positions, mlm_ids, mlm_weights[, nsp], got "
                f"{len(rest)} trailing args")

        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        x = _encode(cfg, params, input_ids, attention_mask, token_type_ids,
                    z3_block_dims=z3_deferred.get("blocks"))

        with S.scope("head"):
            if mlm_positions is None:
                budget = self.mlm_gather_budget
                if budget and L.axis_size_or_1(SEQ_AXIS) == 1:
                    # sparse head for the dense-labels format: select <= budget
                    # masked positions per sequence (top_k of the 0/1 mask is
                    # stable, so masked positions come first, in order), gather
                    # them, and run the vocab projection on [B, P, H] instead
                    # of [B, T, H].  Matches the dense loss exactly while every
                    # sequence's masked count fits the budget (see the field
                    # docstring for the overflow contract).
                    P_ = min(int(budget), mlm_labels.shape[1])
                    maskf = (mlm_labels >= 0).astype(jnp.float32)
                    w, pos = jax.lax.top_k(maskf, P_)           # [B, P] each
                    ids = jnp.clip(                 # w=0 rows: any id
                        jnp.take_along_axis(mlm_labels, pos, axis=1), 0, None)
                    h_m = L.gather_positions(x, pos)
                    logits = self._mlm_head(params, h_m)  # [B, P, vocab/mp]
                    tok_loss = L.vocab_parallel_cross_entropy(logits, ids)
                    loss = (jnp.sum(tok_loss * w)
                            / jnp.maximum(jnp.sum(w), 1.0))
                else:
                    logits = self._mlm_head(params, x)
                    tok_loss = L.vocab_parallel_cross_entropy(
                        logits, mlm_labels)
                    loss = L.masked_mean_loss(tok_loss, mlm_labels >= 0)
            else:
                h_m = L.gather_positions(x, mlm_positions)
                logits = self._mlm_head(params, h_m)      # [B, P, vocab/mp]
                tok_loss = L.vocab_parallel_cross_entropy(logits, mlm_ids)
                w = mlm_weights.astype(jnp.float32)
                loss = jnp.sum(tok_loss * w) / jnp.maximum(jnp.sum(w), 1.0)

            if self.use_nsp and nsp_labels is not None:
                if L.axis_size_or_1(SEQ_AXIS) > 1:
                    raise NotImplementedError(
                        "NSP pools the global [CLS] token, which lives only "
                        "on sequence shard 0 — NSP is not supported under "
                        "context_parallel_size > 1")
                pooled = jnp.tanh(x[:, 0] @ params["pool_w"].astype(x.dtype)
                                  + params["pool_b"].astype(x.dtype))
                nsp_logits = (pooled @ params["nsp_w"].astype(pooled.dtype)
                              + params["nsp_b"].astype(pooled.dtype))
                logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), -1)
                nsp = -jnp.mean(jnp.take_along_axis(
                    logp, nsp_labels[:, None], axis=-1)[:, 0])
                loss = loss + nsp
        return loss

    __call__ = apply


@dataclasses.dataclass
class BertForQuestionAnswering:
    """SQuAD span-extraction fine-tune head (BingBertSquad parity,
    /root/reference/tests/model/BingBertSquad/BingBertSquad_run_func_test.py).

    apply(params, input_ids, attention_mask, token_type_ids, start_positions,
    end_positions) → scalar loss.
    """
    config: T.TransformerConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3; zero3.py)
    zero3_dims: object = None

    @classmethod
    def from_size(cls, size: str, **overrides):
        kw = dict(BERT_SIZES[size])
        kw.update(overrides)
        kw.setdefault("pre_ln", False)
        kw.setdefault("causal", False)
        return cls(T.TransformerConfig(**kw))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        """Engine hook: shape checks against the actual degrees (built for
        every sp / pp degree, so only ``mp_size`` is read)."""
        self.config.validate(mp_size)

    def init_params(self, rng):
        cfg = self.config
        h = cfg.hidden_size
        k_bb, k_qa = jax.random.split(rng, 2)
        params = _init_backbone_params(cfg, k_bb)
        params["qa_w"] = jax.random.normal(k_qa, (h, 2),
                                           jnp.float32) * cfg.init_std
        params["qa_b"] = jnp.zeros((2,), jnp.float32)
        return params

    def partition_specs(self, params=None):
        specs = _backbone_partition_specs()
        specs.update({"qa_w": P(), "qa_b": P()})
        return specs

    def batch_specs(self, batch):
        """Engine hook: (ids, mask, type_ids) are [B, T]; start/end
        positions are [B] per-example scalars."""
        seq = P(DATA_AXIS, SEQ_AXIS)
        return (seq, seq, seq, P(DATA_AXIS), P(DATA_AXIS))

    def zero3_min_dims(self, params):
        """Engine hook (stage 3): block leaves pin dim >= 1 (layer stack)."""
        return _zero3_min_dims(params)

    def span_logits(self, params, input_ids, attention_mask, token_type_ids):
        """(start_logits, end_logits), each [B, T] fp32 — the prediction
        path for EM/F1 evaluation (metrics.best_spans)."""
        if L.axis_size_or_1(SEQ_AXIS) > 1:
            raise NotImplementedError(
                "span extraction softmaxes over the FULL sequence and "
                "indexes global positions — not supported under "
                "context_parallel_size > 1 (fine-tune lengths don't need it)")
        cfg = self.config
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        x = _encode(cfg, params, input_ids, attention_mask, token_type_ids,
                    z3_block_dims=z3_deferred.get("blocks"))
        with S.scope("head"):
            logits = (x @ params["qa_w"].astype(x.dtype)
                      + params["qa_b"].astype(x.dtype)).astype(jnp.float32)
            return logits[..., 0], logits[..., 1]

    def apply(self, params, input_ids, attention_mask, token_type_ids,
              start_positions, end_positions):
        start_logits, end_logits = self.span_logits(
            params, input_ids, attention_mask, token_type_ids)

        def span_loss(lg, pos):
            lg = jnp.where(attention_mask.astype(jnp.bool_), lg, -1e9)
            logp = jax.nn.log_softmax(lg, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, pos[:, None], axis=-1)[:, 0])

        with S.scope("head"):
            return 0.5 * (span_loss(start_logits, start_positions)
                          + span_loss(end_logits, end_positions))

    __call__ = apply

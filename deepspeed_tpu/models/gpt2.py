"""GPT-2 causal LM with Megatron-style tensor parallelism.

The integration model for the engine — the reference's equivalent role is
Megatron-LM GPT-2 driven through the mpu bridge
(/root/reference/tests/model/Megatron_GPT2/ds_gpt2_test.sh:63-97,
run_perf_test.py:18-62 for the 1.5B/4B/8B/20B configs).  Weight-tied
vocab-parallel LM head feeds the vocab-parallel cross-entropy directly, so the
full-vocab logits are never materialised on one shard.

Engine protocol: ``init_params(rng)`` → global param pytree;
``partition_specs(params)`` → PartitionSpec tree; ``apply(params, tokens,
labels)`` → scalar mean loss (runs inside shard_map; see models/layers.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.parallel.topology import MODEL_AXIS


# Published GPT-2 size ladder incl. the reference's perf-test configs
# (/root/reference/tests/model/Megatron_GPT2/run_perf_test.py:18-62).
GPT2_SIZES = {
    "tiny":   dict(num_layers=2,  hidden_size=128,  num_heads=4,
                   max_seq_len=128, vocab_size=512),
    "small":  dict(num_layers=12, hidden_size=768,  num_heads=12),
    "medium": dict(num_layers=24, hidden_size=1024, num_heads=16),
    "large":  dict(num_layers=24, hidden_size=1536, num_heads=16),
    "xl-1.5b": dict(num_layers=48, hidden_size=1600, num_heads=25),
    # the reference's perf-test 1.5B shape (run_perf_test.py:18-31 uses 16
    # heads, not the published 25, so tensor parallelism divides evenly)
    "xl-1.5b-perf": dict(num_layers=48, hidden_size=1600, num_heads=16),
    "4b":     dict(num_layers=64, hidden_size=2304, num_heads=24),
    "8b":     dict(num_layers=72, hidden_size=3072, num_heads=24),
    "20b":    dict(num_layers=111, hidden_size=3808, num_heads=32),
}


@dataclasses.dataclass
class GPT2:
    """Callable model object satisfying the engine protocol."""
    config: T.TransformerConfig
    #: ZeRO-3 partition dims (set by the engine at stage 3; zero3.py).
    #: The block subtree is gathered per layer inside the scan, the rest
    #: at apply entry (transformer.zero3_enter).
    zero3_dims: object = None

    @classmethod
    def from_size(cls, size: str, **overrides) -> "GPT2":
        kw = dict(GPT2_SIZES[size])
        kw.update(overrides)
        kw.setdefault("pre_ln", True)
        kw.setdefault("causal", True)
        return cls(T.TransformerConfig(**kw))

    def validate(self, mp_size: int = 1, sp_size: int = 1, pp_size: int = 1):
        """Engine hook: shape checks against the actual degrees (built for
        every sp / pp degree, so only ``mp_size`` is read)."""
        self.config.validate(mp_size)

    # ------------------------------------------------------------------ init
    def _init_blocks(self, rng):
        """Block-stack init hook (GPT2MoE overrides with expert params)."""
        return T.init_block_params(self.config, rng)

    def _block_specs(self):
        """Block-stack sharding hook."""
        return T.block_partition_specs()

    def init_params(self, rng):
        cfg = self.config
        cfg.validate()
        k_wte, k_wpe, k_blocks = jax.random.split(rng, 3)
        return {
            "wte": jax.random.normal(
                k_wte, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
            * cfg.init_std,
            "wpe": jax.random.normal(
                k_wpe, (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
            * cfg.init_std * 0.5,
            "blocks": self._init_blocks(k_blocks),
            "lnf_s": jnp.ones((cfg.hidden_size,), jnp.float32),
            "lnf_b": jnp.zeros((cfg.hidden_size,), jnp.float32),
        }

    def partition_specs(self, params=None):
        return {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel
            "wpe": P(),
            "blocks": self._block_specs(),
            "lnf_s": P(), "lnf_b": P(),
        }

    def batch_specs(self, batch):
        """Engine hook: (tokens, labels) are both [B, T] — dim 1 is the
        sequence, so it shards over the context-parallel ring."""
        return T.token_batch_specs(batch)

    def zero3_min_dims(self, params):
        """Engine hook (stage 3): lowest partitionable dim per leaf.  Block
        leaves pin dim >= 1 — their dim 0 is the layer stack the scan
        consumes, which must stay whole on every shard."""
        md = jax.tree_util.tree_map(lambda _: 0, params)
        md["blocks"] = jax.tree_util.tree_map(lambda _: 1, md["blocks"])
        return md

    # --------------------------------------------------------------- forward
    def _stack(self, x, blocks, z3_dims=None):
        """Block-stack hook: returns (x, auxiliary loss term).  GPT2MoE
        overrides this with the MoE stack + weighted load-balance loss."""
        return T.stack_apply(x, blocks, self.config, z3_dims=z3_dims), 0.0

    # ------------------------------------------------- serving (inference/)
    def kv_cache_dims(self, mp_size: int = 1):
        """(num_layers, local kv heads, head_dim) — what the serving KV
        cache must hold per token on one model shard."""
        cfg = self.config
        return (cfg.num_layers, cfg.num_heads // mp_size,
                cfg.hidden_size // cfg.num_heads)

    def apply_extend(self, params, tokens, k, v, pos, n_new, rows):
        """A block of NEW tokens forwarded against the KV page pool —
        prefill (``pos=0``), tail prefill over a reused prefix
        (``pos=reused``), and the speculative VERIFY step are all this
        one program shape (runs inside shard_map, like ``apply``).

        tokens: int32 [B, E] left-aligned new tokens (``n_new[b]``
        real); k/v: [L, R, n_local, d] flat page pools; pos: int32 [B]
        absolute position of each slot's first new token; rows: int32
        [B, cap] page-table row map.  Returns ``(logits [B, E,
        vocab/mp], k', v')`` — logits for EVERY block position (the
        verify step consumes all of them; prefill takes row
        ``n_new-1``); pad positions' logits are garbage the caller
        masks.  Pad K/V writes are dropped, never visible."""
        cfg = self.config
        B, E = tokens.shape
        x = L.vocab_parallel_embedding(tokens, params["wte"])
        wpe = params["wpe"]
        positions = jnp.clip(
            pos[:, None] + jnp.arange(E, dtype=jnp.int32)[None, :],
            0, wpe.shape[0] - 1)
        x = x + jnp.take(wpe, positions, axis=0).astype(x.dtype)
        x, k, v = T.stack_extend(x, params["blocks"], cfg, k, v, rows,
                                 pos, n_new)
        x = L.layer_norm(x, params["lnf_s"], params["lnf_b"], cfg.ln_eps)
        logits = L.vocab_parallel_logits(x, params["wte"])
        return logits, k, v

    def apply_decode(self, params, tokens, k, v, pos, active, rows,
                     ring: bool = False):
        """One incremental decode step (runs inside shard_map).

        tokens: int32 [B] (this step's input token per slot); k/v:
        [L, R, n_local, d] flat page pools; pos: int32 [B] absolute
        position the new token occupies; active: bool [B] (inactive
        slots write nothing and keep their state — their logits are
        computed but meaningless); rows: int32 [B, cap] page-table row
        map.  Returns ``(logits [B, vocab/mp], k', v', pos')`` with
        ``pos' = pos + active``."""
        cfg = self.config
        cap = rows.shape[1]
        R = k.shape[1]
        write_idx = (pos % cap) if ring else jnp.clip(pos, 0, cap - 1)
        wrow = jnp.take_along_axis(rows, write_idx[:, None], axis=1)[:, 0]
        wrow = jnp.where(active, wrow, R)     # inactive → drop row
        x = L.vocab_parallel_embedding(tokens[:, None], params["wte"])
        wpe = params["wpe"]
        prow = jnp.take(wpe, jnp.clip(pos, 0, wpe.shape[0] - 1), axis=0)
        x = x + prow[:, None].astype(x.dtype)
        x, k, v = T.stack_decode(x, params["blocks"], cfg, k, v, pos,
                                 rows, wrow, ring=ring)
        x = L.layer_norm(x, params["lnf_s"], params["lnf_b"], cfg.ln_eps)
        logits = L.vocab_parallel_logits(x[:, 0], params["wte"])
        return logits, k, v, pos + active.astype(jnp.int32)

    def apply(self, params, tokens, labels):
        """tokens, labels: int32 [B, T]; labels < 0 are ignored.  Returns the
        mean per-token LM loss (fp32 scalar, local to the DP shard — the
        engine pmean's across data) plus any stack auxiliary loss."""
        cfg = self.config
        T_len = tokens.shape[1]
        params, z3_deferred = T.zero3_enter(params, self.zero3_dims)
        with S.scope("embed"):
            x = L.vocab_parallel_embedding(tokens, params["wte"])
            x = x + L.seq_shard_positions(params["wpe"], T_len).astype(
                x.dtype)[None]
        x, aux = self._stack(x, params["blocks"],
                             z3_dims=z3_deferred.get("blocks"))
        with S.scope("head"):
            x = L.layer_norm(x, params["lnf_s"], params["lnf_b"], cfg.ln_eps)
            logits = L.vocab_parallel_logits(x, params["wte"])
            loss = L.vocab_parallel_cross_entropy(logits, labels)
            return L.masked_mean_loss(loss, labels >= 0) + aux

    __call__ = apply

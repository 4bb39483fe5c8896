"""Operations and bytes an algorithm needs, from shapes alone.

The numerators of ``mfu`` and of a kernel's roofline share.  They count what
the forward and backward passes REQUIRE: an operation the program repeats to
save memory (activation recomputation), pads (vocabulary rows, head dims) or
adds by its choice of kernel (a gather done as a one-hot matmul) is not here,
so the same model under ``selective`` and ``full`` recomputation has the same
FLOPs per token.  Nothing in this file imports the program.
"""


def train_flops_per_token(*, layers, hidden, ffn, seq, vocab, causal,
                          labeled_per_seq, head_dense):
    """Matmul FLOPs one token of a training step requires, forward and
    backward (the backward is twice the forward: one matmul for the input
    gradient, one for the weight gradient), as a dict of parts and ``total``.

    * ``body``: 6 x the block stack's matmul parameters (QKV, attention
      output, the two FFN matrices; biases, LayerNorms and embeddings have no
      matmul).
    * ``attention``: the score (QK^T) and value (PV) matmuls, 2 x 2·seq·hidden
      FLOPs per token per layer forward, x3 with the backward; a causal model
      needs the lower triangle only and counts half.
    * ``head``: the output head over the positions that carry a label,
      spread over the sequence's tokens — the vocabulary projection
      (``hidden`` x ``vocab``), preceded in BERT by a ``hidden`` x ``hidden``
      transform (``head_dense``).
    """
    body = 6.0 * layers * (4 * hidden * hidden + 2 * hidden * ffn)
    attention = 12.0 * layers * seq * hidden * (0.5 if causal else 1.0)
    head_params = hidden * vocab + (hidden * hidden if head_dense else 0)
    head = 6.0 * head_params * labeled_per_seq / seq
    return {"body": body, "attention": attention, "head": head,
            "total": body + attention + head}


def attention_kernel_cost(*, rows, seq, heads, head_dim, causal, itemsize,
                          direction):
    """(FLOPs, bytes) one call of a fused attention kernel needs on ``rows``
    sequences.

    Forward: the two matmuls QK^T and PV; reads Q, K, V and writes O plus
    one fp32 log-sum-exp per query.  Backward (flash-attention style, which
    keeps no probabilities): five matmuls — the scores again, dP = dO·V^T,
    dV = P^T·dO, dQ = dS·K, dK = dS^T·Q; reads Q, K, V, O, dO and the
    log-sum-exp, writes dQ, dK, dV.  A causal call needs half the
    (query, key) pairs.  A backward split into two kernels still needs only
    this much.
    """
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")
    pairs = rows * heads * seq * seq * (0.5 if causal else 1.0)
    matmuls, tensors = (2, 4) if direction == "fwd" else (5, 8)
    flops = 2.0 * pairs * head_dim * matmuls
    nbytes = (tensors * rows * seq * heads * head_dim * itemsize
              + rows * heads * seq * 4)
    return flops, float(nbytes)


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take for ``flops`` operations on
    ``nbytes`` bytes, and which peak bounds it: ``(seconds, "compute" |
    "memory")``."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))

"""Names of the residuals the ``selective`` recomputation policy keeps.

``models/transformer.remat_wrap`` builds ``save_only_these_names`` from
``SELECTIVE_SAVES``; the code that produces such a tensor tags it with
``jax.ad_checkpoint.checkpoint_name`` under the constant below.  A name is
listed when replaying its tensor costs a Pallas call or a whole matmul and
the tensor is no larger than a block's hidden state (``FFN1``: the FFN
width).  Which names exist in a program follows from the program: a pre-LN
block has no ``POST_LN_SUM``, an XLA attention plan no ``ATTN_OUT`` /
``ATTN_LSE``.  Bytes per layer, in the compute dtype (``rows`` = micro-batch
x sequence, ``h`` hidden, ``ffn`` FFN width, ``n`` heads):

* ``QKV``          3 x rows x h   packed q/k/v projection output
* ``FFN1``         rows x ffn     first FFN matmul, before the activation
                                  (SwiGLU: gate and up, 2 x rows x ffn)
* ``ATTN_OUT``     rows x h       streaming kernel's output, unfolded
                                  [B, T, n, d] (folded [G, T, d] a head
                                  size of 64 is lane-padded to twice that)
* ``ATTN_LSE``     rows x n x 4   its fp32 log-sum-exp [G, 1, T]
* ``POST_LN_SUM``  2 x rows x h   post-LN block: ``x + attn(x)`` and
                                  ``x + ffn(x)``, the two LayerNorms' inputs
"""

QKV = "qkv"
FFN1 = "ffn1"
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"
POST_LN_SUM = "post_ln_sum"

SELECTIVE_SAVES = (QKV, FFN1, ATTN_OUT, ATTN_LSE, POST_LN_SUM)

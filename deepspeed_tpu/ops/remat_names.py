"""Names of the residuals the recomputation policies keep.

``models/transformer.remat_wrap`` builds ``save_only_these_names`` from one
of the two tuples below; the code that produces such a tensor tags it with
``jax.ad_checkpoint.checkpoint_name`` under its constant.

``FULL_SAVES``: ``full`` saves each layer application's input and the
residuals of a Pallas kernel, and replays everything XLA computes.  The
streaming kernel's output and log-sum-exp are its backward's own residuals
and no XLA replay rebuilds them for less than the whole kernel call:
``rows x h`` in the compute dtype + ``4 x rows x n`` bytes a layer.

``SELECTIVE_SAVES``: those, and what costs a whole matmul to replay and is
no larger than a block's hidden state (``FFN1``: the FFN width).

Which names exist in a program follows from the program: a pre-LN block has
no ``POST_LN_SUM``, an XLA attention plan no ``ATTN_OUT`` / ``ATTN_LSE``
(under ``full`` it then saves the input alone).  Bytes per layer, in the
compute dtype (``rows`` = micro-batch x sequence, ``h`` hidden, ``ffn`` FFN
width, ``n`` heads):

* ``QKV``          3 x rows x h   packed q/k/v projection output (latent
                                  attention: the queries, the down
                                  projection and the up projection's
                                  per-head keys and values)
* ``FFN1``         rows x ffn     first FFN matmul, before the activation
                                  (SwiGLU: gate and up, 2 x rows x ffn; a
                                  dropless expert layer's grouped gate and
                                  up products over EVERY (token, choice)
                                  pair, its static worst case: 2 x rows x k
                                  x expert width)
* ``ATTN_OUT``     rows x h       streaming kernel's output, unfolded
                                  [B, T, n, d] (folded [G, T, d] a head
                                  size of 64 is lane-padded to twice that)
* ``ATTN_LSE``     rows x n x 4   its fp32 log-sum-exp [G, 1, T]
* ``POST_LN_SUM``  2 x rows x h   post-LN block: ``x + attn(x)`` and
                                  ``x + ffn(x)``, the two LayerNorms' inputs
* ``MIXER_IN``     rows x E       a state-space mixer's input projections
                                  (``u`` and ``z``, 2 x rows x E) and a
                                  Gated Memory Unit's gate, before the
                                  activation (``E`` state-space channels)
* ``SCAN_OUT``     rows x E       the selective scan's output ``y``
* ``SCAN_STATES``  4 x rows x E x N / chunk   its fp32 states at the chunk
                                  boundaries: with ``y``, the scan's own
                                  residuals — saved, the backward does not
                                  run the forward scan a second time
* ``DELTA_OUT``    rows x Hv x dv the gated delta rule's output ``o``
* ``DELTA_STATES`` 4 x rows x Hv x dk x dv / chunk   its fp32 matrix states
                                  at the chunk boundaries (537 MB a layer
                                  at 16,384 rows, 32 heads of 128 x 128,
                                  chunk 64): with ``o``, the rule's own
                                  residuals, as the scan's two
"""

QKV = "qkv"
FFN1 = "ffn1"
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"
POST_LN_SUM = "post_ln_sum"
MIXER_IN = "mixer_in"
SCAN_OUT = "scan_out"
SCAN_STATES = "scan_states"
DELTA_OUT = "delta_out"
DELTA_STATES = "delta_states"

FULL_SAVES = (ATTN_OUT, ATTN_LSE)
SELECTIVE_SAVES = ((QKV, FFN1) + FULL_SAVES
                   + (POST_LN_SUM, MIXER_IN, SCAN_OUT, SCAN_STATES,
                      DELTA_OUT, DELTA_STATES))

"""Selective state-space scan (Mamba-1) and its causal depthwise convolution.

``selective_scan(u, delta, A, B, C, D)`` computes, per row and channel,

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) * B_t      h_0 = 0
    y_t = sum_n h_t[n] * C_t[n] + D * u_t

with ``u``/``delta`` ``[rows, T, E]``, ``A`` ``[E, N]`` (negative), ``B``/``C``
``[rows, T, N]`` and ``D`` ``[E]``.  The recurrence runs in float32 whatever
the inputs' dtype; ``y`` comes back in ``u``'s dtype.

Chunked over time, forward and backward, so that no ``[T, E, N]`` array is
ever in HBM: the carry between chunks is the state ``[rows, N, E]`` float32
(``E`` minor: ``N`` = 16 on the 128-lane axis would be padded eightfold),
the forward saves its inputs and the state at every chunk's START
(``T / chunk`` states), and the backward walks the chunks in reverse,
running each chunk's recurrence again and differentiating it there — what
it holds of size ``[chunk, N, E]`` lives for one chunk.  A chunk is a
``lax.scan`` over its steps that XLA compiles (PERF.md, PR 31, has the
device times at ``T`` 8192, ``E`` 5120); a Pallas chunk would take this
function's place under the same scope and join ``remat_names.FULL_SAVES``.

``causal_conv1d(u, w, b)``: ``y_t = b + sum_k w[k] * u_{t-(K-1)+k}``, ``w``
``[K, E]`` (``w[K-1]`` weighs the current step), zeros before the start.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops.remat_names import SCAN_OUT, SCAN_STATES

#: steps per chunk: 64 boundary states at T 8192 (21 MB at E 5120, N 16).  On
#: a v5e at rows 1, T 8192, E 5120, bf16 inputs, forward / forward +
#: backward: 10.1 / 21.0 ms at 64, 4.4 / 19.8 ms at 128, 4.4 / 21.2 ms at
#: 256, 4.4 / 19.6 ms at 512; with blocks of 16 steps 5.2 / 36.9 ms at 256,
#: of 4 steps 6.9 / 19.7 ms at 64 (my chip run, PR 31)
SCAN_CHUNK = 128
#: steps of a chunk's loop written out per iteration of the compiled loop
#: (a chunk that is no multiple of it runs step by step)
SCAN_UNROLL = 8


def _chunk(h0, A_t, D, dt, u, B, C):
    """One chunk's recurrence.  ``h0`` [rows, N, E] float32, ``A_t`` [N, E],
    ``D`` [E] float32; ``dt``/``u`` [L, rows, E] and ``B``/``C`` [L, rows, N]
    in any float dtype.  Returns ``(h_L, y [L, rows, E] float32)``.

    A loop over blocks of ``SCAN_UNROLL`` steps written out, each block
    under ``jax.checkpoint``: differentiated, the chunk keeps the state at
    the start of every block and runs a block's steps again beside their
    backward steps, inside one loop body — the per-step states, decays and
    products stay on the chip instead of being stacked in HBM (the
    differentiated plain loop was bound by those stacks: 36.6 ms a layer's
    backward at T 8192, E 5120; PERF.md, PR 31)."""
    def step(h, xs):
        dt_t, u_t, b_t, c_t = (x.astype(jnp.float32) for x in xs)
        decay = jnp.exp(dt_t[:, None, :] * A_t[None])
        h = decay * h + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(h * c_t[:, :, None], axis=1) + D[None] * u_t
        return h, y

    length = dt.shape[0]
    block = SCAN_UNROLL if length % SCAN_UNROLL == 0 else 1

    @jax.checkpoint
    def steps(h, xs):
        ys = []
        for i in range(block):
            h, y = step(h, tuple(x[i] for x in xs))
            ys.append(y)
        return h, jnp.stack(ys)

    blocks = tuple(x.reshape(length // block, block, *x.shape[1:])
                   for x in (dt, u, B, C))
    h, y = jax.lax.scan(steps, h0, blocks)
    return h, y.reshape(length, *y.shape[2:])


def _chunks(x, length, n_chunks):
    """[rows, T, F] -> time-major chunks [n_chunks, length, rows, F], the
    tail padded with zeros (a padded step has delta 0: the state stands
    still and y is 0)."""
    rows, T, F = x.shape
    x = jnp.pad(x, ((0, 0), (0, n_chunks * length - T), (0, 0)))
    return jnp.moveaxis(x, 1, 0).reshape(n_chunks, length, rows, F)


def _unchunk(x, T):
    """[n_chunks, length, rows, F] -> [rows, T, F]."""
    n, length, rows, F = x.shape
    return jnp.moveaxis(x.reshape(n * length, rows, F)[:T], 0, 1)


def _forward(u, delta, A, B, C, D):
    rows, T, E = u.shape
    length = min(SCAN_CHUNK, T)
    n_chunks = -(-T // length)
    A_t = A.astype(jnp.float32).T
    Df = D.astype(jnp.float32)
    xs = tuple(_chunks(x, length, n_chunks) for x in (delta, u, B, C))

    def outer(h, x):
        h_end, y = _chunk(h, A_t, Df, *x)
        return h_end, (y.astype(u.dtype), h)

    h0 = jnp.zeros((rows, A.shape[1], E), jnp.float32)
    _, (y, starts) = jax.lax.scan(outer, h0, xs)
    return _unchunk(y, T), starts


@jax.custom_vjp
def selective_scan(u, delta, A, B, C, D):
    """See the module docstring."""
    return _forward(u, delta, A, B, C, D)[0]


def _scan_vjp_fwd(u, delta, A, B, C, D):
    y, starts = _forward(u, delta, A, B, C, D)
    # named for the "selective" recomputation policy: with both saved the
    # replayed forward scan has no consumer and is dropped (as the streaming
    # attention kernel's output and log-sum-exp are, pallas_attention.py)
    y, starts = checkpoint_name(y, SCAN_OUT), checkpoint_name(starts,
                                                              SCAN_STATES)
    return y, (u, delta, A, B, C, D, starts)


def _scan_vjp_bwd(res, dy):
    u, delta, A, B, C, D, starts = res
    T = u.shape[1]
    n_chunks, rows, N, E = starts.shape
    length = min(SCAN_CHUNK, T)
    A_t = A.astype(jnp.float32).T
    Df = D.astype(jnp.float32)
    xs = tuple(_chunks(x, length, n_chunks) for x in (delta, u, B, C, dy))

    def outer(carry, x):
        dh, dA, dD = carry
        h0, dt, uu, bb, cc, dyy = x
        _, pull = jax.vjp(_chunk, h0, A_t, Df, dt, uu, bb, cc)
        dh0, dA_c, dD_c, ddt, du, db, dc = pull(
            (dh, dyy.astype(jnp.float32)))
        return (dh0, dA + dA_c, dD + dD_c), (ddt, du, db, dc)

    zeros = (jnp.zeros((rows, N, E), jnp.float32),
             jnp.zeros((N, E), jnp.float32), jnp.zeros((E,), jnp.float32))
    (_, dA_t, dD), grads = jax.lax.scan(outer, zeros, (starts, *xs),
                                        reverse=True)
    ddelta, du, dB, dC = (_unchunk(g, T) for g in grads)
    return (du, ddelta, dA_t.T.astype(A.dtype), dB, dC, dD.astype(D.dtype))


selective_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _shifted_sum(x, w, T):
    """``sum_k w[k] * x[:, k:k+T]`` in float32, ``x`` [rows, T+K-1, E]."""
    wf = w.astype(jnp.float32)
    return sum(wf[k] * x[:, k:k + T].astype(jnp.float32)
               for k in range(w.shape[0]))


@jax.custom_vjp
def causal_conv1d(u, w, b):
    """Causal depthwise convolution over time: ``u`` [rows, T, E], ``w``
    [K, E], ``b`` [E]; see the module docstring."""
    K = w.shape[0]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    y = _shifted_sum(padded, w, u.shape[1]) + b.astype(jnp.float32)
    return y.astype(u.dtype)


def _conv_vjp_fwd(u, w, b):
    return causal_conv1d(u, w, b), (u, w, b)


def _conv_vjp_bwd(res, dy):
    u, w, b = res
    K, T = w.shape[0], u.shape[1]
    # du_s = sum_k w[k] * dy_{s+(K-1)-k}: the same sum, the taps reversed,
    # over dy padded at the END
    du = _shifted_sum(jnp.pad(dy, ((0, 0), (0, K - 1), (0, 0))), w[::-1], T)
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dw = jnp.stack([jnp.sum(dyf * padded[:, k:k + T], axis=(0, 1))
                    for k in range(K)])
    return (du.astype(u.dtype), dw.astype(w.dtype),
            jnp.sum(dyf, axis=(0, 1)).astype(b.dtype))


causal_conv1d.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)

"""Fused Pallas attention vs the XLA reference math (interpret mode).

The kernel computes QK^T -> mask -> softmax -> .V (and the flash-style
backward) entirely in VMEM; these tests pin forward and gradient parity
against a plain-JAX reference for every mask mode, plus the shape gate.
"""

import dataclasses
import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import remat_wrap
from deepspeed_tpu.ops import pallas_attention as pattn

B, T, N, D = 4, 32, 2, 16


def reference(q, k, v, mask, causal):
    scores = jnp.einsum("btnd,bsnd->bnts", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if causal:
        cmask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        scores = jnp.where(cmask[None, None], scores, -1e9)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :].astype(jnp.bool_),
                           scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnts,bsnd->btnd", probs, v)


def rand_qkv(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B, T, N, D)).astype(np.float32), dtype)
    return mk(), mk(), mk()


def pad_mask():
    m = np.ones((B, T), np.float32)
    m[:, T - 5:] = 0.0
    return jnp.asarray(m)


@pytest.mark.parametrize("causal,masked", [
    (False, False), (True, False), (False, True), (True, True)])
def test_forward_parity(causal, masked):
    q, k, v = rand_qkv()
    mask = pad_mask() if masked else jnp.ones((B, T), jnp.float32)
    got = pattn.fused_attention(q, k, v, mask, causal, True)
    want = reference(q, k, v, mask if masked else None, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,masked", [
    (False, False), (True, True)])
def test_gradient_parity(causal, masked):
    q, k, v = rand_qkv(seed=1)
    mask = pad_mask() if masked else jnp.ones((B, T), jnp.float32)

    def loss_fused(q, k, v):
        out = pattn.fused_attention(q, k, v, mask, causal, True)
        return jnp.sum(out * jnp.cos(out))   # nontrivial cotangent

    def loss_ref(q, k, v):
        out = reference(q, k, v, mask if masked else None, causal)
        return jnp.sum(out * jnp.cos(out))

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_masked_rows_fully_padded_are_finite():
    """A row whose mask is all zeros must not produce NaNs (softmax over
    all -1e9 logits)."""
    q, k, v = rand_qkv(seed=2)
    m = np.ones((B, T), np.float32)
    m[0, :] = 0.0
    out = pattn.fused_attention(q, k, v, jnp.asarray(m), False, True)
    assert np.all(np.isfinite(np.asarray(out)))


def test_supported_gate():
    assert pattn.supported(128, 16, 64)
    # the gate is the BACKWARD budget (ADVICE r2): 8-head block x 256^2 x 4 B
    # = 2 MB score tile exceeds the bwd half-budget even at bb=1
    assert not pattn.supported(256, 16, 64)
    assert not pattn.supported(1024, 16, 64)  # score tile too big
    assert not pattn.supported(100, 16, 64)   # unaligned seq
    assert not pattn.supported(128, 16, 63)   # unaligned head dim
    # odd head counts use the full head dim as the block
    assert pattn.supported(128, 12, 64)
    assert pattn._head_block(12) == 12
    assert pattn._head_block(16) == 8


# ------------------------------------------------------- streaming kernel

ST, SN, SD = 512, 2, 16  # seq must be a STREAM tile multiple


def stream_reference(q, k, v, mask, causal):
    scores = jnp.einsum("btnd,bsnd->bnts", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(SD, jnp.float32))
    Tn = q.shape[1]
    if causal:
        cmask = jnp.tril(jnp.ones((Tn, Tn), jnp.bool_))
        scores = jnp.where(cmask[None, None], scores, -1e9)
    scores = jnp.where(mask[:, None, None, :].astype(jnp.bool_),
                       scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnts,bsnd->btnd", probs, v)


def stream_qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(2, ST, SN, SD)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal,masked", [
    (False, False), (True, False), (False, True), (True, True)])
def test_stream_forward_parity(causal, masked):
    q, k, v = stream_qkv()
    mask = np.ones((2, ST), np.float32)
    if masked:
        mask[:, ST - 37:] = 0.0
    mask = jnp.asarray(mask)
    got = pattn.stream_attention(q, k, v, mask, causal, True)
    want = stream_reference(q, k, v, mask, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_stream_gradient_parity(causal):
    q, k, v = stream_qkv(seed=3)
    mask = np.ones((2, ST), np.float32)
    mask[:, ST - 19:] = 0.0
    mask = jnp.asarray(mask)

    def loss_s(q, k, v):
        return jnp.sum(jnp.sin(
            pattn.stream_attention(q, k, v, mask, causal, True)))

    def loss_r(q, k, v):
        return jnp.sum(jnp.sin(stream_reference(q, k, v, mask, causal)))

    gs = jax.grad(loss_s, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_stream_supported_gate():
    assert pattn.stream_supported(512, 64)
    assert pattn.stream_supported(4096, 64)
    assert not pattn.stream_supported(128, 64)   # below a tile
    assert not pattn.stream_supported(384, 64)   # not a tile multiple
    assert not pattn.stream_supported(512, 12)   # head dim not 8-aligned


def test_stream_bf16_dtype_contract():
    """bf16 inputs (the TPU training dtype): outputs/grads come back bf16
    and match an fp32 reference within bf16 rounding."""
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(rng.normal(size=(2, ST, SN, SD)), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    mask = jnp.ones((2, ST), jnp.float32)
    out = pattn.stream_attention(q, k, v, mask, True, True)
    assert out.dtype == jnp.bfloat16
    want = stream_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), mask, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)
    g = jax.grad(lambda q, k, v: jnp.sum(pattn.stream_attention(
        q, k, v, mask, True, True).astype(jnp.float32)), (0, 1, 2))(q, k, v)
    for a in g:
        assert a.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))


def test_stream_threshold_resolution(monkeypatch):
    """The auto-dispatch threshold resolves env pin > the chip's profile
    row > v5e default (the crossover is chip dependent and must be
    re-pinnable without a code change)."""
    from deepspeed_tpu.analysis import profiles
    from deepspeed_tpu.models import layers as L

    for name in ("DSTPU_STREAM_ATTN_MIN", "DSTPU_STREAM_ATTN_MIN_CAUSAL",
                 "DSTPU_STREAM_ATTN_MIN_BWD",
                 "DSTPU_STREAM_ATTN_MIN_CAUSAL_BWD"):
        monkeypatch.delenv(name, raising=False)
    # CPU test rig: its profile row carries no sweep -> the defaults,
    # causal-aware (causal crossover is lower: the streaming kernel skips
    # fully-masked KV tiles)
    assert L.stream_auto_min() == L.STREAM_AUTO_MIN
    assert L.stream_auto_min(causal=True) == L.STREAM_AUTO_MIN_CAUSAL

    monkeypatch.setitem(
        profiles.PROFILES, "cpu-8", dataclasses.replace(
            profiles.PROFILES["cpu-8"], stream_attn_min_causal=(256, 128),
            stream_attn_min_noncausal=(512, 384)))
    assert L.stream_auto_min(causal=True) == 256   # table wins default
    assert L.stream_auto_min() == 512
    # forward and backward resolve independently from the table
    assert L.stream_auto_min(causal=True, direction="bwd") == 128
    assert L.stream_auto_min(direction="bwd") == 384

    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN", "2048")
    assert L.stream_auto_min() == 2048         # env pin wins everything
    assert L.stream_auto_min(causal=True) == 2048
    assert L.stream_auto_min(causal=True, direction="bwd") == 2048

    # the causal-scoped pin (what calibrate() prints) never leaks into
    # non-causal dispatch — a causal-measured crossover would force the
    # kernel on non-causal shapes where XLA wins
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN_CAUSAL", "256")
    assert L.stream_auto_min(causal=True) == 256
    assert L.stream_auto_min() == 2048

    # direction-scoped pins beat the direction-blind ones for their
    # direction only
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN_CAUSAL_BWD", "128")
    assert L.stream_auto_min(causal=True, direction="bwd") == 128
    assert L.stream_auto_min(causal=True) == 256
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN_BWD", "512")
    assert L.stream_auto_min(direction="bwd") == 512
    assert L.stream_auto_min() == 2048

    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN", "-3")
    with pytest.raises(ValueError, match="non-negative"):
        L.stream_auto_min()
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN", "2048")
    with pytest.raises(ValueError, match="'fwd' or 'bwd'"):
        L.stream_auto_min(direction="sideways")


@pytest.mark.parametrize("causal", [False, True])
def test_stream_backward_fused_matches_split(monkeypatch, causal):
    """The single-pass fused backward (dQ/dK/dV in one kernel) must match
    the classic two-kernel split bit-for-tolerance — same tile math, only
    the recompute count and accumulation order differ."""
    q, k, v = stream_qkv(seed=11)
    mask = np.ones((2, ST), np.float32)
    mask[:, ST - 41:] = 0.0
    mask = jnp.asarray(mask)

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(jnp.tanh(
            pattn.stream_attention(q, k, v, mask, causal, True))),
            (0, 1, 2))(q, k, v)

    monkeypatch.setenv("DSTPU_STREAM_BWD", "fused")
    g_fused = grads()
    monkeypatch.setenv("DSTPU_STREAM_BWD", "split")
    g_split = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_stream_bwd_mode_validation(monkeypatch):
    monkeypatch.setenv("DSTPU_STREAM_BWD", "sideways")
    with pytest.raises(ValueError, match="DSTPU_STREAM_BWD"):
        pattn._stream_bwd_mode()
    monkeypatch.delenv("DSTPU_STREAM_BWD")
    assert pattn._stream_bwd_mode() == "auto"


MIB = 1024 * 1024
V5E_CAP = 96 * MIB      # analysis/profiles.py: the v5e row's kernel_vmem_mib

# (gb, T, d, itemsize) -> the verdict under the v5e's cap.  The boundaries
# are what an AOT compile for a v5e accepts (tests/test_tpu_aot_kernels.py
# compiles the T 8192 calls under the limits asked here): inside Mosaic's
# 16 MiB default the call asks for nothing; d=64 pads to 128 lanes, so it
# costs what d=128 does, d=192 what d=256 does
PLAN_CASES = [
    ((2, 512, 64, 2), ("fused", None)),
    ((2, 4096, 64, 2), ("fused", None)),
    ((2, 8192, 64, 2), ("fused", 24 * MIB)),     # the hybrid stack's calls
    ((2, 4096, 128, 2), ("fused", None)),        # the looped model's
    ((2, 2048, 128, 2), ("fused", None)),
    ((2, 1024, 128, 4), ("fused", None)),
    ((2, 2048, 128, 4), ("fused", 18 * MIB)),
    ((2, 1024, 192, 2), ("fused", None)),
    ((2, 4096, 192, 2), ("fused", 28 * MIB)),
    ((2, 4096, 256, 2), ("fused", 28 * MIB)),
    ((2, 8192, 192, 2), ("fused", 44 * MIB)),    # the latent core's
    ((2, 16384, 192, 2), ("fused", 76 * MIB)),
    ((2, 32768, 192, 2), ("split", None)),       # 140 MiB: past the cap
    ((2, 65536, 64, 2), ("split", None)),
]


@pytest.mark.parametrize("shape,want", PLAN_CASES,
                         ids=["-".join(map(str, c[0])) for c in PLAN_CASES])
def test_stream_bwd_plan_reads_the_verdict_off_the_shape(shape, want):
    """Three outcomes: fused with no compiler parameters inside Mosaic's
    default, fused under the limit it needs up to the generation's cap,
    the split past it; without a declared cap nothing is asked for, and
    DSTPU_STREAM_BWD pins either kernel."""
    assert pattn.stream_bwd_plan(*shape, V5E_CAP) == want
    need = pattn.fused_bwd_vmem(*shape)
    if want == ("fused", None):
        assert need <= pattn.VMEM_SCOPED_LIMIT
        assert pattn.stream_bwd_plan(*shape, None) == want
    else:
        assert need > pattn.VMEM_SCOPED_LIMIT
        assert pattn.stream_bwd_plan(*shape, None) == ("split", None)
        limit = pattn.stream_bwd_plan(*shape, V5E_CAP, "fused")[1]
        assert limit % MIB == 0 and 0 <= limit - need < MIB
    assert pattn.stream_bwd_plan(*shape, V5E_CAP, "split") == ("split", None)
    assert pattn.stream_bwd_plan(*shape, V5E_CAP, "fused")[0] == "fused"


def test_the_cap_is_the_profiles(monkeypatch):
    """The cap comes from the backend's row in analysis/profiles.py: the
    v5e declares one, the CPU rig traces the v5e's program, a generation
    that declares none stays inside the default."""
    from deepspeed_tpu.analysis import profiles
    assert profiles.for_device_kind("TPU v5 lite").kernel_vmem_mib == 96
    assert profiles.PROFILES["v4-8"].kernel_vmem_mib is None
    assert pattn._kernel_vmem_cap() == V5E_CAP
    monkeypatch.setattr(profiles, "default_profile",
                        lambda: profiles.PROFILES["v4-8"])
    assert pattn._kernel_vmem_cap() is None
    monkeypatch.setattr(profiles, "default_profile", lambda: None)
    assert pattn._kernel_vmem_cap() is None


def _bwd_calls(G, T, d, dv, dtype=jnp.bfloat16):
    """The ``pallas_call`` equations of the streaming backward's jaxpr."""
    S = lambda *s, dt=dtype: jax.ShapeDtypeStruct(s, dt)
    args = (S(G, T, d), S(G, T, d), S(G, T, dv), S(G, 1, T, dt=jnp.float32),
            S(G, T, dv), S(G, 1, T, dt=jnp.float32), S(G, T, dv))
    jaxpr = jax.make_jaxpr(lambda *a: pattn._stream_bwd_impl(
        *a, True, True))(*args)
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]


def test_the_traced_call_asks_for_its_vmem_only_past_the_default():
    """(2, 1024, 64): one call with NO compiler parameters, the program it
    always was; (2, 4096, 192 / 128), which the 16 MiB rule split: one
    call carrying ``vmem_limit_bytes``."""
    (call,) = _bwd_calls(2, 1024, 64, 64)
    assert dict(call.params["compiler_params"]) == {}
    (call,) = _bwd_calls(2, 4096, 192, 128)
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert params.vmem_limit_bytes == 28 * MIB
    assert params == type(params)(vmem_limit_bytes=28 * MIB)


def test_fused_and_split_agree_at_a_shape_the_old_rule_refused(monkeypatch):
    """G 2, T 4096, d 192 / dv 128, causal, bf16 (eight tiles a side, the
    latent core's widths): the fused backward under its limit against the
    split, same order of accumulation in fp32."""
    rng = np.random.default_rng(5)
    T = 4096
    q, k = (jnp.asarray(rng.normal(size=(1, T, 2, 192)), jnp.bfloat16)
            for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(1, T, 2, 128)), jnp.bfloat16)
            for _ in range(2))
    mask = jnp.ones((1, T), jnp.float32)
    assert pattn.stream_bwd_plan(2, T, 192, 2, V5E_CAP)[1] is not None

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(
            pattn.stream_attention(q, k, v, mask, True, True)
            .astype(jnp.float32) * w), (0, 1, 2))(q, k, v)

    monkeypatch.setenv("DSTPU_STREAM_BWD", "split")
    g_split = grads()
    monkeypatch.delenv("DSTPU_STREAM_BWD")
    g_fused = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------- hybrid fwd/bwd dispatch

STREAM_COMBOS = [("stream", "stream"), ("stream", "xla"), ("xla", "stream")]
BLOCK_COMBOS = [("block", "block"), ("block", "xla"), ("xla", "block")]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("fwd_impl,bwd_impl", STREAM_COMBOS)
def test_dispatch_stream_combos_parity(causal, fwd_impl, bwd_impl):
    """Mixed forward/backward kernel choices (the per-direction dispatch
    table) agree with the all-XLA reference at seq 512, fwd AND grad."""
    q, k, v = stream_qkv(seed=5)
    mask = np.ones((2, ST), np.float32)
    mask[:, ST - 23:] = 0.0
    mask = jnp.asarray(mask)

    def loss_d(q, k, v):
        return jnp.sum(jnp.sin(pattn.dispatch_attention(
            q, k, v, mask, causal, fwd_impl, bwd_impl, True)))

    def loss_r(q, k, v):
        return jnp.sum(jnp.sin(stream_reference(q, k, v, mask, causal)))

    np.testing.assert_allclose(
        np.asarray(pattn.dispatch_attention(q, k, v, mask, causal,
                                            fwd_impl, bwd_impl, True)),
        np.asarray(stream_reference(q, k, v, mask, causal)),
        rtol=2e-5, atol=2e-5)
    gd = jax.grad(loss_d, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("fwd_impl,bwd_impl", BLOCK_COMBOS)
def test_dispatch_block_combos_parity(causal, fwd_impl, bwd_impl):
    q, k, v = rand_qkv(seed=6)
    mask = pad_mask()

    def loss_d(q, k, v):
        return jnp.sum(jnp.cos(pattn.dispatch_attention(
            q, k, v, mask, causal, fwd_impl, bwd_impl, True)))

    def loss_r(q, k, v):
        return jnp.sum(jnp.cos(reference(q, k, v, mask, causal)))

    np.testing.assert_allclose(
        np.asarray(pattn.dispatch_attention(q, k, v, mask, causal,
                                            fwd_impl, bwd_impl, True)),
        np.asarray(reference(q, k, v, mask, causal)),
        rtol=1e-5, atol=1e-5)
    gd = jax.grad(loss_d, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_dispatch_rejects_block_then_stream():
    q, k, v = rand_qkv()
    mask = jnp.ones((B, T), jnp.float32)
    with pytest.raises(ValueError, match="logsumexp"):
        pattn.dispatch_attention(q, k, v, mask, False, "block", "stream",
                                 True)
    with pytest.raises(ValueError, match="impls must be one of"):
        pattn.dispatch_attention(q, k, v, mask, False, "nope", "xla", True)


# ------------------------------ residuals named for the recomputation policies

def grad_jaxpr_text(wrap, attention):
    """jaxpr text of the gradient of ``attention(q, k, v)`` as a scan body
    that went through ``wrap``."""
    body = wrap(lambda c, _: (attention(c, 2.0 * c, 3.0 * c), None))
    q, _, _ = stream_qkv(seed=9)
    return str(jax.make_jaxpr(
        jax.grad(lambda q: jnp.sum(body(q, None)[0])))(q))


def counter(text):
    """How often a primitive occurs in a jaxpr's text."""
    return lambda prim: len(re.findall(rf"\b{prim}\b", text))


def under(policy):
    """``transformer.remat_wrap`` under ``policy``, as a ``wrap``."""
    cfg = types.SimpleNamespace(remat=True, remat_policy=policy)
    return lambda body: remat_wrap(body, cfg)


def grad_jaxpr_under(policy, attention):
    return counter(grad_jaxpr_text(under(policy), attention))


ONES = jnp.ones((2, ST), jnp.float32)
NAMED_KERNELS = {
    "stream_attention": lambda q, k, v: pattn.stream_attention(
        q, k, v, ONES, True, True),
    "dispatch-stream-stream": lambda q, k, v: pattn.dispatch_attention(
        q, k, v, ONES, True, "stream", "stream", True),
}
XLA_FORWARD_AND_BACKWARD = {
    "dispatch-xla-xla": lambda q, k, v: pattn.dispatch_attention(
        q, k, v, ONES, True, "xla", "xla", True),
    "xla_attention": lambda q, k, v: pattn.xla_attention(
        q, k, v, ONES, True)[0],
}


@pytest.mark.parametrize("policy,calls", [("selective", 2), ("full", 2)])
@pytest.mark.parametrize("kernel", sorted(NAMED_KERNELS))
def test_selective_remat_drops_the_replayed_forward_kernel(kernel, policy,
                                                           calls):
    """The forward kernel's output and log-sum-exp carry checkpoint names,
    so under ``selective`` and under ``full`` the backward pass holds the
    forward and the fused backward ``pallas_call`` and no replay of the
    forward: no policy replays a Pallas call."""
    count = grad_jaxpr_under(policy, NAMED_KERNELS[kernel])
    assert count("pallas_call") == calls


@pytest.mark.parametrize("kernel", sorted(NAMED_KERNELS))
def test_checkpoint_with_no_policy_would_replay_the_forward_kernel(kernel):
    """What the names are for: ``jax.checkpoint`` with no policy (``full``
    as it was) runs the forward ``pallas_call`` a second time."""
    count = counter(grad_jaxpr_text(jax.checkpoint, NAMED_KERNELS[kernel]))
    assert count("pallas_call") == 3


@pytest.mark.parametrize("attention", sorted(XLA_FORWARD_AND_BACKWARD))
def test_full_remat_on_the_xla_plan_is_checkpoint_with_no_policy(attention):
    """A program on the XLA attention plan has neither name, so ``full``
    saves the body's input alone: the gradient's jaxpr is that of
    ``jax.checkpoint`` with no policy, but for the ``policy=`` parameter
    the remat equation prints."""
    fn = XLA_FORWARD_AND_BACKWARD[attention]
    strip = lambda text: re.sub(r"policy=[^\n]*", "policy=", text)
    full = grad_jaxpr_text(under("full"), fn)
    assert "save_only_these_names" in full
    assert strip(full) == strip(grad_jaxpr_text(jax.checkpoint, fn))


def test_remat_keeps_an_xla_forward_for_a_stream_backward():
    """Mixed plan ("xla", "stream"): the same two names save the einsum
    forward's output and log-sum-exp (the backward kernel's residuals), so
    under ``selective`` and ``full`` its two matmuls are not replayed."""
    mixed = lambda q, k, v: pattn.dispatch_attention(
        q, k, v, ONES, True, "xla", "stream", True)
    sel, full = (grad_jaxpr_under(p, mixed) for p in ("selective", "full"))
    none = counter(grad_jaxpr_text(jax.checkpoint, mixed))
    assert sel("pallas_call") == full("pallas_call") == 1
    assert sel("dot_general") == full("dot_general")
    assert none("dot_general") - sel("dot_general") == 2


def test_attention_plan_directions(monkeypatch):
    """The auto plan resolves forward and backward independently, uses the
    whole-tile kernel for short causal shapes (the committed seq-128 causal
    sweep row), and keeps XLA for short non-causal shapes."""
    from deepspeed_tpu.models import layers as L

    for name in ("DSTPU_STREAM_ATTN_MIN", "DSTPU_STREAM_ATTN_MIN_CAUSAL",
                 "DSTPU_STREAM_ATTN_MIN_BWD", "DSTPU_FUSED_ATTN",
                 "DSTPU_STREAM_ATTN_MIN_CAUSAL_BWD",
                 "DSTPU_BLOCK_ATTN_MIN_CAUSAL"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN_CAUSAL", "1024")
    monkeypatch.setenv("DSTPU_STREAM_ATTN_MIN_CAUSAL_BWD", "512")
    # seq 512 causal, 12 heads d64: stream supported; only the backward
    # threshold admits it; whole-tile kernel doesn't fit 512 -> fwd XLA
    assert L.attention_plan(512, 12, 64, causal=True) == ("xla", "stream")
    # seq 128 causal: below both stream tiles -> the whole-tile kernel
    # from the sweep (1.127x) both directions
    assert L.attention_plan(128, 12, 64, causal=True) == ("block", "block")
    monkeypatch.setenv("DSTPU_BLOCK_ATTN_MIN_CAUSAL", "0")
    assert L.attention_plan(128, 12, 64, causal=True) == ("xla", "xla")
    # non-causal short: XLA (0.92x measured) regardless of block support
    assert L.attention_plan(128, 16, 64, causal=False) == ("xla", "xla")
    # force mode: one kernel, both directions
    monkeypatch.setenv("DSTPU_FUSED_ATTN", "1")
    assert L.attention_plan(512, 12, 64, causal=True) == ("stream", "stream")
    assert L.attention_plan(128, 12, 64, causal=False) == ("block", "block")
    monkeypatch.setenv("DSTPU_FUSED_ATTN", "0")
    assert L.attention_plan(2048, 12, 64, causal=True) == ("xla", "xla")


def test_calibrate_requires_tpu(monkeypatch):
    # force a non-TPU answer so the guard path runs everywhere (on a real
    # chip the unguarded call would execute the full sweep instead)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(RuntimeError, match="TPU backend"):
        pattn.calibrate_stream_threshold()


# ------------------------------ window, shared k/v heads, a wider value head
# (PR 31).  The streaming kernels in interpret mode against ``xla_attention``
# with the same mask.

def _window_case(T_len, n_q, n_k, n_v, d, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, T_len, n_q, d))
    k = jax.random.normal(ks[1], (1, T_len, n_k, d))
    v = jax.random.normal(ks[2], (1, T_len, n_v, dv))
    weight = jax.random.normal(ks[3], (1, T_len, n_q, dv))
    return q, k, v, weight, jnp.ones((1, T_len), jnp.float32)


def _value_and_grads(fn, q, k, v, weight):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
            argnums=(0, 1, 2))(q, k, v)


def _assert_same(got, want):
    (a, ga), (b, gb) = got, want
    assert float(a) == pytest.approx(float(b), rel=1e-5, abs=1e-4)
    for name, x, y in zip("qkv", ga, gb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=2e-5, err_msg="d" + name)


@pytest.mark.parametrize("T_len,window,mode", [
    (1024, 128, "auto"), (1024, 512, "split"), (1536, 640, "auto"),
    (2048, 512, "split")])
def test_stream_window_parity(monkeypatch, T_len, window, mode):
    """Forward and the three gradients under a sliding window, fused and
    split backward; 640 spans three kv tiles of 512."""
    monkeypatch.setenv("DSTPU_STREAM_BWD", mode)
    q, k, v, weight, mask = _window_case(T_len, 2, 2, 2, 64, 64, seed=window)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window)[0], q, k, v, weight)
    _assert_same(got, want)
    # and the window does what it says: a dense reference
    pos = jnp.arange(T_len)
    seen = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
    scores = jnp.einsum("btnd,bsnd->bnts", q, k, precision="highest") / 8.0
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    dense = jnp.einsum("bnts,bsnd->btnd", probs, v, precision="highest")
    np.testing.assert_allclose(
        np.asarray(pattn.stream_attention(q, k, v, mask, True, True, window)),
        np.asarray(dense), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window,mode", [(None, "auto"), (512, "split"),
                                         (512, "fused")])
def test_stream_shared_heads_and_a_wider_value_head(monkeypatch, window,
                                                    mode):
    """8 query heads over 4 key heads (group 2) and 2 value heads of twice
    the width (group 4) — differential attention's call — against the
    same call on materialised repeats."""
    monkeypatch.setenv("DSTPU_STREAM_BWD", mode)
    q, k, v, weight, mask = _window_case(1024, 8, 4, 2, 64, 128)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 4, axis=2), mask, True,
        True, window), q, k, v, weight)
    _assert_same(got, want)
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    _assert_same(got, _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window)[0], q, k, v, weight))


def _pallas_grids(fn, *args):
    """The grids of the ``pallas_call``s in ``fn``'s gradient, in order."""
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2)))(*args)
    grids, pending = [], [jaxpr.jaxpr]
    while pending:
        for eqn in pending.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            pending.extend(jax.core.jaxprs_in_params(eqn.params))
    return grids


def test_a_windowed_call_walks_only_the_tiles_in_its_window(monkeypatch):
    """T 8192, window 512, tiles of 512: the kv axis of the forward's grid
    (and the inner axis of both backward kernels') is 2 long, not 16 — 31 of
    the causal triangle's 136 tile pairs are visited, none fetched beyond."""
    monkeypatch.setenv("DSTPU_STREAM_BWD", "split")
    q = jnp.zeros((1, 8192, 4, 64), jnp.bfloat16)
    k = jnp.zeros((1, 8192, 2, 64), jnp.bfloat16)
    v = jnp.zeros((1, 8192, 1, 128), jnp.bfloat16)
    mask = jnp.ones((1, 8192), jnp.float32)
    run = lambda window: _pallas_grids(
        lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True, True, window).astype(jnp.float32), q, k, v)
    assert run(512) == [(2, 16, 2), (2, 16, 2), (2, 16, 2)]
    assert run(None) == [(2, 16, 16), (2, 16, 16), (2, 16, 16)]
    # the first query of a tile sees window - 1 keys back: 513 still ends
    # in the tile before, 514 reaches a third
    assert run(513) == [(2, 16, 2)] * 3
    assert run(514) == [(2, 16, 3)] * 3 and run(640) == [(2, 16, 3)] * 3
    assert run(8192) == [(2, 16, 16)] * 3
    assert pattn._window_tiles(512, 512, 16) == 2
    assert pattn._window_tiles(1, 512, 16) == 1


def test_window_and_shared_heads_are_refused_where_not_built():
    q, k, v, _, mask = _window_case(256, 4, 4, 4, 16, 16)
    with pytest.raises(ValueError, match="causal mask"):
        pattn.stream_attention(q, k, v, mask, False, True, 64)
    with pytest.raises(ValueError, match="whole-tile kernel"):
        pattn.dispatch_attention(q, k, v, mask, True, "block", "block",
                                 True, 64)
    with pytest.raises(ValueError, match="whole groups"):
        pattn.stream_attention(q, k[:, :, :3], v, mask, True, True)
    from deepspeed_tpu.models import layers as L
    assert L.attention_plan(128, 12, 64, True) == ("xla", "xla")


def _normalised(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return re.sub(r" at [^\s:]+:\d+", " at FILE", text)


#: sha256 (first 16 hex digits) of the gradient's jaxpr — addresses and
#: source lines struck out — as the PARENT of PR 31 printed it (jax 0.9.0):
#: a call with no window, as many k/v heads as query heads and one head
#: size is the program the benchmark's five older cells were measured on
PARENT_JAXPRS = {
    "stream_causal_fused": "fd5abd514cfcc926",
    "stream_noncausal": "9a760c4912ccbf2f",
    "stream_causal_split": "083caf3254788b3d",
    "xla": "784faf65c0c932fc",
    "dispatch_xla_stream": "328cc465be1355ec",
}


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_a_plain_call_is_the_parents_program(monkeypatch, name):
    import hashlib
    q = jnp.zeros((2, 1024, 4, 64), jnp.bfloat16)
    mask = jnp.ones((2, 1024))
    if name == "stream_causal_split":
        monkeypatch.setenv("DSTPU_STREAM_BWD", "split")
        q, mask = jnp.zeros((1, 4096, 2, 128), jnp.bfloat16), jnp.ones(
            (1, 4096))
    call = {
        "stream_causal_fused": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True),
        "stream_noncausal": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, False),
        "stream_causal_split": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True),
        "xla": lambda q, k, v: pattn.xla_attention(q, k, v, mask, True)[0],
        "dispatch_xla_stream": lambda q, k, v: pattn.dispatch_attention(
            q, k, v, mask, True, "xla", "stream"),
    }[name]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(call(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q)
    digest = hashlib.sha256(_normalised(jaxpr).encode()).hexdigest()[:16]
    assert digest == PARENT_JAXPRS[name]

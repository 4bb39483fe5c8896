"""The streaming Pallas attention kernel vs the XLA reference math
(interpret mode), and the plan that picks one of the two per call shape.

The kernel streams QK^T -> mask -> online softmax -> .V over KV tiles in
VMEM (and the flash-style backward, fused or split); these tests pin
forward and gradient parity against a plain-JAX reference for every mask
mode, the gates, the backward's plan and the programs it traces.
"""

import functools
import math
import re
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models.transformer import remat_wrap
from deepspeed_tpu.ops import pallas_attention as pattn


PLAN = pattn.stream_bwd_plan


def force_backward(monkeypatch, kind):
    """Make every streaming backward traced under ``monkeypatch`` take
    ``kind``: "split", or "fused" under the VMEM limit it needs whatever
    the chip's cap; "auto" is ``stream_bwd_plan`` as it is."""
    forced = {"auto": PLAN,
              "fused": lambda gb, T, d, itemsize, cap: PLAN(
                  gb, T, d, itemsize, math.inf),
              "split": lambda *shape: ("split", None)}[kind]
    monkeypatch.setattr(pattn, "stream_bwd_plan", forced)


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


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_stream_gradient_parity(causal, masked):
    q, k, v = stream_qkv(seed=3)
    mask = np.ones((2, ST), np.float32)
    if masked:
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


@pytest.mark.parametrize("mode", ["fused", "split"])
@pytest.mark.parametrize("causal", [False, True])
def test_stream_fully_padded_row_is_finite(monkeypatch, causal, mode):
    """A batch row whose mask is all zeros (every logit -1e9) must give
    finite outputs and gradients, through either backward; the other row
    keeps the reference's values."""
    force_backward(monkeypatch, mode)
    q, k, v = stream_qkv(seed=2)
    mask = jnp.asarray(np.array([[0.0] * ST, [1.0] * ST], np.float32))
    out = pattn.stream_attention(q, k, v, mask, causal, True)
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(pattn.stream_attention(
        q, k, v, mask, causal, True))), (0, 1, 2))(q, k, v)
    for x in (out, *grads):
        assert np.all(np.isfinite(np.asarray(x)))
    np.testing.assert_allclose(
        np.asarray(out[1]),
        np.asarray(stream_reference(q, k, v, mask, causal)[1]),
        rtol=2e-5, atol=2e-5)


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

    force_backward(monkeypatch, "fused")
    g_fused = grads()
    force_backward(monkeypatch, "split")
    g_split = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


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
    under a cap that holds the need the limit is the need in whole MiB."""
    assert pattn.stream_bwd_plan(*shape, V5E_CAP) == want
    need = pattn.fused_bwd_vmem(*shape)
    if want == ("fused", None):
        assert need <= pattn.VMEM_SCOPED_LIMIT
        assert pattn.stream_bwd_plan(*shape, None) == want
    else:
        assert need > pattn.VMEM_SCOPED_LIMIT
        assert pattn.stream_bwd_plan(*shape, None) == ("split", None)
        kind, limit = pattn.stream_bwd_plan(*shape, need)
        assert kind == "fused" and limit % MIB == 0
        assert 0 <= limit - need < MIB
        assert pattn.stream_bwd_plan(*shape, need - 1) == ("split", None)


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

    force_backward(monkeypatch, "split")
    g_split = grads()
    force_backward(monkeypatch, "auto")
    g_fused = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------- core_attention's two paths

def core_on(plan, causal=True, attn_mask=None):
    """``layers.core_attention`` on the ``plan`` given, whatever the
    backend, the streaming kernel interpreted."""
    def attention(q, k, v):
        with mock.patch.object(L, "attention_plan",
                               lambda *shape, **kw: plan), \
                mock.patch.object(pattn, "stream_attention",
                                  functools.partial(pattn.stream_attention,
                                                    interpret=True)):
            return L.core_attention(q, k, v, causal=causal,
                                    attn_mask=attn_mask)
    return attention


@pytest.mark.parametrize("mode", ["fused", "split"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_dispatch_stream_combos_parity(monkeypatch, causal, masked, mode):
    """``core_attention`` on the streaming plan agrees with
    ``xla_attention`` at seq 512, forward AND gradients, with and without
    a padding mask, through the fused and the split backward."""
    force_backward(monkeypatch, mode)
    q, k, v = stream_qkv(seed=5)
    mask = np.ones((2, ST), np.float32)
    if masked:
        mask[:, ST - 23:] = 0.0
    mask = jnp.asarray(mask)
    core = core_on("stream", causal, mask if masked else None)
    xla = lambda q, k, v: pattn.xla_attention(q, k, v, mask, causal)
    np.testing.assert_allclose(np.asarray(core(q, k, v)),
                               np.asarray(xla(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    gd = jax.grad(loss(core), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(xla), (0, 1, 2))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


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
    "core_attention-stream": core_on("stream"),
}
XLA_FORWARD_AND_BACKWARD = {
    "core_attention-xla": core_on("xla"),
    "xla_attention": lambda q, k, v: pattn.xla_attention(
        q, k, v, ONES, True),
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


#: (T, n, d, causal, window, kv_heads) -> the plan on a TPU
PLAN_TABLE = [
    ((128, 16, 64, True, None, None), "xla"),       # under a tile
    ((128, 16, 64, False, None, None), "xla"),      # bert-large.seq128
    ((256, 12, 64, True, None, None), "xla"),       # a tile, under 512
    ((512, 12, 64, True, None, None), "stream"),
    ((512, 16, 64, False, None, None), "stream"),   # bert-large.seq512
    ((4096, 28, 128, True, 4096, 4), "stream"),     # a sliding window
    ((8192, 16, 192, True, None, 16), "stream"),    # MLA's wide head
    ((8192, 32, 128, True, None, 8), "stream"),     # grouped query heads
    ((1280, 12, 64, True, None, None), "stream"),   # tiles of 256
    ((1000, 12, 64, True, None, None), "xla"),      # no tile multiple
    ((1024, 12, 60, True, None, None), "xla"),      # head not 8-aligned
]


@pytest.mark.parametrize("shape,want", PLAN_TABLE,
                         ids=["-".join(map(str, c[0])) for c in PLAN_TABLE])
def test_attention_plan_is_one_rule_per_call_shape(monkeypatch, shape,
                                                   want):
    """On a TPU the streaming kernel from ``STREAM_MIN_SEQ`` tokens where
    it takes the length and the head width, XLA otherwise — whatever the
    mask kind, window or number of k/v heads; off a TPU always XLA."""
    T, n, d, causal, window, kv_heads = shape
    plan = lambda: L.attention_plan(T, n, d, causal, window=window,
                                    kv_heads=kv_heads)
    assert plan() == "xla"                       # the CPU rig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert plan() == want
    assert L.attention_plan(T, n, d, causal) == want


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
    force_backward(monkeypatch, mode)
    q, k, v, weight, mask = _window_case(T_len, 2, 2, 2, 64, 64, seed=window)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window), q, k, v, weight)
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
    force_backward(monkeypatch, mode)
    q, k, v, weight, mask = _window_case(1024, 8, 4, 2, 64, 128)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 4, axis=2), mask, True,
        True, window), q, k, v, weight)
    _assert_same(got, want)
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    _assert_same(got, _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window), q, k, v, weight))


@pytest.mark.parametrize("window,mode", [(None, "fused"), (512, "fused"),
                                         (512, "split")])
def test_stream_seven_query_heads_a_shared_head(monkeypatch, window, mode):
    """14 query heads over 2 key/value heads — a group of 7, SmallThinker's
    28 over 4: a pair of query heads would straddle two shared heads, so a
    program takes one (``_stream_gb``) — against the XLA path."""
    force_backward(monkeypatch, mode)
    assert pattn._stream_gb(14, 7, 7) == 1
    assert pattn._stream_gb(28, 1, 1) == pattn._stream_gb(8, 2, 4) == 2
    q, k, v, weight, mask = _window_case(1024, 14, 2, 2, 64, 64)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    _assert_same(got, _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window), q, k, v, weight))
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape


@pytest.mark.parametrize("mode", ["fused", "split"])
def test_a_scale_of_the_scores_reaches_both_directions(monkeypatch, mode):
    """``scale=`` in place of ``1 / sqrt(d)`` — Granite's ``1 / 64`` at d 64,
    8 query heads on 2 key/value heads (its 32 on 8) — in the forward and
    backward kernels alike, against the XLA path at the same scale; and the
    default is not that scale."""
    force_backward(monkeypatch, mode)
    q, k, v, weight, mask = _window_case(1024, 8, 2, 2, 64, 64, seed=4)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, scale=1 / 64), q, k, v, weight)
    _assert_same(got, _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, scale=1 / 64), q, k, v, weight))
    plain = _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True), q, k, v, weight)
    assert abs(float(plain[0]) - float(got[0])) > 1e-2 * abs(float(got[0]))


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
    force_backward(monkeypatch, "split")
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
    with pytest.raises(ValueError, match="whole groups"):
        pattn.stream_attention(q, k[:, :, :3], v, mask, True, True)
    assert L.attention_plan(128, 12, 64, True) == "xla"


# ---------- the causal band: hidden tiles neither computed nor fetched

def _any_pair_seen(i, j, tile, T, window):
    """Brute force: whether some (query, key) pair of query tile i and kv
    tile j inside a sequence of T is visible."""
    t = np.arange(i * tile, (i + 1) * tile)[:, None]
    s = np.arange(j * tile, (j + 1) * tile)[None, :]
    seen = (t >= 0) & (t < T) & (s >= 0) & (s < T) & (s <= t)
    if window is not None:
        seen &= s > t - window
    return bool(seen.any())


@pytest.mark.parametrize("T_len,tile,window", [
    (2048, 256, None), (2048, 512, None), (4096, 512, 1024),
    (4096, 512, 640), (3072, 512, 300), (2048, 256, 1), (2048, 512, 2048)],
    ids=["causal-256", "causal-512", "window-x2-tiles", "window-640",
         "window-300", "window-1", "window-whole-seq"])
def test_tile_visible_agrees_with_every_pair(T_len, tile, window):
    """``_tile_visible`` against every (query, key) pair: visible where any
    pair is; under a window also at an offset grid's indexes past either
    end of the sequence, where no tile is.  A window that is and is not a
    multiple of the tile."""
    n = T_len // tile
    span = range(-2, n + 2) if window is not None else range(n)
    for i in span:
        for j in span:
            assert bool(pattn._tile_visible(i, j, tile, tile, window, n)) \
                == _any_pair_seen(i, j, tile, T_len, window), (i, j)


def _fetches(index_of, visible_at, outer, inner):
    """Walk a grid (outer, inner) in the pipeline's order: for each block
    it fetches (a step whose block index differs from the step before),
    whether a visible step uses that block before the next fetch."""
    runs, last = [], None
    for a in range(outer):
        for b in range(inner):
            idx = int(index_of(a, b))
            if idx != last:
                runs.append(False)
                last = idx
            runs[-1] = runs[-1] or bool(visible_at(a, b))
    return runs


@pytest.mark.parametrize("T_len,window,visible", [
    (16384, None, 528), (16384, 4096, 252), (8192, None, 136),
    (4096, None, 36), (1024, None, 3), (8192, 512, 31)],
    ids=["nope-16k", "window4096-16k", "mla-8k", "looped-4k", "gpt2-1k",
         "window512-8k"])
def test_the_cells_calls_fetch_no_hidden_tile(T_len, window, visible):
    """The cells' causal calls in tiles of 512 — the NoPE call sees 528
    (query tile, kv tile) pairs of its 1,024 grid steps, the window of
    4,096 252 of 288: the forward's kv walk and the fused backward's query
    walk fetch a block only where a visible step uses it.  Without the
    clamp a causal grid fetches the hidden tiles too."""
    n = T_len // 512
    nk = pattn._window_tiles(window, 512, n)
    seen = lambda i, j: pattn._tile_visible(i, j, 512, 512, window, n)
    if window is None:
        kv_at, q_at = (lambda i, j: j), (lambda j, i: i)
    else:
        kv_at, q_at = (lambda i, j: i - (nk - 1) + j), (lambda j, i: j + i)
    fwd_seen = lambda i, j: seen(i, kv_at(i, j))
    bwd_seen = lambda j, i: seen(q_at(j, i), j)
    assert sum(bool(fwd_seen(i, j)) for i in range(n)
               for j in range(nk)) == visible
    assert sum(bool(bwd_seen(j, i)) for j in range(n)
               for i in range(nk)) == visible
    assert all(_fetches(pattn._kv_tile(True, window, nk), fwd_seen, n, nk))
    assert all(_fetches(pattn._q_tile(True, window, n), bwd_seen, n, nk))
    if window is None and n > 1:
        assert not all(_fetches(lambda i, j: j, fwd_seen, n, nk))
        assert not all(_fetches(lambda j, i: i, bwd_seen, n, nk))


def _pad_in_a_whole_tile(T_len):
    """Ones but for keys 100..139: inside kv tile 0, which query tile 1
    sees whole."""
    m = np.ones((1, T_len), np.float32)
    m[:, 100:140] = 0.0
    return jnp.asarray(m)


MASKING_CASES = [(masked, group, window, "auto")
                 for masked in (False, True) for group in (1, 2, 7)
                 for window in (None, 640)] + [
    (True, 2, None, "split"), (False, 7, 640, "split")]


@pytest.mark.parametrize(
    "masked,group,window,mode", MASKING_CASES,
    ids=["-".join(map(str, c)) for c in MASKING_CASES])
def test_stream_parity_where_hidden_cut_and_whole_tiles_meet(
        monkeypatch, masked, group, window, mode):
    """T 1,024 in tiles of 512 (one hidden tile pair, two the diagonal
    cuts, one wholly below it; a window of 640 keeps all three visible
    ones and cuts the last): forward and gradients against the XLA path,
    with no padding and with a mask whose zeros lie inside the tile below
    the diagonal; 1, 2 and 7 query heads a shared head."""
    force_backward(monkeypatch, mode)
    q, k, v, weight, mask = _window_case(1024, 2 * group, 2, 2, 64, 64,
                                         seed=group)
    if masked:
        mask = _pad_in_a_whole_tile(1024)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True, window), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True, window=window), q, k, v, weight)
    _assert_same(got, want)


#: operands each kernel fetches by a kv tile with its query walk (or the
#: other way round) innermost: (operand numbers, which way)
WALKS = {"_stream_fwd_kernel": ((1, 2), "kv"),
         "_stream_dq_kernel": ((1, 2), "kv"),
         "_stream_bwd_fused_kernel": ((0, 4, 5, 6), "q"),
         "_stream_dkv_kernel": ((0, 4, 5, 6), "q")}


@pytest.mark.parametrize("mode", ["fused", "split"])
def test_a_hidden_step_keeps_the_parked_tile(monkeypatch, mode):
    """Causal, T 2,048 in four tiles: where a step of the grid is
    hidden the forward's and the dQ kernel's k and v maps stay on the
    diagonal tile, the fused and dK/dV kernels' q, dO, lse and delta maps
    on the tile the walk is about to see.  Every block the pipeline
    fetches is then one a visible step uses: no hidden tile is fetched.
    The jaxpr digests below print no index map, so this test pins them."""
    force_backward(monkeypatch, mode)
    n = 4
    q = jnp.zeros((1, 512 * n, 2, 64), jnp.bfloat16)
    mask = jnp.ones((1, 512 * n), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(pattn.stream_attention(
            q, k, v, mask, True, True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    steps = [(a, b) for a in range(n) for b in range(n)]
    walked = []
    for eqn in calls:
        kernel = eqn.params["jaxpr"].debug_info.func_name
        ops, inner = WALKS[kernel]
        walked.append(kernel)
        for op in ops:
            index_map = eqn.params["grid_mapping"].block_mappings[op] \
                .index_map_jaxpr
            # the tile is the second block index of q, k, v and dO, the
            # third of the [G, 1, T] rows
            axis = 1 if op in (0, 1, 2, 4) else 2
            idx = [int(jax.core.eval_jaxpr(index_map.jaxpr, index_map.consts,
                                           0, a, b)[axis]) for a, b in steps]
            # (outer tile a, inner step b): the forward's and dQ's outer
            # axis is the query tile, the backward's the kv tile
            want = [min(a, b) if inner == "kv" else max(a, b)
                    for a, b in steps]
            assert idx == want, (kernel, op, idx)
            # every block fetched (a run of one index) is used by a visible
            # step before the next fetch: no hidden tile is fetched
            visible = [b <= a if inner == "kv" else b >= a for a, b in steps]
            runs = [[visible[0]]]
            for k in range(1, len(steps)):
                if idx[k] != idx[k - 1]:
                    runs.append([])
                runs[-1].append(visible[k])
            assert all(any(run) for run in runs), (kernel, op, idx)
            assert len(runs) <= n * (n + 1) // 2 < n * n
    assert sorted(walked) == sorted(
        ["_stream_fwd_kernel", "_stream_bwd_fused_kernel"] if mode == "fused"
        else ["_stream_fwd_kernel", "_stream_dkv_kernel",
              "_stream_dq_kernel"])
    # no band, nothing parked: every step its own tile
    assert [pattn._kv_tile(False, None, n)(a, b) for a, b in steps] == \
        [b for _, b in steps]
    assert [pattn._q_tile(False, None, n)(a, b) for a, b in steps] == \
        [b for _, b in steps]


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
    # ``core_attention`` adds nothing to the program of the path it takes
    "core_attention_stream": "fd5abd514cfcc926",
    "core_attention_xla": "784faf65c0c932fc",
}


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_a_plain_call_is_the_parents_program(monkeypatch, name):
    import hashlib
    q = jnp.zeros((2, 1024, 4, 64), jnp.bfloat16)
    mask = jnp.ones((2, 1024))
    if name == "stream_causal_split":
        force_backward(monkeypatch, "split")
        q, mask = jnp.zeros((1, 4096, 2, 128), jnp.bfloat16), jnp.ones(
            (1, 4096))
    call = {
        "stream_causal_fused": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True),
        "stream_noncausal": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, False),
        "stream_causal_split": lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True),
        "xla": lambda q, k, v: pattn.xla_attention(q, k, v, mask, True),
        "core_attention_stream": lambda q, k, v: L.core_attention(
            q, k, v, causal=True, attn_mask=mask),
        "core_attention_xla": lambda q, k, v: L.core_attention(
            q, k, v, causal=True, attn_mask=mask),
    }[name]
    if name.startswith("core_attention"):
        monkeypatch.setattr(L, "attention_plan",
                            lambda *shape, **kw: name.split("_")[-1])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(call(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q)
    digest = hashlib.sha256(_normalised(jaxpr).encode()).hexdigest()[:16]
    assert digest == PARENT_JAXPRS[name]


#: the same digest, as the PARENT of PR 39 printed it (jax 0.9.0), of the
#: hybrid stack's shape of call — 8 query heads on 4 key heads (group 2) and
#: 2 value heads twice as wide (group 4), whole and under a window of 512:
#: groups that pair up keep two query heads a program, the program the
#: ``phi4-mini-flash.seq8192`` cell was measured on
PAIRED_GROUP_JAXPRS = {None: "6ea346ba5ab4e5bb", 512: "be8340480dff8452"}


@pytest.mark.parametrize("window", sorted(PAIRED_GROUP_JAXPRS, key=str))
def test_groups_that_pair_up_are_the_parents_program(window):
    import hashlib
    q = jnp.zeros((1, 1024, 8, 64), jnp.bfloat16)
    k = jnp.zeros((1, 1024, 4, 64), jnp.bfloat16)
    v = jnp.zeros((1, 1024, 2, 128), jnp.bfloat16)
    mask = jnp.ones((1, 1024))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(pattn.stream_attention(
            q, k, v, mask, True, False, window).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    digest = hashlib.sha256(_normalised(jaxpr).encode()).hexdigest()[:16]
    assert digest == PAIRED_GROUP_JAXPRS[window]

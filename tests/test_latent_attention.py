"""``layers.latent_attention`` (MLA, the expanded form) and the streaming
kernel at its head sizes: a query / key head of 192 beside a value head of
128, neither a multiple of the other, 192 no multiple of the 128-lane tile.
CPU; the kernel in interpret mode.  The plain reference is
``benchmark/reference/kimi_moe.latent_attention``.  (Whether Mosaic takes
these shapes on a v5e: tests/test_tpu_aot_kernels.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import kimi_moe as reference
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.ops import pallas_attention as pattn
from deepspeed_tpu.parallel.topology import MODEL_AXIS, make_mesh
from tests.test_pallas_attention import force_backward

H, N, LATENT, NOPE, ROPE, V = 64, 2, 32, 24, 8, 16
THETA, EPS = 800000.0, 1e-5
DIMS = dict(nope_dim=NOPE, rope_dim=ROPE, v_dim=V, latent=LATENT, eps=EPS)


def weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, *shape: 0.2 * jax.random.normal(k, shape, jnp.float32)
    return {"q_w": n(ks[0], H, N * (NOPE + ROPE)),
            "kv_a_w": n(ks[1], H, LATENT + ROPE),
            "kv_norm_s": 1.0 + n(ks[2], LATENT),
            "kv_b_w": n(ks[3], LATENT, N * (NOPE + V)),
            "o_w": n(ks[4], N * V, H)}


def to_reference(p):
    return {"wq": p["q_w"], "wkv_a": p["kv_a_w"],
            "kv_norm_g": p["kv_norm_s"], "wkv_b": p["kv_b_w"],
            "wo": p["o_w"]}


def run(x, p, mesh=None, specs=None):
    mesh = mesh or make_mesh(devices=jax.devices()[:1])

    def fn(x, p):
        rope = L.rotary_tables(x.shape[1], ROPE, THETA)
        return L.latent_attention(x, p, rope=rope, **DIMS)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(), specs or P()), out_specs=P(),
            check_vma=False))(x, p)


def plain(x, p):
    with jax.default_matmul_precision("highest"):
        return reference.latent_attention(x, to_reference(p), N,
                                          (NOPE, ROPE, V), THETA, EPS, None)


def test_latent_attention_is_the_reference():
    """Output and every gradient, 512 positions (two of the reference's
    query blocks)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, H))
    p = weights()
    np.testing.assert_allclose(np.asarray(run(x, p)), np.asarray(plain(x, p)),
                               rtol=1e-5, atol=1e-5)
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    mesh = make_mesh(devices=jax.devices()[:1])

    def through(x, p):
        rope = L.rotary_tables(x.shape[1], ROPE, THETA)
        return jnp.sum(L.latent_attention(x, p, rope=rope, **DIMS) * weight)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.shard_map(
            jax.grad(through, argnums=(0, 1)), mesh=mesh,
            in_specs=(P(), P()), out_specs=P(), check_vma=False))(x, p)
        want = jax.grad(lambda x, p: jnp.sum(plain(x, p) * weight),
                        argnums=(0, 1))(x, p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))


def test_the_rotary_key_is_one_per_token_and_position_matters():
    """Shifting the sequence by one position changes the output (rotary is
    there), and the shared rotary key reaches every head: zeroing the
    down projection's last ``rope_dim`` columns changes both heads."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, H))
    p = weights()
    base = run(x, p)
    rolled = run(jnp.roll(x, 1, axis=1), p)
    assert float(jnp.max(jnp.abs(jnp.roll(rolled, -1, axis=1)[:, 1:-1]
                                 - base[:, 1:-1]))) > 1e-3
    no_rope_key = dict(p, kv_a_w=p["kv_a_w"].at[:, LATENT:].set(0.0))
    only = dict(p, o_w=p["o_w"].at[V:].set(0.0))        # head 0 alone
    only_no = dict(no_rope_key, o_w=only["o_w"])
    assert float(jnp.max(jnp.abs(run(x, only) - run(x, only_no)))) > 1e-4


def test_heads_shard_over_the_model_axis():
    """Tensor parallelism by heads: ``q_w``, ``kv_b_w`` column-parallel
    (heads contiguous), ``o_w`` row-parallel, the latent's down projection
    and norm whole on every shard."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, H))
    p = weights()
    mesh = make_mesh(model_parallel_size=2, devices=jax.devices()[:2])
    specs = {"q_w": P(None, MODEL_AXIS), "kv_a_w": P(), "kv_norm_s": P(),
             "kv_b_w": P(None, MODEL_AXIS), "o_w": P(MODEL_AXIS, None)}
    np.testing.assert_allclose(np.asarray(run(x, p, mesh, specs)),
                               np.asarray(run(x, p)), rtol=1e-5, atol=1e-5)


# ------------------------------------------- the streaming kernel at 192 / 128

def _case(T_len, n, d, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, T_len, n, d))
    k = jax.random.normal(ks[1], (1, T_len, n, d))
    v = jax.random.normal(ks[2], (1, T_len, n, dv))
    weight = jax.random.normal(ks[3], (1, T_len, n, dv))
    return q, k, v, weight, jnp.ones((1, T_len), jnp.float32)


def _value_and_grads(fn, q, k, v, weight):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
            argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("mode", ["split", "fused"])
def test_stream_kernel_at_a_192_wide_key_and_a_128_wide_value(monkeypatch,
                                                              mode):
    """Outputs and dq, dk, dv against ``xla_attention``, the split and the
    fused backward, two tiles of 512."""
    force_backward(monkeypatch, mode)
    q, k, v, weight, mask = _case(1024, 2, 192, 128)
    assert pattn.stream_supported(1024, 192)
    got = _value_and_grads(lambda q, k, v: pattn.stream_attention(
        q, k, v, mask, True, True), q, k, v, weight)
    want = _value_and_grads(lambda q, k, v: pattn.xla_attention(
        q, k, v, mask, True), q, k, v, weight)
    assert got[0].shape == () and got[1][2].shape == v.shape
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5, abs=1e-4)
    for name, a, b in zip("qkv", got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-5, err_msg="d" + name)
    out = pattn.stream_attention(q, k, v, mask, True, True)
    assert out.shape == (1, 1024, 2, 128)
    # the softmax is scaled by the KEY head: 1 / sqrt(192)
    scores = jnp.einsum("btnd,bsnd->bnts", q, k, precision="highest") / (
        192 ** 0.5)
    pos = jnp.arange(1024)
    probs = jax.nn.softmax(jnp.where(pos[None] <= pos[:, None], scores,
                                     -jnp.inf), axis=-1)
    dense = jnp.einsum("bnts,bsnd->btnd", probs, v, precision="highest")
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-4,
                               atol=1e-5)


def test_the_fused_backward_is_sized_by_rule_at_192():
    """192 pads to two 128-lane tiles: the dQ-resident buffers of the fused
    backward cost what a 256-wide head costs, and half of the working set
    grows with the lane tiles — inside Mosaic's default at T 1024 in bf16,
    44 MiB at T 8192, which the call asks for (the v5e's cap is 96)."""
    plan = lambda *shape: pattn.stream_bwd_plan(*shape,
                                                pattn._kernel_vmem_cap())
    assert plan(2, 1024, 192, 2) == ("fused", None)
    assert plan(2, 4096, 192, 2) == plan(2, 4096, 256, 2)
    assert plan(2, 8192, 192, 2) == ("fused", 44 * 1024 * 1024)
    assert (pattn.fused_bwd_vmem(2, 8192, 192, 2)
            - pattn.fused_bwd_vmem(2, 8192, 128, 2)) == (16 + 4) * 1024 * 1024
    assert plan(2, 2048, 128, 2) == ("fused", None)

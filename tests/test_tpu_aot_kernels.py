"""The kernels of the latent-attention / expert path at their REAL widths,
compiled here for a described TPU v5e (no chip attached): what interpret
mode cannot show — whether Mosaic takes a 192-wide query / key head beside
a 128-wide value head, whether it grants the streaming backward the scoped
VMEM ``stream_bwd_plan`` asks for at T 8192, and whether the grouped
matmuls become kernels and not dense products.  A compile that passes is not a chip run: nothing here
is a time.  The topology is described inside a fixture (never while a module
is imported), and every such test lives in this one file, so one worker
loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.models import moe as M
from deepspeed_tpu.ops import pallas_attention as pattn


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).compile().as_text()


def _stream_loss(window=None):
    def loss(q, k, v, mask):
        return jnp.sum(pattn.stream_attention(q, k, v, mask, True, False,
                                              window).astype(jnp.float32))
    return loss


def test_mosaic_takes_the_streaming_kernel_at_192_and_128(one_chip):
    """Forward, and the backward — ONE fused call at T 8192 under the 44 MiB
    of scoped VMEM ``stream_bwd_plan`` asks Mosaic for (its dQ scratch is
    past the 16 MiB default) — 2 x 8192 tokens, 16 heads, bf16: two Pallas
    calls, no padding of 192 to 256 anywhere in the wrapper."""
    B, T, n, d, dv = 2, 8192, 16, 192, 128
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    args = (S(B, T, n, d), S(B, T, n, d), S(B, T, n, dv),
            S(B, T, dt=jnp.float32))
    assert pattn.stream_bwd_plan(2, T, d, 2, pattn._kernel_vmem_cap()) == (
        "fused", 44 * 1024 * 1024)
    loss = _stream_loss()
    assert compiled_text(loss, *args).count("tpu_custom_call") == 1
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert text.count("tpu_custom_call") == 2
    assert "bf16[32,8192,256]" not in text


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window512"])
def test_mosaic_takes_the_hybrid_stacks_backward_in_one_call(one_chip,
                                                             window):
    """The hybrid stack's attention calls at T 8192: 40 query heads of 64
    over 20 key heads and 10 value heads 128 wide, the whole triangle and
    under a window of 512.  The backward is one fused call under the
    24 MiB the rule asks (Mosaic's default refuses it: 19.3 MiB of 16)."""
    B, T, n, d, dv = 1, 8192, 40, 64, 128
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    args = (S(B, T, n, d), S(B, T, n // 2, d), S(B, T, n // 4, dv),
            S(B, T, dt=jnp.float32))
    assert pattn.stream_bwd_plan(2, T, d, 2, pattn._kernel_vmem_cap()) == (
        "fused", 24 * 1024 * 1024)
    text = compiled_text(jax.grad(_stream_loss(window), argnums=(0, 1, 2)),
                         *args)
    assert text.count("tpu_custom_call") == 2


def test_the_rule_asks_enough_where_a_plain_bound_would_not(one_chip,
                                                            monkeypatch):
    """What the limit is for: the latent core's fused backward is REFUSED
    under Mosaic's default and under the resident buffers + 8 MiB that a
    128-lane head needs (42.9 MiB of 40), and compiles under the rule's
    44; past the cap (T 32,768: 140 MiB) the rule splits and the split
    compiles with no limit at all."""
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)

    def backward(T):
        G, d, dv = 32, 192, 128
        rows = S(G, 1, T, dt=jnp.float32)
        return compiled_text(
            lambda *a: pattn._stream_bwd_impl(*a, True, False),
            S(G, T, d), S(G, T, d), S(G, T, dv), rows, S(G, T, dv), rows,
            S(G, T, dv))

    plan = pattn.stream_bwd_plan
    for limit in (None, 40 * 1024 * 1024):
        monkeypatch.setattr(pattn, "stream_bwd_plan",
                            lambda *a, **k: ("fused", limit))
        with pytest.raises(Exception, match="Scoped allocation"):
            backward(8192)
    monkeypatch.setattr(pattn, "stream_bwd_plan", plan)
    assert backward(8192).count("tpu_custom_call") == 1
    assert plan(2, 32768, 192, 2, pattn._kernel_vmem_cap()) == (
        "split", None)
    assert backward(32768).count("tpu_custom_call") == 2


def test_the_grouped_matmuls_compile_to_kernels(one_chip, monkeypatch):
    """The expert SwiGLU over ragged groups at the cell's sizes (98,304
    sorted rows, 8 experts 2048 x 1408), forward and backward: every product
    — the three forward ones, their input gradients and their weight
    gradients — is a Pallas kernel under jax's own name (the tiles of
    ``ops/grouped_matmul._tiles`` fit Mosaic's VMEM), none a dense masked
    product and none the compiler's ``ragged-dot`` kernel, which carries no
    scope into a trace."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    R, h, f, e = 98304, 2048, 1408, 8
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    p = {"exp_gate_w": S(e, h, f), "exp_up_w": S(e, h, f),
         "exp_down_w": S(e, f, h)}

    def loss(rows, p, sizes):
        out = M.grouped_swiglu(rows, p, sizes, jnp.sum(sizes))
        return jnp.sum(out.astype(jnp.float32))

    def kernels(text):
        return [line for line in text.splitlines()
                if "custom-call(" in line and "tpu_custom_call" in line]

    args = (S(R, h), p, S(e, dt=jnp.int32))
    assert len(kernels(compiled_text(loss, *args))) == 3
    text = compiled_text(jax.grad(loss, argnums=(0, 1)), *args)
    # gate and up again (the gradient of a sum does not need the down
    # product), then two gradients a product: 2 + 3 x 2
    assert len(kernels(text)) == 8
    assert "ragged-dot" not in text
    assert all("dstpu/experts" in line for line in kernels(text))
    # a dense expansion would hold a product over all rows for every expert
    assert f"bf16[{e},{R}," not in text


def test_both_branches_of_the_expert_layer_hold_the_kernels(one_chip,
                                                            monkeypatch):
    """The held experts' part at the cell's shapes (2 x 8,192 tokens, top-6,
    8 of 64 experts): a ``conditional`` on the device whose prefix branch
    runs the grouped-matmul kernels over 24,576 sorted rows and whose
    overflow branch runs them over all 98,304 — the same nine products in
    either (each branch recomputes its three and takes six gradients), all
    Pallas kernels under ``dstpu/experts``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S_, k, h, f, e, E = 2 * 8192, 6, 2048, 1408, 8, 64
    assert M.prefix_rows(S_ * k, e, E) == 24576
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    p = {"exp_gate_w": S(e, h, f), "exp_up_w": S(e, h, f),
         "exp_down_w": S(e, f, h)}

    def loss(flat, p, chosen, gates):
        out = M.held_experts(flat, p, chosen, gates, 0, E)[0]
        return jnp.sum(out.astype(jnp.float32))

    text = compiled_text(
        jax.grad(loss, argnums=(0, 1, 3)), S(S_, h), p,
        S(S_, k, dt=jnp.int32), S(S_, k, dt=jnp.float32))
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert " conditional(" in text and "ragged-dot" not in text
    assert all("dstpu/experts" in line for line in kernels)
    on = lambda rows: sum(f"bf16[{rows}," in line for line in kernels)
    assert on(24576) == on(98304) == 9 and len(kernels) == 18

"""The kernels of the latent-attention / expert path at their REAL widths,
compiled here for a described TPU v5e (no chip attached): what interpret
mode cannot show — whether Mosaic takes a 192-wide query / key head beside
a 128-wide value head, whether it grants the streaming backward the scoped
VMEM ``stream_bwd_plan`` asks for at T 8192, and whether the grouped
matmuls become kernels and not dense products.  A compile that passes is not a chip run: nothing here
is a time.  The topology is described inside a fixture (never while a module
is imported), and every such test lives in this one file, so one worker
loads the TPU's library."""

import math
import re

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
        out, _ = M.grouped_glu(rows, p, sizes, jnp.sum(sizes))
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


@pytest.mark.parametrize("k,f,e,E,prefix", [
    (6, 1408, 8, 64, 24576), (10, 512, 32, 512, 20480)],
    ids=["8-of-64-top6", "32-of-512-top10"])
def test_both_branches_of_the_expert_layer_hold_the_kernels(
        one_chip, monkeypatch, k, f, e, E, prefix):
    """The held experts' part at the two expert cells' shapes (16,384 tokens;
    top-6 of 64 experts 1,408 wide with 8 held; top-10 of 512 experts 512
    wide with 32 held, ~320 rows a group in 512-row tiles): a ``conditional``
    on the device whose prefix branch runs the grouped-matmul kernels over
    the prefix of the sorted rows (24,576; 20,480) and whose overflow branch
    runs them over all of them (98,304; 163,840) — the same nine products in
    either (each branch recomputes its three and takes six gradients), all
    Pallas kernels under ``dstpu/experts``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S_, h = 2 * 8192, 2048
    assert M.prefix_rows(S_ * k, e, E) == prefix
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
    assert on(prefix) == on(S_ * k) == 9 and len(kernels) == 18


# ------------------- the linear-attention / attention hybrid's calls (PR 37)

def test_mosaic_takes_the_256_wide_head_on_two_shared_heads(one_chip):
    """The gated attention's core at its real shape: 1 x 16,384 tokens, 16
    query heads of 256 on 2 key/value heads (8 query heads a group), bf16.
    Forward, and the backward as ONE fused call under the 76 MiB of scoped
    VMEM ``stream_bwd_plan`` asks for at gb 2, T 16,384, d 256 (64 MiB of
    resident dQ + 12), inside the v5e's 96 MiB cap."""
    B, T, n, kv, d = 1, 16384, 16, 2, 256
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    args = (S(B, T, n, d), S(B, T, kv, d), S(B, T, kv, d),
            S(B, T, dt=jnp.float32))
    assert pattn.stream_supported(T, d)
    assert pattn.stream_bwd_plan(2, T, d, 2, pattn._kernel_vmem_cap()) == (
        "fused", 76 * 1024 * 1024)
    loss = _stream_loss()
    assert compiled_text(loss, *args).count("tpu_custom_call") == 1
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert text.count("tpu_custom_call") == 2


def test_the_delta_rule_compiles_with_no_array_of_every_steps_state(
        one_chip, monkeypatch):
    """The chunked gated delta rule at the cell's sizes (16,384 steps, 16
    key and 32 value heads of 128, bf16 q / k / v, float32 gates), forward
    and backward, for the TPU: XLA makes the decays' rows and loops over
    the 8 segments; a segment's 32 chunks are walked inside ONE Pallas call
    a direction (no loop over the 256 chunks is left), and each call forms
    every chunk's ``D``, ``L`` and ``T = (I + L)^-1`` in VMEM, the backward
    also their gradients and the pull to k, g and beta.  What crosses into
    a call is q, k, v (and, backward, the output's cotangent) as they came,
    bf16, and float32 for all the rest: the states, g and the gates' rows —
    no [64, 64] matrix, nothing [T, H, d] wide in float32; out of the
    backward call, of that width, only the gradients of q and k.  Both
    calls compile inside Mosaic's default scoped VMEM (the kernels ask for
    no limit).  The only arrays of 32 x 128 x 128
    states are the 256 kept at the chunk boundaries, never one per step;
    what the program holds at once stays under 3 GB."""
    from deepspeed_tpu.ops import delta_rule as dr
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T = 16384
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    args = (S(1, T, 16, 128), S(1, T, 16, 128), S(1, T, 32, 128),
            S(1, T, 32, dt=jnp.float32), S(1, T, 32, dt=jnp.float32))
    assert dr.kernel_walks(128, 128, T, jnp.bfloat16)
    forward = compiled_text(dr.gated_delta_rule, *args)
    assert forward.count("tpu_custom_call") == forward.count(" while(") == 1
    lowered = jax.jit(jax.grad(
        lambda *a: jnp.sum(dr.gated_delta_rule(*a).astype(jnp.float32)),
        argnums=range(5))).trace(*args).lower(lowering_platforms=("tpu",))
    # by the lowering's types: 32 chunks x 16 key heads (x 2 value heads)
    calls = [line for line in lowered.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2
    qk, vo = "32x16x64x128", "32x16x2x64x128"
    for line, operands, results in zip(calls, (9, 11), (3, 9)):
        types = re.findall(r"tensor<([0-9x]+)x(f32|bf16)>",
                           line[line.rindex(" : ("):])
        assert len(types) == operands + results, line[-400:]
        assert {dims for dims, dtype in types if dtype == "bf16"} == {
            qk, vo}
        assert ("16x2x128x128", "f32") in types          # the state
        assert ("32x16x2x128x128", "f32") in types       # the chunks' starts
        # no [64, 64] matrix of a head and chunk: T and D are made in VMEM
        assert not [t for t in types if t[0].endswith("x64x64")]
        # float32 at q's, k's or v's size: the gradients of q and k alone
        wide = [t for t in types if t[1] == "f32" and t[0] in (qk, vo)]
        assert wide == [(qk, "f32")] * 2 * (results == 9)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "f32[8,32,1,16,2,128,128]" in text        # the boundary states
    # the two walks are kernels, each inside its loop over the segments
    assert text.count("tpu_custom_call") == text.count(" while(") == 2
    per_step = [dims for dims in re.findall(r"f32\[([0-9,]+)\]", text)
                if dims.endswith("128,128") and math.prod(
                    int(n) for n in dims.split(",")) >= T * 32 * 128 * 128]
    assert not per_step, per_step[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


# -------------- the window / NoPE stack over ReGLU experts' calls (PR 39)

@pytest.mark.parametrize("window", [None, 4096], ids=["nope", "window4096"])
def test_mosaic_takes_seven_query_heads_a_shared_head(one_chip, window):
    """SmallThinker's attention core at its real shape: 1 x 16,384 tokens,
    28 query heads of 128 on 4 key/value heads — seven a group, so a
    program takes ONE query head (``_stream_gb``) — over the whole triangle
    and under a window of 4,096 (9 of the 32 kv tiles a query tile).
    Forward, and the backward as ONE fused call under the 24 MiB
    ``stream_bwd_plan`` asks for at gb 1, T 16,384, d 128 (16 MiB of
    resident dQ + 8)."""
    B, T, n, kv, d = 1, 16384, 28, 4, 128
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    args = (S(B, T, n, d), S(B, T, kv, d), S(B, T, kv, d),
            S(B, T, dt=jnp.float32))
    assert pattn._stream_gb(B * n, n // kv, n // kv) == 1
    assert pattn.stream_bwd_plan(1, T, d, 2, pattn._kernel_vmem_cap()) == (
        "fused", 24 * 1024 * 1024)
    assert pattn._window_tiles(window, 512, T // 512) == (
        32 if window is None else 9)
    loss = _stream_loss(window)
    assert compiled_text(loss, *args).count("tpu_custom_call") == 1
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert text.count("tpu_custom_call") == 2


def test_both_branches_of_the_relu_expert_layer_hold_the_kernels(
        one_chip, monkeypatch):
    """The ReGLU experts' part at the cell's shapes (16,384 tokens, top-6 of
    64 experts 768 wide, 16 held, hidden 2,560): the prefix branch over
    49,152 sorted rows and the overflow branch over all 98,304, nine
    grouped-matmul kernels in each, all under ``dstpu/experts`` — the ReLU
    and the count of the units that fire add no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S_, h, k, f, e, E = 16384, 2560, 6, 768, 16, 64
    prefix = M.prefix_rows(S_ * k, e, E)
    assert prefix == 49152
    S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    p = {"exp_gate_w": S(e, h, f), "exp_up_w": S(e, h, f),
         "exp_down_w": S(e, f, h)}

    def loss(flat, p, chosen, gates):
        out, counts = M.held_experts(flat, p, chosen, gates, 0, E, "relu")
        return jnp.sum(out.astype(jnp.float32)) + 0.0 * jax.lax.stop_gradient(
            counts["moe/reglu_active"])

    text = compiled_text(
        jax.grad(loss, argnums=(0, 1, 3)), S(S_, h), p,
        S(S_, k, dt=jnp.int32), S(S_, k, dt=jnp.float32))
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert " conditional(" in text and "ragged-dot" not in text
    assert all("dstpu/experts" in line for line in kernels)
    on = lambda rows: sum(f"bf16[{rows}," in line for line in kernels)
    assert on(prefix) == on(S_ * k) == 9 and len(kernels) == 18

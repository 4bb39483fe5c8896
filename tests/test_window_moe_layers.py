"""The layer functions ``deepspeed_tpu.models.WindowMoELM`` is made of, each
against ``benchmark/reference/smallthinker.py`` (which imports nothing of
the program), tiny sizes, CPU, float32: the windowed (rotary) and the
position-free full ``rotary_multihead_attention`` at seven query heads a
key/value head against plain masks, a window as long as the sequence, the
ReLU-gated grouped GLU against a dense loop, the router on another tensor
than the experts' input, ``moe/reglu_active`` against a count on the host,
THE SHARES — the routed parts of four 16-expert shares, with attention and
the residual counted once, are the uncut reference's whole layer — and the
pins that keep the older cells' programs: the gradient jaxprs of an Ouro-,
a Kimi- and a Qwen3-Next-shaped call and of the latent and delta stacks are
the pinned ones letter for letter.  (The whole model:
tests/test_window_moe_model.py; through the engine:
tests/test_window_moe_engine.py.)"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import smallthinker as reference
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import moe as M
from deepspeed_tpu.models.window_moe import (WindowMoEConfig,
                                             init_layer_params, layer_apply)
from deepspeed_tpu.parallel.topology import make_mesh

#: 14 query heads on 2 key/value heads: seven a group, as published
CFG = WindowMoEConfig(
    vocab_size=512, hidden_size=32, num_heads=14, num_kv_heads=2,
    head_dim=16, rope_theta=1.5e6, window=16, expert_ffn_size=24,
    num_experts=64, experts_per_token=6, experts_held=(0, 64),
    segments=((("nope", "swa"), 1),))
EPS, ALPHA, SEQ = 1e-6, 0.001, 48
ATTN = ("q_w", "k_w", "v_w", "o_w")
EXPERT = ("router_w", "exp_gate_w", "exp_up_w", "exp_down_w")


def on_one_device(fn, *args):
    mesh = make_mesh(devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
            check_vma=False))(*args)


def layer_params(seed=0, held=(0, 64)):
    """One layer's parameters, every leaf off its initial value (a swapped
    or dropped leaf shows), the experts cut to ``held``."""
    p = jax.tree_util.tree_map(
        lambda x: x[0], init_layer_params(CFG, 1, jax.random.PRNGKey(seed)))
    leaves, treedef = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    p = treedef.unflatten([x + 0.3 * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])
    cut = slice(held[0], held[0] + held[1])
    return {**p, **{name: p[name][cut] for name in EXPERT[1:]}}


def tokens(seed=7, seq=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, seq, CFG.hidden_size))


def same_value_and_grads(ours, theirs, args, tol=1e-5):
    """Output and the gradients to every argument (through a fixed
    weighting of the output), to ``tol`` of their largest entry."""
    want = jax.jit(theirs)(*args)
    weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
        want.shape)
    got = on_one_device(ours, *args)
    argnums = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(theirs)(*args)
        want_grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(theirs(*a) * weight), argnums=argnums))(*args)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(jnp.max(jnp.abs(want))))
    grads = on_one_device(jax.grad(
        lambda *a: jnp.sum(ours(*a) * weight), argnums=argnums), *args)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=tol * float(jnp.max(jnp.abs(b))) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------- attention

def ref_attention(p, window, rotary):
    ref = {"wq": p["q_w"], "wk": p["k_w"], "wv": p["v_w"], "wo": p["o_w"]}
    return lambda x: reference.attention(
        x, ref, (14, 2), 16, CFG.rope_theta if rotary else None, window,
        None)


def our_attention(p, window, rotary, seq=SEQ):
    def run(x):
        rope = L.rotary_tables(seq, 16, CFG.rope_theta) if rotary else None
        return L.rotary_multihead_attention(
            x, p["q_w"], p["k_w"], p["v_w"], p["o_w"], rope, head_dim=16,
            causal=True, window=window)
    return run


@pytest.mark.parametrize("window,rotary", [
    (16, True),         # the sliding-window layer: a third of the sequence
    (None, False),      # the NoPE full layer
    (SEQ, True),        # a window as long as the sequence
    (200, True)])       # and longer
def test_attention_is_the_reference_with_plain_masks(window, rotary):
    """Seven query heads a key/value head; the reference masks ``t - window
    < s <= t`` (or ``s <= t``) over a dense softmax and rotates only where
    the layer has positions; output and every gradient to 1e-5."""
    p = {k: v for k, v in layer_params().items() if k in ATTN}
    same_value_and_grads(
        lambda x, p: our_attention(p, window, rotary)(x),
        lambda x, p: ref_attention(p, window, rotary)(x), (tokens(), p))


def test_the_window_and_the_positions_each_change_the_result():
    """A window under the sequence's length is not the full triangle, one
    as long as it is; NoPE is not the rotated layer."""
    p = {k: v for k, v in layer_params().items() if k in ATTN}
    x = tokens()
    run = lambda window, rotary: np.asarray(
        on_one_device(our_attention(p, window, rotary), x))
    full = run(None, True)
    np.testing.assert_array_equal(run(SEQ, True), full)
    assert np.abs(run(16, True) - full).max() > 1e-2
    assert np.abs(run(None, False) - full).max() > 1e-2
    # the first 16 queries see no more than the window: the same rows
    np.testing.assert_allclose(run(16, True)[:, :16], full[:, :16],
                               rtol=1e-6, atol=1e-6)


def test_a_windowed_core_runs_under_its_scope():
    """The windowed layer's core under ``dstpu/swa`` with its rotation under
    ``dstpu/rope``; the NoPE layer has neither scope."""
    p = {k: v for k, v in layer_params().items() if k in ATTN}
    names = []

    def stacks(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                stacks(sub)

    for window, want in ((16, True), (None, False)):
        names.clear()
        stacks(jax.make_jaxpr(on_one(
            our_attention(p, window, window is not None), 1))(
                tokens()).jaxpr)
        assert any("dstpu/swa" in n for n in names) == want
        assert any("dstpu/rope" in n for n in names) == want
        assert any("dstpu/attn" in n for n in names)


# ------------------------------------------------------- the expert layer

def test_the_relu_grouped_glu_is_a_dense_loop():
    """Sorted rows in groups of 5, 0, 9 and 2 of four experts, 3 rows past
    them: ``(relu(r W_g) * (r W_u)) W_d`` per group; the rows past
    ``n_held`` are zero in the pre-activations, and the fraction of units
    that fire is counted over the held rows."""
    sizes = jnp.array([5, 0, 9, 2], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    rows = jax.random.normal(ks[0], (19, 32))
    p = {"exp_gate_w": jax.random.normal(ks[1], (4, 32, 24)),
         "exp_up_w": jax.random.normal(ks[2], (4, 32, 24)),
         "exp_down_w": jax.random.normal(ks[3], (4, 24, 32))}
    with jax.default_matmul_precision("highest"):
        out, counts = jax.jit(lambda r, p, s: M.grouped_glu(
            r, p, s, jnp.sum(s), act="relu"))(rows, p, sizes)
        silu, none = jax.jit(lambda r, p, s: M.grouped_glu(
            r, p, s, jnp.sum(s)))(rows, p, sizes)
    assert none == {} and set(counts) == {"moe/reglu_active"}
    r, W = np.asarray(rows, np.float64), {k: np.asarray(v, np.float64)
                                          for k, v in p.items()}
    starts = np.concatenate([[0], np.cumsum(sizes)])
    active = 0
    for e in range(4):
        g = slice(starts[e], starts[e + 1])
        gate = r[g] @ W["exp_gate_w"][e]
        want = (np.maximum(gate, 0) * (r[g] @ W["exp_up_w"][e])) \
            @ W["exp_down_w"][e]
        np.testing.assert_allclose(out[g], want, rtol=1e-4, atol=1e-4)
        up = r[g] @ W["exp_up_w"][e]
        want = (gate / (1 + np.exp(-gate)) * up) @ W["exp_down_w"][e]
        np.testing.assert_allclose(silu[g], want, rtol=1e-4, atol=1e-4)
        active += int((gate > 0).sum())
    assert float(counts["moe/reglu_active"]) == pytest.approx(
        active / (16 * 24), rel=1e-6)
    assert 0.3 < active / (16 * 24) < 0.7
    with pytest.raises(ValueError, match="expert activation"):
        M.grouped_glu(rows, p, sizes, jnp.sum(sizes), act="gelu")


ROUTING = dict(num_experts=64, top_k=6, route_scale=1.0, balance_alpha=ALPHA,
               scoring="softmax", balance="switch", act="relu")


def expert_params(held):
    return {k: v for k, v in layer_params(held=held).items() if k in EXPERT}


def ref_expert_layer(a, u, p, held):
    ref = {"router": p["router_w"], "e_gate": p["exp_gate_w"],
           "e_up": p["exp_up_w"], "e_down": p["exp_down_w"]}
    return reference.expert_layer(a, u, ref, 6, held, ALPHA, None)


@pytest.mark.parametrize("held", [(0, 64), (16, 16)])
def test_the_router_reads_its_own_input(held):
    """The router scores ``route_x`` and the experts compute on ``x``: the
    output, the balance loss and every gradient — to ``x``, to
    ``route_x`` (through the gates alone) and to every weight — are the
    reference layer's, whole and for the second of four shares; no shared
    expert."""
    x, a, p = tokens(), tokens(seed=9), expert_params(held)

    def ours(x, a, p):
        y, aux, _ = M.dropless_moe_ffn(x, p, held=held, route_x=a,
                                       **ROUTING)
        return y + 100.0 * aux

    def theirs(x, a, p):
        y, aux, _ = ref_expert_layer(a, x, p, held)
        return y + 100.0 * aux

    same_value_and_grads(ours, theirs, (x, a, p))
    with jax.default_matmul_precision("highest"):
        _, aux, pairs = ref_expert_layer(a, x, p, held)
    assert float(aux) == pytest.approx(ALPHA * 6, rel=0.2)      # ~c k
    counts = on_one_device(lambda x, a, p: M.dropless_moe_ffn(
        x, p, held=held, route_x=a, **ROUTING)[2], x, a, p)
    assert int(counts["moe/held_pairs"]) == int(pairs)


def test_routing_on_x_itself_is_the_layer_bit_for_bit():
    """``route_x = x`` is the layer that routes on its own input, bit for
    bit, and another ``route_x`` moves the choices: the routing is what
    changes, not the experts' input."""
    x, a, p = tokens(), tokens(seed=9), expert_params((0, 64))
    run = lambda route: on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 64), route_x=route, **ROUTING), x, p)
    plain = on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 64), **ROUTING), x, p)
    same = on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 64), route_x=x, **ROUTING), x, p)
    for u, v in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(same)):
        np.testing.assert_array_equal(u, v)
    other = run(a)
    assert float(jnp.abs(other[0] - plain[0]).max()) > 1e-2
    assert float(other[1]) != float(plain[1])       # the balance loss too


def test_reglu_active_is_a_count_on_the_host():
    """``moe/reglu_active`` of one pass of a 16-expert share is the fraction
    of the (pair, unit)s that landed here whose gate pre-activation ``u
    W_g,e`` is above zero, counted in numpy from the reference's choices."""
    held = (16, 16)
    x, a, p = tokens(), tokens(seed=9), expert_params(held)
    counts = on_one_device(lambda x, a, p: M.dropless_moe_ffn(
        x, p, held=held, route_x=a, **ROUTING)[2], x, a, p)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(a.reshape(-1, 32) @ p["router_w"], axis=-1)
    chosen = np.asarray(jax.lax.top_k(probs, 6)[1])
    u = np.asarray(x.reshape(-1, 32), np.float64)
    gate_w = np.asarray(p["exp_gate_w"], np.float64)
    active = units = 0
    for t, e in zip(*np.nonzero((chosen >= 16) & (chosen < 32))):
        pre = u[t] @ gate_w[chosen[t, e] - 16]
        active, units = active + int((pre > 0).sum()), units + pre.size
    assert units == 24 * int(counts["moe/held_pairs"])
    assert float(counts["moe/reglu_active"]) == pytest.approx(
        active / units, rel=1e-6)
    assert 0.3 < active / units < 0.7


def test_the_four_shares_add_up_to_the_whole_layer():
    """64 experts in 4 shares of 16, top-6, over a whole layer of each kind:
    the shares' routed parts, with attention and the residual counted once,
    are the uncut reference's layer — every share reports the same balance
    loss, which is over all 64 experts, and every (token, choice) pair lands
    on exactly one share."""
    x, whole = tokens(), layer_params()
    rope = lambda: {"rope": L.rotary_tables(SEQ, 16, CFG.rope_theta)}
    ref = {"norm1_w": whole["norm1_s"], "norm2_w": whole["norm2_s"],
           "wq": whole["q_w"], "wk": whole["k_w"], "wv": whole["v_w"],
           "wo": whole["o_w"], "router": whole["router_w"],
           "e_gate": whole["exp_gate_w"], "e_up": whole["exp_up_w"],
           "e_down": whole["exp_down_w"]}
    for kind in ("nope", "swa"):
        windowed = kind == "swa"
        with jax.default_matmul_precision("highest"):
            a = reference.norm(x, ref["norm1_w"], EPS)
            h = x + reference.attention(
                a, ref, (14, 2), 16, CFG.rope_theta if windowed else None,
                CFG.window if windowed else None, None)
            y, want_aux, pairs = reference.expert_layer(
                a, reference.norm(h, ref["norm2_w"], EPS), ref, 6, (0, 64),
                ALPHA, None)
        want = h + y
        assert int(pairs) == 2 * SEQ * 6

        @jax.jit
        def share(first):
            # ``first`` traced: one compile serves the four shares
            cfg = dataclasses.replace(CFG, experts_held=(first, 16))
            p = {**whole, **{k: jax.lax.dynamic_slice_in_dim(
                whole[k], first, 16) for k in EXPERT[1:]}}
            return on_one_device(lambda x, p: layer_apply(
                kind, cfg, x, p, 0, rope()), x, p)

        residual = on_one_device(lambda x, p: layer_apply(
            kind, dataclasses.replace(CFG, experts_held=(0, 1)), x, p, 0,
            rope())[0], x, {**whole, **{k: whole[k][:1] * 0.0
                                        for k in EXPERT[1:]}})
        np.testing.assert_allclose(residual, h, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(h))))
        routed, landed = 0.0, 0
        for first in range(0, 64, 16):
            out, (aux, counts) = share(jnp.int32(first))
            routed = routed + (out - residual)
            assert float(aux) == pytest.approx(float(want_aux), rel=1e-6)
            landed += int(counts["moe/held_pairs"])
        np.testing.assert_allclose(routed + residual, want, rtol=0,
                                   atol=5e-5 * float(jnp.max(jnp.abs(want))))
        assert landed == int(pairs)


# ---------------------------------------------------- the parent's programs

def normalised(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    text = re.sub(r" at [^\s:]+:\d+", " at FILE", text)
    # a set of axis names prints in the order of the process's string hash
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)


def on_one(fn, n):
    return jax.shard_map(fn, mesh=make_mesh(devices=jax.devices()[:1]),
                         in_specs=(P(),) * n, out_specs=P(), check_vma=False)


def ouro_call():
    """The looped stack's call: rotary, every head its own key/value head,
    no window."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (1, 64, 32))
    w = [jax.random.normal(k, (32, 32)) for k in ks[1:]]

    def f(x, w):
        rope = L.rotary_tables(64, 8, 1e6)
        return jnp.sum(L.rotary_multihead_attention(
            x, *w, rope, head_dim=8, causal=True))
    return jax.make_jaxpr(on_one(jax.grad(f, argnums=(0, 1)), 2))(x, w)


def expert_call(kind):
    """The latent stack's expert layer (sigmoid scores with a correction
    bias, the sequence-wise balance loss, shared experts) or the delta
    stack's (softmax, Switch, a gated shared expert), on a share whose
    prefix is under all the pairs: the branch is in the program."""
    h, f, E = 32, 24, 16
    ks = iter(jax.random.split(jax.random.PRNGKey(1), 12))
    n = lambda *s: jax.random.normal(next(ks), s)
    p = {"router_w": n(h, E), "exp_gate_w": n(4, h, f),
         "exp_up_w": n(4, h, f), "exp_down_w": n(4, f, h),
         "gate_w": n(h, f), "up_w": n(h, f), "down_w": n(f, h)}
    if kind == "kimi":
        p["router_b"] = n(E)
        kw = dict(route_scale=2.446)
    else:
        p["shared_gate_w"] = n(h)
        kw = dict(route_scale=1.0, scoring="softmax", balance="switch")
    x = n(2, 64, h)

    def loss(x, p):
        y, aux, counts = M.dropless_moe_ffn(
            x, p, num_experts=E, top_k=3, held=(4, 4), balance_alpha=0.001,
            **kw)
        return jnp.sum(y) + aux, counts
    return jax.make_jaxpr(on_one(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True), 2))(x, p)


def stack(kind):
    """The whole tiny latent or delta stack's loss and gradient (their
    segment loop now ``moe.expert_segments``)."""
    from deepspeed_tpu.models import DeltaMoELM, LatentMoELM
    from deepspeed_tpu.observability import scalars
    m = (LatentMoELM if kind == "latent" else DeltaMoELM).from_size("tiny")
    params = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    loss = lambda p, t, l: scalars.split(m.apply(p, t, l))[0]
    return jax.make_jaxpr(on_one(jax.value_and_grad(loss), 3))(
        params, tokens, tokens)


#: sha256 (first 16 hex digits) of the normalised jaxpr as the PARENT of
#: PR 39 printed it (jax 0.9.0): a call with its defaults is the program the
#: ``ouro``, ``kimi`` and ``qwen3-next`` cells were measured on.  The delta
#: stack's is pinned anew since its XLA walk makes the rule's per-step rows
#: apart from its [C, C] gates (the kernels' path reads the rows alone): the
#: same operations in another order, its loss and gradients the earlier
#: program's to the bit on the CPU
PARENT_JAXPRS = {
    "ouro_call": ("5bd25f59cb61b687", ouro_call),
    "kimi_call": ("f4e94254d5270240", lambda: expert_call("kimi")),
    "qwen_call": ("12dd44c2783a4734", lambda: expert_call("qwen")),
    "latent_stack": ("a96d652d920385cf", lambda: stack("latent")),
    "delta_stack": ("df45b1f5a296b19d", lambda: stack("delta")),
}


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_a_call_with_its_defaults_is_the_parents_program(name):
    digest, make = PARENT_JAXPRS[name]
    got = hashlib.sha256(normalised(make()).encode()).hexdigest()[:16]
    assert got == digest

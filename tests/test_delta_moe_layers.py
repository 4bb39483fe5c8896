"""The layer functions ``deepspeed_tpu.models.DeltaMoELM`` is made of, each
against ``benchmark/reference/qwen3_next.py`` (which imports nothing of the
program), tiny sizes, CPU, float32: the Gated DeltaNet mixer, the gated
attention (8 query heads a key/value head, a quarter of the head rotated,
per-head zero-centred norms, the output gate), the softmax router and the
Switch balance loss by hand, the gated shared expert, and THE SHARES: the
routed parts of all 16 shares, with the shared expert counted once, are the
uncut reference's whole layer.  (The whole model: tests/test_delta_moe_model.py;
the rule alone: tests/test_delta_rule.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.families import qwen3_next as family
from benchmark.reference import qwen3_next as reference
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import moe as M
from deepspeed_tpu.models.delta_moe import DeltaMoEConfig, init_layer_params
from deepspeed_tpu.parallel.topology import make_mesh

CFG = DeltaMoEConfig(
    vocab_size=512, hidden_size=32, num_heads=8, num_kv_heads=1, head_dim=16,
    rotary_dim=4, key_heads=2, value_heads=4, key_dim=8, value_dim=8,
    expert_ffn_size=24, shared_ffn_size=24, num_experts=32,
    experts_per_token=4, experts_held=(0, 32),
    segments=((("gdn", "full"), 1),))
SZ = {"key_heads": 2, "key_dim": 8}
EPS, THETA, ALPHA, SEQ = 1e-6, 1e7, 0.001, 48


def on_one_device(fn, *args):
    mesh = make_mesh(devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
            check_vma=False))(*args)


def layer_params(kind, seed=0, held=(0, 32)):
    """One layer's parameters, every leaf off its initial value (a swapped
    or dropped leaf shows), the experts cut to ``held``."""
    p = jax.tree_util.tree_map(
        lambda x: x[0], init_layer_params(CFG, kind, 1,
                                          jax.random.PRNGKey(seed)))
    leaves, treedef = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    p = treedef.unflatten([x + 0.3 * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])
    cut = slice(held[0], held[0] + held[1])
    return {**p, **{name: p[name][cut] for name in
                    ("exp_gate_w", "exp_up_w", "exp_down_w")}}


def tokens(seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, SEQ, CFG.hidden_size))


def same_value_and_grads(ours, theirs, x, p, tol=1e-5):
    """Output and the gradients to ``x`` and every leaf of ``p`` (through a
    fixed weighting of the output), to ``tol`` of their largest entry."""
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    got = on_one_device(lambda x, p: ours(x, p), x, p)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(theirs)(x, p)
        want_grads = jax.jit(jax.grad(
            lambda x, p: jnp.sum(theirs(x, p) * weight),
            argnums=(0, 1)))(x, p)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(jnp.max(jnp.abs(want))))
    grads = on_one_device(jax.grad(
        lambda x, p: jnp.sum(ours(x, p) * weight), argnums=(0, 1)), x, p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=tol * float(jnp.max(jnp.abs(b))) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


def test_gated_delta_net_is_the_reference():
    """Projections grouped by key head (the program's order) against the
    reference's ``[q | k | v | z]``, the convolution, the unit q and k, the
    decay and write gates, the rule, the per-head gated norm."""
    p = {k: v for k, v in layer_params("gdn").items() if k in (
        "in_qkv_w", "in_z_w", "in_b_w", "in_a_w", "conv_w", "A_log",
        "dt_bias", "norm_s", "out_w")}

    def theirs(x, p):
        ref = {"w_qkvz": jnp.concatenate(
                   [family._ungroup(p["in_qkv_w"], SZ), p["in_z_w"]], -1),
               "w_ba": jnp.concatenate([p["in_b_w"], p["in_a_w"]], -1),
               "conv": family._ungroup(p["conv_w"], SZ),
               "A_log": p["A_log"], "dt_bias": p["dt_bias"],
               "norm_g": p["norm_s"], "w_out": p["out_w"]}
        return reference.gated_delta_net(x, ref, (2, 4), (8, 8), EPS, None)

    same_value_and_grads(
        lambda x, p: L.gated_delta_net(x, p, key_dim=8, value_dim=8,
                                       eps=EPS), theirs, tokens(), p)


def test_ungrouping_is_a_column_permutation():
    """Key head i's ``[q_i | k_i | v_2i v_2i+1]`` -> ``[q | k | v]``."""
    cols = jnp.arange(2 * (8 + 8 + 16))[None]
    got = np.asarray(family._ungroup(cols, SZ))[0]
    assert sorted(got) == list(range(64))
    assert list(got[:16]) == list(range(0, 8)) + list(range(32, 40))    # q
    assert list(got[16:32]) == list(range(8, 16)) + list(range(40, 48))  # k
    assert list(got[32:]) == list(range(16, 32)) + list(range(48, 64))   # v


def test_gated_attention_is_the_reference():
    """8 query heads on ONE key/value head, rotary on dims 0-3 of 16, norms
    whose offsets are off zero, the sigmoid gate on the context."""
    p = {k: v for k, v in layer_params("full").items() if k in (
        "q_w", "k_w", "v_w", "q_norm_s", "k_norm_s", "o_w")}

    def ours(x, p):
        rope = L.rotary_tables(SEQ, CFG.rotary_dim, THETA)
        return L.gated_attention(x, p, rope=rope, head_dim=16, eps=EPS)

    def theirs(x, p):
        ref = {"wq": p["q_w"], "wk": p["k_w"], "wv": p["v_w"],
               "q_norm_w": p["q_norm_s"], "k_norm_w": p["k_norm_s"],
               "wo": p["o_w"]}
        return reference.gated_attention(x, ref, (8, 1), 16, 4, THETA, EPS,
                                         None)

    same_value_and_grads(ours, theirs, tokens(), p)


def test_zero_centred_norm_by_hand():
    x = jnp.array([[3.0, 4.0], [0.0, 0.0]])
    w = jnp.array([0.5, -1.0])
    got = L.rms_norm(x, w, 1e-6, zero_centred=True)
    rms = np.sqrt(12.5 + 1e-6)
    np.testing.assert_allclose(got, [[1.5 * 3 / rms, 0.0], [0.0, 0.0]],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(L.rms_norm(x, w, 1e-6),
                               [[0.5 * 3 / rms, -4 / rms], [0.0, 0.0]],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got, reference.norm(x, w, 1e-6), rtol=1e-6)


# ------------------------------------------------------- the expert layer

def test_softmax_router_by_hand():
    """Scores are a softmax over ALL experts, the top-k of them is chosen
    with no bias, the gates are the chosen scores renormalised to sum to
    1; the sigmoid rule is what it was (tests/test_moe_dropless.py)."""
    x = tokens()[0]
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (CFG.hidden_size, 32))
    with jax.default_matmul_precision("highest"):
        scores, chosen, gates = M.route_tokens(x, w, None, top_k=4,
                                               scale=1.0, scoring="softmax")
        want = jax.nn.softmax(x @ w, axis=-1)
    np.testing.assert_allclose(scores, want, rtol=1e-5, atol=1e-7)
    order = np.argsort(-np.asarray(want), axis=-1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(order, -1))
    picked = np.take_along_axis(np.asarray(want), np.asarray(chosen), -1)
    np.testing.assert_allclose(gates, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.sum(gates, -1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        M.route_tokens(x, w, None, top_k=4, scale=1.0, scoring="tanh")


def test_switch_balance_loss_by_hand():
    """``c E sum_e F_e P_e`` over ALL tokens of the micro-batch, ``F`` the
    pairs an expert drew per token; an even router reads ``c k``."""
    E, k = 8, 2
    scores = jnp.full((2, 6, E), 1.0 / E)
    chosen = jnp.tile(jnp.arange(E).reshape(E // k, k), (3, 1)).reshape(
        2, 6, k)
    assert float(M.balance_loss(scores, chosen, 0.01, "switch")) == \
        pytest.approx(0.01 * k)
    # every token on experts 0 and 1, which the router also favours
    skew = jnp.zeros((2, 6, E)).at[..., :2].set(0.5)
    chosen = jnp.zeros((2, 6, k), jnp.int32).at[..., 1].set(1)
    assert float(M.balance_loss(skew, chosen, 0.01, "switch")) == \
        pytest.approx(0.01 * E * (1.0 * 0.5 + 1.0 * 0.5))
    # no gradient through F: d/dscores is c E F_e / tokens
    grad = jax.grad(lambda s: M.balance_loss(s, chosen, 0.01, "switch"))(skew)
    np.testing.assert_allclose(grad[0, 0], [0.01 * E / 12] * 2 + [0.0] * 6,
                               rtol=1e-6)
    with pytest.raises(ValueError, match="form"):
        M.balance_loss(skew, chosen, 0.01, "other")


ROUTING = dict(num_experts=32, top_k=4, route_scale=1.0, balance_alpha=ALPHA,
               scoring="softmax", balance="switch")
EXPERT = ("router_w", "exp_gate_w", "exp_up_w", "exp_down_w", "gate_w",
          "up_w", "down_w", "shared_gate_w")


def expert_params(held):
    return {k: v for k, v in layer_params("full", held=held).items()
            if k in EXPERT}


def ref_expert_layer(x, p, held):
    ref = {"router": p["router_w"], "e_gate": p["exp_gate_w"],
           "e_up": p["exp_up_w"], "e_down": p["exp_down_w"],
           "s_gate": p["gate_w"], "s_up": p["up_w"], "s_down": p["down_w"],
           "w_sg": p["shared_gate_w"]}
    return reference.expert_layer(x, ref, 4, held, ALPHA, None)


@pytest.mark.parametrize("held", [(0, 32), (6, 2)])
def test_the_expert_layer_is_the_reference_for_its_share(held):
    """Softmax routing, renormalised gates, the shared expert behind its
    sigmoid gate, the Switch balance loss: output, balance loss and every
    gradient, for the whole layer and for the fourth of 16 shares."""
    x, p = tokens(), expert_params(held)
    ours = lambda x, p: M.dropless_moe_ffn(x, p, held=held, **ROUTING)
    same_value_and_grads(
        lambda x, p: (lambda y, aux, _: y + 100.0 * aux)(*ours(x, p)),
        lambda x, p: (lambda y, aux, _: y + 100.0 * aux)(
            *ref_expert_layer(x, p, held)), x, p)
    with jax.default_matmul_precision("highest"):
        _, aux, pairs = ref_expert_layer(x, p, held)
    assert float(aux) == pytest.approx(ALPHA * 4, rel=0.2)    # ~c k
    counts = on_one_device(lambda x, p: ours(x, p)[2], x, p)
    assert int(counts["moe/held_pairs"]) == int(pairs)
    assert (int(pairs) == 2 * SEQ * 4) == (held == (0, 32))


def test_the_shared_experts_gate_is_a_sigmoid_of_its_own():
    """Without ``shared_gate_w`` the layer is the ungated one (the latent
    stack's); with it the shared part alone is scaled, token by token."""
    x, p = tokens(), expert_params((0, 2))
    dead = dict(p, exp_down_w=p["exp_down_w"] * 0.0)       # no routed part
    gated = on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 2), **ROUTING)[0], x, dead)
    plain = on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 2), **ROUTING)[0], x,
        {k: v for k, v in dead.items() if k != "shared_gate_w"})
    gate = jax.nn.sigmoid(jnp.sum(x * p["shared_gate_w"], axis=-1))
    np.testing.assert_allclose(gated, plain * gate[..., None], rtol=1e-5,
                               atol=1e-6)
    assert float(gate.min()) < 0.1 and float(gate.max()) > 0.9    # a real gate


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """32 experts in 16 shares of 2, top-4: the shares' routed parts, with
    the gated shared expert's part counted once, are the uncut reference's
    layer — every share reports the same balance loss, which is over all 32
    experts, and every (token, choice) pair lands on exactly one share."""
    x, whole = tokens(), expert_params((0, 32))
    with jax.default_matmul_precision("highest"):
        want_y, want_aux, pairs = ref_expert_layer(x, whole, (0, 32))
    assert int(pairs) == 2 * SEQ * 4

    @jax.jit
    def share(first):
        # ``first`` traced: one compile serves the sixteen shares
        p = {**whole, **{k: jax.lax.dynamic_slice_in_dim(whole[k], first, 2)
                         for k in ("exp_gate_w", "exp_up_w", "exp_down_w")}}
        return on_one_device(lambda x, p, first: M.dropless_moe_ffn(
            x, p, held=(first, 2), **ROUTING), x, p, first)

    none_routed = {**whole, **{k: whole[k][:1] * 0.0 for k in
                               ("exp_gate_w", "exp_up_w", "exp_down_w")}}
    shared_only = on_one_device(lambda x, p: M.dropless_moe_ffn(
        x, p, held=(0, 1), **ROUTING)[0], x, none_routed)
    routed, landed = 0.0, 0
    for first in range(0, 32, 2):
        y, aux, counts = share(jnp.int32(first))
        routed = routed + (y - shared_only)
        assert float(aux) == pytest.approx(float(want_aux), rel=1e-6)
        landed += int(counts["moe/held_pairs"])
    np.testing.assert_allclose(routed + shared_only, want_y, rtol=5e-5,
                               atol=5e-5)       # sixteen float32 sums
    assert landed == int(pairs)

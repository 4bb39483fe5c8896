"""``models/moe.dropless_moe_ffn``: the dropless expert layer that is told
which experts it holds.  Tiny sizes, CPU, float32.  The plain reference is
``benchmark/reference/kimi_moe.expert_layer`` (a loop over experts with a
dense mask, no sort), which imports nothing of the program.  (The whole
model against the reference: tests/test_latent_moe_model.py.)"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import kimi_moe as reference
from deepspeed_tpu.models import moe as M
from deepspeed_tpu.ops.grouped_matmul import _tiles, grouped_matmul
from deepspeed_tpu.parallel.topology import MODEL_AXIS, make_mesh

H, F, E, K, SCALE, ALPHA = 32, 24, 16, 3, 2.446, 0.001
ROUTING = dict(num_experts=E, top_k=K, route_scale=SCALE, balance_alpha=ALPHA)


def weights(seed=0, held=(0, E)):
    """The layer's parameters with every routed expert, cut to ``held``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape, jnp.float32)
    first, count = held
    cut = slice(first, first + count)
    return {"router_w": n(ks[0], H, E), "router_b": jnp.zeros((E,)),
            "exp_gate_w": n(ks[1], E, H, F)[cut],
            "exp_up_w": n(ks[2], E, H, F)[cut],
            "exp_down_w": n(ks[3], E, F, H)[cut],
            "gate_w": n(ks[4], H, 2 * F), "up_w": n(ks[5], H, 2 * F),
            "down_w": n(ks[6], 2 * F, H)}


def tokens(seed=1, rows=2, seq=40):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, seq, H))


def to_reference(p):
    return {"router": p["router_w"], "bias": p["router_b"],
            "e_gate": p["exp_gate_w"], "e_up": p["exp_up_w"],
            "e_down": p["exp_down_w"], "w_gate": p["gate_w"],
            "w_up": p["up_w"], "w_down": p["down_w"]}


def on_one_device(fn, *args):
    """``fn`` inside ``shard_map`` on a one-device mesh (the layer's linears
    ``psum`` over the ``model`` axis), at the highest matmul precision."""
    mesh = make_mesh(devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
            check_vma=False))(*args)


def ffn(x, p, held):
    """``(y, balance loss)`` of the layer (its step scalars: ``counted``)."""
    return M.dropless_moe_ffn(x, p, held=held, **ROUTING)[:2]


def counted(x, p, held):
    """The layer's step scalars as numpy numbers."""
    return {name: int(v) for name, v in on_one_device(
        lambda x, p: M.dropless_moe_ffn(x, p, held=held, **ROUTING)[2],
        x, p).items()}


def layer(x, p, held):
    return on_one_device(lambda x, p: ffn(x, p, held), x, p)


def plain(x, p, held):
    with jax.default_matmul_precision("highest"):
        return reference.expert_layer(x, to_reference(p), K, held, SCALE,
                                      ALPHA, None)


def same(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("held", [(0, E), (4, 4), (12, 4), (5, 1)])
def test_the_layer_is_the_reference_for_its_share(held):
    """Output, balance loss and every gradient, for the whole layer and for
    shares at the start, in the middle and at the end of the experts."""
    x, p = tokens(), weights(held=held)

    def total(fn):
        def f(x, p):
            y, aux = fn(x, p, held)[:2]
            return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))
                           ) + 100.0 * aux
        return f

    y, aux = layer(x, p, held)
    want_y, want_aux, pairs = plain(x, p, held)
    same(y, want_y)
    same(aux, want_aux, 1e-6)
    assert 0 < int(pairs) < x.shape[0] * x.shape[1] * K or held == (0, E)
    got = on_one_device(jax.grad(total(ffn), argnums=(0, 1)), x, p)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(total(plain), argnums=(0, 1))(x, p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_the_four_shares_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: the shares' routed parts, with the
    shared experts' part counted once, are the uncut reference's layer —
    and every share reports the same balance loss, which is over all 16."""
    x, whole = tokens(), weights()
    want_y, want_aux, pairs = plain(x, whole, (0, E))
    assert int(pairs) == x.shape[0] * x.shape[1] * K
    no_routed = dict(whole, exp_gate_w=whole["exp_gate_w"][:1] * 0,
                     exp_up_w=whole["exp_up_w"][:1] * 0,
                     exp_down_w=whole["exp_down_w"][:1] * 0)
    shared_only, _ = layer(x, no_routed, (0, 1))
    routed, landed = 0.0, 0
    for first in range(0, E, 4):
        y, aux = layer(x, weights(held=(first, 4)), (first, 4))
        routed = routed + (y - shared_only)
        same(aux, want_aux, 1e-6)
        landed += int(plain(x, weights(held=(first, 4)), (first, 4))[2])
    same(routed + shared_only, want_y)
    assert landed == int(pairs)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts(
        monkeypatch):
    """A bias that sends EVERY token to experts 4, 5, 6: groups of
    ``rows x seq`` pairs each, no capacity anywhere, the reference's
    result; the fourth expert of the share gets no token, contributes
    zeros and gets a zero gradient.  (With the bias at zero the same share
    sees about a quarter of the pairs and runs in its prefix.)"""
    x = tokens(rows=2, seq=64)
    p = weights(held=(4, 4))
    p["router_b"] = jnp.zeros((E,)).at[jnp.array([4, 5, 6])].set(10.0)
    with jax.default_matmul_precision("highest"):
        _, chosen, _ = M.route_tokens(x.reshape(-1, H), p["router_w"],
                                      p["router_b"], top_k=K, scale=SCALE)
    assert np.array_equal(np.sort(np.asarray(chosen), axis=1),
                          np.tile([4, 5, 6], (128, 1)))
    _, _, sizes = M.sort_share(chosen, 4, 4)
    assert list(np.asarray(sizes)) == [128, 128, 128, 0]
    y, _ = layer(x, p, (4, 4))
    same(y, plain(x, p, (4, 4))[0])
    grads = on_one_device(jax.grad(lambda p: jnp.sum(jnp.square(
        ffn(x, p, (4, 4))[0]))), p)
    for name in ("exp_gate_w", "exp_up_w", "exp_down_w"):
        assert float(jnp.max(jnp.abs(grads[name][3]))) == 0.0, name
        assert float(jnp.max(jnp.abs(grads[name][0]))) > 0.0, name
    # the same layer with the idle expert's weights changed: nothing moves
    q = dict(p, exp_down_w=p["exp_down_w"].at[3].set(7.0))
    same(layer(x, q, (4, 4))[0], y, 0)
    # 384 pairs held, every one of them: past the share's prefix of 256
    # rows, so the layer took the overflow branch — shown by a prefix
    # branch that returns NaN and is not seen here, and is with the bias
    # at zero
    assert M.prefix_rows(384, 4, E) == 256
    part = M.routed_part
    monkeypatch.setattr(M, "routed_part", lambda rows, *a: (
        part(rows, *a) if rows == 384 else jnp.nan * part(rows, *a)))
    same(layer(x, p, (4, 4))[0], y, 0)
    assert bool(jnp.all(jnp.isnan(layer(tokens(seed=5, rows=2, seq=64),
                                        weights(held=(4, 4)), (4, 4))[0])))


def test_the_correction_bias_changes_the_chosen_set_and_not_the_gates():
    x = tokens().reshape(-1, H)
    p = weights()
    bias = jnp.zeros((E,)).at[2].set(10.0)
    with jax.default_matmul_precision("highest"):
        s0, c0, g0 = M.route_tokens(x, p["router_w"], p["router_b"],
                                    top_k=K, scale=SCALE)
        s1, c1, g1 = M.route_tokens(x, p["router_w"], bias, top_k=K,
                                    scale=SCALE)
    same(s0, s1, 0)                         # the scores do not read it
    assert np.all(np.any(np.asarray(c1) == 2, axis=1))
    assert not np.all(np.any(np.asarray(c0) == 2, axis=1))
    # the gates are the chosen scores normalised and scaled, bias or not
    for s, c, g in ((s0, c0, g0), (s1, c1, g1)):
        picked = np.take_along_axis(np.asarray(s), np.asarray(c), axis=1)
        same(g, SCALE * picked / picked.sum(1, keepdims=True), 1e-6)
        same(np.asarray(g).sum(1), SCALE, 1e-5)
    # and its gradient is identically zero
    grad = on_one_device(jax.grad(lambda b: jnp.sum(jnp.square(
        ffn(tokens(), dict(p, router_b=b), (0, E))[0]))), bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_dispatch_and_combine_transpose_like_the_plain_gathers():
    """The two custom backward passes (gathers by the known sorted row)
    against autodiff of the plain expressions (scatter-adds)."""
    rows, k, n = 24, K, 40
    chosen = jax.random.randint(jax.random.PRNGKey(0), (rows, k), 0, E)
    order, pos, sizes = M.sort_share(chosen, 4, 4)
    n_held = jnp.sum(sizes)
    assert 0 < int(n_held) < rows * k
    key = np.asarray(chosen).reshape(-1)[np.asarray(order)]
    assert np.all((key[:int(n_held)] >= 4) & (key[:int(n_held)] < 8))
    assert np.all(np.diff(key[:int(n_held)]) >= 0)
    assert np.array_equal(np.asarray(order)[np.asarray(pos).reshape(-1)],
                          np.arange(rows * k))
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, n))
    out = jax.random.normal(jax.random.PRNGKey(2), (rows * k, n))
    gates = jax.random.uniform(jax.random.PRNGKey(3), (rows, k))
    held = (jnp.arange(rows * k) < n_held)[:, None]
    w = jnp.sin(jnp.arange(rows * k * n, dtype=jnp.float32)
                ).reshape(rows * k, n)

    got = jax.grad(lambda x: jnp.sum(
        jnp.where(held, M.dispatch(x, order, pos, n_held), 0) * w))(x)
    want = jax.grad(lambda x: jnp.sum(
        jnp.where(held, x[order // k], 0) * w))(x)
    same(got, want)

    def plain_combine(out, gates):
        picked = jnp.where(held, out, 0)[pos]
        return jnp.sum(picked * gates[..., None], axis=1)

    same(M.combine(out, gates, order, pos, n_held), plain_combine(out, gates))
    got = jax.grad(lambda o, g: jnp.sum(
        M.combine(o, g, order, pos, n_held) * w[:rows]), argnums=(0, 1))(
            out, gates)
    want = jax.grad(lambda o, g: jnp.sum(plain_combine(o, g) * w[:rows]),
                    argnums=(0, 1))(out, gates)
    same(got[0], want[0])
    same(got[1], want[1])


def test_expert_parallel_shards_add_up_to_the_one_device_layer():
    """The held experts split over a ``model`` axis of 2: each shard
    computes its own experts' part, a ``psum`` adds them; the share (4, 4)
    becomes (4, 2) and (6, 2)."""
    held = (4, 4)
    x, p = tokens(), weights(held=held)
    want, want_aux = layer(x, p, held)
    mesh = make_mesh(model_parallel_size=2, devices=jax.devices()[:2])
    by_expert, col, row = (P(MODEL_AXIS, None, None), P(None, MODEL_AXIS),
                           P(MODEL_AXIS, None))
    specs = {"router_w": P(), "router_b": P(), "exp_gate_w": by_expert,
             "exp_up_w": by_expert, "exp_down_w": by_expert, "gate_w": col,
             "up_w": col, "down_w": row}
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(jax.shard_map(
            lambda x, p: M.dropless_moe_ffn(x, p, held=held, **ROUTING)[:2],
            mesh=mesh, in_specs=(P(), specs), out_specs=(P(), P()),
            check_vma=False))(x, p)
    same(got, want)
    same(aux, want_aux, 1e-6)
    with pytest.raises(ValueError, match="experts held over"):
        layer(x, p, (4, 3))


# ------------------------------------------ the prefix and the overflow

@pytest.mark.parametrize("pairs, count, experts, want", [
    (2 * 8192 * 6, 8, 64, 24576),   # the cell: a quarter of 98,304, 48 tiles
    (2 * 8192 * 6, 64, 64, 98304),  # the whole layer: every row
    (384, 4, 16, 256),              # 2 x 192 rounded up to 128-row tiles
    (240, 4, 16, 128),              # no tile divides 240: 128-row steps
    (240, 16, 16, 240),
    (98304, 1, 64, 3072),           # one expert held: 2 x 1,536
    (128, 4, 16, 128),              # too small to round under all rows
])
def test_prefix_rows_by_hand(pairs, count, experts, want):
    assert M.prefix_rows(pairs, count, experts) == want
    assert M.HEADROOM == 2


def hand_chosen(n_held, n_tokens=128, first=4, count=4, seed=0):
    """``chosen`` [tokens, K] with EXACTLY ``n_held`` pairs on the experts
    ``[first, first + count)``, scattered over the tokens; a token's
    choices are distinct experts, as a top-k's are."""
    pairs = n_tokens * K
    on_share = np.zeros(pairs, bool)
    on_share[np.random.default_rng(seed).permutation(pairs)[:n_held]] = True
    others = [e for e in range(E) if not first <= e < first + count]
    t, j = np.divmod(np.arange(pairs), K)
    chosen = np.where(on_share, first + (t + j) % count,
                      np.asarray(others)[(5 * t + j) % len(others)])
    return jnp.asarray(chosen.reshape(n_tokens, K), jnp.int32)


@pytest.mark.parametrize("n_held", [60, 256, 257, 384])
def test_either_branch_is_the_routed_part_on_all_rows(n_held):
    """384 pairs, the share (4, 4), a prefix of 256 rows: with 60 and with
    exactly 256 pairs held the layer runs ``routed_part`` on the prefix,
    with 257 and with all 384 on every row.  Against ``routed_part`` on
    all rows without a branch: the output and the gradient of x, of the
    router (through the gates) and of the three expert matrices — equal
    bit for bit where the overflow branch ran (the same program), to 1e-6
    of the largest entry where the prefix did."""
    chosen = hand_chosen(n_held)
    x = tokens(rows=1, seq=128).reshape(-1, H)
    p = weights(held=(4, 4))
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def branched(x, p, gates):
        return M.held_experts(x, p, chosen, gates, 4, E)[0]

    assert int(jnp.sum(M.sort_share(chosen, 4, 4)[2])) == n_held

    def all_rows(x, p, gates):
        order, pos, sizes = M.sort_share(chosen, 4, 4)
        return M.routed_part(chosen.size, x, p, gates, order, pos, sizes,
                             jnp.sum(sizes))

    def run(part):
        def loss(x, p):
            scores = jax.nn.sigmoid(x @ p["router_w"])
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            y = part(x, p, SCALE * picked / jnp.sum(picked, -1,
                                                     keepdims=True))
            return jnp.sum(y * weight), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))(x, p)

    (_, got_y), got = run(branched)
    (_, want_y), want = run(all_rows)
    assert float(jnp.max(jnp.abs(want_y))) > 0.1
    tol = 0 if n_held > M.prefix_rows(chosen.size, 4, E) else 1e-6
    for name, a, b in [("y", got_y, want_y), ("x", got[0], want[0])] + [
            (k, got[1][k], want[1][k]) for k in
            ("router_w", "exp_gate_w", "exp_up_w", "exp_down_w")]:
        assert float(jnp.max(jnp.abs(b))) > 0, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=tol * float(jnp.max(jnp.abs(b))), err_msg=name)


@pytest.mark.parametrize("n_held", [60, 256, 257, 384])
def test_the_layer_counts_what_it_decided(n_held):
    """The step scalars of ``held_experts`` beside its output: the pairs
    that landed, the busiest held expert's rows (a numpy count from
    ``chosen``), and 1 overflow pass exactly where the pairs held do not
    fit the 256-row prefix."""
    chosen = hand_chosen(n_held)
    x = tokens(rows=1, seq=128).reshape(-1, H)
    gates = jnp.full(chosen.shape, 1.0 / K)
    _, counts = jax.jit(lambda x, p: M.held_experts(
        x, p, chosen, gates, 4, E))(x, weights(held=(4, 4)))
    per_expert = np.bincount(np.asarray(chosen).reshape(-1), minlength=E)
    assert {name: int(v) for name, v in counts.items()} == {
        "moe/held_pairs": n_held,
        "moe/max_expert_rows": int(per_expert[4:8].max()),
        "moe/overflow_passes": int(n_held > 256)}
    assert all(v.dtype == jnp.int32 for v in counts.values())


def test_the_whole_layer_and_a_biased_share_count_too():
    """A layer holding every expert has no branch: it counts every pair and
    never an overflow.  The share (4, 4) under a bias that sends every
    token to experts 4, 5, 6 counts all 384 pairs, 128 rows on the busiest
    expert and one overflow pass; with the bias at zero, none."""
    x = tokens(rows=2, seq=64)
    whole = counted(x, weights(), (0, E))
    assert whole["moe/held_pairs"] == 2 * 64 * K
    assert whole["moe/overflow_passes"] == 0
    p = weights(held=(4, 4))
    unbiased = counted(x, p, (4, 4))
    assert unbiased["moe/overflow_passes"] == 0
    assert 0 < unbiased["moe/held_pairs"] <= 256
    p["router_b"] = jnp.zeros((E,)).at[jnp.array([4, 5, 6])].set(10.0)
    assert counted(x, p, (4, 4)) == {
        "moe/held_pairs": 384, "moe/max_expert_rows": 128,
        "moe/overflow_passes": 1}


def test_the_whole_layer_has_no_branch_and_a_share_has_one():
    """A layer that holds every expert has no prefix under all its rows:
    its program is ``routed_part`` on all rows, the one there was before
    the prefix (the gradient's jaxpr, restated here, letter for letter); a
    share's has the one ``cond``, both branches with grouped matmuls."""
    x = tokens()

    def before_the_prefix(x, p, held):
        flat = x.reshape(-1, H)
        scores, chosen, gates = M.route_tokens(
            flat, p["router_w"], p["router_b"], top_k=K, scale=SCALE)
        aux = M.balance_loss(scores.reshape(*x.shape[:2], E),
                             chosen.reshape(*x.shape[:2], K), ALPHA)
        order, pos, sizes = M.sort_share(chosen, *held)
        n_held = jnp.sum(sizes)
        # (since PR 35 also the layer's step scalars, made here and
        # dropped by ``ffn``: the busiest expert, a constant 0 overflows)
        jnp.max(sizes), jnp.zeros((), jnp.int32)
        rows = M.dispatch(flat, order, pos, n_held)
        rows = M.grouped_swiglu(rows, p, sizes, n_held)
        routed = M.combine(rows, gates, order, pos, n_held)
        return routed.reshape(x.shape) + M.T._gated_mlp(x, p), aux

    def text(fn, held):
        mesh = make_mesh(devices=jax.devices()[:1])
        jaxpr = jax.make_jaxpr(jax.shard_map(
            jax.grad(lambda x, p: jnp.sum(fn(x, p, held)[0]),
                     argnums=(0, 1)),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(x, weights(held=held))
        # a frozenset (shard_map's manual axes) prints in any order
        return re.sub(r"frozenset\(\{[^}]*\}\)", "frozenset", str(jaxpr))

    whole = text(ffn, (0, E))
    assert " cond[" not in whole
    assert whole == text(before_the_prefix, (0, E))
    share = text(ffn, (4, 4))
    assert share.count(" cond[") == 2          # forward, backward
    assert share.count("ragged_dot_general[") == 2 * whole.count(
        "ragged_dot_general[") + 2 * 3         # a branch recomputes its own


def test_one_shard_overflows_and_the_other_does_not():
    """Expert parallelism over a ``model`` axis of 2 with a bias that sends
    every token to experts 4 and 5: the shard holding (4, 2) gets 256 +
    pairs, past its prefix of 128 rows, the shard holding (6, 2) a handful;
    each takes its own branch, the ``psum`` outside adds them, and the sum
    is the one-device layer's and the reference's."""
    held = (4, 4)
    x, p = tokens(rows=2, seq=64), weights(held=held)
    p["router_b"] = jnp.zeros((E,)).at[jnp.array([4, 5])].set(10.0)
    with jax.default_matmul_precision("highest"):
        _, chosen, _ = M.route_tokens(x.reshape(-1, H), p["router_w"],
                                      p["router_b"], top_k=K, scale=SCALE)
    prefix = M.prefix_rows(chosen.size, 2, E)
    landed = [int(jnp.sum(M.sort_share(chosen, first, 2)[2]))
              for first in (4, 6)]
    assert prefix == 128 and landed[0] >= 256 and 0 < landed[1] <= prefix
    mesh = make_mesh(model_parallel_size=2, devices=jax.devices()[:2])
    by_expert, col, row = (P(MODEL_AXIS, None, None), P(None, MODEL_AXIS),
                           P(MODEL_AXIS, None))
    specs = {"router_w": P(), "router_b": P(), "exp_gate_w": by_expert,
             "exp_up_w": by_expert, "exp_down_w": by_expert, "gate_w": col,
             "up_w": col, "down_w": row}

    sharded = jax.shard_map(
        lambda x, p: ffn(x, p, held)[0], mesh=mesh, in_specs=(P(), specs),
        out_specs=P(), check_vma=False)

    def total(fn):
        return lambda x, p: jnp.sum(jnp.square(fn(x, p)))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(sharded)(x, p)
        got_grads = jax.jit(jax.grad(total(sharded), argnums=(0, 1)))(x, p)
        want_grads = jax.grad(total(lambda x, p: plain(x, p, held)[0]),
                              argnums=(0, 1))(x, p)
    same(got, layer(x, p, held)[0])
    same(got, plain(x, p, held)[0])
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_the_pallas_grouped_matmul_is_ragged_dot_inside_the_groups():
    """The megablox kernels (interpret mode) against ``lax.ragged_dot``:
    the product and both gradients on the rows the groups cover, an empty
    group among them; what lies past the groups is never written (NaN in
    interpret mode), which is why the layer masks there."""
    R, k, n, e = 512, 128, 256, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(ks[0], (R, k))
    w = jax.random.normal(ks[1], (e, k, n))
    weight = jax.random.normal(ks[2], (R, n))
    sizes = jnp.array([100, 0, 200, 37], jnp.int32)
    held = (jnp.arange(R) < 337)[:, None]

    def run(interpret):
        def f(rows, w):
            out = grouped_matmul(rows, w, sizes, interpret)
            return jnp.sum(jnp.where(held, out, 0) * weight)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f, argnums=(0, 1))(rows, w)

    (want, (want_rows, want_w)), (got, (got_rows, got_w)) = run(False), run(
        True)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    same(jnp.where(held, got_rows, 0), jnp.where(held, want_rows, 0), 1e-4)
    same(got_w, want_w, 1e-4)
    assert float(jnp.max(jnp.abs(got_w[1]))) == 0.0      # the empty group
    # 100 rows: no whole number of 128-row tiles, so no kernel takes them
    # (the CPU's ragged_dot does; on a TPU it would run under no scope)
    with pytest.raises(ValueError, match="128-row tiles"):
        grouped_matmul(rows[:100], w, jnp.array([50, 0, 50, 0]), True)
    same(grouped_matmul(rows[:100], w, jnp.array([50, 0, 50, 0])),
         jax.lax.ragged_dot(rows[:100], w, jnp.array([50, 0, 50, 0])), 1e-5)


def test_tiles_fit_the_budget_at_the_published_widths():
    """2048 x 1408 and 1408 x 2048 in bf16: the tiles the three kernels
    get (tests/test_tpu_aot_kernels.py compiles them for a v5e)."""
    assert _tiles(512, 2048, 1408, 2, False) == (512, 512, 1408)
    assert _tiles(512, 1408, 2048, 2, False) == (512, 1408, 512)
    assert _tiles(512, 2048, 1408, 2, True) == (512, 256, 1408)
    assert _tiles(512, 1408, 2048, 2, True) == (512, 1408, 256)
    assert _tiles(128, 48, 64, 4, False) == (128, 48, 64)

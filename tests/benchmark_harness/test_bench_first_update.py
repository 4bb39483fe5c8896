"""The traffic kind ``train_steps_update`` and what it stands on: the
reading by hand, the reference's first Adam step by hand, the reference's
differentiable rounding, and — the reason the kind exists — a program that
computes ANOTHER share of the experts than the reference: its first-step
loss is inside the loss tolerance and its first update is not."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark.reference import kimi_moe as reference

CELL = "kimi-vl-a3b.ep8-seq8192"


@pytest.fixture(scope="module")
def cell():
    cell = cells.load(CELL)
    cell.config = cell.family.tiny(cell.config)
    return cell


def test_the_cell_runs_the_kind_with_the_second_check(cell):
    assert cell.traffic["kind"] == "train_steps_update"
    assert cell.kind.__name__.endswith("train_steps_update")
    full = cells.load(CELL).config["checks"]
    assert list(full) == ["loss_tolerance", "loss_tolerance_why",
                          "first_update_limit", "first_update_limit_why",
                          "warmup_loss_drop_share",
                          "warmup_loss_drop_share_why"]
    # between what a sound step reads and 1, what a state left unchanged
    # reads, with the more room above the sound reading
    assert 0.0 < full["first_update_limit"] < 0.5


def test_reading_by_hand(cell):
    read = cell.kind.leaf_readings
    want = {"a": np.array([1e-5, -1e-5, 1e-5, -1e-5]), "b": np.zeros(3),
            "c": np.zeros(2)}
    grad = {"a": np.array([-3.0, 1.0, -1.0, 3.0]), "b": np.zeros(3),
            "c": np.zeros(2)}

    def got(a, b=np.zeros(3), c=np.zeros(2)):
        return read({"a": np.asarray(a), "b": b, "c": c}, want, grad)

    same = got(want["a"])
    assert same == {"['a']": 0.0, "['b']": 0.0, "['c']": 0.0}
    assert got(np.zeros(4))["['a']"] == pytest.approx(1.0)      # unchanged
    assert got(-want["a"])["['a']"] == pytest.approx(2.0)       # the other way
    # one weight the other way: the one of gradient 1 of 8 in all weighs
    # 1/8, the one of gradient 3 weighs 3/8; 2 sqrt(share)
    assert got([1e-5, 1e-5, 1e-5, -1e-5])["['a']"] == pytest.approx(
        2 * math.sqrt(1 / 8))
    assert got([-1e-5, -1e-5, 1e-5, -1e-5])["['a']"] == pytest.approx(
        2 * math.sqrt(3 / 8))
    # a leaf no gradient reaches: it stays, or the reading is infinite
    assert got(want["a"], b=np.array([0.0, 1e-9, 0.0]))["['b']"] == math.inf
    assert cell.kind.worst({"x": 0.1, "y": 0.3, "z": 0.2, "w": 0.0}) == [
        ("y", 0.3), ("z", 0.2), ("x", 0.1)]


def test_first_adam_step_by_hand():
    grads = {"w": jnp.array([3.0, -4.0, 0.0]), "b": jnp.zeros(2)}
    step = reference.first_adam_step(grads, lr=1e-3, clip=0.0)
    np.testing.assert_allclose(step["w"], [-1e-3, 1e-3, 0.0], rtol=1e-6)
    assert not np.any(np.asarray(step["b"]))
    # norm 5 clipped to 1: g / 5; eps joins sqrt(v) before the correction,
    # so it weighs 1 / sqrt(1 - beta2) = 2 times as much as it says
    clipped = reference.first_adam_step(grads, lr=1e-3, clip=1.0, eps=0.1,
                                        beta2=0.75)
    np.testing.assert_allclose(
        clipped["w"], [-1e-3 * 0.6 / 0.8, 1e-3 * 0.8 / 1.0, 0.0], rtol=1e-5)
    # the fused form, one step from zero moments, written out
    g, b1, b2, eps = np.array([3e-7, -4.0, 0.0]), 0.9, 0.999, 1e-8
    m, v = (1 - b1) * g, (1 - b2) * g * g
    fused = -1e-3 * np.sqrt(1 - b2) / (1 - b1) * m / (np.sqrt(v) + eps)
    tiny = reference.first_adam_step({"w": jnp.asarray(g, jnp.float32)},
                                     lr=1e-3, clip=0.0)["w"]
    np.testing.assert_allclose(tiny, fused, rtol=1e-5)
    # ... which is NOT algorithm 1's step where the gradient is that small
    by_algorithm_1 = -1e-3 * g[0] / (abs(g[0]) + eps)
    assert abs(float(tiny[0])) < 0.52 * abs(by_algorithm_1)


def test_rounding_carries_a_gradient():
    x = jnp.array([1.2345678, -0.3333333, 7.7777777])
    assert np.array_equal(reference.rounded(x, None), x)
    coarse = reference.rounded(x, 3)
    assert np.array_equal(coarse, [1.25, -0.34375, 8.0])
    # the cotangent passes through, rounded the same way
    grad = jax.grad(lambda x: jnp.sum(reference.rounded(x, 3) * x))(x)
    np.testing.assert_array_equal(
        grad, np.asarray(coarse) + np.asarray(reference.rounded(x, 3)))
    assert np.array_equal(jax.grad(lambda x: jnp.sum(
        reference.rounded(x, None) * 2.0))(x), [2.0, 2.0, 2.0])


@pytest.fixture(scope="module")
def update(cell):
    fam = cell.family
    model = fam.build_model(cell.config, cell.traffic)
    params = model.init_params(jax.random.PRNGKey(7))
    batch = fam.make_batch(np.random.default_rng(7), 2, cell.config,
                           cell.traffic)

    def of(**precision):
        value, change, gradient = jax.jit(
            lambda p, b: fam.reference_first_update(
                p, b, cell.config, **precision))(params, batch)
        return float(value), jax.device_get(change), jax.device_get(gradient)

    return of


def test_the_reference_update_is_the_jobs_first_step(cell, update):
    """The job's rate and clipping reach ``first_adam_step``; the
    correction bias and the table's unused rows stay where they are."""
    value, change, gradient = update()
    job = cell.config["job"]
    lr, clip = job["optimizer"]["params"]["lr"], job["gradient_clipping"]
    assert 6.0 < value < 6.5
    named = dict(jax.tree_util.tree_flatten_with_path(change)[0])
    grads = dict(jax.tree_util.tree_flatten_with_path(gradient)[0])
    assert len(named) == 43
    norm = math.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                         for g in grads.values()))
    scale = min(1.0, clip / norm)
    moved = total = 0
    for path, leaf in named.items():
        name, g = jax.tree_util.keystr(path), grads[path] * scale
        np.testing.assert_allclose(
            leaf, -lr * g / (np.abs(g) + 1e-8 / math.sqrt(1e-3)),
            rtol=1e-4, atol=1e-12, err_msg=name)
        assert not np.any(leaf[g == 0.0]), name
        if name.endswith("['bias']"):
            assert not np.any(g), name
        moved += int(np.sum(np.abs(leaf) > 0.9 * lr))
        total += leaf.size
    assert (grads[next(p for p in grads if "embed" in str(p))] == 0).mean() \
        > 0.3                                   # rows no token of the batch used
    assert 0.5 < moved / total < 1.0


def test_a_lower_precision_moves_the_update_more_than_the_loss(cell, update):
    """``--probe-reference`` at the tiny size: fp8-wide operands move the
    loss by less than the tolerance and the first update by more than
    bf16 storage does."""
    read = cell.kind.leaf_readings
    exact, coarse, stored = update(), update(operand_bits=3), update(
        dtype=jnp.bfloat16)
    assert abs(coarse[0] - exact[0]) < cell.config["checks"][
        "loss_tolerance"]
    off = {name: max(read(got[1], exact[1], exact[2]).values())
           for name, got in (("coarse", coarse), ("stored", stored))}
    assert 0.0 < off["stored"] < off["coarse"] < 1.0
    assert off["coarse"] > 0.2


def opts(seed):
    return types.SimpleNamespace(seed=seed, probe_reference=False)


class Clock:
    def __call__(self, name, counted=True):
        import contextlib
        return contextlib.nullcontext()


def checks_of(cell, seed=11):
    lines = []
    out = cell.kind.set_up(cell, opts(seed), Clock(), lines.append,
                           jax.devices()[:1])
    return out[2]


def test_another_share_passes_the_loss_and_fails_the_update(cell):
    """The program holds experts 4-7 where the reference holds 0-3: random
    labels make the first-step loss blind to it, the first update is not."""
    sound = checks_of(cell)
    assert all(c["ok"] for c in sound.values()), sound
    assert sound["first_update"]["leaves"] == 43
    reading = sound["first_update"]["worst_leaves"][0][1]
    assert 0.0 < reading < sound["first_update"]["limit"]

    build = cell.family.build_model

    def other_share(config, traffic):
        return build({**config, "first_routed_held": 4}, traffic)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cell.family, "build_model", other_share)
        wrong = checks_of(cell)
    assert wrong["reference"]["ok"], wrong["reference"]
    assert wrong["warmup_loss_drop"]["ok"]
    assert not wrong["first_update"]["ok"]
    # worse than a state left unchanged: the router's and the experts'
    # gradients are another share's
    assert wrong["first_update"]["worst_leaves"][0][1] > 1.0


def test_the_kind_refuses_what_it_cannot_hand_the_reference(cell):
    import copy
    other = copy.copy(cell)
    other.traffic = {**cell.traffic, "gas": 2}
    with pytest.raises(ValueError, match="gas 1, one data shard, ZeRO 0"):
        other.kind.set_up(other, opts(0), Clock(), print, jax.devices()[:1])

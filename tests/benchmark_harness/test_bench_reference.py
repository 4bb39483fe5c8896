"""The plain references against the program's models, loss AND gradients, at
the tiny presets in float32 on the CPU — the sharp form of the check the
benchmark makes on the chip at the published widths (where only the loss at
the initial weights is compared)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import cell as cells
from deepspeed_tpu.parallel.topology import make_mesh

CASES = {
    "bert": ("bert-large.seq128", {"seq": 64, "masked_positions": 10}),
    "gpt2": ("gpt2-xl.1chip", {"seq": 64}),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    """(family, tiny config, traffic, model, params, batch, the program's
    loss and gradients through the model's own ``apply``)."""
    name, traffic = CASES[request.param]
    cell = cells.load(name)
    family, config = cell.family, cell.family.tiny(cell.config)
    model = family.build_model(config, traffic)
    params = model.init_params(jax.random.PRNGKey(0))
    # biases and LayerNorm offsets start at zero: move every leaf, so a
    # swapped or dropped one shows
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])
    batch = family.make_batch(np.random.default_rng(0), 4, config, traffic)
    mesh = make_mesh(devices=jax.devices()[:1])

    def program(p):
        # the models run on local shards inside shard_map; one device
        return jax.shard_map(
            lambda p, *b: model.apply(p, *b), mesh=mesh,
            in_specs=(P(),) * (1 + len(batch)), out_specs=P(),
            check_vma=False)(p, *batch)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(program)(params)
    return family, config, params, batch, float(loss), grads


def test_reference_loss_agrees_with_the_model(case):
    family, config, params, batch, loss, _ = case
    ref = float(family.reference_loss(params, batch, config))
    # float32 both sides; the MLM average's +1e-5 in the denominator is the
    # only arithmetic that differs (2e-8 relative)
    assert ref == pytest.approx(loss, rel=2e-6)


def test_reference_gradients_agree_with_the_model(case):
    family, config, params, batch, _, grads = case
    ref = jax.grad(lambda p: family.reference_loss(p, batch, config))(params)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, ref)))
    # float32 sums in another order: 1e-5 of a leaf's largest gradient
    assert worst < 1e-5


def test_lower_precision_moves_the_reference_loss(case):
    """The reference's precision knobs price a precision step: rounding the
    matmul operands to fp8's 3 mantissa bits moves the loss more than
    bfloat16's 7, and both move it."""
    family, config, params, batch, _, _ = case
    exact = float(family.reference_loss(params, batch, config))
    d7 = abs(float(family.reference_loss(params, batch, config,
                                         operand_bits=7)) - exact)
    d3 = abs(float(family.reference_loss(params, batch, config,
                                         operand_bits=3)) - exact)
    assert 0 < d7 < d3


def test_round_mantissa_by_hand():
    from benchmark.reference.ops import round_mantissa
    x = jnp.asarray([1.0, 1.0 + 2 ** -4, 1.0 + 2 ** -3, -3.3], jnp.float32)
    # 3 mantissa bits: steps of 1/8 in [1, 2), 1/4 in [2, 4); ties up
    assert round_mantissa(x, 3).tolist() == [1.0, 1.125, 1.125, -3.25]
    assert round_mantissa(x, None) is x

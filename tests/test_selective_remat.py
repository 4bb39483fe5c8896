"""What the recomputation policies keep, read in the jaxpr.

``transformer.remat_wrap`` saves the names of ``ops/remat_names.py``:
under ``selective`` the QKV and first FFN matmul outputs, the streaming
kernel's output and log-sum-exp, and a post-LN block's two residual sums;
under ``full`` the kernel's two alone.  These tests count what the backward
pass still replays, and pin that no policy moves the loss or a gradient.
(The kernel-call count is in test_pallas_attention.py and, for the looped
model, in test_looped_model.py.)
"""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import GPT2, BertForPreTraining, LoopedLM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import remat_names
from deepspeed_tpu.parallel.topology import make_mesh

VOCAB, SEQ = 64, 16
TINY = dict(vocab_size=VOCAB, max_seq_len=SEQ, num_layers=2, hidden_size=32,
            num_heads=4)


def one_device(fn, n_args):
    """``fn`` on local shards of a one-device mesh (the layers psum over
    the ``model`` axis, so they need a ``shard_map`` around them)."""
    mesh = make_mesh(devices=jax.devices()[:1])
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * n_args,
                         out_specs=P(), check_vma=False)


def block_grad_dots(pre_ln, wrap):
    """``dot_general``s in the jaxpr of the gradient of one block whose scan
    body went through ``wrap``."""
    cfg = T.TransformerConfig(
        vocab_size=VOCAB, max_seq_len=SEQ, hidden_size=32, num_layers=1,
        num_heads=4, pre_ln=pre_ln, causal=pre_ln, remat_policy="selective")
    p = jax.tree_util.tree_map(
        lambda l: l[0], T.init_block_params(cfg, jax.random.PRNGKey(0)))
    x = jnp.ones((2, SEQ, 32), jnp.float32)
    body = wrap(lambda c, lp: (T.block_apply(c, lp, cfg), None), cfg)
    loss = one_device(lambda x, p: jnp.sum(body(x, p)[0]), 2)
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, p))
    return len(re.findall(r"\bdot_general\b", text))


def two_names(body, cfg):
    """The policy as it was before the kernel's and post-LN names."""
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            remat_names.QKV, remat_names.FFN1))


@pytest.mark.parametrize("pre_ln,fewer", [(False, 2), (True, 0)],
                         ids=["post-ln", "pre-ln"])
def test_selective_replays_no_proj_or_fc2_matmul_in_a_post_ln_block(
        pre_ln, fewer):
    """Post-LN: the two LayerNorms' inputs are saved, so neither the proj
    nor the fc2 matmul runs again.  Pre-LN: no new name, the same program."""
    assert (block_grad_dots(pre_ln, two_names)
            - block_grad_dots(pre_ln, T.remat_wrap)) == fewer


def bert_batch(rows=4, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(rows, SEQ)).astype(np.int32)
    mask = np.ones((rows, SEQ), np.int32)
    mask[:, SEQ - 3:] = 0
    labels = np.where(rng.random((rows, SEQ)) < 0.3, ids, -1).astype(np.int32)
    return ids, mask, np.zeros_like(ids), labels


def lm_batch(rows=4, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, VOCAB, size=(rows, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


#: family -> (model class, overrides of its "tiny" preset, batch maker)
FAMILIES = {"bert": (BertForPreTraining, TINY, bert_batch),
            "gpt2": (GPT2, TINY, lm_batch),
            "ouro": (LoopedLM, {}, lm_batch)}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_loss_and_every_gradient_agree_under_every_policy(family):
    """fp32 on the CPU: recomputation off, ``selective`` and ``full`` give
    the same loss bit for bit and the same gradient of every leaf to the
    tolerance test_models.py pins loss trajectories to (a saved residual is
    the very value its replay produces; XLA orders the fused reductions of
    the three backward programs differently, by under 1e-8 absolute)."""
    cls, tiny, make_batch = FAMILIES[family]
    batch = make_batch()

    def loss_and_grads(**remat):
        model = cls.from_size("tiny", **tiny, **remat)
        params = model.init_params(jax.random.PRNGKey(7))
        fn = one_device(lambda p, *b: model.apply(p, *b), 1 + len(batch))
        return jax.jit(jax.value_and_grad(fn))(params, *batch)

    want_loss, want = loss_and_grads(remat=False)
    assert np.isfinite(float(want_loss))
    for policy in ("selective", "full"):
        loss, got = loss_and_grads(remat=True, remat_policy=policy)
        assert float(loss) == float(want_loss), policy
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                err_msg=f"{policy} {jax.tree_util.keystr(path)}")


def calls_of(name):
    """(file, argument ASTs) of every call of ``name`` in the package."""
    root = pathlib.Path(deepspeed_tpu.__file__).parent
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id",
                                getattr(node.func, "attr", None)) == name):
                yield str(path.relative_to(root)), node.args


def test_selective_saves_is_the_only_list_of_the_names():
    """Every tagger names its tensor by a constant of ``remat_names`` (no
    string literal at a call site), every constant is in ``SELECTIVE_SAVES``
    and has a tagger, ``FULL_SAVES`` is a part of it, and every policy built
    from names takes one of the two tuples."""
    consts = {k: v for k, v in vars(remat_names).items()
              if k.isupper() and isinstance(v, str)}
    assert sorted(consts.values()) == sorted(remat_names.SELECTIVE_SAVES)
    used = set()
    for path, args in calls_of("checkpoint_name"):
        assert isinstance(args[1], ast.Name) and args[1].id in consts, (
            f"{path}: checkpoint_name takes a constant of ops/remat_names.py,"
            f" got {ast.dump(args[1])}")
        used.add(consts[args[1].id])
    assert used == set(remat_names.SELECTIVE_SAVES)
    assert set(remat_names.FULL_SAVES) < set(remat_names.SELECTIVE_SAVES)
    policies = list(calls_of("save_only_these_names"))
    assert {path for path, _ in policies} == {"models/transformer.py"}
    tuples = []
    for _, args in policies:
        (arg,) = args
        assert isinstance(arg, ast.Starred), ast.dump(arg)
        tuples.append(arg.value.id)
    assert sorted(tuples) == ["FULL_SAVES", "SELECTIVE_SAVES"]

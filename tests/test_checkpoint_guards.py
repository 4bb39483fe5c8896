"""Checkpoint robustness guards (ADVICE r5).

1. ZeRO-3 shard records key by FLATTEN-ORDER LEAF INDEX (keystr is a
   debug label): the old hand-formatted path strings broke on any state
   tree with non-string dict keys — pinned by a round trip through a
   model whose params contain an int-keyed dict.
2. Chunk refs are namespaced and validated: user tuples colliding with
   the ref tags round-trip intact (escaped at seal time), corrupt refs
   raise a named ValueError instead of handing back a garbage memmap.
3. The async writer no longer silently rewrites user namedtuples in
   ``client_state`` to plain tuples — they are rejected at save time in
   both modes (docs/features.md "client_state restrictions").
"""

import collections
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import checkpoint as ckpt_mod
from deepspeed_tpu.models import transformer as T

VOCAB, SEQ = 64, 16


class IntLayerModel:
    """Minimal ZeRO-3-cooperating model whose params contain an
    INT-keyed dict ({"layers": {0: ..., 1: ...}}) — jax pytrees allow it,
    and the shard-record keying must survive it."""

    zero3_dims = None

    def init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        n = lambda k, s: jax.random.normal(k, s, jnp.float32) * 0.02
        return {"emb": n(k1, (VOCAB, 32)),
                "layers": {0: n(k2, (32, 32)), 1: n(k3, (32, 32))}}

    def partition_specs(self, params):
        from jax.sharding import PartitionSpec as P
        return jax.tree_util.tree_map(lambda _: P(), params)

    def apply(self, params, toks, labels):
        params, _ = T.zero3_enter(params, self.zero3_dims, deferred=())
        x = params["emb"].astype(jnp.bfloat16)[toks]
        for i in (0, 1):
            x = jnp.tanh(x @ params["layers"][i].astype(x.dtype))
        logits = (x @ params["emb"].astype(x.dtype).T).astype(jnp.float32)
        lse = jax.nn.log_softmax(logits)
        tok = -jnp.take_along_axis(
            lse, jnp.clip(labels, 0, None)[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum(tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    __call__ = apply


def int_model_engine(seed=7):
    model = IntLayerModel()
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8, "steps_per_print": 10 ** 6,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3}},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(seed)))
    return engine


def lm_batch(seed=1):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, size=(8, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


def plain_engine(**cfg_over):
    from deepspeed_tpu.models import GPT2
    model = GPT2.from_size("tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                           num_layers=2, hidden_size=32, num_heads=4)
    cfg = {"train_batch_size": 8, "steps_per_print": 10 ** 6,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True}}
    cfg.update(cfg_over)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(7)))
    return engine


# ---------------------------------------------- leaf-index shard records

def test_zero3_int_keyed_dict_roundtrip(tmp_path):
    """An int-keyed dict in the state tree must save AND restore at stage
    3 (the old keystr-formatted record keys raised KeyError on load)."""
    eng = int_model_engine()
    # the int-keyed leaves really are partitioned (markers in the model
    # file, data in the per-dp shard files)
    import deepspeed_tpu.zero3 as Z
    assert Z.partitioned_any(eng._zero3_dims["layers"])
    eng.train_batch(lm_batch(0))
    eng.save_checkpoint(str(tmp_path), tag="ik")
    ref = float(eng.train_batch(lm_batch(5)))
    e2 = int_model_engine(seed=11)   # different init: must come from disk
    e2.load_checkpoint(str(tmp_path), tag="ik")
    got = float(e2.train_batch(lm_batch(5)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_zero3_records_carry_keystr_label(tmp_path):
    eng = int_model_engine()
    eng.train_batch(lm_batch(0))
    eng.save_checkpoint(str(tmp_path), tag="lbl")
    shard_files = [f for f in os.listdir(os.path.join(str(tmp_path), "lbl"))
                   if f.startswith("zero3_dp_rank_")]
    shard = ckpt_mod._load_obj(
        os.path.join(str(tmp_path), "lbl", shard_files[0]))
    keys = [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(eng.params)]
    for idx, rec in shard["leaves"].items():
        assert isinstance(idx, int)
        assert rec["keystr"] == keys[idx]   # debug label matches the walk


# ------------------------------------------- chunk-ref namespace + guards

@pytest.mark.parametrize("async_save", [False, True])
def test_client_state_tag_collision_roundtrip(tmp_path, async_save):
    """User tuples that LOOK like chunk refs / escape wrappers must
    round-trip intact instead of being resolved into garbage memmaps."""
    eng = plain_engine()
    eng.train_batch(lm_batch(0))
    evil = {
        "fake_ref": (ckpt_mod._CHUNK_TAG, 16, "float32", (4,)),
        "fake_escape": (ckpt_mod._ESCAPE_TAG, ("x",)),
        "nested": [((ckpt_mod._CHUNK_TAG, 0, "int8", ()), "ok")],
    }
    eng.save_checkpoint(str(tmp_path), tag="ns", client_state=evil,
                        async_save=async_save)
    eng.checkpoint_wait()
    e2 = plain_engine()
    _, client = e2.load_checkpoint(str(tmp_path), tag="ns")
    assert client["fake_ref"] == evil["fake_ref"]
    assert client["fake_escape"] == evil["fake_escape"]
    assert client["nested"] == evil["nested"]


def test_corrupt_chunk_ref_raises(tmp_path):
    """A ref whose offset/size falls outside the payload region (or whose
    dtype is unknown) raises a named ValueError BEFORE any memmap is
    built."""
    def write_raw(path, header):
        with open(path, "wb") as f:
            f.write(ckpt_mod._MAGIC)
            f.write((0).to_bytes(8, "little"))
            f.write(b"\x00" * 64)             # payload region
            off = f.tell()
            pickle.dump(header, f)
            f.seek(len(ckpt_mod._MAGIC))
            f.write(off.to_bytes(8, "little"))

    p = str(tmp_path / "corrupt.pt")
    write_raw(p, {"x": (ckpt_mod._CHUNK_TAG, 10 ** 9, "float32", (4,))})
    with pytest.raises(ValueError, match="payload region"):
        ckpt_mod._load_obj(p)
    write_raw(p, {"x": (ckpt_mod._CHUNK_TAG, 16, "not_a_dtype", (4,))})
    with pytest.raises(ValueError, match="dtype"):
        ckpt_mod._load_obj(p)
    write_raw(p, {"x": (ckpt_mod._CHUNK_TAG, "16", "float32", (4,))})
    with pytest.raises(ValueError, match="malformed"):
        ckpt_mod._load_obj(p)


PointNT = collections.namedtuple("PointNT", ["x", "y"])


@pytest.mark.parametrize("async_save", [False, True])
def test_client_state_namedtuple_rejected(tmp_path, async_save):
    """Namedtuples in client_state fail LOUDLY at save time (the async
    writer used to flatten them to plain tuples silently; the restricted
    loader could never reconstruct them anyway)."""
    eng = plain_engine()
    eng.train_batch(lm_batch(0))
    with pytest.raises(TypeError, match="namedtuple"):
        eng.save_checkpoint(str(tmp_path), tag="nt",
                            client_state={"p": PointNT(1, 2)},
                            async_save=async_save)
    with pytest.raises(TypeError, match="namedtuple"):
        eng.save_checkpoint(str(tmp_path), tag="nt2",
                            client_state={"deep": [{"k": PointNT(3, 4)}]})


def test_scheduler_state_namedtuple_rejected_at_call_time(tmp_path):
    """A scheduler whose state_dict() smuggles a namedtuple must also
    fail AT save_checkpoint time (an async save would otherwise defer the
    TypeError to the background writer, surfacing at the next wait())."""

    class EvilSched:
        def step(self):
            pass

        def state_dict(self):
            return {"inner": PointNT(1, 2)}

    eng = plain_engine()
    eng.train_batch(lm_batch(0))
    eng.lr_scheduler = EvilSched()
    with pytest.raises(TypeError, match="namedtuple"):
        eng.save_checkpoint(str(tmp_path), tag="sched",
                            async_save=True)

"""Step scalars (``deepspeed_tpu/observability/scalars.py``): counts a model
takes on the device leave the compiled step beside the loss, are reduced
inside the program, accumulate on the device and are read without a per-step
fence.  A toy model on the CPU, one device and four forced host devices."""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu import analysis
from deepspeed_tpu.models import GPT2, LoopedLM
from deepspeed_tpu.observability import fences, scalars, schema
from deepspeed_tpu.parallel.topology import make_mesh

WIDTH, MICRO = 8, 4


class Toy:
    """A linear model that counts, per micro-batch and shard, the positive
    entries of its input (a ``sum``) and the most in one row (a ``max``)."""

    def __init__(self, returns=("moe/held_pairs", "moe/max_expert_rows"),
                 declares=None, scale=1):
        self.returns = returns
        self.declares = returns if declares is None else declares
        self.scale = scale

    def init_params(self, rng):
        return {"w": 0.1 * jnp.ones((WIDTH, WIDTH), jnp.float32)}

    def partition_specs(self, params=None):
        return {"w": P()}

    def step_scalars(self):
        return {name: 1 for name in self.declares}

    def apply(self, params, x, y):
        loss = jnp.mean(jnp.square(x @ params["w"].astype(x.dtype) - y))
        positive = (x > 0).astype(jnp.int32)
        counted = {
            "moe/held_pairs": self.scale * jnp.sum(positive),
            "moe/max_expert_rows": jnp.max(jnp.sum(positive, axis=1)),
            "not/in_the_table": jnp.sum(positive)}
        if not self.returns:
            return loss
        return scalars.WithScalars(
            loss, {name: counted[name] for name in self.returns})

    __call__ = apply


def config(gas=1, devices=1, stage=0, **over):
    cfg = {"train_batch_size": MICRO * gas * devices,
           "gradient_accumulation_steps": gas, "steps_per_print": 10 ** 9,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}, **over}
    if stage:
        cfg.update(zero_optimization={"stage": stage},
                   bf16={"enabled": True})
    return cfg


def build(model, gas=1, devices=1, stage=0, **over):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config(gas, devices, stage, **over),
        mesh=make_mesh(devices=jax.devices()[:devices]))
    return engine


def batches(n, gas=1, devices=1, seed=0):
    rng = np.random.default_rng(seed)
    shape = (MICRO * gas * devices, WIDTH)
    return [(rng.standard_normal(shape).astype(np.float32),
             rng.standard_normal(shape).astype(np.float32))
            for _ in range(n)]


def by_hand(steps, gas, devices):
    """The totals restated in numpy: a step's batch is split into
    ``devices`` shards of ``gas`` micro-batches of ``MICRO`` rows."""
    held, busiest = 0, 0
    for x, _ in steps:
        positive = (x > 0).reshape(devices * gas, MICRO, WIDTH)
        held += int(positive.sum())
        busiest = max(busiest, int(positive.sum(axis=2).max()))
    return {"moe/held_pairs": held, "moe/max_expert_rows": busiest}


@pytest.mark.parametrize("devices, gas, stage", [
    (1, 1, 0), (1, 4, 0), (4, 1, 0), (4, 4, 0), (4, 1, 1), (4, 4, 1)])
def test_totals_are_the_numpy_restatement(devices, gas, stage):
    engine = build(Toy(), gas, devices, stage)
    steps = batches(3, gas, devices)
    for batch in steps:
        engine.train_batch(batch)
    got = engine.read_step_scalars()
    assert got["values"] == by_hand(steps, gas, devices)
    assert (got["steps"], got["micro_steps"]) == (3, 3 * gas)
    assert (got["batch_shards"], got["model_shards"]) == (devices, 1)
    # the registry's ``model`` group serves the same host-side numbers
    group = engine.telemetry.registry.collect()["model"]
    assert group["moe/held_pairs"] == got["values"]["moe/held_pairs"]
    assert group["scalar_steps"] == 3


@pytest.mark.parametrize("returns, declares, match", [
    (("moe/held_pairs", "not/in_the_table"), ("moe/held_pairs",),
     "unknown step scalar 'not/in_the_table'"),
    (("moe/held_pairs", "moe/max_expert_rows"), ("moe/held_pairs",),
     "returned step scalar 'moe/max_expert_rows'"),
    (("moe/held_pairs",), ("moe/held_pairs", "moe/max_expert_rows"),
     "declares step scalar 'moe/max_expert_rows' and did not return"),
])
def test_a_name_out_of_place_fails_at_trace_time_by_name(returns, declares,
                                                         match):
    engine = build(Toy(returns, declares))
    with pytest.raises(KeyError, match=match):
        engine.train_batch(batches(1)[0])


def test_the_table_refuses_what_it_does_not_hold():
    with pytest.raises(KeyError, match="unknown step scalar"):
        scalars.Channel({"loop/nothing": 1})
    with pytest.raises(TypeError, match="no step_scalars"):
        build(Toy(declares=())).train_batch(batches(1)[0])
    for name, entry in scalars.SCALARS.items():
        assert entry.reduction in scalars.KINDS and entry.unit and \
            entry.counts, name


@pytest.mark.parametrize("devices, gas, stage", [(1, 2, 0), (4, 2, 1)])
def test_the_scalars_change_no_loss_and_no_parameter(devices, gas, stage):
    """Bitwise: the same model with its scalars stripped takes the same
    steps."""
    steps = batches(3, gas, devices)
    runs = []
    for model in (Toy(), Toy(returns=())):
        engine = build(model, gas, devices, stage)
        losses = [np.asarray(engine.train_batch(b)) for b in steps]
        runs.append((losses, np.asarray(engine.params["w"])))
    for a, b in zip(runs[0][0], runs[1][0]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(runs[0][1], runs[1][1])
    assert float(np.abs(runs[0][1] - 0.1).max()) > 0


# ------------------------------------------- a model that declares nothing

def step_shape(jaxpr):
    """(operands, results, equations of every nested jaxpr, sha256 of the
    text with addresses, source lines and set orders struck out)."""
    n, pending = 0, [jaxpr.jaxpr]
    while pending:
        inner = pending.pop()
        n += len(inner.eqns)
        for eqn in inner.eqns:
            pending.extend(jax.core.jaxprs_in_params(eqn.params))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    text = re.sub(r" at [^\s:]+:\d+", " at FILE", text)
    text = re.sub(r"frozenset\(\{[^}]*\}\)", "frozenset", text)
    return (len(jaxpr.jaxpr.invars), len(jaxpr.jaxpr.outvars), n,
            hashlib.sha256(text.encode()).hexdigest()[:16])


def lm_batch(rows, vocab=512, seq=64):
    doc = np.random.default_rng(0).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


#: the fused step of models that declare nothing, as the PARENT of PR 35
#: (commit 1a17d74, jax 0.9.0) traced it: operands, results, equations and
#: the digest of the jaxpr — the channel adds nothing to such a program
PARENT_STEPS = {
    "gpt2-gas1": (79, 77, 1125, "0aa5d8b74b25fbbe"),
    "gpt2-gas2-dp2-zero1": (34, 32, 743, "a0578bcb553aa73e"),
    "looped-gas1": (79, 77, 1346, "258faadd4175baf3"),
    "gpt2-gas2-spool": (81, 79, 1177, "cd20438524d5fbdc"),
}


@pytest.mark.parametrize("name", sorted(PARENT_STEPS))
def test_a_model_that_declares_nothing_runs_the_parents_step(name):
    make, devices, gas, stage, over = {
        "gpt2-gas1": (lambda: GPT2.from_size("tiny"), 1, 1, 0, {}),
        "gpt2-gas2-dp2-zero1": (lambda: GPT2.from_size("tiny"), 2, 2, 1,
                                {}),
        "looped-gas1": (lambda: LoopedLM.from_size("tiny"), 1, 1, 0, {}),
        "gpt2-gas2-spool": (lambda: GPT2.from_size("tiny"), 1, 2, 0,
                            {"observability": {"report_window": 4}}),
    }[name]
    cfg = {"train_batch_size": 2 * gas * devices,
           "gradient_accumulation_steps": gas, "steps_per_print": 10 ** 9,
           "bf16": {"enabled": True}, "gradient_clipping": 1.0,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}
    if stage:
        cfg["zero_optimization"] = {"stage": stage}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make(), config=cfg,
        mesh=make_mesh(devices=jax.devices()[:devices]))
    assert engine._scalars is None and engine.read_step_scalars() is None
    assert scalars.snapshot() is None
    batch = lm_batch(2 * gas * devices)
    fn = engine._build_train_batch(batch)
    assert step_shape(analysis.trace_train_batch(engine, batch, fn=fn)) == \
        PARENT_STEPS[name]


# ------------------------------------------------------ the program itself

def primitives(jaxpr):
    names, pending = [], [jaxpr.jaxpr]
    while pending:
        inner = pending.pop()
        for eqn in inner.eqns:
            names.append(eqn.primitive.name)
            pending.extend(jax.core.jaxprs_in_params(eqn.params))
    return names


@pytest.mark.parametrize("gas", [1, 4])
def test_the_step_holds_no_transfer_and_one_collective_per_kind(gas):
    """Nothing in the step program goes to the host, graph lint's transfer
    pass stays green, and beside the stripped model's collectives there is
    one ``psum`` (the sums) and one ``pmax`` (the maxes) a STEP — outside
    the accumulation scan — whatever the number of names."""
    devices = 4
    batch = batches(1, gas, devices)[0]
    counts, bodies = [], []
    for model in (Toy(), Toy(returns=())):
        engine = build(model, gas, devices)
        engine.train_batch(batch)
        jaxpr = analysis.trace_train_batch(engine, batch)
        bodies.append(shard_map_body(jaxpr))
        names = primitives(jaxpr)
        assert not [n for n in names if "callback" in n or n in (
            "device_put", "infeed", "outfeed")]
        counts.append({p: names.count(p) for p in ("psum", "pmax")})
        report = analysis.analyze_engine_train_batch(engine, batch)
        assert not [f for f in report.findings
                    if f.code.startswith("transfer.")]
    assert counts[0]["psum"] == counts[1]["psum"] + 1
    assert (counts[0]["pmax"], counts[1]["pmax"]) == (1, 0)
    # the two new collectives stand outside every loop
    top = [[eqn.primitive.name for eqn in body.eqns] for body in bodies]
    assert top[0].count("pmax") == 1
    assert top[0].count("psum") == top[1].count("psum") + 1


def shard_map_body(closed):
    """The body of the (one) ``shard_map`` of a traced step program."""
    pending = [closed.jaxpr]
    while pending:
        inner = pending.pop()
        for eqn in inner.eqns:
            if eqn.primitive.name == "shard_map":
                return eqn.params["jaxpr"]
            pending.extend(jax.core.jaxprs_in_params(eqn.params))
    raise AssertionError("no shard_map in the step program")


# ---------------------------------------------------------------- reading

def test_a_read_is_one_fence_and_idempotent():
    engine = build(Toy(), gas=2)
    steps = batches(2, gas=2)
    before = fences.FENCE_COUNT
    for batch in steps:
        engine.train_batch(batch)
    assert fences.FENCE_COUNT == before        # no fence on the step path
    first = engine.read_step_scalars()
    assert fences.FENCE_COUNT == before + 1
    again = engine.read_step_scalars()
    assert fences.FENCE_COUNT == before + 1    # nothing ran in between
    assert first == again and first["values"] == by_hand(steps, 2, 1)
    # the reader with no engine in hand sees the same channel
    assert scalars.snapshot() == first
    # zeros went to the next step: the device holds one step's count alone
    engine.train_batch(steps[0])
    device = {k: np.asarray(v) for k, v in engine._scalars.device.items()}
    assert device["sum"].tolist() == [by_hand(steps[:1], 2, 1)[
        "moe/held_pairs"]]
    assert engine.read_step_scalars()["values"] == by_hand(
        steps + steps[:1], 2, 1)


def test_a_count_past_two_to_the_24_stays_exact_across_reads():
    """fp32 adds are exact to 2**24; every read moves the device's totals
    into Python numbers and starts the device again at zero."""
    per_step = 2 ** 22 + 1
    engine = build(Toy(scale=per_step))
    x = -np.ones((MICRO, WIDTH), np.float32)
    x[0, 0] = 1.0                                  # one positive entry
    assert per_step * 3 < 2 ** 24 < per_step * 4
    for _ in range(3):
        for _ in range(3):
            engine.train_batch((x, x))
        engine.read_step_scalars()
    got = engine.read_step_scalars()
    assert got["steps"] == 9
    assert got["values"]["moe/held_pairs"] == 9 * per_step > 2 ** 25
    # without the reads the device's own sum has rounded
    unread = build(Toy(scale=per_step))
    for _ in range(9):
        unread.train_batch((x, x))
    assert unread.read_step_scalars()["values"]["moe/held_pairs"] != \
        9 * per_step


def test_train_many_gives_the_totals_of_as_many_train_batch_calls():
    steps = batches(4, gas=2, devices=2)
    serial = build(Toy(), gas=2, devices=2)
    for batch in steps:
        serial.train_batch(batch)
    fused = build(Toy(), gas=2, devices=2, train_steps_per_dispatch=2)
    fused.train_many(steps[:2])
    fused.train_many(steps[2:])
    want, got = serial.read_step_scalars(), fused.read_step_scalars()
    assert got == want and got["values"] == by_hand(steps, 2, 2)
    assert (got["steps"], got["micro_steps"]) == (4, 8)
    np.testing.assert_array_equal(np.asarray(serial.params["w"]),
                                  np.asarray(fused.params["w"]))


def test_the_split_api_trains_a_declaring_model_and_reports_nothing():
    """``forward`` / ``backward`` / ``step`` drop the scalars: the same loss
    as the model without them, and nothing in the channel."""
    batch = batches(1)[0]
    losses = []
    for model in (Toy(), Toy(returns=())):
        engine = build(model)
        loss = engine(*batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
        if model.returns:
            got = engine.read_step_scalars()
            assert got["steps"] == 0 and got["values"] == {
                "moe/held_pairs": 0.0, "moe/max_expert_rows": 0.0}
    assert losses[0] == losses[1]


# ----------------------------------------------------------- with the spool

def test_the_window_event_carries_the_windows_scalars(tmp_path):
    """Window 2, five steps: two drained windows and, at ``flush()``, the
    last partial one; each event holds ITS steps' scalars, passes the
    schema, and the ``model`` counters beside them run on since
    ``initialize``.  No fence but the flush's."""
    log = tmp_path / "events.jsonl"
    engine = build(Toy(), gas=2, observability={
        "report_window": 2, "jsonl_path": str(log)})
    steps = batches(5, gas=2)
    before = fences.FENCE_COUNT
    for batch in steps:
        engine.train_batch(batch)
    assert fences.FENCE_COUNT == before
    engine.flush_telemetry()
    assert fences.FENCE_COUNT == before + 1
    assert schema.validate_jsonl(str(log)) == []
    events = [json.loads(line) for line in log.read_text().splitlines()]
    windows = [e for e in events if e["schema"] == schema.SCHEMA_ID]
    assert [e["window_steps"] for e in windows] == [2, 2, 1]
    assert all(e["version"] == 3 for e in windows)
    for event, covered in zip(windows, (steps[:2], steps[2:4], steps[4:])):
        assert event["scalars"] == by_hand(covered, 2, 1)
    assert windows[-1]["counters"]["model/moe/held_pairs"] == by_hand(
        steps, 2, 1)["moe/held_pairs"]
    assert windows[-1]["counters"]["model/scalar_steps"] == 5
    # a read after the drains: nothing left on the device, the same totals
    got = engine.read_step_scalars()
    assert fences.FENCE_COUNT == before + 1
    assert got["values"] == by_hand(steps, 2, 1) and got["steps"] == 5
    engine.telemetry.close()                   # the JSONL sink's file


def test_a_read_between_drains_keeps_the_windows_whole():
    events = []
    engine = build(Toy(), observability={"report_window": 2})
    engine.telemetry.registry.add_sink(type("Sink", (), {
        "emit": lambda self, event, sample_count=None: events.append(event),
        "close": lambda self: None})())
    steps = batches(4)
    engine.train_batch(steps[0])
    assert engine.read_step_scalars()["values"] == by_hand(steps[:1], 1, 1)
    for batch in steps[1:]:
        engine.train_batch(batch)
    engine.flush_telemetry()
    windows = [e for e in events if "window_steps" in e]
    assert [e["scalars"] for e in windows] == [
        by_hand(steps[:2], 1, 1), by_hand(steps[2:], 1, 1)]


def test_the_schema_holds_the_scalars_field_to_numbers():
    base = {"schema": schema.SCHEMA_ID, "version": schema.SCHEMA_VERSION,
            "ts": 1.0, "step": 3, "window_steps": 3, "skipped": 0,
            "counters": {}}
    for name in schema.FIELDS:
        base.setdefault(name, None)
    assert schema.SCHEMA_VERSION == 3 and "scalars" in schema.FIELDS
    assert schema.validate_event(base) is None             # null: declares none
    good = {"moe/held_pairs": 12.0, "loop/exit_ce": [1.0, 2.5]}
    assert schema.validate_event({**base, "scalars": good}) is None
    for bad in ({"moe/held_pairs": "12"}, {"loop/exit_ce": []},
                {"moe/held_pairs": True}, ["moe/held_pairs"]):
        assert schema.validate_event({**base, "scalars": bad}) is not None
    # a log from before the field still validates at its own version
    old = {k: v for k, v in base.items() if k != "scalars"}
    assert schema.validate_event({**old, "version": 2}) is None
    assert "scalars" in schema.validate_event({**old, "version": 3})


def test_folds_from_many_threads_lose_no_update():
    """The spool's drain folds from the runtime's callback thread while the
    step path hands totals over and a reader asks: more workers than cores,
    a shortened switch interval, and the invariant a lost update breaks."""
    import sys
    import threading
    channel = scalars.Channel({"moe/held_pairs": 1, "moe/max_expert_rows": 1})
    workers, rounds = 16, 300
    seen = []

    def work(k):
        for i in range(rounds):
            _, steps, micro = channel.hand_over()
            channel.fold({"sum": np.asarray([3.0]),
                          "max": np.asarray([float(k * rounds + i)])},
                         1, 2)
            seen.append(channel.host()["steps"])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    got = channel.host()
    assert (got["steps"], got["micro_steps"]) == (workers * rounds,
                                                  2 * workers * rounds)
    assert got["values"] == {"moe/held_pairs": 3.0 * workers * rounds,
                             "moe/max_expert_rows": workers * rounds - 1.0}
    assert channel._handed == 0 and max(seen) == workers * rounds

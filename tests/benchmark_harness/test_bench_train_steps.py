"""The window loop of ``traffic_kinds/train_steps.py`` against a fake engine:
what is dispatched, how many steps are in flight, when it stops, what a step
that raises does — and the memory arithmetic on fake runtime statistics."""

import contextlib
import types

import pytest

from benchmark.traffic_kinds import train_steps


class FakeLoss:
    def __init__(self, log, index, value):
        self.log, self.index, self.value = log, index, value

    def block_until_ready(self):
        self.log.append(("ready", self.index))
        return self

    def __float__(self):
        return self.value


class FakeEngine:
    def __init__(self, fail_at=None):
        self.log, self.fail_at = [], fail_at

    def train_batch(self, batch):
        index = sum(1 for kind, _ in self.log if kind == "dispatch")
        if index == self.fail_at:
            raise RuntimeError("boom")
        self.log.append(("dispatch", index))
        self.batches = getattr(self, "batches", []) + [batch]
        return FakeLoss(self.log, index, 10.0 - index)


@contextlib.contextmanager
def no_span(name):
    yield


def test_steps_cycle_the_pool_and_keep_two_in_flight():
    engine = FakeEngine()
    losses, failed, t0, t1 = train_steps.run_steps(
        engine, ["a", "b", "c"], lambda n, _s: n >= 5, no_span)
    assert losses == [10.0, 9.0, 8.0, 7.0, 6.0] and failed == 0 and t1 >= t0
    assert engine.batches == ["a", "b", "c", "a", "b"]
    # step i+2 is dispatched only after step i's loss is ready, and the
    # window closes on the readiness of the last losses
    assert engine.log == [
        ("dispatch", 0), ("dispatch", 1), ("ready", 0), ("dispatch", 2),
        ("ready", 1), ("dispatch", 3), ("ready", 2), ("dispatch", 4),
        ("ready", 3), ("ready", 4)]
    in_flight = worst = 0
    for kind, _ in engine.log:
        in_flight += 1 if kind == "dispatch" else -1
        worst = max(worst, in_flight)
    assert worst == train_steps.MAX_IN_FLIGHT == 2


def test_the_clock_stops_dispatching_not_the_steps_in_flight():
    engine = FakeEngine()
    seen = []

    def stop(n, seconds):
        seen.append((n, seconds >= 0))
        return n >= 1            # "the clock passed" right after step one

    losses, failed, _, _ = train_steps.run_steps(engine, ["a"], stop, no_span)
    assert losses == [10.0] and failed == 0 and seen == [(1, True)]
    assert engine.log == [("dispatch", 0), ("ready", 0)]


def test_a_step_that_raises_ends_the_window_and_counts_as_failed(capsys):
    engine = FakeEngine(fail_at=2)
    losses, failed, _, _ = train_steps.run_steps(
        engine, ["a", "b"], lambda n, _s: n >= 9, no_span)
    assert losses == [10.0, 9.0] and failed == 1
    assert "RuntimeError: boom" in capsys.readouterr().err
    # the steps already in flight are still waited for
    assert engine.log[-2:] == [("ready", 0), ("ready", 1)]


def test_spans_cover_prep_dispatch_and_wait():
    names = []

    @contextlib.contextmanager
    def span(name):
        names.append(name)
        yield

    train_steps.run_steps(FakeEngine(), ["a"], lambda n, _s: n >= 3, span)
    assert names == ["batch_prep", "dispatch", "batch_prep", "dispatch",
                     "loss_wait", "batch_prep", "dispatch", "loss_wait"]


@pytest.mark.parametrize("stats,want", [
    # set-up's transient is the peak (first chip of the dp=4 cell)
    ({"bytes_in_use": 4_450, "peak_bytes_in_use": 16_757,
      "peak_bytes_reserved": 8_829}, 16_757),
    # the step is: live state + what the program reserved
    ({"bytes_in_use": 9_836, "peak_bytes_in_use": 9_836,
      "peak_bytes_reserved": 4_021}, 13_857),
    # a runtime that does not keep the two apart
    ({"bytes_in_use": 5, "peak_bytes_in_use": 9}, 9),
    ({}, None), (None, None),
])
def test_memory_peak_by_hand(stats, want):
    device = types.SimpleNamespace(memory_stats=lambda: stats)
    assert train_steps.memory_peak_bytes(device) == want

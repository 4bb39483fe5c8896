"""``benchmark/flops.py`` against counts made by hand from the published
shapes, and the rule that recomputation never reaches the numerator."""

import copy

import pytest

from benchmark import cell as cells
from benchmark import flops

M = 1e6

# (cell, body, attention, head) in MFLOP per token, by hand:
#   body      = 6 * L * 12 h^2
#   attention = 12 * L * seq * h          (half for a causal model)
#   head      = 6 * (h*V [+ h*h]) * labelled / seq
HAND = [
    # BERT-large: L 24, h 1024, V 30522; 20 of 128 / 80 of 512 masked
    ("bert-large.seq128", 6 * 24 * 12 * 1024 ** 2 / M,
     12 * 24 * 128 * 1024 / M,
     6 * (1024 * 30522 + 1024 ** 2) * 20 / 128 / M),
    ("bert-large.seq512", 1811.939328, 150.994944,
     6 * (1024 * 30522 + 1024 ** 2) * 80 / 512 / M),
    # GPT-2 XL widths: h 1600, V 50257, every position labelled, causal
    ("gpt2-xl.1chip", 6 * 20 * 12 * 1600 ** 2 / M,
     6 * 20 * 1024 * 1600 / M, 6 * 1600 * 50257 / M),
    ("gpt2-xl.dp4-zero1", 6 * 24 * 12 * 1600 ** 2 / M,
     6 * 24 * 1024 * 1600 / M, 482.4672),
]


@pytest.mark.parametrize("name,body,attention,head", HAND,
                         ids=[h[0] for h in HAND])
def test_flops_per_token_match_hand_counts(name, body, attention, head):
    cell = cells.load(name)
    got = cell.family.flops_per_token(cell.config, cell.traffic)
    assert got["body"] / M == pytest.approx(body, rel=1e-9)
    assert got["attention"] / M == pytest.approx(attention, rel=1e-9)
    assert got["head"] / M == pytest.approx(head, rel=1e-9)
    assert got["total"] == got["body"] + got["attention"] + got["head"]


def test_bert_large_seq128_total_is_1_88_gflop():
    cell = cells.load("bert-large.seq128")
    total = cell.family.flops_per_token(cell.config, cell.traffic)["total"]
    assert total / 1e9 == pytest.approx(1.880, abs=5e-4)


@pytest.mark.parametrize("name", [h[0] for h in HAND])
def test_recomputation_does_not_change_required_flops(name):
    """``mfu`` counts no recomputed operation: the same cell under
    ``selective`` and ``full`` activation checkpointing (and with none) has
    the same FLOPs per token."""
    cell = cells.load(name)
    want = cell.family.flops_per_token(cell.config, cell.traffic)
    for policy in ("selective", "full", "dots", None):
        config = copy.deepcopy(cell.config)
        config["job"]["activation_checkpointing"] = policy
        assert cell.family.flops_per_token(config, cell.traffic) == want


def test_attention_kernel_cost_by_hand():
    # BERT-large phase 2, one micro-batch: 8 x 16 heads, 512 x 512 pairs of
    # 64-wide heads, bf16
    pairs = 8 * 16 * 512 * 512
    fwd = flops.attention_kernel_cost(rows=8, seq=512, heads=16, head_dim=64,
                                      causal=False, itemsize=2,
                                      direction="fwd")
    tensor = 8 * 512 * 16 * 64 * 2
    assert fwd == (2 * 2 * pairs * 64, 4 * tensor + 8 * 16 * 512 * 4)
    bwd = flops.attention_kernel_cost(rows=8, seq=512, heads=16, head_dim=64,
                                      causal=False, itemsize=2,
                                      direction="bwd")
    assert bwd == (5 * 2 * pairs * 64, 8 * tensor + 8 * 16 * 512 * 4)
    causal = flops.attention_kernel_cost(rows=8, seq=512, heads=16,
                                         head_dim=64, causal=True,
                                         itemsize=2, direction="fwd")
    assert causal[0] == fwd[0] / 2 and causal[1] == fwd[1]
    with pytest.raises(ValueError):
        flops.attention_kernel_cost(rows=1, seq=1, heads=1, head_dim=1,
                                    causal=False, itemsize=2,
                                    direction="both")


def test_roofline_says_which_peak_bounds():
    peaks = cells.peaks("TPU v5 lite")
    assert (peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]) == (
        197e12, 819e9)
    # 197 TFLOP of work on 1 byte: a second, compute-bound
    assert flops.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    # 819 GB moved for one FLOP: a second, memory-bound
    assert flops.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(cells.CellError, match="not in benchmark/peaks.json"):
        cells.peaks("TPU v9 imaginary")

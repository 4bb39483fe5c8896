"""The full BingBert workflow as an executable test: pretrain on real
text (wordpiece vocab trained in-process) → export checkpoint + vocab →
fine-tune SQuAD from the transferred encoder → evaluate-v1.1 F1.

Drives the actual example scripts in subprocesses (the user-facing
surface), small step counts: this pins the MECHANICS of the hand-off —
vocab reuse, module-tree transfer, F1 reporting — not model quality
(tests/model/test_squad_f1.py owns the quality bar).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "model", "data", "squad_mini.json")


def _env():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    env.pop("_DSTPU_TEST_ENV", None)
    return env


def _cfg(tmp_path, body):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(body))
    return str(p)


def test_pretrain_then_finetune_end_to_end(tmp_path):
    corpus = tmp_path / "corpus.txt"
    with open(DATA) as f:
        data = json.load(f)["data"]
    lines = []
    for art in data:
        for para in art["paragraphs"]:
            lines.append(para["context"])
            lines += [q["question"] for q in para["qas"]]
    corpus.write_text("\n".join(lines))

    vocab = tmp_path / "vocab.txt"
    ckdir = tmp_path / "ck"
    pre_cfg = _cfg(tmp_path, {
        "train_batch_size": 8,
        "optimizer": {"type": "Lamb", "params": {"lr": 2e-3}},
        "fp16": {"enabled": True, "initial_scale_power": 8},
        "steps_per_print": 10 ** 6})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "bert",
                                      "pretrain_bert.py"),
         "--steps", "8", "--seq-len", "160", "--corpus", str(corpus),
         "--vocab-size", "768", "--save-vocab", str(vocab),
         "--save-checkpoint", str(ckdir),
         "--deepspeed_config", pre_cfg],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=420)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "checkpoint saved:" in out, out
    assert vocab.exists()

    ft_cfg = _cfg(tmp_path, {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 2e-3}},
        "bf16": {"enabled": True},
        "steps_per_print": 10 ** 6})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "bert",
                                      "squad_finetune.py"),
         "--steps", "10", "--seq-len", "160", "--doc-stride", "40",
         "--train-file", DATA, "--predict-file", DATA,
         "--vocab-file", str(vocab),
         "--init-checkpoint", str(ckdir),
         "--deepspeed_config", ft_cfg],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=420)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    # the transfer actually moved weights in (and skipped the QA head)
    assert "init-checkpoint: transferred" in out, out
    n_transferred = int(out.split("init-checkpoint: transferred ")[1]
                        .split(" ")[0])
    assert n_transferred >= 8, out
    # evaluate-v1.1 JSON line with the full example count
    result = json.loads([l for l in out.splitlines()
                         if l.startswith("{")][-1])
    assert result["total"] == 32 and 0.0 <= result["f1"] <= 100.0, result

"""The work counts against arithmetic done by hand for ``gpt2m_1chip`` (and
the totals ISSUE 25 states for ``bert_large_1chip``)."""

import json
import os

import pytest

from benchmark.harness import arch, flops, peaks
from conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_medium_counts_by_hand():
    cfg = _cfg("gpt2_medium")
    # a block: qkv 1024x3072 + out 1024x1024 + mlp 2 x 1024x4096
    block = 3_145_728 + 1_048_576 + 8_388_608
    assert block == 12_582_912
    head = 1024 * 50304
    assert arch.of(cfg).matmul_params(cfg) == 24 * block + head
    assert 24 * block + head == 353_501_184
    tokens = 8 * 1024
    matmul = 6 * 353_501_184 * tokens             # 17.375 TFLOP
    attention = (12 * 24 * 1024 * 1024 // 2) * tokens   # 1.237 TFLOP
    assert flops.step_flops(cfg, 8, 1024) == matmul + attention
    assert matmul + attention == 18_612_240_777_216
    work = flops.flash_work(cfg, 8, 1024)
    # forward 4 B H S^2 D, backward 8 B H S^2 D, 24 layers, causal halves
    assert work["fwd"]["flops"] == 4 * 8 * 16 * 1024 ** 2 * 64 * 24 // 2
    assert work["bwd"]["flops"] == 2 * work["fwd"]["flops"]
    # the kernels' count is attention's part of the step's count
    assert work["fwd"]["flops"] + work["bwd"]["flops"] == attention
    tensor = 8 * 16 * 1024 * 64 * 2 * 24          # one of q, k, v, o: bf16
    assert work["fwd"]["bytes"] == 4 * tensor
    assert work["bwd"]["bytes"] == 8 * tensor
    v5e = peaks.peaks_for("TPU v5 lite")
    least = flops.flash_least_seconds(cfg, 8, 1024, v5e)
    # compute-bound both ways: 1.237e12 / 197e12 = 6.28 ms
    assert least == pytest.approx(attention / 197e12)
    assert least == pytest.approx(6.279e-3, rel=1e-3)


def test_bert_large_counts():
    cfg = _cfg("bert_large")
    per_token, per_seq = arch.of(cfg).matmul_params(cfg)
    assert per_token == 24 * 12_582_912 + 1024 * 1024 + 1024 * 30522
    assert per_seq == 1024 * 1024 + 2 * 1024
    total = flops.step_flops(cfg, 32, 128)
    assert total == pytest.approx(8.37e12, rel=1e-3)
    share = arch.of(cfg).attention_flops_per_token(cfg, 128) * 4096 / total
    assert share < 0.03     # the cell's why: attention under 3% of FLOPs


def test_least_seconds_takes_the_larger_bound_of_each_pass():
    row = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    work = {"fwd": {"flops": 200, "bytes": 10},     # 2 s by FLOPs, 1 by bytes
            "bwd": {"flops": 100, "bytes": 50}}     # 1 s by FLOPs, 5 by bytes
    assert flops.least_seconds(work, row) == pytest.approx(7.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")

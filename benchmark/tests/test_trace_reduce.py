"""The trace reduction against small recorded traces.

``data/<cell>_2steps.xplane.pb.gz`` is the first two steps of a trace taken
on the chip, cut by ``tools/trim_trace.py``; the numbers in
``data/<cell>_2steps.expected.json`` were worked out there straight from
the protobuf, by other code than the reduction's, in picoseconds; the
reduction reads ``ProfileData``'s whole nanoseconds, hence the 1e-4.
"""

import glob
import json
import os

import pytest

from benchmark.harness import flops, peaks, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(os.path.basename(p)[:-len(".xplane.pb.gz")]
                for p in glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))


def test_a_recorded_trace_is_kept():
    assert TRACES


@pytest.mark.parametrize("name", TRACES)
def test_reduction_gives_the_hand_numbers(name):
    with open(os.path.join(DATA, name + ".expected.json")) as f:
        expected = json.load(f)["chips"]
    summary = trace_reduce.TraceSummary(trace_reduce.load(
        os.path.join(DATA, name + ".xplane.pb.gz")))
    assert len(summary.chips) == len(expected)
    for chip, want in zip(sorted(summary.chips, key=lambda c: c.index),
                          sorted(expected, key=lambda c: c["plane"])):
        assert chip.steps == want["steps"]
        assert len(chip.ops) == want["ops_inside"] <= want["ops_total"]
        assert chip.window_s == pytest.approx(want["window_ps"] * 1e-12,
                                              rel=1e-4)
        assert chip.busy_s() == pytest.approx(want["busy_ps"] * 1e-12,
                                              rel=1e-4)
        assert chip.seconds_of("hvd_flash_") == pytest.approx(
            want["flash_ps"] * 1e-12, rel=1e-4)
        assert chip.collective_exposed_s() == pytest.approx(
            want["collective_exposed_ps"] * 1e-12, rel=1e-4, abs=1e-7)
    breakdown = summary.breakdown()
    assert 1 <= len(breakdown["device_ops"]) <= 10
    assert len(breakdown["idle_gaps"]) <= 10
    assert all(" = " not in n and len(n) < 80
               for n, _ in breakdown["device_ops"] + breakdown["idle_gaps"])


def test_flash_roofline_of_the_recorded_step_is_a_share():
    name = "gpt2m_1chip_2steps"
    if name not in TRACES:
        pytest.skip("no one-chip GPT-2 medium trace kept")
    with open(os.path.join(os.path.dirname(DATA), "..", "configs",
                           "gpt2_medium.json")) as f:
        cfg = json.load(f)
    summary = trace_reduce.TraceSummary(trace_reduce.load(
        os.path.join(DATA, name + ".xplane.pb.gz")))
    # 24 layers x 3 kernels x 2 steps
    assert sum(1 for o in summary.chips[0].ops
               if o.name.startswith("hvd_flash_")) == 144
    least = flops.flash_least_seconds(cfg, 8, 1024,
                                      peaks.peaks_for("TPU v5 lite"))
    share = 100 * least * summary.steps / summary.seconds_of("hvd_flash_")
    assert 0 < share < 100


def test_interval_arithmetic():
    ns = trace_reduce._union_seconds
    assert ns([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace_reduce._subtract_seconds(
        [(0, 10), (20, 30)], [(5, 25)]) == pytest.approx(10e-9)
    assert trace_reduce.group_name("fusion.123") == "fusion"
    assert trace_reduce.group_name("hvd_flash_bwd_dkv.39") \
        == "hvd_flash_bwd_dkv"
    assert trace_reduce.op_name("%fusion.19 = (f32[2]{0}) fusion(x)") \
        == "fusion.19"

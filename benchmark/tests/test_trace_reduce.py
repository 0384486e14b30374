"""The trace reduction against small recorded traces.

``data/<cell>_2steps.xplane.pb.gz`` is the first two steps of a trace taken
on the chip, cut by ``tools/trim_trace.py``; the numbers in
``data/<cell>_2steps.expected.json`` were worked out there straight from
the protobuf, by other code than the reduction's, in picoseconds; the
reduction reads ``ProfileData``'s whole nanoseconds, hence the 1e-4.
``data/gpt2m_dp4_1step`` (four chips, one step; cut anew in PR 28 from a
trace of PR 26's tree) keeps each event's opcode, and its expected
collectives are the ops the profiler's own ``hlo_category`` calls
``all-reduce``: 12 combined ``all-reduce.N`` and the two that XLA left
named ``psum.N``.
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


def test_collectives_are_counted_whatever_xla_names_them():
    name = "gpt2m_dp4_1step"
    with open(os.path.join(DATA, name + ".expected.json")) as f:
        expected = json.load(f)["chips"]
    summary = trace_reduce.TraceSummary(trace_reduce.load(
        os.path.join(DATA, name + ".xplane.pb.gz")))
    assert len(summary.chips) == 4
    for chip, want in zip(sorted(summary.chips, key=lambda c: c.index),
                          sorted(expected, key=lambda c: c["plane"])):
        found = sorted("%" + o.name for o in chip.ops if o.is_collective)
        assert found == want["collectives"] and len(found) == 14
        assert sum(n.startswith("%psum.") for n in found) == 2
        by_name = sum(o.seconds for o in chip.ops
                      if trace_reduce.COLLECTIVE.search(o.name))
        total = sum(o.seconds for o in chip.ops if o.is_collective)
        assert total == pytest.approx(want["collective_ps"] * 1e-12,
                                      rel=1e-4)
        # what the names alone miss: the two 206 MB buckets, a quarter
        assert 0.70 * total < by_name < 0.78 * total
        # nothing overlaps a collective in this step: all of it is exposed
        assert chip.collective_exposed_s() == pytest.approx(total, rel=1e-4)
    assert 1e3 * summary.collective_exposed_s() / summary.steps \
        == pytest.approx(28.3, abs=0.3)
    # and the breakdown names them all as what they are
    named = dict(summary.breakdown()["device_ops"])
    assert "psum" not in named
    assert named["all-reduce"] == pytest.approx(
        summary.collective_exposed_s(), rel=1e-6)


@pytest.mark.parametrize("line, name, code", [
    # lines of a v5e trace (PR 26's gpt2m_dp4), operands shortened
    ("%psum.197 = f32[51511296]{0:T(1024)} all-reduce(f32[51511296]{0:T(1024)}"
     " %bitcast.4), channel_id=3, replica_groups={{0,1,2,3}}",
     "psum.197", "all-reduce"),
    ("%all-reduce.1 = (f32[1048576]{0:T(1024)}, f32[13650944]{0:T(1024)S(1)})"
     " all-reduce(f32[1048576]{0:T(1024)} %a, f32[13650944]{0:T(1024)} %b)",
     "all-reduce.1", "all-reduce"),
    ("%fusion.19 = (f32[2]{0}, bf16[8,1024]{1,0:T(8,128)(2,1)}) fusion(f32[2]"
     " %p), kind=kLoop, calls=%fused_computation.19", "fusion.19", "fusion"),
    ("%slice-start.1136 = ((f32[8]{0}), f32[4]{0:S(1)}, u32[]{:S(2)})"
     " async-start(f32[8]{0} %p), calls=%async_wrapped", "slice-start.1136",
     "async-start"),
    ("%all-gather-start.2 = (f32[4]{0}, f32[16]{0}) all-gather-start(f32[4]{0}"
     " %p), dimensions={0}", "all-gather-start.2", "all-gather-start"),
    ("%psum.197", "psum.197", None),                # a trimmed or CPU name
    ("hvd_flash_fwd.3", "hvd_flash_fwd.3", None),
])
def test_name_and_opcode_of_an_event(line, name, code):
    assert trace_reduce.op_name(line) == name
    assert trace_reduce.opcode(line) == code
    collective = code is not None and code.startswith("all-")
    assert trace_reduce.Op(name, 0, 1, code).is_collective is collective


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

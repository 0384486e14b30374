"""``harness/scopes.py`` against a small recorded trace that keeps scope
paths and host spans.

``data/gpt2m_dp4_2steps.scoped.pb.gz`` is the first two traced steps of
``gpt2m_dp4`` on chips 0 and 1, cut by ``tools/trim_scoped_trace.py``;
``....scoped.expected.json`` was worked out there straight from the protobuf
with regular expressions over the paths, in picoseconds; ``scopes.py`` walks
the paths' parts and takes whole nanoseconds as ``trace_reduce`` does, hence
the 1e-4.
"""

import json
import os

import pytest

from benchmark.harness import scopes, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "gpt2m_dp4_2steps.scoped"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, NAME + ".pb.gz")
    with open(os.path.join(DATA, NAME + ".expected.json")) as f:
        expected = json.load(f)
    summary = trace_reduce.TraceSummary(trace_reduce.load(path))
    return scopes.ScopedTrace(scopes.load_space(path), summary), summary, \
        expected


def test_phases_give_the_hand_numbers(recorded):
    trace, summary, expected = recorded
    assert len(trace.chips) == len(expected["chips"]) == 2
    for chip, window, want in zip(
            trace.chips, sorted(summary.chips, key=lambda c: c.index),
            expected["chips"]):
        assert want["plane"] == f"/device:TPU:{chip.index}"
        assert chip.steps == expected["steps"] == window.steps
        assert sum(chip.counts.values()) == want["ops_inside"] \
            == len(window.ops)
        for phase in scopes.PHASES:
            assert chip.counts[phase] == want["phase_ops"].get(phase, 0)
            assert chip.seconds[phase] == pytest.approx(
                want["phase_ps"].get(phase, 0) * 1e-12, rel=1e-4)
        # every op once: the phases add up to the chip's busy time (ops of
        # one TPU core do not overlap)
        assert sum(chip.seconds.values()) == pytest.approx(
            window.busy_s(), rel=1e-4)


def test_reduce_holds_every_collective_whatever_xla_names_it(recorded):
    trace, summary, expected = recorded
    for chip, window, want in zip(trace.chips, summary.chips,
                                  expected["chips"]):
        names = want["collectives"]
        # 12 combined all-reduces and the two 206 MB buckets XLA left named
        # psum.N, each step
        assert len(names) == 14 * chip.steps == chip.counts["reduce"]
        assert sum(n.startswith("psum.") for n in names) == 2 * chip.steps
        by_name = sum(o.seconds for o in window.ops
                      if trace_reduce.COLLECTIVE.search(o.name))
        by_psum = sum(o.seconds for o in window.ops
                      if o.name.startswith("psum."))
        assert chip.seconds["reduce"] == pytest.approx(by_name + by_psum,
                                                       rel=1e-6)
        assert by_psum > 0.2 * chip.seconds["reduce"]


def test_unscoped_is_what_the_compiler_made(recorded):
    trace, _, _ = recorded
    for chip in trace.chips:
        busy = sum(chip.seconds.values())
        assert 0 < chip.seconds["unscoped"] < 0.05 * busy
        assert set(k for k, v in chip.unscoped.items() if v > 1e-5) <= {
            "slice-done", "copy-done", "copy", "slice-start", "copy-start",
            "constant_dynamic-update-slice_fusion", "convert_bitcast_fusion"}


def test_host_spans_lie_on_the_device_clock(recorded):
    trace, _, expected = recorded
    want = [s for s in expected["host_spans"]
            if s["name"] == "hvd::shard_batch"]
    spans = trace.host_spans("shard_batch")
    assert [(s.start_ns, s.end_ns - s.start_ns) for s in spans] \
        == [(s["start_ps"] // 1000,
             (s["start_ps"] + s["duration_ps"]) // 1000
             - s["start_ps"] // 1000) for s in want]
    assert len(spans) >= expected["steps"]
    chip = expected["chips"][0]
    step_ps = chip["run_ends_ps"][0] - chip["run_starts_ps"][0]
    for k, start_ps in enumerate(chip["run_starts_ps"]):
        # batch k is placed before the device starts step k, and no more
        # than the loop's two steps in flight (and a third) ahead of it
        assert spans[k].end_ns * 1000 < start_ps
        assert spans[k].end_ns * 1000 > start_ps - 3 * step_ps

"""The manifest check accepts the shipped manifest and refuses the faults
that have refused a PR before."""

import copy

import pytest

from benchmark.harness import manifest
from conftest import ROOT


@pytest.fixture()
def shipped():
    return manifest.load(ROOT)


def test_shipped_manifest_is_sound(shipped):
    assert manifest.check(shipped, ROOT) == []


def _metric(m, name):
    return manifest.entry(m["per_layer"], name, "metric")


@pytest.mark.parametrize("fault, words", [
    (lambda m: _metric(m, "init.compile_s").update(layer="init and topology"),
     "layer must be 1 to 64 characters"),
    (lambda m: m["end_to_end"][0].update(unit="tokens_per_second"),
     "unit 'tokens_per_second'"),
    (lambda m: _metric(m, "step.mfu_pct").update(moves="images_per_s"),
     "does not report"),
    (lambda m: m["end_to_end"][1].update(bound=0.2), "bound must be"),
    (lambda m: m["workloads"][0].update(chips=2), "chips must be 1 or 4"),
    (lambda m: m["workloads"][0].update(chips=4), "ask for 4 chips"),
    (lambda m: m["configs"][0]["reduced"].append("n_embd"),
     "reduced names a width"),
    (lambda m: m.update(run_seconds=60), "run_seconds"),
    (lambda m: _metric(m, "kernels.flash_roofline").update(why="x"),
     "keys not allowed"),
    (lambda m: m["workloads"][1].update(traffic=m["workloads"][0]["traffic"]),
     "appears twice"),
])
def test_known_faults_are_refused(shipped, fault, words):
    broken = copy.deepcopy(shipped)
    fault(broken)
    problems = manifest.check(broken, ROOT)
    assert any(words in line for line in problems), problems

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
    (lambda m: m["configs"][0]["reduced"].append("moe_intermediate_size"),
     "reduced names a width"),
    (lambda m: m["configs"][0]["reduced"].append("num_experts_per_tok"),
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


@pytest.mark.parametrize("key", ["num_hidden_layers", "n_layer", "num_layers",
                                 "mtp_num_hidden_layers", "vocab_size",
                                 "n_routed_experts", "num_local_experts",
                                 "num_attention_heads"])
def test_depth_experts_heads_and_vocabulary_may_be_cut(shipped, key):
    assert not manifest.names_a_width(key)
    shipped["configs"][0]["reduced"].append(key)
    assert manifest.check(shipped, ROOT) == []


@pytest.mark.parametrize("key", ["hidden_size", "intermediate_size",
                                 "head_dim", "moe_intermediate_size",
                                 "num_experts_per_tok", "kv_lora_rank",
                                 "ssm_state_size", "mamba_expand", "n_embd",
                                 "moe_top_k", "mamba_d_conv"])
def test_a_width_may_not(key):
    assert manifest.names_a_width(key)


def test_the_manifest_alone_says_which_cell_reports_a_metric(shipped):
    """A metric's file carries no list of cells: a cell added to a metric's
    list in the manifest needs no edit of the metric's file."""
    import glob
    import json
    import os
    for path in glob.glob(os.path.join(ROOT, "benchmark", "metrics",
                                       "*.json")):
        with open(path) as f:
            assert "workloads" not in json.load(f), path
    entry = _metric(shipped, "allreduce.exposed_ms")
    entry["workloads"] = [w["name"] for w in shipped["workloads"]]
    assert manifest.check(shipped, ROOT) == []

"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip (``--rehearse``: the CPU, a
tiny size), drives the rest of ``run.py`` and plants one fault under it:
a step that returns its state unchanged; half of the batch left out, the
mean taken over the rest; the exchange between chips left out.
"""

import argparse
import json

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import run as bench
from benchmark.harness import manifest, program
from conftest import ROOT


def _run(cell, capsys):
    args = argparse.Namespace(workload=cell, seed=2147483777, seconds=0.3,
                              trace=0, rehearse=True, check_manifest=False)
    bench.run_cell(args, manifest.load(ROOT))
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["gpt2m_1chip", "gpt2m_dp4"])
def test_sound_run_is_correct(cell, capsys):
    result = _run(cell, capsys)
    assert result["correct"] is True, result["check"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"


def test_state_returned_unchanged(monkeypatch, capsys):
    real = program.feed

    def feed(compiled, mesh, state, host_batch):
        kept = jax.tree.map(jnp.copy, state)
        _, loss = real(compiled, mesh, state, host_batch)
        return kept, loss

    monkeypatch.setattr(program, "feed", feed)
    result = _run("gpt2m_1chip", capsys)
    assert result["correct"] is False
    assert result["check"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch, capsys):
    real = program.feed

    def feed(compiled, mesh, state, host_batch):
        half = {k: v[:len(v) // 2] for k, v in host_batch.items()}
        doubled = {k: v.repeat(2, axis=0) for k, v in half.items()}
        return real(compiled, mesh, state, doubled)

    monkeypatch.setattr(program, "feed", feed)
    result = _run("gpt2m_1chip", capsys)
    assert result["correct"] is False
    over = result["check"]["grad_gap"]
    assert over["value"] > over["limit"]


def test_exchange_between_chips_left_out(monkeypatch, capsys):
    def optimizer(cfg):
        a = cfg["assumed"]
        return optax.adamw(a["learning_rate"], b1=a["adam_b1"],
                           b2=a["adam_b2"], eps=a["adam_eps"],
                           weight_decay=a["weight_decay"])

    monkeypatch.setattr(program, "optimizer", optimizer)
    result = _run("gpt2m_dp4", capsys)
    assert result["correct"] is False
    over = result["check"]["grad_gap"]
    assert over["value"] > over["limit"]

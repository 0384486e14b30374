"""The plain reference against the program at a tiny size on the CPU, for
both configurations; and the control (the reference in the precision below
the one the configuration states) reading well above what the program
reads."""

import copy
import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench
from benchmark.harness import check, program, reference, traffic, weights
from conftest import ROOT

CONFIGS = ["gpt2_medium", "bert_large"]


def _tiny(config):
    """(workload, cfg) of a configuration file at the rehearsal's size;
    ``bert_large`` is kept and tested though no cell ships with it yet
    (PERF.md, Open questions)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    return bench.rehearse_cut({"chips": 1}, cfg)


@pytest.mark.parametrize("config", CONFIGS)
def test_names_and_shapes_are_the_programs(config):
    workload, cfg = _tiny(config)
    model, _ = program.load_model_builder(cfg["model"])(cfg)
    batch = traffic.Batches(cfg, workload, 1).next()
    assert weights.flatten(check.plain(program.model_shapes(model, batch))) \
        == weights.flatten(reference.param_shapes(cfg))


@pytest.mark.parametrize("config", CONFIGS)
def test_loss_and_gradient_match_the_program(config):
    workload, cfg = _tiny(config)
    cfg = copy.deepcopy(cfg)
    cfg["assumed"]["compute_dtype"] = "float32"
    model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    shapes = reference.param_shapes(cfg)
    params = weights.make_params(shapes, 7, cfg)
    batch = traffic.Batches(cfg, workload, 7).next()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, {k: jax.numpy.asarray(v) for k, v in batch.items()})
    ref = reference.Reference(cfg, "float32", rows_per_block=1)
    ref_loss, ref_grads = ref.loss_and_grad(params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), (_, r) in zip(weights.flatten(check.plain(grads)),
                                 weights.flatten(ref_grads)):
        scale = float(np.abs(r).max()) + 1e-12
        assert float(np.abs(np.asarray(g) - np.asarray(r)).max()) \
            <= 2e-4 * scale + 1e-9, path


def test_adamw_matches_optax():
    import optax
    _, cfg = _tiny("gpt2_medium")
    a = cfg["assumed"]
    ref = reference.Reference(cfg, "float32")
    rng = np.random.default_rng(0)
    params = {"x": {"w": jax.numpy.asarray(rng.normal(size=(5, 7)),
                                           "float32")}}
    tx = optax.adamw(a["learning_rate"], b1=a["adam_b1"], b2=a["adam_b2"],
                     eps=a["adam_eps"], weight_decay=a["weight_decay"])
    state, opt, mine = tx.init(params), ref.init_opt(params), params
    theirs = params
    for _ in range(3):
        grads = {"x": {"w": jax.numpy.asarray(rng.normal(size=(5, 7)),
                                              "float32")}}
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        mine, opt = ref.adam(mine, grads, opt)
    np.testing.assert_allclose(mine["x"]["w"], theirs["x"]["w"], rtol=1e-6)


@pytest.mark.parametrize("config", CONFIGS)
def test_control_reads_above_the_program(config):
    """fp8 in the reference's place reads a gradient gap three times what
    bfloat16 operands (the stated precision) read on the same seed."""
    workload, cfg = _tiny(config)
    shapes = reference.param_shapes(cfg)
    norms = check.Norms(shapes, cfg, reference.fused_parts(cfg))
    b = traffic.Batches(cfg, workload, 3)
    batches = [b.next() for _ in range(check.CHECK_STEPS)]
    read = {p: check.reference_readings(reference.Reference(cfg, p), norms,
                                        shapes, 3, cfg, batches)
            for p in ("float32", "bfloat16", "fp8")}
    none = dict.fromkeys(check.NUMBERS, 0.0)
    stated = {r["name"]: r["value"] for r in check.compare(
        read["bfloat16"], read["float32"], none)[1]}
    control = {r["name"]: r["value"] for r in check.compare(
        read["fp8"], read["float32"], none)[1]}
    assert control["grad_gap"] >= 3 * stated["grad_gap"], (stated, control)

"""An architecture is a file found by name (``benchmark/archs/<arch>.py``).

- The two architectures that moved there give what the shared files gave
  before the move: ``data/archs_golden.json`` was recorded from the parent's
  code (commit ``recorded_from``; its ``reference.param_shapes`` /
  ``fused_parts``, ``flops.step_flops`` / ``flash_work`` at the cells'
  sizes, and at the rehearsal's size with seed 7 the fresh parameters' norms
  and the reference's loss and gradient norms in ``float32`` and ``fp8``) by
  the statements of ``_now`` below.
- A third architecture enters as new files only: ``toy_arch/`` laid over a
  copy of ``benchmark/``, its entries appended to a copy of
  ``BENCHMARK.json``; the manifest check passes and the cell's rehearsal
  comes out ``correct``.
- An unknown ``arch`` fails with the path it looked for.
"""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark import run as bench
from benchmark.harness import check, flops, reference, traffic, weights
from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_arch")
with open(os.path.join(HERE, "data", "archs_golden.json")) as f:
    GOLDEN = json.load(f)


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def _flat(shapes):
    return [["/".join(p), list(s)] for p, s in weights.flatten(shapes)]


def _now(name, want):
    """What the code gives now, laid out as the golden file's entry."""
    cfg, seed = _cfg(name), GOLDEN["seed"]
    seqs, seq_len = want["sequences"], want["sequence_length"]
    row = {"arch": cfg["arch"], "sequences": seqs, "sequence_length": seq_len,
           "param_shapes": _flat(reference.param_shapes(cfg)),
           "fused_parts": sorted(["/".join(p), n] for p, n
                                 in reference.fused_parts(cfg).items()),
           "step_flops": flops.step_flops(cfg, seqs, seq_len),
           "flash_work": flops.flash_work(cfg, seqs, seq_len)}
    workload, tiny = bench.rehearse_cut({"chips": 1}, _cfg(name))
    shapes = reference.param_shapes(tiny)
    per_chip, length = (workload["sequences_per_chip"],
                        workload["sequence_length"])
    row["tiny"] = {"workload": workload, "param_shapes": _flat(shapes),
                   "step_flops": flops.step_flops(tiny, per_chip, length),
                   "flash_work": flops.flash_work(tiny, per_chip, length)}
    params = weights.make_params(shapes, seed, tiny)
    norms = check.Norms(shapes, tiny, reference.fused_parts(tiny))
    row["tiny"]["param_norms"] = norms.of(params)
    batch = traffic.Batches(tiny, workload, seed).next()
    for operands in ("float32", "fp8"):
        loss, grads = reference.Reference(tiny, operands).loss_and_grad(
            params, batch)
        row["tiny"][operands] = {"loss": float(loss),
                                 "grad_norms": norms.of(grads)}
    return row


def _same(got, want, where=""):
    """Integers, strings and lists exactly; floats to 1e-6."""
    if isinstance(want, dict):
        assert sorted(got) == sorted(want), where
        for k in want:
            _same(got[k], want[k], f"{where}/{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), where
        for i, (g, w) in enumerate(zip(got, want)):
            _same(g, w, f"{where}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=1e-6, abs=1e-9), where
    else:
        assert got == want and type(got) is type(want), where


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_moved_code_gives_the_parents_numbers(name):
    want = GOLDEN["configs"][name]
    _same(_now(name, want), want, name)


def test_unknown_arch_names_the_path_it_looked_for():
    with pytest.raises(FileNotFoundError) as err:
        reference.param_shapes({"arch": "no_such_arch"})
    assert os.path.join("benchmark", "archs", "no_such_arch.py") \
        in str(err.value)
    with pytest.raises(FileNotFoundError, match="no_such_arch.py"):
        flops.step_flops({"arch": "no_such_arch"}, 1, 1)


def test_no_shared_file_names_an_architecture():
    names = [f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark",
                                                     "archs"))
             if f.endswith(".py")]
    assert len(names) >= 2
    shared = [os.path.join(ROOT, "benchmark", "run.py")] + [
        os.path.join(ROOT, "benchmark", "harness", f)
        for f in os.listdir(os.path.join(ROOT, "benchmark", "harness"))
        if f.endswith(".py")]
    for path in shared:
        with open(path) as f:
            text = f.read()
        for word in names + ['"arch"] ==', "_ARCHS", "_KEYS",
                             "REHEARSE_CFG"]:
            assert word not in text, (path, word)


# -- a third architecture, as new files only ---------------------------------

def _hashes(top):
    out = {}
    for folder, _, files in os.walk(top):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def with_toy(tmp_path_factory):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` (without the tests)
    with the toy's files laid over it and its entries appended."""
    top = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(top, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _hashes(os.path.join(top, "benchmark"))
    added = []
    for kind in ("archs", "models", "configs", "workloads"):
        for name in os.listdir(os.path.join(TOY, kind)):
            if name == "__pycache__":
                continue
            rel = os.path.join(kind, name)
            assert rel not in before, f"{rel} would change a file"
            shutil.copy(os.path.join(TOY, kind, name),
                        os.path.join(top, "benchmark", rel))
            added.append(rel)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(TOY, "manifest_entries.json")) as f:
        entries = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    new = [w["name"] for w in entries["workloads"]]
    manifest["configs"] += entries["configs"]
    manifest["workloads"] += entries["workloads"]
    for metric in manifest["per_layer"]:
        if metric["name"] in entries["per_layer_every_cell"]:
            assert metric["workloads"] == cells
            metric["workloads"] = cells + new
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    after = _hashes(os.path.join(top, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == sorted(added)
    return top, entries


def _run_there(top, *argv):
    env = dict(os.environ, PYTHONPATH=ROOT)     # the program: horovod_tpu
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(top, "benchmark", "run.py"), *argv],
        cwd=top, env=env, capture_output=True, text=True, timeout=300)


def test_toy_architecture_enters_as_new_files(with_toy):
    top, entries = with_toy
    out = _run_there(top, "--check-manifest")
    assert out.returncode == 0 and "0 problem(s)" in out.stdout, \
        out.stdout + out.stderr
    cell = entries["workloads"][0]["name"]
    out = _run_there(top, "--workload", cell, "--seed", "2147483659",
                     "--seconds", "0.3", "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["rehearsal"]["steps"] >= 1
    assert set(result["check"]) == set(check.NUMBERS)


def test_toy_states_what_the_shared_rules_do_not():
    """Four layers of two kinds compile two block programs; the gate is made
    by the toy's own rule, the same for the program's start and for the
    change measured from it; the window bounds attention's count."""
    spec = importlib.util.spec_from_file_location(
        "toy_arch_file", os.path.join(TOY, "archs", "toy_rms_alternating.py"))
    toy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(toy)
    with open(os.path.join(TOY, "configs", "toy_rms_alternating.json")) as f:
        cfg = json.load(f)
    cfg.update(toy.REHEARSE)
    net = toy.Net(cfg, reference.product("float32"))
    assert [net.kind_of(i) for i in range(4)] \
        == ["full", "window", "full", "window"]
    # 6 x parameters x tokens would count every pair of a window layer
    s, w = 64, cfg["sliding_window"]
    full_pairs, window_pairs = s * (s + 1) // 2, sum(
        min(q + 1, w) for q in range(s))
    assert window_pairs < full_pairs
    wide = dict(cfg, sliding_window=s)
    d = cfg["head_dim"]
    assert toy.step_flops(wide, 2, s) - toy.step_flops(cfg, 2, s) \
        == 2 * 2 * 12 * cfg["swa_num_attention_heads"] * d \
        * (full_pairs - window_pairs)
    fn = toy.fresh_leaf(cfg, ("layer_1", "gate"), (2,))
    assert toy.fresh_leaf(cfg, ("layer_1", "wq"), (64, 32)) is None
    gate = fn(jax.random.PRNGKey(0))
    assert gate.shape == (2,) and bool(((gate >= 0.5) & (gate < 1.5)).all())

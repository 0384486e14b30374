"""The benchmark's side of ``smallthinker_21b_a3b_ep4`` and of its cell
``smallthinker_ep4_8k_1chip``, on the CPU: the manifest is sound with the
new entries, the cell's rehearsal comes out ``correct`` through the whole
of ``benchmark/run.py``, the configuration keeps every published width,
the architecture's shapes and work counts are the recorded integers, and
the new reader finds its ops by scope and by name."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import arch, flops, manifest, reference, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "smallthinker_ep4_8k_1chip", "smallthinker_21b_a3b_ep4"
# The published config.json, every number of it (the catalog's entry for
# SmallThinker-21BA3B-Instruct), apart from the two 52-entry layouts.
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_theta": 1500000,
    "sliding_window_size": 4096, "vocab_size": 151936}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_manifest_is_sound_on_the_tree():
    out = _run("--check-manifest")
    assert out.returncode == 0 and "0 problem(s)" in out.stdout, \
        out.stdout + out.stderr


def test_the_cells_rehearsal_is_correct():
    out = _run("--workload", CELL, "--seed", "2147483659", "--seconds", "2",
               "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["rehearsal"]["steps"] >= 1
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


class TestConfiguration:
    def test_every_width_is_the_published_one(self):
        cfg, entry = _json("benchmark", "configs", f"{CONFIG}.json"), \
            manifest.entry(_json("BENCHMARK.json")["configs"], CONFIG,
                           "config")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
            == ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
        assert entry["source"] == cfg["source"] and "PowerInfer/" \
            "SmallThinker-21BA3B-Instruct/blob/main/config.json" \
            in cfg["source"]
        for key, value in PUBLISHED.items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] < value
                assert not manifest.names_a_width(key)
            else:
                assert cfg[key] == value, key
        assert len(cfg["rope_layout"]) == 52 \
            and cfg["rope_layout"] == cfg["sliding_window_layout"] \
            == [0, 1, 1, 1] * 13
        assert cfg["tie_word_embeddings"] is False \
            and cfg["norm_topk_prob"] is True \
            and cfg["moe_primary_router_apply_softmax"] is True

    def test_the_cut_is_a_share_of_the_stated_deployment(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        d = cfg["deployment"]
        assert d["chips_that_share_a_layer"] == 4 \
            and d["pipeline_stages"] * cfg["num_hidden_layers"] == 52
        assert cfg["moe_num_primary_experts"] \
            * d["chips_that_share_a_layer"] == 64
        assert d["first_expert_held"] == 0 \
            and d["experts_held"] == cfg["moe_num_primary_experts"]
        # the floors of a configuration's cut
        assert cfg["moe_num_primary_experts"] >= 8 \
            and cfg["vocab_size"] * 8 >= 151936 \
            and cfg["num_hidden_layers"] >= 4
        assert cfg["inputs"]["ids"]["high"] == cfg["vocab_size"] \
            <= cfg["assumed"]["vocab_rows"] < cfg["vocab_size"] + 128
        assert cfg["assumed"]["vocab_rows"] % 128 == 0
        cell = _json("benchmark", "workloads", f"{CELL}.json")
        assert (cell["config"], cell["chips"], cell["sequences_per_chip"],
                cell["sequence_length"]) == (CONFIG, 1, 2, 8192)
        assert set(cfg["assumed"]["why"]) >= {
            "router_read_point", "window_edge", "rope_pairing", "biases",
            "router_dtype"}


class TestGoldens:
    """``benchmark/tests/data/smallthinker_golden.json``: the shapes and
    the work counts as integers, at the cell's sizes and at the
    rehearsal's, recorded from the arithmetic of ``PERF.md`` section 4."""

    @pytest.fixture(scope="class")
    def golden(self):
        return _json("benchmark", "tests", "data", "smallthinker_golden.json")

    @pytest.mark.parametrize("size", ["cell", "tiny"])
    def test_shapes_and_counts(self, golden, size):
        from benchmark import run as bench
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        workload = _json("benchmark", "workloads", f"{CELL}.json")
        if size == "tiny":
            workload, cfg = bench.rehearse_cut(workload, cfg)
        seqs, length = (workload["sequences_per_chip"],
                        workload["sequence_length"])
        want = golden[size]
        assert [seqs, length] == want["sequences_and_length"]
        shapes = reference.param_shapes(cfg)
        assert [["/".join(p), list(s)] for p, s in weights.flatten(shapes)] \
            == want["param_shapes"]
        assert sorted(["/".join(p), n] for p, n
                      in reference.fused_parts(cfg).items()) \
            == want["fused_parts"]
        assert sum(weights._size(s) for _, s in weights.flatten(shapes)) \
            == want["parameters"]
        got = {"step_flops": flops.step_flops(cfg, seqs, length),
               "flash_work": flops.flash_work(cfg, seqs, length),
               "expert_work": arch.of(cfg).expert_work(cfg, seqs, length)}
        for name, value in got.items():
            assert value == want[name], name
            assert all(type(v) is int for v in (
                [value] if name == "step_flops" else
                [x for w in value.values() for x in w.values()])), name

    def test_the_counts_by_hand(self, golden):
        """The count of the cell's step from PERF.md's arithmetic: the
        attention projections, the router, 1.5 experts a token, the head
        over the rows ids are drawn from, and the pairs the masks keep."""
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        tokens, s, w = 2 * 8192, 8192, 4096
        full = s * (s + 1) // 2
        window = w * (w + 1) // 2 + (s - w) * w
        dense = 4 * (2560 * 4608 + 3584 * 2560 + 2560 * 64) \
            + 2560 * cfg["vocab_size"]
        experts = 4 * 3 * 2560 * 768 * (tokens * 6 * 16 // 64)
        want = 6 * dense * tokens + 6 * experts \
            + 12 * 28 * 128 * (full + 3 * window) * 2
        assert golden["cell"]["step_flops"] == want
        assert golden["cell"]["flash_work"]["fwd"]["flops"] \
            == 4 * 28 * 128 * (full + 3 * window) * 2
        assert golden["cell"]["expert_work"]["fwd"]["flops"] == 2 * experts


class TestScopeReader:
    """``benchmark/metrics/readers/scope_ms.py`` on a hand-made list of
    ops: by scope (wrapped by JAX or not), by instruction name, as a share
    of a roofline; nothing to read without a trace or without such ops."""

    @pytest.fixture(scope="class")
    def reader(self):
        path = os.path.join(ROOT, "benchmark", "metrics", "readers",
                            "scope_ms.py")
        spec = importlib.util.spec_from_file_location("scope_ms", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def _ctx(self, reader, ops):
        class Chip:
            steps = 2
        return {"trace": object(), "_scoped_ops": [(Chip, [
            (name, reader._components(path), s) for name, path, s in ops])]}

    def test_scopes_names_and_nothing(self, reader):
        base = "jit(hvd_dp_step)/hvd.loss_and_grad/"
        ctx = self._ctx(reader, [
            ("fusion.1", base + "jvp(M)/layer_0/moe/moe.experts/mul", 0.004),
            ("fusion.2", base + "transpose(jvp(M))/layer_0/moe/"
             "transpose(jvp(moe.experts))/mul", 0.002),
            ("ragged-dot-none.3", "ragged-dot-none", 0.010),
            ("sort.4", base + "jvp(M)/layer_1/moe/moe.dispatch/sort;x/y",
             0.006),
            ("fusion.5", base + "jvp(M)/layer_1/attn.window/attention/mul",
             0.020),
            ("fusion.6", None, 0.5)])
        assert reader.read(ctx, ["moe.experts"]) == pytest.approx(3.0)
        assert reader.read(ctx, ["moe.experts"], "^ragged-dot") \
            == pytest.approx(8.0)
        assert reader.read(ctx, ["moe.route", "moe.dispatch",
                                 "moe.combine"]) == pytest.approx(3.0)
        assert reader.read(ctx, ["attn.window"]) == pytest.approx(10.0)
        assert reader.read(ctx, ["attn.full"]) is None
        assert reader.read({"trace": None}, ["moe.experts"]) is None

    def test_roofline_share(self, reader):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        ctx = self._ctx(reader, [("ragged-dot-none.1", "ragged-dot-none",
                                  0.2)])
        ctx.update(cfg=cfg, peaks={"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9},
                   window={"sequences_per_chip": 2, "sequence_length": 8192})
        work = arch.of(cfg).expert_work(cfg, 2, 8192)
        least = flops.least_seconds(work, ctx["peaks"])
        assert reader.read(ctx, [], "^ragged-dot", "expert_work") \
            == pytest.approx(100 * least / 0.1)
        assert 0.0 < least < 0.1
        assert reader.read(ctx, [], "^ragged-dot", "no_such_work") is None

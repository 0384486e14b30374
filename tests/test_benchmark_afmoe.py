"""The benchmark's side of ``trinity_mini_26b_a3b_ep16`` and of its cell
``trinity_mini_ep16_8k_1chip``, on the CPU: the manifest is sound with the
new entries, the cell's rehearsal comes out ``correct`` through the whole of
``benchmark/run.py``, the configuration keeps every published number
outside ``reduced``, the architecture's shapes and work counts are the
recorded integers, and the new metrics' files name a reader that finds
their ops."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import arch, flops, manifest, reference, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "trinity_mini_ep16_8k_1chip", "trinity_mini_26b_a3b_ep16"
# The catalog's ``config`` of Trinity-Mini, every key of it.
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = ["num_dense_layers", "num_experts", "num_hidden_layers",
           "vocab_size"]
NEW_METRICS = {
    "attn.gate_norm_ms": ("attention", ["attn.qk_norm", "attn.gate"]),
    "block.post_norm_ms": ("compiled_dp_step", ["block.post_norm"]),
    "mlp.dense_ms": ("compiled_dp_step", ["mlp.dense"])}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_the_cells_rehearsal_is_correct():
    out = _run("--workload", CELL, "--seed", "2147483659", "--seconds", "2",
               "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["rehearsal"]["steps"] >= 1
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


class TestManifestEntries:
    def test_the_manifest_is_sound_with_them(self):
        m = _json("BENCHMARK.json")
        assert manifest.check(m, ROOT) == []
        assert CONFIG in [c["name"] for c in m["configs"]]
        assert CELL in [w["name"] for w in m["workloads"]]

    def test_the_cell_and_its_metrics(self):
        m = _json("BENCHMARK.json")
        cell = manifest.entry(m["workloads"], CELL, "workload")
        assert (cell["config"], cell["traffic"], cell["chips"]) \
            == (CONFIG, "2x8192_per_chip_x1", 1)
        assert cell["why"] == _json("benchmark", "workloads",
                                    f"{CELL}.json")["why"]
        by_name = {e["name"]: e for e in m["per_layer"]}
        for name, (layer, scopes) in NEW_METRICS.items():
            entry = by_name[name]
            assert entry["workloads"][0] == CELL, name
            assert (entry["layer"], entry["unit"], entry["moves"],
                    entry["source"]) == (layer, "ms",
                                         "tokens_per_s_per_chip",
                                         "device_trace"), name
            spec = _json("benchmark", "metrics", f"{name}.json")
            assert spec["reader"] == "benchmark/metrics/readers/scope_ms.py"
            assert spec["args"] == {"scopes": scopes}, name
        assert "not beside them" in _json(
            "benchmark", "metrics", "attn.gate_norm_ms.json")["what"]
        for name in ("step.mfu_pct", "kernels.flash_roofline",
                     "moe.dispatch_ms", "moe.experts_ms",
                     "moe.experts_roofline", "moe.buffer_rows_per_token",
                     "moe.overflow_calls", "moe.shared_ms", "attn.window_ms",
                     "attn.full_ms", "init.compile_s", "init.state_s",
                     "device.idle_pct", "step.forward_ms",
                     "step.backward_ms", "step.optimizer_ms",
                     "host.dispatch_ms", "host.shard_batch_ms",
                     "allreduce.bookkeeping_ms", "allreduce.mb_per_step",
                     "init.import_s", "init.hvd_init_s", "init.recorders_s",
                     "init.broadcast_s"):
            assert CELL in by_name[name]["workloads"], name
        for name in ("allreduce.exposed_ms", "allreduce.reduce_ms",
                     "ssm.mixer_ms", "ssm.scan_roofline"):
            assert CELL not in by_name[name]["workloads"], name

    def test_the_new_metrics_read_their_scopes(self):
        """``readers/scope_ms.py`` on op paths as the cell's trace has
        them: the gate and the head norms lie INSIDE attn.window /
        attn.full, the post norms and the dense feed-forward beside
        them."""
        spec = _json("benchmark", "metrics", "attn.gate_norm_ms.json")
        path = os.path.join(ROOT, spec["reader"])
        mod_spec = importlib.util.spec_from_file_location("scope_ms", path)
        reader = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(reader)

        class Chip:
            steps = 2
        base = "jit(hvd_dp_step)/hvd.loss_and_grad/"
        ops = [("fusion.1", base + "jvp(Afmoe)/layer_1/attn.window/"
                "attention/attn.qk_norm/q_norm/mul", 0.02),
               ("fusion.2", base + "transpose(jvp(Afmoe))/layer_2/attn.full/"
                "attention/attn.gate/gate/shard/dot_general", 0.06),
               ("fusion.3", base + "jvp(Afmoe)/layer_1/attn.window/"
                "attention/qkv/shard/dot_general", 0.10),
               ("fusion.4", base + "jvp(Afmoe)/layer_1/block.post_norm/"
                "post_attn_norm/mul", 0.01),
               ("fusion.5", base + "jvp(Afmoe)/layer_0/mlp.dense/mlp/"
                "gate_up/shard/dot_general", 0.04),
               ("fusion.6", base + "jvp(Afmoe)/layer_1/moe.shared/shared/"
                "out/shard/dot_general", 0.03)]
        ctx = {"trace": object(), "_scoped_ops": [(Chip, [
            (n, reader._components(p), s) for n, p, s in ops])]}
        got = {name: reader.read(
            ctx, **_json("benchmark", "metrics", f"{name}.json")["args"])
            for name in list(NEW_METRICS) + ["attn.window_ms",
                                             "attn.full_ms", "moe.shared_ms"]}
        assert got == pytest.approx({
            "attn.gate_norm_ms": 40.0, "block.post_norm_ms": 5.0,
            "mlp.dense_ms": 20.0, "attn.window_ms": 60.0,
            "attn.full_ms": 30.0, "moe.shared_ms": 15.0})


class TestConfiguration:
    def test_every_published_number_outside_reduced_is_kept(self):
        cfg, entry = _json("benchmark", "configs", f"{CONFIG}.json"), \
            manifest.entry(_json("BENCHMARK.json")["configs"], CONFIG,
                           "config")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
        assert entry["source"] == cfg["source"] == (
            "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/"
            "config.json")
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        for key, value in PUBLISHED.items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] < value
                assert not manifest.names_a_width(key)
            else:
                assert cfg[key] == value, key
        assert set(cfg["published"]) == set(REDUCED)
        assert (cfg["model"], cfg["arch"]) == ("afmoe", "afmoe_decoder")

    def test_the_cut_is_a_share_of_the_stated_deployment(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        d = cfg["deployment"]
        assert d["chips_that_share_a_layer"] == 16 \
            and cfg["num_experts"] * 16 == 128
        assert d["chips_that_share_the_vocabulary"] == 8 \
            and cfg["vocab_size"] * 8 == 200192
        assert d["layers_held"] == [0, 2, 3, 4, 5] \
            and cfg["num_hidden_layers"] == 5 \
            and cfg["num_dense_layers"] == 1
        assert arch.of(cfg).kinds_held(cfg) == d["layer_kinds_held"] == [
            "dense_window", "sparse_window", "sparse_full", "sparse_window",
            "sparse_window"]
        assert d["first_expert_held"] == 0 \
            and d["experts_held"] == cfg["num_experts"] >= 8
        assert cfg["inputs"]["ids"]["high"] == cfg["vocab_size"] == 25024
        assert cfg["assumed"]["vocab_rows"] == d["vocab_rows_held"] \
            == 25088 == 196 * 128
        cell = _json("benchmark", "workloads", f"{CELL}.json")
        assert (cell["config"], cell["chips"], cell["sequences_per_chip"],
                cell["sequence_length"]) == (CONFIG, 1, 2, 8192)
        assert set(cfg["assumed"]["why"]) >= {
            "qk_norm", "attention_gate", "four_norms", "window_edge",
            "positions", "router", "selection_bias", "embedding_scale",
            "dtypes", "vocab_rows", "optimizer", "init_std",
            "embedding_std"}
        assert "NOT from the source" in cfg["assumed"]["why"][
            "embedding_std"]

    def test_the_departures_say_what_is_not_built(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        said = " ".join(cfg["departures"])
        for words in ("selection bias b is zero and is not updated",
                      "load_balance_coeff is read by nothing",
                      "PARTIAL SUM IS NORMED", "no auxiliary loss",
                      "random from the seed"):
            assert words in said, words
        assert "13.85 GiB" in cfg["program"]["why"]


class TestGoldens:
    """``benchmark/tests/data/afmoe_golden.json``: the shapes and the work
    counts as integers, at the cell's sizes and at the rehearsal's,
    recorded from the arithmetic of ISSUE 35."""

    @pytest.fixture(scope="class")
    def golden(self):
        return _json("benchmark", "tests", "data", "afmoe_golden.json")

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
        a = arch.of(cfg)
        got = {"step_flops": flops.step_flops(cfg, seqs, length),
               "flash_work": flops.flash_work(cfg, seqs, length),
               "expert_work": a.expert_work(cfg, seqs, length)}
        for name, value in got.items():
            assert value == want[name], name
            assert all(type(v) is int for v in (
                [value] if name == "step_flops" else
                [x for w in value.values() for x in w.values()])), name

    def test_the_counts_by_hand(self, golden):
        """ISSUE 35's arithmetic: a dense layer 65,020,160, an expert layer
        holding 8 of 128 84,156,672, an eighth of the untied vocabulary
        102,760,448, the final norm: 504,409,344 parameters; a step's
        FLOPs from the parameters a token multiplies (264.1M) and the
        pairs the masks keep."""
        cell = golden["cell"]
        h, tokens = 2048, 2 * 8192
        attention = h * 5120 + 2 * h * 4096
        norms = 4 * h + 2 * 128
        dense_layer = attention + norms + 3 * h * 6144
        expert_layer = attention + norms + h * 128 + 3 * h * 1024 \
            + 8 * 3 * h * 1024
        assert (attention + 2 * 128, dense_layer, expert_layer) \
            == (27_263_232, 65_020_160, 84_156_672)
        assert cell["parameters"] == dense_layer + 4 * expert_layer \
            + 2 * 25088 * h + h == 504_409_344
        rows = tokens * 8 * 8 // 128                    # 0.5 a token
        dense = 5 * attention + 3 * h * 6144 \
            + 4 * (h * 128 + 3 * h * 1024) + h * 25024
        assert dense + 4 * 3 * h * 1024 * rows // tokens == 264_110_080
        window = 2048 * 2049 // 2 + (8192 - 2048) * 2048
        full = 8192 * 8193 // 2
        pairs = 4 * window + full
        assert cell["step_flops"] == 6 * dense * tokens \
            + 4 * 6 * 3 * h * 1024 * rows + 12 * 32 * 128 * pairs * 2 \
            == 35_034_853_539_840
        assert cell["flash_work"]["fwd"] == {
            "flops": 4 * 32 * 128 * pairs * 2,
            "bytes": (2 * 32 + 2 * 4) * tokens * 128 * 2 * 5}
        assert cell["expert_work"]["fwd"] == {
            "flops": 2 * 4 * 3 * h * 1024 * rows,
            "bytes": 4 * 8 * 3 * h * 1024 * 2
            + 4 * (2 * h + 3 * 1024) * 2 * rows}
        assert cell["expert_work"]["bwd"]["flops"] \
            == 2 * cell["expert_work"]["fwd"]["flops"]

    def test_the_buffer_and_the_tiles_at_the_cells_sizes(self):
        """0.5 rows a token expected, a buffer of 0.75; the grouped
        products' tiles at 2048 x 2048 and 1024 x 2048 are the kernels'
        (path 1)."""
        from horovod_tpu.parallel.moe import buffer_rows, product_tiles
        rows = buffer_rows(2 * 8192, 8, 8, 128)
        assert rows == 12288 == 0.75 * 2 * 8192
        assert product_tiles(rows, 2048, 2048)[0] == 1
        assert product_tiles(rows, 1024, 2048)[0] == 1

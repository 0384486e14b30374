"""The benchmark's side of ``kimi_linear_48b_a3b_ep32`` and of its cell
``kimi_linear_ep32_8k_1chip``, on the CPU: the manifest is sound with the
new entries, the cell's rehearsal comes out ``correct`` through the whole of
``benchmark/run.py``, the configuration keeps every published number
outside ``reduced``, the cut's counts are the ones worked out by hand, and the
new metrics' files name a reader that finds their ops."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import arch, flops, manifest, reference, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "kimi_linear_ep32_8k_1chip", "kimi_linear_48b_a3b_ep32"
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26]
# The catalog's ``config`` of Kimi-Linear-48B-A3B-Instruct, every key of it.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": KDA_LAYERS, "num_heads": 32,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = ["num_experts", "num_hidden_layers", "vocab_size"]
NEW_METRICS = {"kda.mixer_ms": ["kda.mixer"], "kda.core_ms": ["kda.core"],
               "kda.conv_ms": ["kda.conv"], "kda.core_roofline": ["kda.core"]}
# Existing metrics whose scope, kernel or counter the cell runs.
LISTED = (
    "init.compile_s", "init.state_s", "init.import_s", "init.hvd_init_s",
    "init.recorders_s", "init.broadcast_s", "init.trace_s", "init.lower_s",
    "init.backend_s", "init.cache_load_s", "step.mfu_pct", "step.forward_ms",
    "step.backward_ms", "step.optimizer_ms", "step.unnamed_ms",
    "step.no_path_ms", "host.dispatch_ms", "host.shard_batch_ms",
    "device.idle_pct", "allreduce.bookkeeping_ms", "allreduce.mb_per_step",
    "kernels.flash_roofline", "moe.dispatch_ms", "moe.experts_ms",
    "moe.experts_roofline", "moe.buffer_rows_per_token",
    "moe.overflow_calls", "moe.shared_ms", "attn.full_ms", "attn.core_ms",
    "attn.latent_ms", "mlp.dense_ms", "lm.head_ms", "lm.loss_ms")
NOT_LISTED = ("attn.rope_ms", "attn.window_ms", "attn.gate_norm_ms",
              "block.post_norm_ms", "attn.proj_ms",
              "mtp.module_ms", "allreduce.exposed_ms", "allreduce.reduce_ms",
              "ssm.mixer_ms", "ssm.scan_ms", "ssm.conv_ms",
              "ssm.scan_roofline", "ssm.chunk_state_mb")


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_the_cells_rehearsal_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["rehearsal"]["steps"] >= 1
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


class TestManifestEntries:
    def test_the_manifest_is_sound_with_them(self):
        m = _json("BENCHMARK.json")
        assert manifest.check(m, ROOT) == []
        assert m["configs"][-1]["name"] == CONFIG
        assert m["workloads"][-1]["name"] == CELL
        assert sum(w["chips"] == 4 for w in m["workloads"]) == 1

    def test_the_cell_and_its_metrics(self):
        m = _json("BENCHMARK.json")
        cell = manifest.entry(m["workloads"], CELL, "workload")
        assert (cell["config"], cell["traffic"], cell["chips"]) \
            == (CONFIG, "2x8192_per_chip_x1", 1)
        assert cell["why"] == _json("benchmark", "workloads",
                                    f"{CELL}.json")["why"]
        by_name = {e["name"]: e for e in m["per_layer"]}
        assert [e["name"] for e in m["per_layer"][-5:]] \
            == list(NEW_METRICS) + ["kda.chunk_state_mb"]
        for name, scopes in NEW_METRICS.items():
            entry = by_name[name]
            assert entry["workloads"] == [CELL], name
            assert (entry["layer"], entry["moves"], entry["source"]) == (
                "delta_rule", "tokens_per_s_per_chip", "device_trace")
            spec = _json("benchmark", "metrics", f"{name}.json")
            assert spec["reader"] == "benchmark/metrics/readers/scope_ms.py"
            assert spec["args"]["scopes"] == scopes, name
        assert _json("benchmark", "metrics", "kda.core_roofline.json")[
            "args"]["roofline_of"] == "kda_work"
        gauge = _json("benchmark", "metrics", "kda.chunk_state_mb.json")
        assert gauge["args"] == {"gauge": "hvd_kda_chunk_state_bytes",
                                 "scale": 1e-06}
        for name in LISTED:
            assert by_name[name]["workloads"][-1] == CELL, name
        for name in NOT_LISTED:
            assert CELL not in by_name[name]["workloads"], name

    def test_the_new_metrics_read_their_scopes(self):
        """``readers/scope_ms.py`` on op paths as the cell's trace has
        them: the mixer's leaves inside ``kda.mixer``, the kernels inside
        ``kda.core``; the roofline is the least time of ``kda_work`` over
        the core's time."""
        spec = _json("benchmark", "metrics", "kda.core_ms.json")
        mod_spec = importlib.util.spec_from_file_location(
            "scope_ms", os.path.join(ROOT, spec["reader"]))
        reader = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(reader)

        class Chip:
            steps = 2
        base = "jit(hvd_dp_step)/hvd.loss_and_grad/"
        mixer = "lm.model/layer_1/kda/kda.mixer/"
        ops = [("fusion.1", base + "jvp(KimiLinear)/" + mixer
                + "kda.in_proj/qkv/shard/dot_general", 0.02),
               ("fusion.2", base + "transpose(jvp(KimiLinear))/" + mixer
                + "kda.conv/mul", 0.04),
               ("hvd_kda_bwd_128x128", base + "transpose(jvp(KimiLinear))/"
                + mixer + "kda.core/pallas_call", 0.10),
               ("fusion.4", base + "jvp(KimiLinear)/lm.model/layer_3/"
                "attn.full/attention/attn.core/pallas_call", 0.03)]
        ctx = {"trace": object(), "_scoped_ops": [(Chip, [
            (n, reader._components(p), s) for n, p, s in ops])]}
        got = {name: reader.read(
            ctx, **_json("benchmark", "metrics", f"{name}.json")["args"])
            for name in ("kda.mixer_ms", "kda.core_ms", "kda.conv_ms",
                         "attn.core_ms")}
        assert got == pytest.approx({"kda.mixer_ms": 80.0,
                                     "kda.core_ms": 50.0,
                                     "kda.conv_ms": 20.0,
                                     "attn.core_ms": 15.0})
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        peaks = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}
        ctx.update(cfg=cfg, peaks=peaks, window={
            "sequences_per_chip": 2, "sequence_length": 8192})
        share = reader.read(ctx, scopes=["kda.core"],
                            roofline_of="kda_work")
        least = flops.least_seconds(
            arch.of(cfg).kda_work(cfg, 2, 8192), peaks)
        assert share == pytest.approx(100 * least / 0.05)


class TestConfiguration:
    def test_every_published_number_outside_reduced_is_kept(self):
        cfg, entry = _json("benchmark", "configs", f"{CONFIG}.json"), \
            manifest.entry(_json("BENCHMARK.json")["configs"], CONFIG,
                           "config")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
        assert entry["source"] == cfg["source"] == (
            "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct"
            "/blob/main/config.json")
        for key, value in PUBLISHED.items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] < value
                assert not manifest.names_a_width(key)
            else:
                assert cfg[key] == value, key
        assert set(cfg["published"]) == set(REDUCED)
        assert (cfg["model"], cfg["arch"]) == ("kimi_linear",
                                               "kimi_linear_decoder")

    def test_the_cut_is_a_share_of_the_stated_deployment(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        d = cfg["deployment"]
        assert d["chips_that_share_a_layer"] == 32 \
            and cfg["num_experts"] * 32 == 256
        assert d["chips_that_share_the_vocabulary"] == 8 \
            and cfg["vocab_size"] * 8 == 163840
        assert d["layers_held"] == [0, 1, 2, 3, 4] \
            and cfg["num_hidden_layers"] == 5
        assert [" ".join(arch.of(cfg).kind_of_layer(cfg, i))
                for i in range(5)] == d["layer_kinds_held"]
        assert d["first_expert_held"] == 0 \
            and d["experts_held"] == cfg["num_experts"] == 8
        assert cfg["inputs"]["ids"]["high"] == cfg["vocab_size"] \
            == cfg["assumed"]["vocab_rows"] == d["vocab_rows_held"] == 20480
        cell = _json("benchmark", "workloads", f"{CELL}.json")
        assert (cell["config"], cell["chips"], cell["sequences_per_chip"],
                cell["sequence_length"]) == (CONFIG, 1, 2, 8192)
        assert "NOT in config.json" in cfg["assumed"]["why"]["kda_assumed"]
        assert "NOT from the source" in cfg["assumed"]["why"][
            "embedding_std"]
        said = " ".join(cfg["departures"])
        for words in ("correction bias b is zero and is not updated",
                      "PARTIAL SUM GOES ON", "no auxiliary loss",
                      "random from the seed", "cache for decoding"):
            assert words in said, words
        assert "recomput" in cfg["program"]["why"]


class TestCounts:
    def test_the_cuts_parameters_by_hand(self):
        """A KDA mixer 39,514,272, an MLA mixer 29,114,880, the dense layer
        103,219,872, a KDA expert layer 103,809,696, the MLA expert layer
        93,410,304, an eighth of the untied vocabulary 94,371,840 and the
        final norm: 602,433,408 parameters."""
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        shapes = reference.param_shapes(cfg)

        def size(tree):
            return sum(weights._size(s) for _, s in weights.flatten(tree))
        layers = [size(shapes[f"layer_{i}"]) for i in range(5)]
        assert size(shapes["layer_0"]["kda"]) == 39_514_272
        assert size(shapes["layer_3"]["attention"]) == 29_114_880
        assert layers == [103_219_872, 103_809_696, 103_809_696,
                          93_410_304, 103_809_696]
        assert size(shapes) == sum(layers) + 94_371_840 + 2304 \
            == 602_433_408

    def test_the_work_counts(self):
        """The delta rule's work is 7 D^2 a token and head forward, twice
        that backward, in the four KDA layers; a step's FLOPs hold three
        times it; the flash kernels' work is the one MLA layer's."""
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        a = arch.of(cfg)
        tokens = 2 * 8192
        work = a.kda_work(cfg, 2, 8192)
        assert work["fwd"]["flops"] == 4 * tokens * 32 * 7 * 128 * 128
        assert work["bwd"]["flops"] == 2 * work["fwd"]["flops"]
        assert work["fwd"]["bytes"] == 4 * tokens * (
            3 * 4096 * 2 + 4 * 4096 + 4 * 32 + 4096 * 2)
        pairs = 8192 * 8193 // 2
        assert a.flash_work(cfg, 2, 8192)["fwd"]["flops"] \
            == 2 * 320 * 32 * pairs * 2
        total = flops.step_flops(cfg, 2, 8192)
        assert 37e12 < total < 39e12
        assert type(total) is int

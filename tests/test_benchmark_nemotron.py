"""The benchmark's side of ``nemotron_twotower_30b_a3b_ep16`` and of its
cell ``nemotron_tt_ep16_8k_1chip``, on the CPU: the manifest is sound with
the new entries, the cell's rehearsal comes out ``correct`` through the
whole of ``benchmark/run.py``, the configuration keeps every published
number outside ``reduced``, the architecture's shapes and work counts are
the recorded integers, and the new metrics' files name readers that find
their ops."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import arch, flops, manifest, reference, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "nemotron_tt_ep16_8k_1chip", "nemotron_twotower_30b_a3b_ep16"
# The catalog's ``config`` of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
# every key of it.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
NEW_METRICS = {
    "ssm.mixer_ms": ("state_space", "ms"),
    "ssm.scan_ms": ("state_space", "ms"),
    "ssm.conv_ms": ("state_space", "ms"),
    "ssm.scan_roofline": ("state_space", "%"),
    "moe.shared_ms": ("expert_layer", "ms"),
    "ssm.chunk_state_mb": ("state_space", "MB")}


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


class TestManifestEntries:
    def test_the_cell_and_its_metrics(self):
        m = _json("BENCHMARK.json")
        cell = manifest.entry(m["workloads"], CELL, "workload")
        assert (cell["config"], cell["traffic"], cell["chips"]) \
            == (CONFIG, "2x8192_per_chip_x1", 1)
        assert cell["why"] == _json("benchmark", "workloads",
                                    f"{CELL}.json")["why"]
        by_name = {e["name"]: e for e in m["per_layer"]}
        for name, (layer, unit) in NEW_METRICS.items():
            entry = by_name[name]
            # the cell that brought the metric is its first; later cells
            # append themselves (``moe.shared_ms``)
            assert entry["workloads"][0] == CELL, name
            assert (entry["layer"], entry["unit"], entry["moves"]) \
                == (layer, unit, "tokens_per_s_per_chip"), name
            spec = _json("benchmark", "metrics", f"{name}.json")
            assert os.path.isfile(os.path.join(ROOT, spec["reader"])), name
        for name in ("step.mfu_pct", "kernels.flash_roofline",
                     "moe.dispatch_ms", "moe.experts_ms",
                     "moe.experts_roofline", "moe.buffer_rows_per_token",
                     "moe.overflow_calls", "attn.full_ms", "init.compile_s",
                     "device.idle_pct", "step.forward_ms"):
            assert CELL in by_name[name]["workloads"], name
        for name in ("attn.window_ms", "allreduce.exposed_ms",
                     "allreduce.reduce_ms"):
            assert CELL not in by_name[name]["workloads"], name

    def test_the_scan_roofline_reads_the_architectures_scan_work(self):
        spec = _json("benchmark", "metrics", "ssm.scan_roofline.json")
        assert spec["args"] == {"scopes": ["ssm.scan"],
                                "roofline_of": "scan_work"}
        path = os.path.join(ROOT, spec["reader"])
        mod_spec = importlib.util.spec_from_file_location("scope_ms", path)
        reader = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(reader)

        class Chip:
            steps = 2
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        base = "jit(hvd_dp_step)/hvd.loss_and_grad/"
        ops = [("fusion.1", base + "jvp(NemotronH)/layer_0/mixer/checkpoint/"
                "ssm.mixer/ssm.scan/bchij,bcjhp->bcihp/dot_general", 0.05),
               ("fusion.2", base + "transpose(jvp(NemotronH))/layer_0/mixer/"
                "rematted_computation/ssm.mixer/ssm.scan/mul", 0.15),
               ("fusion.3", base + "jvp(NemotronH)/layer_0/mixer/checkpoint/"
                "ssm.mixer/ssm.conv/add", 0.01),
               ("fusion.4", base + "jvp(NemotronH)/layer_1/moe.shared/shared/"
                "up/dot_general", 0.03)]
        ctx = {"trace": object(), "cfg": cfg, "_scoped_ops": [(Chip, [
            (n, reader._components(p), s) for n, p, s in ops])],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "window": {"sequences_per_chip": 2, "sequence_length": 8192}}
        least = flops.least_seconds(arch.of(cfg).scan_work(cfg, 2, 8192),
                                    ctx["peaks"])
        assert reader.read(ctx, **spec["args"]) \
            == pytest.approx(100 * least / 0.1)
        assert reader.read(ctx, ["ssm.scan"]) == pytest.approx(100.0)
        assert reader.read(ctx, ["ssm.mixer"]) == pytest.approx(105.0)
        assert reader.read(ctx, ["ssm.conv"]) == pytest.approx(5.0)
        assert reader.read(ctx, ["moe.shared"]) == pytest.approx(15.0)
        # bound by bytes: its inputs and outputs once each way
        assert 0.003 < least < 0.004


class TestConfiguration:
    def test_every_published_number_outside_reduced_is_kept(self):
        cfg, entry = _json("benchmark", "configs", f"{CONFIG}.json"), \
            manifest.entry(_json("BENCHMARK.json")["configs"], CONFIG,
                           "config")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
            == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
        assert entry["source"] == cfg["source"] == (
            "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-"
            "Base-BF16/blob/main/config.json")
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        for key, value in PUBLISHED.items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] < value
                assert not manifest.names_a_width(key)
            else:
                assert cfg[key] == value, key
        assert (cfg["model"], cfg["arch"]) == ("nemotron_h",
                                               "nemotron_h_hybrid")

    def test_the_cut_is_a_share_of_the_stated_deployment(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        d = cfg["deployment"]
        assert d["chips_that_share_a_layer"] == 16 \
            and cfg["n_routed_experts"] * 16 == 128
        assert d["chips_that_share_the_vocabulary"] == 8 \
            and cfg["vocab_size"] * 8 == 131072
        # ISSUE 33's one fallback: layers 0..6, 3 : 3 : 1
        assert d["layers_held"] == list(range(7)) \
            and cfg["num_hidden_layers"] == 7
        assert cfg["hybrid_override_pattern"][:7] == d["layer_kinds_held"] \
            == "MEMEM*E"
        assert any("14.91 GiB" in x and "RESOURCE_EXHAUSTED" in x
                   for x in cfg["departures"])
        assert d["first_expert_held"] == 0 \
            and d["experts_held"] == cfg["n_routed_experts"] >= 8
        assert cfg["inputs"]["ids"]["high"] == cfg["vocab_size"] \
            == cfg["assumed"]["vocab_rows"] == d["vocab_rows_held"] == 16384
        cell = _json("benchmark", "workloads", f"{CELL}.json")
        assert (cell["config"], cell["chips"], cell["sequences_per_chip"],
                cell["sequence_length"]) == (CONFIG, 1, 2, 8192)
        assert set(cfg["assumed"]["why"]) >= {
            "positions", "inner_width", "projection_order",
            "gate_before_norm", "ssm_dtype", "router", "selection_bias",
            "biases", "conv_init"}

    def test_nothing_says_the_second_tower_is_built(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        first = cfg["departures"][0]
        assert "not built and not guessed" in first \
            and "denoising tower" in first and "diffusion" in first
        assert any("selection bias" in d and "not updated" in d
                   for d in cfg["departures"])


class TestGoldens:
    """``benchmark/tests/data/nemotron_golden.json``: the shapes and the
    work counts as integers, at the cell's sizes and at the rehearsal's,
    recorded from the arithmetic of ``PERF.md`` section 4."""

    @pytest.fixture(scope="class")
    def golden(self):
        return _json("benchmark", "tests", "data", "nemotron_golden.json")

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
               "expert_work": a.expert_work(cfg, seqs, length),
               "scan_work": a.scan_work(cfg, seqs, length)}
        for name, value in got.items():
            assert value == want[name], name
            assert all(type(v) is int for v in (
                [value] if name == "step_flops" else
                [x for w in value.values() for x in w.values()])), name

    def test_the_counts_by_hand(self, golden):
        """ISSUE 33's arithmetic for its fallback: 3 x 38,744,896 + 3 x
        100,125,312 + 23,399,040 + 2 x 16,384 x 2688 + 2688 parameters
        (528.1M; nine layers were 666,962,944); a step's FLOPs from the
        parameters a token multiplies, the causal pairs and the
        recurrence."""
        cell = golden["cell"]
        h, tokens = 2688, 2 * 8192
        mamba = h * 10304 + 4096 * h
        mamba_all = mamba + 4 * 6144 + 6144 + 3 * 64 + 4096 + h
        moe_all = h * 128 + 8 * 2 * h * 1856 + 2 * h * 3712 + h
        attention = h * 4608 + 4096 * h
        assert (mamba_all, moe_all, attention + h) \
            == (38_744_896, 100_125_312, 23_399_040)
        assert 4 * mamba_all + 4 * moe_all + attention + h \
            + 2 * 16384 * h + h == 666_962_944
        assert cell["parameters"] == 3 * mamba_all + 3 * moe_all \
            + attention + h + 2 * 16384 * h + h == 528_092_736
        rows = tokens * 6 * 8 // 128                    # 0.375 a token
        dense = 3 * mamba + 3 * (h * 128 + 2 * h * 3712) + attention \
            + h * 16384
        pairs = 8192 * 8193 // 2
        scan = 5 * 64 * 64 * 128 * tokens * 3
        assert cell["step_flops"] == 6 * dense * tokens \
            + 3 * 6 * 2 * h * 1856 * rows + 12 * 32 * 128 * pairs * 2 \
            + 3 * scan == 28_820_102_971_392
        assert cell["flash_work"]["fwd"]["flops"] == 4 * 32 * 128 * pairs * 2
        assert cell["expert_work"]["fwd"]["flops"] \
            == 2 * 3 * 2 * h * 1856 * rows
        assert cell["scan_work"]["fwd"] == {
            "flops": scan,
            "bytes": 3 * tokens * (2 * 4096 * 2 + 2 * 8 * 128 * 2 + 4 * 64)}
        assert cell["scan_work"]["bwd"]["flops"] == 2 * scan

    def test_chunk_states_of_a_mixer_call(self):
        """``ssm.chunk_state_mb`` at the cell's sizes: one set of chunk
        states is 2 sequences x 64 chunks x 64 heads x 64 x 128 elements a
        mixer call. The cell's call lies on the kernels' grid: the forward
        pass writes none, the backward pass's first sweep one set in
        bfloat16, 134.2 MB; the ``jax.numpy`` form's float32 closing states
        were 268.4 MB."""
        from horovod_tpu.parallel.ssm import chunk_states_bytes, scan_path
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        sizes = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                 cfg["ssm_state_size"], cfg["chunk_size"])
        assert chunk_states_bytes(2, 8192, *sizes) == 268_435_456
        path, blocks = scan_path((2, 8192, *sizes[:2]), cfg["n_groups"],
                                 sizes[2], sizes[3], 2)
        assert (path, blocks) == (1, (128, 512, 128))
        assert chunk_states_bytes(2, 8192, *sizes, itemsize=2) == 134_217_728
        spec = _json("benchmark", "metrics", "ssm.chunk_state_mb.json")
        assert spec["args"] == {"gauge": "hvd_ssm_chunk_state_bytes",
                                "scale": 1e-06}

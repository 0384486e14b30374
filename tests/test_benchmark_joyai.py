"""The benchmark's side of ``joyai_llm_flash_ep32`` and of its cell
``joyai_flash_ep32_8k_1chip``, on the CPU: the manifest is sound with the
new entries, the cell's rehearsal comes out ``correct`` through the whole of
``benchmark/run.py``, the configuration keeps every published number
outside ``reduced``, the architecture's shapes, work counts and reference
are the recorded ones, and the new metrics' files name a reader that finds
their ops."""

import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmark.harness import arch, flops, manifest, reference, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "joyai_flash_ep32_8k_1chip", "joyai_llm_flash_ep32"
# The catalog's ``config`` of JoyAI-LLM-Flash, every key of it.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
REDUCED = ["n_routed_experts", "num_hidden_layers", "vocab_size"]
NEW_METRICS = {
    "attn.latent_ms": ("attention", ["attn.q_latent", "attn.kv_latent"]),
    "mtp.module_ms": ("compiled_dp_step", ["mtp"])}
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
    "moe.overflow_calls", "moe.shared_ms", "attn.full_ms", "attn.proj_ms",
    "attn.core_ms", "mlp.dense_ms", "lm.head_ms", "lm.loss_ms")


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _run(*argv, script=("run.py",)):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_the_cells_rehearsal_is_correct():
    out = _run("--workload", CELL, "--seed", "2147483659", "--seconds", "2",
               "--trace", "1", "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["rehearsal"]["steps"] >= 1
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


def test_the_load_probe_reads_every_sparse_layer():
    """``tools/load_probe.py`` at the rehearsal's size: one line a (std,
    seed), every sparse layer of the stack and the MTP module's, each
    reading a share of the ``k`` choices a token makes."""
    out = _run("--workload", CELL, "--stds", "0.02,1.0", "--seeds",
               "2147483659", "--steps", "8", "--rehearse",
               script=("tools", "load_probe.py"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x[len("[probe] "):]) for x in out.stdout.splitlines()
             if x.startswith("[probe] {")]
    assert [x["std"] for x in lines] == [0.02, 1.0]
    k = 2   # the rehearsal's num_experts_per_tok, of 16 published
    for x in lines:
        assert x["layers"] == ["layer_1", "layer_2", "layer_3", "layer_4",
                               "mtp/block"]
        assert x["expected"] == k * 2 / 16
        assert [r[0] for r in x["rows"]] == [0, 8]
        assert 0 <= x["min"] <= x["max"] <= k


class TestManifestEntries:
    def test_the_manifest_is_sound_with_them(self):
        m = _json("BENCHMARK.json")
        assert manifest.check(m, ROOT) == []
        # appended directly behind Trinity's; later entries come after
        configs = [c["name"] for c in m["configs"]]
        cells = [w["name"] for w in m["workloads"]]
        assert configs.index(CONFIG) \
            == configs.index("trinity_mini_26b_a3b_ep16") + 1
        assert cells.index(CELL) \
            == cells.index("trinity_mini_ep16_8k_1chip") + 1
        assert sum(w["chips"] == 4 for w in m["workloads"]) \
            <= max(1, len(m["workloads"]) // 4)

    def test_the_cell_and_its_metrics(self):
        m = _json("BENCHMARK.json")
        cell = manifest.entry(m["workloads"], CELL, "workload")
        assert (cell["config"], cell["traffic"], cell["chips"]) \
            == (CONFIG, "2x8192_per_chip_x1", 1)
        assert cell["why"] == _json("benchmark", "workloads",
                                    f"{CELL}.json")["why"]
        by_name = {e["name"]: e for e in m["per_layer"]}
        names = [e["name"] for e in m["per_layer"]]
        first = names.index(list(NEW_METRICS)[0])
        assert names[first:first + 2] == list(NEW_METRICS)
        for name, (layer, scopes) in NEW_METRICS.items():
            entry = by_name[name]
            # this cell brought the metric; later cells may join it
            assert entry["workloads"][0] == CELL, name
            assert (entry["layer"], entry["unit"], entry["moves"],
                    entry["source"]) == (layer, "ms",
                                         "tokens_per_s_per_chip",
                                         "device_trace"), name
            spec = _json("benchmark", "metrics", f"{name}.json")
            assert spec["reader"] == "benchmark/metrics/readers/scope_ms.py"
            assert spec["args"] == {"scopes": scopes}, name
        for name in LISTED:
            assert CELL in by_name[name]["workloads"], name
        for name in ("attn.rope_ms", "attn.window_ms", "attn.gate_norm_ms",
                     "block.post_norm_ms", "allreduce.exposed_ms",
                     "allreduce.reduce_ms", "ssm.mixer_ms"):
            assert CELL not in by_name[name]["workloads"], name

    def test_the_new_metrics_read_their_scopes(self):
        """``readers/scope_ms.py`` on op paths as the cell's trace has
        them: the latent leaves lie INSIDE attn.full, the MTP module's ops
        under the container mtp whatever leaf is innermost."""
        spec = _json("benchmark", "metrics", "attn.latent_ms.json")
        path = os.path.join(ROOT, spec["reader"])
        mod_spec = importlib.util.spec_from_file_location("scope_ms", path)
        reader = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(reader)

        class Chip:
            steps = 2
        base = "jit(hvd_dp_step)/hvd.loss_and_grad/"
        ops = [("fusion.1", base + "jvp(JoyAIFlash)/lm.model/layer_1/"
                "attn.full/attention/attn.q_latent/q_b/shard/dot_general",
                0.02),
               ("fusion.2", base + "transpose(jvp(JoyAIFlash))/lm.model/"
                "layer_2/attn.full/attention/attn.kv_latent/concatenate",
                0.06),
               ("fusion.3", base + "jvp(JoyAIFlash)/lm.model/layer_1/"
                "attn.full/attention/attn.core/pallas_call", 0.10),
               ("fusion.4", base + "jvp(JoyAIFlash)/lm.model/mtp/mtp/"
                "eh_proj/dot_general", 0.01),
               ("fusion.5", base + "jvp(JoyAIFlash)/lm.model/mtp/mtp/block/"
                "attn.full/attention/attn.q_latent/q_a/dot_general", 0.04),
               ("fusion.6", base + "jvp(JoyAIFlash)/lm.model/mtp/lm.head/"
                "dot_general", 0.03)]
        ctx = {"trace": object(), "_scoped_ops": [(Chip, [
            (n, reader._components(p), s) for n, p, s in ops])]}
        got = {name: reader.read(
            ctx, **_json("benchmark", "metrics", f"{name}.json")["args"])
            for name in list(NEW_METRICS) + ["attn.full_ms", "attn.core_ms",
                                             "lm.head_ms"]}
        assert got == pytest.approx({
            "attn.latent_ms": 60.0, "mtp.module_ms": 40.0,
            "attn.full_ms": 110.0, "attn.core_ms": 50.0, "lm.head_ms": 15.0})


class TestConfiguration:
    def test_every_published_number_outside_reduced_is_kept(self):
        cfg, entry = _json("benchmark", "configs", f"{CONFIG}.json"), \
            manifest.entry(_json("BENCHMARK.json")["configs"], CONFIG,
                           "config")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
        assert entry["source"] == cfg["source"] == (
            "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
            "config.json")
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        for key, value in PUBLISHED.items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] < value
                assert not manifest.names_a_width(key)
            else:
                assert cfg[key] == value, key
        assert set(cfg["published"]) == set(REDUCED)
        assert (cfg["model"], cfg["arch"]) == ("joyai_flash",
                                               "joyai_flash_decoder")

    def test_the_cut_is_a_share_of_the_stated_deployment(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        d = cfg["deployment"]
        assert d["chips_that_share_a_layer"] == 32 \
            and cfg["n_routed_experts"] * 32 == 256
        assert d["chips_that_share_the_vocabulary"] == 8 \
            and cfg["vocab_size"] * 8 == 129280
        assert d["layers_held"] == [0, 1, 2, 3, 4] \
            and cfg["num_hidden_layers"] == 5 and d["mtp_module_held"]
        assert [arch.of(cfg).kind_of_layer(cfg, i) for i in range(5)] \
            == d["layer_kinds_held"] == ["dense"] + ["sparse"] * 4
        assert d["first_expert_held"] == 0 \
            and d["experts_held"] == cfg["n_routed_experts"] >= 8
        assert cfg["inputs"]["ids"]["high"] == cfg["vocab_size"] == 16160
        assert cfg["assumed"]["vocab_rows"] == d["vocab_rows_held"] \
            == 16256 == 127 * 128
        assert cfg["assumed"]["mtp_loss_weight"] == 0.3
        cell = _json("benchmark", "workloads", f"{CELL}.json")
        assert (cell["config"], cell["chips"], cell["sequences_per_chip"],
                cell["sequence_length"]) == (CONFIG, 1, 2, 8192)
        assert set(cfg["assumed"]["why"]) >= {
            "mla", "rope", "blocks", "router", "selection_bias",
            "shared_expert", "mtp", "mtp_loss_weight", "mtp_filler",
            "dtypes", "vocab_rows", "init_std", "optimizer",
            "embedding_std"}
        assert "NOT from the source" in cfg["assumed"]["why"][
            "embedding_std"]
        assert "NOT in the config" in cfg["assumed"]["why"]["mtp"]

    def test_the_departures_say_what_is_not_built(self):
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        said = " ".join(cfg["departures"])
        for words in ("correction bias b is zero and is not updated",
                      "PARTIAL SUM GOES ON", "no auxiliary loss",
                      "random from the seed", "absorbed form of MLA"):
            assert words in said, words
        assert "recompute" in cfg["program"]["why"]


class TestGoldens:
    """``benchmark/tests/data/joyai_golden.json``: the shapes and the work
    counts as integers at the cell's sizes and at the rehearsal's, and the
    reference's loss and gradient norms at the rehearsal's."""

    @pytest.fixture(scope="class")
    def golden(self):
        return _json("benchmark", "tests", "data", "joyai_golden.json")

    @staticmethod
    def _cut(size):
        from benchmark import run as bench
        cfg = _json("benchmark", "configs", f"{CONFIG}.json")
        workload = _json("benchmark", "workloads", f"{CELL}.json")
        if size == "tiny":
            workload, cfg = bench.rehearse_cut(workload, cfg)
        return workload, cfg

    @pytest.mark.parametrize("size", ["cell", "tiny"])
    def test_shapes_and_counts(self, golden, size):
        workload, cfg = self._cut(size)
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

    def test_the_reference_at_the_rehearsals_size(self, golden):
        """Loss and every leaf's gradient norm of the float32 reference on
        seed 11's weights and first batch, as recorded."""
        from benchmark.harness import traffic
        workload, cfg = self._cut("tiny")
        params = weights.make_params(reference.param_shapes(cfg), 11, cfg)
        batch = traffic.Batches(cfg, workload, 11).next()
        loss, grads = reference.Reference(cfg, "float32").loss_and_grad(
            params, batch)
        want = golden["tiny"]["reference_seed_11"]
        assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
        got = {"/".join(p): float(jnp.sqrt(jnp.sum(jnp.square(g))))
               for p, g in weights.flatten(grads)}
        assert sorted(got) == sorted(want["grad_norms"])
        for name, norm in want["grad_norms"].items():
            assert got[name] == pytest.approx(norm, rel=1e-4, abs=1e-9), name

    def test_the_counts_by_hand(self, golden):
        """The cut's arithmetic: an MLA layer 26,347,520, the dense layer
        70,391,808, an expert layer holding 8 of 256 69,343,232, the MTP
        module 77,737,984, an eighth of the untied vocabulary 66,584,576,
        the final norm: 492,089,344 parameters; a step's FLOPs from the
        parameters a token multiplies and the kept causal pairs at
        2 (192 + 128) forward and 4 (192 + 128) backward a head."""
        cell = golden["cell"]
        h, tokens, pairs = 2048, 2 * 8192, 8192 * 8193 // 2
        mla = h * 1536 + 1536 * 32 * 192 + h * 576 + 512 * 32 * 256 \
            + 32 * 128 * h
        norms = 1536 + 512 + 2 * h
        dense_layer = mla + norms + 3 * h * 7168
        expert_layer = mla + norms + h * 256 + 3 * h * 768 + 8 * 3 * h * 768
        mtp = expert_layer + 2 * h * h + 3 * h
        assert (mla + 2048, dense_layer, expert_layer, mtp) \
            == (26_347_520, 70_391_808, 69_343_232, 77_737_984)
        assert cell["parameters"] == dense_layer + 4 * expert_layer + mtp \
            + 2 * 16256 * h + h == 492_089_344
        rows = tokens * 8 * 8 // 256                    # 0.25 a token
        dense = 6 * mla + 3 * h * 7168 + 5 * (h * 256 + 3 * h * 768) \
            + 2 * h * h + 2 * h * 16160
        assert cell["step_flops"] == 6 * dense * tokens \
            + 5 * 6 * 3 * h * 768 * rows + 6 * 320 * 6 * 32 * pairs * 2 \
            == 55_098_860_371_968
        assert cell["flash_work"]["fwd"] == {
            "flops": 2 * 320 * 6 * 32 * pairs * 2,
            "bytes": (2 * 192 + 2 * 128) * tokens * 32 * 2 * 6}
        assert cell["flash_work"]["bwd"]["flops"] \
            == 2 * cell["flash_work"]["fwd"]["flops"]
        assert cell["expert_work"]["fwd"]["flops"] \
            == 2 * 5 * 3 * h * 768 * rows

    def test_the_buffer_and_the_tiles_at_the_cells_sizes(self):
        """0.25 rows a token expected, a buffer of 0.375; the grouped
        products' tiles at 2048 x 1536 and 768 x 2048 are the kernels'
        (path 1)."""
        from horovod_tpu.parallel.moe import buffer_rows, product_tiles
        rows = buffer_rows(2 * 8192, 8, 8, 256)
        assert rows == 6144 == 0.375 * 2 * 8192
        assert product_tiles(rows, 2048, 1536)[0] == 1
        assert product_tiles(rows, 768, 2048)[0] == 1

"""``models.joyai_flash`` and ``parallel.mla`` against the benchmark's plain
float32 reference (``benchmark/archs/joyai_flash_decoder.py``, which imports
nothing of ``horovod_tpu``), at a small size in the published ratios:
query and key heads wider than the value heads, both latents narrower than
the model, one dense layer then sparse ones, 16 experts, 2 a token, 2 held
beside a shared one, the MTP module. Seeded random weights made by the
benchmark's own rule. And the flash kernels at two head widths, whose
schedule is the one every equal-width call had."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.harness import (arch, check, program, reference, traffic,
                               weights)
from horovod_tpu.models.joyai_flash import (JoyAIFlash, JoyAIFlashBlock,
                                            JoyAIFlashConfig)
from horovod_tpu.parallel.mla import TPLatentAttention, rope_pairs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "joyai_llm_flash_ep32"
SEED, ROWS, LENGTH = 11, 2, 64
# A correction bias that is not zero: it has to move some token's choice.
BIAS = tuple(0.6 * np.cos(np.arange(16.0)))


def _cfg(**over):
    """The benchmark's configuration cut to the test's size (the
    rehearsal's sizes): every ratio kept, float32 so that the comparison
    sees the arithmetic and not the rounding."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    for key, tiny in arch.load("joyai_flash_decoder").REHEARSE.items():
        cfg[key] = dict(cfg[key], **tiny) if isinstance(tiny, dict) else tiny
    cfg["vocab_size"] = 250
    cfg["assumed"] = dict(cfg["assumed"], vocab_rows=256)
    cfg["inputs"] = {"ids": {"per": "token", "high": 250}}
    cfg.update(over)
    return cfg


def _setup(cfg):
    shapes = reference.param_shapes(cfg)
    params = weights.make_params(shapes, SEED, cfg)
    batch = traffic.Batches(
        cfg, {"sequences_per_chip": ROWS, "chips": 1,
              "sequence_length": LENGTH}, SEED).next()
    return shapes, params, batch


def _fa():
    import importlib
    return importlib.import_module("horovod_tpu.ops.pallas.flash_attention")


def _close(got, want, rel, what=""):
    """Every entry within ``rel`` of the largest entry of ``want``."""
    scale = float(jnp.abs(want).max())
    assert scale > 0, what
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=rel * scale, rtol=0, err_msg=what)


class TestLatentAttention:
    @pytest.mark.parametrize("use_flash", [False, True])
    def test_forward_and_gradients_match_the_reference(self, use_flash):
        """The layer (plain products, or the flash kernels through the
        interpreter) against the reference's ``attention`` on the same
        float32 weights: the output and the gradient of every weight and of
        the input, to 2e-5 of each one's largest entry."""
        cfg = _cfg()
        s = arch.of(cfg).sizes(cfg)
        net = arch.of(cfg).Net(cfg, reference.product("float32"))
        layer = TPLatentAttention(
            s["heads"], s["hidden"], s["q_rank"], s["kv_rank"], s["nope"],
            s["rope"], s["v"], cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
            axis_name=None, use_flash=use_flash)
        p = weights.make_params(reference.param_shapes(cfg), SEED,
                                cfg)["layer_1"]["attention"]
        x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, LENGTH, 64))
        dy = jax.random.normal(jax.random.PRNGKey(4), (ROWS, LENGTH, 64))

        def pulled(fn):
            y, pull = jax.vjp(fn, p, x)
            return y, pull(dy)
        got = pulled(lambda p, x: layer.apply({"params": p}, x))
        want = pulled(net.attention)
        _close(got[0], want[0], 2e-5, "output")
        _close(got[1][1], want[1][1], 2e-5, "dx")
        for path, leaf in weights.flatten(want[1][0]):
            _close(dict(weights.flatten(got[1][0]))[path], leaf, 2e-5,
                   "/".join(path))

    def test_the_rotation_is_interleaved_and_on_the_rope_part_alone(self):
        """Pair (2j, 2j+1) turns by pos theta^(-2j/d) and lands at
        (j, j + d/2); a dot product of two rotated rows is the interleaved
        form's; position 0 is only reordered."""
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
        got = rope_pairs(x, jnp.arange(5), 100.0)
        pos, j = 3, 2
        ang = pos * 100.0 ** (-2 * j / 8)
        e, o = x[0, pos, 1, 2 * j], x[0, pos, 1, 2 * j + 1]
        np.testing.assert_allclose(
            got[0, pos, 1, [j, j + 4]],
            [e * np.cos(ang) - o * np.sin(ang),
             o * np.cos(ang) + e * np.sin(ang)], rtol=1e-5)
        np.testing.assert_allclose(got[0, 0, :, :4], x[0, 0, :, 0::2])
        np.testing.assert_allclose(got[0, 0, :, 4:], x[0, 0, :, 1::2])
        interleaved = arch.load("joyai_flash_decoder").rope_interleaved(
            x, 100.0)
        np.testing.assert_allclose(
            jnp.einsum("blhd,bmhd->blmh", got, got),
            jnp.einsum("blhd,bmhd->blmh", interleaved, interleaved),
            rtol=1e-4, atol=1e-5)

    def test_every_head_shares_one_rotary_key(self):
        """The rotary part of every head's key is the one ``k_pe``: moving
        the shared key's columns of ``W_kva`` moves every head's score."""
        cfg = _cfg()
        s = arch.of(cfg).sizes(cfg)
        layer = TPLatentAttention(
            s["heads"], s["hidden"], s["q_rank"], s["kv_rank"], s["nope"],
            s["rope"], s["v"], 1e4, axis_name=None)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
        params = layer.init(jax.random.PRNGKey(2), x)["params"]
        assert params["kv_a"]["kernel"].shape == (64, s["kv_rank"]
                                                  + s["rope"])
        assert params["q_b"]["shard"]["kernel"].shape == (
            s["q_rank"], s["heads"] * (s["nope"] + s["rope"]))
        assert params["kv_b"]["shard"]["kernel"].shape == (
            s["kv_rank"], s["heads"] * (s["nope"] + s["v"]))
        assert params["out"]["shard"]["kernel"].shape == (s["heads"]
                                                          * s["v"], 64)

    @pytest.mark.parametrize("kw", [{"decode": True}, {"sp_axis": "hvd"}])
    def test_raises_off_the_full_sequence_path(self, kw):
        layer = TPLatentAttention(4, 64, 48, 32, 16, 8, 16, 1e4,
                                  axis_name=None, **kw)
        with pytest.raises(ValueError, match="full-sequence path only"):
            layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))

    def test_the_gauge_holds_the_widths(self):
        from horovod_tpu import metrics
        TPLatentAttention(4, 64, 48, 32, 16, 8, 16, 1e4,
                          axis_name=None).init(jax.random.PRNGKey(0),
                                               jnp.zeros((1, 8, 64)))
        got = {s["labels"]["kind"]: s["value"] for s in
               metrics.snapshot()["hvd_attn_layer"]["series"]}
        assert {k: got[k] for k in (
            "heads", "qk_head_dim", "v_head_dim", "q_lora_rank",
            "kv_lora_rank", "rope_head_dim")} == {
            "heads": 4, "qk_head_dim": 24, "v_head_dim": 16,
            "q_lora_rank": 48, "kv_lora_rank": 32, "rope_head_dim": 8}


class TestAgainstTheReference:
    def test_names_and_shapes_are_the_references(self):
        cfg = _cfg()
        shapes, _, batch = _setup(cfg)
        model, _ = program.load_model_builder(cfg["model"])(cfg)
        assert weights.flatten(check.plain(program.model_shapes(
            model, batch))) == weights.flatten(shapes)
        assert set(shapes["mtp"]) == {"enorm", "hnorm", "norm", "eh_proj",
                                      "block"}
        assert "moe" in shapes["mtp"]["block"] \
            and "mlp" in shapes["layer_0"] and "moe" in shapes["layer_1"]

    @pytest.mark.parametrize("bias", [None, BIAS])
    def test_loss_and_every_gradient(self, bias):
        """float32 on both sides, the program through latent attention on
        the flash kernels (interpreter), the sorted dispatch, the MTP
        module and the two-term loss; the reference through whole masked
        squares, a loop over the experts and its own MTP module. The loss
        to 1e-5 relative; every leaf's gradient to 2e-4 of that leaf's
        largest entry (sums in another order); each planted fault moves
        some leaf by fifty times that (test_a_planted_fault_is_seen)."""
        cfg = _cfg(selection_bias=bias)
        _, params, batch = _setup(cfg)
        _, loss_fn = program.load_model_builder(cfg["model"])(cfg)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, {k: jnp.asarray(v) for k, v in batch.items()})
        want_loss, want = reference.Reference(cfg, "float32").loss_and_grad(
            params, batch)
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        got = dict(weights.flatten(grads))
        for path, leaf in weights.flatten(want):
            _close(got[path], leaf, 2e-4, "/".join(path))

    def test_the_mtp_term_is_the_loss_on_the_token_after_next(self):
        """The program's two terms apart: the MTP logits scored against
        ids two ahead carry the configuration's weight, 0.3."""
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
        ids = jnp.asarray(batch["ids"])
        logits, mtp = model.apply({"params": params}, ids)
        assert logits.shape == mtp.shape == (ROWS, LENGTH, 256)
        xent = optax.softmax_cross_entropy_with_integer_labels
        main = xent(logits[:, :-1], ids[:, 1:]).mean()
        ahead = xent(mtp[:, :-2], ids[:, 2:]).mean()
        assert cfg["assumed"]["mtp_loss_weight"] == 0.3
        assert float(loss_fn(params, {"ids": ids})) == pytest.approx(
            float(main + 0.3 * ahead), rel=1e-6)

    def test_the_mtp_module_reads_the_next_id_and_the_filler_is_unread(self):
        """Another last id moves the MTP logits of the last two positions
        alone (position i reads t_{i+1}; attention is causal); another
        filler row, the last position's next embedding, moves the last
        position's alone, whose two targets the loss leaves out."""
        from horovod_tpu.models.joyai_flash import JoyAIFlashMTP
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        model, _ = program.load_model_builder(cfg["model"])(cfg)
        ids = jnp.asarray(batch["ids"])
        moved = ids.at[:, -1].set((ids[:, -1] + 7) % 250)
        a = model.apply({"params": params}, ids)[1]
        b = model.apply({"params": params}, moved)[1]
        assert float(jnp.abs(a[:, :-2] - b[:, :-2]).max()) < 1e-5
        assert float(jnp.abs(a[:, -2] - b[:, -2]).max()) > 1e-3
        mtp = JoyAIFlashMTP(model.config)
        e, h = (jax.random.normal(jax.random.PRNGKey(i), (ROWS, LENGTH, 64))
                for i in (6, 7))
        z = mtp.apply({"params": params["mtp"]}, e, h)
        other = mtp.apply({"params": params["mtp"]}, e.at[:, -1].set(3.0), h)
        assert float(jnp.abs(z[:, :-1] - other[:, :-1]).max()) < 1e-5
        assert float(jnp.abs(z[:, -1] - other[:, -1]).max()) > 1e-3

    @pytest.fixture(scope="class")
    def sound(self):
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        return cfg, params, batch, reference.Reference(
            cfg, "float32").loss_and_grad(params, batch)

    @pytest.mark.parametrize("fault", sorted(
        arch.load("joyai_flash_decoder").FAULTS))
    def test_a_planted_fault_is_seen(self, sound, fault):
        """The reference with one fault of this architecture's own moves
        the loss or some leaf's gradient by over a hundredth of its largest
        entry, fifty times the tolerance above."""
        cfg, params, batch, sound = sound
        faulty = reference.Reference(
            dict(cfg, planted_fault=fault), "float32").loss_and_grad(
                params, batch)
        gaps = [abs(float(faulty[0]) / float(sound[0]) - 1)]
        for (_, a), (_, b) in zip(weights.flatten(faulty[1]),
                                  weights.flatten(sound[1])):
            gaps.append(float(jnp.abs(a - b).max() / jnp.abs(b).max()))
        assert max(gaps) > 0.01, (fault, max(gaps))

    def test_an_unknown_fault_or_router_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="unknown planted fault"):
            reference.Reference(dict(cfg, planted_fault="no_such"),
                                "float32")
        with pytest.raises(ValueError, match="scoring_func"):
            reference.param_shapes(dict(cfg, scoring_func="softmax"))
        with pytest.raises(ValueError, match="rope_interleave"):
            reference.param_shapes(dict(cfg, rope_interleave=False))
        with pytest.raises(ValueError, match="num_nextn_predict_layers"):
            reference.param_shapes(dict(cfg, num_nextn_predict_layers=2))

    def test_shares_add_up_to_the_uncut_layer(self):
        """The routed part of the program's layer for each of the 16
        shares (one of 16 experts each) on one input, plus the shared
        expert counted once: the reference's ``f`` of the uncut layer
        (every expert held)."""
        from horovod_tpu.parallel.moe import DroplessMoE
        from horovod_tpu.parallel.tp import TPSwiGLUMlp
        whole = _cfg(n_routed_experts=16)
        net = arch.of(whole).Net(whole, reference.product("float32"))
        p = weights.make_params(reference.param_shapes(whole), SEED,
                                whole)["layer_2"]
        m = jax.random.normal(jax.random.PRNGKey(5), (ROWS, LENGTH, 64))
        want = net.sparse(p, m)
        total = TPSwiGLUMlp(32, 64, axis_name=None).apply(
            {"params": p["shared"]}, m)
        assert float(jnp.abs(total).max()) > 0
        for first in range(16):
            mine = dict(p["moe"], **{
                name: p["moe"][name][first:first + 1]
                for name in ("w_gate_up", "w_down")})
            total = total + DroplessMoE(
                16, 2, 64, 32, experts_held=1, first_expert=first,
                weighting="sigmoid",
                weight_scale=whole["routed_scaling_factor"],
                expert_form="gated_silu").apply({"params": mine}, m)
        _close(total, want, 1e-5)


class TestModel:
    def test_layers_say_what_they_hold_and_the_bias_is_no_leaf(self):
        config = JoyAIFlashConfig.tiny(experts_held=2, first_expert_held=6)
        ids = jnp.zeros((2, 32), jnp.int32)
        params = JoyAIFlash(config).init(jax.random.PRNGKey(0),
                                         ids)["params"]
        assert set(params) == {"embed", "layer_0", "layer_1", "layer_2",
                               "head", "mtp"}
        assert set(params["layer_0"]) == {"input_norm", "post_attn_norm",
                                          "attention", "mlp"}
        assert set(params["layer_1"]["moe"]) == {"router", "w_gate_up",
                                                 "w_down"}
        assert params["layer_1"]["moe"]["w_gate_up"].shape == (2, 64, 64)
        assert set(params["head"]) == {"ln_f", "lm_head"}
        with pytest.raises(ValueError, match="unknown kind of layer"):
            JoyAIFlashBlock(config, "hybrid").init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))

    def test_trains_through_make_train_step(self, hvd):
        """The normal path: broadcast_parameters -> DistributedOptimizer ->
        make_train_step on the CPU mesh, the batch split over every
        device, both terms of the loss; the loss falls."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, make_train_step,
                                          shard_batch)
        config = JoyAIFlashConfig.tiny(experts_held=4, first_expert_held=4,
                                       use_flash=True)
        model = JoyAIFlash(config)
        rows = 2 * hvd.size()
        ids = np.random.default_rng(0).integers(0, 256, (rows, 40),
                                                dtype=np.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
        xent = optax.softmax_cross_entropy_with_integer_labels

        def loss_fn(params, batch):
            logits, mtp = model.apply({"params": params}, batch["ids"])
            return xent(logits[:, :-1], batch["ids"][:, 1:]).mean() \
                + 0.3 * xent(mtp[:, :-2], batch["ids"][:, 2:]).mean()

        opt = DistributedOptimizer(optax.adam(1e-2))
        mesh = hvd.global_process_set.mesh
        state = TrainState.create(
            hvd.broadcast_parameters(params, root_rank=0), opt)
        step = make_train_step(loss_fn, opt, mesh, donate=False)
        batch = shard_batch({"ids": ids}, mesh)
        losses = []
        for _ in range(6):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1

    def test_the_steps_scopes_are_listed_and_on_their_ops(self):
        """The new names are on ops forward and backward: the two latent
        leaves inside ``attn.full``, the ``mtp`` container; every
        scope-like name in the lowered step is in ``trace/scopes.py``."""
        from horovod_tpu.trace import scopes
        model = JoyAIFlash(JoyAIFlashConfig.tiny(use_flash=True))
        ids = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]

        def loss(p):
            logits, mtp = model.apply({"params": p}, ids)
            return logits.sum() + mtp.sum()
        text = jax.jit(jax.grad(loss)).lower(params).as_text(
            debug_info=True)
        # named locations, loc("path"(...)), not a file's loc("f.py":1:2)
        paths = re.findall(r'loc\("([^"]*)"\(', text)
        wrapped = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")
        parts = [[wrapped.sub(r"\1", p) for p in path.split("/")]
                 for path in paths]
        for name in ("attn.q_latent", "attn.kv_latent", "attn.core",
                     "attn.out", "mtp", "lm.head", "moe.shared"):
            mine = [path for path, ps in zip(paths, parts) if name in ps]
            assert any("transpose(" not in p for p in mine), name
            assert any("transpose(" in p for p in mine), name
        for ps in parts:
            if "attn.q_latent" in ps or "attn.kv_latent" in ps:
                assert "attn.full" in ps
        scope_like = re.compile(r"^(lm|attn|ssm|moe|mlp|block|hvd)\.[a-z_]+$"
                                r"|^mtp$")
        met = {p for ps in parts for p in ps if scope_like.match(p)}
        assert {"mtp", "attn.q_latent", "attn.kv_latent"} <= met
        assert met <= set(scopes.KINDS), met - set(scopes.KINDS)


# -- the flash kernels at two head widths --------------------------------------

def _flash_gauge():
    from horovod_tpu import metrics
    out = {}
    for s in metrics.snapshot()["hvd_flash_tiles"]["series"]:
        out.setdefault(s["labels"]["kernel"], {})[
            s["labels"]["kind"]] = s["value"]
    return out


class TestFlashAtTwoWidths:
    DQK, DV = 192, 128

    def _operands(self, rng, heads, lq, lk):
        q = jnp.asarray(rng.standard_normal((heads, lq, self.DQK)),
                        np.float32)
        k = jnp.asarray(rng.standard_normal((heads, lk, self.DQK)),
                        np.float32)
        v, do = (jnp.asarray(rng.standard_normal((heads, n, self.DV)),
                             np.float32) for n in (lk, lq))
        return q, k, v, do

    def _against_oracles(self, fa, q, k, v, do, **kw):
        sm = 1.0 / self.DQK ** 0.5
        o, lse = fa._fa_forward(q, k, v, True, sm, **kw)
        assert o.shape == v.shape[:1] + q.shape[1:2] + (self.DV,)
        o_ref, lse_ref = fa._jnp_block_fwd(q, k, v, True, sm)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-5)
        got = fa._fa_backward(q, k, v, o_ref, lse_ref, do, True, sm, **kw)
        want = fa._jnp_block_bwd(q, k, v, o_ref, lse_ref, do, True, sm)
        for a, b, nm in zip(got, want, "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{nm}")

    def test_by_block_kind_at_2048(self, rng, monkeypatch, flash_backward):
        """Forward and backward (the one kernel, and the pair) of a causal
        call of 2048 (two blocks of 1024: the schedule by block kind)
        through the interpreter at Dqk 192, Dv 128, against the jnp
        oracles."""
        fa = _fa()
        monkeypatch.delenv("HVD_FLASH_BLOCK", raising=False)
        assert fa._by_block(2048, 2048, 0, 2048, True, None)
        self._against_oracles(fa, *self._operands(rng, 1, 2048, 2048))
        last = "bwd_dqkv" if flash_backward == "bwd_dqkv" else "bwd_dkv"
        assert _flash_gauge()[last]["blocks_diagonal"] == 2

    def test_rolled_tiles_and_chunks(self, rng, monkeypatch, flash_backward):
        """Small tiles in chunks whose bounds follow the grid (no block
        kind): 256 causal in tiles of 32 x 64, chunks of 128; the one
        backward kernel, and the pair."""
        fa = _fa()
        monkeypatch.delenv("HVD_FLASH_BLOCK", raising=False)
        pick = fa._pick_chunk
        monkeypatch.setattr(
            fa, "_pick_chunk",
            lambda n, block, cap=4096: pick(n, block, min(cap, 128)))
        assert not fa._by_block(256, 256, 0, 256, True, None)
        self._against_oracles(fa, *self._operands(rng, 2, 256, 256),
                              block_q=32, block_k=64)

    @pytest.mark.parametrize("length", [256, 2048])
    def test_the_public_call_against_local_attention(self, rng, length):
        """``flash_attention`` on (B, L, H, D) with a narrower ``v``: the
        output is ``v``'s width and it and the three gradients are
        ``local_attention``'s, which scales by 1/sqrt of the query's
        width."""
        from horovod_tpu.parallel.sequence import local_attention
        fa = _fa()
        q, k = (jnp.asarray(rng.standard_normal((1, length, 2, self.DQK)),
                            np.float32) for _ in range(2))
        v = jnp.asarray(rng.standard_normal((1, length, 2, self.DV)),
                        np.float32)
        do = jnp.asarray(rng.standard_normal((1, length, 2, self.DV)),
                         np.float32)

        def pulled(fn):
            y, pull = jax.vjp(fn, q, k, v)
            return (y,) + pull(do)
        got = pulled(lambda q, k, v: fa.flash_attention(q, k, v,
                                                        causal=True))
        want = pulled(lambda q, k, v: local_attention(q, k, v, causal=True))
        assert got[0].shape == (1, length, 2, self.DV)
        for a, b, nm in zip(got, want, ("o", "dq", "dk", "dv")):
            _close(a, b, 2e-5, nm)

    # each call of the five cells (batch, lq, heads, kv heads, d, window):
    # its gauge per (batch, head), recorded at equal widths
    CELL_CALLS = {
        "gpt2m": ((8, 1024, 16, 16, 64, None),
                  {"fwd": (16, 12, 8), "bwd_dqkv": (16, 10, 4)}),
        "sparse_causal_8192": ((2, 8192, 28, 4, 128, None), None),
        "window_4096": ((2, 8192, 28, 4, 128, 4096), None),
        "window_2048": ((2, 8192, 32, 4, 128, 2048), None),
    }

    @pytest.mark.parametrize("call", sorted(CELL_CALLS))
    def test_every_cells_schedule_is_the_width_blind_one(self, monkeypatch,
                                                         call):
        """The schedule and the gauge of a call read its lengths, offset
        and window alone: the calls of the five cells give the same
        tiles and counts at their equal widths as at 192 / 128 (the
        recorded ``gpt2m_*`` counts, total / visited / masked, as
        PERF.md's table has them), and the cells' 8192 calls go by block
        kind with the blocks the table names. Every cell's backward is
        the one kernel, at both widths."""
        fa = _fa()
        monkeypatch.delenv("HVD_FLASH_BLOCK", raising=False)
        (b, length, h, kv, d, window), recorded = self.CELL_CALLS[call]

        def gauges(dqk, dv):
            out = {}
            q = jax.ShapeDtypeStruct((b * h, length, dqk), jnp.bfloat16)
            k = jax.ShapeDtypeStruct((b * kv, length, dqk), jnp.bfloat16)
            v = jax.ShapeDtypeStruct((b * kv, length, dv), jnp.bfloat16)
            jax.eval_shape(lambda q, k, v: fa._fa_forward(
                q, k, v, True, 0.1, heads=h, kv_heads=kv, window=window),
                q, k, v)
            out["fwd"] = _flash_gauge()["fwd"]
            k, v = (jax.ShapeDtypeStruct((b * h,) + t.shape[1:], t.dtype)
                    for t in (k, v))
            r = jax.ShapeDtypeStruct((b * h, length), jnp.float32)
            o = jax.ShapeDtypeStruct((b * h, length, dv), jnp.bfloat16)
            assert fa.backward_path(length, dqk, 2) == ("bwd_dqkv",)
            jax.eval_shape(lambda *a: fa._fa_backward(
                *a, True, 0.1, window=window), q, k, v, o, r, o)
            out["bwd_dqkv"] = _flash_gauge()["bwd_dqkv"]
            return out

        equal = gauges(d, d)
        assert gauges(self.DQK, self.DV) == equal
        if recorded:
            assert {kernel: (g["total"], g["visited"], g["masked"])
                    for kernel, g in equal.items()} == recorded
        else:
            blocks = tuple(equal["fwd"]["blocks_" + kind] for kind in (
                "inside", "diagonal", "edge", "skipped"))
            assert blocks == {None: (28, 8, 0, 28), 4096: (18, 8, 4, 34),
                              2048: (7, 8, 6, 43)}[window]

"""``models.afmoe`` against the benchmark's plain float32 reference
(``benchmark/archs/afmoe_decoder.py``, which imports nothing of
``horovod_tpu``), at a small size in the published ratios: the benchmark's
cut (one dense layer, then window, full, window, window), 16 experts, 2 a
token, 2 held beside a shared one, 8:1 grouped heads twice as wide
together as the model. Seeded random weights made by the benchmark's own
rule. And the programs the other cells trace, which this model's arrival
must not have changed."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.harness import (arch, check, program, reference, traffic,
                               weights)
from horovod_tpu.models.afmoe import (LAYER_TYPES, Afmoe, AfmoeBlock,
                                      AfmoeConfig, layer_kinds)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "trinity_mini_26b_a3b_ep16"
SEED, ROWS, LENGTH = 11, 2, 64
# A balancing bias that is not zero: it has to move some token's choice.
BIAS = tuple(0.6 * np.cos(np.arange(16.0)))


def _cfg(**over):
    """The benchmark's configuration cut to the test's size (the
    rehearsal's sizes): every ratio kept, float32 so that the comparison
    sees the arithmetic and not the rounding."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    for key, tiny in arch.load("afmoe_decoder").REHEARSE.items():
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


class TestAgainstTheReference:
    def test_names_and_shapes_are_the_references(self):
        cfg = _cfg()
        shapes, _, batch = _setup(cfg)
        model, _ = program.load_model_builder(cfg["model"])(cfg)
        assert weights.flatten(check.plain(program.model_shapes(
            model, batch))) == weights.flatten(shapes)
        assert arch.of(cfg).kinds_held(cfg) == [
            "dense_window", "sparse_window", "sparse_full", "sparse_window",
            "sparse_window"]

    def test_fresh_leaves_follow_the_configurations_assumed(self):
        cfg = _cfg()
        assert cfg["assumed"]["embedding_std"] == 0.5
        _, params, _ = _setup(cfg)
        layer = params["layer_1"]
        for name in ("input_norm", "post_attn_norm", "pre_ffn_norm",
                     "post_ffn_norm"):
            assert bool(jnp.all(layer[name]["scale"] == 1))
        assert bool(jnp.all(layer["attention"]["q_norm"]["scale"] == 1))
        assert float(jnp.std(params["embed"]["tok_emb"]["embedding"])) \
            == pytest.approx(0.5, rel=0.05)
        assert float(jnp.std(layer["moe"]["w_gate_up"])) \
            == pytest.approx(0.02, rel=0.05)

    @pytest.mark.parametrize("bias", [None, BIAS])
    def test_loss_and_every_gradient(self, bias):
        """float32 on both sides, the program through the sorted dispatch
        with sigmoid weights and gated SiLU experts, the fused projections
        and the flash kernels (interpreter); the reference through a loop
        over the experts and whole masked squares. The loss to 1e-5
        relative; every leaf's gradient to 2e-4 of that leaf's largest
        entry (sums in another order). Each planted fault moves some leaf
        by fifty times that (test_a_planted_fault_is_seen). With a
        balancing bias that is not zero some token chooses other
        experts."""
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
            scale = float(jnp.abs(leaf).max())
            assert scale > 0, path
            np.testing.assert_allclose(
                got[path], leaf, atol=2e-4 * scale, err_msg="/".join(path))

    def test_the_bias_moves_some_tokens_choice_and_the_loss(self):
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        net = arch.of(cfg).Net(cfg, reference.product("float32"))
        x = net.block("dense_window", params["layer_0"],
                      net.embed(params["embed"], batch))
        p = params["layer_1"]
        m = arch.of(cfg).rms(x, p["pre_ffn_norm"], cfg["rms_norm_eps"])
        scores = jax.nn.sigmoid(m @ p["moe"]["router"]["kernel"])
        plain = jnp.sort(jax.lax.top_k(scores, 2)[1], -1)
        biased = jnp.sort(jax.lax.top_k(scores + jnp.asarray(BIAS), 2)[1], -1)
        moved = jnp.any(plain != biased, -1)
        assert 0 < int(moved.sum()) < moved.size
        losses = [float(reference.Reference(
            dict(cfg, selection_bias=b), "float32").loss_and_grad(
                params, batch)[0]) for b in (None, BIAS)]
        assert losses[0] != losses[1]

    @pytest.fixture(scope="class")
    def sound(self):
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        return cfg, params, batch, reference.Reference(
            cfg, "float32").loss_and_grad(params, batch)

    @pytest.mark.parametrize("fault", sorted(
        arch.load("afmoe_decoder").FAULTS))
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

    def test_an_unknown_fault_kind_or_router_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="unknown planted fault"):
            reference.Reference(dict(cfg, planted_fault="no_such"),
                                "float32")
        with pytest.raises(ValueError, match="unknown layer_types entry"):
            reference.param_shapes(dict(
                cfg, layer_types=["linear_attention"] * 32))
        with pytest.raises(ValueError, match="score_func"):
            reference.param_shapes(dict(cfg, score_func="softmax"))

    def test_shares_add_up_to_the_uncut_layer(self):
        """The routed part of the program's layer for each of the 16
        shares (one of 16 experts each) on one input, plus the shared
        expert counted once: the reference's ``f`` of the uncut layer
        (every expert held), before ``g_post_ffn``."""
        from horovod_tpu.parallel.moe import DroplessMoE
        from horovod_tpu.parallel.tp import TPSwiGLUMlp
        whole = _cfg(num_experts=16)
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
                weighting="sigmoid", weight_scale=whole["route_scale"],
                expert_form="gated_silu").apply({"params": mine}, m)
        np.testing.assert_allclose(total, want, atol=1e-5 * float(
            jnp.abs(want).max()))


class TestModel:
    def test_kinds_follow_the_published_layer_types(self):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{CONFIG}.json")) as f:
            cfg = json.load(f)
        assert tuple(cfg["layer_types"]) == LAYER_TYPES * 8
        kinds = AfmoeConfig().kinds
        assert len(kinds) == 32 and kinds == layer_kinds(
            cfg["layer_types"], cfg["published"]["num_dense_layers"])
        assert [k[0] for k in kinds].count("dense") == 2 \
            and kinds[0] == kinds[1] == ("dense", "window")
        assert [k[1] for k in kinds].count("full") == 8 \
            and all(k == ("sparse", "full") for k in kinds[3::4])
        held = layer_kinds(cfg["layer_types"], cfg["num_dense_layers"],
                           cfg["deployment"]["layers_held"])
        assert held == AfmoeConfig.tiny().kinds == (
            ("dense", "window"), ("sparse", "window"), ("sparse", "full"),
            ("sparse", "window"), ("sparse", "window"))
        with pytest.raises(ValueError, match="no kind of attention"):
            layer_kinds(["sliding_attention", "mamba"], 1)
        with pytest.raises(ValueError, match="no kind of attention"):
            layer_kinds(LAYER_TYPES, 1, (0, 4))
        with pytest.raises(ValueError, match="unknown kind of layer"):
            AfmoeBlock(AfmoeConfig.tiny(), ("sparse", "linear")).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))

    def test_a_full_layer_carries_no_positions_a_window_layer_does(self):
        """With the mask out of the way (the last position sees every key
        inside its window) a full layer cannot tell the order of the
        earlier tokens; a window layer, which rotates q and k, can."""
        config = AfmoeConfig.tiny()
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
        swapped = x.at[:, [12, 20]].set(x[:, [20, 12]])
        out = {}
        for attention in ("full", "window"):
            block = AfmoeBlock(config, ("sparse", attention))
            params = block.init(jax.random.PRNGKey(1), x)["params"]
            out[attention] = float(jnp.abs(
                block.apply({"params": params}, x)[:, -1]
                - block.apply({"params": params}, swapped)[:, -1]).max())
        assert out["full"] < 1e-5 < out["window"]

    def test_layers_say_what_they_hold_and_the_bias_is_no_leaf(self):
        from horovod_tpu import metrics
        config = AfmoeConfig.tiny(experts_held=2, first_expert_held=6)
        model = Afmoe(config)
        ids = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        assert set(params["layer_0"]) == {
            "input_norm", "post_attn_norm", "pre_ffn_norm", "post_ffn_norm",
            "attention", "mlp"}
        assert set(params["layer_1"]) == {
            "input_norm", "post_attn_norm", "pre_ffn_norm", "post_ffn_norm",
            "attention", "moe", "shared"}
        # the router's kernel, the experts' two matrices: no bias among them
        assert set(params["layer_1"]["moe"]) == {"router", "w_gate_up",
                                                 "w_down"}
        assert params["layer_1"]["moe"]["w_gate_up"].shape == (2, 64, 64)
        assert params["layer_1"]["moe"]["router"]["kernel"].shape == (64, 16)
        assert params["layer_0"]["mlp"]["gate_up"]["shard"]["kernel"].shape \
            == (64, 384)
        assert set(params["layer_0"]["attention"]) == {
            "qkv", "gate", "out", "q_norm", "k_norm"}
        snap = metrics.snapshot()
        got = {s["labels"]["kind"]: s["value"] for s in
               snap["hvd_moe_experts"]["series"]}
        assert got == {"routed": 16, "held": 2, "per_token": 2}
        got = {s["labels"]["kind"]: s["value"] for s in
               snap["hvd_attn_layer"]["series"]}
        assert got["heads"] == 8 and got["kv_heads"] == 1 \
            and got["normed"] == got["gated"] == 1

    def test_the_embedding_is_scaled_by_the_root_of_the_width(self):
        from horovod_tpu.models.afmoe import AfmoeEmbed
        embed = AfmoeEmbed(AfmoeConfig.tiny())
        ids = jnp.arange(8)[None]
        params = embed.init(jax.random.PRNGKey(0), ids)["params"]
        np.testing.assert_allclose(
            embed.apply({"params": params}, ids)[0],
            8.0 * params["tok_emb"]["embedding"][:8], rtol=1e-6)

    def test_trains_through_make_train_step(self, hvd):
        """The normal path: broadcast_parameters -> DistributedOptimizer ->
        make_train_step on the CPU mesh, the batch split over every
        device; the loss falls."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, make_train_step,
                                          shard_batch)
        config = AfmoeConfig.tiny(experts_held=4, first_expert_held=4)
        model = Afmoe(config)
        rows = 2 * hvd.size()
        ids = np.random.default_rng(0).integers(0, 256, (rows, 40),
                                                dtype=np.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]

        def loss_fn(params, batch):
            logits = model.apply({"params": params}, batch["ids"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], batch["ids"][:, 1:]).mean()

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


# -- what the other cells trace is the parent's --------------------------------

def _model_digest(model, ids):
    from test_moe_dropless import _zeros, jaxpr_digest
    params = _zeros(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                   ids)["params"])
    return jaxpr_digest(jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids).sum()), params)


def _models():
    from horovod_tpu.models import (GPT, GPTConfig, NemotronH,
                                    NemotronHConfig)
    long, short = jnp.zeros((2, 512), jnp.int32), jnp.zeros((2, 64),
                                                            jnp.int32)
    return {
        # PR 36 meant to alter this one: the mixer's checkpoint now holds
        # the activation and the split beside the scan (1a0c0ff61873e94a
        # before)
        "nemotron_h": (NemotronH(NemotronHConfig.tiny(
            experts_held=2, first_expert_held=2)), long, "7c3025bd832587d8"),
        "gpt": (GPT(GPTConfig.tiny()), short, "d068edd8e7aab78d"),
        "gpt_flash": (GPT(GPTConfig.tiny(use_flash=True)), short,
                      "cb4c5ffd803c118e"),
    }


ATTENTION = {
    "mha_bias": (dict(num_heads=4, hidden_size=64), "57bc7d8138cedef7"),
    "gqa_window_rope_flash": (
        dict(num_heads=4, hidden_size=64, num_kv_heads=2, head_dim=32,
             window=16, rope_theta=1e4, use_flash=True, causal=True,
             use_bias=False), "ab687d941ac9d186"),
    "gqa_full_plain": (
        dict(num_heads=4, hidden_size=64, num_kv_heads=1, head_dim=16,
             causal=True, use_bias=False), "532b304b27c6853c"),
}


class TestTheOtherProgramsAreTheParents:
    """``TPSelfAttention`` with its default ``qk_norm_eps`` and ``gated``,
    ``NemotronH.tiny()`` holding a share and a tiny ``GPT`` trace, forward
    and backward, the jaxprs they traced at commit a475bcc (PR 34;
    ``NemotronH``'s since PR 36), to the digest (``SmallThinker.tiny()``'s is held by ``tests/test_smallthinker.py``,
    ``DroplessMoE``'s by ``tests/test_moe_dropless.py``). A change that
    means to alter one records a new digest and says so."""

    @pytest.mark.parametrize("name", ["nemotron_h", "gpt", "gpt_flash"])
    def test_model(self, name):
        model, ids, recorded = _models()[name]
        assert _model_digest(model, ids) == recorded

    @pytest.mark.parametrize("name", sorted(ATTENTION))
    def test_attention_with_default_arguments(self, name):
        from horovod_tpu.parallel.tp import TPSelfAttention
        from test_moe_dropless import _zeros, jaxpr_digest
        kw, recorded = ATTENTION[name]
        layer = TPSelfAttention(axis_name=None, **kw)
        x = jnp.zeros((2, 64, 64), jnp.float32)
        params = _zeros(jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                                       x)["params"])
        assert jaxpr_digest(jax.value_and_grad(
            lambda p, x: layer.apply({"params": p}, x).sum(), (0, 1)),
            params, x) == recorded

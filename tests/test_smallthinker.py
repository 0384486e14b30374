"""``models.smallthinker`` against the benchmark's plain float32 reference
(``benchmark/archs/smallthinker_moe_decoder.py``, which imports nothing of
``horovod_tpu``), at a small size in the published ratios: 8 experts, 2 a
token, 2 held, four layers in the published pattern (full, window, window,
window), grouped K/V, a window shorter than the sequence. Seeded random
weights made by the benchmark's own rule."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.harness import arch, program, reference, traffic, weights
from horovod_tpu.models.smallthinker import (SmallThinker,
                                             SmallThinkerBlock,
                                             SmallThinkerConfig, layer_kinds)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED, ROWS, LENGTH = 11, 2, 64


def _cfg(**over):
    """The benchmark's configuration cut to the test's size: every ratio
    kept, float32 so that the comparison sees the arithmetic and not the
    rounding."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b_ep4.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_ffn_hidden_size=32,
               moe_num_primary_experts=2, moe_num_active_primary_experts=2,
               sliding_window_size=16, num_hidden_layers=4, vocab_size=250)
    cfg["published"] = dict(cfg["published"], moe_num_primary_experts=8)
    cfg["assumed"] = dict(cfg["assumed"], vocab_rows=256,
                          compute_dtype="float32")
    cfg["inputs"] = {"ids": {"per": "token", "high": 250}}
    cfg["program"] = dict(cfg["program"], use_flash=True)
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
        from benchmark.harness import check
        assert weights.flatten(check.plain(program.model_shapes(
            model, batch))) == weights.flatten(shapes)

    def test_loss_and_every_gradient(self):
        """float32 on both sides, the program through the flash kernels
        (interpreter) with the window, the sorted dispatch and the grouped
        products, the reference through whole masked squares and a loop
        over the experts. The loss to 1e-5 relative; every leaf's gradient
        to 2e-4 of that leaf's largest entry (sums in another order: the
        online softmax against the whole row). A window ignored, an expert left out or a
        missed RoPE each move some leaf by fifty times that
        (test_a_planted_fault_is_seen)."""
        cfg = _cfg()
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

    @pytest.mark.parametrize("fault", sorted(
        arch.load("smallthinker_moe_decoder").FAULTS))
    def test_a_planted_fault_is_seen(self, fault):
        """The reference with one fault of this architecture's own moves
        the loss or some leaf's gradient by over a hundredth of its largest
        entry, fifty times the tolerance above (RoPE left out, the least of
        them at this size, moves one by 3 %)."""
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        sound = reference.Reference(cfg, "float32").loss_and_grad(
            params, batch)
        faulty = reference.Reference(
            dict(cfg, planted_fault=fault), "float32").loss_and_grad(
                params, batch)
        gaps = [abs(float(faulty[0]) / float(sound[0]) - 1)]
        for (_, a), (_, b) in zip(weights.flatten(faulty[1]),
                                  weights.flatten(sound[1])):
            gaps.append(float(jnp.abs(a - b).max() / jnp.abs(b).max()))
        assert max(gaps) > 0.01, (fault, max(gaps))

    def test_shares_add_up_to_the_uncut_layer(self):
        """A ``window`` block of the program for each of the four shares
        (2 of 8 experts) on one input: the shares' outputs, with what every
        share computes alike (the input plus attention) counted once, add
        up to the reference's block when it holds all 8 experts."""
        cut = _cfg()
        whole = _cfg(moe_num_primary_experts=8)
        net = arch.of(whole).Net(whole, reference.product("float32"))
        p = weights.make_params(reference.param_shapes(whole), SEED,
                                whole)["layer_1"]
        x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, LENGTH, 64))
        want = net.block("window", p, x)
        alike = x + net.attention(
            "window", p["attention"],
            arch.of(whole).rms(x, p["ln_attn"], whole["rms_norm_eps"]))
        model, _ = program.load_model_builder(cut["model"])(cut)
        total = 0.0
        for first in range(0, 8, 2):
            config = SmallThinkerConfig(**{
                **model.config.__dict__, "first_expert_held": first})
            mine = copy.deepcopy(p)
            for name in ("w_gate_up", "w_down"):
                mine["moe"][name] = p["moe"][name][first:first + 2]
            total = total + SmallThinkerBlock(config, "window").apply(
                {"params": mine}, x) - alike
        np.testing.assert_allclose(total + alike, want, atol=2e-5 * float(
            jnp.abs(want).max()))


class TestModel:
    def test_kinds_follow_the_published_layouts(self):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "smallthinker_21b_a3b_ep4.json")) as f:
            cfg = json.load(f)
        kinds = layer_kinds(cfg["rope_layout"], cfg["sliding_window_layout"])
        assert len(kinds) == 52
        assert kinds == ("full", "window", "window", "window") * 13
        assert kinds == SmallThinkerConfig().kinds
        assert layer_kinds(cfg["rope_layout"], cfg["sliding_window_layout"],
                           4) == kinds[:4]
        with pytest.raises(ValueError, match="differ"):
            layer_kinds([0, 1], [0, 0])
        with pytest.raises(ValueError, match="not 3"):
            layer_kinds([0, 1], [0, 1], 3)

    def test_window_layers_alone_carry_positions(self):
        """A ``full`` block has no positional encoding: with the mask out
        of the way (the last position sees every key) it cannot tell the
        order of the earlier tokens; a ``window`` block, which rotates q
        and k, can."""
        config = SmallThinkerConfig.tiny(sliding_window=64)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
        swapped = x.at[:, [3, 9]].set(x[:, [9, 3]])
        out = {}
        for kind in ("full", "window"):
            block = SmallThinkerBlock(config, kind)
            params = block.init(jax.random.PRNGKey(1), x)["params"]
            out[kind] = float(jnp.abs(
                block.apply({"params": params}, x)[:, -1]
                - block.apply({"params": params}, swapped)[:, -1]).max())
        assert out["full"] < 1e-5 < out["window"]

    def test_trains_through_make_train_step(self, hvd):
        """The normal path: broadcast_parameters -> DistributedOptimizer ->
        make_train_step on the CPU mesh, the batch split over every
        device; the loss falls."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, make_train_step,
                                          shard_batch)
        config = SmallThinkerConfig.tiny(experts_held=4, first_expert_held=4)
        model = SmallThinker(config)
        rows = 2 * hvd.size()
        ids = np.random.default_rng(0).integers(0, 256, (rows, 32),
                                                dtype=np.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
        assert params["layer_2"]["moe"]["w_down"].shape == (4, 32, 64)

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


@pytest.mark.parametrize("product, recorded", [
    ("the_kernels", "3c3334890888e037"), ("ragged_dot", "ed1e32c8740b7c33")])
def test_the_traced_program_is_the_parents(product, recorded, monkeypatch):
    """``SmallThinker.tiny()`` holding a share, at a length at which its
    expert layers have their branch (1024 tokens), traces forward and
    backward the jaxpr recorded here. With ``lax.ragged_dot`` for the
    experts' grouped products it is the jaxpr of commit dbf7cc0 (before
    ``DroplessMoE`` took a weighting and an expert form) still; PR 34 gave
    the products to ``ops/pallas/grouped_matmul.py``'s kernels, meant to,
    and recorded the digest with them."""
    from horovod_tpu.parallel import moe
    from test_moe_dropless import jaxpr_digest
    jitted = (moe._forward_where_they_fit, moe._backward_where_they_fit)
    if product == "ragged_dot":
        monkeypatch.setattr(moe, "_grouped_dot", jax.lax.ragged_dot)
        for f in jitted:
            f.clear_cache()
    model = SmallThinker(SmallThinkerConfig.tiny(experts_held=2,
                                                 first_expert_held=2))
    ids = jnp.zeros((2, 512), jnp.int32)
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"])
    try:
        assert jaxpr_digest(jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids).sum()), params) \
            == recorded
    finally:
        if product == "ragged_dot":
            for f in jitted:
                f.clear_cache()

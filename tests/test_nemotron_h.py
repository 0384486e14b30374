"""``models.nemotron_h`` against the benchmark's plain float32 reference
(``benchmark/archs/nemotron_h_hybrid.py``, which imports nothing of
``horovod_tpu`` and runs the recurrence one token at a time), at a small
size in the published ratios: the first seven letters of the pattern
(MEMEM*E, the benchmark's cut), 16 experts, 2 a token, 2 held beside a
shared one, 8 state-space heads in 2 groups, grouped K/V, chunks shorter
than the sequence. Seeded random weights made by the benchmark's own
rule."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.harness import (arch, check, program, reference, traffic,
                               weights)
from horovod_tpu.models.nemotron_h import (KINDS, PATTERN, NemotronH,
                                           NemotronHBlock, NemotronHConfig,
                                           layer_kinds)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "nemotron_twotower_30b_a3b_ep16"
SEED, ROWS, LENGTH = 11, 2, 64


def _cfg(**over):
    """The benchmark's configuration cut to the test's size (the
    rehearsal's sizes): every ratio kept, float32 so that the comparison
    sees the arithmetic and not the rounding."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    for key, tiny in arch.load("nemotron_h_hybrid").REHEARSE.items():
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

    def test_fresh_leaves_follow_the_configs_keys(self):
        cfg = _cfg()
        _, params, _ = _setup(cfg)
        mixer = params["layer_0"]["mixer"]
        dt = jax.nn.softplus(mixer["dt_bias"])
        assert cfg["time_step_min"] * 0.999 <= float(dt.min()) \
            and float(dt.max()) <= cfg["time_step_max"] * 1.001
        assert 1.0 <= float(jnp.exp(mixer["A_log"]).min()) \
            and float(jnp.exp(mixer["A_log"]).max()) <= 16.0
        assert bool(jnp.all(mixer["D"] == 1)) \
            and bool(jnp.all(mixer["conv"]["bias"] == 0)) \
            and bool(jnp.all(mixer["gate_norm"]["scale"] == 1))
        kernel = mixer["conv"]["kernel"]
        assert 0.2 < float(jnp.abs(kernel).max()) <= 0.5
        assert float(jnp.std(mixer["in_proj"]["kernel"])) \
            == pytest.approx(0.02, rel=0.05)
        # residual-stream projections rescaled, the embedding as the
        # configuration's ``assumed`` says
        assert float(jnp.std(mixer["out_proj"]["kernel"])) \
            == pytest.approx(0.02 / 52 ** 0.5, rel=0.05)
        assert float(jnp.std(params["embed"]["tok_emb"]["embedding"])) \
            == pytest.approx(cfg["assumed"]["embedding_std"], rel=0.05)

    def test_model_init_draws_the_step_from_the_same_keys(self):
        """``model.init`` (what a user of the module gets) and
        ``fresh_leaf`` (what the benchmark times) read the mixer's fresh
        step from one statement, the configuration's ``time_step_*``; the
        range of ``-A`` has no key and is [1, 16] in both."""
        cfg = _cfg(time_step_min=0.01, time_step_max=0.05,
                   time_step_floor=0.02)
        _, params, batch = _setup(cfg)
        model, _ = program.load_model_builder(cfg["model"])(cfg)
        own = check.plain(model.init(jax.random.PRNGKey(3),
                                     batch["ids"])["params"])
        for mixer in (own["layer_0"]["mixer"], params["layer_0"]["mixer"]):
            dt = jax.nn.softplus(mixer["dt_bias"])
            assert 0.02 * 0.999 <= float(dt.min()) \
                and float(dt.max()) <= 0.05 * 1.001
            a = jnp.exp(mixer["A_log"])
            assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0

    def test_loss_and_every_gradient(self):
        """float32 on both sides, the program through the chunked scan
        (four chunks), the sorted dispatch with sigmoid weights and
        ``relu^2`` experts, and the flash kernels (interpreter); the
        reference through the recurrence one token at a time, a loop over
        the experts and whole masked squares. The loss to 1e-5 relative;
        every leaf's gradient to 2e-4 of that leaf's largest entry (sums in
        another order). Each planted fault moves some leaf by fifty times
        that (test_a_planted_fault_is_seen)."""
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
        arch.load("nemotron_h_hybrid").FAULTS))
    def test_a_planted_fault_is_seen(self, fault):
        """The reference with one fault of this architecture's own moves
        the loss or some leaf's gradient by over a hundredth of its largest
        entry, fifty times the tolerance above."""
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

    def test_an_unknown_fault_or_letter_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="unknown planted fault"):
            reference.Reference(dict(cfg, planted_fault="no_such"),
                                "float32")
        with pytest.raises(ValueError, match="unknown kind of layer 'X'"):
            reference.param_shapes(dict(
                cfg, hybrid_override_pattern="MXM" + PATTERN[3:]))

    def test_shares_add_up_to_the_uncut_layer(self):
        """An expert block of the program for each of the 16 shares (one
        of 16 experts each) on one input: the shares' routed parts, with
        what every share computes alike (the input plus the shared expert)
        counted once, add up to the reference's block when it holds all 16
        experts."""
        cut = _cfg(n_routed_experts=1)
        whole = _cfg(n_routed_experts=16)
        net = arch.of(whole).Net(whole, reference.product("float32"))
        p = weights.make_params(reference.param_shapes(whole), SEED,
                                whole)["layer_1"]
        x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, LENGTH, 64))
        want = net.block("moe", p, x)
        u = arch.of(whole).rms(x, p["norm"], whole["norm_eps"])
        alike = x + net._shared(p["shared"], u.reshape(-1, 64)).reshape(
            x.shape)
        model, _ = program.load_model_builder(cut["model"])(cut)
        total = 0.0
        for first in range(16):
            config = NemotronHConfig(**{
                **model.config.__dict__, "first_expert_held": first})
            mine = copy.deepcopy(p)
            for name in ("w_up", "w_down"):
                mine["moe"][name] = p["moe"][name][first:first + 1]
            total = total + NemotronHBlock(config, "E").apply(
                {"params": mine}, x) - alike
        # The routed part is small beside the input (the down products
        # start rescaled), so it is compared by itself: a thousandth of
        # its largest entry, ten times what float32 leaves of a difference
        # of values a thousand times larger.
        routed = want - alike
        assert float(jnp.abs(routed).max()) > 0
        np.testing.assert_allclose(total, routed, atol=1e-3 * float(
            jnp.abs(routed).max()))


class TestModel:
    def test_kinds_follow_the_published_pattern(self):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{CONFIG}.json")) as f:
            cfg = json.load(f)
        assert cfg["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
        kinds = layer_kinds(PATTERN)
        assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) \
            == (23, 23, 6)
        assert NemotronHConfig().kinds == kinds and set(kinds) == set(KINDS)
        assert "".join(layer_kinds(PATTERN, 9)) == "MEMEM*EME" \
            == "".join(NemotronHConfig.tiny().kinds)
        with pytest.raises(ValueError, match="unknown kind of layer '-'"):
            layer_kinds("ME-M")
        with pytest.raises(ValueError, match="not 53"):
            layer_kinds(PATTERN, 53)
        with pytest.raises(ValueError, match="unknown kind of layer 'Q'"):
            NemotronHBlock(NemotronHConfig.tiny(), "Q").init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))

    def test_attention_carries_no_positions_the_mixer_does(self):
        """An attention block has no positional encoding: with the mask out
        of the way (the last position sees every key) it cannot tell the
        order of the earlier tokens; a Mamba-2 block, whose state decays,
        can."""
        config = NemotronHConfig.tiny()
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
        swapped = x.at[:, [3, 9]].set(x[:, [9, 3]])
        out = {}
        for kind in ("*", "M"):
            block = NemotronHBlock(config, kind)
            params = block.init(jax.random.PRNGKey(1), x)["params"]
            out[kind] = float(jnp.abs(
                block.apply({"params": params}, x)[:, -1]
                - block.apply({"params": params}, swapped)[:, -1]).max())
        assert out["*"] < 1e-5 < out["M"]

    def test_expert_layers_say_what_they_hold(self):
        from horovod_tpu import metrics
        config = NemotronHConfig.tiny(experts_held=2, first_expert_held=6)
        model = NemotronH(config)
        ids = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        assert set(params["layer_1"]) == {"norm", "moe", "shared"}
        assert params["layer_1"]["moe"]["w_up"].shape == (2, 64, 32)
        assert params["layer_1"]["moe"]["router"]["kernel"].shape == (64, 16)
        assert params["layer_1"]["shared"]["up"]["kernel"].shape == (64, 64)
        got = {s["labels"]["kind"]: s["value"] for s in
               metrics.snapshot()["hvd_moe_experts"]["series"]}
        assert got == {"routed": 16, "held": 2, "per_token": 2}

    def test_trains_through_make_train_step(self, hvd):
        """The normal path: broadcast_parameters -> DistributedOptimizer ->
        make_train_step on the CPU mesh, the batch split over every
        device; the loss falls."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, make_train_step,
                                          shard_batch)
        config = NemotronHConfig.tiny(experts_held=4, first_expert_held=4)
        model = NemotronH(config)
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

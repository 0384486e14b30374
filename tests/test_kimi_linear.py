"""``parallel.kda``, ``ops/pallas/kda.py`` and ``models.kimi_linear``
against the recurrence written one token at a time and against the
benchmark's plain float32 reference (``benchmark/archs/
kimi_linear_decoder.py``, which imports nothing of ``horovod_tpu``), at
small sizes in the published ratios: three KDA layers and one latent
attention layer behind a dense one, 16 experts, 2 a token, 2 held beside a
shared one. The kernels run in the Pallas interpreter here; what Mosaic
refuses shows in ``tests/test_pallas_tpu_compile.py``."""

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
from horovod_tpu.models.kimi_linear import (KimiLinear, KimiLinearBlock,
                                            KimiLinearConfig)
from horovod_tpu.ops.pallas import kda as kernels
from horovod_tpu.parallel import kda
from horovod_tpu.parallel.mla import TPLatentAttention
from horovod_tpu.parallel.ssm import CausalConv1d

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, ARCH = "kimi_linear_48b_a3b_ep32", "kimi_linear_decoder"
SEED, ROWS, LENGTH = 11, 2, 64
NAMES = ("q", "k", "v", "g", "beta")


def token_by_token(q, k, v, g, beta):
    """The recurrence as ``parallel/kda.py`` writes it: S' = Diag(exp g_t)
    S, u = beta_t (v_t - S'^T k_t), S_t = S' + k_t u^T, o_t = S_t^T q_t,
    one position at a time, float32 at ``highest``."""
    hi = jax.lax.Precision.HIGHEST

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=hi))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi)
    b, _, heads, d = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, d, v.shape[-1])),
                        tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                              for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(rng, length, heads=2, d=16, extreme=False):
    """Normed q and k as the mixer hands them over, decays drawn as the
    mixer's gate draws them: ``A_log`` in log [1, 16] and softplus inputs
    near -3, or every head at ``A_log = log 16`` with softplus inputs near
    +20 (decays of e^-320 a step)."""
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = kda.l2_normed(normal(ROWS, length, heads, d)) * d ** -0.5
    k = kda.l2_normed(normal(ROWS, length, heads, d))
    a = jnp.full((heads, 1), jnp.log(16.0)) if extreme else jnp.log(
        jnp.asarray(rng.uniform(1, 16, (heads, 1)), jnp.float32))
    g = -jnp.exp(a) * jax.nn.softplus(
        normal(ROWS, length, heads, d) + (20.0 if extreme else -3.0))
    beta = jax.nn.sigmoid(normal(ROWS, length, heads))
    return q, k, normal(ROWS, length, heads, d), g, beta


def _close(got, want, rel, what=""):
    """Every entry within ``rel`` of the largest entry of ``want``, and
    every entry finite."""
    got = np.asarray(got)
    assert np.isfinite(got).all(), what
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, np.asarray(want),
                               atol=rel * max(scale, 1e-30), rtol=0,
                               err_msg=what)


def _against_the_recurrence(fn, args, rel):
    """``fn``'s output and the gradients of all five inputs against
    :func:`token_by_token`'s."""
    want = token_by_token(*args)
    dy = jnp.asarray(np.random.default_rng(5).standard_normal(want.shape),
                     jnp.float32)
    got, pull = jax.vjp(fn, *args)
    _close(got, want, rel, "o")
    wants = jax.vjp(token_by_token, *args)[1](dy)
    for name, g, w in zip(NAMES, pull(dy), wants):
        _close(g, w, rel, name)


CASES = {"last_chunk_not_whole": (80, 32, False),
         "shorter_than_a_chunk": (20, 32, False),
         "gates_at_their_extreme": (64, 32, True)}


class TestDeltaRule:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_chunked_form_against_the_recurrence(self, case):
        """:func:`parallel.kda.chunked_kda` (chunks of 32 in sub-chunks of
        16, padded where the length is no whole number of them), output
        and gradients to 2e-5 of each one's largest entry, finite at the
        extreme gates."""
        length, chunk, extreme = CASES[case]
        args = _inputs(np.random.default_rng(0), length, extreme=extreme)
        _against_the_recurrence(
            lambda *a: kda.chunked_kda(*a, chunk), args, 2e-5)

    @pytest.mark.parametrize("extreme", [False, True],
                             ids=["usual_gates", "gates_at_their_extreme"])
    def test_kernels_against_the_recurrence(self, extreme):
        """The forward sweep, the states sweep and the reverse sweep
        (interpreter) on heads of 128 in chunks of 64: two chunks a
        sequence, output and gradients to 1e-4."""
        args = _inputs(np.random.default_rng(1), 128, d=128,
                       extreme=extreme)
        assert kernels.fits(128, 128)
        _against_the_recurrence(lambda *a: kernels.kda(*a, 64), args, 1e-4)

    def test_the_path_is_picked_from_shapes(self):
        """Kernels at the cell's call (2 x 8192, 32 heads of 128, bfloat16)
        in chunks of 128; the chunked form off their grid: a length that
        is no whole number of 128, heads narrower than a lane tile."""
        assert kda.kda_path((2, 8192, 32, 128), 2) == (1, kernels.CHUNK)
        for shape in ((2, 8256, 32, 128), (2, 8200, 32, 128),
                      (2, 8192, 32, 64)):
            assert kda.kda_path(shape, 2) == (0, kda.JNP_CHUNK)

    def test_a_decay_of_one_and_no_write_keep_the_state(self):
        """Positions of ``g`` 0 and ``beta`` 0, the chunked form's padding,
        leave the state as it is: what follows them reads it unchanged."""
        q, k, v, g, beta = _inputs(np.random.default_rng(2), 32)
        held = [t.at[:, 16:24].set(0.0) for t in (g, beta)]
        o = token_by_token(q, k, v, *held)
        # the same sequence without the eight idle positions
        keep = jnp.r_[0:16, 24:32]
        cut = token_by_token(*(t[:, keep] for t in (q, k, v, *held)))
        _close(o[:, 24:], cut[:, 16:], 1e-6)


def _cfg(**over):
    """The benchmark's configuration cut to the test's size (the
    rehearsal's sizes), float32 so that the comparison sees the arithmetic
    and not the rounding."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    for key, tiny in arch.load(ARCH).REHEARSE.items():
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


def _layer_against_the_reference(layer, fn, p, rel):
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, LENGTH, 64))
    dy = jax.random.normal(jax.random.PRNGKey(4), (ROWS, LENGTH, 64))

    def pulled(f):
        y, pull = jax.vjp(f, p, x)
        return y, pull(dy)
    got = pulled(lambda p, x: layer.apply({"params": p}, x))
    want = pulled(fn)
    _close(got[0], want[0], rel, "output")
    _close(got[1][1], want[1][1], rel, "dx")
    for path, leaf in weights.flatten(want[1][0]):
        _close(dict(weights.flatten(got[1][0]))[path], leaf, rel,
               "/".join(path))


class TestMixers:
    def test_kda_mixer_against_the_reference(self):
        """:class:`parallel.kda.KDAMixer` (the chunked form, one chunk)
        against the reference's ``kda``, token by token, on the same
        float32 weights: output and every gradient to 1e-4."""
        cfg = _cfg()
        s = arch.of(cfg).sizes(cfg)
        net = arch.of(cfg).Net(cfg, reference.product("float32"))
        layer = kda.KDAMixer(s["hidden"], s["k_heads"], s["k_dim"],
                             s["rank"], axis_name=None)
        p = weights.make_params(reference.param_shapes(cfg), SEED,
                                cfg)["layer_1"]["kda"]
        _layer_against_the_reference(layer, net.kda, p, 1e-4)

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_latent_attention_without_query_latent_or_positions(
            self, use_flash):
        """``TPLatentAttention(q_lora_rank=None, rope=False)`` against the
        reference's ``attention``: one query product named ``q``, no query
        norm, nothing rotated; output and every gradient to 2e-5."""
        cfg = _cfg()
        s = arch.of(cfg).sizes(cfg)
        net = arch.of(cfg).Net(cfg, reference.product("float32"))
        layer = TPLatentAttention(
            s["heads"], s["hidden"], None, s["kv_rank"], s["nope"],
            s["rope"], s["v"], 0.0, rms_eps=cfg["rms_norm_eps"],
            axis_name=None, use_flash=use_flash, rope=False)
        p = weights.make_params(reference.param_shapes(cfg), SEED,
                                cfg)["layer_3"]["attention"]
        assert set(p) == {"q", "kv_a", "kv_a_norm", "kv_b", "out"}
        _layer_against_the_reference(layer, net.attention, p, 2e-5)

    def test_the_convolution_without_its_bias(self):
        """``CausalConv1d(use_bias=False)`` holds no bias and is the taps'
        sum alone; the default keeps its bias."""
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
        conv = CausalConv1d(4, use_bias=False)
        params = conv.init(jax.random.PRNGKey(1), x)["params"]
        assert set(params) == {"kernel"}
        w = params["kernel"]
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        want = sum(padded[:, t:t + 9] * w[t] for t in range(4))
        _close(conv.apply({"params": params}, x), want, 1e-6)
        assert set(CausalConv1d(4).init(jax.random.PRNGKey(1),
                                        x)["params"]) == {"kernel", "bias"}

    def test_the_gauges_hold_the_layer_and_its_path(self):
        """A trace of the mixer sets ``hvd_kda_layer``, the chunk states'
        bytes (one float32 (d, d) state a sequence, chunk and head) and the
        path: the chunked form for heads of 16."""
        from horovod_tpu import metrics
        kda.KDAMixer(64, 4, 16, 16, axis_name=None).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 40, 64)))
        snap = metrics.snapshot()

        def by(name, label):
            return {s["labels"][label]: s["value"]
                    for s in snap[name]["series"]}
        assert by("hvd_kda_layer", "kind") == {
            "kernels": 0, "heads": 4, "head_dim": 16,
            "chunk": kda.JNP_CHUNK, "chunks": 1}
        assert by("hvd_kda_chunk_state_bytes", "axis_size")["1"] \
            == 4 * 2 * 1 * 4 * 16 * 16


class TestAgainstTheReference:
    def test_names_and_shapes_are_the_references(self):
        cfg = _cfg()
        shapes, _, batch = _setup(cfg)
        model, _ = program.load_model_builder(cfg["model"])(cfg)
        assert weights.flatten(check.plain(program.model_shapes(
            model, batch))) == weights.flatten(shapes)
        assert [arch.of(cfg).kind_of_layer(cfg, i) for i in range(5)] == [
            ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
            ("mla", "sparse"), ("kda", "sparse")]

    def test_loss_gradients_and_one_adamw_step(self):
        """float32 on both sides, the program through the chunked delta
        rule and the flash kernels (interpreter), the reference token by
        token: the loss to 1e-5 relative, every leaf's gradient to 2e-4 of
        its largest entry, and each leaf's move in one AdamW step (optax on
        the program's gradient, the reference's own on its) to 1e-2 of its
        largest move: a first step moves an entry by about the learning
        rate whatever its gradient, so one whose gradient is near
        ``adam_eps`` moves by what rounding gives it."""
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        _, loss_fn = program.load_model_builder(cfg["model"])(cfg)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, {k: jnp.asarray(v) for k, v in batch.items()})
        ref = reference.Reference(cfg, "float32")
        want_loss, want = ref.loss_and_grad(params, batch)
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        got = dict(weights.flatten(grads))
        for path, leaf in weights.flatten(want):
            _close(got[path], leaf, 2e-4, "/".join(path))
        a = cfg["assumed"]
        opt = optax.adamw(a["learning_rate"], b1=a["adam_b1"],
                          b2=a["adam_b2"], eps=a["adam_eps"],
                          weight_decay=a["weight_decay"])
        updates, _ = opt.update(grads, opt.init(params), params)
        stepped = optax.apply_updates(params, updates)
        want_p, _ = ref.adam(params, want, ref.init_opt(params))
        got_p, before = (dict(weights.flatten(t)) for t in (stepped, params))
        for path, leaf in weights.flatten(want_p):
            _close(got_p[path] - before[path], leaf - before[path], 1e-2,
                   "/".join(path))

    @pytest.fixture(scope="class")
    def sound(self):
        cfg = _cfg()
        _, params, batch = _setup(cfg)
        return cfg, params, batch, reference.Reference(
            cfg, "float32").loss_and_grad(params, batch)

    @pytest.mark.parametrize("fault", sorted(arch.load(ARCH).FAULTS))
    def test_a_planted_fault_is_seen(self, sound, fault):
        """The reference with one fault of this architecture's own moves
        the loss or some leaf's gradient by over a hundredth of its largest
        entry (or makes it no number), fifty times the tolerance above."""
        cfg, params, batch, sound = sound
        faulty = reference.Reference(
            dict(cfg, planted_fault=fault), "float32").loss_and_grad(
                params, batch)
        gaps = [abs(float(faulty[0]) / float(sound[0]) - 1)]
        for (_, a), (_, b) in zip(weights.flatten(faulty[1]),
                                  weights.flatten(sound[1])):
            gaps.append(float(jnp.abs(a - b).max() / jnp.abs(b).max()))
        assert not np.isfinite(gaps).all() or max(gaps) > 0.01, \
            (fault, max(gaps))

    def test_an_unknown_fault_or_a_form_it_does_not_state_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="unknown planted fault"):
            reference.Reference(dict(cfg, planted_fault="no_such"),
                                "float32")
        for key, value in (("moe_router_activation_func", "softmax"),
                           ("q_lora_rank", 1536), ("mla_use_nope", False),
                           ("num_nextn_predict_layers", 1)):
            with pytest.raises(ValueError, match=key):
                reference.param_shapes(dict(cfg, **{key: value}))

    def test_shares_add_up_to_the_uncut_layer(self):
        """The routed part of the program's layer for each of the 16
        shares (one of 16 experts each) on one input, plus the shared
        expert counted once: the reference's ``f`` of the uncut layer
        (every expert held)."""
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
    def test_layers_by_kind(self):
        config = KimiLinearConfig.tiny(experts_held=2, first_expert_held=6)
        assert config.mixers == ("kda", "kda", "kda", "mla", "kda")
        assert config.ffns == ("dense",) + ("sparse",) * 4
        assert KimiLinearConfig().mixers.count("mla") == 7
        params = KimiLinear(config).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))["params"]
        assert set(params) == {"embed", "ln_f", "lm_head"} | {
            f"layer_{i}" for i in range(5)}
        assert set(params["layer_0"]) == {"input_norm", "post_attn_norm",
                                          "kda", "mlp"}
        assert "attention" in params["layer_3"]
        assert params["layer_1"]["moe"]["w_gate_up"].shape == (2, 64, 64)
        with pytest.raises(ValueError, match="unknown kind of layer"):
            KimiLinearBlock(config, "mamba", "dense").init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))

    def test_trains_through_make_train_step(self, hvd):
        """The normal path: broadcast_parameters -> DistributedOptimizer ->
        make_train_step on the CPU mesh, the batch split over every
        device; the loss falls."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, make_train_step,
                                          shard_batch)
        model = KimiLinear(KimiLinearConfig.tiny(
            experts_held=4, first_expert_held=4, use_flash=True))
        rows = 2 * hvd.size()
        ids = np.random.default_rng(0).integers(0, 256, (rows, 40),
                                                dtype=np.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
        xent = optax.softmax_cross_entropy_with_integer_labels

        def loss_fn(params, batch):
            logits = model.apply({"params": params}, batch["ids"])
            return xent(logits[:, :-1], batch["ids"][:, 1:]).mean()

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
        """The ``kda.*`` names are on ops forward and backward, the leaves
        inside ``kda.mixer``; every scope-like name in the lowered step is
        in ``trace/scopes.py``."""
        from horovod_tpu.trace import scopes
        model = KimiLinear(KimiLinearConfig.tiny(use_flash=True))
        ids = jnp.zeros((2, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        text = jax.jit(jax.grad(
            lambda p: model.apply({"params": p}, ids).sum())).lower(
                params).as_text(debug_info=True)
        paths = re.findall(r'loc\("([^"]*)"\(', text)
        wrapped = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")
        parts = [[wrapped.sub(r"\1", p) for p in path.split("/")]
                 for path in paths]
        leaves = ("kda.in_proj", "kda.conv", "kda.core", "kda.gate_norm",
                  "kda.out_proj")
        for name in leaves + ("attn.q_latent", "lm.head"):
            mine = [path for path, ps in zip(paths, parts) if name in ps]
            assert any("transpose(" not in p for p in mine), name
            assert any("transpose(" in p for p in mine), name
        for ps in parts:
            if set(leaves) & set(ps):
                assert "kda.mixer" in ps
        scope_like = re.compile(
            r"^(lm|attn|ssm|kda|moe|mlp|block|hvd)\.[a-z_]+$")
        met = {p for ps in parts for p in ps if scope_like.match(p)}
        assert set(leaves) | {"kda.mixer"} <= met
        assert met <= set(scopes.KINDS), met - set(scopes.KINDS)

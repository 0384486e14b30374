"""Attention's prologue as one pass (``ops/pallas/attn_prologue.py``, PR 38):
the heads' RMS norm, the rotation and the move into the flash kernels'
layout, forward and backward, on the CPU through the Pallas interpreter.

Held against today's path (the split, ``nn.RMSNorm``, ``apply_rope`` and
``flash_attention``'s own layout change) in values and gradients; the rule
that picks the pass (``parallel.tp.prologue_path``); the parameter trees it
must leave as they are; and the gauge ``hvd_attn_prologue_layers``.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import attn_prologue as ap
from horovod_tpu.parallel import tp

D = 128
# (eps, theta): what a layer carries
MODES = {"norm_and_rotation": (1e-5, 1e4), "norm": (1e-6, None),
         "rotation": (None, 1.5e6)}
HEADS = [(32, 4), (28, 4), (32, 2)]
# (length, row tile): two tiles at each length
LENGTHS = [(256, 128), (256, 256), (1024, 256), (1024, 512)]


def todays_operands(qkv, q_scale, k_scale, heads, kv_heads, eps, theta):
    """What ``TPSelfAttention`` did before the pass: split, norm, rotate,
    and ``flash_attention``'s ``to3``."""
    b, length, _ = qkv.shape
    q, k, v = jnp.split(qkv, [heads * D, (heads + kv_heads) * D], axis=-1)
    q, k, v = (t.reshape(b, length, -1, D) for t in (q, k, v))
    if eps is not None:
        norm = nn.RMSNorm(epsilon=eps, dtype=qkv.dtype)
        q = norm.apply({"params": {"scale": q_scale}}, q)
        k = norm.apply({"params": {"scale": k_scale}}, k)
    if theta is not None:
        positions = jnp.arange(length, dtype=jnp.int32)
        q, k = (tp.apply_rope(t, positions, theta) for t in (q, k))

    def to3(t):
        return jnp.moveaxis(t, 2, 1).reshape(b * t.shape[2], length, D)
    return to3(q), to3(k), to3(v)


def _inputs(length, heads, kv_heads, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkv = (2 * jax.random.normal(
        ks[0], (2, length, (heads + 2 * kv_heads) * D))).astype(dtype)
    scales = [1 + 0.2 * jax.random.normal(k, (D,)) for k in ks[1:3]]
    cotangents = tuple(jax.random.normal(k, (2 * n, length, D)).astype(dtype)
                       for k, n in zip(ks[3:], (heads, kv_heads, kv_heads)))
    return qkv, scales, cotangents


def _gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _as_mosaic_rounds(fn, *args):
    """``fn(*args)`` compiled so that every rounding written down is made,
    as Mosaic makes it on the chip (``tests/test_ssm.py``, PR 36): the
    interpreter's XLA otherwise drops a float32 -> bfloat16 -> float32 round
    trip where it fuses, here the one today's path makes between the norm
    and the rotation."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


class TestAgainstTodaysPath:
    """The pass's two kernels against the XLA they replace, the operands'
    gradients routed through the backward kernel as ``flash``'s backward
    hands them (dK and dV on the key-value heads)."""

    @pytest.mark.parametrize("length,tile", LENGTHS)
    @pytest.mark.parametrize("heads,kv_heads", HEADS)
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_values_and_gradients(self, mode, heads, kv_heads, length, tile):
        """float32: the operands, and the gradients of the rows and both
        scales, to 1e-5 of each one's largest entry."""
        eps, theta = MODES[mode]
        qkv, (qs, ks), cts = _inputs(length, heads, kv_heads, jnp.float32)
        static = dict(heads=heads, kv_heads=kv_heads, eps=eps, theta=theta,
                      tile=tile)
        assert ap.row_tile(length, heads, kv_heads, D) in (256, 512, 128)
        got = ap.operands(qkv, qs, ks, **static)
        want, pull = jax.vjp(lambda *a: todays_operands(
            *a, heads, kv_heads, eps, theta), qkv, qs, ks)
        for name, a, b in zip("qkv", got, want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert _gap(a, b) < 1e-5, name
        g_got = ap.operands_grad(qkv, qs, ks, *cts, **static)
        g_want = pull(cts)
        assert g_got[0].shape == qkv.shape
        assert _gap(g_got[0], g_want[0]) < 1e-5, "rows"
        for name, a, b in zip(("q_scale", "k_scale"), g_got[1:], g_want[1:]):
            if eps is None:
                assert a is None, name
            else:
                assert a.shape == (D,) and _gap(a, b) < 1e-5, name

    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_bfloat16_rounds_once(self, mode):
        """bfloat16 rows, each rounding made as the chip makes it: the
        pass's operands lie within one rounding (2^-8) of the float32
        arithmetic on the same rows, and no further from it than today's
        path, which rounds once more between the norm and the rotation."""
        eps, theta = MODES[mode]
        heads, kv_heads = 32, 4
        qkv, (qs, ks), _ = _inputs(256, heads, kv_heads, jnp.bfloat16)
        exact = todays_operands(qkv.astype(jnp.float32), qs, ks, heads,
                                kv_heads, eps, theta)
        got = _as_mosaic_rounds(lambda *a: ap.operands(
            *a, heads=heads, kv_heads=kv_heads, eps=eps, theta=theta,
            tile=256), qkv, qs, ks)
        today = _as_mosaic_rounds(lambda *a: todays_operands(
            *a, heads, kv_heads, eps, theta), qkv, qs, ks)
        for name, g, t, e in zip("qkv", got, today, exact):
            assert g.dtype == jnp.bfloat16, name
            assert _gap(g, e) <= 2.0 ** -8, name
            assert _gap(g, e) <= _gap(t, e) + 1e-7, name


class TestThroughTheLayer:
    """``TPSelfAttention`` on the pass (path 1) against the same layer on
    today's path (path 0), flash kernels and all."""

    @pytest.mark.parametrize("gated", [True, False])
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_output_and_every_gradient(self, monkeypatch, mode, gated):
        eps, theta = MODES[mode]
        layer = tp.TPSelfAttention(
            4, 256, axis_name=None, causal=True, use_flash=True,
            num_kv_heads=2, head_dim=D, rope_theta=theta,
            window=128 if theta else None, use_bias=False, qk_norm_eps=eps,
            gated=gated)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 256))
        params = layer.init(jax.random.PRNGKey(0), x)

        def loss(p, x):
            return (layer.apply(p, x) ** 2).sum()
        got = jax.value_and_grad(loss, (0, 1))(params, x)
        monkeypatch.setattr(tp, "prologue_path", lambda *a, **k: (0, 0))
        want = jax.value_and_grad(loss, (0, 1))(params, x)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            assert _gap(a, b) < 1e-5


@pytest.fixture
def gauge():
    """Reads ``hvd_attn_prologue_layers`` as {path: layers}, from a clean
    slate."""
    from horovod_tpu import metrics
    from horovod_tpu.metrics import instruments
    instruments._attn_prologue_layers.clear()

    def read():
        family = metrics.snapshot().get("hvd_attn_prologue_layers", {})
        return {int(s["labels"]["path"]): s["value"]
                for s in family.get("series", ())}
    yield read
    instruments._attn_prologue_layers.clear()


class TestTheRule:
    """Path 1 only where the flash kernels take the call with nothing
    between, the layer norms or rotates its heads, the heads are whole
    lane tiles and the length needs no padding."""

    BASE = dict(causal=True, use_flash=True, num_kv_heads=2, head_dim=D,
                rope_theta=1e4, qk_norm_eps=1e-5, use_bias=False)
    # name: (layer changes, length, call keywords, path)
    CALLS = {
        "taken": ({}, 256, {}, 1),
        "rotation_alone": ({"qk_norm_eps": None}, 256, {}, 1),
        "heads_of_64": ({"head_dim": 64}, 256, {}, 0),
        "padded_length": ({}, 200, {}, 0),
        "a_mask": ({}, 256, {"mask": True}, 0),
        "decode": ({"decode": True, "cache_len": 8, "qk_norm_eps": None},
                   1, {}, 0),
        "an_sp_axis": ({"sp_axis": "sp", "qk_norm_eps": None}, 256, {}, 0),
        "neither_norm_nor_positions": ({"qk_norm_eps": None,
                                        "rope_theta": None}, 256, {}, 0),
        "no_flash": ({"use_flash": False}, 256, {}, 0),
    }

    @pytest.mark.parametrize("call", sorted(CALLS))
    def test_the_path_of_a_call(self, gauge, call):
        changes, length, kw, path = self.CALLS[call]
        layer = tp.TPSelfAttention(4, 256, axis_name=None,
                                   **{**self.BASE, **changes})
        x = jnp.zeros((2, length, 256))
        if kw.get("mask"):
            kw = {"mask": jnp.ones((2, length), bool)}
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x, **kw))
        assert gauge() == {path: 1, 1 - path: 0}


def _model(kind, **kw):
    if kind == "afmoe":
        from horovod_tpu.models.afmoe import Afmoe, AfmoeConfig
        return Afmoe(AfmoeConfig.tiny(use_flash=True, **kw))
    from horovod_tpu.models.smallthinker import (SmallThinker,
                                                 SmallThinkerConfig)
    return SmallThinker(SmallThinkerConfig.tiny(use_flash=True, **kw))


@pytest.mark.parametrize("kind", ["afmoe", "smallthinker"])
def test_the_parameter_tree_is_todays(monkeypatch, gauge, kind):
    """The models whose layers take the pass keep their parameters' names,
    shapes and dtypes (the benchmark's reference maps weights by them), and
    the norms' scales start at one, as ``nn.RMSNorm``'s do."""
    model = _model(kind, head_dim=D)
    ids = jnp.zeros((2, 128), jnp.int32)

    def tree():
        return jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
            model.init, jax.random.PRNGKey(0), ids))
    on_the_pass = tree()
    assert gauge()[1] > 0
    monkeypatch.setattr(tp, "prologue_path", lambda *a, **k: (0, 0))
    assert on_the_pass == tree()
    if kind == "afmoe":
        monkeypatch.undo()
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        for norm in ("q_norm", "k_norm"):
            scale = params["layer_0"]["attention"][norm]["scale"]
            assert scale.shape == (D,) and bool(jnp.all(scale == 1))


# cell: (path 1 layers, path 0 layers)
_CELLS = {"trinity_mini_ep16_8k_1chip": (5, 0),
          "smallthinker_ep4_8k_1chip": (3, 1),
          "nemotron_tt_ep16_8k_1chip": (0, 1),
          "gpt2m_1chip": (0, 24)}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_the_gauge_counts_each_cells_layers(gauge, cell):
    """One trace of a cell's loss and gradient at its real size (nothing
    runs) and the gauge says how many layers took each path: every layer
    of the Trinity cell on the pass, SmallThinker's three window layers (the
    full layer has neither norm nor positions), none of Nemotron's or GPT-2
    medium's. A second trace counts nothing twice."""
    import os
    from benchmark import run as bench
    from benchmark.harness import manifest, program, reference, traffic
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _, workload, cfg = bench.load_cell(manifest.load(root), cell, False)
    model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    batch = traffic.Batches(cfg, workload, 7).next()
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.float32),
        reference.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    for _ in range(2):
        jax.eval_shape(jax.grad(loss_fn), params, batch)
        on, off = _CELLS[cell]
        assert gauge() == {1: on, 0: off}

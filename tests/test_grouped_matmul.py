"""The grouped product of ``parallel.moe.DroplessMoE`` (``_grouped_dot``)
and the kernels behind it (``ops/pallas/grouped_matmul.py``), through the
Pallas interpreter on the CPU as the flash kernels' tests are: against a
plain loop of ``jnp.dot`` over the groups in float32, forward and both
gradients, at widths the TPU compiler would tile at 128 (what the kernels
were written for), at widths no tile divides, and at widths it tiles well
(which take the kernels too: they were ahead there as well). What mosaic
refuses shows in
``tests/test_pallas_tpu_compile.py``, times only on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import grouped_matmul as gmm
from horovod_tpu.parallel import moe


def _by_group(lhs, rhs, sizes):
    """The product one group at a time: float32, every row of every group
    under a mask; zeros behind the last group."""
    rows = jnp.arange(lhs.shape[0])[:, None]
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), 0
    for g, size in enumerate(sizes):
        mine = (rows >= start) & (rows < start + size)
        out = out + jnp.where(mine, jnp.dot(
            jnp.where(mine, lhs, 0), rhs[g], precision="highest"), 0)
        start += size
    return out


# (rows, K, N, sizes): 600 rows are two tiles of 256 and a partial one
_CALLS = {
    "k384_n192_128_divides_256_does_not": (600, 384, 192, (100, 200, 57, 243)),
    "k384_n320": (600, 384, 320, (256, 256, 88)),
    "n232_no_tile_divides": (600, 384, 232, (150, 150, 150, 150)),
    "k232_contracted_whole": (600, 232, 384, (300, 1, 255, 44)),
    "a_group_of_no_rows": (600, 384, 192, (100, 0, 300, 57)),
    "first_and_last_groups_empty": (700, 232, 384, (0, 300, 300, 0)),
    "groups_end_inside_tiles": (1024, 128, 320, (255, 2, 511, 129, 127)),
    "fewer_rows_than_a_tile": (24, 32, 16, (10, 14)),
    "one_group": (300, 192, 128, (260,)),
    "no_row_live": (300, 192, 128, (0, 0)),
    "widths_256_divides": (600, 256, 512, (100, 0, 300, 57)),
}


@pytest.fixture
def operands(rng, request):
    m, k, n, sizes = _CALLS[request.param]
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    return lhs, rhs, sizes, cot


@pytest.mark.parametrize("operands", sorted(_CALLS), indirect=True)
class TestGroupedDot:
    def test_forward_and_both_gradients(self, operands):
        """To float32 rounding (the two differ by the order of summation:
        1e-5 of the largest entry), with NaN in the rows behind the last
        group going in, forward and backward, and whatever the product
        leaves there coming out: no live row and no gradient of the
        weights may read them."""
        lhs, rhs, sizes, cot = operands
        live = (jnp.arange(lhs.shape[0]) < sum(sizes))[:, None]
        spoiled = jnp.where(live, lhs, jnp.nan)

        def got(lhs, rhs):
            out = moe._grouped_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32))
            return jnp.sum(jnp.where(live, out * cot, 0)), out

        def want(lhs, rhs):
            out = _by_group(lhs, rhs, sizes)
            return jnp.sum(out * cot), out
        (_, out), (d_lhs, d_rhs) = jax.value_and_grad(
            got, (0, 1), has_aux=True)(spoiled, rhs)
        (_, ref), (r_lhs, r_rhs) = jax.value_and_grad(
            want, (0, 1), has_aux=True)(lhs, rhs)
        for a, b in ((out, ref), (d_lhs, r_lhs)):
            np.testing.assert_allclose(
                jnp.where(live, a, 0), b,
                atol=1e-5 * max(1.0, float(jnp.abs(b).max())))
        assert bool(jnp.all(jnp.isfinite(d_rhs)))
        np.testing.assert_allclose(
            d_rhs, r_rhs, atol=1e-5 * max(1.0, float(jnp.abs(r_rhs).max())))

    def test_the_kernels_take_every_shape(self, operands):
        """One path: the repo's kernels, in the tiles ``product_tiles``
        reads off the shapes; the jaxpr shows them by name."""
        lhs, rhs, sizes, _ = operands
        text = str(jax.make_jaxpr(moe._grouped_dot)(
            lhs, rhs, jnp.asarray(sizes, jnp.int32)))
        path, (tm, _, tn) = moe.product_tiles(lhs.shape[0], *rhs.shape[1:], 4)
        assert path == 1 and "ragged_dot" not in text
        assert f"hvd_gmm_{tm}x{lhs.shape[1]}x{tn}" in text

    def test_rows_behind_the_last_group_are_left_unwritten(self, operands):
        """The interpreter hands a kernel its output as NaN, so on the CPU
        the rows no step visits read as on the chip: whatever was
        there."""
        lhs, rhs, sizes, _ = operands
        out = moe._grouped_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32))
        assert bool(jnp.all(jnp.isnan(out[sum(sizes):])))
        assert bool(jnp.all(jnp.isfinite(out[:sum(sizes)])))


# (rows, K, N, bytes an element) -> (path, (tm, tk, tn)): rows a step, the
# block of the left operand's gradient, the block of the output
_TILES = {
    # nemotron_tt_ep16_8k_1chip: w_up, w_down; the overflow branch's rows
    (9216, 2688, 1856, 2): (1, (256, 896, 1856)),
    (9216, 1856, 2688, 2): (1, (256, 1856, 896)),
    (98304, 2688, 1856, 2): (1, (256, 896, 1856)),
    # smallthinker_ep4_8k_1chip: w_gate_up, w_down
    (36864, 2560, 1536, 2): (1, (256, 1280, 1536)),
    (36864, 768, 2560, 2): (1, (256, 768, 1280)),
    (98304, 768, 2560, 2): (1, (256, 768, 1280)),
    # ISSUE 34's probes: widths the compiler tiles at 128 in one dimension
    (9216, 2688, 2048, 2): (1, (256, 896, 2048)),
    (9216, 3072, 2048, 2): (1, (256, 1536, 2048)),
    # the tests' own widths; fewer rows than a tile are one tile
    (48, 32, 16, 4): (1, (48, 32, 16)),
    (1024, 32, 32, 4): (1, (256, 32, 32)),
    # no divisor from 384 up: an even split over a partial last block
    (4096, 2176, 4224, 2): (1, (256, 1152, 1408)),
    # float32 operands take narrower blocks in the same budget
    (4096, 4096, 4096, 4): (1, (256, 512, 512)),
    # a contraction whose slab fits at no width keeps lax.ragged_dot, whose
    # tile the compiler picks: the largest of 512, 256 that divides, or 128
    (4096, 65536, 1856, 2): (0, (512, 512, 128)),
    (4096, 2688, 65536, 2): (0, (512, 128, 512)),
}


@pytest.mark.parametrize("shape", sorted(_TILES))
def test_the_tile_picker(shape):
    assert moe.product_tiles(*shape) == _TILES[shape]
    path, (tm, tk, tn) = _TILES[shape]
    if path == 1:
        m, k, n, itemsize = shape
        assert gmm._vmem_bytes(tm, tk, tn, k, n, itemsize) <= gmm.VMEM_BUDGET
        assert gmm.VMEM_BUDGET < gmm.VMEM_LIMIT
        assert all(t % 128 == 0 or t == w for t, w in ((tk, k), (tn, n)))


def test_gauges_say_path_and_tiles():
    from horovod_tpu import metrics
    x = jax.ShapeDtypeStruct((2, 8192, 2688), jnp.bfloat16)
    layer = moe.DroplessMoE(128, 6, 2688, 1856, experts_held=8,
                            weighting="sigmoid", expert_form="relu2",
                            dtype=jnp.bfloat16)
    jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    snap = metrics.snapshot()
    path = {s["labels"]["product"]: s["value"]
            for s in snap["hvd_moe_product_path"]["series"]}
    assert path == {"in": 1, "down": 1}
    tiles = {(s["labels"]["product"], s["labels"]["dim"]): s["value"]
             for s in snap["hvd_moe_product_tiles"]["series"]}
    assert tiles == {("in", "m"): 256, ("in", "k"): 896, ("in", "n"): 1856,
                     ("down", "m"): 256, ("down", "k"): 1856,
                     ("down", "n"): 896}


def test_bfloat16_operands_float32_sums(rng):
    """bf16 in, bf16 out, as ``lax.ragged_dot`` gives them: against the
    float32 loop over the same rounded operands, half a bf16 ulp of the
    largest entry for the output's own rounding, forward and backward."""
    m, k, n, sizes = 600, 384, 320, (100, 200, 57, 243)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((4, k, n)), jnp.bfloat16)

    def got(lhs, rhs):
        out = moe._grouped_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32))
        return jnp.sum(out.astype(jnp.float32)), out

    def want(lhs, rhs):
        out = _by_group(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                        sizes)
        return jnp.sum(out), out
    (_, out), grads = jax.value_and_grad(got, (0, 1), has_aux=True)(lhs, rhs)
    (_, ref), refs = jax.value_and_grad(want, (0, 1), has_aux=True)(lhs, rhs)
    assert out.dtype == jnp.bfloat16
    for a, b in zip((out, *grads), (ref, *refs)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   atol=2 ** -8 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("form, first", [("gated_relu", "w_gate_up"),
                                         ("relu2", "w_up")])
def test_a_layer_at_widths_256_does_not_divide(rng, form, first):
    """``DroplessMoE`` holding a share, hidden 128 and experts of width
    160 (a fused gated pair is 320 wide: its halves split at 160, inside
    a lane tile): output and every gradient against the layer written
    densely, through the buffer's branch."""
    E, K, D, F, T, held = 8, 2, 128, 160, 1024, 2
    x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
    layer = moe.DroplessMoE(E, K, D, F, experts_held=held, first_expert=2,
                            expert_form=form)
    params = layer.init(jax.random.PRNGKey(3), x)["params"]

    def dense(p, x):
        xt = x.reshape(-1, D)
        logits = jnp.dot(xt, p["router"]["kernel"], precision="highest")
        top, chosen = jax.lax.top_k(logits, K)
        weights = jax.nn.softmax(top, -1)
        out = jnp.zeros_like(xt)
        for e in range(held):
            w_e = jnp.sum(jnp.where(chosen == 2 + e, weights, 0.0), -1)
            h = jnp.dot(xt, p[first][e], precision="highest")
            h = jax.nn.relu(h[:, :F]) * h[:, F:] if form == "gated_relu" \
                else jnp.square(jax.nn.relu(h))
            out = out + w_e[:, None] * jnp.dot(h, p["w_down"][e],
                                               precision="highest")
        return out.reshape(x.shape)
    w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    got = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
        w * layer.apply({"params": p}, x)), (0, 1)))(params, x)
    want = jax.value_and_grad(lambda p, x: jnp.sum(w * dense(p, x)),
                              (0, 1))(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()))

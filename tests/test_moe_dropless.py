"""``parallel.moe.DroplessMoE``: many-of-many routing with no capacity and no
drop, for a layer that holds a share of the experts. Small sizes in the
published ratios (8 experts, 2 a token, 2 held), seeded random weights,
against the layer written densely: every expert on every token under a
mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel.moe import DroplessMoE

T, D, F, E, K = 48, 32, 16, 8, 2


def _dense(params, x, r, experts=range(E), act=jax.nn.relu):
    """sum over the chosen experts among ``experts`` of w_e * expert_e(x),
    with ``params`` holding all E experts."""
    xt, rt = x.reshape(-1, D), r.reshape(-1, D)
    logits = jnp.dot(rt, params["router"]["kernel"], precision="highest")
    top, chosen = jax.lax.top_k(logits, K)
    weights = jax.nn.softmax(top, -1)
    out = jnp.zeros_like(xt)
    for e in experts:
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        gate_up = xt @ params["w_gate_up"][e]
        hidden = act(gate_up[:, :F]) * gate_up[:, F:]
        out = out + w_e[:, None] * (hidden @ params["w_down"][e])
    return out.reshape(x.shape)


def _share(params, first, held):
    return dict(params, w_gate_up=params["w_gate_up"][first:first + held],
                w_down=params["w_down"][first:first + held])


@pytest.fixture
def setup(rng):
    x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
    params = DroplessMoE(E, K, D, F).init(jax.random.PRNGKey(3), x, r)[
        "params"]
    return params, x, r


class TestDroplessMoE:
    def test_whole_layer_matches_the_dense_sum(self, setup):
        """Forward and every gradient (experts, router, the experts' input
        and the router's input). float32 on the CPU: the two differ by the
        order of summation alone, so 1e-5 relative to the largest entry."""
        params, x, r = setup
        layer = DroplessMoE(E, K, D, F)
        assert set(params) == {"router", "w_gate_up", "w_down"}
        assert params["w_gate_up"].shape == (E, D, 2 * F)
        got = layer.apply({"params": params}, x, r)
        want = _dense(params, x, r)
        np.testing.assert_allclose(got, want, atol=1e-5 * float(
            jnp.abs(want).max()))
        w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

        def loss(f):
            return lambda p, x, r: jnp.sum(w * f(p, x, r))
        g_got = jax.grad(loss(lambda p, x, r: layer.apply(
            {"params": p}, x, r)), (0, 1, 2))(params, x, r)
        g_want = jax.grad(loss(_dense), (0, 1, 2))(params, x, r)
        flat_got, tree = jax.tree.flatten(g_got)
        flat_want, tree_want = jax.tree.flatten(g_want)
        assert tree == tree_want
        for a, b in zip(flat_got, flat_want):
            assert float(jnp.abs(b).max()) > 0
            np.testing.assert_allclose(a, b, atol=1e-5 * float(
                jnp.abs(b).max()))

    def test_router_reads_its_own_input(self, setup):
        params, x, r = setup
        layer = DroplessMoE(E, K, D, F)
        same = layer.apply({"params": params}, x)
        np.testing.assert_allclose(
            same, layer.apply({"params": params}, x, x), atol=0)
        other = layer.apply({"params": params}, x, r)
        assert float(jnp.abs(other - same).max()) > 1e-3

    @pytest.mark.parametrize("held", [1, 2, 4])
    def test_shares_add_up_to_the_whole_layer(self, setup, held):
        """Each share routes over all E experts and returns its own
        experts' part; the parts of all E / held shares sum to the layer."""
        params, x, r = setup
        parts = []
        for first in range(0, E, held):
            share = DroplessMoE(E, K, D, F, experts_held=held,
                                first_expert=first)
            p = _share(params, first, held)
            assert share.init(jax.random.PRNGKey(0), x, r)["params"][
                "w_down"].shape == (held, F, D)
            part = share.apply({"params": p}, x, r)
            np.testing.assert_allclose(
                part, _dense(params, x, r, range(first, first + held)),
                atol=1e-5)
            parts.append(part)
        np.testing.assert_allclose(sum(parts), _dense(params, x, r),
                                   atol=1e-5)

    @pytest.mark.parametrize("first,held", [(0, E), (2, 2), (4, 2)])
    def test_no_token_is_dropped_under_total_imbalance(self, setup, first,
                                                       held):
        """A router that sends every token to experts 2 and 3: a layer
        that holds them computes all T x K rows (a capacity of 2 x K x T /
        E would have kept a quarter), one that holds neither returns
        zero."""
        params, x, r = setup
        kernel = np.zeros((D, E), np.float32)
        kernel[:, 2], kernel[:, 3] = 1.0, 0.9
        params = dict(params, router={"kernel": jnp.asarray(kernel)})
        r = jnp.abs(r) + 0.1            # every token: logit 2 > logit 3 > 0
        layer = DroplessMoE(E, K, D, F, experts_held=held,
                            first_expert=first)
        got = layer.apply({"params": _share(params, first, held)}, x, r)
        want = _dense(params, x, r, range(first, first + held))
        np.testing.assert_allclose(got, want, atol=1e-5)
        rows = np.abs(np.asarray(got)).reshape(T, D).max(-1)
        if first <= 2 < first + held:
            assert (rows > 0).all(), "a token got no expert"
        else:
            assert (rows == 0).all()

    def test_bfloat16_stays_close(self, setup):
        """bfloat16 rows and weights, float32 router and combine: 2 % of
        the largest entry (8 bits of mantissa through two products)."""
        params, x, r = setup
        got = DroplessMoE(E, K, D, F, dtype=jnp.bfloat16).apply(
            {"params": params}, x.astype(jnp.bfloat16), r)
        assert got.dtype == jnp.bfloat16
        want = _dense(params, x, r)
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   atol=0.02 * float(jnp.abs(want).max()))

    def test_gauges_say_what_was_traced(self, setup):
        from horovod_tpu import metrics
        params, x, r = setup
        jax.eval_shape(lambda p, x, r: DroplessMoE(
            E, K, D, F, experts_held=2, first_expert=2).apply(
                {"params": p}, x, r), _share(params, 2, 2), x, r)
        snap = metrics.snapshot()
        experts = {s["labels"]["kind"]: s["value"]
                   for s in snap["hvd_moe_experts"]["series"]}
        assert experts == {"routed": E, "held": 2, "per_token": K}
        rows = {s["labels"]["axis_size"]: s["value"] for s in
                snap["hvd_moe_buffer_rows_per_token"]["series"]}
        assert rows == {"1": float(K)}

    @pytest.mark.parametrize("kw", [
        dict(top_k=0), dict(top_k=E + 1), dict(experts_held=0),
        dict(experts_held=4, first_expert=6), dict(first_expert=-1)])
    def test_refuses_what_is_no_share(self, setup, kw):
        params, x, r = setup
        args = dict(num_experts=E, top_k=K, hidden_size=D,
                    intermediate_size=F)
        args.update(kw)
        with pytest.raises(ValueError):
            DroplessMoE(**args).init(jax.random.PRNGKey(0), x, r)

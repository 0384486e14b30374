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


# A share whose buffer is smaller than its pairs: 1024 tokens, 2 of 8
# experts a token, experts 2 and 3 held: 2048 pairs, 512 expected live,
# a buffer of 1.5 x 512 rounded up to the row tile = 1024 rows.
T2, FIRST, HELD = 1024, 2, 2
LEAVES = ["output", "router", "w_down", "w_gate_up", "x", "router_input"]


def _crowded(params, r):
    """Router and router input that send every token to experts 2 and 3:
    2048 live pairs, over the buffer's 1024."""
    kernel = np.zeros((D, E), np.float32)
    kernel[:, 2], kernel[:, 3] = 1.0, 0.9
    return (dict(params, router={"kernel": jnp.asarray(kernel)}),
            jnp.abs(r) + 0.1)


def _count_runs(monkeypatch):
    """{rows: times a branch of that many rows RAN} (both are traced), by
    a callback the test wraps round ``moe._on_rows``."""
    from horovod_tpu.parallel import moe
    runs, on_rows = {}, moe._on_rows

    def counted(rows, *args):
        jax.debug.callback(
            lambda: runs.__setitem__(rows, runs.get(rows, 0) + 1))
        return on_rows(rows, *args)
    monkeypatch.setattr(moe, "_on_rows", counted)
    return runs


@pytest.fixture
def unwritten_rows(monkeypatch):
    """The TPU's grouped product leaves the rows behind the last group
    unwritten, in the product and in the gradient it hands its left
    operand. Here both hold NaN, whatever the product's path leaves there
    on the CPU (the Pallas interpreter NaN, ``lax.ragged_dot`` zeros)."""
    from horovod_tpu.parallel import moe
    grouped_dot = moe._grouped_dot

    @jax.custom_vjp
    def spoil(x, n):
        return jnp.where(jnp.arange(x.shape[0])[:, None] < n, x, jnp.nan)
    spoil.defvjp(lambda x, n: (spoil(x, n), n),
                 lambda n, g: (spoil(g, n), None))

    def spoiled(lhs, rhs, sizes):
        n = jnp.sum(sizes)
        # forward the operand is read as it is (those rows are not
        # visited); backward its gradient's dead rows are spoiled
        lhs = jnp.where(jnp.arange(lhs.shape[0])[:, None] < n,
                        spoil(lhs, n), lhs)
        return spoil(grouped_dot(lhs, rhs, sizes), n)
    monkeypatch.setattr(moe, "_grouped_dot", spoiled)


@pytest.fixture
def share(rng):
    x = jnp.asarray(rng.standard_normal((2, T2 // 2, D)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((2, T2 // 2, D)), jnp.float32)
    layer = DroplessMoE(E, K, D, F, experts_held=HELD, first_expert=FIRST)
    params = layer.init(jax.random.PRNGKey(3), x, r)["params"]
    return layer, params, x, r


def _output_and_grads(layer, params, x, r):
    """{name: array} of ``LEAVES``, the gradients those of a fixed
    weighting of the output."""
    w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x, r):
        out = layer.apply({"params": p}, x, r)
        return jnp.sum(w * out), out
    (_, out), (g_p, g_x, g_r) = jax.jit(jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True))(params, x, r)
    return {"output": out, "router": g_p["router"]["kernel"],
            "w_down": g_p["w_down"], "w_gate_up": g_p["w_gate_up"],
            "x": g_x, "router_input": g_r}


class TestBufferOfTheRowsExpected:
    @pytest.fixture(autouse=True)
    def fresh_traces(self):
        """The two conditionals are jitted on their own, so that a model's
        layers share one trace; a test that patches what they call has to
        have them traced anew, and leave no patched trace behind."""
        from horovod_tpu.parallel import moe
        jitted = (moe._forward_where_they_fit, moe._backward_where_they_fit)
        for f in jitted:
            f.clear_cache()
        yield
        for f in jitted:
            f.clear_cache()

    def test_buffer_rows(self):
        from horovod_tpu.parallel.moe import ROW_TILE, SLACK, buffer_rows
        assert (SLACK, ROW_TILE) == (1.5, 512)
        assert buffer_rows(16384, 6, 16, 64) == 36864     # 2.25 a token
        assert buffer_rows(T2, K, HELD, E) == 1024
        assert buffer_rows(T2, K, E, E) == T2 * K         # every expert
        assert buffer_rows(T, K, 2, E) == T * K           # under one tile

    @pytest.mark.parametrize("leaf", LEAVES)
    @pytest.mark.parametrize("wrap", ["plain", "remat"])
    def test_the_buffer_equals_all_pairs(self, share, monkeypatch,
                                         unwritten_rows, leaf, wrap):
        """Output and every gradient through the 1024-row buffer against
        the same layer made to move all 2048 pairs (``SLACK`` so large
        that the buffer is every pair: the code a layer holding all
        experts runs), to float32 rounding, with NaN in the rows the
        grouped products do not write; ``remat`` as ``SmallThinkerBlock``
        calls it, inside ``jit``."""
        import flax.linen as nn
        from horovod_tpu.parallel import moe
        layer, params, x, r = share
        if wrap == "remat":
            layer = nn.remat(DroplessMoE)(E, K, D, F, experts_held=HELD,
                                          first_expert=FIRST)
        runs = _count_runs(monkeypatch)
        got = _output_and_grads(layer, params, x, r)[leaf]
        jax.effects_barrier()
        assert set(runs) == {1024}, "the live pairs fit: the buffer's branch"
        monkeypatch.setattr(moe, "SLACK", 1e9)
        want = _output_and_grads(layer, params, x, r)[leaf]
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(got, want, atol=2e-6 * float(
            jnp.abs(want).max()))

    @pytest.mark.parametrize("leaf", LEAVES)
    def test_overflow_runs_all_pairs_and_drops_nothing(self, share,
                                                       monkeypatch, leaf):
        """Every token routed to the two experts held, twice the buffer:
        the branch over all pairs runs (and the buffer's does not), and
        output and gradients are the dense sum's."""
        layer, params, x, r = share
        params, r = _crowded(params, r)
        runs = _count_runs(monkeypatch)
        got = _output_and_grads(layer, params, x, r)
        jax.effects_barrier()
        assert set(runs) == {T2 * K}
        whole = dict(params, **{
            name: jnp.zeros((E,) + params[name].shape[1:]).at[
                FIRST:FIRST + HELD].set(params[name])
            for name in ("w_gate_up", "w_down")})

        class Dense:
            def apply(self, variables, x, r):
                return _dense(variables["params"], x, r,
                              range(FIRST, FIRST + HELD))
        want = _output_and_grads(Dense(), whole, x, r)
        for name in ("w_gate_up", "w_down"):
            want[name] = want[name][FIRST:FIRST + HELD]
        rows = np.abs(np.asarray(got["output"])).reshape(T2, D).max(-1)
        assert (rows > 0).all(), "a token got no expert"
        assert float(jnp.abs(want[leaf]).max()) > 0
        # 5e-5: the router's gradient here is a difference of two nearly
        # equal sums over 32 columns
        np.testing.assert_allclose(got[leaf], want[leaf], atol=5e-5 * float(
            jnp.abs(want[leaf]).max()))

    def test_every_expert_held_has_no_branch(self, setup):
        params, x, r = setup
        counts = _primitives(jax.make_jaxpr(lambda p, x, r: DroplessMoE(
            E, K, D, F).apply({"params": p}, x, r))(params, x, r).jaxpr)
        assert "cond" not in counts and counts["custom_vjp_call"] > 0

    def test_a_share_has_one_branch_each_way(self, share):
        layer, params, x, r = share
        counts = _primitives(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
            layer.apply({"params": p}, x, r))))(params).jaxpr)
        assert counts["cond"] == 2              # forward, backward

    def test_gauges_of_a_share_with_a_buffer(self, share):
        from horovod_tpu import metrics
        layer, params, x, r = share
        metrics.instruments.MOE_OVERFLOW_CALLS.labels(1).set(7)
        jax.eval_shape(lambda p: layer.apply({"params": p}, x, r), params)
        snap = metrics.snapshot()
        rows = {s["labels"]["axis_size"]: s["value"] for s in
                snap["hvd_moe_buffer_rows_per_token"]["series"]}
        assert rows == {"1": 1024 / T2}
        calls = {s["labels"]["axis_size"]: s["value"] for s in
                 snap["hvd_moe_overflow_calls"]["series"]}
        assert calls == {"1": 0.0}


def _primitives(jaxpr, counts=None):
    """{primitive: equations of it} of ``jaxpr`` and every jaxpr its
    equations carry, a kernel's own body apart (a ``pl.when`` is a
    ``cond`` of the kernel's, not of the layer's)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, counts)
    return counts


def jaxpr_digest(fn, *args):
    """sha256 (16 hex digits) of the jaxpr ``fn`` traces on ``args``, with
    the addresses in the reprs of function objects taken out."""
    import hashlib
    import re
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _zeros(tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)


class TestTheDefaultLayerIsTheParents:
    """With its default arguments the layer traces, forward and backward,
    the jaxpr recorded here, to the letter. Until PR 34 the digests were
    those of commit dbf7cc0 (before ``weighting`` and ``expert_form``
    existed: 6d64aef40e13cfca, a0bfbf7980f800ee). **PR 34 meant to alter
    the default layer and recorded new ones**: the two grouped products are
    ``ops/pallas/grouped_matmul.py``'s kernels and no longer
    ``lax.ragged_dot``, at every width (the kernels were ahead on the chip
    at the SmallThinker cell's widths too, so there is one path: PERF.md,
    section 6, PR 34), and nothing else in the layer moved: with
    ``_grouped_dot`` made ``lax.ragged_dot`` again the three cases trace
    the parent's digests (``test_all_but_the_product_is_the_parents``). A
    change that means to alter the default layer, or the kernels' bodies,
    records new ones and says so."""

    # tokens, layer arguments -> digest with the kernels, digest at the
    # parent commit 71d82cb (which PR 34's tree traces with lax.ragged_dot)
    CASES = {
        "every_expert_held": (48, 32, 16, {}, "float32",
                              "4acade00a616ae47", "6d64aef40e13cfca"),
        "a_share_with_its_branch": (
            1024, 32, 16, {"num_experts": 8, "top_k": 2, "experts_held": 2,
                           "first_expert": 2}, "float32",
            "e6b6a08a8e82c7de", "a0bfbf7980f800ee"),
        # the SmallThinker cell's layer at its real shapes, traced from
        # shapes alone: 2 x 8192 tokens, 6 of 64 experts a token, 16 held
        "smallthinker_ep4_8k_real_shapes": (
            16384, 2560, 768, {"num_experts": 64, "top_k": 6,
                               "experts_held": 16, "first_expert": 16},
            "bfloat16", "40fc68b72e3a91c4", "1521117c1275fea7"),
    }

    @staticmethod
    def _digest(tokens, d, f, kw, dtype):
        kw = dict({"num_experts": 8, "top_k": 2}, **kw)
        x = jax.ShapeDtypeStruct((2, tokens // 2, d), jnp.dtype(dtype))
        layer = DroplessMoE(hidden_size=d, intermediate_size=f,
                            dtype=jnp.dtype(dtype), **kw)
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x,
                                x)["params"]
        return jaxpr_digest(jax.value_and_grad(
            lambda p, x, r: layer.apply({"params": p}, x, r).astype(
                jnp.float32).sum(), (0, 1, 2)), params, x, x)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_jaxpr_digest(self, case):
        *args, recorded, _ = self.CASES[case]
        assert self._digest(*args) == recorded

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_all_but_the_product_is_the_parents(self, case, monkeypatch):
        from horovod_tpu.parallel import moe
        monkeypatch.setattr(moe, "_grouped_dot", jax.lax.ragged_dot)
        for f in (moe._forward_where_they_fit, moe._backward_where_they_fit):
            f.clear_cache()
        *args, _, parents = self.CASES[case]
        try:
            assert self._digest(*args) == parents
        finally:
            for f in (moe._forward_where_they_fit,
                      moe._backward_where_they_fit):
                f.clear_cache()


# -- the weightings and the expert forms (PR 33) ------------------------------

SCALE = 2.5


def _dense_by(params, x, r, weighting, form, scale=1.0, experts=range(E),
              bias=None):
    """The layer written densely for any weighting and expert form: every
    expert on every token under a mask; ``params`` hold all E experts.
    ``bias`` (E,) moves the choice and nothing else."""
    xt, rt = x.reshape(-1, D), r.reshape(-1, D)
    logits = jnp.dot(rt, params["router"]["kernel"], precision="highest")
    scores = logits if weighting == "softmax" else jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias, K)
    top = jnp.take_along_axis(scores, chosen, -1)
    weights = jax.nn.softmax(top, -1) if weighting == "softmax" \
        else top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(xt)
    for e in experts:
        w_e = scale * jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        if form in ("gated_relu", "gated_silu"):
            act = jax.nn.relu if form == "gated_relu" else jax.nn.silu
            gate_up = xt @ params["w_gate_up"][e]
            hidden = act(gate_up[:, :F]) * gate_up[:, F:]
        else:
            hidden = jnp.square(jax.nn.relu(xt @ params["w_up"][e]))
        out = out + w_e[:, None] * (hidden @ params["w_down"][e])
    return out.reshape(x.shape)


class TestWeightingsAndExpertForms:
    @pytest.fixture
    def inputs(self, rng):
        x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
        r = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
        return x, r

    @pytest.mark.parametrize("weighting, form, own_router_input", [
        ("sigmoid", "relu2", True), ("sigmoid", "relu2", False),
        ("sigmoid", "gated_relu", True), ("softmax", "relu2", True),
        ("softmax", "gated_relu", False), ("sigmoid", "gated_silu", False),
        ("softmax", "gated_silu", True)])
    def test_against_a_dense_loop_over_the_experts(self, inputs, weighting,
                                                   form, own_router_input):
        """Forward and every gradient against the layer written densely,
        float32: 1e-5 of the largest entry; the router reading an input of
        its own, or ``x``."""
        x, r = inputs
        layer = DroplessMoE(E, K, D, F, weighting=weighting,
                            weight_scale=SCALE, expert_form=form)
        params = layer.init(jax.random.PRNGKey(3), x, r)["params"]
        first = "w_up" if form == "relu2" else "w_gate_up"
        assert set(params) == {"router", first, "w_down"}
        assert params[first].shape == (E, D, F if form == "relu2" else 2 * F)
        w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

        def got(p, x, r):
            return jnp.sum(w * layer.apply(
                {"params": p}, x, r if own_router_input else None))

        def want(p, x, r):
            return jnp.sum(w * _dense_by(
                p, x, r if own_router_input else x, weighting, form, SCALE))
        dense = _dense_by(params, x, r if own_router_input else x,
                          weighting, form, SCALE)
        np.testing.assert_allclose(
            layer.apply({"params": params}, x,
                        r if own_router_input else None),
            dense, atol=1e-5 * float(jnp.abs(dense).max()))
        wrt = (0, 1, 2) if own_router_input else (0, 1)
        for a, b in zip(jax.tree.leaves(jax.grad(got, wrt)(params, x, r)),
                        jax.tree.leaves(jax.grad(want, wrt)(params, x, r))):
            assert float(jnp.abs(b).max()) > 0
            np.testing.assert_allclose(a, b, atol=1e-5 * float(
                jnp.abs(b).max()))

    def test_sigmoid_weights_sum_to_the_scale(self, inputs):
        """Experts that return their input times one (identity-like) show
        the weights: with every expert held a token's weights sum to
        ``weight_scale`` whatever its scores."""
        x, r = inputs
        layer = DroplessMoE(E, K, D, D, weighting="sigmoid",
                            weight_scale=SCALE, expert_form="relu2")
        params = layer.init(jax.random.PRNGKey(3), x, r)["params"]
        eye = jnp.broadcast_to(jnp.eye(D), (E, D, D))
        params = dict(params, w_up=eye, w_down=eye)
        positive = jnp.abs(x) + 0.1
        got = layer.apply({"params": params}, positive, r)
        np.testing.assert_allclose(got, SCALE * jnp.square(positive),
                                   rtol=1e-5)

    @pytest.mark.parametrize("held", [1, 2, 4])
    def test_shares_of_sigmoid_relu2_add_up(self, inputs, held):
        x, r = inputs
        whole = DroplessMoE(E, K, D, F, weighting="sigmoid",
                            weight_scale=SCALE, expert_form="relu2")
        params = whole.init(jax.random.PRNGKey(3), x, r)["params"]
        want = whole.apply({"params": params}, x, r)
        total = 0.0
        for first in range(0, E, held):
            share = dict(params, w_up=params["w_up"][first:first + held],
                         w_down=params["w_down"][first:first + held])
            total = total + DroplessMoE(
                E, K, D, F, experts_held=held, first_expert=first,
                weighting="sigmoid", weight_scale=SCALE,
                expert_form="relu2").apply({"params": share}, x, r)
        np.testing.assert_allclose(total, want, atol=1e-5 * float(
            jnp.abs(want).max()))

    def test_a_share_with_its_branch_and_unwritten_rows(self, rng,
                                                        unwritten_rows):
        """The buffer of the rows expected, with the rows the grouped
        product leaves unwritten spoiled as on the chip, for the new form
        and weighting: output and every gradient against the dense loop."""
        from horovod_tpu.parallel import moe
        for f in (moe._forward_where_they_fit, moe._backward_where_they_fit):
            f.clear_cache()
        x = jnp.asarray(rng.standard_normal((2, T2 // 2, D)), jnp.float32)
        r = jnp.asarray(rng.standard_normal((2, T2 // 2, D)), jnp.float32)
        whole = DroplessMoE(E, K, D, F, weighting="sigmoid",
                            weight_scale=SCALE, expert_form="relu2")
        params = whole.init(jax.random.PRNGKey(3), x, r)["params"]
        layer = DroplessMoE(E, K, D, F, experts_held=HELD,
                            first_expert=FIRST, weighting="sigmoid",
                            weight_scale=SCALE, expert_form="relu2")
        share = dict(params, w_up=params["w_up"][FIRST:FIRST + HELD],
                     w_down=params["w_down"][FIRST:FIRST + HELD])
        w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
        got = jax.jit(jax.value_and_grad(lambda p, x, r: jnp.sum(
            w * layer.apply({"params": p}, x, r)), (0, 1, 2)))(share, x, r)
        want = jax.value_and_grad(lambda p, x, r: jnp.sum(w * _dense_by(
            p, x, r, "sigmoid", "relu2", SCALE,
            range(FIRST, FIRST + HELD))), (0, 1, 2))(params, x, r)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
        want_p = dict(want[1][0], w_up=want[1][0]["w_up"][FIRST:FIRST + HELD],
                      w_down=want[1][0]["w_down"][FIRST:FIRST + HELD])
        for a, b in zip(jax.tree.leaves((got[1][0], got[1][1:])),
                        jax.tree.leaves((want_p, want[1][1:]))):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(a, b, atol=5e-5 * float(
                jnp.abs(b).max()))
        for f in (moe._forward_where_they_fit, moe._backward_where_they_fit):
            f.clear_cache()

    @pytest.mark.parametrize("kw, named", [
        (dict(weighting="tanh"), "unknown weighting 'tanh'.*softmax.*sigmoid"),
        (dict(expert_form="swiglu"),
         "unknown expert_form 'swiglu'.*gated_relu.*relu2.*gated_silu")])
    def test_an_unknown_name_raises_by_name(self, inputs, kw, named):
        x, r = inputs
        with pytest.raises(ValueError, match=named):
            DroplessMoE(E, K, D, F, **kw).init(jax.random.PRNGKey(0), x, r)


# -- the selection bias (PR 35) ------------------------------------------------

class TestSelectionBias:
    """``selection_bias``: added to the scores for the choice of the
    ``top_k`` alone. It moves the choice and not the weights, and gets no
    gradient."""

    BIAS = jnp.asarray([0.0, 0.9, 0.0, -0.9, 0.0, 0.0, 0.4, 0.0])

    @pytest.fixture
    def case(self, rng):
        x = jnp.asarray(rng.standard_normal((2, T // 2, D)), jnp.float32)
        layer = DroplessMoE(E, K, D, F, weighting="sigmoid",
                            weight_scale=SCALE, expert_form="gated_silu")
        params = layer.init(jax.random.PRNGKey(3), x)["params"]
        return layer, params, x

    @pytest.mark.parametrize("held, first", [(E, 0), (2, 1)])
    def test_against_a_dense_loop_with_the_bias(self, case, held, first):
        """Output and every gradient, the whole layer and a share, float32:
        1e-5 of the largest entry."""
        whole, params, x = case
        layer = DroplessMoE(E, K, D, F, experts_held=held, first_expert=first,
                            weighting="sigmoid", weight_scale=SCALE,
                            expert_form="gated_silu")
        share = _share(params, first, held)
        w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
        got = jax.value_and_grad(lambda p, x: jnp.sum(w * layer.apply(
            {"params": p}, x, None, self.BIAS)), (0, 1))(share, x)
        want = jax.value_and_grad(lambda p, x: jnp.sum(w * _dense_by(
            p, x, x, "sigmoid", "gated_silu", SCALE,
            range(first, first + held), self.BIAS)), (0, 1))(params, x)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
        want_p = _share(want[1][0], first, held)
        for a, b in zip(jax.tree.leaves((got[1][0], got[1][1])),
                        jax.tree.leaves((want_p, want[1][1]))):
            np.testing.assert_allclose(a, b, atol=1e-5 * float(
                jnp.abs(b).max()))

    def test_it_moves_the_choice_and_not_the_weights(self, case):
        """With identity-like experts of one output each the layer shows
        whom it chose and how it weighed them: with the bias some token's
        choice changes, and every chosen expert's weight is its unbiased
        score's share of the chosen unbiased scores."""
        layer, params, x = case
        scores = jax.nn.sigmoid(jnp.dot(
            x.reshape(-1, D), params["router"]["kernel"],
            precision="highest"))
        plain = jax.lax.top_k(scores, K)[1]
        biased = jax.lax.top_k(scores + self.BIAS, K)[1]
        moved = jnp.any(jnp.sort(plain, -1) != jnp.sort(biased, -1), -1)
        assert 0 < int(moved.sum()) < moved.size
        # expert e writes SCALE-free weight into column e: gate 1 * up 1
        mark = DroplessMoE(E, K, D, 1, weighting="sigmoid",
                           expert_form="relu2")
        ones = jnp.ones((E, D, 1)) / D
        down = jnp.zeros((E, 1, D)).at[jnp.arange(E), 0,
                                       jnp.arange(E)].set(1.0)
        marks = dict(router=params["router"], w_up=ones, w_down=down)
        pos = jnp.ones_like(x)            # relu(mean of ones)^2 = 1
        got = mark.apply({"params": marks}, pos, x, self.BIAS).reshape(-1, D)
        top = jnp.take_along_axis(scores, biased, -1)
        want = jnp.zeros_like(got).at[
            jnp.arange(got.shape[0])[:, None], biased].set(
                top / top.sum(-1, keepdims=True))
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_it_gets_no_gradient_and_zero_changes_nothing(self, case):
        layer, params, x = case
        g = jax.grad(lambda b: jnp.sum(jnp.square(layer.apply(
            {"params": params}, x, None, b))))(self.BIAS)
        assert bool(jnp.all(g == 0))
        np.testing.assert_array_equal(
            layer.apply({"params": params}, x, None, jnp.zeros(E)),
            layer.apply({"params": params}, x))

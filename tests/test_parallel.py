"""DP train-step + multi-level strategy tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

N = 8


class TestMakeTrainStep:
    def test_mlp_converges_and_stays_in_sync(self, hvd, rng):
        from horovod_tpu.models import MLP
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import TrainState, make_train_step

        model = MLP(features=(16, 4))
        x = np.asarray(rng.standard_normal((64, 8)), np.float32)
        w_true = rng.standard_normal((8, 4)).astype(np.float32)
        y = np.argmax(x @ w_true, axis=1)

        params = model.init(jax.random.PRNGKey(0), x[:1])
        opt = DistributedOptimizer(optax.adam(1e-2))

        def loss_fn(params, batch):
            logits = model.apply(params, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        mesh = hvd.global_process_set.mesh
        step = make_train_step(loss_fn, opt, mesh, donate=False)
        state = TrainState.create(params, opt)

        losses = []
        for i in range(60):
            state, loss = step(state, {"x": x, "y": y})
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::10]

        # replicated params must remain bitwise-identical across devices
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        per_dev = [np.asarray(s.data) for s in leaf.addressable_shards]
        for d in per_dev[1:]:
            np.testing.assert_array_equal(per_dev[0], d)

    def test_grad_is_global_mean(self, hvd, rng):
        """One SGD step == step with manually averaged global gradient."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import TrainState, make_train_step

        w0 = np.asarray(rng.standard_normal(6), np.float32)
        x = np.asarray(rng.standard_normal((N * 4, 6)), np.float32)

        def loss_fn(params, batch):
            return jnp.mean(jnp.square(batch @ params))

        opt = DistributedOptimizer(optax.sgd(0.1))
        mesh = hvd.global_process_set.mesh
        step = make_train_step(loss_fn, opt, mesh, donate=False)
        state = TrainState.create(jnp.asarray(w0), opt)
        state, _ = step(state, x)

        # manual: mean over shard-mean gradients == global mean gradient
        g = np.stack([
            2 * (x[r * 4:(r + 1) * 4] @ w0) @ x[r * 4:(r + 1) * 4] / 4
            for r in range(N)]).mean(0)
        np.testing.assert_allclose(np.asarray(state.params), w0 - 0.1 * g,
                                   rtol=1e-4)

    def test_eval_step_metric_average(self, hvd, rng):
        from horovod_tpu.parallel import make_eval_step
        x = np.asarray(rng.standard_normal((N * 2, 3)), np.float32)

        def eval_fn(params, batch):
            return {"m": jnp.mean(batch * params)}

        mesh = hvd.global_process_set.mesh
        ev = make_eval_step(eval_fn, mesh)
        out = ev(jnp.ones(()), x)
        np.testing.assert_allclose(float(out["m"]), x.mean(), rtol=1e-5)


class TestStrategies:
    def _run2d(self, hvd, fn, x):
        mesh2d = hvd.topology().mesh2d  # (cross=1, local=8) in tests
        return jax.jit(jax.shard_map(
            fn, mesh=mesh2d, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local"))))(x)

    def test_torus_equals_flat(self, hvd, rng):
        from horovod_tpu.parallel import allreduce_torus
        x = np.asarray(rng.standard_normal((N, 5, 3)), np.float32)

        def fn(xl):
            return allreduce_torus(jnp.squeeze(xl, 0))[None]

        out = np.asarray(self._run2d(hvd, fn, x))
        for r in range(N):
            np.testing.assert_allclose(out[r], x.sum(0), rtol=1e-4)

    def test_torus_average_odd_size(self, hvd, rng):
        from horovod_tpu.parallel import allreduce_torus
        x = np.asarray(rng.standard_normal((N, 7)), np.float32)  # 7 % 8 != 0

        def fn(xl):
            return allreduce_torus(jnp.squeeze(xl, 0), average=True)[None]

        out = np.asarray(self._run2d(hvd, fn, x))
        np.testing.assert_allclose(out[3], x.mean(0), rtol=1e-4)

    def test_torus_int8_cross_leg(self, hvd, rng):
        """cross_compression="int8": DCN leg quantized, ICI legs exact —
        result within the two quantization error bounds."""
        from jax.sharding import Mesh
        from horovod_tpu.parallel import allreduce_torus
        mesh = Mesh(np.array(jax.devices()[:N], dtype=object).reshape(4, 2),
                    ("cross", "local"))
        # per-chip shard = 16384/2 = 8192 >= cross_n*1024: int8 leg engages
        x = np.asarray(rng.standard_normal((N, 16384)), np.float32)

        def fn(xl):
            return allreduce_torus(jnp.squeeze(xl, 0),
                                   cross_compression="int8")[None]

        out = np.asarray(jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local"))))(x))
        exact = x.sum(0)
        # cross leg sees local sums of 2 rows; 4 cross ranks, 2 quant legs
        local_max = np.abs(x.reshape(4, 2, -1).sum(1)).max()
        tol = 4 * local_max / 254 + np.abs(exact).max() / 254 + 1e-6
        np.testing.assert_allclose(out[0], exact, rtol=0.2, atol=tol)
        np.testing.assert_allclose(out[5], exact, rtol=0.2, atol=tol)
        assert np.abs(out[0] - exact).max() > 0, "suspiciously exact"

        # Tiny shards fall back to the exact psum (padding would cost more
        # bytes than it saves): bit-identical to the uncompressed torus.
        small = np.asarray(rng.standard_normal((N, 64)), np.float32)
        out_s = np.asarray(jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local"))))(small))
        np.testing.assert_allclose(out_s[2], small.sum(0), rtol=1e-4)

    def test_hierarchical(self, hvd, rng):
        from horovod_tpu.parallel import allreduce_hierarchical
        x = np.asarray(rng.standard_normal((N, 4)), np.float32)

        def fn(xl):
            return allreduce_hierarchical(jnp.squeeze(xl, 0))[None]

        out = np.asarray(self._run2d(hvd, fn, x))
        np.testing.assert_allclose(out[0], x.sum(0), rtol=1e-4)


class TestDcnMesh:
    """Multi-slice (DCN) factorization: the 'cross' axis of the 2-level
    strategies must sit on the slice boundary when the job spans slices
    (reference mapping SURVEY §5.8; the fork's torus node boundary)."""

    def test_forced_slices_build_dcn_mesh(self, hvd, rng, monkeypatch):
        from horovod_tpu.common.topology import build_topology
        monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
        topo = build_topology()
        assert topo.num_slices == 2
        assert topo.mesh_dcn is not None
        assert topo.mesh_dcn.devices.shape == (2, N // 2)
        assert topo.hierarchical_mesh is topo.mesh_dcn

        # Torus allreduce over the DCN mesh matches numpy.
        from horovod_tpu.parallel import allreduce_torus
        x = np.asarray(rng.standard_normal((N, 6)), np.float32)

        def fn(xl):
            return allreduce_torus(jnp.squeeze(xl, 0))[None]

        out = np.asarray(jax.jit(jax.shard_map(
            fn, mesh=topo.hierarchical_mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local"))))(x))
        for r in range(N):
            np.testing.assert_allclose(out[r], x.sum(0), rtol=1e-4)

    def test_no_slices_falls_back_to_host_mesh(self, hvd):
        from horovod_tpu.common.topology import build_topology
        topo = build_topology()
        assert topo.mesh_dcn is None
        assert topo.hierarchical_mesh is topo.mesh2d

    def test_slice_id_attr_detection(self):
        from horovod_tpu.common.topology import _slice_id

        class D1:
            slice_index = 3

        class D3:
            pass

        assert _slice_id(D1()) == 3
        assert _slice_id(D3()) is None


class TestZeroTrainStep:
    """ZeRO-1 optimizer-state sharding over the DP axis (beyond reference
    parity: the reference replicates optimizer state on every worker)."""

    def _setup(self, hvd, rng):
        import optax
        from horovod_tpu.models import MLP
        model = MLP(features=[16, 8, 4])
        x = np.asarray(rng.standard_normal((16, 8)), np.float32)
        y = np.asarray(rng.integers(0, 4, (16,)), np.int32)
        params = model.init(jax.random.PRNGKey(0), x[:1])["params"]

        def loss_fn(p, batch):
            logits = model.apply({"params": p}, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        return model, params, loss_fn, {"x": jnp.asarray(x),
                                        "y": jnp.asarray(y)}

    def test_matches_replicated_adam(self, hvd, rng):
        import optax
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import (TrainState, ZeroTrainState,
                                          make_train_step,
                                          make_zero_train_step)
        mesh = hvd.global_process_set.mesh
        _, params, loss_fn, batch = self._setup(hvd, rng)

        ref_opt = DistributedOptimizer(optax.adam(1e-2))
        ref_step = make_train_step(loss_fn, ref_opt, mesh, donate=False)
        ref_state = TrainState.create(params, ref_opt)

        tx = optax.adam(1e-2)
        z_step = make_zero_train_step(loss_fn, tx, mesh, donate=False)
        z_state = ZeroTrainState.create(params, tx, mesh)

        for _ in range(3):
            ref_state, ref_loss = ref_step(ref_state, batch)
            z_state, z_loss = z_step(z_state, batch)
        np.testing.assert_allclose(float(z_loss), float(ref_loss),
                                   rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(ref_state.params),
                        jax.tree_util.tree_leaves(z_state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_moments_are_sharded(self, hvd, rng):
        import optax
        from horovod_tpu.parallel import ZeroTrainState, make_zero_train_step
        mesh = hvd.global_process_set.mesh
        n = hvd.size()
        _, params, loss_fn, batch = self._setup(hvd, rng)
        tx = optax.adam(1e-2)
        step = make_zero_train_step(loss_fn, tx, mesh, donate=False)
        state = ZeroTrainState.create(params, tx, mesh)
        state, _ = step(state, batch)
        # Every moment vector is laid out 1/n per chip.
        flat_len = sum(p.size for p in jax.tree_util.tree_leaves(params))
        padded = flat_len + (-flat_len) % n
        mus = [l for l in jax.tree_util.tree_leaves(state.opt_state)
               if getattr(l, "ndim", 0) == 1]
        assert mus, "no moment vectors found"
        for mu in mus:
            assert mu.shape == (padded,)
            shard_shapes = {s.data.shape for s in mu.addressable_shards}
            assert shard_shapes == {(padded // n,)}, shard_shapes


class TestFSDP:
    """ZeRO-3 parameter sharding via GSPMD (parallel/fsdp.py)."""

    def _setup(self, hvd, rng, min_size=128):
        import optax
        from horovod_tpu.parallel.fsdp import (make_fsdp_train_step,
                                               shard_batch)
        mesh = hvd.global_process_set.mesh
        d, f = 32, 64
        params = {
            "w1": jnp.asarray(rng.standard_normal((d, f)) * 0.1,
                              jnp.float32),
            "b1": jnp.zeros((f,), jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((f, d)) * 0.1,
                              jnp.float32),
        }
        X = jnp.asarray(rng.standard_normal((64, d)), jnp.float32)
        Y = jnp.asarray(rng.standard_normal((64, d)), jnp.float32)

        def loss_fn(p, b):
            h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

        tx = optax.adam(1e-2)
        init_fn, step_fn = make_fsdp_train_step(loss_fn, tx, mesh,
                                                min_size=min_size)
        batch = shard_batch({"x": X, "y": Y}, mesh)
        return params, loss_fn, tx, init_fn, step_fn, batch, (X, Y)

    def test_matches_single_device_trajectory(self, hvd, rng):
        import optax
        params, loss_fn, tx, init_fn, step_fn, batch, (X, Y) = \
            self._setup(hvd, rng)
        p_ref = jax.tree.map(jnp.array, params)
        o_ref = tx.init(p_ref)
        sp, so = init_fn(params)
        for _ in range(5):
            sp, so, loss = step_fn(sp, so, batch)
            l_ref, g = jax.value_and_grad(loss_fn)(p_ref,
                                                   {"x": X, "y": Y})
            up, o_ref = tx.update(g, o_ref, p_ref)
            p_ref = optax.apply_updates(p_ref, up)
        np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
        for k in params:
            np.testing.assert_allclose(np.asarray(sp[k]),
                                       np.asarray(p_ref[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_params_and_moments_actually_sharded(self, hvd, rng):
        params, _, _, init_fn, step_fn, batch, _ = self._setup(hvd, rng)
        sp, so = init_fn(params)
        assert not sp["w1"].sharding.is_fully_replicated
        assert not sp["w2"].sharding.is_fully_replicated
        assert sp["b1"].sharding.is_fully_replicated  # < min_size
        # adam moments mirror the param shardings
        mu = so[0].mu
        assert not mu["w1"].sharding.is_fully_replicated
        # shardings survive a step (no silent re-replication)
        sp, so, _ = step_fn(sp, so, batch)
        assert not sp["w1"].sharding.is_fully_replicated
        assert not so[0].mu["w1"].sharding.is_fully_replicated

    def test_small_leaves_replicated_by_min_size(self, hvd):
        from horovod_tpu.parallel.fsdp import fsdp_spec
        from jax.sharding import PartitionSpec as P
        assert fsdp_spec((8, 8), 8, min_size=128) == P()       # too small
        assert fsdp_spec((64, 64), 8, min_size=128) == P("hvd", None)
        assert fsdp_spec((63, 65), 8, min_size=128) == P()     # indivisible
        assert fsdp_spec((63, 64), 8, min_size=128) == P(None, "hvd")


    def test_fsdp_on_gpt(self, hvd, rng):
        """FSDP shards a real transformer pytree: GPT-tiny trains one step
        with every large leaf sharded (embeddings, attention, MLP)."""
        import optax
        from horovod_tpu.models.gpt import GPT, GPTConfig
        from horovod_tpu.parallel import make_fsdp_train_step, shard_batch

        mesh = hvd.global_process_set.mesh
        cfg = GPTConfig.tiny(tp_axis=None, ep_axis=None)
        model = GPT(cfg)
        ids = jnp.asarray(np.asarray(rng.integers(0, 256, (8, 32)),
                                     np.int32))
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]

        def loss_fn(p, b):
            logits = model.apply({"params": p}, b["ids"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), b["ids"][:, 1:]).mean()

        init_fn, step_fn = make_fsdp_train_step(
            loss_fn, optax.adamw(1e-3), mesh, min_size=4096, donate=False)
        sp, so = init_fn(params)
        # The big leaves actually sharded
        assert not sp["embed"]["tok_emb"]["embedding"] \
            .sharding.is_fully_replicated
        assert not sp["head"]["lm_head"]["kernel"] \
            .sharding.is_fully_replicated
        batch = shard_batch({"ids": ids}, mesh)
        losses = []
        for _ in range(2):
            sp, so, loss = step_fn(sp, so, batch)
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[1] < losses[0]

"""Pallas kernels (interpret mode on CPU) vs plain-JAX references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _qkv(rng, B=2, L=128, H=4, D=32, dtype=np.float32):
    def t():
        return jnp.asarray(rng.standard_normal((B, L, H, D)), dtype)
    return t(), t(), t()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("L", [128, 96])
    def test_matches_reference(self, rng, causal, L):
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, k, v = _qkv(rng, L=L)
        out = flash_attention(q, k, v, causal=causal)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_unaligned_length_padded_kernel(self, rng):
        """No block divides 100: the wrapper pads to 128 and masks the
        padded keys inside the kernel — exact vs the oracle."""
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, k, v = _qkv(rng, L=100)
        out = flash_attention(q, k, v, causal=True)
        ref = local_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("L", [196, 197, 200, 224, 255, 256])
    @pytest.mark.parametrize("causal", [False, True])
    def test_padded_single_chunk_bisect(self, rng, causal, L):
        """VERDICT r4 item 3: the padded-grid bisect 196->256. Every
        length here pads to a 256-key SINGLE-chunk grid (except 256,
        the aligned control), exercising the static specialization that
        replaced the pl.when + dynamic-clip structure suspected of the
        on-chip Mosaic hang (docs/troubleshooting.md). ViT's 197 is the
        original failing config; fwd AND bwd vs the oracle."""
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, k, v = _qkv(rng, B=1, L=L, H=2, D=16)
        out = flash_attention(q, k, v, causal=causal)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        g = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=causal).astype(jnp.float32)
            ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(local_attention(
            a, b, c, causal=causal).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(g, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                err_msg=f"d{nm} L={L} causal={causal}")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("lq,lk", [(100, 100), (60, 100), (100, 60)])
    def test_unaligned_gradients_match(self, rng, causal, lq, lk):
        """Padded-kernel VJP == oracle grads at non-aligned, cross lengths
        (padded positions must contribute exactly zero)."""
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        if causal and lq > lk:
            # the oracle NaNs on fully-masked rows (softmax of all -inf);
            # the kernel's zero-output behavior for that case is pinned by
            # test_fully_masked_rows_zero_gradients instead
            pytest.skip("oracle NaNs on fully-masked rows")
        q, _, _ = _qkv(rng, B=1, L=lq, H=2, D=16)
        _, k, v = _qkv(rng, B=1, L=lk, H=2, D=16)
        g = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=causal).astype(jnp.float32)
            ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(local_attention(
            a, b, c, causal=causal).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(g, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                err_msg=f"d{nm} (causal={causal}, lq={lq}, lk={lk})")

    def test_bf16(self, rng):
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, k, v = _qkv(rng, L=64, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        ref = local_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match(self, rng, causal):
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, k, v = _qkv(rng, B=1, L=64, H=2, D=16)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(local_attention(q, k, v, causal=causal)
                           .astype(jnp.float32) ** 2)

        g = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_attention_lengths(self, rng, causal):
        """Lq != Lk, including the end-aligned causal convention (query i
        attends keys <= i + Lk - Lq, matching local_attention's tril)."""
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, _, _ = _qkv(rng, L=64)
        _, k, v = _qkv(rng, L=128)
        out = flash_attention(q, k, v, causal=causal)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_fully_masked_rows_zero_gradients(self, rng):
        """causal with Lq > Lk: early rows attend nothing; outputs and
        gradients must be exactly zero, not exp(1e30) garbage."""
        from horovod_tpu.ops.pallas import flash_attention
        q, _, _ = _qkv(rng, B=1, L=64, H=2, D=16)
        _, k, v = _qkv(rng, B=1, L=32, H=2, D=16)
        out = flash_attention(q, k, v, causal=True)
        # rows i < Lq - Lk = 32 are fully masked (end-aligned convention)
        np.testing.assert_array_equal(np.asarray(out)[:, :32], 0.0)
        g = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for t in g:
            arr = np.asarray(t)
            assert np.isfinite(arr).all()
        np.testing.assert_array_equal(np.asarray(g[0])[:, :32], 0.0)

    def test_cross_length_causal_gradients(self, rng):
        from horovod_tpu.ops.pallas import flash_attention
        from horovod_tpu.parallel.sequence import local_attention
        q, _, _ = _qkv(rng, B=1, L=32, H=2, D=16)
        _, k, v = _qkv(rng, B=1, L=64, H=2, D=16)

        g = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(local_attention(
            a, b, c, causal=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("lq,lk", [(64, 64), (32, 64), (64, 32)])
    def test_fused_backward_kernels_match_jnp(self, rng, causal, lq, lk):
        """The TPU backward kernels (_fa_backward, run here through the
        interpreter) must reproduce the jnp backward that CPU mode uses —
        the jnp path is the oracle the kernels are pinned to."""
        import importlib
        # the package re-exports the same-named function, shadowing the
        # submodule attribute — import the module explicitly
        fa = importlib.import_module(
            "horovod_tpu.ops.pallas.flash_attention")
        H, D = 2, 16
        bq = fa._pick_block(lq)
        bk = fa._pick_block(lk)
        q = jnp.asarray(rng.standard_normal((H, lq, D)), np.float32)
        k = jnp.asarray(rng.standard_normal((H, lk, D)), np.float32)
        v = jnp.asarray(rng.standard_normal((H, lk, D)), np.float32)
        do = jnp.asarray(rng.standard_normal((H, lq, D)), np.float32)
        sm = 1.0 / D ** 0.5
        o, lse = fa._fa_forward(q, k, v, causal, sm, bq, bk)
        got = fa._fa_backward(q, k, v, o, lse, do, causal, sm, bq, bk)
        want = fa._flash_bwd(causal, sm, bq, bk, None, None, None, None,
                             (q, k, v, o, lse), do)
        for a, b, nm in zip(got, want, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"d{nm} mismatch (causal={causal})")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("L", [128, 100])
    def test_gqa_narrow_kv_matches_repeat(self, rng, causal, L):
        """Grouped-query attention: narrow k/v streamed through the
        index-mapped kernels (and padded-length masking) must equal the
        repeat-then-MHA result — forward AND all gradients, with dK/dV
        group-summed back to the kv heads."""
        from horovod_tpu.ops.pallas import flash_attention
        B, H, KV, D = 2, 8, 2, 32
        q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, L, KV, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, L, KV, D)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal)
        ref = flash_attention(q, jnp.repeat(k, H // KV, 2),
                              jnp.repeat(v, H // KV, 2), causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

        def loss_narrow(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_wide(q, k, v):
            return jnp.sum(flash_attention(
                q, jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2),
                causal=causal) ** 2)

        gn = jax.grad(loss_narrow, argnums=(0, 1, 2))(q, k, v)
        gw = jax.grad(loss_wide, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gn, gw):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        assert gn[1].shape == (B, L, KV, D)

    def test_gqa_indivisible_heads_raises(self, rng):
        from horovod_tpu.ops.pallas import flash_attention
        q = jnp.zeros((1, 128, 4, 32))
        k = jnp.zeros((1, 128, 3, 32))
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, k)

    @pytest.mark.parametrize("L", [100, 128])
    def test_compiled_path_never_leaves_the_kernels(self, rng, monkeypatch,
                                                    L):
        """Off the interpreter (simulated: _interpret -> False) every
        length, aligned or padded (197 -> 256 compiled and matched the
        oracle on a v5e, PR 21), enters the kernels: nothing on that path
        may give way to plain attention quietly."""
        import importlib
        fa = importlib.import_module(
            "horovod_tpu.ops.pallas.flash_attention")
        monkeypatch.setattr(fa, "_interpret", lambda: False)

        class Entered(Exception):
            pass

        def boom(*a, **kw):
            raise Entered
        monkeypatch.setattr(fa, "_flash", boom)
        q, k, v = _qkv(rng, L=L)
        with pytest.raises(Entered):
            fa.flash_attention(q, k, v, causal=True)

    def test_tp_attention_flash_flag(self, hvd, rng):
        """TPSelfAttention(use_flash=True) == use_flash=False (same params)."""
        from horovod_tpu.parallel.tp import TPSelfAttention
        x = jnp.asarray(rng.standard_normal((2, 64, 32)), np.float32)
        a_plain = TPSelfAttention(num_heads=4, hidden_size=32, causal=True,
                                  axis_name=None)
        a_flash = TPSelfAttention(num_heads=4, hidden_size=32, causal=True,
                                  axis_name=None, use_flash=True)
        params = a_plain.init(jax.random.PRNGKey(0), x)
        y0 = a_plain.apply(params, x)
        y1 = a_flash.apply(params, x)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-4, atol=2e-5)


class TestScaleKernels:
    def test_scale_buffer(self, rng):
        from horovod_tpu.ops.pallas import scale_buffer
        x = jnp.asarray(rng.standard_normal((37, 19)), np.float32)
        out = scale_buffer(x, 2.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.5,
                                   rtol=1e-6)

    def test_scale_buffers_batched(self, rng):
        from horovod_tpu.ops.pallas import scale_buffers
        ts = [jnp.asarray(rng.standard_normal(s), np.float32)
              for s in [(5,), (3, 7), (2, 2, 2)]]
        outs = scale_buffers(ts, 0.5)
        for t, o in zip(ts, outs):
            assert o.shape == t.shape
            np.testing.assert_allclose(np.asarray(o), np.asarray(t) * 0.5,
                                       rtol=1e-6)

    def test_large_fallback(self, rng):
        from horovod_tpu.ops.pallas import scale_buffer
        x = jnp.ones((1 << 21,), jnp.float32)
        np.testing.assert_allclose(np.asarray(scale_buffer(x, 3.0))[:4], 3.0)


class TestAdasumKernel:
    def test_matches_reference(self, rng):
        from horovod_tpu.ops.adasum import adasum_combine
        from horovod_tpu.ops.pallas import adasum_combine_pallas
        a = jnp.asarray(rng.standard_normal((33, 17)), np.float32)
        b = jnp.asarray(rng.standard_normal((33, 17)), np.float32)
        out = adasum_combine_pallas(a, b)
        ref = adasum_combine(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_scale_invariance(self, rng):
        """The defining Adasum property: combine(a, a) == a (orthogonality
        handling) — well, combine(a, 2a) direction invariance."""
        from horovod_tpu.ops.pallas import adasum_combine_pallas
        a = jnp.asarray(rng.standard_normal((64,)), np.float32)
        out = adasum_combine_pallas(a, 2.0 * a)
        # parallel gradients: each is scaled by (1 - dot/(2 norm^2))
        # combine(a, 2a) = (1 - 1) * a + (1 - 1/4) * 2a = 1.5 a
        np.testing.assert_allclose(np.asarray(out), 1.5 * np.asarray(a),
                                   rtol=1e-5)

"""Quantized wire tier (horovod_tpu/ops/wire.py): block quantizers, the
two-phase exchange, error feedback, per-process-set wire registry, all
three dispatch paths, and the elastic-reset residual contract."""

import sys

import cloudpickle
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import wire

# Cluster workers can't import this module by name; ship workers by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])


def _events(hvd, name):
    snap = hvd.metrics_snapshot()
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in snap.get(name, {}).get("series", ())}


def _wire_events(hvd):
    return _events(hvd, "wire_compression_events_total")


def _wire_bytes(hvd, dtype):
    # summed across the tier label (the counter is {dtype, tier})
    snap = hvd.metrics_snapshot()
    return sum(s["value"]
               for s in snap.get("wire_bytes_total", {}).get("series", ())
               if s["labels"].get("dtype") == dtype)


@pytest.fixture
def clean_wire(hvd):
    """Full-precision registry + empty residual store around each test."""
    from horovod_tpu.common import basics
    cfg = basics.config()
    prev_ef = cfg.wire_error_feedback
    wire.clear_wire_registry()
    wire.reset_error_feedback()
    yield cfg
    cfg.wire_error_feedback = prev_ef
    wire.clear_wire_registry()
    wire.reset_error_feedback()


class TestQuantizers:
    def test_int8_roundtrip_error_bounded_by_block_max(self):
        rng = np.random.default_rng(0)
        t = jnp.asarray(rng.standard_normal((4, 3, wire.BLOCK)), jnp.float32)
        q, s = wire.symmetric_int8_quantize(t)
        assert q.dtype == jnp.int8 and s.shape == (4, 3)
        err = np.abs(np.asarray(wire.dequantize(q, s)) - np.asarray(t))
        bound = np.asarray(jnp.max(jnp.abs(t), axis=-1))[..., None] / 254.0
        assert (err <= bound + 1e-7).all()

    def test_int8_zero_block_is_exact(self):
        q, s = wire.symmetric_int8_quantize(jnp.zeros((2, wire.BLOCK)))
        assert np.asarray(wire.dequantize(q, s)).max() == 0.0

    def test_fp8_roundtrip_relative_error(self):
        rng = np.random.default_rng(1)
        t = jnp.asarray(rng.standard_normal((2, wire.BLOCK)), jnp.float32)
        q, s = wire.symmetric_fp8_quantize(t)
        assert q.dtype == jnp.float8_e4m3fn
        err = np.abs(np.asarray(wire.dequantize(q, s)) - np.asarray(t))
        # e4m3: 3 mantissa bits -> relative error <= 2^-4 per element
        # (plus the scale's own rounding), relative to the block max.
        bound = np.abs(np.asarray(t)) / 16.0 + \
            np.asarray(jnp.max(jnp.abs(t), axis=-1))[..., None] / 256.0
        assert (err <= bound + 1e-6).all()

    def test_labels_and_resolution(self):
        assert wire.quantized_label("int8") == "int8"
        assert wire.quantized_label(jnp.int8) == "int8"
        assert wire.quantized_label("bfloat16") is None
        assert wire.quantized_label("") is None
        assert wire.quantized_label(None) is None
        assert wire.quantized_label("fp8") == "fp8"
        assert wire.quantized_label(jnp.float8_e4m3fn) == "fp8"
        assert wire.wire_numpy_type("fp8") is jnp.float8_e4m3fn
        assert wire.wire_numpy_type("") is None
        assert jnp.dtype(wire.wire_numpy_type("int8")) == jnp.int8

    def test_exchange_wire_bytes_accounting(self):
        n = 8
        elems = 128 * 1024                     # per-rank, block-aligned
        got = wire.exchange_wire_bytes(elems, n)
        scales = (elems // wire.BLOCK) * 4
        assert got == n * (2 * elems + 2 * scales)
        # padding counts: 1 element still pays a full n*BLOCK round
        assert wire.exchange_wire_bytes(1, n) == \
            wire.exchange_wire_bytes(n * wire.BLOCK, n)
        # fp32 allreduce: both internal legs at 4 B/elem
        payload = n * elems * 4
        assert wire.allreduce_wire_bytes(payload, 4, n, "") == 2 * payload
        # the headline ratio: int8 < 0.3x fp32 for block-aligned payloads
        ratio = wire.allreduce_wire_bytes(payload, 4, n, "int8") \
            / wire.allreduce_wire_bytes(payload, 4, n, "")
        assert ratio < 0.3

    def test_registry_and_one_shot(self, clean_wire):
        assert wire.wire_dtype_for("global", default="") == ""
        assert wire.set_wire_dtype("int8") == "int8"
        assert wire.wire_dtype_for("global") == "int8"
        assert wire.wire_dtype_for("set1", default="bfloat16") == "bfloat16"
        wire.set_wire_dtype("", "global")
        assert wire.wire_dtype_for("global", default="int8") == ""
        with pytest.raises(ValueError):
            wire.set_wire_dtype("int4")
        wire.request_wire_once("int8")
        assert wire.consume_wire_request() == "int8"
        assert wire.consume_wire_request() is None   # one-shot


class TestBlockScaledAllreduce:
    def _run(self, hvd, fn, x):
        mesh = hvd.global_process_set.mesh
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("hvd"),
                                  out_specs=P("hvd"), check_vma=False))
        return np.asarray(f(x))

    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_matches_exact_psum_within_bound(self, hvd, fmt):
        n = hvd.size()
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((n, 4096)), jnp.float32)

        def quant(v):
            out, _ = wire.block_scaled_allreduce(
                v.reshape(-1), axis_name="hvd", wire=fmt, average=True)
            return out.reshape(v.shape)

        got = self._run(hvd, quant, x)
        exact = np.asarray(x).mean(axis=0)
        rel = np.abs(got[0] - exact).max() / (np.abs(exact).max() + 1e-9)
        assert rel < (0.02 if fmt == "int8" else 0.1), rel

    def test_prescale_postscale_average_order(self, hvd):
        n = hvd.size()
        x = jnp.ones((n, 2048), jnp.float32)

        def quant(v):
            out, _ = wire.block_scaled_allreduce(
                v.reshape(-1), axis_name="hvd", wire="int8", average=True,
                prescale_factor=2.0, postscale_factor=0.5)
            return out.reshape(v.shape)

        got = self._run(hvd, quant, x)
        # mean(2 * 1) * 0.5 == 1 exactly representable in int8 blocks
        assert np.allclose(got, 1.0, atol=1e-5)

    def test_error_feedback_residual_roundtrip(self, hvd):
        """The returned residual is exactly what the wire dropped: adding
        it to a second identical round makes the two-round SUM match two
        exact rounds far better than two plain quantized rounds."""
        n = hvd.size()
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((n, 4096)), jnp.float32)

        def two_rounds_ef(v):
            flat = v.reshape(-1)
            o1, r = wire.block_scaled_allreduce(
                flat, residual=jnp.zeros_like(flat), axis_name="hvd",
                wire="int8")
            o2, _ = wire.block_scaled_allreduce(flat, residual=r,
                                                axis_name="hvd",
                                                wire="int8")
            return (o1 + o2).reshape(v.shape)

        def two_rounds_plain(v):
            flat = v.reshape(-1)
            o1, _ = wire.block_scaled_allreduce(flat, axis_name="hvd",
                                                wire="int8")
            o2, _ = wire.block_scaled_allreduce(flat, axis_name="hvd",
                                                wire="int8")
            return (o1 + o2).reshape(v.shape)

        exact = 2 * np.asarray(x).sum(axis=0)
        err_ef = np.abs(self._run(hvd, two_rounds_ef, x)[0] - exact).max()
        err_plain = np.abs(
            self._run(hvd, two_rounds_plain, x)[0] - exact).max()
        # plain pays the full quantization error twice; EF's second round
        # re-injects the first round's error, leaving ~one round's worth.
        assert err_ef < err_plain


class TestEagerWireRouting:
    def test_registry_flip_quantizes_and_restores(self, hvd, clean_wire):
        n = hvd.size()
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((n, 2 * n * wire.BLOCK)),
                        jnp.float32)
        exact = np.asarray(hvd.allreduce(x, op=hvd.Average))
        before = _wire_events(hvd).get(
            (("dtype", "int8"), ("path", "eager")), 0)
        hvd.set_wire_dtype("int8")
        got = np.asarray(hvd.allreduce(x, op=hvd.Average))
        after = _wire_events(hvd).get(
            (("dtype", "int8"), ("path", "eager")), 0)
        assert after == before + 1
        rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
        assert 0 < rel < 0.05   # lossy but close
        hvd.set_wire_dtype("")
        restored = np.asarray(hvd.allreduce(x, op=hvd.Average))
        assert np.array_equal(restored, exact)

    def test_small_payload_stays_exact(self, hvd, clean_wire):
        hvd.set_wire_dtype("int8")
        n = hvd.size()
        x = jnp.ones((n, 8), jnp.float32)   # << one BLOCK per rank
        before = _wire_events(hvd).get(
            (("dtype", "int8"), ("path", "eager")), 0)
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum))
        assert np.array_equal(out, np.full((n, 8), n, np.float32))
        assert _wire_events(hvd).get(
            (("dtype", "int8"), ("path", "eager")), 0) == before

    def test_non_linear_ops_never_quantize(self, hvd, clean_wire):
        hvd.set_wire_dtype("int8")
        n = hvd.size()
        x = jnp.tile(jnp.arange(n, dtype=jnp.float32)[:, None],
                     (1, 2 * n * wire.BLOCK))
        out = np.asarray(hvd.allreduce(x, op=hvd.Max))
        assert np.array_equal(out, np.full_like(out, n - 1))

    def test_compression_int8_one_shot_route(self, hvd, clean_wire):
        """Compression.int8's eager refusal is lifted: compress() routes
        the NEXT allreduce through the wire tier (and only that one)."""
        n = hvd.size()
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((n, n * wire.BLOCK)),
                        jnp.float32)
        import warnings as _warnings
        key = (("dtype", "int8"), ("path", "eager"))
        before = _wire_events(hvd).get(key, 0)
        with _warnings.catch_warnings(record=True) as record:
            _warnings.simplefilter("always")
            t, ctx = hvd.Compression.int8.compress(x)
            out = hvd.Compression.int8.decompress(
                hvd.allreduce(t, op=hvd.Average), ctx)
        assert not [w for w in record
                    if "UNCOMPRESSED" in str(w.message)], \
            "the stale not-honored warning is gone"
        assert _wire_events(hvd).get(key, 0) == before + 1
        exact = np.asarray(x).mean(axis=0)
        rel = np.abs(np.asarray(out)[0] - exact).max() \
            / (np.abs(exact).max() + 1e-9)
        assert rel < 0.05
        # the request was one-shot: the next plain allreduce is exact
        again = np.asarray(hvd.allreduce(x, op=hvd.Average))
        assert np.array_equal(again[0], exact)


class TestAllThreePaths:
    def test_one_run_shows_eager_fused_and_jit_events(self, hvd,
                                                      clean_wire):
        """Acceptance: int8 wire works on all three dispatch paths,
        verified by wire_compression_events_total{path} carrying all
        three labels in one run."""
        from horovod_tpu.ops import fusion
        from horovod_tpu.parallel.strategies import scaled_allreduce_int8
        n = hvd.size()
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((n, n * wire.BLOCK)),
                        jnp.float32)
        exact = np.asarray(x).mean(axis=0)

        hvd.set_wire_dtype("int8")
        eager = np.asarray(hvd.allreduce(x, op=hvd.Average))

        rt = fusion.get_runtime()
        prev = rt.wire_dtype
        rt.wire_dtype = jnp.int8
        try:
            fused = np.asarray(
                hvd.allreduce_async(x, op=hvd.Average,
                                    name="wire3").synchronize())
        finally:
            rt.wire_dtype = prev

        mesh = hvd.global_process_set.mesh
        f = jax.jit(jax.shard_map(
            lambda v: scaled_allreduce_int8(
                v.reshape(-1), axis_name="hvd",
                average=True).reshape(v.shape),
            mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
            check_vma=False))
        injit = np.asarray(f(x))

        for got in (eager, fused, injit):
            rel = np.abs(got[0] - exact).max() / (np.abs(exact).max() + 1e-9)
            assert rel < 0.05, rel
        ev = _wire_events(hvd)
        got_paths = {dict(k).get("path") for k in ev
                     if dict(k).get("dtype") == "int8"}
        assert {"eager", "fused", "jit"} <= got_paths, ev


class TestErrorFeedbackLifecycle:
    def test_residuals_zeroed_on_clear_program_caches(self, hvd,
                                                      clean_wire):
        """Elastic-reset contract: a resized mesh must not replay stale
        residuals — clear_program_caches (wired through
        basics.teardown_distributed) empties the store."""
        from horovod_tpu.ops import collective_ops
        n = hvd.size()
        x = jnp.ones((n, n * wire.BLOCK), jnp.float32) * 0.37
        hvd.set_wire_dtype("int8")
        hvd.allreduce(x, op=hvd.Average)
        assert wire.ef_keys(), "EF residual should be stored after dispatch"
        collective_ops.clear_program_caches()
        assert wire.ef_keys() == []

    def test_ef_disabled_keeps_store_empty(self, hvd, clean_wire):
        clean_wire.wire_error_feedback = False
        n = hvd.size()
        x = jnp.ones((n, n * wire.BLOCK), jnp.float32)
        hvd.set_wire_dtype("int8")
        hvd.allreduce(x, op=hvd.Average)
        assert wire.ef_keys() == []

    def test_fused_bucket_residual_lifecycle(self, hvd, clean_wire):
        from horovod_tpu.ops import collective_ops, fusion
        n = hvd.size()
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.standard_normal((n, 2 * n * wire.BLOCK)),
                        jnp.float32)
        rt = fusion.get_runtime()
        prev = rt.wire_dtype
        rt.wire_dtype = jnp.int8
        try:
            h = hvd.allreduce_async(x, op=hvd.Average, name="eflife")
            h.synchronize()
        finally:
            rt.wire_dtype = prev
        assert any(k[0] == "fusion" for k in wire.ef_keys())
        collective_ops.clear_program_caches()
        assert wire.ef_keys() == []


class TestConvergenceParity:
    def test_int8_ef_matches_fp32_and_beats_plain_int8(self, hvd,
                                                       clean_wire):
        """CPU-tier convergence parity on the eager path (the 8-proc
        cluster leg below runs the same scenario across processes):
        int8+error-feedback tracks the fp32 trajectory within tolerance
        AND measurably closer than plain int8 on the same run."""
        n, D = hvd.size(), 2 * hvd.size() * wire.BLOCK
        rng = np.random.default_rng(7)
        t = rng.standard_normal((n, D)).astype(np.float32)
        outliers = rng.random((n, D)) < 0.01
        t = t + outliers * rng.standard_normal((n, D)).astype(np.float32) \
            * 200.0
        s = (0.5 + rng.random((n, D))).astype(np.float32)
        t_j, s_j = jnp.asarray(t), jnp.asarray(s)
        cfg = clean_wire

        def train(steps=60, lr=0.6):
            w = jnp.zeros(D, jnp.float32)
            for _ in range(steps):
                grads = s_j * (w[None, :] - t_j)
                g = hvd.allreduce(grads, op=hvd.Average)
                w = w - lr * g[0]
            return np.asarray(w)

        hvd.set_wire_dtype("")
        w_fp32 = train()
        hvd.set_wire_dtype("int8")
        cfg.wire_error_feedback = True
        wire.reset_error_feedback()
        w_ef = train()
        cfg.wire_error_feedback = False
        wire.reset_error_feedback()
        w_plain = train()
        hvd.set_wire_dtype("")

        ref = np.linalg.norm(w_fp32) + 1e-12
        d_ef = float(np.linalg.norm(w_ef - w_fp32) / ref)
        d_plain = float(np.linalg.norm(w_plain - w_fp32) / ref)
        assert d_ef < 0.05, f"int8+EF diverged from fp32: {d_ef}"
        assert d_ef < 0.9 * d_plain, \
            f"error feedback not measurably better: ef={d_ef} " \
            f"plain={d_plain}"


class TestReviewRegressions:
    def test_complex_payload_keeps_exact_wire(self, hvd, clean_wire):
        """_is_float admits complexfloating (needed for Average
        validation), but the block quantizer's abs/round math drops the
        imaginary part — a complex Sum allreduce big enough to qualify
        must REFUSE the quantized wire and stay exact (the static cost
        model already prices it as exact; PR-11 review reproduction:
        expected (1+2j), got (1+0j))."""
        n = hvd.size()
        x = jnp.full((n, n * wire.BLOCK), 1.0 + 2.0j, jnp.complex64)
        key = (("dtype", "int8"), ("path", "eager"))
        before = _wire_events(hvd).get(key, 0)
        hvd.set_wire_dtype("int8")
        try:
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum))
        finally:
            hvd.set_wire_dtype("")
        np.testing.assert_allclose(out[0], n * (1.0 + 2.0j), rtol=1e-6)
        assert _wire_events(hvd).get(key, 0) == before

    def test_bf16_bucket_rides_the_fused_exchange(self, hvd, clean_wire):
        """ml_dtypes bfloat16 is not np.floating — the fused eligibility
        check must use jnp.issubdtype or the COMMON bf16-training case
        silently never quantizes."""
        from horovod_tpu.ops import fusion
        n = hvd.size()
        x = jnp.ones((n, 2 * n * wire.BLOCK), jnp.bfloat16) * 0.5
        rt = fusion.get_runtime()
        prev = rt.wire_dtype
        rt.wire_dtype = jnp.int8
        key = (("dtype", "int8"), ("path", "fused"))
        before = _wire_events(hvd).get(key, 0)
        try:
            out = hvd.allreduce_async(x, op=hvd.Average,
                                      name="bf16q").synchronize()
        finally:
            rt.wire_dtype = prev
        assert _wire_events(hvd).get(key, 0) == before + 1
        assert np.allclose(np.asarray(out, np.float32), 0.5, atol=0.01)

    def test_user_pin_survives_flush_boundary_sync(self, hvd, clean_wire):
        """hvd.set_wire_dtype is the documented mid-run A/B bisect: a
        fusion flush (the runtime/autotuner sync site) must not stomp an
        explicit user pin back to the runtime's wire."""
        from horovod_tpu.ops import fusion
        n = hvd.size()
        rt = fusion.get_runtime()
        prev = rt.wire_dtype
        rt.wire_dtype = jnp.int8
        try:
            hvd.set_wire_dtype("")      # the user's explicit A/B pin
            hvd.allreduce_async(jnp.ones((n, n * wire.BLOCK), jnp.float32),
                                op=hvd.Sum, name="pin").synchronize()
            assert wire.wire_dtype_for("global", default="int8") == ""
            # without a pin the same flush DOES adopt (boundary test
            # above); runtime_sync must also report the pinned value
            assert wire.runtime_sync_wire_dtype("int8") == ""
        finally:
            rt.wire_dtype = prev

    def test_grouped_async_consumes_one_shot(self, hvd, clean_wire):
        """Compression.int8's one-shot must be consumed by the grouped
        async entry point too — never leak to the next unrelated eager
        dispatch."""
        n = hvd.size()
        xs = [jnp.ones((n, n * wire.BLOCK), jnp.float32) for _ in range(2)]
        key = (("dtype", "int8"), ("path", "eager"))
        before = _wire_events(hvd).get(key, 0)
        hvd.Compression.int8.compress(xs[0])
        h = hvd.grouped_allreduce_async(xs, op=hvd.Sum, name="grp8")
        outs = h.synchronize()
        assert wire.consume_wire_request() is None   # consumed, not leaked
        assert _wire_events(hvd).get(key, 0) == before + 1
        for o in outs:
            assert np.allclose(np.asarray(o), n, rtol=0.02)
        # the NEXT plain allreduce is exact (no leaked request)
        exact = np.asarray(hvd.allreduce(xs[0], op=hvd.Sum))
        assert np.array_equal(exact, np.full_like(exact, n))

    def test_ef_store_evicts_one_not_all(self):
        wire.reset_error_feedback()
        try:
            for i in range(wire._EF_CAP):
                wire.ef_put(("k", i), i)
            wire.ef_put(("k", wire._EF_CAP), "new")
            keys = wire.ef_keys()
            assert len(keys) == wire._EF_CAP
            assert ("k", 0) not in keys          # oldest evicted
            assert ("k", 1) in keys              # the rest survive
            assert ("k", wire._EF_CAP) in keys
        finally:
            wire.reset_error_feedback()

    def test_fp8_is_quantized(self):
        assert wire.is_quantized("fp8")
        assert not wire.is_quantized("bfloat16")


class TestTuningBoundaryFlip:
    def test_flush_snapshot_adopts_into_eager_registry(self, hvd,
                                                       clean_wire):
        """The autotuner's wire decision lands in FusionRuntime.wire_dtype
        and takes effect at the next flush — whose knob snapshot must also
        steer the EAGER path (the per-process-set registry), so eager and
        fused programs flip at the same boundary."""
        from horovod_tpu.ops import fusion
        n = hvd.size()
        x = jnp.ones((n, n * wire.BLOCK), jnp.float32)
        rt = fusion.get_runtime()
        prev = rt.wire_dtype
        rt.wire_dtype = jnp.int8      # the ParameterManager's apply site
        try:
            hvd.allreduce_async(x, op=hvd.Sum,
                                name="fliptest").synchronize()
            assert wire.wire_dtype_for("global") == "int8"
            key = (("dtype", "int8"), ("path", "eager"))
            before = _wire_events(hvd).get(key, 0)
            hvd.allreduce(x, op=hvd.Sum)       # eager follows the flip
            assert _wire_events(hvd).get(key, 0) == before + 1
        finally:
            rt.wire_dtype = prev

    def test_check_program_cross_check_after_flip(self, hvd, clean_wire):
        """check_program cross-check of the flip: the predicted per-rank
        collective streams stay identical under either wire dtype — a
        registry flip is a program-key change, never a stream change, so
        no rank can desync at the boundary."""
        from horovod_tpu.analysis import events as an_events
        n = hvd.size()
        x = np.ones((n, n * wire.BLOCK), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        hvd.set_wire_dtype("")
        rep_fp32 = hvd.check_program(step, (x,), world_size=n)
        hvd.set_wire_dtype("int8")
        rep_int8 = hvd.check_program(step, (x,), world_size=n)
        for rep in (rep_fp32, rep_int8):
            assert not [f for f in rep.findings
                        if f.severity == "error"], rep.findings
        h32 = {r: an_events.sequence_hash(seq)
               for r, seq in rep_fp32.sequences.items()}
        h8 = {r: an_events.sequence_hash(seq)
              for r, seq in rep_int8.sequences.items()}
        assert len(set(h32.values())) == 1     # rank-invariant
        assert h32 == h8                       # flip-invariant


def _boundary_flip_worker():
    """2-proc leg: the COORDINATOR flips the wire knob (the tuner's apply
    site); the follower adopts it from the flush boundary — and the next
    SYNC eager collective compiles the same quantized program on both,
    or this hangs/mismatches."""
    import numpy as np

    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.ops import fusion, wire as _w

    hvd.init()
    n = hvd.size()
    rt = fusion.get_runtime()
    x = jnp.ones((1, n * _w.BLOCK), jnp.float32)
    if hvd.cross_rank() == 0:
        rt.wire_dtype = jnp.int8          # coordinator-only decision
    h = hvd.allreduce_async(x, op=hvd.Sum, name="flip")
    h.synchronize()                       # flush -> boundary carries int8
    out = hvd.allreduce(x, op=hvd.Sum)    # sync eager after the boundary
    return {"wire": _w.wire_dtype_for("global"),
            "sum": float(np.asarray(out).sum()),
            "rank": hvd.cross_rank()}


@pytest.mark.slow
class TestTuningBoundaryFlip2Proc:
    def test_coordinator_flip_adopted_without_desync(self, shared_cluster):
        out = shared_cluster("localhost:1,127.0.0.1:1").run(
            _boundary_flip_worker, timeout=300)
        assert len(out) == 2
        n, blk = 2, wire.BLOCK
        for r in out:
            assert r["wire"] == "int8", out
            # quantized sum of all-ones: n per element, within block error
            assert abs(r["sum"] - n * blk * n) < 0.01 * n * blk * n, out


def _parity_worker(steps, lr):
    """8-process convergence-parity leg (runs inside runner.run workers —
    importable by name like chaos.soak.soak_train)."""
    import numpy as np

    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.common import basics
    from horovod_tpu.ops import wire as _w

    hvd.init()
    n = hvd.size()
    me = hvd.cross_rank()
    D = 2 * n * _w.BLOCK
    rng = np.random.default_rng(7)
    t = rng.standard_normal((n, D)).astype(np.float32)
    outliers = rng.random((n, D)) < 0.01
    t = t + outliers * rng.standard_normal((n, D)).astype(np.float32) * 200.0
    s = (0.5 + rng.random((n, D))).astype(np.float32)
    cfg = basics.config()

    def train():
        w = np.zeros(D, np.float32)
        for _ in range(steps):
            grads = s[me:me + 1] * (w[None, :] - t[me:me + 1])
            g = hvd.allreduce(jnp.asarray(grads), op=hvd.Average)
            w = w - lr * np.asarray(g)[0]
        return w

    hvd.set_wire_dtype("")
    w_fp32 = train()
    hvd.set_wire_dtype("int8")
    cfg.wire_error_feedback = True
    _w.reset_error_feedback()
    w_ef = train()
    cfg.wire_error_feedback = False
    _w.reset_error_feedback()
    w_plain = train()
    hvd.set_wire_dtype("")
    ref = float(np.linalg.norm(w_fp32)) + 1e-12
    snap = hvd.metrics_snapshot()
    paths = sorted({ser["labels"]["path"]
                    for ser in snap.get("wire_compression_events_total",
                                        {}).get("series", ())})
    return {
        "d_ef": float(np.linalg.norm(w_ef - w_fp32)) / ref,
        "d_plain": float(np.linalg.norm(w_plain - w_fp32)) / ref,
        "paths": paths,
        "rank": me,
    }


@pytest.mark.slow
class TestConvergenceParity8Proc:
    def test_cluster_parity_int8_ef_vs_fp32(self, shared_cluster):
        """8-process CPU-tier leg of the parity acceptance: every worker's
        int8+EF trajectory matches its fp32 one within tolerance and beats
        plain int8 — across real multi-process eager dispatch (join
        fences, boundary discipline, make_array staging)."""
        cluster = shared_cluster(
            "localhost:1,127.0.0.1:1,127.0.0.2:1,127.0.0.3:1,"
            "127.0.0.4:1,127.0.0.5:1,127.0.0.6:1,127.0.0.7:1")
        out = cluster.run(_parity_worker, args=(40, 0.6), timeout=600)
        assert len(out) == 8
        for r in out:
            assert r["d_ef"] < 0.05, r
            assert r["d_ef"] < 0.9 * r["d_plain"], r
            assert "eager" in r["paths"], r

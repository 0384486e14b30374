"""``parallel.ssm``: the Mamba-2 scan in chunked form against the recurrence
written one token at a time, the scan's kernels (``ops/pallas/ssm_scan.py``,
in the interpreter here) against both, the rule that picks between the two
ways from a call's shapes, the mixer's parts against plain loops, and what
the mixer tells the metrics registry. Small sizes in the published ratios
(heads in groups, a state wider than a head), float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import ssm
from horovod_tpu.parallel.ssm import (CausalConv1d, GatedGroupRMSNorm,
                                      Mamba2Mixer, chunked_scan, ssm_scan)

B, H, P, G, N, CHUNK = 2, 4, 8, 2, 16, 16
NAMES = ("x", "dt", "A", "B", "C", "D")


def token_by_token(x, dt, A, Bm, Cm, D):
    """The recurrence as the module's docstring writes it: S_t = a_t S_{t-1}
    + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, one position at a time."""
    b, length, heads, head = x.shape
    per_group = heads // Bm.shape[2]
    Bh, Ch = (jnp.repeat(t, per_group, axis=2) for t in (Bm, Cm))

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + D[:, None] * x_t
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, head, Bm.shape[-1])),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(rng, length, dtype=jnp.float32, sizes=(B, H, P, G, N),
            decays=(1, 16)):
    b, heads, head, groups, state = sizes

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    return (normal(b, length, heads, head),
            jnp.asarray(rng.uniform(0.01, 0.5, (b, length, heads)),
                        jnp.float32),
            -jnp.asarray(rng.uniform(*decays, (heads,)), jnp.float32),
            normal(b, length, groups, state),
            normal(b, length, groups, state),
            jnp.asarray(rng.standard_normal((heads,)), jnp.float32))


class TestChunkedScan:
    @pytest.mark.parametrize("length", [
        pytest.param(CHUNK, id="one_chunk"),
        pytest.param(4 * CHUNK, id="several_chunks"),
        pytest.param(3 * CHUNK + 2, id="last_chunk_not_whole"),
        pytest.param(7, id="shorter_than_a_chunk")])
    def test_outputs_and_every_gradient(self, rng, length):
        """Against the recurrence one token at a time: the output and the
        gradient of a fixed weighting of it with respect to x, dt, A, B, C
        and D, to 1e-5 of each one's largest entry (float32 sums in another
        order; a decay dropped from one chunk to the next moves them by
        tenths)."""
        args = _inputs(rng, length)
        w = jnp.asarray(rng.standard_normal((B, length, H, P)), jnp.float32)
        got = ssm_scan(*args, CHUNK)
        want = token_by_token(*args)
        assert got.shape == want.shape == (B, length, H, P)
        np.testing.assert_allclose(got, want,
                                   atol=1e-5 * float(jnp.abs(want).max()))
        g_got = jax.grad(lambda *a: jnp.sum(w * ssm_scan(*a, CHUNK)),
                         range(6))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(w * token_by_token(*a)),
                          range(6))(*args)
        for name, a, b in zip(NAMES, g_got, g_want):
            assert float(jnp.abs(b).max()) > 0, name
            np.testing.assert_allclose(
                a, b, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)

    def test_the_state_crosses_chunks(self, rng):
        """With slow decays the first chunk's tokens reach the last
        chunk's outputs: zeroing them changes those by much."""
        x, dt, _, Bm, Cm, D = _inputs(rng, 4 * CHUNK)
        A = -jnp.full((H,), 0.05)
        whole = ssm_scan(x, dt, A, Bm, Cm, D, CHUNK)
        cut = ssm_scan(x.at[:, :CHUNK].set(0), dt, A, Bm, Cm, D, CHUNK)
        last = slice(3 * CHUNK, None)
        assert float(jnp.abs(whole[:, last] - cut[:, last]).max()) \
            > 0.05 * float(jnp.abs(whole[:, last]).max())

    def test_bfloat16_activations_stay_close(self, rng):
        """bfloat16 x, B, C with float32 dt, A and states: within 3 % of
        the float32 result's largest entry (three roundings of 2^-9 a
        product, sums of up to 16 + 16 terms)."""
        args = _inputs(rng, 4 * CHUNK)
        want = ssm_scan(*args, CHUNK)
        low = [a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
               for i, a in enumerate(args)]
        got = ssm_scan(*low, CHUNK)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   atol=0.03 * float(jnp.abs(want).max()))

    def test_heads_must_fill_the_groups(self, rng):
        x, dt, A, Bm, Cm, D = _inputs(rng, CHUNK)
        with pytest.raises(ValueError, match="no whole multiple"):
            ssm_scan(x[:, :, :3], dt[:, :, :3], A[:3], Bm, Cm, D[:3], CHUNK)

    def test_chunk_state_bytes_at_the_published_sizes(self):
        # 2 sequences x 64 chunks x 64 heads x 64 x 128 float32
        assert ssm.chunk_states_bytes(2, 8192, 64, 64, 128, 128) \
            == 268_435_456
        assert ssm.chunk_states_bytes(1, 130, 2, 4, 8, 128) == 4 * 2 * 2 * 32


# (sequences, length, heads, head size, groups, state, chunk): calls on the
# kernels' grid, more than one sequence, group and chunk
_ON_THE_GRID = {
    "two_heads_a_lane_tile": (2, 384, 4, 64, 2, 128, 128),
    "a_head_a_lane_tile": (1, 256, 2, 128, 1, 128, 128),
}


def _grid_inputs(rng, call, dtype):
    """Decays slow enough that a chunk's state is a fifth of itself two
    chunks on."""
    b, length, *sizes, _ = _ON_THE_GRID[call]
    return _inputs(rng, length, dtype, (b, *sizes), decays=(0.02, 0.3))


def _as_mosaic_rounds(fn, *args):
    """``fn(*args)`` compiled so that every rounding the kernels write
    down is made, as Mosaic makes it on the chip. The interpreter's XLA
    otherwise drops a float32 -> bfloat16 -> float32 round trip where it
    fuses (excess precision) and keeps it where a product reads the
    bfloat16 operand: the two sides of a cancelling sum then differ by a
    rounding, which the reverse running sum behind ``A``'s gradient
    amplifies to several percent."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


class TestScanKernels:
    """``ssm_scan`` on the kernels' grid runs the kernels (the Pallas
    interpreter on the CPU)."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("call", sorted(_ON_THE_GRID))
    def test_outputs_and_every_gradient(self, rng, call, dtype):
        """``y`` and the gradients of a fixed weighting of it with respect
        to x, dt, A, B, C and D, against the ``jax.numpy`` chunked form and
        against the recurrence one token at a time, both in float32
        arithmetic on the same values. float32 activations: to 1e-5 of each
        one's largest entry. bfloat16 activations: to 3 % (roundings of
        2^-9 in the products' operands, as the chunked form makes them);
        every row of ``y`` is written."""
        chunk = _ON_THE_GRID[call][-1]
        args = _grid_inputs(rng, call, jnp.dtype(dtype))
        assert ssm.scan_path(args[0].shape, args[3].shape[2],
                             args[3].shape[3], chunk,
                             jnp.dtype(dtype).itemsize)[0] == 1
        exact = tuple(a.astype(jnp.float32) for a in args)
        w = jnp.asarray(rng.standard_normal(args[0].shape), jnp.float32)
        tol = 1e-5 if dtype == "float32" else 0.03

        def weighed(fn):
            return lambda *a: jnp.sum(w * fn(*a).astype(jnp.float32))
        got = _as_mosaic_rounds(lambda *a: ssm_scan(*a, chunk), *args)
        assert got.dtype == jnp.dtype(dtype) and got.shape == args[0].shape
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
        g_got = _as_mosaic_rounds(
            jax.grad(weighed(lambda *a: ssm_scan(*a, chunk)), range(6)),
            *args)
        for oracle in (lambda *a: chunked_scan(*a, chunk), token_by_token):
            want = oracle(*exact)
            np.testing.assert_allclose(
                got.astype(jnp.float32), want,
                atol=tol * float(jnp.abs(want).max()))
            g_want = jax.grad(weighed(oracle), range(6))(*exact)
            for name, a, b in zip(NAMES, g_got, g_want):
                assert a.dtype == b.dtype or dtype == "bfloat16", name
                assert float(jnp.abs(b).max()) > 0, name
                np.testing.assert_allclose(
                    a.astype(jnp.float32), b,
                    atol=tol * float(jnp.abs(b).max()), err_msg=name)

    def test_the_state_crosses_chunks_both_ways(self, rng):
        """Forward, the first chunk's tokens reach the last chunk's outputs
        through the carried state; backward, the last chunk's outputs reach
        the first chunk's x, B and dt through the carried gradient, as in
        the recurrence."""
        call = "two_heads_a_lane_tile"
        chunk = _ON_THE_GRID[call][-1]
        args = _grid_inputs(rng, call, jnp.float32)
        whole = ssm_scan(*args, chunk)
        cut = ssm_scan(args[0].at[:, :chunk].set(0), *args[1:], chunk)
        last = slice(2 * chunk, None)
        assert float(jnp.abs(whole[:, last] - cut[:, last]).max()) \
            > 0.05 * float(jnp.abs(whole[:, last]).max())

        def of_the_last_chunk(fn):
            return lambda *a: jnp.sum(fn(*a)[:, last] ** 2)
        got = jax.grad(of_the_last_chunk(lambda *a: ssm_scan(*a, chunk)),
                       (0, 1, 3))(*args)
        want = jax.grad(of_the_last_chunk(token_by_token), (0, 1, 3))(*args)
        for name, a, b in zip(("x", "dt", "B"), got, want):
            first = float(jnp.abs(b[:, :chunk]).max())
            assert first > 1e-3 * float(jnp.abs(b).max()), name
            np.testing.assert_allclose(a[:, :chunk], b[:, :chunk],
                                       atol=1e-5 * float(jnp.abs(b).max()),
                                       err_msg=name)

    @pytest.mark.parametrize("call, path", [
        # (length, heads, head size, groups, state, chunk, itemsize)
        pytest.param((8192, 64, 64, 8, 128, 128, 2), (1, (128, 512, 128)),
                     id="the_nemotron_cells_call"),
        pytest.param((8192, 64, 64, 8, 128, 128, 4), (1, (128, 512, 128)),
                     id="the_cells_call_in_float32"),
        pytest.param((256, 2, 128, 1, 128, 128, 4), (1, (128, 256, 128)),
                     id="a_head_of_128"),
        pytest.param((128, 4, 64, 2, 128, 256, 2), (1, (128, 128, 128)),
                     id="one_chunk_shorter_than_chunk_size"),
        pytest.param((8192, 64, 64, 8, 128, 256, 2), (1, (256, 512, 128)),
                     id="chunks_of_256"),
        pytest.param((4 * CHUNK, H, P, G, N, CHUNK, 4), None,
                     id="this_files_tiny_shapes"),
        pytest.param((8194, 64, 64, 8, 128, 128, 2), None,
                     id="a_length_that_is_padded"),
        pytest.param((8192, 64, 64, 8, 128, 64, 2), None,
                     id="chunks_of_64"),
        pytest.param((8192, 64, 64, 8, 64, 128, 2), None,
                     id="a_state_of_64"),
        pytest.param((8192, 8, 64, 8, 128, 128, 2), None,
                     id="a_group_of_64_channels"),
        pytest.param((8192, 32, 96, 8, 128, 128, 2), None,
                     id="heads_of_96"),
        pytest.param((8192, 64, 64, 1, 128, 128, 2), None,
                     id="a_group_too_wide_for_vmem"),
    ])
    def test_the_path_is_read_off_the_shapes(self, call, path):
        length, heads, head, groups, state, chunk, itemsize = call
        assert ssm.scan_path((2, length, heads, head), groups, state, chunk,
                             itemsize) == (path or (0, (0, 0, 0)))


class TestMixerParts:
    def test_convolution_is_causal_and_depthwise(self, rng):
        x = jnp.asarray(rng.standard_normal((2, 11, 6)), jnp.float32)
        conv = CausalConv1d(4)
        params = conv.init(jax.random.PRNGKey(0), x)["params"]
        assert params["kernel"].shape == (4, 6) \
            and params["bias"].shape == (6,)
        assert float(jnp.abs(params["kernel"]).max()) <= 0.5
        params = dict(params, bias=jnp.arange(6, dtype=jnp.float32))
        got = conv.apply({"params": params}, x)
        want = np.zeros((2, 11, 6), np.float32)
        for t in range(11):
            for k in range(4):
                if t - 3 + k >= 0:
                    want[:, t] += np.asarray(params["kernel"][k]) \
                        * np.asarray(x[:, t - 3 + k])
            want[:, t] += np.arange(6)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_gated_norm_takes_the_mean_square_by_group(self, rng):
        y = jnp.asarray(rng.standard_normal((2, 5, 12)), jnp.float32)
        z = jnp.asarray(rng.standard_normal((2, 5, 12)), jnp.float32)
        norm = GatedGroupRMSNorm(3, 1e-5)
        scale = jnp.asarray(rng.uniform(0.5, 2, (12,)), jnp.float32)
        got = norm.apply({"params": {"scale": scale}}, y, z)
        g = np.asarray(y * z * jax.nn.sigmoid(z)).reshape(2, 5, 3, 4)
        want = g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got, want.reshape(2, 5, 12)
                                   * np.asarray(scale), atol=1e-5)


class TestMamba2Mixer:
    def _mixer(self):
        return Mamba2Mixer(32, H, P, N, G, chunk_size=CHUNK)

    def test_names_shapes_and_fresh_values(self):
        u = jnp.zeros((2, 40, 32))
        params = self._mixer().init(jax.random.PRNGKey(0), u)["params"]
        shapes = jax.tree.map(lambda a: a.shape, params)
        inner, conv_dim = H * P, H * P + 2 * G * N
        assert shapes == {
            "in_proj": {"kernel": (32, inner + conv_dim + H)},
            "conv": {"kernel": (4, conv_dim), "bias": (conv_dim,)},
            "dt_bias": (H,), "A_log": (H,), "D": (H,),
            "gate_norm": {"scale": (inner,)},
            "out_proj": {"kernel": (inner, 32)}}
        dt = jax.nn.softplus(params["dt_bias"])
        assert float(dt.min()) >= 1e-3 * 0.999 \
            and float(dt.max()) <= 1e-1 * 1.001
        a = jnp.exp(params["A_log"])
        assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
        assert bool(jnp.all(params["D"] == 1))

    def test_fresh_step_follows_the_fields(self):
        mixer = Mamba2Mixer(32, H, P, N, G, chunk_size=CHUNK,
                            time_step_min=0.01, time_step_max=0.05,
                            time_step_floor=0.02)
        params = mixer.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 40, 32)))["params"]
        dt = jax.nn.softplus(params["dt_bias"])
        assert 0.02 * 0.999 <= float(dt.min()) \
            and float(dt.max()) <= 0.05 * 1.001

    def test_against_its_parts_one_token_at_a_time(self, rng):
        """The mixer's output equals its equations with the recurrence run
        one token at a time."""
        mixer = self._mixer()
        u = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
        p = mixer.init(jax.random.PRNGKey(1), u)["params"]
        p = dict(p, D=jnp.asarray(rng.standard_normal((H,)), jnp.float32))
        inner = H * P
        zxbcdt = u @ p["in_proj"]["kernel"]
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N], -1)
        xbc = jax.nn.silu(CausalConv1d(4).apply({"params": p["conv"]}, xbc))
        x, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], -1)
        y = token_by_token(
            x.reshape(2, 40, H, P), jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]), Bm.reshape(2, 40, G, N),
            Cm.reshape(2, 40, G, N), p["D"])
        y = GatedGroupRMSNorm(G, 1e-5).apply(
            {"params": p["gate_norm"]}, y.reshape(2, 40, inner), z)
        want = y @ p["out_proj"]["kernel"]
        got = mixer.apply({"params": p}, u)
        np.testing.assert_allclose(got, want,
                                   atol=2e-5 * float(jnp.abs(want).max()))

    @pytest.mark.parametrize("path", [
        pytest.param(0, id="off_the_grid"),
        pytest.param(1, id="on_the_kernels_grid")])
    def test_gauges_say_what_was_traced(self, path):
        """Off the kernels' grid the chunked form writes float32 closing
        states; on it the kernels write none forward and the backward
        pass's first sweep one set in the activations' dtype."""
        from horovod_tpu import metrics
        if path:
            sizes = dict(heads=4, head_dim=64, state=128, groups=2,
                         chunk=128, chunks=2)
            mixer = Mamba2Mixer(32, 4, 64, 128, 2, chunk_size=128,
                                dtype=jnp.bfloat16)
            u, blocks, itemsize = jnp.zeros((2, 256, 32)), (128, 128, 128), 2
        else:
            sizes = dict(heads=H, head_dim=P, state=N, groups=G, chunk=CHUNK,
                         chunks=3)
            mixer, u, blocks, itemsize = self._mixer(), jnp.zeros(
                (2, 40, 32)), (0, 0, 0), 4
        mixer.apply(mixer.init(jax.random.PRNGKey(0), u), u)
        snap = metrics.snapshot()
        got = {s["labels"]["kind"]: s["value"]
               for s in snap["hvd_ssm_layer"]["series"]}
        assert got == sizes
        series = {s["labels"]["axis_size"]: s["value"]
                  for s in snap["hvd_ssm_chunk_state_bytes"]["series"]}
        assert series["1"] == itemsize * 2 * sizes["chunks"] \
            * sizes["heads"] * sizes["head_dim"] * sizes["state"]
        got = {s["labels"]["kind"]: s["value"]
               for s in snap["hvd_ssm_scan_path"]["series"]}
        assert got == dict(zip(("kernels", "positions", "channels", "state"),
                               (path, *blocks)))

    def test_scopes_name_the_mixers_parts(self):
        """Every scope a per-layer metric reads is on the path of some
        equation of the traced mixer, inside ``ssm.mixer``."""
        mixer = self._mixer()
        u = jnp.zeros((2, 40, 32))
        params = mixer.init(jax.random.PRNGKey(0), u)
        jaxpr = jax.make_jaxpr(lambda p, u: mixer.apply(p, u))(params, u)
        stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.eqns}
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                      "ssm.out_proj"):
            assert any(f"ssm.mixer/{scope}" in s for s in stacks), scope
        assert not any("hvd." in s for s in stacks)

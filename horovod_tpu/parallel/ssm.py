"""State-space layers: the Mamba-2 mixer and its scan in chunked form.

The selective state-space recurrence of Mamba-2, per head ``h`` with a
state ``S`` of (head size ``P``) x (state size ``N``), zero where a
sequence starts::

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        a_t = exp(dt_t A),  A < 0
    y_t = S_t C_t + D x_t

``B`` and ``C`` are shared by the heads of a group. Run one token at a time
this is 8192 dependent steps on a state that no matrix unit sees, so
:func:`ssm_scan` computes it by chunks of ``chunk`` tokens (the
state-space-duality form): inside a chunk the outputs are products under
the mask of decays ``exp(sum of dt A over (j, i])``, every chunk closes
with the state its own tokens add, the recurrence runs over those closing
states alone (``L / chunk`` steps), and the state a chunk opens with gives
the rest of its outputs. The decays and their running sums, ``dt`` and the
states stay float32; the products take the activations' dtype and
accumulate in float32. Two ways run the same chunks, picked from the
call's shapes alone (:func:`scan_path`): the Pallas kernels of
``ops/pallas/ssm_scan.py``, which keep the masks and the carried state in
VMEM, and :func:`chunked_scan`, ``jax.numpy`` products with masks and chunk
states in HBM, for calls off the kernels' grid and as the tests' oracle.

:class:`Mamba2Mixer` is the layer round it: one input projection to
``[z | x B C | dt]``, a causal depthwise convolution and SiLU over
``x B C``, the scan, ``RMSNorm_groups(y * silu(z))`` and the output
projection. Scopes (``ssm.mixer`` > ``ssm.in_proj``, ``ssm.conv``,
``ssm.scan``, ``ssm.gate_norm``, ``ssm.out_proj``) are not
``hvd.``-prefixed: the phase of an op is its innermost ``hvd.`` scope.
"""

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pallas import ssm_scan as kernels
from horovod_tpu.trace.scopes import scope

# A fresh head's ``-A`` is drawn uniformly from here (Mamba-2's range; no
# published config has a key for it).
A_RANGE = (1.0, 16.0)


def chunk_states_bytes(batch, length, heads, head_dim, state, chunk,
                       itemsize=4):
    """Bytes of one set of chunk states, one (``head_dim``, ``state``)
    matrix a head and chunk, at ``itemsize`` bytes an element: float32 as
    :func:`chunked_scan` writes its closing states."""
    return itemsize * batch * -(-length // chunk) * heads * head_dim * state


def scan_path(x_shape, groups, state, chunk, itemsize):
    """``(path, blocks)`` of an :func:`ssm_scan` call, read off its shapes.
    Path 1: the kernels of ``ops/pallas/ssm_scan.py`` in ``blocks``
    (positions, channels and state columns a grid step), wherever their
    rule admits the call (``pick_blocks``: a whole number of chunks of a
    multiple of 128, a group's channels and the state's size multiples of
    128, all of it inside the VMEM budget). Path 0: :func:`chunked_scan`,
    ``blocks`` zeros, for every other call."""
    _, length, heads, head_dim = x_shape
    blocks = kernels.pick_blocks(length, heads, head_dim, groups, state,
                                 min(chunk, length), itemsize)
    return (0, (0, 0, 0)) if blocks is None else (1, blocks)


def ssm_scan(x, dt, A, B, C, D, chunk):
    """``y`` (b, L, H, P) of the recurrence above, in chunks of ``chunk``.

    ``x`` (b, L, H, P) and ``B``, ``C`` (b, L, G, N) in the activations'
    dtype (head ``h`` reads group ``h // (H / G)``); ``dt`` (b, L, H), the
    positive step, and ``A``, ``D`` (H,) float32. A length that is no
    whole number of chunks is padded with steps of ``dt`` 0, which neither
    decay the state nor add to it. One of two ways runs the chunks
    (:func:`scan_path`), both differentiable in all six: the kernels under
    a ``custom_vjp`` whose backward pass keeps the six alone, or
    :func:`chunked_scan` as it stands.
    """
    H, (G, N) = x.shape[2], B.shape[-2:]
    if H % G:
        raise ValueError(f"{H} heads are no whole multiple of {G} groups")
    path, blocks = scan_path(x.shape, G, N, chunk, x.dtype.itemsize)
    from horovod_tpu.metrics import instruments as hvd_metrics
    hvd_metrics.record_ssm_scan_path(path, blocks)
    if path == 1:
        return kernels.scan(x, dt, A, B, C, D, min(chunk, x.shape[1]))
    return chunked_scan(x, dt, A, B, C, D, chunk)


def chunked_scan(x, dt, A, B, C, D, chunk):
    """:func:`ssm_scan` as ``jax.numpy`` products: the decay masks and two
    sets of float32 chunk states go through HBM. Differentiable as it
    stands, keeping all of them."""
    b, length, H, P = x.shape
    G, N = B.shape[-2:]
    R, Q = H // G, min(chunk, length)
    pad = -length % Q
    if pad:
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, B, C))
    nc = (length + pad) // Q
    dtype = x.dtype
    dt = dt.astype(jnp.float32).reshape(b, nc, Q, H)
    xc = x.reshape(b, nc, Q, H, P)
    Bc, Cc = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)

    # Running sums of the log decays inside each chunk, float32, with the
    # positions of a chunk last: (b, nc, H, Q).
    cs = jnp.cumsum(jnp.swapaxes(dt, 2, 3)
                    * A.astype(jnp.float32)[:, None], axis=-1)
    total = cs[..., -1]                                     # (b, nc, H)
    by_position = jnp.swapaxes(cs, 2, 3)                    # (b, nc, Q, H)
    xdt = xc * dt[..., None].astype(dtype)                  # dt_j x_j

    # Inside a chunk: (C_i . B_j) exp(cs_i - cs_j) for j <= i, times dt_j x_j.
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=jnp.float32)
    later = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(later, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                    # (b,nc,H,Q,Q)
    mask = (cb[:, :, :, None] * decay.reshape(b, nc, G, R, Q, Q)).astype(
        dtype).reshape(b, nc, H, Q, Q)
    y = jnp.einsum("bchij,bcjhp->bcihp", mask, xdt,
                   preferred_element_type=jnp.float32)

    # What each chunk's own tokens leave in the state when it closes.
    to_end = jnp.exp(total[:, :, None] - by_position)       # (b, nc, Q, H)
    closing = jnp.einsum(
        "bcjgn,bcjgrp->cbgrpn", Bc,
        (xdt * to_end[..., None].astype(dtype)).reshape(b, nc, Q, G, R, P),
        preferred_element_type=jnp.float32)

    # The recurrence over the closing states: the state each chunk opens
    # with, float32.
    def carry_on(state, closed):
        decay_c, closing_c = closed
        return decay_c[..., None, None] * state + closing_c, state

    _, opening = lax.scan(
        carry_on, jnp.zeros((b, G, R, P, N), jnp.float32),
        (jnp.exp(jnp.moveaxis(total, 1, 0)).reshape(nc, b, G, R), closing))

    # The opening state's part of a chunk's outputs, and the skip.
    y = y + jnp.einsum("bcign,cbgrpn->bcigrp", Cc, opening.astype(dtype),
                       preferred_element_type=jnp.float32).reshape(
                           b, nc, Q, H, P) * jnp.exp(by_position)[..., None]
    y = y + D.astype(jnp.float32)[:, None] * xc.astype(jnp.float32)
    return y.reshape(b, nc * Q, H, P)[:, :length].astype(dtype)


class CausalConv1d(nn.Module):
    """Depthwise convolution over the sequence axis of (b, L, C): position
    ``t`` reads ``t - taps + 1 .. t`` (zeros before a sequence's start),
    tap ``taps - 1`` its own position; with a bias unless ``use_bias`` is
    False. A fresh kernel is uniform in +-1 / sqrt(taps)."""
    taps: int
    dtype: Any = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        channels, bound = x.shape[-1], 1.0 / math.sqrt(self.taps)
        kernel = self.param(
            "kernel", lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -bound, bound),
            (self.taps, channels))
        length = x.shape[1]
        padded = jnp.pad(x, ((0, 0), (self.taps - 1, 0), (0, 0)))
        out = 0
        if self.use_bias:
            out = jnp.asarray(self.param("bias", nn.initializers.zeros,
                                         (channels,)), self.dtype)
        for k in range(self.taps):
            out = out + padded[:, k:k + length] \
                * jnp.asarray(kernel[k], self.dtype)
        return out


class GatedGroupRMSNorm(nn.Module):
    """``RMSNorm(y * silu(z)) * scale`` with the mean square taken over
    each of ``groups`` equal runs of channels, in float32."""
    groups: int
    epsilon: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, z):
        channels = y.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (channels,))
        g = (y * nn.silu(z)).astype(jnp.float32)
        g = g.reshape(g.shape[:-1] + (self.groups, channels // self.groups))
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + self.epsilon)
        return (g.reshape(y.shape) * scale).astype(self.dtype)


def _fresh_dt_bias(step_min, step_max, floor):
    """Initializer: the inverse softplus of a step drawn log-uniformly in
    [``step_min``, ``step_max``] and floored at ``floor``."""
    def init(key, shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(step_min),
            math.log(step_max))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _fresh_a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer of ``num_heads`` heads of ``head_dim`` with a state
    of ``state_size`` a channel, ``num_groups`` groups of ``B`` and ``C``,
    on (b, L, ``hidden_size``). No bias but the convolution's. ``dt``, ``A``
    and the scan's decays and states are float32 whatever ``dtype``. A
    fresh head's step ``softplus(dt_bias)`` is drawn log-uniformly in
    [``time_step_min``, ``time_step_max``], floored at ``time_step_floor``
    (the published configs' keys, Mamba-2's values by default). Of the scan
    the backward pass keeps the convolution's output, the step and the
    scan's output alone (``jax.checkpoint``), always."""
    hidden_size: int
    num_heads: int
    head_dim: int
    state_size: int
    num_groups: int
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        H, P, G, N = (self.num_heads, self.head_dim, self.num_groups,
                      self.state_size)
        inner, b, length = H * P, u.shape[0], u.shape[1]
        chunk = min(self.chunk_size, length)
        from horovod_tpu.metrics import instruments as hvd_metrics
        # One set of chunk states through HBM: float32 closing states on
        # path 0; on path 1 the states the backward pass's first sweep
        # writes, in the dtype the products read them in.
        itemsize = jnp.dtype(self.dtype).itemsize
        path, _ = scan_path((b, length, H, P), G, N, chunk, itemsize)
        hvd_metrics.record_ssm_layer(
            H, P, N, G, chunk, -(-length // chunk),
            chunk_states_bytes(b, length, H, P, N, chunk,
                               itemsize if path else 4))
        with scope("ssm.mixer"):
            with scope("ssm.in_proj"):
                zxbcdt = nn.Dense(2 * inner + 2 * G * N + H, use_bias=False,
                                  dtype=self.dtype, name="in_proj")(u)
                z, xbc, dt = jnp.split(
                    zxbcdt, [inner, 2 * inner + 2 * G * N], axis=-1)
            with scope("ssm.conv"):
                xbc = CausalConv1d(self.conv_kernel, self.dtype,
                                   name="conv")(xbc)
            dt_bias = self.param("dt_bias", _fresh_dt_bias(
                self.time_step_min, self.time_step_max,
                self.time_step_floor), (H,))
            a_log = self.param("A_log", _fresh_a_log, (H,))
            skip = self.param("D", nn.initializers.ones, (H,))

            def activate_and_scan(xbc, step, A, skip):
                with scope("ssm.conv"):
                    x, B, C = jnp.split(nn.silu(xbc), [inner, inner + G * N],
                                        axis=-1)
                with scope("ssm.scan"):
                    return ssm_scan(x.reshape(b, length, H, P), step, A,
                                    B.reshape(b, length, G, N),
                                    C.reshape(b, length, G, N), skip, chunk)
            with scope("ssm.scan"):
                step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                A = -jnp.exp(a_log)
            # The backward pass starts from the convolution's output and
            # the step alone: x, B and C (as much again) are activated and
            # split a second time, and on path 0 the masks and chunk states
            # computed again. On path 1 that second pass runs no kernel:
            # the custom_vjp keeps its inputs, so its forward sweep is dead
            # code there.
            y = jax.checkpoint(activate_and_scan)(xbc, step, A, skip)
            with scope("ssm.gate_norm"):
                y = GatedGroupRMSNorm(G, self.norm_eps, self.dtype,
                                      name="gate_norm")(
                    y.reshape(b, length, inner), z)
            with scope("ssm.out_proj"):
                return nn.Dense(self.hidden_size, use_bias=False,
                                dtype=self.dtype, name="out_proj")(y)

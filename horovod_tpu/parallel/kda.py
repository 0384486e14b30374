"""Kimi Delta Attention (KDA): a gated delta rule with one decay a key
channel, and the mixer round it, with heads sharded over the tp axis.

Per head, with a (key x value) state ``S`` zero where a sequence starts::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                 alpha_t = exp(g_t) in (0, 1)^d

Run one token at a time this is ``L`` dependent rank-one updates of a
(d, d) state a head, so :func:`kda_core` runs it by chunks in the WY form
(``ops/pallas/kda.py``'s docstring has the equations): inside a chunk a
triangular solve and products, across chunks the state alone. Two ways run
the same arithmetic (``ops.pallas.kda.chunk``), picked from the call's
shapes alone (:func:`kda_path`): the Pallas kernels, which keep the decays,
the solve and the carried state in VMEM, and :func:`chunked_kda`, the
``jax.numpy`` form with the chunks' states through HBM, for calls off the
kernels' grid and as the tests' second form. Decays, their running sums,
``beta`` and the state are float32; the products with the activations take
their dtype and accumulate in float32.

:class:`KDAMixer` is the layer round it (``x`` the block's normed input)::

    [q~ | k~ | v~] = x W_qkv;  q, k, v = SiLU(conv4([q~ | k~ | v~]))
    q_h <- q_h / |q_h| d^-1/2;  k_h <- k_h / |k_h|
    beta = sigmoid(x W_b);  g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)
    o = KDA(q, k, v, g, beta);  o_h <- RMSNorm_d(o_h) sigmoid(x W_ga W_gb)_h
    out = o W_o

The convolution is depthwise and causal with no bias; the output norm's
scale is one d-wide vector all heads share. ``W_qkv``, ``W_b``, ``W_fb`` and
``W_gb`` are column-parallel (a shard's columns are its heads; the fused
``W_qkv`` is [q | k | v] of a shard's heads), ``W_o`` row-parallel (one
psum), the two low-rank down products replicated. Scopes (not
``hvd.``-prefixed): ``kda.mixer`` > ``kda.in_proj``, ``kda.conv``,
``kda.core``, ``kda.gate_norm``, ``kda.out_proj``.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops.pallas import kda as kernels
from horovod_tpu.parallel.ssm import A_RANGE, CausalConv1d
from horovod_tpu.parallel.tp import (TP_AXIS, ColumnParallelDense,
                                     RowParallelDense, axis_size_or_1)
from horovod_tpu.trace.scopes import scope

# The name the delta rule's output carries (``jax.ad_checkpoint.
# checkpoint_name``), for a remat policy that keeps it.
RULE_OUTPUT = "kda.rule_output"


def chunk_states_bytes(batch, length, heads, head_dim, chunk):
    """Bytes of one set of float32 chunk states: one (d, d) matrix a
    sequence, chunk and head."""
    return 4 * batch * -(-length // chunk) * heads * head_dim * head_dim


# The chunk of the jax.numpy form (a multiple of ops.pallas.kda.SUB).
JNP_CHUNK = 64


def kda_path(shape, itemsize):
    """``(path, chunk)`` of a :func:`kda_core` call on (b, L, H, d), read
    off its shapes. Path 1: the kernels of ``ops/pallas/kda.py`` in chunks
    of their ``CHUNK``, where they fit (``fits``); path 0:
    :func:`chunked_kda` in chunks of :data:`JNP_CHUNK`, for every other
    call."""
    _, length, _, head_dim = shape
    if kernels.fits(length, head_dim, itemsize):
        return 1, kernels.CHUNK
    return 0, JNP_CHUNK


def kda_core(q, k, v, g, beta):
    """``o`` (b, L, H, d) of the gated delta rule: ``q``, ``k``, ``v`` (b,
    L, H, d) in the activations' dtype (``q`` and ``k`` normed and scaled
    by the caller), ``g`` (b, L, H, d) float32 log decays (at most zero),
    ``beta`` (b, L, H) float32. One of two ways runs the chunks
    (:func:`kda_path`), both differentiable in all five."""
    path, chunk = kda_path(q.shape, q.dtype.itemsize)
    if path == 1:
        return kernels.kda(q, k, v, g, beta, chunk)
    return chunked_kda(q, k, v, g, beta, chunk)


def chunked_kda(q, k, v, g, beta, chunk):
    """:func:`kda_core` as ``jax.numpy``: ``ops.pallas.kda.chunk`` over
    every sequence and head at once, a ``lax.scan`` over the chunks whose
    backward pass keeps the state each chunk opens with and computes the
    chunk again. A length that is no whole number of chunks is padded with
    positions of ``g`` 0 and ``beta`` 0, which leave the state as it
    is."""
    b, length, heads, d = q.shape
    sub = kernels.SUB
    size = min(chunk, -(-length // sub) * sub)
    if size % sub:
        raise ValueError(f"chunk {chunk} is no multiple of {sub}")
    pad = -length % size
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (length + pad) // size

    def by_chunk(t):                # (b, L, H, w) -> (nc, b, H, C, w)
        return jnp.moveaxis(t.reshape(b, nc, size, heads, -1), (1, 3),
                            (0, 2))
    xs = tuple(by_chunk(t) for t in (q, k, v, g.astype(jnp.float32),
                                     beta.astype(jnp.float32)[..., None]))
    one = jax.vmap(jax.vmap(kernels.chunk))

    def body(state, x):
        o, state = one(*x, state)
        return state, o
    _, o = lax.scan(jax.checkpoint(body),
                    jnp.zeros((b, heads, d, v.shape[-1]), jnp.float32), xs)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, nc * size, heads, -1)
    return o[:, :length].astype(v.dtype)


def _fresh_a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))


def _fresh_dt_bias(key, shape):
    """The inverse softplus of a step drawn log-uniformly in [1e-3, 1e-1]
    and floored at 1e-4, Mamba-2's rule."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def l2_normed(x, eps=1e-6):
    """``x / |x|`` over the last axis, float32 (``|x|`` floored by
    ``eps``)."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.maximum(jnp.sum(jnp.square(x), -1,
                                             keepdims=True), eps * eps))


class KDAMixer(nn.Module):
    """The KDA mixer (module docstring) of ``num_heads`` heads of
    ``head_dim`` on (b, L, ``hidden_size``); ``gate_rank`` is the width of
    the two low-rank gate paths. No bias anywhere. ``A_log`` is one value a
    head, ``dt_bias`` one a key channel; both start as Mamba-2's do."""
    hidden_size: int
    num_heads: int
    head_dim: int
    gate_rank: int
    conv_kernel: int = 4
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS

    @nn.compact
    def __call__(self, x):
        n = axis_size_or_1(self.axis_name)
        if self.num_heads % n:
            raise ValueError(f"num_heads {self.num_heads} not divisible by "
                             f"tp={n}")
        H, d, f32 = self.num_heads, self.head_dim, jnp.float32
        heads, b, length = H // n, x.shape[0], x.shape[1]
        inner = heads * d
        path, chunk = kda_path((b, length, heads, d),
                               jnp.dtype(self.dtype).itemsize)
        from horovod_tpu.metrics import instruments as hvd_metrics
        hvd_metrics.record_kda_layer(
            path, H, d, chunk, -(-length // chunk),
            chunk_states_bytes(b, length, heads, d, chunk))

        def column(features, name):
            return ColumnParallelDense(features, use_bias=False,
                                       dtype=self.dtype,
                                       axis_name=self.axis_name, name=name)

        def low_rank(name):
            return nn.Dense(self.gate_rank, use_bias=False, dtype=self.dtype,
                            name=name)

        with scope("kda.mixer"):
            with scope("kda.in_proj"):
                qkv = column(3 * H * d, "qkv")(x)
                beta = jax.nn.sigmoid(column(H, "b_proj")(x).astype(f32))
                f = column(H * d, "f_b")(low_rank("f_a")(x))
                gate = column(H * d, "g_b")(low_rank("g_a")(x))
            with scope("kda.conv"):
                qkv = nn.silu(CausalConv1d(self.conv_kernel, self.dtype,
                                           use_bias=False, name="conv")(qkv))
                q, k, v = (t.reshape(b, length, heads, d)
                           for t in jnp.split(qkv, 3, -1))
                q = (l2_normed(q) * d ** -0.5).astype(self.dtype)
                k = l2_normed(k).astype(self.dtype)
            a_log = self.param("A_log", _fresh_a_log, (heads,))
            dt_bias = self.param("dt_bias", _fresh_dt_bias, (inner,))
            with scope("kda.core"):
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    (f.astype(f32) + dt_bias).reshape(b, length, heads, d))
                o = checkpoint_name(kda_core(q, k, v, g, beta),
                                    RULE_OUTPUT)
            with scope("kda.gate_norm"):
                o = nn.RMSNorm(epsilon=self.norm_eps, dtype=f32,
                               name="o_norm")(o.astype(f32))
                o = o * jax.nn.sigmoid(gate.astype(f32)).reshape(o.shape)
                o = o.reshape(b, length, inner).astype(self.dtype)
            with scope("kda.out_proj"):
                return RowParallelDense(self.hidden_size, use_bias=False,
                                        dtype=self.dtype,
                                        axis_name=self.axis_name,
                                        name="o_proj")(o)

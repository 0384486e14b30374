"""The plain float32 reference: a training step of any architecture of
``benchmark/archs/``, and its AdamW.

Straightforward ``jax.numpy``: no kernels, no fused buckets, no sharding.
It imports nothing of ``horovod_tpu`` and takes nothing the program made:
the architecture's file states the names and shapes of the parameters
itself (``param_shapes``), their values come from the seed
(``weights.make_params``) and the batches from the seed
(``traffic.Batches``).

The architecture is the configuration file's ``arch``, found by name
(``harness/arch.py`` says what its file states). Here is what every
architecture shares: the three precisions of a matrix product, the helpers
an architecture's file may import (``ln``, ``gelu``, ``attention``,
``xent``), the step in blocks of rows and layer by layer, and AdamW.

So that a step at the timed sizes fits beside nothing else on the chip, a
step is computed in blocks of rows and layer by layer: the forward pass
keeps each layer's input, the backward pass walks the layers in reverse and
recomputes each layer inside its own ``jax.vjp``. A layer is of a kind
(``net.kind_of(i)``); a block is jitted once per kind, so a model whose
layers differ compiles one program per kind of layer and a model of one
kind compiles one.

``operands`` sets the precision of every matrix product: ``float32`` (at
``highest``), ``bfloat16`` (what the program states), ``fp8`` (e4m3
operands forward, e5m2 gradients backward, a scale per tensor: the step
below bfloat16 -- the control).
"""

import functools

import jax
import jax.numpy as jnp

from . import arch

LN_EPS = 1e-6


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    """The parameter tree as nested dicts of shape tuples, under the
    program's names."""
    return arch.of(cfg).param_shapes(cfg)


def fused_parts(cfg):
    """Leaves that fuse several matrices: path -> equal parts along the last
    axis."""
    return arch.of(cfg).fused_parts(cfg)


# -- arithmetic --------------------------------------------------------------

def _quant(x, dtype, top):
    """Round to an 8-bit float type with one scale per tensor (``top`` is
    the type's largest finite value)."""
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    """A product as fp8 training recipes compute it: operands rounded to
    e4m3 on the way forward, the incoming gradient to e5m2 on the way back,
    every tensor with its own scale, accumulation in float32."""
    return _mm_fp8_fwd(spec, a, b)[0]


def _mm_fp8_fwd(spec, a, b):
    qa = _quant(a, jnp.float8_e4m3fn, 448.0)
    qb = _quant(b, jnp.float8_e4m3fn, 448.0)
    return jnp.einsum(spec, qa, qb,
                      precision=jax.lax.Precision.HIGHEST), (qa, qb)


def _mm_fp8_bwd(spec, saved, g):
    product = functools.partial(jnp.einsum, spec,
                                precision=jax.lax.Precision.HIGHEST)
    return jax.vjp(product, *saved)[1](_quant(g, jnp.float8_e5m2, 57344.0))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def product(operands):
    """The matrix product ``einsum(spec, a, b)`` at one precision."""
    if operands == "float32":
        return functools.partial(jnp.einsum,
                                 precision=jax.lax.Precision.HIGHEST)
    if operands == "bfloat16":
        def mm(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return mm
    if operands == "fp8":
        return _mm_fp8
    raise ValueError(f"unknown operand precision {operands!r}")


def ln(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def attention(mm, x, w_qkv, b_qkv, w_out, b_out, heads, causal):
    b, s, h = x.shape
    d = h // heads
    qkv = mm("bsh,hk->bsk", x, w_qkv) + b_qkv
    q, k, v = (t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1))
    scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -1e30)
    probs = jax.nn.softmax(scores, -1)
    out = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
    return mm("bsh,hk->bsk", out, w_out) + b_out


def xent(logits, labels):
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return logz - picked


# -- a training step, in blocks of rows and layer by layer -------------------

def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


class Reference:
    """Loss and gradient of the mean loss over a batch's rows, and AdamW."""

    def __init__(self, cfg, operands="float32", rows_per_block=4):
        self.cfg = cfg
        self.net = arch.of(cfg).Net(cfg, product(operands))
        self.rows_per_block = rows_per_block
        net = self.net
        self._embed = jax.jit(net.embed)
        self._block = jax.jit(net.block, static_argnums=0)
        self._head = jax.jit(jax.value_and_grad(net.head_loss, (0, 1)))

        def block_vjp(kind, p, x, dy):
            return jax.vjp(functools.partial(net.block, kind), p, x)[1](dy)

        def embed_vjp(p, batch, dy):
            return jax.vjp(lambda q: net.embed(q, batch), p)[1](dy)[0]

        self._block_vjp = jax.jit(block_vjp, static_argnums=0)
        self._embed_vjp = jax.jit(embed_vjp)
        self._adam = jax.jit(self._adam_update)

    def _rows_loss_and_grad(self, parts, batch):
        """Sum over these rows of the per-row loss, and its gradient."""
        embed_p, layer_ps, head_p = parts
        kinds = [self.net.kind_of(i) for i in range(len(layer_ps))]
        xs = [self._embed(embed_p, batch)]
        for kind, p in zip(kinds, layer_ps):
            xs.append(self._block(kind, p, xs[-1]))
        loss, (g_head, dx) = self._head(head_p, xs.pop(), batch)
        g_layers = []
        for kind, p in zip(reversed(kinds), reversed(layer_ps)):
            g, dx = self._block_vjp(kind, p, xs.pop(), dx)
            g_layers.append(g)
        g_embed = self._embed_vjp(embed_p, batch, dx)
        return loss, (g_embed, g_layers[::-1], g_head)

    def loss_and_grad(self, params, batch):
        """Mean loss over the batch's rows and its gradient, as a tree
        named like ``params``."""
        parts = self.net.split(params)
        rows = next(iter(batch.values())).shape[0]
        total, grads = 0.0, None
        for lo in range(0, rows, self.rows_per_block):
            block = {k: jnp.asarray(v[lo:lo + self.rows_per_block])
                     for k, v in batch.items()}
            loss, g = self._rows_loss_and_grad(parts, block)
            total = total + loss
            grads = g if grads is None else _add(grads, g)
        grads = jax.tree.map(lambda g: g / rows, grads)
        return total / rows, self.net.join(*grads)

    def _adam_update(self, params, grads, mu, nu, count):
        a = self.cfg["assumed"]
        b1, b2, eps = a["adam_b1"], a["adam_b2"], a["adam_eps"]
        lr, wd = a["learning_rate"], a["weight_decay"]
        count = count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu, count

    def init_opt(self, params):
        def zeros():
            return jax.tree.map(jnp.zeros_like, params)
        return zeros(), zeros(), jnp.zeros((), jnp.int32)

    def adam(self, params, grads, opt):
        """One AdamW update, sub-tree by sub-tree so that no program holds
        the whole state twice."""
        mu, nu, count = opt
        new_p, new_mu, new_nu = {}, {}, {}
        for k in params:
            new_p[k], new_mu[k], new_nu[k], new_count = self._adam(
                params[k], grads[k], mu[k], nu[k], count)
        return new_p, (new_mu, new_nu, new_count)

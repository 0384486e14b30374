"""Attention's prologue as one pass: from the qkv product's rows to the
flash kernels' operands.

``parallel/tp.py`` ``TPSelfAttention`` splits the fused product's rows
``(B, L, (H + 2 KV) D)`` into q, k and v, norms every query and key head
(``qk_norm_eps``: an RMS norm over ``D``, float32 statistics, one learned
scale for q and one for k), rotates them (``rope_theta``: rotate-half, the
angles and the rotation float32), and moves the three into the kernels'
``(B H, L, D)`` / ``(B KV, L, D)``; compiled, each is its own pass over HBM
forward and again backward. Here the same arithmetic is one kernel each
way, and the move is free: with ``D`` a multiple of 128 a head is a
lane-aligned slab of the row, so moving heads from lanes to the major axis
is picking slabs.

- :func:`_forward`: grid (row tile, sequence). A step reads the q, k and v
  columns of a tile of rows (three blocks of the one array), and for each
  query and key head its ``D`` lanes: ``x rsqrt(mean(x^2) + eps) scale``
  (flax's ``RMSNorm``: statistics and product float32), then ``x cos +
  roll(x, D / 2) sin`` with the tables of :func:`tables`, one rounding to
  the activations' dtype at the end; a value head is copied. It writes the
  ``(H, tile, D)``, ``(KV, tile, D)``, ``(KV, tile, D)`` blocks of the
  three operands.
- :func:`_backward`: the same grid over the operands' gradients (dK and dV
  already folded onto the key-value heads), the rotation transposed, the
  row's statistic recomputed from the saved rows; writes the ``(tile, (H +
  2 KV) D)`` block of the rows' gradient and, where the layer norms, each
  scale's ``(1, D)`` float32 partial, which XLA sums.

:func:`attention` is one ``custom_vjp`` over the pass and the flash
``custom_vjp``'s own two rules. Its residuals are the rows, the scales and
the kernels' output and row statistics; the backward pass makes the
operands again, one more pass over the rows, where keeping them too would
hold another ``(H + 2 KV) D`` bf16 columns a token through the step (PERF.md,
PR 38: with them the Trinity cell's step needed 0.16 GiB more and XLA's
rematerialisation cost time). :func:`row_tile` is the rule that says, from
the call's shapes, whether the kernels can run it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas.flash_attention import (_flash_bwd, _flash_fwd,
                                                    _vma)
from horovod_tpu.trace.scopes import scope

LANES = 128
# Bytes of VMEM a call may plan for (both buffers of every block of the
# backward kernel, the hungrier, and its float32 temporaries), under the
# limit the kernels ask for.
VMEM_BUDGET = 24 * 1024 * 1024
VMEM_LIMIT = 48 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _vmem_bytes(tile, heads, kv_heads, head_dim, itemsize):
    """The backward kernel's: both buffers of the operands' gradients, the
    saved q and k rows, the rows' gradient and the tables, and a dozen
    float32 temporaries of a head's width."""
    width = (heads + 2 * kv_heads) * head_dim
    blocks = itemsize * tile * (2 * width + (heads + kv_heads) * head_dim) \
        + 4 * 2 * tile * head_dim
    return 2 * blocks + 4 * 12 * tile * head_dim


def row_tile(length, heads, kv_heads, head_dim, itemsize=2):
    """Rows of a grid step where the kernels can run the call, else None:
    a head a whole number of lane tiles, and the largest of 512, 256, 128
    dividing the length whose blocks fit :data:`VMEM_BUDGET` (a length
    under 128 that is whole sublane tiles is one step)."""
    if head_dim % LANES or heads % kv_heads:
        return None
    tiles = (512, 256, 128) if length >= 128 else \
        ((length,) if length % 8 == 0 else ())
    for tile in tiles:
        if length % tile == 0 and _vmem_bytes(
                tile, heads, kv_heads, head_dim, itemsize) <= VMEM_BUDGET:
            return tile
    return None


def tables(length, head_dim, theta):
    """(cos, sin) (L, D) float32 for the rotate-half pairing, the angles as
    ``parallel.tp.apply_rope`` makes them: ``[cos | cos]`` and ``[-sin |
    sin]``, so that the rotation is ``x cos + roll(x, D / 2) sin``."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim)
    ang = jnp.arange(length, dtype=jnp.int32).astype(jnp.float32)[:, None] \
        * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], axis=1),
            jnp.concatenate([-sin, sin], axis=1))


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT)


def _half_turn(x):
    """``x`` with the two halves of its lanes swapped."""
    return pltpu.roll(x, x.shape[1] // 2, 1)


def _lanes(h, head_dim):
    """Head ``h``'s lanes of a row: a traced head index, so that a kernel
    loops over heads (``lax.fori_loop``) where 40 unrolled heads cost the
    set-up seconds of tracing and lowering them (PERF.md, PR 38)."""
    return pl.ds(pl.multiple_of(h * head_dim, LANES), head_dim)


def _rows_specs(tile, heads, kv_heads, head_dim, parts):
    """BlockSpecs of the q, k (and v) columns of a tile of rows of the
    fused product, (b, L, (H + 2 KV) D), on the grid (row tile, sequence):
    the k columns are block H / KV of KV D lanes, v the next."""
    kv = heads // kv_heads
    specs = [pl.BlockSpec((None, tile, heads * head_dim),
                          lambda i, b: (b, i, 0))]
    for block in (kv, kv + 1)[:parts - 1]:
        specs.append(pl.BlockSpec((None, tile, kv_heads * head_dim),
                                  lambda i, b, block=block: (b, i, block)))
    return specs


def _by_head(tile, heads, head_dim):
    """BlockSpec of a (B n, L, D) operand's ``(n, tile, D)`` block."""
    return pl.BlockSpec((heads, tile, head_dim), lambda i, b: (b, i, 0))


_STATICS = ("heads", "kv_heads", "eps", "theta", "tile", "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def _forward(qkv, q_scale, k_scale, *, heads, kv_heads, eps, theta, tile,
             interpret):
    b, length, width = qkv.shape
    head_dim = width // (heads + 2 * kv_heads)
    normed, rotated = eps is not None, theta is not None
    dtype, f32 = qkv.dtype, jnp.float32

    def kernel(*refs):
        q_ref, k_ref, v_ref, *refs = refs
        if normed:
            qs_ref, ks_ref, *refs = refs
        if rotated:
            cos_ref, sin_ref, *refs = refs
        qo_ref, ko_ref, vo_ref = refs

        def head(x, scale_ref):
            x = x.astype(f32)
            if normed:
                x = x * (lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True)
                                   + eps) * scale_ref[...])
            if rotated:
                x = x * cos_ref[...] + _half_turn(x) * sin_ref[...]
            return x.astype(dtype)

        def q_head(h, c):
            qo_ref[h] = head(q_ref[:, _lanes(h, head_dim)],
                             qs_ref if normed else None)
            return c

        def kv_head(h, c):
            lanes = _lanes(h, head_dim)
            ko_ref[h] = head(k_ref[:, lanes], ks_ref if normed else None)
            vo_ref[h] = v_ref[:, lanes]
            return c
        lax.fori_loop(0, heads, q_head, 0)
        lax.fori_loop(0, kv_heads, kv_head, 0)

    operands = [qkv, qkv, qkv]
    in_specs = _rows_specs(tile, heads, kv_heads, head_dim, 3)
    if normed:
        operands += [q_scale.reshape(1, head_dim).astype(f32),
                     k_scale.reshape(1, head_dim).astype(f32)]
        in_specs += [pl.BlockSpec((1, head_dim), lambda i, b: (0, 0))] * 2
    if rotated:
        operands += list(tables(length, head_dim, theta))
        in_specs += [pl.BlockSpec((tile, head_dim),
                                  lambda i, b: (i, 0))] * 2
    vma = _vma(qkv)

    def out(n):
        return jax.ShapeDtypeStruct((b * n, length, head_dim), dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        name="hvd_attn_prologue_fwd",
        grid=(length // tile, b),
        in_specs=in_specs,
        out_specs=[_by_head(tile, heads, head_dim),
                   _by_head(tile, kv_heads, head_dim),
                   _by_head(tile, kv_heads, head_dim)],
        out_shape=[out(heads), out(kv_heads), out(kv_heads)],
        compiler_params=_params(interpret),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _backward(qkv, q_scale, k_scale, dq, dk, dv, *, heads, kv_heads, eps,
              theta, tile, interpret):
    b, length, width = qkv.shape
    head_dim = width // (heads + 2 * kv_heads)
    normed, rotated = eps is not None, theta is not None
    dtype, f32 = qkv.dtype, jnp.float32
    steps = length // tile * b

    def kernel(*refs):
        dq_ref, dk_ref, dv_ref, *refs = refs
        if normed:
            q_ref, k_ref, qs_ref, ks_ref, *refs = refs
        if rotated:
            cos_ref, sin_ref, *refs = refs
        out_ref, *partials = refs

        def head(g, x, scale_ref):
            """The gradient of a head's rows, and where the layer norms
            its scale's (1, D) part."""
            g = g.astype(f32)
            if rotated:
                g = g * cos_ref[...] + _half_turn(g * sin_ref[...])
            if not normed:
                return g, None
            x = x.astype(f32)
            r = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
            y = x * r
            gs = g * scale_ref[...]
            dx = r * (gs - y * jnp.mean(gs * y, axis=1, keepdims=True))
            return dx, jnp.sum(g * y, axis=0, keepdims=True)

        for which, (n, first, g_ref) in enumerate((
                (heads, 0, dq_ref), (kv_heads, heads, dk_ref))):
            x_ref = (q_ref, k_ref)[which] if normed else None
            s_ref = (qs_ref, ks_ref)[which] if normed else None

            def one(h, part, first=first, g_ref=g_ref, x_ref=x_ref,
                    s_ref=s_ref):
                dx, ds = head(g_ref[h], None if x_ref is None
                              else x_ref[:, _lanes(h, head_dim)], s_ref)
                out_ref[:, _lanes(first + h, head_dim)] = dx.astype(dtype)
                return part if ds is None else part + ds
            part = lax.fori_loop(0, n, one, jnp.zeros((1, head_dim), f32))
            if normed:
                partials[which][...] = part

        def v_head(h, c):
            out_ref[:, _lanes(heads + kv_heads + h, head_dim)] = dv_ref[h]
            return c
        lax.fori_loop(0, kv_heads, v_head, 0)

    operands = [dq, dk, dv]
    in_specs = [_by_head(tile, heads, head_dim),
                _by_head(tile, kv_heads, head_dim),
                _by_head(tile, kv_heads, head_dim)]
    out_specs = [pl.BlockSpec((None, tile, width), lambda i, b: (b, i, 0))]
    vma = _vma(qkv, dq, dk, dv)
    out_shape = [jax.ShapeDtypeStruct(qkv.shape, dtype, vma=vma)]
    if normed:
        operands += [qkv, qkv, q_scale.reshape(1, head_dim).astype(f32),
                     k_scale.reshape(1, head_dim).astype(f32)]
        in_specs += _rows_specs(tile, heads, kv_heads, head_dim, 2)
        in_specs += [pl.BlockSpec((1, head_dim), lambda i, b: (0, 0))] * 2
        nb = b
        out_specs += [pl.BlockSpec((None, 1, head_dim),
                                   lambda i, b: (i * nb + b, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((steps, 1, head_dim), f32,
                                           vma=vma)] * 2
    if rotated:
        operands += list(tables(length, head_dim, theta))
        in_specs += [pl.BlockSpec((tile, head_dim),
                                  lambda i, b: (i, 0))] * 2
    return pl.pallas_call(
        kernel,
        name="hvd_attn_prologue_bwd",
        grid=(length // tile, b),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(interpret),
        interpret=interpret,
    )(*operands)


def operands(qkv, q_scale, k_scale, *, heads, kv_heads, eps, theta, tile):
    """``(q3, k3, v3)``: the flash kernels' operands ``(B H, L, D)``,
    ``(B KV, L, D)``, ``(B KV, L, D)`` from the fused product's rows
    ``(B, L, (H + 2 KV) D)`` laid ``[q | k | v]``, each query and key head
    normed (``eps`` not None: scales ``q_scale``, ``k_scale`` of ``D``) and
    rotated (``theta`` not None: positions 0 .. L - 1), in one pass; a call
    :func:`row_tile` admits, ``tile`` its answer."""
    return _forward(qkv, q_scale, k_scale, heads=heads, kv_heads=kv_heads,
                    eps=eps, theta=theta, tile=tile, interpret=_interpret())


def operands_grad(qkv, q_scale, k_scale, dq, dk, dv, *, heads, kv_heads,
                  eps, theta, tile):
    """The gradients of the rows and of the two scales (None where the
    layer does not norm) from those of :func:`operands`' three, in one
    pass."""
    dqkv, *partials = _backward(
        qkv, q_scale, k_scale, dq, dk, dv, heads=heads, kv_heads=kv_heads,
        eps=eps, theta=theta, tile=tile, interpret=_interpret())
    if eps is None:
        return dqkv, None, None
    dqs, dks = (p.sum(axis=(0, 1)).astype(s.dtype)
                for p, s in zip(partials, (q_scale, k_scale)))
    return dqkv, dqs, dks


def _first_scope(eps):
    """The scope of the pass: of the first thing it does."""
    return scope("attn.qk_norm" if eps is not None else "attn.rope")


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 10)))
def attention(qkv, q_scale, k_scale, heads, kv_heads, eps, theta, tile,
              causal, window):
    """The flash kernels' output ``(B H, L, D)`` over :func:`operands`, by
    the flash ``custom_vjp``'s own two rules (``causal``, ``window`` as
    ``flash_attention`` takes them). The pass carries the scope of its
    first step, ``attn.qk_norm`` or ``attn.rope``, the kernels
    ``attn.core``. Differentiable in the rows and the scales. The
    residuals are the rows, the scales and the kernels' output and row
    statistics, not the kernels' operands: the backward pass makes those
    again (one more pass over the rows) where keeping them beside the rows
    would hold ``(H + 2 KV) D`` more bf16 columns a token through the step
    (PERF.md, PR 38)."""
    return _attention_fwd(qkv, q_scale, k_scale, heads, kv_heads, eps,
                          theta, tile, causal, window)[0]


def _sm_scale(head_dim):
    return 1.0 / (head_dim ** 0.5)       # flash_attention's default


def _attention_fwd(qkv, q_scale, k_scale, heads, kv_heads, eps, theta,
                   tile, causal, window):
    with _first_scope(eps):
        q3, k3, v3 = operands(qkv, q_scale, k_scale, heads=heads,
                              kv_heads=kv_heads, eps=eps, theta=theta,
                              tile=tile)
    with scope("attn.core"):
        o, (*_, lse) = _flash_fwd(
            q3, k3, v3, causal, _sm_scale(q3.shape[-1]), None, None, 0,
            qkv.shape[1], heads, kv_heads, window)
    return o, (qkv, q_scale, k_scale, o, lse)


def _attention_bwd(heads, kv_heads, eps, theta, tile, causal, window, res,
                   do):
    qkv, q_scale, k_scale, o, lse = res
    static = dict(heads=heads, kv_heads=kv_heads, eps=eps, theta=theta,
                  tile=tile)
    with _first_scope(eps):
        # The barrier keeps XLA from taking this pass for the forward
        # one and holding that one's output through the step instead.
        q3, k3, v3 = operands(*lax.optimization_barrier(
            (qkv, q_scale, k_scale)), **static)
    with scope("attn.core"):
        grads = _flash_bwd(causal, _sm_scale(q3.shape[-1]), None, None, 0,
                           qkv.shape[1], heads, kv_heads, window,
                           (q3, k3, v3, o, lse), do)
    with _first_scope(eps):
        return operands_grad(qkv, q_scale, k_scale, *grads, **static)


attention.defvjp(_attention_fwd, _attention_bwd)

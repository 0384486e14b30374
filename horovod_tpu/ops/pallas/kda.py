"""Kimi Delta Attention's chunked gated delta rule as Pallas kernels: the
decays, the chunk's triangular solve and the carried state stay in VMEM.

The recurrence (``parallel/kda.py``), per head with a (key x value) state
``S`` zero where a sequence starts, ``alpha_t = exp(g_t)`` one decay a key
channel::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A chunk of ``C`` positions with the state ``S0`` it opens with, ``G`` the
running sum of ``g`` inside the chunk (non-increasing, float32), is the WY
form::

    A_kk[i, j] = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)      j < i
    A_qk[i, j] =        sum_c q_ic k_jc exp(G_ic - G_jc)      j <= i
    T = (I + A_kk)^-1;  W = T (beta k e^G);  U = T (beta v)
    u = U - W S0;  o = (q e^G) S0 + A_qk u
    S_end = e^{G_C} . S0 + (k e^{G_C - G})^T u

Every exponent here is a difference that is never positive: ``exp(G_i -
G_j)`` for ``j < i`` is formed by offset inside sub-chunks of :data:`SUB`
rows (a row shift, one product a channel) and between sub-chunks through
the last running sum of the earlier sub-chunk as the reference, so that
both factors of ``(k_i e^{G_i - r}) . (k_j e^{r - G_j})`` are at most one.
One reference point over a whole chunk would overflow float32 once the
decays of a chunk pass ~88 in all. ``T`` is the product ``(I + N)(I +
N^2)(I + N^4) ...`` of ``N = -A_kk`` (nilpotent), float32 products at
``highest``; the products with the activations take their dtype with
float32 sums.

:func:`chunk` is that arithmetic on one chunk of one head; the kernels and
``parallel.kda.chunked_kda`` (the ``jax.numpy`` form) both run it. One grid
for the kernels: (sequence, head, chunk), the chunk axis last and in
order; a head is 128 contiguous lanes of (b, L, H d).

- :func:`_sweep`, forward: the chunk's outputs and the carried state, a
  float32 (d, d) VMEM scratch; with ``states=True`` (the backward pass's
  first sweep) the state each chunk opens with, float32, and no output.
- :func:`_reverse_sweep`: the chunks in reverse, the state's gradient
  carried in VMEM; each chunk is computed again from its opening state and
  its VJP taken inside the kernel.

:func:`kda` is the ``custom_vjp`` over them; its residuals are its inputs
alone. :func:`fits` says, from the call's shapes, whether the kernels can
run it in chunks of :data:`CHUNK`.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows of a sub-chunk: decays inside one are formed by offset, one row
# shift each, and between sub-chunks through a reference point.
SUB = 16
# Rows of the beta / d(beta) blocks: beta on row 0, positions on lanes.
_ROWS = 8
# The chunk length the kernels run in (PERF.md: one layer's call on a TPU
# v5e at 2 x 8192, 32 heads of 128, bfloat16: 70.2 ms forward and backward
# in chunks of 128, 76.9 in chunks of 64).
CHUNK = 128
# Bytes of VMEM a call may plan for, under the limit the kernels ask for.
VMEM_BUDGET = 40 * 1024 * 1024
VMEM_LIMIT = 96 * 1024 * 1024
_HI = lax.Precision.HIGHEST


def _interpret():
    return jax.default_backend() != "tpu"


def _vmem_bytes(chunk, head_dim, itemsize):
    """What the reverse sweep keeps in VMEM: both buffers of its blocks,
    the carried gradient, and the chunk's values with their VJP's
    residuals (some 60 float32 arrays of a chunk's rows, 30 of (C, C))."""
    wide = chunk * head_dim
    blocks = 2 * (8 * itemsize * wide + 2 * 4 * wide
                  + 2 * 4 * _ROWS * chunk + 4 * head_dim * head_dim)
    return blocks + 4 * head_dim * head_dim \
        + 4 * (60 * wide + 30 * chunk * chunk)


def fits(length, head_dim, itemsize=2):
    """Whether the kernels can run a call in chunks of :data:`CHUNK` (else
    the caller keeps the ``jax.numpy`` form): the length a whole number of
    chunks, the head a multiple of 128 lanes, and the blocks and
    temporaries inside :data:`VMEM_BUDGET`."""
    return length % CHUNK == 0 and head_dim % LANES == 0 \
        and _vmem_bytes(CHUNK, head_dim, itemsize) <= VMEM_BUDGET


# -- the arithmetic of one chunk ---------------------------------------------

def _dims(form):
    return {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}[form]


def _dot(a, b, form, dtype):
    """A product of two float32 values with operands in ``dtype`` and a
    float32 sum (``highest`` where ``dtype`` is float32)."""
    precision = _HI if dtype == jnp.float32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype),
                           (_dims(form), ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, form, dtype):
    """:func:`_dot` with its gradients as products of the same kind, so
    that no product of mixed operand types reaches the kernels."""
    return _dot(a, b, form, dtype)


def _mm_fwd(a, b, form, dtype):
    return _dot(a, b, form, dtype), (a, b)


def _mm_bwd(form, dtype, res, g):
    a, b = res
    if form == "nn":
        return _dot(g, b, "nt", dtype), _dot(a, g, "tn", dtype)
    if form == "nt":
        return _dot(g, b, "nn", dtype), _dot(g, a, "tn", dtype)
    return _dot(b, g, "nt", dtype), _dot(a, g, "nn", dtype)


_mm.defvjp(_mm_fwd, _mm_bwd)


def _hi(a, b):
    return lax.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def jnp_roll(x, n):
    """Rows shifted down by ``n`` (row ``i`` gets row ``i - n``, wrapping)."""
    return jnp.roll(x, n, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _kernel_roll(x, n):
    """:func:`jnp_roll` inside a kernel (``pltpu.roll``), with its VJP."""
    return pltpu.roll(x, n, 0)


def _kernel_roll_fwd(x, n):
    return _kernel_roll(x, n), None


def _kernel_roll_bwd(n, _, g):
    return (pltpu.roll(g, g.shape[0] - n, 0),)


_kernel_roll.defvjp(_kernel_roll_fwd, _kernel_roll_bwd)


def _running_sum(g, roll):
    """Running sums down the rows of ``g`` (C, d), by doubling shifts."""
    row = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    n = 1
    while n < g.shape[0]:
        g = g + jnp.where(row >= n, roll(g, n), 0.0)
        n *= 2
    return g


def _row(t, i):
    """Row ``i`` of ``t`` as (1, width), by a masked sum."""
    row = lax.broadcasted_iota(jnp.int32, t.shape, 0)
    return jnp.sum(jnp.where(row == i, t, 0.0), axis=0, keepdims=True)


def chunk(q, k, v, g, beta, state, *, roll=jnp_roll, dtype=None):
    """One chunk of one head (module docstring): ``q``, ``k``, ``v`` (C, d)
    in the activations' dtype, ``g`` (C, d) float32 log decays (at most
    zero), ``beta`` (C, 1) float32, ``state`` (d_k, d_v) float32, the state
    the chunk opens with. Returns ``o`` (C, d_v) and the closing state,
    both float32. ``C`` is a multiple of :data:`SUB` or below it."""
    f32 = jnp.float32
    dtype = dtype or q.dtype
    rows, width = k.shape
    sub = min(SUB, rows)
    G = _running_sum(g.astype(f32), roll)
    kf, qf = k.astype(f32), q.astype(f32)
    row = lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    col = lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    at_row = lax.broadcasted_iota(jnp.int32, (rows, width), 0)

    # Inside sub-chunks, by offset: the pairs (i, i - n).
    a_qk = jnp.where(row == col, jnp.sum(qf * kf, 1, keepdims=True), 0.0)
    a_kk = jnp.zeros((rows, rows), f32)
    for n in range(1, sub):
        earlier = roll(kf, n) * jnp.exp(jnp.minimum(G - roll(G, n), 0.0))
        at = (col == row - n) & ((row & (sub - 1)) >= n)
        a_kk = a_kk + jnp.where(
            at, jnp.sum(kf * earlier, 1, keepdims=True), 0.0)
        a_qk = a_qk + jnp.where(
            at, jnp.sum(qf * earlier, 1, keepdims=True), 0.0)

    # Between sub-chunks: the rows after sub-chunk s against its columns,
    # through its last running sum r.
    for s in range(rows // sub - 1):
        last = (s + 1) * sub - 1
        r = _row(G, last)
        mine = (at_row > last - sub) & (at_row <= last)
        later = at_row > last
        right = jnp.where(mine, kf * jnp.exp(jnp.minimum(r - G, 0.0)), 0.0)
        down = jnp.exp(jnp.minimum(G - r, 0.0))
        a_kk = a_kk + _mm(jnp.where(later, kf * down, 0.0), right, "nt",
                          dtype)
        a_qk = a_qk + _mm(jnp.where(later, qf * down, 0.0), right, "nt",
                          dtype)

    # T = (I + A_kk)^-1 = (I + N)(I + N^2)(I + N^4) ..., N = -A_kk.
    power = -beta * a_kk
    inverse = jnp.where(row == col, 1.0, 0.0) + power
    n = 2
    while n < rows:
        power = _hi(power, power)
        inverse = inverse + _hi(inverse, power)
        n *= 2

    grow = jnp.exp(G)
    w = _hi(inverse, beta * kf * grow)
    u = _hi(inverse, beta * v.astype(f32)) - _mm(w, state, "nn", dtype)
    o = _mm(qf * grow, state, "nn", dtype) + _mm(a_qk, u, "nn", dtype)
    total = _row(G, rows - 1)
    closing = state * jnp.broadcast_to(jnp.exp(total), (width, width)).T \
        + _mm(kf * jnp.exp(total - G), u, "tn", dtype)
    return o, closing


# -- the kernels --------------------------------------------------------------

def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _beta_of(rows):
    """(C, 1) beta from its (8, C) rows."""
    return rows.T[:, 0:1]


def _arith(interpret, dtype):
    roll = jnp_roll if interpret else _kernel_roll
    return functools.partial(chunk, roll=roll, dtype=dtype)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "chunk_size", "states", "interpret"))
def _sweep(q, k, v, g, brows, *, head_dim, chunk_size, states, interpret):
    """The chunks in order. ``q``, ``k``, ``v`` (b, L, H d), ``g`` (b, L,
    H d) float32, ``brows`` (b, H, chunks, 8, C) float32 (beta on row 0).
    Returns ``o`` like ``q`` or, if ``states``, the state each chunk opens
    with, (b, H, chunks, d, d) float32."""
    b, length, channels = q.shape
    heads, nc, d = channels // head_dim, length // chunk_size, head_dim
    dtype = q.dtype
    arith = _arith(interpret, dtype)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, out_ref, state):
        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros_like(state)

        opening = state[...]
        o, closing = arith(q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                           _beta_of(b_ref[...]), opening)
        out_ref[...] = opening if states else o.astype(dtype)
        state[...] = closing

    by_position = pl.BlockSpec((None, chunk_size, d),
                               lambda bi, hi, ci: (bi, ci, hi))

    def by_chunk(*block):
        return pl.BlockSpec((None, None, None, *block),
                            lambda bi, hi, ci: (bi, hi, ci, 0, 0))
    if states:
        out_spec = by_chunk(d, d)
        out_shape = jax.ShapeDtypeStruct((b, heads, nc, d, d), jnp.float32)
    else:
        out_spec, out_shape = by_position, jax.ShapeDtypeStruct(
            q.shape, dtype)
    return pl.pallas_call(
        kernel,
        name=f"hvd_kda_{'states' if states else 'fwd'}_{chunk_size}x{d}",
        grid=(b, heads, nc),
        in_specs=[by_position] * 4 + [by_chunk(_ROWS, chunk_size)],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
    )(q, k, v, g, brows)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "chunk_size", "interpret"))
def _reverse_sweep(q, k, v, g, brows, opening, do, *, head_dim, chunk_size,
                   interpret):
    """The chunks in reverse, given ``do`` like ``q`` and ``opening`` from
    :func:`_sweep`. Returns ``dq``, ``dk``, ``dv``, ``dg`` like their
    inputs and ``dbrows`` like ``brows`` (d(beta) on every row)."""
    b, length, channels = q.shape
    heads, nc, d = channels // head_dim, length // chunk_size, head_dim
    dtype, f32 = q.dtype, jnp.float32
    arith = _arith(interpret, dtype)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, open_ref, do_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
        @pl.when(pl.program_id(2) == 0)
        def _():
            dstate[...] = jnp.zeros_like(dstate)

        _, pull = jax.vjp(arith, q_ref[...], k_ref[...], v_ref[...],
                          g_ref[...], _beta_of(b_ref[...]), open_ref[...])
        dq, dk, dv, dg, dbeta, dopen = pull(
            (do_ref[...].astype(f32), dstate[...]))
        dq_ref[...] = dq.astype(dtype)
        dk_ref[...] = dk.astype(dtype)
        dv_ref[...] = dv.astype(dtype)
        dg_ref[...] = dg
        db_ref[...] = jnp.broadcast_to(dbeta, (chunk_size, LANES)
                                       ).T[:_ROWS]
        dstate[...] = dopen

    by_position = pl.BlockSpec((None, chunk_size, d),
                               lambda bi, hi, ci: (bi, nc - 1 - ci, hi))

    def by_chunk(*block):
        return pl.BlockSpec((None, None, None, *block),
                            lambda bi, hi, ci: (bi, hi, nc - 1 - ci, 0, 0))

    def like(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        kernel,
        name=f"hvd_kda_bwd_{chunk_size}x{d}",
        grid=(b, heads, nc),
        in_specs=[by_position] * 4 + [by_chunk(_ROWS, chunk_size),
                                      by_chunk(d, d), by_position],
        out_specs=[by_position] * 4 + [by_chunk(_ROWS, chunk_size)],
        out_shape=[like(q), like(k), like(v), like(g), like(brows)],
        scratch_shapes=[pltpu.VMEM((d, d), f32)],
        compiler_params=_params(interpret),
        interpret=interpret,
    )(q, k, v, g, brows, opening, do)


def _operands(q, k, v, g, beta, chunk_size):
    """The kernels' operands: (b, L, H d) views and beta's rows."""
    b, length, heads, d = q.shape
    nc = length // chunk_size
    rows = jnp.swapaxes(beta.astype(jnp.float32), 1, 2).reshape(
        b, heads, nc, 1, chunk_size)
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, _ROWS - 1), (0, 0)))
    flat = tuple(t.reshape(b, length, heads * d) for t in (q, k, v))
    return flat + (g.astype(jnp.float32).reshape(b, length, heads * d),
                   rows)


def _static(q, chunk_size):
    return dict(head_dim=q.shape[3], chunk_size=chunk_size,
                interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda(q, k, v, g, beta, chunk_size):
    """``parallel.kda.kda_core`` by the kernels in chunks of
    ``chunk_size`` (:data:`CHUNK` for a call :func:`fits` admits; the
    tests' smaller chunks keep the interpreter quick): ``q``, ``k``, ``v``
    (b, L, H, d), ``g`` (b, L, H, d) float32 log decays, ``beta`` (b, L,
    H). Differentiable in all five; the backward pass keeps the five alone
    and writes one set of float32 chunk states (the state each chunk opens
    with), the forward pass none."""
    o = _sweep(*_operands(q, k, v, g, beta, chunk_size), states=False,
               **_static(q, chunk_size))
    return o.reshape(v.shape)


def _kda_fwd(q, k, v, g, beta, chunk_size):
    return kda(q, k, v, g, beta, chunk_size), (q, k, v, g, beta)


def _kda_bwd(chunk_size, res, do):
    q, k, v, g, beta = res
    b, length, heads, d = q.shape
    static = _static(q, chunk_size)
    operands = _operands(q, k, v, g, beta, chunk_size)
    opening = _sweep(*operands, states=True, **static)
    dq, dk, dv, dg, drows = _reverse_sweep(
        *operands, opening, do.reshape(operands[0].shape), **static)
    dbeta = jnp.swapaxes(drows[:, :, :, 0].reshape(b, heads, length), 1, 2)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))


kda.defvjp(_kda_fwd, _kda_bwd)

"""Mamba-2's chunked scan as kernels: masks and chunk states stay in VMEM.

``parallel/ssm.py`` writes the chunked (state-space-duality) form of the
recurrence as ``jax.numpy`` products; compiled, every call materialises a
``chunk x chunk`` float32 mask of decays a head and chunk and two sets of
float32 chunk states in HBM and reads each back, three times over a step.
The kernels here run the SAME chunks with the same arithmetic (``dt``,
``A``, the running sums, the decays and the carried state float32; the
products' operands in the activations' dtype where that form casts them,
float32 sums) and keep all of it on the chip.

One grid for all three kernels: (sequence, group, chunk), the chunk axis
last and in order, one step a (sequence, group, chunk); more chunks a step
bought 3 % of the kernels' time on a v5e (PERF.md, PR 36). A group's heads
are contiguous channels of ``x`` (b, L, H P) and its ``B`` and ``C``
contiguous channels of (b, L, G N), so no operand is transposed. The
running sums and ``dt`` come as ``rows`` (b, G, chunks, 2 R, chunk)
float32, positions on lanes; a step transposes its (2 R, chunk) block once
for the orientation with positions on sublanes.

- :func:`_sweep`, forward: ``C B^T`` once a step, a head's decays under
  the causal mask, ``mask @ (dt x)``, the opening state's part as one
  product for the group, the skip, and the carried state, a float32
  (N, R P) VMEM scratch, moved on by one closing product. Writes ``y``.
- :func:`_sweep` with ``states=True``, the backward pass's first sweep:
  the closing product and the carry alone; writes the state each chunk
  opens with, once, in the dtype the products read it in.
- :func:`_reverse_sweep`: the chunks in reverse, the state's gradient
  carried in VMEM; writes the gradients of ``x``, ``B``, ``C`` and, by
  position, of the running sums and of ``dt``'s direct use, and three row
  sums a chunk (``sums``) that a few MB of XLA turn into the rest.

Heads narrower than 128 lanes share a 128-lane block (two heads of 64):
a head's product runs over the whole block and a select keeps its lanes,
so no slice of a value starts inside a lane tile; the matrix unit is 128
wide either way. The gradient of a running sum needs no second pass over
a mask: with ``E = d(mask) * mask``, the column sums of ``E`` are taken
where ``E`` is made, and its row sums equal ``sum_p (dt x) d(dt x)``,
which a product with a 0/1 matrix sums by head.

:func:`scan` is the ``custom_vjp`` over the three; its residuals are the
scan's inputs alone. :func:`pick_blocks` is the rule that says, from the
call's shapes, whether the kernels can run it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Bytes of VMEM a call may plan for (both buffers of every block, the
# carried state and the reverse sweep's float32 temporaries), under the
# limit the kernels ask for.
VMEM_BUDGET = 24 * 1024 * 1024
VMEM_LIMIT = 48 * 1024 * 1024
# Rows of the reverse sweep's ``sums`` block: xdt's part of d(total), the
# state's part of it, D's gradient; the rest of a sublane tile is zeros.
_SUM_ROWS = 8


def _interpret():
    return jax.default_backend() != "tpu"


def _vmem_bytes(chunk, channels, state, itemsize):
    """What the reverse sweep, the hungriest of the three, keeps in VMEM:
    both buffers of its blocks, the carried gradient and some twenty
    float32 temporaries of a block's width."""
    wide, narrow = chunk * channels, chunk * state
    blocks = itemsize * (4 * wide + 4 * narrow + state * channels) \
        + 4 * (2 * chunk * 2 * LANES + _SUM_ROWS * channels)
    return 2 * blocks + 4 * state * channels \
        + 4 * (20 * wide + 8 * chunk * chunk)


def pick_blocks(length, heads, head_dim, groups, state, chunk, itemsize=2):
    """``(chunk, R P, N)``, the positions, channels and state columns of a
    grid step, where the kernels can run the call, else None (the caller
    keeps the ``jax.numpy`` form). They can where the length is a whole
    number of chunks, the chunk a multiple of 128 (positions lie on lanes
    in ``rows``), a group's channels ``R P`` and the state's size ``N``
    multiples of 128 (the blocks' lanes), a head 128 lanes or a whole
    fraction or multiple of them, and the blocks, the carried state and
    the temporaries fit :data:`VMEM_BUDGET`."""
    if heads % groups or length % chunk or chunk % LANES or state % LANES:
        return None
    channels = heads // groups * head_dim
    if channels % LANES or (LANES % head_dim and head_dim % LANES):
        return None
    if _vmem_bytes(chunk, channels, state, itemsize) > VMEM_BUDGET:
        return None
    return chunk, channels, state


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _wide(cols, head_dim):
    """(M, R P) from R columns (M, 1), one a head: each over its head's
    ``head_dim`` lanes. Heads narrower than a lane tile are laid into it by
    selects on whole tiles."""
    m = cols[0].shape[0]
    if head_dim % LANES == 0:
        tiles = [jnp.broadcast_to(c, (m, head_dim)) for c in cols]
    else:
        per = LANES // head_dim
        lane = lax.broadcasted_iota(jnp.int32, (m, LANES), 1)
        tiles = []
        for k in range(0, len(cols), per):
            tile = jnp.broadcast_to(cols[k], (m, LANES))
            for i in range(1, per):
                tile = jnp.where(lane >= i * head_dim, jnp.broadcast_to(
                    cols[k + i], (m, LANES)), tile)
            tiles.append(tile)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _heads_of_tiles(heads, head_dim):
    """[(first lane, lanes, [(head, lanes of the tile that are its own or
    None for all)])]: the lane tiles of a group's channels and the heads
    in each."""
    width = max(head_dim, LANES)
    per = width // head_dim
    out = []
    for k in range(heads // per):
        mine = [(k * per + i,
                 None if per == 1 else (i * head_dim, (i + 1) * head_dim))
                for i in range(per)]
        out.append((k * width, width, mine))
    return out


def _own(lanes, shape):
    """Where a (chunk, tile) value's lanes are one head's own."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= lanes[0]) & (lane < lanes[1])


def _columns(rows, heads):
    """Of a chunk's ``rows`` (2 R padded, chunk): its transpose (positions
    on sublanes), and by head the columns of the running sum, of ``exp``
    of it, of the decay from a position to the chunk's end, of ``dt``, and
    the (1, 1) decay of the whole chunk."""
    cols = rows.T
    cs = cols[:, :heads]
    total = cs[-1:, :]
    grow, to_end, whole = jnp.exp(cs), jnp.exp(total - cs), jnp.exp(total)

    def by_head(t, first=0):
        return [t[:, first + h:first + h + 1] for h in range(heads)]
    return (by_head(cols), by_head(grow), by_head(to_end),
            by_head(cols, heads), by_head(whole))


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "states", "interpret"))
def _sweep(x, Bm, Cm, rows, skip, *, heads, head_dim, states, interpret):
    """The chunks in order. ``x`` (b, L, G R P), ``Bm``, ``Cm`` (b, L, G N),
    ``rows`` (b, G, chunks, 2 R padded, chunk) float32, ``skip`` (1, G R P)
    float32 (D over each head's channels), ``heads`` = R. Returns ``y``
    like ``x``, or, if ``states``, the state each chunk opens with, (b, G,
    chunks, N, R P) in ``x``'s dtype, and no ``y``."""
    b, length, channels = x.shape
    groups, nc, _, chunk = rows.shape[1:]
    n, rp = Bm.shape[2] // groups, channels // groups
    dtype = x.dtype
    tiles = _heads_of_tiles(heads, head_dim)

    def kernel(*refs):
        if states:
            x_ref, b_ref, rows_ref, out_ref, state = refs
        else:
            x_ref, b_ref, c_ref, rows_ref, skip_ref, out_ref, state = refs

        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros_like(state)

        rows = rows_ref[...]
        cs, grow, to_end, dt, whole = _columns(rows, heads)
        xc, Bc = x_ref[...], b_ref[...]
        xdt = xc * _wide(dt, head_dim).astype(dtype)
        opening = state[...]
        if states:
            out_ref[...] = opening.astype(dtype)
        else:
            Cc = c_ref[...]
            cb = _dot(Cc, Bc, _NT)                       # [i, j]
            later = lax.broadcasted_iota(jnp.int32, cb.shape, 0) \
                >= lax.broadcasted_iota(jnp.int32, cb.shape, 1)
            inside = []
            for first, width, mine in tiles:
                block, acc = xdt[:, first:first + width], None
                for h, lanes in mine:
                    decay = jnp.exp(jnp.where(
                        later, cs[h] - rows[h:h + 1, :], -jnp.inf))
                    part = _dot((cb * decay).astype(dtype), block, _NN)
                    acc = part if acc is None else jnp.where(
                        _own(lanes, part.shape), part, acc)
                inside.append(acc)
            y = inside[0] if len(inside) == 1 \
                else jnp.concatenate(inside, axis=1)
            y = y + _dot(Cc, opening.astype(dtype), _NN) \
                * _wide(grow, head_dim)
            y = y + skip_ref[...] * xc.astype(jnp.float32)
            out_ref[...] = y.astype(dtype)
        closing = _dot(Bc, xdt * _wide(to_end, head_dim).astype(dtype), _TN)
        state[...] = opening * _wide(whole, head_dim) + closing

    def by_position(width):
        return pl.BlockSpec((None, chunk, width),
                            lambda bi, gi, ci: (bi, ci, gi))

    def by_chunk(*block):
        return pl.BlockSpec((None, None, None, *block),
                            lambda bi, gi, ci: (bi, gi, ci, 0, 0))
    rows_spec = by_chunk(rows.shape[3], chunk)
    if states:
        operands = (x, Bm, rows)
        in_specs = [by_position(rp), by_position(n), rows_spec]
        out_spec, out_shape = by_chunk(n, rp), (b, groups, nc, n, rp)
    else:
        operands = (x, Bm, Cm, rows, skip)
        in_specs = [by_position(rp), by_position(n), by_position(n),
                    rows_spec,
                    pl.BlockSpec((1, rp), lambda bi, gi, ci: (0, gi))]
        out_spec, out_shape = by_position(rp), x.shape
    return pl.pallas_call(
        kernel,
        name=f"hvd_ssm_{'states' if states else 'fwd'}_{chunk}x{rp}x{n}",
        grid=(b, groups, nc),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        scratch_shapes=[pltpu.VMEM((n, rp), jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
    )(*operands)


def _head_sums(channels, head_dim, dtype):
    """(2 R P, 128) of zeros and ones in ``dtype``: a product with it sums
    the first R P columns by head into columns 0 .. R - 1 and the second
    R P into columns R .. 2 R - 1."""
    head = jnp.arange(2 * channels) // head_dim
    return (head[:, None] == jnp.arange(LANES)[None, :]).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "interpret"))
def _reverse_sweep(x, Bm, Cm, rows, skip, dy, opening, *, heads, head_dim,
                   interpret):
    """The chunks in reverse, given ``dy`` like ``x`` and ``opening`` from
    :func:`_sweep`. Returns ``dx``, ``dB``, ``dC`` like their inputs;
    ``drows`` like ``rows``: the gradient of the running sums (rows 0 ..
    R - 1; without what reaches them through the chunk's whole decay) and
    of ``dt`` where it multiplies ``x`` (rows R .. 2 R - 1), by position;
    and ``sums`` (b, G, chunks, 8, R P) float32, sums over a chunk by
    channel: row 0 of ``(dt x) to_end d(closing operand)``, row 1 of
    ``d(state) opening`` (both parts of the whole decay's gradient), row 2
    of ``dy x`` (D's gradient)."""
    b, length, channels = x.shape
    groups, nc, size, chunk = rows.shape[1:]
    n, rp = Bm.shape[2] // groups, channels // groups
    dtype, f32 = x.dtype, jnp.float32
    tiles = _heads_of_tiles(heads, head_dim)
    # Sums by head of float32 values through the matrix unit: whole in
    # float32 activations, as two bfloat16 parts (to 2^-17) otherwise.
    exact = dtype == f32
    by_head = _head_sums(rp, head_dim, f32 if exact else jnp.bfloat16)

    def kernel(x_ref, b_ref, c_ref, rows_ref, skip_ref, dy_ref, open_ref,
               by_head_ref, dx_ref, db_ref, dc_ref, drows_ref, sums_ref,
               dstate):
        @pl.when(pl.program_id(2) == 0)
        def _():
            dstate[...] = jnp.zeros_like(dstate)

        rows = rows_ref[...]
        cs, grow, to_end, dt, whole = _columns(rows, heads)
        xc, Bc, Cc, g = x_ref[...], b_ref[...], c_ref[...], dy_ref[...]
        opening, d_next = open_ref[...], dstate[...]
        d_next_lo = d_next.astype(dtype)
        xf, gf = xc.astype(f32), g.astype(f32)
        dt_lo = _wide(dt, head_dim).astype(dtype)
        xdt = xc * dt_lo
        to_end_lo = _wide(to_end, head_dim).astype(dtype)

        # The closing product: closing = Bc^T (xdt to_end).
        d_closing_operand = _dot(Bc, d_next_lo, _NN)        # (Q, R P)
        dB = _dot(xdt * to_end_lo, d_next_lo, _NT)          # (Q, N)
        through_end = to_end_lo.astype(f32) * d_closing_operand

        # The opening state's part: y = (Cc opening) grow.
        from_open = _dot(Cc, opening, _NN)
        g_grow = gf * _wide(grow, head_dim)
        g_grow_lo = g_grow.astype(dtype)
        dC = _dot(g_grow_lo, opening, _NT)
        dstate[...] = d_next * _wide(whole, head_dim) \
            + _dot(Cc, g_grow_lo, _TN)

        # Inside the chunk, transposed: [j, i], the source on sublanes.
        cbT = _dot(Bc, Cc, _NT)
        earlier = lax.broadcasted_iota(jnp.int32, cbT.shape, 0) \
            <= lax.broadcasted_iota(jnp.int32, cbT.shape, 1)
        d_cbT, d_inside = jnp.zeros_like(cbT), []
        for first, width, mine in tiles:
            g_tile, xdt_tile = g[:, first:first + width], \
                xdt[:, first:first + width]
            acc = None
            for h, lanes in mine:
                decay = jnp.exp(jnp.where(
                    earlier, rows[h:h + 1, :] - cs[h], -jnp.inf))
                maskT = (cbT * decay).astype(dtype)
                part = _dot(maskT, g_tile, _NN)
                own = None if lanes is None else _own(lanes, part.shape)
                acc = part if acc is None else jnp.where(own, part, acc)
                xdt_h = xdt_tile if own is None else jnp.where(
                    own, xdt_tile, jnp.zeros_like(xdt_tile))
                d_maskT = _dot(xdt_h, g_tile, _NT)
                d_cbT = d_cbT + d_maskT * decay
                # The mask as the product above read it: these column
                # sums and the row sums below (xdt d_xdt) then cancel
                # over a chunk as they must, to float32 rounding.
                drows_ref[h:h + 1, :] = jnp.sum(
                    d_maskT * maskT.astype(f32), axis=0, keepdims=True)
            d_inside.append(acc)
        d_cb_lo = d_cbT.astype(dtype)
        db_ref[...] = (dB + _dot(d_cb_lo, Cc, _NN)).astype(dtype)
        dc_ref[...] = (dC + _dot(d_cb_lo, Bc, _TN)).astype(dtype)

        d_xdt = (d_inside[0] if len(d_inside) == 1
                 else jnp.concatenate(d_inside, axis=1)) + through_end
        dx_ref[...] = (d_xdt * dt_lo.astype(f32)
                       + skip_ref[...] * gf).astype(dtype)

        # By position and head: the running sum's gradient (the column
        # sums above, and here) and dt's, summed over a head's channels.
        xdtf = xdt.astype(f32)
        per_channel = jnp.concatenate(
            [g_grow * from_open - xdtf * d_xdt, d_xdt * xf], axis=1)
        if exact:
            per_head = jnp.dot(per_channel, by_head_ref[...],
                               preferred_element_type=f32,
                               precision=lax.Precision.HIGHEST)
        else:
            hi = per_channel.astype(jnp.bfloat16)
            lo = (per_channel - hi.astype(f32)).astype(jnp.bfloat16)
            per_head = _dot(hi, by_head_ref[...], _NN) \
                + _dot(lo, by_head_ref[...], _NN)
        drows_ref[heads:, :] = jnp.zeros((size - heads, chunk), f32)
        drows_ref[...] = drows_ref[...] + per_head.T[:size]

        def over_rows(t):
            return jnp.sum(t, axis=0, keepdims=True)
        sums_ref[0:1, :] = over_rows(xdtf * through_end)
        sums_ref[1:2, :] = over_rows(d_next * opening.astype(f32))
        sums_ref[2:3, :] = over_rows(gf * xf)
        sums_ref[3:, :] = jnp.zeros((_SUM_ROWS - 3, rp), f32)

    def by_position(width):
        return pl.BlockSpec((None, chunk, width),
                            lambda bi, gi, ci: (bi, nc - 1 - ci, gi))

    def by_chunk(*block):
        return pl.BlockSpec((None, None, None, *block),
                            lambda bi, gi, ci: (bi, gi, nc - 1 - ci, 0, 0))
    operands = (x, Bm, Cm, rows, skip, dy, opening, by_head)

    def like(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        kernel,
        name=f"hvd_ssm_bwd_{chunk}x{rp}x{n}",
        grid=(b, groups, nc),
        in_specs=[
            by_position(rp), by_position(n), by_position(n),
            by_chunk(size, chunk),
            pl.BlockSpec((1, rp), lambda bi, gi, ci: (0, gi)),
            by_position(rp), by_chunk(n, rp),
            pl.BlockSpec(by_head.shape, lambda bi, gi, ci: (0, 0))],
        out_specs=[
            by_position(rp), by_position(n), by_position(n),
            by_chunk(size, chunk), by_chunk(_SUM_ROWS, rp)],
        out_shape=[like(x), like(Bm), like(Cm), like(rows),
                   jax.ShapeDtypeStruct((b, groups, nc, _SUM_ROWS, rp),
                                        f32)],
        scratch_shapes=[pltpu.VMEM((n, rp), f32)],
        compiler_params=_params(interpret),
        interpret=interpret,
    )(*operands)


def _running_sum(t, backwards=False):
    """The cumulative sum over the last axis (from its end if
    ``backwards``), float32, as a product with a triangle of ones: XLA's
    windowed sum took 0.38 ms a layer on a v5e where this takes none to
    speak of (PERF.md, PR 36)."""
    n = t.shape[-1]
    upto = jnp.arange(n)[:, None] <= jnp.arange(n)[None, :]
    return jnp.matmul(t, (upto.T if backwards else upto).astype(t.dtype),
                      precision=lax.Precision.HIGHEST)


def _operands(x, dt, A, B, C, D, chunk):
    """The kernels' operands from the scan's: the channel-major views of
    ``x``, ``B`` and ``C``, ``rows`` (a chunk and group: the running sums
    of ``dt A`` inside the chunk, then ``dt``, a row a head, zeros up to a
    whole sublane tile), D over each head's channels, and the float32
    ``dt`` and sums (b, chunks, H, chunk) they were laid from."""
    b, length, H, P = x.shape
    G, N = B.shape[-2:]
    R, nc = H // G, length // chunk
    dt = jnp.swapaxes(dt.astype(jnp.float32).reshape(b, nc, chunk, H), 2, 3)
    cs = _running_sum(dt * A.astype(jnp.float32)[:, None])

    def by_group(t):                # (b, nc, H, Q) -> (b, G, nc, R, Q)
        return jnp.swapaxes(t.reshape(b, nc, G, R, chunk), 1, 2)
    rows = jnp.concatenate(
        [by_group(cs), by_group(dt),
         jnp.zeros((b, G, nc, -2 * R % 8, chunk), jnp.float32)], axis=3)
    skip = jnp.repeat(D.astype(jnp.float32), P)[None]
    return (x.reshape(b, length, H * P), B.reshape(b, length, G * N),
            C.reshape(b, length, G * N), rows, skip), dt, cs


def _static(x, B):
    """The kernels' static arguments of a call on ``x`` and ``B``."""
    return dict(heads=x.shape[2] // B.shape[2], head_dim=x.shape[3],
                interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, A, B, C, D, chunk):
    """``parallel.ssm.ssm_scan`` by the kernels, for a call
    :func:`pick_blocks` admits. Differentiable in all six; the backward
    pass keeps the six alone and writes one set of chunk states (the state
    each chunk opens with, in ``x``'s dtype), the forward pass none."""
    operands, _, _ = _operands(x, dt, A, B, C, D, chunk)
    y = _sweep(*operands, states=False, **_static(x, B))
    return y.reshape(x.shape)


def _scan_fwd(x, dt, A, B, C, D, chunk):
    return scan(x, dt, A, B, C, D, chunk), (x, dt, A, B, C, D)


def _scan_bwd(chunk, res, dy):
    x, dt_in, A, B, C, D = res
    b, length, H, P = x.shape
    G = B.shape[2]
    R, nc = H // G, length // chunk
    static = _static(x, B)
    operands, dt, cs = _operands(x, dt_in, A, B, C, D, chunk)
    xs, Bs, _, rows, _ = operands
    opening = _sweep(xs, Bs, None, rows, None, states=True, **static)
    dx, dB, dC, drows, sums = _reverse_sweep(
        *operands, dy.reshape(xs.shape), opening, **static)

    def by_chunk(t):                # (b, G, nc, R, Q) -> (b, nc, H, Q)
        return jnp.swapaxes(t, 1, 2).reshape(b, nc, H, chunk)

    def by_head(t):                 # (b, G, nc, R P) -> (b, nc, H)
        return jnp.swapaxes(t.reshape(b, G, nc, R, P).sum(-1), 1, 2
                            ).reshape(b, nc, H)
    # The whole decay of a chunk is its last running sum.
    d_total = by_head(sums[:, :, :, 0]) \
        + jnp.exp(cs[..., -1]) * by_head(sums[:, :, :, 1])
    d_cs = by_chunk(drows[:, :, :, :R]) + jnp.where(
        jnp.arange(chunk) == chunk - 1, d_total[..., None], 0.0)
    # A running sum's gradient reaches every step before it in its chunk.
    d_step = _running_sum(d_cs, backwards=True)
    A32 = A.astype(jnp.float32)
    d_dt = by_chunk(drows[:, :, :, R:2 * R]) + d_step * A32[:, None]
    d_dt = jnp.swapaxes(d_dt, 2, 3).reshape(b, length, H)
    dA = jnp.sum(d_step * dt, axis=(0, 1, 3))
    dD = sums[:, :, :, 2].sum((0, 2)).reshape(H, P).sum(-1)
    return (dx.reshape(x.shape), d_dt.astype(dt_in.dtype),
            dA.astype(A.dtype), dB.reshape(B.shape), dC.reshape(C.shape),
            dD.astype(D.dtype))


scan.defvjp(_scan_fwd, _scan_bwd)

"""Flash attention as a Pallas TPU kernel.

The hot op of every transformer in the model zoo (models/bert.py,
models/gpt.py, parallel/tp.py). Tiled online-softmax attention: for each
query block the kernel streams key/value blocks through VMEM, keeping the
running max/denominator in registers — O(L) memory instead of materializing
the (L, L) score matrix, and every matmul lands on the MXU as a
(block_q x D) @ (D x block_k) tile. Which tiles a kernel visits, and on
which it runs mask code, is the tile schedule (_key_tile_bounds,
_query_tile_bounds): a causal call skips the tiles over the diagonal and
masks only the tiles the diagonal or the padding edge crosses; with a
sliding ``window`` it also skips the tiles wholly behind the window and
masks the tiles the window's edge crosses (_key_window_bounds,
_query_window_bounds). A long causal sequence is cut into blocks of
_OUTER_CHUNK a side, and each block runs the static schedule of its kind
(_block_kinds: diagonal, inside, the window's edge).

The reference framework has no attention code (SURVEY.md §5.7 — Horovod
operates below the model level); this kernel is part of the TPU build's
model-level capability, in the spirit of the reference's hand-written CUDA
hot loops (reference: horovod/common/ops/cuda/cuda_kernels.cu).

Queries and keys may be wider than values (latent attention: 192 against
128): the score products contract over the query/key width, ``p v`` and
the value gradients over the value width, and the output is as wide as the
values. Nothing is padded to make the widths equal.

Backward pass: custom VJP using the saved per-row logsumexp, one Pallas
kernel on TPU (hvd_flash_bwd_dqkv: tiled over key blocks, each score tile
computed once in VMEM for dK, dV and dQ, dQ of the whole (batch, head)
accumulating in VMEM) — O(L) memory end to end. A call whose dQ does not
fit that kernel's VMEM budget (backward_path, read off the call's shapes)
takes two kernels instead, a dQ pass tiled over query blocks and a dK/dV
pass tiled over key blocks, each recomputing its score tiles. Interpret
mode (CPU tests) keeps the plain jnp backward, which doubles as the
numerical oracle for the kernels.

On CPU (tests, no TPU) the kernel runs through the Pallas interpreter.
Sequence lengths with no aligned block size are padded to the next block
multiple with the padding masked inside the kernels (kv_valid), so
arbitrary lengths run the kernel path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite big-negative: avoids inf-inf NaNs in the masking


def _interpret():
    return jax.default_backend() != "tpu"


def _env_cap(cap):
    """``cap``, or HVD_FLASH_BLOCK (at least 128) where that is lower."""
    import os
    env_cap = os.environ.get("HVD_FLASH_BLOCK")
    return min(cap, max(128, int(env_cap))) if env_cap else cap


def _pick_block(length, cap=1024):
    """Tile side for a sequence of ``length``: its largest divisor among
    1024, 512, 256 and 128 up to ``cap``; a sequence under 128 is one tile
    (any multiple of the 8 sublanes); None otherwise (flash_attention then
    pads to a multiple of 128: the dK/dV kernel slices its row statistics
    along lanes, where mosaic wants multiples of 128).

    HVD_FLASH_BLOCK caps the tile lower for on-chip sweeps (128, 256 or
    512 per model without code edits); it never raises a tile."""
    cap = _env_cap(cap)
    for b in (1024, 512, 256, 128):
        if b <= cap and length % b == 0:
            return b
    return length if length < 128 and length % 8 == 0 else None


# The outer chunk: what one grid step holds of the axis a kernel writes
# (_pick_chunk). A sequence up to it is one chunk on every axis of every
# kernel, so its whole tile sweep has static bounds and unrolls (_sweep).
_OUTER_CHUNK = 1024

# (block_q, block_k) caps of a CAUSAL call whose sweep unrolls, by kernel:
# what a sweep of 128, 256 and 512 a side picked on a v5e at 8 x 16 heads x
# 1024 x 64 (PERF.md, PR 27: 14.2 + 9.9 + 11.6 ms a step against 17.8 + 9.9
# + 13.4 with 256 x 256 in all three). A tile costs a fixed ~240 cycles per
# 128 rows beside ~5 a vreg, so the forward kernel likes wide key tiles; the
# dK/dV kernel likes its tile and both accumulators in registers. The one
# backward kernel (bwd_dqkv: dK/dV with dQ beside) took 1.505 ms a call at
# 128 x 128 against the pair's 1.446, and 1.253 at 256 x 256 (16 heads x 8
# x 1024 x 64 on a v5e: PERF.md section 6).
_CAUSAL_TILE = {"fwd": (128, 512), "bwd_dq": (256, 256),
                "bwd_dkv": (128, 128), "bwd_dqkv": (256, 256)}

# The kernels that write key tiles and sweep query tiles: the dK/dV kernel
# and the one backward kernel, which is the dK/dV kernel with dQ added.
_BY_KEY = ("bwd_dkv", "bwd_dqkv")


def _pick_tiles(lq, lk, causal, kernel="fwd"):
    """(block_q, block_k) of one score tile of ``kernel``, or None where a
    length has no aligned block.

    The largest divisor up to 1024, so a non-causal sequence of up to 1024
    is one tile (nothing to skip, and only the padding edge to mask), and a
    longer one is swept in 1024 x 1024 tiles by loops whose bounds follow
    the chunk index (the diagonal bounds skip whole tiles there already;
    small tiles in rolled loops took 1.7-2.1x as long at 2048 to 8192:
    PERF.md, PR 27).
    Causal, up to _OUTER_CHUNK a side: a tile spanning the sequence
    computes the whole square for the mask to throw half away, so each side
    is capped by _CAUSAL_TILE and at half the sequence (never under the
    MXU's 128 rows): the diagonal bounds of the unrolled sweeps then skip
    what lies wholly over the diagonal, and tiles wholly under it run the
    loop body that has no mask code."""
    small = causal and max(lq, lk) <= _OUTER_CHUNK
    caps = _CAUSAL_TILE[kernel] if small else (1024, 1024)
    bq, bk = (_pick_block(n, min(cap, max(128, n // 2)) if small else cap)
              for n, cap in zip((lq, lk), caps))
    return (bq, bk) if bq and bk else None


# (block_q, block_k) inside one block of _OUTER_CHUNK a side of a causal call
# that is longer (_by_block), by kernel: ``crossed`` for a block the diagonal
# or the window's edge crosses, ``inside`` for a block wholly under the
# diagonal and inside the window, which has no mask code and nothing to skip.
# From two sweeps on a v5e at 2 x 28 heads x 8192 x 128, ms a call, causal /
# window 4096 (PERF.md, PR 32; the rolled 1024 x 1024 tiles took 10.72 /
# 10.05, 10.27 / 8.96 and 13.98 / 12.39). An inside block is one tile in all
# three: the static tile runs in 0.82, 0.95 and 0.91 of the rolled one's
# time, and 128 x 512 tiles took 1.65x as long in the forward kernel, whose
# row statistics cost a fixed sum per 128 rows of every tile. For the same
# reason its crossed block is one masked tile too (7.80 / 6.78; 256 x 512:
# 8.56 / 7.73). dQ: 8.52 / 6.82 (512 x 512: 8.65 / 7.02). dK/dV: 10.85 /
# 8.57; 128 x 128 read 10.75 / 8.40 and takes 0.9 s longer to lower.
_BLOCK_TILE = {
    "fwd": {"crossed": (1024, 1024), "inside": (1024, 1024)},
    "bwd_dq": {"crossed": (256, 256), "inside": (1024, 1024)},
    "bwd_dkv": {"crossed": (256, 256), "inside": (1024, 1024)},
    "bwd_dqkv": {"crossed": (256, 256), "inside": (1024, 1024)},
}


def _by_block(lq, lk, q_offset, kv_valid, causal, window):
    """Whether a call runs the static schedule by block kind: a causal call
    longer than _OUTER_CHUNK on both axes, with no padding, whose lengths,
    offset and window are whole blocks, so that the mask's edges cross a
    block only corner to corner. Every other call keeps one tile shape and
    bounds that follow the chunk index."""
    whole = (lq, lk, q_offset) + (() if window is None else (window,))
    return bool(causal and kv_valid == lk and min(lq, lk) > _OUTER_CHUNK
                and (window is None or window > 0)
                and not any(n % _OUTER_CHUNK for n in whole))


def _block_tiles(kernel):
    """((block_q, block_k) of a crossed block, of an inside block)."""
    cap = _env_cap(_OUTER_CHUNK)
    return tuple(tuple(min(side, cap) for side in _BLOCK_TILE[kernel][kind])
                 for kind in ("crossed", "inside"))


def _vma(*operands):
    """How the operands vary over the mesh inside a VMA-checked shard_map;
    the kernel outputs must declare the same."""
    return frozenset().union(*(jax.typeof(t).vma for t in operands))


def _scratch(shape):
    """VMEM scratch accumulator (persists across the sequential innermost
    grid sweep on one core)."""
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(interpret):
    """Raise mosaic's scoped-VMEM budget (default 16 MB). The small causal
    tiles (at most 64 Ki elements: 256 KiB a float32 temporary) fit the
    default; what needs the room is a 1024 x 1024 tile (4 MiB a temporary,
    half a dozen alive: it compiles and runs on a v5e under this limit,
    PR 21 and PR 27's sweep). v5e has far more physical VMEM than the
    default admits."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _pick_chunk(length, block, cap=4096):
    """Largest multiple of ``block`` dividing ``length``, capped.

    The chunk is the unit the grid streams through VMEM (bounding VMEM at
    O(chunk) so 8k+ contexts fit the scoped budget); within a chunk
    register-carried fori_loops sweep ``block``-sized MXU tiles (grid
    steps are too fine-grained to carry the softmax state efficiently,
    and cost up to 0.6 us each: PERF.md, PR 25). Both axes are chunked: the
    axis the kernel accumulates over in chunks of up to 4096, the axis it
    writes in chunks of up to _OUTER_CHUNK."""
    c = min(length, cap)
    while c > block and length % c:
        c -= block
    return c


# ---------------------------------------------------------------------------
# The tile schedule: which (block_q, block_k) tiles of the score matrix a
# kernel visits and which of those need mask code. Element (i, j) is allowed
# iff j < kv_valid and (not causal or j <= i + q_offset) and (no window or
# j > i + q_offset - window: the query counts among its window). These
# functions give the kernels their loop bounds AND the hvd_flash_tiles gauge
# its counts; tile indices may be Python ints (static bounds) or traced
# scalars (the chunk index is a grid variable).
# ---------------------------------------------------------------------------

def _clip(x, lo, hi):
    if isinstance(x, int):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _select(cond, a, b):
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


def _least(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _most(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _key_tile_bounds(q0, block_q, block_k, n_kt, q_offset, kv_valid, causal):
    """One row of tiles: query rows [q0, q0 + block_q) against ``n_kt`` key
    tiles. Returns (n_plain, n_vis): tiles [0, n_plain) are wholly allowed
    (no mask code), [n_plain, n_vis) are crossed by the diagonal or the
    padding edge, [n_vis, n_kt) are wholly masked and not visited."""
    n_vis = min(n_kt, -(-kv_valid // block_k))
    n_plain = min(n_kt, kv_valid // block_k)
    if causal:
        # The last row attends keys <= q0 + block_q - 1 + q_offset, the
        # first (which sees least) keys <= q0 + q_offset.
        n_vis = _clip(-(-(q0 + block_q + q_offset) // block_k), 0, n_vis)
        n_plain = _clip((q0 + q_offset + 1) // block_k, 0, n_plain)
    return n_plain, n_vis


def _query_tile_bounds(k0, block_q, block_k, n_qt, q_offset, kv_valid,
                       causal):
    """One column of tiles: keys [k0, k0 + block_k) against ``n_qt`` query
    tiles. Returns (t_first, t_plain): tiles [0, t_first) are wholly masked
    and not visited, [t_first, t_plain) are crossed, [t_plain, n_qt) are
    wholly allowed."""
    t_first = t_plain = 0
    if causal:
        t_first = _clip((k0 - q_offset) // block_q, 0, n_qt)
        t_plain = _clip(-(-(k0 + block_k - 1 - q_offset) // block_q),
                        0, n_qt)
    # A key tile of padding alone is never visited; one the padding edge
    # crosses is masked on every visit.
    t_first = _select(k0 < kv_valid, t_first, n_qt)
    t_plain = _select(k0 + block_k <= kv_valid, t_plain, n_qt)
    return t_first, t_plain


def _key_window_bounds(q0, block_q, block_k, n_kt, q_offset, window):
    """The same row of tiles against the window's edge. Returns (n_skip,
    n_inside): tiles [0, n_skip) lie wholly behind the window of every row
    and are not visited, [n_skip, n_inside) are crossed by the edge,
    [n_inside, n_kt) lie wholly on the window's side of it."""
    # The first row (whose window reaches back furthest) sees keys >
    # q0 + q_offset - window, the last keys > q0 + block_q - 1 + q_offset
    # - window.
    n_skip = _clip((q0 + q_offset - window + 1) // block_k, 0, n_kt)
    n_inside = _clip(-(-(q0 + block_q + q_offset - window) // block_k),
                     0, n_kt)
    return n_skip, n_inside


def _query_window_bounds(k0, block_q, block_k, n_qt, q_offset, window):
    """The same column of tiles against the window's edge. Returns
    (t_inside, t_end): tiles [0, t_inside) lie wholly on the window's side
    of the edge, [t_inside, t_end) are crossed by it, [t_end, n_qt) see
    none of these keys and are not visited."""
    # Key k0 (which leaves the window first) is seen by rows < k0 + window
    # - q_offset, key k0 + block_k - 1 by rows < k0 + block_k - 1 + window
    # - q_offset.
    t_inside = _clip((k0 + window - q_offset) // block_q, 0, n_qt)
    t_end = _clip(-(-(k0 + block_k - 1 + window - q_offset) // block_q),
                  0, n_qt)
    return t_inside, t_end


def _key_sweeps(q0, block_q, block_k, n_kt, q_offset, kv_valid, causal,
                window):
    """(n_skip, n_low, n_plain, n_vis) of one row of tiles: [n_skip, n_low)
    is swept with mask code (the window's edge), [n_low, n_plain) without,
    [n_plain, n_vis) with (the diagonal, the padding edge); nothing else is
    visited. A tile that both edges cross is in a masked sweep. Without a
    window the first sweep is empty: 0, 0, and _key_tile_bounds' two."""
    n_plain, n_vis = _key_tile_bounds(q0, block_q, block_k, n_kt, q_offset,
                                      kv_valid, causal)
    if window is None:
        return 0, 0, n_plain, n_vis
    n_skip, n_low = (_least(b, n_vis) for b in _key_window_bounds(
        q0, block_q, block_k, n_kt, q_offset, window))
    return n_skip, n_low, _most(n_plain, n_low), n_vis


def _query_sweeps(k0, block_q, block_k, n_qt, q_offset, kv_valid, causal,
                  window):
    """(t_first, t_plain, t_high, t_end) of one column of tiles:
    [t_first, t_plain) is swept with mask code (the diagonal, the padding
    edge), [t_plain, t_high) without, [t_high, t_end) with (the window's
    edge). Without a window the last sweep is empty: t_high = t_end =
    n_qt."""
    t_first, t_plain = _query_tile_bounds(k0, block_q, block_k, n_qt,
                                          q_offset, kv_valid, causal)
    if window is None:
        return t_first, t_plain, n_qt, n_qt
    t_inside, t_end = _query_window_bounds(k0, block_q, block_k, n_qt,
                                           q_offset, window)
    t_end = _most(t_end, t_first)
    t_plain = _least(t_plain, t_end)
    return t_first, t_plain, _most(_least(t_inside, t_end), t_plain), t_end


def _in_chunk(bounds, c, tpc, n_tiles):
    """Tile bounds along a whole axis of ``n_tiles``, as indices into its
    chunk ``c`` of ``tpc`` tiles. A static bound at the axis's start or end
    stays static at every chunk's start or end, so that a sweep that is
    empty on the whole axis (no window) is no code in any chunk."""
    def one(b):
        if isinstance(b, int) and b in (0, n_tiles):
            return 0 if b == 0 else tpc
        return _clip(b - c * tpc, 0, tpc)
    return tuple(one(b) for b in bounds)


def _visited_tiles(kernel, lq, lk, q_offset, kv_valid, block_q, block_k,
                   causal, window=None):
    """(first row, first key, masked) of every score tile one call of
    ``kernel`` visits, masked = with mask code, from the bounds the
    kernels sweep by: the forward and dQ kernels rows of tiles, the dK/dV
    kernel columns."""
    by_key = kernel in _BY_KEY
    n_qt, n_kt = lq // block_q, lk // block_k
    for o in range(n_kt if by_key else n_qt):
        # Either way the four bounds are: masked, plain, masked.
        a, b, c, d = _query_sweeps(
            o * block_k, block_q, block_k, n_qt, q_offset, kv_valid, causal,
            window) if by_key else _key_sweeps(
            o * block_q, block_q, block_k, n_kt, q_offset, kv_valid, causal,
            window)
        for t in range(a, d):
            row, col = (t, o) if by_key else (o, t)
            yield row * block_q, col * block_k, not b <= t < c


def tile_counts(kernel, lq, lk, q_offset, kv_valid, block_q, block_k,
                causal, window=None):
    """Score tiles per (batch, head) of one call of ``kernel``: ``total``,
    ``visited`` and ``masked`` (visited with mask code)."""
    masked = [m for _, _, m in _visited_tiles(
        kernel, lq, lk, q_offset, kv_valid, block_q, block_k, causal, window)]
    return {"total": (lq // block_q) * (lk // block_k),
            "visited": len(masked), "masked": sum(masked)}


def _block_kinds(delta, block, window):
    """What a block of ``block`` a side can be, by ``delta``: its query
    index (the offset counted in) less its key index. A list of (kind,
    whether the block is of it, the (q_offset, window) that put the mask's
    edges where they lie in such a block, counted from the block's own
    corner). A block of no kind is wholly masked and not visited.
    ``delta`` may be a Python int or a traced scalar."""
    diagonal = ("diagonal", delta == 0, (0, None))
    if window is None:
        return [diagonal, ("inside", delta > 0, (block, None))]
    w = window // block
    # Inside the window every key of the block is seen by every row, as it
    # is one block under the diagonal of a call with no window.
    inside = [("inside", (delta > 0) & (delta < w), (block, None))]
    return [diagonal] + inside * (w > 1) + [
        ("edge", delta == w, (block, block))]


def _tile_of(tiles, kind):
    """(block_q, block_k) of a block of ``kind`` from _block_tiles' pair."""
    return tiles[kind == "inside"]


def block_tiles(kernel, lq, lk, q_offset, window, tiles, block):
    """The score tiles one call of ``kernel`` visits under the schedule by
    block kind, as (kind, first row, first key, block_q, block_k, masked):
    every block of a kind is the square _visited_tiles cuts for it."""
    for i in range(lq // block):
        for j in range(lk // block):
            for kind, is_kind, (off, win) in _block_kinds(
                    i + q_offset // block - j, block, window):
                if is_kind:
                    tile = _tile_of(tiles, kind)
                    for row, col, masked in _visited_tiles(
                            kernel, block, block, off, block, *tile, True,
                            win):
                        yield (kind, i * block + row, j * block + col, *tile,
                               masked)


def block_counts(kernel, lq, lk, q_offset, window, tiles, block):
    """tile_counts under the schedule by block kind: ``visited`` and
    ``masked`` tiles as block_tiles gives them, ``blocks_<kind>`` the
    blocks of each kind, ``total`` every block cut in its kind's tiles (a
    skipped block in the crossed kind's)."""
    visited = list(block_tiles(kernel, lq, lk, q_offset, window, tiles,
                               block))
    blocks = {(r // block, c // block): kind
              for kind, r, c, *_ in visited}
    counts = {f"blocks_{kind}": list(blocks.values()).count(kind)
              for kind in ("inside", "diagonal", "edge")}
    n_blocks = (lq // block) * (lk // block)
    counts["blocks_skipped"] = n_blocks - len(blocks)
    (cq, ck), (iq, ik) = tiles
    n_inside = counts["blocks_inside"]
    counts["total"] = (n_inside * (block // iq) * (block // ik)
                       + (n_blocks - n_inside) * (block // cq) * (block // ck))
    counts["visited"] = len(visited)
    counts["masked"] = sum(t[-1] for t in visited)
    return counts


def _record_tiles(kernel, counts):
    from horovod_tpu.metrics import instruments as hvd_metrics
    blocks = dict.fromkeys(("blocks_inside", "blocks_diagonal",
                            "blocks_edge", "blocks_skipped"), 0)
    hvd_metrics.record_flash_tiles(kernel, {**blocks, **counts})


# A statically bounded sweep of up to this many tiles is unrolled.
_UNROLL_TILES = 8


def _sweep(lo, hi, body, carry):
    """``carry`` through ``body(t, carry)`` for t in [lo, hi).

    Static bounds (one chunk on both grid axes: every sequence up to 1024)
    unroll into straight-line code: a tile's chain of matmul, row
    statistics and matmul is latency-bound, and only unrolled can the
    scheduler overlap the chains of independent tiles (a rolled loop took
    1.6x as long at 256 x 256 on a v5e: PERF.md, PR 27). Traced bounds
    (the chunk index is a grid variable) take a fori_loop."""
    if isinstance(lo, int) and isinstance(hi, int) \
            and hi - lo <= _UNROLL_TILES:
        for t in range(lo, hi):
            carry = body(t, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _when(cond, fn):
    """``fn()`` if ``cond``, for a static or a traced condition."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _apply_mask(s, *, causal, masked, q0, k0, kv_valid, q_axis=0,
                window=None):
    """Combined causal + key-validity masking for one score tile, (BQ, BK)
    or, with ``q_axis=1``, its transpose.

    ``masked`` (static) is True when the key axis was padded to a block
    multiple: keys at global position >= kv_valid are padding and must not
    receive weight. ``q0``/``k0`` are the tile's global row/key offsets.
    ``window`` (causal only) keeps the keys j with q - window < j <= q.
    """
    if not (causal or masked):
        return s
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    ok = None
    if masked:
        ok = k_pos < kv_valid
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        c = q_pos >= k_pos
        if window is not None:
            c &= k_pos > q_pos - window
        ok = c if ok is None else ok & c
    return jnp.where(ok, s, NEG_INF)


def _from(base, i):
    """``base + i``; a static zero base adds no op (a call outside the
    schedule by block kind keeps its code to the letter)."""
    return i if isinstance(base, int) and base == 0 else base + i


def _each_block(n, block, fn):
    """``fn(first row)`` for the n blocks of ``block`` rows of a chunk: a
    rolled loop, one copy of the code whatever the chunk holds (a block's
    own sweeps unroll: their bounds do not depend on where it lies)."""
    if n == 1:
        return fn(0)

    def body(b, carry):
        fn(pl.multiple_of(b * block, block))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _sweep_chunk(sweep_tile, ic, jc, *, by_key, tiles, block, chunks,
                 q_offset, n_swept, kv_valid, causal, masked, window):
    """The schedule of one grid step: chunk ``ic`` of the axis the kernel
    writes (queries; ``by_key`` keys, the dK/dV kernel) against chunk ``jc``
    of ``n_swept`` of the axis it sweeps. For every tile of the written
    chunk, ``sweep_tile(at, swept0, side, bounds, mask)``: the tile at
    ``at`` of its chunk against the tiles of ``side`` that ``bounds``
    (_key_sweeps; by_key _query_sweeps) name, counted from position
    ``swept0`` of the swept chunk; ``mask(s, t)`` masks tile t of them.

    ``block`` None: one tile shape, ``tiles``, and bounds that follow the
    chunk index. Else (_by_block) the written chunk is one block of
    ``block`` a side, and each block of the swept chunk runs the sweeps of
    its kind, at that kind's tiles, with static bounds."""
    w = int(by_key)                  # of (queries, keys): the axis written
    sweeps = _query_sweeps if by_key else _key_sweeps

    def run(tile, origin, n, swept0, swept_origin, bounds_of, off, **edges):
        """The ``n`` written positions from ``origin`` on, tile by tile,
        against tiles that start at position ``swept_origin``; ``off`` and
        ``edges``: the q_offset and the rest of what _apply_mask takes."""
        for i in range(n // tile[w]):
            o = origin + i * tile[w]               # first position, this tile

            def mask(s, t, o=o):
                at = swept_origin + t * tile[1 - w]
                q0, k0 = (at, o) if by_key else (o, at)
                return _apply_mask(s, q0=off + q0, k0=k0, q_axis=w, **edges)

            sweep_tile(pl.ds(i * tile[w], tile[w]), swept0, tile[1 - w],
                       bounds_of(o), mask)

    if block is None:
        tpc = chunks[1 - w] // tiles[1 - w]        # swept tiles per chunk
        run(tiles, ic * chunks[w], chunks[w], 0, jc * chunks[1 - w],
            lambda o: _in_chunk(
                sweeps(o, *tiles, n_swept * tpc, q_offset, kv_valid, causal,
                       window), jc, tpc, n_swept * tpc),
            # End-aligned causal convention (tril with k = Lk - Lq),
            # matching local_attention.
            q_offset, causal=causal, masked=masked, kv_valid=kv_valid,
            window=window)
        return

    def one_block(swept0):
        at = jc * chunks[1 - w] + swept0           # of the swept axis
        delta = (at + q_offset) // block - ic if by_key \
            else ic + (q_offset - at) // block
        # Rows and keys count from the block's own corner from here on.
        for kind, is_kind, (off, win) in _block_kinds(delta, block, window):
            tile = _tile_of(tiles, kind)
            _when(is_kind, functools.partial(
                run, tile, 0, block, swept0, 0,
                lambda o: sweeps(o, *tile, block // tile[1 - w], off, block,
                                 True, win),
                off, causal=True, masked=False, kv_valid=block, window=win))

    _each_block(chunks[1 - w] // block, block, one_block)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
               *, sm_scale, causal, tiles, block, q_chunk, k_chunk,
               q_offset, n_qc, n_kc, kv_valid, masked, window):
    """One (query-chunk, key-chunk) grid step of the online softmax.

    The key-chunk sweep is the INNERMOST grid dimension; the running
    (m, l, acc) state lives in VMEM scratch across chunk steps and in
    registers within a query tile's sweep over the chunk's key tiles:
    with a window first the tiles its edge crosses, then the tiles wholly
    under the diagonal and inside kv_valid (no mask code in the loop
    body), then those the diagonal or the padding edge crosses; past
    _OUTER_CHUNK block by block (_sweep_chunk).
    """
    # A grid axis of one chunk gives a STATIC chunk index, and with both
    # static every loop bound below is a compile-time constant.
    ic = 0 if n_qc == 1 else pl.program_id(1)
    jc = 0 if n_kc == 1 else pl.program_id(2)

    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _when(jc == 0, _init)

    def row_of_tiles(rows, keys0, block_k, bounds, mask):
        """The query tile at ``rows`` of its chunk against key tiles of
        ``block_k``, counted from key ``keys0`` of their chunk, that
        ``bounds`` (_key_sweeps) name; ``mask(s, t)`` masks tile t."""
        n_skip, n_low, n_plain, n_vis = bounds

        def _compute():
            q = q_ref[0, rows, :].astype(jnp.float32) * sm_scale  # (BQ, D)

            def body(t, carry, crossed):
                m, l, acc = carry
                keys = pl.ds(_from(keys0, t * block_k), block_k)
                kb = k_ref[0, keys, :].astype(jnp.float32)
                vb = v_ref[0, keys, :].astype(jnp.float32)
                s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if crossed:
                    s = mask(s, t)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                corr = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[:, None])
                if crossed:
                    # Rows where every score so far is masked give
                    # exp(0) = 1; zero them.
                    p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[:, None] + jax.lax.dot_general(
                    p, vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            carry = (m_ref[rows, 0], l_ref[rows, 0], acc_ref[rows, :])
            carry = _sweep(n_skip, n_low,
                           functools.partial(body, crossed=True), carry)
            carry = _sweep(n_low, n_plain,
                           functools.partial(body, crossed=False), carry)
            m, l, acc = _sweep(n_plain, n_vis,
                               functools.partial(body, crossed=True), carry)
            m_ref[rows, :] = m[:, None]
            l_ref[rows, :] = l[:, None]
            acc_ref[rows, :] = acc

        _when(n_vis > n_skip, _compute)

    _sweep_chunk(row_of_tiles, ic, jc, by_key=False, tiles=tiles,
                 block=block, chunks=(q_chunk, k_chunk), q_offset=q_offset,
                 n_swept=n_kc, kv_valid=kv_valid, causal=causal,
                 masked=masked, window=window)

    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)            # (q_chunk, 1)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lse leaves as a (1, 1, q_chunk) row: a (q_chunk, 1) column pads
        # every element to a 128-lane tile in HBM (64 MiB for 128 x 1024
        # floats), which the backward kernels then stream back in.
        lse_ref[0, 0, :] = (m_ref[...] + jnp.log(l_safe))[:, 0]

    _when(jc == n_kc - 1, _finalize)


def _fa_forward(q, k, v, causal, sm_scale, block_q=None, block_k=None,
                q_offset=None, kv_valid=None, heads=None, kv_heads=None,
                window=None):
    """(B*H, Lq, Dqk) x (B*KV, Lk, Dqk) x (B*KV, Lk, Dv) -> (o, lse), o of
    (B*H, Lq, Dv).

    ``block_q``/``block_k`` default to :func:`_pick_tiles`' choice for
    the forward kernel. ``q_offset``/``kv_valid`` override the end-aligned
    causal offset and the number of VALID keys when the inputs were padded
    to block multiples (positions are always in ORIGINAL coordinates).
    ``window`` (causal only): row i sees keys j with i + q_offset - window
    < j <= i + q_offset.

    Grouped-query attention: with ``kv_heads < heads`` the K/V tensors
    carry only the grouped heads and the kernel streams each kv head's
    chunks to its ``heads/kv_heads`` query heads via the BlockSpec index
    map — no materialized broadcast, 1/g the K/V HBM traffic."""
    lq, lk = q.shape[1], k.shape[1]
    if q_offset is None:
        q_offset = lk - lq
    if kv_valid is None:
        kv_valid = lk
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")
    tiles, block = _schedule("fwd", lq, lk, q_offset, kv_valid, causal,
                             window, block_q, block_k)
    gqa = heads is not None and kv_heads is not None and heads != kv_heads
    return _fwd_call(
        q, k, v, causal=causal, sm_scale=sm_scale, tiles=tiles, block=block,
        chunks=(_pick_chunk(lq, block or tiles[0], _OUTER_CHUNK),
                _pick_chunk(lk, block or tiles[1])),
        q_offset=q_offset, kv_valid=kv_valid, window=window,
        group=(heads, kv_heads) if gqa else None, interpret=_interpret())


def _schedule(kernel, lq, lk, q_offset, kv_valid, causal, window,
              block_q=None, block_k=None):
    """(tiles, block) of one call of ``kernel``, read off the call's shapes
    alone, and the hvd_flash_tiles gauge set to what they visit: by block
    kind (_by_block; tiles as _block_tiles gives them, block =
    _OUTER_CHUNK), else one (block_q, block_k), :func:`_pick_tiles`' unless
    given, and block None."""
    if block_q is None and _by_block(lq, lk, q_offset, kv_valid, causal,
                                     window):
        tiles, block = _block_tiles(kernel), _OUTER_CHUNK
        _record_tiles(kernel, block_counts(kernel, lq, lk, q_offset, window,
                                           tiles, block))
        return tiles, block
    tiles = (block_q, block_k) if block_q else \
        _pick_tiles(lq, lk, causal, kernel)
    _record_tiles(kernel, tile_counts(kernel, lq, lk, q_offset, kv_valid,
                                      *tiles, causal, window))
    return tiles, None


def _fetched(i, j, *, by_key, block, chunk, n, q_offset, window):
    """The chunk of the swept axis (of ``n`` positions in chunks of
    ``chunk``) that grid step (i, j) fetches: its own, j, on every path but
    the schedule by block kind. There the written chunk i is one block,
    which sees the blocks from the window's edge to the diagonal (a query
    block; ``by_key`` a key block, which the rows from the diagonal to the
    window's edge see), and a step whose chunk holds none of them asks for
    the nearest chunk that does: the one the pipeline already holds, so
    that no copy starts for a step that computes nothing (4096 keys and
    values of 128 are 2 MiB, 2.6 us of HBM time)."""
    if block is None:
        return j
    off, last_block = q_offset // block, n // block - 1
    if by_key:
        first = i - off
        last = last_block if window is None else first + window // block
    else:
        last = i + off
        first = 0 if window is None else last - window // block
    first, last = (jnp.clip(x, 0, last_block) // (chunk // block)
                   for x in (first, last))
    return jnp.clip(j, first, last)


# The pallas_calls sit in jitted functions of their own, every choice a
# static argument, so that the 24 layers of a model trace, lower and hash one
# kernel and not 24: a kernel body of a dozen unrolled tiles takes 0.1-0.3 s
# to trace, and traced per layer the three kernels added 50 s to the set-up
# of gpt2m_1chip (PERF.md, PR 27).
_CALL_STATICS = ("causal", "sm_scale", "tiles", "block", "chunks",
                 "q_offset", "kv_valid", "window", "interpret")


@functools.partial(jax.jit, static_argnames=_CALL_STATICS + ("group",))
def _fwd_call(q, k, v, *, causal, sm_scale, tiles, block, chunks, q_offset,
              kv_valid, window, group, interpret):
    bh, lq, d = q.shape
    lk, d_v = k.shape[1], v.shape[2]
    q_chunk, k_chunk = chunks
    swept = functools.partial(_fetched, by_key=False, block=block,
                              chunk=k_chunk, n=lk, q_offset=q_offset,
                              window=window)

    if group is None:
        def kv_map(b, i, j):
            return (b, swept(i, j), 0)
    else:
        heads, kv_heads = group
        g = heads // kv_heads

        def kv_map(b, i, j):
            return ((b // heads) * kv_heads + (b % heads) // g, swept(i, j),
                    0)
    n_qc, n_kc = lq // q_chunk, lk // k_chunk
    kernel = functools.partial(_fa_kernel, sm_scale=sm_scale, causal=causal,
                               tiles=tiles, block=block,
                               q_chunk=q_chunk, k_chunk=k_chunk,
                               q_offset=q_offset, n_qc=n_qc, n_kc=n_kc,
                               kv_valid=kv_valid, masked=kv_valid < lk,
                               window=window)
    vma = _vma(q, k, v)
    o, lse = pl.pallas_call(
        kernel,
        name="hvd_flash_fwd",
        grid=(bh, n_qc, n_kc),
        in_specs=[
            pl.BlockSpec((1, q_chunk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, q_chunk, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, q_chunk), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d_v), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[_scratch((q_chunk, 1)), _scratch((q_chunk, 1)),
                        _scratch((q_chunk, d_v))],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, 0]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, causal, sm_scale, block_q=None, block_k=None,
           q_offset=None, kv_valid=None, heads=None, kv_heads=None,
           window=None):
    """``block_q``/``block_k`` None: each kernel takes its own tile shape
    from :func:`_pick_tiles`.

    ``heads``/``kv_heads`` (static) turn on grouped-query attention:
    q carries B*heads rows, k/v only B*kv_heads. The forward streams the
    NARROW k/v through the kernel (index-mapped, no broadcast); the
    backward broadcasts once and group-sums dK/dV — forward/serving
    bandwidth is where GQA pays."""
    o, _ = _fa_forward(q, k, v, causal, sm_scale, block_q, block_k,
                       q_offset, kv_valid, heads=heads, kv_heads=kv_heads,
                       window=window)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, block_q=None, block_k=None,
               q_offset=None, kv_valid=None, heads=None, kv_heads=None,
               window=None):
    o, lse = _fa_forward(q, k, v, causal, sm_scale, block_q, block_k,
                         q_offset, kv_valid, heads=heads, kv_heads=kv_heads,
                         window=window)
    return o, (q, k, v, o, lse)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, sm_scale, causal, tiles, block,
                      q_chunk, k_chunk, q_offset, n_qc, n_kc, kv_valid,
                      masked, window):
    """dQ pass: (query-chunk, key-chunk) grid with the dq accumulator in
    scratch across key chunks; per query tile the same register sweeps
    over the chunk's key tiles as _fa_kernel (the window's edge, plain,
    the diagonal), and with ``block`` the same schedule by block kind."""
    ic = 0 if n_qc == 1 else pl.program_id(1)
    jc = 0 if n_kc == 1 else pl.program_id(2)

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _when(jc == 0, _init)

    def row_of_tiles(rows, keys0, block_k, bounds, mask):
        n_skip, n_low, n_plain, n_vis = bounds

        def _compute():
            q = q_ref[0, rows, :].astype(jnp.float32)              # (BQ, D)
            do = do_ref[0, rows, :].astype(jnp.float32)
            lse = lse_ref[0, 0, rows]                              # (BQ,)
            delta = delta_ref[0, 0, rows]

            def body(t, dq, crossed):
                keys = pl.ds(_from(keys0, t * block_k), block_k)
                kb = k_ref[0, keys, :].astype(jnp.float32)
                vb = v_ref[0, keys, :].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if crossed:
                    s = mask(s, t)
                    p = jnp.where(s > NEG_INF * 0.5,
                                  jnp.exp(s - lse[:, None]), 0.0)
                else:
                    p = jnp.exp(s - lse[:, None])
                dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = p * (dp - delta[:, None]) * sm_scale
                return dq + jax.lax.dot_general(
                    ds, kb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            dq = _sweep(n_skip, n_low, functools.partial(body, crossed=True),
                        acc_ref[rows, :])
            dq = _sweep(n_low, n_plain,
                        functools.partial(body, crossed=False), dq)
            acc_ref[rows, :] = _sweep(
                n_plain, n_vis, functools.partial(body, crossed=True), dq)

        _when(n_vis > n_skip, _compute)

    _sweep_chunk(row_of_tiles, ic, jc, by_key=False, tiles=tiles,
                 block=block, chunks=(q_chunk, k_chunk), q_offset=q_offset,
                 n_swept=n_kc, kv_valid=kv_valid, causal=causal,
                 masked=masked, window=window)

    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)

    _when(jc == n_kc - 1, _finalize)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       *refs, sm_scale, causal, tiles, block, q_chunk,
                       k_chunk, q_offset, n_qc, n_kc, kv_valid, masked,
                       window, fused):
    """dK/dV pass: (key-chunk, query-chunk) grid; per-key-chunk
    accumulators in scratch across query chunks; per key tile register
    sweeps over the chunk's query tiles: first those the mask edge
    crosses (the diagonal comes first going down a column), then the
    plain ones under it, then with a window those its edge crosses. With
    ``block`` the key chunk is one block and each block of the query chunk
    runs the sweeps of its kind.

    The tile is computed TRANSPOSED, (BK, BQ) = k q^T, so that both
    accumulating products (p^T dO, ds^T q) contract the tile's lane axis
    as plain matmuls; from the (BQ, BK) tile they contract its row axis,
    and mosaic transposes p and ds through the XLU on every tile (dK/dV at
    128 x 128 on a v5e: 15.8 ms a step that way, 11.6 this way: PERF.md,
    PR 27). lse and delta arrive as (1, q_chunk) rows for it."""
    # ``fused`` (hvd_flash_bwd_dqkv): dQ from the same ds, into a float32
    # accumulator of the whole (batch, head) held TRANSPOSED, (Dqk, Lq), in
    # VMEM across both inner grid axes: each tile adds k^T ds, whose k^T is
    # taken once per key tile, so that no tile is transposed; the
    # accumulator is transposed once, into the dQ block, at the (batch,
    # head)'s last step.
    if fused:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    ic = 0 if n_kc == 1 else pl.program_id(1)      # key chunk (written)
    jc = 0 if n_qc == 1 else pl.program_id(2)      # query chunk (swept)

    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _when(jc == 0, _init)
    if fused:
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        _when((ic == 0) & (jc == 0), _init_dq)

    def column_of_tiles(cols, rows0, block_q, bounds, mask):
        """The key tile at ``cols`` of its chunk against query tiles of
        ``block_q``, counted from row ``rows0`` of their chunk, that
        ``bounds`` (_query_sweeps) name; ``mask(s, t)`` masks tile t."""
        t_first, t_plain, t_high, t_end = bounds

        def _compute():
            kb = k_ref[0, cols, :].astype(jnp.float32)             # (BK, D)
            vb = v_ref[0, cols, :].astype(jnp.float32)
            if fused:
                kt = kb.T                                          # (D, BK)
                q0 = _from(jc * q_chunk, rows0)    # of the whole sequence

            def body(t, carry, crossed):
                dk, dv = carry
                tile = pl.ds(_from(rows0, t * block_q), block_q)
                qb = q_ref[0, tile, :].astype(jnp.float32)
                dob = do_ref[0, tile, :].astype(jnp.float32)
                lse_b = lse_ref[0, :, tile]                        # (1, BQ)
                delta_b = delta_ref[0, :, tile]
                s = jax.lax.dot_general(
                    kb, qb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if crossed:
                    s = mask(s, t)
                    p = jnp.where(s > NEG_INF * 0.5,
                                  jnp.exp(s - lse_b), 0.0)
                else:
                    p = jnp.exp(s - lse_b)
                dv = dv + jax.lax.dot_general(
                    p, dob, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(vb, dob, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = p * (dp - delta_b) * sm_scale
                dk = dk + jax.lax.dot_general(
                    ds, qb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if fused:
                    at = pl.ds(_from(q0, t * block_q), block_q)
                    dq_acc[:, at] += jax.lax.dot_general(
                        kt, ds, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                return dk, dv

            carry = _sweep(t_first, t_plain,
                           functools.partial(body, crossed=True),
                           (dk_acc[cols, :], dv_acc[cols, :]))
            carry = _sweep(t_plain, t_high,
                           functools.partial(body, crossed=False), carry)
            dk, dv = _sweep(t_high, t_end,
                            functools.partial(body, crossed=True), carry)
            dk_acc[cols, :] = dk
            dv_acc[cols, :] = dv

        _when(t_first < t_end, _compute)

    _sweep_chunk(column_of_tiles, ic, jc, by_key=True, tiles=tiles,
                 block=block, chunks=(q_chunk, k_chunk), q_offset=q_offset,
                 n_swept=n_qc, kv_valid=kv_valid, causal=causal,
                 masked=masked, window=window)

    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    _when(jc == n_qc - 1, _finalize)
    if fused:
        def _store_dq():
            dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)

        _when((ic == n_kc - 1) & (jc == n_qc - 1), _store_dq)


# VMEM the fused backward may give the dQ of one (batch, head): its float32
# accumulator and the two buffers of its output block, whose lanes pad to
# 128. Compiled for a v5e, the rest of that kernel at 1024 x 1024 tiles asks
# 5-6 MiB of _compiler_params' 64 MiB (34816 x 192 asked 65 MiB for 59.5
# held, 65536 x 128 69 for 64), so 40 MiB: 40960 queries at 128 wide,
# 22528 at 192, in bfloat16.
_DQ_VMEM_BUDGET = 40 * 1024 * 1024


def backward_path(lq, dqk, itemsize):
    """The backward kernels of a call whose queries are ``lq`` long and
    ``dqk`` wide, of ``itemsize`` bytes: ("bwd_dqkv",), the one kernel,
    where its dQ of a whole (batch, head) fits _DQ_VMEM_BUDGET; else the
    pair ("bwd_dq", "bwd_dkv"), which recomputes each score tile for dQ."""
    lanes = -(-dqk // 128) * 128
    held = lq * dqk * 4 + 2 * lq * lanes * itemsize
    return ("bwd_dqkv",) if held <= _DQ_VMEM_BUDGET else ("bwd_dq", "bwd_dkv")


def _fa_backward(q, k, v, o, lse, do, causal, sm_scale, block_q=None,
                 block_k=None, q_offset=None, kv_valid=None, window=None):
    """O(L)-memory backward: (dq, dk, dv) by the kernels
    :func:`backward_path` names, each with :func:`_pick_tiles`' tile shape
    for it unless one is given."""
    lq, lk = q.shape[1], k.shape[1]
    if q_offset is None:
        q_offset = lk - lq
    if kv_valid is None:
        kv_valid = lk
    kernels = backward_path(lq, q.shape[2], q.dtype.itemsize)
    tiles, blocks = zip(*(
        _schedule(kernel, lq, lk, q_offset, kv_valid, causal, window,
                  block_q, block_k) for kernel in kernels))
    block = blocks[0]

    def chunks(kernel, tile):
        # Each kernel streams the axis it accumulates over in chunks of up
        # to 4096 and writes the other in chunks of up to _OUTER_CHUNK.
        bq, bk = (block, block) if block else tile
        if kernel in _BY_KEY:
            return _pick_chunk(lq, bq), _pick_chunk(lk, bk, _OUTER_CHUNK)
        return _pick_chunk(lq, bq, _OUTER_CHUNK), _pick_chunk(lk, bk)
    return _bwd_call(
        q, k, v, o, lse, do, causal=causal, sm_scale=sm_scale,
        kernels=kernels, tiles=tiles, block=block,
        chunks=tuple(map(chunks, kernels, tiles)),
        q_offset=q_offset, kv_valid=kv_valid, window=window,
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=_CALL_STATICS + ("kernels",))
def _bwd_call(q, k, v, o, lse, do, *, causal, sm_scale, kernels, tiles,
              block, chunks, q_offset, kv_valid, window, interpret):
    bh, lq, d = q.shape
    lk, d_v = k.shape[1], v.shape[2]
    # The row statistics travel as (BH, 1, Lq) rows: see _fa_kernel.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    lse = lse[:, None, :]
    vma = _vma(q, k, v, do)

    def kernel(body, tile, chunk, **kw):
        q_chunk, k_chunk = chunk
        return functools.partial(
            body, sm_scale=sm_scale, causal=causal, tiles=tile,
            block=block, q_chunk=q_chunk, k_chunk=k_chunk,
            n_qc=lq // q_chunk, n_kc=lk // k_chunk, q_offset=q_offset,
            kv_valid=kv_valid, masked=kv_valid < lk, window=window, **kw)

    def swept(by_key, chunk, n):
        return functools.partial(_fetched, by_key=by_key, block=block,
                                 chunk=chunk, n=n, q_offset=q_offset,
                                 window=window)

    if kernels[0] == "bwd_dq":
        # dQ: grid over query chunks; key chunks stream innermost.
        q_chunk, k_chunk = chunks[0]
        # Each operand's block is its whole width: q and k Dqk, v and dO Dv.
        q_blk, o_blk = (pl.BlockSpec((1, q_chunk, w),
                                     lambda b, i, j: (b, i, 0))
                        for w in (d, d_v))
        r_blk = pl.BlockSpec((1, 1, q_chunk), lambda b, i, j: (b, 0, i))
        keys = swept(False, k_chunk, lk)
        k_blk, v_blk = (pl.BlockSpec((1, k_chunk, w),
                                     lambda b, i, j: (b, keys(i, j), 0))
                        for w in (d, d_v))
        dq = pl.pallas_call(
            kernel(_fa_bwd_dq_kernel, tiles[0], chunks[0]),
            name="hvd_flash_bwd_dq",
            grid=(bh, lq // q_chunk, lk // k_chunk),
            in_specs=[q_blk, k_blk, v_blk, o_blk, r_blk, r_blk],
            out_specs=q_blk,
            out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype, vma=vma),
            scratch_shapes=[_scratch((q_chunk, d))],
            compiler_params=_compiler_params(interpret),
            interpret=interpret,
        )(q, k, v, do, lse, delta)
    # dK/dV: grid over key chunks; query chunks stream innermost. Fused,
    # dQ too: its block is the (batch, head)'s whole dQ, written once.
    fused = kernels[-1] == "bwd_dqkv"
    q_chunk, k_chunk = chunks[-1]
    rows = swept(True, q_chunk, lq)
    q_blk, o_blk = (pl.BlockSpec((1, q_chunk, w),
                                 lambda b, i, j: (b, rows(i, j), 0))
                    for w in (d, d_v))
    r_blk = pl.BlockSpec((1, 1, q_chunk), lambda b, i, j: (b, 0, rows(i, j)))
    k_blk, v_blk = (pl.BlockSpec((1, k_chunk, w), lambda b, i, j: (b, i, 0))
                    for w in (d, d_v))
    dq_out = [pl.BlockSpec((1, lq, d), lambda b, i, j: (b, 0, 0))] * fused
    grads = pl.pallas_call(
        kernel(_fa_bwd_dkv_kernel, tiles[-1], chunks[-1], fused=fused),
        name="hvd_flash_" + kernels[-1],
        grid=(bh, lk // k_chunk, lq // q_chunk),
        in_specs=[q_blk, k_blk, v_blk, o_blk, r_blk, r_blk],
        out_specs=[k_blk, v_blk] + dq_out,
        out_shape=[jax.ShapeDtypeStruct((bh, lk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, lk, d_v), v.dtype, vma=vma)]
        + [jax.ShapeDtypeStruct((bh, lq, d), q.dtype, vma=vma)] * fused,
        scratch_shapes=[_scratch((k_chunk, d)), _scratch((k_chunk, d_v))]
        + [_scratch((d, lq))] * fused,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if fused:
        dk, dv, dq = grads
    else:
        dk, dv = grads
    return dq, dk, dv


def _mask_jnp(s, causal, q_offset, kv_valid, window=None):
    """Full-matrix analog of _apply_mask for the jnp oracles."""
    lq, lk = s.shape[1], s.shape[2]
    if q_offset is None:
        q_offset = lk - lq
    if kv_valid is None:
        kv_valid = lk
    ok = None
    if kv_valid < lk:
        ok = (jnp.arange(lk) < kv_valid)[None, :]
    if causal:
        q_pos = (q_offset + jnp.arange(lq))[:, None]
        c = q_pos >= jnp.arange(lk)[None, :]
        if window is not None:
            c &= jnp.arange(lk)[None, :] > q_pos - window
        ok = c if ok is None else ok & c
    if ok is None:
        return s
    return jnp.where(ok[None], s, NEG_INF)


def _jnp_block_fwd(q3, k3, v3, causal, scale, q_offset=None, kv_valid=None,
                   window=None):
    """jnp oracle for one attention block on (BH, Lq, D): returns
    (o, lse) with the same contract as the forward kernel (end-aligned
    causal, per-row logsumexp, optional key-validity bound). Shared by the
    interpret-mode paths here and the ring hops in parallel/sequence.py."""
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    s = _mask_jnp(s, causal, q_offset, kv_valid, window)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    o = (jnp.einsum("bqk,bkd->bqd", p, v3.astype(jnp.float32))
         / l[..., None]).astype(q3.dtype)
    return o, m + jnp.log(l)


def _jnp_block_bwd(q3, k3, v3, o3, lse, do3, causal, scale,
                   q_offset=None, kv_valid=None, window=None):
    """jnp oracle for the block backward against a given logsumexp: with
    the block's own lse this is exact flash backward; with a ring-wide lse
    it yields the hop's contribution to the global gradient."""
    qf, kf, vf, of, dof = (t.astype(jnp.float32)
                           for t in (q3, k3, v3, o3, do3))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    s = _mask_jnp(s, causal, q_offset, kv_valid, window)
    # Masked entries have s = NEG_INF and a fully-masked row has
    # lse ~= NEG_INF, where exp(s - lse) would blow up instead of vanishing
    # — zero them explicitly (the forward kernel does the same).
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse[..., None]), 0.0)
    dv = jnp.einsum("bqk,bqd->bkd", p, dof)
    dp = jnp.einsum("bqd,bkd->bqk", dof, vf)
    delta = jnp.sum(dof * of, axis=-1)                    # (BH, Lq)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, kf)
    dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


def gqa_repeat3(t3, b, kv, g):
    """(B*KV, L, D) -> (B*KV*g, L, D): each kv head's block repeated g
    times CONTIGUOUSLY, matching the (B*heads, L, D) query row layout the
    kernels (and their GQA index maps) use."""
    _, L, D = t3.shape
    return jnp.repeat(t3.reshape(b, kv, L, D), g, axis=1).reshape(
        b * kv * g, L, D)


def gqa_fold3(t3, b, kv, g):
    """Group-sum (B*heads, L, D) gradients back onto the narrow kv rows —
    the VJP of :func:`gqa_repeat3`."""
    _, L, D = t3.shape
    return t3.reshape(b, kv, g, L, D).sum(axis=2).reshape(
        b * kv, L, D).astype(t3.dtype)


def _flash_bwd(causal, sm_scale, block_q, block_k, q_offset, kv_valid,
               heads, kv_heads, window, res, do):
    q, k, v, o, lse = res
    gqa = heads is not None and kv_heads is not None and heads != kv_heads
    if gqa:
        # Broadcast the narrow residual k/v once, run the MHA backward,
        # then group-sum dK/dV back to the kv heads (the VJP of the
        # implicit broadcast).
        g = heads // kv_heads
        b = q.shape[0] // heads
        k = gqa_repeat3(k, b, kv_heads, g)
        v = gqa_repeat3(v, b, kv_heads, g)
    if not _interpret():
        dq, dk, dv = _fa_backward(q, k, v, o, lse, do, causal, sm_scale,
                                  block_q, block_k, q_offset, kv_valid,
                                  window)
    else:
        dq, dk, dv = _jnp_block_bwd(q, k, v, o, lse, do, causal, sm_scale,
                                    q_offset=q_offset, kv_valid=kv_valid,
                                    window=window)
    if gqa:
        dk, dv = gqa_fold3(dk, b, kv_heads, g), gqa_fold3(dv, b, kv_heads, g)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None, window=None):
    """Tiled attention over (B, L, H, D) tensors (the layout used throughout
    this codebase, e.g. parallel/sequence.py). ``v`` may be narrower than
    ``q`` and ``k`` (latent attention); the output has ``v``'s width, and
    ``sm_scale`` defaults to 1/sqrt of ``q``'s.

    ``window`` (with ``causal``): a sliding window, query t sees the keys j
    with t - window < j <= t (the query counts among its window); tiles
    wholly behind the window are skipped like those over the diagonal.

    Lengths with no aligned block size are PADDED to the next block
    multiple and the padding masked inside the kernels (``kv_valid``), so
    arbitrary sequence lengths (e.g. ViT's 196 patches) run the kernels.
    Falls back to :func:`horovod_tpu.parallel.sequence.local_attention`
    (the correctness oracle, same end-aligned causal convention) only
    where the kernels can't run at all (a VMA-checked shard_map under the
    interpreter).
    """
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    if kv != h and (kv == 0 or h % kv):
        raise ValueError(
            f"kv heads {kv} must divide query heads {h} (grouped-query)")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")

    def plain_fallback():
        """local_attention with any custom scale folded into q (it scales
        by 1/sqrt(D) internally, and broadcasts grouped K/V itself)."""
        from horovod_tpu.parallel.sequence import local_attention
        q_adj = q if sm_scale == 1.0 / (d ** 0.5) \
            else q * (sm_scale * d ** 0.5)
        return local_attention(q_adj, k, v, causal=causal, window=window)

    # Interpret mode (CPU tests) lowers the kernel body to ordinary JAX ops,
    # whose internal dynamic_slices the shard_map VMA checker rejects when
    # the operands are device-varying; the plain path is bit-compatible
    # there. On TPU the compiled kernel is opaque to the checker.
    if _interpret() and _vma(q, k, v):
        return plain_fallback()

    # Pad only genuinely unaligned lengths (e.g. ViT's 196) to the next
    # multiple of 128: aligned ones keep their unpadded, unmasked kernels
    # (no pad copy, no mask work).
    pad_q = 0 if _pick_block(lq) else (-lq) % 128
    pad_k = 0 if _pick_block(lk) else (-lk) % 128

    def to3(t, pad):
        nh = t.shape[2]
        t3 = jnp.moveaxis(t, 2, 1).reshape(t.shape[0] * nh, t.shape[1],
                                           t.shape[3])
        if pad:
            t3 = jnp.pad(t3, ((0, 0), (0, pad), (0, 0)))
        return t3

    # kv != h: grouped-query — the kernels stream the NARROW k/v (1/g the
    # HBM traffic); no broadcast is materialized on the forward path.
    out = _flash(to3(q, pad_q), to3(k, pad_k), to3(v, pad_k), causal,
                 sm_scale, q_offset=lk - lq, kv_valid=lk, heads=h,
                 kv_heads=kv, window=window)
    return heads_last(out, b, lq)


def heads_last(o3, batch, length):
    """The kernels' output (B*H, L', D), each sequence's heads contiguous,
    as (B, length, H, D): its first ``length`` positions, heads after
    them (``ops/pallas/attn_prologue.py`` shares it)."""
    bh, _, d = o3.shape
    return jnp.moveaxis(o3[:, :length].reshape(batch, bh // batch, length,
                                               d), 1, 2)

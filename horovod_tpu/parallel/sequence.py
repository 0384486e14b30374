"""Sequence/context parallelism: ring attention and Ulysses.

The reference has no attention code (SURVEY.md §5.7: Horovod operates below the
model level) but exposes exactly the primitives sequence parallelism composes
from — AllToAll with splits (Ulysses' head scatter, reference:
collective_operations.h:199-268) and point-to-point rings. This module builds
both schemes as first-class capabilities of the TPU framework:

- **Ulysses** (all-to-all SP): tokens sharded over the ``sp`` axis are
  exchanged for heads via one AllToAll, every chip computes full-sequence
  attention for its head subset, and a second AllToAll restores the token
  sharding. Communication: 2 all-to-alls of the activations, ICI-friendly.
- **Ring attention**: K/V blocks rotate around the ring via
  ``lax.ppermute`` while each chip accumulates flash-style online-softmax
  partial results for its resident Q block. Communication overlaps compute;
  memory stays O(L/n) per chip — the long-context workhorse.

Both are numerically exact (fp32 accumulators, online softmax) and verified
against full attention in tests.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.common import logging as hvd_logging

SP_AXIS = "hvd"  # default: sequence parallelism over the global mesh axis


def _attention_weights(q, k, scale, mask=None):
    # q: (B, Lq, H, D), k: (B, Lk, H, D) -> scores (B, H, Lq, Lk) in fp32
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    return s


def local_attention(q, k, v, causal=False, window=None):
    """Plain softmax attention on local (unsharded) tensors; the correctness
    oracle for the parallel schemes. ``k``/``v`` may carry fewer (grouped)
    heads than ``q`` — they are broadcast here, locally. ``window`` (with
    ``causal``): each query sees its last ``window`` keys, itself
    included."""
    k, v = broadcast_kv_heads(q, k, v)
    scale = 1.0 / np.sqrt(q.shape[-1])
    mask = None
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq - window)
        mask = mask[None, None]
    s = _attention_weights(q, k, scale, mask)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def broadcast_kv_heads(q, k, v):
    """Repeat grouped K/V heads up to the query head count (no-op for MHA).
    The sp schemes call this as LATE as possible — after the collective
    exchange — so ring/Ulysses traffic keeps GQA's 1/g bandwidth saving."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"kv heads {k.shape[2]} must divide query heads "
                         f"{q.shape[2]}")
    g = q.shape[2] // k.shape[2]
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def _axis_bound(axis_name):
    """True when ``axis_name`` is bound in the current trace (i.e. we're
    inside shard_map over it). Lets the attention schemes run un-sharded —
    e.g. during flax ``Module.init`` outside the mesh context — by degrading
    to local attention. (Shared predicate: parallel/tp.py axis_bound.)"""
    from horovod_tpu.parallel.tp import axis_bound
    return axis_bound(axis_name)


def ulysses_attention(q, k, v, axis_name=SP_AXIS, causal=False,
                      use_flash=False):
    """DeepSpeed-Ulysses-style sequence parallelism.

    Inputs are sequence-sharded: local shapes (B, L/n, H, D) with H divisible
    by n. Two AllToAlls re-shard tokens<->heads around a full-sequence local
    attention. Outside the axis context (e.g. parameter init) this computes
    plain local attention.

    ``use_flash=True`` runs the per-head-shard full-sequence attention
    through the Pallas flash kernels (flash_attention handles its own
    non-TPU fallback), cutting the O(L²) score materialization.

    ``k``/``v`` may carry fewer (grouped) heads than ``q``: when the kv
    head count divides the sp degree they ride the all-to-alls NARROW
    (1/g the exchange bytes) and are broadcast only on the local,
    post-exchange side; otherwise they are broadcast before the exchange.
    """
    if use_flash:
        from horovod_tpu.ops.pallas import flash_attention as attn
    else:
        attn = local_attention
    if not _axis_bound(axis_name):
        return attn(q, k, v, causal=causal)
    n = lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"num heads {q.shape[2]} not divisible by sp={n}")
    if k.shape[2] % n != 0:
        # grouped heads don't split over sp — broadcast first (correct,
        # but loses the narrow exchange; ring SP keeps it at any g)
        k, v = broadcast_kv_heads(q, k, v)

    def scatter_heads(t):
        # (B, L/n, H, D) -> (B, L, H/n, D)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(t):
        # (B, L, H/n, D) -> (B, L/n, H, D)
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    # flash streams grouped K/V natively; the jnp oracle broadcasts —
    # either way the broadcast (if any) happens AFTER the all-to-all.
    oh = attn(qh, kh, vh, causal=causal)
    return gather_heads(oh)


def next_token_labels(ids, axis_name=SP_AXIS, pad_id=-100):
    """Per-shard next-token labels under sequence sharding.

    With tokens sharded over ``axis_name`` each shard's LAST position's
    label is the FIRST token of the next shard — a shift inside the local
    slice silently trains the boundary position on the wrong target. This
    fetches the boundary token with one ``ppermute``; the final global
    position gets ``pad_id`` (mask it out of the loss, e.g. optax's
    ``where=labels != pad_id``). Outside the axis context this is the
    ordinary global shift.

    ``ids``: (B, L_local) int tokens. Returns same-shape labels.
    ``axis_name=None`` (tokens not sequence-sharded) always takes the
    plain-shift path — even when some OTHER mesh axis named like the
    default happens to be bound.
    """
    pad = jnp.full_like(ids[:, :1], pad_id)
    if axis_name is None or not _axis_bound(axis_name):
        return jnp.concatenate([ids[:, 1:], pad], axis=1)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    # rank i receives rank i+1's first token (reverse ring direction).
    first_next = lax.ppermute(ids[:, :1], axis_name,
                              [((i + 1) % n, i) for i in range(n)])
    boundary = jnp.where(idx == n - 1, pad, first_next)
    return jnp.concatenate([ids[:, 1:], boundary], axis=1)


def _block_attn_fwd(q3, ks, vs, causal, scale, blocks, heads=None,
                    kv_heads=None):
    """(o_b, lse_b) for one ring hop on (BH, L, D) blocks: the Pallas flash
    kernel on TPU, the shared jnp block oracle elsewhere (the interpreter
    can't run the kernel under a VMA-checked shard_map). With
    ``kv_heads < heads`` the ks/vs blocks stay NARROW (B*KV rows): the
    kernel streams them via its GQA index maps; the oracle broadcasts
    locally — either way the ring traffic carried only the narrow blocks."""
    from horovod_tpu.ops.pallas.flash_attention import (_fa_forward,
                                                        _interpret,
                                                        _jnp_block_fwd,
                                                        gqa_repeat3)
    gqa = heads is not None and kv_heads is not None and heads != kv_heads
    if blocks is not None and not _interpret():
        return _fa_forward(q3, ks, vs, causal, scale,
                           heads=heads if gqa else None,
                           kv_heads=kv_heads if gqa else None)
    if gqa:
        b = q3.shape[0] // heads
        g = heads // kv_heads
        ks = gqa_repeat3(ks, b, kv_heads, g)
        vs = gqa_repeat3(vs, b, kv_heads, g)
    return _jnp_block_fwd(q3, ks, vs, causal, scale)


def _block_attn_bwd(q3, ks, vs, out3, lse, do3, causal, scale, blocks,
                    heads=None, kv_heads=None):
    """Per-hop (dq, dk, dv) against the GLOBAL softmax: p = exp(s - lse)
    with the ring-wide logsumexp, so summing hop contributions reproduces
    the exact full-attention gradient. Under GQA the returned dk/dv are
    group-summed back onto the NARROW kv rows, so the gradient
    accumulators rotate narrow too."""
    from horovod_tpu.ops.pallas.flash_attention import (_fa_backward,
                                                        _interpret,
                                                        _jnp_block_bwd,
                                                        gqa_fold3,
                                                        gqa_repeat3)
    gqa = heads is not None and kv_heads is not None and heads != kv_heads
    if gqa:
        # The backward kernel is MHA-shaped (like _flash_bwd): broadcast
        # the narrow hop blocks LOCALLY, group-sum dk/dv back. The ring
        # still only ever carried the narrow blocks.
        b = q3.shape[0] // heads
        g = heads // kv_heads
        ks = gqa_repeat3(ks, b, kv_heads, g)
        vs = gqa_repeat3(vs, b, kv_heads, g)
    if blocks is not None and not _interpret():
        dq, dk, dv = _fa_backward(q3, ks, vs, out3, lse, do3, causal,
                                  scale)
    else:
        dq, dk, dv = _jnp_block_bwd(q3, ks, vs, out3, lse, do3, causal,
                                    scale)
    if gqa:
        dk = gqa_fold3(dk, b, kv_heads, g)
        dv = gqa_fold3(dv, b, kv_heads, g)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q3, k3, v3, causal, axis_name, scale, blocks, heads=None,
                kv_heads=None):
    out, _ = _ring_flash_fwd(q3, k3, v3, causal, axis_name, scale, blocks,
                             heads, kv_heads)
    return out


def _ring_flash_fwd(q3, k3, v3, causal, axis_name, scale, blocks,
                    heads=None, kv_heads=None):
    """Ring forward: rotate K/V blocks, run the flash block kernel per hop,
    combine hop outputs by their logsumexp weights (exact). Under GQA
    (``kv_heads < heads``) the rotated k3/v3 carry only B*kv_heads rows —
    1/g the ppermute bytes."""
    from horovod_tpu.ops.in_jit import mark_varying_like
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    bh, L, d = q3.shape
    perm = [(i, (i - 1) % n) for i in range(n)]

    m = jnp.full((bh, L), -1e30, jnp.float32)
    norm = jnp.zeros((bh, L), jnp.float32)
    acc = jnp.zeros((bh, L, d), jnp.float32)
    # carry varying over sp AND any axes the data is sharded over (dp/pp
    # on a composite mesh)
    m, norm, acc = mark_varying_like((m, norm, acc), q3, axis_name)
    ks, vs = k3, v3
    for s in range(n):
        src = (idx + s) % n
        if causal and s > 0:
            # Blocks from ranks ahead of this one are entirely above the
            # causal diagonal: skip their kernels outright (the per-device
            # scalar predicate branches locally; no collective inside).
            o_b, lse_b = lax.cond(
                src < idx,
                lambda ks=ks, vs=vs: _block_attn_fwd(
                    q3, ks, vs, False, scale, blocks, heads, kv_heads),
                lambda: (q3 * 0,
                         q3[..., 0].astype(jnp.float32) * 0 - 1e30))
            visible = (src < idx).astype(jnp.float32)       # whole block
        else:
            o_b, lse_b = _block_attn_fwd(q3, ks, vs, causal and s == 0,
                                         scale, blocks, heads, kv_heads)
            visible = jnp.float32(1.0)
        m_new = jnp.maximum(m, jnp.where(visible > 0, lse_b, -1e30))
        # m_new stays -1e30 only while NO block is visible yet; exp(0)=1
        # corrections are harmless there because norm/acc are still zero.
        corr = jnp.exp(m - m_new)
        w = visible * jnp.exp(jnp.minimum(lse_b - m_new, 0.0))
        norm = norm * corr + w
        acc = acc * corr[..., None] + w[..., None] * o_b.astype(jnp.float32)
        m = m_new
        if s != n - 1:
            ks = lax.ppermute(ks, axis_name, perm)
            vs = lax.ppermute(vs, axis_name, perm)
    norm_safe = jnp.maximum(norm, 1e-30)
    out = (acc / norm_safe[..., None]).astype(q3.dtype)
    lse_tot = m + jnp.log(norm_safe)
    return out, (q3, k3, v3, out, lse_tot)


def _ring_flash_bwd(causal, axis_name, scale, blocks, heads, kv_heads, res,
                    do3):
    """Ring backward: rotate K/V (and their gradient accumulators) around
    the ring again; each hop's dk/dv lands home after n-1 rotations. Under
    GQA the rotated blocks AND accumulators stay narrow (B*kv_heads rows)."""
    q3, k3, v3, out3, lse_tot = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i - 1) % n) for i in range(n)]
    from horovod_tpu.ops.in_jit import mark_varying_like

    dq = jnp.zeros(q3.shape, jnp.float32)
    dk_rot = jnp.zeros(k3.shape, jnp.float32)
    dv_rot = jnp.zeros(v3.shape, jnp.float32)
    dq, dk_rot, dv_rot = mark_varying_like((dq, dk_rot, dv_rot), q3,
                                           axis_name)
    # Fully-masked rows (possible only without a visible diagonal) carry
    # lse ~ -1e30; clamp so exp(s - lse) cannot overflow — their hop
    # contributions are already zeroed by the visibility gate.
    lse_safe = jnp.where(lse_tot > -1e29, lse_tot, 0.0)
    ks, vs = k3, v3
    for s in range(n):
        src = (idx + s) % n
        if causal and s > 0:
            dq_b, dk_b, dv_b = lax.cond(
                src < idx,
                lambda ks=ks, vs=vs: _block_attn_bwd(
                    q3, ks, vs, out3, lse_safe, do3, False, scale, blocks,
                    heads, kv_heads),
                lambda ks=ks, vs=vs: (q3 * 0, ks * 0, vs * 0))
            visible = (src < idx).astype(jnp.float32)
        else:
            dq_b, dk_b, dv_b = _block_attn_bwd(
                q3, ks, vs, out3, lse_safe, do3, causal and s == 0, scale,
                blocks, heads, kv_heads)
            visible = jnp.float32(1.0)
        dq = dq + visible * dq_b.astype(jnp.float32)
        dk_rot = dk_rot + visible * dk_b.astype(jnp.float32)
        dv_rot = dv_rot + visible * dv_b.astype(jnp.float32)
        if s != n - 1:
            ks = lax.ppermute(ks, axis_name, perm)
            vs = lax.ppermute(vs, axis_name, perm)
            dk_rot = lax.ppermute(dk_rot, axis_name, perm)
            dv_rot = lax.ppermute(dv_rot, axis_name, perm)
    # After n-1 hops the accumulators sit one rotation short of home.
    dk_home = lax.ppermute(dk_rot, axis_name, perm)
    dv_home = lax.ppermute(dv_rot, axis_name, perm)
    return (dq.astype(q3.dtype), dk_home.astype(k3.dtype),
            dv_home.astype(v3.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name=SP_AXIS, causal=False,
                   use_flash=False):
    """Ring attention with online softmax (Liu et al.; blockwise parallel
    transformers): exact attention over the full sequence with O(L/n) memory
    and K/V rotating over ICI.

    Local shapes (B, L/n, H, D); every chip owns the Q block for its sequence
    shard and receives each K/V block exactly once. Outside the axis context
    (e.g. parameter init) this computes plain local attention.

    ``use_flash=True`` runs each hop's block attention through the Pallas
    flash kernels (forward AND backward) and combines hops by their
    logsumexp weights — same exact math, MXU-tiled and O(block) VMEM. On
    non-TPU backends the hops use an equivalent jnp block kernel, so the
    path is testable on the virtual CPU mesh.

    ``k``/``v`` may carry fewer (grouped) heads than ``q``: the narrow
    tensors rotate the ring directly (1/g the ppermute bytes AND 1/g the
    resident K/V memory) and are expanded only at the hop kernels — the
    flash path streams them without materializing the broadcast at all.
    """
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"kv heads {k.shape[2]} must divide query heads "
                         f"{q.shape[2]}")
    if not _axis_bound(axis_name):
        if use_flash:
            from horovod_tpu.ops.pallas import flash_attention as _flash_fn
            return _flash_fn(q, k, v, causal=causal)
        return local_attention(q, k, v, causal=causal)
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    if use_flash:
        import importlib
        fa = importlib.import_module(
            "horovod_tpu.ops.pallas.flash_attention")
        # None where a local length has no aligned tile; each hop picks
        # its own tile shape from its own mask (the diagonal hop alone is
        # causal).
        blocks = fa._pick_tiles(Lq, k.shape[1], False)
        if blocks is None and not fa._interpret():
            hvd_logging.warning(
                "ring_attention(use_flash=True): local lengths %d/%d have "
                "no aligned block; the hops run the jnp block path, not "
                "the Pallas kernels", Lq, k.shape[1])
        scale = 1.0 / np.sqrt(D)

        def to3(t):
            h = t.shape[2]
            return jnp.moveaxis(t, 2, 1).reshape(t.shape[0] * h,
                                                 t.shape[1], D)

        o3 = _ring_flash(to3(q), to3(k), to3(v), causal, axis_name, scale,
                         blocks, H if KV != H else None,
                         KV if KV != H else None)
        return jnp.moveaxis(o3.reshape(B, H, Lq, D), 1, 2)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    g = H // KV
    scale = 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)

    # global positions of my Q rows (for causal masking)
    q_pos = idx * Lq + jnp.arange(Lq)  # (Lq,)

    perm = [(i, (i - 1) % n) for i in range(n)]  # block s lives at rank+s

    def step(s, carry):
        o, m, l, ks, vs = carry
        src = (idx + s) % n
        # narrow (grouped) K/V rotate the ring; broadcast only here,
        # locally, for the einsum
        ksf, vsf = (jnp.repeat(t, g, axis=2) if g > 1 else t
                    for t in (ks, vs))
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            ksf.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * Lq + jnp.arange(Lq)
            mask = q_pos[:, None] >= k_pos[None, :]        # (Lq, Lk)
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)                  # (B, H, Lq)
        m_new = jnp.maximum(m, blk_max)
        # guard fully-masked rows (m_new = -inf): keep them at zero weight
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] \
            + jnp.einsum("bhqk,bkhd->bhqd", p, vsf.astype(jnp.float32))
        ks = lax.ppermute(ks, axis_name, perm)
        vs = lax.ppermute(vs, axis_name, perm)
        return o_new, m_new, l_new, ks, vs

    from horovod_tpu.ops.in_jit import mark_varying_like
    o = jnp.zeros((B, H, Lq, D), jnp.float32)
    m = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Lq), jnp.float32)
    # constants start axis-invariant; the loop carry must be device-varying
    # over sp and any other axes the data is sharded over
    o, m, l = mark_varying_like((o, m, l), q, axis_name)
    o, m, l, _, _ = lax.fori_loop(0, n, step, (o, m, l, k, v),
                                  unroll=True)
    out = o / jnp.maximum(l, 1e-30)[..., None]              # (B, H, Lq, D)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)          # (B, Lq, H, D)

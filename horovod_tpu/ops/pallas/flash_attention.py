"""Flash attention as a Pallas TPU kernel.

The hot op of every transformer in the model zoo (models/bert.py,
models/gpt.py, parallel/tp.py). Tiled online-softmax attention: for each
query block the kernel streams key/value blocks through VMEM, keeping the
running max/denominator in registers — O(L) memory instead of materializing
the (L, L) score matrix, and every matmul lands on the MXU as a
(block_q x D) @ (D x block_k) tile.

The reference framework has no attention code (SURVEY.md §5.7 — Horovod
operates below the model level); this kernel is part of the TPU build's
model-level capability, in the spirit of the reference's hand-written CUDA
hot loops (reference: horovod/common/ops/cuda/cuda_kernels.cu).

Backward pass: custom VJP using the saved per-row logsumexp, fused as two
Pallas kernels on TPU (a dQ pass tiled over query blocks and a dK/dV pass
tiled over key blocks, each recomputing its score tile in VMEM) — O(L)
memory end to end. Interpret mode (CPU tests) keeps the plain jnp backward,
which doubles as the numerical oracle for the kernels.

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


def _pick_block(length, cap=1024):
    # Large tiles keep the MXU fed; 1024 rows need the raised scoped-VMEM
    # budget of _compiler_params (they compile and run at seq 1024 on a
    # v5e, PR 21; tile sizes are not re-measured on current code).
    # HVD_FLASH_BLOCK caps the tile lower for on-chip sweeps (the MFU
    # tuning loop: sweep 128/256/512 per model without code edits).
    import os
    env_cap = os.environ.get("HVD_FLASH_BLOCK")
    if env_cap:
        cap = min(cap, int(env_cap))
    for b in (cap, 512, 256, 128, 64, 32, 16, 8):
        if b <= cap and length % b == 0:
            return b
    return None


def _vma(*operands):
    """How the operands vary over the mesh inside a VMA-checked shard_map;
    the kernel outputs must declare the same."""
    return frozenset().union(*(jax.typeof(t).vma for t in operands))


def _scratch(shape):
    """VMEM scratch accumulator (persists across the sequential innermost
    grid sweep on one core)."""
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    """Raise mosaic's scoped-VMEM budget (default 16 MB) — the 512-row MXU
    tiles this kernel prefers need ~17-32 MB of stack at long context; v5e
    has far more physical VMEM than the default budget admits."""
    if _interpret():
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _pick_chunk(length, block, cap=4096):
    """Largest multiple of ``block`` dividing ``length``, capped.

    The chunk is the unit the grid streams through VMEM (bounding VMEM at
    O(chunk) so 8k+ contexts fit the ~16 MB scoped budget); within a chunk
    a register-carried fori_loop sweeps ``block``-sized MXU tiles (grid
    steps are too fine-grained to carry the softmax state efficiently).
    """
    c = min(length, cap)
    while c > block and length % c:
        c -= block
    return c


def _apply_mask(s, *, causal, masked, q0, k0, kv_valid, block_q, block_k):
    """Combined causal + key-validity masking for one (BQ, BK) score tile.

    ``masked`` (static) is True when the key axis was padded to a block
    multiple: keys at global position >= kv_valid are padding and must not
    receive weight. ``q0``/``k0`` are the tile's global row/key offsets.
    """
    if not (causal or masked):
        return s
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = None
    if masked:
        ok = k_pos < kv_valid
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        c = q_pos >= k_pos
        ok = c if ok is None else ok & c
    return jnp.where(ok, s, NEG_INF)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
               *, sm_scale, causal, block_q, block_k, k_chunk, q_offset,
               n_kc, kv_valid, masked):
    """One (query-block, key-chunk) grid step of the online softmax.

    The key-chunk sweep is the INNERMOST grid dimension; the running
    (m, l, acc) state lives in VMEM scratch across chunk steps and in
    registers within the chunk's fori tile sweep.
    """
    qi = pl.program_id(1)
    # Single-chunk grids (n_kc == 1) are specialized to STATIC control
    # flow: jc is the literal 0, init/finalize run unconditionally, and
    # the masked trip count below is a compile-time constant. The generic
    # path's pl.when(contributes) + dynamically-clipped fori_loop is only
    # ever needed when the chunk index is a real grid variable.
    single = n_kc == 1
    jc = 0 if single else pl.program_id(2)

    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if single:
        _init()
    else:
        pl.when(jc == 0)(_init)

    # End-aligned causal convention (tril with k = Lk - Lq), matching
    # local_attention and the backward pass: query row i may attend keys
    # <= i + (Lk - Lq). q_offset = Lk - Lq.
    q_end = q_offset + (qi + 1) * block_q - 1  # last query row's key bound
    contributes = None                 # None == statically always-true
    if causal:
        contributes = q_end >= jc * k_chunk
    if masked and not single:
        c = jc * k_chunk < kv_valid
        contributes = c if contributes is None else contributes & c

    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale        # (BQ, D)

        def body(t, carry):
            m, l, acc = carry
            kb = k_ref[0, pl.ds(t * block_k, block_k), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(t * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = _apply_mask(s, causal=causal, masked=masked,
                            q0=q_offset + qi * block_q,
                            k0=jc * k_chunk + t * block_k,
                            kv_valid=kv_valid, block_q=block_q,
                            block_k=block_k)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            # Rows where every score is masked give exp(0)=1; zero them.
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        n_t = k_chunk // block_k
        if masked and single:
            # The chunk starts at key 0, so the last valid key tile is a
            # compile-time constant: a static trip count, no dynamic clip.
            n_t = min(n_t, max(0, (kv_valid + block_k - 1) // block_k))
        if causal:
            # Bound the tile sweep at the diagonal within this chunk.
            n_t = jnp.clip(
                pl.cdiv(q_end + 1 - jc * k_chunk, block_k), 0, n_t)
        if masked and not single:
            # ...and at the last VALID key tile.
            n_t = jnp.clip(
                pl.cdiv(kv_valid - jc * k_chunk, block_k), 0, n_t)
        m, l, acc = jax.lax.fori_loop(
            0, n_t, body, (m_ref[:, 0], l_ref[:, 0], acc_ref[...]))
        m_ref[...] = m[:, None]
        l_ref[...] = l[:, None]
        acc_ref[...] = acc

    if contributes is None:
        _compute()
    else:
        pl.when(contributes)(_compute)

    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse rides a (1, block_q, 1) block: TPU mosaic requires the
        # block's last two dims to be (8k, 128k) or equal to the array's —
        # a trailing singleton satisfies that where (1, block_q) cannot.
        lse_ref[0] = (m_ref[:, 0] + jnp.log(l_safe))[:, None]

    if single:
        _finalize()
    else:
        pl.when(jc == n_kc - 1)(_finalize)


def _fa_forward(q, k, v, causal, sm_scale, block_q, block_k,
                q_offset=None, kv_valid=None, heads=None, kv_heads=None):
    """(B*H, Lq, D) x (B*KV, Lk, D)^2 -> (o, lse).

    ``q_offset``/``kv_valid`` override the end-aligned causal offset and
    the number of VALID keys when the inputs were padded to block
    multiples (positions are always in ORIGINAL coordinates).

    Grouped-query attention: with ``kv_heads < heads`` the K/V tensors
    carry only the grouped heads and the kernel streams each kv head's
    chunks to its ``heads/kv_heads`` query heads via the BlockSpec index
    map — no materialized broadcast, 1/g the K/V HBM traffic."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    if q_offset is None:
        q_offset = lk - lq
    if kv_valid is None:
        kv_valid = lk
    if heads is None or kv_heads is None or heads == kv_heads:
        def kv_map(b, i, j):
            return (b, j, 0)
    else:
        g = heads // kv_heads

        def kv_map(b, i, j):
            return ((b // heads) * kv_heads + (b % heads) // g, j, 0)
    masked = kv_valid < lk
    k_chunk = _pick_chunk(lk, block_k)
    n_kc = lk // k_chunk
    grid = (bh, lq // block_q, n_kc)
    kernel = functools.partial(_fa_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               k_chunk=k_chunk, q_offset=q_offset,
                               n_kc=n_kc, kv_valid=kv_valid, masked=masked)
    vma = _vma(q, k, v)
    o, lse = pl.pallas_call(
        kernel,
        name="hvd_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[_scratch((block_q, 1)), _scratch((block_q, 1)),
                        _scratch((block_q, d))],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v)
    return o, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, q_offset=None,
           kv_valid=None, heads=None, kv_heads=None):
    """``heads``/``kv_heads`` (static) turn on grouped-query attention:
    q carries B*heads rows, k/v only B*kv_heads. The forward streams the
    NARROW k/v through the kernel (index-mapped, no broadcast); the
    backward broadcasts once and group-sums dK/dV — forward/serving
    bandwidth is where GQA pays."""
    o, _ = _fa_forward(q, k, v, causal, sm_scale, block_q, block_k,
                       q_offset, kv_valid, heads=heads, kv_heads=kv_heads)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, q_offset=None,
               kv_valid=None, heads=None, kv_heads=None):
    o, lse = _fa_forward(q, k, v, causal, sm_scale, block_q, block_k,
                         q_offset, kv_valid, heads=heads, kv_heads=kv_heads)
    return o, (q, k, v, o, lse)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, sm_scale, causal, block_q,
                      block_k, k_chunk, q_offset, n_kc, kv_valid, masked):
    """dQ pass: (query-block, key-chunk) grid with the dq accumulator in
    scratch across chunks and a register fori sweep within each chunk."""
    qi = pl.program_id(1)
    # Same single-chunk static specialization as _fa_kernel (see there).
    single = n_kc == 1
    jc = 0 if single else pl.program_id(2)

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if single:
        _init()
    else:
        pl.when(jc == 0)(_init)

    q_end = q_offset + (qi + 1) * block_q - 1
    contributes = None
    if causal:
        contributes = q_end >= jc * k_chunk
    if masked and not single:
        c = jc * k_chunk < kv_valid
        contributes = c if contributes is None else contributes & c

    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (BQ, D)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]                             # (BQ,)
        delta = delta_ref[0, :, 0]

        def body(t, dq):
            kb = k_ref[0, pl.ds(t * block_k, block_k), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(t * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = _apply_mask(s, causal=causal, masked=masked,
                            q0=q_offset + qi * block_q,
                            k0=jc * k_chunk + t * block_k,
                            kv_valid=kv_valid, block_q=block_q,
                            block_k=block_k)
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse[:, None]), 0.0)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None]) * sm_scale
            return dq + jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        n_t = k_chunk // block_k
        if masked and single:
            n_t = min(n_t, max(0, (kv_valid + block_k - 1) // block_k))
        if causal:
            n_t = jnp.clip(
                pl.cdiv(q_end + 1 - jc * k_chunk, block_k), 0, n_t)
        if masked and not single:
            n_t = jnp.clip(
                pl.cdiv(kv_valid - jc * k_chunk, block_k), 0, n_t)
        acc_ref[...] = jax.lax.fori_loop(0, n_t, body, acc_ref[...])

    if contributes is None:
        _compute()
    else:
        pl.when(contributes)(_compute)

    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)

    if single:
        _finalize()
    else:
        pl.when(jc == n_kc - 1)(_finalize)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                       block_q, block_k, q_chunk, q_offset, n_qc, kv_valid,
                       masked):
    """dK/dV pass: (key-block, query-chunk) grid; per-key-block accumulators
    in scratch across query chunks, register fori sweep within."""
    ki = pl.program_id(1)
    # Single-chunk static specialization for the QUERY-chunk grid dim
    # (n_qc == 1): literal jc, unconditional init/finalize. The masked
    # and causal gates ride ki — a real grid variable — and remain.
    single = n_qc == 1
    jc = 0 if single else pl.program_id(2)

    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if single:
        _init()
    else:
        pl.when(jc == 0)(_init)

    contributes = None
    if causal:
        # Query chunks ending above this key block's diagonal contribute
        # nothing: rows i attend keys <= i + q_offset.
        contributes = (q_offset + (jc + 1) * q_chunk - 1) >= ki * block_k
    if masked:
        # Entirely-padding key blocks receive zero gradient.
        c = ki * block_k < kv_valid
        contributes = c if contributes is None else contributes & c

    def _compute():
        kb = k_ref[0].astype(jnp.float32)                  # (BK, D)
        vb = v_ref[0].astype(jnp.float32)

        def body(t, carry):
            dk, dv = carry
            qb = q_ref[0, pl.ds(t * block_q, block_q), :].astype(jnp.float32)
            dob = do_ref[0, pl.ds(t * block_q, block_q), :].astype(
                jnp.float32)
            lse_b = lse_ref[0, pl.ds(t * block_q, block_q), 0]
            delta_b = delta_ref[0, pl.ds(t * block_q, block_q), 0]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = _apply_mask(s, causal=causal, masked=masked,
                            q0=q_offset + jc * q_chunk + t * block_q,
                            k0=ki * block_k, kv_valid=kv_valid,
                            block_q=block_q, block_k=block_k)
            p = jnp.where(s > NEG_INF * 0.5,
                          jnp.exp(s - lse_b[:, None]), 0.0)
            dv = dv + jax.lax.dot_general(
                p, dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_b[:, None]) * sm_scale
            dk = dk + jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        n_t = q_chunk // block_q
        if causal:
            # First query row attending key block ki within this chunk.
            t0 = jnp.clip(
                (ki * block_k - q_offset - jc * q_chunk) // block_q, 0, n_t)
        else:
            t0 = 0
        dk, dv = jax.lax.fori_loop(
            t0, n_t, body, (dk_acc[...], dv_acc[...]))
        dk_acc[...] = dk
        dv_acc[...] = dv

    if contributes is None:
        _compute()
    else:
        pl.when(contributes)(_compute)

    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if single:
        _finalize()
    else:
        pl.when(jc == n_qc - 1)(_finalize)


def _fa_backward(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
                 q_offset=None, kv_valid=None):
    """Fused O(L)-memory backward: (dq, dk, dv) via two pallas_calls."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    if q_offset is None:
        q_offset = lk - lq
    if kv_valid is None:
        kv_valid = lk
    masked = kv_valid < lk
    k_chunk = _pick_chunk(lk, block_k)
    q_chunk = _pick_chunk(lq, block_q)
    n_kc = lk // k_chunk
    n_qc = lq // q_chunk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (BH, Lq, 1)
    lse3 = lse[..., None]                                  # (BH, Lq, 1)
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, q_offset=q_offset, kv_valid=kv_valid,
                  masked=masked)
    q_blk = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    r_blk = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kc_swept = pl.BlockSpec((1, k_chunk, d), lambda b, i, j: (b, j, 0))
    vma = _vma(q, k, v, do)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, k_chunk=k_chunk, n_kc=n_kc,
                          **common),
        name="hvd_flash_bwd_dq",
        grid=(bh, lq // block_q, n_kc),
        in_specs=[q_blk, kc_swept, kc_swept, q_blk, r_blk, r_blk],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype, vma=vma),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v, do, lse3, delta)
    # dK/dV: grid over key blocks; query chunks stream innermost.
    qc_swept = pl.BlockSpec((1, q_chunk, d), lambda b, i, j: (b, j, 0))
    rc_swept = pl.BlockSpec((1, q_chunk, 1), lambda b, i, j: (b, j, 0))
    k_blk = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, q_chunk=q_chunk, n_qc=n_qc,
                          **common),
        name="hvd_flash_bwd_dkv",
        grid=(bh, lk // block_k, n_qc),
        in_specs=[qc_swept, k_blk, k_blk, qc_swept, rc_swept, rc_swept],
        out_specs=[pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, lk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, lk, d), v.dtype, vma=vma)],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv


def _mask_jnp(s, causal, q_offset, kv_valid):
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
        c = (q_offset + jnp.arange(lq))[:, None] >= jnp.arange(lk)[None, :]
        ok = c if ok is None else ok & c
    if ok is None:
        return s
    return jnp.where(ok[None], s, NEG_INF)


def _jnp_block_fwd(q3, k3, v3, causal, scale, q_offset=None, kv_valid=None):
    """jnp oracle for one attention block on (BH, Lq, D): returns
    (o, lse) with the same contract as the forward kernel (end-aligned
    causal, per-row logsumexp, optional key-validity bound). Shared by the
    interpret-mode paths here and the ring hops in parallel/sequence.py."""
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    s = _mask_jnp(s, causal, q_offset, kv_valid)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    o = (jnp.einsum("bqk,bkd->bqd", p, v3.astype(jnp.float32))
         / l[..., None]).astype(q3.dtype)
    return o, m + jnp.log(l)


def _jnp_block_bwd(q3, k3, v3, o3, lse, do3, causal, scale,
                   q_offset=None, kv_valid=None):
    """jnp oracle for the block backward against a given logsumexp: with
    the block's own lse this is exact flash backward; with a ring-wide lse
    it yields the hop's contribution to the global gradient."""
    qf, kf, vf, of, dof = (t.astype(jnp.float32)
                           for t in (q3, k3, v3, o3, do3))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    s = _mask_jnp(s, causal, q_offset, kv_valid)
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
               heads, kv_heads, res, do):
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
                                  block_q, block_k, q_offset, kv_valid)
    else:
        dq, dk, dv = _jnp_block_bwd(q, k, v, o, lse, do, causal, sm_scale,
                                    q_offset=q_offset, kv_valid=kv_valid)
    if gqa:
        dk, dv = gqa_fold3(dk, b, kv_heads, g), gqa_fold3(dv, b, kv_heads, g)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """Tiled attention over (B, L, H, D) tensors (the layout used throughout
    this codebase, e.g. parallel/sequence.py).

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

    def plain_fallback():
        """local_attention with any custom scale folded into q (it scales
        by 1/sqrt(D) internally, and broadcasts grouped K/V itself)."""
        from horovod_tpu.parallel.sequence import local_attention
        q_adj = q if sm_scale == 1.0 / (d ** 0.5) \
            else q * (sm_scale * d ** 0.5)
        return local_attention(q_adj, k, v, causal=causal)

    # Interpret mode (CPU tests) lowers the kernel body to ordinary JAX ops,
    # whose internal dynamic_slices the shard_map VMA checker rejects when
    # the operands are device-varying; the plain path is bit-compatible
    # there. On TPU the compiled kernel is opaque to the checker.
    if _interpret() and _vma(q, k, v):
        return plain_fallback()

    # Pad only genuinely unaligned lengths (e.g. ViT's 196): aligned ones
    # keep their unpadded, unmasked kernels (no pad copy, no mask work).
    pad_q = 0 if _pick_block(lq) else (-lq) % 128
    pad_k = 0 if _pick_block(lk) else (-lk) % 128
    lq_p, lk_p = lq + pad_q, lk + pad_k

    def to3(t, pad):
        nh = t.shape[2]
        t3 = jnp.moveaxis(t, 2, 1).reshape(t.shape[0] * nh, t.shape[1], d)
        if pad:
            t3 = jnp.pad(t3, ((0, 0), (0, pad), (0, 0)))
        return t3

    def from3(t):
        return jnp.moveaxis(t[:, :lq].reshape(b, h, lq, d), 1, 2)

    # kv != h: grouped-query — the kernels stream the NARROW k/v (1/g the
    # HBM traffic); no broadcast is materialized on the forward path.
    out = _flash(to3(q, pad_q), to3(k, pad_k), to3(v, pad_k), causal,
                 sm_scale, _pick_block(lq_p), _pick_block(lk_p),
                 lk - lq, lk, h, kv)
    return from3(out)

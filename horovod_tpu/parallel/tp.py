"""Tensor (model) parallelism: Megatron-style sharded transformer layers.

The reference is data-parallel only (SURVEY.md §2.6: the only request types
are whole-tensor collectives, message.h:61-70) — TP is *new* capability this
framework adds, built from the same primitive the reference exposes as
``allreduce`` (reference: horovod/common/operations.cc:1480
EnqueueTensorAllreduces): a weight matrix is split across the ``tp`` mesh
axis, each chip computes its shard's contribution on the MXU, and one
``lax.psum`` over ICI restores the full activation.

Layout follows the Megatron pairing so each attention/MLP block needs exactly
ONE collective on the forward pass (and one on backward, psum's transpose):

- **column-parallel** linear: weight split on the *output* dim; no comm in
  forward (activations come out shard-local), gradient w.r.t. input is
  reduced by AD's transpose of the downstream row-parallel psum.
- **row-parallel** linear: weight split on the *input* dim, consuming the
  column-parallel layer's sharded activations; one ``psum`` completes the
  matmul. Bias is added *after* the psum so it is applied once.

All modules are flax and size their parameters by the *local* shard: call
(and init) them inside ``shard_map`` with the ``tp`` axis bound. Outside the
axis context they degrade to the dense layer (tp=1), so the same module
definition doubles as the single-chip reference.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.trace.scopes import scope

TP_AXIS = "tp"


def axis_size_or_1(axis_name) -> int:
    """Size of ``axis_name`` when bound in the current trace, else 1."""
    if axis_name is None:
        return 1
    try:
        return lax.axis_size(axis_name)
    except NameError:
        return 1


def axis_bound(axis_name) -> bool:
    """True when ``axis_name`` is bound in the current trace — even at
    size 1, where collectives are numeric no-ops but still clear the
    varying-manual-axes type (a size-1 tp axis on a composite mesh types
    sharded weights tp-varying; skipping the row-parallel psum would leak
    that varying-ness into shape-invariant carries)."""
    if axis_name is None:
        return False
    try:
        lax.axis_size(axis_name)
        return True
    except NameError:
        return False


def tp_shard_rng(rng, axis_name=TP_AXIS):
    """Fold the tp coordinate into an init rng so each shard draws distinct
    weights (a sharded weight is one logical matrix, not n copies)."""
    if axis_size_or_1(axis_name) == 1:
        return rng
    return jax.random.fold_in(rng, lax.axis_index(axis_name))


def shard_init(base_init, axis_name):
    """Wrap a flax initializer so each shard of a weight draws distinct
    values from ONE logical rng (the shard coordinate is folded in here, not
    by the caller). Keeping the fold inside the initializer lets a module mix
    sharded weights with replicated ones (LayerNorm, biases) under a single
    init rng — the replicated params stay axis-invariant, which the VMA
    (varying-manual-axes) type system verifies under ``shard_map``."""

    def init(rng, shape, dtype=jnp.float32):
        if axis_size_or_1(axis_name) > 1:
            rng = jax.random.fold_in(rng, lax.axis_index(axis_name))
        return base_init(rng, shape, dtype)

    return init


class ColumnParallelDense(nn.Module):
    """Linear layer with the weight split along the output dimension.

    ``features`` is the GLOBAL output width; each tp shard holds
    ``features / tp`` columns and produces the matching activation shard.
    """
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS

    @nn.compact
    def __call__(self, x):
        n = axis_size_or_1(self.axis_name)
        if self.features % n != 0:
            raise ValueError(
                f"features {self.features} not divisible by tp={n}")
        return nn.Dense(
            self.features // n, use_bias=self.use_bias, dtype=self.dtype,
            kernel_init=shard_init(nn.initializers.lecun_normal(),
                                   self.axis_name),
            bias_init=shard_init(nn.initializers.zeros, self.axis_name),
            name="shard")(x)


class RowParallelDense(nn.Module):
    """Linear layer with the weight split along the input dimension.

    Consumes activations sharded on the last dim (a column-parallel output);
    the partial products are summed with one ``psum`` over the tp axis, then
    the (replicated) bias is added once.
    """
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(
            self.features, use_bias=False, dtype=self.dtype,
            kernel_init=shard_init(nn.initializers.lecun_normal(),
                                   self.axis_name),
            name="shard")(x)
        if axis_bound(self.axis_name):
            # psum whenever the axis is BOUND — at size 1 it's a numeric
            # no-op the compiler elides, but it clears the tp-varying VMA
            # type the sharded kernel imprinted on y.
            y = lax.psum(y, self.axis_name)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + jnp.asarray(bias, self.dtype)
        return y


def apply_rope(x, positions, theta):
    """Rotary position embedding (rotate-half pairing), fp32 rotation.

    ``x``: (B, L, h, d) with d even; ``positions``: (L,) int32 GLOBAL token
    positions (under sequence parallelism pass the shard's global offsets),
    or (B, L) PER-ROW positions — the continuous-batching decode path,
    where each batch row sits at its own sequence offset.
    Rotation is position-absolute, so pre-rotated keys stay correct when a
    ring/Ulysses scheme later moves them between chips.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope needs an even head_dim, got {d}")
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)    # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv   # ([B,] L, d/2)
    cos = jnp.cos(ang)[..., None, :]                       # (+ head axis)
    sin = jnp.sin(ang)[..., None, :]
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]                    # broadcast batch
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def prologue_path(qkv_shape, heads, kv_heads, head_dim, itemsize, *,
                  flash, normed, rotated, varying=False):
    """``(path, tile)``: how a full-sequence call goes from its fused qkv
    rows ``qkv_shape`` (B, L, (heads + 2 kv_heads) head_dim) to attention,
    read off the call alone. Path 1, the one pass of
    ``ops/pallas/attn_prologue.py`` (norm, rotation and the move into the
    flash kernels' layout; ``tile`` its rows a grid step), where the call
    takes the flash kernels with nothing between (``flash``: flash, no
    mask, no sp axis, not decoding), the layer norms its heads or rotates
    them, the length needs no padding and the kernels admit the shapes
    (``row_tile``: a head whole lane tiles). Path 0 (tile 0), the split,
    ``nn.RMSNorm``, :func:`apply_rope` and the kernels' own layout change,
    for every other call; also for operands that vary over a mesh axis
    under the Pallas interpreter, as in :func:`flash_attention`."""
    from horovod_tpu.ops.pallas import attn_prologue
    from horovod_tpu.ops.pallas.flash_attention import (_interpret,
                                                        _pick_block)
    length = qkv_shape[1]
    tile = attn_prologue.row_tile(length, heads, kv_heads, head_dim,
                                  itemsize)
    if not (flash and (normed or rotated) and tile and _pick_block(length)) \
            or (varying and _interpret()):
        return 0, 0
    return 1, tile


def plain_attention(q, k, v, out_dtype, mask=None, bias=None, causal=False,
                    window=None):
    """The ONE plain-XLA attend kernel (scaled scores, optional additive
    bias, -1e9 causal/key masking, fp32 softmax) shared by self- and
    cross-attention. q/k/v: (B, L, h, d); ``mask``: (B, Lk) True on valid
    keys; ``bias``: (h, Lq, Lk) added to scores; ``window`` (causal only):
    each query sees its last ``window`` keys, itself included."""
    head_dim = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
    if bias is not None:
        scores = scores + bias[None].astype(scores.dtype)
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        cmask = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        if window is not None:
            cmask &= ~jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq - window)
        scores = jnp.where(cmask[None, None], scores,
                           jnp.asarray(-1e9, scores.dtype))
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :], scores,
                           jnp.asarray(-1e9, scores.dtype))
    probs = nn.softmax(scores.astype(jnp.float32)).astype(out_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class HeadScale(nn.Module):
    """The learned scale of an ``nn.RMSNorm`` over a head, alone and under
    the same name (``<name>/scale``: ``features`` float32 ones), for the
    prologue pass that applies it."""
    features: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.features,),
                          jnp.float32)


class TPSelfAttention(nn.Module):
    """Multi-head attention with heads sharded over the tp axis.

    Fused QKV projection is column-parallel (each shard owns
    ``num_heads / tp`` heads — one large MXU matmul per shard), the output
    projection is row-parallel: exactly one psum per attention block.

    ``num_kv_heads`` < ``num_heads`` turns on grouped-query attention: the
    fused projection emits only that many K/V heads (smaller matmul and —
    the real win — a ``num_heads/num_kv_heads``-times smaller KV cache in
    decode mode); K/V are broadcast to the query heads at attend time.
    ``rope_theta`` replaces additive position embeddings with rotary ones
    applied to Q/K inside the block (global positions are derived from the
    sp shard index / the decode cache cursor, so RoPE composes with both);
    None is the no-position case. ``head_dim`` states the head size where
    it is not ``hidden_size / num_heads`` (28 heads of 128 at hidden 2560).
    ``window`` (causal, full-sequence, no sp): each query attends its last
    ``window`` keys, itself included; the flash kernels skip the tiles
    behind it. ``qk_norm_eps`` (None: no norm) puts an RMS norm over the
    ``head_dim`` of every query head and every key head, before the
    rotation, with one learned scale for all query heads and one for all
    key heads (``q_norm/scale``, ``k_norm/scale``; float32 statistics). ``gated``
    adds a fourth column-parallel projection of the same input, ``gate``,
    as wide as the query heads and sharded with them; the heads' output is
    multiplied by its sigmoid before the output projection. Both act on the
    full-sequence path, plain and flash alike, under the scopes
    ``attn.qk_norm`` and ``attn.gate``; with ``decode=True`` or an
    ``sp_axis`` they raise. The rest of a call lies under the leaf scopes
    ``attn.qkv``, ``attn.rope``, ``attn.core`` (``_attend`` and the
    heads' merge) and ``attn.out`` (``trace/scopes.py``). Where
    :func:`prologue_path` admits the call, the split, the norm, the
    rotation and the flash kernels' layout change are one Pallas pass each
    way (``ops/pallas/attn_prologue.py``) under the scope of the first of
    them it does, ``attn.qk_norm`` or ``attn.rope``.
    """
    num_heads: int
    hidden_size: int
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS
    causal: bool = False
    use_flash: bool = False   # tiled Pallas attention (ops/pallas)
    sp_axis: Optional[str] = None   # sequence-parallel axis (tokens sharded)
    sp_impl: str = "ring"           # "ring" | "ulysses"
    decode: bool = False            # KV-cache single-token decoding
    cache_len: int = 0              # cache capacity when decode=True
    kv_cache_int8: bool = False     # quantized decode cache (lossy)
    num_kv_heads: Optional[int] = None   # None -> MHA (= num_heads)
    rope_theta: Optional[float] = None   # None -> no rotary embedding
    use_bias: bool = True
    head_dim: Optional[int] = None       # None -> hidden_size // num_heads
    window: Optional[int] = None         # None -> every earlier key
    qk_norm_eps: Optional[float] = None  # None -> q and k heads not normed
    gated: bool = False                  # sigmoid(gate(x)) * heads' output

    def _decode_attend(self, q, k, v, bias=None, pos=None):
        """Cached decode against the KV cache: ``s`` query tokens per call
        (s=1 is the classic one-token step; s>1 is a CHUNK — the
        speculative-verification path scores gamma+1 proposals in one
        feed). q: (B, s, h, d), k/v: (B, s, kv, d) — the cache stores only
        the kv heads, the GQA serving win. Within the chunk attention is
        causal (query row i sees cache positions <= idx + i). ``bias``:
        (local_heads, 1, cache_len) additive scores bias for a
        SINGLE-token step (T5 relative positions; the caller computes it
        from the cache cursor — chunked T5 decode is not supported).
        Cache variables are created on the first call (B and capacity fix
        the shapes; flax initializes them lazily under
        mutable=['cache']).

        ``pos`` as a (B,) int32 VECTOR switches to explicit per-row
        positions — the continuous-batching serving path, where every
        batch row (slot) decodes at its own sequence offset: K/V rows are
        scattered at ``pos[b] + i``, RoPE rotates by the same per-row
        positions, and the causal mask bounds each row by its own cursor.
        The internal scalar cursor is bypassed (the caller owns the
        per-row cursors); scalar/None ``pos`` keeps the classic
        shared-cursor semantics unchanged.

        ``kv_cache_int8``: rows are stored int8 with one fp32 scale per
        (batch, position, kv-head) — ~1/2 the HBM of a bf16 cache (1/4 of
        fp32) and half the cache bandwidth per step, the usual serving
        bottleneck; dequantization is fused into the attend. Lossy: one
        symmetric-quantization error per row, bounded by max|row|/127."""
        B, s, h, d = q.shape
        kv = k.shape[2]
        L = self.cache_len
        int8c = self.kv_cache_int8
        cache_dt = jnp.int8 if int8c else q.dtype
        ck = self.variable("cache", "k", jnp.zeros, (B, L, kv, d), cache_dt)
        cv = self.variable("cache", "v", jnp.zeros, (B, L, kv, d), cache_dt)
        ci = self.variable("cache", "idx",
                           lambda: jnp.zeros((), jnp.int32))
        if int8c:
            cks = self.variable("cache", "k_scale", jnp.zeros,
                                (B, L, kv), jnp.float32)
            cvs = self.variable("cache", "v_scale", jnp.zeros,
                                (B, L, kv), jnp.float32)
        idx = ci.value
        per_row = pos is not None and jnp.ndim(pos) == 1
        if per_row:
            if bias is not None:
                raise ValueError("per-row decode positions do not compose "
                                 "with an attention bias (T5 relative "
                                 "positions feed the shared-cursor path)")
            posm = pos.astype(jnp.int32)[:, None] + jnp.arange(s)   # (B, s)
        if self.rope_theta is not None:
            rp = posm if per_row else idx + jnp.arange(s)
            q = apply_rope(q, rp, self.rope_theta)
            k = apply_rope(k, rp, self.rope_theta)    # cache holds rotated K

        if int8c:
            from horovod_tpu.parallel.strategies import \
                symmetric_int8_quantize

            def quant(t):
                # per-(B, s, kv)-row scale over the head dim, fp32 math
                return symmetric_int8_quantize(t.astype(jnp.float32))

            k8, ks = quant(k)
            v8, vs_ = quant(v)
            if per_row:
                b_ix = jnp.arange(B)[:, None]                     # (B, 1)
                ck.value = ck.value.at[b_ix, posm].set(k8)
                cv.value = cv.value.at[b_ix, posm].set(v8)
                cks.value = cks.value.at[b_ix, posm].set(ks)
                cvs.value = cvs.value.at[b_ix, posm].set(vs_)
            else:
                ck.value = lax.dynamic_update_slice(ck.value, k8,
                                                    (0, idx, 0, 0))
                cv.value = lax.dynamic_update_slice(cv.value, v8,
                                                    (0, idx, 0, 0))
                cks.value = lax.dynamic_update_slice(cks.value, ks,
                                                     (0, idx, 0))
                cvs.value = lax.dynamic_update_slice(cvs.value, vs_,
                                                     (0, idx, 0))
            keys = (ck.value.astype(jnp.float32)
                    * cks.value[..., None]).astype(q.dtype)
            vals = (cv.value.astype(jnp.float32)
                    * cvs.value[..., None]).astype(q.dtype)
        elif per_row:
            b_ix = jnp.arange(B)[:, None]                         # (B, 1)
            ck.value = ck.value.at[b_ix, posm].set(k)
            cv.value = cv.value.at[b_ix, posm].set(v)
            keys, vals = ck.value, cv.value
        else:
            ck.value = lax.dynamic_update_slice(ck.value, k, (0, idx, 0, 0))
            cv.value = lax.dynamic_update_slice(cv.value, v, (0, idx, 0, 0))
            keys, vals = ck.value, cv.value
        ci.value = idx + s
        # Grouped attend: q heads reshaped to (kv, group) contract directly
        # against the NARROW cache — no materialized broadcast of K/V to the
        # query heads, so the GQA cache shrinks bandwidth, not just capacity.
        g = h // kv
        qg = q.reshape(B, s, kv, g, d)
        scores = jnp.einsum("bqngd,bknd->bngqk", qg, keys) / np.sqrt(d)
        if bias is not None:
            scores = scores + bias.reshape(kv, g, 1, L)[None].astype(
                scores.dtype)
        # causal within the chunk, bounded by the filled prefix: query row
        # i attends cache positions <= idx + i (per-row: <= pos[b] + i)
        if per_row:
            valid = jnp.arange(L)[None, None, :] <= posm[:, :, None]
            scores = jnp.where(valid[:, None, None, :, :], scores,
                               jnp.asarray(-1e9, scores.dtype))
        else:
            valid = jnp.arange(L)[None, :] <= idx + jnp.arange(s)[:, None]
            scores = jnp.where(valid[None, None, None, :, :], scores,
                               jnp.asarray(-1e9, scores.dtype))
        probs = jax.nn.softmax(scores.astype(jnp.float32)).astype(self.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", probs, vals)
        return out.reshape(B, s, h, d)

    def _check_window(self):
        if self.window is not None and (self.sp_axis is not None
                                        or not self.causal):
            raise ValueError("a sliding window needs causal=True and no "
                             "sp_axis")

    def _attend(self, q, k, v, mask, bias=None):
        """Route full-sequence attention: sp ring/Ulysses, Pallas flash,
        or plain XLA. ``k``/``v`` may carry FEWER (grouped) heads than
        ``q``: the flash kernels stream the narrow tensors natively (no
        broadcast, 1/g the K/V HBM traffic), the sp schemes rotate/exchange
        them narrow (1/g the collective bytes); only the plain einsum
        broadcasts here. ``bias``: additive (local_heads, Lq, Lk) scores bias
        (T5-style relative positions) — plain path only. The guard mirrors
        the dispatch below: flash with a mask falls back to the plain
        path, where bias IS supported."""
        if bias is not None and (self.sp_axis is not None
                                 or (self.use_flash and mask is None)):
            raise ValueError(
                "additive attention bias is supported on the plain XLA "
                "path only (not flash/sp)")
        self._check_window()
        g = q.shape[2] // k.shape[2]
        if g > 1 and self.sp_axis is None and not (self.use_flash
                                                   and mask is None):
            # Only the plain einsum needs MHA shapes here. Flash streams
            # grouped K/V natively, and the sp schemes keep them NARROW
            # through their collectives (1/g the ring/all-to-all bytes),
            # broadcasting — if at all — on the far side of the exchange.
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        if self.sp_axis is not None:
            # Sequence parallelism: x carries this chip's token shard; the
            # QKV/out projections are token-local, the attention itself
            # runs over the sp ring (or Ulysses head exchange). Composes
            # with tp: heads are already the tp-local subset. Outside the
            # axis (init) both schemes degrade to local attention.
            if mask is not None:
                raise ValueError(
                    "padding masks are not supported with sp_axis (causal "
                    "masking is handled inside the sp schemes)")
            from horovod_tpu.parallel.sequence import (ring_attention,
                                                       ulysses_attention)
            if self.sp_impl == "ring":
                return ring_attention(q, k, v, axis_name=self.sp_axis,
                                      causal=self.causal,
                                      use_flash=self.use_flash)
            if self.sp_impl == "ulysses":
                return ulysses_attention(q, k, v, axis_name=self.sp_axis,
                                         causal=self.causal,
                                         use_flash=self.use_flash)
            raise ValueError(f"unknown sp_impl {self.sp_impl!r}")
        if self.use_flash and mask is None:
            from horovod_tpu.ops.pallas import flash_attention
            return flash_attention(q, k, v, causal=self.causal,
                                   window=self.window)
        return plain_attention(q, k, v, out_dtype=self.dtype, mask=mask,
                               bias=bias, causal=self.causal,
                               window=self.window)

    @nn.compact
    def __call__(self, x, mask=None, bias=None, pos=None):
        n = axis_size_or_1(self.axis_name)
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % n != 0 or kv_heads % n != 0:
            raise ValueError(
                f"num_heads {self.num_heads} / num_kv_heads {kv_heads} "
                f"not divisible by tp={n}")
        if self.num_heads % kv_heads != 0:
            raise ValueError(
                f"num_kv_heads {kv_heads} must divide num_heads "
                f"{self.num_heads}")
        local_heads = self.num_heads // n
        local_kv = kv_heads // n
        head_dim = self.head_dim or self.hidden_size // self.num_heads
        normed = self.qk_norm_eps is not None
        if normed or self.gated:
            if self.decode or self.sp_axis is not None:
                raise ValueError(
                    "qk_norm_eps and gated act on the full-sequence path "
                    "only (neither decode=True nor an sp_axis)")
            from horovod_tpu.metrics import instruments as hvd_metrics
            hvd_metrics.record_attn_layer(
                self.num_heads, kv_heads, head_dim, self.window or 0,
                normed, self.gated)

        # Column-parallel fused QKV: shard s's local output is
        # [q_s | k_s | v_s] for its heads [s*local_heads, (s+1)*local_heads)
        # (and the matching kv-head slice), i.e. the global logical weight is
        # the head-blocked interleaving of the shards — one large MXU matmul
        # per shard.
        def heads(t):
            return t.reshape(t.shape[:-1] + (-1, head_dim))

        with scope("attn.qkv"):
            qkv = ColumnParallelDense(
                (self.num_heads + 2 * kv_heads) * head_dim, dtype=self.dtype,
                use_bias=self.use_bias, axis_name=self.axis_name,
                name="qkv")(x)
        from horovod_tpu.ops.pallas.flash_attention import _vma
        path, tile = prologue_path(
            qkv.shape, local_heads, local_kv, head_dim, qkv.dtype.itemsize,
            flash=(self.use_flash and mask is None and bias is None
                   and self.sp_axis is None and not self.decode),
            normed=normed, rotated=self.rope_theta is not None,
            varying=bool(_vma(qkv)))
        from horovod_tpu.metrics import instruments as hvd_metrics
        hvd_metrics.record_attn_prologue(path, "/".join(self.path))
        if path:
            from horovod_tpu.ops.pallas.attn_prologue import attention
            from horovod_tpu.ops.pallas.flash_attention import heads_last
            self._check_window()
            scales = (HeadScale(head_dim, name="q_norm")(),
                      HeadScale(head_dim, name="k_norm")()) if normed \
                else (None, None)
            # One pass from the rows to the kernels' operands, under the
            # scope of the first thing it does, then the kernels under
            # attn.core: their (B H, L, D) output (ops/pallas/attn_prologue)
            out = attention(qkv, *scales, local_heads, local_kv,
                            self.qk_norm_eps, self.rope_theta, tile,
                            self.causal, self.window)
        else:
            with scope("attn.qkv"):
                q, k, v = jnp.split(
                    qkv, [local_heads * head_dim,
                          (local_heads + local_kv) * head_dim], axis=-1)
                q, k, v = heads(q), heads(k), heads(v)
            if normed:
                with scope("attn.qk_norm"):
                    q = nn.RMSNorm(epsilon=self.qk_norm_eps,
                                   dtype=self.dtype, name="q_norm")(q)
                    k = nn.RMSNorm(epsilon=self.qk_norm_eps,
                                   dtype=self.dtype, name="k_norm")(k)
            if self.decode:
                if self.sp_axis is not None or mask is not None \
                        or self.window is not None:
                    raise ValueError(
                        "decode mode supports neither sp_axis, masks nor a "
                        "sliding window")
                if bias is not None and x.shape[1] != 1:
                    raise ValueError(
                        f"decode with an attention bias (T5 relative "
                        f"positions) feeds ONE token per call, got "
                        f"{x.shape[1]}")
                if self.cache_len < 1:
                    raise ValueError("decode=True requires cache_len >= 1")
                # RoPE + grouped KV handled inside; bias is this step's
                # relative-position row over the cache; a (B,) pos vector
                # switches to explicit per-row (continuous-batching) cursors
                out = self._decode_attend(q, k, v, bias=bias, pos=pos)
            else:
                if self.rope_theta is not None:
                    # Global token positions: under sequence parallelism x
                    # holds this chip's contiguous token shard (same offset
                    # math as GPTEmbed's sp path); otherwise 0..L-1.
                    L = x.shape[-2]
                    off = 0
                    if (self.sp_axis is not None
                            and axis_size_or_1(self.sp_axis) > 1):
                        off = lax.axis_index(self.sp_axis) * L
                    with scope("attn.rope"):
                        positions = off + jnp.arange(L, dtype=jnp.int32)
                        q = apply_rope(q, positions, self.rope_theta)
                        k = apply_rope(k, positions, self.rope_theta)
                # Grouped kv heads stay NARROW here: _attend broadcasts
                # them for the paths that need MHA shapes and streams them
                # natively through the flash kernels. (Decode above instead
                # contracts grouped q heads against the narrow cache.)
                with scope("attn.core"):
                    out = self._attend(q, k, v, mask, bias=bias)

        def merge(out, gate=None):
            with scope("attn.core"):      # the heads' merge belongs to it
                if path:
                    out = heads_last(out, x.shape[0], x.shape[1])
                out = out.reshape(out.shape[:-2] + (local_heads * head_dim,))
            if gate is None:
                return out
            with scope("attn.gate"):
                return out * nn.sigmoid(gate)

        if self.gated:
            with scope("attn.gate"):
                gate = ColumnParallelDense(
                    self.num_heads * head_dim, dtype=self.dtype,
                    use_bias=self.use_bias, axis_name=self.axis_name,
                    name="gate")(x)
            # On path 1 the backward pass merges the heads again from the
            # kernels' output, which the pass keeps, where the product
            # would keep the merged copy (PERF.md, PR 38).
            out = (jax.checkpoint(merge) if path else merge)(out, gate)
        else:
            out = merge(out)
        with scope("attn.out"):
            return RowParallelDense(self.hidden_size, dtype=self.dtype,
                                    use_bias=self.use_bias,
                                    axis_name=self.axis_name,
                                    name="out")(out)


class TPMlp(nn.Module):
    """Transformer MLP: column-parallel expansion, gelu, row-parallel
    contraction — one psum per MLP block."""
    intermediate_size: int
    hidden_size: int
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS

    @nn.compact
    def __call__(self, x):
        h = ColumnParallelDense(self.intermediate_size, dtype=self.dtype,
                                axis_name=self.axis_name, name="in")(x)
        h = nn.gelu(h)
        return RowParallelDense(self.hidden_size, dtype=self.dtype,
                                axis_name=self.axis_name, name="out")(h)


class TPSwiGLUMlp(nn.Module):
    """Gated MLP: fused column-parallel gate+up projection (one MXU
    matmul), ``act(gate) * up``, row-parallel contraction — still exactly
    one psum per MLP block. Gate and up interact only elementwise, so
    sharding both along the intermediate dim keeps every shard
    self-contained until the row-parallel reduce. ``activation``: "silu"
    (LLaMA SwiGLU) or "gelu" (T5 1.1 GEGLU)."""
    intermediate_size: int
    hidden_size: int
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS
    use_bias: bool = False
    activation: str = "silu"

    @nn.compact
    def __call__(self, x):
        acts = {"silu": nn.silu, "gelu": nn.gelu}
        if self.activation not in acts:
            raise ValueError(f"unknown activation {self.activation!r}; "
                             f"choose from {sorted(acts)}")
        h = ColumnParallelDense(2 * self.intermediate_size, dtype=self.dtype,
                                use_bias=self.use_bias,
                                axis_name=self.axis_name, name="gate_up")(x)
        g, u = jnp.split(h, 2, axis=-1)
        h = acts[self.activation](g) * u
        return RowParallelDense(self.hidden_size, dtype=self.dtype,
                                use_bias=self.use_bias,
                                axis_name=self.axis_name, name="out")(h)


class TPCrossAttention(nn.Module):
    """Encoder-decoder cross-attention with heads sharded over tp.

    Queries project from the decoder stream ``x`` (column-parallel), keys
    and values from the encoder ``memory`` (one fused column-parallel
    matmul); the output projection is row-parallel — one psum per block,
    exactly like :class:`TPSelfAttention`. ``memory_mask``: (B, Lk) True
    for valid encoder positions."""
    num_heads: int
    hidden_size: int
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS
    use_bias: bool = True

    def _kv_proj(self):
        return ColumnParallelDense(2 * self.hidden_size, dtype=self.dtype,
                                   use_bias=self.use_bias,
                                   axis_name=self.axis_name, name="kv")

    @nn.compact
    def __call__(self, x, memory, memory_mask=None, cached_kv=None,
                 project_only=False):
        """``project_only=True`` returns the fused K/V projection of
        ``memory`` (x ignored) — decode loops call it ONCE and feed the
        result back per step as ``cached_kv``, skipping the per-step
        O(Ls d^2) projection of a static encoder memory."""
        n = axis_size_or_1(self.axis_name)
        if self.num_heads % n != 0:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by tp={n}")
        local_heads = self.num_heads // n
        head_dim = self.hidden_size // self.num_heads
        if project_only:
            return self._kv_proj()(memory)

        q = ColumnParallelDense(self.hidden_size, dtype=self.dtype,
                                use_bias=self.use_bias,
                                axis_name=self.axis_name, name="q")(x)
        kv = cached_kv if cached_kv is not None else self._kv_proj()(memory)
        k, v = jnp.split(kv, 2, axis=-1)

        def heads(t):
            return t.reshape(t.shape[:-1] + (-1, head_dim))

        q, k, v = heads(q), heads(k), heads(v)
        out = plain_attention(q, k, v, out_dtype=self.dtype,
                              mask=memory_mask)
        out = out.reshape(out.shape[:-2] + (local_heads * head_dim,))
        return RowParallelDense(self.hidden_size, dtype=self.dtype,
                                use_bias=self.use_bias,
                                axis_name=self.axis_name, name="out")(out)


class TPTransformerBlock(nn.Module):
    """Pre-LN transformer block with TP attention + TP MLP (2 psums total).

    LayerNorm parameters are replicated across tp; their gradients are made
    consistent by the data-parallel gradient reduction exactly as in
    Megatron.
    """
    num_heads: int
    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS
    causal: bool = False
    use_flash: bool = False
    sp_axis: Optional[str] = None
    sp_impl: str = "ring"
    decode: bool = False
    cache_len: int = 0
    kv_cache_int8: bool = False

    @nn.compact
    def __call__(self, x, mask=None, pos=None):
        with scope("block.norm"):
            h = nn.LayerNorm(dtype=self.dtype, name="ln_attn")(x)
        with scope("attn.full"):
            a = TPSelfAttention(self.num_heads, self.hidden_size,
                                dtype=self.dtype, axis_name=self.axis_name,
                                causal=self.causal, use_flash=self.use_flash,
                                sp_axis=self.sp_axis, sp_impl=self.sp_impl,
                                decode=self.decode, cache_len=self.cache_len,
                                kv_cache_int8=self.kv_cache_int8,
                                name="attention")(h, mask, pos=pos)
        x = x + a
        with scope("block.norm"):
            h = nn.LayerNorm(dtype=self.dtype, name="ln_mlp")(x)
        with scope("mlp.dense"):
            h = TPMlp(self.intermediate_size, self.hidden_size,
                      dtype=self.dtype, axis_name=self.axis_name,
                      name="mlp")(h)
        return x + h

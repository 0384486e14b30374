"""Multi-head latent attention (MLA, DeepSeek-V3's form) with heads sharded
over the tp axis.

Queries and keys reach the heads through low-rank latents, each normed:

    c_q  = RMSNorm(x W_qa)                         (q_lora_rank)
    [q_nope_h | q_pe_h] = c_q W_qb                 (per head: nope | rope)
    [c_kv | k_pe] = x W_kva;  c_kv <- RMSNorm(c_kv)   (kv_lora_rank | rope)
    [k_nope_h | v_h] = c_kv W_kvb                  (per head: nope | v)

With ``q_lora_rank=None`` the query has no latent: ``[q_nope_h | q_pe_h]
= x W_q``, one column-parallel product (Kimi-Linear's form). With
``rope=False`` nothing is rotated: ``q_pe_h`` and ``k_pe`` enter the
scores as they are (no layer has positions).

``k_pe`` is ONE rotary key shared by every head. The rotation is RoPE on
interleaved pairs ``(2j, 2j+1)``, by ``pos * theta^(-2j / rope_dim)``, of
``q_pe_h`` and ``k_pe`` alone (computed as rotate-half over the pairs
moved apart, the same order for both: ``rope_pairs``). Then ``q_h = [q_nope_h | rot(q_pe_h)]`` and
``k_h = [k_nope_h | rot(k_pe)]`` are ``qk_nope + rope`` wide, ``v_h`` is
``v_head_dim`` wide, and ``o_h = softmax(q_h k_h^T / sqrt(qk width) +
causal) v_h`` goes through the flash kernels at the two widths as they
are (``ops/pallas/flash_attention.py``): nothing pads ``v`` to the query's
width. ``out = [o_1 .. o_n] W_o``; no biases.

As :class:`~horovod_tpu.parallel.tp.TPSelfAttention` shards its heads:
``W_qb`` and ``W_kvb`` are column-parallel (a shard's columns are its
heads, each head's block contiguous), ``W_o`` row-parallel (one psum); the
down products and the latent norms are replicated. The layer acts on the
full-sequence path alone: with ``decode=True`` (the absorbed form and a
latent cache are not built) or an ``sp_axis`` it raises.

Scopes (``trace/scopes.py``): ``attn.q_latent`` (the query's down product,
its norm, the up product and the rotation; the one query product where
there is no latent), ``attn.kv_latent`` (the key and
value latent's, the shared rotary key, and ``k`` assembled), ``attn.core``
(the kernels and the heads' merge) and ``attn.out``.
"""

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.tp import (TP_AXIS, ColumnParallelDense,
                                     RowParallelDense, apply_rope,
                                     axis_size_or_1, plain_attention)
from horovod_tpu.trace.scopes import scope


def rope_pairs(x, positions, theta):
    """RoPE on interleaved pairs, up to one fixed order of the last axis:
    pair ``(2j, 2j+1)`` is moved to ``(j, j + d/2)`` and turned by
    ``positions * theta^(-2j / d)`` through rotate-half
    (:func:`~horovod_tpu.parallel.tp.apply_rope`), and left in that order.
    Queries and the rotary key take the same order, so every ``q . k`` is
    the interleaved form's. ``x``: (B, L, h, d), ``positions``: (L,)."""
    d = x.shape[-1]
    halves = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2)
    return apply_rope(halves.reshape(x.shape), positions, theta)


class TPLatentAttention(nn.Module):
    """Latent attention (module docstring), causal over the full
    sequence. ``rms_eps`` is the latent norms' epsilon; ``q_lora_rank``
    None gives the query no latent, ``rope`` False rotates nothing."""
    num_heads: int
    hidden_size: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float = 1e-6
    dtype: Any = jnp.float32
    axis_name: Optional[str] = TP_AXIS
    use_flash: bool = False
    sp_axis: Optional[str] = None
    decode: bool = False
    rope: bool = True

    @nn.compact
    def __call__(self, x):
        if self.decode or self.sp_axis is not None:
            raise ValueError("latent attention acts on the full-sequence "
                             "path only (neither decode=True nor an "
                             "sp_axis)")
        n = axis_size_or_1(self.axis_name)
        if self.num_heads % n:
            raise ValueError(f"num_heads {self.num_heads} not divisible by "
                             f"tp={n}")
        heads = self.num_heads // n
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        from horovod_tpu.metrics import instruments as hvd_metrics
        hvd_metrics.record_latent_attn_layer(
            self.num_heads, nope + rope, dv, self.q_lora_rank or 0,
            self.kv_lora_rank, rope)
        b, length = x.shape[0], x.shape[1]
        positions = jnp.arange(length, dtype=jnp.int32)

        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_eps, dtype=self.dtype,
                              name=name)

        def down(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        with scope("attn.q_latent"):
            if self.q_lora_rank is None:
                c_q, name = x, "q"
            else:
                c_q = norm("q_a_norm")(down(self.q_lora_rank, "q_a")(x))
                name = "q_b"
            q = ColumnParallelDense(
                self.num_heads * (nope + rope), use_bias=False,
                dtype=self.dtype, axis_name=self.axis_name,
                name=name)(c_q).reshape(b, length, heads, nope + rope)
            if self.rope:
                q = jnp.concatenate([q[..., :nope], rope_pairs(
                    q[..., nope:], positions, self.rope_theta)], -1)
        with scope("attn.kv_latent"):
            c_kv, k_pe = jnp.split(
                down(self.kv_lora_rank + rope, "kv_a")(x),
                [self.kv_lora_rank], -1)
            kv = ColumnParallelDense(
                self.num_heads * (nope + dv), use_bias=False,
                dtype=self.dtype, axis_name=self.axis_name,
                name="kv_b")(norm("kv_a_norm")(c_kv)).reshape(
                    b, length, heads, nope + dv)
            k_pe = k_pe[:, :, None]
            if self.rope:
                k_pe = rope_pairs(k_pe, positions, self.rope_theta)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_pe, (b, length, heads, rope))], -1)
            v = kv[..., nope:]
        with scope("attn.core"):
            if self.use_flash:
                from horovod_tpu.ops.pallas import flash_attention
                out = flash_attention(q, k, v, causal=True)
            else:
                out = plain_attention(q, k, v, out_dtype=self.dtype,
                                      causal=True)
            out = out.reshape(b, length, heads * dv)
        with scope("attn.out"):
            return RowParallelDense(self.hidden_size, use_bias=False,
                                    dtype=self.dtype,
                                    axis_name=self.axis_name,
                                    name="out")(out)

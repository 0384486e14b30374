"""GPT-style decoder-only LM, built from the framework's parallel layers.

The reference framework has no model zoo of its own (its examples/ tree is
absent from the snapshot, SURVEY.md intro) — models here exist to exercise and
benchmark the distributed machinery. This one is the composite-parallelism
flagship: tensor-parallel attention/MLP blocks (parallel/tp.py), optional
expert-parallel MoE FFN (parallel/moe.py), and a shape-invariant block design
so the same blocks pipeline over a ``pp`` axis (parallel/pp.py).

TPU-first choices: bf16 activations with fp32 params/logits, fused QKV, static
causal mask, no data-dependent control flow.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.moe import MoEMlp
from horovod_tpu.parallel.tp import TPTransformerBlock
from horovod_tpu.trace.scopes import scope


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    num_experts: int = 0            # 0 -> dense MLP blocks only
    moe_k: int = 1
    capacity_factor: float = 2.0
    # Hierarchical expert dispatch over the ep axis: None = auto (the
    # HOROVOD_HIERARCHICAL_ALLTOALL / a2a strategy registry chain),
    # True/False force it (parallel/moe.py).
    moe_hierarchical: Optional[bool] = None
    dtype: Any = jnp.float32
    tp_axis: Optional[str] = "tp"   # None -> no tensor parallelism
    ep_axis: Optional[str] = "ep"   # axis carrying the experts (often = dp)
    use_flash: bool = False         # Pallas flash attention (ops/pallas)
    sp_axis: Optional[str] = None   # sequence parallelism: tokens sharded
    sp_impl: str = "ring"           # "ring" | "ulysses" (parallel/sequence)
    # Rematerialize each block's activations in the backward pass
    # (jax.checkpoint): activation memory drops from O(layers) to O(1)
    # blocks at ~1/3 extra FLOPs — the lever for bigger per-chip batches
    # (MFU) and longer contexts on fixed HBM.
    remat: bool = False
    kv_cache_int8: bool = False     # quantized decode cache (serving)

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs."""
        base = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
                    intermediate_size=128, max_position_embeddings=64)
        base.update(kw)
        return GPTConfig(**base)


class GPTEmbed(nn.Module):
    """Token + learned position embeddings (replicated params).

    ``pos`` (decode mode): a traced scalar — the global position of the
    FIRST token in ``input_ids`` (shape (B, s)); the table is sliced
    dynamically at positions ``pos..pos+s-1`` instead of by the static
    prefix (s=1 is the classic one-token step; s>1 is the chunked feed
    the speculative verifier uses). A (B,) VECTOR ``pos`` is the
    continuous-batching serving path: every batch row (slot) sits at its
    own position, so the table is gathered per row.
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, pos=None):
        c = self.config
        L = input_ids.shape[-1]
        tok = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="tok_emb")(input_ids)
        table = self.param("pos_emb", nn.initializers.normal(0.02),
                           (c.max_position_embeddings, c.hidden_size),
                           jnp.float32)
        if pos is not None:
            import jax
            if jnp.ndim(pos) == 1:            # per-row (serving) positions
                rows = pos.astype(jnp.int32)[:, None] + jnp.arange(L)
                sl = jnp.take(table, rows, axis=0)          # (B, s, H)
                return tok + jnp.asarray(sl, c.dtype)
            sl = jax.lax.dynamic_slice_in_dim(table, pos, L)   # (s, H)
            return tok + jnp.asarray(sl, c.dtype)[None]
        pos = table  # legacy local name for the static paths below
        if c.sp_axis is not None:
            # Sequence-parallel: input_ids carry this chip's token shard;
            # index the position table at the GLOBAL positions of the shard
            # (outside the axis, e.g. init, the offset is zero).
            from horovod_tpu.parallel.tp import axis_size_or_1
            n_sp = axis_size_or_1(c.sp_axis)
            if n_sp > 1:
                import jax
                if n_sp * L > c.max_position_embeddings:
                    # dynamic_slice would CLAMP out-of-range shards onto
                    # the last positions — fail loudly like the unsharded
                    # path's broadcast error does.
                    raise ValueError(
                        f"global sequence {n_sp}x{L} exceeds "
                        f"max_position_embeddings="
                        f"{c.max_position_embeddings}")
                off = jax.lax.axis_index(c.sp_axis) * L
                sl = jax.lax.dynamic_slice_in_dim(pos, off, L)
                return tok + jnp.asarray(sl, c.dtype)[None]
        return tok + jnp.asarray(pos[:L], c.dtype)[None]


class GPTHead(nn.Module):
    """Final LayerNorm + language-model head (fp32 logits)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = nn.LayerNorm(dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


class GPTMoEBlock(nn.Module):
    """Pre-LN block: TP causal attention + expert-parallel MoE FFN.

    Returns only the hidden state (shape-invariant, pipelineable); the MoE
    load-balance loss is accumulated in the ``"losses"`` collection via
    ``Module.sow`` so callers fetch it with ``mutable=["losses"]``.
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.parallel.tp import TPSelfAttention
        c = self.config
        a = TPSelfAttention(c.num_heads, c.hidden_size, dtype=c.dtype,
                            axis_name=c.tp_axis, causal=True,
                            use_flash=c.use_flash, sp_axis=c.sp_axis,
                            sp_impl=c.sp_impl, name="attention")(
                                nn.LayerNorm(dtype=c.dtype, name="ln_attn")(x))
        x = x + a
        h, aux = MoEMlp(c.num_experts, c.hidden_size, c.intermediate_size,
                        k=c.moe_k, capacity_factor=c.capacity_factor,
                        dtype=c.dtype, axis_name=c.ep_axis,
                        hierarchical=c.moe_hierarchical, name="moe")(
                            nn.LayerNorm(dtype=c.dtype, name="ln_mlp")(x))
        self.sow("losses", "moe_aux", aux)
        return x + h


class GPT(nn.Module):
    """Full (non-pipelined) model: embed -> blocks -> head.

    Blocks are dense TP blocks, with MoE blocks interleaved every
    ``moe_every``-th layer when ``config.num_experts > 0``. For pipeline
    parallelism, compose :class:`GPTEmbed` / block modules / :class:`GPTHead`
    yourself via :func:`horovod_tpu.parallel.pp.pipeline` (see
    ``parallel/composite.py``).
    """
    config: GPTConfig
    moe_every: int = 2
    decode: bool = False   # KV-cache single-token decoding (dense only)

    @nn.compact
    def __call__(self, input_ids, pos=None, features_only=False):
        """``features_only=True`` (apply-time only) returns the pre-head
        hidden states ``(B, L, H)`` — feed them to
        :func:`horovod_tpu.optim.next_token_xent_chunked` with the head
        bound to ``params["head"]`` so the full (B, L, V) logits tensor
        never materializes (initialize with the default False so the head
        params exist)."""
        c = self.config
        if self.decode:
            if c.num_experts:
                raise ValueError("decode mode does not support MoE blocks")
            if pos is None:
                raise ValueError("decode mode requires pos (the token's "
                                 "global position)")
        # remat (training only — decode has no backward): recompute each
        # block in the vjp instead of stashing its activations.
        dense_cls = TPTransformerBlock
        moe_cls = GPTMoEBlock
        if c.remat and not self.decode:
            dense_cls = nn.remat(TPTransformerBlock)
            moe_cls = nn.remat(GPTMoEBlock)
        with scope("lm.model"):
            with scope("lm.embed"):
                x = GPTEmbed(c, name="embed")(input_ids,
                                              pos if self.decode else None)
            for i in range(c.num_layers):
                if c.num_experts and i % self.moe_every == self.moe_every - 1:
                    x = moe_cls(c, name=f"layer_{i}")(x)
                else:
                    x = dense_cls(
                        c.num_heads, c.hidden_size, c.intermediate_size,
                        dtype=c.dtype, axis_name=c.tp_axis, causal=True,
                        use_flash=c.use_flash, sp_axis=c.sp_axis,
                        sp_impl=c.sp_impl, decode=self.decode,
                        cache_len=c.max_position_embeddings,
                        kv_cache_int8=c.kv_cache_int8,
                        name=f"layer_{i}")(
                            x, pos=pos if self.decode else None)
            if features_only:
                return x
            with scope("lm.head"):
                return GPTHead(c, name="head")(x)

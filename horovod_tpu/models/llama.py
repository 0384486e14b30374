"""LLaMA-family decoder-only LM: RMSNorm, RoPE, SwiGLU, grouped-query
attention.

The reference framework has no model zoo (SURVEY.md intro) — models here
exercise and benchmark the distributed machinery. Where :class:`GPT` is the
GPT-2 lineage (learned positions, LayerNorm, gelu MLP, MHA), this is the
modern open-weights lineage: rotary positions applied inside attention
(``parallel/tp.py`` ``apply_rope``), pre-RMSNorm, gated SwiGLU MLP, and
``num_kv_heads < num_heads`` grouped-query attention whose decode-time KV
cache shrinks by the group factor.

TPU-first choices mirror GPT's: bf16 activations with fp32 params/logits,
fused projections (QKV in one column-parallel matmul, gate+up in another),
static shapes, and shape-invariant blocks so the same stack composes with
tensor (tp_axis), sequence (sp_axis: ring / Ulysses + Pallas flash), and
pipeline parallelism.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.tp import TPSelfAttention, TPSwiGLUMlp


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None    # None -> MHA
    intermediate_size: int = 11008
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    tp_axis: Optional[str] = "tp"   # None -> no tensor parallelism
    use_flash: bool = False         # Pallas flash attention (ops/pallas)
    sp_axis: Optional[str] = None   # sequence parallelism: tokens sharded
    sp_impl: str = "ring"           # "ring" | "ulysses" (parallel/sequence)
    # jax.checkpoint each block's backward (see GPTConfig.remat)
    remat: bool = False
    kv_cache_int8: bool = False     # quantized decode cache (serving)

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs (GQA on: 4 query heads per 2 kv heads)."""
        base = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
                    num_kv_heads=2, intermediate_size=128,
                    max_position_embeddings=64)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw):
        """LLaMA-2-7B shapes (MHA, 4k context)."""
        base = dict()
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama3_8b(**kw):
        """LLaMA-3-8B shapes: GQA 32q/8kv, 128k vocab, theta 5e5."""
        base = dict(vocab_size=128256, hidden_size=4096, num_layers=32,
                    num_heads=32, num_kv_heads=8, intermediate_size=14336,
                    max_position_embeddings=8192, rope_theta=500000.0)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def bench(**kw):
        """~400M-param config sized so a full training step (fp32 master +
        adam moments) fits one chip's HBM."""
        base = dict(vocab_size=32000, hidden_size=1024, num_layers=24,
                    num_heads=16, num_kv_heads=8, intermediate_size=2816,
                    max_position_embeddings=4096)
        base.update(kw)
        return LlamaConfig(**base)


class LlamaBlock(nn.Module):
    """Pre-RMSNorm block: GQA+RoPE attention, SwiGLU MLP, no biases
    (2 psums total under tp, exactly like :class:`TPTransformerBlock`).
    Shape-invariant, so it pipelines over a ``pp`` axis unchanged."""
    config: LlamaConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, pos=None):
        c = self.config
        a = TPSelfAttention(
            c.num_heads, c.hidden_size, dtype=c.dtype, axis_name=c.tp_axis,
            causal=True, use_flash=c.use_flash, sp_axis=c.sp_axis,
            sp_impl=c.sp_impl, decode=self.decode,
            cache_len=c.max_position_embeddings,
            kv_cache_int8=c.kv_cache_int8,
            num_kv_heads=c.num_kv_heads, rope_theta=c.rope_theta,
            use_bias=False, name="attention")(
                nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype,
                           name="ln_attn")(x), pos=pos)
        x = x + a
        h = TPSwiGLUMlp(c.intermediate_size, c.hidden_size, dtype=c.dtype,
                        axis_name=c.tp_axis, name="mlp")(
                            nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype,
                                       name="ln_mlp")(x))
        return x + h


class LlamaEmbed(nn.Module):
    """Token embedding only — no positional table; positions enter via RoPE
    inside every attention block. ``pos`` is accepted for the decoder
    interface but carries no embedding work."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, pos=None):
        c = self.config
        return nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name="tok_emb")(input_ids)


class LlamaHead(nn.Module):
    """Final RMSNorm + fp32 LM head (bias-free)."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


class Llama(nn.Module):
    """Full model: token embed -> blocks -> RMSNorm -> fp32 LM head.

    Compose :class:`LlamaEmbed` / :class:`LlamaBlock` / :class:`LlamaHead`
    yourself for pipeline parallelism (see ``parallel/composite.py``'s
    ``CompositeLlama``).
    """
    config: LlamaConfig
    decode: bool = False   # KV-cache single-token decoding

    @nn.compact
    def __call__(self, input_ids, pos=None, features_only=False):
        """``features_only=True``: pre-head hidden states — see
        :class:`horovod_tpu.models.gpt.GPT` and
        :func:`horovod_tpu.optim.next_token_xent_chunked`."""
        c = self.config
        if self.decode and pos is None:
            raise ValueError("decode mode requires pos (the token's "
                             "global position)")
        x = LlamaEmbed(c, name="embed")(input_ids, pos)
        block_cls = (nn.remat(LlamaBlock) if c.remat and not self.decode
                     else LlamaBlock)
        for i in range(c.num_layers):
            x = block_cls(c, decode=self.decode, name=f"layer_{i}")(
                x, pos=pos if self.decode else None)
        if features_only:
            return x
        return LlamaHead(c, name="head")(x)

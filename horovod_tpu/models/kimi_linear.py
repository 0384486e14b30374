"""``kimi_linear``-family decoder-only LM: Kimi Delta Attention (a gated
delta rule with one decay a key channel) in most layers, latent attention
without positions in the rest, a leading dense layer, then sparse layers of
many small SiLU experts scored by sigmoid beside a shared one.

Every layer is pre-norm, ``h <- h + Mixer(RMSNorm(h))``, ``h <- h +
FFN(RMSNorm(h))``. The mixer of layer ``i`` (numbered from 1, as the
configuration's ``linear_attn_config`` numbers them) is
:class:`~horovod_tpu.parallel.kda.KDAMixer` where ``i`` is one of
``kda_layers`` and :class:`~horovod_tpu.parallel.mla.TPLatentAttention`
with no query latent and no rotation otherwise. The feed-forward of the
leading ``dense`` layers is one SwiGLU MLP; behind them every layer is
``sparse``: ``experts_per_token`` of ``num_experts`` SwiGLU experts a token,
chosen by the largest ``sigmoid(x W_r) + b`` and weighed by their sigmoid
scores over the chosen's sum times ``routed_scale``, none dropped
(``parallel/moe.py`` ``DroplessMoE``), beside a shared expert every token
passes. ``b``, the correction bias, is a constant of the configuration
(``selection_bias``; zero by default) and nothing here updates it.

The stack ends in a final RMSNorm and an untied float32 head.

A model may hold a share of every sparse layer's experts (``experts_held``
contiguous experts from ``first_expert_held`` on): the router keeps its
full width, each layer computes its own experts' part of the sum plus the
shared expert, and that partial sum goes on; the holders of the other
shares complete the sum over their exchange, which a single chip does not
have.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.parallel.kda import RULE_OUTPUT, KDAMixer
from horovod_tpu.parallel.mla import TPLatentAttention
from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.tp import TPSwiGLUMlp
from horovod_tpu.trace.scopes import scope

MIXER_KINDS = ("kda", "mla")
KEEP_RULE_OUTPUT = jax.checkpoint_policies.save_only_these_names(
    RULE_OUTPUT)
FFN_KINDS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_heads: int = 32                 # latent attention's heads
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_gate_rank: int = 128            # the two low-rank gate paths
    conv_kernel: int = 4
    dense_size: int = 9216              # a dense layer's feed-forward
    expert_size: int = 1024             # an expert's width
    shared_experts: int = 1             # the shared expert's, in experts
    num_experts: int = 256              # the router's width
    experts_per_token: int = 8
    routed_scale: float = 2.446
    experts_held: Optional[int] = None  # None -> all of them
    first_expert_held: int = 0
    # b, one float a routed expert; None -> zero, as a fresh model has it
    selection_bias: Optional[Tuple[float, ...]] = None
    num_layers: int = 27
    num_dense_layers: int = 1           # first_k_dense_replace
    # layers (from 1) whose mixer is KDA; linear_attn_config's kda_layers
    kda_layers: Tuple[int, ...] = tuple(
        i for i in range(1, 28) if i % 4 and i != 27)
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    use_flash: bool = False         # Pallas flash attention (ops/pallas)

    @property
    def mixers(self):
        return tuple("kda" if i + 1 in self.kda_layers else "mla"
                     for i in range(self.num_layers))

    @property
    def ffns(self):
        return tuple("dense" if i < self.num_dense_layers else "sparse"
                     for i in range(self.num_layers))

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs: one period of three KDA layers and one
        latent-attention layer behind a dense layer, KDA heads of 16 (the
        ``jax.numpy`` form of the rule), query and key heads wider than the
        value heads, 16 experts, 2 a token."""
        base = dict(vocab_size=256, hidden_size=64, num_heads=4,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, kda_heads=4,
                    kda_head_dim=16, kda_gate_rank=16,
                    dense_size=192, expert_size=32, num_experts=16,
                    experts_per_token=2, num_layers=5,
                    kda_layers=(1, 2, 3, 5))
        base.update(kw)
        return KimiLinearConfig(**base)


class KimiLinearBlock(nn.Module):
    """One pre-norm layer: a ``mixer`` (kda | mla) and an ``ffn`` (dense |
    sparse). Shape-invariant."""
    config: KimiLinearConfig
    mixer: str
    ffn: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.mixer not in MIXER_KINDS or self.ffn not in FFN_KINDS:
            raise ValueError(f"unknown kind of layer {self.mixer!r} / "
                             f"{self.ffn!r}; choose from {MIXER_KINDS} and "
                             f"{FFN_KINDS}")

        def norm(name):
            return nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name=name)

        with scope("block.norm"):
            h = norm("input_norm")(x)
        if self.mixer == "kda":
            # The backward pass computes the mixer again from its input
            # but for the delta rule's output, which it keeps: a layer's
            # projections, convolution and gates are some 1.5 GB at 2 x
            # 8192, the kept output 0.13 GB.
            x = x + nn.remat(KDAMixer, policy=KEEP_RULE_OUTPUT)(
                c.hidden_size, c.kda_heads, c.kda_head_dim, c.kda_gate_rank,
                conv_kernel=c.conv_kernel,
                norm_eps=c.rms_eps, dtype=c.dtype, axis_name=None,
                name="kda")(h)
        else:
            with scope("attn.full"):
                x = x + TPLatentAttention(
                    c.num_heads, c.hidden_size, None, c.kv_lora_rank,
                    c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                    0.0, rms_eps=c.rms_eps, dtype=c.dtype, axis_name=None,
                    use_flash=c.use_flash, rope=False, name="attention")(h)
        with scope("block.norm"):
            m = norm("post_attn_norm")(x)
        if self.ffn == "dense":
            with scope("mlp.dense"):
                return x + TPSwiGLUMlp(c.dense_size, c.hidden_size,
                                       dtype=c.dtype, axis_name=None,
                                       name="mlp")(m)
        bias = jnp.zeros((c.num_experts,), jnp.float32) \
            if c.selection_bias is None \
            else jnp.asarray(c.selection_bias, jnp.float32)
        # The backward pass computes the routed experts again, as the other
        # sparse models do.
        f = nn.remat(DroplessMoE)(
            c.num_experts, c.experts_per_token, c.hidden_size, c.expert_size,
            experts_held=c.experts_held, first_expert=c.first_expert_held,
            dtype=c.dtype, weighting="sigmoid", weight_scale=c.routed_scale,
            expert_form="gated_silu", name="moe")(m, None, bias)
        with scope("moe.shared"):
            f = f + TPSwiGLUMlp(c.expert_size * c.shared_experts,
                                c.hidden_size, dtype=c.dtype, axis_name=None,
                                name="shared")(m)
        return x + f


class KimiLinear(nn.Module):
    """Full model: token embed -> blocks by kind -> RMSNorm -> fp32 head.
    Returns the logits."""
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        with scope("lm.model"):
            with scope("lm.embed"):
                x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                             name="embed")(input_ids)
            for i, (mixer, ffn) in enumerate(zip(c.mixers, c.ffns)):
                x = KimiLinearBlock(c, mixer, ffn, name=f"layer_{i}")(x)
            with scope("lm.head"):
                x = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype,
                               name="ln_f")(x)
                return nn.Dense(c.vocab_size, use_bias=False,
                                dtype=jnp.float32, name="lm_head")(x)

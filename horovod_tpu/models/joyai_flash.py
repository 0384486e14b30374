"""``joyai_llm_flash``-family decoder-only LM (DeepSeek-V3's form): latent
attention, a leading dense layer, then sparse layers of many small SiLU
experts scored by sigmoid beside a shared one, and a multi-token-prediction
module.

Every layer is pre-norm, ``h <- h + MLA(RMSNorm(h))``, ``h <- h +
FFN(RMSNorm(h))``. Attention is :class:`~horovod_tpu.parallel.mla.
TPLatentAttention`. The feed-forward of the leading ``dense`` layers is one
SwiGLU MLP; behind them every layer is ``sparse``: ``experts_per_token`` of
``num_experts`` SwiGLU experts a token, chosen by the largest ``sigmoid(x
W_r) + b`` and weighed by their sigmoid scores over the chosen's sum times
``routed_scale``, none dropped (``parallel/moe.py`` ``DroplessMoE``), beside
a shared expert every token passes. ``b``, the correction bias a training
recipe moves against each expert's load, is an input of the layer and no
parameter: this model hands it a constant (``selection_bias``; zero by
default, as a fresh model has it), and nothing here updates it.

The main stack ends in a final RMSNorm and an untied float32 head. The
multi-token-prediction module (one depth) predicts the token after next:

    h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)]

with ``h_i`` the main stack's final normed output, then one more sparse
layer, its own RMSNorm and the SAME head. It runs over every position: the
id after the last is the first id again (a filler); by causality that
touches the last position alone, whose two targets a loss leaves out. The
model returns both sets of logits.

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
import jax.numpy as jnp

from horovod_tpu.parallel.mla import TPLatentAttention
from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.tp import TPSwiGLUMlp
from horovod_tpu.trace.scopes import scope

FFN_KINDS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_size: int = 7168              # a dense layer's feed-forward
    expert_size: int = 768              # an expert's width
    shared_experts: int = 1             # the shared expert's, in experts
    num_experts: int = 256              # the router's width
    experts_per_token: int = 8
    routed_scale: float = 2.5
    experts_held: Optional[int] = None  # None -> all of them
    first_expert_held: int = 0
    # b, one float a routed expert; None -> zero, as a fresh model has it
    selection_bias: Optional[Tuple[float, ...]] = None
    num_layers: int = 40
    num_dense_layers: int = 1           # first_k_dense_replace
    rope_theta: float = 3.2e7
    rms_eps: float = 1e-6
    dtype: Any = jnp.float32
    use_flash: bool = False         # Pallas flash attention (ops/pallas)

    @property
    def kinds(self):
        return tuple("dense" if i < self.num_dense_layers else "sparse"
                     for i in range(self.num_layers))

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs: query and key heads wider than the value
        heads, both latents narrower than the model, one dense layer and
        two sparse ones, 16 experts, 2 a token."""
        base = dict(vocab_size=256, hidden_size=64, num_heads=4,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, dense_size=192,
                    expert_size=32, num_experts=16, experts_per_token=2,
                    num_layers=3, rope_theta=1e4)
        base.update(kw)
        return JoyAIFlashConfig(**base)


class JoyAIFlashBlock(nn.Module):
    """One pre-norm layer of ``kind`` (dense | sparse). Shape-invariant."""
    config: JoyAIFlashConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.kind not in FFN_KINDS:
            raise ValueError(f"unknown kind of layer {self.kind!r}; choose "
                             f"from {FFN_KINDS}")

        def norm(name):
            return nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name=name)

        with scope("block.norm"):
            h = norm("input_norm")(x)
        with scope("attn.full"):
            x = x + TPLatentAttention(
                c.num_heads, c.hidden_size, c.q_lora_rank, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                c.rope_theta, rms_eps=c.rms_eps, dtype=c.dtype,
                axis_name=None, use_flash=c.use_flash, name="attention")(h)
        with scope("block.norm"):
            m = norm("post_attn_norm")(x)
        if self.kind == "dense":
            with scope("mlp.dense"):
                return x + TPSwiGLUMlp(c.dense_size, c.hidden_size,
                                       dtype=c.dtype, axis_name=None,
                                       name="mlp")(m)
        # The correction bias: a constant, zero as a fresh model has it
        # unless the configuration states one; no parameter and no leaf of
        # the state (the module's docstring).
        bias = jnp.zeros((c.num_experts,), jnp.float32) \
            if c.selection_bias is None \
            else jnp.asarray(c.selection_bias, jnp.float32)
        # The backward pass computes the routed experts again, as the other
        # sparse models do: their buffers are most of the layer's saved
        # bytes and little of its time.
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


class JoyAIFlashHead(nn.Module):
    """The final RMSNorm and the float32 head (bias-free, untied), the
    head's product apart so that the MTP module shares it."""
    config: JoyAIFlashConfig

    def setup(self):
        c = self.config
        self.ln_f = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype)
        self.lm_head = nn.Dense(c.vocab_size, use_bias=False,
                                dtype=jnp.float32)

    def __call__(self, x):
        return self.ln_f(x)

    def logits(self, x):
        return self.lm_head(x)


class JoyAIFlashMTP(nn.Module):
    """One depth of multi-token prediction (module docstring) up to the
    head: ``W_eh [RMSNorm_e(e_next) | RMSNorm_h(h)]``, a sparse layer, its
    own RMSNorm."""
    config: JoyAIFlashConfig

    @nn.compact
    def __call__(self, e_next, h):
        c = self.config

        def norm(name):
            return nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name=name)

        with scope("block.norm"):
            z = jnp.concatenate([norm("enorm")(e_next), norm("hnorm")(h)],
                                -1)
        z = nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype,
                     name="eh_proj")(z)
        z = JoyAIFlashBlock(c, "sparse", name="block")(z)
        with scope("block.norm"):
            return norm("norm")(z)


class JoyAIFlash(nn.Module):
    """Full model: token embed -> blocks by kind -> RMSNorm -> fp32 head,
    and the MTP module on the normed output. Returns ``(logits,
    mtp_logits)``."""
    config: JoyAIFlashConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        with scope("lm.model"):
            with scope("lm.embed"):
                e = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                             name="embed")(input_ids)
            x = e
            for i, kind in enumerate(c.kinds):
                x = JoyAIFlashBlock(c, kind, name=f"layer_{i}")(x)
            head = JoyAIFlashHead(c, name="head")
            with scope("lm.head"):
                h = head(x)
                logits = head.logits(h)
            with scope("mtp"):
                # Emb(t_{i+1}); the last position's is the first id's.
                z = JoyAIFlashMTP(c, name="mtp")(jnp.roll(e, -1, axis=1), h)
                with scope("lm.head"):
                    return logits, head.logits(z)

"""``afmoe``-family decoder-only LM: gated, QK-normed grouped-query
attention, window and full layers in one stack, four norms a layer, dense
layers leading a stack of sparse ones.

The lineage beside :class:`SmallThinker` and :class:`NemotronH`, named for
the source's ``model_type``. A layer is a *sandwich*: every sub-layer has a
norm before it and one behind it,

    h <- h + RMSNorm(attn(RMSNorm(h)));  h <- h + RMSNorm(ffn(RMSNorm(h))).

Attention norms every query and key head (RMS over the head, one learned
scale for the query heads and one for the key heads) before the rotation,
and multiplies the heads' output by the sigmoid of a fourth projection of
the layer's normed input before the output product. ``window`` layers carry
RoPE and a sliding window, ``full`` layers attend causally over everything
with no positional encoding. The feed-forward of the leading ``dense``
layers is one SwiGLU MLP; behind them every layer is ``sparse``: many small
SwiGLU experts scored by sigmoid, ``experts_per_token`` a token, none
dropped (``parallel/moe.py`` ``DroplessMoE``), beside a shared expert that
every token passes. The chosen experts are those of the largest ``score +
b``; the weights come from the scores alone. ``b``, the balancing bias a
training recipe moves against each expert's load, is an input of the layer
and no parameter: this model hands it a constant (``selection_bias`` of the
configuration; zero by default, as a fresh model has it), and nothing here
updates it. The embedding is scaled by
``sqrt(hidden_size)``; untied float32 head, no biases.

A model may hold a share of every sparse layer's experts (``experts_held``
contiguous experts from ``first_expert_held`` on): the router keeps its
full width, each layer computes its own experts' part of the sum plus the
shared expert, and that partial sum goes through the norm behind it and on;
the holders of the other shares complete the sum over their exchange,
before the norm, which a single chip does not have.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.tp import TPSelfAttention, TPSwiGLUMlp
from horovod_tpu.trace.scopes import scope

FFN_KINDS = ("dense", "sparse")
ATTENTION_KINDS = {"sliding_attention": "window", "full_attention": "full"}
LAYER_TYPES = ("sliding_attention",) * 3 + ("full_attention",)


def layer_kinds(layer_types, num_dense_layers, layers_held=None):
    """(feed-forward, attention) of every layer held: ``layers_held`` are
    published indices into ``layer_types`` (all of them by default), the
    first ``num_dense_layers`` of those held are ``dense``, the rest
    ``sparse``; ``sliding_attention`` is a ``window`` layer,
    ``full_attention`` a ``full`` one."""
    held = range(len(layer_types)) if layers_held is None else layers_held
    kinds = []
    for at, i in enumerate(held):
        if not 0 <= i < len(layer_types) \
                or layer_types[i] not in ATTENTION_KINDS:
            raise ValueError(f"layer {i}: no kind of attention among "
                             f"{len(layer_types)} layer_types of "
                             f"{tuple(ATTENTION_KINDS)}")
        kinds.append(("dense" if at < num_dense_layers else "sparse",
                      ATTENTION_KINDS[layer_types[i]]))
    return tuple(kinds)


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    dense_size: int = 6144              # a dense layer's feed-forward
    expert_size: int = 1024             # an expert's width
    shared_experts: int = 1             # the shared expert's, in experts
    num_experts: int = 128              # the router's width
    experts_per_token: int = 8
    routed_scale: float = 2.826
    experts_held: Optional[int] = None  # None -> all of them
    first_expert_held: int = 0
    # b, one float a routed expert; None -> zero, as a fresh model has it
    selection_bias: Optional[Tuple[float, ...]] = None
    # one (dense | sparse, window | full) a layer
    kinds: Tuple[Tuple[str, str], ...] = layer_kinds(LAYER_TYPES * 8, 2)
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    use_flash: bool = False         # Pallas flash attention (ops/pallas)

    @property
    def num_layers(self):
        return len(self.kinds)

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs, in the published ratios: one dense layer
        and a period of sparse ones, 8:1 grouped heads twice as wide
        together as the model, 16 experts, 2 a token, a dense layer three
        times and an expert half as wide as the model, a window shorter
        than the sequences the tests use."""
        base = dict(vocab_size=256, hidden_size=64, num_heads=8,
                    num_kv_heads=1, head_dim=16, dense_size=192,
                    expert_size=32, num_experts=16, experts_per_token=2,
                    kinds=layer_kinds(LAYER_TYPES * 2, 1, (0, 2, 3, 4, 5)),
                    sliding_window=16)
        base.update(kw)
        return AfmoeConfig(**base)


class AfmoeBlock(nn.Module):
    """One sandwich layer of ``kind`` = (feed-forward, attention).
    Shape-invariant."""
    config: AfmoeConfig
    kind: Tuple[str, str]

    @nn.compact
    def __call__(self, x):
        c = self.config
        ffn, attention = self.kind
        if ffn not in FFN_KINDS \
                or attention not in ATTENTION_KINDS.values():
            raise ValueError(
                f"unknown kind of layer {self.kind!r}; choose from "
                f"{FFN_KINDS} x {tuple(ATTENTION_KINDS.values())}")

        def norm(name):
            return nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name=name)

        windowed = attention == "window"
        with scope("attn.window" if windowed else "attn.full"):
            with scope("block.norm"):
                h = norm("input_norm")(x)
            a = TPSelfAttention(
                c.num_heads, c.hidden_size, dtype=c.dtype, axis_name=None,
                causal=True, use_flash=c.use_flash,
                num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
                rope_theta=c.rope_theta if windowed else None,
                window=c.sliding_window if windowed else None,
                use_bias=False, qk_norm_eps=c.rms_eps, gated=True,
                name="attention")(h)
        with scope("block.post_norm"):
            x = x + norm("post_attn_norm")(a)
        with scope("block.norm"):
            m = norm("pre_ffn_norm")(x)
        if ffn == "dense":
            with scope("mlp.dense"):
                f = TPSwiGLUMlp(c.dense_size, c.hidden_size, dtype=c.dtype,
                                axis_name=None, name="mlp")(m)
        else:
            # The balancing bias: a constant, zero as a fresh model has it
            # unless the configuration states one; no parameter and no
            # leaf of the state (the module's docstring).
            bias = jnp.zeros((c.num_experts,), jnp.float32) \
                if c.selection_bias is None \
                else jnp.asarray(c.selection_bias, jnp.float32)
            # The backward pass computes the routed experts again, as the
            # other two sparse models do: their buffers are most of the
            # layer's saved bytes and little of its time.
            f = nn.remat(DroplessMoE)(
                c.num_experts, c.experts_per_token, c.hidden_size,
                c.expert_size, experts_held=c.experts_held,
                first_expert=c.first_expert_held, dtype=c.dtype,
                weighting="sigmoid", weight_scale=c.routed_scale,
                expert_form="gated_silu", name="moe")(m, None, bias)
            with scope("moe.shared"):
                f = f + TPSwiGLUMlp(
                    c.expert_size * c.shared_experts, c.hidden_size,
                    dtype=c.dtype, axis_name=None, name="shared")(m)
        with scope("block.post_norm"):
            return x + norm("post_ffn_norm")(f)


class AfmoeEmbed(nn.Module):
    """Token embedding times ``sqrt(hidden_size)``; positions enter via
    RoPE on ``window`` layers."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        return nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name="tok_emb")(input_ids) \
            * jnp.asarray(math.sqrt(c.hidden_size), c.dtype)


class AfmoeHead(nn.Module):
    """Final RMSNorm + fp32 LM head (bias-free, untied)."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


class Afmoe(nn.Module):
    """Full model: scaled token embed -> blocks by kind -> RMSNorm -> fp32
    head."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        with scope("lm.model"):
            with scope("lm.embed"):
                x = AfmoeEmbed(c, name="embed")(input_ids)
            for i, kind in enumerate(c.kinds):
                x = AfmoeBlock(c, kind, name=f"layer_{i}")(x)
            with scope("lm.head"):
                return AfmoeHead(c, name="head")(x)

"""SmallThinker-family decoder-only LM: every layer sparse, window and full
attention in one stack.

The 2025 fine-grained-mixture lineage beside :class:`GPT` and
:class:`Llama`: pre-RMSNorm blocks whose feed-forward is a mixture of many
small gated experts (64 of width 768, 6 a token, ReLU gate) routed with no
token dropped (``parallel/moe.py`` ``DroplessMoE``), a router that reads
the block's INPUT (before the norm and before attention), grouped-query
attention with a stated head size (28 heads of 128 at hidden 2560), and two
kinds of layer laid in a published pattern: ``full`` layers attend
causally over everything with no positional encoding, ``window`` layers
carry RoPE and a sliding window (the flash kernels skip the tiles behind
it). Untied head, no biases, no auxiliary loss.

A model may hold a share of every layer's experts (``experts_held``
contiguous experts from ``first_expert_held`` on): the router keeps its
full width, each layer computes its own experts' part of the sum and that
partial sum goes on to the next layer; the holders of the other shares
complete it over their exchange, which a single chip does not have.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.tp import TPSelfAttention
from horovod_tpu.trace.scopes import scope

KINDS = ("full", "window")


def layer_kinds(rope_layout, sliding_window_layout, num_layers=None):
    """The kinds of the first ``num_layers`` layers from the published
    per-layer flags: 1 in both lists is a ``window`` layer (RoPE and the
    sliding window), 0 in both a ``full`` one; they agree on every layer of
    the published models."""
    n = len(rope_layout) if num_layers is None else num_layers
    kinds = []
    for i, (rope, window) in enumerate(
            zip(rope_layout[:n], sliding_window_layout[:n])):
        if bool(rope) != bool(window):
            raise ValueError(f"layer {i}: rope_layout {rope} and "
                             f"sliding_window_layout {window} differ")
        kinds.append("window" if window else "full")
    if len(kinds) != n:
        raise ValueError(f"the layouts give {len(kinds)} layers, not {n}")
    return tuple(kinds)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_size: int = 768              # an expert's width
    num_experts: int = 64               # the router's width
    experts_per_token: int = 6
    experts_held: Optional[int] = None  # None -> all of them
    first_expert_held: int = 0
    # one entry a layer: "full" | "window"
    kinds: Tuple[str, ...] = ("full", "window", "window", "window") * 13
    sliding_window: int = 4096
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    dtype: Any = jnp.float32
    use_flash: bool = False         # Pallas flash attention (ops/pallas)

    @property
    def num_layers(self):
        return len(self.kinds)

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs, in the published ratios: 8 experts, 2 a
        token, one period of the pattern, a window shorter than the
        sequences the tests use."""
        base = dict(vocab_size=256, hidden_size=64, num_heads=4,
                    num_kv_heads=2, head_dim=16, expert_size=32,
                    num_experts=8, experts_per_token=2,
                    kinds=("full", "window", "window", "window"),
                    sliding_window=16)
        base.update(kw)
        return SmallThinkerConfig(**base)


class SmallThinkerBlock(nn.Module):
    """Pre-RMSNorm block of one ``kind``: GQA attention (RoPE and the
    window on ``window`` layers, neither on ``full`` ones), then the
    experts held here on the tokens the router, reading the block's input,
    sends them. Shape-invariant."""
    config: SmallThinkerConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.kind not in KINDS:
            raise ValueError(f"unknown kind of layer {self.kind!r}; "
                             f"choose from {KINDS}")
        windowed = self.kind == "window"
        with scope("block.norm"):
            h = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype,
                           name="ln_attn")(x)
        with scope("attn.window" if windowed else "attn.full"):
            a = TPSelfAttention(
                c.num_heads, c.hidden_size, dtype=c.dtype, axis_name=None,
                causal=True, use_flash=c.use_flash,
                num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
                rope_theta=c.rope_theta if windowed else None,
                window=c.sliding_window if windowed else None,
                use_bias=False, name="attention")(h)
        a = x + a
        with scope("block.norm"):
            h = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype,
                           name="ln_mlp")(a)
        # The backward pass computes the expert layer again: its T x top_k
        # rows are most of a block's saved bytes and little of its time.
        y = nn.remat(DroplessMoE)(
            c.num_experts, c.experts_per_token, c.hidden_size, c.expert_size,
            experts_held=c.experts_held, first_expert=c.first_expert_held,
            dtype=c.dtype, name="moe")(h, x)
        return a + y


class SmallThinkerEmbed(nn.Module):
    """Token embedding only: positions enter via RoPE on ``window``
    layers."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        return nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name="tok_emb")(input_ids)


class SmallThinkerHead(nn.Module):
    """Final RMSNorm + fp32 LM head (bias-free, untied)."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = nn.RMSNorm(epsilon=c.rms_eps, dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


class SmallThinker(nn.Module):
    """Full model: token embed -> blocks by kind -> RMSNorm -> fp32 head."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        with scope("lm.model"):
            with scope("lm.embed"):
                x = SmallThinkerEmbed(c, name="embed")(input_ids)
            for i, kind in enumerate(c.kinds):
                x = SmallThinkerBlock(c, kind, name=f"layer_{i}")(x)
            with scope("lm.head"):
                return SmallThinkerHead(c, name="head")(x)

"""Nemotron-H-family decoder-only LM: Mamba-2, expert and attention layers
in one stack, one sub-layer a layer.

The hybrid lineage beside :class:`GPT`, :class:`Llama` and
:class:`SmallThinker`: a layer is ONE mixer behind one RMSNorm,
``x + mixer(norm(x))``, and a published string lays the kinds
(``hybrid_override_pattern``): ``M`` a Mamba-2 mixer
(``parallel/ssm.py``), ``E`` a mixture of many small ``relu^2`` experts
scored by sigmoid, none dropped (``parallel/moe.py`` ``DroplessMoE``),
beside a shared expert that every token passes, ``*`` grouped-query
attention, causal, with no positional encoding (positions enter through
the state-space layers alone). Untied float32 head, no biases but the
convolution's.

A model may hold a share of every expert layer's experts
(``experts_held`` contiguous experts from ``first_expert_held`` on): the
router keeps its full width, each layer computes its own experts' part of
the sum plus the shared expert, and that partial sum goes on; the holders
of the other shares complete it over their exchange, which a single chip
does not have. What the family's TwoTower checkpoints add to this tower (a
second, denoising tower conditioned on it, and a loss by diffusion over
blocks) is not here: this is the tower ``model_type: nemotron_h`` declares,
trained causally on the next token.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.ssm import Mamba2Mixer
from horovod_tpu.parallel.tp import TPSelfAttention
from horovod_tpu.trace.scopes import scope

KINDS = ("M", "E", "*")     # Mamba-2 mixer | experts | attention
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def layer_kinds(pattern, num_layers=None):
    """The kinds of the first ``num_layers`` layers, one letter of
    ``pattern`` each (all of them by default)."""
    n = len(pattern) if num_layers is None else num_layers
    if not 0 < n <= len(pattern):
        raise ValueError(f"the pattern gives {len(pattern)} layers, "
                         f"not {n}")
    for i, kind in enumerate(pattern[:n]):
        if kind not in KINDS:
            raise ValueError(f"layer {i}: unknown kind of layer {kind!r}; "
                             f"choose from {KINDS}")
    return tuple(pattern[:n])


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = PATTERN              # one letter a layer: M | E | *
    num_layers: Optional[int] = None    # None -> the whole pattern
    # M: the Mamba-2 mixer
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    state_size: int = 128
    state_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3         # a fresh head's step, log-uniform
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    # E: routed experts beside a shared one
    num_experts: int = 128              # the router's width
    experts_per_token: int = 6
    expert_size: int = 1856
    shared_expert_size: int = 3712
    routed_scale: float = 2.5
    experts_held: Optional[int] = None  # None -> all of them
    first_expert_held: int = 0
    # *: attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    norm_eps: float = 1e-5              # the layers' and the final norm
    gate_norm_eps: float = 1e-5         # the mixer's gated group norm
    dtype: Any = jnp.float32
    use_flash: bool = False         # Pallas flash attention (ops/pallas)

    @property
    def kinds(self):
        return layer_kinds(self.pattern, self.num_layers)

    @staticmethod
    def tiny(**kw):
        """For tests / dry runs, in the published ratios: the first nine
        letters of the pattern (4 : 4 : 1), 16 experts, 2 a token, 8 heads
        in 2 groups, chunks shorter than the sequences the tests use."""
        base = dict(vocab_size=256, hidden_size=64, num_layers=9,
                    mamba_heads=8, mamba_head_dim=8, state_size=16,
                    state_groups=2, chunk_size=16, num_experts=16,
                    experts_per_token=2, expert_size=32,
                    shared_expert_size=64, num_heads=4, num_kv_heads=2,
                    head_dim=16)
        base.update(kw)
        return NemotronHConfig(**base)


class SharedExpert(nn.Module):
    """The expert every token passes, weight 1: ``relu(x W_up)^2 W_down``
    at its own width, two dense products."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        h = nn.Dense(c.shared_expert_size, use_bias=False, dtype=c.dtype,
                     name="up")(x)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype,
                        name="down")(jnp.square(nn.relu(h)))


class NemotronHBlock(nn.Module):
    """``x + mixer(RMSNorm(x))`` with the mixer of one ``kind`` (a letter
    of the pattern). Shape-invariant."""
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        if self.kind not in KINDS:
            raise ValueError(f"unknown kind of layer {self.kind!r}; "
                             f"choose from {KINDS}")
        with scope("block.norm"):
            h = nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype,
                           name="norm")(x)
        if self.kind == "M":
            return x + Mamba2Mixer(
                c.hidden_size, c.mamba_heads, c.mamba_head_dim, c.state_size,
                c.state_groups, conv_kernel=c.conv_kernel,
                chunk_size=c.chunk_size, norm_eps=c.gate_norm_eps,
                time_step_min=c.time_step_min,
                time_step_max=c.time_step_max,
                time_step_floor=c.time_step_floor,
                dtype=c.dtype, name="mixer")(h)
        if self.kind == "*":
            with scope("attn.full"):
                return x + TPSelfAttention(
                    c.num_heads, c.hidden_size, dtype=c.dtype,
                    axis_name=None, causal=True, use_flash=c.use_flash,
                    num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
                    rope_theta=None, window=None, use_bias=False,
                    name="attention")(h)
        # The backward pass computes the routed experts again, as
        # SmallThinker's does: their buffers are most of the layer's saved
        # bytes and little of its time.
        routed = nn.remat(DroplessMoE)(
            c.num_experts, c.experts_per_token, c.hidden_size, c.expert_size,
            experts_held=c.experts_held, first_expert=c.first_expert_held,
            dtype=c.dtype, weighting="sigmoid", weight_scale=c.routed_scale,
            expert_form="relu2", name="moe")(h)
        with scope("moe.shared"):
            shared = SharedExpert(c, name="shared")(h)
        return x + routed + shared


class NemotronHEmbed(nn.Module):
    """Token embedding only: no positions are added anywhere."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        return nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name="tok_emb")(input_ids)


class NemotronHHead(nn.Module):
    """Final RMSNorm + fp32 LM head (bias-free, untied)."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = nn.RMSNorm(epsilon=c.norm_eps, dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


class NemotronH(nn.Module):
    """Full model: token embed -> blocks by kind -> RMSNorm -> fp32 head."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        c = self.config
        with scope("lm.model"):
            with scope("lm.embed"):
                x = NemotronHEmbed(c, name="embed")(input_ids)
            for i, kind in enumerate(c.kinds):
                x = NemotronHBlock(c, kind, name=f"layer_{i}")(x)
            with scope("lm.head"):
                return NemotronHHead(c, name="head")(x)

"""``toy_rms_alternating``: the worked example of a third architecture
(``benchmark/README.md``), and what ``tests/test_archs.py`` lays over a copy
of ``benchmark/`` to show that one enters as new files only. No public
model; not part of the benchmark.

RMS norm, no bias anywhere, no positions. Layers of two kinds alternate:
``full`` (even layers: causal attention over every earlier position,
``num_attention_heads`` heads) and ``window`` (odd layers: causal attention
over the last ``sliding_window`` positions, ``swa_num_attention_heads``
heads, each head's output scaled by a learnt ``gate``); both end in a gated
MLP. ``gate`` starts uniform in [0.5, 1.5): neither normal, one nor zero,
so this file states how it is made fresh.
"""

import jax
import jax.numpy as jnp

from benchmark.harness.reference import xent

REHEARSE = {"hidden_size": 64, "num_hidden_layers": 4, "head_dim": 16,
            "num_attention_heads": 4, "swa_num_attention_heads": 2,
            "intermediate_size": 96, "sliding_window": 16, "vocab_size": 500}
RMS_EPS = 1e-6


def heads_of(cfg, kind):
    return cfg["num_attention_heads" if kind == "full"
               else "swa_num_attention_heads"]


def kind_of_layer(i):
    return "full" if i % 2 == 0 else "window"


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    v = cfg["assumed"]["vocab_rows"]
    norm = {"scale": (h,)}
    tree = {"tok_emb": {"embedding": (v, h)}, "norm_f": norm,
            "lm_head": {"kernel": (h, v)}}
    for i in range(cfg["num_hidden_layers"]):
        kind = kind_of_layer(i)
        n = heads_of(cfg, kind)
        layer = {"norm_attn": norm, "norm_mlp": norm,
                 "wq": (h, n * d), "wk": (h, n * d), "wv": (h, n * d),
                 "wo": (n * d, h),
                 "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
        if kind == "window":
            layer["gate"] = (n,)
        tree[f"layer_{i}"] = layer
    return tree


def fused_parts(cfg):
    return {}


def fresh_leaf(cfg, path, shape):
    if path[-1] == "gate":
        return lambda key: jax.random.uniform(key, shape, jnp.float32,
                                              0.5, 1.5)
    return None


# -- the network -------------------------------------------------------------

def rms(x, p):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + RMS_EPS) * p["scale"]


class Net:
    def __init__(self, cfg, mm):
        self.cfg, self.mm = cfg, mm
        self.layers = cfg["num_hidden_layers"]

    def split(self, params):
        embed = {"tok_emb": params["tok_emb"]}
        head = {k: params[k] for k in ("norm_f", "lm_head")}
        return embed, [params[f"layer_{i}"]
                       for i in range(self.layers)], head

    def join(self, embed, layers, head):
        tree = dict(embed, **head)
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return tree

    def embed(self, p, batch):
        return p["tok_emb"]["embedding"][batch["ids"]]

    def kind_of(self, i):
        return kind_of_layer(i)

    def block(self, kind, p, x):
        mm, d = self.mm, self.cfg["head_dim"]
        b, s, _ = x.shape
        y = rms(x, p["norm_attn"])
        q, k, v = (mm("bsh,hk->bsk", y, p[w]).reshape(b, s, -1, d)
                   for w in ("wq", "wk", "wv"))
        scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        pos = jnp.arange(s)
        keep = pos[None, :] <= pos[:, None]
        if kind == "window":
            keep &= pos[None, :] > pos[:, None] - self.cfg["sliding_window"]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), -1)
        out = mm("bhqk,bkhd->bqhd", probs, v)
        if kind == "window":
            out = out * p["gate"][:, None]
        x = x + mm("bsk,kh->bsh", out.reshape(b, s, -1), p["wo"])
        y = rms(x, p["norm_mlp"])
        y = jax.nn.silu(mm("bsh,hf->bsf", y, p["w_gate"])) \
            * mm("bsh,hf->bsf", y, p["w_up"])
        return x + mm("bsf,fh->bsh", y, p["w_down"])

    def head_loss(self, p, x, batch):
        logits = self.mm("bsh,hv->bsv", rms(x, p["norm_f"]),
                         p["lm_head"]["kernel"])
        losses = xent(logits[:, :-1], batch["ids"][:, 1:])
        return jnp.sum(losses) / losses[0].size


# -- work counts -------------------------------------------------------------

def step_flops(cfg, sequences, seq_len):
    """6 FLOPs a token for every parameter in a matrix product, and the
    attention products of the (query, key) pairs the mask keeps: all
    earlier positions in a ``full`` layer, at most ``sliding_window`` in a
    ``window`` layer. 12 d a pair and head: two products forward, four
    backward."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    matmul = h * cfg["assumed"]["vocab_rows"]
    attention = 0
    for i in range(cfg["num_hidden_layers"]):
        kind = kind_of_layer(i)
        n = heads_of(cfg, kind)
        reach = seq_len if kind == "full" else cfg["sliding_window"]
        pairs = sum(min(q + 1, reach) for q in range(seq_len))
        matmul += 4 * h * n * d + 3 * h * f
        attention += 12 * n * d * pairs
    return 6 * matmul * sequences * seq_len + attention * sequences

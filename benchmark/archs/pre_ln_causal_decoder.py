"""``pre_ln_causal_decoder`` (GPT-2): x + attn(ln(x)), x + mlp(ln(x)), final
norm, untied head, next-token cross entropy over positions 0..S-2.

It follows the program's departures from the published model (tanh gelu,
norm epsilon 1e-6, fused qkv laid out [q | k | v], no dropout), which the
configuration file lists. Sizes under GPT-2's own keys: ``n_embd``,
``n_layer``, ``n_head``, ``n_inner``, ``n_positions``.
"""

import jax.numpy as jnp

from benchmark.harness.reference import attention, gelu, ln, xent

REHEARSE = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
            "n_positions": 64, "vocab_size": 500}


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    hidden = cfg["n_embd"]
    return dict(hidden=hidden, layers=cfg["n_layer"], heads=cfg["n_head"],
                inner=cfg["n_inner"], head_dim=hidden // cfg["n_head"],
                vocab_rows=cfg["assumed"]["vocab_rows"])


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    s = sizes(cfg)
    h, f, v = s["hidden"], s["inner"], s["vocab_rows"]
    norm = {"scale": (h,), "bias": (h,)}

    def dense(i, o):
        return {"kernel": (i, o), "bias": (o,)}

    layer = {
        "ln_attn": norm, "ln_mlp": norm,
        "attention": {
            "qkv": {"shard": dense(h, 3 * h)},
            "out": {"shard": {"kernel": (h, h)}, "bias": (h,)}},
        "mlp": {
            "in": {"shard": dense(h, f)},
            "out": {"shard": {"kernel": (f, h)}, "bias": (h,)}},
    }
    tree = {"embed": {"tok_emb": {"embedding": (v, h)},
                      "pos_emb": (cfg["n_positions"], h)},
            "head": {"ln_f": norm, "lm_head": {"kernel": (h, v)}}}
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = layer
    return tree


def fused_parts(cfg):
    """The qkv projection is [q | k | v]."""
    out = {}
    for i in range(sizes(cfg)["layers"]):
        base = (f"layer_{i}", "attention", "qkv", "shard")
        out[base + ("kernel",)] = out[base + ("bias",)] = 3
    return out


# -- the network -------------------------------------------------------------

class Net:
    """Embed, pre-LN blocks (one kind), head + loss. Each method takes its
    own sub-tree of the parameters."""

    def __init__(self, cfg, mm):
        self.heads, self.mm = sizes(cfg)["heads"], mm
        self.layers = sizes(cfg)["layers"]

    def split(self, params):
        return (params["embed"],
                [params[f"layer_{i}"] for i in range(self.layers)],
                params["head"])

    def join(self, embed, layers, head):
        tree = {"embed": embed, "head": head}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return tree

    def embed(self, p, batch):
        ids = batch["ids"]
        return (p["tok_emb"]["embedding"][ids]
                + p["pos_emb"][:ids.shape[1]][None])

    def kind_of(self, i):
        return "pre_ln"

    def block(self, kind, p, x):
        a = p["attention"]
        x = x + attention(
            self.mm, ln(x, p["ln_attn"]), a["qkv"]["shard"]["kernel"],
            a["qkv"]["shard"]["bias"], a["out"]["shard"]["kernel"],
            a["out"]["bias"], self.heads, causal=True)
        m = p["mlp"]
        y = gelu(self.mm("bsh,hf->bsf", ln(x, p["ln_mlp"]),
                         m["in"]["shard"]["kernel"])
                 + m["in"]["shard"]["bias"])
        return x + self.mm("bsf,fh->bsh", y, m["out"]["shard"]["kernel"]) \
            + m["out"]["bias"]

    def head_loss(self, p, x, batch):
        """Sum of the next-token losses of these rows, and their count."""
        logits = self.mm("bsh,hv->bsv", ln(x, p["ln_f"]),
                         p["lm_head"]["kernel"])
        losses = xent(logits[:, :-1], batch["ids"][:, 1:])
        return jnp.sum(losses) / losses[0].size


# -- work counts: what the algorithm needs, not what a kernel does ----------

def matmul_params(cfg):
    """Parameters that sit in matrix products, all seen once per token:
    block weights and the output head; not embeddings, positions, norms or
    biases."""
    s = sizes(cfg)
    h, f = s["hidden"], s["inner"]
    block = h * 3 * h + h * h + 2 * h * f
    return s["layers"] * block + h * s["vocab_rows"]


def attention_flops_per_token(cfg, seq_len):
    """Forward + backward attention products per token: 12 L s h, halved
    because causal."""
    s = sizes(cfg)
    return 12 * s["layers"] * seq_len * s["hidden"] // 2


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need."""
    tokens = sequences * seq_len
    return (6 * matmul_params(cfg) * tokens
            + attention_flops_per_token(cfg, seq_len) * tokens)


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (all layers),
    for the forward pass (one kernel) and the backward pass (two).

    Forward 4 B H S^2 D (two products); backward 8 B H S^2 D (dV, dP, dQ,
    dK; the scores a kernel computes again are recomputation and are not
    counted); both halved because causal. Bytes: q, k, v, o read or written
    once forward; q, k, v, o, do, dq, dk, dv once backward.
    """
    s = sizes(cfg)
    b, h, d, layers = sequences, s["heads"], s["head_dim"], s["layers"]
    unit = b * h * seq_len * seq_len * d * layers // 2
    tensor = b * h * seq_len * d * bytes_per_element * layers
    return {
        "fwd": {"flops": 4 * unit, "bytes": 4 * tensor},
        "bwd": {"flops": 8 * unit, "bytes": 8 * tensor},
    }

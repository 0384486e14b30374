"""``post_ln_encoder_mlm_nsp`` (BERT): ln(x + attn(x)), ln(x + mlp(x)), MLM
head on every position plus NSP head on the pooled first position.

It follows the program's departures from the published model (tanh gelu,
norm epsilon 1e-6, fused qkv laid out [q | k | v], no dropout), which the
configuration file lists. Sizes under BERT's own keys: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``intermediate_size``,
``max_position_embeddings``, ``type_vocab_size``.
"""

import jax.numpy as jnp

from benchmark.harness.reference import attention, gelu, ln, xent

REHEARSE = {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 128,
            "max_position_embeddings": 64, "vocab_size": 500}


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(hidden=hidden, layers=cfg["num_hidden_layers"], heads=heads,
                inner=cfg["intermediate_size"], head_dim=hidden // heads,
                vocab_rows=cfg["assumed"]["vocab_rows"])


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    s = sizes(cfg)
    h, f, v = s["hidden"], s["inner"], s["vocab_rows"]
    norm = {"scale": (h,), "bias": (h,)}

    def dense(i, o):
        return {"kernel": (i, o), "bias": (o,)}

    layer = {
        "attention": {"qkv": dense(h, 3 * h), "out": dense(h, h)},
        "ln_attn": norm, "mlp_in": dense(h, f), "mlp_out": dense(f, h),
        "ln_mlp": norm,
    }
    bert = {"tok_emb": {"embedding": (v, h)},
            "pos_emb": {"embedding": (cfg["max_position_embeddings"], h)},
            "type_emb": {"embedding": (cfg["type_vocab_size"], h)},
            "ln_emb": norm, "pooler": dense(h, h)}
    for i in range(s["layers"]):
        bert[f"layer_{i}"] = layer
    return {"bert": bert, "mlm_transform": dense(h, h), "mlm_ln": norm,
            "mlm_head": dense(h, v), "nsp_head": dense(h, 2)}


def fused_parts(cfg):
    """The qkv projection is [q | k | v]."""
    out = {}
    for i in range(sizes(cfg)["layers"]):
        base = ("bert", f"layer_{i}", "attention", "qkv")
        out[base + ("kernel",)] = out[base + ("bias",)] = 3
    return out


# -- the network -------------------------------------------------------------

class Net:
    """Embeddings + norm, post-LN blocks (one kind), MLM + NSP heads +
    loss."""

    def __init__(self, cfg, mm):
        self.heads, self.mm = sizes(cfg)["heads"], mm
        self.layers = sizes(cfg)["layers"]

    def split(self, params):
        b = params["bert"]
        embed = {k: b[k] for k in ("tok_emb", "pos_emb", "type_emb",
                                   "ln_emb")}
        head = {k: params[k] for k in ("mlm_transform", "mlm_ln", "mlm_head",
                                       "nsp_head")}
        head["pooler"] = b["pooler"]
        return embed, [b[f"layer_{i}"] for i in range(self.layers)], head

    def join(self, embed, layers, head):
        head = dict(head)
        bert = dict(embed, pooler=head.pop("pooler"))
        bert.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return dict(head, bert=bert)

    def embed(self, p, batch):
        ids = batch["ids"]
        x = (p["tok_emb"]["embedding"][ids]
             + p["pos_emb"]["embedding"][:ids.shape[1]][None]
             + p["type_emb"]["embedding"][0])
        return ln(x, p["ln_emb"])

    def kind_of(self, i):
        return "post_ln"

    def block(self, kind, p, x):
        a = p["attention"]
        x = ln(x + attention(
            self.mm, x, a["qkv"]["kernel"], a["qkv"]["bias"],
            a["out"]["kernel"], a["out"]["bias"], self.heads, causal=False),
            p["ln_attn"])
        y = gelu(self.mm("bsh,hf->bsf", x, p["mlp_in"]["kernel"])
                 + p["mlp_in"]["bias"])
        y = self.mm("bsf,fh->bsh", y, p["mlp_out"]["kernel"]) \
            + p["mlp_out"]["bias"]
        return ln(x + y, p["ln_mlp"])

    def head_loss(self, p, x, batch):
        """Per row: mean MLM loss over its positions plus its NSP loss,
        summed over these rows."""
        pooled = jnp.tanh(self.mm("bh,hk->bk", x[:, 0],
                                  p["pooler"]["kernel"])
                          + p["pooler"]["bias"])
        t = gelu(self.mm("bsh,hk->bsk", x, p["mlm_transform"]["kernel"])
                 + p["mlm_transform"]["bias"])
        mlm = self.mm("bsh,hv->bsv", ln(t, p["mlm_ln"]),
                      p["mlm_head"]["kernel"]) + p["mlm_head"]["bias"]
        nsp = self.mm("bh,hk->bk", pooled, p["nsp_head"]["kernel"]) \
            + p["nsp_head"]["bias"]
        return (jnp.sum(jnp.mean(xent(mlm, batch["mlm"]), -1))
                + jnp.sum(xent(nsp, batch["nsp"])))


# -- work counts: what the algorithm needs, not what a kernel does ----------

def matmul_params(cfg):
    """(per_token, per_sequence): parameters that sit in matrix products.

    Block weights, the MLM transform and the output head per token; the
    pooler and the NSP head see one position a sequence, so they count per
    sequence. Not embeddings, positions, norms or biases.
    """
    s = sizes(cfg)
    h, f = s["hidden"], s["inner"]
    block = h * 3 * h + h * h + 2 * h * f
    per_token = s["layers"] * block + h * s["vocab_rows"] + h * h
    return per_token, h * h + 2 * h


def attention_flops_per_token(cfg, seq_len):
    """Forward + backward attention products per token: 12 L s h."""
    s = sizes(cfg)
    return 12 * s["layers"] * seq_len * s["hidden"]


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need."""
    per_token, per_sequence = matmul_params(cfg)
    tokens = sequences * seq_len
    return (6 * per_token * tokens + 6 * per_sequence * sequences
            + attention_flops_per_token(cfg, seq_len) * tokens)


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (all layers),
    for the forward pass (one kernel) and the backward pass (two).

    Forward 4 B H S^2 D (two products); backward 8 B H S^2 D (dV, dP, dQ,
    dK; the scores a kernel computes again are recomputation and are not
    counted). Bytes: q, k, v, o read or written once forward; q, k, v, o,
    do, dq, dk, dv once backward.
    """
    s = sizes(cfg)
    b, h, d, layers = sequences, s["heads"], s["head_dim"], s["layers"]
    unit = b * h * seq_len * seq_len * d * layers
    tensor = b * h * seq_len * d * bytes_per_element * layers
    return {
        "fwd": {"flops": 4 * unit, "bytes": 4 * tensor},
        "bwd": {"flops": 8 * unit, "bytes": 8 * tensor},
    }

"""Plain float32 reference of both architectures, and its AdamW.

Straightforward ``jax.numpy``: no kernels, no fused buckets, no sharding.
It imports nothing of ``horovod_tpu`` and takes nothing the program made:
it states the names and shapes of the parameters itself (``param_shapes``),
gets their values from the seed (``weights.make_params``) and its batches
from the seed (``traffic.Batches``).

Two architectures, told apart by the configuration file's ``arch``:

- ``pre_ln_causal_decoder`` (GPT-2): x + attn(ln(x)), x + mlp(ln(x)), final
  norm, untied head, next-token cross entropy over positions 0..S-2.
- ``post_ln_encoder_mlm_nsp`` (BERT): ln(x + attn(x)), ln(x + mlp(x)), MLM
  head on every position plus NSP head on the pooled first position.

Both follow the program's departures from the published models (tanh gelu,
norm epsilon 1e-6, fused qkv laid out [q | k | v], no dropout), which the
configuration files list.

So that a step at the timed sizes fits beside nothing else on the chip, a
step is computed in blocks of rows and layer by layer: the forward pass
keeps each layer's input, the backward pass walks the layers in reverse and
recomputes each layer inside its own ``jax.vjp``.

``operands`` sets the precision of every matrix product: ``float32`` (at
``highest``), ``bfloat16`` (what the program states), ``fp8`` (e4m3
operands forward, e5m2 gradients backward, a scale per tensor: the step
below bfloat16 -- the control).
"""

import functools

import jax
import jax.numpy as jnp

from . import flops

LN_EPS = 1e-6


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    """The parameter tree as nested dicts of shape tuples, under the
    program's names."""
    s = flops.sizes(cfg)
    h, f, v = s["hidden"], s["inner"], s["vocab_rows"]
    ln = {"scale": (h,), "bias": (h,)}

    def dense(i, o):
        return {"kernel": (i, o), "bias": (o,)}

    if cfg["arch"] == "pre_ln_causal_decoder":
        layer = {
            "ln_attn": ln, "ln_mlp": ln,
            "attention": {
                "qkv": {"shard": dense(h, 3 * h)},
                "out": {"shard": {"kernel": (h, h)}, "bias": (h,)}},
            "mlp": {
                "in": {"shard": dense(h, f)},
                "out": {"shard": {"kernel": (f, h)}, "bias": (h,)}},
        }
        tree = {"embed": {"tok_emb": {"embedding": (v, h)},
                          "pos_emb": (cfg["n_positions"], h)},
                "head": {"ln_f": ln, "lm_head": {"kernel": (h, v)}}}
        for i in range(s["layers"]):
            tree[f"layer_{i}"] = layer
        return tree
    if cfg["arch"] == "post_ln_encoder_mlm_nsp":
        layer = {
            "attention": {"qkv": dense(h, 3 * h), "out": dense(h, h)},
            "ln_attn": ln, "mlp_in": dense(h, f), "mlp_out": dense(f, h),
            "ln_mlp": ln,
        }
        bert = {"tok_emb": {"embedding": (v, h)},
                "pos_emb": {"embedding": (cfg["max_position_embeddings"], h)},
                "type_emb": {"embedding": (cfg["type_vocab_size"], h)},
                "ln_emb": ln, "pooler": dense(h, h)}
        for i in range(s["layers"]):
            bert[f"layer_{i}"] = layer
        return {"bert": bert, "mlm_transform": dense(h, h), "mlm_ln": ln,
                "mlm_head": dense(h, v), "nsp_head": dense(h, 2)}
    raise ValueError(f"unknown arch {cfg['arch']!r}")


def fused_parts(cfg):
    """Leaves that fuse several matrices: path -> equal parts along the last
    axis. The qkv projection is [q | k | v]."""
    s = flops.sizes(cfg)
    out = {}
    for i in range(s["layers"]):
        if cfg["arch"] == "pre_ln_causal_decoder":
            base = (f"layer_{i}", "attention", "qkv", "shard")
        else:
            base = ("bert", f"layer_{i}", "attention", "qkv")
        out[base + ("kernel",)] = out[base + ("bias",)] = 3
    return out


# -- arithmetic --------------------------------------------------------------

def _quant(x, dtype, top):
    """Round to an 8-bit float type with one scale per tensor (``top`` is
    the type's largest finite value)."""
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    """A product as fp8 training recipes compute it: operands rounded to
    e4m3 on the way forward, the incoming gradient to e5m2 on the way back,
    every tensor with its own scale, accumulation in float32."""
    return _mm_fp8_fwd(spec, a, b)[0]


def _mm_fp8_fwd(spec, a, b):
    qa = _quant(a, jnp.float8_e4m3fn, 448.0)
    qb = _quant(b, jnp.float8_e4m3fn, 448.0)
    return jnp.einsum(spec, qa, qb,
                      precision=jax.lax.Precision.HIGHEST), (qa, qb)


def _mm_fp8_bwd(spec, saved, g):
    product = functools.partial(jnp.einsum, spec,
                                precision=jax.lax.Precision.HIGHEST)
    return jax.vjp(product, *saved)[1](_quant(g, jnp.float8_e5m2, 57344.0))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(operands):
    """The matrix product ``einsum(spec, a, b)`` at one precision."""
    if operands == "float32":
        return functools.partial(jnp.einsum,
                                 precision=jax.lax.Precision.HIGHEST)
    if operands == "bfloat16":
        def mm(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return mm
    if operands == "fp8":
        return _mm_fp8
    raise ValueError(f"unknown operand precision {operands!r}")


def _ln(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(mm, x, w_qkv, b_qkv, w_out, b_out, heads, causal):
    b, s, h = x.shape
    d = h // heads
    qkv = mm("bsh,hk->bsk", x, w_qkv) + b_qkv
    q, k, v = (t.reshape(b, s, heads, d) for t in jnp.split(qkv, 3, -1))
    scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -1e30)
    probs = jax.nn.softmax(scores, -1)
    out = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
    return mm("bsh,hk->bsk", out, w_out) + b_out


def _xent(logits, labels):
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return logz - picked


class _Decoder:
    """GPT-2: embed, pre-LN blocks, head + loss. Each method takes its own
    sub-tree of the parameters."""

    def __init__(self, cfg, mm):
        self.heads, self.mm = flops.sizes(cfg)["heads"], mm
        self.layers = flops.sizes(cfg)["layers"]

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

    def block(self, p, x):
        a = p["attention"]
        x = x + _attention(
            self.mm, _ln(x, p["ln_attn"]), a["qkv"]["shard"]["kernel"],
            a["qkv"]["shard"]["bias"], a["out"]["shard"]["kernel"],
            a["out"]["bias"], self.heads, causal=True)
        m = p["mlp"]
        y = _gelu(self.mm("bsh,hf->bsf", _ln(x, p["ln_mlp"]),
                          m["in"]["shard"]["kernel"])
                  + m["in"]["shard"]["bias"])
        return x + self.mm("bsf,fh->bsh", y, m["out"]["shard"]["kernel"]) \
            + m["out"]["bias"]

    def head_loss(self, p, x, batch):
        """Sum of the next-token losses of these rows, and their count."""
        logits = self.mm("bsh,hv->bsv", _ln(x, p["ln_f"]),
                         p["lm_head"]["kernel"])
        losses = _xent(logits[:, :-1], batch["ids"][:, 1:])
        return jnp.sum(losses) / losses[0].size


class _Encoder:
    """BERT: embeddings + norm, post-LN blocks, MLM + NSP heads + loss."""

    def __init__(self, cfg, mm):
        self.heads, self.mm = flops.sizes(cfg)["heads"], mm
        self.layers = flops.sizes(cfg)["layers"]

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
        return _ln(x, p["ln_emb"])

    def block(self, p, x):
        a = p["attention"]
        x = _ln(x + _attention(
            self.mm, x, a["qkv"]["kernel"], a["qkv"]["bias"],
            a["out"]["kernel"], a["out"]["bias"], self.heads, causal=False),
            p["ln_attn"])
        y = _gelu(self.mm("bsh,hf->bsf", x, p["mlp_in"]["kernel"])
                  + p["mlp_in"]["bias"])
        y = self.mm("bsf,fh->bsh", y, p["mlp_out"]["kernel"]) \
            + p["mlp_out"]["bias"]
        return _ln(x + y, p["ln_mlp"])

    def head_loss(self, p, x, batch):
        """Per row: mean MLM loss over its positions plus its NSP loss,
        summed over these rows."""
        pooled = jnp.tanh(self.mm("bh,hk->bk", x[:, 0],
                                  p["pooler"]["kernel"])
                          + p["pooler"]["bias"])
        t = _gelu(self.mm("bsh,hk->bsk", x, p["mlm_transform"]["kernel"])
                  + p["mlm_transform"]["bias"])
        mlm = self.mm("bsh,hv->bsv", _ln(t, p["mlm_ln"]),
                      p["mlm_head"]["kernel"]) + p["mlm_head"]["bias"]
        nsp = self.mm("bh,hk->bk", pooled, p["nsp_head"]["kernel"]) \
            + p["nsp_head"]["bias"]
        return (jnp.sum(jnp.mean(_xent(mlm, batch["mlm"]), -1))
                + jnp.sum(_xent(nsp, batch["nsp"])))


_ARCHS = {"pre_ln_causal_decoder": _Decoder,
          "post_ln_encoder_mlm_nsp": _Encoder}


# -- a training step, in blocks of rows and layer by layer -------------------

def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


class Reference:
    """Loss and gradient of the mean loss over a batch's rows, and AdamW."""

    def __init__(self, cfg, operands="float32", rows_per_block=4):
        self.cfg = cfg
        self.net = _ARCHS[cfg["arch"]](cfg, _mm(operands))
        self.rows_per_block = rows_per_block
        net = self.net
        self._embed = jax.jit(net.embed)
        self._block = jax.jit(net.block)
        self._head = jax.jit(jax.value_and_grad(net.head_loss, (0, 1)))

        def block_vjp(p, x, dy):
            return jax.vjp(net.block, p, x)[1](dy)

        def embed_vjp(p, batch, dy):
            return jax.vjp(lambda q: net.embed(q, batch), p)[1](dy)[0]

        self._block_vjp = jax.jit(block_vjp)
        self._embed_vjp = jax.jit(embed_vjp)
        self._adam = jax.jit(self._adam_update)

    def _rows_loss_and_grad(self, parts, batch):
        """Sum over these rows of the per-row loss, and its gradient."""
        embed_p, layer_ps, head_p = parts
        xs = [self._embed(embed_p, batch)]
        for p in layer_ps:
            xs.append(self._block(p, xs[-1]))
        loss, (g_head, dx) = self._head(head_p, xs.pop(), batch)
        g_layers = []
        for p in reversed(layer_ps):
            g, dx = self._block_vjp(p, xs.pop(), dx)
            g_layers.append(g)
        g_embed = self._embed_vjp(embed_p, batch, dx)
        return loss, (g_embed, g_layers[::-1], g_head)

    def loss_and_grad(self, params, batch):
        """Mean loss over the batch's rows and its gradient, as a tree
        named like ``params``."""
        parts = self.net.split(params)
        rows = next(iter(batch.values())).shape[0]
        total, grads = 0.0, None
        for lo in range(0, rows, self.rows_per_block):
            block = {k: jnp.asarray(v[lo:lo + self.rows_per_block])
                     for k, v in batch.items()}
            loss, g = self._rows_loss_and_grad(parts, block)
            total = total + loss
            grads = g if grads is None else _add(grads, g)
        grads = jax.tree.map(lambda g: g / rows, grads)
        return total / rows, self.net.join(*grads)

    def _adam_update(self, params, grads, mu, nu, count):
        a = self.cfg["assumed"]
        b1, b2, eps = a["adam_b1"], a["adam_b2"], a["adam_eps"]
        lr, wd = a["learning_rate"], a["weight_decay"]
        count = count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu, count

    def init_opt(self, params):
        def zeros():
            return jax.tree.map(jnp.zeros_like, params)
        return zeros(), zeros(), jnp.zeros((), jnp.int32)

    def adam(self, params, grads, opt):
        """One AdamW update, sub-tree by sub-tree so that no program holds
        the whole state twice."""
        mu, nu, count = opt
        new_p, new_mu, new_nu = {}, {}, {}
        for k in params:
            new_p[k], new_mu[k], new_nu[k], new_count = self._adam(
                params[k], grads[k], mu[k], nu[k], count)
        return new_p, (new_mu, new_nu, new_count)

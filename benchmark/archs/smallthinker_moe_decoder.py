"""``smallthinker_moe_decoder`` (SmallThinker-21BA3B-Instruct): every layer
is sparse, and one chip holds a share of each layer's experts.

``T`` tokens, ``x`` the block's input, every product without bias:

    r   = x W_r                       W_r: hidden x experts published (64)
    S   = the k largest of r per token (6);  w_e = softmax of r over S
    h   = rmsnorm(x);  q, k, v = h W_q, h W_k, h W_v     (28 / 4 / 4 heads)
    ``window`` layers (rope_layout 1): RoPE (rotate-half) on q and k; query
        t sees keys j with t - window < j <= t
    ``full`` layers (rope_layout 0): no positions; query t sees keys j <= t
    a   = x + attn(q, k, v) W_o
    g   = rmsnorm(a)
    y   = a + sum over e in S held here of
              w_e (relu(g W_gate,e) * (g W_up,e)) W_down,e
    out = rmsnorm(y_last) W_head;  next-token cross entropy over the
          vocabulary rows held, positions 0..S-2; no auxiliary loss

The router reads the block's input before any norm and routes over all the
published experts; the layer computes the part of the sum that the experts
held here give (``moe_num_primary_experts`` of the configuration file, the
contiguous run that starts at ``deployment.first_expert_held``), and that
partial sum is what goes on. Sizes under the source's own keys:
``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``moe_ffn_hidden_size``,
``moe_num_active_primary_experts``, ``moe_num_primary_experts`` (held),
``published.moe_num_primary_experts`` (the router's width),
``num_hidden_layers``, ``rope_layout``, ``sliding_window_layout``,
``sliding_window_size``, ``rope_theta``, ``rms_norm_eps``.

At the timed sizes both 8192-token rows reach ``block`` at once, so
attention is computed by key-value head and by block of queries, each
under ``jax.checkpoint`` with the plain mask over every key and nothing
skipped (the whole square of scores would be 15 GB), and the experts by
block of tokens, a loop over the experts held with masks. The loops are
rolled (``lax.map``, ``lax.scan``): unrolled, a block's two programs took
three minutes each to compile for the chip.

``cfg["planted_fault"]`` (never in a configuration file; set by
``tools/arch_faults.py`` alone) plants one fault of this architecture's own
in the network: ``window_ignored``, ``expert_left_out``, ``rope_left_out``.
"""

import jax
import jax.numpy as jnp

from benchmark.harness.reference import xent

# The rehearsal computes in float32: at its 128 tokens an expert sees 32,
# and bfloat16 rounding then moves an expert leaf's norm by several per cent
# (0.1 % at the cell's 1,536), so that under the cell's limits ``correct``
# would say which seed was drawn and not whether the control flow is right.
REHEARSE = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
            "num_key_value_heads": 2, "moe_ffn_hidden_size": 32,
            "moe_num_primary_experts": 2, "moe_num_active_primary_experts": 2,
            "published": {"moe_num_primary_experts": 8},
            "sliding_window_size": 16, "num_hidden_layers": 4,
            "vocab_size": 500, "assumed": {"compute_dtype": "float32"}}
QUERY_BLOCK = 1024
TOKEN_BLOCK = 4096
FAULTS = ("window_ignored", "expert_left_out", "rope_left_out")


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    return dict(
        hidden=cfg["hidden_size"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        inner=cfg["moe_ffn_hidden_size"],
        experts=cfg["published"]["moe_num_primary_experts"],
        held=cfg["moe_num_primary_experts"],
        first_held=cfg.get("deployment", {}).get("first_expert_held", 0),
        top_k=cfg["moe_num_active_primary_experts"],
        layers=cfg["num_hidden_layers"], window=cfg["sliding_window_size"],
        vocab_rows=cfg["assumed"]["vocab_rows"])


def kind_of_layer(cfg, i):
    """``window`` where the published layouts give layer ``i`` RoPE and the
    sliding window (they agree on every layer), else ``full``."""
    rope, window = cfg["rope_layout"][i], cfg["sliding_window_layout"][i]
    if rope != window:
        raise ValueError(f"layer {i}: rope_layout {rope} and "
                         f"sliding_window_layout {window} differ")
    return "window" if window else "full"


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    s = sizes(cfg)
    h, d, f, v = s["hidden"], s["head_dim"], s["inner"], s["vocab_rows"]
    norm = {"scale": (h,)}
    layer = {
        "ln_attn": norm, "ln_mlp": norm,
        "attention": {
            "qkv": {"shard": {"kernel": (
                h, (s["heads"] + 2 * s["kv_heads"]) * d)}},
            "out": {"shard": {"kernel": (s["heads"] * d, h)}}},
        "moe": {"router": {"kernel": (h, s["experts"])},
                "w_gate_up": (s["held"], h, 2 * f),
                "w_down": (s["held"], f, h)},
    }
    tree = {"embed": {"tok_emb": {"embedding": (v, h)}},
            "head": {"ln_f": norm, "lm_head": {"kernel": (h, v)}}}
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = layer
    return tree


def fused_parts(cfg):
    """The qkv projection is [q | k | v] by heads: equal parts of one
    key-value head's width (7 of q, one of k, one of v at the published
    sizes); the experts' first product is [gate | up]."""
    s = sizes(cfg)
    out = {}
    for i in range(s["layers"]):
        out[(f"layer_{i}", "attention", "qkv", "shard", "kernel")] = \
            s["heads"] // s["kv_heads"] + 2
        out[(f"layer_{i}", "moe", "w_gate_up")] = 2
    return out


def fresh_leaf(cfg, path, shape):
    """Every matrix is drawn by itself, normal(``init_std``) from the key
    folded with the leaf's position, and not cut from one vector of all
    657M values: beside the program's state (7.3 GiB) that vector and its
    slices do not fit the chip when ``check.Norms`` makes the fresh
    parameters again to measure the change from them. Norm scales start at
    one by the shared rule. The embedding's rows start at
    ``assumed.embedding_std`` (the configuration file says why)."""
    if path[-1] == "scale":
        return None
    a = cfg["assumed"]
    std = a.get("embedding_std", a["init_std"]) \
        if path[-1] == "embedding" else a["init_std"]
    return lambda key: jax.random.normal(key, shape, jnp.float32) * std


# -- the network -------------------------------------------------------------

def rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def rope(x, theta):
    """Rotate-half RoPE over the whole head of ``x`` (rows, positions,
    heads, head size), positions 0..S-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Net:
    """Embed, blocks of two kinds (``full``, ``window``), head + loss. Each
    method takes its own sub-tree of the parameters."""

    def __init__(self, cfg, mm):
        self.cfg, self.mm, self.s = cfg, mm, sizes(cfg)
        self.layers = self.s["layers"]
        self.fault = cfg.get("planted_fault")
        if self.fault not in (None,) + FAULTS:
            raise ValueError(f"unknown planted fault {self.fault!r}")

    def split(self, params):
        return (params["embed"],
                [params[f"layer_{i}"] for i in range(self.layers)],
                params["head"])

    def join(self, embed, layers, head):
        """The gradient as a tree named like the parameters, handed back on
        the host: ``Reference.adam`` keeps the old parameters and moments
        beside the new ones until it returns (24 B a parameter, 14.7 GiB of
        the chip's 15.75 at this cut's 656.7M), and a gradient left on the
        device beside them (4 B more) does not fit. ``adam`` and
        ``check.Norms`` take host arrays as they take device arrays, a
        sub-tree at a time."""
        tree = {"embed": embed, "head": head}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return jax.device_get(tree)

    def embed(self, p, batch):
        return p["tok_emb"]["embedding"][batch["ids"]]

    def kind_of(self, i):
        return kind_of_layer(self.cfg, i)

    # attention of one key-value head's group over one block of queries
    def _attend(self, kind, q, k, v, first):
        """``q`` (rows, block, group, d) are the queries from position
        ``first`` on, ``k`` and ``v`` (rows, S, d) every key."""
        scores = self.mm("bqgd,bkd->bgqk", q, k) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        t = first + jnp.arange(q.shape[1])[:, None]
        j = jnp.arange(k.shape[1])[None, :]
        keep = j <= t
        if kind == "window" and self.fault != "window_ignored":
            keep &= j > t - self.s["window"]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), -1)
        return self.mm("bgqk,bkd->bqgd", probs, v)

    def attention(self, kind, p, h):
        s, mm = self.s, self.mm
        b, length, _ = h.shape
        n, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
        qkv = mm("bsh,hk->bsk", h, p["qkv"]["shard"]["kernel"])
        q, k, v = jnp.split(qkv, [n * d, (n + kv) * d], -1)
        q = q.reshape(b, length, n, d)
        k, v = k.reshape(b, length, kv, d), v.reshape(b, length, kv, d)
        if kind == "window" and self.fault != "rope_left_out":
            q = rope(q, self.cfg["rope_theta"])
            k = rope(k, self.cfg["rope_theta"])
        # Rolled loops (one compiled body): over the key-value heads and,
        # inside, over blocks of queries; each block under jax.checkpoint.
        step = min(QUERY_BLOCK, length)
        blocks = length // step
        if blocks * step != length:
            raise ValueError(f"sequence length {length} is no multiple of "
                             f"the query block {step}")
        q = q.reshape(b, blocks, step, kv, n // kv, d).transpose(
            3, 1, 0, 2, 4, 5)                     # (kv, blocks, b, step, g, d)
        attend = jax.checkpoint(self._attend, static_argnums=0)

        def head(qkv_j):
            q_j, k_j, v_j = qkv_j
            return jax.lax.map(
                lambda blk: attend(kind, blk[1], k_j, v_j, blk[0] * step),
                (jnp.arange(blocks), q_j))

        out = jax.lax.map(head, (q, jnp.moveaxis(k, 2, 0),
                                 jnp.moveaxis(v, 2, 0)))
        out = out.transpose(2, 1, 3, 0, 4, 5).reshape(b, length, n * d)
        return mm("bsk,kh->bsh", out, p["out"]["shard"]["kernel"])

    # the experts held here, for one block of tokens
    def _experts(self, p, g, chosen, weights):
        f, first = self.s["inner"], self.s["first_held"]
        held = self.s["held"] - (self.fault == "expert_left_out")

        def add_expert(y, expert):
            e, w_gate_up, w_down = expert
            w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            gate_up = self.mm("th,hf->tf", g, w_gate_up)
            act = jax.nn.relu(gate_up[:, :f]) * gate_up[:, f:]
            return y + w_e[:, None] * self.mm("tf,fh->th", act, w_down), None

        return jax.lax.scan(add_expert, jnp.zeros_like(g), (
            jnp.arange(held), p["w_gate_up"][:held], p["w_down"][:held]))[0]

    def experts(self, p, g, chosen, weights):
        b, length, h = g.shape
        tokens = b * length
        step = min(TOKEN_BLOCK, tokens)
        if tokens % step:
            raise ValueError(f"{tokens} tokens are no multiple of the token "
                             f"block {step}")
        g, chosen, weights = (t.reshape(tokens // step, step, -1)
                              for t in (g, chosen, weights))
        block = jax.checkpoint(self._experts)
        return jax.lax.map(lambda t: block(p, *t),
                           (g, chosen, weights)).reshape(b, length, h)

    def block(self, kind, p, x):
        eps = self.cfg["rms_norm_eps"]
        logits = self.mm("bsh,he->bse", x, p["moe"]["router"]["kernel"])
        top, chosen = jax.lax.top_k(logits, self.s["top_k"])
        weights = jax.nn.softmax(top, -1)
        a = x + self.attention(kind, p["attention"],
                               rms(x, p["ln_attn"], eps))
        return a + self.experts(p["moe"], rms(a, p["ln_mlp"], eps), chosen,
                                weights)

    def _head_block(self, p, x, labels, counted):
        logits = self.mm("bsh,hv->bsv",
                         rms(x, p["ln_f"], self.cfg["rms_norm_eps"]),
                         p["lm_head"]["kernel"])
        return jnp.sum(jnp.where(counted, xent(logits, labels), 0.0))

    def head_loss(self, p, x, batch):
        """Sum over these rows of the mean next-token loss of a row, by
        block of positions under ``jax.checkpoint``: the float32 logits of
        both rows at once and their gradient are 5 GB beside the state."""
        b, length, h = x.shape
        step = min(QUERY_BLOCK, length)
        if length % step:
            raise ValueError(f"sequence length {length} is no multiple of "
                             f"the block of positions {step}")
        ids = batch["ids"]
        labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
        counted = jnp.broadcast_to(jnp.arange(length) < length - 1,
                                   (b, length))
        x, labels, counted = (
            jnp.moveaxis(t.reshape(b, length // step, step, *t.shape[2:]),
                         1, 0) for t in (x, labels, counted))
        block = jax.checkpoint(self._head_block)
        return jnp.sum(jax.lax.map(lambda t: block(p, *t),
                                   (x, labels, counted))) / (length - 1)


# -- work counts: what the algorithm needs of this share ---------------------

def kept_pairs(cfg, kind, seq_len):
    """(query, key) pairs the mask of a layer of ``kind`` keeps in one
    sequence: every earlier position and the query's own, at most the
    window's in a ``window`` layer."""
    reach = seq_len if kind == "full" else min(seq_len,
                                               cfg["sliding_window_size"])
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def kept_pairs_of_layers(cfg, seq_len):
    """The same, summed over the layers held."""
    return sum(kept_pairs(cfg, kind_of_layer(cfg, i), seq_len)
               for i in range(cfg["num_hidden_layers"]))


def expert_rows(cfg, tokens):
    """Rows the experts held here are expected to compute a step and layer:
    ``top_k`` of the published experts a token, the held share of them
    (6 x 16 / 64 = 1.5 a token at the published sizes). A fraction with the
    published count as denominator, kept whole: (numerator, denominator)."""
    s = sizes(cfg)
    return tokens * s["top_k"] * s["held"], s["experts"]


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need: 6 a token for
    every parameter of the attention projections, the router and the head
    (the vocabulary rows ids are drawn from, not the padded rows held); 6 a
    routed row for an expert's three matrices, for the rows expected here;
    12 x head size a kept pair and query head for attention's products."""
    s = sizes(cfg)
    h, d, n, kv = s["hidden"], s["head_dim"], s["heads"], s["kv_heads"]
    tokens = sequences * seq_len
    per_layer = h * (n + 2 * kv) * d + n * d * h + h * s["experts"]
    dense = s["layers"] * per_layer + h * cfg["vocab_size"]
    rows, over = expert_rows(cfg, tokens)
    experts = s["layers"] * 6 * 3 * h * s["inner"] * rows // over
    pairs = kept_pairs_of_layers(cfg, seq_len)
    return 6 * dense * tokens + experts + 12 * n * d * pairs * sequences


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (all layers):
    forward 4 D a kept pair and query head (two products), backward 8 D
    (dV, dP, dQ, dK; the scores computed again are recomputation). Bytes: q
    and o at the query heads' width, k and v at the key-value heads', read
    or written once forward; q, k, v, o, do, dq, dk, dv once backward."""
    s = sizes(cfg)
    d, n, kv = s["head_dim"], s["heads"], s["kv_heads"]
    unit = sequences * n * d * kept_pairs_of_layers(cfg, seq_len)
    row = sequences * seq_len * d * bytes_per_element * s["layers"]
    return {
        "fwd": {"flops": 4 * unit, "bytes": (2 * n + 2 * kv) * row},
        "bwd": {"flops": 8 * unit, "bytes": (4 * n + 4 * kv) * row},
    }


def expert_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the experts' grouped products over one step
    (all layers), whatever implements them: per routed row expected here 2
    FLOPs a parameter of an expert's three matrices forward, 4 backward
    (the gradient of the rows and of the weights). Bytes: the experts'
    matrices held once, a row's input and output at the hidden width and
    its two activations at the expert width once, forward; those and their
    gradients backward."""
    s = sizes(cfg)
    h, f = s["hidden"], s["inner"]
    rows, over = expert_rows(cfg, sequences * seq_len)
    unit = s["layers"] * 3 * h * f * rows // over
    held = s["layers"] * s["held"] * 3 * h * f * bytes_per_element
    per_row = s["layers"] * (2 * h + 3 * f) * bytes_per_element * rows // over
    return {
        "fwd": {"flops": 2 * unit, "bytes": held + per_row},
        "bwd": {"flops": 4 * unit, "bytes": 2 * (held + per_row)},
    }

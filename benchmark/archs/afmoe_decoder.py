"""``afmoe_decoder`` (Trinity-Mini, ``model_type`` ``afmoe``): gated,
QK-normed grouped-query attention in window and full layers, four norms a
layer, a leading dense layer, then sparse layers of which one chip holds a
share of the experts.

``d`` = ``hidden_size``, ``eps`` = ``rms_norm_eps``, ``RMSNorm(x; g) = x /
sqrt(mean(x^2) + eps) * g``, every product without bias:

    h = Embedding[ids] * sqrt(d)                          (``mup_enabled``)
    layer i (the i-th held: published index ``deployment.layers_held[i]``,
    of the kind ``layer_types`` gives that index):
    a = RMSNorm(h; g_in);  q, k, v, G = a W_q, a W_k, a W_v, a W_g
                      (n query heads of D, kv key-value heads, G n D wide)
    q <- RMSNorm(q; g_q),  k <- RMSNorm(k; g_k)      over the D of a head
    ``sliding_attention`` (kind ``*_window``): RoPE (rotate-half over the
        whole head, ``rope_theta``) on q and k; query t sees keys j with
        t - ``sliding_window`` < j <= t
    ``full_attention`` (kind ``*_full``): no rotation; every j <= t
    o = softmax(q k^T / sqrt(D)) v, query head n on key-value head
        n // (n / kv);  attn = (o * sigmoid(G)) W_o
    h <- h + RMSNorm(attn; g_post_attn);  m = RMSNorm(h; g_pre_ffn)
    i < ``num_dense_layers`` (kind ``dense_*``):
        f = (silu(m W_gate) * (m W_up)) W_down     at ``intermediate_size``
    else (kind ``sparse_*``):
        s = sigmoid(m W_r)            float32, ``published.num_experts``
        S = the ``num_experts_per_tok`` largest of s + b
        w_e = ``route_scale`` s_e / (sum of s over S + 1e-20)
        f = shared(m) + sum over e in S HELD HERE of
                w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e
        (the shared expert: the dense form at ``moe_intermediate_size`` x
        ``num_shared_experts``, on every token, weight 1)
    h <- h + RMSNorm(f; g_post_ffn)
    out = RMSNorm(h_last; g_final) W_head; next-token cross entropy over
          the vocabulary rows held, positions 0..S-2; no auxiliary loss

``b`` is ``cfg["selection_bias"]`` (one float a published expert; absent:
zero, as in the configuration file). The experts held are
``num_experts`` of the configuration file, the contiguous run from
``deployment.first_expert_held``; the router routes over all the published
ones and the share's partial sum is what is normed and goes on.

At the timed sizes both 8192-token rows reach ``block`` at once, so
attention goes by key-value head and block of queries, each under
``jax.checkpoint`` with the plain mask over every key and nothing skipped,
and the experts by block of tokens, a loop over the experts held with the
rows masked. The loops are rolled (``lax.map``, ``lax.scan``): unrolled,
such programs took minutes to compile for the chip.

``cfg["planted_fault"]`` (never in a configuration file; set by
``tools/arch_faults.py`` alone) plants one fault of this architecture's
own: see ``FAULTS``.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import xent

# The rehearsal computes in float32, as the other sparse configurations'
# do: at its 128 tokens an expert sees a handful, and bfloat16 rounding
# then decides ``correct`` by the seed drawn.
REHEARSE = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 8,
            "num_key_value_heads": 1, "intermediate_size": 192,
            "moe_intermediate_size": 32, "num_experts": 2,
            "num_experts_per_tok": 2, "published": {"num_experts": 16},
            "sliding_window": 16, "vocab_size": 500,
            "assumed": {"compute_dtype": "float32"}}
QUERY_BLOCK = 512
TOKEN_BLOCK = 4096
FAULTS = ("gate_left_out", "qk_norm_left_out", "post_norms_left_out",
          "rope_on_full_layer", "window_ignored", "shared_expert_left_out",
          "softmax_for_sigmoid", "embed_scale_left_out")
ATTENTION = {"sliding_attention": "window", "full_attention": "full"}


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this architecture states sigmoid scores normed "
                         "over the chosen with no group limit (score_func, "
                         "route_norm, n_group, topk_group)")
    deployment = cfg.get("deployment", {})
    return dict(
        hidden=cfg["hidden_size"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        dense=cfg["intermediate_size"], expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        experts=cfg["published"]["num_experts"], held=cfg["num_experts"],
        first_held=deployment.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"], scale=cfg["route_scale"],
        layers=cfg["num_hidden_layers"], window=cfg["sliding_window"],
        vocab_rows=cfg["assumed"]["vocab_rows"])


def kind_of_layer(cfg, i):
    """``dense_window`` | ``sparse_window`` | ``sparse_full`` (|
    ``dense_full``) of the i-th layer held."""
    held = cfg.get("deployment", {}).get(
        "layers_held", list(range(cfg["num_hidden_layers"])))
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(f"deployment.layers_held names {len(held)} layers, "
                         f"num_hidden_layers {cfg['num_hidden_layers']}")
    kind = cfg["layer_types"][held[i]]
    if kind not in ATTENTION:
        raise ValueError(f"layer {held[i]}: unknown layer_types entry "
                         f"{kind!r}")
    ffn = "dense" if i < cfg["num_dense_layers"] else "sparse"
    return f"{ffn}_{ATTENTION[kind]}"


def kinds_held(cfg):
    return [kind_of_layer(cfg, i) for i in range(cfg["num_hidden_layers"])]


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    s = sizes(cfg)
    h, d, v = s["hidden"], s["head_dim"], s["vocab_rows"]
    n, kv = s["heads"], s["kv_heads"]
    norm = {"scale": (h,)}

    def swiglu(width):
        return {"gate_up": {"shard": {"kernel": (h, 2 * width)}},
                "out": {"shard": {"kernel": (width, h)}}}

    shared = {
        "input_norm": norm, "post_attn_norm": norm, "pre_ffn_norm": norm,
        "post_ffn_norm": norm,
        "attention": {
            "qkv": {"shard": {"kernel": (h, (n + 2 * kv) * d)}},
            "gate": {"shard": {"kernel": (h, n * d)}},
            "out": {"shard": {"kernel": (n * d, h)}},
            "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}}}
    ffn = {"dense": {"mlp": swiglu(s["dense"])},
           "sparse": {"moe": {"router": {"kernel": (h, s["experts"])},
                              "w_gate_up": (s["held"], h, 2 * s["expert"]),
                              "w_down": (s["held"], s["expert"], h)},
                      "shared": swiglu(s["shared"])}}
    tree = {"embed": {"tok_emb": {"embedding": (v, h)}},
            "head": {"ln_f": norm, "lm_head": {"kernel": (h, v)}}}
    for i, kind in enumerate(kinds_held(cfg)):
        tree[f"layer_{i}"] = dict(shared, **ffn[kind.split("_")[0]])
    return tree


def fused_parts(cfg):
    """The qkv projection is [q | k | v] by heads: equal parts of one
    key-value head's width (8 of q, one of k, one of v at the published
    sizes). ``gate`` is a leaf of its own (hidden x the query heads'
    width), not fused with them. The dense feed-forward's, the shared
    expert's and the experts' first products are [gate | up]."""
    s = sizes(cfg)
    out = {}
    for i, kind in enumerate(kinds_held(cfg)):
        layer = f"layer_{i}"
        out[(layer, "attention", "qkv", "shard", "kernel")] = \
            s["heads"] // s["kv_heads"] + 2
        if kind.startswith("dense"):
            out[(layer, "mlp", "gate_up", "shard", "kernel")] = 2
        else:
            out[(layer, "shared", "gate_up", "shard", "kernel")] = 2
            out[(layer, "moe", "w_gate_up")] = 2
    return out


def fresh_leaf(cfg, path, shape):
    """Every matrix is drawn by itself, normal(``init_std``) from the key
    folded with the leaf's position, and not cut from one vector of all
    504M values (2 GiB and as much again in slices, beside the program's
    state, when ``check.Norms`` makes the fresh parameters again). Norm
    scales start at one by the shared rule. The embedding's rows start at
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
    """Embed, blocks by kind (``dense_window``, ``sparse_window``,
    ``sparse_full``, ``dense_full``), head + loss. Each method takes its
    own sub-tree of the parameters."""

    def __init__(self, cfg, mm):
        self.cfg, self.mm, self.s = cfg, mm, sizes(cfg)
        self.layers, self.eps = self.s["layers"], cfg["rms_norm_eps"]
        self.fault = cfg.get("planted_fault")
        if self.fault not in (None,) + FAULTS:
            raise ValueError(f"unknown planted fault {self.fault!r}")
        bias = cfg.get("selection_bias")
        self.bias = jnp.zeros((self.s["experts"],), jnp.float32) \
            if bias is None else jnp.asarray(bias, jnp.float32)

    def split(self, params):
        return (params["embed"],
                [params[f"layer_{i}"] for i in range(self.layers)],
                params["head"])

    def join(self, embed, layers, head):
        """The gradient as a tree named like the parameters, handed back on
        the host, as the other two sparse architectures do and for their
        reason: ``Reference.adam`` keeps old and new state (24 B a
        parameter, 11.27 GiB of the chip's 15.75 at this cut's 504.4M), and
        a gradient left on the device beside them is 1.9 GiB more."""
        tree = {"embed": embed, "head": head}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return jax.device_get(tree)

    def embed(self, p, batch):
        x = p["tok_emb"]["embedding"][batch["ids"]]
        if self.cfg["mup_enabled"] and self.fault != "embed_scale_left_out":
            x = x * math.sqrt(self.s["hidden"])
        return x

    def kind_of(self, i):
        return kind_of_layer(self.cfg, i)

    # attention of one key-value head's group over one block of queries
    def _attend(self, windowed, q, k, v, first):
        """``q`` (rows, block, group, d) are the queries from position
        ``first`` on, ``k`` and ``v`` (rows, S, d) every key."""
        scores = self.mm("bqgd,bkd->bgqk", q, k) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        t = first + jnp.arange(q.shape[1])[:, None]
        j = jnp.arange(k.shape[1])[None, :]
        keep = j <= t
        if windowed and self.fault != "window_ignored":
            keep &= j > t - self.s["window"]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), -1)
        return self.mm("bgqk,bkd->bqgd", probs, v)

    def attention(self, windowed, p, a):
        s, mm = self.s, self.mm
        b, length, _ = a.shape
        n, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
        qkv = mm("bsh,hk->bsk", a, p["qkv"]["shard"]["kernel"])
        q, k, v = jnp.split(qkv, [n * d, (n + kv) * d], -1)
        q = q.reshape(b, length, n, d)
        k, v = k.reshape(b, length, kv, d), v.reshape(b, length, kv, d)
        if self.fault != "qk_norm_left_out":
            q, k = rms(q, p["q_norm"], self.eps), rms(k, p["k_norm"],
                                                      self.eps)
        if windowed or self.fault == "rope_on_full_layer":
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
                lambda blk: attend(windowed, blk[1], k_j, v_j,
                                   blk[0] * step), (jnp.arange(blocks), q_j))

        out = jax.lax.map(head, (q, jnp.moveaxis(k, 2, 0),
                                 jnp.moveaxis(v, 2, 0)))
        out = out.transpose(2, 1, 3, 0, 4, 5).reshape(b, length, n * d)
        if self.fault != "gate_left_out":
            out = out * jax.nn.sigmoid(
                mm("bsh,hk->bsk", a, p["gate"]["shard"]["kernel"]))
        return mm("bsk,kh->bsh", out, p["out"]["shard"]["kernel"])

    def _gated(self, m, w_gate_up, w_down):
        """``(silu(m W_gate) * (m W_up)) W_down`` with [gate | up] fused,
        on (tokens, hidden)."""
        gate, up = jnp.split(self.mm("th,hf->tf", m, w_gate_up), 2, -1)
        return self.mm("tf,fh->th", jax.nn.silu(gate) * up, w_down)

    def _swiglu(self, p, m):
        return self._gated(m, p["gate_up"]["shard"]["kernel"],
                           p["out"]["shard"]["kernel"])

    # the experts held here and the shared one, for one block of tokens
    def _experts(self, p, m, chosen, weights):
        first = self.s["first_held"]

        def add_expert(y, expert):
            e, w_gate_up, w_down = expert
            w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            return y + w_e[:, None] * self._gated(m, w_gate_up, w_down), None

        y = jax.lax.scan(add_expert, jnp.zeros_like(m), (
            jnp.arange(self.s["held"]), p["moe"]["w_gate_up"],
            p["moe"]["w_down"]))[0]
        if self.fault != "shared_expert_left_out":
            y = y + self._swiglu(p["shared"], m)
        return y

    def sparse(self, p, m):
        s = self.s
        b, length, h = m.shape
        logits = self.mm("bsh,he->bse", m, p["moe"]["router"]["kernel"])
        scores = jax.nn.softmax(logits, -1) \
            if self.fault == "softmax_for_sigmoid" else jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + self.bias, s["top_k"])
        top = jnp.take_along_axis(scores, chosen, -1)
        weights = s["scale"] * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
        tokens = b * length
        step = min(TOKEN_BLOCK, tokens)
        if tokens % step:
            raise ValueError(f"{tokens} tokens are no multiple of the token "
                             f"block {step}")
        block = jax.checkpoint(self._experts)
        return jax.lax.map(lambda t: block(p, *t), tuple(
            t.reshape(tokens // step, step, -1)
            for t in (m, chosen, weights))).reshape(b, length, h)

    def block(self, kind, p, x):
        ffn, attention = kind.split("_")
        eps = self.eps

        def post(y, scale):
            return y if self.fault == "post_norms_left_out" \
                else rms(y, scale, eps)

        x = x + post(self.attention(attention == "window", p["attention"],
                                    rms(x, p["input_norm"], eps)),
                     p["post_attn_norm"])
        m = rms(x, p["pre_ffn_norm"], eps)
        if ffn == "dense":
            f = self._swiglu(p["mlp"], m.reshape(-1, m.shape[-1])).reshape(
                m.shape)
        else:
            f = self.sparse(p, m)
        return x + post(f, p["post_ffn_norm"])

    def _head_block(self, p, x, labels, counted):
        logits = self.mm("bsh,hv->bsv", rms(x, p["ln_f"], self.eps),
                         p["lm_head"]["kernel"])
        return jnp.sum(jnp.where(counted, xent(logits, labels), 0.0))

    def head_loss(self, p, x, batch):
        """Sum over these rows of the mean next-token loss of a row, by
        block of positions under ``jax.checkpoint``."""
        b, length, h = x.shape
        step = min(2 * QUERY_BLOCK, length)
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

def layers_of(cfg, word):
    """Layers held whose kind carries ``word`` (dense | sparse | window |
    full)."""
    return sum(word in kind.split("_") for kind in kinds_held(cfg))


def kept_pairs(cfg, windowed, seq_len):
    """(query, key) pairs the mask of a layer keeps in one sequence: every
    earlier position and the query's own, at most the window's in a
    ``window`` layer."""
    reach = min(seq_len, cfg["sliding_window"]) if windowed else seq_len
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def kept_pairs_of_layers(cfg, seq_len):
    """The same, summed over the layers held."""
    return layers_of(cfg, "window") * kept_pairs(cfg, True, seq_len) \
        + layers_of(cfg, "full") * kept_pairs(cfg, False, seq_len)


def expert_rows(cfg, tokens):
    """Rows the routed experts held here are expected to compute a step and
    layer: ``top_k`` of the published experts a token, the held share of
    them (8 x 8 / 128 = 0.5 a token at the published sizes). A fraction
    with the published count as denominator, kept whole: (numerator,
    denominator)."""
    s = sizes(cfg)
    return tokens * s["top_k"] * s["held"], s["experts"]


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need: 6 a token for
    every parameter of attention's four projections, the dense
    feed-forward, the router, the shared expert and the head (the
    vocabulary rows ids are drawn from, not the padded rows held); 6 a
    routed row for an expert's three matrices, for the rows expected here
    (experts active, not held); 12 x head size a kept pair and query head
    for attention's products (the pairs a window keeps, not the square)."""
    s = sizes(cfg)
    h, d, n, kv = s["hidden"], s["head_dim"], s["heads"], s["kv_heads"]
    tokens = sequences * seq_len
    attention = h * (n + 2 * kv) * d + 2 * n * d * h
    sparse = h * s["experts"] + 3 * h * s["shared"]
    dense = s["layers"] * attention \
        + layers_of(cfg, "dense") * 3 * h * s["dense"] \
        + layers_of(cfg, "sparse") * sparse + h * cfg["vocab_size"]
    rows, over = expert_rows(cfg, tokens)
    experts = layers_of(cfg, "sparse") * 6 * 3 * h * s["expert"] * rows \
        // over
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
    """FLOPs and HBM bytes of the routed experts' grouped products over one
    step (all sparse layers), whatever implements them: per routed row
    expected here 2 FLOPs a parameter of an expert's three matrices
    forward, 4 backward. Bytes: the experts' matrices held once, a row's
    input and output at the hidden width and its two activations at the
    expert width once, forward; those and their gradients backward. The
    shared expert is two dense products and not counted here."""
    s = sizes(cfg)
    h, f, layers = s["hidden"], s["expert"], layers_of(cfg, "sparse")
    rows, over = expert_rows(cfg, sequences * seq_len)
    unit = layers * 3 * h * f * rows // over
    held = layers * s["held"] * 3 * h * f * bytes_per_element
    per_row = layers * (2 * h + 3 * f) * bytes_per_element * rows // over
    return {
        "fwd": {"flops": 2 * unit, "bytes": held + per_row},
        "bwd": {"flops": 4 * unit, "bytes": 2 * (held + per_row)},
    }

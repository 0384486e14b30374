"""``joyai_flash_decoder`` (JoyAI-LLM Flash, ``model_type``
``joyai_llm_flash``, DeepSeek-V3's form): latent attention, a leading dense
layer, then sparse layers of which one chip holds a share of the experts,
and one depth of multi-token prediction (MTP).

``d`` = ``hidden_size``, ``n`` heads, ``eps`` = ``rms_norm_eps``,
``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, every product without
bias:

    h = Embedding[ids]
    layer i < ``first_k_dense_replace`` (kind ``dense``), else ``sparse``:
    a = RMSNorm(h; g_in)
    c_q = RMSNorm(a W_qa; g_qa)                          (``q_lora_rank``)
    [q_nope | q_pe] = c_q W_qb          per head: ``qk_nope_head_dim`` |
                                        ``qk_rope_head_dim``
    [c_kv | k_pe] = a W_kva;  c_kv <- RMSNorm(c_kv; g_kva)   (one k_pe)
    [k_nope | v] = c_kv W_kvb           per head: nope | ``v_head_dim``
    q_pe, k_pe rotated: pairs (2j, 2j+1) by t theta^(-2j / rope width)
                        (``rope_interleave``, ``rope_theta``)
    q = [q_nope | q_pe], k = [k_nope | k_pe] (k_pe the same for every head)
    o = softmax(q k^T / sqrt(``qk_head_dim``), j <= t) v;  h <- h + o W_o
    m = RMSNorm(h; g_post)
    dense: f = (silu(m W_gate) * (m W_up)) W_down     at ``intermediate_size``
    sparse: s = sigmoid(m W_r)            float32, ``published.n_routed_experts``
        S = the ``num_experts_per_tok`` largest of s + b
        w_e = ``routed_scaling_factor`` s_e / (sum of s over S + 1e-20)
        f = shared(m) + sum over e in S HELD HERE of
                w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e
        (shared: the dense form at ``moe_intermediate_size`` x
        ``n_shared_experts``, every token, weight 1)
    h <- h + f
    main: hf = RMSNorm(h_last; g_final); logits = hf W_head; next-token
          cross entropy, positions 0..S-2
    MTP:  z = [RMSNorm(Embedding[t_{i+1}]; g_e) | RMSNorm(hf; g_h)] W_eh;
          z <- a ``sparse`` layer of its own (z); logits' = RMSNorm(z; g_mtp)
          W_head (the same head); cross entropy against t_{i+2}, positions
          0..S-3 (the id after the last is the first: a filler the loss
          never reads)
    loss = main + ``assumed.mtp_loss_weight`` x MTP; no auxiliary loss

``b`` is ``cfg["selection_bias"]`` (one float a published expert; absent:
zero). The experts held are ``n_routed_experts`` of the configuration file,
the contiguous run from ``deployment.first_expert_held``; the router routes
over all the published ones and the share's partial sum goes on.

The MTP module needs the embedding at the head's end, and
``harness/reference.py`` hands ``head_loss`` the head's group alone: so
``split`` puts the table in that group too, and ``join`` sums its two
gradients.

At the timed sizes attention goes by head and block of queries, each under
``jax.checkpoint`` with the plain mask over every key and nothing skipped,
the experts by block of tokens, a loop over the experts held with the rows
masked, and the head by block of positions. The loops are rolled
(``lax.map``, ``lax.scan``).

``cfg["planted_fault"]`` (never in a configuration file; set by
``tools/arch_faults.py`` alone) plants one fault of this architecture's
own: see ``FAULTS``.
"""

import jax
import jax.numpy as jnp

from benchmark.harness.reference import xent

# The rehearsal computes in float32, as the other sparse configurations'
# do; the query and key heads stay wider than the value heads.
REHEARSE = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 48,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
            "intermediate_size": 192, "moe_intermediate_size": 32,
            "n_routed_experts": 2, "num_experts_per_tok": 2,
            "published": {"n_routed_experts": 16}, "vocab_size": 500,
            "assumed": {"compute_dtype": "float32"}}
QUERY_BLOCK = 512
TOKEN_BLOCK = 4096
FAULTS = ("rope_part_left_out", "latent_norms_left_out",
          "scale_of_the_value_width", "mtp_fed_the_current_token",
          "mtp_loss_left_out", "shared_expert_left_out",
          "softmax_for_sigmoid", "routed_scale_left_out")


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["topk_method"] != "noaux_tc":
        raise ValueError("this architecture states sigmoid scores normed "
                         "over the chosen with no group limit (scoring_func, "
                         "norm_topk_prob, n_group, topk_group, topk_method)")
    if not cfg["rope_interleave"] or cfg["qk_head_dim"] != \
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("this architecture states interleaved RoPE on the "
                         "rotary part of a query/key head of nope + rope "
                         "(rope_interleave, qk_head_dim)")
    if cfg["num_nextn_predict_layers"] != 1 or cfg["moe_layer_freq"] != 1:
        raise ValueError("this architecture states one MTP depth and every "
                         "layer after the dense ones sparse "
                         "(num_nextn_predict_layers, moe_layer_freq)")
    deployment = cfg.get("deployment", {})
    return dict(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], dense=cfg["intermediate_size"],
        expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"],
        first_held=deployment.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        vocab_rows=cfg["assumed"]["vocab_rows"])


def kind_of_layer(cfg, i):
    """``dense`` | ``sparse`` of the i-th layer of the main stack."""
    return "dense" if i < cfg["first_k_dense_replace"] else "sparse"


# -- names and shapes -------------------------------------------------------

def _layer_shapes(s, kind):
    h, n = s["hidden"], s["heads"]

    def norm(width):
        return {"scale": (width,)}

    def swiglu(width):
        return {"gate_up": {"shard": {"kernel": (h, 2 * width)}},
                "out": {"shard": {"kernel": (width, h)}}}

    layer = {
        "input_norm": norm(h), "post_attn_norm": norm(h),
        "attention": {
            "q_a": {"kernel": (h, s["q_rank"])},
            "q_a_norm": norm(s["q_rank"]),
            "q_b": {"shard": {"kernel": (
                s["q_rank"], n * (s["nope"] + s["rope"]))}},
            "kv_a": {"kernel": (h, s["kv_rank"] + s["rope"])},
            "kv_a_norm": norm(s["kv_rank"]),
            "kv_b": {"shard": {"kernel": (
                s["kv_rank"], n * (s["nope"] + s["v"]))}},
            "out": {"shard": {"kernel": (n * s["v"], h)}}}}
    if kind == "dense":
        layer["mlp"] = swiglu(s["dense"])
    else:
        layer["moe"] = {"router": {"kernel": (h, s["experts"])},
                        "w_gate_up": (s["held"], h, 2 * s["expert"]),
                        "w_down": (s["held"], s["expert"], h)}
        layer["shared"] = swiglu(s["shared"])
    return layer


def param_shapes(cfg):
    s = sizes(cfg)
    h, v = s["hidden"], s["vocab_rows"]
    norm = {"scale": (h,)}
    tree = {"embed": {"embedding": (v, h)},
            "head": {"ln_f": norm, "lm_head": {"kernel": (h, v)}},
            "mtp": {"enorm": norm, "hnorm": norm, "norm": norm,
                    "eh_proj": {"kernel": (2 * h, h)},
                    "block": _layer_shapes(s, "sparse")}}
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = _layer_shapes(s, kind_of_layer(cfg, i))
    return tree


def fused_parts(cfg):
    """The dense feed-forward's, the shared expert's and the experts' first
    products are [gate | up]: two equal parts."""
    s = sizes(cfg)
    out = {}
    layers = [(f"layer_{i}",) for i in range(s["layers"])] + [("mtp",
                                                               "block")]
    for at in layers:
        if at[0].startswith("layer_") \
                and kind_of_layer(cfg, int(at[0][6:])) == "dense":
            out[at + ("mlp", "gate_up", "shard", "kernel")] = 2
        else:
            out[at + ("shared", "gate_up", "shard", "kernel")] = 2
            out[at + ("moe", "w_gate_up")] = 2
    return out


def fresh_leaf(cfg, path, shape):
    """Every matrix is drawn by itself, normal(``init_std``) from the key
    folded with the leaf's position, and not cut from one vector of all
    492M values (2 GiB and as much again in slices). Norm scales start at
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


def rope_interleaved(x, theta):
    """RoPE on the pairs (2j, 2j+1) of the last axis of ``x`` (rows,
    positions, heads, width), positions 0..S-1, each pair left in place."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


class Net:
    """Embed, blocks by kind (``dense``, ``sparse``), head + MTP + loss.
    Each method takes its own sub-tree of the parameters."""

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
        """The head's group carries the MTP module and the embedding's
        table, which the MTP module reads (module docstring)."""
        return (params["embed"],
                [params[f"layer_{i}"] for i in range(self.layers)],
                {"head": params["head"], "mtp": params["mtp"],
                 "embed": params["embed"]})

    def join(self, embed, layers, head):
        """The gradient as a tree named like the parameters, the table's two
        parts summed, handed back on the host as the other sparse
        architectures do and for their reason: ``Reference.adam`` keeps old
        and new state (24 B a parameter, 11.0 GiB at this cut's 492.1M),
        and a gradient left on the device beside them is 1.8 GiB more."""
        tree = {"embed": jax.tree.map(jnp.add, embed, head["embed"]),
                "head": head["head"], "mtp": head["mtp"]}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return jax.device_get(tree)

    def embed(self, p, batch):
        return p["embedding"][batch["ids"]]

    def kind_of(self, i):
        return kind_of_layer(self.cfg, i)

    # attention of one head over one block of queries
    def _attend(self, q, k, v, first, scale):
        """``q`` (rows, block, width) are one head's queries from position
        ``first`` on, ``k`` and ``v`` (rows, S, width) its every key."""
        scores = self.mm("bqd,bkd->bqk", q, k) * scale
        t = first + jnp.arange(q.shape[1])[:, None]
        j = jnp.arange(k.shape[1])[None, :]
        probs = jax.nn.softmax(jnp.where(j <= t, scores, -1e30), -1)
        return self.mm("bqk,bkd->bqd", probs, v)

    def attention(self, p, a):
        s, mm, eps = self.s, self.mm, self.eps
        b, length, _ = a.shape
        n, nope, rope, dv = s["heads"], s["nope"], s["rope"], s["v"]
        theta = self.cfg["rope_theta"]

        def latent_norm(x, g):
            return x if self.fault == "latent_norms_left_out" \
                else rms(x, g, eps)

        c_q = latent_norm(mm("bsh,hr->bsr", a, p["q_a"]["kernel"]),
                          p["q_a_norm"])
        q = mm("bsr,rk->bsk", c_q, p["q_b"]["shard"]["kernel"]).reshape(
            b, length, n, nope + rope)
        c_kv, k_pe = jnp.split(mm("bsh,hr->bsr", a, p["kv_a"]["kernel"]),
                               [s["kv_rank"]], -1)
        kv = mm("bsr,rk->bsk", latent_norm(c_kv, p["kv_a_norm"]),
                p["kv_b"]["shard"]["kernel"]).reshape(b, length, n,
                                                      nope + dv)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        if self.fault == "rope_part_left_out":
            q, k = q[..., :nope], k_nope
        else:
            q = jnp.concatenate([q[..., :nope], rope_interleaved(
                q[..., nope:], theta)], -1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                rope_interleaved(k_pe[:, :, None], theta),
                (b, length, n, rope))], -1)
        width = dv if self.fault == "scale_of_the_value_width" \
            else nope + rope
        scale = 1.0 / jnp.sqrt(jnp.float32(width))
        # Rolled loops (one compiled body): over the heads and, inside,
        # over blocks of queries; each block under jax.checkpoint.
        step = min(QUERY_BLOCK, length)
        blocks = length // step
        if blocks * step != length:
            raise ValueError(f"sequence length {length} is no multiple of "
                             f"the query block {step}")
        q = q.reshape(b, blocks, step, n, -1).transpose(3, 1, 0, 2, 4)
        attend = jax.checkpoint(self._attend)

        def head(qkv_h):
            q_h, k_h, v_h = qkv_h
            return jax.lax.map(
                lambda blk: attend(blk[1], k_h, v_h, blk[0] * step, scale),
                (jnp.arange(blocks), q_h))

        out = jax.lax.map(head, (q, jnp.moveaxis(k, 2, 0),
                                 jnp.moveaxis(v, 2, 0)))
        out = out.transpose(2, 1, 3, 0, 4).reshape(b, length, n * dv)
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
        scale = 1.0 if self.fault == "routed_scale_left_out" else s["scale"]
        weights = scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
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
        eps = self.eps
        x = x + self.attention(p["attention"], rms(x, p["input_norm"], eps))
        m = rms(x, p["post_attn_norm"], eps)
        if kind == "dense":
            return x + self._swiglu(p["mlp"], m.reshape(-1, m.shape[-1])) \
                .reshape(m.shape)
        return x + self.sparse(p, m)

    def _head_block(self, w, x, labels, counted):
        logits = self.mm("bsh,hv->bsv", x, w)
        return jnp.sum(jnp.where(counted, xent(logits, labels), 0.0))

    def _loss_sum(self, w, x, ids, ahead):
        """Sum over these rows and the positions counted of the loss of the
        normed ``x`` against the id ``ahead`` positions on, by block of
        positions under ``jax.checkpoint``."""
        b, length, h = x.shape
        step = min(2 * QUERY_BLOCK, length)
        if length % step:
            raise ValueError(f"sequence length {length} is no multiple of "
                             f"the block of positions {step}")
        labels = jnp.concatenate(
            [ids[:, ahead:], jnp.zeros_like(ids[:, :ahead])], 1)
        counted = jnp.broadcast_to(jnp.arange(length) < length - ahead,
                                   (b, length))
        x, labels, counted = (
            jnp.moveaxis(t.reshape(b, length // step, step, *t.shape[2:]),
                         1, 0) for t in (x, labels, counted))
        block = jax.checkpoint(self._head_block)
        return jnp.sum(jax.lax.map(lambda t: block(w, *t),
                                   (x, labels, counted)))

    def head_loss(self, p, x, batch):
        """Sum over these rows of a row's mean next-token loss plus the
        MTP weight times its mean loss on the token after next."""
        eps, length = self.eps, x.shape[1]
        ids = batch["ids"]
        w = p["head"]["lm_head"]["kernel"]
        h = rms(x, p["head"]["ln_f"], eps)
        main = self._loss_sum(w, h, ids, 1) / (length - 1)
        if self.fault == "mtp_loss_left_out":
            return main
        mtp = p["mtp"]
        nxt = ids if self.fault == "mtp_fed_the_current_token" \
            else jnp.roll(ids, -1, 1)
        z = self.mm("bsk,kh->bsh", jnp.concatenate(
            [rms(p["embed"]["embedding"][nxt], mtp["enorm"], eps),
             rms(h, mtp["hnorm"], eps)], -1), mtp["eh_proj"]["kernel"])
        z = jax.checkpoint(self.block, static_argnums=0)("sparse",
                                                          mtp["block"], z)
        z = rms(z, mtp["norm"], eps)
        return main + self.cfg["assumed"]["mtp_loss_weight"] \
            * self._loss_sum(w, z, ids, 2) / (length - 2)


# -- work counts: what the algorithm needs of this share ---------------------

def sparse_layers(cfg):
    """Sparse layers held: those of the main stack and the MTP module's."""
    s = sizes(cfg)
    return s["layers"] - s["dense_layers"] + 1


def attention_layers(cfg):
    """Layers with latent attention: the main stack's and the MTP
    module's."""
    return sizes(cfg)["layers"] + 1


def kept_pairs(seq_len):
    """(query, key) pairs the causal mask keeps in one sequence."""
    return seq_len * (seq_len + 1) // 2


def expert_rows(cfg, tokens):
    """Rows the routed experts held here are expected to compute a step and
    layer: ``top_k`` of the published experts a token, the held share of
    them (8 x 8 / 256 = 0.25 a token at the published sizes). A fraction
    with the published count as denominator, kept whole: (numerator,
    denominator)."""
    s = sizes(cfg)
    return tokens * s["top_k"] * s["held"], s["experts"]


def attention_params(cfg):
    """Matrix parameters a token multiplies in one latent attention."""
    s = sizes(cfg)
    h, n = s["hidden"], s["heads"]
    return h * s["q_rank"] + s["q_rank"] * n * (s["nope"] + s["rope"]) \
        + h * (s["kv_rank"] + s["rope"]) \
        + s["kv_rank"] * n * (s["nope"] + s["v"]) + n * s["v"] * h


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need: 6 a token for
    every parameter of the latent attentions' five products, the dense
    feed-forward, the routers, the shared experts, the MTP projection and
    the head's two passes (the vocabulary rows ids are drawn from, not the
    padded rows held); 6 a routed row for an expert's three matrices, for
    the rows expected here; 6 (qk width + v width) a kept pair and head
    for attention's products."""
    s = sizes(cfg)
    h = s["hidden"]
    tokens = sequences * seq_len
    dense = attention_layers(cfg) * attention_params(cfg) \
        + s["dense_layers"] * 3 * h * s["dense"] \
        + sparse_layers(cfg) * (h * s["experts"] + 3 * h * s["shared"]) \
        + 2 * h * h + 2 * h * cfg["vocab_size"]
    rows, over = expert_rows(cfg, tokens)
    experts = sparse_layers(cfg) * 6 * 3 * h * s["expert"] * rows // over
    pairs = attention_layers(cfg) * s["heads"] * kept_pairs(seq_len) \
        * sequences
    return 6 * dense * tokens + experts \
        + 6 * (s["nope"] + s["rope"] + s["v"]) * pairs


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (all layers):
    forward 2 (Dqk + Dv) a kept pair and head (q k^T over the query/key
    width, p v over the value width), backward 4 (Dqk + Dv) (dP and dV over
    the value width, dQ and dK over the query/key width; the scores
    computed again are recomputation). Bytes: q and k at the query/key
    width, v and o at the value width, read or written once forward; q, k,
    v, o, dO, dQ, dK, dV once backward."""
    s = sizes(cfg)
    dqk, dv = s["nope"] + s["rope"], s["v"]
    layers = attention_layers(cfg)
    unit = sequences * s["heads"] * kept_pairs(seq_len) * layers
    row = sequences * seq_len * s["heads"] * bytes_per_element * layers
    return {
        "fwd": {"flops": 2 * (dqk + dv) * unit,
                "bytes": (2 * dqk + 2 * dv) * row},
        "bwd": {"flops": 4 * (dqk + dv) * unit,
                "bytes": (4 * dqk + 4 * dv) * row},
    }


def expert_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the routed experts' grouped products over one
    step (all sparse layers, the MTP module's among them), whatever
    implements them: per routed row expected here 2 FLOPs a parameter of
    an expert's three matrices forward, 4 backward. Bytes: the experts'
    matrices held once, a row's input and output at the hidden width and
    its two activations at the expert width once, forward; those and their
    gradients backward. The shared expert is two dense products and not
    counted here."""
    s = sizes(cfg)
    h, f, layers = s["hidden"], s["expert"], sparse_layers(cfg)
    rows, over = expert_rows(cfg, sequences * seq_len)
    unit = layers * 3 * h * f * rows // over
    held = layers * s["held"] * 3 * h * f * bytes_per_element
    per_row = layers * (2 * h + 3 * f) * bytes_per_element * rows // over
    return {
        "fwd": {"flops": 2 * unit, "bytes": held + per_row},
        "bwd": {"flops": 4 * unit, "bytes": 2 * (held + per_row)},
    }

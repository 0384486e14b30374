"""``nemotron_h_hybrid`` (the tower that the config of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 declares, ``model_type``
``nemotron_h``): Mamba-2, expert and attention layers in one stack, one
chip's share of each expert layer.

Layer ``i`` is of the kind ``hybrid_override_pattern[i]``; with ``u`` =
RMSNorm(x; ``norm_eps``) every layer is ``x <- x + mixer(u)``, every
product without bias:

``M`` (kind ``mamba``), H heads of P with a state of N, G groups:
    [z | xBC | dt] = u W_in           widths H P | H P + 2 G N | H
    xBC = silu(conv(xBC) + b_conv)    causal, depthwise, ``conv_kernel``
                                      taps (tap K-1 reads the position
                                      itself), zeros before the start
    [x | B | C] = xBC                 x (H, P), B and C (G, N); head h
                                      reads group h // (H / G)
    dt = softplus(dt + dt_bias);  a = exp(dt A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t   per head,
                                      S (P, N) zero at the start
    y = RMSNorm_groups(y * silu(z); ``layer_norm_epsilon``) * scale
                                      over G runs of H P / G channels
    mixer = y W_out
``E`` (kind ``moe``):
    s = sigmoid(u W_r)                float32, ``published.n_routed_experts``
    S = the ``num_experts_per_tok`` largest of s (+ b, which is zero)
    w_e = ``routed_scaling_factor`` s_e / sum of s over S
    mixer = sum over e in S held here of w_e relu(u W_up,e)^2 W_down,e
            + relu(u W_up,shared)^2 W_down,shared
``*`` (kind ``attention``):
    q, k, v = u W_q, u W_k, u W_v (32 / 2 / 2 heads of 128); no positions;
    query t sees keys j <= t; mixer = attn(q, k, v) W_o
out = RMSNorm(x_last) W_head; next-token cross entropy over the vocabulary
rows held, positions 0..S-2; no auxiliary loss.

The recurrence is computed AS WRITTEN, one token at a time in float32
(``lax.scan`` over the positions, its two products through ``mm`` like
every other product; ``jax.checkpoint`` where a block of ``chunk_size``
positions ends so that the backward pass keeps one state a block and not
one a position): the program runs the chunked matrix form, so the two share
no algorithm. Experts go by block of tokens and attention by key-value
head and block of queries, in rolled loops (``lax.map``, ``lax.scan``):
unrolled such programs took minutes to compile for the chip.

``cfg["planted_fault"]`` (never in a configuration file; set by
``tools/arch_faults.py`` alone) plants one fault of this architecture's own:
``decay_left_out`` (a = 1), ``conv_left_out`` (xBC = silu(xBC)),
``shared_expert_left_out``, ``softmax_for_sigmoid`` (s = softmax(u W_r)).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import xent

# The rehearsal computes in float32, as the other sparse configuration's
# does: at its 128 tokens an expert sees a handful, and bfloat16 rounding
# then decides ``correct`` by the seed drawn.
REHEARSE = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
            "num_key_value_heads": 2, "mamba_num_heads": 8,
            "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
            "chunk_size": 16, "moe_intermediate_size": 32,
            "moe_shared_expert_intermediate_size": 64,
            "n_routed_experts": 2, "num_experts_per_tok": 2,
            "published": {"n_routed_experts": 16},
            "num_hidden_layers": 7, "vocab_size": 500,
            "assumed": {"compute_dtype": "float32"}}
QUERY_BLOCK = 512
TOKEN_BLOCK = 4096
FAULTS = ("decay_left_out", "conv_left_out", "shared_expert_left_out",
          "softmax_for_sigmoid")
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    heads, head = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        hidden=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        m_heads=heads, m_head=head, groups=groups, state=state,
        inner=heads * head, conv_dim=heads * head + 2 * groups * state,
        taps=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"],
        first_held=cfg.get("deployment", {}).get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"], expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_shared_expert_intermediate_size"],
        scale=cfg["routed_scaling_factor"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab_rows=cfg["assumed"]["vocab_rows"])


def kind_of_layer(cfg, i):
    letter = cfg["hybrid_override_pattern"][i]
    if letter not in KINDS:
        raise ValueError(f"layer {i}: unknown kind of layer {letter!r} in "
                         f"hybrid_override_pattern")
    return KINDS[letter]


def kinds_held(cfg):
    return [kind_of_layer(cfg, i) for i in range(cfg["num_hidden_layers"])]


# -- names and shapes -------------------------------------------------------

def param_shapes(cfg):
    s = sizes(cfg)
    h, v = s["hidden"], s["vocab_rows"]
    n, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
    norm = {"scale": (h,)}
    mixers = {
        "mamba": {"mixer": {
            "in_proj": {"kernel": (h, s["inner"] + s["conv_dim"]
                                   + s["m_heads"])},
            "conv": {"kernel": (s["taps"], s["conv_dim"]),
                     "bias": (s["conv_dim"],)},
            "dt_bias": (s["m_heads"],), "A_log": (s["m_heads"],),
            "D": (s["m_heads"],),
            "gate_norm": {"scale": (s["inner"],)},
            "out_proj": {"kernel": (s["inner"], h)}}},
        "moe": {
            "moe": {"router": {"kernel": (h, s["experts"])},
                    "w_up": (s["held"], h, s["expert"]),
                    "w_down": (s["held"], s["expert"], h)},
            "shared": {"up": {"kernel": (h, s["shared"])},
                       "down": {"kernel": (s["shared"], h)}}},
        "attention": {"attention": {
            "qkv": {"shard": {"kernel": (h, (n + 2 * kv) * d)}},
            "out": {"shard": {"kernel": (n * d, h)}}}},
    }
    tree = {"embed": {"tok_emb": {"embedding": (v, h)}},
            "head": {"ln_f": norm, "lm_head": {"kernel": (h, v)}}}
    for i, kind in enumerate(kinds_held(cfg)):
        tree[f"layer_{i}"] = dict(mixers[kind], norm=norm)
    return tree


def fused_parts(cfg):
    """The qkv projection is [q | k | v] by heads: equal parts of one
    key-value head's width (16 of q, one of k, one of v at the published
    sizes). The mixer's input projection [z | xBC | dt] is stated as ONE
    leaf: its parts are unequal (4096 | 6144 | 64), and ``check.Norms``
    splits a fused leaf into equal parts only; ``dt``'s own gradient is
    read off ``dt_bias`` and the convolution's off its kernel."""
    s = sizes(cfg)
    return {(f"layer_{i}", "attention", "qkv", "shard", "kernel"):
            s["heads"] // s["kv_heads"] + 2
            for i, kind in enumerate(kinds_held(cfg)) if kind == "attention"}


def fresh_leaf(cfg, path, shape):
    """Every leaf but the norms' scales and the convolution's bias (one and
    zero by the shared rule) is drawn by itself from the key folded with the
    leaf's position, not cut from one vector of all 528M values (beside the
    program's state that vector does not fit the chip). Matrices are
    normal(``init_std``). The mixer's own leaves start as Mamba-2 starts
    them, from the configuration's keys: ``dt_bias`` the inverse softplus
    of a step drawn log-uniformly in [``time_step_min``, ``time_step_max``]
    and floored at ``time_step_floor``; ``A_log`` the log of a uniform draw
    in [1, 16]; ``D`` one; the convolution's kernel uniform in
    +-1 / sqrt(``conv_kernel``) (``assumed.why.conv_init``). The
    embedding's rows start at ``assumed.embedding_std`` and, where the
    config says ``rescale_prenorm_residual``, the projections that write
    into the residual stream (the mixer's ``out_proj``, the experts' and the
    shared expert's down products, attention's output product) at
    ``init_std`` / sqrt(published ``num_hidden_layers``), one residual a
    layer (``assumed.why.embedding_std`` says what rests on the two)."""
    name = path[-1]
    if name in ("scale", "bias"):
        return None
    if name == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])

        def dt_bias(key):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        return dt_bias
    if name == "A_log":
        return lambda key: jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 1.0, 16.0))
    if name == "D":
        return lambda key: jnp.ones(shape, jnp.float32)
    if path[-2:] == ("conv", "kernel"):
        bound = 1.0 / math.sqrt(cfg["conv_kernel"])
        return lambda key: jax.random.uniform(key, shape, jnp.float32,
                                              -bound, bound)
    a = cfg["assumed"]
    std = a["init_std"]
    if name == "embedding":
        std = a.get("embedding_std", std)
    elif cfg["rescale_prenorm_residual"] and (
            name == "w_down" or path[-2] in ("out_proj", "down")
            or path[-3:-1] == ("out", "shard")):
        # the projections that write into the residual stream, one a layer
        std = std / math.sqrt(cfg["published"]["num_hidden_layers"])
    return lambda key: jax.random.normal(key, shape, jnp.float32) * std


# -- the network -------------------------------------------------------------

def rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


class Net:
    """Embed, blocks of three kinds (``mamba``, ``moe``, ``attention``),
    head + loss. Each method takes its own sub-tree of the parameters."""

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
        the host, as ``smallthinker_moe_decoder`` does and for its reason:
        ``Reference.adam`` keeps the old parameters and moments beside the
        new ones until it returns (24 B a parameter, 11.80 GiB of the
        chip's 15.75 at this cut's 528.1M), and a gradient left on the
        device beside them does not fit."""
        tree = {"embed": embed, "head": head}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return jax.device_get(tree)

    def embed(self, p, batch):
        return p["tok_emb"]["embedding"][batch["ids"]]

    def kind_of(self, i):
        return kind_of_layer(self.cfg, i)

    # -- M: the recurrence itself, one token at a time ----------------------
    def _recurrence(self, a, dtx, B, C):
        """``y`` (L, b, G, R, P): ``a`` (L, b, G, R) the decays, ``dtx``
        (L, b, G, R, P) the inputs times their steps, ``B``, ``C``
        (L, b, G, N); float32, the state (b, G, R, P, N) zero at the
        start. Positions go in blocks of ``chunk_size`` under
        ``jax.checkpoint``."""
        mm = self.mm

        def token(state, t):
            a_t, dtx_t, b_t, c_t = t
            state = a_t[..., None, None] * state \
                + mm("bgrp,bgn->bgrpn", dtx_t, b_t)
            return state, mm("bgrpn,bgn->bgrp", state, c_t)

        @jax.checkpoint
        def block(state, ts):
            return jax.lax.scan(token, state, ts)

        length = a.shape[0]
        step = math.gcd(length, self.s["chunk"])
        state = jnp.zeros(dtx.shape[1:] + B.shape[-1:], jnp.float32)
        _, y = jax.lax.scan(block, state, tuple(
            t.reshape((length // step, step) + t.shape[1:])
            for t in (a, dtx, B, C)))
        return y.reshape((length,) + y.shape[2:])

    def mamba(self, p, u):
        s, mm = self.s, self.mm
        b, length, _ = u.shape
        H, P, G, N = s["m_heads"], s["m_head"], s["groups"], s["state"]
        inner, taps = s["inner"], s["taps"]
        zxbcdt = mm("bsh,hk->bsk", u, p["in_proj"]["kernel"])
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + s["conv_dim"]], -1)
        if self.fault != "conv_left_out":
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            xbc = sum(padded[:, k:k + length] * p["conv"]["kernel"][k]
                      for k in range(taps)) + p["conv"]["bias"]
        xbc = jax.nn.silu(xbc)
        x, B, C = jnp.split(xbc, [inner, inner + G * N], -1)
        x = x.reshape(b, length, G, H // G, P)
        dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(b, length, G, H // G)
        a = jnp.exp(dt * -jnp.exp(p["A_log"]).reshape(G, H // G))
        if self.fault == "decay_left_out":
            a = jnp.ones_like(a)
        y = self._recurrence(*(jnp.moveaxis(t, 1, 0) for t in (
            a, dt[..., None] * x, B.reshape(b, length, G, N),
            C.reshape(b, length, G, N))))
        y = jnp.moveaxis(y, 0, 1) + p["D"].reshape(G, H // G, 1) * x
        gated = (y.reshape(b, length, inner) * jax.nn.silu(z)).reshape(
            b, length, G, inner // G)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(jnp.square(gated), -1, keepdims=True)
            + self.cfg["layer_norm_epsilon"])
        return mm("bsk,kh->bsh", gated.reshape(b, length, inner)
                  * p["gate_norm"]["scale"], p["out_proj"]["kernel"])

    # -- E: the experts held here, for one block of tokens ------------------
    def _experts(self, p, u, chosen, weights):
        first = self.s["first_held"]

        def add_expert(y, expert):
            e, w_up, w_down = expert
            w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            act = relu2(self.mm("th,hf->tf", u, w_up))
            return y + w_e[:, None] * self.mm("tf,fh->th", act, w_down), None

        return jax.lax.scan(add_expert, jnp.zeros_like(u), (
            jnp.arange(self.s["held"]), p["w_up"], p["w_down"]))[0]

    def _shared(self, p, u):
        return self.mm("tf,fh->th", relu2(self.mm(
            "th,hf->tf", u, p["up"]["kernel"])), p["down"]["kernel"])

    def _moe_block(self, p, u, chosen, weights):
        y = self._experts(p["moe"], u, chosen, weights)
        if self.fault != "shared_expert_left_out":
            y = y + self._shared(p["shared"], u)
        return y

    def moe(self, p, u):
        s = self.s
        b, length, h = u.shape
        logits = self.mm("bsh,he->bse", u, p["moe"]["router"]["kernel"])
        scores = jax.nn.softmax(logits, -1) \
            if self.fault == "softmax_for_sigmoid" else jax.nn.sigmoid(logits)
        top, chosen = jax.lax.top_k(scores, s["top_k"])     # b is zero
        weights = s["scale"] * top / jnp.sum(top, -1, keepdims=True)
        tokens = b * length
        step = min(TOKEN_BLOCK, tokens)
        if tokens % step:
            raise ValueError(f"{tokens} tokens are no multiple of the token "
                             f"block {step}")
        block = jax.checkpoint(self._moe_block)
        return jax.lax.map(lambda t: block(p, *t), tuple(
            t.reshape(tokens // step, step, -1)
            for t in (u, chosen, weights))).reshape(b, length, h)

    # -- *: attention of one key-value head's group over a block of queries -
    def _attend(self, q, k, v, first):
        """``q`` (rows, block, group, d) are the queries from position
        ``first`` on, ``k`` and ``v`` (rows, S, d) every key."""
        scores = self.mm("bqgd,bkd->bgqk", q, k) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        t = first + jnp.arange(q.shape[1])[:, None]
        keep = jnp.arange(k.shape[1])[None, :] <= t
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), -1)
        return self.mm("bgqk,bkd->bqgd", probs, v)

    def attention(self, p, u):
        s, mm = self.s, self.mm
        b, length, _ = u.shape
        n, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
        qkv = mm("bsh,hk->bsk", u, p["qkv"]["shard"]["kernel"])
        q, k, v = jnp.split(qkv, [n * d, (n + kv) * d], -1)
        k, v = k.reshape(b, length, kv, d), v.reshape(b, length, kv, d)
        step = min(QUERY_BLOCK, length)
        blocks = length // step
        if blocks * step != length:
            raise ValueError(f"sequence length {length} is no multiple of "
                             f"the query block {step}")
        q = q.reshape(b, blocks, step, kv, n // kv, d).transpose(
            3, 1, 0, 2, 4, 5)                     # (kv, blocks, b, step, g, d)
        attend = jax.checkpoint(self._attend)

        def head(qkv_j):
            q_j, k_j, v_j = qkv_j
            return jax.lax.map(
                lambda blk: attend(blk[1], k_j, v_j, blk[0] * step),
                (jnp.arange(blocks), q_j))

        out = jax.lax.map(head, (q, jnp.moveaxis(k, 2, 0),
                                 jnp.moveaxis(v, 2, 0)))
        out = out.transpose(2, 1, 3, 0, 4, 5).reshape(b, length, n * d)
        return mm("bsk,kh->bsh", out, p["out"]["shard"]["kernel"])

    def block(self, kind, p, x):
        u = rms(x, p["norm"], self.cfg["norm_eps"])
        if kind == "mamba":
            return x + self.mamba(p["mixer"], u)
        if kind == "moe":
            return x + self.moe(p, u)
        if kind == "attention":
            return x + self.attention(p["attention"], u)
        raise ValueError(f"unknown kind of layer {kind!r}")

    def _head_block(self, p, x, labels, counted):
        logits = self.mm("bsh,hv->bsv",
                         rms(x, p["ln_f"], self.cfg["norm_eps"]),
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

def layers_of(cfg, kind):
    return kinds_held(cfg).count(kind)


def kept_pairs(seq_len):
    """(query, key) pairs the causal mask keeps in one sequence."""
    return seq_len * (seq_len + 1) // 2


def expert_rows(cfg, tokens):
    """Rows the routed experts held here are expected to compute a step and
    layer: ``top_k`` of the published experts a token, the held share of
    them (6 x 8 / 128 = 0.375 a token at the published sizes). A fraction
    with the published count as denominator, kept whole: (numerator,
    denominator)."""
    s = sizes(cfg)
    return tokens * s["top_k"] * s["held"], s["experts"]


def scan_flops_per_token(cfg):
    """FLOPs of the recurrence itself for one token of one ``M`` layer,
    forward: per head and entry of its (P, N) state a multiply by the
    decay, a multiply and an add for ``dt x B^T``, a multiply and an add
    for ``S C``: 5 P N a head."""
    s = sizes(cfg)
    return 5 * s["m_heads"] * s["m_head"] * s["state"]


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need: 6 a token for
    every parameter of the mixers' projections, the router, the shared
    expert, the attention projections and the head (the vocabulary rows ids
    are drawn from); 6 a routed row for an expert's two matrices, for the
    rows expected here (experts active, not held); 12 x head size a kept
    pair and query head for attention's products; 3 x the recurrence's own
    forward count a token and ``M`` layer."""
    s = sizes(cfg)
    h, n, kv, d = s["hidden"], s["heads"], s["kv_heads"], s["head_dim"]
    tokens = sequences * seq_len
    mamba = h * (s["inner"] + s["conv_dim"] + s["m_heads"]) + s["inner"] * h
    moe = h * s["experts"] + 2 * h * s["shared"]
    attention = h * (n + 2 * kv) * d + n * d * h
    dense = layers_of(cfg, "mamba") * mamba + layers_of(cfg, "moe") * moe \
        + layers_of(cfg, "attention") * attention + h * cfg["vocab_size"]
    rows, over = expert_rows(cfg, tokens)
    experts = layers_of(cfg, "moe") * 6 * 2 * h * s["expert"] * rows // over
    pairs = layers_of(cfg, "attention") * kept_pairs(seq_len)
    scan = layers_of(cfg, "mamba") * 3 * scan_flops_per_token(cfg) * tokens
    return 6 * dense * tokens + experts + 12 * n * d * pairs * sequences \
        + scan


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (the
    attention layers held): forward 4 D a kept pair and query head,
    backward 8 D; q and o at the query heads' width, k and v at the
    key-value heads', once forward; q, k, v, o, do, dq, dk, dv once
    backward."""
    s = sizes(cfg)
    d, n, kv = s["head_dim"], s["heads"], s["kv_heads"]
    layers = layers_of(cfg, "attention")
    unit = sequences * n * d * layers * kept_pairs(seq_len)
    row = sequences * seq_len * d * bytes_per_element * layers
    return {
        "fwd": {"flops": 4 * unit, "bytes": (2 * n + 2 * kv) * row},
        "bwd": {"flops": 8 * unit, "bytes": (4 * n + 4 * kv) * row},
    }


def expert_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the routed experts' grouped products over one
    step (all ``E`` layers), whatever implements them: per routed row
    expected here 2 FLOPs a parameter of an expert's two matrices forward,
    4 backward. Bytes: the experts' matrices held once, a row's input and
    output at the hidden width and its activation at the expert width once,
    forward; those and their gradients backward. The shared expert is two
    dense products and not counted here."""
    s = sizes(cfg)
    h, f, layers = s["hidden"], s["expert"], layers_of(cfg, "moe")
    rows, over = expert_rows(cfg, sequences * seq_len)
    unit = layers * 2 * h * f * rows // over
    held = layers * s["held"] * 2 * h * f * bytes_per_element
    per_row = layers * (2 * h + 2 * f) * bytes_per_element * rows // over
    return {
        "fwd": {"flops": 2 * unit, "bytes": held + per_row},
        "bwd": {"flops": 4 * unit, "bytes": 2 * (held + per_row)},
    }


def scan_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the state-space scans over one step (all
    ``M`` layers), whatever implements them: the recurrence's own FLOPs
    (``scan_flops_per_token``) forward, twice that backward. Bytes: its
    inputs and its output once each way: x and y at the inner width, B and
    C at groups x state, in the activations' dtype, and the float32 step
    ``dt`` a head, forward; x, B, C, ``dt`` and y's gradient read and the
    four gradients written, backward. States that need never reach HBM are
    not counted."""
    s = sizes(cfg)
    tokens, layers = sequences * seq_len, layers_of(cfg, "mamba")
    unit = layers * tokens * scan_flops_per_token(cfg)
    wide = s["inner"] * bytes_per_element
    narrow = 2 * s["groups"] * s["state"] * bytes_per_element \
        + 4 * s["m_heads"]
    row = layers * tokens
    return {
        "fwd": {"flops": unit, "bytes": row * (2 * wide + narrow)},
        "bwd": {"flops": 2 * unit, "bytes": row * (3 * wide + 2 * narrow)},
    }

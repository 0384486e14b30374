"""``kimi_linear_decoder`` (Kimi-Linear, ``model_type`` ``kimi_linear``):
Kimi Delta Attention (KDA) in three of every four layers, latent attention
without positions in the rest, a leading dense layer, then expert layers of
which one chip holds a share.

``d`` = ``hidden_size``, ``eps`` = ``rms_norm_eps``, ``RMSNorm(x; g) = x /
sqrt(mean(x^2) + eps) * g``, every product without bias. Layer ``i``
(0-based) has the mixer KDA where ``i + 1`` is in
``linear_attn_config.kda_layers``, MLA where it is in ``full_attn_layers``:

    h = Embedding[ids]
    a = RMSNorm(h; g_in)
    KDA (H = ``linear_attn_config.num_heads`` heads of D = ``head_dim``):
      [q | k | v] = SiLU(conv([a W_q | a W_k | a W_v]))   depthwise, causal,
                    ``short_conv_kernel_size`` taps (tap K-1 reads the
                    position itself), zeros before the start, no bias
      q_h <- q_h / |q_h| D^-1/2;  k_h <- k_h / |k_h|
      beta = sigmoid(a W_b);  g = -exp(A_log_h) softplus(a W_fa W_fb + dt_bias)
      per head, S (D x D) zero at the start, one token at a time:
        S' = Diag(exp g_t) S_{t-1};  u = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u^T;  o_t = S_t^T q_t
      o_h <- RMSNorm_D(o_h; g_o) sigmoid(a W_ga W_gb)_h  (``assumed``: eps,
                    the gate paths' rank); mixer = o W_o
    MLA (``q_lora_rank`` null, ``mla_use_nope``):
      [q_nope | q_pe] = a W_q             per head: ``qk_nope_head_dim`` |
                                          ``qk_rope_head_dim``
      [c_kv | k_pe] = a W_kva;  c_kv <- RMSNorm(c_kv; g_kva)   (one k_pe)
      [k_nope | v] = c_kv W_kvb           per head: nope | ``v_head_dim``
      q = [q_nope | q_pe], k = [k_nope | k_pe] (k_pe the same for every
      head, nothing rotated)
      o = softmax(q k^T / sqrt(nope + rope), j <= t) v;  mixer = o W_o
    h <- h + mixer
    m = RMSNorm(h; g_post)
    layer 0 .. ``first_k_dense_replace`` - 1: f = (silu(m W_gate) * (m
        W_up)) W_down at ``intermediate_size``
    every later layer: s = sigmoid(m W_r) float32 over the published
        ``num_experts``; S = the ``num_experts_per_token`` largest of s + b;
        w_e = ``routed_scaling_factor`` s_e / sum of s over S; f = shared(m)
        + sum over e in S HELD HERE of w_e (silu(m W_gate,e) * (m W_up,e))
        W_down,e (shared: the dense form at ``moe_intermediate_size``)
    h <- h + f
    out = RMSNorm(h_last; g_final) W_head; next-token cross entropy over
    the vocabulary rows held, positions 0..S-2; no auxiliary loss.

The delta rule is computed AS WRITTEN, one token at a time in float32
(``lax.scan``; its two contractions through ``mm`` like every other
product, the outer product elementwise), with ``jax.checkpoint`` round each
segment of :data:`SEGMENT` positions so that the backward pass keeps one
state a segment and not one a token: the program runs the chunked WY form,
so the two share no algorithm. Latent attention, the routed experts, the
shared expert, the dense feed-forward and the loss are JoyAI's reference
code (``joyai_flash_decoder``), loaded through ``harness/arch.py``.

``cfg["planted_fault"]`` (never in a configuration file; set by
``tools/arch_faults.py`` alone) plants one fault of this architecture's
own: see ``FAULTS``.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.harness import arch

_JOY = arch.load("joyai_flash_decoder")
rms = _JOY.rms

# The rehearsal computes in float32, as the other sparse configurations'
# do; the query and key heads of MLA stay wider than its value heads.
REHEARSE = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "intermediate_size": 192,
            "moe_intermediate_size": 32, "num_experts": 2,
            "num_experts_per_token": 2,
            "linear_attn_config": {"num_heads": 4, "head_dim": 16},
            "published": {"num_experts": 16}, "vocab_size": 500,
            "assumed": {"compute_dtype": "float32", "kda_gate_rank": 16}}
SEGMENT = 64
KDA_HEAD_GROUP = 8
FAULTS = ("beta_left_out_of_the_delta", "decay_after_the_update",
          "qk_l2_norm_left_out", "output_gate_sigmoid_left_out",
          "mla_key_part_rotated")


def sizes(cfg):
    """The sizes the shapes and the counts need, under plain names."""
    lin = cfg["linear_attn_config"]
    if cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"] or cfg["num_expert_group"] != 1 \
            or cfg["topk_group"] != 1:
        raise ValueError("this architecture states sigmoid scores normed "
                         "over the chosen with no group limit "
                         "(moe_router_activation_func, moe_renormalize, "
                         "num_expert_group, topk_group)")
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"] \
            or cfg["num_nextn_predict_layers"] or cfg["moe_layer_freq"] != 1:
        raise ValueError("this architecture states MLA with no query latent "
                         "and no positions, no MTP module and every layer "
                         "after the dense ones sparse (q_lora_rank, "
                         "mla_use_nope, num_nextn_predict_layers, "
                         "moe_layer_freq)")
    a = cfg["assumed"]
    return dict(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        k_heads=lin["num_heads"], k_dim=lin["head_dim"],
        taps=lin["short_conv_kernel_size"], rank=a["kda_gate_rank"],
        dense=cfg["intermediate_size"], expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        experts=cfg["published"]["num_experts"], held=cfg["num_experts"],
        first_held=cfg.get("deployment", {}).get("first_expert_held", 0),
        top_k=cfg["num_experts_per_token"],
        scale=cfg["routed_scaling_factor"],
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        vocab_rows=a["vocab_rows"])


def kind_of_layer(cfg, i):
    """(mixer, ffn) of the i-th layer (0-based): ``kda`` | ``mla``,
    ``dense`` | ``sparse``."""
    lin = cfg["linear_attn_config"]
    if i + 1 in lin["kda_layers"]:
        mixer = "kda"
    elif i + 1 in lin["full_attn_layers"]:
        mixer = "mla"
    else:
        raise ValueError(f"layer {i + 1} is in neither kda_layers nor "
                         f"full_attn_layers")
    return mixer, "dense" if i < cfg["first_k_dense_replace"] else "sparse"


# -- names and shapes -------------------------------------------------------

def _layer_shapes(s, kind):
    h, mixer, ffn = s["hidden"], *kind

    def norm(width):
        return {"scale": (width,)}

    def column(rows, cols):
        return {"shard": {"kernel": (rows, cols)}}

    def swiglu(width):
        return {"gate_up": column(h, 2 * width), "out": column(width, h)}

    layer = {"input_norm": norm(h), "post_attn_norm": norm(h)}
    if mixer == "kda":
        inner = s["k_heads"] * s["k_dim"]
        layer["kda"] = {
            "qkv": column(h, 3 * inner), "b_proj": column(h, s["k_heads"]),
            "f_a": {"kernel": (h, s["rank"])}, "f_b": column(s["rank"], inner),
            "g_a": {"kernel": (h, s["rank"])}, "g_b": column(s["rank"], inner),
            "conv": {"kernel": (s["taps"], 3 * inner)},
            "A_log": (s["k_heads"],), "dt_bias": (inner,),
            "o_norm": norm(s["k_dim"]), "o_proj": column(inner, h)}
    else:
        n = s["heads"]
        layer["attention"] = {
            "q": column(h, n * (s["nope"] + s["rope"])),
            "kv_a": {"kernel": (h, s["kv_rank"] + s["rope"])},
            "kv_a_norm": norm(s["kv_rank"]),
            "kv_b": column(s["kv_rank"], n * (s["nope"] + s["v"])),
            "out": column(n * s["v"], h)}
    if ffn == "dense":
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
    tree = {"embed": {"embedding": (v, h)}, "ln_f": {"scale": (h,)},
            "lm_head": {"kernel": (h, v)}}
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = _layer_shapes(s, kind_of_layer(cfg, i))
    return tree


def fused_parts(cfg):
    """KDA's [q | k | v] product and its convolution are three equal parts;
    the dense feed-forward's, the shared expert's and the experts' first
    products [gate | up] two."""
    s = sizes(cfg)
    out = {}
    for i in range(s["layers"]):
        at = (f"layer_{i}",)
        mixer, ffn = kind_of_layer(cfg, i)
        if mixer == "kda":
            out[at + ("kda", "qkv", "shard", "kernel")] = 3
            out[at + ("kda", "conv", "kernel")] = 3
        if ffn == "dense":
            out[at + ("mlp", "gate_up", "shard", "kernel")] = 2
        else:
            out[at + ("shared", "gate_up", "shard", "kernel")] = 2
            out[at + ("moe", "w_gate_up")] = 2
    return out


def fresh_leaf(cfg, path, shape):
    """Every leaf but the norms' scales (one by the shared rule) is drawn by
    itself from the key folded with the leaf's position. Matrices are
    normal(``init_std``), the embedding's rows normal(``assumed.
    embedding_std``). KDA's own leaves start as Mamba-2's do
    (``assumed``): ``A_log`` the log of a uniform draw in ``a_log_range``,
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly in
    ``dt_bias_step_range`` and floored at ``dt_bias_step_floor``, the
    convolution uniform in +-1 / sqrt(taps)."""
    name, a = path[-1], cfg["assumed"]
    if name == "scale":
        return None
    if name == "A_log":
        lo, hi = a["a_log_range"]
        return lambda key: jnp.log(jax.random.uniform(
            key, shape, jnp.float32, lo, hi))
    if name == "dt_bias":
        lo, hi = (math.log(t) for t in a["dt_bias_step_range"])

        def dt_bias(key):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, lo, hi)), a["dt_bias_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        return dt_bias
    if path[-2:] == ("conv", "kernel"):
        bound = 1.0 / math.sqrt(shape[0])
        return lambda key: jax.random.uniform(key, shape, jnp.float32,
                                              -bound, bound)
    std = a.get("embedding_std", a["init_std"]) if name == "embedding" \
        else a["init_std"]
    return lambda key: jax.random.normal(key, shape, jnp.float32) * std


# -- the network -------------------------------------------------------------

def l2_normed(x):
    return x * jax.lax.rsqrt(jnp.maximum(
        jnp.sum(jnp.square(x), -1, keepdims=True), 1e-12))


class Net:
    """Embed, blocks by (mixer, ffn), head + loss. Each method takes its own
    sub-tree of the parameters. The routed experts, the shared expert, the
    dense feed-forward, one head's attention and the loss are JoyAI's
    reference methods; they read ``s``, ``mm``, ``bias`` and ``fault``
    alike."""

    _attend = _JOY.Net._attend
    _gated = _JOY.Net._gated
    _swiglu = _JOY.Net._swiglu
    _experts = _JOY.Net._experts
    sparse = _JOY.Net.sparse
    _head_block = _JOY.Net._head_block
    _loss_sum = _JOY.Net._loss_sum

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
                {"ln_f": params["ln_f"], "lm_head": params["lm_head"]})

    def join(self, embed, layers, head):
        """The gradient as a tree named like the parameters, handed back on
        the host as the other sparse architectures do and for their reason:
        ``Reference.adam`` keeps old and new state (24 B a parameter, 13.5
        GiB at this cut's 602.4M), and a gradient left on the device beside
        them does not fit."""
        tree = {"embed": embed, **head}
        tree.update({f"layer_{i}": g for i, g in enumerate(layers)})
        return jax.device_get(tree)

    def embed(self, p, batch):
        return p["embedding"][batch["ids"]]

    def kind_of(self, i):
        return kind_of_layer(self.cfg, i)

    # -- KDA: the delta rule itself, one token at a time --------------------
    def _delta_rule(self, q, k, v, g, beta):
        """``o`` (L, b, H, D) from ``q``, ``k``, ``v``, ``g`` (L, b, H, D)
        and ``beta`` (L, b, H), float32; the state (b, H, D, D) zero at the
        start. Positions go in segments of :data:`SEGMENT` under
        ``jax.checkpoint``."""
        mm, fault = self.mm, self.fault

        def token(S, t):
            q_t, k_t, v_t, g_t, b_t = t
            decay = jnp.exp(g_t)[..., None]
            if fault != "decay_after_the_update":
                S = decay * S
            u = v_t - mm("bhkv,bhk->bhv", S, k_t)
            if fault != "beta_left_out_of_the_delta":
                u = b_t[..., None] * u
            S = S + k_t[..., None] * u[..., None, :]
            if fault == "decay_after_the_update":
                S = decay * S
            return S, mm("bhkv,bhk->bhv", S, q_t)

        @jax.checkpoint
        def segment(S, ts):
            return jax.lax.scan(token, S, ts)

        length = q.shape[0]
        step = math.gcd(length, SEGMENT)
        S = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
        _, o = jax.lax.scan(segment, S, tuple(
            t.reshape((length // step, step) + t.shape[1:])
            for t in (q, k, v, g, beta)))
        return o.reshape((length,) + o.shape[2:])

    def kda(self, p, a):
        """The mixer by sequence and by group of :data:`KDA_HEAD_GROUP`
        heads, each under ``jax.checkpoint``, the output projection summed
        over the groups: a layer's float32 projections, convolution, gates
        and token states for every sequence and head at once are more than
        the chip holds beside the reference's parameters and gradient (6.9
        GB asked for with 6.5 free on a TPU v5e)."""
        s, mm = self.s, self.mm
        H, D = s["k_heads"], s["k_dim"]
        n = H // math.gcd(H, KDA_HEAD_GROUP)

        def by_group(w, parts, width):
            """Columns [part][head][width] of ``w`` as (groups, rows, parts x
            a group's heads x width)."""
            rows = w.shape[0]
            return jnp.moveaxis(w.reshape(rows, parts, n, -1), 2, 0).reshape(
                n, rows, parts * (H // n) * width)
        groups = {
            "qkv": by_group(p["qkv"]["shard"]["kernel"], 3, D),
            "conv": by_group(p["conv"]["kernel"], 3, D),
            "b_proj": by_group(p["b_proj"]["shard"]["kernel"], 1, 1),
            "f_b": by_group(p["f_b"]["shard"]["kernel"], 1, D),
            "g_b": by_group(p["g_b"]["shard"]["kernel"], 1, D),
            "dt_bias": p["dt_bias"].reshape(n, -1),
            "A_log": p["A_log"].reshape(n, -1),
            "o_proj": p["o_proj"]["shard"]["kernel"].reshape(
                n, -1, s["hidden"])}
        low = (mm("bsh,hr->bsr", a, p["f_a"]["kernel"]),
               mm("bsh,hr->bsr", a, p["g_a"]["kernel"]))
        heads = jax.checkpoint(self._kda_heads)

        def row(args):
            def add(out, g):
                return out + heads(p["o_norm"]["scale"], g, *args), None
            return jax.lax.scan(add, jnp.zeros_like(args[0]), groups)[0]
        return jax.lax.map(row, (a, *low))

    def _kda_heads(self, scale, g, a, fa, ga):
        """One sequence's mixer output from one group of heads: ``a``
        (S, d) the normed input, ``fa``, ``ga`` (S, rank) its two low-rank
        gate products, ``g`` the group's columns of every matrix."""
        s, mm = self.s, self.mm
        length, D, taps = a.shape[0], s["k_dim"], s["taps"]
        heads = g["A_log"].shape[0]
        qkv = mm("sh,hk->sk", a, g["qkv"])
        padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[t:t + length] * g["conv"][t]
                              for t in range(taps)))
        q, k, v = (t.reshape(length, 1, heads, D)
                   for t in jnp.split(qkv, 3, -1))
        if self.fault == "qk_l2_norm_left_out":
            q = q * D ** -0.5
        else:
            q, k = l2_normed(q) * D ** -0.5, l2_normed(k)
        beta = jax.nn.sigmoid(mm("sh,hk->sk", a, g["b_proj"]))[:, None]
        f = mm("sr,rk->sk", fa, g["f_b"]) + g["dt_bias"]
        decay = -jnp.exp(g["A_log"])[:, None] * jax.nn.softplus(
            f.reshape(length, 1, heads, D))
        o = self._delta_rule(q, k, v, decay, beta)[:, 0]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + self.cfg["assumed"]["kda_output_norm_eps"]) \
            * scale
        gate = mm("sr,rk->sk", ga, g["g_b"]).reshape(o.shape)
        if self.fault != "output_gate_sigmoid_left_out":
            gate = jax.nn.sigmoid(gate)
        return mm("sk,kh->sh", (o * gate).reshape(length, heads * D),
                  g["o_proj"])

    # -- MLA: no query latent, no positions ---------------------------------
    def attention(self, p, a):
        s, mm = self.s, self.mm
        b, length, _ = a.shape
        n, nope, rope, dv = s["heads"], s["nope"], s["rope"], s["v"]
        q = mm("bsh,hk->bsk", a, p["q"]["shard"]["kernel"]).reshape(
            b, length, n, nope + rope)
        c_kv, k_pe = jnp.split(mm("bsh,hr->bsr", a, p["kv_a"]["kernel"]),
                               [s["kv_rank"]], -1)
        kv = mm("bsr,rk->bsk", rms(c_kv, p["kv_a_norm"], self.eps),
                p["kv_b"]["shard"]["kernel"]).reshape(b, length, n,
                                                      nope + dv)
        k_pe = k_pe[:, :, None]
        if self.fault == "mla_key_part_rotated":
            theta = self.cfg["rope_theta"]
            q = jnp.concatenate([q[..., :nope], _JOY.rope_interleaved(
                q[..., nope:], theta)], -1)
            k_pe = _JOY.rope_interleaved(k_pe, theta)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, (b, length, n, rope))], -1)
        v = kv[..., nope:]
        scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))
        # Rolled loops (one compiled body): over the heads and, inside,
        # over blocks of queries; each block under jax.checkpoint.
        step = min(_JOY.QUERY_BLOCK, length)
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

    def block(self, kind, p, x):
        mixer, ffn = kind
        a = rms(x, p["input_norm"], self.eps)
        x = x + (self.kda(p["kda"], a) if mixer == "kda"
                 else self.attention(p["attention"], a))
        m = rms(x, p["post_attn_norm"], self.eps)
        if ffn == "dense":
            return x + self._swiglu(p["mlp"], m.reshape(-1, m.shape[-1])) \
                .reshape(m.shape)
        return x + self.sparse(p, m)

    def head_loss(self, p, x, batch):
        """Sum over these rows of a row's mean next-token loss."""
        h = rms(x, p["ln_f"], self.eps)
        return self._loss_sum(p["lm_head"]["kernel"], h, batch["ids"], 1) \
            / (x.shape[1] - 1)


# -- work counts: what the algorithm needs of this share ---------------------

def layers_of(cfg, mixer):
    return sum(kind_of_layer(cfg, i)[0] == mixer
               for i in range(cfg["num_hidden_layers"]))


def kept_pairs(seq_len):
    """(query, key) pairs the causal mask keeps in one sequence."""
    return seq_len * (seq_len + 1) // 2


def expert_rows(cfg, tokens):
    """Rows the routed experts held here are expected to compute a step and
    layer: ``top_k`` of the published experts a token, the held share of
    them (8 x 8 / 256 = 0.25 a token at the published sizes). A fraction
    with the published count as denominator: (numerator, denominator)."""
    s = sizes(cfg)
    return tokens * s["top_k"] * s["held"], s["experts"]


def delta_rule_flops_per_token(cfg):
    """FLOPs of the delta rule itself for one token of one KDA layer,
    forward: per head and entry of its (D, D) state a multiply by the
    decay, a multiply and an add for ``S'^T k``, a multiply and an add for
    the rank-one update, a multiply and an add for ``S^T q``: 7 D^2 a
    head."""
    s = sizes(cfg)
    return 7 * s["k_heads"] * s["k_dim"] * s["k_dim"]


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need: 6 a token for
    every parameter of the mixers' projections, the dense feed-forward, the
    routers, the shared experts and the head (the vocabulary rows held); 6
    a routed row for an expert's three matrices, for the rows expected
    here; 6 (qk width + v width) a kept pair and head for attention's
    products; 3 x the delta rule's own forward count a token and KDA
    layer."""
    s = sizes(cfg)
    h, r = s["hidden"], s["rank"]
    inner, n = s["k_heads"] * s["k_dim"], s["heads"]
    tokens = sequences * seq_len
    kda = 4 * h * inner + h * s["k_heads"] + 2 * (h * r + r * inner)
    mla = h * n * (s["nope"] + s["rope"]) + h * (s["kv_rank"] + s["rope"]) \
        + s["kv_rank"] * n * (s["nope"] + s["v"]) + n * s["v"] * h
    sparse = s["layers"] - s["dense_layers"]
    dense = layers_of(cfg, "kda") * kda + layers_of(cfg, "mla") * mla \
        + s["dense_layers"] * 3 * h * s["dense"] \
        + sparse * (h * s["experts"] + 3 * h * s["shared"]) \
        + h * cfg["vocab_size"]
    rows, over = expert_rows(cfg, tokens)
    experts = sparse * 6 * 3 * h * s["expert"] * rows // over
    pairs = layers_of(cfg, "mla") * n * kept_pairs(seq_len) * sequences
    rule = layers_of(cfg, "kda") * 3 * delta_rule_flops_per_token(cfg) \
        * tokens
    return 6 * dense * tokens + experts \
        + 6 * (s["nope"] + s["rope"] + s["v"]) * pairs + rule


def flash_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the flash kernels over one step (the MLA
    layers): forward 2 (Dqk + Dv) a kept pair and head, backward 4 (Dqk +
    Dv); q and k at the query/key width, v and o at the value width, once
    forward; q, k, v, o, dO, dQ, dK, dV once backward."""
    s = sizes(cfg)
    dqk, dv = s["nope"] + s["rope"], s["v"]
    layers = layers_of(cfg, "mla")
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
    step (all sparse layers), whatever implements them: per routed row
    expected here 2 FLOPs a parameter of an expert's three matrices
    forward, 4 backward; the experts' matrices held once, a row's input and
    output at the hidden width and its two activations at the expert width
    once forward, those and their gradients backward."""
    s = sizes(cfg)
    h, f = s["hidden"], s["expert"]
    layers = s["layers"] - s["dense_layers"]
    rows, over = expert_rows(cfg, sequences * seq_len)
    unit = layers * 3 * h * f * rows // over
    held = layers * s["held"] * 3 * h * f * bytes_per_element
    per_row = layers * (2 * h + 3 * f) * bytes_per_element * rows // over
    return {
        "fwd": {"flops": 2 * unit, "bytes": held + per_row},
        "bwd": {"flops": 4 * unit, "bytes": 2 * (held + per_row)},
    }


def kda_work(cfg, sequences, seq_len, bytes_per_element=2):
    """FLOPs and HBM bytes of the delta rule over one step (all KDA
    layers), whatever implements it: its own FLOPs
    (``delta_rule_flops_per_token``) forward, twice that backward. Bytes:
    its inputs and its output once each way: q, k, v and o in the
    activations' dtype, the float32 log decays g a channel and beta a
    head, forward; those read with o's gradient and their five gradients
    written, backward. States that need never reach HBM are not
    counted."""
    s = sizes(cfg)
    tokens, layers = sequences * seq_len, layers_of(cfg, "kda")
    unit = layers * tokens * delta_rule_flops_per_token(cfg)
    wide = s["k_heads"] * s["k_dim"]
    inputs = 3 * wide * bytes_per_element + 4 * wide + 4 * s["k_heads"]
    out = wide * bytes_per_element
    row = layers * tokens
    return {
        "fwd": {"flops": unit, "bytes": row * (inputs + out)},
        "bwd": {"flops": 2 * unit, "bytes": row * (2 * inputs + out)},
    }

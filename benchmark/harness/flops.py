"""Work counts from shapes: what the algorithm needs, not what a kernel does.

``step.mfu_pct`` and ``kernels.flash_roofline`` both read these functions,
so the kernels' count equals attention's part of the step's count and the
two can never disagree. Recomputed operations are not counted.
"""

# Which keys of a configuration file hold which size, per ``arch``.
_KEYS = {
    "pre_ln_causal_decoder": dict(
        hidden="n_embd", layers="n_layer", heads="n_head", inner="n_inner",
        causal=True),
    "post_ln_encoder_mlm_nsp": dict(
        hidden="hidden_size", layers="num_hidden_layers",
        heads="num_attention_heads", inner="intermediate_size",
        causal=False),
}


def sizes(cfg):
    """The sizes the counts need, under plain names."""
    k = _KEYS[cfg["arch"]]
    hidden = cfg[k["hidden"]]
    return dict(hidden=hidden, layers=cfg[k["layers"]], heads=cfg[k["heads"]],
                inner=cfg[k["inner"]], head_dim=hidden // cfg[k["heads"]],
                vocab_rows=cfg["assumed"]["vocab_rows"], causal=k["causal"])


def matmul_params(cfg):
    """(per_token, per_sequence): parameters that sit in matrix products.

    Block weights and the output head; not embeddings, positions, norms or
    biases. BERT's pooler and NSP head see one position a sequence, so they
    count per sequence.
    """
    s = sizes(cfg)
    h, f = s["hidden"], s["inner"]
    block = h * 3 * h + h * h + 2 * h * f
    per_token = s["layers"] * block + h * s["vocab_rows"]
    per_sequence = 0
    if cfg["arch"] == "post_ln_encoder_mlm_nsp":
        per_token += h * h            # MLM transform
        per_sequence = h * h + 2 * h  # pooler + NSP head
    return per_token, per_sequence


def attention_flops_per_token(cfg, seq_len):
    """Forward + backward attention products per token: 12 L s h, halved
    when causal."""
    s = sizes(cfg)
    full = 12 * s["layers"] * seq_len * s["hidden"]
    return full // 2 if s["causal"] else full


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
    counted). Halved when causal. Bytes: q, k, v, o read or written once
    forward; q, k, v, o, do, dq, dk, dv once backward.
    """
    s = sizes(cfg)
    b, h, d, layers = sequences, s["heads"], s["head_dim"], s["layers"]
    unit = b * h * seq_len * seq_len * d * layers
    if s["causal"]:
        unit //= 2
    tensor = b * h * seq_len * d * bytes_per_element * layers
    return {
        "fwd": {"flops": 4 * unit, "bytes": 4 * tensor},
        "bwd": {"flops": 8 * unit, "bytes": 8 * tensor},
    }


def flash_least_seconds(cfg, sequences, seq_len, peaks):
    """Least time the chip could take for the flash kernels' work of one
    step: per pass the larger of FLOPs / peak FLOP/s and bytes / peak
    bytes/s, summed over forward and backward."""
    work = flash_work(cfg, sequences, seq_len)
    return sum(max(w["flops"] / peaks["bf16_flops_per_s"],
                   w["bytes"] / peaks["hbm_bytes_per_s"])
               for w in work.values())

"""Registry entry ``smallthinker``: a configuration file -> the program's
SmallThinker and its loss, as ``models/gpt.py`` builds GPT. The sizes come
from the configuration under the source's own keys; the experts held and the
vocabulary rows are this chip's share (``deployment``, ``assumed``)."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.smallthinker import (SmallThinker,
                                             SmallThinkerConfig, layer_kinds)


def build(cfg):
    """(model, loss_fn) for a ``model: smallthinker`` configuration."""
    prog = cfg.get("program", {})
    config = SmallThinkerConfig(
        vocab_size=cfg["assumed"]["vocab_rows"],
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_size=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["published"]["moe_num_primary_experts"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        experts_held=cfg["moe_num_primary_experts"],
        first_expert_held=cfg.get("deployment", {}).get(
            "first_expert_held", 0),
        kinds=layer_kinds(cfg["rope_layout"], cfg["sliding_window_layout"],
                          cfg["num_hidden_layers"]),
        sliding_window=cfg["sliding_window_size"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        use_flash=prog.get("use_flash", True))
    model = SmallThinker(config)

    def loss_fn(params, batch):
        ids = batch["ids"]
        logits = model.apply({"params": params}, ids)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()

    return model, loss_fn

"""Registry entry ``afmoe``: a configuration file -> the program's Afmoe and
its loss, as ``models/smallthinker.py`` builds SmallThinker. The sizes come
from the configuration under the source's own keys; the layers and experts
held and the vocabulary rows are this chip's share (``deployment``,
``assumed``)."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.afmoe import Afmoe, AfmoeConfig, layer_kinds


def build(cfg):
    """(model, loss_fn) for a ``model: afmoe`` configuration."""
    prog = cfg.get("program", {})
    deployment = cfg.get("deployment", {})
    bias = cfg.get("selection_bias")
    config = AfmoeConfig(
        vocab_size=cfg["assumed"]["vocab_rows"],
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        dense_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        num_experts=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["route_scale"]),
        experts_held=cfg["num_experts"],
        first_expert_held=deployment.get("first_expert_held", 0),
        kinds=layer_kinds(cfg["layer_types"], cfg["num_dense_layers"],
                          deployment.get("layers_held",
                                         range(cfg["num_hidden_layers"]))),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        selection_bias=None if bias is None else tuple(bias),
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        use_flash=prog.get("use_flash", True))
    model = Afmoe(config)

    def loss_fn(params, batch):
        ids = batch["ids"]
        logits = model.apply({"params": params}, ids)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()

    return model, loss_fn

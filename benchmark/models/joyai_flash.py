"""Registry entry ``joyai_flash``: a configuration file -> the program's
JoyAIFlash and its two-term loss, as ``models/afmoe.py`` builds Afmoe. The
sizes come from the configuration under the source's own keys; the layers
and experts held and the vocabulary rows are this chip's share
(``deployment``, ``assumed``)."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.joyai_flash import JoyAIFlash, JoyAIFlashConfig


def build(cfg):
    """(model, loss_fn) for a ``model: joyai_flash`` configuration (one MTP
    depth, ``num_nextn_predict_layers`` 1: the architecture's file refuses
    another): the next-token loss plus ``assumed.mtp_loss_weight`` times
    the MTP module's loss on the token after next, both over the one
    head."""
    prog = cfg.get("program", {})
    deployment = cfg.get("deployment", {})
    bias = cfg.get("selection_bias")
    config = JoyAIFlashConfig(
        vocab_size=cfg["assumed"]["vocab_rows"],
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        dense_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        num_experts=cfg["published"]["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"],
        first_expert_held=deployment.get("first_expert_held", 0),
        selection_bias=None if bias is None else tuple(bias),
        num_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        use_flash=prog.get("use_flash", True))
    model = JoyAIFlash(config)
    weight = cfg["assumed"]["mtp_loss_weight"]

    def loss_fn(params, batch):
        ids = batch["ids"]
        logits, mtp_logits = model.apply({"params": params}, ids)
        main = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()
        return main + weight * optax.softmax_cross_entropy_with_integer_labels(
            mtp_logits[:, :-2].astype(jnp.float32), ids[:, 2:]).mean()

    return model, loss_fn

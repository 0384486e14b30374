"""Registry entry ``kimi_linear``: a configuration file -> the program's
KimiLinear and its next-token loss, as ``models/joyai_flash.py`` builds
JoyAIFlash. The sizes come from the configuration under the source's own
keys; the layers and experts held and the vocabulary rows are this chip's
share (``deployment``, ``assumed``)."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig


def build(cfg):
    """(model, loss_fn) for a ``model: kimi_linear`` configuration."""
    prog = cfg.get("program", {})
    deployment = cfg.get("deployment", {})
    lin = cfg["linear_attn_config"]
    a = cfg["assumed"]
    bias = cfg.get("selection_bias")
    config = KimiLinearConfig(
        vocab_size=a["vocab_rows"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_gate_rank=a["kda_gate_rank"],
        conv_kernel=lin["short_conv_kernel_size"],
        dense_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        num_experts=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_token"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["num_experts"],
        first_expert_held=deployment.get("first_expert_held", 0),
        selection_bias=None if bias is None else tuple(bias),
        num_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        kda_layers=tuple(lin["kda_layers"]), rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(a["compute_dtype"]),
        use_flash=prog.get("use_flash", True))
    model = KimiLinear(config)

    def loss_fn(params, batch):
        ids = batch["ids"]
        logits = model.apply({"params": params}, ids)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()

    return model, loss_fn

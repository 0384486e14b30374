"""Registry entry ``nemotron_h``: a configuration file -> the program's
NemotronH and its loss, as ``models/smallthinker.py`` builds SmallThinker.
The sizes come from the configuration under the source's own keys; the
experts held, the layers read off the pattern and the vocabulary rows are
this chip's share (``deployment``, ``assumed``)."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.nemotron_h import NemotronH, NemotronHConfig


def build(cfg):
    """(model, loss_fn) for a ``model: nemotron_h`` configuration."""
    prog = cfg.get("program", {})
    config = NemotronHConfig(
        vocab_size=cfg["assumed"]["vocab_rows"],
        hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        num_layers=cfg["num_hidden_layers"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        state_size=cfg["ssm_state_size"], state_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_experts=cfg["published"]["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        shared_expert_size=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"],
        first_expert_held=cfg.get("deployment", {}).get(
            "first_expert_held", 0),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        norm_eps=cfg["norm_eps"], gate_norm_eps=cfg["layer_norm_epsilon"],
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        use_flash=prog.get("use_flash", True))
    model = NemotronH(config)

    def loss_fn(params, batch):
        ids = batch["ids"]
        logits = model.apply({"params": params}, ids)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()

    return model, loss_fn

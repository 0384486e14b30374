"""Registry entry ``gpt``: a configuration file -> the program's GPT and its
loss, as ``chip_smoke.py`` builds them."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.gpt import GPT, GPTConfig


def build(cfg):
    """(model, loss_fn, init_batch_keys) for a ``model: gpt`` configuration."""
    prog = cfg.get("program", {})
    model = GPT(GPTConfig(
        vocab_size=cfg["assumed"]["vocab_rows"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"],
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        tp_axis=prog.get("tp_axis"), ep_axis=prog.get("ep_axis"),
        use_flash=prog.get("use_flash", True),
        remat=prog.get("remat", False)))

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["ids"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), batch["ids"][:, 1:]).mean()

    return model, loss_fn

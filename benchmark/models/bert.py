"""Registry entry ``bert``: a configuration file -> the program's
BertForPreTraining and its MLM + NSP loss, as ``bench.py``'s bert leg writes
it."""

import jax.numpy as jnp
import optax

from horovod_tpu.models.bert import BertConfig, BertForPreTraining


def build(cfg):
    """(model, loss_fn) for a ``model: bert`` configuration."""
    prog = cfg.get("program", {})
    model = BertForPreTraining(BertConfig(
        vocab_size=cfg["assumed"]["vocab_rows"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        dtype=jnp.dtype(cfg["assumed"]["compute_dtype"]),
        use_flash=prog.get("use_flash", True),
        remat=prog.get("remat", False)))

    def loss_fn(params, batch):
        mlm_logits, nsp_logits = model.apply({"params": params},
                                             batch["ids"])
        mlm = optax.softmax_cross_entropy_with_integer_labels(
            mlm_logits, batch["mlm"]).mean()
        nsp = optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, batch["nsp"]).mean()
        return mlm + nsp

    return model, loss_fn

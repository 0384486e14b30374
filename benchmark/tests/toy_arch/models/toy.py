"""Registry entry ``toy``: the program side of ``toy_rms_alternating``, a
flax module under the names ``archs/toy_rms_alternating.py`` states, in
float32. A real kind builds one of the program's own model classes
(``models/gpt.py``); the toy has none there, so it is written out here, on
purpose by other statements than the reference's (per-head loops, a
``where`` on position differences)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax


class Norm(nn.Module):
    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


class Block(nn.Module):
    heads: int
    head_dim: int
    inner: int
    window: int      # 0: every earlier position

    @nn.compact
    def __call__(self, x):
        h, n, d = x.shape[-1], self.heads, self.head_dim
        init = nn.initializers.normal(0.02)
        y = Norm(name="norm_attn")(x)
        q = y @ self.param("wq", init, (h, n * d))
        k = y @ self.param("wk", init, (h, n * d))
        v = y @ self.param("wv", init, (h, n * d))
        wo = self.param("wo", init, (n * d, h))
        gate = self.param("gate", nn.initializers.ones, (n,)) \
            if self.window else jnp.ones((n,))
        ahead = jnp.arange(x.shape[1])[:, None] - jnp.arange(x.shape[1])
        seen = (ahead >= 0) & ((ahead < self.window) if self.window
                               else True)
        outs = []
        for i in range(n):
            cut = slice(i * d, (i + 1) * d)
            scores = jnp.einsum("bqd,bkd->bqk", q[..., cut], k[..., cut])
            probs = jax.nn.softmax(
                jnp.where(seen, scores / d ** 0.5, -jnp.inf), -1)
            outs.append(gate[i] * jnp.einsum("bqk,bkd->bqd", probs,
                                             v[..., cut]))
        x = x + jnp.concatenate(outs, -1) @ wo
        y = Norm(name="norm_mlp")(x)
        up = y @ self.param("w_up", init, (h, self.inner))
        y = nn.silu(y @ self.param("w_gate", init, (h, self.inner))) * up
        return x + y @ self.param("w_down", init, (self.inner, h))


class Toy(nn.Module):
    vocab: int
    hidden: int
    layers: int
    head_dim: int
    heads: int
    swa_heads: int
    inner: int
    window: int

    @nn.compact
    def __call__(self, ids):
        x = nn.Embed(self.vocab, self.hidden, name="tok_emb")(ids)
        for i in range(self.layers):
            x = Block(self.swa_heads if i % 2 else self.heads, self.head_dim,
                      self.inner, self.window if i % 2 else 0,
                      name=f"layer_{i}")(x)
        return nn.Dense(self.vocab, use_bias=False, name="lm_head")(
            Norm(name="norm_f")(x))


def build(cfg):
    """(model, loss_fn) for a ``model: toy`` configuration."""
    model = Toy(cfg["assumed"]["vocab_rows"], cfg["hidden_size"],
                cfg["num_hidden_layers"], cfg["head_dim"],
                cfg["num_attention_heads"], cfg["swa_num_attention_heads"],
                cfg["intermediate_size"], cfg["sliding_window"])

    def loss_fn(params, batch):
        with jax.default_matmul_precision("highest"):
            logits = model.apply({"params": params}, batch["ids"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], batch["ids"][:, 1:]).mean()

    return model, loss_fn

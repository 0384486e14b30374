"""The device scopes of the compiled step: one list, one way in.

Time the program spends on the device belongs to a name the program gave
it. A name is a ``jax.named_scope`` on the ``op_name`` of the ops traced
under it (HLO metadata: the lowered program is the same with and without);
a profile, and the benchmark's readers, find an op's name on that path.
Every scope on the compiled step's path is entered through :func:`scope`
with a name of :data:`SCOPES`, so the list is what a reader can count on
(``tests/test_step_tracing.py`` holds the HLO of the four models to it;
``docs/observability.md`` is the catalogue with who reads what).

An op belongs to the INNERMOST listed name on its path (the last one:
under ``jax.checkpoint`` and ``custom_vjp`` a path repeats itself, and JAX
wraps the first component a transform meets, ``transpose(jvp(x))``).
Three kinds:

``leaf``       the op's time is that name's (leaves may nest: the
               innermost wins, ``hvd.grad_exchange`` inside
               ``hvd.optimizer``);
``container``  groups leaves; an op whose innermost name is a container
               has no leaf of its own and counts as unnamed;
``rule``       no scope is entered anywhere: the name is given by rule to
               what is left directly under the container ``under``. The
               loss is written by the user of the package, outside every
               model, so ``lm.loss`` is what lies under
               ``hvd.loss_and_grad`` and outside ``lm.model``.

The per-bucket names ``bucket<k>`` / ``leaf<i>`` under
``hvd.grad_exchange`` are built at trace time and are not listed: they say
WHICH bucket, not what kind of work.
"""

import collections

import jax

Scope = collections.namedtuple("Scope", "name kind layer holds under",
                               defaults=(None,))

# ``layer`` is the layer of PERF.md section 3 the scope's time belongs to.
SCOPES = (
    # the step's phases (parallel/dp.py, parallel/fsdp.py)
    Scope("hvd.loss_and_grad", "container", "compiled_dp_step",
          "value_and_grad of the loss: forward, and backward under JAX's "
          "transpose( mark"),
    Scope("hvd.optimizer", "leaf", "compiled_dp_step",
          "optimizer.update and apply_updates (the exchange inside it has "
          "its own name)"),
    # the gradient exchange (optim/optimizer.py, ops/in_jit.py)
    Scope("hvd.grad_exchange", "leaf", "fused_allreduce",
          "fused_allreduce_tree outside its parts: the division of "
          "Average, compression"),
    Scope("pack", "leaf", "fused_allreduce",
          "gradients written into a bucket, under hvd.grad_exchange"),
    Scope("unpack", "leaf", "fused_allreduce",
          "a reduced bucket sliced back into leaves"),
    Scope("hvd.wire", "leaf", "fused_allreduce",
          "the collective primitive alone (a bucket's, or the loss's "
          "mean outside the exchange)"),
    # the model (models/*.py)
    Scope("lm.model", "container", "compiled_dp_step",
          "the body of the top module's __call__: embedding, blocks, head"),
    Scope("lm.embed", "leaf", "compiled_dp_step",
          "the embedding module: the gather, positions or the scale, and "
          "the scatter-add coming back"),
    Scope("lm.head", "leaf", "compiled_dp_step",
          "final norm and the float32 head product, with its gradient and "
          "the update XLA fuses into it"),
    Scope("lm.loss", "rule", "compiled_dp_step",
          "the user's loss on the logits: softmax, the labels' gather, "
          "the mean, and the gradient's accumulation outside the model",
          under="hvd.loss_and_grad"),
    Scope("block.norm", "leaf", "compiled_dp_step",
          "the norm in front of a sub-layer"),
    Scope("block.post_norm", "leaf", "compiled_dp_step",
          "the norm behind a sub-layer and its residual add (sandwich "
          "blocks)"),
    Scope("mlp.dense", "leaf", "compiled_dp_step",
          "a dense feed-forward: its products and the activation"),
    # attention (parallel/tp.py TPSelfAttention; the containers in models/)
    Scope("attn.full", "container", "attention",
          "a layer that attends over everything before it"),
    Scope("attn.window", "container", "attention",
          "a layer with a sliding window"),
    Scope("attn.qkv", "leaf", "attention",
          "the fused q, k, v projection and the split into heads"),
    Scope("attn.qk_norm", "leaf", "attention",
          "the RMS norm of every query and key head"),
    Scope("attn.rope", "leaf", "attention", "the rotation of q and k"),
    Scope("attn.core", "leaf", "attention",
          "the _attend call: the flash kernels (or the plain products) "
          "and the reshapes and broadcasts round them"),
    Scope("attn.gate", "leaf", "attention",
          "the gate's projection, its sigmoid and the product with the "
          "heads' output"),
    Scope("attn.out", "leaf", "attention", "the output projection"),
    # latent attention (parallel/mla.py TPLatentAttention)
    Scope("attn.q_latent", "leaf", "attention",
          "the query's down product, its latent norm, the up product to "
          "the heads and the rotation of their rotary part"),
    Scope("attn.kv_latent", "leaf", "attention",
          "the key-value latent's down product and norm, the up product "
          "to the heads' keys and values, the shared rotary key's "
          "rotation and the keys assembled"),
    # multi-token prediction (models/joyai_flash.py)
    Scope("mtp", "container", "compiled_dp_step",
          "the multi-token-prediction module: its norms, the projection of "
          "[embedding | hidden], its block and its pass through the head"),
    # the Mamba-2 mixer (parallel/ssm.py)
    Scope("ssm.mixer", "container", "state_space", "one Mamba2Mixer call"),
    Scope("ssm.in_proj", "leaf", "state_space",
          "the input projection to [z | xBC | dt] and its split"),
    Scope("ssm.conv", "leaf", "state_space",
          "the causal convolution, SiLU and the split into x, B, C"),
    Scope("ssm.scan", "leaf", "state_space",
          "softplus of dt and ssm_scan, kernels or chunked form"),
    Scope("ssm.gate_norm", "leaf", "state_space",
          "the gated group RMS norm"),
    Scope("ssm.out_proj", "leaf", "state_space", "the output projection"),
    # the Kimi Delta Attention mixer (parallel/kda.py)
    Scope("kda.mixer", "container", "delta_rule", "one KDAMixer call"),
    Scope("kda.in_proj", "leaf", "delta_rule",
          "the fused q, k, v projection, beta's projection and sigmoid, "
          "and the two low-rank gate paths"),
    Scope("kda.conv", "leaf", "delta_rule",
          "the causal convolution of q, k and v, SiLU, the split into "
          "heads and the L2 norms"),
    Scope("kda.core", "leaf", "delta_rule",
          "the forget gate (softplus, decay) and kda_core, kernels or "
          "chunked form"),
    Scope("kda.gate_norm", "leaf", "delta_rule",
          "the heads' RMS norm and the sigmoid output gate"),
    Scope("kda.out_proj", "leaf", "delta_rule", "the output projection"),
    # the expert layer (parallel/moe.py DroplessMoE; the shared expert in
    # models/)
    Scope("moe.route", "leaf", "expert_layer",
          "the float32 router, top-k and the weights"),
    Scope("moe.dispatch", "leaf", "expert_layer",
          "the sort by expert and the rows taken into the buffer"),
    Scope("moe.experts", "leaf", "expert_layer",
          "the two grouped products and the activation between"),
    Scope("moe.combine", "leaf", "expert_layer",
          "the weighted sum of the experts' rows by token"),
    Scope("moe.shared", "leaf", "expert_layer",
          "the shared expert every token passes"),
)

KINDS = {s.name: s.kind for s in SCOPES}


def scope(name):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES` that is
    entered somewhere (a ``rule`` is not); any other name raises at trace
    time, before a reader can miss it."""
    if KINDS.get(name) not in ("leaf", "container"):
        raise ValueError(f"{name!r} is not a scope of "
                         f"horovod_tpu/trace/scopes.py SCOPES")
    return jax.named_scope(name)

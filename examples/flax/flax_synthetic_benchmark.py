"""ResNet-50 synthetic data-parallel throughput — standalone version of the
repo's headline bench (reference analog: examples/pytorch/
pytorch_synthetic_benchmark.py; procedure docs/benchmarks.rst:15-64).

    python flax_synthetic_benchmark.py [--batch-size 128] [--num-iters 20]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50
from horovod_tpu.optim import DistributedOptimizer
from horovod_tpu.parallel import TrainState, make_train_step


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=128,
                   help="per-chip batch size")
    p.add_argument("--num-iters", type=int, default=20)
    p.add_argument("--num-warmup", type=int, default=2)
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    mesh = hvd.global_process_set.mesh
    batch = args.batch_size * n

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, train=True)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)

    variables = jax.jit(model.init)(jax.random.PRNGKey(0), images[:1])
    opt = DistributedOptimizer(optax.sgd(0.1, momentum=0.9))

    def loss_fn(p, b, extra):
        logits, updates = model.apply(
            {"params": p, "batch_stats": extra}, b["x"],
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()
        return loss, updates["batch_stats"]

    step = make_train_step(loss_fn, opt, mesh, has_aux=True, donate=True)
    state = TrainState.create(variables["params"], opt,
                              extra=variables.get("batch_stats", {}))
    data = {"x": images, "y": labels}

    for _ in range(args.num_warmup):
        state, loss = step(state, data)
        float(loss)  # device get: waits for the step

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        state, loss = step(state, data)
    float(loss)
    dt = time.perf_counter() - t0

    if hvd.rank() == 0:
        total = batch * args.num_iters / dt
        print(f"Total img/sec on {n} chip(s): {total:.1f}")
        print(f"Img/sec per chip: {total / n:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()

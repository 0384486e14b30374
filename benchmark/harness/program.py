"""The system under test, built as a user of horovod_tpu builds it.

``hvd.init`` -> weights (from the benchmark's seed) ->
``hvd.broadcast_parameters`` -> ``TrainState.create`` over
``DistributedOptimizer(optax.adamw)`` -> placed replicated ->
``parallel.make_train_step(..., donate=True)``, compiled ahead of time. The
construction is ``chip_smoke.py``'s; the loop is the benchmark's.

Every run, whatever its seed or ``--trace``, reaches ``lower().compile()``
through ``compile_step`` below from ``run.py``'s ``set_up``: the compile
cache's key of a program with Pallas kernels covers the Python traceback of
the call site (PERF.md, PR 21).
"""

import importlib.util
import os
import time

import jax
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_model_builder(kind):
    """``benchmark/models/<kind>.py``'s ``build``: the registry is the
    directory, so a new kind of model is one new file."""
    path = os.path.join(HERE, "models", f"{kind}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no model kind {kind!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_model_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build


def start(chips):
    """``hvd.init`` on the first ``chips`` devices; returns (hvd, mesh)."""
    import horovod_tpu as hvd
    devices = jax.devices()
    hvd.init(devices=devices[:chips] if len(devices) > chips else None)
    if hvd.size() != chips:
        raise RuntimeError(f"topology size {hvd.size()} != {chips} chips")
    return hvd, hvd.global_process_set.mesh


def optimizer(cfg):
    from horovod_tpu.optim import DistributedOptimizer
    a = cfg["assumed"]
    if a["optimizer"] != "adamw":
        raise ValueError(f"unknown optimizer {a['optimizer']!r}")
    return DistributedOptimizer(optax.adamw(
        a["learning_rate"], b1=a["adam_b1"], b2=a["adam_b2"],
        eps=a["adam_eps"], weight_decay=a["weight_decay"]))


def model_shapes(model, batch):
    """Names and shapes ``model.init`` asks for, as nested dicts of tuples
    (nothing is computed)."""
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          batch["ids"][:1])["params"]
    return jax.tree.map(lambda x: tuple(x.shape), tree)


def build(hvd, mesh, cfg, loss_fn, params):
    """(step, state): the weights broadcast from rank 0, wrapped in a
    TrainState over the DistributedOptimizer and placed replicated on the
    mesh, and the jitted data-parallel step over the same optimizer."""
    from horovod_tpu.parallel import TrainState, make_train_step
    opt = optimizer(cfg)
    params = hvd.broadcast_parameters(params, root_rank=0)
    state = jax.device_put(TrainState.create(params, opt),
                           NamedSharding(mesh, P()))
    return make_train_step(loss_fn, opt, mesh, donate=True), state


def compile_step(step, state, batch):
    """AOT compile (or load from the persistent cache); returns
    (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    return compiled, time.perf_counter() - t0


def feed(compiled, mesh, state, host_batch):
    """What a user's loop does each step: place the host batch split over
    the mesh and call the compiled step. Returns (state, loss) as soon as
    the call returns, not when the device is done."""
    from horovod_tpu.parallel import shard_batch
    return compiled(state, shard_batch(host_batch, mesh))

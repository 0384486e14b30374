"""FSDP / ZeRO-3: fully-sharded parameters via GSPMD.

The ladder of optimizer/parameter sharding this framework offers:

- DP (``make_train_step``): params + optimizer state replicated; gradients
  fused-allreduced (the reference's only mode).
- ZeRO-1 (``make_zero_train_step``): optimizer MOMENTS sharded 1/n; params
  replicated; reduce-scatter + all-gather per step (dp.py).
- FSDP / ZeRO-3 (this module): PARAMS, gradients, and optimizer state all
  sharded 1/n per chip. Beyond reference parity — Horovod has no parameter
  sharding at all (SURVEY.md §2.6).

TPU-first design: no hand-written gather/scatter schedule. Parameters are
laid out with per-leaf ``NamedSharding``s (largest divisible dim split over
the mesh axis) and the train step is a plain ``jax.jit`` — XLA's GSPMD
partitioner inserts the all-gathers before each layer's compute and
reduce-scatters the gradients, then overlaps them with compute on the ICI
torus. That schedule is exactly what hand-rolled FSDP implementations
approximate; on TPU the compiler already owns it (SURVEY.md §5.8 stance:
let XLA fuse — don't hand-schedule what the compiler already does).

Memory per chip: params + grads + moments all drop by n× (vs n× for
moments only under ZeRO-1); the cost is an all-gather of each layer's
weights per step, which GSPMD overlaps with the previous layer's compute.
"""

import functools
from typing import Callable

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.common.topology import HVD_AXIS
from horovod_tpu.trace.scopes import scope


def fsdp_spec(shape, n, min_size=16384, axis_name=HVD_AXIS):
    """PartitionSpec sharding the largest n-divisible dim of ``shape``.

    Leaves smaller than ``min_size`` elements stay replicated: sharding a
    LayerNorm bias saves nothing and costs a gather.
    """
    if int(np.prod(shape)) < min_size:
        return P()
    dims = [(d, i) for i, d in enumerate(shape) if d % n == 0]
    if not dims:
        return P()
    _, best = max(dims, key=lambda t: (t[0], -t[1]))  # ties -> first dim
    spec = [None] * len(shape)
    spec[best] = axis_name
    return P(*spec)


def fsdp_shardings(tree, mesh, axis_name=HVD_AXIS, min_size=16384):
    """Per-leaf NamedShardings for a parameter pytree."""
    n = mesh.shape[axis_name]

    def leaf(x):
        shape = getattr(x, "shape", ())
        return NamedSharding(mesh, fsdp_spec(shape, n, min_size, axis_name))

    return jax.tree.map(leaf, tree)


def _place(x, sharding):
    """Place host data with ``sharding``; under a multi-process mesh the
    sharding spans non-addressable devices, where device_put can't be used
    — build the global array from the host-replicated value instead."""
    if jax.process_count() > 1:
        arr = np.asarray(x)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])
    return jax.device_put(x, sharding)


def shard_params(params, mesh, axis_name=HVD_AXIS, min_size=16384):
    """Lay a parameter pytree out FSDP-sharded on the mesh (params must be
    host-identical across processes, e.g. seeded or broadcast)."""
    sh = fsdp_shardings(params, mesh, axis_name, min_size)
    return jax.tree.map(_place, params, sh)


def make_fsdp_train_step(loss_fn: Callable, tx, mesh, axis_name=HVD_AXIS,
                         donate=True, min_size=16384):
    """Build an FSDP training step.

    ``loss_fn(params, batch)`` is written on GLOBAL arrays (plain jnp — no
    shard_map, no axis names): under jit the batch arrives sharded on its
    leading dim, params arrive FSDP-sharded, and GSPMD inserts the
    all-gather / reduce-scatter schedule. Returns
    ``(init_fn, step_fn)``:

    - ``init_fn(params) -> (params, opt_state)`` — places params sharded
      and initializes the optimizer state with matching (propagated)
      shardings.
    - ``step_fn(params, opt_state, batch) -> (params, opt_state, loss)``
      — one fused step; params/opt_state stay sharded across calls.
    """
    n = mesh.shape[axis_name]

    def init_fn(params):
        params = shard_params(params, mesh, axis_name, min_size)
        # Moment-like leaves share their param's shape, hence its sharding;
        # counts/scalars come out replicated (below min_size).
        opt_state = jax.jit(
            tx.init,
            out_shardings=fsdp_shardings(
                jax.eval_shape(tx.init, params), mesh, axis_name,
                min_size))(params)
        return params, opt_state

    # Named as make_train_step names its step and phases, so that a
    # profile of either reads alike (docs/observability.md).
    @functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def hvd_fsdp_step(params, opt_state, batch):
        with scope("hvd.loss_and_grad"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with scope("hvd.optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return init_fn, hvd_fsdp_step


def shard_batch(batch, mesh, axis_name=HVD_AXIS):
    """Place a host batch with its leading dim split over the mesh axis.

    Span ``shard_batch``: in the profiler's trace whenever a session is on;
    in the span store only under a step trace (``hvd.step_marker``)."""

    def leaf(x):
        spec = [axis_name] + [None] * (np.ndim(x) - 1)
        return _place(x, NamedSharding(mesh, P(*spec)))

    with trace.span("shard_batch", cat="train"):
        return jax.tree.map(leaf, batch)

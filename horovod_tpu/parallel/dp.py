"""Data-parallel training steps over the mesh.

This is the TPU-native realization of "wrap your optimizer, train as usual"
(reference: docs + horovod/torch/optimizer.py DistributedOptimizer usage): a
builder that takes a user loss function and a (Distributed-)optax optimizer
and returns ONE compiled SPMD step, with the whole Horovod pipeline — local
backward, fused gradient allreduce, optimizer update — inside a single XLA
program that the compiler overlaps and schedules on the ICI torus.

Two idioms are supported:

- ``make_train_step`` (explicit SPMD): shard_map over the mesh; parameters are
  replicated; gradients stay device-local until the DistributedOptimizer's
  fused psum — the literal Horovod dataflow, with the fusion buffer replaced
  by :func:`horovod_tpu.optim.fused_allreduce_tree`.
- Plain GSPMD: because parameters enter replicated and the batch enters
  sharded, simply jitting the same loss under ``jax.jit`` with NamedShardings
  lets XLA's partitioner insert the gradient all-reduce itself. That mode
  needs no code from us beyond shardings — it is what the compile-time
  "response cache" means on TPU — so this module only provides the explicit
  variant, which exercises this framework's collectives.
"""

from typing import Any, Callable

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.common.topology import HVD_AXIS
from horovod_tpu.ops import in_jit
from horovod_tpu.trace.scopes import scope


class TrainState(struct.PyTreeNode):
    """Minimal train state (params + optimizer state + step counter)."""
    step: Any
    params: Any
    opt_state: Any
    extra: Any = None  # e.g. batch_stats

    @classmethod
    def create(cls, params, optimizer, extra=None):
        with trace.run_span("opt_state_init"):
            opt_state = optimizer.init(params)
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, extra=extra)


def _loss_and_grad(loss_fn, has_aux, params, batch, extra):
    """``(loss, aux, grads)`` of the local batch under the device scope
    ``hvd.loss_and_grad``; within it JAX marks the backward pass's ops
    ``transpose(jvp(...))``."""
    with scope("hvd.loss_and_grad"):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, extra)
            return loss, aux, grads
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, None, grads


def make_train_step(loss_fn: Callable, optimizer, mesh, axis_name=HVD_AXIS,
                    batch_spec=None, has_aux=False, donate=True):
    """Build the compiled DP train step.

    ``loss_fn(params, batch)`` computes the LOCAL loss on this chip's batch
    shard. With ``has_aux`` the signature is ``loss_fn(params, batch, extra)
    -> (loss, new_extra)`` where ``extra`` is ``state.extra`` (e.g. BatchNorm
    ``batch_stats``); the returned extra is pmean'd across the axis so stored
    state stays replicated. The returned function maps ``(state, batch) ->
    (state, loss)`` with the batch sharded over ``axis_name`` and everything
    else replicated.

    The optimizer should be a :func:`horovod_tpu.optim.DistributedOptimizer`
    built with the same ``axis_name`` — its fused allreduce is the only
    cross-chip communication in the step.
    """
    if batch_spec is None:
        batch_spec = P(axis_name)

    def local_step(state, batch):
        # Parameters arrive replicated (axis-invariant). Lift them to
        # device-varying so autodiff keeps gradients local — the reduction
        # belongs to the DistributedOptimizer, not to AD's transpose rule.
        params = in_jit.mark_varying(state.params, axis_name)
        opt_state = in_jit.mark_varying(state.opt_state, axis_name)
        extra = in_jit.mark_varying(state.extra, axis_name)

        loss, aux, grads = _loss_and_grad(loss_fn, has_aux, params, batch,
                                          extra)
        # The DistributedOptimizer's exchange runs inside ``update`` and
        # names itself ``hvd.grad_exchange``: an op belongs to the
        # innermost ``hvd.*`` scope on its path.
        with scope("hvd.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        # The means of loss and aux through in_jit.allreduce (Average), so
        # that every all-reduce of the step lies under ``hvd.wire``: XLA
        # may combine the loss's with a bucket's and keep either's name.
        loss = in_jit.allreduce(loss, axis_name=axis_name)
        if has_aux:
            # Per-shard aux (e.g. local batch-norm statistics) diverges across
            # devices; average it so the stored state is truly replicated —
            # the cross-replica running-stats sync SyncBatchNorm does inline.
            aux = jax.tree_util.tree_map(
                lambda a: in_jit.allreduce(a, axis_name=axis_name)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, aux)
        new_state = state.replace(step=state.step + 1, params=params,
                                  opt_state=opt_state,
                                  extra=aux if has_aux else state.extra)
        return new_state, loss

    # check_vma=False: the updated params/opt_state are device-varying *types*
    # but replicated *values* (every chip applies the same psum'd gradient),
    # which the static VMA analysis cannot prove. test_parallel asserts the
    # bitwise cross-device equality this relies on.
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()), check_vma=False)

    def hvd_dp_step(state, batch):     # a profile's module: jit_hvd_dp_step
        return sharded(state, batch)

    return jax.jit(hvd_dp_step, donate_argnums=(0,) if donate else ())


def make_eval_step(eval_fn: Callable, mesh, axis_name=HVD_AXIS,
                   batch_spec=None):
    """Compiled eval step: per-shard metrics are pmean'd — the MetricAverage
    semantics (reference: _keras/callbacks.py:62 MetricAverageCallback)."""
    if batch_spec is None:
        batch_spec = P(axis_name)

    def local_eval(params, batch):
        metrics = eval_fn(in_jit.mark_varying(params, axis_name), batch)
        return jax.tree_util.tree_map(
            lambda m: lax.pmean(m, axis_name), metrics)

    sharded = jax.shard_map(local_eval, mesh=mesh,
                            in_specs=(P(), batch_spec), out_specs=P(),
                            check_vma=False)
    return jax.jit(sharded)


def make_zero_train_step(loss_fn: Callable, tx, mesh, axis_name=HVD_AXIS,
                         batch_spec=None, has_aux=False, donate=True,
                         average=True):
    """DP train step with ZeRO-1 optimizer-state sharding over the DP axis.

    Beyond reference parity (the reference replicates optimizer state on
    every worker, like every Horovod job): gradients are REDUCE-SCATTERED
    instead of all-reduced, each chip updates only its 1/n shard of the
    (flattened) parameters with its 1/n shard of the optimizer state, and
    the updated shards are all-gathered back — the same bytes on the wire
    as an allreduce (RS + AG is how ring allreduce decomposes), but adamw
    moment memory drops from 2×params to 2×params/n per chip.

    ``tx`` is a plain optax transform (NOT DistributedOptimizer — the
    reduction is fused into the scatter here). Transforms must be
    elementwise over the flat parameter vector (sgd/momentum/adam/adamw/
    rmsprop are; global-norm clipping is not, since a shard-local norm is
    not the global norm).

    Use ``ZeroTrainState.create(params, tx, mesh)`` for the matching state;
    ``state.opt_state`` holds flat shard-shaped leaves.
    """
    if batch_spec is None:
        batch_spec = P(axis_name)
    n = int(np.prod([mesh.shape[a] for a in
                     (axis_name if isinstance(axis_name, tuple)
                      else (axis_name,))]))

    def local_step(state, batch):
        params = in_jit.mark_varying(state.params, axis_name)
        opt_state = in_jit.mark_varying(state.opt_state, axis_name)
        extra = in_jit.mark_varying(state.extra, axis_name)

        loss, aux, grads = _loss_and_grad(loss_fn, has_aux, params, batch,
                                          extra)

        flat_g, _ = jax.flatten_util.ravel_pytree(grads)
        flat_p, unravel = jax.flatten_util.ravel_pytree(params)
        pad = (-flat_g.size) % n
        flat_g = jnp.pad(flat_g, (0, pad))
        # Fused reduce+shard: this chip receives the reduced shard
        # [idx*L : (idx+1)*L] of the gradient.
        g_shard = lax.psum_scatter(flat_g, axis_name, scatter_dimension=0,
                                   tiled=True)
        if average:
            g_shard = g_shard / n
        shard_len = flat_g.size // n
        idx = lax.axis_index(axis_name)
        p_shard = lax.dynamic_slice(jnp.pad(flat_p, (0, pad)),
                                    (idx * shard_len,), (shard_len,))
        with scope("hvd.optimizer"):
            updates, opt_state = tx.update(g_shard, opt_state, p_shard)
            p_shard = optax.apply_updates(p_shard, updates)
        flat_new = lax.all_gather(p_shard, axis_name, tiled=True)
        params = unravel(flat_new[:flat_p.size])

        loss = lax.pmean(loss, axis_name)
        if has_aux:
            aux = jax.tree_util.tree_map(
                lambda a: lax.pmean(a, axis_name)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, aux)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state,
                             extra=aux if has_aux else state.extra), loss

    # opt_state shards stay device-varying across steps: their specs carry
    # the axis so each chip keeps only its 1/n moments. Vector leaves
    # (moments) shard; scalar leaves (step counts) replicate.
    opt_struct = jax.eval_shape(tx.init,
                                jax.ShapeDtypeStruct((n,), jnp.float32))
    opt_specs = jax.tree_util.tree_map(
        lambda x: P(axis_name) if getattr(x, "ndim", 0) >= 1 else P(),
        opt_struct)
    state_specs = ZeroTrainState(step=P(), params=P(), opt_state=opt_specs,
                                 extra=P())
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, P()), check_vma=False)

    def hvd_zero_step(state, batch):
        return sharded(state, batch)

    return jax.jit(hvd_zero_step, donate_argnums=(0,) if donate else ())


class ZeroTrainState(TrainState):
    """TrainState whose opt_state moment leaves are flat 1/n shards."""

    @classmethod
    def create(cls, params, tx, mesh, axis_name=HVD_AXIS, extra=None):
        n = int(np.prod([mesh.shape[a] for a in
                         (axis_name if isinstance(axis_name, tuple)
                          else (axis_name,))]))
        flat, _ = jax.flatten_util.ravel_pytree(params)
        shard_len = (flat.size + (-flat.size) % n) // n
        # GLOBAL moment arrays of n * shard_len: the sharded specs of
        # make_zero_train_step lay 1/n on each chip, so per-chip memory is
        # moments/n — the ZeRO-1 saving.
        opt_state = tx.init(jnp.zeros((n * shard_len,), flat.dtype))
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, extra=extra)

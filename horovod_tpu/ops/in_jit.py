"""Collectives for use *inside* jitted/sharded programs.

This is the API users call inside their own ``shard_map``/``pjit`` training
steps — the TPU-native analog of the reference's in-graph TF ops
(reference: horovod/tensorflow/mpi_ops.py:58-170) and of its XLA CustomCall
path (reference: horovod/tensorflow/xla_mpi_ops.cc): on TPU *every* op is
already inside XLA, so "the XLA path" is simply ``jax.lax`` collectives over a
named mesh axis, fused and scheduled by the compiler.

Process-set semantics (reference's per-set communicators,
horovod/common/process_set.cc) are implemented SPMD-style: all ranks execute
the op, and subset reductions use identity-masked full-axis collectives
(Sum/Average ride one ``psum`` with non-members contributing the identity) or
an ``all_gather`` + static local select for the non-linear ops. Non-member
ranks receive a well-defined value they are expected to ignore, mirroring how
non-member processes simply don't call the op in the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.common.topology import HVD_AXIS
from horovod_tpu.ops.collective_ops import (Adasum, Average, Max, Min, Product,
                                            ReduceOp, Sum)
from horovod_tpu.trace.scopes import scope


def _ranks(process_set):
    if process_set is None or getattr(process_set, "ranks", None) is None:
        return None
    return list(process_set.ranks)


def _member_mask(ranks, axis_name):
    idx = lax.axis_index(axis_name)
    return jnp.isin(idx, jnp.asarray(np.array(ranks)))


def size(axis_name=HVD_AXIS):
    return lax.axis_size(axis_name)


def rank(axis_name=HVD_AXIS):
    return lax.axis_index(axis_name)


def _wire():
    """Device scope ``hvd.wire``: round the collective primitive alone, so
    a profile finds the exchange by where the program put it, whatever the
    compiler names the op (docs/observability.md)."""
    return scope("hvd.wire")


def _gather_select(x, ranks, axis_name):
    """all_gather the full axis, select the process set's slices (static)."""
    with _wire():
        g = lax.all_gather(x, axis_name)  # (world, ...)
    return g[jnp.asarray(np.array(ranks))]  # (set_size, ...)


def _pos_in_set(ranks, axis_name):
    """This rank's index within the set (0 for non-members)."""
    idx = lax.axis_index(axis_name)
    r = jnp.asarray(np.array(ranks))
    return jnp.sum(jnp.where(r == idx, jnp.arange(len(ranks)), 0))


def allreduce(x, op=Average, axis_name=HVD_AXIS, process_set=None,
              prescale_factor=1.0, postscale_factor=1.0):
    ranks = _ranks(process_set)
    op = ReduceOp(op)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, x.dtype)
    if ranks is None:
        n = lax.axis_size(axis_name)
        if op in (Sum, Average):
            with _wire():
                y = lax.psum(x, axis_name)
            if op == Average:
                y = y / jnp.asarray(n, y.dtype)
        elif op == Min:
            with _wire():
                y = lax.pmin(x, axis_name)
        elif op == Max:
            with _wire():
                y = lax.pmax(x, axis_name)
        elif op == Product:
            with _wire():
                g = lax.all_gather(x, axis_name)
            y = jnp.prod(g, axis=0)
        elif op == Adasum:
            from horovod_tpu.ops.adasum import adasum_tree
            with _wire():
                g = lax.all_gather(x, axis_name)
            y = adasum_tree([g[i] for i in range(n)])
        else:
            raise ValueError(f"unknown op {op}")
    else:
        n = len(ranks)
        if op in (Sum, Average):
            mask = _member_mask(ranks, axis_name)
            masked = jnp.where(mask, x, jnp.zeros_like(x))
            with _wire():
                y = lax.psum(masked, axis_name)
            if op == Average:
                y = y / jnp.asarray(n, y.dtype)
        elif op in (Min, Max, Product):
            g = _gather_select(x, ranks, axis_name)
            reducer = {Min: jnp.min, Max: jnp.max, Product: jnp.prod}[op]
            y = reducer(g, axis=0)
        elif op == Adasum:
            from horovod_tpu.ops.adasum import adasum_tree
            g = _gather_select(x, ranks, axis_name)
            y = adasum_tree([g[i] for i in range(n)])
        else:
            raise ValueError(f"unknown op {op}")
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, y.dtype)
    return y


def allgather(x, axis_name=HVD_AXIS, process_set=None, axis=0, tiled=True):
    ranks = _ranks(process_set)
    if ranks is None:
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    g = _gather_select(x, ranks, axis_name)  # (set, ...)
    g = jnp.moveaxis(g, 0, axis)
    if tiled:
        shape = list(g.shape)
        shape[axis] = shape[axis] * shape[axis + 1]
        del shape[axis + 1]
        # (set, m, ...) -> (set*m, ...) along `axis`
        g = g.reshape(shape)
    return g


def broadcast(x, root_rank, axis_name=HVD_AXIS, process_set=None):
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    if jnp.issubdtype(x.dtype, jnp.bool_):
        return lax.psum(masked.astype(jnp.int32), axis_name).astype(x.dtype)
    return lax.psum(masked, axis_name)


def reducescatter(x, op=Sum, axis_name=HVD_AXIS, process_set=None,
                  scatter_axis=0):
    op = ReduceOp(op)
    if op not in (Sum, Average):
        raise ValueError("reducescatter supports Sum/Average")
    ranks = _ranks(process_set)
    if ranks is None:
        n = lax.axis_size(axis_name)
        y = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                             tiled=True)
    else:
        n = len(ranks)
        if x.shape[scatter_axis] % n != 0:
            raise ValueError(
                f"reducescatter: axis {scatter_axis} size "
                f"{x.shape[scatter_axis]} not divisible by set size {n}")
        mask = _member_mask(ranks, axis_name)
        full = lax.psum(jnp.where(mask, x, jnp.zeros_like(x)), axis_name)
        chunk = x.shape[scatter_axis] // n
        pos = _pos_in_set(ranks, axis_name)
        y = lax.dynamic_slice_in_dim(full, pos * chunk, chunk, axis=scatter_axis)
    if op == Average:
        y = y / jnp.asarray(n, y.dtype)
    return y


def alltoall(x, axis_name=HVD_AXIS, process_set=None, split_axis=0,
             concat_axis=0):
    ranks = _ranks(process_set)
    if ranks is None:
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    n = len(ranks)
    if x.shape[split_axis] % n != 0:
        raise ValueError(
            f"alltoall: axis {split_axis} size {x.shape[split_axis]} not "
            f"divisible by set size {n}")
    chunk = x.shape[split_axis] // n
    g = _gather_select(x, ranks, axis_name)  # (set, ..., m, ...)
    pos = _pos_in_set(ranks, axis_name)
    parts = [lax.dynamic_slice_in_dim(g[i], pos * chunk, chunk, axis=split_axis)
             for i in range(n)]
    return jnp.concatenate(parts, axis=concat_axis)


def ppermute(x, perm, axis_name=HVD_AXIS):
    """Point-to-point ring shifts — the primitive ring attention builds on."""
    return lax.ppermute(x, axis_name, perm)


def mark_varying(tree, axis_name=HVD_AXIS):
    """Lift every leaf to device-varying over ``axis_name`` (no-op for leaves
    already varying). Needed when mixing replicated values (e.g. an initial
    carry built from constants) with per-rank values inside shard_map scans
    and conds under JAX's varying-manual-axes checking."""
    import jax as _jax

    def mv(x):
        vma = getattr(_jax.typeof(x), "vma", ())
        if axis_name in vma:
            return x
        return lax.pcast(x, axis_name, to="varying")

    return _jax.tree_util.tree_map(mv, tree)


def mark_varying_like(tree, ref, axis_name=HVD_AXIS):
    """Lift every leaf of ``tree`` to device-varying over ``axis_name`` AND
    every axis ``ref`` (a data operand) is already varying over. Use for
    scan/loop carries whose steady-state type combines constants with data
    that may itself be sharded over MORE mesh axes (e.g. a ring-attention
    accumulator on a dp x pp x sp mesh is varying over all three)."""
    import jax as _jax

    axes = set(getattr(_jax.typeof(ref), "vma", ())) | {axis_name}
    for ax in axes:
        tree = mark_varying(tree, ax)
    return tree

"""DistributedOptimizer and gradient-reduction transforms.

Reference surface being matched (horovod/torch/optimizer.py:132-344
``DistributedOptimizer`` + horovod/tensorflow/__init__.py:822
``DistributedOptimizer`` / :957 ``_DistributedGradientTape`` and the
local-gradient-aggregation helpers horovod/tensorflow/gradient_aggregation.py):
wrap a local optimizer so gradients are averaged across workers before the
update, with optional fp16 wire compression and ``backward_passes_per_step``
local aggregation.

TPU-native design: the wrapper is an ``optax.GradientTransformation`` meant to
run *inside* the jitted, shard_mapped train step. There are no per-parameter
hooks or async handles — XLA sees every gradient at once, so we implement the
fusion buffer (reference: fusion_buffer_manager.h) ahead-of-time:
:func:`fused_allreduce_tree` groups all leaves by dtype, concatenates them
into flat buffers capped at ``HOROVOD_FUSION_THRESHOLD`` bytes, and reduces
each bucket with a single ICI ``psum`` — collectives per step scale with
total gradient bytes over the threshold (a handful for typical models), not
with parameter count, and XLA is free to overlap them with the backward
pass. ``backward_passes_per_step`` maps onto
``optax.MultiSteps`` (local accumulation; the allreduce runs only on the
boundary step, exactly the reference's aggregation semantics).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from horovod_tpu.common.topology import HVD_AXIS
from horovod_tpu.ops import in_jit
from horovod_tpu.ops.collective_ops import Adasum, Average, ReduceOp, Sum
from horovod_tpu.ops.compression import Compression
from horovod_tpu.trace.scopes import scope


def fused_allreduce_tree(tree, op=Average, axis_name=HVD_AXIS,
                         process_set=None, compression=Compression.none,
                         prescale_factor=1.0, postscale_factor=1.0):
    """Allreduce every leaf of a pytree with per-dtype flat-buffer fusion.

    The in-jit analog of Horovod's tensor fusion: instead of one collective
    per parameter (reference enqueues per-tensor and fuses in the background
    cycle), leaves are packed into flat buckets of up to
    ``HOROVOD_FUSION_THRESHOLD`` bytes per wire dtype — so the collective
    count is ``ceil(group_bytes / threshold)`` per dtype group (one for
    models under the threshold; e.g. BERT-Large's 1.4 GB fp32 gradients at
    the default 64 MB threshold reduce in ~22 buckets).

    Device scopes (docs/observability.md): everything here lies under
    ``hvd.grad_exchange``; bucket k under ``bucket<k>`` with ``pack``
    (reshape, concatenate, pad) and ``unpack`` (slice, reshape) round the
    collective, which ``in_jit.allreduce`` puts under ``hvd.wire``; a leaf
    reduced alone under ``leaf<i>``. Each trace of this function sets the
    gauges ``hvd_fused_allreduce_buckets`` / ``hvd_fused_allreduce_bytes``
    (collectives issued, bytes they carry with padding).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    with scope("hvd.grad_exchange"):
        out = _fused_allreduce_leaves(
            leaves, ReduceOp(op), axis_name, process_set, compression,
            prescale_factor, postscale_factor)
    return jax.tree_util.tree_unflatten(treedef, out)


def _fused_allreduce_leaves(leaves, op, axis_name, process_set, compression,
                            prescale_factor, postscale_factor):
    """The body of :func:`fused_allreduce_tree` over the flat leaves."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.exceptions import NotInitializedError
    try:
        threshold = basics.config().fusion_threshold
    except NotInitializedError:
        threshold = Config().fusion_threshold
    int8_route = (compression is Compression.int8 and process_set is None
                  and op in (Sum, Average))
    if compression is Compression.int8:
        # Quantization happens inside the bucket exchange below (or not at
        # all when the combination can't express it); compress() is the
        # EAGER paths' routing hook and must not arm a one-shot wire
        # request from inside a jit trace.
        compressed = [(jnp.asarray(l), None) for l in leaves]
    else:
        with scope("pack"):
            compressed = [compression.compress(jnp.asarray(l))
                          for l in leaves]
    groups = {}
    for i, (c, _) in enumerate(compressed):
        groups.setdefault(jnp.dtype(c.dtype), []).append(i)
    out = [None] * len(leaves)
    n_buckets = wire_bytes = 0
    for dt, idxs in groups.items():
        if op == Average and not jnp.issubdtype(dt, jnp.floating):
            raise ValueError(
                "Average is not supported for integer tensors; use hvd.Sum "
                "(matches the eager allreduce API and reference "
                "torch/mpi_ops.py checks).")
        if op == Adasum or not jnp.issubdtype(dt, jnp.number) \
                or jnp.issubdtype(dt, jnp.integer):
            # Adasum normalizes per-tensor, and non-float leaves shouldn't be
            # folded into a float buffer: reduce these leaves individually.
            for i in idxs:
                with jax.named_scope(f"leaf{i}"):
                    out[i] = in_jit.allreduce(
                        compressed[i][0], op=op, axis_name=axis_name,
                        process_set=process_set,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor)
                n_buckets += 1
                wire_bytes += compressed[i][0].size * dt.itemsize
            continue
        # Bucket the group at the fusion threshold (reference:
        # HOROVOD_FUSION_THRESHOLD, fusion_buffer_manager.h:40): one giant
        # flat buffer both doubles peak gradient memory and — with an
        # awkward element count (e.g. BERT-Large's 367,480,636 = 4 × a
        # large prime) — pushes XLA into pathological 2-D re-tilings of
        # the 1-D vector that OOM on padding.
        buckets, cur, cur_bytes = [], [], 0
        for i in idxs:
            nbytes = compressed[i][0].size * dt.itemsize
            if cur and cur_bytes + nbytes > threshold:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        buckets.append(cur)
        for bucket in buckets:
            parts = [compressed[i][0] for i in bucket]
            with jax.named_scope(f"bucket{n_buckets}"):
                with scope("pack"):
                    flats = [x.reshape(-1) for x in parts]
                    buf = jnp.concatenate(flats) if len(flats) > 1 \
                        else flats[0]
                    # Tile-friendly length (the FUSION_BUFFER_ATOMIC_UNIT
                    # move, common.h:156): without it XLA may factor an
                    # odd-length vector into (huge, 2) and pad the lane
                    # dim 64x.
                    pad = (-buf.size) % 1024
                    if pad:
                        buf = jnp.pad(buf, (0, pad))
                n_buckets += 1
                wire_bytes += buf.size * dt.itemsize
                if int8_route and jnp.issubdtype(dt, jnp.floating):
                    # int8 can't ride a plain psum (overflow + per-rank
                    # scales): route the bucket through the two-phase
                    # quantized exchange (shared wrapper so the eager
                    # fusion path can never diverge on scaling order).
                    from horovod_tpu.parallel.strategies import \
                        scaled_allreduce_int8
                    buf = scaled_allreduce_int8(
                        buf, axis_name=axis_name, average=(op == Average),
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor)
                else:
                    buf = in_jit.allreduce(
                        buf, op=op, axis_name=axis_name,
                        process_set=process_set,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor)
                with scope("unpack"):
                    off = 0
                    for i, x in zip(bucket, parts):
                        out[i] = jax.lax.slice_in_dim(
                            buf, off, off + x.size).reshape(x.shape)
                        off += x.size
    from horovod_tpu.metrics import instruments as hvd_metrics
    hvd_metrics.record_fused_allreduce(lax.axis_size(axis_name), n_buckets,
                                       wire_bytes)
    with scope("unpack"):
        return [compression.decompress(o, ctx)
                for o, (_, ctx) in zip(out, compressed)]


def allreduce_gradients_transform(op=Average, axis_name=HVD_AXIS,
                                  process_set=None,
                                  compression=Compression.none,
                                  prescale_factor=1.0, postscale_factor=1.0):
    """An optax transform that allreduces the incoming gradients."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        if axis_name is None:
            return updates, state
        return fused_allreduce_tree(
            updates, op=op, axis_name=axis_name, process_set=process_set,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor), state

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer, op=Average, axis_name=HVD_AXIS,
                         process_set=None, compression=Compression.none,
                         backward_passes_per_step=1,
                         average_aggregated_gradients=True,
                         prescale_factor=1.0, postscale_factor=1.0):
    """Wrap an optax optimizer with cross-replica gradient reduction.

    Use inside a shard_mapped/pjitted train step whose data axis is
    ``axis_name``; pass ``axis_name=None`` for single-replica runs (the
    reduction becomes a no-op, like running the reference without hvd ranks).

    reference: torch/optimizer.py:517 DistributedOptimizer(...) /
    tensorflow/__init__.py:822; backward_passes_per_step aggregation
    reference: gradient_aggregation.py.
    """
    if backward_passes_per_step < 1:
        raise ValueError(
            f"backward_passes_per_step must be >= 1, got "
            f"{backward_passes_per_step}")
    from horovod_tpu.optim.powersgd import (PowerSGDCompressor,
                                            powersgd_gradients_transform)
    if isinstance(compression, PowerSGDCompressor):
        # Stateful low-rank compression: its own transform carries the
        # warm-start factors + error feedback (powersgd.py).
        reduce_tx = powersgd_gradients_transform(
            rank=compression.rank, op=op, axis_name=axis_name,
            process_set=process_set,
            min_compression_rate=compression.min_compression_rate,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            ef_dtype=compression.ef_dtype)
    else:
        reduce_tx = allreduce_gradients_transform(
            op=op, axis_name=axis_name, process_set=process_set,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    tx = optax.chain(reduce_tx, optimizer)
    if backward_passes_per_step > 1:
        tx = _local_aggregation(tx, backward_passes_per_step,
                                average_aggregated_gradients, axis_name)
    return tx


class _AggState(NamedTuple):
    step: jnp.ndarray
    acc: any
    inner: any


def _local_aggregation(inner, k, average, axis_name):
    """Accumulate gradients locally for ``k`` backward passes; run the inner
    transform (which contains the allreduce) only on the boundary step — so
    cross-replica communication happens once per ``k`` passes
    (reference: gradient_aggregation.py LocalGradientAggregationHelper).

    Hand-rolled rather than optax.MultiSteps because the skip/do branches must
    carry identical device-varying types inside shard_map (MultiSteps' cond
    branches trip the vma check); we harmonize with lax.pcast/pvary.
    """

    def _mark_varying(tree):
        if axis_name is None:
            return tree
        return in_jit.mark_varying(tree, axis_name)

    def init_fn(params):
        return _AggState(step=jnp.zeros((), jnp.int32),
                         acc=jax.tree_util.tree_map(jnp.zeros_like, params),
                         inner=inner.init(params))

    def update_fn(updates, state, params=None):
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, updates)
        boundary = (state.step + 1) % k == 0

        def do(acc, inner_state, params):
            g = jax.tree_util.tree_map(
                lambda a: a / k, acc) if average else acc
            u, s = inner.update(g, inner_state, params)
            zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return _mark_varying((u, s, zero))

        def skip(acc, inner_state, params):
            u = jax.tree_util.tree_map(jnp.zeros_like, updates)
            return _mark_varying((u, inner_state, acc))

        u, inner_state, acc = lax.cond(boundary, do, skip, acc, state.inner,
                                       params)
        return u, _AggState(step=state.step + 1, acc=acc, inner=inner_state)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def distributed_value_and_grad(fun, op=Average, axis_name=HVD_AXIS,
                               process_set=None, compression=Compression.none,
                               **grad_kwargs):
    """``jax.value_and_grad`` + allreduce — the DistributedGradientTape analog
    (reference: tensorflow/__init__.py:957 _DistributedGradientTape)."""
    vg = jax.value_and_grad(fun, **grad_kwargs)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        if axis_name is not None:
            grads = fused_allreduce_tree(grads, op=op, axis_name=axis_name,
                                         process_set=process_set,
                                         compression=compression)
        return value, grads

    return wrapped


def broadcast_parameters(params, root_rank=0, process_set=None,
                         stacked=False):
    """Eager broadcast of a parameter pytree from ``root_rank`` so all ranks
    start identical (reference: torch/__init__.py broadcast_parameters /
    _keras/callbacks.py BroadcastGlobalVariablesCallback).

    With ``stacked=False`` (default) every leaf is a replicated array and all
    ranks receive the root's value. With ``stacked=True`` every leaf must be
    rank-major stacked (leading axis == set size) and broadcasts slice-wise.
    The mode is explicit because a replicated leaf whose first dim happens to
    equal the world size is indistinguishable from a stacked one.
    """
    from horovod_tpu import trace
    from horovod_tpu.common import basics
    from horovod_tpu.common.process_sets import global_process_set
    from horovod_tpu.ops import collective_ops as C

    ps = process_set if process_set is not None else global_process_set
    n = ps.size() if ps.ranks is not None else basics.size()
    # Eager stacked contract: single-process supplies all n rows, a
    # multi-process member only the rows of its local chips.
    n_rows = C._expected_rows(ps.mesh, n)

    def bcast_leaf(leaf):
        leaf = jnp.asarray(leaf)
        if stacked:
            return C.broadcast(leaf, root_rank, process_set=process_set)
        tiled = jnp.broadcast_to(leaf[None], (n_rows,) + leaf.shape)
        out = C.broadcast(tiled, root_rank, process_set=process_set)
        return out[0]

    leaves = jax.tree_util.tree_leaves(params)
    with trace.run_span("broadcast_parameters", args={
            "leaves": len(leaves),
            "bytes": sum(getattr(x, "nbytes", 0) for x in leaves)}):
        return jax.tree_util.tree_map(bcast_leaf, params)


def broadcast_object_tree(obj, root_rank=0, process_set=None):
    """Broadcast an arbitrary python object (optimizer hyperparams, epoch
    counters, ...) — reference: broadcast_object (torch/functions.py)."""
    from horovod_tpu.ops.collective_ops import broadcast_object
    return broadcast_object(obj, root_rank=root_rank, process_set=process_set)

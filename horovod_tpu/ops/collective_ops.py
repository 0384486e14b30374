"""Eager collective operations over the TPU mesh.

Reference surface being matched: ``hvd.allreduce / grouped_allreduce / allgather /
broadcast / alltoall / reducescatter`` + async variants and handles
(reference: horovod/torch/mpi_ops.py:134-1285, horovod/common/operations.cc:1453-2086
``EnqueueTensorAllreduces`` etc., op math in horovod/common/ops/
collective_operations.cc).

TPU-native design — NOT a port of the background-thread/NCCL model:

- A Horovod rank is a chip in the global ``Mesh``. Eager tensors use the
  **rank-major stacked layout**: a collective input has leading axis ``set_size``
  and is sharded over the mesh's ``hvd`` axis, so slice ``[r]`` lives on chip
  ``r`` — the moral equivalent of "each rank's local tensor".
- Each (op, signature) pair compiles once into a ``shard_map``-wrapped XLA
  program using native ICI collectives (``lax.psum/all_gather/psum_scatter/
  all_to_all``). The compile cache keyed on the signature replaces the
  reference's coordinator negotiation + response cache
  (reference: horovod/common/controller.cc:74 ComputeResponseList,
  response_cache.h:45): a cache hit is a steady-state step with zero
  host-side negotiation.
- Async semantics come for free: JAX dispatch is asynchronous, so ``*_async``
  returns a handle wrapping the in-flight device array; ``synchronize`` blocks,
  ``poll`` checks readiness — matching the HandleManager contract
  (reference: horovod/torch/handle_manager.h, mpi_ops.py:1245-1283).
"""

import enum
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import trace as _trace
from horovod_tpu.chaos import injector as _chaos
from horovod_tpu.common import basics
from horovod_tpu.flight import recorder as _flight
from horovod_tpu.ops import wire as _wire
from horovod_tpu.profile import ledger as _profile
from horovod_tpu.common.exceptions import TensorShapeMismatchError
from horovod_tpu.common.process_sets import global_process_set
from horovod_tpu.common.topology import HVD_AXIS


class ReduceOp(enum.IntEnum):
    """reference: horovod/common/message.h:43-50 (enum ReduceOp)."""
    AVERAGE = 0
    SUM = 1
    ADASUM = 2
    MIN = 3
    MAX = 4
    PRODUCT = 5


# Public aliases matching hvd.Average / hvd.Sum / hvd.Adasum / ...
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


# ----------------------------------------------------------------------------
# Static-analysis interception (horovod_tpu/analysis/program.py).
#
# ``hvd.check_program`` abstract-evals a user step function per simulated
# rank with ZERO device execution; while it traces, every eager entry point
# below routes through this hook, which records the would-be dispatch
# (op, process set, signature) and returns an abstract stand-in result.
# One ``is not None`` check on the hot path when no analysis is running.
# ----------------------------------------------------------------------------

_intercept = None


def set_intercept(hook):
    """Install (or clear, with ``None``) the eager-dispatch interceptor.
    ``hook(kind, args, kwargs)`` may return ``NotImplemented`` to fall
    through to the real dispatch. Analysis-only: not thread-safe by
    design — the analyzer owns the process while tracing."""
    global _intercept
    prev = _intercept
    _intercept = hook
    return prev


def _interceptable(kind):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            hook = _intercept
            if hook is not None:
                out = hook(kind, args, kwargs)
                if out is not NotImplemented:
                    return out
            return fn(*args, **kwargs)

        wrapper.__wrapped__ = fn
        return wrapper

    return deco


def _mesh_for(process_set):
    ps = process_set if process_set is not None else global_process_set
    return ps.mesh, ps


@functools.lru_cache(maxsize=1024)
def _local_mesh_info(mesh):
    """``(spans_processes, local_positions)`` for a mesh: whether it includes
    devices owned by other processes, and the flat positions of this
    process's devices within it (rank-major).

    Multi-process eager semantics: each process supplies/receives the
    **local** slice of the rank-major stack — ``local_positions`` rows —
    while the compiled program runs over the global mesh (the multi-host
    contract the reference implements with per-rank buffers + NCCL/Gloo;
    here the global array is assembled with
    ``jax.make_array_from_process_local_data``).
    """
    devs = list(mesh.devices.flat)
    me = jax.process_index()
    local = tuple(i for i, d in enumerate(devs) if d.process_index == me)
    return len(local) != len(devs), local


def _mesh_processes(mesh):
    """Sorted process indices owning devices of ``mesh`` — the participant
    list for control-plane negotiations scoped to a process set."""
    return sorted({d.process_index for d in mesh.devices.flat})


def _expected_rows(mesh, n):
    """Leading-axis size of the eager stacked layout this process must
    supply: all ``n`` rows single-process, only the local rows otherwise."""
    multi, local_pos = _local_mesh_info(mesh)
    return len(local_pos) if multi else n


def _check_stacked(x, n, what):
    if x.ndim < 1 or x.shape[0] != n:
        raise TensorShapeMismatchError(
            f"{what}: expected rank-major stacked tensor with leading axis "
            f"{n} (one slice per rank), got shape {tuple(x.shape)}. ")


import contextlib
import time


def _ps_label(process_set):
    """Bounded-cardinality process-set label for metrics series: 'global'
    or the registered set id."""
    if process_set is None or process_set.ranks is None:
        return "global"
    pid = getattr(process_set, "process_set_id", None)
    return f"set{pid}" if pid is not None else "unregistered"


def _translate_dispatch_error(name, op_label, e):
    """Runtime-failure epilogue shared by :func:`_timeline_op` and the
    dispatch-plan fast path: count the error, then re-raise — translating
    transport/peer failures to :class:`HorovodInternalError`.

    Inside the dispatch only the compiled program executes (inputs were
    validated before it). Translate ONLY transport/peer failures to
    HorovodInternalError — those are what elastic recovery can fix by
    re-rendezvousing (e.g. status UNKNOWN "Gloo all-reduce failed:
    Connection closed by peer" maps to ValueError, coordination
    aborts to JaxRuntimeError). Deterministic runtime errors (OOM =
    RESOURCE_EXHAUSTED, shape/layout issues) must propagate as-is or
    the elastic @run wrapper would retry them forever."""
    from horovod_tpu.metrics import instruments as hvd_metrics
    hvd_metrics.record_collective_error(op_label)
    if _flight.armed:
        # The flight recorder's reason to exist: a failed dispatch leaves
        # a per-rank JSONL dump (ring of recent collectives + this error)
        # for horovod_tpu.flight.analyze to merge — no pre-arming needed.
        _flight.record_event("error", op=op_label, name=name,
                             what=(str(e).splitlines() or [""])[0][:200])
        _flight.dump("dispatch_error")
    from horovod_tpu.common.exceptions import HorovodInternalError
    if isinstance(e, HorovodInternalError):
        raise e
    msg = str(e)
    transport = any(m in msg for m in (
        "UNAVAILABLE", "UNKNOWN", "DEADLINE_EXCEEDED", "ABORTED",
        "CANCELLED", "Gloo", "gloo", "onnection",  # Connection/connection
        "peer", "heartbeat", "oordination", "socket", "Socket"))
    if jax.process_count() > 1 and transport:
        raise HorovodInternalError(
            f"collective {name} failed at runtime: "
            f"{(msg.splitlines() or [''])[0][:200]}") from e
    raise e


def _set_wire_tiers(process_set, wire_nbytes, sched):
    """Per-tier split of a NON-planned eager dispatch's wire bytes over
    its process set's member ranks — the plan path's ``_flat_tiers`` rule
    (the static model classifies by real members, so a set confined to
    one slice books zero dcn even when the world spans several). Returns
    ``None`` for the global set / single-slice layouts, where
    ``record_wire``'s world-level default split already matches."""
    try:
        if process_set is None or getattr(process_set, "ranks", None) is None:
            return None
        st = basics._state
        world = st.topology.size if st is not None else 0
        slices, slice_size = _live_slices(world) if world else (1, 1)
        if slices <= 1 or not wire_nbytes:
            return None
        members = process_set.rank_list()
        frac = _wire.a2a_dcn_fraction(members, slice_size) \
            if sched == "a2a" \
            else _wire.ring_dcn_fraction(members, slice_size)
        return _wire.split_tiers(wire_nbytes, frac)
    except Exception:  # noqa: BLE001 — accounting must never break a
        return None    # dispatch


@contextlib.contextmanager
def _op_span(tl, op_kind, name):
    """One eager dispatch under its name: the profiler's annotation
    ``hvd::<OP>::<name>``, so device profiles correlate with timeline
    buckets by name (SURVEY §5.1: the reference's NVTX ranges around every
    enqueue, nvtx_op_range.h), round the timeline's bucket where one is
    open. Not written to the span store: the dispatch's own record there
    carries its flight seq (``_timeline_op``)."""
    with _trace.span(f"{op_kind}::{name}", store=False):
        with tl.op_span(name, op_kind) if tl is not None \
                else contextlib.nullcontext():
            yield


@contextlib.contextmanager
def _timeline_op(name, op_kind, tensors=(), process_set=None,
                 op_label=None, ps_label=None, wire=None):
    """Timeline span + metrics + failure translation around one eager
    collective.

    Metrics: the span is the single choke point every eager dispatch (sync
    ops AND fused flush buckets) passes through, so per-op count/bytes go
    in at entry (failures still count as attempts) and the latency
    histogram on successful return — the aggregate layer the reference
    never had (its observability stops at the timeline trace).
    ``op_label``/``ps_label``: precomputed label strings (the dispatch-plan
    fast path passes them so nothing is re-formatted per call).

    ``wire``: optional ``(path, dtype_label, wire_nbytes, compressed[,
    tiers])`` override — or a LIST of such tuples (the hierarchical
    dispatch paths record one per link tier) — for the wire-byte
    accounting (the fused flush and the quantized eager path pass their
    exact on-wire estimate); without it the payload dtype/bytes are
    derived here (allreduce counts both internal RS+AG legs).

    A collective that dies at runtime (peer process gone, transport torn
    down mid-op) must surface as :class:`HorovodInternalError` so the
    elastic ``@run`` wrapper can restore the last commit and re-rendezvous
    (reference: common/exceptions.py — op status callbacks raise
    HorovodInternalError; nccl_operations.h:70 async error polling)."""
    from horovod_tpu.metrics import instruments as hvd_metrics
    if op_label is None:
        op_label = op_kind.lower()
    # Profiler bracket opens BEFORE the chaos site: an injected delay is a
    # host-side stall of THIS rank's dispatch path, and landing it in the
    # ledger's host_dispatch category is what lets the watchdog name the
    # straggler by its own-rank signal (its peers book the wait under
    # `collective` instead).
    profile_on = _profile.armed
    if profile_on:
        t_api = time.perf_counter()
    if _chaos.armed:
        # Chaos site: a delay here holds THIS rank's enqueue back while its
        # peers dispatch — the straggler mode of the SPMD contract.
        _chaos.fire("collective.dispatch")
    # Gated HERE, not just inside the helpers: the nbytes sum is
    # O(n_tensors) and must cost nothing under HOROVOD_METRICS=0.
    metrics_on = hvd_metrics.enabled()
    flight_on = _flight.armed
    if metrics_on or flight_on or profile_on:
        nbytes = sum(getattr(t, "nbytes", 0) for t in tensors)
        if ps_label is None:
            ps_label = _ps_label(process_set)
        t0 = time.perf_counter()
    if metrics_on:
        hvd_metrics.record_collective(op_label, nbytes, ps_label)
        if wire is not None:
            for w in (wire if isinstance(wire, list) else [wire]):
                hvd_metrics.record_wire(
                    w[0], w[1], w[2], w[3],
                    tiers=w[4] if len(w) > 4 else None)
        elif tensors:
            wb = nbytes * (2 if op_kind == "ALLREDUCE" else 1)
            sched = "a2a" if op_kind == "ALLTOALL" else "ring"
            hvd_metrics.record_wire(
                "eager", str(_dtype_of(tensors[0])), wb, sched=sched,
                tiers=_set_wire_tiers(process_set, wb, sched))
    if flight_on:
        # SPMD contract: every process dispatches the same collectives in
        # the same order, so the per-process-set seq assigned here lines
        # up across ranks — the analyzer's desync key. Caveat: seq is
        # arrival-ordered, so when the fusion CYCLE THREAD flushes
        # concurrently with main-thread eager dispatches the eager/fused
        # interleaving (and thus seq->op mapping) can differ per rank;
        # max-seq comparisons stay valid, first-diverging identification
        # is corroborated by op/sig in the analyzer.
        fl_seq = _flight.record_dispatch(op_label, ps_label, nbytes,
                                         _flight.signature(tensors), name)
    try:
        with _op_span(basics.timeline(), op_kind, name):
            yield
        if metrics_on or flight_on or profile_on:
            dur = time.perf_counter() - t0
        if metrics_on:
            hvd_metrics.record_collective_latency(op_label, dur)
        if flight_on:
            _flight.record_complete(op_label, ps_label, fl_seq, dur)
            # Dispatch span under the ACTIVE step trace (rotated by
            # step_marker); correlates with the flight ring via the seq
            # the dispatch event carries.
            _trace.add_span(_trace.get_active(), "dispatch",
                            time.time() - dur, dur, cat="train",
                            args={"op": op_label, "seq": fl_seq})
        if profile_on:
            # dur covers the program call (+ localize on the caller side
            # of the yield) = `collective`; everything else between the
            # bracket open and here is dispatch-path overhead.
            _profile.record_dispatch(
                op_label, dur, time.perf_counter() - t_api - dur, nbytes)
    except (ValueError, RuntimeError) as e:
        _translate_dispatch_error(name, op_label, e)


def _is_float(dtype):
    return jnp.issubdtype(dtype, jnp.floating) or \
        jnp.issubdtype(dtype, jnp.complexfloating)


def _dtype_of(t):
    """Dtype without materializing a device array (hot-path friendly)."""
    dt = getattr(t, "dtype", None)
    return dt if dt is not None else np.result_type(t)


# ----------------------------------------------------------------------------
# In-jit reduction bodies (applied per-shard inside shard_map).
# ----------------------------------------------------------------------------

def _reduce_shard(x, op, n, prescale, postscale, axis_name, active=None):
    """Reduce one rank's shard across ``axis_name``. x: (1, ...) local slice.

    ``active``: optional 0/1 numpy vector over ranks — joined ranks are
    excluded (reference: JOIN / joined_size accounting,
    controller.cc:269-327): Sum treats them as zeros, Average divides by the
    active count, Min/Max/Product/Adasum statically drop their slices.
    """
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, x.dtype)
    n_active = n if active is None else int(active.sum())
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        if active is not None:
            keep = jnp.asarray(active)[lax.axis_index(axis_name)]
            x = x * keep.astype(x.dtype)
        y = lax.psum(x, axis_name)
        if op == ReduceOp.AVERAGE:
            y = y / jnp.asarray(n_active, y.dtype)
    elif active is not None:
        # non-linear ops: gather all, statically select the active ranks
        g = lax.all_gather(jnp.squeeze(x, 0), axis_name)
        g = g[np.nonzero(active)[0]]
        if op == ReduceOp.MIN:
            y = jnp.min(g, axis=0)[None]
        elif op == ReduceOp.MAX:
            y = jnp.max(g, axis=0)[None]
        elif op == ReduceOp.PRODUCT:
            y = jnp.prod(g, axis=0)[None]
        elif op == ReduceOp.ADASUM:
            from horovod_tpu.ops.adasum import adasum_tree
            y = adasum_tree([g[i] for i in range(n_active)])[None]
        else:
            raise ValueError(f"Unknown reduce op {op}")
    elif op == ReduceOp.MIN:
        y = lax.pmin(x, axis_name)
    elif op == ReduceOp.MAX:
        y = lax.pmax(x, axis_name)
    elif op == ReduceOp.PRODUCT:
        g = lax.all_gather(x, axis_name)  # (n, 1, ...)
        y = jnp.prod(g, axis=0)
    elif op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_reduce_shard
        y = adasum_reduce_shard(x, axis_name, n)
    else:
        raise ValueError(f"Unknown reduce op {op}")
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, y.dtype)
    return y


# ----------------------------------------------------------------------------
# Compiled-program cache: signature -> jitted shard_map program.
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _allreduce_program(mesh, n, op, prescale, postscale, shapes, dtypes,
                       active_mask=None, donate=False):
    """``active_mask``: optional tuple of 0/1 per rank — joined ranks are
    masked out of the reduction and Average divides by the active count
    (reference: JOIN handling / joined_size accounting, controller.cc:269-327
    and operations.cc global joined_size). ``donate``: donate every input
    buffer to XLA so the output reuses its HBM — the eager-path opt-in
    (``HOROVOD_DONATE_BUFFERS`` set explicitly; used by the dispatch-plan
    fast path only when the inputs are already sharded jax.Arrays, where
    in-place reuse is actually possible)."""
    active = None if active_mask is None else np.array(active_mask)

    def body(*xs):
        return tuple(
            _reduce_shard(x, op, n, prescale, postscale, HVD_AXIS, active)
            for x in xs)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(P(HVD_AXIS) for _ in shapes),
                      out_specs=tuple(P(HVD_AXIS) for _ in shapes))
    return jax.jit(f, donate_argnums=tuple(range(len(shapes)))
                   if donate else ())


@functools.lru_cache(maxsize=1024)
def _quantized_allreduce_program(mesh, n, op, prescale, postscale, shapes,
                                 dtypes, wire_name, ef):
    """Eager allreduce over the block-scaled quantized exchange
    (ops/wire.py): the group's tensors are concatenated into ONE flat
    fp32 buffer (minimizing the exchange's n×BLOCK padding, exactly like
    the fused path), exchanged at 1 byte/element with per-block scales,
    then split/cast back per tensor. With ``ef`` the program additionally
    takes the bucket's fp32 residual — global ``(n, L)`` sharded rank-major
    — and returns the new residual as its last output (error feedback:
    residual added after prescale, before quantization)."""
    sizes = [int(np.prod(s[1:])) for s in shapes]
    flat_len = sum(sizes)

    def body(*args):
        xs = args[:len(shapes)]
        flats = [x.reshape(-1).astype(jnp.float32) for x in xs]
        buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        residual = args[-1].reshape(-1) if ef else None
        red, new_res = _wire.block_scaled_allreduce(
            buf, residual=residual, axis_name=HVD_AXIS, wire=wire_name,
            average=(op == ReduceOp.AVERAGE), prescale_factor=prescale,
            postscale_factor=postscale)
        outs, off = [], 0
        for x, sz in zip(xs, sizes):
            piece = lax.slice_in_dim(red, off, off + sz).astype(x.dtype)
            outs.append(piece.reshape(x.shape))
            off += sz
        if ef:
            outs.append(new_res.reshape(1, flat_len))
        return tuple(outs)

    n_args = len(shapes) + (1 if ef else 0)
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(P(HVD_AXIS) for _ in range(n_args)),
                      out_specs=tuple(P(HVD_AXIS) for _ in range(n_args)))
    return jax.jit(f)


def _live_slices(n):
    """``(num_slices, slice_size)`` the dispatch layer sees RIGHT NOW for
    an ``n``-rank world: the forced ``HOROVOD_MESH_SLICES`` knob (read
    live, like the static model's ``resolve_slices``), else the
    initialized topology's DCN hierarchy — both through
    ``topology.slice_layout``'s divisibility rules, so runtime and static
    layouts can never disagree."""
    from horovod_tpu.common import topology as _topology
    k = _topology.forced_slices()
    if not k:
        st = basics._state
        topo = st.topology if st is not None else None
        if topo is not None and topo.num_slices > 1 and topo.size == n:
            k = topo.num_slices
        else:
            return 1, max(int(n), 1)
    return _topology.slice_layout(n, k)


@functools.lru_cache(maxsize=64)
def _hier_mesh(mesh, num_slices):
    """(slice x chips-per-slice) mesh over one process set's devices — the
    2-level decomposition's (cross=DCN, local=ICI) factorization. The
    initialized topology's real DCN mesh is preferred when it covers the
    same devices (its device order is slice-sorted); a forced/virtual
    hierarchy reshapes the set's rank-major device array like
    ``topology._build_dcn_mesh`` does. Cleared by
    :func:`clear_program_caches` — an elastic resize must never replay a
    stale slice layout."""
    from jax.sharding import Mesh
    from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
    devs = list(mesh.devices.flat)
    st = basics._state
    topo = st.topology if st is not None else None
    if topo is not None and topo.mesh_dcn is not None \
            and topo.num_slices == num_slices \
            and set(topo.mesh_dcn.devices.flat) == set(devs):
        return topo.mesh_dcn
    per = len(devs) // int(num_slices)
    arr = np.array(devs, dtype=object).reshape(int(num_slices), per)
    return Mesh(arr, (CROSS_AXIS, LOCAL_AXIS))


@functools.lru_cache(maxsize=1024)
def _hier_allreduce_program(hier_mesh, n, op, prescale, postscale, shapes,
                            dtypes, cross_wire, ef):
    """Eager allreduce through the hierarchical dispatch tier: the group's
    (dtype-homogeneous) tensors are concatenated into ONE flat buffer,
    decomposed as local RS (exact, ICI) -> cross-slice allreduce on
    ``cross_wire`` (DCN; ``""`` = exact psum) -> local AG
    (``strategies.allreduce_torus`` — the fork's NCCLTorusAllreduce
    shape), then split back per tensor. With ``ef`` the program takes the
    bucket's fp32 cross-leg residual — global ``(n, shard_len)`` sharded
    rank-major — and returns the new residual as its last output."""
    from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
    from horovod_tpu.ops.in_jit import mark_varying
    from horovod_tpu.parallel.strategies import allreduce_torus
    sizes = [int(np.prod(s[1:])) for s in shapes]
    total = sum(sizes)
    local_n = int(hier_mesh.shape[LOCAL_AXIS])
    shard_len = -(-total // local_n)
    spec = P((CROSS_AXIS, LOCAL_AXIS))

    def body(*args):
        xs = args[:len(shapes)]
        flats = [x.reshape(-1) for x in xs]
        buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        if prescale != 1.0:
            buf = buf * jnp.asarray(prescale, buf.dtype)
        residual = args[-1].reshape(-1) if ef else None
        out = allreduce_torus(buf, average=(op == ReduceOp.AVERAGE),
                              cross_compression=cross_wire or None,
                              cross_residual=residual, record=False)
        if residual is not None:
            out, new_res = out
        if postscale != 1.0:
            out = out * jnp.asarray(postscale, out.dtype)
        # The cross psum/exchange leaves the value cross-invariant; the
        # stacked out_specs need it typed varying over both mesh axes.
        out = mark_varying(mark_varying(out, CROSS_AXIS), LOCAL_AXIS)
        outs, off = [], 0
        for x, sz in zip(xs, sizes):
            piece = lax.slice_in_dim(out, off, off + sz).astype(x.dtype)
            outs.append(piece.reshape(x.shape))
            off += sz
        if ef:
            res_out = mark_varying(
                mark_varying(new_res.reshape(1, shard_len), CROSS_AXIS),
                LOCAL_AXIS)
            outs.append(res_out)
        return tuple(outs)

    n_args = len(shapes) + (1 if ef else 0)
    f = jax.shard_map(body, mesh=hier_mesh,
                      in_specs=tuple(spec for _ in range(n_args)),
                      out_specs=tuple(spec for _ in range(n_args)))
    return jax.jit(f)


@functools.lru_cache(maxsize=4096)
def _allgather_program(mesh, n, shapes, dtypes, active_mask=None,
                       hierarchical=False):
    """``active_mask``: joined ranks contribute a zero-size slice, i.e. their
    rows are statically dropped from the concatenated output (reference: JOIN
    gives joined ranks zero-size allgather contributions,
    controller.cc:269-327). ``hierarchical``: 2-level gather over the
    (cross, local) mesh2d — ``mesh`` must then be it (knob
    HOROVOD_HIERARCHICAL_ALLGATHER; reference MPIHierarchicalAllgather)."""
    active_idx = None if active_mask is None else \
        np.nonzero(np.array(active_mask))[0]
    if hierarchical:
        from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
        from horovod_tpu.parallel.strategies import allgather_hierarchical
        spec = P((CROSS_AXIS, LOCAL_AXIS))
    else:
        spec = P(HVD_AXIS)

    def body(*xs):
        out = []
        for x in xs:
            # x: (1, m, ...) local slice; gather along the stacked axis and
            # flatten to the concatenated layout Horovod returns
            # (reference: collective_operations.h:137-174 size/displacement math).
            if hierarchical:
                # record=False: this eager program's dispatches are
                # metered per call by the plan/_timeline_op — trace-time
                # recording on top would double-count.
                g = allgather_hierarchical(x[0], record=False)  # (n, m, …)
                from horovod_tpu.ops.in_jit import mark_varying
                g = mark_varying(mark_varying(g, CROSS_AXIS), LOCAL_AXIS)
            else:
                g = lax.all_gather(x, HVD_AXIS, axis=0, tiled=True)
            if active_idx is not None:
                g = g[active_idx]
            g = g.reshape((1, -1) + g.shape[2:]) if g.ndim > 1 else g
            out.append(g)
        return tuple(out)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(spec for _ in shapes),
                      out_specs=tuple(spec for _ in shapes))
    return jax.jit(f)


@functools.lru_cache(maxsize=4096)
def _broadcast_program(mesh, n, root_rank, shapes, dtypes):
    def body(*xs):
        out = []
        for x in xs:
            idx = lax.axis_index(HVD_AXIS)
            mask = (idx == root_rank)
            # One-hot mask + psum == broadcast from root; a single ICI
            # collective, like the reference's tree broadcast
            # (reference: MPIBroadcast mpi_operations.cc).
            if _is_float(x.dtype) or jnp.issubdtype(x.dtype, jnp.integer):
                masked = jnp.where(mask, x, jnp.zeros_like(x))
                out.append(lax.psum(masked, HVD_AXIS))
            else:  # bool etc.
                masked = jnp.where(mask, x.astype(jnp.int32),
                                   jnp.zeros(x.shape, jnp.int32))
                out.append(lax.psum(masked, HVD_AXIS).astype(x.dtype))
        return tuple(out)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(P(HVD_AXIS) for _ in shapes),
                      out_specs=tuple(P(HVD_AXIS) for _ in shapes))
    return jax.jit(f)


@functools.lru_cache(maxsize=4096)
def _reducescatter_program(mesh, n, op, prescale, postscale, shapes, dtypes,
                           active_mask=None):
    """``active_mask``: joined ranks contribute zeros to the reduction and
    Average divides by the active count (reference: joined_size accounting,
    controller.cc:269-327)."""
    active = None if active_mask is None else np.array(active_mask)
    n_active = n if active is None else int(active.sum())

    def body(*xs):
        out = []
        for x in xs:
            # x: (1, m, ...) — scatter the reduction of the m-axis across ranks
            # (reference: ReducescatterOp shape math collective_operations.h:282-309).
            x = jnp.squeeze(x, 0)
            if prescale != 1.0:
                x = x * jnp.asarray(prescale, x.dtype)
            if active is not None:
                keep = jnp.asarray(active)[lax.axis_index(HVD_AXIS)]
                x = x * keep.astype(x.dtype)
            if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
                y = lax.psum_scatter(x, HVD_AXIS, scatter_dimension=0, tiled=True)
                if op == ReduceOp.AVERAGE:
                    y = y / jnp.asarray(n_active, y.dtype)
            else:
                raise ValueError(
                    "reducescatter supports Sum/Average (reference parity: "
                    "reducescatter has no min/max/product either, message.h:43-50)")
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, y.dtype)
            out.append(y[None])
        return tuple(out)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(P(HVD_AXIS) for _ in shapes),
                      out_specs=tuple(P(HVD_AXIS) for _ in shapes))
    return jax.jit(f)


@functools.lru_cache(maxsize=4096)
def _alltoall_program(mesh, n, shapes, dtypes):
    def body(*xs):
        out = []
        for x in xs:
            x = jnp.squeeze(x, 0)  # (m, ...), m divisible by n
            y = lax.all_to_all(x, HVD_AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
            out.append(y[None])
        return tuple(out)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple(P(HVD_AXIS) for _ in shapes),
                      out_specs=tuple(P(HVD_AXIS) for _ in shapes))
    return jax.jit(f)


@functools.lru_cache(maxsize=1024)
def _hier_alltoall_program(hier_mesh, n, shapes, dtypes, cross_wire):
    """Eager equal-splits alltoall through the hierarchical dispatch
    tier: slice-local a2a (ICI) then ONE cross-slice a2a on the per-tier
    wire (DCN; ``""`` = exact, ``int8``/``fp8`` = block-scaled), compiled
    over the (slice x chips-per-slice) mesh
    (``strategies.alltoall_tiered`` — the a2a twin of
    :func:`_hier_allreduce_program`)."""
    from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
    from horovod_tpu.ops.in_jit import mark_varying
    from horovod_tpu.parallel.strategies import alltoall_tiered
    spec = P((CROSS_AXIS, LOCAL_AXIS))

    def body(*xs):
        out = []
        for x in xs:
            x = jnp.squeeze(x, 0)  # (m, ...), m divisible by n
            # record=False: this eager program's dispatches are metered
            # per call by the plan — trace-time recording on top would
            # double-count.
            y = alltoall_tiered(x, cross_wire=cross_wire or None,
                                record=False)
            y = mark_varying(mark_varying(y, CROSS_AXIS), LOCAL_AXIS)
            out.append(y[None])
        return tuple(out)

    f = jax.shard_map(body, mesh=hier_mesh,
                      in_specs=tuple(spec for _ in shapes),
                      out_specs=tuple(spec for _ in shapes))
    return jax.jit(f)


def clear_program_caches():
    """Drop all compiled eager-collective programs (and the mesh/device
    objects they capture). Needed when the backend is rebuilt — e.g. an
    elastic membership change (basics.teardown_distributed); the analog of
    the reference invalidating its response cache on world reconfig
    (response_cache.h:45, elastic abort path)."""
    for prog in (_local_mesh_info, _allreduce_program,
                 _quantized_allreduce_program, _hier_allreduce_program,
                 _hier_mesh, _allgather_program,
                 _broadcast_program, _reducescatter_program,
                 _alltoall_program, _hier_alltoall_program,
                 _barrier_program,
                 _alltoall_pack_index, _hier_verdict, _a2a_hier_verdict):
        prog.cache_clear()
    # The cached flat-schedule tier split reads the slice layout; a
    # resized/re-sliced mesh must re-resolve it (like the hierarchy-keyed
    # plans and programs above — elastic resize never replays a stale
    # slice layout).
    from horovod_tpu.metrics import instruments as _ins
    _ins.reset_tier_split()
    # Error-feedback residuals are device arrays of the torn-down backend
    # (and sized for the old world): a resized mesh must start clean.
    _wire.reset_error_feedback()
    # Dispatch plans capture compiled programs + NamedShardings of the
    # torn-down backend; a stale hit after an elastic resize would dispatch
    # into a dead client.
    _invalidate_plans()
    # Fused eager programs are keyed by Mesh too; stale entries would pin a
    # torn-down XLA client (and its buffers) for the rest of the job.
    from horovod_tpu.ops import fusion
    fusion._fused_program.cache_clear()
    fusion._flush_plans.clear()


@functools.lru_cache(maxsize=1024)
def _barrier_program(mesh):
    def body(x):
        return lax.psum(x, HVD_AXIS)

    f = jax.shard_map(body, mesh=mesh, in_specs=P(HVD_AXIS), out_specs=P(HVD_AXIS))
    return jax.jit(f)


# ----------------------------------------------------------------------------
# Input normalization
# ----------------------------------------------------------------------------

def _order_check(what, tensors, mesh):
    """HOROVOD_ORDER_CHECK=1 (debug): verify every process is dispatching
    THIS op with THIS signature — the runtime cross-rank analog of the
    reference coordinator's shape/dtype mismatch errors
    (controller.h:158-163), extended to catch order divergence (which
    otherwise surfaces as a hang or silent corruption). A rank calling a
    different number of collectives times out inside the exchange instead
    of hanging forever."""
    st = basics._get_state()
    if not st.config.order_check or jax.process_count() <= 1:
        return
    from horovod_tpu.common import negotiation
    # Leading axis excluded: it is the LOCAL chip count, which legitimately
    # differs across heterogeneous hosts.
    sig = [what] + [f"{tuple(getattr(t, 'shape', ()))[1:]}:"
                    f"{getattr(t, 'dtype', type(t).__name__)}"
                    for t in tensors]
    sigs = negotiation.exchange("order_check", sig,
                                procs=_mesh_processes(mesh))
    bad = {i: s for i, s in enumerate(sigs) if s != sig}
    if bad:
        raise TensorShapeMismatchError(
            f"collective order/signature mismatch: this process dispatched "
            f"{sig}, but process(es) {sorted(bad)} dispatched "
            f"{list(bad.values())[:3]} at the same point in the program — "
            f"every process must issue the same collectives in the same "
            f"order (docs/api.md eager multi-process contract).")


def _prepare(tensors, mesh, n, what):
    """Convert to device arrays sharded rank-major over the mesh.

    Single process: a single device_put per tensor (host numpy goes straight
    to the sharded layout; device arrays just reshard) — the moral analog of
    the fusion buffer's one-memcpy-in guarantee
    (reference: fusion_buffer_manager.h:40).

    Multi-process: each process passes the **local** rows of the rank-major
    stack (one per chip it owns); the global sharded array is assembled from
    the per-process pieces without touching non-addressable devices.
    """
    _order_check(what, tensors, mesh)
    sharding = NamedSharding(mesh, P(HVD_AXIS))
    multi, local_pos = _local_mesh_info(mesh)
    out = []
    for t in tensors:
        if not hasattr(t, "ndim"):
            t = np.asarray(t)
        if multi:
            n_local = len(local_pos)
            if t.ndim < 1 or t.shape[0] != n_local:
                raise TensorShapeMismatchError(
                    f"{what}: multi-process eager collectives take the "
                    f"local rank-major stack — leading axis {n_local} (one "
                    f"slice per local chip), got shape {tuple(t.shape)}.")
            out.append(jax.make_array_from_process_local_data(
                sharding, np.asarray(t), (n,) + tuple(t.shape[1:])))
        else:
            _check_stacked(t, n, what)
            out.append(jax.device_put(t, sharding))
    return out


def _localize(outs, mesh):
    """Return per-process local results in multi-process mode.

    The compiled program yields global arrays whose shards live on every
    host; a process can only read its own. Mirroring ``_prepare``'s input
    contract, each output is narrowed to the local rank-major stack (rows of
    this process's chips, in rank order).
    """
    multi, _ = _local_mesh_info(mesh)
    if not multi:
        return outs
    res = []
    for o in outs:
        shards = sorted(o.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        res.append(np.concatenate([np.asarray(s.data) for s in shards],
                                  axis=0))
    return res


def _signature(tensors):
    return (tuple(tuple(t.shape) for t in tensors),
            tuple(str(t.dtype) for t in tensors))


# ----------------------------------------------------------------------------
# Dispatch-plan cache: the eager hot path's one-cache-hit steady state.
#
# The compiled-program cache already replaces the reference's negotiation
# (response_cache.h:45), but every eager call still paid Python-side costs
# the program cache does not amortize: signature/string formatting,
# NamedSharding construction, per-call device_put of inputs, timeline/
# metrics setup even when observability is off, and a sort-per-call
# _localize. A _DispatchPlan resolves all of that ONCE per
# (op kind, mesh, process set, op params, tensor signature); steady state
# is: tuple-key dict hit -> compiled-program call -> indexed localization.
#
# Input staging on the plan path is the compiled program's own C++
# dispatch: jit uploads/reshards host or mismatched-sharding inputs and
# caches one executable per input-sharding signature, so no Python-side
# device_put runs per call (measured ~2x cheaper than device_put + call on
# the CPU tier), and an input that is already a correctly-sharded
# jax.Array passes through zero-copy. Multi-process keeps the explicit
# make_array_from_process_local_data assembly (local rows -> global array
# cannot be inferred by jit).
# ----------------------------------------------------------------------------

_PLAN_CAP = 4096
_plans = {}
_plan_stats = {"hits": 0, "misses": 0, "invalidations": 0}


def plan_cache_stats():
    """Copy of the dispatch-plan cache counters (always on — plain ints;
    the metrics registry carries the same series when enabled)."""
    return dict(_plan_stats, size=len(_plans))


def _invalidate_plans():
    if _plans:
        _plan_stats["invalidations"] += 1
        _plans.clear()


def _plan_sig(tensors):
    """Hashable per-tensor (shape, dtype) signature of a call, or None
    when any input is not ndarray-like (python scalars/lists take the
    generic path — they need np.asarray normalization first)."""
    sig = []
    for t in tensors:
        if not isinstance(t, (jax.Array, np.ndarray)):
            return None
        sig.append((t.shape, t.dtype))
    return tuple(sig)


def _plan_lookup(key, ps):
    """Return the hit plan for ``key`` — after re-checking the runtime
    conditions a plan cannot capture (join armed/active, debug order
    check), which re-route to the generic negotiated path. A hit fences
    in-flight fused async work exactly like :func:`_join_sync` does."""
    st = basics._state
    if st is None:
        return None
    plan = _plans.get(key)
    if plan is None:
        _plan_stats["misses"] += 1
        from horovod_tpu.metrics import instruments as hvd_metrics
        hvd_metrics.record_plan_cache("miss")
        return None
    cfg = st.config
    if cfg.order_check or st.joined_ranks or ps.joined_ranks \
            or (cfg.join_mode and jax.process_count() > 1):
        return None
    if st.fusion is not None:
        st.fusion.fence()
    _plan_stats["hits"] += 1
    from horovod_tpu.metrics import instruments as hvd_metrics
    hvd_metrics.record_plan_cache("hit")
    return plan


def _plan_eligible(st, active_mask):
    """A plan may be registered only for dispatches whose control path is
    pure (no join mask, no armed per-op negotiation, no debug order
    check) — everything a plan precomputes is then call-invariant."""
    return (active_mask is None and not st.config.order_check
            and not (st.config.join_mode and jax.process_count() > 1))


def _register_plan(key, plan):
    if len(_plans) >= _PLAN_CAP:
        _plans.pop(next(iter(_plans)))      # drop the oldest entry
    _plans[key] = plan
    return plan


class _DispatchPlan:
    """Everything one eager-collective signature needs per call, resolved
    once: compiled program (plus the opt-in donating variant), input
    NamedSharding, global stacked shapes, metrics label strings, and the
    output localization order (shard order resolved on first use —
    localization becomes indexed ``np.asarray`` without re-sorting)."""

    __slots__ = ("kind", "op_kind", "op_label", "default_name", "program",
                 "donate_program", "mesh", "sharding", "ps", "ps_label",
                 "multi", "global_shapes", "nbytes", "sig", "wire_label",
                 "wire_nbytes", "wire_sched", "wire_tiers",
                 "_localize_order", "_stage_memo")

    _STAGE_MEMO_CAP = 16

    @staticmethod
    def _spec_for(mesh):
        """Input/output PartitionSpec over ``mesh`` — the rank-major 1-D
        stack by default; the hierarchical plan shards the same leading
        axis over its (cross, local) factorization instead."""
        return P(HVD_AXIS)

    def __init__(self, kind, op_kind, program, mesh, ps, staged,
                 default_name, donate_program=None):
        self.kind = kind
        self.op_kind = op_kind
        self.op_label = op_kind.lower()
        self.default_name = default_name
        self.program = program
        self.donate_program = donate_program
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, self._spec_for(mesh))
        self.ps = ps
        self.ps_label = _ps_label(ps)
        self.multi = _local_mesh_info(mesh)[0]
        # Derived from the registration call's staged (global) tensors:
        # every later key-matched call has the same shapes/dtypes, so the
        # metrics byte count is a plan constant, not a per-call walk.
        self.global_shapes = tuple(tuple(t.shape) for t in staged)
        self.nbytes = sum(getattr(t, "nbytes", 0) for t in staged)
        # Flight-recorder signature: a plan constant (every key-matched
        # call shares shapes/dtypes), so the hot path never re-hashes.
        self.sig = _flight.signature(staged)
        # Wire accounting constants (first tensor's dtype stands for the
        # group; allreduce counts both internal RS+AG legs; the leg
        # schedule steers the default tier split — alltoall legs use the
        # foreign-destination fraction like the static model).
        self.wire_label = str(staged[0].dtype) if staged else None
        self.wire_nbytes = self.nbytes * (2 if op_kind == "ALLREDUCE" else 1)
        self.wire_sched = "a2a" if op_kind == "ALLTOALL" else "ring"
        # Plan-constant tier split over THIS SET'S member ranks (the
        # static model classifies by real members, and e.g. a process set
        # confined to one slice must book zero dcn even though the world
        # spans several): None on single-slice layouts — record_wire's
        # default (which matches for the global set) then applies.
        self.wire_tiers = self._flat_tiers()
        self._localize_order = None
        # id(src) -> (weakref(src), staged): re-sharding the SAME
        # immutable jax.Array every step (re-reducing a pinned buffer)
        # is pure waste — stage once, reuse while the source is alive.
        # WEAK source refs: a fresh-gradient-per-step loop gets no memo
        # hits, and strong refs would pin up to CAP dead source+staged
        # buffer pairs per plan; the weakref callback drops the staged
        # copy the moment the caller's array dies, and the liveness
        # check (wr() is t) guards id reuse. Host numpy is NEVER
        # memoized (mutable in place).
        self._stage_memo = {}

    def _flat_tiers(self):
        """{"ici","dcn"} split of this plan's wire bytes by its set's
        member ranks against the live slice layout, or None when
        single-slice (everything defaults to ici)."""
        try:
            st = basics._state
            world = st.topology.size if st is not None else 0
            slices, slice_size = _live_slices(world) if world else (1, 1)
            if slices <= 1 or not self.wire_nbytes:
                return None
            n = self.global_shapes[0][0] if self.global_shapes else 1
            members = self.ps.rank_list() if self.ps.ranks is not None \
                else list(range(n))
            frac = _wire.a2a_dcn_fraction(members, slice_size) \
                if self.wire_sched == "a2a" \
                else _wire.ring_dcn_fraction(members, slice_size)
            return _wire.split_tiers(self.wire_nbytes, frac)
        except Exception:  # noqa: BLE001 — accounting must never break
            return None    # plan construction

    def run(self, tensors, name=None):
        # Profiler bracket opens at API entry so input staging (and the
        # chaos delay site inside dispatch) land in host_dispatch.
        t_api = time.perf_counter() if _profile.armed else None
        if self.multi:
            sharding = self.sharding
            staged = [jax.make_array_from_process_local_data(
                          sharding, np.asarray(t), g)
                      for t, g in zip(tensors, self.global_shapes)]
            return self.dispatch(staged, name, prog=self.program,
                                 t_api=t_api)
        sharding = self.sharding
        staged = []
        passthrough = True
        memo = self._stage_memo
        for t in tensors:
            if isinstance(t, jax.Array):
                if t.sharding == sharding:
                    staged.append(t)        # zero-copy passthrough
                    continue
                passthrough = False
                m = memo.get(id(t))
                if m is not None and m[0]() is t:
                    staged.append(m[1])
                    continue
                s = jax.device_put(t, sharding)
                if len(memo) >= self._STAGE_MEMO_CAP:
                    memo.clear()
                try:
                    wr = weakref.ref(
                        t, lambda _, k=id(t), m=memo: m.pop(k, None))
                except TypeError:
                    pass            # not weakref-able: stage, don't memo
                else:
                    memo[id(t)] = (wr, s)
                staged.append(s)
            else:
                # Host numpy: the program's own C++ dispatch stages it.
                passthrough = False
                staged.append(t)
        # Donation ONLY for all-passthrough calls: the caller's own
        # correctly-sharded arrays (the explicit opt-in contract). A
        # memoized staged copy must never be donated — its buffer would
        # be dead on the next memo hit.
        prog = self.donate_program \
            if self.donate_program is not None and passthrough \
            else self.program
        return self.dispatch(staged, name, prog=prog, t_api=t_api)

    def _program_for(self, staged):
        """The donating program applies only when every input is already a
        correctly-sharded jax.Array: donation is then real in-place buffer
        reuse (and the caller explicitly opted into losing its inputs via
        HOROVOD_DONATE_BUFFERS); anything else keeps the plain program —
        donating a to-be-resharded buffer is a no-op plus an XLA warning."""
        if self.donate_program is None:
            return self.program
        sharding = self.sharding
        for t in staged:
            if not (isinstance(t, jax.Array) and t.sharding == sharding):
                return self.program
        return self.donate_program

    def dispatch(self, staged, name=None, prog=None, t_api=None):
        from horovod_tpu.metrics import instruments as hvd_metrics
        profile_on = _profile.armed
        if profile_on and t_api is None:
            t_api = time.perf_counter()
        if _chaos.armed:
            _chaos.fire("collective.dispatch")
        if prog is None:
            # Slow-path registration call: staged buffers are fresh
            # _prepare outputs, safe to donate under the opt-in.
            prog = self._program_for(staged)
        metrics_on = hvd_metrics.enabled()
        flight_on = _flight.armed
        if flight_on:
            # Plan fast path stays plan-cheap: every flight field (label,
            # byte count, signature) is a plan constant resolved once.
            fl_seq = _flight.record_dispatch(self.op_label, self.ps_label,
                                             self.nbytes, self.sig, name)
            t0f = time.perf_counter()
        tl = basics.timeline()
        if tl is None and not metrics_on:
            # Observability (timeline/metrics) off: no span/annotation
            # bookkeeping — the compiled call, error translation, and the
            # always-armed flight record above.
            if profile_on:
                t0p = time.perf_counter()
            try:
                outs = prog(*staged)
            except (ValueError, RuntimeError) as e:
                _translate_dispatch_error(name or self.default_name,
                                          self.op_label, e)
            if flight_on:
                _flight.record_complete(self.op_label, self.ps_label,
                                        fl_seq, time.perf_counter() - t0f)
            outs = self._localize(outs)
            if profile_on:
                # collective = program + localize (the multi-process
                # peer-wait); host_dispatch = everything before the call.
                _profile.record_dispatch(
                    self.op_label, time.perf_counter() - t0p,
                    t0p - t_api, self.nbytes)
            return outs
        # Inline _timeline_op with the plan's precomputed labels/byte
        # count (no contextmanager frame, no per-call nbytes walk; the
        # XPlane TraceAnnotation rides only with an active timeline).
        if metrics_on:
            hvd_metrics.record_collective(self.op_label, self.nbytes,
                                          self.ps_label)
            hvd_metrics.record_wire("eager", self.wire_label,
                                    self.wire_nbytes,
                                    tiers=self.wire_tiers,
                                    sched=self.wire_sched)
            t0 = time.perf_counter()
        if profile_on:
            t0p = time.perf_counter()
        try:
            if tl is not None:
                with _op_span(tl, self.op_kind, name or self.default_name):
                    outs = prog(*staged)
            else:
                outs = prog(*staged)
            if metrics_on:
                hvd_metrics.record_collective_latency(
                    self.op_label, time.perf_counter() - t0)
            if flight_on:
                _flight.record_complete(self.op_label, self.ps_label,
                                        fl_seq, time.perf_counter() - t0f)
        except (ValueError, RuntimeError) as e:
            _translate_dispatch_error(name or self.default_name,
                                      self.op_label, e)
        outs = self._localize(outs)
        if profile_on:
            _profile.record_dispatch(
                self.op_label, time.perf_counter() - t0p,
                t0p - t_api, self.nbytes)
        return outs

    def _localize(self, outs):
        """Per-process local rows of each output (multi-process), with the
        shard order resolved once per plan instead of sorted per call."""
        if not self.multi:
            return list(outs)
        order = self._localize_order
        res = []
        for o in outs:
            shards = o.addressable_shards
            if order is None:
                order = tuple(int(i) for i in np.argsort(
                    [s.index[0].start or 0 for s in shards]))
                self._localize_order = order
            if len(order) == 1:
                res.append(np.asarray(shards[0].data))
            else:
                res.append(np.concatenate(
                    [np.asarray(shards[i].data) for i in order], axis=0))
        return res


def _quantized_wire_tiers(flat_len, n, members):
    """Per-tier split of the flat block-scaled exchange — first leg
    AllToAll (foreign-destination fraction), second leg AllGather (ring
    slice-boundary fraction) — mirroring the static cost model's per-leg
    classification byte-for-byte. None on single-slice layouts (the
    default record_wire split books everything to ici there anyway)."""
    st = basics._state
    world = st.topology.size if st is not None else n
    slices, slice_size = _live_slices(world)
    if slices <= 1:
        return None
    leg = _wire.exchange_leg_bytes(flat_len, n)
    t1 = _wire.split_tiers(leg, _wire.a2a_dcn_fraction(members, slice_size))
    t2 = _wire.split_tiers(leg, _wire.ring_dcn_fraction(members,
                                                        slice_size))
    return {"ici": t1["ici"] + t2["ici"], "dcn": t1["dcn"] + t2["dcn"]}


class _WireDispatchPlan(_DispatchPlan):
    """Dispatch plan for eager allreduces riding the quantized wire tier
    (ops/wire.py). Beyond the base plan it owns the bucket's error-feedback
    residual — fetched from the wire store before the call, stored after —
    and records the exchange's exact on-wire byte estimate (split per
    link tier when a slice hierarchy exists). Keyed (like every plan) on
    the wire dtype, so a per-process-set wire flip routes the next call
    through a fresh plan with a fresh residual."""

    __slots__ = ("wire_name", "ef", "ef_key", "flat_len", "wire_records",
                 "res_len")

    def __init__(self, program, mesh, ps, staged, wire_name, ef, ef_key):
        super().__init__("allreduce", "ALLREDUCE", program, mesh, ps,
                         staged, "grouped_allreduce")
        self.wire_name = wire_name
        self.ef = ef
        self.ef_key = ef_key
        self.flat_len = sum(int(np.prod(s[1:])) for s in self.global_shapes)
        self.res_len = self.flat_len
        n = self.global_shapes[0][0] if self.global_shapes else 1
        # Plan-constant wire accounting: (path, dtype, bytes, compressed,
        # tiers) per record — built once by the subclass hook (the
        # hierarchical plan books one record per decomposed leg).
        self._init_wire_records(n, staged)

    def _init_wire_records(self, n, staged):
        self.wire_label = self.wire_name
        self.wire_nbytes = _wire.exchange_wire_bytes(self.flat_len, n)
        members = self.ps.rank_list() if self.ps.ranks is not None \
            else list(range(n))
        self.wire_records = [
            ("eager", self.wire_name, self.wire_nbytes, True,
             _quantized_wire_tiers(self.flat_len, n, members))]

    def _zero_residual(self):
        return _wire.zero_residual(self.mesh, self.sharding,
                                   self.global_shapes[0][0], self.res_len)

    def dispatch(self, staged, name=None, prog=None, t_api=None):
        # Instrumentation inlined like the base fast path (no
        # contextmanager frame, plan-constant labels/bytes): the wire
        # tier's HOST cost over the fp32 plan is just the residual store
        # round-trip — guarded at 2x by test_perf_guards.
        from horovod_tpu.metrics import instruments as hvd_metrics
        profile_on = _profile.armed
        if profile_on and t_api is None:
            t_api = time.perf_counter()
        if _chaos.armed:
            _chaos.fire("collective.dispatch")
        args = list(staged)
        ef = self.ef
        if ef:
            res = _wire.ef_get(self.ef_key)
            if res is None:
                res = self._zero_residual()
            args.append(res)
        metrics_on = hvd_metrics.enabled()
        flight_on = _flight.armed
        if flight_on:
            fl_seq = _flight.record_dispatch(self.op_label, self.ps_label,
                                             self.nbytes, self.sig, name)
            t0f = time.perf_counter()
        if metrics_on:
            hvd_metrics.record_collective(self.op_label, self.nbytes,
                                          self.ps_label)
            for path, dtype, nbytes, compressed, tiers in self.wire_records:
                hvd_metrics.record_wire(path, dtype, nbytes, compressed,
                                        tiers=tiers)
            t0 = time.perf_counter()
        if profile_on:
            t0p = time.perf_counter()
        tl = basics.timeline()
        try:
            if tl is not None:
                with _op_span(tl, self.op_kind, name or self.default_name):
                    outs = self.program(*args)
            else:
                outs = self.program(*args)
            if ef:
                # The residual stays a DEVICE-RESIDENT global array
                # between steps (never localized): it feeds straight
                # back into the next key-matched dispatch.
                _wire.ef_put(self.ef_key, outs[-1])
                outs = outs[:-1]
            if metrics_on:
                hvd_metrics.record_collective_latency(
                    self.op_label, time.perf_counter() - t0)
            if flight_on:
                _flight.record_complete(self.op_label, self.ps_label,
                                        fl_seq, time.perf_counter() - t0f)
        except (ValueError, RuntimeError) as e:
            # Never resume error feedback over a failed exchange: the
            # residual's pairing with the result stream is broken (and
            # after an elastic recovery it would be a dead-backend array).
            if ef:
                _wire.ef_pop(self.ef_key)
            _translate_dispatch_error(name or self.default_name,
                                      self.op_label, e)
        except Exception:
            if ef:
                _wire.ef_pop(self.ef_key)
            raise
        outs = self._localize(list(outs))
        if profile_on:
            _profile.record_dispatch(
                self.op_label, time.perf_counter() - t0p,
                t0p - t_api, self.nbytes)
        return outs


class _HierDispatchPlan(_WireDispatchPlan):
    """Dispatch plan for eager allreduces riding the HIERARCHICAL dispatch
    tier: local RS (exact, ICI) -> cross-slice allreduce on the per-tier
    wire (DCN) -> local AG, compiled over the (slice x chips-per-slice)
    mesh. Byte accounting books each decomposed leg to its own link tier
    (wire.hierarchical_wire_bytes — the same integers the static model's
    hierarchical what-if predicts); the error-feedback residual covers
    the CROSS leg's shard only. Keyed on the slice layout and cross wire,
    so an autotuner strategy flip (or an elastic resize through
    clear_program_caches) routes through a fresh plan."""

    __slots__ = ("cross_label", "num_slices")

    @staticmethod
    def _spec_for(mesh):
        from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
        return P((CROSS_AXIS, LOCAL_AXIS))

    def __init__(self, program, hier_mesh, ps, staged, hier, ef_key):
        # Slots the _init_wire_records hook needs; assigned before the
        # base __init__ that invokes it.
        self.cross_label = hier["cross"]
        self.num_slices = hier["slices"]
        super().__init__(program, hier_mesh, ps, staged,
                         hier["cross"], hier["ef"], ef_key)

    def _init_wire_records(self, n, staged):
        payload_dtype = str(staged[0].dtype) if staged else "float32"
        width = np.dtype(staged[0].dtype).itemsize if staged else 4
        h = _wire.hierarchical_wire_bytes(
            self.flat_len, n, self.num_slices, width,
            cross_wire=self.cross_label or "")
        self.res_len = h["shard_elems"]
        self.wire_label = self.cross_label or payload_dtype
        self.wire_nbytes = h["ici"] + h["dcn"]
        self.wire_records = [
            ("eager", payload_dtype, h["ici"], False, {"ici": h["ici"]}),
            ("eager", self.cross_label or payload_dtype, h["dcn"],
             self.cross_label is not None, {"dcn": h["dcn"]})]


class _HierAlltoallPlan(_WireDispatchPlan):
    """Dispatch plan for eager equal-splits alltoalls riding the
    HIERARCHICAL dispatch tier: slice-local a2a (ICI) -> cross-slice a2a
    on the per-tier wire (DCN), compiled over the (slice x
    chips-per-slice) mesh. Byte accounting books the local leg all-ICI
    and splits the cross leg by its own ``(S-1)/S`` foreign-slice
    fraction (``wire.hierarchical_a2a_bytes`` — the same integers the
    static model's hierarchical a2a what-if predicts, keeping
    ``cross_check_bytes`` at delta 0). NO error feedback: an alltoall
    moves data without reducing, so there is no accumulated sum for a
    residual to correct — each element pays one bounded round-off on the
    quantized cross leg. Keyed on the slice layout and cross wire, so a
    strategy flip (or an elastic resize through clear_program_caches)
    routes through a fresh plan."""

    __slots__ = ("cross_label", "num_slices")

    @staticmethod
    def _spec_for(mesh):
        from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
        return P((CROSS_AXIS, LOCAL_AXIS))

    def __init__(self, program, hier_mesh, ps, staged, hier):
        # Slots the _init_wire_records hook needs; assigned before the
        # base init that precedes it. _WireDispatchPlan.__init__ is
        # bypassed on purpose: its wire/ef plumbing is allreduce-shaped
        # (residual store, exchange_wire_bytes); only its multi-record
        # dispatch() is shared.
        self.cross_label = hier["cross"]
        self.num_slices = hier["slices"]
        _DispatchPlan.__init__(self, "alltoall", "ALLTOALL", program,
                               hier_mesh, ps, staged, "alltoall")
        self.wire_name = hier["cross"]
        self.ef = False
        self.ef_key = None
        self.flat_len = sum(int(np.prod(s[1:])) for s in self.global_shapes)
        self.res_len = 0
        n = self.global_shapes[0][0] if self.global_shapes else 1
        self._init_wire_records(n, staged)

    def _init_wire_records(self, n, staged):
        payload_dtype = str(staged[0].dtype) if staged else "float32"
        width = np.dtype(staged[0].dtype).itemsize if staged else 4
        h = _wire.hierarchical_a2a_bytes(
            self.flat_len, n, self.num_slices, width,
            cross_wire=self.cross_label or "")
        self.cross_label = h["cross_label"]
        self.wire_label = self.cross_label or payload_dtype
        self.wire_nbytes = h["local"] + h["cross"]
        self.wire_sched = "a2a"
        self.wire_records = [
            ("eager", payload_dtype, h["local"], False,
             {"ici": h["local"]}),
            ("eager", self.cross_label or payload_dtype, h["cross"],
             self.cross_label is not None, dict(h["cross_tiers"]))]


@functools.lru_cache(maxsize=4096)
def _hier_verdict(strategy, cross, op, sig, n, slices, ef_cfg):
    """Memoized tail of the hierarchical-dispatch verdict: everything
    derivable from the resolved policy values and the call signature
    (the per-dispatch cost of the armed tier must stay plan-key cheap —
    guarded at 2x the flat plan by test_perf_guards)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        return None
    dtypes = {dt for _, dt in sig}
    if len(dtypes) != 1 or not all(
            jnp.issubdtype(dt, jnp.floating) for dt in dtypes):
        return None
    total = sum(int(np.prod(shape[1:])) if len(shape) >= 1 else 0
                for shape, _ in sig)
    width = np.dtype(next(iter(dtypes))).itemsize
    h = _wire.hierarchical_wire_bytes(total, n, slices, width,
                                      cross_wire=cross)
    label = h["cross_label"]
    return {"strategy": strategy, "cross": label, "slices": slices,
            "ef": bool(ef_cfg) and label is not None}


def _eager_hier_for(ps, op, sig):
    """Hierarchical-dispatch verdict for one eager allreduce: a dict
    (strategy facts the program/plan need) or None for the flat path.

    Eligibility — shared, deliberately, with the static cost model's
    mirror (analysis/cost.py): the per-set strategy registry (autotuner /
    hvd.set_dispatch_strategy) else the HOROVOD_HIERARCHICAL_DISPATCH
    default; global process set only (slice membership of a sub-set is
    undefined); float Sum/Average groups of ONE dtype (the decomposition
    concatenates); and a live slice hierarchy (HOROVOD_MESH_SLICES /
    multi-slice topology) — a 1-slice layout would pay two extra ICI legs
    for no DCN saving (hvdlint HVP113)."""
    st = basics._state
    if st is None or sig is None:
        return None
    cfg = st.config
    hier_cfg = getattr(cfg, "hierarchical_dispatch", False)
    if not hier_cfg and not _wire._strategy_registry:
        return None          # hot-path fast exit: tier disarmed everywhere
    default = "hier_qcross" if hier_cfg else ""
    strategy = _wire.dispatch_strategy_for(_ps_label(ps), default)
    if strategy not in ("hier", "hier_qcross"):
        return None
    if ps.ranks is not None:
        return None
    n = ps.size()
    slices, _ = _live_slices(n)
    if slices <= 1:
        return None
    cross = ""
    if strategy == "hier_qcross":
        cross = _wire.cross_wire_for(_ps_label(ps), cfg)
    return _hier_verdict(strategy, cross, ReduceOp(op), sig, n, slices,
                         bool(cfg.wire_error_feedback))


@functools.lru_cache(maxsize=4096)
def _a2a_hier_verdict(strategy, cross, sig, n, slices):
    """Memoized tail of the hierarchical-ALLTOALL verdict (the a2a twin
    of :func:`_hier_verdict`): single-tensor equal-splits calls whose
    per-rank dim divides the world. The cross wire label survives only
    for float payloads the shared eligibility predicate accepts — below
    one BLOCK per destination slice the exchange padding would inflate
    the wire and the cross leg stays exact."""
    if len(sig) != 1:
        return None
    (shape, dtype), = sig
    if len(shape) < 2 or shape[1] % n != 0:
        return None
    per = int(np.prod(shape[1:]))
    label = _wire.quantized_label(cross) if cross else None
    if label is not None and not (
            jnp.issubdtype(np.dtype(dtype), jnp.floating)
            and _wire.quantized_eligible(per, slices, True, True)):
        label = None
    return {"strategy": strategy, "cross": label, "slices": slices}


def _eager_a2a_hier_for(ps, sig):
    """Hierarchical-dispatch verdict for one eager equal-splits alltoall:
    a dict (strategy facts the program/plan need) or None for the flat
    path — the a2a twin of :func:`_eager_hier_for`, sharing its
    eligibility philosophy (global process set only, live slice
    hierarchy, hvdlint HVP113 on 1-slice layouts) but keyed on the a2a
    strategy registry / ``HOROVOD_HIERARCHICAL_ALLTOALL`` default, with
    the expert cross wire resolved through
    :func:`horovod_tpu.ops.wire.alltoall_cross_wire_for` — NEVER the
    allreduce wire knobs: alltoall payloads are activations and quantize
    only by explicit choice (docs/performance.md)."""
    st = basics._state
    if st is None or sig is None:
        return None
    cfg = st.config
    hier_cfg = getattr(cfg, "hierarchical_alltoall", False)
    if not hier_cfg and not _wire._a2a_strategy_registry:
        return None          # hot-path fast exit: tier disarmed everywhere
    default = "hier_qcross" if hier_cfg else ""
    strategy = _wire.alltoall_strategy_for(_ps_label(ps), default)
    if strategy not in ("hier", "hier_qcross"):
        return None
    if ps.ranks is not None:
        return None
    n = ps.size()
    slices, _ = _live_slices(n)
    if slices <= 1:
        return None
    cross = ""
    if strategy == "hier_qcross":
        cross = _wire.alltoall_cross_wire_for(_ps_label(ps), cfg)
    return _a2a_hier_verdict(strategy, cross, sig, n, slices)


def _eager_wire_for(ps, op, sig, wire_req):
    """Effective QUANTIZED wire dtype for one eager allreduce — ``(label,
    error_feedback)`` with label None for the exact full-precision path.
    The decision honors the one-shot compressor request, then the
    per-process-set registry (autotuner / hvd.set_wire_dtype), then the
    config knob; it quantizes only float Sum/Average groups big enough
    that the exchange's n×BLOCK padding doesn't inflate the wire (below
    one block per destination rank the exact psum moves fewer bytes)."""
    st = basics._state
    if st is None or sig is None:
        return None, False
    cfg = st.config
    req = wire_req or _wire.wire_dtype_for(_ps_label(ps), cfg.wire_dtype)
    label = _wire.quantized_label(req)
    if label is None:
        return None, False
    # REAL floats only — _is_float admits complex (correct for Average
    # validation), but the block quantizer's abs/round math silently
    # drops the imaginary part; complex payloads keep the exact wire,
    # matching the static cost model's float-only gate.
    all_float = all(jnp.issubdtype(dt, jnp.floating) for _, dt in sig)
    total = sum(int(np.prod(shape[1:])) if len(shape) >= 1 else 0
                for shape, _ in sig)
    if not _wire.quantized_eligible(
            total, ps.size(), all_float,
            ReduceOp(op) in (ReduceOp.SUM, ReduceOp.AVERAGE)):
        return None, False
    return label, bool(cfg.wire_error_feedback)


# ----------------------------------------------------------------------------
# Public eager API
# ----------------------------------------------------------------------------

def allreduce(tensor, op=Average, prescale_factor=1.0, postscale_factor=1.0,
              process_set=None, name=None):
    """Allreduce a rank-major stacked tensor; returns the stacked per-rank
    results (every slice equals the reduction).

    reference: hvd.allreduce (torch/mpi_ops.py:294-360; op semantics
    message.h:43-50, pre/postscale operations.cc:1480).
    """
    return grouped_allreduce([tensor], op=op, prescale_factor=prescale_factor,
                             postscale_factor=postscale_factor,
                             process_set=process_set, name=name)[0]


@_interceptable("allreduce")
def grouped_allreduce(tensors, op=Average, prescale_factor=1.0,
                      postscale_factor=1.0, process_set=None, name=None):
    """One fused dispatch for a group of tensors — completes atomically like
    the reference's grouped ops (reference: EnqueueTensorAllreduces
    operations.cc:1480, group_table.h:39). When the effective wire dtype
    for this process set is quantized (int8/fp8 — config knob, per-set
    registry, or a one-shot Compression.int8 request), eligible float
    Sum/Average groups ride the block-scaled exchange with error feedback
    instead of the exact psum (ops/wire.py). When the hierarchical
    dispatch tier is armed over a live slice hierarchy
    (HOROVOD_HIERARCHICAL_DISPATCH / hvd.set_dispatch_strategy), eligible
    groups instead decompose into local RS (ICI) -> cross-slice allreduce
    on the per-tier wire (DCN) -> local AG."""
    mesh, ps = _mesh_for(process_set)
    sig = _plan_sig(tensors)
    wire_req = _wire.consume_wire_request()
    # A one-shot Compression.int8 request is an explicit per-dispatch
    # opt-in to the FLAT quantized exchange — it must never be silently
    # dropped by the hierarchical verdict (exact-cross hier would move
    # full precision on every leg while the caller believes otherwise).
    hier = None if _wire.quantized_label(wire_req) is not None \
        else _eager_hier_for(ps, op, sig)
    if hier is not None:
        wire_name, wire_ef = None, False
    else:
        wire_name, wire_ef = _eager_wire_for(ps, op, sig, wire_req)
    if sig is not None:
        key = ("allreduce", mesh, ps, int(op), float(prescale_factor),
               float(postscale_factor), sig, wire_name, wire_ef,
               None if hier is None
               else (hier["slices"], hier["cross"], hier["ef"]))
        plan = _plan_lookup(key, ps)
        if plan is not None:
            return plan.run(tensors, name)
    n = ps.size()
    if op == Average and any(
            not _is_float(_dtype_of(t)) for t in tensors):
        raise ValueError("Average is not supported for integer tensors; use "
                         "hvd.Sum (matches reference torch/mpi_ops.py checks).")
    active_mask = _join_sync(ps, mesh, {
        "kind": "allreduce", "op": int(ReduceOp(op)),
        "pre": float(prescale_factor), "post": float(postscale_factor),
        "slices": _slice_desc(tensors, mesh, n, "allreduce")})
    tensors = _prepare(tensors, mesh, n, "allreduce")
    shapes, dtypes = _signature(tensors)
    st = basics._get_state()
    if hier is not None and active_mask is None \
            and _plan_eligible(st, active_mask):
        hmesh = _hier_mesh(mesh, hier["slices"])
        prog = _hier_allreduce_program(
            hmesh, n, ReduceOp(op), float(prescale_factor),
            float(postscale_factor), shapes, dtypes, hier["cross"] or "",
            hier["ef"])
        plan = _register_plan(key, _HierDispatchPlan(
            prog, hmesh, ps, tensors, hier, key))
        return plan.dispatch(tensors, name)
    # A hierarchical verdict on a non-plannable control path (join mask,
    # armed join mode, debug order check) falls back to the exact flat
    # program: the 2-level decomposition composes with neither the
    # active-mask math nor a stable residual identity.
    if wire_name is not None and active_mask is None:
        if _plan_eligible(st, active_mask):
            prog = _quantized_allreduce_program(
                mesh, n, ReduceOp(op), float(prescale_factor),
                float(postscale_factor), shapes, dtypes, wire_name, wire_ef)
            plan = _register_plan(key, _WireDispatchPlan(
                prog, mesh, ps, tensors, wire_name, wire_ef, key))
            return plan.dispatch(tensors, name)
        # Non-plannable control path (debug order check, armed join mode):
        # quantize without error feedback — there is no stable per-bucket
        # residual identity to key the store on.
        prog = _quantized_allreduce_program(
            mesh, n, ReduceOp(op), float(prescale_factor),
            float(postscale_factor), shapes, dtypes, wire_name, False)
        flat_len = sum(int(np.prod(s[1:])) for s in shapes)
        with _timeline_op(name or "grouped_allreduce", "ALLREDUCE", tensors,
                          process_set=ps,
                          wire=("eager", wire_name,
                                _wire.exchange_wire_bytes(flat_len, n),
                                True)):
            return _localize(list(prog(*tensors)), mesh)
    prog = _allreduce_program(mesh, n, ReduceOp(op), float(prescale_factor),
                              float(postscale_factor), shapes, dtypes,
                              active_mask)
    if sig is not None and _plan_eligible(st, active_mask):
        donate_prog = _allreduce_program(
            mesh, n, ReduceOp(op), float(prescale_factor),
            float(postscale_factor), shapes, dtypes, active_mask,
            donate=True) if st.config.donate_eager else None
        plan = _register_plan(key, _DispatchPlan(
            "allreduce", "ALLREDUCE", prog, mesh, ps, tensors,
            "grouped_allreduce", donate_program=donate_prog))
        return plan.dispatch(tensors, name)
    with _timeline_op(name or "grouped_allreduce", "ALLREDUCE", tensors,
                      process_set=ps):
        return _localize(list(prog(*tensors)), mesh)


def allgather(tensor, process_set=None, name=None):
    """Gather rank slices; output slice ``[r]`` is the concatenation of every
    rank's data (identical across ranks), shape ``(n, n*m, ...)``.

    reference: hvd.allgather (torch/mpi_ops.py:655-712). Ragged first dims are
    supported via :func:`allgather_ragged`.
    """
    return grouped_allgather([tensor], process_set=process_set, name=name)[0]


@_interceptable("allgather")
def grouped_allgather(tensors, process_set=None, name=None):
    mesh, ps = _mesh_for(process_set)
    sig = _plan_sig(tensors)
    if sig is not None:
        key = ("allgather", mesh, ps, sig)
        plan = _plan_lookup(key, ps)
        if plan is not None:
            return plan.run(tensors, name)
    n = ps.size()
    slices = _slice_desc(tensors, mesh, n, "allgather")
    # Validate BEFORE the join round: an active raising after publishing
    # its descriptor would leave the joined processes' mirrors launching a
    # collective nobody else joins (a hang, not an error).
    for s, _ in slices:
        if len(s) < 1:
            raise TensorShapeMismatchError(
                "allgather requires per-rank tensors of rank>=1 "
                "(stacked input rank>=2)")
    active_mask = _join_sync(ps, mesh, {"kind": "allgather",
                                        "slices": slices})
    tensors = _prepare(tensors, mesh, n, "allgather")
    shapes, dtypes = _signature(tensors)
    # HOROVOD_HIERARCHICAL_ALLGATHER: 2-level gather over the (cross,
    # local) mesh — global set only, and the masked (join) variant stays
    # flat (the static row-drop composes with the 1-D gather).
    topo = basics.topology()
    hier = (basics.config().hierarchical_allgather
            and ps.ranks is None and active_mask is None
            and getattr(topo, "mesh2d", None) is not None)
    prog = _allgather_program(topo.mesh2d if hier else mesh, n, shapes,
                              dtypes, active_mask, hier)
    st = basics._get_state()
    if sig is not None and _plan_eligible(st, active_mask):
        plan = _register_plan(key, _DispatchPlan(
            "allgather", "ALLGATHER", prog, mesh, ps, tensors,
            "grouped_allgather"))
        return plan.dispatch(tensors, name)
    with _timeline_op(name or "grouped_allgather", "ALLGATHER", tensors,
                      process_set=ps):
        return _localize(list(prog(*tensors)), mesh)


@_interceptable("allgather_ragged")
def allgather_ragged(tensors, process_set=None, name=None,
                     return_sizes=False, _mirror=False):
    """Allgather of per-rank tensors with differing first dims.

    ``tensors`` is a list of arrays whose shapes agree on all but the first
    axis — one per rank (single process) or one per **local** rank
    (multi-process). Returns the concatenated array (same value for every
    rank); with ``return_sizes=True`` also the per-block first-dim sizes (in
    active-rank order), so callers can split the concatenation without
    re-negotiating. This is the dynamic-shape path that needs host-side size
    negotiation in the reference (reference: controller.cc:74 allgather
    first-dim exchange, collective_operations.h:137-174): multi-process
    launches exchange the per-rank first dims through the jax.distributed
    control plane (:mod:`horovod_tpu.common.negotiation`) before building the
    padded program, so each distinct size vector compiles once everywhere.
    """
    mesh, ps = _mesh_for(process_set)
    n = ps.size()
    multi, local_pos = _local_mesh_info(mesh)
    n_rows = len(local_pos) if multi else n
    if len(tensors) != n_rows:
        raise TensorShapeMismatchError(
            f"allgather_ragged needs one tensor per "
            f"{'local ' if multi else ''}rank ({n_rows}), got {len(tensors)}")
    tensors = [jnp.asarray(t) for t in tensors]
    # Armed-mode round BEFORE the size negotiation so active and joined
    # processes interleave the control plane identically. A joined
    # process's mirror re-enters this function with zero-row tensors
    # AFTER its loop already consumed the round (_mirror=True): it starts
    # at the size exchange, in lockstep with the actives.
    if not _mirror:
        _join_sync(ps, mesh, {
            "kind": "allgather_ragged",
            "tail": [int(s) for s in tensors[0].shape[1:]],
            "dtype": str(tensors[0].dtype)})
    local_sizes = [int(t.shape[0]) for t in tensors]
    if multi:
        from horovod_tpu.common import negotiation
        sizes = negotiation.exchange_sizes("allgather_ragged", local_sizes,
                                           procs=_mesh_processes(mesh))
    else:
        sizes = local_sizes
    max_size = max(sizes)
    padded = jnp.stack([
        jnp.pad(t, [(0, max_size - s)] + [(0, 0)] * (t.ndim - 1))
        for t, s in zip(tensors, local_sizes)])
    gathered = allgather(padded, process_set=process_set, name=name)
    # Joined ranks' slices were dropped by the masked allgather, so the
    # output rows hold n_active blocks, in active-rank order.
    mask = _active_mask(ps)
    active = range(n) if mask is None else np.nonzero(np.array(mask))[0]
    row0 = np.asarray(gathered[0]).reshape(
        (len(list(active)), max_size) + tuple(tensors[0].shape[1:]))
    out = jnp.concatenate(
        [row0[i, :sizes[r]] for i, r in enumerate(active)], axis=0)
    if return_sizes:
        return out, [sizes[r] for r in active]
    return out


def broadcast(tensor, root_rank, process_set=None, name=None):
    """Broadcast the root rank's slice to all ranks
    (reference: hvd.broadcast torch/mpi_ops.py:843-900)."""
    return grouped_broadcast([tensor], root_rank, process_set=process_set,
                             name=name)[0]


@_interceptable("broadcast")
def grouped_broadcast(tensors, root_rank, process_set=None, name=None):
    mesh, ps = _mesh_for(process_set)
    sig = _plan_sig(tensors)
    if sig is not None:
        key = ("broadcast", mesh, ps, int(root_rank), sig)
        plan = _plan_lookup(key, ps)
        if plan is not None:
            return plan.run(tensors, name)
    n = ps.size()
    if ps.ranks is not None:
        try:
            root = ps.rank_list().index(root_rank)
        except ValueError:
            raise ValueError(
                f"broadcast root_rank {root_rank} is not a member of "
                f"{ps} (ranks {ps.rank_list()})") from None
    else:
        root = root_rank
    if not (0 <= root < n):
        raise ValueError(f"root_rank {root_rank} out of range [0,{n})")
    mask = _join_sync(ps, mesh, {"kind": "broadcast", "root": int(root),
                                 "slices": _slice_desc(tensors, mesh, n,
                                                       "broadcast")})
    if mask is not None and not mask[root]:
        # Reference errors when the broadcast root has already joined
        # (controller.cc join/root checks) — there is no data to send.
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(
            f"broadcast root_rank {root_rank} has joined")
    tensors = _prepare(tensors, mesh, n, "broadcast")
    shapes, dtypes = _signature(tensors)
    prog = _broadcast_program(mesh, n, int(root), shapes, dtypes)
    st = basics._get_state()
    if sig is not None and _plan_eligible(st, mask):
        plan = _register_plan(key, _DispatchPlan(
            "broadcast", "BROADCAST", prog, mesh, ps, tensors,
            "grouped_broadcast"))
        return plan.dispatch(tensors, name)
    with _timeline_op(name or "grouped_broadcast", "BROADCAST", tensors,
                      process_set=ps):
        return _localize(list(prog(*tensors)), mesh)


def reducescatter(tensor, op=Sum, prescale_factor=1.0, postscale_factor=1.0,
                  process_set=None, name=None):
    """Reduce across ranks and scatter the result: input slices ``(m, ...)``
    (m divisible by n), output slices ``(m/n, ...)``.

    reference: hvd.reducescatter (torch/mpi_ops.py:1066-1123,
    EnqueueTensorReducescatters operations.cc:1797).
    """
    return grouped_reducescatter([tensor], op=op, prescale_factor=prescale_factor,
                                 postscale_factor=postscale_factor,
                                 process_set=process_set, name=name)[0]


@_interceptable("reducescatter")
def grouped_reducescatter(tensors, op=Sum, prescale_factor=1.0,
                          postscale_factor=1.0, process_set=None, name=None):
    mesh, ps = _mesh_for(process_set)
    sig = _plan_sig(tensors)
    if sig is not None:
        key = ("reducescatter", mesh, ps, int(op), float(prescale_factor),
               float(postscale_factor), sig)
        plan = _plan_lookup(key, ps)
        if plan is not None:
            return plan.run(tensors, name)
    n = ps.size()
    slices = _slice_desc(tensors, mesh, n, "reducescatter")
    # Validate BEFORE the join round (see grouped_allgather).
    for s, _ in slices:
        if len(s) < 1 or s[0] % n != 0:
            raise TensorShapeMismatchError(
                f"reducescatter: per-rank first dim must be divisible by "
                f"{n}, got {tuple(s)}")
    active_mask = _join_sync(ps, mesh, {
        "kind": "reducescatter", "op": int(ReduceOp(op)),
        "pre": float(prescale_factor), "post": float(postscale_factor),
        "slices": slices})
    tensors = _prepare(tensors, mesh, n, "reducescatter")
    shapes, dtypes = _signature(tensors)
    prog = _reducescatter_program(mesh, n, ReduceOp(op), float(prescale_factor),
                                  float(postscale_factor), shapes, dtypes,
                                  active_mask)
    st = basics._get_state()
    if sig is not None and _plan_eligible(st, active_mask):
        plan = _register_plan(key, _DispatchPlan(
            "reducescatter", "REDUCESCATTER", prog, mesh, ps, tensors,
            "grouped_reducescatter"))
        return plan.dispatch(tensors, name)
    with _timeline_op(name or "grouped_reducescatter", "REDUCESCATTER",
                      tensors, process_set=ps):
        return _localize(list(prog(*tensors)), mesh)


@_interceptable("alltoall")
def alltoall(tensor, splits=None, process_set=None, name=None):
    """All-to-all exchange. Equal splits ride a single XLA AllToAll; uneven
    ``splits`` (per-rank row counts to send to each peer) use the padded path.

    Returns ``(output, received_splits)`` when ``splits`` is given, else output
    — matching the reference (reference: hvd.alltoall torch/mpi_ops.py:928-1014,
    splits negotiation collective_operations.h:199-268).

    Multi-process: ``tensor`` is the local rank-major stack and ``splits`` has
    one row per **local** rank; the full splits matrix is negotiated through
    the jax.distributed control plane, playing the role of the reference's
    cross-rank splits exchange.
    """
    mesh, ps = _mesh_for(process_set)
    n = ps.size()
    sig = _plan_sig((tensor,)) if splits is None else None
    hier = _eager_a2a_hier_for(ps, sig) if sig is not None else None
    if sig is not None:
        # The hierarchy facts join the key: a strategy/cross-wire flip (or
        # a slice-layout change through clear_program_caches) routes the
        # next call through a differently-keyed plan — no desync window.
        key = ("alltoall", mesh, ps, sig,
               None if hier is None else (hier["slices"], hier["cross"]))
        plan = _plan_lookup(key, ps)
        if plan is not None:
            return plan.run([tensor], name)[0]
    if _join_sync(ps, mesh, {"kind": "alltoall"}) is not None:
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(
            "alltoall is not supported while ranks have joined (matches the "
            "reference: JOIN covers allreduce/allgather/broadcast only)")
    t = jnp.asarray(tensor)
    multi, local_pos = _local_mesh_info(mesh)
    n_rows = len(local_pos) if multi else n
    _check_stacked(t, n_rows, "alltoall")
    if splits is None:
        if t.ndim < 2 or t.shape[1] % n != 0:
            raise TensorShapeMismatchError(
                f"alltoall without splits: per-rank first dim must be "
                f"divisible by {n}")
        (tt,) = _prepare([t], mesh, n, "alltoall")
        shapes, dtypes = _signature([tt])
        st = basics._get_state()
        if hier is not None and _plan_eligible(st, None):
            hmesh = _hier_mesh(mesh, hier["slices"])
            prog = _hier_alltoall_program(hmesh, n, shapes, dtypes,
                                          hier["cross"] or "")
            plan = _register_plan(key, _HierAlltoallPlan(
                prog, hmesh, ps, (tt,), hier))
            return plan.dispatch([tt], name)[0]
        # Non-plannable control paths (debug order check, join armed)
        # fall back to the exact flat program, like the allreduce tier.
        prog = _alltoall_program(mesh, n, shapes, dtypes)
        if sig is not None and _plan_eligible(st, None):
            plan = _register_plan(key, _DispatchPlan(
                "alltoall", "ALLTOALL", prog, mesh, ps, (tt,),
                "alltoall"))
            return plan.dispatch([tt], name)[0]
        with _timeline_op(name or "alltoall", "ALLTOALL", (tt,),
                          process_set=ps):
            return _localize([prog(tt)[0]], mesh)[0]

    splits = np.asarray(splits)
    if splits.shape != (n_rows, n):
        raise TensorShapeMismatchError(
            f"splits must be ({n_rows},{n}) [{'local ' if multi else ''}rank,"
            f" peer] row counts, got {splits.shape}")
    if (splits < 0).any():
        raise TensorShapeMismatchError("splits must be non-negative")
    row_sums = splits.sum(axis=1)
    if (row_sums > t.shape[1]).any():
        # The reference rejects splits that don't match the tensor size
        # (collective_operations.h:199-268 splits validation). In the stacked
        # layout rows beyond splits[r].sum() are permitted as padding, but a
        # sum *exceeding* the available rows is always an error.
        bad = int(np.argmax(row_sums > t.shape[1]))
        raise TensorShapeMismatchError(
            f"alltoall splits for rank {bad} sum to {int(row_sums[bad])} "
            f"but each rank only has {t.shape[1]} rows")
    if multi:
        # Host-side splits negotiation (reference:
        # collective_operations.h:199-268): every process learns the full
        # [rank, peer] matrix so it can size and slice its receive side.
        from horovod_tpu.common import negotiation
        per_proc = negotiation.exchange("alltoall_splits", splits.tolist(),
                                        procs=_mesh_processes(mesh))
        full = np.concatenate([np.asarray(s, np.int64) for s in per_proc])
        if full.shape != (n, n):
            raise TensorShapeMismatchError(
                f"negotiated alltoall splits have shape {full.shape}, "
                f"expected ({n},{n}) — mismatched splits across processes")
    else:
        full = splits.astype(np.int64)
    rows_global = list(local_pos) if multi else list(range(n))

    # Pad every (rank, peer) block to the max block size with ONE gather per
    # rank row (an index map built host-side), run the dense AllToAll, then
    # slice the ragged rows back out with one gather each. O(n) device ops
    # total — not the O(n^2) per-block slicing a naive port would do — and
    # the index maps are data, so distinct splits matrices reuse the same
    # compiled programs as long as the padded shape matches.
    block = max(int(full.max()), 1)
    m = int(t.shape[1])
    pack_idx = _alltoall_pack_index(full.tobytes(), n, m,
                                    tuple(rows_global))
    pad_width = [(0, 0), (0, 1)] + [(0, 0)] * (t.ndim - 2)
    t_pad = jnp.pad(t, pad_width)
    dense = jax.vmap(lambda row, idx: row[idx])(t_pad, pack_idx)
    (dense,) = _prepare([dense], mesh, n, "alltoall")
    shapes, dtypes = _signature([dense])
    prog = _alltoall_program(mesh, n, shapes, dtypes)
    with _timeline_op(name or "alltoall", "ALLTOALL", (dense,),
                      process_set=ps):
        exchanged = _localize([prog(dense)[0]], mesh)[0]
    received = full.T  # received[r][p] = rows rank r got from peer p
    rows = []
    for i, g in enumerate(rows_global):
        keep = np.concatenate(
            [p * block + np.arange(int(received[g, p])) for p in range(n)]
        ).astype(np.int64)
        rows.append(exchanged[i][keep])
    return rows, received[np.asarray(rows_global)]


@functools.lru_cache(maxsize=64)
def _alltoall_pack_index(full_bytes, n, m, rows_global):
    """Device-resident pack-index map for the uneven alltoall, cached by
    (splits matrix, tensor rows, local rows): a repeated splits pattern —
    the steady state of MoE dispatch — reuses both the host index build
    (O(n²·block)) and its device upload instead of rebuilding per step
    (the reference negotiates splits once per response, not per call:
    collective_operations.h:199-268)."""
    full = np.frombuffer(full_bytes, np.int64).reshape(n, n)
    block = max(int(full.max()), 1)
    offs = np.concatenate([np.zeros((n, 1), np.int64),
                           np.cumsum(full, axis=1)], axis=1)
    j = np.arange(block, dtype=np.int64)
    # pack_idx[i, p*block + k] = offs[g,p] + k for k < full[g,p], else m
    # (m indexes the zero sentinel row appended by the caller).
    pack = offs[:, :-1, None] + j[None, None, :]          # (n, n, block)
    pack = np.where(j[None, None, :] < full[:, :, None], pack, m)
    return jnp.asarray(pack.reshape(n, n * block)[list(rows_global)])


@_interceptable("barrier")
def barrier(process_set=None, name=None):
    """Block until all ranks reach the barrier
    (reference: hvd.barrier operations.cc EnqueueBarrier, message.h BARRIER)."""
    mesh, ps = _mesh_for(process_set)
    multi, local_pos = _local_mesh_info(mesh)
    rows = len(local_pos) if multi else ps.size()
    _join_sync(ps, mesh, {"kind": "barrier"})
    token = np.zeros((rows, 1), np.int32)
    (token,) = _prepare([token], mesh, ps.size(), "barrier")
    with _timeline_op(name or "barrier", "BARRIER", process_set=ps):
        jax.block_until_ready(_barrier_program(mesh)(token))


def _active_mask(ps):
    """0/1 tuple over the set's ranks excluding joined ranks, or None when
    nobody has joined (the fast path). Joined state is the union of the
    global protocol's (st.joined_ranks) and this set's own armed-mode
    accounting (ps.joined_ranks, reference: per-ProcessSet joined_size)."""
    st = basics._get_state()
    set_joined = getattr(ps, "joined_ranks", set())
    if not st.joined_ranks and not set_joined:
        return None
    joined_union = set(st.joined_ranks) | set_joined
    ranks = ps.rank_list()
    if all(r in joined_union for r in ranks):
        # Every participant of this set joined — there is nobody left to
        # contribute, so the collective is a contract violation (the global
        # set can't reach here: join() resets on world completion).
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(
            f"collective on process set {ranks} after all its ranks joined")
    return tuple(0 if r in joined_union else 1 for r in ranks)


# ----------------------------------------------------------------------------
# Multi-process JOIN (reference: controller.cc:269-327 joined-size
# accounting, torch/mpi_ops_v2.cc:972 DoJoin).
#
# The reference's background controller negotiates EVERY collective, which
# is what lets a joined rank keep answering negotiations and contributing
# zeros until everyone has joined. The TPU hot path deliberately has no
# per-op negotiation (compiled programs replace it), so JOIN across
# processes is an armed MODE (HOROVOD_JOIN_MODE=1): while armed, every
# global-set eager collective opens with one tiny KV "join round" in which
# each process publishes either the descriptor of the op it is dispatching
# or the set of ranks it has joined. A joined process sits inside join()
# mirroring each negotiated descriptor with zero-filled inputs — its chips
# must still launch the XLA program for the device collective to complete —
# while the active ranks' programs carry the negotiated active-mask, giving
# exact reference semantics (Sum-as-zero, Average over n_active, static
# drop for Min/Max/Prod/Adasum, root-joined error). When a round shows
# every rank joined, state resets and join() returns the last rank to join.
# ----------------------------------------------------------------------------

def _join_armed():
    """Whether the multi-process join protocol is on (armed) — every
    global-set eager collective then pays one KV round, joined or not."""
    st = basics._get_state()
    return st.config.join_mode and jax.process_count() > 1


def _exchange_join_round(tag, procs, payload):
    """One raw protocol round on ``tag``: each participant publishes
    ``{"joined": [...], "desc": ...}`` and reads everyone else's.
    Returns ``(joined_union, descs)``."""
    from horovod_tpu.common import negotiation
    payloads = negotiation.exchange(tag, payload, procs=procs)
    joined = set()
    descs = []
    for p in payloads:
        joined.update(int(r) for r in p["joined"])
        if p.get("desc") is not None:
            descs.append(p["desc"])
    return joined, descs


def _join_round(payload):
    """Global-set protocol round; updates st.joined_ranks to the union."""
    joined, descs = _exchange_join_round("join_round", None, payload)
    st = basics._get_state()
    st.joined_ranks.clear()
    st.joined_ranks.update(joined)
    return joined, descs


def _join_round_set(ps, mesh, payload):
    """SET-SCOPED protocol round: only the processes owning devices of
    ``ps``'s mesh participate (reference: joined_size is per ProcessSet,
    controller.cc:269-327 — the complement of the set never pays the
    round). The tag carries the set's rank list so two sets with the same
    owner processes keep distinct descriptor streams. Updates
    ``ps.joined_ranks`` to the union."""
    tag = "join_round_set/" + ",".join(str(r) for r in ps.rank_list())
    joined, descs = _exchange_join_round(tag, _mesh_processes(mesh), payload)
    ps.joined_ranks = set(joined)
    return joined, descs


def _round_mask(joined, descs, desc, ranks, what):
    """Shared active-dispatch epilogue of a join round: verify every
    active peer dispatched the same descriptor, then build the 0/1 active
    mask over ``ranks`` (set positions) — None when nobody has joined."""
    bad = [d for d in descs if d != desc]
    if bad:
        raise TensorShapeMismatchError(
            f"join-mode collective mismatch on {what}: this process "
            f"dispatched {desc}, peer(s) dispatched {bad[:2]} at the same "
            f"round — every process must issue the same collectives in "
            f"the same order")
    if not joined:
        return None
    if len(joined) >= len(ranks):
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(
            f"collective on {what} after all its ranks joined")
    return tuple(0 if r in joined else 1 for r in ranks)


def _set_local_ranks(ps, mesh):
    """Global ranks of this process's devices WITHIN the set's mesh
    (submesh device order == rank_list order, topology.build_submesh)."""
    _, local_pos = _local_mesh_info(mesh)
    ranks = ps.rank_list()
    return [ranks[i] for i in local_pos]


def _join_sync(ps, mesh, desc):
    """Pre-dispatch hook for every eager collective: fence in-flight
    fused ASYNC work (so sync and async device collectives submit in the
    same order on every process — see FusionRuntime.fence), then the
    armed-mode join round (or the plain local mask when not armed). Must
    run BEFORE any other cross-process interaction of the op
    (``_prepare``'s order check, size negotiations) so active and
    mirroring processes interleave their control-plane exchanges in the
    same order."""
    st = basics._get_state()
    if st.fusion is not None:
        st.fusion.fence()
    if not _join_armed():
        return _active_mask(ps)
    if ps.ranks is not None:
        multi, _ = _local_mesh_info(mesh)
        if not multi:
            return _active_mask(ps)
        # Set-scoped armed round among the set's owner processes only
        # (the complement keeps training untouched).
        mine = sorted(set(ps.joined_ranks) & set(_set_local_ranks(ps, mesh)))
        joined, descs = _join_round_set(ps, mesh,
                                        {"joined": mine, "desc": desc})
        return _round_mask(joined, descs, desc, ps.rank_list(),
                           f"process set {ps.rank_list()}")
    _, local_pos = _local_mesh_info(mesh)
    mine = sorted(st.joined_ranks.intersection(local_pos))
    joined, descs = _join_round({"joined": mine, "desc": desc})
    return _round_mask(joined, descs, desc, list(range(ps.size())),
                       "the global set")


def _slice_desc(tensors, mesh=None, n=None, what=None):
    """JSON-able per-tensor (slice-shape, dtype) signature, leading
    (local-rank) axis excluded. With ``mesh``/``n``/``what`` the stacked
    leading axis is validated HERE — i.e. before the join round — so a
    malformed input raises before any descriptor is published (an active
    raising after publishing would leave joined mirrors launching a
    collective nobody joins)."""
    rows = _expected_rows(mesh, n) if mesh is not None else None
    out = []
    for t in tensors:
        if not hasattr(t, "ndim"):
            t = np.asarray(t)
        if rows is not None:
            _check_stacked(t, rows, what)
        out.append([[int(s) for s in t.shape[1:]], str(_dtype_of(t))])
    return out


def _mirror_dispatch(desc, joined, process_set=None):
    """Run on a JOINED process: launch the XLA program the active ranks
    negotiated, feeding zero-filled local rows (the mask makes the math
    exact; the launch itself is what the device collective needs).
    ``process_set`` scopes the mirror to a sub-set's mesh (set-scoped
    armed join); default is the global set."""
    mesh, ps = _mesh_for(process_set)
    n = ps.size()
    _, local_pos = _local_mesh_info(mesh)
    rows = len(local_pos)
    # Mask positions follow the SET's rank order (global set: identity).
    mask = tuple(0 if r in joined else 1 for r in ps.rank_list())
    kind = desc["kind"]
    if kind == "alltoall":
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(
            "alltoall is not supported while ranks have joined (matches "
            "the reference: JOIN covers allreduce/allgather/broadcast "
            "only)")
    if kind == "allgather_ragged":
        # Mirror the active sequence exactly: the size negotiation (zero
        # rows from joined ranks), then the inner public allgather — whose
        # own join round lines up with the actives' inner round.
        tail = tuple(desc["tail"])
        zeros = [jnp.zeros((0,) + tail, desc["dtype"])
                 for _ in range(rows)]
        allgather_ragged(zeros, process_set=process_set, _mirror=True)
        return
    if kind == "barrier":
        token = np.zeros((rows, 1), np.int32)
        (token,) = _prepare([token], mesh, n, "barrier")
        with _timeline_op("join_mirror_barrier", "JOIN"):
            jax.block_until_ready(_barrier_program(mesh)(token))
        return
    zeros = [np.zeros([rows] + list(s), np.dtype(d))
             for s, d in desc["slices"]]
    tensors = _prepare(zeros, mesh, n, kind)
    shapes, dtypes = _signature(tensors)
    if kind == "allreduce":
        prog = _allreduce_program(mesh, n, ReduceOp(desc["op"]),
                                  float(desc["pre"]), float(desc["post"]),
                                  shapes, dtypes, mask)
    elif kind == "reducescatter":
        prog = _reducescatter_program(mesh, n, ReduceOp(desc["op"]),
                                      float(desc["pre"]),
                                      float(desc["post"]), shapes, dtypes,
                                      mask)
    elif kind == "allgather":
        prog = _allgather_program(mesh, n, shapes, dtypes, mask)
    elif kind == "broadcast":
        if not mask[int(desc["root"])]:
            # The actives raise this after the same round and never launch
            # a program — raise symmetrically instead of hanging in a
            # mirror launch nobody joins.
            from horovod_tpu.common.exceptions import HorovodInternalError
            raise HorovodInternalError(
                f"broadcast root_rank {desc['root']} has joined")
        prog = _broadcast_program(mesh, n, int(desc["root"]), shapes,
                                  dtypes)
    else:
        from horovod_tpu.common.exceptions import HorovodInternalError
        raise HorovodInternalError(f"join mirror: unknown op kind {kind!r}")
    with _timeline_op(f"join_mirror_{kind}", "JOIN"):
        jax.block_until_ready(prog(*tensors))


def _join_multiprocess(st, rank):
    """join() under HOROVOD_JOIN_MODE: publish this process's ranks as
    joined and service the protocol loop — mirroring every collective the
    still-active ranks dispatch — until the world has joined. Returns the
    highest rank of the final round's newly-joined set (all processes
    compute the same value from the same round sequence)."""
    mesh = global_process_set.mesh
    _, local_pos = _local_mesh_info(mesh)
    my_ranks = sorted(local_pos)
    if rank is not None:
        raise ValueError(
            "multi-process join() takes no rank argument: each process "
            "joins all the ranks (chips) it owns — call join() from the "
            "process whose data ran out")
    n = basics.size()
    # Every process participates in every round (actives via _join_sync),
    # so st.joined_ranks here is the union as of the LAST completed round —
    # the same value every looping process holds as its previous-round
    # union. Snapshot it BEFORE adding my ranks so the final round's
    # newly-joined set (which determines the returned last rank) is
    # computed identically everywhere, including by the last joiner.
    prev = set(st.joined_ranks)
    st.joined_ranks.update(my_ranks)
    while True:
        joined, descs = _join_round({"joined": my_ranks, "desc": None})
        if descs:
            if any(d != descs[0] for d in descs[1:]):
                raise TensorShapeMismatchError(
                    f"join-mode collective mismatch among active ranks: "
                    f"{descs[:3]}")
            # The round rewrote st.joined_ranks to the union; the mirror's
            # own nested rounds (ragged) need my ranks marked joined.
            st.joined_ranks.update(my_ranks)
            _mirror_dispatch(descs[0], joined)
            prev = joined
            continue
        if len(joined) >= n:
            newly = joined - prev
            st.joined_ranks.clear()
            return max(newly) if newly else n - 1
        prev = joined


def _join_multiprocess_set(ps):
    """join(process_set=ps) under HOROVOD_JOIN_MODE: publish this
    process's ranks WITHIN the set as joined and service the set-scoped
    protocol loop — mirroring every collective the set's still-active
    ranks dispatch — until the whole set has joined. Processes outside
    the set never participate (reference: per-ProcessSet joined_size,
    controller.cc:269-327). Returns the highest GLOBAL rank of the final
    round's newly-joined set (like the global join(); NOT the set-local
    index — index into rank_list() to convert).

    Contract: while any process is inside ``join(process_set=ps)``, the
    set's other owner processes may only dispatch ``ps``-scoped
    collectives until the set join completes (the joining process cannot
    answer other meshes' control rounds while it loops here) — the same
    same-order SPMD contract every armed-mode exchange carries.
    """
    mesh = ps.mesh
    my_ranks = sorted(_set_local_ranks(ps, mesh))
    if not my_ranks:
        raise ValueError(
            f"join(process_set=...): this process owns no ranks of "
            f"{ps.rank_list()}")
    ranks = ps.rank_list()
    n = len(ranks)
    prev = set(ps.joined_ranks)
    ps.joined_ranks = prev | set(my_ranks)
    while True:
        joined, descs = _join_round_set(ps, mesh,
                                        {"joined": my_ranks, "desc": None})
        if descs:
            if any(d != descs[0] for d in descs[1:]):
                raise TensorShapeMismatchError(
                    f"join-mode collective mismatch among active ranks of "
                    f"process set {ranks}: {descs[:3]}")
            _mirror_dispatch(descs[0], joined, process_set=ps)
            prev = joined
            continue
        if len(joined) >= n:
            newly = joined - prev
            ps.joined_ranks = set()
            return max(newly) if newly else ranks[-1]
        prev = joined


def join(rank=None, process_set=None):
    """Signal that ``rank`` (default: every rank this controller owns) has
    exhausted its uneven workload.

    reference semantics (torch/mpi_ops.py DoJoin, controller.cc:269-327,
    joined_size accounting): a joined rank contributes nothing to subsequent
    collectives — Sum treats it as zeros, Average divides by the active
    count, Min/Max/Product/Adasum exclude it — until every rank has joined,
    at which point the join completes and returns the id of the last rank to
    join (and the join state resets).

    Multi-process semantics: set ``HOROVOD_JOIN_MODE=1`` on every process.
    While armed, each global-set eager collective opens with one small KV
    round (the control-plane cost the reference pays on every collective
    through its background controller); a process whose data ran out calls
    ``join()``, which joins ALL the ranks (chips) it owns and services the
    protocol loop — mirroring the still-active ranks' collectives with
    zero contributions — until every rank has joined. Without the mode
    flag, calling join() under a multi-process launch raises rather than
    corrupting state (a process cannot silently drop out of SPMD
    dispatch). alltoall raises while ranks are joined (reference: JOIN
    covers allreduce/allgather/broadcast).

    ``process_set``: join only within that set (reference: joined_size is
    per ProcessSet, controller.cc:269-327). The set's OTHER owner
    processes keep dispatching set-scoped collectives with this process's
    ranks masked out; processes outside the set are untouched and keep
    training. The join loop services set-scoped rounds only — see
    :func:`_join_multiprocess_set` for the ordering contract.
    """
    st = basics._get_state()
    if process_set is not None and process_set.ranks is not None:
        if rank is not None:
            raise ValueError(
                "join(process_set=...) takes no rank argument: the process "
                "joins all the ranks it owns within the set")
        multi, _ = _local_mesh_info(process_set.mesh)
        if multi:
            if not st.config.join_mode:
                raise NotImplementedError(
                    "hvd.join(process_set=...) across processes requires "
                    "HOROVOD_JOIN_MODE=1 on every owner process of the set")
            return _join_multiprocess_set(process_set)
        # Single owner process: all the set's ranks are ours — the join
        # completes immediately (nothing to mirror, nobody else to wait
        # for) and the set's joined state resets.
        process_set.joined_ranks = set()
        return process_set.rank_list()[-1]
    if jax.process_count() > 1:
        if st.config.join_mode:
            return _join_multiprocess(st, rank)
        # Deliberately NOT HorovodInternalError: that is the retryable
        # collective-failure type the elastic @run wrapper restores-and-
        # retries, which would loop forever on this deterministic usage
        # error.
        raise NotImplementedError(
            "hvd.join() across processes requires HOROVOD_JOIN_MODE=1 on "
            "every process (it arms a per-collective negotiation round). "
            "Without it, multi-process eager dispatch is SPMD and cannot "
            "drop one process from subsequent collectives — pad uneven "
            "batches or use the elastic API.")
    if rank is None:
        st.joined_ranks.update(range(basics.size()))
    else:
        if not (0 <= rank < basics.size()):
            raise ValueError(f"join: rank {rank} out of range")
        st.joined_ranks.add(rank)
    if len(st.joined_ranks) >= basics.size():
        st.joined_ranks.clear()
        barrier()
        return basics.size() - 1
    return -1


# ----------------------------------------------------------------------------
# Async handles (reference: handle_manager.h + mpi_ops.py:1245-1283)
# ----------------------------------------------------------------------------

class Handle:
    """In-flight collective result. JAX dispatch is already asynchronous, so
    the handle just wraps the pending device arrays."""

    __slots__ = ("_outputs", "name")

    def __init__(self, outputs, name=None):
        self._outputs = outputs
        self.name = name

    def poll(self):
        # Leaves without is_ready() are concrete host values (numpy etc.),
        # which are by definition complete; jax.Arrays report readiness.
        return all(
            o.is_ready() if hasattr(o, "is_ready") else True
            for o in jax.tree_util.tree_leaves(self._outputs))

    def synchronize(self):
        jax.block_until_ready(self._outputs)
        return self._outputs


@_interceptable("allreduce_async")
def allreduce_async(tensor, op=Average, prescale_factor=1.0,
                    postscale_factor=1.0, process_set=None, name=None):
    """Async allreduce through the tensor-fusion runtime: small tensors
    submitted back-to-back are batched into one fused collective
    (reference: every async allreduce rides the fusion buffer + cycle loop,
    operations.cc:747-853). Process-set ops bypass fusion (the runtime fuses
    per the global mesh only, like the reference fuses per process set)."""
    if (process_set is not None and process_set.ranks is not None) \
            or _join_armed():
        # Armed join mode: the fusion runtime's deferred flush cannot open
        # the per-collective join round at enqueue time (the op set isn't
        # final until flush), so async falls back to an immediate sync
        # dispatch — correctness over overlap while the mode is on.
        return Handle(allreduce(tensor, op=op, prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                process_set=process_set, name=name), name)
    from horovod_tpu.ops.fusion import get_runtime
    t = tensor if hasattr(tensor, "ndim") else np.asarray(tensor)
    _check_stacked(t, _expected_rows(global_process_set.mesh, basics.size()),
                   "allreduce_async")
    if op == Average and not _is_float(_dtype_of(t)):
        raise ValueError("Average is not supported for integer tensors; use "
                         "hvd.Sum (matches reference torch/mpi_ops.py checks).")
    rt = get_runtime()
    req = _wire.consume_wire_request()
    if req and _wire.quantized_label(req) is not None and \
            _wire.quantized_label(getattr(rt, "wire_dtype", None)) is None:
        # Compression.int8 on the async path while the fusion runtime's own
        # wire is full precision: honor the request with a sync quantized
        # dispatch (correctness over overlap — the runtime quantizes whole
        # buckets only when its own wire knob is quantized, and a per-call
        # request cannot retroactively re-key an open bucket).
        _wire.request_wire_once(req)
        return Handle(allreduce(t, op=op, prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                name=name), name)
    return rt.enqueue_allreduce(t, op, prescale_factor,
                                postscale_factor, name)


@_interceptable("allreduce_async")
def grouped_allreduce_async(tensors, op=Average, prescale_factor=1.0,
                            postscale_factor=1.0, process_set=None, name=None):
    """Async grouped allreduce through the fusion runtime: the group
    completes atomically and same-signature groups ride ONE fused bucket
    (reference: grouped enqueue + GroupTable, operations.cc:1480,
    group_table.h). Process-set groups bypass fusion like allreduce_async;
    so does armed join mode (see allreduce_async)."""
    if (process_set is not None and process_set.ranks is not None) \
            or _join_armed():
        out = grouped_allreduce(tensors, op=op,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                process_set=process_set, name=name)
        return Handle(out, name)
    from horovod_tpu.ops.fusion import get_runtime
    ts = [t if hasattr(t, "ndim") else np.asarray(t) for t in tensors]
    rows = _expected_rows(global_process_set.mesh, basics.size())
    for t in ts:
        _check_stacked(t, rows, "grouped_allreduce_async")
        if op == Average and not _is_float(_dtype_of(t)):
            raise ValueError(
                "Average is not supported for integer tensors; use hvd.Sum "
                "(matches reference torch/mpi_ops.py checks).")
    rt = get_runtime()
    req = _wire.consume_wire_request()
    if req and _wire.quantized_label(req) is not None and \
            _wire.quantized_label(getattr(rt, "wire_dtype", None)) is None:
        # Same one-shot discipline as allreduce_async: the request must be
        # consumed HERE (not leak to the next unrelated eager dispatch),
        # and when the fusion runtime's own wire is full precision it is
        # honored with a sync quantized grouped dispatch.
        _wire.request_wire_once(req)
        return Handle(grouped_allreduce(
            ts, op=op, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, name=name), name)
    return rt.enqueue_grouped_allreduce(
        ts, op, prescale_factor, postscale_factor, name)


@_interceptable("allgather_async")
def allgather_async(tensor, process_set=None, name=None):
    return Handle(allgather(tensor, process_set=process_set, name=name), name)


@_interceptable("broadcast_async")
def broadcast_async(tensor, root_rank, process_set=None, name=None):
    return Handle(broadcast(tensor, root_rank, process_set=process_set,
                            name=name), name)


@_interceptable("alltoall_async")
def alltoall_async(tensor, splits=None, process_set=None, name=None):
    return Handle(alltoall(tensor, splits=splits, process_set=process_set,
                           name=name), name)


@_interceptable("reducescatter_async")
def reducescatter_async(tensor, op=Sum, process_set=None, name=None):
    return Handle(reducescatter(tensor, op=op, process_set=process_set,
                                name=name), name)


def poll(handle):
    return handle.poll()


def synchronize(handle):
    return handle.synchronize()


# ----------------------------------------------------------------------------
# Object collectives (reference: torch/functions.py broadcast_object /
# allgather_object — pickle to a byte tensor, exchange, unpickle).
# ----------------------------------------------------------------------------

def broadcast_object(obj, root_rank=0, process_set=None, name=None):
    import cloudpickle  # available via baked-in deps
    mesh, ps = _mesh_for(process_set)
    n = ps.size()
    payload = cloudpickle.dumps(obj)
    buf = np.frombuffer(payload, dtype=np.uint8)
    n_rows = _expected_rows(mesh, n)
    # Pad (or truncate — non-root payloads are discarded anyway) all ranks
    # to the root's length (length broadcast first).
    ln = int(broadcast(jnp.full((n_rows, 1), len(buf), jnp.int32), root_rank,
                       process_set=process_set)[0, 0])
    row = jnp.pad(jnp.asarray(buf), (0, max(0, ln - len(buf))))[:ln]
    stacked = jnp.tile(row[None], (n_rows, 1))
    out = broadcast(stacked, root_rank, process_set=process_set, name=name)
    data = bytes(np.asarray(out[0, :ln], np.uint8))
    return cloudpickle.loads(data)


def allgather_object_single(obj, process_set=None, name=None):
    """Frontend convenience: gather ONE object for this caller — the object
    stands for each rank this process owns (all of them single-controller,
    the local chips multi-process). Shared by the torch/tf/mxnet
    ``allgather_object`` wrappers."""
    mesh, ps = _mesh_for(process_set)
    n_rows = _expected_rows(mesh, ps.size())
    return allgather_object([obj] * n_rows, process_set=process_set,
                            name=name)


def allgather_object(objs, process_set=None, name=None):
    """Gather every rank's object(s); returns the full per-rank list on
    every caller. ``objs``: one object per rank (single process) or per
    local chip (multi-process); the global split sizes come back from the
    ragged allgather's negotiation."""
    import cloudpickle
    mesh, ps = _mesh_for(process_set)
    n = ps.size()
    n_rows = _expected_rows(mesh, n)
    if not isinstance(objs, (list, tuple)) or len(objs) != n_rows:
        raise ValueError(
            f"allgather_object expects a list of {n_rows} objects "
            f"(one per {'local chip' if n_rows != n else 'rank'})")
    bufs = [np.frombuffer(cloudpickle.dumps(o), dtype=np.uint8) for o in objs]
    gathered, sizes = allgather_ragged([jnp.asarray(b) for b in bufs],
                                       process_set=process_set, name=name,
                                       return_sizes=True)
    out, off = [], 0
    arr = np.asarray(gathered, np.uint8)
    for s in sizes:
        out.append(cloudpickle.loads(bytes(arr[off:off + s])))
        off += s
    return out

"""Multi-level allreduce strategies for 2-D (cross × local) meshes.

Reference algorithms being mapped:

- ``NCCLHierarchicalAllreduce`` (reference: horovod/common/ops/
  nccl_operations.cc ~200-580, knob HOROVOD_HIERARCHICAL_ALLREDUCE
  common.h:130): node-local ReduceScatter → cross-node allreduce of the
  scattered shards → node-local Allgather.
- ``NCCLTorusAllreduce`` (fork-specific; reference: nccl_operations.cc:606-843,
  knob HOROVOD_TORUS_ALLREDUCE common.h:132): the same 2-level scheme with the
  cross-node leg running per-local-rank on separate communicators — i.e. each
  local shard's cross-node reduction proceeds in parallel.

TPU-native mapping: ``local`` = chips within a slice (ICI), ``cross`` = slices
(DCN). ``psum_scatter(local) → psum(cross) → all_gather(local)`` expresses
exactly the torus schedule, and XLA runs each cross-slice shard reduction in
parallel — the property the fork's custom NCCL code buys — while moving only
1/local_size of the bytes over the slow cross link.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.topology import CROSS_AXIS, LOCAL_AXIS
from horovod_tpu.trace.scopes import scope


def allreduce_torus(x, cross_axis=CROSS_AXIS, local_axis=LOCAL_AXIS,
                    average=False, flatten=True, cross_compression=None,
                    cross_residual=None, record=True):
    """2-level allreduce: ICI reduce-scatter, DCN shard allreduce, ICI
    all-gather. Bit-equivalent to a flat allreduce (UNLESS
    ``cross_compression`` is set); bandwidth-optimal when the cross link is
    the bottleneck.

    ``x`` is this chip's local value. Requires ``x.size`` divisible by the
    local axis size when ``flatten`` (pads otherwise).

    ``cross_compression="int8"``/``"fp8"`` (lossy) quantizes ONLY the
    cross (DCN) leg through the block-scaled exchange — the ICI
    reduce-scatter/all-gather stay full precision while the slow
    inter-slice hop moves ~2 bytes/element (the EQuARX deployment shape:
    quantize where bandwidth hurts). Eligibility rides THE shared
    :func:`horovod_tpu.ops.wire.quantized_eligible` predicate (the same
    refusal the flat wire applies): shards below one BLOCK per cross rank
    would INFLATE on the exchange's padding and stay exact.

    ``cross_residual`` (per-bucket error feedback for the quantized cross
    leg): an fp32 buffer of the local SHARD's size
    (``ceil(x.size / local_n)``) holding the previous round's cross-leg
    quantization error; when given, returns ``(out, new_residual)`` —
    the residual passes through unchanged when the cross leg stays exact.

    ``record=False`` suppresses the per-tier trace-time wire accounting:
    the runtime's eager/fused hierarchical programs pass it because they
    meter each dispatch themselves — double counting would break the
    cost model's exact cross-check.
    """
    from horovod_tpu.ops import wire as _wire
    local_n = lax.axis_size(local_axis)
    cross_n = lax.axis_size(cross_axis)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % local_n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    label = None
    if cross_compression is not None:
        label = _wire.quantized_label(cross_compression)
        if label is None and cross_compression not in (
                "", "int8", "fp8", "float16", "bfloat16"):
            raise ValueError(
                f"unknown cross_compression {cross_compression!r}; "
                "use None/'' (exact), 'int8' or 'fp8' (16-bit wire names "
                "are accepted for policy-chain compatibility and keep the "
                "cross leg exact — a cast cross wire is not implemented)")
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)
    all_float = jnp.issubdtype(x.dtype, jnp.floating)
    if label is not None and not _wire.quantized_eligible(
            shard.size, cross_n, all_float, True):
        # Shared refusal with the flat wire tier: below one BLOCK per
        # cross rank the padded exchange moves MORE bytes than the exact
        # psum (and non-float payloads never quantize).
        label = None
    if record:
        _record_jit_wire_tiered(x, flat.size, local_n, cross_n, label)
    new_res = cross_residual
    if label is not None:
        shard, new_res = _wire.block_scaled_allreduce(
            shard, residual=cross_residual, axis_name=cross_axis,
            wire=label)
    else:
        shard = lax.psum(shard, cross_axis)
    full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    out = full.reshape(orig_shape)
    if average:
        n = local_n * cross_n
        out = out / jnp.asarray(n, out.dtype)
    if cross_residual is not None:
        return out, new_res
    return out


def allreduce_tiered(x, cross_axis=CROSS_AXIS, local_axis=LOCAL_AXIS,
                     average=False, cross_wire=None, residual=None,
                     prescale_factor=1.0, postscale_factor=1.0):
    """The in-jit entry of the hierarchical dispatch tier: local RS
    (exact, ICI) -> cross-slice allreduce on ``cross_wire`` (DCN) ->
    local AG, with the reference's pre/postscale applied around the
    decomposition. Delegates to :func:`allreduce_torus`; ``cross_wire``
    defaults to the per-tier policy
    (:func:`horovod_tpu.ops.wire.cross_wire_for` of the global set) so a
    jit step follows the same HOROVOD_WIRE_DTYPE_DCN / registry chain as
    the eager and fused paths. With ``residual`` (fp32, the local shard's
    size, threaded through the caller's optimizer state — zero it on
    elastic reset, hvdlint HVP109) returns ``(out, new_residual)``."""
    if cross_wire is None:
        from horovod_tpu.common import basics
        from horovod_tpu.ops import wire as _wire
        try:
            cross_wire = _wire.cross_wire_for("global", basics.config())
        except Exception:  # noqa: BLE001 — uninitialized: exact cross
            cross_wire = ""
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, x.dtype)
    out = allreduce_torus(x, cross_axis=cross_axis, local_axis=local_axis,
                          average=average, cross_compression=cross_wire or
                          None, cross_residual=residual)
    out, new_res = out if residual is not None else (out, None)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, out.dtype)
    return out if residual is None else (out, new_res)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _quantized_a2a(x, axis_name, num_participants, wire,
                   axis_index_groups=None):
    """One block-scaled alltoall leg (the EQuARX exchange's first-leg
    shape): ``x``'s leading dim holds one destination row per participant;
    each row is quantized block-wise (one fp32 scale per
    :data:`horovod_tpu.ops.wire.BLOCK` elements), the 1-byte rows plus
    their scales move on an AllToAll, receivers dequantize. Returns the
    exchanged array in ``x``'s shape/dtype.

    Deliberately STATELESS — an alltoall moves data without reducing, so
    there is no accumulated sum for an error-feedback residual to correct
    (unlike the allreduce exchange): each element pays one bounded
    round-off (``block max/254`` for int8) exactly once.

    Differentiation is straight-through: the backward exchange is the
    a2a's own transpose (split0/concat0 is an involution) run EXACT —
    ``round``'s a.e.-zero derivative would otherwise kill every gradient
    crossing a slice, and quantizing gradients without error feedback is
    precisely what the expert-leg policy refuses (docs/performance.md)."""
    from horovod_tpu.ops import wire as _wire
    s = int(num_participants)
    orig_shape, orig_dtype = x.shape, x.dtype
    rows = x.reshape(s, -1).astype(jnp.float32)
    pad = (-rows.shape[1]) % _wire.BLOCK
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    blocks = rows.reshape(s, rows.shape[1] // _wire.BLOCK, _wire.BLOCK)
    q, scale = _wire.quantize_blocks(blocks, wire)
    qt = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=axis_index_groups)
    st = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=axis_index_groups)
    out = _wire.dequantize(qt, st).reshape(s, -1)
    if pad:
        out = out[:, :-pad]
    return out.reshape(orig_shape).astype(orig_dtype)


def _quantized_a2a_fwd(x, axis_name, num_participants, wire,
                       axis_index_groups):
    return _quantized_a2a(x, axis_name, num_participants, wire,
                          axis_index_groups), None


def _quantized_a2a_bwd(axis_name, num_participants, wire, axis_index_groups,
                       _res, g):
    xbar = lax.all_to_all(g, axis_name, split_axis=0, concat_axis=0,
                          axis_index_groups=axis_index_groups)
    return (xbar,)


_quantized_a2a.defvjp(_quantized_a2a_fwd, _quantized_a2a_bwd)


def alltoall_tiered(x, cross_axis=CROSS_AXIS, local_axis=LOCAL_AXIS,
                    cross_wire=None, record=True):
    """2-level alltoall over a (cross × local) mesh: slice-local a2a (ICI)
    first, then one cross-slice a2a (DCN) of already-grouped rows — with
    the cross leg optionally block-scaled (``cross_wire="int8"``/
    ``"fp8"``). Bit-equivalent to the flat
    ``lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)``
    over the rank-major flattened (cross, local) pair UNLESS the cross leg
    quantizes.

    ``x``'s leading dim must divide by ``cross_n * local_n`` (the same
    equal-splits contract as the flat tiled a2a). The genuinely
    cross-slice rows move over DCN exactly once — the decomposition's win
    is that the ``1/cross_n`` slice-internal share of every payload never
    leaves the ICI, and the rest can ride the 1-byte wire.

    Eligibility of the quantized cross leg rides THE shared
    :func:`horovod_tpu.ops.wire.quantized_eligible` predicate (per-rank
    payload below one BLOCK per destination slice would inflate on the
    exchange padding and stays exact) — the same refusal
    :func:`horovod_tpu.ops.wire.hierarchical_a2a_bytes` applies, so
    recorded bytes always match the wire.

    ``record=False`` suppresses the per-tier trace-time accounting (the
    runtime's eager hierarchical program meters each dispatch itself)."""
    from horovod_tpu.ops import wire as _wire
    cross_n = int(lax.axis_size(cross_axis))
    local_n = int(lax.axis_size(local_axis))
    n = cross_n * local_n
    m = x.shape[0]
    if m % n:
        raise ValueError(
            f"alltoall_tiered: leading dim {m} not divisible by the "
            f"{cross_n}x{local_n} mesh size {n}")
    label = _wire.quantized_label(cross_wire) if cross_wire else None
    all_float = jnp.issubdtype(x.dtype, jnp.floating)
    if label is not None and not _wire.quantized_eligible(
            x.size, cross_n, all_float, True):
        label = None
    if record:
        _record_jit_a2a_tiered(x, n, cross_n, label)
    blocks = x.reshape((cross_n, local_n, m // n) + x.shape[1:])
    blocks = lax.all_to_all(blocks, local_axis, split_axis=1,
                            concat_axis=1, tiled=True)
    if label is not None:
        blocks = _quantized_a2a(blocks, cross_axis, cross_n, label, None)
    else:
        blocks = lax.all_to_all(blocks, cross_axis, split_axis=0,
                                concat_axis=0, tiled=True)
    return blocks.reshape((m,) + x.shape[1:])


def alltoall_tiered_groups(x, axis_name, num_slices, cross_wire=None,
                           record=True):
    """The flat-axis form of :func:`alltoall_tiered` for meshes that do
    not factor the axis: the SAME 2-level schedule expressed with
    ``axis_index_groups`` over one flat ``axis_name`` in rank-major
    (slice, chips-in-slice) layout — phase 1 exchanges within each slice's
    contiguous group (ICI), phase 2 across slices between same-local-index
    ranks (DCN, optionally block-scaled). This is what
    ``parallel/moe.py`` routes expert dispatch/combine through inside an
    arbitrary named mesh (the composite dp×pp scenario's dp axis
    included), where no (cross, local) axis pair exists to shard over."""
    from horovod_tpu.ops import wire as _wire
    n = int(lax.axis_size(axis_name))
    s = int(num_slices)
    if s <= 1 or n % s:
        raise ValueError(
            f"alltoall_tiered_groups: {s} slices do not divide the "
            f"{n}-rank axis {axis_name!r} (resolve the hierarchy with "
            "a2a_hierarchy_for first)")
    local_n = n // s
    m = x.shape[0]
    if m % n:
        raise ValueError(
            f"alltoall_tiered_groups: leading dim {m} not divisible by "
            f"axis size {n}")
    # Tuples: the quantized leg's custom_vjp carries the groups as a
    # non-differentiable (hashable) argument.
    local_groups = tuple(tuple(c * local_n + l for l in range(local_n))
                         for c in range(s))
    cross_groups = tuple(tuple(c * local_n + l for c in range(s))
                         for l in range(local_n))
    label = _wire.quantized_label(cross_wire) if cross_wire else None
    all_float = jnp.issubdtype(x.dtype, jnp.floating)
    if label is not None and not _wire.quantized_eligible(
            x.size, s, all_float, True):
        label = None
    if record:
        _record_jit_a2a_tiered(x, n, s, label)
    blocks = x.reshape((s, local_n, m // n) + x.shape[1:])
    blocks = lax.all_to_all(blocks, axis_name, split_axis=1, concat_axis=1,
                            tiled=True, axis_index_groups=local_groups)
    if label is not None:
        blocks = _quantized_a2a(blocks, axis_name, s, label, cross_groups)
    else:
        blocks = lax.all_to_all(blocks, axis_name, split_axis=0,
                                concat_axis=0, tiled=True,
                                axis_index_groups=cross_groups)
    return blocks.reshape((m,) + x.shape[1:])


def a2a_hierarchy_for(axis_name, hierarchical=None):
    """Trace-time hierarchy resolution for an in-jit alltoall over
    ``axis_name``: ``(num_slices, cross_label_or_None)`` when the 2-level
    route applies, else ``None``. THE resolution chain the MoE layer and
    the static cost model share: explicit ``hierarchical`` override from
    the layer, else the a2a strategy registry /
    ``HOROVOD_HIERARCHICAL_ALLTOALL`` default; slice count from the
    forced ``HOROVOD_MESH_SLICES`` layout (or the initialized topology's
    DCN hierarchy when the axis spans the whole world), through
    ``topology.slice_layout``'s divisibility rules; the cross wire from
    :func:`horovod_tpu.ops.wire.alltoall_cross_wire_for` — a plain
    ``hier`` pin keeps the cross leg exact, ``hier_qcross`` (the default
    when the knob is on) follows the expert cross-dtype chain."""
    try:
        from horovod_tpu.common import basics
        from horovod_tpu.common import topology as _topology
        from horovod_tpu.ops import wire as _wire
        n = int(lax.axis_size(axis_name))
        if n <= 1:
            return None
        try:
            cfg = basics.config()
        except Exception:  # noqa: BLE001 — uninitialized: flat dispatch
            return None
        if hierarchical is None:
            default = ("hier_qcross"
                       if getattr(cfg, "hierarchical_alltoall", False)
                       else "")
            strategy = _wire.alltoall_strategy_for("global", default)
            if strategy not in ("hier", "hier_qcross"):
                return None
        elif not hierarchical:
            return None
        else:
            strategy = "hier_qcross"
        k = _topology.forced_slices()
        if not k:
            st = basics._state
            topo = st.topology if st is not None else None
            if topo is not None and topo.num_slices > 1 and topo.size == n:
                k = topo.num_slices
        if not k:
            return None
        num_slices, _ = _topology.slice_layout(n, k)
        if num_slices <= 1:
            return None
        cross = None
        if strategy == "hier_qcross":
            cross = _wire.quantized_label(
                _wire.alltoall_cross_wire_for("global", cfg))
        return num_slices, cross
    except Exception:  # noqa: BLE001 — resolution must never break a trace
        return None


def allgather_hierarchical(x, cross_axis=CROSS_AXIS, local_axis=LOCAL_AXIS,
                           record=True):
    """2-level allgather: gather within each host's chips first, then one
    cross-host gather of whole host-blocks (reference:
    MPIHierarchicalAllgather, mpi_operations.cc — node-local gather then
    cross-node exchange of node blocks; knob
    HOROVOD_HIERARCHICAL_ALLGATHER common.h:131). ``record=False``
    suppresses the trace-time wire accounting (the runtime's eager
    allgather program meters its own dispatches).

    ``x`` is this chip's local value; returns ``(n_total, *x.shape)`` in
    global rank-major order (rank = cross * local_size + local, matching
    :func:`horovod_tpu.common.topology.build_topology`'s layout) — the
    same value a flat all_gather produces, but the cross link moves one
    contiguous block per HOST instead of interleaving per-chip messages
    (the cross axis of mesh2d is the host boundary, like the reference's
    node boundary)."""
    try:
        if record:
            local_n = int(lax.axis_size(local_axis))
            cross_n = int(lax.axis_size(cross_axis))
            n = local_n * cross_n
            width = jnp.dtype(x.dtype).itemsize
            # Local gather: n ranks each contribute x.size over ICI;
            # cross gather: n ranks each move their whole local block
            # (local_n * x.size) over DCN — the per-tier trace-time twin
            # of _record_jit_wire.
            _record_wire_tiers(str(jnp.dtype(x.dtype)), {
                "ici": n * int(x.size) * width,
                "dcn": n * local_n * int(x.size) * width})
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        pass
    loc = lax.all_gather(x, local_axis, axis=0, tiled=False)
    full = lax.all_gather(loc, cross_axis, axis=0, tiled=False)
    return full.reshape((-1,) + x.shape)


def allreduce_hierarchical(x, cross_axis=CROSS_AXIS, local_axis=LOCAL_AXIS,
                           average=False, record=True):
    """Hierarchical 2-phase allreduce: full local reduce then cross reduce.
    Moves the whole buffer on the cross link (unlike torus) but needs no
    divisibility; matches NCCLHierarchicalAllreduce's structure.
    ``record=False`` suppresses the trace-time wire accounting (the
    fusion runtime meters its own bucket dispatches)."""
    try:
        if record:
            local_n = int(lax.axis_size(local_axis))
            cross_n = int(lax.axis_size(cross_axis))
            n = local_n * cross_n
            width = jnp.dtype(x.dtype).itemsize
            # Both psum stages count both internal legs; the cross stage
            # moves the WHOLE buffer per rank (the structural difference
            # from torus this accounting makes visible).
            _record_wire_tiers(str(jnp.dtype(x.dtype)), {
                "ici": 2 * n * int(x.size) * width,
                "dcn": 2 * n * int(x.size) * width})
    except Exception:  # noqa: BLE001
        pass
    out = lax.psum(lax.psum(x, local_axis), cross_axis)
    if average:
        n = lax.axis_size(local_axis) * lax.axis_size(cross_axis)
        out = out / jnp.asarray(n, out.dtype)
    return out


# THE symmetric int8 quantizer lives in the wire tier now (one definition
# for the wire exchange AND the quantized KV cache); re-exported here for
# the existing import sites.
from horovod_tpu.ops.wire import symmetric_int8_quantize  # noqa: F401,E402


def _record_jit_wire(x, axis_name, wire):
    """Trace-time wire accounting for the in-jit entry points: the shapes
    are static during tracing, so this records once per compiled program
    (documented in wire_compression_events_total's help text), never on
    the device hot path."""
    try:
        from horovod_tpu.metrics import instruments as hvd_metrics
        from horovod_tpu.ops import wire as _wire
        n = int(lax.axis_size(axis_name))
        hvd_metrics.record_wire(
            "jit", wire, _wire.exchange_wire_bytes(int(x.size), n),
            compressed=True)
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        pass


def _record_wire_tiers(dtype_label, tiers, compressed=False):
    """Record an explicit per-tier byte split on the jit path (trace-time,
    like :func:`_record_jit_wire`)."""
    from horovod_tpu.metrics import instruments as hvd_metrics
    total = sum(tiers.values())
    if total:
        hvd_metrics.record_wire("jit", dtype_label, total,
                                compressed=compressed, tiers=dict(tiers))


def _record_jit_wire_tiered(x, padded_elems, local_n, cross_n, cross_label):
    """Per-tier trace-time accounting for the 2-level torus/tiered
    allreduce: ICI legs (local RS + AG) at the payload dtype, the DCN leg
    at the cross wire — the SAME integer formulas as
    :func:`horovod_tpu.ops.wire.hierarchical_wire_bytes`, so the runtime
    counters and the static model's hierarchical what-if agree exactly."""
    try:
        from horovod_tpu.ops import wire as _wire
        n = int(local_n) * int(cross_n)
        width = jnp.dtype(x.dtype).itemsize
        # hierarchical_wire_bytes expects the per-rank PRE-padding size;
        # padded_elems is already local_n-aligned, so shard math matches.
        h = _wire.hierarchical_wire_bytes(
            int(padded_elems), n, int(cross_n), width,
            cross_wire=cross_label or "")
        _record_wire_tiers(str(jnp.dtype(x.dtype)), {"ici": h["ici"]})
        _record_wire_tiers(cross_label or str(jnp.dtype(x.dtype)),
                           {"dcn": h["dcn"]},
                           compressed=cross_label is not None)
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        pass


def _record_jit_a2a_flat(x, n):
    """Trace-time wire accounting for a FLAT in-jit alltoall of a
    per-rank buffer ``x`` over ``n`` ranks: ``n * size * width`` total
    (self-destined chunks included, the a2a convention), split by the
    live topology's a2a foreign-destination fraction — the baseline the
    hierarchical records are compared against in the moe_sweep bench."""
    try:
        from horovod_tpu.metrics import instruments as hvd_metrics
        width = jnp.dtype(x.dtype).itemsize
        hvd_metrics.record_wire("jit", str(jnp.dtype(x.dtype)),
                                int(n) * int(x.size) * width, sched="a2a")
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        pass


def _record_jit_a2a_tiered(x, n, num_slices, cross_label):
    """Per-tier trace-time accounting for the 2-level alltoall: the local
    (ICI) leg at the payload dtype, the cross leg at its wire dtype with
    the ``(S-1)/S`` genuinely-cross-slice share booked to DCN — the SAME
    integer formulas as
    :func:`horovod_tpu.ops.wire.hierarchical_a2a_bytes`, so the runtime
    counters and the static model's hierarchical a2a what-if agree
    exactly (``cross_check_bytes`` delta 0)."""
    try:
        from horovod_tpu.metrics import instruments as hvd_metrics
        from horovod_tpu.ops import wire as _wire
        width = jnp.dtype(x.dtype).itemsize
        h = _wire.hierarchical_a2a_bytes(int(x.size), int(n),
                                         int(num_slices), width,
                                         cross_wire=cross_label or "")
        hvd_metrics.record_wire("jit", str(jnp.dtype(x.dtype)), h["local"],
                                tiers={"ici": h["local"]}, sched="a2a")
        hvd_metrics.record_wire(
            "jit", h["cross_label"] or str(jnp.dtype(x.dtype)), h["cross"],
            compressed=h["cross_label"] is not None,
            tiers=dict(h["cross_tiers"]), sched="a2a")
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        pass


def scaled_allreduce_int8(x, axis_name="hvd", average=False,
                          prescale_factor=1.0, postscale_factor=1.0):
    """:func:`allreduce_int8` with the reference's pre/postscale applied
    around the exchange — the ONE wrapper both the jit fused path
    (optim/optimizer.py) and the eager fusion runtime (ops/fusion.py)
    call, so the scaling order can never diverge between them."""
    from horovod_tpu.ops import wire as _wire
    _record_jit_wire(x, axis_name, "int8")
    # The quantized exchange as one unit of the wire (its blocks' scales
    # and both int8 legs), as in_jit.allreduce scopes its psum.
    with scope("hvd.wire"):
        out, _ = _wire.block_scaled_allreduce(
            x, axis_name=axis_name, wire="int8", average=average,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    return out


def allreduce_int8(x, axis_name="hvd", average=False):
    """Quantized allreduce: int8 on the wire, fp32 accumulation.

    The EQuARX-style two-phase exchange (arXiv:2506.17615) — int8 both
    legs, one fp32 scale per 1024-element block, reduce in fp32 — now
    implemented once in :func:`horovod_tpu.ops.wire.block_scaled_allreduce`
    (which also offers the fp8 variant and the error-feedback form whose
    residual the caller threads through its own state). This entry point
    is the stable in-jit API; it keeps the exchange exact-shape/dtype
    preserving and records trace-time wire accounting.
    """
    from horovod_tpu.ops import wire as _wire
    _record_jit_wire(x, axis_name, "int8")
    out, _ = _wire.block_scaled_allreduce(
        x, axis_name=axis_name, wire="int8", average=average)
    return out


def allreduce_quantized(x, axis_name="hvd", wire_dtype="int8", average=False,
                        prescale_factor=1.0, postscale_factor=1.0,
                        residual=None):
    """Generalized in-jit quantized allreduce: ``wire_dtype`` selects the
    block format — ``int8``, or ``fp8`` where this jax build has the
    dtype (an fp8-less build falls back to the int8 blocks: this function
    promises a QUANTIZED wire, and the accounting records the format
    actually used). With ``residual`` (an fp32 buffer of ``x``'s flat
    size threaded through the caller's optimizer state) returns ``(out,
    new_residual)`` — the in-jit error-feedback form; the caller MUST
    zero the residual on elastic reset (hvdlint HVP109 flags
    configurations that look like they won't). Without it returns just
    ``out``."""
    from horovod_tpu.ops import wire as _wire
    label = _wire.quantized_label(wire_dtype) or "int8"
    _record_jit_wire(x, axis_name, label)
    out, new_res = _wire.block_scaled_allreduce(
        x, residual=residual, axis_name=axis_name, wire=label,
        average=average, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor)
    return out if residual is None else (out, new_res)

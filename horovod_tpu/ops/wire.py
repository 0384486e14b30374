"""Quantized wire tier: block-scaled int8/fp8 collectives with error feedback.

This module owns everything "bytes-on-the-wire" shaped that used to live
scattered across the stack: the symmetric block quantizers (promoted out of
``parallel/strategies.py`` — one definition for the wire exchange AND the
quantized KV cache), the EQuARX-style two-phase quantized allreduce
(arXiv:2506.17615 — quantization fused into the reduce-scatter→all-gather
phases inside XLA), per-bucket **error-feedback** accumulators (residual
kept in fp32, added back before the next quantize — the Horovod compression
design of arXiv:1802.05799 pairs lossy wire formats with exactly this), the
per-process-set wire-dtype registry the autotuner steers (with per-LINK-TIER
keys — ``ps@dcn`` is the cross-slice leg's policy of the hierarchical
dispatch tier — and the sibling dispatch-strategy registry), the
slice-boundary tier-split math shared with the static cost model
(``ring_dcn_fraction``/``a2a_dcn_fraction``/``hierarchical_wire_bytes``),
and the wire-byte accounting behind ``wire_bytes_total{dtype,tier}``.

Three dispatch paths consume it (each records
``wire_compression_events_total{path,dtype}``):

- **eager** — ``ops/collective_ops.grouped_allreduce`` routes float
  Sum/Average allreduces through :func:`block_scaled_allreduce` when the
  effective wire dtype is quantized (``_WireDispatchPlan``), with the
  residual held in the process-local :func:`ef_get`/:func:`ef_put` store.
- **fused** — ``ops/fusion._fused_program`` rides the same exchange per
  fusion bucket, one residual per bucket signature.
- **jit** — ``parallel/strategies.allreduce_int8`` /
  ``scaled_allreduce_int8`` delegate here for use inside user
  ``shard_map``/``pjit`` steps; :func:`block_scaled_allreduce` with an
  explicit ``residual`` is the in-jit error-feedback entry point (the
  caller threads the residual through its own optimizer state — and must
  zero it on elastic reset; hvdlint HVP109 flags the configuration).

Wire formats: ``int8`` (symmetric, ±127) and ``fp8`` (e4m3, ±448 — gated
on the installed jax exposing ``float8_e4m3fn``; otherwise the tier falls
back to a bf16 cast wire with a one-time warning). Scales are one fp32 per
:data:`BLOCK` (1024) elements — block scales keep small-magnitude tensors
in a mixed fused bucket from rounding to zero (≈0.4 % wire overhead).

Error-feedback residuals live in the SUM domain after prescale: the
residual is added after the prescale multiply and before quantization, so
the compensated error re-enters the very next reduction of the same
bucket. Residuals are device arrays of the torn-down backend after an
elastic resize, so :func:`reset_error_feedback` is wired into
``collective_ops.clear_program_caches`` — a resized mesh must never replay
stale residuals.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# One fp32 scale per this many elements (EQuARX's block quantization).
BLOCK = 1024

# Largest finite magnitude of float8_e4m3fn.
FP8_MAX = 448.0

# Quantized wire labels (the rest of the accepted wire dtypes are casts).
QUANTIZED = ("int8", "fp8")


def quantized_label(dtype_like):
    """``"int8"``/``"fp8"`` when ``dtype_like`` (a wire string, numpy/jnp
    dtype, or scalar type) names a quantized wire format, else None."""
    if dtype_like is None or dtype_like == "":
        return None
    if isinstance(dtype_like, str) and dtype_like in QUANTIZED:
        return dtype_like
    try:
        name = jnp.dtype(dtype_like).name
    except TypeError:
        return None
    if name == "int8":
        return "int8"
    if name.startswith("float8"):
        return "fp8"
    return None


def is_quantized(name):
    return quantized_label(name) is not None


def wire_numpy_type(name):
    """Numpy/jnp scalar type for a configured wire dtype string, or None
    for the full-precision wire. This is what the fusion runtime stores in
    ``wire_dtype`` (its bucket keys and boundary payloads serialize it via
    ``jnp.dtype(...).name``)."""
    if not name:
        return None
    if name == "fp8":
        return jnp.float8_e4m3fn
    return jnp.dtype(name).type


# ----------------------------------------------------------------------------
# Block quantizers
# ----------------------------------------------------------------------------

def symmetric_int8_quantize(t):
    """THE symmetric int8 quantizer (one definition for the wire exchange
    AND the quantized KV cache): per-LAST-axis scale ``max|t|/127``
    clamped at 1e-30, round + clip to ±127. Returns ``(q8, scale)`` with
    ``scale.shape == t.shape[:-1]`` (fp32 math expected in ``t``)."""
    scale = jnp.maximum(jnp.max(jnp.abs(t), axis=-1) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(t / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def symmetric_fp8_quantize(t):
    """fp8 (e4m3) sibling of :func:`symmetric_int8_quantize`: per-LAST-axis
    scale ``max|t|/448``, cast to ``float8_e4m3fn`` (the cast rounds).
    fp8's mantissa gives ~2 decimal digits but its exponent keeps relative
    error flat across each block's dynamic range — better than int8 on
    heavy-tailed gradient blocks, same 1 byte/element on the wire."""
    scale = jnp.maximum(jnp.max(jnp.abs(t), axis=-1) / FP8_MAX, 1e-30)
    q = (t / scale[..., None]).astype(jnp.float8_e4m3fn)
    return q, scale


def quantize_blocks(t, wire):
    """Dispatch to the block quantizer for wire format ``wire``."""
    if wire == "fp8":
        return symmetric_fp8_quantize(t)
    return symmetric_int8_quantize(t)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


# ----------------------------------------------------------------------------
# The two-phase block-scaled exchange (EQuARX shape), with optional
# error feedback.
# ----------------------------------------------------------------------------

def block_scaled_allreduce(x, residual=None, axis_name="hvd", wire="int8",
                           average=False, prescale_factor=1.0,
                           postscale_factor=1.0):
    """Quantized allreduce: ``wire`` bytes on the wire, fp32 accumulation.

    Two-phase exchange built from XLA collectives:

    1. each rank splits its buffer into n destination shards and quantizes
       block-wise (one fp32 scale per :data:`BLOCK` elements),
    2. one AllToAll moves the 1-byte shards (+ a tiny fp32 scale AllToAll),
    3. each rank dequantizes and accumulates its shard in fp32
       (the reduce-scatter leg, 1 byte/element on the wire),
    4. the reduced shard is requantized block-wise and AllGathered
       (+ fp32 scales), then dequantized (the all-gather leg, 1 B/el).

    Total wire traffic ≈ 2 bytes/element vs ~8 for an fp32 psum's internal
    reduce-scatter + all-gather — at the cost of one quantization error
    per leg, bounded per element by its own block's ``max/254`` (int8).

    ``residual`` (error feedback): an fp32 buffer of ``x``'s flat size
    holding the previous round's quantization error in the prescaled SUM
    domain. It is added before quantization; the new residual — this
    round's first-leg error plus the second-leg error of the shard this
    rank owns — is returned alongside the result. Returns ``(out, None)``
    without a residual, ``(out, new_residual)`` with one.

    Works on any local shape; ``out`` has the same shape/dtype as ``x``.
    """
    n = lax.axis_size(axis_name)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    if prescale_factor != 1.0:
        flat = flat * jnp.asarray(prescale_factor, flat.dtype)
    ef = residual is not None
    if ef:
        flat = flat + residual.reshape(-1).astype(jnp.float32)
    size = flat.size
    pad = (-size) % (n * BLOCK)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nb = flat.size // (n * BLOCK)                    # blocks per shard
    blocks = flat.reshape(n, nb, BLOCK)              # [dest, block, elem]
    q, scale = quantize_blocks(blocks, wire)         # scale (n, nb)
    if ef:
        err1 = blocks - dequantize(q, scale)         # first-leg local error
    # Row d goes to rank d; row r of the result came from rank r.
    qt = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    st = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0)
    part = jnp.sum(dequantize(qt, st), axis=0)       # (nb, BLOCK) fp32
    q2, s2 = quantize_blocks(part, wire)             # s2 (nb,)
    deq2 = dequantize(q2, s2)
    full_q = lax.all_gather(q2, axis_name, axis=0, tiled=False)  # (n,nb,blk)
    full_s = lax.all_gather(s2, axis_name, axis=0, tiled=False)  # (n, nb)
    out = dequantize(full_q, full_s).reshape(-1)
    new_res = None
    if ef:
        # This rank compensates (a) the quantization error of everything it
        # SENT (first leg, whole buffer) and (b) the requantization error
        # of the one shard it OWNS (second leg) — each global error term is
        # thus re-injected into the sum exactly once, by exactly one rank.
        res = err1.reshape(-1)
        shard_len = nb * BLOCK
        start = lax.axis_index(axis_name) * shard_len
        err2 = part - deq2                           # (nb, BLOCK)
        own = lax.dynamic_slice_in_dim(res, start, shard_len)
        res = lax.dynamic_update_slice_in_dim(
            res, own + err2.reshape(-1), start, axis=0)
        new_res = res[:size] if pad else res
    if pad:
        out = out[:-pad]
    if average:
        out = out / jnp.asarray(n, out.dtype)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, out.dtype)
    return out.reshape(orig_shape).astype(orig_dtype), new_res


# ----------------------------------------------------------------------------
# Error-feedback store (eager + fused paths; process-local, fp32 residuals
# as device arrays). In-jit callers thread residuals through their own
# state instead — this store cannot reach inside a jitted optimizer.
# ----------------------------------------------------------------------------

_EF_CAP = 64
_ef_lock = threading.RLock()
_ef_store = {}


def ef_get(key):
    with _ef_lock:
        return _ef_store.get(key)


def ef_put(key, residual):
    with _ef_lock:
        if key not in _ef_store and len(_ef_store) >= _EF_CAP:
            # Runaway-signature guard: evict the OLDEST entry (insertion
            # order), never the whole store — residuals are a convergence
            # aid, and a job legitimately cycling through many bucket
            # signatures must not lose every other bucket's feedback each
            # time one new key arrives. Dropping one costs that bucket a
            # single uncompensated round, never a wrong result.
            _ef_store.pop(next(iter(_ef_store)))
        _ef_store[key] = residual


def ef_pop(key):
    with _ef_lock:
        return _ef_store.pop(key, None)


def ef_keys():
    with _ef_lock:
        return list(_ef_store)


def reset_error_feedback():
    """Drop every error-feedback residual. Wired into
    ``collective_ops.clear_program_caches`` (and through it the elastic
    reset path): residuals are device arrays of the torn-down backend, and
    a resized mesh must not replay stale errors sized for the old world."""
    with _ef_lock:
        n = len(_ef_store)
        _ef_store.clear()
    return n


# ----------------------------------------------------------------------------
# Per-process-set wire-dtype registry.
#
# The config knob (HOROVOD_WIRE_DTYPE) is the default for every set; the
# registry overrides per set — set by the user (hvd.set_wire_dtype) or by
# the autotuner's categorical sweep. Multi-process discipline: the fusion
# coordinator updates "global" when it PUBLISHES a flush boundary (the
# knob snapshot its programs really used) and followers update when they
# ADOPT that boundary — so at any sync-collective program point (which
# fences fused work first) every process reads the same value. Direct
# set_wire_dtype calls under multi-process launches are themselves subject
# to the SPMD contract: every process must make the same call at the same
# program point.
# ----------------------------------------------------------------------------

_wire_lock = threading.RLock()
_wire_registry = {}            # key -> (value, source: "user"|"runtime")
#                                key = ps_label, or "ps@tier" for the
#                                per-link-tier policy (tier="dcn": the
#                                cross-slice leg of the hierarchical
#                                dispatch tier)

_ACCEPTED = ("", "float16", "bfloat16", "int8", "fp8")

# Link tiers of the slice hierarchy (the wire_bytes_total{tier} label
# values): "ici" = in-slice interconnect, "dcn" = the scarce cross-slice
# network the 2-level decomposition exists to relieve.
TIERS = ("ici", "dcn")


def _registry_key(ps_label, tier=None):
    return str(ps_label) if not tier else f"{ps_label}@{tier}"


def _normalize(dtype):
    name = {"fp16": "float16", "bf16": "bfloat16"}.get(dtype or "",
                                                       dtype or "")
    try:
        name = name if name in _ACCEPTED else jnp.dtype(name).name
    except TypeError:
        raise ValueError(
            f"wire dtype {dtype!r}: expected one of {_ACCEPTED}") from None
    if name.startswith("float8"):
        name = "fp8"
    if name not in _ACCEPTED:
        raise ValueError(
            f"wire dtype {dtype!r}: expected one of {_ACCEPTED}")
    return name


def set_wire_dtype(dtype, ps_label="global", tier=None):
    """Set the wire dtype for one process set ('' restores full
    precision). Returns the normalized value in effect. Dispatch plans are
    keyed on the wire dtype, so a flip simply routes subsequent eager
    collectives through differently-keyed plans — no explicit
    invalidation, no desync window. An explicit call here PINS the set:
    the fusion runtime's boundary sync (the autotuner's adoption path)
    no longer overwrites it — that is what makes the troubleshooting
    'bisect with the registry' A/B stick while async flushes continue.
    ``tier="dcn"`` sets the per-link-tier policy instead: the wire of the
    cross-slice leg of the hierarchical dispatch tier."""
    name = _normalize(dtype)
    with _wire_lock:
        _wire_registry[_registry_key(ps_label, tier)] = (name, "user")
    return name


def runtime_sync_wire_dtype(dtype, ps_label="global", tier=None):
    """Fusion-boundary adoption of the runtime/autotuner wire snapshot:
    like :func:`set_wire_dtype` but it DEFERS to an explicit user pin
    (see above). Returns the value actually in effect."""
    name = _normalize(dtype)
    with _wire_lock:
        key = _registry_key(ps_label, tier)
        cur = _wire_registry.get(key)
        if cur is not None and cur[1] == "user":
            return cur[0]
        _wire_registry[key] = (name, "runtime")
    return name


def wire_dtype_for(ps_label, default="", tier=None):
    """Effective wire dtype for a process set: the registry's entry, else
    ``default`` (normally the config knob). With ``tier`` the per-tier
    entry (``ps@tier``) is consulted; absent one, ``default`` applies —
    pass the resolved tier default (e.g. ``config.wire_dtype_dcn or
    config.wire_dtype`` for the DCN leg)."""
    with _wire_lock:
        v = _wire_registry.get(_registry_key(ps_label, tier))
    return (default or "") if v is None else v[0]


def cross_wire_for(ps_label, config):
    """Effective wire dtype of the CROSS-SLICE (DCN) leg for one process
    set — THE resolution chain runtime and static model share: per-tier
    registry entry (``ps@dcn``), else ``HOROVOD_WIRE_DTYPE_DCN``, else
    the flat wire knob (a job that quantizes its flat wire wants the
    scarce leg quantized at least as much)."""
    default = getattr(config, "wire_dtype_dcn", "") \
        or getattr(config, "wire_dtype", "")
    return wire_dtype_for(ps_label, default, tier="dcn")


def clear_wire_registry():
    with _wire_lock:
        _wire_registry.clear()


# ----------------------------------------------------------------------------
# Per-process-set dispatch-strategy registry (flat / hier / hier_qcross).
#
# The autotuner's strategy categorical is adopted per process set at flush
# boundaries exactly like the wire dtype above: the coordinator syncs when
# it publishes a boundary, followers when they apply it, and an explicit
# hvd.set_dispatch_strategy call pins the set against runtime sync.
# ----------------------------------------------------------------------------

STRATEGIES = ("", "flat", "hier", "hier_qcross")

_strategy_registry = {}        # ps_label -> (value, source)


def _normalize_strategy(strategy):
    s = strategy or ""
    if s not in STRATEGIES:
        raise ValueError(
            f"dispatch strategy {strategy!r}: expected one of {STRATEGIES}"
            " ('' = config default; hier = 2-level RS/cross/AG; "
            "hier_qcross = hierarchical with the cross leg on the "
            "quantized wire)")
    return s


def set_dispatch_strategy(strategy, ps_label="global"):
    """Pin the eager allreduce dispatch strategy for one process set
    ('' restores the config default). Like :func:`set_wire_dtype`, plans
    are keyed on the strategy, so a flip routes the next dispatch through
    a differently-keyed plan with no desync window."""
    s = _normalize_strategy(strategy)
    with _wire_lock:
        _strategy_registry[str(ps_label)] = (s, "user")
    return s


def runtime_sync_dispatch_strategy(strategy, ps_label="global"):
    """Flush-boundary adoption of the autotuner's strategy choice; defers
    to an explicit user pin like :func:`runtime_sync_wire_dtype`."""
    s = _normalize_strategy(strategy)
    with _wire_lock:
        cur = _strategy_registry.get(str(ps_label))
        if cur is not None and cur[1] == "user":
            return cur[0]
        _strategy_registry[str(ps_label)] = (s, "runtime")
    return s


def dispatch_strategy_for(ps_label, default=""):
    """Effective dispatch strategy for a process set: registry entry,
    else ``default`` (normally derived from
    ``config.hierarchical_dispatch``)."""
    with _wire_lock:
        v = _strategy_registry.get(str(ps_label))
    return (default or "") if v is None or not v[0] else v[0]


def clear_strategy_registry():
    with _wire_lock:
        _strategy_registry.clear()
        _a2a_strategy_registry.clear()


# ----------------------------------------------------------------------------
# Per-process-set ALLTOALL strategy + cross-wire registry.
#
# The hierarchical alltoall tier (MoE expert dispatch) has its own lever
# pair — strategy (flat / hier / hier_qcross) and cross-slice wire dtype —
# steered by the autopilot at flush boundaries exactly like the allreduce
# pair above. It is a SEPARATE registry: alltoall moves activations, not
# error-fed gradients, so its quantization policy must never ride the
# allreduce knobs implicitly (docs/performance.md: when NOT to quantize
# the expert leg). The cross dtype reuses the wire registry under the
# namespaced ``a2a:<ps>@dcn`` key so user pins / runtime sync / clear all
# behave identically.
# ----------------------------------------------------------------------------

_a2a_strategy_registry = {}    # ps_label -> (value, source)


def set_alltoall_strategy(strategy, ps_label="global"):
    """Pin the eager/moe alltoall dispatch strategy for one process set
    ('' restores the config default). Plans are keyed on the strategy, so
    a flip routes the next dispatch through a differently-keyed plan with
    no desync window — the same contract as
    :func:`set_dispatch_strategy`."""
    s = _normalize_strategy(strategy)
    with _wire_lock:
        _a2a_strategy_registry[str(ps_label)] = (s, "user")
    return s


def runtime_sync_alltoall_strategy(strategy, ps_label="global"):
    """Flush-boundary adoption of the autotuner's alltoall strategy
    choice; defers to an explicit user pin like
    :func:`runtime_sync_dispatch_strategy`."""
    s = _normalize_strategy(strategy)
    with _wire_lock:
        cur = _a2a_strategy_registry.get(str(ps_label))
        if cur is not None and cur[1] == "user":
            return cur[0]
        _a2a_strategy_registry[str(ps_label)] = (s, "runtime")
    return s


def alltoall_strategy_for(ps_label, default=""):
    """Effective alltoall dispatch strategy for a process set: registry
    entry, else ``default`` (normally derived from
    ``config.hierarchical_alltoall``)."""
    with _wire_lock:
        v = _a2a_strategy_registry.get(str(ps_label))
    return (default or "") if v is None or not v[0] else v[0]


def set_alltoall_cross_dtype(dtype, ps_label="global"):
    """Pin the wire dtype of the hierarchical alltoall's cross-slice
    (DCN) leg for one process set ('' restores the config default)."""
    return set_wire_dtype(dtype, f"a2a:{ps_label}", tier="dcn")


def runtime_sync_alltoall_cross_dtype(dtype, ps_label="global"):
    """Flush-boundary adoption of the autotuner's expert cross-wire
    choice; defers to an explicit user pin."""
    return runtime_sync_wire_dtype(dtype, f"a2a:{ps_label}", tier="dcn")


def alltoall_cross_wire_for(ps_label, config):
    """Effective wire dtype of the hierarchical alltoall's CROSS-SLICE
    (DCN) leg — THE resolution chain runtime and static model share:
    per-set registry entry (``a2a:<ps>@dcn``), else
    ``HOROVOD_ALLTOALL_CROSS_DTYPE``. Deliberately does NOT fall back to
    the allreduce DCN wire: alltoall payloads are activations without
    error feedback, so quantizing them must be an explicit choice."""
    default = getattr(config, "alltoall_cross_dtype", "")
    return wire_dtype_for(f"a2a:{ps_label}", default, tier="dcn")


def zero_residual(mesh, sharding, n, flat_len):
    """Fresh all-zero error-feedback residual for one bucket: global
    ``(n, flat_len)`` fp32, sharded rank-major like the bucket's stacked
    inputs — the ONE constructor both the eager wire plan and the fusion
    runtime use."""
    from horovod_tpu.ops.collective_ops import _local_mesh_info
    multi, local_pos = _local_mesh_info(mesh)
    if multi:
        loc = np.zeros((len(local_pos), flat_len), np.float32)
        return jax.make_array_from_process_local_data(
            sharding, loc, (n, flat_len))
    return jax.device_put(jnp.zeros((n, flat_len), jnp.float32), sharding)


# ----------------------------------------------------------------------------
# One-shot per-dispatch wire request (the Compression.int8 eager route:
# compress() arms it, the immediately-following eager allreduce consumes
# it — read-and-clear, so it can never leak past one dispatch).
# ----------------------------------------------------------------------------

_tls = threading.local()


def request_wire_once(dtype):
    _tls.once = dtype


def consume_wire_request():
    v = getattr(_tls, "once", None)
    _tls.once = None
    return v


# ----------------------------------------------------------------------------
# Wire-byte accounting (the metrics registry's wire_bytes_total{dtype}).
# ----------------------------------------------------------------------------

def exchange_leg_bytes(per_rank_elems, n):
    """Bytes on the wire for ONE leg of the block-scaled exchange over
    ``n`` ranks of a ``per_rank_elems``-element buffer: the 1-byte payload
    plus the fp32 block scales, padding included (the exchange pads to
    n×BLOCK). Both legs move the same byte count, but over different
    schedules — the first is an AllToAll, the second an AllGather — which
    is why the analysis cost model splits them per leg when classifying
    ICI vs DCN traffic."""
    per_rank_elems = int(per_rank_elems)
    n = max(int(n), 1)
    padded = -(-per_rank_elems // (n * BLOCK)) * n * BLOCK
    blocks = padded // BLOCK
    return n * (padded + blocks * 4)


def exchange_wire_bytes(per_rank_elems, n):
    """Bytes on the wire for one block-scaled exchange over ``n`` ranks of
    a ``per_rank_elems``-element buffer: both 1-byte legs plus the fp32
    block scales, padding included (the exchange pads to n×BLOCK)."""
    return 2 * exchange_leg_bytes(per_rank_elems, n)


def quantized_eligible(total_per_rank_elems, n, all_float, sum_or_avg):
    """THE quantized-wire eligibility predicate shared by the runtime
    (``collective_ops._eager_wire_for``) and the static cost model
    (``analysis/cost.py``), so the analyzer can never predict a wire the
    dispatch layer would refuse: only float Sum/Average payloads of at
    least one BLOCK per destination rank ride the exchange — below that
    the n×BLOCK padding INFLATES the wire and the exact collective moves
    fewer bytes."""
    return bool(all_float and sum_or_avg
                and int(total_per_rank_elems) >= max(int(n), 1) * BLOCK)


def ring_dcn_fraction(members, slice_size):
    """Fraction of a rank-ordered ring's hops that cross a slice boundary
    (wraparound included): ``S/n`` for the world-spanning global set. THE
    tier-split rule shared by the runtime counters
    (``metrics.record_wire``'s default split) and the static cost model
    (``analysis/cost.py``), so the two can never disagree."""
    m = len(members)
    if m <= 1:
        return 0.0
    from horovod_tpu.common.topology import slice_of_rank
    crossings = sum(
        slice_of_rank(members[i], slice_size)
        != slice_of_rank(members[(i + 1) % m], slice_size)
        for i in range(m))
    return crossings / m


def a2a_dcn_fraction(members, slice_size):
    """Fraction of all-to-all destination rows that land in a foreign
    slice: ``1 - slice_size/n`` for the world-spanning global set (shared
    with the static cost model like :func:`ring_dcn_fraction`)."""
    m = len(members)
    if m <= 1:
        return 0.0
    from horovod_tpu.common.topology import slice_of_rank
    counts = {}
    for r in members:
        s = slice_of_rank(r, slice_size)
        counts[s] = counts.get(s, 0) + 1
    same = sum(c * c for c in counts.values())
    return (m * m - same) / (m * m)


def split_tiers(nbytes, frac_dcn):
    """``{"ici": b, "dcn": b}`` for one leg's bytes at a DCN fraction —
    one rounding rule (round-half-even on the DCN share) everywhere, so
    runtime counters and static predictions agree to the byte."""
    nbytes = int(nbytes)
    dcn = int(round(nbytes * frac_dcn))
    return {"ici": nbytes - dcn, "dcn": dcn}


def hierarchical_wire_bytes(per_rank_elems, n, num_slices, itemsize,
                            cross_wire=""):
    """Per-tier byte accounting for ONE 2-level hierarchical allreduce
    (local RS -> cross-slice allreduce -> local AG) of a
    ``per_rank_elems``-element per-rank buffer over ``n`` ranks in
    ``num_slices`` slices — the SAME integer formulas the runtime
    dispatch records and the static model's hierarchical what-if
    predicts, which is what makes ``cross_check_bytes`` exact (delta 0)
    on the CPU tier.

    Convention (matching the flat accounting): each leg counts
    participants x per-participant payload x width; the exact cross
    allreduce counts both its internal legs. Returns ``{"ici", "dcn",
    "cross_label", "shard_elems", "local_size", "num_slices"}`` —
    ``cross_label`` is the quantized label actually eligible on the
    cross leg (None = exact: sub-block shards would INFLATE on the
    exchange's S x BLOCK padding, same refusal as the flat wire)."""
    n = max(int(n), 1)
    num_slices = max(int(num_slices), 1)
    itemsize = max(int(itemsize), 1)
    local = max(n // num_slices, 1)
    shard = -(-int(per_rank_elems) // local)         # ceil
    padded = shard * local
    ici = 2 * n * padded * itemsize                  # local RS + local AG
    label = quantized_label(cross_wire)
    if label is not None and not quantized_eligible(
            shard, num_slices, True, True):
        label = None
    if label is not None:
        dcn = local * exchange_wire_bytes(shard, num_slices)
    else:
        dcn = 2 * n * shard * itemsize               # exact cross RS+AG
    return {"ici": ici, "dcn": dcn, "cross_label": label,
            "shard_elems": shard, "local_size": local,
            "num_slices": num_slices}


def hierarchical_a2a_bytes(per_rank_elems, n, num_slices, itemsize,
                           cross_wire=""):
    """Per-tier byte accounting for ONE 2-level hierarchical alltoall
    (slice-local a2a on ICI -> cross-slice a2a on the per-tier wire) of a
    ``per_rank_elems``-element per-rank buffer over ``n`` ranks in
    ``num_slices`` slices — the SAME integer formulas the runtime dispatch
    records and the static model's a2a what-if predicts, which is what
    keeps ``cross_check_bytes`` at delta 0 on the CPU tier.

    Convention (matching the flat accounting): each leg counts
    participants x per-participant payload x width, self-destined chunks
    included. The local leg is entirely in-slice (all ici). The cross leg
    runs one a2a over ``num_slices`` participants per local group — its
    members sit in ``num_slices`` DISTINCT slices, so its own
    :func:`a2a_dcn_fraction` is ``(S-1)/S`` and :func:`split_tiers` books
    that share to dcn (the genuinely cross-slice rows move exactly once,
    the information-theoretic floor). Returns ``{"local", "cross",
    "cross_tiers", "ici", "dcn", "cross_label", "local_size",
    "num_slices"}`` — ``cross_label`` is the quantized label actually
    eligible on the cross leg (None = exact: payloads below one BLOCK per
    destination slice would INFLATE on the exchange's S x BLOCK padding,
    the same refusal as the flat wire)."""
    n = max(int(n), 1)
    num_slices = max(int(num_slices), 1)
    itemsize = max(int(itemsize), 1)
    local_size = max(n // num_slices, 1)
    per = int(per_rank_elems)
    local_leg = n * per * itemsize
    label = quantized_label(cross_wire)
    if label is not None and not quantized_eligible(
            per, num_slices, True, True):
        label = None
    if label is not None:
        cross_leg = local_size * exchange_leg_bytes(per, num_slices)
    else:
        cross_leg = n * per * itemsize
    frac = (num_slices - 1) / num_slices if num_slices > 1 else 0.0
    cross_tiers = split_tiers(cross_leg, frac)
    return {"local": local_leg, "cross": cross_leg,
            "cross_tiers": cross_tiers,
            "ici": local_leg + cross_tiers["ici"],
            "dcn": cross_tiers["dcn"],
            "cross_label": label, "local_size": local_size,
            "num_slices": num_slices}


def allreduce_wire_bytes(payload_bytes, itemsize, n, wire):
    """Bytes-on-wire estimate for one allreduce of a global rank-major
    payload. Full-precision / cast wires model the ring allreduce's
    internal reduce-scatter + all-gather (every element crosses the wire
    twice at the wire width); quantized wires use the exchange's exact
    accounting. This is the estimate ``wire_bytes_total`` accumulates —
    the <0.3x int8-vs-fp32 guard in tests/test_wire.py holds it honest."""
    itemsize = max(int(itemsize), 1)
    elems = int(payload_bytes) // itemsize
    if quantized_label(wire):
        return exchange_wire_bytes(max(elems // max(int(n), 1), 0), n)
    width = {"float16": 2, "bfloat16": 2}.get(wire or "", itemsize)
    return 2 * elems * width

"""hvdcost: static per-link-tier communication cost model.

Builds on :func:`horovod_tpu.analysis.program.check_program`'s per-rank
abstract eval: every predicted collective event is priced in BYTES ON THE
WIRE — with the exact formulas the runtime meters (``wire_bytes_total``:
``ops/wire.py`` quantized-exchange accounting incl. scales and padding,
the RS+AG double-crossing for full-precision allreduces) — and every leg
is classified against the slice hierarchy from ``common/topology.py``
(``num_slices`` / ``mesh_dcn`` / forced ``HOROVOD_MESH_SLICES``) as
``ici`` (in-slice interconnect) or ``dcn`` (the scarce cross-slice tier,
where arXiv 2510.20171 locates the bandwidth cliff at 100k-GPU scale).

Tier classification rules (rank-major layout, ``slice = rank //
slice_size`` — the same reshape ``_build_dcn_mesh`` materializes):

- **ring-scheduled legs** (allreduce RS+AG, allgather, broadcast,
  reducescatter; jit ``psum``/``all_gather``/``reduce_scatter``/
  ``ppermute``): the DCN share is the fraction of ring hops that cross a
  slice boundary — ``S/n`` for a world-spanning set over ``S`` slices.
- **all-to-all-scheduled legs** (eager ``alltoall``, jit ``all_to_all``,
  and the FIRST leg of the block-scaled quantized exchange): the DCN
  share is the fraction of destination rows living in a foreign slice —
  ``1 - slice_size/n`` for a world-spanning set.
- jit collectives over the ``cross`` axis of the DCN mesh are pure DCN;
  over ``local`` pure ICI; unknown axes are assumed rank-major contiguous
  groups (in-slice when the group fits inside one slice).

Totals are CONSERVED across the split (the tier fractions of each leg sum
to 1), so ``sum(bytes_by_tier.values())`` equals the flat-schedule wire
estimate the runtime counters accumulate — which is what makes
:func:`cross_check_bytes` a tight (<5 %) comparison instead of a
hand-wave. The 2-level **hierarchical what-if** (local RS -> cross-slice
-> local AG, the fork's ``NCCLTorusAllreduce`` shape, ROADMAP item 3) is
reported alongside: same ICI volume, DCN divided by the slice width —
the target the future hierarchical allreduce will be validated against.

``python -m horovod_tpu.analysis.cost`` renders the per-tier table and
the HVP111 budget verdict with lint-style exit codes (see
``docs/static_analysis.md``); ``scripts/lint.py --cost`` runs it behind
the self-lint gate.
"""

import dataclasses
import json

from horovod_tpu.analysis import jaxpr_walk
from horovod_tpu.analysis.findings import (ERROR, INFO, Finding,
                                           sort_findings)
from horovod_tpu.common.topology import (CROSS_AXIS, LOCAL_AXIS,
                                         slice_layout, slice_of_rank)
from horovod_tpu.ops import wire as _wire

# Leg schedules per op label (flight labels + canonical jit primitives).
_A2A_OPS = {"alltoall", "all_to_all"}
_GATHER_LIKE = {"allgather", "broadcast", "reducescatter", "all_gather",
                "reduce_scatter", "ppermute", "pgather"}


def _is_float_name(dtype_str):
    s = str(dtype_str)
    return "float" in s or "bfloat" in s


def resolve_slices(world_size, num_slices=None):
    """``(num_slices, slice_size)`` for the cost model: an explicit
    ``num_slices`` wins, then the live topology's DCN hierarchy (when
    initialized at this world size), then the forced
    ``HOROVOD_MESH_SLICES`` knob — each subject to the mesh construction's
    own divisibility rules (:func:`topology.slice_layout`)."""
    if num_slices:
        return slice_layout(world_size, num_slices)
    try:
        from horovod_tpu.common import basics
        if basics.is_initialized() and basics._sim_world is None:
            topo = basics.topology()
            if topo.size == int(world_size) and topo.num_slices > 1:
                return slice_layout(world_size, topo.num_slices)
    except Exception:  # noqa: BLE001 — fall through to the env layout
        pass
    return slice_layout(world_size)


def _member_ranks(event, world_size, num_slices, slice_size):
    """Representative member-rank list for one event's exchange group —
    what the tier fractions are computed over. Eager sets use their real
    ranks; jit axes map onto the DCN mesh's (cross, local) structure, and
    unknown user axes are assumed rank-major contiguous."""
    if event.origin == "jit":
        axes = event.ps[len("axis:"):].split(",") \
            if event.ps.startswith("axis:") else []
        if axes == [CROSS_AXIS]:
            return [i * slice_size for i in range(num_slices)]
        if axes == [LOCAL_AXIS]:
            p = event.group_size(None) or slice_size
            return list(range(min(int(p), slice_size)))
        p = event.group_size(world_size) or world_size
        return list(range(min(int(p), world_size)))
    if event.ps_ranks:
        return [r for r in event.ps_ranks if r < world_size] \
            or list(event.ps_ranks)
    n = event.group_size(world_size) or world_size
    return list(range(min(int(n), world_size)))


# The ring / all-to-all slice-boundary fractions live in the WIRE tier now
# (one definition for this static classifier AND the runtime counters'
# default split — metrics.record_wire); re-exported here for the existing
# call sites.
_ring_dcn_fraction = _wire.ring_dcn_fraction
_a2a_dcn_fraction = _wire.a2a_dcn_fraction


def _hier_for_event(event, config, num_slices, use_registry=True):
    """Mirror of the runtime's hierarchical-dispatch verdict
    (``collective_ops._eager_hier_for``) for one predicted eager/fused
    allreduce: the effective cross-leg wire string when the dispatch
    layer would decompose this event (local RS -> cross-slice -> local
    AG), else None for the flat path. Shares the strategy registry /
    ``HOROVOD_HIERARCHICAL_DISPATCH`` chain and the float-Sum/Average
    single-dtype gates, so the analyzer can never predict a schedule the
    dispatch layer would refuse."""
    if event.op != "allreduce" or event.origin == "jit" \
            or event.ps != "global" or num_slices <= 1:
        return None
    default = "hier_qcross" \
        if getattr(config, "hierarchical_dispatch", False) else ""
    strategy = _wire.dispatch_strategy_for(event.ps, default) \
        if use_registry else default
    if strategy not in ("hier", "hier_qcross"):
        return None
    if event.red_op not in (None, "Sum", "Average"):
        return None
    dtypes = set(event.dtypes)
    if len(dtypes) != 1 or not all(_is_float_name(d) for d in dtypes):
        return None
    if strategy != "hier_qcross":
        return ""
    if use_registry:
        return _wire.cross_wire_for(event.ps, config)
    return (getattr(config, "wire_dtype_dcn", "")
            or getattr(config, "wire_dtype", ""))


def _a2a_hier_for_event(event, config, num_slices, use_registry=True):
    """Mirror of the runtime's hierarchical-ALLTOALL verdict
    (``collective_ops._eager_a2a_hier_for``) for one predicted eager
    alltoall: the effective cross-leg wire string when the dispatch layer
    would decompose this event (slice-local a2a -> cross-slice a2a), else
    None for the flat path. Shares the a2a strategy registry /
    ``HOROVOD_HIERARCHICAL_ALLTOALL`` chain and the single-tensor
    equal-splits gates — and, deliberately, the no-inherit cross-wire
    policy: ``alltoall_cross_dtype`` only, never the allreduce wire
    knobs."""
    if event.op != "alltoall" or event.origin == "jit" \
            or event.ps != "global" or num_slices <= 1:
        return None
    default = "hier_qcross" \
        if getattr(config, "hierarchical_alltoall", False) else ""
    strategy = _wire.alltoall_strategy_for(event.ps, default) \
        if use_registry else default
    if strategy not in ("hier", "hier_qcross"):
        return None
    if len(event.shapes) != 1:
        return None
    shape = event.shapes[0]
    n = int(shape[0]) if shape else 0
    if len(shape) < 2 or n < 2 or int(shape[1]) % n != 0:
        return None
    if strategy != "hier_qcross":
        return ""
    if use_registry:
        return _wire.alltoall_cross_wire_for(event.ps, config)
    return getattr(config, "alltoall_cross_dtype", "")


def _event_legs(event, world_size, config, use_registry=True,
                num_slices=1):
    """Transfer legs for one predicted event: a list of ``(bytes,
    schedule, dtype_label)`` with schedule in ``{"ring", "a2a", "ici",
    "dcn"}`` — the SAME byte totals the runtime's
    ``wire_bytes_total{dtype,tier}`` counter would accumulate for this
    dispatch (``_timeline_op`` / ``_DispatchPlan`` / the fused flush),
    split per leg so the tier classifier can price each leg's schedule
    separately. ``ici``/``dcn`` legs are tier-EXPLICIT (the hierarchical
    decomposition's local and cross legs — no fraction applied);
    ``ring``/``a2a`` legs are classified by the slice-boundary fractions.
    ``use_registry=False`` prices against the config knobs alone
    (counterfactual "as if" pricing), ignoring live registry entries.
    Returns ``[]`` for zero-byte events (barrier)."""
    if event.op == "barrier" or not event.shapes:
        return []
    dtypes = event.dtypes
    width = jaxpr_walk.dtype_width(dtypes[0]) if dtypes else 4
    if event.origin == "jit":
        p = int(event.group_size(world_size) or world_size)
        e = event.per_rank_elems()
        if event.op in ("psum", "pmin", "pmax"):
            # participants x payload x both internal legs — the global-
            # payload convention the eager allreduce accounting uses.
            return [(2 * p * e * width, "ring", str(dtypes[0]))]
        sched = "a2a" if event.op in _A2A_OPS else "ring"
        return [(p * e * width, sched, str(dtypes[0]))]
    n = int(event.group_size(world_size) or world_size)
    if event.op == "allreduce":
        flat_len = event.per_rank_elems()
        cfg_wire = getattr(config, "wire_dtype", "")
        req = _wire.wire_dtype_for(event.ps, cfg_wire) if use_registry \
            else cfg_wire
        all_float = all(_is_float_name(d) for d in dtypes)
        hier_cross = _hier_for_event(event, config, num_slices,
                                     use_registry)
        if hier_cross is not None:
            # The hierarchical dispatch tier: local RS + AG at the
            # payload dtype (explicit ici), the cross-slice allreduce at
            # the per-tier wire (explicit dcn) — the same
            # wire.hierarchical_wire_bytes integers the runtime records,
            # which is what makes cross_check_bytes exact. One fused
            # wrinkle mirrored from _fused_program's cast_wire: a 16-bit
            # cast wire applies to the EXACT-cross strategy ("torus") —
            # every leg then moves the cast dtype — while torus_qcross
            # keeps the payload dtype on its ICI legs by design.
            label, w = str(dtypes[0]), width
            if event.origin == "fused" and not hier_cross and all_float \
                    and req in ("float16", "bfloat16"):
                label, w = req, 2
            h = _wire.hierarchical_wire_bytes(flat_len, n, num_slices,
                                              w,
                                              cross_wire=hier_cross)
            return [(h["ici"], "ici", label),
                    (h["dcn"], "dcn", h["cross_label"] or label)]
        quant = _wire.quantized_label(req)
        sum_avg = event.red_op in (None, "Sum", "Average")
        if quant and _wire.quantized_eligible(flat_len, n, all_float,
                                              sum_avg):
            leg = _wire.exchange_leg_bytes(flat_len, n)
            # First leg: AllToAll of the 1-byte shards (+ scales);
            # second: AllGather of the reduced shards (+ scales).
            return [(leg, "a2a", quant), (leg, "ring", quant)]
        if event.origin == "fused" and req in ("float16", "bfloat16") \
                and all_float:
            # The fusion runtime casts float buckets to the 16-bit wire;
            # sync eager dispatches never cast (they record the payload
            # dtype), matching the runtime's accounting exactly.
            return [(2 * n * flat_len * 2, "ring", req)]
        return [(2 * event.nbytes, "ring", str(dtypes[0]))]
    if event.op == "alltoall":
        hier_cross = _a2a_hier_for_event(event, config, num_slices,
                                         use_registry)
        if hier_cross is not None:
            # The hierarchical a2a tier: slice-local exchange at the
            # payload dtype (explicit ici), cross-slice exchange on the
            # expert cross wire split by its own (S-1)/S foreign-slice
            # fraction — the same wire.hierarchical_a2a_bytes integers
            # _HierAlltoallPlan records, which is what makes
            # cross_check_bytes exact. Non-float payloads keep the cross
            # leg exact (the runtime verdict's float gate).
            all_float = all(_is_float_name(d) for d in dtypes)
            h = _wire.hierarchical_a2a_bytes(
                event.per_rank_elems(), n, num_slices, width,
                cross_wire=hier_cross if all_float else "")
            label = str(dtypes[0])
            cross_label = h["cross_label"] or label
            ct = h["cross_tiers"]
            return [(h["local"], "ici", label),
                    (ct["ici"], "ici", cross_label),
                    (ct["dcn"], "dcn", cross_label)]
    sched = "a2a" if event.op in _A2A_OPS else "ring"
    return [(event.nbytes, sched, str(dtypes[0]))]


@dataclasses.dataclass
class EventCost:
    """One predicted event priced and tier-classified."""

    op: str
    ps: str
    seq: int
    origin: str
    dtype: str          # effective wire label (the runtime counter label)
    wire_bytes: int     # one dispatch, both legs
    ici_bytes: int      # total across repeats
    dcn_bytes: int      # total across repeats
    repeat: int         # 0 = unknown trip count (totals are lower bounds)

    @property
    def total_bytes(self):
        return self.ici_bytes + self.dcn_bytes

    @property
    def exact(self):
        return self.repeat != 0

    def describe(self):
        rep = "" if self.repeat == 1 \
            else (" x? (lower bound)" if self.repeat == 0
                  else f" x{self.repeat}")
        return (f"{self.op}[{self.ps}] seq={self.seq} dtype={self.dtype} "
                f"wire={self.wire_bytes}B ici={self.ici_bytes}B "
                f"dcn={self.dcn_bytes}B{rep} ({self.origin})")


@dataclasses.dataclass
class CostReport:
    """Result of :func:`cost_report`."""

    world_size: int
    num_slices: int
    slice_size: int
    rows: list                     # [EventCost]
    bytes_by_tier: dict            # {"ici": B, "dcn": B} — all rows
    bytes_by_dtype: dict           # eager+fused rows (runtime-metered)
    jit_bytes_by_dtype: dict       # static-only jit rows
    hierarchical: dict             # 2-level what-if {"ici","dcn",...}
    time_estimate: dict            # roofline.tier_time_estimate(...)
    findings: list
    exact: bool                    # False when any repeat is unbounded
    dcn_budget_bytes: int = 0
    # Runtime-metered (eager+fused) subset of bytes_by_tier — what the
    # live wire_bytes_total{tier} counters accumulate per step; the
    # per-tier side of cross_check_bytes diffs against THIS.
    runtime_bytes_by_tier: dict = dataclasses.field(default_factory=dict)
    # Control-plane load prediction: negotiation rounds per step and the
    # per-role KV RPC counts of one round under the resolved strategy
    # (control_plane.exchange_plan — the same layout math the runtime
    # decomposes with), so the static model predicts host-side fan-out
    # alongside wire bytes.
    control_plane: dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self):
        return not any(f.severity == ERROR for f in self.findings)

    def errors(self):
        return [f for f in self.findings if f.severity == ERROR]

    def to_dict(self):
        return {
            "world_size": self.world_size,
            "num_slices": self.num_slices,
            "slice_size": self.slice_size,
            "bytes_by_tier": dict(self.bytes_by_tier),
            "runtime_bytes_by_tier": dict(self.runtime_bytes_by_tier),
            "bytes_by_dtype": dict(self.bytes_by_dtype),
            "jit_bytes_by_dtype": dict(self.jit_bytes_by_dtype),
            "hierarchical": dict(self.hierarchical),
            "time_estimate": dict(self.time_estimate),
            "control_plane": dict(self.control_plane),
            "exact": self.exact,
            "dcn_budget_bytes": self.dcn_budget_bytes,
            "rows": [dataclasses.asdict(r) for r in self.rows],
            "findings": [f.to_dict() for f in self.findings],
            "ok": self.ok,
        }

    def render(self):
        bound = "" if self.exact else " (lower bound: unbounded repeats)"
        lines = [f"hvdcost: world={self.world_size} "
                 f"slices={self.num_slices} "
                 f"(slice_size={self.slice_size})"]
        lines.append(f"  predicted wire traffic per step (rank 0), "
                     f"{len(self.rows)} event(s):")
        for r in self.rows[:32]:
            lines.append(f"    {r.describe()}")
        if len(self.rows) > 32:
            lines.append(f"    ... {len(self.rows) - 32} more")
        lines.append(f"  bytes_by_tier: ici={self.bytes_by_tier['ici']} "
                     f"dcn={self.bytes_by_tier['dcn']}{bound}")
        if self.runtime_bytes_by_tier:
            lines.append(
                "  runtime bytes_by_tier (cross-checkable vs "
                f"wire_bytes_total{{tier}}): "
                f"ici={self.runtime_bytes_by_tier.get('ici', 0)} "
                f"dcn={self.runtime_bytes_by_tier.get('dcn', 0)}")
        if self.bytes_by_dtype:
            lines.append("  bytes_by_dtype (cross-checkable vs "
                         "wire_bytes_total): "
                         + " ".join(f"{k}={v}" for k, v in
                                    sorted(self.bytes_by_dtype.items())))
        if self.jit_bytes_by_dtype:
            lines.append("  jit bytes_by_dtype (static-only estimate): "
                         + " ".join(f"{k}={v}" for k, v in
                                    sorted(self.jit_bytes_by_dtype.items())))
        if self.num_slices > 1:
            h = self.hierarchical
            lines.append(
                f"  hierarchical what-if (local RS -> cross-slice -> "
                f"local AG): ici={h['ici']} dcn={h['dcn']} "
                f"(DCN x{h['dcn_vs_flat']:.3f} of the flat schedule)")
        cp = self.control_plane
        if cp:
            per = cp.get("per_round", {})
            if cp.get("strategy") == "hier":
                detail = (f"member gets {cp['member_gets']} "
                          f"(flat would be {cp['flat_gets']}), leader "
                          f"gets {cp['leader_gets']} (local "
                          f"{per.get('leader_local_gets', 0)} + cross "
                          f"{per.get('leader_cross_gets', 0)} per round)")
            else:
                detail = f"per-rank gets {cp['member_gets']}"
            lines.append(
                f"  control plane ({cp.get('strategy')}): "
                f"{cp.get('rounds_per_step', 0)} negotiation round(s)/"
                f"step — {detail}")
        t = self.time_estimate
        if t.get("ici_s") is not None or t.get("dcn_s") is not None:
            est = " [placeholder peaks]" if t.get("estimate") else ""
            lines.append(
                "  roofline lower bound: "
                f"ici {1e6 * (t.get('ici_s') or 0.0):.1f}us, "
                f"dcn {1e6 * (t.get('dcn_s') or 0.0):.1f}us -> "
                f"{t.get('bound')}-bound (chip={t.get('chip')}){est}")
        if self.dcn_budget_bytes:
            verdict = "EXCEEDED" \
                if self.bytes_by_tier["dcn"] > self.dcn_budget_bytes \
                else "OK"
            lines.append(f"  dcn budget: {self.bytes_by_tier['dcn']} B "
                         f"vs {self.dcn_budget_bytes} B -> {verdict}")
        if not self.findings:
            lines.append("  findings: none")
        else:
            lines.append(f"  findings: {len(self.findings)}")
            for f in sort_findings(self.findings):
                lines.append(f"    {f.render()}")
        return "\n".join(lines)


def cost_report(report, *, config=None, num_slices=None,
                dcn_budget_bytes=None, use_registry=True):
    """Price a :class:`~horovod_tpu.analysis.program.CheckReport`'s
    predicted collective stream per link tier. ``num_slices`` defaults to
    the live topology / forced ``HOROVOD_MESH_SLICES`` hierarchy;
    ``dcn_budget_bytes`` defaults to the ``HOROVOD_DCN_BYTES_BUDGET``
    knob (0 = no budget); ``use_registry=False`` prices against
    ``config.wire_dtype`` alone, ignoring live per-process-set wire pins
    (counterfactual what-if pricing — the bench's ``static_cost`` record
    uses it). Returns a :class:`CostReport` whose per-dtype totals use
    the runtime's own wire-byte formulas, so :func:`cross_check_bytes`
    can diff them against the real ``wire_bytes_total{dtype}``
    counters."""
    if config is None:
        from horovod_tpu.common import basics
        from horovod_tpu.common.config import Config
        try:
            config = basics.config()
        except Exception:  # noqa: BLE001 — uninitialized analysis is fine
            config = Config()
    world = report.world_size
    n_slices, slice_size = resolve_slices(world, num_slices)
    if dcn_budget_bytes is None:
        dcn_budget_bytes = int(getattr(config, "dcn_bytes_budget", 0) or 0)
    events = report.sequences[report.ranks[0]]
    rows = []
    tier = {"ici": 0, "dcn": 0}
    runtime_tier = {"ici": 0, "dcn": 0}
    by_dtype, jit_by_dtype = {}, {}
    hier = {"ici": 0, "dcn": 0}
    findings = []
    seen_unbounded = set()
    for e in events:
        legs = _event_legs(e, world, config, use_registry, n_slices)
        if not legs:
            continue
        members = _member_ranks(e, world, n_slices, slice_size)
        ring_f = _ring_dcn_fraction(members, slice_size) \
            if n_slices > 1 else 0.0
        a2a_f = _a2a_dcn_fraction(members, slice_size) \
            if n_slices > 1 else 0.0
        occurrences = max(e.repeat, 1)
        wire_bytes = sum(b for b, _, _ in legs)
        ici = dcn = 0
        for leg_bytes, sched, leg_dtype in legs:
            # Tier-explicit legs (the hierarchical decomposition) book
            # whole; ring/a2a legs split on the slice-boundary fraction.
            frac = {"ici": 0.0, "dcn": 1.0}.get(
                sched, a2a_f if sched == "a2a" else ring_f)
            leg_total = leg_bytes * occurrences
            leg_dcn = int(round(leg_total * frac))
            dcn += leg_dcn
            ici += leg_total - leg_dcn
            target = jit_by_dtype if e.origin == "jit" else by_dtype
            target[leg_dtype] = target.get(leg_dtype, 0) + leg_total
        label = "+".join(dict.fromkeys(d for _, _, d in legs))
        rows.append(EventCost(
            op=e.op, ps=e.ps, seq=e.seq, origin=e.origin, dtype=label,
            wire_bytes=wire_bytes, ici_bytes=ici, dcn_bytes=dcn,
            repeat=e.repeat))
        tier["ici"] += ici
        tier["dcn"] += dcn
        if e.origin != "jit":
            # The runtime-metered subset: what wire_bytes_total{tier}
            # accumulates per step (jit rows record at trace time only).
            runtime_tier["ici"] += ici
            runtime_tier["dcn"] += dcn
        # 2-level what-if: an allreduce over a multi-slice group priced
        # AS IF dispatched hierarchically — local RS + local AG on ICI
        # (the full flat volume), only the slice-reduced shards (on the
        # per-tier cross wire) over DCN: flat total divided by the slice
        # width, via the SAME wire.hierarchical_wire_bytes integers the
        # runtime records, so the what-if is exactly the counters the
        # hierarchical dispatch tier produces. Non-allreduce exchanges
        # keep their flat split (their hierarchical decompositions are
        # workload-specific).
        slices_spanned = len({slice_of_rank(r, slice_size)
                              for r in members}) if members else 1
        if e.op in ("allreduce", "psum") and slices_spanned > 1:
            width = jaxpr_walk.dtype_width(e.dtypes[0]) if e.dtypes else 4
            cross = ""
            if e.origin != "jit":
                cross = _wire.cross_wire_for(e.ps, config) if use_registry \
                    else (getattr(config, "wire_dtype_dcn", "")
                          or getattr(config, "wire_dtype", ""))
            hh = _wire.hierarchical_wire_bytes(
                e.per_rank_elems(), len(members), slices_spanned, width,
                cross_wire=cross)
            hier["ici"] += hh["ici"] * occurrences
            hier["dcn"] += hh["dcn"] * occurrences
        elif e.op == "alltoall" and e.origin != "jit" \
                and slices_spanned > 1:
            # The a2a twin: an eager alltoall priced AS IF dispatched
            # hierarchically (slice-local leg all-ICI, cross leg on the
            # expert cross wire split (S-1)/S) — the same
            # wire.hierarchical_a2a_bytes integers _HierAlltoallPlan
            # records, so when the tier is armed the what-if IS the
            # as-dispatched prediction. Cross dtype resolves through the
            # a2a chain only (alltoall_cross_dtype — activations never
            # inherit the allreduce wire knobs).
            width = jaxpr_walk.dtype_width(e.dtypes[0]) if e.dtypes else 4
            all_float = all(_is_float_name(d) for d in e.dtypes)
            cross = ""
            if all_float:
                cross = _wire.alltoall_cross_wire_for(e.ps, config) \
                    if use_registry else getattr(
                        config, "alltoall_cross_dtype", "")
            hh = _wire.hierarchical_a2a_bytes(
                e.per_rank_elems(), len(members), slices_spanned, width,
                cross_wire=cross)
            hier["ici"] += hh["ici"] * occurrences
            hier["dcn"] += hh["dcn"] * occurrences
        else:
            hier["ici"] += ici
            hier["dcn"] += dcn
        if e.repeat == 0 and (e.op, e.ps) not in seen_unbounded:
            seen_unbounded.add((e.op, e.ps))
            findings.append(Finding(
                code="HVP112", severity=INFO,
                message=(f"{e.op} on {e.ps} sits under a while loop with "
                         "no static trip count — its bytes are counted "
                         "ONCE, so per-tier totals are lower bounds, not "
                         "exact"),
                op=e.op, ps=e.ps, seq=e.seq))
    exact = not seen_unbounded
    hier["dcn_vs_flat"] = (hier["dcn"] / tier["dcn"]) if tier["dcn"] else 1.0
    if dcn_budget_bytes and tier["dcn"] > dcn_budget_bytes:
        findings.append(Finding(
            code="HVP111", severity=ERROR,
            message=(f"tier budget exceeded: predicted per-step DCN "
                     f"traffic {tier['dcn']} B > declared budget "
                     f"{dcn_budget_bytes} B (HOROVOD_DCN_BYTES_BUDGET) — "
                     "quantize the cross-slice leg (wire tier), shrink "
                     "the payload, or raise the budget"
                     + ("" if exact else "; note the prediction is "
                        "itself a LOWER bound (unbounded repeats)")),
            ps="dcn"))
    from horovod_tpu.profile import roofline
    t = roofline.tier_time_estimate(tier, world, n_slices)
    return CostReport(
        world_size=world, num_slices=n_slices, slice_size=slice_size,
        rows=rows, bytes_by_tier=tier, bytes_by_dtype=by_dtype,
        jit_bytes_by_dtype=jit_by_dtype, hierarchical=hier,
        time_estimate=t, findings=sort_findings(findings), exact=exact,
        dcn_budget_bytes=dcn_budget_bytes,
        runtime_bytes_by_tier=runtime_tier,
        control_plane=_control_plane_cost(events, world, n_slices,
                                          config))


def _control_plane_cost(events, world, num_slices, config):
    """Predicted control-plane load of one step: negotiation rounds (the
    dynamic-shape exchanges — ragged allgather / uneven alltoall — plus
    the per-dispatch join/order-check rounds when those modes are armed)
    priced with :func:`control_plane.exchange_plan` under the resolved
    strategy. Each rank is priced as one process — the launcher's
    worst-case (1 chip per process) and exactly the CPU-tier test
    layout, so the guard's measured counters are directly comparable."""
    from horovod_tpu.common import control_plane as _cp
    negotiated = sum(max(e.repeat, 1) for e in events
                     if e.origin != "jit"
                     and e.op in ("alltoall", "allgather_ragged"))
    extra = (1 if getattr(config, "join_mode", False) else 0) \
        + (1 if getattr(config, "order_check", False) else 0)
    if extra:
        negotiated += extra * sum(
            max(e.repeat, 1) for e in events if e.origin != "jit")
    strategy = "flat" if _cp.configured() == "flat" or num_slices <= 1 \
        else "hier"
    plan = _cp.exchange_plan(world, num_slices if strategy == "hier"
                             else 1)
    return {
        "strategy": plan["strategy"], "rounds_per_step": negotiated,
        "per_round": plan,
        # Per-step blocking gets by role, vs the flat fan-out — the
        # O(slice_size + num_slices) vs O(world) claim as numbers.
        "member_gets": negotiated * plan["member_gets"],
        "leader_gets": negotiated * plan["leader_gets"],
        "flat_gets": negotiated * (world - 1),
    }


def collective_step_tiers(per_rank_elems, world, num_slices, *,
                          strategy="flat", width=4, cross_wire=""):
    """Per-tier wire bytes of ONE allreduce of ``per_rank_elems``
    elements under ``strategy`` — the callable per-event pricing seam
    the scale digital twin's step model uses (:mod:`horovod_tpu.sim.
    autopilot`): same ``wire.hierarchical_wire_bytes`` /
    ``ring_dcn_fraction`` integers :func:`cost_report` books, so the
    twin and the static model can never disagree on a step's bytes.

    Returns ``{"ici": int, "dcn": int}``. Hierarchical strategies book
    tier-explicit legs (local RS+AG on ICI, slice-reduced shards on the
    cross wire over DCN; ``torus_qcross`` forces the int8 cross leg);
    flat books the full ring volume split on the slice-boundary
    fraction of a rank-major contiguous group."""
    e = max(int(per_rank_elems), 0)
    n = max(int(world), 1)
    k, slice_size = resolve_slices(n, num_slices)
    if strategy in ("hierarchical", "torus", "torus_qcross") and k > 1:
        h = _wire.hierarchical_wire_bytes(
            e, n, k, width,
            cross_wire=("int8" if strategy == "torus_qcross"
                        else cross_wire or ""))
        return {"ici": int(h["ici"]), "dcn": int(h["dcn"])}
    total = 2 * n * e * width
    frac = _ring_dcn_fraction(list(range(n)), slice_size) if k > 1 else 0.0
    dcn = int(round(total * frac))
    return {"ici": total - dcn, "dcn": dcn}


def check_cost(step_fn, args=(), kwargs=None, *, world_size=None,
               num_slices=None, config=None, dcn_budget_bytes=None,
               **check_kwargs):
    """Convenience: :func:`check_program` + :func:`cost_report` in one
    call. Returns ``(check_report, cost_report)``."""
    from horovod_tpu.analysis.program import check_program
    rep = check_program(step_fn, args, kwargs, world_size=world_size,
                        config=config, **check_kwargs)
    return rep, cost_report(rep, config=config, num_slices=num_slices,
                            dcn_budget_bytes=dcn_budget_bytes)


def _measured_wire_bytes(snapshot):
    """``dtype -> value`` from a metrics snapshot's ``wire_bytes_total``
    family (``hvd.metrics_snapshot()`` shape), summed across the tier
    label (the counter is ``{dtype, tier}`` since the hierarchical
    dispatch tier split it)."""
    out = {}
    fam = (snapshot or {}).get("wire_bytes_total") or {}
    for s in fam.get("series", ()):
        dtype = str(s.get("labels", {}).get("dtype"))
        out[dtype] = out.get(dtype, 0.0) + float(s.get("value", 0.0))
    return out


def _measured_tier_bytes(snapshot):
    """``tier -> value`` from ``wire_bytes_total{dtype,tier}``, summed
    across dtypes."""
    out = {}
    fam = (snapshot or {}).get("wire_bytes_total") or {}
    for s in fam.get("series", ()):
        t = s.get("labels", {}).get("tier")
        if t:
            out[str(t)] = out.get(str(t), 0.0) + float(s.get("value",
                                                             0.0))
    return out


def cross_check_bytes(cost, metrics_snapshot, baseline_snapshot=None,
                      rel_tol=0.05, steps=1):
    """Diff the static per-dtype wire prediction against the runtime's
    ``wire_bytes_total{dtype}`` counters in one call.

    ``cost`` is a :class:`CostReport` (or a
    :class:`~horovod_tpu.analysis.program.CheckReport`, priced with
    defaults); ``metrics_snapshot`` an ``hvd.metrics_snapshot()`` taken
    after the measured window, ``baseline_snapshot`` one taken before it
    (so compile-time jit accounting and earlier traffic subtract out);
    ``steps`` divides the measured deltas when the window ran the step
    more than once. Returns ``{"match", "rel_tol", "per_dtype": {dtype:
    {"predicted", "measured", "delta", "within"}}, "per_tier": {tier:
    ...}, "unpredicted"}`` — ``match`` is True when every predicted
    dtype AND tier lands within ``rel_tol`` and the prediction is exact
    (no unbounded repeats). The per-tier side diffs the runtime-metered
    prediction (``runtime_bytes_by_tier`` — under hierarchical dispatch
    that IS the hierarchical what-if, leg for leg) against the
    ``wire_bytes_total{tier}`` counters: delta 0 on the CPU tier."""
    if not isinstance(cost, CostReport):
        cost = cost_report(cost)
    measured = _measured_wire_bytes(metrics_snapshot)
    measured_tier = _measured_tier_bytes(metrics_snapshot)
    if baseline_snapshot is not None:
        base = _measured_wire_bytes(baseline_snapshot)
        measured = {k: v - base.get(k, 0.0) for k, v in measured.items()}
        base_t = _measured_tier_bytes(baseline_snapshot)
        measured_tier = {k: v - base_t.get(k, 0.0)
                         for k, v in measured_tier.items()}
    steps = max(int(steps), 1)
    measured = {k: v / steps for k, v in measured.items()}
    measured_tier = {k: v / steps for k, v in measured_tier.items()}
    per_dtype = {}
    ok = cost.exact
    for dtype, predicted in sorted(cost.bytes_by_dtype.items()):
        got = measured.get(dtype, 0.0)
        delta = got - predicted
        within = abs(delta) <= rel_tol * max(predicted, 1.0)
        per_dtype[dtype] = {"predicted": predicted, "measured": got,
                            "delta": delta, "within": within}
        ok = ok and within
    # The per-tier diff gates `match` only when the LIVE slice layout is
    # the one the report priced: a counterfactual what-if (e.g. priced at
    # num_slices=2 against a 1-slice run) keeps its per-dtype gate but
    # reports the tier rows informationally.
    try:
        from horovod_tpu.ops.collective_ops import _live_slices
        tier_gates = _live_slices(cost.world_size)[0] == cost.num_slices
    except Exception:  # noqa: BLE001 — uninitialized: dtype gate only
        tier_gates = False
    per_tier = {}
    for t, predicted in sorted(cost.runtime_bytes_by_tier.items()):
        got = measured_tier.get(t, 0.0)
        delta = got - predicted
        within = abs(delta) <= rel_tol * max(predicted, 1.0)
        per_tier[t] = {"predicted": predicted, "measured": got,
                       "delta": delta, "within": within,
                       "gates_match": tier_gates}
        if tier_gates:
            ok = ok and within
    unpredicted = {k: v for k, v in measured.items()
                   if k not in cost.bytes_by_dtype and v > 0}
    return {"match": ok, "rel_tol": rel_tol, "per_dtype": per_dtype,
            "per_tier": per_tier, "unpredicted": unpredicted}


# ----------------------------------------------------------------------------
# CLI: the CI gate. `python -m horovod_tpu.analysis.cost` prices a step
# program (the built-in representative fused+quantized step by default, or
# a user factory via --spec) and exits 1 on any error-severity finding —
# lint-style, wired behind scripts/lint.py --cost and the tier-1
# TestCostCLI gate.
# ----------------------------------------------------------------------------

def _representative_step(world, payload_elems):
    """The built-in CLI subject: one quantized-eligible fused-size
    allreduce, one tiny fp32 allreduce, one allgather, one barrier — the
    shape of a real training step's collective tail."""
    import numpy as np

    import horovod_tpu as hvd

    def step(grads, stats, metrics):
        g = hvd.allreduce(grads, op=hvd.Sum, name="grads")
        s = hvd.allreduce(stats, name="stats")
        gathered = hvd.allgather(metrics, name="metrics")
        hvd.barrier()
        return g, s, gathered

    grads = np.zeros((world, int(payload_elems)), np.float32)
    stats = np.zeros((world, 8), np.float32)
    metrics = np.zeros((world, 8), np.float32)
    return step, (grads, stats, metrics)


def _load_spec(spec):
    """``module:attr`` -> ``(step_fn, args[, kwargs])`` from a factory
    callable (called with no arguments)."""
    import importlib
    mod_name, _, attr = spec.partition(":")
    if not attr:
        raise ValueError(f"--spec {spec!r}: expected module:callable")
    factory = getattr(importlib.import_module(mod_name), attr)
    built = factory()
    if not isinstance(built, tuple) or len(built) < 2:
        raise ValueError(f"--spec {spec!r}: factory must return "
                         "(step_fn, args[, kwargs])")
    step_fn, args = built[0], built[1]
    kwargs = built[2] if len(built) > 2 else None
    return step_fn, args, kwargs


def main(argv=None):
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.analysis.cost",
        description="Static per-link-tier communication cost model + "
                    "elastic world-transition checker "
                    "(docs/static_analysis.md).")
    p.add_argument("--world", type=int, default=8,
                   help="simulated world size (default 8)")
    p.add_argument("--slices", type=int, default=0,
                   help="slice count for the tier split (default: live "
                        "topology / HOROVOD_MESH_SLICES / 1)")
    p.add_argument("--wire", default=None,
                   help="wire dtype to price (int8/fp8/bfloat16/float16; "
                        "default: HOROVOD_WIRE_DTYPE)")
    p.add_argument("--dcn-budget", type=int, default=None,
                   help="per-step DCN byte budget (HVP111; default: "
                        "HOROVOD_DCN_BYTES_BUDGET)")
    p.add_argument("--payload-kb", type=int, default=4096,
                   help="per-rank payload of the built-in representative "
                        "step, in KiB (default 4096)")
    p.add_argument("--elastic", default=None, metavar="W1,W2,...",
                   help="also model-check the step across this resize "
                        "ladder (e.g. 8,7,4,8) — HVP110 on any "
                        "world-dependent stream property")
    p.add_argument("--spec", default=None, metavar="MODULE:CALLABLE",
                   help="factory returning (step_fn, args[, kwargs]) to "
                        "analyze instead of the built-in step")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    args = p.parse_args(argv)

    from horovod_tpu.analysis.program import check_elastic, check_program
    from horovod_tpu.common.config import Config

    config = Config.from_env()
    if args.wire is not None:
        config.wire_dtype = args.wire
        config.__post_init__()
    if args.spec:
        step_fn, step_args, step_kwargs = _load_spec(args.spec)
    else:
        step_fn, step_args = _representative_step(
            args.world, args.payload_kb * 1024 // 4)
        step_kwargs = None
    rep = check_program(step_fn, step_args, step_kwargs,
                        world_size=args.world, config=config)
    cost = cost_report(rep, config=config,
                       num_slices=args.slices or None,
                       dcn_budget_bytes=args.dcn_budget)
    elastic = None
    if args.elastic:
        ladder = tuple(int(w) for w in args.elastic.split(","))

        def ladder_args(w):
            if args.spec:
                return step_args            # spec inputs are fixed
            return _representative_step(w, args.payload_kb * 1024 // 4)[1]

        elastic = check_elastic(step_fn, step_args, step_kwargs,
                                worlds=ladder, args_for=ladder_args,
                                config=config)
    failed = (not rep.ok) or (not cost.ok) \
        or (elastic is not None and not elastic.ok)
    if args.json:
        out = {"check": {"ok": rep.ok,
                         "findings": [f.to_dict() for f in rep.findings]},
               "cost": cost.to_dict()}
        if elastic is not None:
            out["elastic"] = {
                "ok": elastic.ok, "worlds": list(elastic.worlds),
                "findings": [f.to_dict() for f in elastic.findings]}
        print(json.dumps(out, indent=2, default=str))
    else:
        print(rep.render())
        print(cost.render())
        if elastic is not None:
            print(elastic.render())
        print("hvdcost: " + ("FAILED" if failed else "OK"))
    return 1 if failed else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())

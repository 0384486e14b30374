"""The framework's series catalogue + recording helpers.

Every instrumentation point in the stack goes through one of the
``record_*`` functions here, so the catalogue below is the single source
of truth for series names, labels and units (documented in
docs/observability.md). All helpers are no-ops when metrics are disabled
(``HOROVOD_METRICS=0``) and never raise into the hot path.

Catalogue (names shown without the ``HOROVOD_METRICS_PREFIX``, default
``horovod``):

- ``collective_ops_total{op,process_set}``          dispatches (counter)
- ``collective_bytes_total{op,process_set}``        payload bytes (counter)
- ``collective_latency_seconds{op}``                dispatch latency (histogram)
- ``collective_errors_total{op}``                   failed dispatches (counter)
- ``fusion_flushes_total``                          bucket flushes (counter)
- ``fusion_flush_tensors``                          tensors per flush (histogram)
- ``fusion_flush_bytes``                            bytes per flush (histogram)
- ``fusion_fill_ratio``                             flushed/threshold (histogram)
- ``fusion_boundary_outcomes_total{outcome}``       applied|deferred (counter)
- ``fusion_kv_rpcs_total{kind}``                    boundary KV set/get (counter)
- ``dispatch_plan_events_total{event}``             plan cache hit|miss (counter)
- ``compile_cache_events_total{event}``             XLA persistent-cache
  request|hit (counter; armed by ``hvd.init()``)
- ``negotiation_rounds_total``                      exchange() rounds (counter)
- ``control_plane_rpcs_total{transport,kind}``      every KV RPC (counter)
- ``control_plane_payload_bytes_total{transport}``  KV payload bytes (counter)
- ``elastic_events_total{event}``                   rendezvous/reset/... (counter)
- ``elastic_recovery_seconds{cause}``               failure detection →
  training re-entry (histogram; cause=failure|host_update)
- ``stall_events_total{kind}``                      warning|shutdown (counter)
- ``kv_client_retries_total``                       HTTP-KV client retries (counter)
- ``chaos_injections_total{site,kind}``             chaos faults fired (counter)
- ``step_time_seconds``                             step wall time from the
  step profiler's marker-to-marker windows (histogram)
- ``step_profiler_events_total{kind}``              watchdog findings:
  straggler|regression (counter; horovod_tpu/profile)
- ``wire_bytes_total{dtype,tier}``                  estimated bytes on the
  wire per collective at the effective wire dtype, split per link tier
  (tier=ici|dcn — ops/wire.py accounting; allreduces count both RS+AG
  legs; the flat default split books the ring/a2a slice-boundary
  fraction to dcn, hierarchical dispatches book each leg's tier exactly)
- ``wire_compression_events_total{path,dtype}``     dispatches that
  actually compressed the wire (path=eager|fused|jit; counter)
- ``serving_requests_total{event}``                 request lifecycle
  (event=submitted|admitted|completed|requeued|rejected; counter)
- ``serving_ttft_seconds``                          submit → first
  generated token (histogram; the SLO p50/p99 source)
- ``serving_token_latency_seconds``                 decode-step wall time
  = inter-token latency for every active request (histogram)
- ``serving_tokens_total``                          generated tokens
  (counter; rate() = tokens/sec)
- ``serving_queue_depth``                           admission queue depth
  (gauge)
- ``serving_batch_fill_ratio``                      active slots / total
  slots per decode step (histogram; low values mean the fleet is
  over-provisioned or admission is starved)
- ``slo_burn_rate{objective}``                      rolling-window SLO
  error-budget burn (objective=ttft_p99|tps; gauge — 0 inside the SLO,
  1.0 = consuming the budget exactly, > 1 = burning; computed by
  horovod_tpu/telemetry/slo.py from the declared
  HOROVOD_SLO_TTFT_P99_MS / HOROVOD_SLO_TPS objectives)
- ``hvd_fused_allreduce_buckets{axis_size}``        collectives one trace
  of the in-jit ``fused_allreduce_tree`` issues (buckets plus leaves
  reduced alone; gauge, set while the step is traced, not per step)
- ``hvd_fused_allreduce_bytes{axis_size}``          bytes those carry a
  step, padding included (gauge, as above)
- ``hvd_flash_tiles{kernel,kind}``                  score tiles per
  (batch, head) of the last traced flash-attention call
  (kernel=fwd|bwd_dqkv|bwd_dq|bwd_dkv: the backward is the one kernel
  bwd_dqkv, or the pair bwd_dq + bwd_dkv for a call past its dQ's VMEM
  budget; kind=total|visited|masked, masked = visited with mask code,
  and blocks_inside|blocks_diagonal|blocks_edge|
  blocks_skipped: the 1024 x 1024 blocks of each kind where a long causal
  call runs its static schedule by block kind, 0 elsewhere; gauge, set
  while the call is traced)
- ``hvd_moe_experts{kind}``                         experts of the last
  traced ``parallel.moe.DroplessMoE`` call (kind=routed|held|per_token:
  the router's width, the experts this layer holds, the experts a token
  is routed to; gauge, set while the call is traced)
- ``hvd_moe_buffer_rows_per_token{axis_size}``      rows the buffer of
  that call carries, per token of the call: the buffer sized for the rows
  expected (``parallel.moe.buffer_rows``), not the top_k rows a token of
  the overflow path (gauge, as above; axis_size = chips the experts are
  exchanged over, 1 without an exchange)
- ``hvd_moe_overflow_calls{axis_size}``             DroplessMoE calls whose
  live rows outnumbered that buffer and ran over all top_k rows a token.
  Set to 0 when a layer with such an overflow path is traced and NOT
  raised from the device: the branch is chosen there, and a host callback
  in it keeps the whole step out of the persistent compile cache (gauge;
  absent where no traced layer has the path)
- ``hvd_moe_product_path{product}``                 how the last traced
  DroplessMoE call runs each of its two grouped products (product=in|down):
  1 = the repo's kernels (``ops/pallas/grouped_matmul.py``), 0 =
  ``lax.ragged_dot`` as the compiler tiles it, read off the shapes
  (``parallel.moe.product_tiles``; gauge, set while the call is traced)
- ``hvd_moe_product_tiles{product,dim}``            the tile of that
  product in rows, contraction and output columns (dim=m|k|n; gauge, as
  above)
- ``autopilot_decisions_total{lever,outcome}``      autopilot control
  decisions (lever=tuner|overlap|cross_wire|remediate; counter)
- ``autopilot_remediations_total{cause,outcome}``   autopilot-initiated
  removals (cause=dead|stalled|straggler; outcome=requested|applied|
  rejected_*; counter)
"""

import os
import threading
import time

from horovod_tpu.flight import recorder as _flight
from horovod_tpu.metrics.registry import MetricsRegistry, exponential_buckets

_enabled = os.environ.get("HOROVOD_METRICS", "1").lower() \
    not in ("0", "false", "no", "off")

REGISTRY = MetricsRegistry(
    prefix=os.environ.get("HOROVOD_METRICS_PREFIX", "horovod"))


def get_registry():
    return REGISTRY


def enabled():
    return _enabled


def set_enabled(value):
    global _enabled
    _enabled = bool(value)


def set_prefix(prefix):
    REGISTRY.prefix = prefix


# --- the catalogue (created eagerly so HELP/TYPE lines are always part of
# the exposition, observed or not) --------------------------------------

_LAT_BUCKETS = exponential_buckets(1e-5, 2.0, 22)          # 10us .. ~21s
_BYTE_BUCKETS = exponential_buckets(1024, 4.0, 14)         # 1KiB .. 64GiB
_COUNT_BUCKETS = exponential_buckets(1, 2.0, 13)           # 1 .. 4096
_RATIO_BUCKETS = exponential_buckets(1.0 / 64, 2.0, 9)     # ~0.016 .. 4

COLLECTIVE_OPS = REGISTRY.counter(
    "collective_ops_total",
    "Eager collective dispatches (sync ops and fused async flush buckets).",
    ("op", "process_set"))
COLLECTIVE_BYTES = REGISTRY.counter(
    "collective_bytes_total",
    "Bytes moved by eager collectives (global rank-major stacked layout).",
    ("op", "process_set"))
COLLECTIVE_LATENCY = REGISTRY.histogram(
    "collective_latency_seconds",
    "Host-side dispatch latency of eager collectives (enqueue to program "
    "return; device execution is async beyond it).",
    ("op",), buckets=_LAT_BUCKETS)
COLLECTIVE_ERRORS = REGISTRY.counter(
    "collective_errors_total",
    "Eager collective dispatches that raised.",
    ("op",))
FUSION_FLUSHES = REGISTRY.counter(
    "fusion_flushes_total",
    "Fusion-runtime bucket flushes dispatched by this process.")
FUSION_FLUSH_TENSORS = REGISTRY.histogram(
    "fusion_flush_tensors",
    "Tensors per fusion flush (bucket size).",
    buckets=_COUNT_BUCKETS)
FUSION_FLUSH_BYTES = REGISTRY.histogram(
    "fusion_flush_bytes",
    "Bytes per fusion flush.",
    buckets=_BYTE_BUCKETS)
FUSION_FILL_RATIO = REGISTRY.histogram(
    "fusion_fill_ratio",
    "Flushed bytes / fusion threshold (1.0 = a full bucket; small values "
    "mean cycle/explicit flushes dominate threshold flushes).",
    buckets=_RATIO_BUCKETS)
FUSION_BOUNDARY_OUTCOMES = REGISTRY.counter(
    "fusion_boundary_outcomes_total",
    "Follower handling of coordinator flush boundaries: applied "
    "immediately vs deferred (boundary ahead of the local enqueue stream).",
    ("outcome",))
FUSION_KV_RPCS = REGISTRY.counter(
    "fusion_kv_rpcs_total",
    "Coordination-service KV RPCs issued by the fusion boundary "
    "publish/consume path (the ADVICE.md hot-poll class shows up here).",
    ("kind",))
DISPATCH_PLAN_EVENTS = REGISTRY.counter(
    "dispatch_plan_events_total",
    "Eager dispatch-plan cache outcomes (event=hit|miss|invalidate). A "
    "steady-state training loop is all hits; misses mean new signatures "
    "(or churn past the plan-cache cap).",
    ("event",))
COMPILE_CACHE_EVENTS = REGISTRY.counter(
    "compile_cache_events_total",
    "JAX persistent-compilation-cache outcomes (event=request|hit). "
    "Armed by hvd.init(); "
    "request-minus-hit is the fresh-XLA-compile count.",
    ("event",))
NEGOTIATION_ROUNDS = REGISTRY.counter(
    "negotiation_rounds_total",
    "Host-side negotiation.exchange() rounds (dynamic-shape collectives, "
    "join mode, order checks).")
CONTROL_PLANE_RPCS = REGISTRY.counter(
    "control_plane_rpcs_total",
    "Control-plane KV RPCs by transport (coord = jax.distributed "
    "coordination service, http = runner HTTP KV store) and verb.",
    ("transport", "kind"))
CONTROL_PLANE_PAYLOAD = REGISTRY.counter(
    "control_plane_payload_bytes_total",
    "Serialized payload bytes written to the control plane.",
    ("transport",))
ELASTIC_EVENTS = REGISTRY.counter(
    "elastic_events_total",
    "Elastic lifecycle events: rank_ready, rendezvous, reset, restore, "
    "host_update, sync, abort (watchdog severed in-flight collectives).",
    ("event",))
ELASTIC_RECOVERY = REGISTRY.histogram(
    "elastic_recovery_seconds",
    "Elastic recovery latency: failure detection (HorovodInternalError / "
    "HostsUpdatedInterrupt caught by the @elastic.run wrapper) to re-entry "
    "into the training function at the new membership "
    "(cause=failure|host_update).",
    ("cause",), buckets=exponential_buckets(0.01, 2.0, 16))  # 10ms..~5min
STALL_EVENTS = REGISTRY.counter(
    "stall_events_total",
    "Stall-inspector findings (kind=warning|shutdown).",
    ("kind",))
GOODPUT_SECONDS = REGISTRY.counter(
    "goodput_seconds_total",
    "Wall-clock seconds decomposed by the goodput ledger "
    "(horovod_tpu/goodput): category=productive_compute plus the named "
    "badput categories (init_compile, rendezvous_recovery, "
    "checkpoint_commit, straggler_wait, cross_wait_comm, autopilot_trial, "
    "wedge_idle). Conservation contract: the categories sum to the "
    "rank's measured wall time within 1%, so "
    "rate(goodput_seconds_total{category='productive_compute'}) over the "
    "sum of all categories IS the job's goodput ratio.",
    ("category",))
KV_CLIENT_RETRIES = REGISTRY.counter(
    "kv_client_retries_total",
    "Runner HTTP-KV client attempts that failed transiently and were "
    "retried (bounded, jittered exponential backoff — HOROVOD_KV_RETRIES).")
CHAOS_INJECTIONS = REGISTRY.counter(
    "chaos_injections_total",
    "Faults fired by the chaos injection runtime (horovod_tpu/chaos; "
    "always zero unless a HOROVOD_CHAOS_PLAN is armed).",
    ("site", "kind"))
STEP_TIME = REGISTRY.histogram(
    "step_time_seconds",
    "Training-step wall time measured by the step profiler's "
    "marker-to-marker windows (hvd.step_marker / optimizer wrapper / "
    "elastic State.commit).",
    buckets=exponential_buckets(1e-4, 2.0, 22))        # 100us .. ~3.5min
STEP_PROFILER_EVENTS = REGISTRY.counter(
    "step_profiler_events_total",
    "Online watchdog findings from the step profiler "
    "(kind=straggler|regression; horovod_tpu/profile/watchdog.py).",
    ("kind",))
WIRE_BYTES = REGISTRY.counter(
    "wire_bytes_total",
    "Estimated bytes-on-wire per collective at the effective wire dtype, "
    "split per link tier (tier=ici|dcn). ops/wire.py accounting: "
    "allreduce counts both internal legs — reduce-scatter + all-gather — "
    "at the wire width; quantized wires count both 1-byte legs plus fp32 "
    "block scales and padding. Flat dispatches book the slice-boundary "
    "ring/a2a fraction of their bytes to dcn (the static cost model's "
    "tier-split rule, shared via wire.ring_dcn_fraction); hierarchical "
    "dispatches book each decomposed leg's tier exactly. The "
    "int8-vs-float32 ratio here is the provable off-chip savings; the "
    "dcn-vs-flat ratio is the hierarchical tier's.",
    ("dtype", "tier"))
WIRE_COMPRESSION_EVENTS = REGISTRY.counter(
    "wire_compression_events_total",
    "Collective dispatches whose wire was actually compressed "
    "(path=eager|fused|jit, dtype=int8|fp8|float16|bfloat16). jit-path "
    "events are recorded at trace time: once per compiled program, not "
    "per execution.",
    ("path", "dtype"))
SERVING_REQUESTS = REGISTRY.counter(
    "serving_requests_total",
    "Serving-engine request lifecycle events (horovod_tpu/serving): "
    "submitted|admitted|completed|requeued (re-queued from the last "
    "committed token after an elastic disruption)|rejected (queue full).",
    ("event",))
SERVING_TTFT = REGISTRY.histogram(
    "serving_ttft_seconds",
    "Time-to-first-token per request: submit() to the first generated "
    "token's commit (includes queue wait + prefill — the user-facing "
    "p50/p99 SLO).",
    buckets=exponential_buckets(1e-4, 2.0, 22))        # 100us .. ~3.5min
SERVING_TOKEN_LATENCY = REGISTRY.histogram(
    "serving_token_latency_seconds",
    "Decode-step wall time — the inter-token latency every active "
    "request observed on that step (admission/prefill excluded; they "
    "land in serving_ttft_seconds).",
    buckets=exponential_buckets(1e-5, 2.0, 22))        # 10us .. ~21s
SERVING_TOKENS = REGISTRY.counter(
    "serving_tokens_total",
    "Generated tokens committed by the serving engine (rate() is the "
    "fleet tokens/sec).")
SERVING_QUEUE_DEPTH = REGISTRY.gauge(
    "serving_queue_depth",
    "Requests waiting for a slot in the serving admission queue "
    "(sampled at every submit/admit; the first thing to read when "
    "requests time out — docs/troubleshooting.md).")
SLO_BURN = REGISTRY.gauge(
    "slo_burn_rate",
    "Rolling-window SLO error-budget burn per declared objective "
    "(objective=ttft_p99|tps; horovod_tpu/telemetry/slo.py). 0 = inside "
    "the SLO, 1.0 = consuming the error budget exactly, > 1 = burning "
    "it — the admission/scale-up signal the autopilot SignalFrame and "
    "`telemetry top --serving` read.",
    ("objective",))
SERVING_FILL = REGISTRY.histogram(
    "serving_batch_fill_ratio",
    "Active slots / total slots at each decode step (1.0 = the "
    "continuous batch is full; persistently low fill under a deep queue "
    "means admission is starved — a scheduler bug).",
    buckets=_RATIO_BUCKETS)
FUSED_ALLREDUCE_BUCKETS = REGISTRY.gauge(
    "hvd_fused_allreduce_buckets",
    "Collectives one trace of the in-jit fused_allreduce_tree issues "
    "(fusion buckets plus leaves reduced alone), by the size of the "
    "reduced axis. Set while the step is traced, not per step.",
    ("axis_size",))
FUSED_ALLREDUCE_BYTES = REGISTRY.gauge(
    "hvd_fused_allreduce_bytes",
    "Bytes the collectives of one fused_allreduce_tree carry a step "
    "(wire dtype, padding included), by the size of the reduced axis.",
    ("axis_size",))
FLASH_TILES = REGISTRY.gauge(
    "hvd_flash_tiles",
    "Score tiles per (batch, head) of the last traced flash-attention "
    "call, by kernel (fwd; bwd_dqkv, the one backward kernel, or the pair "
    "bwd_dq + bwd_dkv for a call past its dQ's VMEM budget) and kind: "
    "total, visited (not wholly masked) and masked (visited with mask "
    "code: the diagonal, the window's or the padding edge crosses the "
    "tile); blocks_inside, "
    "blocks_diagonal, blocks_edge, blocks_skipped: the 1024 x 1024 blocks "
    "of each kind where a causal call past 1024 runs the static schedule "
    "by block kind (0 on every other call). From the functions that give "
    "the kernels their loop bounds. Set while the call is traced.",
    ("kernel", "kind"))
MOE_EXPERTS = REGISTRY.gauge(
    "hvd_moe_experts",
    "Experts of the last traced DroplessMoE call, by kind: routed (the "
    "router's width), held (the experts this layer holds) and per_token "
    "(the experts a token is routed to). Set while the call is traced.",
    ("kind",))
MOE_BUFFER_ROWS_PER_TOKEN = REGISTRY.gauge(
    "hvd_moe_buffer_rows_per_token",
    "Rows the grouped product's buffer of the last traced DroplessMoE "
    "call carries, per token of the call: the rows expected are "
    "per_token x held / routed, the rest is room for imbalance. By the "
    "chips the experts are exchanged over (1 without an exchange). Set "
    "while the call is traced.",
    ("axis_size",))
MOE_OVERFLOW_CALLS = REGISTRY.gauge(
    "hvd_moe_overflow_calls",
    "DroplessMoE calls whose live rows outnumbered the buffer sized for "
    "the rows expected and ran over every (token, choice) pair instead. "
    "Set to 0 when a layer with such a path is traced; the device picks "
    "the branch and reports nothing back (a host callback would keep the "
    "step out of the persistent compile cache), so the series says that "
    "the path exists, not how often it ran. By the chips the experts are "
    "exchanged over.",
    ("axis_size",))
MOE_PRODUCT_PATH = REGISTRY.gauge(
    "hvd_moe_product_path",
    "How the last traced DroplessMoE call runs each of its two grouped "
    "products (in: the rows times the experts' first matrix; down: times "
    "their second): 1 = the repo's Pallas kernels in tiles picked from the "
    "shapes, 0 = lax.ragged_dot in the tile the compiler gives its widths, "
    "kept only where no slab of the kernels' fits VMEM. Set while the call "
    "is traced.",
    ("product",))
MOE_PRODUCT_TILES = REGISTRY.gauge(
    "hvd_moe_product_tiles",
    "The tile of each grouped product of the last traced DroplessMoE "
    "call, by dim: m (rows a step), k (of the contraction: the block of "
    "the left operand's gradient on path 1) and n (output columns). A "
    "value of 128 in k or n on path 0 is the compiler's slow tile. Set "
    "while the call is traced.",
    ("product", "dim"))
ATTN_LAYER = REGISTRY.gauge(
    "hvd_attn_layer",
    "Sizes of the last traced TPSelfAttention call that norms its query "
    "and key heads or gates its output, by kind: heads, kv_heads, head_dim, "
    "window (0: every earlier key), normed and gated (1 or 0); of a "
    "TPLatentAttention call: heads, qk_head_dim, v_head_dim, q_lora_rank, "
    "kv_lora_rank and rope_head_dim. Set while the call is traced; a "
    "TPSelfAttention layer with neither norm nor gate records nothing.",
    ("kind",))
ATTN_PROLOGUE_LAYERS = REGISTRY.gauge(
    "hvd_attn_prologue_layers",
    "TPSelfAttention layers, by the path their last traced call took from "
    "the fused qkv rows to attention (parallel.tp.prologue_path): 1 = one "
    "Pallas pass of ops/pallas/attn_prologue.py (the heads' RMS norm, the "
    "rotation and the move into the flash kernels' layout, forward and "
    "backward), 0 = the split, nn.RMSNorm, apply_rope and flash's own "
    "layout change, or no flash at all. Layers are told apart by module "
    "path, so a second trace of a model counts nothing twice.",
    ("path",))
SSM_LAYER = REGISTRY.gauge(
    "hvd_ssm_layer",
    "Sizes of the last traced Mamba2Mixer call, by kind: heads, head_dim, "
    "state (the state's size a channel), groups (of B and C), chunk (the "
    "scan's chunk length) and chunks (chunks a sequence). Set while the "
    "call is traced.",
    ("kind",))
SSM_CHUNK_STATE_BYTES = REGISTRY.gauge(
    "hvd_ssm_chunk_state_bytes",
    "Bytes of one set of chunk states that one traced Mamba2Mixer call's "
    "scan writes to HBM: one (head_dim, state) matrix a sequence, chunk "
    "and head. The jax.numpy form (hvd_ssm_scan_path 0) writes float32 "
    "closing states forward; the kernels (path 1) write none forward and, "
    "in the backward pass's first sweep, the state each chunk opens with "
    "in the activations' dtype: that sweep's bytes. By the chips the "
    "sequence is split over (1: the scan has no exchange). Set while the "
    "call is traced.",
    ("axis_size",))
SSM_SCAN_PATH = REGISTRY.gauge(
    "hvd_ssm_scan_path",
    "How the last traced parallel.ssm.ssm_scan call runs its chunks, by "
    "kind: kernels (1: ops/pallas/ssm_scan.py, decay masks and the carried "
    "state in VMEM; 0: the jax.numpy chunked form) and the kernels' block "
    "of a grid step: positions, channels (a group's heads x head_dim), "
    "state (zeros on path 0). Picked from the call's shapes "
    "(parallel.ssm.scan_path). Set while the call is traced.",
    ("kind",))
KDA_LAYER = REGISTRY.gauge(
    "hvd_kda_layer",
    "The last traced parallel.kda.KDAMixer call, by kind: kernels (1: the "
    "delta rule runs in ops/pallas/kda.py, the decays, the chunk's solve "
    "and the carried state in VMEM; 0: the jax.numpy chunked form; picked "
    "from the call's shapes by parallel.kda.kda_path), heads, head_dim, "
    "chunk (the rule's chunk length) and chunks (chunks a sequence). Set "
    "while the call is traced.",
    ("kind",))
KDA_CHUNK_STATE_BYTES = REGISTRY.gauge(
    "hvd_kda_chunk_state_bytes",
    "Bytes of chunk states that one traced KDAMixer call writes to HBM for "
    "its backward pass: one float32 (head_dim, head_dim) state a sequence, "
    "chunk and head, the state each chunk opens with (the kernels' first "
    "backward sweep, or what the jax.numpy form's scan keeps). By the "
    "chips the sequence is split over (1: the rule has no exchange). Set "
    "while the call is traced.",
    ("axis_size",))
AUTOPILOT_DECISIONS = REGISTRY.counter(
    "autopilot_decisions_total",
    "Autopilot controller decisions per lever and outcome "
    "(horovod_tpu/autopilot: lever=tuner|overlap|cross_wire|remediate; "
    "outcome=adopt|hold|frozen|reverted|no_signal|baseline|"
    "drift_detected|trial|adopted|requested|unreachable). Every decision "
    "also lands in the flight ring as an autopilot_decision event.",
    ("lever", "outcome"))
AUTOPILOT_REMEDIATIONS = REGISTRY.counter(
    "autopilot_remediations_total",
    "Autopilot remediation requests and their driver-side outcomes "
    "(cause=dead|stalled|straggler; outcome=requested|no_driver|"
    "publish_failed on the coordinator, applied|rejected_floor|"
    "rejected_rate|rejected_unknown_host on the driver arm).",
    ("cause", "outcome"))
TELEMETRY_RPCS = REGISTRY.counter(
    "telemetry_rpcs_total",
    "Telemetry-plane KV RPCs by phase (horovod_tpu/telemetry): the "
    "aggregation round's beacon_put|probe_get|slice_get|slice_put|"
    "job_get|job_put — whose scaling contract is job_get per round == "
    "slice count, not world size (TestTelemetryScaling) — plus read_get, "
    "the demand-driven /cluster/* endpoint reads that scale with scrape "
    "rate instead.",
    ("phase",))


# --- recording helpers (the stack's API) --------------------------------

def record_collective(op, nbytes, process_set="global"):
    """One eager collective dispatch attempt: count + bytes (recorded at
    entry; failures still count as attempts)."""
    if not _enabled:
        return
    COLLECTIVE_OPS.labels(op, process_set).inc()
    if nbytes:
        COLLECTIVE_BYTES.labels(op, process_set).inc(float(nbytes))


def record_collective_latency(op, seconds):
    """Dispatch latency of one SUCCESSFUL eager collective."""
    if not _enabled:
        return
    COLLECTIVE_LATENCY.labels(op).observe(seconds)


def record_collective_error(op):
    if not _enabled:
        return
    COLLECTIVE_ERRORS.labels(op).inc()


def record_fusion_flush(n_tensors, nbytes, threshold):
    if not _enabled:
        return
    FUSION_FLUSHES.inc()
    FUSION_FLUSH_TENSORS.observe(n_tensors)
    FUSION_FLUSH_BYTES.observe(nbytes)
    if threshold:
        FUSION_FILL_RATIO.observe(nbytes / float(threshold))


def record_boundary(outcome):
    if not _enabled:
        return
    FUSION_BOUNDARY_OUTCOMES.labels(outcome).inc()


def record_fusion_kv(sets=0, gets=0, payload_bytes=0):
    if not _enabled:
        return
    if sets:
        FUSION_KV_RPCS.labels("set").inc(sets)
        CONTROL_PLANE_RPCS.labels("coord", "set").inc(sets)
    if gets:
        FUSION_KV_RPCS.labels("get").inc(gets)
        CONTROL_PLANE_RPCS.labels("coord", "get").inc(gets)
    if payload_bytes:
        CONTROL_PLANE_PAYLOAD.labels("coord").inc(payload_bytes)


# Default flat-schedule DCN fractions (per schedule: ring / a2a),
# resolved lazily from the live slice layout and cached (reset by
# collective_ops.clear_program_caches — an elastic resize must never
# replay a stale slice split). None = not yet resolved.
_tier_frac = None


def _default_dcn_fraction(sched="ring"):
    """Slice-boundary DCN fraction of the live topology for one flat
    schedule — the static cost model's rules for flat dispatches, shared
    via wire.ring_dcn_fraction / a2a_dcn_fraction: ``S/n`` for ring legs,
    ``1 - L/n`` for all-to-all legs when a slice hierarchy exists, else 0
    (single-slice worlds book everything to ici)."""
    global _tier_frac
    if _tier_frac is None:
        fracs = {"ring": 0.0, "a2a": 0.0}
        try:
            from horovod_tpu.common import basics, topology
            from horovod_tpu.ops import wire as _wire
            if basics.is_initialized():
                topo = basics.topology()
                size = topo.size
                k = topology.forced_slices()
                slices, slice_size = topology.slice_layout(
                    size, k or (topo.num_slices if topo.num_slices > 1
                                else None))
                if slices > 1:
                    members = list(range(size))
                    fracs = {
                        "ring": _wire.ring_dcn_fraction(members,
                                                        slice_size),
                        "a2a": _wire.a2a_dcn_fraction(members,
                                                      slice_size)}
        except Exception:  # noqa: BLE001 — accounting must never break
            fracs = {"ring": 0.0, "a2a": 0.0}   # a dispatch
        _tier_frac = fracs
    return _tier_frac.get(sched, _tier_frac["ring"])


def reset_tier_split():
    """Forget the cached flat-schedule tier split (topology changed)."""
    global _tier_frac
    _tier_frac = None


def record_wire(path, dtype, nbytes, compressed=False, tiers=None,
                sched="ring"):
    """Wire accounting for one collective dispatch: bytes at the effective
    wire dtype, plus a compression event when the wire was actually
    narrowed (quantized exchange or 16-bit cast). ``tiers`` (a
    ``{"ici": b, "dcn": b}`` dict) books an explicit per-tier split (the
    hierarchical dispatch paths and the quantized exchange's per-leg
    schedules pass it); without it the flat split of the live slice
    layout at this leg's ``sched`` (``"ring"``/``"a2a"``) applies —
    all-ici on single-slice worlds."""
    if not _enabled or not dtype:
        return
    if nbytes and tiers is None:
        from horovod_tpu.ops import wire as _wire
        tiers = _wire.split_tiers(nbytes, _default_dcn_fraction(sched))
    if tiers:
        for tier, b in tiers.items():
            if b:
                WIRE_BYTES.labels(str(dtype), tier).inc(float(b))
    if compressed:
        WIRE_COMPRESSION_EVENTS.labels(path, str(dtype)).inc()


def record_plan_cache(event):
    """One dispatch-plan cache outcome (event=hit|miss|invalidate)."""
    if not _enabled:
        return
    DISPATCH_PLAN_EVENTS.labels(event).inc()


def record_compile_cache(event):
    """One persistent-compilation-cache outcome (event=request|hit)."""
    if not _enabled:
        return
    COMPILE_CACHE_EVENTS.labels(event).inc()


# JAX's monitoring bus: the events mirrored into the registry, and the
# time spans that become spans of the ``run`` trace (``fun`` in ``args``;
# docs/observability.md).
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# A traced step traces hundreds of small inner functions, and a process
# may compile for as long as it lives: spans under a millisecond are
# dropped, so the ``run`` trace keeps the ones worth reading and stays
# under the store's cap.
COMPILE_SPAN_MIN_S = 1e-3

_listeners_installed = set()      # "counts", "spans"
_cache_load = threading.local()   # .seconds: a cache read not yet placed


def _on_compile_event(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        record_compile_cache("hit")
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        record_compile_cache("request")


def _on_compile_duration(event, duration_secs, **kwargs):
    # JAX reports the cache read from inside the backend's span, before
    # that span closes and with no function name: held for the span.
    if event == _CACHE_LOAD_EVENT:
        _cache_load.seconds = duration_secs


def _compiled_fun(name):
    """The traced function's own name: JAX says ``hvd_dp_step`` when it
    traces and ``jit(hvd_dp_step)`` when it lowers and compiles."""
    name = str(name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_compile_span(event, start_time, end_time, fun_name="", **kwargs):
    """One stage of a compile as a span of the ``run`` trace: JAX's clock
    is the store's (``time.time``). ``compile.cache_load`` is the child of
    the ``compile.backend`` it was read in and ends where that ends."""
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    load = None
    if name == "compile.backend":
        load = getattr(_cache_load, "seconds", None)
        _cache_load.seconds = None
    if end_time - start_time < COMPILE_SPAN_MIN_S:
        return
    from horovod_tpu import trace as _trace
    tid = _trace.run_tid()
    args = {"fun": _compiled_fun(fun_name)}
    kept = _trace.add_span(tid, name, start_time, end_time - start_time,
                           args=args)
    if load is not None and kept is not None:
        _trace.add_span(tid, "compile.cache_load", end_time - load, load,
                        parent=name, args=args)


def install_compile_cache_listener():
    """Listen to JAX's monitoring bus, the one site that does. Idempotent;
    installed by ``basics.init``.

    Always: the persistent compilation cache's events mirrored into the
    registry, so cache effectiveness (and the zero-fresh-compiles restart
    guarantee) is assertable from metrics. While tracing is armed
    (``HOROVOD_TRACE``): the three stages of every compile as spans of the
    ``run`` trace, ``compile.trace`` / ``compile.lower`` /
    ``compile.backend`` with the function's name, and the cache read as
    ``compile.cache_load`` under the last. A function that compiles again
    in the middle of a run shows as a second ``compile.backend``."""
    from jax._src import monitoring as _jax_monitoring
    from horovod_tpu import trace as _trace
    if "counts" not in _listeners_installed:
        _jax_monitoring.register_event_listener(_on_compile_event)
        _listeners_installed.add("counts")
    if _trace.armed and "spans" not in _listeners_installed:
        _jax_monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        _jax_monitoring.register_event_time_span_listener(_on_compile_span)
        _listeners_installed.add("spans")


def record_negotiation(gets, payload_bytes, sets=1, tier_gets=None):
    """One negotiation.exchange() round: ``sets`` publishes + ``gets``
    peer-read RPC attempts. Hierarchical rounds additionally pass
    ``tier_gets`` ({"local","cross","fanback"}) so the scrape shows the
    per-tier fan-out the slice-leader decomposition is supposed to bound
    (kind=neg_get_<tier>)."""
    if not _enabled:
        return
    NEGOTIATION_ROUNDS.inc()
    CONTROL_PLANE_RPCS.labels("coord", "set").inc(max(int(sets), 1))
    if gets:
        CONTROL_PLANE_RPCS.labels("coord", "get").inc(gets)
    for tier, n in (tier_gets or {}).items():
        if n:
            CONTROL_PLANE_RPCS.labels("coord", f"neg_get_{tier}").inc(n)
    if payload_bytes:
        CONTROL_PLANE_PAYLOAD.labels("coord").inc(payload_bytes)


def record_http_kv(kind, payload_bytes=0):
    """One runner HTTP-KV client RPC (kind=get|put|delete|wait)."""
    if not _enabled:
        return
    CONTROL_PLANE_RPCS.labels("http", kind).inc()
    if payload_bytes:
        CONTROL_PLANE_PAYLOAD.labels("http").inc(payload_bytes)


def record_elastic_event(event):
    # Flight ring BEFORE the metrics gate: elastic transitions are exactly
    # the events a post-mortem needs, and the recorder has its own switch.
    if _flight.armed:
        _flight.record_event("elastic", what=event)
    if not _enabled:
        return
    ELASTIC_EVENTS.labels(event).inc()


def record_elastic_recovery(cause, seconds):
    """One completed elastic recovery: detection → training re-entry."""
    if _flight.armed:
        _flight.record_event("elastic", what=f"recovered_{cause}",
                             dur=seconds)
    if not _enabled:
        return
    ELASTIC_RECOVERY.labels(cause).observe(seconds)


def record_goodput_seconds(category, seconds):
    """Delta export from the goodput ledger (horovod_tpu/goodput/ledger
    throttles and computes the per-category deltas; counters only grow)."""
    if not _enabled:
        return
    GOODPUT_SECONDS.labels(category).inc(float(seconds))


def record_kv_retry():
    if not _enabled:
        return
    KV_CLIENT_RETRIES.inc()


def record_chaos(site, kind):
    """One chaos fault fired. Recorded even though injections are test
    machinery: the counter is how a soak (or an operator reading a scrape)
    correlates observed symptoms with injected causes."""
    if not _enabled:
        return
    CHAOS_INJECTIONS.labels(site, kind).inc()


def record_step(seconds):
    """One completed step-profiler window (wall seconds)."""
    if not _enabled:
        return
    STEP_TIME.observe(seconds)


def record_profiler_event(kind):
    """One watchdog finding (kind=straggler|regression)."""
    if not _enabled:
        return
    STEP_PROFILER_EVENTS.labels(kind).inc()


def record_profiler_kv(sets=0, gets=0):
    """Watchdog cross-rank publish traffic on the coordination service —
    reported under control_plane_rpcs_total like every other KV user."""
    if not _enabled:
        return
    if sets:
        CONTROL_PLANE_RPCS.labels("coord", "prof_set").inc(sets)
    if gets:
        CONTROL_PLANE_RPCS.labels("coord", "prof_get").inc(gets)


def record_serving_request(event):
    """One serving request lifecycle event (event=submitted|admitted|
    completed|requeued|rejected). Requeues also land in the flight ring:
    they are exactly the events a zero-drop post-mortem needs."""
    if _flight.armed and event in ("requeued", "rejected"):
        _flight.record_event("serving", what=event)
    if not _enabled:
        return
    SERVING_REQUESTS.labels(event).inc()


def record_serving_ttft(seconds):
    if not _enabled:
        return
    SERVING_TTFT.observe(seconds)


def record_serving_step(seconds, active, slots, tokens=0):
    """One serving decode step: inter-token latency, fill ratio, and the
    tokens it committed."""
    if not _enabled:
        return
    SERVING_TOKEN_LATENCY.observe(seconds)
    if slots:
        SERVING_FILL.observe(active / float(slots))
    if tokens:
        SERVING_TOKENS.inc(tokens)


def record_serving_queue(depth):
    if not _enabled:
        return
    SERVING_QUEUE_DEPTH.set(depth)


def record_slo_burn(objective, burn):
    """Current burn rate of one declared SLO objective (set by
    telemetry/slo.py whenever a window is recomputed)."""
    if not _enabled:
        return
    SLO_BURN.labels(objective).set(float(burn))


def record_autopilot_decision(lever, outcome):
    """One autopilot controller decision (horovod_tpu/autopilot)."""
    if not _enabled:
        return
    AUTOPILOT_DECISIONS.labels(lever, outcome).inc()


def record_autopilot_remediation(cause, outcome):
    """One autopilot remediation lifecycle event (request or driver-arm
    outcome). The flight-ring mirror is recorded at the call sites —
    they carry the rank/host detail this counter aggregates away."""
    if not _enabled:
        return
    AUTOPILOT_REMEDIATIONS.labels(cause, outcome).inc()


def record_fused_allreduce(axis_size, buckets, nbytes):
    """What one trace of ``optim.fused_allreduce_tree`` makes: known while
    the step is traced, so set there once and not per step."""
    if not _enabled:
        return
    FUSED_ALLREDUCE_BUCKETS.labels(axis_size).set(buckets)
    FUSED_ALLREDUCE_BYTES.labels(axis_size).set(nbytes)


def record_moe_layer(routed, held, per_token, buffer_rows, tokens,
                     axis_size=1, products=None):
    """What one trace of ``parallel.moe.DroplessMoE`` makes: known while
    the call is traced, so set there once and not per step. A buffer of
    fewer rows than the call's pairs has an overflow path, whose series
    appears here. ``products``: ``{name: (path, (tm, tk, tn))}`` of the
    call's grouped products (``parallel.moe.product_tiles``)."""
    if not _enabled:
        return
    for kind, n in (("routed", routed), ("held", held),
                    ("per_token", per_token)):
        MOE_EXPERTS.labels(kind).set(n)
    MOE_BUFFER_ROWS_PER_TOKEN.labels(axis_size).set(buffer_rows / tokens)
    if buffer_rows < per_token * tokens:
        MOE_OVERFLOW_CALLS.labels(axis_size).set(0)
    for product, (path, tiles) in (products or {}).items():
        MOE_PRODUCT_PATH.labels(product).set(path)
        for dim, tile in zip("mkn", tiles):
            MOE_PRODUCT_TILES.labels(product, dim).set(tile)


def record_attn_layer(heads, kv_heads, head_dim, window, normed, gated):
    """What one trace of a ``parallel.tp.TPSelfAttention`` with a norm on
    its heads or a gate makes: known while the call is traced, so set there
    once and not per step."""
    if not _enabled:
        return
    for kind, n in (("heads", heads), ("kv_heads", kv_heads),
                    ("head_dim", head_dim), ("window", window),
                    ("normed", int(normed)), ("gated", int(gated))):
        ATTN_LAYER.labels(kind).set(n)


def record_latent_attn_layer(heads, qk_head_dim, v_head_dim, q_lora_rank,
                             kv_lora_rank, rope_head_dim):
    """The widths of one trace of a ``parallel.mla.TPLatentAttention``
    call: known while the call is traced, so set there once and not per
    step."""
    if not _enabled:
        return
    for kind, n in (("heads", heads), ("qk_head_dim", qk_head_dim),
                    ("v_head_dim", v_head_dim), ("q_lora_rank", q_lora_rank),
                    ("kv_lora_rank", kv_lora_rank),
                    ("rope_head_dim", rope_head_dim)):
        ATTN_LAYER.labels(kind).set(n)


# module path of a TPSelfAttention layer -> the path its last traced call
# took (hvd_attn_prologue_layers)
_attn_prologue_layers = {}


def record_attn_prologue(path, layer):
    """The path (1 or 0) one traced call of the ``TPSelfAttention`` at
    module path ``layer`` took (``parallel.tp.prologue_path``): known while
    the call is traced."""
    if not _enabled:
        return
    _attn_prologue_layers[layer] = path
    for p in (0, 1):
        ATTN_PROLOGUE_LAYERS.labels(p).set(
            sum(v == p for v in _attn_prologue_layers.values()))


def record_ssm_layer(heads, head_dim, state, groups, chunk, chunks,
                     chunk_state_bytes, axis_size=1):
    """What one trace of ``parallel.ssm.Mamba2Mixer`` makes: known while
    the call is traced, so set there once and not per step."""
    if not _enabled:
        return
    for kind, n in (("heads", heads), ("head_dim", head_dim),
                    ("state", state), ("groups", groups), ("chunk", chunk),
                    ("chunks", chunks)):
        SSM_LAYER.labels(kind).set(n)
    SSM_CHUNK_STATE_BYTES.labels(axis_size).set(chunk_state_bytes)


def record_ssm_scan_path(path, blocks):
    """Which way one trace of ``parallel.ssm.ssm_scan`` runs its chunks
    (``parallel.ssm.scan_path``): known while the call is traced."""
    if not _enabled:
        return
    for kind, n in zip(("kernels", "positions", "channels", "state"),
                       (path, *blocks)):
        SSM_SCAN_PATH.labels(kind).set(n)


def record_kda_layer(path, heads, head_dim, chunk, chunks, chunk_state_bytes,
                     axis_size=1):
    """What one trace of ``parallel.kda.KDAMixer`` makes: known while the
    call is traced, so set there once and not per step."""
    if not _enabled:
        return
    for kind, n in (("kernels", path), ("heads", heads),
                    ("head_dim", head_dim), ("chunk", chunk),
                    ("chunks", chunks)):
        KDA_LAYER.labels(kind).set(n)
    KDA_CHUNK_STATE_BYTES.labels(axis_size).set(chunk_state_bytes)


def record_flash_tiles(kernel, counts):
    """The tile schedule of one flash-attention kernel call, known while
    it is traced: ``counts`` maps kind (total|visited|masked, tiles; the
    four blocks_*, blocks of 1024 a side) to its number per (batch,
    head)."""
    if not _enabled:
        return
    for kind, n in counts.items():
        FLASH_TILES.labels(kernel, kind).set(n)


def record_telemetry_rpc(phase, n=1):
    """One (or ``n``) telemetry-plane KV RPCs in the given aggregation
    phase (horovod_tpu/telemetry/aggregator.py)."""
    if not _enabled:
        return
    TELEMETRY_RPCS.labels(phase).inc(n)


def record_stall(kind):
    if _flight.armed:
        _flight.record_event("stall", what=kind)
    if not _enabled:
        return
    STALL_EVENTS.labels(kind).inc()


# --- timeline integration ----------------------------------------------
#
# Registry values double as Chrome-trace COUNTER events ("ph": "C") so the
# aggregate series land in the same chrome://tracing file as the op spans
# (the reference's timeline has no counter tracks at all). Emission is
# throttled: the fusion runtime calls maybe_emit_timeline_counters() after
# every flush, which at kHz flush rates would otherwise snapshot the whole
# registry per flush.

_TL_MIN_INTERVAL_S = 0.1
_tl_last = 0.0
_tl_lock = threading.Lock()


def emit_timeline_counters(timeline):
    """Dump every series' current value into ``timeline`` as counter
    events. Histograms emit their _count and _sum. No-op when metrics are
    disabled — an all-zero dump would pollute the trace of a user who
    explicitly turned the registry off."""
    if timeline is None or not _enabled:
        return 0
    n = 0
    for name, fam in REGISTRY.snapshot().items():
        for s in fam["series"]:
            lab = ",".join(f"{k}={v}" for k, v in s["labels"].items())
            label = f"{name}{{{lab}}}" if lab else name
            if fam["type"] == "histogram":
                timeline.record_counter(label + "_count", s["count"])
                timeline.record_counter(label + "_sum", s["sum"])
                n += 2
            else:
                timeline.record_counter(label, s["value"])
                n += 1
    return n


def maybe_emit_timeline_counters(timeline):
    """Throttled emit_timeline_counters (at most once per 100 ms)."""
    global _tl_last
    if timeline is None or not _enabled:
        return 0
    now = time.monotonic()
    with _tl_lock:
        if now - _tl_last < _TL_MIN_INTERVAL_S:
            return 0
        _tl_last = now
    return emit_timeline_counters(timeline)

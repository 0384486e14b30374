"""Runtime configuration knobs.

The reference core reads ~30 ``HOROVOD_*`` environment variables at background-thread
start (reference: horovod/common/operations.cc:456-646, full knob list
horovod/common/common.h:115-149). This module is the TPU-native mirror: every knob is
an attribute of :class:`Config`, populated from the same environment variable names so
launcher-side ``config_parser.set_env_from_args`` semantics carry over unchanged.

Knobs that only make sense for the CUDA/NCCL runtime (num NCCL streams, GPU ops
selection) are accepted-and-ignored for compatibility; TPU-specific knobs are added
under the same naming convention.
"""

import dataclasses
import os


def _env_bool(name, default=False):
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_int(name, default):
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_float(name, default):
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return float(v)
    except ValueError:
        return default


# <checkout>/.horovod_compile_cache, from the package location: the cache
# key includes its path, so it must not depend on cwd, pid or time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".horovod_compile_cache")


@dataclasses.dataclass
class Config:
    # --- fusion / cycle (reference common.h:119-121, operations.cc:515,551) ---
    # Fusion buffer threshold in bytes; batches small eager tensors into one
    # fused collective. Reference default 128 MB - ours is 64 MB because XLA
    # fuses aggressively already and HBM is the scarce resource.
    fusion_threshold: int = 64 * 1024 * 1024
    # Background flush cycle in ms for the eager bucketing runtime.
    cycle_time_ms: float = 1.0

    # --- cache (reference common.h:122-123) ---
    # Compiled-program cache capacity (the response cache's TPU analog is the
    # jit cache keyed on the fused tensor-set signature).
    cache_capacity: int = 1024

    # --- algorithm selection (reference common.h:130-132) ---
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    torus_allreduce: bool = False  # fork knob HOROVOD_TORUS_ALLREDUCE (common.h:132)

    # --- autotune (reference common.h:133-138) ---
    autotune: bool = False
    autotune_log_file: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8

    # --- autopilot (horovod_tpu/autopilot; ROADMAP item 4 — the online
    # self-driving controller: closed-loop tuning over the signal plane
    # plus automated straggler/dead-rank remediation through the elastic
    # driver). Opt-in: decisions change compiled programs and can remove
    # hosts; see docs/performance.md for the levers/guardrails and the
    # docs/troubleshooting.md "the controller removed my rank" runbook.
    autopilot: bool = False
    # Decision-epoch cadence in seconds (the controller thread's tick).
    autopilot_interval: float = 10.0
    # Remediation rate limiter: at most this many controller-initiated
    # host removals per rolling window (autopilot/remediate.WINDOW_S).
    autopilot_max_removals: int = 1
    # Hysteresis: a rank must be named (watchdog straggler / telemetry
    # dead/stalled) this many CONSECUTIVE decision epochs before the
    # controller may act on it.
    autopilot_hysteresis: int = 3
    # Do-not-shrink floor: never remediate below this world size
    # (0 = derive: the elastic launch's --min-np, else 1).
    autopilot_min_world: int = 0
    # Twin-pretrained warm start: path to an export_observations JSON
    # artifact (horovod_tpu.sim.autopilot writes one) — the controller
    # skips the categorical sweep and starts the numeric search at the
    # twin's best point. "" = cold start. A mismatched/malformed prior
    # is rejected with a warning, never fatal.
    autopilot_prior: str = ""

    # --- hvdsim scale digital twin (horovod_tpu/sim; ROADMAP item 3) —
    # latency model of the virtual control plane: base cost of one KV
    # RPC and the cross-slice (DCN) surcharge, in microseconds. The
    # twin's guards assert on RPC *counts*; these only shape virtual
    # timings (docs/scale_validation.md).
    sim_kv_us: float = 5.0
    sim_dcn_us: float = 50.0

    # --- timeline (reference common.h:117-118) ---
    timeline_filename: str = ""
    timeline_mark_cycles: bool = False

    # --- stall inspector (reference common.h:124-125) ---
    stall_check_disable: bool = False
    stall_check_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    # Debug: cross-rank verification that every process dispatches the same
    # eager collective with the same signature, in the same order — turns
    # SPMD-contract violations (which otherwise hang or corrupt) into
    # immediate errors. The runtime analog of the reference coordinator's
    # shape/dtype mismatch checks (controller.h:158-163), extended with
    # order checking. Costs one tiny KV exchange per collective: debug only.
    order_check: bool = False

    # --- elastic / process sets (reference common.h:139-143) ---
    elastic: bool = False
    # Accepted for launcher compatibility; NOT a gate here. The reference
    # requires HOROVOD_DYNAMIC_PROCESS_SETS=1 before add/remove at runtime
    # because dynamic sets cost it communicator construction; on TPU a
    # process set is a sub-mesh + compiled-program cache entry, so dynamic
    # add/remove is always available (process_sets.py).
    dynamic_process_sets: bool = False
    # Multi-process JOIN (uneven final batches across hosts, reference
    # controller.cc:269-327). The reference's background controller
    # negotiates every collective, which is what lets a joined rank keep
    # answering with zero contributions; the TPU hot path has no such
    # negotiation, so JOIN across processes is an opt-in mode: while armed,
    # every global-set eager collective starts with one tiny KV round
    # (see ops/collective_ops._join_sync). Single-controller join() needs
    # no mode flag.
    join_mode: bool = False

    # --- bootstrap (reference gloo_run.py:203-214 env plumbing) ---
    rank: int = 0
    local_rank: int = 0
    cross_rank: int = 0
    size: int = -1
    local_size: int = -1
    cross_size: int = -1
    coordinator_addr: str = ""
    coordinator_port: int = 0

    # --- TPU-specific additions ---
    # Wire dtype for collective payloads ("" = keep dtype): float16/bfloat16
    # cast the fused buckets; int8/fp8 route eligible allreduces — eager,
    # fused AND the in-jit entry points — through the block-scaled
    # quantized exchange (ops/wire.py; fp8 falls back to bfloat16 when the
    # dtype is missing). Overridable per process set at runtime via
    # hvd.set_wire_dtype (the autotuner steers the global set through the
    # same registry).
    wire_dtype: str = ""
    # Per-link-tier wire policy: the wire dtype of the CROSS-SLICE (DCN)
    # leg of the hierarchical dispatch tier ("" = inherit wire_dtype).
    # The ICI legs of the 2-level decomposition always stay exact — this
    # knob quantizes only the scarce inter-slice hop (the EQuARX
    # deployment shape). Overridable per process set via
    # hvd.set_wire_dtype(dtype, tier="dcn").
    wire_dtype_dcn: str = ""
    # Hierarchical dispatch tier (ROADMAP item 3): when a slice hierarchy
    # exists (HOROVOD_MESH_SLICES / multi-slice topology), eligible
    # allreduces on all three dispatch paths decompose into local RS
    # (exact, ICI) -> cross-slice allreduce (wire_dtype_dcn, DCN) ->
    # local AG. Opt-in: a 1-slice layout would pay two extra ICI legs for
    # no DCN saving (hvdlint HVP113).
    hierarchical_dispatch: bool = False
    # Hierarchical ALLTOALL tier (MoE expert dispatch, ISSUE 18): when a
    # slice hierarchy exists, eligible equal-splits alltoalls (eager) and
    # the MoE layer's dispatch/combine (jit) decompose into a slice-local
    # a2a (ICI) -> cross-slice a2a on the per-tier wire (DCN). Opt-in for
    # the same reason as hierarchical_dispatch (hvdlint HVP113). Distinct
    # from the allreduce knob on purpose: a2a moves activations.
    hierarchical_alltoall: bool = False
    # Wire dtype of the hierarchical alltoall's CROSS-SLICE (DCN) leg
    # ("" = exact). Deliberately does NOT inherit wire_dtype/
    # wire_dtype_dcn: alltoall payloads are activations without error
    # feedback, so quantizing them is an explicit choice
    # (docs/performance.md "when NOT to quantize the expert leg").
    # Overridable per process set via hvd.set_alltoall_cross_dtype.
    alltoall_cross_dtype: str = ""
    # Cross-leg overlap in the fusion flush scheduler: the DCN leg of a
    # hierarchical bucket is left in flight at flush return and only
    # awaited when the next flush (or the step boundary / a sync
    # collective's fence) needs it, booking the wait to the step
    # profiler's cross_wait category instead of the flush critical path.
    cross_overlap: bool = True
    # Error feedback for the quantized wire: keep each bucket's fp32
    # quantization error and add it back before the next quantize
    # (eager + fused paths; in-jit callers thread residuals themselves).
    # Residuals are zeroed by clear_program_caches / elastic reset.
    wire_error_feedback: bool = True
    # Donate fused buffers to XLA (buffer reuse).
    donate_buffers: bool = True
    # Donate SYNC eager-collective inputs that are already correctly-sharded
    # jax.Arrays (the dispatch-plan fast path). Requires the caller to treat
    # allreduce as consuming its input, so it is opt-in: armed only when
    # HOROVOD_DONATE_BUFFERS is set EXPLICITLY (and truthy) in the
    # environment — the default-on donate_buffers above covers only the
    # fusion runtime's host-staged buckets, which alias nothing.
    donate_eager: bool = False
    # Persistent XLA compilation cache directory: JAX_COMPILATION_CACHE_DIR
    # when set (jax reads it itself; basics.init then sets no other), else
    # HOROVOD_COMPILE_CACHE_DIR, else the fixed checkout path above. Armed
    # in basics.init so repeat launches and elastic re-rendezvous skip
    # recompiles. See docs/performance.md.
    compile_cache_dir: str = DEFAULT_COMPILE_CACHE_DIR

    # --- hierarchical control plane (common/control_plane.py) ---
    # flat | hier | "" = auto (hier whenever the slice layout has >1
    # slice). hier decomposes negotiation.exchange into slice-local +
    # leaders-only rounds, mirrors fusion boundaries through slice
    # leaders, and (launcher-side) shards the HTTP-KV per slice — member
    # ranks issue O(1) blocking control-plane reads instead of O(world).
    control_plane: str = ""
    # Per-slice HTTP-KV shard listeners started by the launcher (0 =
    # one per slice when the hierarchical control plane is armed; an
    # explicit count overrides the slice layout).
    kv_shard_count: int = 0
    # First shard listener port; shard k binds base + k (0 = ephemeral
    # ports, propagated to workers via HOROVOD_KV_SHARD_PORTS).
    kv_shard_port_base: int = 0
    # Leader lease for the hierarchical fusion-boundary stream: a member
    # that observes a root boundary its slice leader has not re-published
    # within this window takes the re-publish role over.
    control_lease_ms: float = 2000.0

    # --- control-plane resilience (runner/http_kv.py KVStoreClient) ---
    # A single transient connection reset mid-negotiation used to kill the
    # caller; the client now retries transient transport faults (URLError,
    # connection reset, HTTP 5xx) this many times with jittered exponential
    # backoff before surfacing the error. 404s and other 4xx are semantic
    # answers, never retried.
    kv_retries: int = 3
    kv_retry_backoff_ms: float = 50.0
    kv_retry_backoff_max_ms: float = 2000.0

    # --- chaos / fault injection (horovod_tpu/chaos; docs/robustness.md).
    # A seeded declarative fault plan: path to a YAML/JSON file or inline
    # text. "" = disarmed (every injection site is a single bool check).
    chaos_plan: str = ""
    # Seed overriding the plan's own (probabilistic triggers are a
    # counter-hash of seed x spec x call count — reproducible schedules).
    chaos_seed: int = 0
    # Directory for the per-rank JSONL injection ledgers.
    chaos_ledger: str = ""

    # --- flight recorder (horovod_tpu/flight; no reference analog — the
    # reference's timeline must be armed BEFORE the run, so unpredicted
    # failures leave no artifact). Always-on bounded event ring, dumped on
    # failure paths; budgeted by TestFlightRecorderOverhead.
    flight: bool = True
    # Ring capacity in events (two per collective: dispatch + complete).
    flight_capacity: int = 4096
    # Dump directory ("" = ./flight_dumps); hvdrun --flight-dir exports it
    # to every worker so the elastic driver collects per-rank dumps in one
    # place.
    flight_dir: str = ""

    # --- step profiler (horovod_tpu/profile; the Horovod-timeline idea
    # rebuilt as structured per-step accounting — docs/observability.md).
    # Always-on by default: the ledger hot path is a short lock + float
    # adds (guarded by TestStepProfilerOverhead).
    step_profiler: bool = True
    # JSONL stream of per-step records ("" = in-memory ring only);
    # rendered by `python -m horovod_tpu.profile.report`.
    step_report_file: str = ""
    # "a:b" = capture a jax.profiler trace from the step-a marker to the
    # step-b marker ("" = off).
    profile_steps: str = ""
    # Capture output directory ("" = ./profile_traces).
    profile_dir: str = ""
    # Watchdog cross-rank publish cadence in steps (0 = local-only).
    profile_publish_steps: int = 16

    # --- cluster telemetry plane (horovod_tpu/telemetry; no reference
    # analog — the reference's observability is strictly per-rank).
    # Hierarchical rank → slice-leader → job-view aggregation over the
    # launcher HTTP-KV; armed by hvd.init when the KV is reachable and
    # the world is multi-process. See docs/observability.md.
    telemetry: bool = True
    # Beacon/aggregation round cadence in seconds.
    telemetry_interval: float = 2.0
    # Include the mergeable metrics snapshot in each digest (=0 keeps
    # beacons minimal: liveness + step + anomaly counts only).
    telemetry_metrics: bool = True
    # Health thresholds (0 = derive from the interval; see
    # telemetry/health.thresholds): beacon age marking a rank dead, step
    # clock stop marking it stalled.
    telemetry_dead_after: float = 0.0
    telemetry_stall_after: float = 0.0
    # Step-lag (vs the job median) marking a rank straggling, and
    # global-collective-seq lag marking it desynced.
    telemetry_step_lag: int = 5
    telemetry_seq_lag: int = 64

    # --- logging (common/logging.py; reference: HOROVOD_LOG_LEVEL /
    # HOROVOD_LOG_HIDE_TIME, horovod/common/logging.cc) ---
    log_level: str = "warning"
    log_hide_time: bool = False

    # --- elastic control knobs read outside Config (declared here so the
    # launcher propagates them and the docs catalogue them; the reading
    # sites keep their import-time env reads) ---
    # Coordination-service heartbeat window under HOROVOD_ELASTIC (s).
    elastic_heartbeat_timeout: int = 10
    # "min,max" cooldown seconds before a blacklisted host is retried
    # (runner/elastic/discovery.py; "" = built-in defaults).
    blacklist_cooldown_range: str = ""
    # Ports the membership watchdog's data-plane abort must never sever
    # (comma-separated; common/sockets.py).
    abort_exclude_ports: str = ""
    # Virtual slice layout override "slices" or "slices:len" for the
    # hierarchical telemetry/topology plane (common/topology.py).
    mesh_slices: str = ""

    # --- step-profiler tuning (horovod_tpu/profile; the always-on knobs
    # above arm the subsystem, these tune it) ---
    # Completed per-step records kept in the in-memory ring.
    profile_history: int = 512
    # Per-peer KV read budget in the watchdog's cross-rank round (ms).
    profile_publish_timeout_ms: int = 250
    # Robust z-score marking a rank a straggler, and the minimum absolute
    # excess (ms) so microsecond jitter never trips it.
    profile_z_threshold: float = 4.0
    profile_straggler_min_ms: float = 5.0
    # Roofline peak overrides (0 = detected chip table): bf16 TFLOP/s,
    # HBM / ICI / DCN GB/s (profile/roofline.py).
    peak_tflops: float = 0.0
    peak_hbm_gbs: float = 0.0
    peak_ici_gbs: float = 0.0
    peak_dcn_gbs: float = 0.0

    # --- Pallas flash-attention kernels (ops/pallas/flash_attention.py) ---
    # Tile-size cap for on-chip sweeps (0 = auto).
    flash_block: int = 0

    # --- static cost model (horovod_tpu/analysis/cost.py) ---
    # Per-step DCN byte budget for the static link-tier cost model: when
    # > 0, `python -m horovod_tpu.analysis.cost` (and cost_report) raise
    # HVP111 tier_budget_exceeded if the predicted cross-slice bytes of
    # one step exceed it. 0 = no budget declared.
    dcn_bytes_budget: int = 0

    # --- serving (horovod_tpu/serving; docs/inference.md) ---
    # Serving mode: `hvdrun --serving` sets it; `python -m
    # horovod_tpu.serving` is the reference worker it launches.
    serving: bool = False
    # Request-frontend base port (each process binds port + local_rank,
    # like the metrics endpoint; 0 = bind a free port).
    serving_port: int = 0
    # Decode-batch slot count — the continuous batch's fixed width (one
    # compiled decode program; slots retire/refill independently).
    serving_slots: int = 4
    # KV-cache capacity per slot in tokens (prompt + generation; 0 =
    # the model config's max_position_embeddings).
    serving_max_len: int = 0
    # Chunked-prefill feed width in tokens (time-to-first-token costs
    # ~P/chunk forwards; the fp32 score transient scales with it).
    serving_prefill_chunk: int = 64
    # Admission-queue capacity (0 = unbounded). At the limit submits are
    # rejected with backpressure (HTTP 503 + Retry-After) and the
    # /serving/health frame reports saturated — the readiness gate's
    # stop-routing-here signal.
    serving_queue_limit: int = 0
    # Migrate in-flight KV caches through elastic membership changes as
    # host snapshots (graceful scale up/down resumes decoding without
    # re-prefill). Off: in-flight requests re-queue from their last
    # committed token and re-prefill — same token streams either way.
    serving_migrate_kv: bool = False
    # Reference-worker model selector (gpt_tiny | gpt2 | llama_tiny).
    serving_model: str = "gpt_tiny"
    # Elastic commit cadence in engine steps (the requeue granularity: a
    # disruption replays at most this many tokens per in-flight request).
    serving_commit_steps: int = 1

    # --- request tracing + SLO burn rate (horovod_tpu/trace,
    # telemetry/slo.py; docs/observability.md) ---
    # Request/step-level span tracing: every serving request carries a
    # trace id from admission through requeue to completion, read live
    # at GET /debug/trace/<rid>; training steps trace negotiation /
    # flush / cross_wait spans. Always-on like the flight recorder (the
    # perf guard bounds the dispatch host cost at <= 2x tracing-off).
    trace: bool = True
    # Live request traces kept per process (bounded store; step traces
    # have their own smaller cap).
    trace_capacity: int = 256
    # Directory for per-rank trace shard dumps ("" = no dumps): the
    # serving frontend writes trace_r<rank>.json on stop, merged by
    # `python -m horovod_tpu.trace.analyze`.
    trace_dir: str = ""
    # Declared SLO objectives (0 = not declared): p99 TTFT target in ms
    # and a generated-tokens/sec floor. Burn rates are computed over
    # slo_window_s and exported as slo_burn_rate{objective}.
    slo_ttft_p99_ms: float = 0.0
    slo_tps: float = 0.0
    slo_window_s: float = 60.0

    # --- goodput/badput accounting + durable run history
    # (horovod_tpu/goodput; docs/observability.md "Goodput accounting").
    # Per-rank wall-clock decomposition into productive_compute vs named
    # badput categories with a 1% conservation guarantee. Always-on like
    # the flight recorder: the hot path is one boundary call per step.
    goodput: bool = True
    # Directory for per-rank goodput summary dumps at shutdown
    # (goodput_r<rank>.json; "" = no dumps).
    goodput_dir: str = ""
    # Durable cross-run history: rank 0 appends run_<id>.jsonl journals
    # (run id, config fingerprint, goodput heartbeats, bench records,
    # final cluster view) under this directory, one flushed line per
    # record so a killed run still leaves evidence. "" = off.
    run_history_dir: str = ""
    # Journal goodput-heartbeat cadence in seconds (HOROVOD_GOODPUT_JOURNAL_S):
    # how often rank 0 appends a goodput summary line, so a SIGKILLed run's
    # last record is at most this stale.
    goodput_journal_s: float = 10.0
    # Run id override (HOROVOD_RUN_ID): names the journal file
    # run_<id>.jsonl; default is a launch-time timestamp+pid. Set it when
    # an external scheduler already has a job id worth correlating on.
    run_id: str = ""

    # --- metrics / telemetry (horovod_tpu/metrics; no reference analog —
    # the reference's observability stops at timeline + stall inspector).
    # Always-on by default: the registry hot path is O(1) and lock-light
    # (guarded by tests/test_perf_guards.py TestMetricsOverheadBudget).
    metrics: bool = True
    # Scrape endpoint port; 0 = no HTTP server. Each process binds
    # port + local_rank: same-host processes must not collide, but every
    # host keeps the same base port for uniform scrape configs.
    metrics_port: int = 0
    # Scrape endpoint bind address. Prometheus-exporter convention is
    # bind-all (the payload is read-only telemetry, and an off-host
    # scraper is the point of the endpoint); set 127.0.0.1 to keep it
    # host-local on shared machines.
    metrics_addr: str = "0.0.0.0"
    # Series-name prefix in the text exposition.
    metrics_prefix: str = "horovod"

    def __post_init__(self):
        # Normalize/validate on EVERY construction path (env, CLI, direct):
        # the fusion runtime CASTS float buffers to a 16-bit wire dtype,
        # while "int8" routes the fused bucket through the two-phase
        # quantized exchange (strategies.allreduce_int8) — any other value
        # would silently destroy gradients.
        for attr in ("wire_dtype", "wire_dtype_dcn",
                     "alltoall_cross_dtype"):
            val = {"fp16": "float16",
                   "bf16": "bfloat16"}.get(getattr(self, attr),
                                           getattr(self, attr))
            setattr(self, attr, val)
            if val and val not in ("float16", "bfloat16", "int8", "fp8"):
                raise ValueError(
                    f"{attr}={val!r}: float16/bfloat16 (cast) "
                    "or int8/fp8 (block-scaled quantized exchange) are the "
                    "wire options; inside jit the same tier is reachable "
                    "via Compression.int8 on the optimizer or "
                    "strategies.allreduce_quantized")
        self.control_plane = (self.control_plane or "").strip().lower()
        if self.control_plane not in ("", "flat", "hier"):
            raise ValueError(
                f"control_plane={self.control_plane!r}: flat, hier, or "
                "empty (auto: hier when the slice layout has >1 slice)")
        if self.trace_capacity < 1:
            raise ValueError(
                f"trace_capacity={self.trace_capacity}: need >= 1 (the "
                "trace store must hold at least the request being read)")
        if self.slo_ttft_p99_ms < 0.0 or self.slo_tps < 0.0:
            raise ValueError(
                f"SLO targets must be >= 0 (0 = not declared), got "
                f"slo_ttft_p99_ms={self.slo_ttft_p99_ms}, "
                f"slo_tps={self.slo_tps}")
        if self.slo_window_s <= 0.0:
            raise ValueError(
                f"slo_window_s={self.slo_window_s}: the burn-rate "
                "window must be positive")
        # Normalize the goodput paths: surrounding whitespace in an env
        # var must not silently create a different directory, and the
        # journal requires goodput accounting (there would be nothing to
        # heartbeat into it).
        self.goodput_dir = (self.goodput_dir or "").strip()
        self.run_history_dir = (self.run_history_dir or "").strip()
        if self.run_history_dir and not self.goodput:
            raise ValueError(
                "run_history_dir requires goodput=True "
                "(HOROVOD_GOODPUT=1): the run journal's heartbeat IS the "
                "goodput summary")
        if self.goodput_journal_s <= 0.0:
            raise ValueError(
                f"goodput_journal_s={self.goodput_journal_s}: the journal "
                "heartbeat cadence must be positive")

    @classmethod
    def from_env(cls):
        c = cls()
        c.fusion_threshold = _env_int("HOROVOD_FUSION_THRESHOLD", c.fusion_threshold)
        c.cycle_time_ms = _env_float("HOROVOD_CYCLE_TIME", c.cycle_time_ms)
        c.cache_capacity = _env_int("HOROVOD_CACHE_CAPACITY", c.cache_capacity)
        c.hierarchical_allreduce = _env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE",
                                             c.hierarchical_allreduce)
        c.hierarchical_allgather = _env_bool("HOROVOD_HIERARCHICAL_ALLGATHER",
                                             c.hierarchical_allgather)
        c.torus_allreduce = _env_bool("HOROVOD_TORUS_ALLREDUCE", c.torus_allreduce)
        c.autotune = _env_bool("HOROVOD_AUTOTUNE", c.autotune)
        c.autotune_log_file = os.environ.get("HOROVOD_AUTOTUNE_LOG", c.autotune_log_file)
        c.autotune_warmup_samples = _env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES",
                                             c.autotune_warmup_samples)
        c.autotune_steps_per_sample = _env_int("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE",
                                               c.autotune_steps_per_sample)
        c.autotune_bayes_opt_max_samples = _env_int(
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", c.autotune_bayes_opt_max_samples)
        c.autotune_gaussian_process_noise = _env_float(
            "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", c.autotune_gaussian_process_noise)
        c.autopilot = _env_bool("HOROVOD_AUTOPILOT", c.autopilot)
        c.autopilot_interval = _env_float("HOROVOD_AUTOPILOT_INTERVAL",
                                          c.autopilot_interval)
        c.autopilot_max_removals = _env_int(
            "HOROVOD_AUTOPILOT_MAX_REMOVALS", c.autopilot_max_removals)
        c.autopilot_hysteresis = _env_int("HOROVOD_AUTOPILOT_HYSTERESIS",
                                          c.autopilot_hysteresis)
        c.autopilot_min_world = _env_int("HOROVOD_AUTOPILOT_MIN_WORLD",
                                         c.autopilot_min_world)
        c.autopilot_prior = os.environ.get("HOROVOD_AUTOPILOT_PRIOR",
                                           c.autopilot_prior)
        c.sim_kv_us = _env_float("HOROVOD_SIM_KV_US", c.sim_kv_us)
        c.sim_dcn_us = _env_float("HOROVOD_SIM_DCN_US", c.sim_dcn_us)
        c.timeline_filename = os.environ.get("HOROVOD_TIMELINE", c.timeline_filename)
        c.timeline_mark_cycles = _env_bool("HOROVOD_TIMELINE_MARK_CYCLES",
                                           c.timeline_mark_cycles)
        c.stall_check_disable = _env_bool("HOROVOD_STALL_CHECK_DISABLE",
                                          c.stall_check_disable)
        c.stall_check_time_seconds = _env_float("HOROVOD_STALL_CHECK_TIME_SECONDS",
                                                c.stall_check_time_seconds)
        c.stall_shutdown_time_seconds = _env_float(
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", c.stall_shutdown_time_seconds)
        c.order_check = _env_bool("HOROVOD_ORDER_CHECK", c.order_check)
        c.elastic = _env_bool("HOROVOD_ELASTIC", c.elastic)
        c.dynamic_process_sets = _env_bool("HOROVOD_DYNAMIC_PROCESS_SETS",
                                           c.dynamic_process_sets)
        c.join_mode = _env_bool("HOROVOD_JOIN_MODE", c.join_mode)
        c.rank = _env_int("HOROVOD_RANK", c.rank)
        c.local_rank = _env_int("HOROVOD_LOCAL_RANK", c.local_rank)
        c.cross_rank = _env_int("HOROVOD_CROSS_RANK", c.cross_rank)
        c.size = _env_int("HOROVOD_SIZE", c.size)
        c.local_size = _env_int("HOROVOD_LOCAL_SIZE", c.local_size)
        c.cross_size = _env_int("HOROVOD_CROSS_SIZE", c.cross_size)
        # mpirun/jsrun/srun-launched workers get no per-host HOROVOD_* rank
        # env; derive the process index from the MPI/PMI/Slurm-provided env
        # (reference: test/utils/common.py:32-64 mpi_env_rank_and_size reads
        # the same variables).
        if "HOROVOD_CROSS_RANK" not in os.environ:
            for rank_var, size_var in (
                    ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"),
                    ("PMI_RANK", "PMI_SIZE"),
                    ("SLURM_PROCID", "SLURM_NTASKS")):
                if rank_var in os.environ:
                    c.cross_rank = _env_int(rank_var, c.cross_rank)
                    c.cross_size = _env_int(size_var, c.cross_size)
                    break
        c.coordinator_addr = os.environ.get("HOROVOD_COORDINATOR_ADDR",
                                            c.coordinator_addr)
        c.coordinator_port = _env_int("HOROVOD_COORDINATOR_PORT", c.coordinator_port)
        c.wire_dtype = os.environ.get("HOROVOD_WIRE_DTYPE", c.wire_dtype)
        c.wire_dtype_dcn = os.environ.get("HOROVOD_WIRE_DTYPE_DCN",
                                          c.wire_dtype_dcn)
        c.hierarchical_dispatch = _env_bool("HOROVOD_HIERARCHICAL_DISPATCH",
                                            c.hierarchical_dispatch)
        c.hierarchical_alltoall = _env_bool("HOROVOD_HIERARCHICAL_ALLTOALL",
                                            c.hierarchical_alltoall)
        c.alltoall_cross_dtype = os.environ.get(
            "HOROVOD_ALLTOALL_CROSS_DTYPE", c.alltoall_cross_dtype)
        c.cross_overlap = _env_bool("HOROVOD_CROSS_OVERLAP",
                                    c.cross_overlap)
        c.wire_error_feedback = _env_bool("HOROVOD_WIRE_ERROR_FEEDBACK",
                                          c.wire_error_feedback)
        c.control_plane = os.environ.get("HOROVOD_CONTROL_PLANE",
                                         c.control_plane)
        c.kv_shard_count = _env_int("HOROVOD_KV_SHARD_COUNT",
                                    c.kv_shard_count)
        c.kv_shard_port_base = _env_int("HOROVOD_KV_SHARD_PORT_BASE",
                                        c.kv_shard_port_base)
        c.control_lease_ms = _env_float("HOROVOD_CONTROL_LEASE_MS",
                                        c.control_lease_ms)
        c.__post_init__()  # re-normalize after the env override
        c.donate_buffers = _env_bool("HOROVOD_DONATE_BUFFERS", c.donate_buffers)
        # Eager-path donation only on an EXPLICIT opt-in (see field docs).
        c.donate_eager = "HOROVOD_DONATE_BUFFERS" in os.environ \
            and c.donate_buffers
        c.compile_cache_dir = (
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.environ.get("HOROVOD_COMPILE_CACHE_DIR")
            or c.compile_cache_dir)
        c.kv_retries = _env_int("HOROVOD_KV_RETRIES", c.kv_retries)
        c.kv_retry_backoff_ms = _env_float("HOROVOD_KV_RETRY_BACKOFF_MS",
                                           c.kv_retry_backoff_ms)
        c.kv_retry_backoff_max_ms = _env_float(
            "HOROVOD_KV_RETRY_BACKOFF_MAX_MS", c.kv_retry_backoff_max_ms)
        c.chaos_plan = os.environ.get("HOROVOD_CHAOS_PLAN", c.chaos_plan)
        c.chaos_seed = _env_int("HOROVOD_CHAOS_SEED", c.chaos_seed)
        c.chaos_ledger = os.environ.get("HOROVOD_CHAOS_LEDGER",
                                        c.chaos_ledger)
        c.flight = _env_bool("HOROVOD_FLIGHT_RECORDER", c.flight)
        c.flight_capacity = _env_int("HOROVOD_FLIGHT_CAPACITY",
                                     c.flight_capacity)
        c.flight_dir = os.environ.get("HOROVOD_FLIGHT_DIR", c.flight_dir)
        c.step_profiler = _env_bool("HOROVOD_STEP_PROFILER",
                                    c.step_profiler)
        c.step_report_file = os.environ.get("HVD_STEP_REPORT_FILE",
                                            c.step_report_file)
        c.profile_steps = os.environ.get("HOROVOD_PROFILE_STEPS",
                                         c.profile_steps)
        c.profile_dir = os.environ.get("HOROVOD_PROFILE_DIR",
                                       c.profile_dir)
        c.profile_publish_steps = _env_int("HOROVOD_PROFILE_PUBLISH_STEPS",
                                           c.profile_publish_steps)
        c.telemetry = _env_bool("HOROVOD_TELEMETRY", c.telemetry)
        c.telemetry_interval = _env_float("HOROVOD_TELEMETRY_INTERVAL",
                                          c.telemetry_interval)
        c.telemetry_metrics = _env_bool("HOROVOD_TELEMETRY_METRICS",
                                        c.telemetry_metrics)
        c.telemetry_dead_after = _env_float("HOROVOD_TELEMETRY_DEAD_AFTER",
                                            c.telemetry_dead_after)
        c.telemetry_stall_after = _env_float(
            "HOROVOD_TELEMETRY_STALL_AFTER", c.telemetry_stall_after)
        c.telemetry_step_lag = _env_int("HOROVOD_TELEMETRY_STEP_LAG",
                                        c.telemetry_step_lag)
        c.telemetry_seq_lag = _env_int("HOROVOD_TELEMETRY_SEQ_LAG",
                                       c.telemetry_seq_lag)
        c.log_level = os.environ.get("HOROVOD_LOG_LEVEL", c.log_level)
        c.log_hide_time = _env_bool("HOROVOD_LOG_HIDE_TIME",
                                    c.log_hide_time)
        c.elastic_heartbeat_timeout = _env_int(
            "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", c.elastic_heartbeat_timeout)
        c.blacklist_cooldown_range = os.environ.get(
            "HOROVOD_BLACKLIST_COOLDOWN_RANGE", c.blacklist_cooldown_range)
        c.abort_exclude_ports = os.environ.get(
            "HOROVOD_ABORT_EXCLUDE_PORTS", c.abort_exclude_ports)
        c.mesh_slices = os.environ.get("HOROVOD_MESH_SLICES",
                                       c.mesh_slices)
        c.profile_history = _env_int("HOROVOD_PROFILE_HISTORY",
                                     c.profile_history)
        c.profile_publish_timeout_ms = _env_int(
            "HOROVOD_PROFILE_PUBLISH_TIMEOUT_MS",
            c.profile_publish_timeout_ms)
        c.profile_z_threshold = _env_float("HOROVOD_PROFILE_Z_THRESHOLD",
                                           c.profile_z_threshold)
        c.profile_straggler_min_ms = _env_float(
            "HOROVOD_PROFILE_STRAGGLER_MIN_MS", c.profile_straggler_min_ms)
        c.peak_tflops = _env_float("HOROVOD_PEAK_TFLOPS", c.peak_tflops)
        c.peak_hbm_gbs = _env_float("HOROVOD_PEAK_HBM_GBS", c.peak_hbm_gbs)
        c.peak_ici_gbs = _env_float("HOROVOD_PEAK_ICI_GBS", c.peak_ici_gbs)
        c.peak_dcn_gbs = _env_float("HOROVOD_PEAK_DCN_GBS", c.peak_dcn_gbs)
        c.flash_block = _env_int("HVD_FLASH_BLOCK", c.flash_block)
        c.dcn_bytes_budget = _env_int("HOROVOD_DCN_BYTES_BUDGET",
                                      c.dcn_bytes_budget)
        c.serving = _env_bool("HOROVOD_SERVING", c.serving)
        c.serving_port = _env_int("HOROVOD_SERVING_PORT", c.serving_port)
        c.serving_slots = _env_int("HOROVOD_SERVING_SLOTS",
                                   c.serving_slots)
        c.serving_max_len = _env_int("HOROVOD_SERVING_MAX_LEN",
                                     c.serving_max_len)
        c.serving_prefill_chunk = _env_int("HOROVOD_SERVING_PREFILL_CHUNK",
                                           c.serving_prefill_chunk)
        c.serving_queue_limit = _env_int("HOROVOD_SERVING_QUEUE_LIMIT",
                                         c.serving_queue_limit)
        c.serving_migrate_kv = _env_bool("HOROVOD_SERVING_MIGRATE_KV",
                                         c.serving_migrate_kv)
        c.serving_model = os.environ.get("HOROVOD_SERVING_MODEL",
                                         c.serving_model)
        c.serving_commit_steps = _env_int("HOROVOD_SERVING_COMMIT_STEPS",
                                          c.serving_commit_steps)
        c.trace = _env_bool("HOROVOD_TRACE", c.trace)
        c.trace_capacity = _env_int("HOROVOD_TRACE_CAPACITY",
                                    c.trace_capacity)
        c.trace_dir = os.environ.get("HOROVOD_TRACE_DIR", c.trace_dir)
        c.slo_ttft_p99_ms = _env_float("HOROVOD_SLO_TTFT_P99_MS",
                                       c.slo_ttft_p99_ms)
        c.slo_tps = _env_float("HOROVOD_SLO_TPS", c.slo_tps)
        c.slo_window_s = _env_float("HOROVOD_SLO_WINDOW_S",
                                    c.slo_window_s)
        c.goodput = _env_bool("HOROVOD_GOODPUT", c.goodput)
        c.goodput_dir = os.environ.get("HOROVOD_GOODPUT_DIR",
                                       c.goodput_dir)
        c.run_history_dir = os.environ.get("HOROVOD_RUN_HISTORY_DIR",
                                           c.run_history_dir)
        c.goodput_journal_s = _env_float("HOROVOD_GOODPUT_JOURNAL_S",
                                         c.goodput_journal_s)
        c.run_id = os.environ.get("HOROVOD_RUN_ID", c.run_id)
        c.__post_init__()  # re-validate: goodput paths read after the
        # control-plane re-normalization above
        c.metrics = _env_bool("HOROVOD_METRICS", c.metrics)
        c.metrics_port = _env_int("HOROVOD_METRICS_PORT", c.metrics_port)
        c.metrics_addr = os.environ.get("HOROVOD_METRICS_ADDR",
                                        c.metrics_addr)
        c.metrics_prefix = os.environ.get("HOROVOD_METRICS_PREFIX",
                                          c.metrics_prefix)
        return c

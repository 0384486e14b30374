"""``hvdrun`` — the launcher CLI.

Reference: horovod/runner/launch.py (``horovodrun``; arg surface :286-596,
``_run``:806, ``run_commandline``:830). Differences by design:

- No gloo/mpi/jsrun controller selection — the data plane is XLA over ICI/DCN
  and bootstrap is ``jax.distributed``; the launcher only chooses hosts.
- One worker process per *host* (it owns all local chips), not per slot.
- The rendezvous HTTP-KV server still exists, serving elastic membership and
  out-of-band metadata (reference: RendezvousServer http_server.py:192).

Example::

    hvdrun -np 8 -H host1:4,host2:4 python train.py
    hvdrun -np 2 --min-np 1 --max-np 4 --host-discovery-script ./disc.sh \
        python train.py     # elastic
"""

import argparse
import os
import socket
import sys

from horovod_tpu.common import logging as hvd_logging
from horovod_tpu.runner import config_parser
from horovod_tpu.runner.exec import (WorkerProcess,
                                     wait_for_any_failure_or_all_success)
from horovod_tpu.runner.hosts import (get_host_assignments,
                                      host_assignment_by_host, parse_host_files,
                                      parse_hosts)
from horovod_tpu.runner.http_kv import KVStoreServer
from horovod_tpu.runner.secret import SECRET_ENV, make_secret_key


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch TPU-native Horovod training across hosts.")
    p.add_argument("-v", "--version", action="store_true")
    p.add_argument("-np", "--num-proc", dest="np", type=int,
                   help="Total number of chips (ranks).")
    p.add_argument("-H", "--hosts", dest="hosts",
                   help="host1:chips,host2:chips list.")
    p.add_argument("-hostfile", "--hostfile", dest="hostfile",
                   help="Hostfile with 'host slots=N' lines.")
    p.add_argument("--ssh-port", type=int, dest="ssh_port")
    p.add_argument("--ssh-identity-file", dest="ssh_identity_file")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--config-file", dest="config_file")
    p.add_argument("--check-build", action="store_true")
    p.add_argument("--start-timeout", type=int, default=600,
                   dest="start_timeout")
    p.add_argument("--disable-cache", action="store_true")
    p.add_argument("--launcher", dest="launcher", default="auto",
                   choices=["auto", "ssh", "mpi", "jsrun"],
                   help="Process launcher: ssh fan-out (default), mpirun, or "
                        "jsrun on LSF. 'auto' picks jsrun inside an LSF "
                        "allocation with jsrun available, else ssh. "
                        "(reference: horovodrun --gloo/--mpi/... selection, "
                        "launch.py:286-596 + js_run path)")
    # Launcher-selection aliases (reference: --gloo/--mpi/--jsrun,
    # launch.py:286-596). "gloo" maps to the native ssh/KV rendezvous path —
    # the role gloo plays in the reference.
    p.add_argument("--gloo", action="store_true", dest="use_gloo")
    p.add_argument("--mpi", action="store_true", dest="use_mpi")
    p.add_argument("--jsrun", action="store_true", dest="use_jsrun")
    p.add_argument("--network-interface", "--network-interfaces",
                   dest="nics",
                   help="Comma-separated NICs workers may bind/probe on")
    p.add_argument("--output-filename", dest="output_filename",
                   help="Mirror each worker's output to "
                        "<dir>/rank.<NN>/stdout")
    p.add_argument("--prefix-output-with-timestamp", action="store_true",
                   dest="prefix_output_with_timestamp")
    p.add_argument("--tcp", action="store_true", dest="tcp_flag",
                   help="Accepted for compatibility (TPU data plane is ICI)")
    p.add_argument("--num-nccl-streams", type=int, dest="num_nccl_streams",
                   help="Accepted for compatibility; n/a on TPU")
    p.add_argument("--thread-affinity", type=int, dest="thread_affinity")
    p.add_argument("--binding-args", dest="binding_args")
    p.add_argument("--mpi-threads-disable", action="store_true",
                   dest="mpi_threads_disable", default=None)
    p.add_argument("--no-mpi-threads-disable", action="store_false",
                   dest="mpi_threads_disable")
    p.add_argument("--gloo-timeout-seconds", type=int,
                   dest="gloo_timeout_seconds")
    p.add_argument("--mpi-args", dest="mpi_args", default="",
                   help="Extra args appended to mpirun/jsrun.")

    tuning = p.add_argument_group("tuning")
    tuning.add_argument("--fusion-threshold-mb", type=float,
                        dest="fusion_threshold_mb")
    tuning.add_argument("--cycle-time-ms", type=float, dest="cycle_time_ms")
    tuning.add_argument("--cache-capacity", type=int, dest="cache_capacity")
    tuning.add_argument("--no-hierarchical-allreduce", action="store_false",
                        dest="hierarchical_allreduce", default=False)
    tuning.add_argument("--no-hierarchical-allgather", action="store_false",
                        dest="hierarchical_allgather", default=False)
    tuning.add_argument("--no-torus-allreduce", action="store_false",
                        dest="torus_allreduce", default=False)
    tuning.add_argument("--hierarchical-allreduce", action="store_true",
                        dest="hierarchical_allreduce")
    tuning.add_argument("--hierarchical-allgather", action="store_true",
                        dest="hierarchical_allgather")
    tuning.add_argument("--torus-allreduce", action="store_true",
                        dest="torus_allreduce",
                        help="2-level ICI/DCN torus allreduce "
                             "(fork knob HOROVOD_TORUS_ALLREDUCE)")
    tuning.add_argument("--wire-dtype", dest="wire_dtype",
                        choices=["", "bfloat16", "float16", "bf16", "fp16",
                                 "int8", "fp8"],
                        help="Collective wire dtype: 16-bit casts the fused "
                             "buckets; int8/fp8 ride the block-scaled "
                             "quantized exchange with error feedback "
                             "(HOROVOD_WIRE_DTYPE; docs/performance.md)")
    tuning.add_argument("--hierarchical-alltoall", action="store_true",
                        dest="hierarchical_alltoall",
                        help="2-level ICI/DCN alltoall for eligible "
                             "equal-splits exchanges and MoE expert "
                             "dispatch/combine when a slice hierarchy "
                             "exists (HOROVOD_HIERARCHICAL_ALLTOALL; "
                             "docs/performance.md)")
    tuning.add_argument("--alltoall-cross-dtype",
                        dest="alltoall_cross_dtype",
                        choices=["", "bfloat16", "float16", "bf16", "fp16",
                                 "int8", "fp8"],
                        help="Wire dtype of the hierarchical alltoall's "
                             "cross-slice (DCN) leg; int8/fp8 ride the "
                             "block-scaled exchange, 16-bit names keep it "
                             "exact (HOROVOD_ALLTOALL_CROSS_DTYPE). "
                             "Deliberately independent of --wire-dtype: "
                             "alltoall payloads are activations.")
    tuning.add_argument("--no-wire-error-feedback", action="store_true",
                        dest="no_wire_error_feedback",
                        help="Disable the quantized wire's error-feedback "
                             "residuals (HOROVOD_WIRE_ERROR_FEEDBACK=0)")
    tuning.add_argument("--control-plane", dest="control_plane",
                        choices=["flat", "hier"],
                        help="Control-plane strategy "
                             "(HOROVOD_CONTROL_PLANE): hier decomposes "
                             "negotiation + fusion-boundary sync into "
                             "slice-local rounds with leaders-only "
                             "cross-slice (DCN) rendezvous and shards "
                             "the HTTP-KV per slice; default is hier "
                             "whenever the slice layout has >1 slice. "
                             "See docs/scale_validation.md.")
    tuning.add_argument("--kv-shard-count", type=int,
                        dest="kv_shard_count",
                        help="Per-slice HTTP-KV shard listeners "
                             "(HOROVOD_KV_SHARD_COUNT; 0 = one per "
                             "slice when the hierarchical control "
                             "plane is armed).")
    tuning.add_argument("--kv-shard-port-base", type=int,
                        dest="kv_shard_port_base",
                        help="First shard listener port; shard k binds "
                             "base + k (HOROVOD_KV_SHARD_PORT_BASE; "
                             "0 = ephemeral).")
    tuning.add_argument("--control-lease-ms", type=float,
                        dest="control_lease_ms",
                        help="Boundary-stream leader lease in ms "
                             "(HOROVOD_CONTROL_LEASE_MS): a member "
                             "whose slice leader goes stale past this "
                             "window takes the re-publish over.")
    tuning.add_argument("--compile-cache-dir", dest="compile_cache_dir",
                        help="Persistent XLA compile-cache directory "
                             "exported to every worker "
                             "(HOROVOD_COMPILE_CACHE_DIR; "
                             "JAX_COMPILATION_CACHE_DIR wins when set). "
                             "Default: <checkout>/.horovod_compile_cache "
                             "on each host.")

    autotune = p.add_argument_group("autotune")
    autotune.add_argument("--autotune", action="store_true", dest="autotune")
    autotune.add_argument("--no-autotune", action="store_false",
                          dest="autotune")
    autotune.add_argument("--autotune-log-file", dest="autotune_log_file")
    autotune.add_argument("--autotune-warmup-samples", type=int,
                          dest="autotune_warmup_samples")
    autotune.add_argument("--autotune-steps-per-sample", type=int,
                          dest="autotune_steps_per_sample")
    autotune.add_argument("--autotune-bayes-opt-max-samples", type=int,
                          dest="autotune_bayes_opt_max_samples")
    autotune.add_argument("--autotune-gaussian-process-noise", type=float,
                          dest="autotune_gaussian_process_noise")

    autopilot = p.add_argument_group("autopilot")
    autopilot.add_argument("--autopilot", action="store_true",
                           dest="autopilot", default=False,
                           help="Arm the online self-driving controller "
                                "(HOROVOD_AUTOPILOT=1): closed-loop "
                                "tuning of fusion threshold/cycle, "
                                "dispatch strategy and wire dtype from "
                                "the signal plane, plus automated "
                                "straggler/dead-rank blacklist + "
                                "re-rendezvous under elastic launches. "
                                "See docs/performance.md.")
    autopilot.add_argument("--no-autopilot", action="store_true",
                           dest="no_autopilot",
                           help="Explicitly disarm the autopilot "
                                "(HOROVOD_AUTOPILOT=0), overriding an "
                                "ambient env opt-in.")
    autopilot.add_argument("--autopilot-interval", type=float,
                           dest="autopilot_interval",
                           help="Decision-epoch cadence in seconds "
                                "(HOROVOD_AUTOPILOT_INTERVAL, "
                                "default 10).")
    autopilot.add_argument("--autopilot-prior", type=str,
                           dest="autopilot_prior",
                           help="Twin-pretrained warm start: path to an "
                                "export_observations JSON artifact "
                                "written by horovod_tpu.sim.autopilot "
                                "(HOROVOD_AUTOPILOT_PRIOR). The "
                                "controller skips the categorical sweep "
                                "and starts the numeric search at the "
                                "twin's best point; a mismatched prior "
                                "is rejected with a warning, never "
                                "fatal. See docs/scale_validation.md.")

    tracing = p.add_argument_group("tracing")
    tracing.add_argument("--trace", action="store_true", dest="trace",
                         default=False,
                         help="Arm request/step span tracing "
                              "(HOROVOD_TRACE=1; the default — this flag "
                              "overrides an ambient env opt-out). Serving "
                              "requests expose their span tree at "
                              "GET /debug/trace/<rid>; merge per-rank "
                              "shards with `python -m "
                              "horovod_tpu.trace.analyze`.")
    tracing.add_argument("--no-trace", action="store_true",
                         dest="no_trace",
                         help="Disarm tracing (HOROVOD_TRACE=0).")
    tracing.add_argument("--trace-dir", dest="trace_dir",
                         help="Directory for per-rank trace shard dumps "
                              "(HOROVOD_TRACE_DIR).")

    goodput = p.add_argument_group("goodput accounting")
    goodput.add_argument("--goodput", action="store_true", dest="goodput",
                         default=False,
                         help="Arm job goodput/badput accounting "
                              "(HOROVOD_GOODPUT=1; the default — this "
                              "flag overrides an ambient env opt-out). "
                              "See docs/observability.md.")
    goodput.add_argument("--no-goodput", action="store_true",
                         dest="no_goodput",
                         help="Disarm goodput accounting "
                              "(HOROVOD_GOODPUT=0).")
    goodput.add_argument("--goodput-dir", dest="goodput_dir",
                         help="Directory for per-rank goodput summary "
                              "dumps at exit (HOROVOD_GOODPUT_DIR).")
    goodput.add_argument("--run-history-dir", dest="run_history_dir",
                         help="Durable cross-run history root "
                              "(HOROVOD_RUN_HISTORY_DIR): rank 0 appends "
                              "a per-run JSONL journal here; render and "
                              "regress with `python -m "
                              "horovod_tpu.goodput.report`.")

    timeline = p.add_argument_group("timeline")
    timeline.add_argument("--timeline-filename", dest="timeline_filename")
    timeline.add_argument("--no-timeline-mark-cycles", action="store_false",
                          dest="timeline_mark_cycles", default=False)
    timeline.add_argument("--timeline-mark-cycles", action="store_true",
                          dest="timeline_mark_cycles")

    stall = p.add_argument_group("stall")
    stall.add_argument("--stall-check", action="store_false",
                       dest="no_stall_check", default=False)
    stall.add_argument("--no-stall-check", action="store_true",
                       dest="no_stall_check")
    stall.add_argument("--stall-check-warning-time-seconds", type=float,
                       dest="stall_check_warning_time_seconds")
    stall.add_argument("--stall-check-shutdown-time-seconds", type=float,
                       dest="stall_check_shutdown_time_seconds")
    stall.add_argument("--order-check", action="store_true",
                       dest="order_check", default=False,
                       help="debug: cross-check every eager collective's "
                            "op/shape/dtype signature across ranks before "
                            "dispatch (catches SPMD order divergence as an "
                            "error instead of a hang)")

    flight = p.add_argument_group("flight recorder")
    flight.add_argument("--flight-dir", dest="flight_dir",
                        help="Directory for per-rank flight-recorder crash "
                             "dumps (HOROVOD_FLIGHT_DIR), exported to every "
                             "worker so an elastic disruption collects all "
                             "ranks' dumps in one place for "
                             "`python -m horovod_tpu.flight.analyze`. "
                             "See docs/observability.md.")
    flight.add_argument("--no-flight-recorder", action="store_true",
                        dest="no_flight_recorder",
                        help="Disable the always-armed flight recorder "
                             "(HOROVOD_FLIGHT_RECORDER=0).")

    profiler = p.add_argument_group("step profiler")
    profiler.add_argument("--no-step-profiler", action="store_true",
                          dest="no_step_profiler",
                          help="Disable the always-on step profiler "
                               "(HOROVOD_STEP_PROFILER=0).")
    profiler.add_argument("--step-report-file", dest="step_report_file",
                          help="Per-step attribution JSONL stream "
                               "(HVD_STEP_REPORT_FILE), exported to every "
                               "worker; render with `python -m "
                               "horovod_tpu.profile.report`.")
    profiler.add_argument("--profile-steps", dest="profile_steps",
                          help="a:b — capture a jax.profiler trace from "
                               "the step-a marker to the step-b marker "
                               "(HOROVOD_PROFILE_STEPS).")
    profiler.add_argument("--profile-dir", dest="profile_dir",
                          help="Trace-capture output directory "
                               "(HOROVOD_PROFILE_DIR).")
    profiler.add_argument("--profile-publish-steps", type=int,
                          dest="profile_publish_steps",
                          help="Watchdog cross-rank publish cadence in "
                               "steps (HOROVOD_PROFILE_PUBLISH_STEPS; "
                               "0 = local-only).")

    telemetry = p.add_argument_group("cluster telemetry")
    telemetry.add_argument("--no-telemetry", action="store_true",
                           dest="no_telemetry",
                           help="Disable the hierarchical cluster "
                                "telemetry plane (HOROVOD_TELEMETRY=0). "
                                "See docs/observability.md.")
    telemetry.add_argument("--telemetry-interval", type=float,
                           dest="telemetry_interval",
                           help="Telemetry beacon/aggregation cadence in "
                                "seconds (HOROVOD_TELEMETRY_INTERVAL, "
                                "default 2.0). Health thresholds derive "
                                "from it unless overridden.")

    chaos = p.add_argument_group("chaos")
    chaos.add_argument("--chaos-plan", dest="chaos_plan",
                       help="Fault-injection plan exported to every worker "
                            "(HOROVOD_CHAOS_PLAN): a YAML/JSON file path "
                            "(must be readable on the worker hosts) or "
                            "inline YAML/JSON. See docs/robustness.md.")
    chaos.add_argument("--chaos-seed", type=int, dest="chaos_seed",
                       help="Seed overriding the plan's own "
                            "(HOROVOD_CHAOS_SEED) — probabilistic triggers "
                            "are a counter-hash of it, so a seed pins the "
                            "whole injection schedule.")
    chaos.add_argument("--chaos-ledger", dest="chaos_ledger",
                       help="Directory for the per-rank JSONL injection "
                            "ledgers (HOROVOD_CHAOS_LEDGER).")

    serving = p.add_argument_group("serving")
    serving.add_argument("--serving", action="store_true", dest="serving",
                         default=False,
                         help="Serving mode (HOROVOD_SERVING=1): workers "
                              "run the continuous-batching inference "
                              "engine instead of a training loop — e.g. "
                              "`hvdrun --serving -np 8 python -m "
                              "horovod_tpu.serving`. Composes with the "
                              "elastic flags for zero-drop rolling "
                              "restarts (docs/inference.md).")
    serving.add_argument("--serving-port", type=int, dest="serving_port",
                         help="Request-frontend base port "
                              "(HOROVOD_SERVING_PORT); each process binds "
                              "port + local_rank, like --metrics-port.")
    serving.add_argument("--serving-slots", type=int, dest="serving_slots",
                         help="Decode-batch slot count "
                              "(HOROVOD_SERVING_SLOTS; the continuous "
                              "batch's fixed width).")
    serving.add_argument("--serving-queue-limit", type=int,
                         dest="serving_queue_limit",
                         help="Admission-queue capacity "
                              "(HOROVOD_SERVING_QUEUE_LIMIT; 0 = "
                              "unbounded). At the limit the frontend "
                              "answers 503 and /serving/health reports "
                              "saturated.")

    elastic = p.add_argument_group("elastic")
    elastic.add_argument("--min-np", "--min-num-proc", type=int,
                         dest="min_np")
    elastic.add_argument("--max-np", "--max-num-proc", type=int,
                         dest="max_np")
    elastic.add_argument("--elastic-timeout", type=int,
                         dest="elastic_timeout")
    elastic.add_argument("--blacklist-cooldown-range", nargs=2, type=float,
                         dest="blacklist_cooldown_range",
                         help="Base and cap (seconds) of the per-host "
                              "blacklist exponential cooldown")
    elastic.add_argument("--slots-per-host", type=int, dest="slots_per_host")
    elastic.add_argument("--host-discovery-script",
                         dest="host_discovery_script")
    elastic.add_argument("--reset-limit", type=int, dest="reset_limit")

    logg = p.add_argument_group("logging")
    logg.add_argument("--log-level", dest="log_level",
                      choices=["trace", "debug", "info", "warning", "error",
                               "fatal"])
    logg.add_argument("--log-without-timestamp", action="store_true",
                      dest="log_hide_timestamp")
    logg.add_argument("--log-with-timestamp", "--no-log-hide-timestamp",
                      action="store_false", dest="log_hide_timestamp")
    logg.add_argument("--log-hide-timestamp", action="store_true",
                      dest="log_hide_timestamp")

    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Training command, e.g. python train.py")
    args = p.parse_args(argv)
    if args.config_file:
        config_parser.parse_config_file(args, args.config_file)
    # Launcher-selection aliases override --launcher auto-detection.
    if getattr(args, "use_mpi", False):
        args.launcher = "mpi"
    elif getattr(args, "use_jsrun", False):
        args.launcher = "jsrun"
    elif getattr(args, "use_gloo", False) and args.launcher == "auto":
        args.launcher = "ssh"
    return args


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _resolve_hosts(args):
    if args.hostfile:
        return parse_host_files(args.hostfile)
    if args.hosts:
        return parse_hosts(args.hosts)
    # Inside an LSF allocation with no explicit hosts, use the allocation
    # (reference: launch.py LSF default via util/lsf.py).
    from horovod_tpu.runner import lsf
    if lsf.using_lsf():
        return parse_hosts(lsf.lsf_hosts_string())
    # Default: all local chips, single host (reference defaults to
    # localhost:np, launch.py).
    nlocal = args.np or 1
    return parse_hosts(f"localhost:{nlocal}")


def _resolve_launcher(args):
    if getattr(args, "launcher", "auto") != "auto":
        return args.launcher
    # jsrun places tasks according to the LSF allocation, so auto-selecting it
    # is only valid when the user did not name hosts explicitly.
    if not args.hosts and not args.hostfile:
        from horovod_tpu.runner import lsf
        if lsf.using_jsrun():
            return "jsrun"
    return "ssh"


def build_worker_env(base_env, slot_infos_for_host, coordinator_addr,
                     coordinator_port, kv_port, args,
                     kv_shard_ports=None):
    """Per-host env (reference: gloo_run.py:66-78, 203-227 — the rank/size env
    contract between launcher and core)."""
    first = slot_infos_for_host[0]
    env = dict(base_env)
    env.update({
        "HOROVOD_RANK": str(first.rank),
        "HOROVOD_SIZE": str(first.size),
        "HOROVOD_LOCAL_RANK": str(first.local_rank),
        "HOROVOD_LOCAL_SIZE": str(first.local_size),
        "HOROVOD_CROSS_RANK": str(first.cross_rank),
        "HOROVOD_CROSS_SIZE": str(first.cross_size),
        "HOROVOD_COORDINATOR_ADDR": coordinator_addr,
        "HOROVOD_COORDINATOR_PORT": str(coordinator_port),
        "HOROVOD_KV_ADDR": coordinator_addr,
        "HOROVOD_KV_PORT": str(kv_port),
    })
    # Sharded KV plane: slice-local scopes resolve to these per-slice
    # listeners through the KVStoreClient scope router (the hierarchical
    # control plane's HTTP tier — common/control_plane.py).
    if kv_shard_ports:
        env["HOROVOD_KV_SHARD_PORTS"] = ",".join(
            str(p) for p in kv_shard_ports)
    if os.environ.get(SECRET_ENV):
        env[SECRET_ENV] = os.environ[SECRET_ENV]
    # Flight-recorder collection point: every worker dumps into the same
    # directory so a disruption leaves one analyzable set of per-rank
    # rings (flight.analyze merges them). Elastic launches default it —
    # that is exactly the launch mode whose failures need forensics.
    flight_dir = os.environ.get("HOROVOD_FLIGHT_DIR") \
        or getattr(args, "flight_dir", None)
    if not flight_dir and env.get("HOROVOD_ELASTIC"):
        from horovod_tpu.flight.recorder import default_collection_dir
        flight_dir = default_collection_dir(
            getattr(args, "output_filename", None))
    if flight_dir:
        env.setdefault("HOROVOD_FLIGHT_DIR", flight_dir)
    if os.environ.get("HOROVOD_FLIGHT_RECORDER"):
        env.setdefault("HOROVOD_FLIGHT_RECORDER",
                       os.environ["HOROVOD_FLIGHT_RECORDER"])
    # Step-profiler knobs ride through to every worker (the ledger/
    # watchdog/capture run per process; the JSONL stream and capture dirs
    # are shared collection points like the flight dir).
    for var in ("HOROVOD_STEP_PROFILER", "HVD_STEP_REPORT_FILE",
                "HOROVOD_PROFILE_STEPS", "HOROVOD_PROFILE_DIR",
                "HOROVOD_PROFILE_PUBLISH_STEPS"):
        if os.environ.get(var):
            env.setdefault(var, os.environ[var])
    # Cluster-telemetry knobs + the virtual-slice override (slice
    # membership and health thresholds must agree across ranks), and every
    # remaining declared knob (common/config.py::Config) — logging,
    # elastic control, profiler tuning, roofline peaks, flash kernels and
    # the bench progress stream — ride through so a knob set on the
    # launcher is never silently single-process. scripts/lint.py (HVL002)
    # pins the "declared implies propagated" contract this loop exists
    # for.
    for var in ("HOROVOD_TELEMETRY", "HOROVOD_TELEMETRY_INTERVAL",
                "HOROVOD_TELEMETRY_METRICS", "HOROVOD_TELEMETRY_DEAD_AFTER",
                "HOROVOD_TELEMETRY_STALL_AFTER",
                "HOROVOD_TELEMETRY_STEP_LAG", "HOROVOD_TELEMETRY_SEQ_LAG",
                "HOROVOD_MESH_SLICES",
                "HOROVOD_LOG_LEVEL", "HOROVOD_LOG_HIDE_TIME",
                "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT",
                "HOROVOD_BLACKLIST_COOLDOWN_RANGE",
                "HOROVOD_ABORT_EXCLUDE_PORTS",
                "HOROVOD_PROFILE_HISTORY",
                "HOROVOD_PROFILE_PUBLISH_TIMEOUT_MS",
                "HOROVOD_PROFILE_Z_THRESHOLD",
                "HOROVOD_PROFILE_STRAGGLER_MIN_MS",
                "HOROVOD_PEAK_TFLOPS", "HOROVOD_PEAK_HBM_GBS",
                "HOROVOD_PEAK_ICI_GBS", "HOROVOD_PEAK_DCN_GBS",
                "HVD_FLASH_BLOCK",
                "HVD_BENCH_PROGRESS_FILE", "HOROVOD_DCN_BYTES_BUDGET",
                "HOROVOD_WIRE_DTYPE", "HOROVOD_WIRE_ERROR_FEEDBACK",
                "HOROVOD_WIRE_DTYPE_DCN", "HOROVOD_HIERARCHICAL_DISPATCH",
                "HOROVOD_CROSS_OVERLAP", "HOROVOD_HIERARCHICAL_ALLTOALL",
                "HOROVOD_ALLTOALL_CROSS_DTYPE",
                "HOROVOD_CONTROL_PLANE", "HOROVOD_KV_SHARD_COUNT",
                "HOROVOD_KV_SHARD_PORT_BASE", "HOROVOD_CONTROL_LEASE_MS",
                "HOROVOD_AUTOPILOT", "HOROVOD_AUTOPILOT_INTERVAL",
                "HOROVOD_AUTOPILOT_MAX_REMOVALS",
                "HOROVOD_AUTOPILOT_HYSTERESIS",
                "HOROVOD_AUTOPILOT_MIN_WORLD",
                "HOROVOD_AUTOPILOT_PRIOR",
                "HOROVOD_SIM_KV_US", "HOROVOD_SIM_DCN_US",
                "HOROVOD_SERVING", "HOROVOD_SERVING_PORT",
                "HOROVOD_SERVING_SLOTS", "HOROVOD_SERVING_MAX_LEN",
                "HOROVOD_SERVING_PREFILL_CHUNK",
                "HOROVOD_SERVING_QUEUE_LIMIT",
                "HOROVOD_SERVING_MIGRATE_KV", "HOROVOD_SERVING_MODEL",
                "HOROVOD_SERVING_COMMIT_STEPS",
                "HOROVOD_TRACE", "HOROVOD_TRACE_CAPACITY",
                "HOROVOD_TRACE_DIR",
                "HOROVOD_DONATE_BUFFERS", "HOROVOD_DYNAMIC_PROCESS_SETS",
                "HOROVOD_JOIN_MODE", "HOROVOD_FLIGHT_CAPACITY",
                "HOROVOD_KV_RETRIES", "HOROVOD_KV_RETRY_BACKOFF_MS",
                "HOROVOD_KV_RETRY_BACKOFF_MAX_MS",
                "HOROVOD_SLO_TTFT_P99_MS", "HOROVOD_SLO_TPS",
                "HOROVOD_SLO_WINDOW_S",
                "HOROVOD_GOODPUT", "HOROVOD_GOODPUT_DIR",
                "HOROVOD_GOODPUT_JOURNAL_S", "HOROVOD_RUN_HISTORY_DIR",
                "HOROVOD_RUN_ID",
                "HOROVOD_METRICS", "HOROVOD_METRICS_PORT",
                "HOROVOD_METRICS_ADDR", "HOROVOD_METRICS_PREFIX",
                # An explicit compile-cache directory rides to every
                # worker; with none, each uses the fixed path in its own
                # checkout (common/config.py DEFAULT_COMPILE_CACHE_DIR).
                "JAX_COMPILATION_CACHE_DIR", "HOROVOD_COMPILE_CACHE_DIR"):
        if os.environ.get(var):
            env.setdefault(var, os.environ[var])
    # On the virtual-CPU tier (tests, dry runs) a rank is a virtual XLA CPU
    # device: pin each worker's device count to its slot count so the world
    # size equals the requested slots regardless of ambient XLA_FLAGS.
    ambient = {**os.environ, **env}
    if ambient.get("JAX_PLATFORMS", "").startswith("cpu"):
        flags = [f for f in ambient.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count="
                     f"{len(slot_infos_for_host)}")
        env["XLA_FLAGS"] = " ".join(flags)
    config_parser.set_env_from_args(env, args)
    return env


def _start_rendezvous(args):
    """Shared bootstrap for all static launchers: host assignment, coordinator
    address/port selection, KV server (reference: RendezvousServer start in
    launch_gloo, gloo_run.py:242-260)."""
    hosts = _resolve_hosts(args)
    slot_infos = get_host_assignments(hosts, args.np or None)
    by_host = host_assignment_by_host(slot_infos)
    coordinator_addr = socket.gethostname() \
        if len(by_host) > 1 else "localhost"
    coordinator_port = _free_port()
    # Mint a per-job secret so all KV control-plane traffic is HMAC-signed
    # (reference: secret.py per-job key + network.py:306 signed messages).
    os.environ.setdefault(SECRET_ENV, make_secret_key())
    # Per-slice shard listeners when the hierarchical control plane is
    # armed (HOROVOD_CONTROL_PLANE + slice layout / explicit
    # HOROVOD_KV_SHARD_COUNT): slice-local scopes never touch the root
    # listener, so no single HTTP socket carries O(world) traffic.
    from horovod_tpu.common import control_plane as _cp
    from horovod_tpu.common.config import _env_int
    kv = KVStoreServer(
        shards=_cp.kv_shard_count(slot_infos[0].size),
        shard_port_base=_env_int("HOROVOD_KV_SHARD_PORT_BASE", 0))
    kv_port = kv.start()
    kv.put("global", "size", str(slot_infos[0].size).encode())
    return slot_infos, by_host, coordinator_addr, coordinator_port, kv, kv_port


def _run_static_mpi(args, launcher, extra_env=None):
    """mpirun/jsrun fan-out: a single launcher invocation starts one worker
    per host; workers derive their process index from the MPI-provided env
    (OMPI_COMM_WORLD_RANK / PMI_RANK, see Config.from_env fallbacks) instead
    of per-host HOROVOD_CROSS_RANK."""
    from horovod_tpu.runner import js_run as js_mod
    from horovod_tpu.runner import mpi_run as mpi_mod

    slot_infos, by_host, coordinator_addr, coordinator_port, kv, kv_port = \
        _start_rendezvous(args)
    first = slot_infos[0]

    env = dict(extra_env or {})
    env.update({
        "HOROVOD_SIZE": str(first.size),
        "HOROVOD_LOCAL_SIZE": str(first.local_size),
        "HOROVOD_CROSS_SIZE": str(len(by_host)),
        "HOROVOD_COORDINATOR_ADDR": coordinator_addr,
        "HOROVOD_COORDINATOR_PORT": str(coordinator_port),
        "HOROVOD_KV_ADDR": coordinator_addr,
        "HOROVOD_KV_PORT": str(kv_port),
    })
    if kv.shard_ports:
        env["HOROVOD_KV_SHARD_PORTS"] = ",".join(
            str(p) for p in kv.shard_ports)
    if os.environ.get(SECRET_ENV):
        env[SECRET_ENV] = os.environ[SECRET_ENV]
    config_parser.set_env_from_args(env, args)
    import shlex
    extra = shlex.split(args.mpi_args) if getattr(args, "mpi_args", "") \
        else None
    host_slots = [(h, slots[0].local_size) for h, slots in by_host.items()]
    try:
        if launcher == "jsrun":
            return js_mod.js_run(host_slots, env, args.command,
                                 extra_js_args=extra)
        return mpi_mod.mpi_run(host_slots, env, args.command,
                               extra_mpi_args=extra)
    finally:
        kv.stop()


def _bootstrap_watchdog(kv, expected_cross_ranks, warn_after=45.0):
    """Diagnose dead launches early: workers register a reachability probe
    in the KV before collective init (runner/task.py _register_bootstrap,
    the analog of the reference's NIC probing task_fn.py:23-54). If some
    never do, warn naming the missing host slots — long before
    jax.distributed's multi-minute init timeout expires silently."""
    import threading as _threading
    import time as _time

    done = _threading.Event()

    def watch():
        deadline = _time.time() + warn_after
        missing = set(expected_cross_ranks)
        while missing and _time.time() < deadline:
            for r in list(missing):
                if kv.get("bootstrap", str(r)) is not None:
                    missing.discard(r)
            if done.wait(1.0):
                return  # run finished: a missing probe is moot, not a fault
        if missing and not done.is_set():
            hvd_logging.warning(
                "no bootstrap probe from host slot(s) %s after %.0fs — "
                "check ssh/network reachability from those hosts to the "
                "driver (KV port)", sorted(missing), warn_after)

    t = _threading.Thread(target=watch, daemon=True)
    t.start()
    t.cancel = done.set
    return t


def _run_static(args, extra_env=None, harvest=None, kv_preload=None):
    slot_infos, by_host, coordinator_addr, coordinator_port, kv, kv_port = \
        _start_rendezvous(args)
    for (scope, key), value in (kv_preload or {}).items():
        kv.put(scope, key, value)

    workers = []
    try:
        for host, slots in by_host.items():
            env = build_worker_env(dict(extra_env or {}), slots,
                                   coordinator_addr, coordinator_port,
                                   kv_port, args,
                                   kv_shard_ports=kv.shard_ports)
            workers.append(WorkerProcess(
                host, args.command, env, tag=f"{host}",
                ssh_port=args.ssh_port,
                ssh_identity_file=args.ssh_identity_file,
                output_dir=getattr(args, "output_filename", None),
                rank=slots[0].rank,
                prefix_timestamp=getattr(args, "prefix_output_with_timestamp",
                                         False)))
        expected_slots = [slots[0].cross_rank for slots in by_host.values()]
        watchdog = _bootstrap_watchdog(kv, expected_slots)
        failures = wait_for_any_failure_or_all_success(workers)
        watchdog.cancel()
        if failures:
            hvd_logging.error("workers failed: %s", failures)
            # Immediate reachability diagnosis (the watchdog was cancelled):
            # slots that never probed in likely couldn't reach the driver.
            missing = [r for r in expected_slots
                       if kv.get("bootstrap", str(r)) is None]
            if missing:
                hvd_logging.error(
                    "host slot(s) %s never reached the driver control "
                    "plane — check ssh/network from those hosts to the "
                    "driver (KV port)", sorted(missing))
            return 1
        if harvest is not None:
            harvest(kv)
        return 0
    finally:
        # Reap surviving workers on ANY exit path: an exception propagating
        # out of the wait (driver-side timeout/interrupt) must not leave
        # orphaned worker processes running — possibly blocked inside a
        # device collective that outlives the KV store (reference:
        # gloo_run terminates the job on driver exit). No-op for workers
        # that already exited.
        try:
            for w in workers:
                try:
                    w.terminate()
                except Exception:  # noqa: BLE001 — best-effort reaping
                    pass
        finally:
            kv.stop()


def _run_elastic(args):
    from horovod_tpu.runner.elastic.driver import run_elastic_driver
    return run_elastic_driver(args)


def run_commandline(argv=None):
    args = parse_args(argv)
    if args.version:
        from horovod_tpu.version import __version__
        print(__version__)
        return 0
    if args.check_build:
        from horovod_tpu.version import __version__
        print(f"Horovod-TPU v{__version__}:\n\n"
              "Available Frameworks:\n    [X] JAX/Flax\n\n"
              "Available Backends:\n    [X] XLA/ICI\n    [X] XLA/DCN\n\n"
              "Available Controllers:\n    [X] jax.distributed\n\n"
              "Available Features:\n    [X] elastic\n    [X] autotune\n"
              "    [X] timeline\n    [X] process sets\n")
        return 0
    if not args.command:
        print("error: no training command given", file=sys.stderr)
        return 2
    if args.log_level:
        os.environ["HOROVOD_LOG_LEVEL"] = args.log_level
    try:
        if args.host_discovery_script or args.min_np or args.max_np:
            return _run_elastic(args)
        launcher = _resolve_launcher(args)
        if launcher in ("mpi", "jsrun"):
            return _run_static_mpi(args, launcher)
        return _run_static(args)
    except (ValueError, TimeoutError) as e:
        print(f"hvdrun: error: {e}", file=sys.stderr)
        return 1


def main():
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()

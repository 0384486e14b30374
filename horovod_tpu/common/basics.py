"""Global runtime state and the core init/query API.

TPU-native equivalent of the reference's ``HorovodBasics`` ctypes wrapper +
C API (reference: horovod/common/basics.py:29-505 and
horovod/common/operations.cc:934-1449 ``horovod_init``/``horovod_rank``/...).

Key semantic shift: the reference binds one OS process to one accelerator, so
``rank()`` is "my process". On TPU a single controller process typically owns
many chips, so a *rank is a chip* (a position in the global mesh). For each
process:

- ``size()``/``local_size()``/``cross_size()`` describe the global chip mesh,
- ``rank()`` is the first chip this process owns (0 on a single controller),
- ``process_index()``/``process_count()`` expose the host-level view.

There is no background negotiation thread: jitted collectives need no per-step
negotiation (the compile cache keyed on tensor signatures plays the reference's
response-cache role, reference: horovod/common/response_cache.h:45), and the
eager path's bucketing runtime lives in :mod:`horovod_tpu.ops.fusion`.
"""

import atexit
import os
import threading

import jax

from horovod_tpu.common import logging as hvd_logging
from horovod_tpu.common.config import Config
from horovod_tpu.common.exceptions import NotInitializedError
from horovod_tpu.common.topology import build_topology

_lock = threading.RLock()
_state = None


def _distributed_client_active():
    return jax.distributed.is_initialized()


def _current_coordinator():
    try:
        from jax._src import distributed as _dist
        return _dist.global_state.coordinator_address
    except Exception:
        return None


class _State:
    def __init__(self, topology, config):
        self.topology = topology
        self.config = config
        self.process_set_table = None   # set by process_sets module
        self.timeline = None            # set lazily by timeline module
        self.fusion = None              # set lazily by ops.fusion
        self.parameter_manager = None   # set lazily by autotune
        self.joined_ranks = set()       # ranks that called join()
        self.shutdown_called = False


def init(comm=None, process_sets=None, devices=None):
    """Initialize Horovod-TPU.

    Mirrors ``hvd.init(comm, process_sets)`` (reference: basics.py:51-148).
    ``comm`` is accepted for API compatibility; rank subsets should use
    ``process_sets`` / :class:`horovod_tpu.ProcessSet` instead.

    In a multi-host launch (``hvdrun``), ``jax.distributed`` bootstrap replaces
    the reference's Gloo HTTP-KV rendezvous (reference:
    horovod/common/gloo/gloo_context.cc:160-230): the launcher exports
    ``HOROVOD_COORDINATOR_ADDR/PORT`` + ``HOROVOD_CROSS_RANK/CROSS_SIZE`` and we
    call ``jax.distributed.initialize`` here.

    The whole of it is the ``init`` span of the ``run`` trace, with
    children ``init.recorders`` (one child per recorder armed),
    ``init.distributed``, ``init.compile_cache`` and ``init.topology``
    (docs/observability.md).
    """
    if _state is not None:
        return
    from horovod_tpu import trace as _trace
    with _trace.run_span("init"):
        _init(process_sets, devices)


def _init(process_sets, devices):
    global _state
    from horovod_tpu import trace as _trace
    span = _trace.run_span
    with _lock:
        if _state is not None:
            return
        config = Config.from_env()

        # Chaos fault injection (HOROVOD_CHAOS_PLAN): armed before any
        # control-plane traffic so the whole init/rendezvous path is
        # injectable. Idempotent across elastic in-place re-inits — site
        # counters and the injection ledger survive shutdown()/init()
        # cycles within one process (a mid-plan reset would re-fire
        # already-spent faults).
        if config.chaos_plan:
            from horovod_tpu.chaos import injector as _chaos_injector
            _chaos_injector.install_from_env()

        # The recorders armed before the bootstrap; the rest follow once
        # the topology is known (the second ``init.recorders`` below).
        with span("init.recorders"):
            # Flight recorder (always-armed crash forensics): configured
            # before any dispatch so the ring covers init/rendezvous too.
            # configure() never clears a live ring — elastic in-place
            # re-init must keep the pre-failure events (they ARE the
            # evidence a post-mortem needs).
            with span("init.recorders.flight"):
                from horovod_tpu.flight import recorder as _flight_recorder
                _flight_recorder.configure(config)

            # Step profiler: arm the per-step ledger/watchdog/capture
            # knobs before any dispatch so attribution covers the first
            # step. Completed records survive re-init (like the flight
            # ring); only the open window resets (basics.shutdown).
            with span("init.recorders.profile"):
                from horovod_tpu.profile import ledger as _profile_ledger
                _profile_ledger.configure(config)

            # Request/step tracing + declared SLOs: armed next to the
            # flight recorder (the trace store survives re-init for the
            # same reason the ring does — a requeued request's spans ARE
            # its history).
            with span("init.recorders.trace_slo"):
                _trace.configure(config)
                from horovod_tpu.telemetry import slo as _slo
                _slo.configure(config)

            # Goodput accounting: start the wall clock before the
            # distributed bootstrap so rendezvous + compile book to
            # init_compile. The ledger survives elastic re-init
            # (configure is start-once); the durable run journal arms
            # after bootstrap, once the rank is known (rank 0 only).
            with span("init.recorders.goodput"):
                from horovod_tpu.goodput import ledger as _goodput
                _goodput.configure(config)

        with span("init.distributed"):
            _bootstrap_distributed(config)

        # Persistent XLA compile cache BEFORE the first compile, so every
        # compile this job performs (including the eager collective
        # programs) is eligible: elastic re-rendezvous and repeat launches
        # then skip XLA recompiles entirely (see docs/performance.md).
        with span("init.compile_cache"):
            _setup_compile_cache(config.compile_cache_dir)
            # The one listener on JAX's monitoring bus: the cache's
            # counts, and while tracing is armed the compile's stages as
            # spans of the run trace, whatever became of the cache.
            from horovod_tpu import metrics as hvd_metrics
            hvd_metrics.install_compile_cache_listener()

        with span("init.topology"):
            topology = build_topology(devices)
            _state = _State(topology, config)

            from horovod_tpu.common import process_sets as ps
            ps._init_table(_state, process_sets)

        with span("init.recorders"):
            _arm_recorders(config, topology)  # hvdrace: disable=HVR202 -- start_timeline's one-shot native lib build at init, bounded by subprocess timeout=120 and cached by native._tried

        hvd_logging.info(
            "horovod_tpu initialized: size=%d local_size=%d cross_size=%d",
            topology.size, topology.local_size, topology.cross_size)
        atexit.register(shutdown)


def _arm_recorders(config, topology):
    """The recorders that need the topology: timeline, metrics endpoint,
    telemetry plane, run-history journal, autopilot. One child span of
    ``init.recorders`` each (the caller holds that span open)."""
    from horovod_tpu.trace import run_span as span
    if config.timeline_filename:
        with span("init.recorders.timeline"):
            start_timeline(config.timeline_filename,
                           mark_cycles=config.timeline_mark_cycles)

    # Metrics: arm the always-on registry with this job's knobs and
    # (optionally) the scrape endpoint. Offset by the LOCAL (per-host)
    # process rank only — same-host processes must not fight over one
    # bind, while every host keeps the same base port so a uniform
    # scrape config works across the fleet.
    with span("init.recorders.metrics"):
        from horovod_tpu import metrics as hvd_metrics
        hvd_metrics.set_enabled(config.metrics)
        hvd_metrics.set_prefix(config.metrics_prefix)
        if config.metrics and config.metrics_port:
            # Topology-derived, not config.local_rank: launchers that skip
            # the HOROVOD_LOCAL_RANK env (direct jax.distributed, SLURM)
            # would leave every same-host process at offset 0.
            local_rank_now = (
                topology.local_device_ranks[0] % topology.local_size
                if topology.local_device_ranks else 0)
            try:
                port = hvd_metrics.start_http_server(
                    config.metrics_port + local_rank_now,
                    addr=config.metrics_addr)
                hvd_logging.info("metrics scrape endpoint on :%d", port)
            except OSError as e:  # busy port must not kill training
                hvd_logging.warning("metrics endpoint failed to bind: %s", e)

    # Cluster telemetry plane: rank → slice-leader → job-view
    # aggregation over the launcher HTTP-KV (horovod_tpu/telemetry).
    # Armed after the topology is known (slice membership comes from
    # it); no-ops on single-process or KV-less runs, where
    # hvd.cluster_snapshot() serves the local-only view.
    with span("init.recorders.telemetry"):
        try:
            from horovod_tpu.telemetry import aggregator as _telemetry
            _telemetry.start_from_config(config, topology)
        except Exception as e:  # noqa: BLE001 — telemetry must not block init
            hvd_logging.warning("telemetry plane failed to start: %s", e)

    # Durable run-history journal (HOROVOD_RUN_HISTORY_DIR): armed on
    # the coordinator rank once the world shape is known. Arm-once
    # like the goodput ledger — an elastic re-init keeps appending to
    # the same run's journal (a new coordinator after a rank-0 death
    # opens its own).
    with span("init.recorders.run_history"):
        try:
            from horovod_tpu.goodput import history as _run_history
            if _run_history.get_journal() is None:
                # config.cross_rank is the launcher-assigned process id
                # (0 on the coordinator / single-controller).
                _run_history.journal_configure(
                    config, rank=config.cross_rank, world=topology.size)
        except Exception as e:  # noqa: BLE001 — must not block init
            hvd_logging.warning("run-history journal failed to arm: %s", e)

    # Autopilot (HOROVOD_AUTOPILOT): the online controller closing the
    # signal plane → knobs loop, coordinator rank only (followers
    # adopt flips at flush boundaries). Armed AFTER telemetry so its
    # first frame can already read the health plane. An elastic
    # re-init restarts it under the new membership like the
    # telemetry agent.
    with span("init.recorders.autopilot"):
        try:
            from horovod_tpu.autopilot import controller as _autopilot
            _autopilot.start_from_config(config)
        except Exception as e:  # noqa: BLE001 — must not block init
            hvd_logging.warning("autopilot failed to start: %s", e)


def _bootstrap_distributed(config):
    """``jax.distributed`` bootstrap of a multi-host launch (the
    ``init.distributed`` span); nothing to do in a single process."""
    # Decide on distributed bootstrap from the env alone: probing
    # jax.process_count() here would initialize the local backend and
    # forbid jax.distributed.initialize afterwards.
    if config.coordinator_addr and config.cross_size > 1:
        target = f"{config.coordinator_addr}:{config.coordinator_port}"
        replace = False
        if _distributed_client_active():
            current = _current_coordinator()
            if current == target:
                replace = False  # our cluster already bootstrapped
                # Reused service ⇒ its KV store may hold the previous
                # incarnation's last (un-GC'd) negotiation keys; move
                # every participant to a fresh epoch namespace.
                from horovod_tpu.common import negotiation
                negotiation.bump_epoch()
            else:
                # A distributed client that doesn't belong to our
                # cluster (user code, an earlier membership): replace.
                hvd_logging.warning(
                    "replacing pre-existing jax.distributed client "
                    "(%s) with launcher coordinator %s", current, target)
                jax.distributed.shutdown()
                replace = True
        else:
            replace = True
        if replace:
            # Backends created before distributed bootstrap (user
            # warmup, or a previous smaller world during elastic
            # scale-up) would freeze a stale topology view; clear them
            # so they rebuild with the cluster's global topology.
            # Failures propagate: continuing with a stale backend is
            # the exact wedge this block exists to prevent.
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                hvd_logging.warning(
                    "clearing pre-initialized XLA backends before "
                    "distributed bootstrap")
                _clear_backends_and_program_caches()
            if os.environ.get("HOROVOD_ELASTIC"):
                # Elastic membership: a peer dying must surface as a
                # recoverable collective error in survivors, not a
                # process-fatal coordination abort, and failure
                # detection should beat the default 100 s heartbeat
                # (reference: NCCL comms marked elastic abort instead
                # of hanging, nccl_operations.h:55).
                hb = int(os.environ.get(
                    "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", "10"))
                jax.config.update("jax_enable_recoverability", True)
                jax.distributed.initialize(
                    coordinator_address=target,
                    num_processes=config.cross_size,
                    process_id=config.cross_rank,
                    heartbeat_timeout_seconds=hb,
                    shutdown_timeout_seconds=hb)
            else:
                jax.distributed.initialize(
                    coordinator_address=target,
                    num_processes=config.cross_size,
                    process_id=config.cross_rank)
            # Fresh coordination service: empty KV store, epoch 0 for
            # every participant (incl. replacement elastic workers).
            from horovod_tpu.common import negotiation
            negotiation.reset_epoch()


def _setup_compile_cache(path):
    """Arm JAX's persistent compilation cache at ``path``
    (``Config.compile_cache_dir``, which is ``JAX_COMPILATION_CACHE_DIR``
    whenever that is set — jax has then read it itself and no directory is
    set here).

    The min-compile-time / min-entry-size gates are zeroed: the eager
    collective programs are individually cheap compiles, but an elastic
    restart pays ALL of them again back-to-back — exactly the latency this
    cache exists to remove. Cache-hit/request totals are mirrored into the
    metrics registry (``compile_cache_events_total``). Failures downgrade
    to a warning: a broken cache dir must not block training (delete a
    stale directory if hits stay at zero — docs/troubleshooting.md)."""
    try:
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax latches its "is the cache usable" decision at the first
        # compile; compiles before init() (user warmup) would have latched
        # it off — reset so the directory takes effect.
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
        hvd_logging.info("persistent XLA compile cache at %s", path)
    except Exception as e:  # noqa: BLE001 — cache is an optimization only
        hvd_logging.warning("compile cache setup failed (%s): %s", path, e)


def _clear_backends_and_program_caches():
    """Drop every XLA client AND every compiled-program cache that captures
    mesh/device objects, so everything rebuilds against the next backend.

    Must be the PUBLIC clear (``jax.extend.backend.clear_backends``) — the
    private ``xla_bridge._clear_backends`` leaves the ``get_backend``
    util.cache serving the old client, which keeps ``jax.devices()``
    returning a dead multi-process world after an elastic resize."""
    from jax.extend.backend import clear_backends
    clear_backends()
    from horovod_tpu.ops import collective_ops
    collective_ops.clear_program_caches()
    # The old CPU client must actually DIE here, not linger until an
    # arbitrary later GC: it owns the gloo contexts, and its destruction
    # is what closes their TCP connections — peers blocked on us in a
    # collective unblock on that close. Reference-cycle stragglers
    # (exception tracebacks through jit frames are the usual holders)
    # otherwise keep the sockets open indefinitely.
    import gc
    gc.collect()


def teardown_distributed():
    """Fully dissolve the jax.distributed cluster membership so backends
    rebuilt afterwards see a single-process world.

    ``jax.distributed.shutdown()`` alone resets the client/service but
    leaves ``num_processes``/``process_id`` behind, and the CPU/TPU client
    factories read those at backend creation — without this, a worker
    shrinking to world size 1 rebuilds a backend that still believes in its
    dead peers. Used by elastic in-place re-initialization
    (horovod_tpu/elastic/state.py _reset)."""
    try:
        jax.distributed.shutdown()
    except Exception as e:  # old cluster half-dead: proceed with teardown
        hvd_logging.warning("jax.distributed shutdown: %s", e)
    try:
        from jax._src import distributed as _dist
        # A failed shutdown barrier (elastic: a DEAD peer can never join
        # it) raises out of State.shutdown BEFORE it nulls client/service;
        # the next initialize would then refuse with "should only be
        # called once". Finish the dismantling by hand.
        if _dist.global_state.client is not None:
            _dist.global_state.client = None
        if _dist.global_state.service is not None:
            try:
                _dist.global_state.service.shutdown()
            except Exception:  # noqa: BLE001 — stopping a dead service
                pass
            _dist.global_state.service = None
        _dist.global_state.preemption_sync_manager = None
        _dist.global_state.process_id = 0
        _dist.global_state.num_processes = 1
        _dist.global_state.coordinator_address = None
    except Exception as e:  # pragma: no cover
        hvd_logging.warning("distributed state reset: %s", e)
    _clear_backends_and_program_caches()


def shutdown():
    """Finalize: flush pending fused collectives and the timeline
    (reference: horovod_shutdown, operations.cc:1006-1013)."""
    global _state
    with _lock:
        if _state is None or _state.shutdown_called:
            return
        _state.shutdown_called = True
        if _state.fusion is not None:
            try:
                _state.fusion.shutdown()
            except Exception as e:  # pragma: no cover
                hvd_logging.warning("flush on shutdown failed: %s", e)
        if _state.timeline is not None:
            # Final registry dump as Chrome-trace counter events, so the
            # written trace ends with the job's aggregate totals.
            try:
                from horovod_tpu import metrics as hvd_metrics
                hvd_metrics.emit_timeline_counters(_state.timeline)
            except Exception:  # noqa: BLE001 — telemetry must not block
                pass
            _state.timeline.close()
        from horovod_tpu import metrics as hvd_metrics
        hvd_metrics.stop_http_server()
        # Run-history journal: append the current cluster view + goodput
        # summary while the telemetry agent is still alive (the reader
        # takes the LAST of each kind, so mid-run elastic resets just
        # refresh the evidence; the final run_end marker comes from the
        # goodput atexit finalizer).
        try:
            from horovod_tpu.goodput import history as _run_history
            if _run_history.get_journal() is not None:
                from horovod_tpu.goodput import ledger as _goodput_ledger
                from horovod_tpu.telemetry import aggregator as _telemetry
                agent = _telemetry.get_agent()
                if agent is not None:
                    _run_history.journal_append(
                        "cluster", view=agent.cluster_snapshot())
                _run_history.journal_append(
                    "goodput", summary=_goodput_ledger.snapshot())
        except Exception:  # noqa: BLE001 — history must not block exit
            pass
        # Telemetry agent: stopped here, restarted by the next init (an
        # elastic re-init restarts it under the new membership generation
        # — rank numbering changes across memberships, so the old agent's
        # keys must not outlive it).
        try:
            from horovod_tpu.telemetry import aggregator as _telemetry
            _telemetry.stop()
        except Exception:  # noqa: BLE001 — telemetry must not block exit
            pass
        # Autopilot control thread: stopped with the runtime it steers
        # (an elastic re-init re-arms it under the new membership).
        try:
            from horovod_tpu.autopilot import controller as _autopilot
            _autopilot.stop()
        except Exception:  # noqa: BLE001 — must not block exit
            pass
        # Step profiler: discard the OPEN window and bump the record
        # epoch — an elastic reset's recovery traffic must not be
        # attributed to the first post-restore step, and reports must not
        # double-count across a rendezvous (completed records are kept).
        try:
            from horovod_tpu.profile import ledger as _profile_ledger
            _profile_ledger.reset_window()
            # A trace capture still open (step window never reached its
            # stop marker, mid-/debug/profile shutdown) must flush to
            # disk now — the session would otherwise leak past teardown.
            from horovod_tpu.profile import capture as _profile_capture
            _profile_capture.shutdown()
        except Exception:  # noqa: BLE001 — profiling must not block exit
            pass
        trace_dir = _state.config.trace_dir
        t = _state.topology
        trace_rank = t.local_device_ranks[0] if t.local_device_ranks \
            else 0
        from horovod_tpu.common import negotiation
        negotiation.reset()
        _state = None
    # Membership watchdog: terminate it on the way out (it joins a thread,
    # so — like the trace dump below — it runs after releasing the lock).
    try:
        from horovod_tpu.elastic import worker as _elastic_worker
        _elastic_worker.stop_collective_abort()
    except Exception:  # noqa: BLE001 — must not block exit
        pass
    # Trace shard: a configured HOROVOD_TRACE_DIR gets this process's
    # span store on the way out (trace_r<rank>.json, merged by
    # `python -m horovod_tpu.trace.analyze`) — written AFTER releasing
    # the state lock: a dump is file I/O and must not sit in the
    # critical section (the PR-5 signal-handler deadlock class).
    if trace_dir:
        try:
            import os as _os

            from horovod_tpu import trace as _trace
            _os.makedirs(trace_dir, exist_ok=True)
            _trace.dump(_os.path.join(trace_dir,
                                      f"trace_r{trace_rank}.json"),
                        rank=trace_rank)
        except Exception:  # noqa: BLE001 — must not block exit
            pass


def is_initialized():
    """reference: horovod_is_initialized (operations.cc:1027)."""
    return _state is not None


def _get_state():
    if _state is None:
        raise NotInitializedError()
    return _state


def topology():
    return _get_state().topology


def config():
    return _get_state().config


# --- rank / size queries (reference: operations.cc:1119-1229) ---

# Simulated-world overlay (horovod_tpu/analysis/program.py): while the
# static analyzer abstract-evals a step function "as rank r of n", the
# rank/size queries below answer from this overlay instead of the live
# topology — rank-conditional Python control flow then resolves per
# simulated rank with zero device execution. None = no simulation.
_sim_world = None


class _SimWorld:
    __slots__ = ("rank", "size", "local_rank", "local_size",
                 "cross_rank", "cross_size")

    def __init__(self, rank, size, local_size=None):
        self.rank = rank
        self.size = size
        self.local_size = size if local_size is None else local_size
        self.local_rank = rank % self.local_size
        self.cross_rank = rank // self.local_size
        self.cross_size = max(1, size // self.local_size)


def _set_sim_world(sim):
    """Install (or clear, with ``None``) the simulated world. Returns the
    previous overlay so nested simulations can restore it."""
    global _sim_world
    prev = _sim_world
    _sim_world = sim
    return prev


def size():
    if _sim_world is not None:
        return _sim_world.size
    return _get_state().topology.size


def local_size():
    if _sim_world is not None:
        return _sim_world.local_size
    return _get_state().topology.local_size


def cross_size():
    if _sim_world is not None:
        return _sim_world.cross_size
    return _get_state().topology.cross_size


def rank():
    if _sim_world is not None:
        return _sim_world.rank
    t = _get_state().topology
    return t.local_device_ranks[0] if t.local_device_ranks else 0


def local_rank():
    if _sim_world is not None:
        return _sim_world.local_rank
    t = _get_state().topology
    return rank() % t.local_size


def cross_rank():
    if _sim_world is not None:
        return _sim_world.cross_rank
    t = _get_state().topology
    return rank() // t.local_size


def process_index():
    return _get_state().topology.process_index


def process_count():
    return jax.process_count()


def is_homogeneous():
    """TPU slices are homogeneous by construction
    (reference: horovod_is_homogeneous, operations.cc:1233)."""
    _get_state()
    return True


# --- build-capability queries (reference: operations.cc:1307-1449).
# These exist so code written against the reference API keeps working; the
# honest answers for a TPU runtime are below.

def mpi_threads_supported():
    return False


def mpi_enabled():
    return False


def mpi_built():
    return False


def gloo_enabled():
    return False


def gloo_built():
    return False


def nccl_built():
    return 0


def ddl_built():
    return False


def ccl_built():
    return False


def cuda_built():
    return False


def rocm_built():
    return False


def xla_built():
    """The entire data plane is XLA on this framework."""
    return True


def ici_built():
    """TPU inter-chip-interconnect collectives available."""
    return True


# --- timeline control (reference: horovod_start_timeline, operations.cc:1079) ---

def start_timeline(file_path, mark_cycles=False):
    """Start (or restart) the Chrome-trace timeline.

    Multi-process: the COORDINATOR (process 0) writes ``file_path``, like
    the reference's rank-0 timeline writer (timeline.cc); every other
    process writes ``file_path.p<index>`` — same observability per host
    without processes clobbering one shared file."""
    st = _get_state()
    from horovod_tpu.timeline import Timeline
    if st.timeline is not None:
        st.timeline.close()
    if jax.process_count() > 1 and jax.process_index() != 0:
        file_path = f"{file_path}.p{jax.process_index()}"
    st.timeline = Timeline(file_path, mark_cycles=mark_cycles)
    return st.timeline


def stop_timeline():
    st = _get_state()
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None


def timeline():
    st = _state
    return st.timeline if st is not None else None


# --- metrics (horovod_tpu/metrics; no reference analog — the reference's
# observability stops at the timeline + stall inspector) ---

def metrics_snapshot():
    """JSON-able dict of every metrics series' current value (counters,
    gauges, histograms with cumulative buckets). Works before init too —
    the registry is process-global."""
    from horovod_tpu import metrics as hvd_metrics
    return hvd_metrics.snapshot()


def metrics_text():
    """The metrics registry in Prometheus text exposition format 0.0.4 —
    the same payload the ``HOROVOD_METRICS_PORT`` scrape endpoint serves."""
    from horovod_tpu import metrics as hvd_metrics
    return hvd_metrics.render_text()


def cluster_snapshot():
    """The job-level cluster view from the hierarchical telemetry plane
    (horovod_tpu/telemetry): per-rank health states
    (healthy/straggling/desynced/stalled/dead), per-slice digest counts
    and leader, job step progress, and the bounded state-transition event
    log — the same payload ``GET /cluster/health`` serves. Falls back to
    a local-only view on single-process or KV-less runs; never returns
    None. Works before init too (local fallback)."""
    from horovod_tpu.telemetry import aggregator as _telemetry
    return _telemetry.cluster_snapshot()

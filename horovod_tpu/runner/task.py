"""Worker-side bootstrap for ``horovod_tpu.run()``.

Reference: horovod/runner/run_task.py + task_fn.py — loads the cloudpickled
function, initializes, executes, reports the result.
"""

import os
import sys

import cloudpickle


def _load_payload(spec):
    """``kv:scope/key`` fetches the pickled function from the launcher's KV
    store (works without a shared filesystem — reference ships the pickle
    over its driver/task socket RPC, runner/run_task.py); anything else is a
    local path."""
    if spec.startswith("kv:"):
        from horovod_tpu.runner.http_kv import KVStoreClient
        scope, key = spec[3:].split("/", 1)
        client = KVStoreClient(os.environ["HOROVOD_KV_ADDR"],
                               int(os.environ["HOROVOD_KV_PORT"]))
        return client.wait_for(scope, key, timeout=60)
    with open(spec, "rb") as f:
        return f.read()


def _register_bootstrap():
    """Reachability probe: record which address this worker routes to the
    driver from, keyed by its host slot, BEFORE collective init.

    Reference: task_fn.py:23-54 — tasks probe routable NICs and report
    their interfaces so the driver can diagnose dead launches early.
    Here the successful signed KV write IS the routability proof (worker →
    driver control plane), and the recorded source address tells operators
    which interface that was. Failures are non-fatal — the probe is a
    diagnostic, not a gate."""
    kv_addr = os.environ.get("HOROVOD_KV_ADDR")
    kv_port = os.environ.get("HOROVOD_KV_PORT")
    if not (kv_addr and kv_port):
        return
    try:
        import json
        import socket
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect((kv_addr, int(kv_port)))
            src = s.getsockname()[0]
        from horovod_tpu.common.config import Config
        from horovod_tpu.runner.http_kv import KVStoreClient
        # Config.from_env resolves the process index for ALL launch paths
        # (HOROVOD_CROSS_RANK for ssh; OMPI/PMI/Slurm env for mpirun/jsrun,
        # which export no per-host HOROVOD rank) — a plain env read here
        # would collapse every MPI worker onto slot "0".
        slot = str(Config.from_env().cross_rank)
        KVStoreClient(kv_addr, int(kv_port)).put(
            "bootstrap", slot,
            json.dumps({"hostname": socket.gethostname(), "src_addr": src,
                        "pid": os.getpid()}).encode())
    except Exception as e:  # diagnostic only
        print(f"# bootstrap probe failed (continuing): {e}", file=sys.stderr)


def main():
    _register_bootstrap()
    func, args, kwargs = cloudpickle.loads(_load_payload(sys.argv[1]))

    import horovod_tpu as hvd
    hvd.init()
    result = func(*args, **kwargs)

    kv_addr = os.environ.get("HOROVOD_KV_ADDR")
    kv_port = os.environ.get("HOROVOD_KV_PORT")
    if kv_addr and kv_port:
        from horovod_tpu.runner.http_kv import KVStoreClient
        key = str(hvd.cross_rank())
        init_version = os.environ.get("HOROVOD_ELASTIC_INIT_VERSION")
        if os.environ.get("HOROVOD_ELASTIC") and init_version:
            # Version-scoped so results computed under a superseded
            # membership are ignored by the harvest (see elastic driver).
            key = f"{init_version}/{key}"
        client = KVStoreClient(kv_addr, int(kv_port))
        client.put("results", key, cloudpickle.dumps(result))
        if os.environ.get("HOROVOD_ELASTIC"):
            # Declare the job winding down BEFORE exiting: the driver must
            # not rebalance on a discovery blip once any worker finished
            # cleanly (its result would be wiped and the new membership
            # would wait forever on this exited rank).
            client.put("elastic", "finished", b"1")
    # Finalize the goodput run journal on the clean-exit path, while the
    # telemetry agent is still alive to contribute the cluster view (the
    # atexit fallback runs after hvd.shutdown() has stopped it).
    try:
        from horovod_tpu.goodput import ledger as _goodput
        _goodput.shutdown()
    except Exception:
        pass
    hvd.shutdown()
    _orderly_distributed_exit()


def _orderly_distributed_exit():
    """CLEAN-finish disconnect from the jax.distributed cluster: run the
    real shutdown barrier, then dismantle local state.

    The elastic FAILURE-recovery teardown must never run the barrier (a
    dead peer can't join it — common/basics.py teardown_distributed drops
    references instead), but a clean finish is the opposite case: every
    rank is alive and exiting together, so the barrier completes, each
    agent sends a proper ShutdownTask (stopping its heartbeat/error-poll
    threads first), and nobody's teardown looks like a task death to the
    coordination service. Skipping this and letting references (or
    interpreter finalization) destroy clients abruptly races each
    client's destructor against its own polling thread and the service's
    stream-break detection — either race ends in the hardwired fatal
    callback, turning a clean exit into a crash the driver then
    blacklists."""
    if not os.environ.get("HOROVOD_ELASTIC"):
        return
    from horovod_tpu.common import basics
    if not basics._distributed_client_active():
        return
    try:
        from jax._src import distributed as _dist
        client = _dist.global_state.client
        if client is not None:
            client.shutdown()
    except Exception as e:  # peer died post-training: fall through
        print(f"# distributed shutdown barrier failed (continuing): {e}",
              file=sys.stderr)
    basics.teardown_distributed()


if __name__ == "__main__":
    main()

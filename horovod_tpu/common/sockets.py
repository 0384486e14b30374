"""Data-plane socket abort: the collective abort lever of the CPU tier.

Reference semantics: on a membership change Horovod ABORTS in-flight gloo
collectives on every worker (the WorkerNotificationService push flips the
shutdown flag and the gloo context's pairs are closed, making blocked
send/recv calls raise instead of waiting out their timeout). jaxlib
exposes no abort on its gloo CPU collectives — ``make_gloo_tcp_collectives``
takes no timeout and XLA's collective thunks wait ~30 minutes — so a worker
blocked in an allreduce against a dead peer it is not directly connected to
would outlive every recovery deadline (only the dead rank's ring NEIGHBORS
see a connection reset; everyone else blocks on live-but-equally-stuck
peers).

This module implements abort at the file-descriptor level: ``shutdown(2)``
every ESTABLISHED TCP socket of this process except the control-plane
connections the recovery path still needs (coordination service, runner KV
store, metrics server). A shutdown makes the kernel send FIN/RST, so the
peer's blocked gloo read fails immediately AND this process's own blocked
collective errors out — surfacing as the ``HorovodInternalError`` the
elastic recovery loop already handles.

Safety: fds are never closed here — each is dup'd, the dup is wrapped,
``shutdown`` (which acts on the shared socket, not the descriptor) is
issued, and only the dup is closed. The C++ owner's later ``close`` of the
original fd therefore cannot double-close a recycled descriptor.
"""

import os
import socket

from horovod_tpu.common import logging as hvd_logging

_TCP_ESTABLISHED = "01"


def _established_inodes():
    """socket-inode -> (local_port, remote_port) for this netns's
    ESTABLISHED TCP connections (/proc/net/tcp + tcp6)."""
    out = {}
    for name in ("tcp", "tcp6"):
        try:
            with open(f"/proc/net/{name}") as f:
                lines = f.readlines()[1:]
        except OSError:
            continue
        for line in lines:
            parts = line.split()
            if len(parts) < 10 or parts[3] != _TCP_ESTABLISHED:
                continue
            try:
                local_port = int(parts[1].rsplit(":", 1)[1], 16)
                remote_port = int(parts[2].rsplit(":", 1)[1], 16)
                inode = int(parts[9])
            except (ValueError, IndexError):
                continue
            out[inode] = (local_port, remote_port)
    return out


def _socket_fds():
    """fd -> socket inode for this process."""
    out = {}
    try:
        fds = os.listdir("/proc/self/fd")
    except OSError:
        return out
    for fd in fds:
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith("socket:["):
            out[int(fd)] = int(target[8:-1])
    return out


def abort_data_plane_sockets(exclude_ports=()):
    """Shut down every ESTABLISHED TCP socket of this process whose local
    AND remote port are both outside ``exclude_ports``. Returns the number
    of connections aborted.

    Callers exclude the control-plane ports (coordination service, KV
    store, metrics) so only data-plane (gloo) connections — which cannot
    be told apart by port, both ends being ephemeral — are severed.
    LISTEN sockets are never touched (not ESTABLISHED), so servers keep
    accepting after an abort."""
    exclude = {int(p) for p in exclude_ports if p}
    inodes = _established_inodes()
    aborted = 0
    for fd, inode in _socket_fds().items():
        ports = inodes.get(inode)
        if ports is None or any(p in exclude for p in ports):
            continue
        try:
            dup = os.dup(fd)
        except OSError:
            continue
        try:
            s = socket.socket(fileno=dup)
        except OSError:
            os.close(dup)
            continue
        try:
            s.shutdown(socket.SHUT_RDWR)
            aborted += 1
        except OSError:
            pass
        finally:
            s.close()  # closes only the dup; the original fd stays valid
    if aborted:
        hvd_logging.warning(
            "aborted %d in-flight data-plane connection(s)", aborted)
    return aborted


def control_plane_ports():
    """The ports the elastic recovery path still needs after an abort:
    coordination service, runner KV store, the metrics endpoint — plus
    any application connections the user declared off-limits via
    ``HOROVOD_ABORT_EXCLUDE_PORTS`` (comma-separated local-or-remote
    ports: data loaders, object stores, anything whose severed
    connection would surface as an error the elastic recovery loop does
    not handle)."""
    ports = set()
    for env in ("HOROVOD_COORDINATOR_PORT", "HOROVOD_KV_PORT"):
        v = os.environ.get(env)
        if v and v.isdigit():
            ports.add(int(v))
    for tok in os.environ.get("HOROVOD_ABORT_EXCLUDE_PORTS", "").split(","):
        tok = tok.strip()
        if tok.isdigit():
            ports.add(int(tok))
    try:
        from horovod_tpu.metrics import server as _srv
        p = _srv.http_server_port()
        if p:
            ports.add(p)
    except Exception:  # noqa: BLE001 — metrics absence must not block abort
        pass
    return ports

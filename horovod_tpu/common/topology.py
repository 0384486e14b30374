"""Device topology and mesh construction.

The reference derives rank/local_rank/cross_rank from the launcher env and builds
MPI/Gloo communicators for the global, node-local, and cross-node rings
(reference: horovod/common/mpi/mpi_context.h, gloo/gloo_context.cc:67-94).

TPU-native replacement: one ``jax.sharding.Mesh`` over all addressable devices.
A Horovod *rank* is a mesh position (one chip), not an OS process:

- ``hvd`` axis   — flat 1-D axis over all chips; global collectives ride ICI.
- ``cross``/``local`` axes — 2-D factorization (host × chip-per-host) used by the
  hierarchical/torus allreduce equivalents, mapping the reference's
  NCCLHierarchicalAllreduce / NCCLTorusAllreduce two-level strategies
  (reference: horovod/common/ops/nccl_operations.cc:606-843) onto DCN × ICI.

Multi-process (multi-host) setups get the same mesh via ``jax.distributed``; each
process contributes its local devices, and rank r owns device ``mesh.devices[r]``.
"""

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

HVD_AXIS = "hvd"
CROSS_AXIS = "cross"
LOCAL_AXIS = "local"


@dataclasses.dataclass
class Topology:
    """Resolved topology for one init() call."""

    devices: list                  # all devices, rank-major order
    mesh: Mesh                     # 1-D mesh, axis ('hvd',)
    mesh2d: Mesh                   # 2-D mesh, axes ('cross', 'local')
    size: int                      # number of ranks == number of chips
    local_size: int                # chips per host
    cross_size: int                # number of hosts
    process_index: int             # this process's index (0 in single-controller)
    local_device_ranks: list       # ranks owned by this process
    num_slices: int = 1            # TPU slices (DCN-connected groups)
    mesh_dcn: Mesh = None          # ('cross', 'local') = (slice, chips-in-
    #                                slice) when multi-slice, else None

    def rank_of_device(self, device):
        return self.devices.index(device)

    @property
    def hierarchical_mesh(self):
        """The mesh the 2-level strategies (torus/hierarchical allreduce,
        parallel/strategies.py) should run over: slice-boundary factorization
        when the job spans DCN (the slow link the 2-level schedule exists
        for), host-boundary otherwise."""
        return self.mesh_dcn if self.mesh_dcn is not None else self.mesh2d


def _sorted_devices(devices):
    # Rank-major order: group by host (process_index), then stable device order
    # within the host. This makes local_rank = rank % local_size and
    # cross_rank = rank // local_size, matching the reference's host-major slot
    # assignment (reference: horovod/runner/common/util/hosts.py:100
    # get_host_assignments).
    return sorted(devices, key=lambda d: (d.process_index, d.id))


def _slice_id(d):
    """TPU slice id of a device in a multi-slice (DCN) job, else None
    (single-slice TPU devices and CPU devices carry no ``slice_index``)."""
    return getattr(d, "slice_index", None)


def forced_slices():
    """The ``HOROVOD_MESH_SLICES`` override, or 0 when unset (same
    semantics as the mesh construction's own read)."""
    from horovod_tpu.common.config import _env_int
    return _env_int("HOROVOD_MESH_SLICES", 0)


def slice_layout(size, num_slices=None):
    """``(num_slices, slice_size)`` for a ``size``-rank world: the SAME
    divisibility rules :func:`_build_dcn_mesh` applies when it builds the
    real DCN mesh, so static consumers (the analysis cost model's tier
    classifier) and the runtime hierarchy can never disagree. An explicit
    ``num_slices`` wins over the forced env knob; an undivisible or <2
    slice count collapses to the single-slice layout, exactly like the
    mesh construction does."""
    size = max(int(size), 1)
    k = int(num_slices) if num_slices else forced_slices()
    if k <= 1 or size % k != 0:
        return 1, size
    return k, size // k


def slice_of_rank(rank, slice_size):
    """Slice id of a rank under the rank-major (slice, chips-in-slice)
    reshape — the layout :func:`_build_dcn_mesh` materializes."""
    return int(rank) // max(int(slice_size), 1)


def _build_dcn_mesh(devices, size):
    """(slice × chips-per-slice) mesh when the job spans multiple TPU
    slices — the factorization whose 'cross' axis is the DCN, which is what
    the 2-level allreduce strategies actually want (reference mapping:
    SURVEY §5.8; NCCLTorusAllreduce's node boundary ↔ slice boundary).

    ``HOROVOD_MESH_SLICES=k`` overrides/fakes the slice count (virtual-CPU
    tier testing of the DCN path; also multi-slice setups whose devices
    don't expose slice ids).
    """
    if forced_slices():
        k, per = slice_layout(size)
        if k <= 1:
            return 1, None
        arr = np.array(devices, dtype=object).reshape(k, per)
        return k, Mesh(arr, (CROSS_AXIS, LOCAL_AXIS))
    sids = [_slice_id(d) for d in devices]
    if any(s is None for s in sids):
        return 1, None
    uniq = sorted(set(sids))
    k = len(uniq)
    # Every slice must hold exactly size/k devices: a reshape over unequal
    # slices would mix slices within a row, silently putting the 'local'
    # axis across DCN — the opposite of what this mesh exists for.
    if k <= 1 or any(sids.count(s) != size // k for s in uniq):
        return max(k, 1), None
    order = sorted(devices,
                   key=lambda d: (_slice_id(d), d.process_index, d.id))
    arr = np.array(order, dtype=object).reshape(k, size // k)
    return k, Mesh(arr, (CROSS_AXIS, LOCAL_AXIS))


def build_topology(devices=None):
    """Build the global topology over all (or the given) devices."""
    if devices is None:
        devices = jax.devices()
    devices = _sorted_devices(list(devices))
    size = len(devices)

    # Chips per host. On TPU pods every host exposes the same number of chips;
    # fall back to size (single host) when process information is unavailable.
    proc_ids = sorted({d.process_index for d in devices})
    cross_size = len(proc_ids)
    if size % cross_size != 0:
        raise ValueError(
            f"Non-uniform hosts: {size} devices over {cross_size} processes. "
            f"Horovod-TPU requires the same chip count per host.")
    local_size = size // cross_size

    dev_array = np.array(devices, dtype=object)
    mesh = Mesh(dev_array, (HVD_AXIS,))
    mesh2d = Mesh(dev_array.reshape(cross_size, local_size), (CROSS_AXIS, LOCAL_AXIS))

    process_index = jax.process_index()
    local_device_ranks = [i for i, d in enumerate(devices)
                          if d.process_index == process_index]

    num_slices, mesh_dcn = _build_dcn_mesh(devices, size)

    return Topology(
        devices=devices,
        mesh=mesh,
        mesh2d=mesh2d,
        size=size,
        local_size=local_size,
        cross_size=cross_size,
        process_index=process_index,
        local_device_ranks=local_device_ranks,
        num_slices=num_slices,
        mesh_dcn=mesh_dcn,
    )


def build_submesh(topology, ranks):
    """A 1-D mesh over a subset of ranks (a process set's communicator).

    Maps the reference's per-process-set communicators
    (reference: horovod/common/process_set.cc) onto a device sub-mesh.
    """
    devs = np.array([topology.devices[r] for r in ranks], dtype=object)
    return Mesh(devs, (HVD_AXIS,))

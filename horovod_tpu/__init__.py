"""horovod_tpu: a TPU-native distributed training framework.

Same capabilities as Horovod (the reference at yhlim5221/horovod), re-designed
for TPU: XLA collectives over the ICI mesh instead of NCCL/MPI, jit compile
caching instead of coordinator negotiation, ``jax.distributed`` bootstrap
instead of Gloo HTTP-KV rendezvous.

Typical use mirrors ``import horovod.torch as hvd``:

    import horovod_tpu as hvd
    hvd.init()
    grads = hvd.allreduce(stacked_grads)          # eager, rank-major layout
    # ... or inside your pjit'd train step:
    from horovod_tpu.ops import in_jit
    g = in_jit.allreduce(g, axis_name='hvd')
"""

import time as _time

_import_t0 = _time.time()    # the ``import`` span: first statement to last

from horovod_tpu.version import __version__  # noqa: F401

# HVD_LOCK_WITNESS=1: swap threading.Lock/RLock for hvdrace's recording
# proxies BEFORE any package module allocates a lock, so every
# acquisition edge lands in the witness log
# (docs/static_analysis.md#concurrency-analysis-hvdrace).
import os as _os  # noqa: E402

if _os.environ.get("HVD_LOCK_WITNESS", "").strip() in ("1", "true", "on"):
    from horovod_tpu.analysis import race as _race

    _race.maybe_install_from_env()
del _os

from horovod_tpu.common.basics import (  # noqa: F401
    init, shutdown, is_initialized, rank, local_rank, cross_rank, size,
    local_size, cross_size, process_index, process_count, is_homogeneous,
    mpi_threads_supported, mpi_enabled, mpi_built, gloo_enabled, gloo_built,
    nccl_built, ddl_built, ccl_built, cuda_built, rocm_built, xla_built,
    ici_built, start_timeline, stop_timeline, topology, config,
    metrics_snapshot, metrics_text, cluster_snapshot,
)
from horovod_tpu import metrics  # noqa: F401
from horovod_tpu import trace  # noqa: F401
from horovod_tpu import flight  # noqa: F401
from horovod_tpu import profile  # noqa: F401
from horovod_tpu import telemetry  # noqa: F401
from horovod_tpu.flight.recorder import step_marker  # noqa: F401
from horovod_tpu.flight.recorder import summary as flight_summary  # noqa: F401
from horovod_tpu.profile import (  # noqa: F401
    step_report, step_report_summary, set_flops_per_step,
)
from horovod_tpu.common.exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt, NotInitializedError,
)
from horovod_tpu.common.process_sets import (  # noqa: F401
    ProcessSet, global_process_set, add_process_set, remove_process_set,
    process_set_by_id, process_sets, number_of_process_sets,
    is_process_set_included,
)
from horovod_tpu.ops.collective_ops import (  # noqa: F401
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
    allreduce, grouped_allreduce, allgather, grouped_allgather,
    allgather_ragged, broadcast, grouped_broadcast, reducescatter,
    grouped_reducescatter, alltoall, barrier, join,
    allreduce_async, grouped_allreduce_async, allgather_async,
    broadcast_async, alltoall_async, reducescatter_async,
    poll, synchronize, Handle, broadcast_object, allgather_object,
)
from horovod_tpu import callbacks  # noqa: F401
from horovod_tpu import chaos  # noqa: F401
from horovod_tpu import analysis  # noqa: F401
from horovod_tpu.analysis.program import (  # noqa: F401
    check_elastic, check_program,
)
from horovod_tpu.runner.api import run, run_elastic  # noqa: F401
from horovod_tpu import checkpoint  # noqa: F401
from horovod_tpu import elastic  # noqa: F401
from horovod_tpu.ops import in_jit  # noqa: F401
from horovod_tpu.ops import wire  # noqa: F401
from horovod_tpu.ops.wire import (set_dispatch_strategy,  # noqa: F401
                                  set_wire_dtype, wire_dtype_for,
                                  set_alltoall_strategy,
                                  set_alltoall_cross_dtype)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.ops.sync_batch_norm import SyncBatchNorm  # noqa: F401
from horovod_tpu.optim import (  # noqa: F401
    DistributedOptimizer, allreduce_gradients_transform, fused_allreduce_tree,
    distributed_value_and_grad, broadcast_parameters, broadcast_object_tree,
)

trace.add_span(trace.run_tid(), "import", _import_t0,
               _time.time() - _import_t0)
del _time, _import_t0

from horovod_tpu.parallel.dp import (  # noqa: F401
    make_train_step, make_eval_step, make_zero_train_step, TrainState,
    ZeroTrainState,
)
from horovod_tpu.parallel.strategies import (  # noqa: F401
    allreduce_hierarchical, allreduce_int8, allreduce_torus,
)
from horovod_tpu.parallel.fsdp import (  # noqa: F401
    fsdp_shardings, make_fsdp_train_step, shard_batch, shard_params,
)
from horovod_tpu.parallel.sequence import (  # noqa: F401
    local_attention, next_token_labels, ring_attention,
    ulysses_attention,
)
from horovod_tpu.parallel.tp import (  # noqa: F401
    ColumnParallelDense, RowParallelDense, TPMlp, TPSelfAttention,
    TPTransformerBlock,
)
from horovod_tpu.parallel.pp import (  # noqa: F401
    pipeline, pipeline_1f1b, split_microbatches, stack_stage_params,
)
from horovod_tpu.parallel.moe import DroplessMoE, MoEMlp  # noqa: F401
from horovod_tpu.parallel.composite import (  # noqa: F401
    CompositeGPT, CompositeLlama, build_mesh3d, build_mesh4d,
)

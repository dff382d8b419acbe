"""horovod_tpu — a TPU-native distributed training framework with the
capability surface of Horovod v0.11.2 (reference: ``/root/reference``).

Public API parity with ``horovod/tensorflow/__init__.py:34-43``::

    import horovod_tpu as hvd
    hvd.init()
    hvd.size(); hvd.rank(); hvd.local_rank()
    hvd.allreduce(t); hvd.allgather(t); hvd.broadcast(t, root_rank)
    hvd.broadcast_global_variables(params, root_rank)
    opt = hvd.DistributedOptimizer(optax_optimizer)

Design: the world is a 1-D ``jax.sharding.Mesh`` over every chip (axis
``"hvd"``); collectives are XLA collectives over ICI inside compiled code and
cached compiled dispatches (single-controller) or a host DCN coordination
plane (multi-process) eagerly. See ``runtime.py`` and ``ops/``.
"""

import time as _time

# The span ``hvd.import`` starts here, before anything is imported: jax,
# flax, optax, Pallas and the package's own modules are a job's first
# seconds (docs/timeline.md). Recorded by this file's last statement.
_IMPORT_START_NS = _time.perf_counter_ns()

from .version import __version__  # noqa: F401

# Before anything can start the TPU backend: libtpu reads its arguments once.
from .utils.chips import enable_async_collectives as _enable_async_collectives
_enable_async_collectives()

from .runtime import (  # noqa: F401
    AXIS,
    init,
    shutdown,
    is_initialized,
    size,
    rank,
    local_rank,
    process_index,
    process_count,
    mesh,
    world,
)
from .ops.collectives import (  # noqa: F401
    Op,
    allreduce,
    allgather,
    allgather_ragged,
    broadcast,
    alltoall,
    reducescatter,
    grouped_allreduce,
    allreduce_async_,
    allgather_async_,
    broadcast_async_,
    synchronize,
    broadcast_object,
    allgather_object,
)
from .ops.sparse import IndexedSlices  # noqa: F401
from .ops.fusion import (  # noqa: F401
    BucketSchedule,
    GradSync,
    plan_grad_sync,
    plan_schedule,
    probe_grad_order,
    resolve_wire_dtype,
)
from .optimizer import (  # noqa: F401
    Compression,
    DistributedOptimizer,
    ZeroShardedState,
    allreduce_gradients,
    broadcast_global_variables,
    broadcast_parameters,
    broadcast_optimizer_state,
    partition_optimizer,
)
from . import callbacks  # noqa: F401
from . import data  # noqa: F401
from . import elastic  # noqa: F401
from . import hooks  # noqa: F401
from .hooks import BroadcastGlobalVariablesHook  # noqa: F401
from . import models  # noqa: F401
from . import obs  # noqa: F401
from . import serve  # noqa: F401
from . import training  # noqa: F401
from .trainer import (  # noqa: F401
    AsyncCheckpointer,
    Trainer,
    save_checkpoint,
    restore_checkpoint,
    latest_checkpoint_step,
)
from .exceptions import (  # noqa: F401
    HorovodError,
    NotInitializedError,
    FailedPreconditionError,
    TransportError,
    StalledError,
    WorkerFailureError,
    ServerOverloadedError,
    DeadlineExceededError,
    ServerClosedError,
    FailoverExhaustedError,
    CheckpointCorruptError,
    CheckpointTimeoutError,
    NonFiniteGradError,
)

from .utils.timeline import record_span as _record_span
_record_span("hvd.import", _IMPORT_START_NS, _time.perf_counter_ns())

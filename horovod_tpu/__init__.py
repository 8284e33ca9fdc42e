"""horovod_tpu — a TPU-native distributed data-parallel training framework.

A ground-up rebuild of the capabilities of Horovod v0.18.2 (reference:
Agoniii/horovod) for TPU: named asynchronous collectives (allreduce /
allgather / broadcast / adasum / join / alltoall) with tensor fusion, optimizer
and gradient wrappers averaging gradients across replicas, parameter broadcast,
fp16/bf16 compression, timeline profiling, stall detection, autotuning, and a
``horovodrun``-style launcher — implemented on XLA collectives over TPU
ICI/DCN meshes instead of NCCL/MPI/Gloo.

Typical use (JAX-native, eager parity API)::

    import horovod_tpu as hvd
    hvd.init()
    avg = hvd.allreduce(grad, name="g")          # psum/size over all ranks

SPMD fast path (the performance path — everything in one jitted step)::

    import horovod_tpu as hvd
    hvd.init()
    step = hvd.spmd.make_train_step(loss_fn, optimizer)
"""

import time as _time

_IMPORT_START = _time.perf_counter()   # the `import` span: here to the end

from .basics import (  # noqa: F401,E402
    Adasum,
    Average,
    Sum,
    cross_rank,
    cross_size,
    ddl_built,
    gloo_built,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mlsl_built,
    mpi_built,
    gloo_enabled,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    num_replicas,
    rank,
    shutdown,
    size,
    xla_built,
)
from .exceptions import (  # noqa: F401
    CollectiveTimeoutError,
    DuplicateNameError,
    HorovodError,
    HorovodInternalError,
    NonFiniteError,
    NotInitializedError,
    ParameterDesyncError,
    RanksChangedError,
    ShutdownError,
    WorkerLostError,
)
from .ops.collective_ops import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    broadcast,
    broadcast_async,
    join,
    poll,
    synchronize,
)
from .ops.compression import Compression  # noqa: F401
from .ops.sparse import (  # noqa: F401
    IndexedSlices,
    allreduce_sparse,
)
from .optim.broadcast import (  # noqa: F401
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
)
from .optim.distributed import (  # noqa: F401
    DistributedAdasumOptimizer,
    DistributedGradientTape,
    DistributedOptimizer,
    allreduce_gradients,
    grad,
)
from . import callbacks  # noqa: F401
from .callbacks import ConsistencyCheckCallback, MetricsCallback  # noqa: F401
from . import checkpoint  # noqa: F401
from . import elastic  # noqa: F401
from . import integrity  # noqa: F401
from .integrity import ConsistencyAuditor, GradGuard  # noqa: F401
# NOTE: this import makes the *function* shadow the `horovod_tpu.metrics`
# module as a package attribute (hvd.metrics() returns the aggregated
# snapshot). The module stays importable as `from horovod_tpu.metrics
# import ...` / `import horovod_tpu.metrics` via sys.modules.
from .metrics import metrics  # noqa: F401
from . import parallel  # noqa: F401
from . import spmd  # noqa: F401
from . import tracing  # noqa: F401
from .run.api import run  # noqa: F401

__version__ = "0.1.0"

from .metrics import phases as _phases  # noqa: E402

_phases.record("import", _IMPORT_START, _time.perf_counter())

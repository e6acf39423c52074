"""horovod_tpu — TPU-native distributed training with Horovod's capabilities.

A brand-new, TPU-first framework (see SURVEY.md for the reference analysis):
XLA collectives over ICI as the data plane, a background coordinator with
tensor fusion / response caching / timeline / stall detection as the control
plane, ``DistributedOptimizer``-family APIs for JAX and PyTorch, an
ICI-topology-aware launcher, and elastic training.

The top-level module mirrors the reference's ``import horovod.torch as hvd``
surface so users can write ``import horovod_tpu as hvd``:

    hvd.init()
    hvd.rank(), hvd.size(), hvd.local_rank()
    hvd.allreduce(x), hvd.allgather(x), hvd.broadcast(x, root_rank=0)
    hvd.alltoall(x), hvd.reducescatter(x), hvd.grouped_allreduce(xs)
    hvd.DistributedOptimizer(...), hvd.broadcast_parameters(...)
"""

__version__ = "0.1.0"

# The start-up record (trace/core.py): ``hvd/process`` ends and
# ``hvd/import`` begins at this line, before anything else is imported.
import sys as _sys
import time as _time
_import_t0, _jax_imported = _time.time(), "jax" in _sys.modules
from .trace import core as _startup_record  # noqa: E402
_import_span = _startup_record.begin_import(_import_t0, _jax_imported)

from .common.basics import (  # noqa: F401
    init, shutdown, is_initialized,
    rank, size, local_rank, local_size, cross_rank, cross_size,
    mesh, is_homogeneous,
    add_process_set, remove_process_set, process_set_included,
    xla_built, nccl_built, mpi_enabled, gloo_enabled, mpi_threads_supported,
    cuda_built, rocm_built, tpu_available,
    start_timeline, stop_timeline, start_profile, stop_profile, profile_step,
    NotInitializedError,
)
from .common.process_sets import ProcessSet, global_process_set  # noqa: F401
from .ops.collectives import (  # noqa: F401
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
)
from .ops.eager import (  # noqa: F401
    allreduce, allreduce_async,
    grouped_allreduce, grouped_allreduce_async,
    grouped_allgather, grouped_allgather_async,
    grouped_reducescatter, grouped_reducescatter_async,
    allgather, allgather_async,
    broadcast, broadcast_async, broadcast_object, allgather_object,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async,
    synchronize, poll, barrier, join,
    stack_per_rank, replicated, to_local, to_global,
)
from . import ops  # noqa: F401
from .jax.optimizer import (  # noqa: F401
    DistributedOptimizer, DistributedGradientTape,
    broadcast_parameters, broadcast_optimizer_state, allreduce_gradients,
)
from .jax.compression import Compression  # noqa: F401
from . import elastic  # noqa: F401
from . import callbacks  # noqa: F401
from . import checkpoint  # noqa: F401
from . import data  # noqa: F401
from . import analysis  # noqa: F401  (collective-correctness analyzer)

_import_span.__exit__(None, None, None)     # ``hvd/import`` ends here

"""horovod_tpu — a TPU-native distributed deep-learning training framework.

Provides the capabilities of Horovod v0.19.2 (reference: /root/reference,
``horovod/__init__.py``) re-designed TPU-first:

- process/topology model: ``init()``, ``rank()``, ``size()``, ``local_rank()``,
  ``local_size()``, ``cross_rank()``, ``cross_size()`` (reference:
  ``horovod/common/basics.py:22``)
- named asynchronous collectives with tensor fusion, response cache, timeline,
  stall inspection and Join semantics (reference: ``horovod/common/operations.cc``)
- the data plane is JAX/XLA collectives (``psum`` / ``all_gather`` /
  ``ppermute``) compiled over a :class:`jax.sharding.Mesh` — ICI within a
  slice, DCN across slices — instead of MPI/NCCL/Gloo.

The top-level module exposes the JAX-native binding.  Framework bindings live
in ``horovod_tpu.torch``, ``horovod_tpu.tensorflow`` (gated),
``horovod_tpu.keras`` (gated) and ``horovod_tpu.mxnet`` (gated).
"""

__version__ = "0.1.0"

# hvd-race (docs/race_detection.md): the shim must patch the threading
# primitives BEFORE the runtime modules below import and build their
# locks, so this gate runs first.  With HVD_TPU_RACE unset the shim
# module is never imported and threading stays stock — the gate's cost
# is one env read.
from horovod_tpu.utils import env as _env_util

if _env_util.get_bool(_env_util.HVD_TPU_RACE):
    from horovod_tpu.tools.race import shim as _race_shim

    _race_shim.install_from_env()

from horovod_tpu.common.basics import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    abort,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    mesh,
    local_device,
    nccl_built,
    mpi_built,
    gloo_built,
    xla_built,
    mpi_enabled,
    gloo_enabled,
    xla_enabled,
    ccl_built,
    ddl_built,
    mpi_threads_supported,
    is_homogeneous,
)
from horovod_tpu.common.handles import (  # noqa: F401
    HvdAbortedError,
    HvdDrainedError,
    HvdError,
    HvdReconfigureError,
)
from horovod_tpu import checkpoint  # noqa: F401
from horovod_tpu import elastic  # noqa: F401
from horovod_tpu.common.ops_enum import Average, Sum, Adasum  # noqa: F401
from horovod_tpu.ops.eager import (  # noqa: F401
    allreduce,
    allreduce_async,
    allgather,
    allgather_async,
    barrier,
    broadcast,
    broadcast_async,
    alltoall,
    alltoall_async,
    grouped_allreduce,
    grouped_allgather,
    reduce_scatter,
    reduce_scatter_async,
    synchronize,
    poll,
    join,
)
from horovod_tpu.groups import (  # noqa: F401
    Grid,
    GroupUnsatisfiableError,
    ProcessGroup,
    grid,
    new_group,
)
from horovod_tpu.common.objects import broadcast_object  # noqa: F401
from horovod_tpu.jax_api import (  # noqa: F401
    DistributedOptimizer,
    ShardedDistributedOptimizer,
    broadcast_parameters,
    broadcast_optimizer_state,
    allreduce_gradients,
    shard_chunk_size,
    sharded_state_wrap,
    sharded_state_unwrap,
)
from horovod_tpu.sharding import (  # noqa: F401
    ZeroDistributedOptimizer,
    gather_zero_state,
    reshard_zero_state,
)
from horovod_tpu.common.compression import Compression  # noqa: F401
from horovod_tpu.utils.trace import eager_stats, input_stats  # noqa: F401

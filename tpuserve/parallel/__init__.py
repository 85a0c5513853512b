"""Parallelism layer (SURVEY.md §2 C7, §2.1).

TPU-native parallelism is expressed through ``jax.sharding``: a ``Mesh`` over
the device grid, ``NamedSharding``/``PartitionSpec`` annotations on inputs,
params, and outputs, and XLA-inserted collectives riding ICI. There is no
user-managed NCCL/MPI backend to configure — the communication backend IS the
sharding layout (SURVEY.md §5 "Distributed communication backend").

Submodules:

- ``mesh``        — mesh construction (dp/tp/sp axes, host-major multi-host grid)
- ``partition``   — regex partition rules -> PartitionSpec pytrees
- ``distributed`` — jax.distributed.initialize seam for multi-host pods
"""

from tpuserve.parallel.distributed import (  # noqa: F401
    init_distributed, local_devices_info, process_info)
from tpuserve.parallel.mesh import (  # noqa: F401
    MeshPlan,
    axis_size,
    can_shard,
    host_major_grid,
    make_mesh,
    batch_sharding,
    replicated_sharding,
    local_device_count,
    plan_for,
    select_devices,
)
from tpuserve.parallel.partition import (  # noqa: F401
    match_partition_rules,
    named_leaves,
    shard_pytree,
    struct_shardings,
)

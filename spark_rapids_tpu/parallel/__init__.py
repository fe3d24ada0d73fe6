"""Multi-chip SPMD execution: device meshes, ICI all-to-all shuffle,
distributed query stages (the TPU-native replacement for the reference's
UCX accelerated-shuffle plugin, shuffle-plugin/)."""

from .alltoall import (allgather_batch, allgather_supported,
                       exchange_by_pid, exchange_supported)
from .mesh import DATA_AXIS, build_mesh, mesh_sharding

from .distributed import (DistributedAggregate, DistributedExchange,
                          shards_to_table, stack_shards, unstack_shards)

__all__ = [
    "DATA_AXIS", "DistributedAggregate", "DistributedExchange",
    "allgather_batch", "allgather_supported", "build_mesh",
    "exchange_by_pid", "exchange_supported", "mesh_sharding",
    "shards_to_table", "stack_shards", "unstack_shards",
]

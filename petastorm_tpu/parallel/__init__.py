"""Mesh/sharding utilities for pod-scale input pipelines."""

from petastorm_tpu.parallel.mesh import (DeviceShardPlan,  # noqa: F401
                                         batch_sharding, device_shard_plan,
                                         make_mesh, process_shard,
                                         replicated_sharding,
                                         sequence_sharding)
from petastorm_tpu.parallel.pod_guard import (PodAbortError,  # noqa: F401
                                              PodSafeIterator, global_all)

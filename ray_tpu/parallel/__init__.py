"""TPU-native parallelism layer.

This package replaces the reference's NCCL/MPI tensor plane
(``python/ray/util/collective/collective.py``; NCCL group
``nccl_collective_group.py:127``) with XLA collectives over a device mesh:
ICI axes inside a slice, DCN axes across slices (SURVEY §5.8).

- :mod:`ray_tpu.parallel.mesh` — ``MeshSpec`` / mesh construction with
  named axes (``dp``/``fsdp``/``tp``/``sp``/``ep``/``pp``).
- :mod:`ray_tpu.parallel.sharding` — sharding-rule tables mapping pytree
  paths to ``PartitionSpec``s (the ``prepare_model`` analog for jax), the
  two FSDP helpers the models call (weights gathered per layer, activations
  on the batch) and ``collective_profile`` of a compiled step.
- :mod:`ray_tpu.parallel.collective` — group-based collective API with the
  surface of ``ray.util.collective`` backed by ``jax.lax`` collectives.
"""

from ray_tpu.parallel.mesh import (
    MeshSpec,
    create_mesh,
    get_abstract_mesh,
    local_mesh,
)
from ray_tpu.parallel.pipeline import gpipe, pp_size
from ray_tpu.parallel.sharding import (
    ShardingRules,
    collective_profile,
    gather_for_compute,
    infer_sharding,
    logical_to_sharding,
    shard_activations,
    with_sharding_constraint,
)

__all__ = [
    "gpipe",
    "pp_size",
    "MeshSpec",
    "create_mesh",
    "local_mesh",
    "get_abstract_mesh",
    "ShardingRules",
    "collective_profile",
    "gather_for_compute",
    "shard_activations",
    "infer_sharding",
    "logical_to_sharding",
    "with_sharding_constraint",
]

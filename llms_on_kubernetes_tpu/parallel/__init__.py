from llms_on_kubernetes_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_MODEL,
    make_mesh,
)
from llms_on_kubernetes_tpu.parallel.sharding import (
    pool_sharding,
    param_specs,
    shard_params,
)

__all__ = [
    "AXIS_DATA", "AXIS_EXPERT", "AXIS_MODEL",
    "make_mesh", "param_specs", "pool_sharding", "shard_params",
]

"""Multi-chip parallelism: device meshes, sharded nonce search, ICI winner
election. See mesh_search for the design rationale.

Two gang implementations share one contract: the shard_map mesh
(mesh_search) and the pmap fan (fan_search). ROADMAP C3 weighs keeping
only one."""

from .fan_search import (  # noqa: F401
    FAN_AXIS,
    fan_devices,
    fan_search_chunk_batch,
    fan_search_devices,
    fan_search_run,
    fan_search_run_controlled,
)
from .mesh_search import (  # noqa: F401
    BATCH_AXIS,
    NONCE_AXIS,
    expected_steps,
    make_mesh,
    replicate_params,
    sharded_search_chunk_batch,
    sharded_search_run,
    sharded_search_run_controlled,
)
from .multihost import (  # noqa: F401
    arrange_by_host,
    init_distributed,
    make_multihost_mesh,
)

"""Multi-chip parallelism: device meshes, sharded nonce search, ICI winner
election. See mesh_search for the design rationale.

Two gang implementations serve the engine: the shard_map mesh
(mesh_search: ``sharded_search_chunk_batch``, a pmin election on device)
and the pmap fan (fan_search: ``fan_search_devices`` chunked,
``fan_search_run_controlled`` persistent; the host elects the winner).
Every chunked Pallas launch takes its window count at run time, so each
compiles one program per batch size. ``sharded_search_run`` and
multihost.py serve no engine path (ROADMAP C6); ROADMAP C3 weighs keeping
only one gang."""

from .fan_search import (  # noqa: F401
    FAN_AXIS,
    fan_devices,
    fan_search_devices,
    fan_search_run_controlled,
)
from .mesh_search import (  # noqa: F401
    BATCH_AXIS,
    NONCE_AXIS,
    make_mesh,
    replicate_params,
    sharded_search_chunk_batch,
    sharded_search_run,
)
from .multihost import (  # noqa: F401
    arrange_by_host,
    init_distributed,
    make_multihost_mesh,
)
